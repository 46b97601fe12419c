"""TPU validation batch: one watchdogged child process per section.

Times flash-vs-dense attention (fwd+bwd, long context), the s2d-vs-plain
ResNet stem, LM and serving sections, one JSON object per line, each
naming the device it ran on.  A section that raises still prints its
error line (a partial run keeps its other numbers) but the script then
exits non-zero.  The parent never touches JAX, so each child in turn can
own the chip.  Superseded by the first ``benchmark`` PR (ROADMAP D1).
"""

from __future__ import annotations

import json
import os
import sys
import time

# runnable as `python scripts/tpu_validation.py` from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(x):
    import jax.numpy as jnp

    return float(jnp.sum(x.astype(jnp.float32)))


def _time_grad(scalar_loss, q, steps):
    """Seconds/step of ``jit(grad(scalar_loss))``: one warmup compile,
    ``steps`` dispatches, one trailing sync — the SHARED timing protocol,
    so every section's ms numbers stay comparable (review finding: three
    diverging copies)."""
    import jax

    loss = jax.jit(jax.grad(scalar_loss))
    _sync(loss(q))
    t0 = time.perf_counter()
    for _ in range(steps):
        g = loss(q)
    _sync(g)
    return (time.perf_counter() - t0) / steps


def flash_vs_dense(B=4, T=2048, H=8, D=64, steps=20):
    import jax
    import jax.numpy as jnp

    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)
    from distributed_deep_learning_tpu.ops.attention_pallas import (
        flash_attention)

    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
               for kk in ks)

    def bench(fn):
        return _time_grad(lambda q: jnp.sum(fn(q, k, v) ** 2), q, steps)

    td = bench(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True, dtype=jnp.bfloat16))
    tf = bench(lambda q, k, v: flash_attention(
        q, k, v, causal=True).astype(jnp.bfloat16))
    tw = bench(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=512).astype(jnp.bfloat16))
    return {"section": "flash_vs_dense", "T": T,
            "dense_ms": round(td * 1e3, 3), "flash_ms": round(tf * 1e3, 3),
            "windowed512_ms": round(tw * 1e3, 3),
            "speedup": round(td / tf, 3)}


def s2d_vs_plain(batch=128, steps=10):
    import jax

    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from bench import _train_throughput
    from distributed_deep_learning_tpu.models.resnet import resnet50
    import jax.numpy as jnp

    mesh = build_mesh({"data": len(jax.devices())})
    ips_plain, _ = _train_throughput(
        resnet50(dtype=jnp.bfloat16), image_size=224, num_classes=1000,
        batch=batch, steps=steps, mesh=mesh)
    ips_s2d, _ = _train_throughput(
        resnet50(dtype=jnp.bfloat16, stem_s2d=True), image_size=224,
        num_classes=1000, batch=batch, steps=steps, mesh=mesh)
    return {"section": "s2d_stem", "batch": batch,
            "plain_ips": round(ips_plain, 1), "s2d_ips": round(ips_s2d, 1),
            "speedup": round(ips_s2d / ips_plain, 4)}


def batch_sweep(steps=10):
    """MFU playbook step 1 (PERFORMANCE.md): per-chip batch 64/128/256 on
    the headline ResNet-50 — the knee is where arithmetic intensity
    saturates the MXU."""
    import jax
    import jax.numpy as jnp

    from bench import chip_peak_flops, _train_throughput
    from distributed_deep_learning_tpu.models.resnet import resnet50
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    devices = jax.devices()
    mesh = build_mesh({"data": len(devices)})
    peak = chip_peak_flops(devices[0].device_kind)
    rows = []
    for per_chip in (64, 128, 256):
        batch = per_chip * len(devices)
        ips, fps = _train_throughput(
            resnet50(dtype=jnp.bfloat16, stem_s2d=True), image_size=224,
            num_classes=1000, batch=batch, steps=steps, mesh=mesh)
        mfu = ips * fps / batch / peak if fps and peak else None
        rows.append({"per_chip_batch": per_chip, "ips": round(ips, 1),
                     "mfu": round(mfu, 4) if mfu else None})
    return {"section": "batch_sweep", "rows": rows}


def lm_tokens(steps=10):
    """CausalLM tokens/sec/chip + MFU at the bench shape."""
    import jax
    import jax.numpy as jnp

    from bench import chip_peak_flops, _lm_throughput
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    devices = jax.devices()
    mesh = build_mesh({"data": len(devices)})
    peak = chip_peak_flops(devices[0].device_kind)
    batch, seq = 8 * len(devices), 2048
    tps, fps = _lm_throughput(batch=batch, seq_len=seq, steps=steps,
                              mesh=mesh, dtype=jnp.bfloat16)
    mfu = tps * (fps / (batch * seq)) / peak if fps and peak else None
    return {"section": "lm", "tokens_per_sec_per_chip": round(tps, 1),
            "mfu": round(mfu, 4) if mfu else None}


def flash_block_sweep(B=4, T=2048, H=8, D=64, steps=10):
    """Tune the flash kernel's (block_q, block_k) on this hardware — the
    first lever if the kernel lands below dense parity.  Records the best
    config so :func:`..ops.attention_pallas.flash_attention` picks it up
    as its TPU default (``tpu:flash_best_blocks``)."""
    import jax
    import jax.numpy as jnp

    from distributed_deep_learning_tpu.ops.attention_pallas import (
        flash_attention)

    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
               for kk in ks)
    rows = []
    best = None
    for bq, bk in ((128, 128), (128, 256), (256, 128), (256, 256),
                   (512, 128), (128, 512), (512, 512)):
        try:
            ms = _time_grad(
                lambda q, bq=bq, bk=bk: jnp.sum(flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk) ** 2),
                q, steps) * 1e3
        except Exception as exc:  # a VMEM-overflowing config is a data
            rows.append({"bq": bq, "bk": bk,      # point, not an abort
                         "error": f"{type(exc).__name__}"})
            continue
        rows.append({"bq": bq, "bk": bk, "ms": round(ms, 3)})
        if best is None or ms < best[2]:
            best = (bq, bk, ms)
    if best is None:
        return {"section": "flash_block_sweep", "T": T, "rows": rows,
                "best": None}
    if jax.default_backend() == "tpu":
        from distributed_deep_learning_tpu.utils.bench_records import (
            record_flash_blocks)

        record_flash_blocks(best[0], best[1])
    return {"section": "flash_block_sweep", "T": T, "rows": rows,
            "best": {"bq": best[0], "bk": best[1],
                     "ms": round(best[2], 3)}}


def gqa_speedup(B=4, T=2048, H=8, Hkv=2, D=64, steps=10):
    """GQA-native vs full-MHA flash at the bench shape: quantifies what
    the group× K/V HBM saving buys on this chip (the kernel maps query
    heads onto shared K/V heads in-kernel — round 5)."""
    import jax
    import jax.numpy as jnp

    from distributed_deep_learning_tpu.ops.attention_pallas import (
        flash_attention)

    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.bfloat16)

    def bench(hkv):
        k = jax.random.normal(ks[1], (B, T, hkv, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, T, hkv, D), jnp.bfloat16)
        return _time_grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=True) ** 2), q, steps)

    t_mha = bench(H)
    t_gqa = bench(Hkv)
    return {"section": "gqa_speedup", "T": T, "H": H, "Hkv": Hkv,
            "mha_ms": round(t_mha * 1e3, 3),
            "gqa_ms": round(t_gqa * 1e3, 3),
            "speedup": round(t_mha / t_gqa, 3)}


def lm_sweep(configs=((16, False), (32, False), (32, True),
                      (32, "dots_no_batch"), (64, True),
                      (64, "dots_no_batch")),
             seq=2048, steps=10, **model_kw):
    """LM MFU playbook: per-chip batch × remat on the bench LM shape.
    The first hardware datum (batch 8, from the lm_tokens section —
    deliberately NOT re-measured here: 26.7% MFU) is likely
    under-batched at T=2048; remat rows test whether trading ~⅓ more
    FLOPs for activation residency lets a bigger batch raise MFU.

    Each row PRINTS as its own JSON line the moment it completes: six
    cold compiles can cross a single 420 s section watchdog, so
    the parent grants this section a doubled budget AND keeps whole
    printed lines on timeout — completed rows always survive.  MFU for remat rows uses the model FLOPs/token from the
    first successful non-remat row — cost_analysis FLOPs on a remat
    program include the recompute, which is HFU, not MFU; both are
    recorded.  Failing configs (OOM at 64×2048 is plausible) record the
    full exception text as rows."""
    import jax
    import jax.numpy as jnp

    from bench import chip_peak_flops, _lm_throughput
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    devices = jax.devices()
    mesh = build_mesh({"data": len(devices)})
    peak = chip_peak_flops(devices[0].device_kind)
    model_flops_per_token = None
    done = 0
    for per_chip, remat in configs:
        batch = per_chip * len(devices)
        try:
            tps, fps = _lm_throughput(batch=batch, seq_len=seq,
                                      steps=steps, mesh=mesh,
                                      dtype=jnp.bfloat16, remat=remat,
                                      **model_kw)
        except Exception as exc:
            print(json.dumps({"section": "lm_sweep", "seq": seq,
                              "per_chip_batch": per_chip, "remat": remat,
                              "error": f"{type(exc).__name__}: {exc}"}),
                  flush=True)
            continue
        own_fpt = fps / (batch * seq) if fps else None
        if own_fpt and not remat and model_flops_per_token is None:
            model_flops_per_token = own_fpt
        row = {"section": "lm_sweep", "seq": seq,
               "per_chip_batch": per_chip, "remat": remat,
               "tokens_per_sec_per_chip": round(tps, 1)}
        mfu_fpt = own_fpt if not remat else model_flops_per_token
        if mfu_fpt and peak:
            row["mfu"] = round(tps * mfu_fpt / peak, 4)
        if remat and own_fpt and peak:
            # hardware FLOP/s utilisation incl. the remat recompute
            row["hfu"] = round(tps * own_fpt / peak, 4)
        print(json.dumps(row), flush=True)
        done += 1
    return {"section": "lm_sweep", "rows_completed": done,
            "configs": len(configs)}


def mfu_diag(batches=(128, 256)):
    """Roofline diagnosis of the headline step (VERDICT r4 #3: 29.6% MFU
    needs either a fix or a written analysis).  Pulls XLA ``cost_analysis``
    on the EXACT compiled train step: FLOPs, bytes accessed, arithmetic
    intensity, and the roofline-implied MFU ceiling for this chip
    (peak_flops / hbm_bw ridge point ≈ 240 FLOPs/byte on v5e)."""
    import jax
    import jax.numpy as jnp

    from bench import _build_train_step, chip_peak_flops
    from distributed_deep_learning_tpu.models.resnet import resnet50
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    devices = jax.devices()
    mesh = build_mesh({"data": len(devices)})
    on_tpu = devices[0].platform == "tpu"
    peak = chip_peak_flops(devices[0].device_kind) if on_tpu else None
    # v5e/v5p/v4 HBM GB/s by device_kind substring (public chip specs)
    hbm = None
    kind = devices[0].device_kind.lower()
    for sub, bw in (("v6", 1640e9), ("v5 lite", 819e9), ("v5e", 819e9),
                    ("v5p", 2765e9), ("v5", 2765e9), ("v4", 1228e9)):
        if sub in kind:
            hbm = bw
            break
    from bench import _cost_analysis

    rows = []
    for batch in batches:
        try:  # a failing batch (256/chip can OOM) is a data point, not
            step, state, x, y = _build_train_step(  # an abort — keep the
                resnet50(dtype=jnp.bfloat16 if on_tpu else jnp.float32,  # rows
                         stem_s2d=on_tpu), image_size=224,  # already earned
                num_classes=1000, batch=batch * len(devices), mesh=mesh)
            analysis = _cost_analysis(step.lower(state, x, y).compile())
        except Exception as exc:
            rows.append({"per_chip_batch": batch,
                         "error": f"{type(exc).__name__}: {exc}"})
            continue
        flops = float(analysis.get("flops", 0.0))
        byt = float(analysis.get("bytes accessed", 0.0))
        ai = flops / byt if byt else None
        row = {"per_chip_batch": batch, "flops": flops,
               "bytes_accessed": byt,
               "arith_intensity": round(ai, 1) if ai else None}
        opt_s = float(analysis.get("optimal_seconds", 0.0))
        if opt_s and peak:
            # XLA's own roofline estimate -> the MFU it thinks is possible
            row["xla_optimal_seconds"] = opt_s
            row["xla_implied_mfu"] = round(flops / opt_s / peak, 3)
        if ai and peak and hbm:
            ridge = peak / hbm
            # roofline ceiling: HBM-bound below the ridge point
            row["ridge_flops_per_byte"] = round(ridge, 1)
            row["roofline_mfu_ceiling"] = round(
                min(1.0, ai / ridge), 3)
        rows.append(row)
    return {"section": "mfu_diag", "device": devices[0].device_kind,
            "rows": rows}


def serving(n_requests=48, max_slots=16):
    """Continuous-batching engine vs naive generate() at a TPU-shaped
    geometry (GPT-2-small-ish trunk, long mixed-length trace).  On TPU
    the per-tick device time is small, so this also measures the host
    round-trip share of the tick — the datum that decides whether the
    next engine iteration needs multi-tick device loops."""
    import jax

    from distributed_deep_learning_tpu.serve.bench import serving_bench

    on_tpu = jax.default_backend() == "tpu"
    model_kw = (dict(vocab_size=32768, num_layers=12, d_model=768,
                     num_heads=12, mlp_dim=3072, max_len=1024)
                if on_tpu else
                dict(vocab_size=512, num_layers=2, d_model=128,
                     num_heads=4, mlp_dim=256, max_len=192))
    rec = serving_bench(
        n_requests=n_requests if on_tpu else 8,
        max_slots=max_slots if on_tpu else 4,
        model_kw=model_kw,
        prompt_lens=(16, 256) if on_tpu else (4, 32),
        new_tokens=(16, 256) if on_tpu else (4, 16))
    return {"section": "serving", "on_tpu": on_tpu, **rec}


def serving_paged(n_requests=48, max_slots=16):
    """Paged engine under trace-driven SLO load at a TPU-shaped geometry
    (ISSUE 9): shared-system-prompt Poisson trace, chunked prefill,
    1-layer speculative draft, A/B'd against the v1 engine on the same
    trace.  On TPU the interesting harvest is whether prefix reuse and
    speculation still pay once the per-token device time shrinks — the
    host-side block bookkeeping is a fixed cost per tick, so this section
    decides how much of the paged win is compute saved vs host overhead
    moved."""
    import jax

    from distributed_deep_learning_tpu.serve.bench import paged_serving_bench

    on_tpu = jax.default_backend() == "tpu"
    model_kw = (dict(vocab_size=32768, num_layers=12, d_model=768,
                     num_heads=12, mlp_dim=3072, max_len=1024)
                if on_tpu else
                dict(vocab_size=512, num_layers=2, d_model=128,
                     num_heads=4, mlp_dim=256, max_len=192))
    load_kw = (dict(n_requests=n_requests, arrival="poisson", rate=4.0,
                    prompt_short=(16, 64), prompt_long=(128, 384),
                    long_frac=0.3, shared_prefix_len=128, shared_frac=0.6,
                    new_tokens=(16, 128), slo_ttft_ms=500.0,
                    slo_e2e_ms=5000.0)
               if on_tpu else
               dict(n_requests=10))
    rec = paged_serving_bench(
        load_kw=load_kw,
        model_kw=model_kw,
        max_slots=max_slots if on_tpu else 4,
        kv_block_size=32 if on_tpu else 16,
        prefill_chunk=128 if on_tpu else 32,
        draft_layers=2 if on_tpu else 1,
        spec_k=4)
    return {"section": "serving_paged", "on_tpu": on_tpu, **rec}


def serving_quant(n_requests=48, max_slots=16):
    """Quantized serving hot path at a TPU-shaped geometry (ISSUE 14):
    the full-precision vs int8-KV+int8-weight A/B on one trace, PLUS the
    block-table-aware flash-decode Pallas kernel
    (ops/paged_decode_pallas.py) timed against the gather-then-mask lax
    reference on the real pools.  On TPU the kernel number is the
    harvest: scalar-prefetch block indexing replaces the HBM gather, so
    kernel-vs-lax is a direct read of how much of the decode tick was
    the gather — and the int8 variant measures whether in-register
    dequant keeps the 3.5x wire-byte cut free of MXU stalls."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_deep_learning_tpu.ops.paged_decode_pallas import (
        paged_decode_reference, paged_flash_decode)
    from distributed_deep_learning_tpu.serve.bench import (
        quantized_serving_bench)
    from distributed_deep_learning_tpu.serve.quant import quantize_rows

    on_tpu = jax.default_backend() == "tpu"
    model_kw = (dict(vocab_size=32768, num_layers=12, d_model=768,
                     num_heads=12, mlp_dim=3072, max_len=1024)
                if on_tpu else
                dict(vocab_size=512, num_layers=2, d_model=128,
                     num_heads=4, mlp_dim=256, max_len=192))
    load_kw = (dict(n_requests=n_requests, arrival="poisson", rate=4.0,
                    prompt_short=(16, 64), prompt_long=(128, 384),
                    long_frac=0.3, shared_prefix_len=128, shared_frac=0.6,
                    new_tokens=(16, 128), slo_ttft_ms=500.0,
                    slo_e2e_ms=5000.0)
               if on_tpu else
               dict(n_requests=10))
    rec = quantized_serving_bench(
        load_kw=load_kw, model_kw=model_kw,
        max_slots=max_slots if on_tpu else 4,
        kv_block_size=32 if on_tpu else 16,
        prefill_chunk=128 if on_tpu else 32)

    # kernel vs lax reference on pool shapes matching the A/B geometry
    B = max_slots if on_tpu else 4
    Hkv = model_kw["num_heads"]
    D = model_kw["d_model"] // Hkv
    bs = 32 if on_tpu else 16
    Bps = (model_kw["max_len"] // bs)
    N = B * Bps + 1
    rng = np.random.default_rng(0)
    from distributed_deep_learning_tpu.serve.paged import merge_trailing

    # pool leaves as the paged engine rests them: trailing dims merged
    q = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, bs, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, bs, Hkv, D)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(N - 1)[:B * Bps].reshape(B, Bps).astype(np.int32))
    lens = jnp.asarray(rng.integers(1, Bps * bs + 1, B), jnp.int32)

    kq, vq = (merge_trailing(quantize_rows(p)) for p in (kp, vp))
    kp, vp = merge_trailing(kp), merge_trailing(vp)

    def timed(fn, *a, **kw):
        out = jax.block_until_ready(fn(*a, **kw))   # compile
        reps = 20 if on_tpu else 3
        t0 = _time.perf_counter()
        for _ in range(reps):
            out = jax.block_until_ready(fn(*a, **kw))
        return out, (_time.perf_counter() - t0) / reps

    interp = None if on_tpu else True    # CPU smoke: interpret mode
    ref, t_lax = timed(jax.jit(paged_decode_reference), q, kp, vp,
                       tables, lens)
    out, t_kern = timed(paged_flash_decode, q, kp, vp, tables, lens,
                        interpret=interp)
    outq, t_kern_q = timed(paged_flash_decode, q, kq, vq, tables, lens,
                           interpret=interp)
    kernel = {
        "shapes": {"slots": B, "heads": Hkv, "head_dim": D,
                   "block_size": bs, "blocks_per_slot": Bps},
        "lax_reference_ms": round(t_lax * 1e3, 3),
        "kernel_ms": round(t_kern * 1e3, 3),
        "kernel_int8_ms": round(t_kern_q * 1e3, 3),
        "kernel_speedup_vs_lax": round(t_lax / t_kern, 3) if t_kern else None,
        "max_abs_err_vs_lax": float(jnp.max(jnp.abs(out - ref))),
        "interpret_mode": bool(interp),
    }
    return {"section": "serving_quant", "on_tpu": on_tpu,
            "kernel": kernel, **rec}


def serving_fleet(n_requests=64, replicas=3):
    """Fleet serving at a TPU-shaped geometry (ISSUE 15): N paged
    replicas behind the health-checked prefix-affinity router on one
    shared-prefix Poisson trace with priority classes.  On TPU the
    harvest is throughput and routing quality at real decode speeds —
    predicted prefix-hit tokens, per-priority SLO attainment and the
    per-replica compile counts (decode_compiles staying 1 per replica
    is the compile-once discipline surviving the router)."""
    import jax

    from distributed_deep_learning_tpu.serve.bench import (
        fleet_serving_bench)

    on_tpu = jax.default_backend() == "tpu"
    model_kw = (dict(vocab_size=32768, num_layers=12, d_model=768,
                     num_heads=12, mlp_dim=3072, max_len=1024)
                if on_tpu else
                dict(vocab_size=512, num_layers=2, d_model=128,
                     num_heads=4, mlp_dim=256, max_len=192))
    load_kw = (dict(n_requests=n_requests, arrival="poisson", rate=4.0,
                    prompt_short=(16, 64), prompt_long=(128, 256),
                    long_frac=0.3, shared_prefix_len=128, shared_frac=0.6,
                    new_tokens=(16, 128), slo_ttft_ms=500.0,
                    slo_e2e_ms=5000.0)
               if on_tpu else
               dict(n_requests=12, prompt_long=(16, 32),
                    shared_prefix_len=16, new_tokens=(4, 16)))
    rec = fleet_serving_bench(
        replicas=replicas, load_kw=load_kw, model_kw=model_kw,
        max_slots=16 if on_tpu else 4,
        kv_block_size=32 if on_tpu else 16,
        prefill_chunk=128 if on_tpu else 32)
    return {"section": "serving_fleet", "on_tpu": on_tpu, **rec}


def serving_disagg(n_requests=48):
    """Disaggregated prefill/decode serving at a TPU-shaped geometry
    (ISSUE 16): prefill worker pool + decode worker pool on separate
    chips, joined by device-to-device KV-block migration, A/B'd against
    the unified paged engine on the same shared-prefix Poisson trace.
    On TPU this is the first run where the migration primitive moves
    blocks over real ICI (the CPU number times emulated-host
    device_put) and where the prefill pool's batched chunk program runs
    on silicon the decode pool never shares — the interference-free ITL
    DistServe buys.  Greedy outputs must stay bit-identical to the
    unified engine (``token_agreement`` 1.0) and every compile counter
    must read 1."""
    # one device per pool: on the CPU smoke box force an emulated pair
    # before backend init (no-op on TPU — the flag only shapes the
    # host platform)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()

    import jax

    from distributed_deep_learning_tpu.runtime.bootstrap import (
        require_devices)
    from distributed_deep_learning_tpu.serve.bench import (
        disagg_serving_bench)

    require_devices(2)
    on_tpu = jax.default_backend() == "tpu"
    model_kw = (dict(vocab_size=32768, num_layers=12, d_model=768,
                     num_heads=12, mlp_dim=3072, max_len=1024)
                if on_tpu else None)
    load_kw = (dict(n_requests=n_requests, arrival="poisson", rate=4.0,
                    prompt_short=(16, 64), prompt_long=(128, 256),
                    long_frac=0.3, shared_prefix_len=128, shared_frac=0.6,
                    new_tokens=(16, 128), slo_ttft_ms=500.0,
                    slo_e2e_ms=5000.0)
               if on_tpu else dict(n_requests=12))
    rec = disagg_serving_bench(
        seed=17, load_kw=load_kw, model_kw=model_kw,
        prefill_workers=1, decode_workers=1,
        prefill_streams=4, max_slots=16 if on_tpu else 8,
        kv_block_size=32 if on_tpu else 16,
        prefill_chunk=128 if on_tpu else 32)
    return {"section": "serving_disagg", "on_tpu": on_tpu, **rec}


def serving_rebalance(seed=0):
    """Live fleet rebalancing on real hardware (ISSUE 18): the full
    rebalance gauntlet — mid-request slot evacuation off a degraded
    replica with digest-verified committed-KV migration (bit-identical
    resume over fp32 AND int8 pools), ``evac_drop`` payload corruption
    rolled back with zero loss, a target crash mid-evacuation aborted
    and ledger-replayed, elastic autoscaling with the drain-protocol
    shrink, ``scale_thrash`` hysteresis damping, and disaggregated
    prefill/decode pool reassignment.  On TPU the evacuation path moves
    committed KV over real ICI instead of emulated-host device_put —
    the first measurement of mid-request drain latency at silicon
    transfer rates."""
    # the pool-elasticity scenario needs a reassignable third device:
    # on the CPU smoke box force an emulated quad before backend init
    # (no-op on TPU — the flag only shapes the host platform)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()

    import jax

    from distributed_deep_learning_tpu.runtime.bootstrap import (
        require_devices)
    from distributed_deep_learning_tpu.utils.chaos import (
        run_rebalance_drill)

    require_devices(3)
    on_tpu = jax.default_backend() == "tpu"
    rec = run_rebalance_drill(seed=seed)
    return {"section": "serving_rebalance", "on_tpu": on_tpu, **rec}


def autotune(workload="gpt"):
    """Auto-parallelism planner on real hardware: search the plan lattice
    for a TPU-shaped LM geometry (small-GPT on TPU, toy on CPU smoke) and
    report the winning plan + measured best-vs-default step rate.  On TPU
    this is the first run where the analytic HBM model has a real
    ``bytes_limit`` budget to prune against and ``memory_analysis()``
    reports device bytes — the cross-check data the CPU box cannot
    produce."""
    import jax

    from distributed_deep_learning_tpu.tune.artifact import plan_hash
    from distributed_deep_learning_tpu.tune.memory import hbm_budget
    from distributed_deep_learning_tpu.tune.search import run_search
    from distributed_deep_learning_tpu.utils.config import parse_args
    from distributed_deep_learning_tpu.workloads import get_spec

    on_tpu = jax.default_backend() == "tpu"
    argv = (["-e", "1", "-b", "64", "-m", "data", "-l", "4", "-s", "256"]
            if on_tpu else
            ["-e", "1", "-b", "16", "-m", "data", "-l", "2", "-s", "64"])
    os.environ.setdefault("DDL_DATA_LIMIT", "512")
    spec = get_spec(workload)
    config = parse_args(argv, workload=workload)
    result = run_search(
        spec, config, trial_steps=4 if on_tpu else 2,
        max_trials=8 if on_tpu else 4,
        space_options=dict(zero_options=("none", "fsdp"),
                           compress_options=("none",),
                           grad_accum_options=(1,)))
    best_trial = next((t for t in result.trials
                       if t.plan == result.best and not t.infeasible), None)
    return {
        "section": "autotune", "on_tpu": on_tpu, "workload": workload,
        "plan_hash": plan_hash(result.best),
        "plan": result.best.describe(),
        "best_steps_per_sec": round(result.best_sps, 3),
        "baseline_steps_per_sec": round(result.baseline_sps, 3),
        "speedup": round(result.best_sps / result.baseline_sps, 4)
            if result.baseline_sps else None,
        "n_candidates": result.n_candidates,
        "n_pruned_analytic": result.n_pruned,
        "n_infeasible": result.n_infeasible,
        "hbm_budget_bytes": hbm_budget(jax.devices()),
        "xla_memory_analysis": best_trial.memory if best_trial else {},
        "search_seconds": round(result.search_seconds, 1),
    }


def reshard():
    """Cross-topology reshard on real hardware: redistribution bandwidth
    for the host-gather and chunked per-shard paths across an N → N-2
    mesh change, plus the full shrink drill (kill 2, re-plan, reshard,
    continue).  On TPU this is the first run where the chunked path's
    point — the host never materialises the full array, and shard slices
    move at real ICI/PCIe bandwidth — shows up in seconds/GB; the CPU
    numbers in bench.py only time the slicing logic."""
    import jax

    from bench import _reshard

    return {"section": "reshard", "on_tpu": jax.default_backend() == "tpu",
            **(_reshard() or {})}


def collectives():
    """Quantized + ring-overlapped FSDP collectives on real hardware: the
    full ``scripts/comm_bench.py`` record — int8/bf16 wire-byte cut, ring
    bit-parity, fused ``gather_matmul`` overlap fraction, explicit-FSDP
    loss parity.  On TPU the overlap fraction measures actual ICI wire
    time pipelined under matmuls (the double-buffered ppermutes); the CPU
    number in bench.py only sees the materialisation win."""
    import jax

    from bench import _collectives

    return {"section": "collectives",
            "on_tpu": jax.default_backend() == "tpu",
            **(_collectives() or {})}


def observability(steps_hint=10):
    """Unified telemetry e2e on real hardware: a short ``--obs`` training
    run, then harvest the goodput breakdown + MFU straight from the
    emitted JSONL stream — the numbers PERFORMANCE.md §Observability
    records.  On TPU the MFU field is live (the chip is in the peak
    table); on CPU smoke it exercises the same path via
    ``DDL_OBS_PEAK_FLOPS``.  Also runs the instrumentation-overhead A/B
    (the <2% acceptance bar) on this box.

    Generation 2 (ISSUE 11): the run also exports the per-step span
    trace (``--obs-trace``) so the harvest proves the Perfetto export
    path on real hardware (span count + dropped count from the
    ``obs_trace`` event), and the tracing-overhead A/B
    (:func:`obs.bench.trace_overhead_bench`, its own <2% bar) runs
    beside the gen-1 one."""
    import tempfile

    import jax

    from distributed_deep_learning_tpu.obs.bench import (
        overhead_bench, trace_overhead_bench)
    from distributed_deep_learning_tpu.obs.export import read_events
    from distributed_deep_learning_tpu.utils.config import parse_args
    from distributed_deep_learning_tpu.workloads import (get_spec,
                                                         run_workload)

    on_tpu = jax.default_backend() == "tpu"
    os.environ.setdefault("DDL_DATA_LIMIT", "512" if on_tpu else "256")
    if not on_tpu:
        # exercise the full MFU path on the smoke box (arbitrary peak)
        os.environ.setdefault("DDL_OBS_PEAK_FLOPS", "1e12")
    tmpdir = tempfile.mkdtemp(prefix="obs_val_")
    stream = os.path.join(tmpdir, "obs_events.jsonl")
    trace = os.path.join(tmpdir, "trace.json")
    argv = ["-e", "2", "-b", "64" if on_tpu else "32", "-m", "data",
            "--obs", "--obs-file", stream, "--obs-trace", trace]
    run_workload(get_spec("mlp"), parse_args(argv, workload="mlp"))

    events = list(read_events(stream))
    run_gp = next((e for e in events if e.get("event") == "obs_goodput"
                   and e.get("scope") == "run"), {})
    mfu = next((e for e in events if e.get("event") == "obs_mfu"), {})
    tr = next((e for e in events if e.get("event") == "obs_trace"), {})
    return {
        "section": "observability", "on_tpu": on_tpu,
        "goodput_fractions": run_gp.get("fractions"),
        "wall_seconds": run_gp.get("wall_seconds"),
        "steps": run_gp.get("steps"),
        "mfu": mfu.get("mfu"),
        "steps_per_sec": mfu.get("steps_per_sec"),
        "step_flops": mfu.get("step_flops"),
        "device_kind": mfu.get("device_kind"),
        "trace_spans": tr.get("spans"),
        "trace_dropped": tr.get("dropped"),
        "overhead": overhead_bench(
            steps=48, repeats=5 if on_tpu else 3),
        "trace_overhead": trace_overhead_bench(
            steps=48, repeats=5 if on_tpu else 3),
    }


def _record_flash_gate(result: dict) -> None:
    """Persist the measured ratio as the `--attention auto` gate datum."""
    from distributed_deep_learning_tpu.utils.bench_records import (
        record_flash_speedup)

    record_flash_speedup(result["speedup"])


SECTIONS = ("flash_block_sweep", "flash_vs_dense", "gqa_speedup",
            "s2d_vs_plain", "batch_sweep", "lm_tokens", "serving",
            "serving_paged", "serving_quant", "serving_fleet",
            "serving_disagg", "serving_rebalance", "autotune", "reshard",
            "observability", "collectives", "mfu_diag", "lm_sweep")


def _run_section(name: str) -> int:
    """Run one section inline; returns the child's exit code (1 when the
    section raised — its error line still prints)."""
    import jax

    from distributed_deep_learning_tpu.runtime.bootstrap import (
        describe_devices, enable_compile_cache)

    # persistent XLA cache: a re-run of the same section skips its compiles
    enable_compile_cache()
    fn = globals()[name]
    try:
        result = fn()
        print(json.dumps({**result, "device": describe_devices()}),
              flush=True)
        if name == "flash_vs_dense" and jax.default_backend() == "tpu":
            _record_flash_gate(result)
        return 0
    except Exception as exc:  # the other sections still get their shot
        print(json.dumps({"section": name,
                          "error": f"{type(exc).__name__}: {exc}"}),
              flush=True)
        return 1


def main() -> int:
    """Each section runs in ITS OWN watchdogged subprocess: a hang in
    section 1 must not eat the whole run — later sections still get their
    shot — and one process at a time owns the chip (this parent stays off
    JAX).  ``--section NAME`` runs one section inline (the child mode).
    ``TPU_VALIDATION_SECTION_TIMEOUT`` (default 420 s) bounds each.
    Returns non-zero when any section failed, timed out or crashed."""
    import subprocess

    if len(sys.argv) > 2 and sys.argv[1] == "--section":
        return _run_section(sys.argv[2])
    failed = 0
    budget = float(os.environ.get("TPU_VALIDATION_SECTION_TIMEOUT", 420))
    # lm_sweep runs 6 cold compiles; a single default budget would cut
    # its tail rows (the 64-per-chip data the sweep exists to collect)
    budgets = {"lm_sweep": 2 * budget}
    for name in SECTIONS:
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--section", name],
                timeout=budgets.get(name, budget),
                stdout=subprocess.PIPE, text=True)
            sys.stdout.write(proc.stdout)
            sys.stdout.flush()
            failed += proc.returncode != 0
            if proc.returncode != 0 and not proc.stdout.strip():
                # crashed (OOM-kill, segfault in the TPU runtime, import
                # error) rather than hung: record it like the old inline
                # loop did instead of silently dropping the section
                print(json.dumps({"section": name,
                                  "error": f"child rc={proc.returncode}"}),
                      flush=True)
        except subprocess.TimeoutExpired as exc:
            failed += 1
            if exc.stdout:  # results printed before the hang still count
                out = exc.stdout if isinstance(exc.stdout, str) \
                    else exc.stdout.decode(errors="replace")
                # keep whole lines only: a child killed mid-write must not
                # corrupt the one-JSON-object-per-line contract
                out = out[:out.rfind("\n") + 1]
                sys.stdout.write(out)
            print(json.dumps({"section": name,
                              "error": f"timeout after "
                                       f"{budgets.get(name, budget):.0f}s"}),
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
