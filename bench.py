"""Headline benchmark: ResNet-50 bf16 train throughput (images/sec/chip) + MFU.

The driver-assigned north star (``BASELINE.json``: "ResNet-50/ImageNet
images/sec/chip") is the headline metric; the reference's own flagship CNN
(DenseNet-BC on 64x64 PCB crops) is kept as a secondary key.  Prints ONE
JSON line ``{"metric", "value", "unit", "vs_baseline", ...}`` with extra
keys: ``mfu`` (measured FLOP/s / chip peak bf16 FLOP/s, from XLA
``cost_analysis`` on the exact compiled train step), ``flops_per_image``,
``device_kind``, and ``secondary`` (the DenseNet number).

The reference publishes no numbers (BASELINE.md) — the baseline here is this
repo's own first recorded measurement per (platform, model) key, stored in
``bench_baseline.json``.  ``vs_baseline`` is value / stored-baseline.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Chip peak table + lookup now live with the MFU accounting in obs/mfu.py
# (ISSUE 7); re-exported here so existing `from bench import ...` users keep
# working.  The import is cheap — obs.mfu touches neither jax nor devices.
from distributed_deep_learning_tpu.obs.mfu import (  # noqa: E402,F401
    PEAK_BF16_FLOPS, chip_peak_flops)


def _build_train_step(model, *, image_size, num_classes, batch, mesh):
    """The EXACT headline train-step setup: (train_step, state, x, y).

    Shared by the timing loop and the mfu_diag cost probe so the roofline
    numbers describe the same compiled program the throughput came from.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_deep_learning_tpu.data.loader import BATCH_AXES
    from distributed_deep_learning_tpu.train.objectives import (
        cross_entropy_loss)
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.train.step import (make_step_fns,
                                                          place_state)

    rng = np.random.default_rng(42)
    x = jnp.asarray(rng.standard_normal(
        (batch, image_size, image_size, 3), dtype=np.float32))
    y = jax.nn.one_hot(jnp.asarray(rng.integers(0, num_classes, batch)),
                       num_classes)

    state = create_train_state(model, jax.random.key(0), x[:1],
                               optax.sgd(0.01, momentum=0.9))
    state = place_state(state, mesh)
    train_step, _ = make_step_fns(mesh, cross_entropy_loss)
    sh = NamedSharding(mesh, P(BATCH_AXES))
    x, y = jax.device_put(x, sh), jax.device_put(y, sh)
    return train_step, state, x, y


def _train_throughput(model, *, image_size, num_classes, batch, steps, mesh):
    """images/sec/chip + FLOPs/step for one jitted train step of ``model``.

    Sync via a host scalar fetch: a device-to-host read of the last
    step's loss is an end-to-end barrier on everything dispatched before it.
    """
    n_chips = len(mesh.devices.flatten())
    train_step, state, x, y = _build_train_step(
        model, image_size=image_size, num_classes=num_classes, batch=batch,
        mesh=mesh)
    return _timed_steps(train_step, state, x, y, steps=steps,
                        n_chips=n_chips, batch=batch)


def _cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict — shared by the timing loop
    and mfu_diag."""
    return compiled.cost_analysis() or {}


def _timed_steps(train_step, state, x, y, *, steps, n_chips, batch):
    """Time ``steps`` dispatches of ``train_step``; see _train_throughput
    for the host-fetch sync rationale."""
    # AOT-compile once: the same executable serves cost_analysis AND the
    # timing loop (lower().compile() does not seed jit's dispatch cache, so
    # calling the jitted fn after it would compile a second time)
    step = train_step.lower(state, x, y).compile()
    # per-device module FLOPs x device count = whole-step FLOPs
    flops_per_step = float(
        _cost_analysis(step).get("flops", 0.0)) * n_chips or None

    state, m = step(state, x, y)  # warmup
    float(m["loss"])
    state, m = step(state, x, y)
    float(m["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, x, y)
    float(m["loss"])
    dt = time.perf_counter() - t0

    return batch * steps / dt / n_chips, flops_per_step


def _lm_throughput(*, batch, seq_len, steps, mesh, dtype, remat=False,
                   vocab_size=32768, num_layers=12, d_model=768,
                   num_heads=12, mlp_dim=3072):
    """tokens/sec/chip + FLOPs/step for a CausalLM train step (flash
    attention + fused linear-cross-entropy head, weight-tied).

    ``remat`` wraps the forward in ``jax.checkpoint``: ``True`` is the
    whole-forward recompute-everything policy; a policy NAME from
    ``train.step.REMAT_POLICIES`` (e.g. ``"dots_no_batch"``) keeps
    matmul outputs so only elementwise chains recompute.  ~⅓ more FLOPs
    (less under the dots policies) buys the activation memory back, so
    larger per-chip batches fit — the lm_sweep validation section
    measures whether the trade raises MFU at T=2048."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_deep_learning_tpu.data.loader import BATCH_AXES
    from distributed_deep_learning_tpu.models.transformer import CausalLM
    from distributed_deep_learning_tpu.ops.attention_pallas import (
        make_attention_fn)

    n_chips = len(mesh.devices.flatten())
    on_tpu = mesh.devices.flatten()[0].platform == "tpu"
    model = CausalLM(vocab_size=vocab_size, num_layers=num_layers,
                     d_model=d_model, num_heads=num_heads, mlp_dim=mlp_dim,
                     dtype=dtype,
                     attention_fn=make_attention_fn() if on_tpu else None)
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(1, vocab_size, (batch, seq_len + 1)),
                       jnp.int32)

    params = model.init(jax.random.key(0), toks[:1, :-1])
    tx = optax.adamw(1e-4)
    opt_state = tx.init(params)

    def step(params, opt_state, toks):
        def loss_fn(p):
            h = model.apply(p, toks[:, :-1], train=True)
            return model.loss(p, h, toks[:, 1:])

        if remat:
            from distributed_deep_learning_tpu.train.step import (
                _remat_policy)

            policy = _remat_policy(remat if isinstance(remat, str)
                                   else "nothing")
            loss_fn = jax.checkpoint(loss_fn, policy=policy)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2, loss

    sh = NamedSharding(mesh, P(BATCH_AXES))
    repl = NamedSharding(mesh, P())
    toks = jax.device_put(toks, sh)
    params, opt_state = jax.device_put((params, opt_state), repl)
    jstep = jax.jit(step, in_shardings=(repl, repl, sh),
                    out_shardings=(repl, repl, repl), donate_argnums=(0, 1))

    flops_per_step = None
    run = jstep
    try:
        compiled = jstep.lower(params, opt_state, toks).compile()
        flops_per_step = float(
            _cost_analysis(compiled).get("flops", 0.0)) * n_chips or None
        run = compiled
    except Exception:
        pass

    params, opt_state, loss = run(params, opt_state, toks)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = run(params, opt_state, toks)
    float(loss)
    dt = time.perf_counter() - t0
    return batch * seq_len * steps / dt / n_chips, flops_per_step


def _input_pipeline(*, mesh, dtype) -> dict | None:
    """End-to-end train throughput THROUGH the host input pipeline
    (VERDICT r4 item: the reference's data layer was its known bottleneck,
    ``CNN/dataset.py:90-107`` per-item ``.to(device)``; this repo fixed the
    design — batch-level gather + one sharded device_put + thread
    prefetch — and this section measures it instead of asserting it).

    Times the SAME DenseNet train step three ways: preloaded
    device-resident tensors (compute floor), a synthetic in-memory
    ArrayDataset through DeviceLoader+PrefetchLoader, and an
    ImageFolderDataset over freshly generated JPEG files (PIL decode +
    native C++ resize on the measured path).  ``stall_fraction`` =
    1 - preloaded_time/loader_time (0 = input fully hidden).
    """
    import tempfile

    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_deep_learning_tpu.data.datasets import synthetic_pcb
    from distributed_deep_learning_tpu.data.loader import (BATCH_AXES,
                                                           DeviceLoader,
                                                           PrefetchLoader)
    from distributed_deep_learning_tpu.train.objectives import (
        cross_entropy_loss)
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.train.step import (make_step_fns,
                                                          place_state)
    from __graft_entry__ import _flagship
    import jax.numpy as jnp

    n_chips = len(mesh.devices.flatten())
    on_tpu = mesh.devices.flatten()[0].platform == "tpu"
    batch = int(os.environ.get("BENCH_INPUT_BATCH",
                               256 * n_chips if on_tpu else 8))
    steps = int(os.environ.get("BENCH_INPUT_STEPS", 12 if on_tpu else 2))
    n_rows = max(2 * batch, 512)

    ds = synthetic_pcb(n=n_rows)
    model = _flagship(dtype=dtype)
    state = create_train_state(model, jax.random.key(0),
                               jnp.ones((1, 64, 64, 3)),
                               optax.sgd(0.01, momentum=0.9))
    state = place_state(state, mesh)
    train_step, _ = make_step_fns(mesh, cross_entropy_loss)
    sh = NamedSharding(mesh, P(BATCH_AXES))

    def run_epochs(loader, n_steps):
        """Drive ``n_steps`` train steps from ``loader``, cycling epochs;
        returns seconds/step (host fetch at the end = device barrier)."""
        nonlocal state
        it, done = iter(loader), 0
        # warmup one batch (compile with these shapes)
        x, y = next(it)
        state, m = train_step(state, x, y)
        float(m["loss"])
        t0 = time.perf_counter()
        while done < n_steps:
            try:
                x, y = next(it)
            except StopIteration:
                it = iter(loader)
                continue
            state, m = train_step(state, x, y)
            done += 1
        float(m["loss"])
        return (time.perf_counter() - t0) / n_steps

    # --- floor: preloaded device tensors --------------------------------
    rng = np.random.default_rng(3)
    xh = rng.standard_normal((batch, 64, 64, 3), dtype=np.float32)
    yh = np.eye(6, dtype=np.float32)[rng.integers(0, 6, batch)]
    xd, yd = jax.device_put(xh, sh), jax.device_put(yh, sh)
    state, m = train_step(state, xd, yd)
    float(m["loss"])  # warm
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = train_step(state, xd, yd)
    float(m["loss"])
    t_pre = (time.perf_counter() - t0) / steps

    out: dict = {"batch": batch,
                 "preloaded_images_per_sec_per_chip":
                     round(batch / t_pre / n_chips, 2)}

    # --- synthetic twin through DeviceLoader + prefetch -----------------
    loader = PrefetchLoader(DeviceLoader(ds, np.arange(n_rows), batch, mesh,
                                         shuffle=True), depth=2)
    t_syn = run_epochs(loader, steps)
    out["synthetic"] = {
        "images_per_sec_per_chip": round(batch / t_syn / n_chips, 2),
        "stall_fraction": round(max(0.0, 1 - t_pre / t_syn), 4)}

    # --- ImageFolder over generated JPEGs (decode + resize measured) ----
    try:
        from PIL import Image

        from distributed_deep_learning_tpu.data.imagefolder import (
            ImageFolderDataset)

        with tempfile.TemporaryDirectory() as root:
            # enough files for at least one full batch (6 classes)
            per = max(85, -(-batch // 6))
            r2 = np.random.default_rng(4)
            for c in range(6):
                d = os.path.join(root, f"class{c}")
                os.makedirs(d)
                for i in range(per):
                    arr = r2.integers(0, 255, (72, 72, 3), dtype=np.uint8)
                    Image.fromarray(arr).save(
                        os.path.join(d, f"im{i}.jpg"))
            ifds = ImageFolderDataset(root, image_size=64,
                                      max_cached_images=1)
            n_use = (len(ifds) // batch) * batch
            if n_use:
                il = PrefetchLoader(
                    DeviceLoader(ifds, np.arange(n_use), batch, mesh,
                                 shuffle=True), depth=2)
                t_img = run_epochs(il, steps)
                out["imagefolder"] = {
                    "images_per_sec_per_chip":
                        round(batch / t_img / n_chips, 2),
                    "stall_fraction":
                        round(max(0.0, 1 - t_pre / t_img), 4)}

                # --- the same JPEGs through the packed mmap cache -------
                # (decode once offline, then zero per-sample Python work
                # per epoch — data/packed.py; the stall_fraction here is
                # the one --packed-cache training actually sees)
                from distributed_deep_learning_tpu.data.packed import (
                    PackedDataset, pack_dataset)

                cache = os.path.join(root, "cache.ddlpack")
                t0p = time.perf_counter()
                pack_dataset(ifds, cache)
                t_pack = time.perf_counter() - t0p
                pds = PackedDataset(cache)
                pl = PrefetchLoader(
                    DeviceLoader(pds, np.arange(n_use), batch, mesh,
                                 shuffle=True), depth=2)
                t_pk = run_epochs(pl, steps)
                out["packed"] = {
                    "images_per_sec_per_chip":
                        round(batch / t_pk / n_chips, 2),
                    "stall_fraction":
                        round(max(0.0, 1 - t_pre / t_pk), 4),
                    "pack_seconds": round(t_pack, 2)}
    except Exception as exc:
        print(f"bench: imagefolder input section failed "
              f"({type(exc).__name__}: {exc})", file=sys.stderr)
    return out


def _serving() -> dict | None:
    """Serving throughput A/B (ISSUE 2): the continuous-batching engine
    vs run-to-completion ``generate()`` on a seeded mixed-length trace —
    CPU-measurable like ``input_pipeline`` (host scheduling + XLA decode
    both run for real on the CI box; the TPU-shaped harvest lives in
    ``scripts/tpu_validation.py``'s ``serving`` section).  Reports
    tokens/sec both ways, the speedup, mean slot occupancy, and compile
    counts (decode must be 1 — the compile-once contract).

    The paged second generation (ISSUE 9) rides in the same section: a
    trace-driven SLO load (shared system prompts, Poisson arrivals,
    per-request deadlines) through the paged engine with a 1-layer
    speculative draft, A/B'd against the v1 engine on the same trace.
    Its three headline numbers — ``prefix_hit_rate``,
    ``slo_attainment``, ``spec_acceptance`` — are lifted to the top of
    the record for baseline tracking (``cpu:serving_*_v1``)."""
    from distributed_deep_learning_tpu.serve.bench import (
        paged_serving_bench, serving_bench)

    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", 32))
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    rec = serving_bench(n_requests=n_req, max_slots=slots)
    out = {
        "metric": "serving tokens/sec (mixed-length trace)",
        "engine_tokens_per_sec": rec["engine"]["tokens_per_sec"],
        "naive_tokens_per_sec": rec["naive"]["tokens_per_sec"],
        "speedup": rec["speedup"],
        "mean_slot_occupancy": rec["engine"]["mean_slot_occupancy"],
        "decode_compiles": rec["engine"]["decode_compiles"],
        "prefill_compiles": rec["engine"]["prefill_compiles"],
        "naive_compiles": rec["naive"]["compiles"],
        "naive_wasted_fraction": rec["naive"]["wasted_fraction"],
        "max_slots": slots,
        "requests": n_req,
    }
    p_req = int(os.environ.get("BENCH_SERVE_PAGED_REQUESTS", 12))
    draft = int(os.environ.get("BENCH_SERVE_DRAFT", 1))
    prec = paged_serving_bench(load_kw=dict(n_requests=p_req),
                               max_slots=slots,
                               draft_layers=draft or None)
    pe = prec["paged_engine"]
    out["paged"] = {
        "tokens_per_sec": pe["tokens_per_sec"],
        "speedup_vs_v1": prec.get("speedup_vs_v1"),
        "prefill_tokens_saved_frac": prec.get("prefill_tokens_saved_frac"),
        "cow_copies": pe["paged"]["cow_copies"],
        "chunk_compiles": pe["chunk_compiles"],
        "decode_compiles": pe["decode_compiles"],
        "verify_compiles": pe["verify_compiles"],
        "requests": p_req,
        "draft_layers": draft or None,
    }
    out["prefix_hit_rate"] = round(pe["prefix_hit_rate"], 4)
    out["slo_attainment"] = pe["slo_attainment"]
    out["spec_acceptance"] = round(pe["spec_acceptance"], 4) \
        if pe["spec_acceptance"] is not None else None
    # exact KV footprints (allocated cache pytree bytes, ISSUE 12) — the
    # denominators of every future "HBM saved per slot" claim
    out["kv_cache_bytes"] = rec["engine"]["kv_cache_bytes"]
    out["paged"]["kv_cache_bytes"] = pe["kv_cache_bytes"]
    return out


def _serving_quant() -> dict | None:
    """Quantized serving hot path A/B (ISSUE 14): the same trace through
    the paged engine at full precision and with int8 block pools + int8
    per-channel weights (serve/quant.py).  CPU-measurable: the shrink is
    exact allocated bytes (the ``kv_cache_bytes`` gauge on the REAL
    pools, scales included), the drift is the calibrated per-token
    greedy logprob bound, and throughput exercises the same
    quantize/dequant hot loop XLA compiles on TPU.  The
    block-table-aware flash-decode kernel itself
    (ops/paged_decode_pallas.py) harvests on TPU via
    ``scripts/tpu_validation.py``'s ``serving_quant`` section; CPU runs
    its interpret-mode parity in tests."""
    from distributed_deep_learning_tpu.serve.bench import (
        quantized_serving_bench)

    q_req = int(os.environ.get("BENCH_SERVE_QUANT_REQUESTS", 10))
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    rec = quantized_serving_bench(load_kw=dict(n_requests=q_req),
                                  max_slots=slots)
    return {
        "metric": "quantized serving A/B (int8 KV pools + int8 weights)",
        "kv_dtype": rec["kv_dtype"],
        "weight_dtype": rec["weight_dtype"],
        "tokens_per_sec": rec["quantized"]["tokens_per_sec"],
        "baseline_tokens_per_sec": rec["baseline"]["tokens_per_sec"],
        "kv_shrink_x": rec["kv_shrink_x"],
        "kv_bytes_per_slot": rec["quantized"]["kv_bytes_per_slot"],
        "baseline_kv_bytes_per_slot": rec["baseline"]["kv_bytes_per_slot"],
        "max_context_at_budget": rec["quantized"]["max_context_at_budget"],
        "baseline_max_context_at_budget":
            rec["baseline"]["max_context_at_budget"],
        "token_agreement": rec["token_agreement"],
        "logprob_drift": rec["logprob_drift"],
        "declared_drift_bound": rec["declared_drift_bound"],
        "decode_compiles": rec["quantized"]["decode_compiles"],
        "weight_bytes": rec["quantized"]["weight_bytes"],
        "requests": q_req,
        "max_slots": slots,
    }


def _no_cpu_child_under_accelerator(section: str, need: int) -> None:
    """A section that needs `need` devices re-measures in a forced-CPU
    child when the worker has fewer — which is only honest when the
    worker itself is on the CPU.  Started after this process took the
    chip, such a child runs only because it is forced to the host, and
    its numbers would land in a ``tpu:`` line: refuse instead."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"{section} needs {need} devices, this {platform} host has "
            f"{len(jax.devices())}: not run (a CPU child's numbers are "
            f"not written into a {platform} line)")


def _serving_disagg() -> dict | None:
    """Disaggregated prefill/decode serving A/B (ISSUE 16): the same
    shared-prefix Poisson trace through the unified paged engine and
    through ``serve/disagg.py``'s prefill-pool + decode-pool split
    joined by device-to-device KV-block migration.  CPU-measurable: the
    mechanism being bought — per-role pool sizing, batched
    compile-once prefill off the decode device, migration overlapped
    with the next chunk — runs for real on the emulated multi-device
    host.  Baseline-tracked: the disagg/unified speedup, disagg
    tokens/sec and sync-measured migration GB/s; ``itl_p99_ratio``
    rides the record (must stay ~1 — disaggregation that trades
    inter-token latency for throughput is not a win), and
    ``token_agreement`` must be 1.0 (decode workers run the unified
    engine's own compiled program)."""
    import subprocess

    import jax

    d_req = int(os.environ.get("BENCH_SERVE_DISAGG_REQUESTS", 24))
    # seed 17's arrival pattern keeps the decode pool busy during
    # prefill bursts (the overlap the split exists to exploit); seed 0
    # happens to serialise the phases and measures mostly noise
    d_seed = int(os.environ.get("BENCH_SERVE_DISAGG_SEED", 17))
    if len(jax.devices()) < 2:
        _no_cpu_child_under_accelerator("serving_disagg", 2)
        # disaggregation needs one device per pool; a single-device CPU
        # worker re-measures in a child under the forced-host CPU mesh
        # (XLA_FLAGS must land before the child imports jax — same
        # dance as _collectives)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "scripts", "disagg_bench.py"),
             "--requests", str(d_req), "--seed", str(d_seed)],
            stdout=subprocess.PIPE, text=True, timeout=600, env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"disagg_bench subprocess exited {proc.returncode}")
        rec = json.loads(proc.stdout)
        rec["fallback"] = "cpu-subprocess-2dev"
    else:
        from distributed_deep_learning_tpu.serve.bench import (
            disagg_serving_bench)

        rec = disagg_serving_bench(seed=d_seed,
                                   load_kw=dict(n_requests=d_req))
    return {
        "metric": "disaggregated prefill/decode serving A/B",
        "speedup": rec["speedup"],
        "tokens_per_sec": rec["disagg"]["tokens_per_sec"],
        "unified_tokens_per_sec": rec["unified"]["tokens_per_sec"],
        "itl_p99_ratio": rec["itl_p99_ratio"],
        "itl_p99_ms": round(1e3 * rec["disagg"]["itl_p99_s"], 3),
        "unified_itl_p99_ms": round(1e3 * rec["unified"]["itl_p99_s"], 3),
        "token_agreement": rec["token_agreement"],
        "migration_gbps": rec["migration_gbps"],
        "migration_ms_per_move": rec["migration_ms_per_move"],
        "int8_wire_shrink_x": rec["int8_wire_shrink_x"],
        "prefill_util": round(rec["disagg"]["prefill_util"], 4),
        "decode_compiles": rec["disagg"]["decode_compiles"],
        "chunk_compiles": rec["disagg"]["chunk_compiles"],
        "migrate_gather_compiles": rec["disagg"]["migrate_gather_compiles"],
        "migrate_scatter_compiles": rec["disagg"]["migrate_scatter_compiles"],
        "migration": rec["disagg"]["migration"],
        "prefill_workers": rec["prefill_workers"],
        "decode_workers": rec["decode_workers"],
        "prefill_streams": rec["prefill_streams"],
        "max_slots": rec["max_slots"],
        "requests": d_req,
        "seed": d_seed,
        "errors": rec["errors"],
        "fallback": rec.get("fallback"),
    }


def _resilience() -> dict | None:
    """Self-healing drill (ISSUE 3): detection latency of the anomaly
    sentinel, checkpoint-corruption fallback, and elastic recovery wall
    time, measured by the same code path ``scripts/chaos_drill.py``
    exposes.  CPU-measurable (host + XLA logic).  The sentinel is OFF in
    every other bench section, so the headline numbers are regression-free
    by construction; ``sentinel_overhead_frac`` quantifies what turning it
    on would cost on this (tiny, worst-case) model."""
    from distributed_deep_learning_tpu.utils.chaos import run_resilience_drill

    rec = run_resilience_drill(seed=int(os.environ.get("BENCH_CHAOS_SEED",
                                                       "0")))
    return {"metric": "self-healing drill (chaos-injected)", **rec}


def _serve_resilience() -> dict | None:
    """Serve-side self-healing drill (ISSUE 13): engine crash / NaN
    logits / corrupted KV block / stalled tick injected mid-decode under
    the supervisor (zero requests lost, bit-identical replay), slow-tick
    SLO load under admission control, and the hot weight-swap gauntlet
    (canary promote, canary rollback, bit-flipped publication rejected
    by the integrity manifest) — the same code path
    ``scripts/chaos_drill.py --scenario serve`` exposes.  One engine
    survives the whole gauntlet; ``decode_compiles`` staying 1 is part
    of the record."""
    from distributed_deep_learning_tpu.utils.chaos import (
        run_serve_resilience_drill)

    return run_serve_resilience_drill(
        seed=int(os.environ.get("BENCH_CHAOS_SEED", "0")))


def _fleet_resilience() -> dict | None:
    """Fleet-tier self-healing drill (ISSUE 15): three router-fronted
    paged replicas under a shared-prefix Poisson trace with priority
    classes — replica crash quarantined with zero-loss bit-identical
    cross-replica replay, straggler health-degraded, router flake
    survived, and priority preemption spilling low-priority KV to host
    and resuming it bit-identically (priority 0 never preempted) — the
    same code path ``scripts/chaos_drill.py --scenario fleet`` exposes.
    The replica engines survive the whole gauntlet; the surviving max
    ``decode_compiles`` staying 1 is part of the record."""
    from distributed_deep_learning_tpu.utils.chaos import (
        run_fleet_resilience_drill)

    return run_fleet_resilience_drill(
        seed=int(os.environ.get("BENCH_CHAOS_SEED", "0")))


def _fleet_rebalance() -> dict | None:
    """Live fleet rebalancing drill (ISSUE 18): mid-request slot
    evacuation off a degraded replica (digest-verified committed-KV
    migration, bit-identical resume over fp32 AND int8 pools), a
    corrupted evacuation payload rolled back by the digest with zero
    loss, a target crash mid-evacuation aborted and ledger-replayed,
    the elastic autoscaler's grow + drain-protocol shrink, and the
    ``scale_thrash`` hysteresis gauntlet — the same code path
    ``scripts/chaos_drill.py --scenario rebalance`` exposes.  Also runs
    ``scripts/check_baselines.py`` (the band/section hygiene gate) and
    folds its verdict into the record, so a band pointing at a
    nonexistent bench section fails HERE, where the bands are used."""
    import subprocess

    from distributed_deep_learning_tpu.utils.chaos import (
        run_rebalance_drill)

    record = run_rebalance_drill(
        seed=int(os.environ.get("BENCH_CHAOS_SEED", "0")))
    check = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "check_baselines.py")
    try:
        proc = subprocess.run([sys.executable, check],
                              capture_output=True, text=True, timeout=120)
        record["baseline_check_ok"] = proc.returncode == 0
        if proc.returncode != 0:
            record["baseline_check_errors"] = \
                proc.stdout.strip().splitlines()[-8:]
    except Exception as exc:  # the drill result stands on its own
        record["baseline_check_ok"] = None
        record["baseline_check_errors"] = [f"{type(exc).__name__}: {exc}"]
    n_scen = [s for s in record["scenarios"].values()
              if isinstance(s, dict)]
    record["scenarios_passed_frac"] = (
        sum(1 for s in n_scen if s.get("passed")) / len(n_scen)
        if n_scen else None)
    return record


def _autotune() -> dict | None:
    """Auto-parallelism planner (ISSUE 5): search the plan lattice for the
    MLP workload on this box's devices and report best-vs-default measured
    step time — CPU-measurable (the trials compile and run the real train
    step).  The chosen ``plan_hash`` is recorded so BENCH_*.json tracks
    plan churn across commits; the search space here is the cheap
    (mesh x remat) slice sized for the bench budget."""
    from distributed_deep_learning_tpu.tune.search import run_search
    from distributed_deep_learning_tpu.utils.config import parse_args
    from distributed_deep_learning_tpu.workloads import get_spec

    batch = int(os.environ.get("BENCH_AUTOTUNE_BATCH", 32))
    trials = int(os.environ.get("BENCH_AUTOTUNE_TRIALS", 6))
    spec = get_spec("mlp")
    config = parse_args(["-e", "1", "-b", str(batch), "-m", "data"],
                        workload="mlp")
    result = run_search(
        spec, config, trial_steps=2, max_trials=trials,
        space_options=dict(zero_options=("none", "fsdp"),
                           compress_options=("none",),
                           grad_accum_options=(1,)))
    from distributed_deep_learning_tpu.tune.artifact import plan_hash

    best_ms = 1e3 / result.best_sps if result.best_sps else None
    base_ms = 1e3 / result.baseline_sps if result.baseline_sps else None
    return {
        "metric": "autotuned plan vs hand default (mlp train step)",
        "plan_hash": plan_hash(result.best),
        "plan": result.best.describe(),
        "best_steps_per_sec": round(result.best_sps, 2),
        "best_examples_per_sec": round(result.best_sps * batch, 1),
        "baseline_steps_per_sec": round(result.baseline_sps, 2),
        "best_step_ms": round(best_ms, 3) if best_ms else None,
        "baseline_step_ms": round(base_ms, 3) if base_ms else None,
        "speedup": round(result.best_sps / result.baseline_sps, 4)
            if result.baseline_sps else None,
        "n_candidates": result.n_candidates,
        "n_pruned_analytic": result.n_pruned,
        "n_infeasible": result.n_infeasible,
        "rungs": result.rungs,
        "search_seconds": round(result.search_seconds, 2),
    }


def _memory_model() -> dict | None:
    """Memory-model calibration (ISSUE 12): compile the MLP workload's
    real train step at each remat corner of the lattice, read XLA's
    measured temp bytes, fit ``ACT_FRACTION``/``RECOMPUTE_COST``, and
    report predicted-vs-measured error for BOTH the analytic tables and
    the fitted constants — CPU-measurable (``memory_analysis()`` reports
    argument/temp bytes on the CPU backend too).  The calibrated mean
    error is tracked under ``{platform}:mem_model_error_v1`` with an
    absolute 25% ceiling; the uncalibrated analytic error rides in the
    record as the before/after evidence."""
    from distributed_deep_learning_tpu.tune.calibrate import run_calibration
    from distributed_deep_learning_tpu.utils.config import parse_args
    from distributed_deep_learning_tpu.workloads import get_spec

    batch = int(os.environ.get("BENCH_MEMORY_BATCH", 32))
    steps = int(os.environ.get("BENCH_MEMORY_STEPS", 2))
    spec = get_spec("mlp")
    config = parse_args(["-e", "1", "-b", str(batch), "-m", "data"],
                        workload="mlp")
    record = run_calibration(spec, config, steps=steps)
    errors = record["errors"]
    analytic, calibrated = errors["analytic"], errors["calibrated"]
    return {
        "metric": "analytic HBM model error vs XLA measured bytes "
                  "(mlp, remat/ZeRO corners)",
        "workload": "mlp",
        "calibration_key": record["key"],
        "constants": record["constants"],
        "corners_measured": calibrated["corners"] if calibrated else 0,
        "analytic_error_mean": analytic["mean"] if analytic else None,
        "analytic_error_max": analytic["max"] if analytic else None,
        "calibrated_error_mean": calibrated["mean"] if calibrated else None,
        "calibrated_error_max": calibrated["max"] if calibrated else None,
    }


def _reshard() -> dict | None:
    """Cross-topology reshard (ISSUE 6): redistribution bandwidth for the
    two paths — host-gather fallback vs chunked per-shard streaming — on
    a checkpoint-sized array moved across a REAL mesh change (N → N-2
    devices: 8→6 on the CI box, a non-power-of-2 target), plus the full
    shrink drill (kill 2, re-plan via tune/, reshard-restore, continue)
    timed end to end.  CPU-measurable (redistribution is slicing +
    device_put logic); the TPU-shaped harvest lives in
    ``scripts/tpu_validation.py``'s ``reshard`` section."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_deep_learning_tpu.reshard.redistribute import (
        redistribute_leaf)
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    devices = jax.devices()
    n = len(devices)
    m = n - 2 if n > 2 else 1
    mb = int(os.environ.get("BENCH_RESHARD_MB", 64))
    cols = 1024
    quantum = math.lcm(n, m)  # rows divide both source and target meshes
    rows = max(quantum,
               (mb * (1 << 20) // (4 * cols)) // quantum * quantum)
    host = np.random.default_rng(11).standard_normal(
        (rows, cols)).astype(np.float32)
    src = jax.device_put(jnp.asarray(host),
                         NamedSharding(build_mesh({"data": n}, devices),
                                       P("data")))
    dst = NamedSharding(build_mesh({"data": m}, devices[:m]), P("data"))
    gb = host.nbytes / (1 << 30)

    out: dict = {
        "metric": "cross-topology reshard (redistribution + shrink drill)",
        "array_mb": round(host.nbytes / (1 << 20), 1),
        "devices": f"{n}->{m}"}
    for method in ("gather", "chunked"):
        moved, _ = redistribute_leaf(src, dst, method=method)  # warm path
        jax.block_until_ready(moved)
        t0 = time.perf_counter()
        moved, _ = redistribute_leaf(src, dst, method=method)
        jax.block_until_ready(moved)
        dt = time.perf_counter() - t0
        out[f"{method}_seconds_per_gb"] = round(dt / gb, 4)
        out[f"{method}_gb_per_sec"] = round(gb / dt, 3)

    if n >= 8:
        from distributed_deep_learning_tpu.reshard.drill import (
            run_shrink_drill)

        drill = run_shrink_drill(
            seed=int(os.environ.get("BENCH_CHAOS_SEED", "0")),
            hidden=128, rows=512, min_leaf_size=2 ** 10)
        out["drill"] = {k: drill[k] for k in
                       ("plan", "plan_hash", "survivors", "restore_mode",
                        "restore_seconds", "drill_passed")}
    return out


def _observability() -> dict | None:
    """Telemetry overhead A/B (ISSUE 7): steps/sec with RunTelemetry
    attached vs the bare train loop, on the real ``_run_phase`` over a
    ~1 ms jitted step — the worst case for per-step instrumentation
    cost.  CPU-measurable (the hot path is host-side ``perf_counter``
    reads + dict adds either way).  The acceptance bar is overhead
    < 2%; the measured fraction is tracked under
    ``{platform}:obs_overhead_fraction_v1``."""
    from distributed_deep_learning_tpu.obs.bench import (overhead_bench,
                                                         trace_overhead_bench)

    steps = int(os.environ.get("BENCH_OBS_STEPS", 48))
    repeats = int(os.environ.get("BENCH_OBS_REPEATS", 5))
    rec = overhead_bench(steps=steps, repeats=repeats)
    # gen-2 increment (ISSUE 11): spans on vs off, same loop, same bar
    rec["trace"] = trace_overhead_bench(steps=steps, repeats=repeats)
    return rec


def _collectives() -> dict | None:
    """Quantized + ring-overlapped FSDP collectives (ISSUE 10): the
    ``scripts/comm_bench.py`` record — analytic wire bytes per method
    (the int8-vs-fp32 >= 3x gate), ring bit-parity and quantized
    numerics, the fused ``gather_matmul`` overlap fraction, and the
    explicit-FSDP-step loss parity against the ``parallel/zero.py``
    annotation path.  CPU-measurable (the ring schedule's win on host
    devices is never materialising the gathered operand); the wire-time
    harvest lives in ``scripts/tpu_validation.py``'s ``collectives``
    section."""
    import subprocess

    import jax

    steps = int(os.environ.get("BENCH_COMM_STEPS", 5))
    if len(jax.devices()) < 2:
        _no_cpu_child_under_accelerator("collectives", 2)
        # single-device CPU worker: the mesh collectives need shards, so
        # re-measure in a child with the 8-way forced-host CPU mesh —
        # XLA_FLAGS must be set before the child imports jax, which is
        # why this can't happen in-process
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "scripts", "comm_bench.py"),
             "--steps", str(steps), "--parity-steps",
             os.environ.get("BENCH_COMM_PARITY_STEPS", "3")],
            stdout=subprocess.PIPE, text=True, timeout=600, env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"comm_bench subprocess exited {proc.returncode}")
        rec = json.loads(proc.stdout)
        rec["fallback"] = "cpu-subprocess-8dev"
        return rec

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import comm_bench

    return comm_bench.run(
        steps=steps,
        parity_steps=int(os.environ.get("BENCH_COMM_PARITY_STEPS", 3)))


def _attention_speedup(steps: int = 20) -> float | None:
    """Fused (Pallas flash) vs dense attention fwd+bwd at a long-context
    shape; returns flash/dense step-time ratio > 1 = flash faster.  TPU
    only (interpret mode on CPU measures nothing useful)."""
    import jax
    import jax.numpy as jnp

    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)
    from distributed_deep_learning_tpu.ops.attention_pallas import (
        flash_attention)

    B, T, H, D = 4, 2048, 8, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16) for kk in ks)

    def time_fn(fn):
        loss = jax.jit(jax.grad(lambda q: jnp.sum(fn(q, k, v) ** 2)))
        float(jnp.sum(loss(q)))  # compile + warm, host-fetch sync
        t0 = time.perf_counter()
        for _ in range(steps):
            g = loss(q)
        float(jnp.sum(g))
        return (time.perf_counter() - t0) / steps

    try:
        t_dense = time_fn(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True, dtype=jnp.bfloat16))
        t_flash = time_fn(lambda q, k, v: flash_attention(
            q, k, v, causal=True).astype(jnp.bfloat16))
        return t_dense / t_flash
    except Exception:
        return None


def _time_left() -> float:
    """Seconds until the orchestrator's soft deadline (inf when unset).

    Optional sections consult this so the headline line always prints
    inside the watchdog window — shedding the DenseNet/LM/attention
    extras beats the whole attempt being killed mid-compile."""
    dl = os.environ.get("BENCH_DEADLINE")
    return float("inf") if not dl else float(dl) - time.time()


#: bench_baseline.json key carrying the best MEASURED TPU ResNet MFU
#: (updated by any TPU run that beats it).  It is history for the
#: regression sentry only: a run prints the MFU it measured or null,
#: never this.
RECORDED_MFU_KEY = "tpu:resnet50_mfu_v1"


def _recorded_mfu(baselines: dict) -> float | None:
    """The best recorded TPU ResNet MFU, or None when never measured."""
    v = baselines.get(RECORDED_MFU_KEY)
    return float(v) if isinstance(v, (int, float)) and v > 0 else None


#: Every baseline-tracked value this run actually measured (key ->
#: value), recorded by ``_vs_baseline`` — what the regression sentry
#: walks.  A section that errored or was shed simply never lands here,
#: so the sentry only judges numbers that exist.
_MEASURED: dict[str, float] = {}

#: Noise-aware tolerance bands per baseline-key suffix (ISSUE 11).
#: ``("higher", band)``: the metric should stay >= baseline * (1-band);
#: the band is sized to each harness's observed run-to-run noise on a
#: loaded CI box (throughputs swing hard, analytic ratios barely move).
#: ``("lower_abs", ceiling)``: an absolute ceiling for
#: lower-is-better fractions — the obs overheads are ~0.01-0.02 with
#: noise of the same magnitude, so a ratio against a near-zero baseline
#: would be meaningless; the acceptance bar (2% + measurement slack)
#: is the honest gate.
REGRESSION_BANDS: dict[str, tuple[str, float]] = {
    "resnet50_224_train_v1": ("higher", 0.30),
    "densenet_bc_train_v2": ("higher", 0.30),
    "causal_lm_2048_train_v1": ("higher", 0.30),
    "serving_tokens_per_sec_v1": ("higher", 0.30),
    "serving_prefix_hit_rate_v1": ("higher", 0.10),
    "serving_slo_attainment_v1": ("higher", 0.25),
    "serving_spec_acceptance_v1": ("higher", 0.25),
    # quantized serving (ISSUE 14): the shrink is exact allocated bytes
    # at fixed geometry (deterministic — tight band); throughput rides
    # the usual CI wall-clock band; the drift ceiling is absolute — the
    # declared int8 bound (~0.02 on the calibrated probe) plus headroom,
    # because a ratio against a near-zero drift would be meaningless
    "serving_quant_kv_shrink_v1": ("higher", 0.05),
    "serving_quant_tokens_per_sec_v1": ("higher", 0.30),
    "serving_quant_logprob_drift_v1": ("lower_abs", 0.05),
    # disaggregated serving (ISSUE 16): the speedup and throughput ride
    # the wide CI wall-clock band (the A/B's two arms share one box, so
    # the RATIO is steadier than either arm, but single-core scheduling
    # noise still moves it); migration GB/s is a sync-measured
    # device_put rate — noisy on a loaded host.  The ITL ceiling is
    # absolute: disagg inter-token p99 beyond 2x unified's means the
    # handoff is backing up no matter what an earlier run recorded.
    "serving_disagg_speedup_v1": ("higher", 0.30),
    "serving_disagg_tokens_per_sec_v1": ("higher", 0.30),
    "serving_disagg_migration_gbps_v1": ("higher", 0.50),
    "serving_disagg_itl_p99_ratio_v1": ("lower_abs", 2.0),
    "autotune_mlp_steps_per_sec_v1": ("higher", 0.30),
    "reshard_chunked_gb_per_sec_v1": ("higher", 0.35),
    "comm_int8_bytes_reduction_v1": ("higher", 0.05),
    "comm_overlap_fraction_v1": ("higher", 0.40),
    "obs_overhead_fraction_v1": ("lower_abs", 0.025),
    "obs_trace_overhead_fraction_v1": ("lower_abs", 0.025),
    # predicted-vs-measured HBM model error after calibration (ISSUE 12):
    # the acceptance bar is <= 25% mean relative error on the calibrated
    # corners; a ratio against a near-zero baseline would be meaningless,
    # so the bar itself is the gate
    "mem_model_error_v1": ("lower_abs", 0.25),
    # serve self-healing drill (ISSUE 13): absolute bars, not ratios —
    # a fault the watchdog needs >3 ticks to see, a recovery past 5 s on
    # the tiny drill engine, or ANY lost request is a broken chain no
    # matter what an earlier run recorded.  Clean SLO attainment ratios
    # against its record with a wide band (wall-clock CI noise).
    "serve_resilience_detection_ticks_v1": ("lower_abs", 3.0),
    "serve_resilience_recovery_s_v1": ("lower_abs", 5.0),
    "serve_resilience_requests_lost_v1": ("lower_abs", 0.5),
    "serve_resilience_slo_attainment_v1": ("higher", 0.5),
    # fleet self-healing drill (ISSUE 15): same philosophy, fleet tier —
    # a replica crash the router needs >3 ticks to see, a failover
    # replay past 15 s on the tiny drill fleet, or ANY lost request is
    # a broken chain regardless of history
    "fleet_detection_ticks_v1": ("lower_abs", 3.0),
    "fleet_recovery_s_v1": ("lower_abs", 15.0),
    "fleet_requests_lost_v1": ("lower_abs", 0.5),
    "fleet_slo_attainment_v1": ("higher", 0.5),
    # live rebalancing drill (ISSUE 18): ANY lost request during an
    # evacuation / drain / rebalance fault is a broken chain, full
    # stop; per-slot evacuation latency has an absolute ceiling (the
    # tiny drill engine moves a handful of KV blocks — if that takes
    # >1 s something structural regressed, whatever history says); an
    # oscillating load must never move the fleet more than the
    # hysteresis allows; and every drill scenario must pass.
    "rebalance_requests_lost_v1": ("lower_abs", 0.5),
    "rebalance_evac_ms_v1": ("lower_abs", 1000.0),
    "rebalance_scale_events_v1": ("lower_abs", 6.5),
    "rebalance_scenarios_passed_v1": ("higher", 0.05),
}

#: Band-key suffix -> the bench JSON-line section its metric rides in
#: (ISSUE 18 satellite: ``scripts/check_baselines.py`` verifies every
#: ``REGRESSION_BANDS`` entry names a section that actually exists, so
#: a renamed/removed section can't leave its bands silently orphaned).
BAND_SECTIONS: dict[str, str] = {
    "resnet50_224_train_v1": "value",
    "densenet_bc_train_v2": "secondary",
    "causal_lm_2048_train_v1": "lm",
    "serving_tokens_per_sec_v1": "serving",
    "serving_prefix_hit_rate_v1": "serving",
    "serving_slo_attainment_v1": "serving",
    "serving_spec_acceptance_v1": "serving",
    "serving_quant_kv_shrink_v1": "serving_quant",
    "serving_quant_tokens_per_sec_v1": "serving_quant",
    "serving_quant_logprob_drift_v1": "serving_quant",
    "serving_disagg_speedup_v1": "serving_disagg",
    "serving_disagg_tokens_per_sec_v1": "serving_disagg",
    "serving_disagg_migration_gbps_v1": "serving_disagg",
    "serving_disagg_itl_p99_ratio_v1": "serving_disagg",
    "autotune_mlp_steps_per_sec_v1": "autotune",
    "reshard_chunked_gb_per_sec_v1": "reshard",
    "comm_int8_bytes_reduction_v1": "collectives",
    "comm_overlap_fraction_v1": "collectives",
    "obs_overhead_fraction_v1": "observability",
    "obs_trace_overhead_fraction_v1": "observability",
    "mem_model_error_v1": "memory_model",
    "serve_resilience_detection_ticks_v1": "serve_resilience",
    "serve_resilience_recovery_s_v1": "serve_resilience",
    "serve_resilience_requests_lost_v1": "serve_resilience",
    "serve_resilience_slo_attainment_v1": "serve_resilience",
    "fleet_detection_ticks_v1": "fleet_resilience",
    "fleet_recovery_s_v1": "fleet_resilience",
    "fleet_requests_lost_v1": "fleet_resilience",
    "fleet_slo_attainment_v1": "fleet_resilience",
    "rebalance_requests_lost_v1": "fleet_rebalance",
    "rebalance_evac_ms_v1": "fleet_rebalance",
    "rebalance_scale_events_v1": "fleet_rebalance",
    "rebalance_scenarios_passed_v1": "fleet_rebalance",
}

#: The section keys the bench JSON line actually carries (kept in sync
#: with the ``line`` dict ``main`` assembles) — the target universe
#: ``BAND_SECTIONS`` values must live in.
SECTION_KEYS: frozenset = frozenset({
    "value", "secondary", "lm", "input_pipeline", "serving",
    "serving_quant", "serving_disagg", "resilience", "serve_resilience",
    "fleet_resilience", "fleet_rebalance", "autotune", "reshard",
    "observability", "memory_model", "collectives",
    "flash_attention_speedup",
})


def regression_sentry(baselines: dict,
                      measured: dict | None = None) -> list[dict]:
    """Compare this run's measured values against their recorded
    baselines with per-metric tolerance bands; return one failure dict
    per breach (empty list = clean).

    A freshly seeded baseline compares at ratio 1.0 and can never fail —
    the first measurement defines the record, later runs defend it."""
    measured = _MEASURED if measured is None else measured
    failures: list[dict] = []
    for key in sorted(measured):
        value = measured[key]
        rule = REGRESSION_BANDS.get(key.split(":", 1)[-1])
        if rule is None:
            continue
        direction, band = rule
        if direction == "lower_abs":
            if value > band:
                failures.append({
                    "key": key, "value": value, "ceiling": band,
                    "kind": "absolute ceiling exceeded"})
            continue
        base = baselines.get(key)
        if not isinstance(base, (int, float)) or base <= 0:
            continue
        ratio = value / base
        if ratio < 1.0 - band:
            failures.append({
                "key": key, "value": value, "baseline": base,
                "ratio": round(ratio, 4), "band": band,
                "kind": "below tolerance band"})
    return failures


def regress_from(path: str) -> int:
    """The cheap CI gate (``BENCH_REGRESS_FROM=rec.json python
    bench.py``): judge a previously recorded bench JSON line against the
    current baselines WITHOUT running any benches.  Reads the line's
    ``measured`` map (every ``_vs_baseline`` datum of that run), applies
    the same tolerance bands, exits 3 on breach / 2 on an unusable
    record / 0 clean."""
    measured: dict[str, float] = {}
    try:
        with open(path) as f:
            for raw in f:
                raw = raw.strip()
                if raw.startswith("{"):
                    measured.update(json.loads(raw).get("measured") or {})
    except (OSError, ValueError) as e:
        print(f"bench: cannot read record {path}: {e}", file=sys.stderr)
        return 2
    if not measured:
        print(f"bench: no 'measured' map in {path} (older record "
              "format? re-run bench.py to produce one)", file=sys.stderr)
        return 2
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_baseline.json")
    baselines = {}
    if os.path.exists(base_path):
        with open(base_path) as f:
            baselines = json.load(f)
    regs = regression_sentry(baselines, measured)
    for r in regs:
        print(f"bench: REGRESSION {r['key']}: {r}", file=sys.stderr)
    print(json.dumps({"regress_from": path, "checked": len(measured),
                      "regressions": regs}))
    return 3 if regs else 0


def _vs_baseline(baselines: dict, key: str, value: float,
                 base_path: str) -> float:
    _MEASURED[key] = value
    if key not in baselines:
        baselines[key] = value
        try:
            with open(base_path, "w") as f:
                json.dump(baselines, f, indent=1)
        except OSError:
            pass
    return value / baselines[key] if baselines[key] else 1.0


def main() -> int:
    from distributed_deep_learning_tpu.runtime.bootstrap import (
        enable_compile_cache)

    enable_compile_cache()
    section_secs: dict[str, float] = {}

    class _section_timer:
        """Record a section's wall time (stderr + the JSON line) so a
        timed-out attempt leaves a diagnosis, not a mystery (the round-5
        window was lost to exactly that)."""

        def __init__(self, name: str) -> None:
            self.name = name

        def __enter__(self):
            self.t0 = time.perf_counter()

        def __exit__(self, *exc):
            section_secs[self.name] = round(time.perf_counter() - self.t0, 1)
            print(f"bench: section {self.name} took "
                  f"{section_secs[self.name]}s", file=sys.stderr)

    import jax
    import jax.numpy as jnp

    from distributed_deep_learning_tpu.models.resnet import resnet50
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from __graft_entry__ import _flagship

    devices = jax.devices()
    platform = devices[0].platform
    device_kind = devices[0].device_kind
    n_chips = len(devices)
    on_tpu = platform == "tpu"
    mesh = build_mesh({"data": n_chips})
    dtype = jnp.bfloat16 if on_tpu else jnp.float32

    # --- headline: ResNet-50, ImageNet geometry (224x224, 1000 classes) ----
    # one attempt per process; the batch-backoff ladder lives in
    # orchestrate(), which retries smaller sizes in fresh watchdogged
    # workers (a single policy, and failed attempts can't pin HBM)
    batch_env = os.environ.get("BENCH_BATCH")
    per_chip = os.environ.get("BENCH_BATCH_PER_CHIP")
    if batch_env:
        batch = int(batch_env)
    elif per_chip:
        batch = int(per_chip) * n_chips
    else:
        batch = 256 * n_chips if on_tpu else 8
    steps = int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 2))
    # space-to-depth stem (mathematically-equivalent 4x4-s1 packed conv,
    # models/resnet.py) is the TPU default; BENCH_S2D=0 reverts
    s2d = on_tpu and os.environ.get("BENCH_S2D", "1") != "0"
    with _section_timer("headline"):
        ips, flops_per_step = _train_throughput(
            resnet50(dtype=dtype, stem_s2d=s2d), image_size=224,
            num_classes=1000, batch=batch, steps=steps, mesh=mesh)

    mfu = flops_per_image = None
    peak = chip_peak_flops(device_kind) if on_tpu else None
    if flops_per_step:
        flops_per_image = flops_per_step / batch
        if peak:
            mfu = ips * flops_per_image / peak

    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_baseline.json")
    baselines = {}
    if os.path.exists(base_path):
        with open(base_path) as f:
            baselines = json.load(f)
    vs = _vs_baseline(baselines, f"{platform}:resnet50_224_train_v1", ips,
                      base_path)

    # MFU bookkeeping: a TPU run that beats the recorded best updates it
    if on_tpu and mfu and mfu > (_recorded_mfu(baselines) or 0.0):
        baselines[RECORDED_MFU_KEY] = round(mfu, 4)
        try:
            with open(base_path, "w") as f:
                json.dump(baselines, f, indent=1)
        except OSError:
            pass

    # Optional sections each guard themselves: the headline ResNet number
    # must print even if a secondary model OOMs or hits a compile bug
    # (their absence reads as null).
    # --- secondary: the reference's flagship (DenseNet-BC, PCB 64x64) ------
    # Shed thresholds are cold-compile worst cases (ResNet compile ~90s,
    # LM section ~200s, input ~250s with JPEG tree).  They gate on
    # on_tpu: CPU sections compile in seconds.
    t_secondary, t_lm, t_input = (150, 300, 250) if on_tpu else (60, 120, 60)
    secondary = None
    if os.environ.get("BENCH_SECONDARY", "1") != "0" and \
            _time_left() < t_secondary:
        print(f"bench: shedding densenet section ({_time_left():.0f}s left)",
              file=sys.stderr)
    elif os.environ.get("BENCH_SECONDARY", "1") != "0":
        try:
            dbatch = int(os.environ.get("BENCH_DENSENET_BATCH",
                                        1024 * n_chips if on_tpu else 16))
            dsteps = int(os.environ.get("BENCH_DENSENET_STEPS",
                                        30 if on_tpu else 2))
            with _section_timer("densenet"):
                dips, _ = _train_throughput(
                    _flagship(dtype=dtype), image_size=64, num_classes=6,
                    batch=dbatch, steps=dsteps, mesh=mesh)
            dvs = _vs_baseline(baselines,
                               f"{platform}:densenet_bc_train_v2",
                               dips, base_path)
            secondary = {"metric": "densenet_bc64 train images/sec/chip",
                         "value": round(dips, 2),
                         "vs_baseline": round(dvs, 4)}
        except Exception as exc:
            print(f"bench: densenet secondary failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- LM: decoder-only transformer, flash attention + fused CE head -----
    lm = None
    if os.environ.get("BENCH_LM", "1" if on_tpu else "0") != "0" and \
            _time_left() < t_lm:
        print(f"bench: shedding lm section ({_time_left():.0f}s left)",
              file=sys.stderr)
    elif os.environ.get("BENCH_LM", "1" if on_tpu else "0") != "0":
        try:
            lbatch = int(os.environ.get("BENCH_LM_BATCH",
                                        8 * n_chips if on_tpu else 2))
            lseq = int(os.environ.get("BENCH_LM_SEQ",
                                      2048 if on_tpu else 128))
            lsteps = int(os.environ.get("BENCH_LM_STEPS",
                                        10 if on_tpu else 2))
            with _section_timer("lm"):
                ltps, lflops = _lm_throughput(batch=lbatch, seq_len=lseq,
                                              steps=lsteps, mesh=mesh,
                                              dtype=dtype)
            lvs = _vs_baseline(baselines,
                               f"{platform}:causal_lm_2048_train_v1",
                               ltps, base_path)
            lmfu = None
            if lflops and peak:
                lmfu = ltps * (lflops / (lbatch * lseq)) / peak
            lm = {"metric": "causal_lm_768x12 T2048 train tokens/sec/chip",
                  "value": round(ltps, 2), "vs_baseline": round(lvs, 4),
                  "mfu": round(lmfu, 4) if lmfu else None}
        except Exception as exc:
            print(f"bench: lm section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- host input pipeline on the measured path --------------------------
    input_pipe = None
    if os.environ.get("BENCH_INPUT", "1") != "0" and _time_left() < t_input:
        print(f"bench: shedding input-pipeline section ({_time_left():.0f}s "
              "left)", file=sys.stderr)
    elif os.environ.get("BENCH_INPUT", "1") != "0":
        try:
            with _section_timer("input_pipeline"):
                input_pipe = _input_pipeline(mesh=mesh, dtype=dtype)
        except Exception as exc:
            print(f"bench: input-pipeline section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- serving: continuous-batching engine vs naive generate() -----------
    serving = None
    t_serving = 120 if on_tpu else 60
    if os.environ.get("BENCH_SERVE", "1") != "0" and \
            _time_left() < t_serving:
        print(f"bench: shedding serving section ({_time_left():.0f}s left)",
              file=sys.stderr)
    elif os.environ.get("BENCH_SERVE", "1") != "0":
        try:
            with _section_timer("serving"):
                serving = _serving()
            svs = _vs_baseline(baselines,
                               f"{platform}:serving_tokens_per_sec_v1",
                               serving["engine_tokens_per_sec"], base_path)
            serving["vs_baseline"] = round(svs, 4)
            # paged-generation headline numbers (ISSUE 9): hit rate and
            # SLO attainment regress toward 0, so a ratio < 1 flags them
            # the same way a throughput drop would
            for bkey, val in (
                    ("serving_prefix_hit_rate_v1",
                     serving.get("prefix_hit_rate")),
                    ("serving_slo_attainment_v1",
                     serving.get("slo_attainment")),
                    ("serving_spec_acceptance_v1",
                     serving.get("spec_acceptance"))):
                if val is not None:
                    serving[bkey.replace("_v1", "_vs_baseline")] = round(
                        _vs_baseline(baselines, f"{platform}:{bkey}",
                                     val, base_path), 4)
        except Exception as exc:
            print(f"bench: serving section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- serving quantization: int8 KV + int8 weights A/B ------------------
    serving_quant = None
    t_squant = 120 if on_tpu else 60
    if os.environ.get("BENCH_SERVE_QUANT", "1") != "0" and \
            _time_left() < t_squant:
        print(f"bench: shedding serving-quant section ({_time_left():.0f}s "
              "left)", file=sys.stderr)
    elif os.environ.get("BENCH_SERVE_QUANT", "1") != "0":
        try:
            with _section_timer("serving_quant"):
                serving_quant = _serving_quant()
            for bkey, val in (
                    ("serving_quant_kv_shrink_v1",
                     serving_quant.get("kv_shrink_x")),
                    ("serving_quant_tokens_per_sec_v1",
                     serving_quant.get("tokens_per_sec")),
                    ("serving_quant_logprob_drift_v1",
                     serving_quant.get("logprob_drift"))):
                if val is not None:
                    serving_quant[bkey.replace("_v1", "_vs_baseline")] = \
                        round(_vs_baseline(baselines, f"{platform}:{bkey}",
                                           float(val), base_path), 4)
        except Exception as exc:
            print(f"bench: serving-quant section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- serving disaggregation: prefill/decode pools + KV migration -------
    serving_disagg = None
    t_disagg = 150 if on_tpu else 120
    if os.environ.get("BENCH_SERVE_DISAGG", "1") != "0" and \
            _time_left() < t_disagg:
        print(f"bench: shedding serving-disagg section "
              f"({_time_left():.0f}s left)", file=sys.stderr)
    elif os.environ.get("BENCH_SERVE_DISAGG", "1") != "0":
        try:
            with _section_timer("serving_disagg"):
                serving_disagg = _serving_disagg()
            for bkey, val in (
                    ("serving_disagg_speedup_v1",
                     serving_disagg.get("speedup")),
                    ("serving_disagg_tokens_per_sec_v1",
                     serving_disagg.get("tokens_per_sec")),
                    ("serving_disagg_migration_gbps_v1",
                     serving_disagg.get("migration_gbps")),
                    ("serving_disagg_itl_p99_ratio_v1",
                     serving_disagg.get("itl_p99_ratio"))):
                if val is not None:
                    serving_disagg[bkey.replace("_v1", "_vs_baseline")] = \
                        round(_vs_baseline(baselines, f"{platform}:{bkey}",
                                           float(val), base_path), 4)
        except Exception as exc:
            print(f"bench: serving-disagg section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- resilience: the self-healing chain under injected faults ----------
    resilience = None
    t_res = 90 if on_tpu else 60
    if os.environ.get("BENCH_RESILIENCE", "1") != "0" and \
            _time_left() < t_res:
        print(f"bench: shedding resilience section ({_time_left():.0f}s "
              "left)", file=sys.stderr)
    elif os.environ.get("BENCH_RESILIENCE", "1") != "0":
        try:
            with _section_timer("resilience"):
                resilience = _resilience()
        except Exception as exc:
            print(f"bench: resilience section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- serve resilience: supervisor + hot swap under injected faults -----
    serve_resilience = None
    t_sres = 150 if on_tpu else 120
    if os.environ.get("BENCH_SERVE_RESILIENCE", "1") != "0" and \
            _time_left() < t_sres:
        print(f"bench: shedding serve-resilience section "
              f"({_time_left():.0f}s left)", file=sys.stderr)
    elif os.environ.get("BENCH_SERVE_RESILIENCE", "1") != "0":
        try:
            with _section_timer("serve_resilience"):
                serve_resilience = _serve_resilience()
            for bkey, val in (
                    ("serve_resilience_detection_ticks_v1",
                     serve_resilience.get("detection_ticks_max")),
                    ("serve_resilience_recovery_s_v1",
                     serve_resilience.get("recovery_seconds_max")),
                    ("serve_resilience_requests_lost_v1",
                     serve_resilience.get("requests_lost_total")),
                    ("serve_resilience_slo_attainment_v1",
                     serve_resilience.get("slo_attainment_clean"))):
                if val is not None:
                    serve_resilience[bkey.replace("_v1", "_vs_baseline")] = \
                        round(_vs_baseline(baselines, f"{platform}:{bkey}",
                                           float(val), base_path), 4)
        except Exception as exc:
            print(f"bench: serve-resilience section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- fleet resilience: router failover + preemption under faults --------
    fleet_resilience = None
    t_fleet = 180 if on_tpu else 150
    if os.environ.get("BENCH_FLEET_RESILIENCE", "1") != "0" and \
            _time_left() < t_fleet:
        print(f"bench: shedding fleet-resilience section "
              f"({_time_left():.0f}s left)", file=sys.stderr)
    elif os.environ.get("BENCH_FLEET_RESILIENCE", "1") != "0":
        try:
            with _section_timer("fleet_resilience"):
                fleet_resilience = _fleet_resilience()
            for bkey, val in (
                    ("fleet_detection_ticks_v1",
                     fleet_resilience.get("detection_ticks_max")),
                    ("fleet_recovery_s_v1",
                     fleet_resilience.get("recovery_seconds_max")),
                    ("fleet_requests_lost_v1",
                     fleet_resilience.get("requests_lost_total")),
                    ("fleet_slo_attainment_v1",
                     fleet_resilience.get("slo_attainment"))):
                if val is not None:
                    fleet_resilience[bkey.replace("_v1", "_vs_baseline")] = \
                        round(_vs_baseline(baselines, f"{platform}:{bkey}",
                                           float(val), base_path), 4)
        except Exception as exc:
            print(f"bench: fleet-resilience section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- fleet rebalance: live evacuation + elastic autoscaling -------------
    fleet_rebalance = None
    t_rebal = 220 if on_tpu else 180
    if os.environ.get("BENCH_FLEET_REBALANCE", "1") != "0" and \
            _time_left() < t_rebal:
        print(f"bench: shedding fleet-rebalance section "
              f"({_time_left():.0f}s left)", file=sys.stderr)
    elif os.environ.get("BENCH_FLEET_REBALANCE", "1") != "0":
        try:
            with _section_timer("fleet_rebalance"):
                fleet_rebalance = _fleet_rebalance()
            for bkey, val in (
                    ("rebalance_requests_lost_v1",
                     fleet_rebalance.get("requests_lost_total")),
                    ("rebalance_evac_ms_v1",
                     fleet_rebalance.get("evac_ms_mean")),
                    ("rebalance_scale_events_v1",
                     fleet_rebalance.get("scale_events_total")),
                    ("rebalance_scenarios_passed_v1",
                     fleet_rebalance.get("scenarios_passed_frac"))):
                if val is not None:
                    fleet_rebalance[bkey.replace("_v1", "_vs_baseline")] = \
                        round(_vs_baseline(baselines, f"{platform}:{bkey}",
                                           float(val), base_path), 4)
        except Exception as exc:
            print(f"bench: fleet-rebalance section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- autotune: planner search vs hand default ---------------------------
    autotune = None
    t_tune = 120 if on_tpu else 60
    if os.environ.get("BENCH_AUTOTUNE", "1") != "0" and \
            _time_left() < t_tune:
        print(f"bench: shedding autotune section ({_time_left():.0f}s left)",
              file=sys.stderr)
    elif os.environ.get("BENCH_AUTOTUNE", "1") != "0":
        try:
            with _section_timer("autotune"):
                autotune = _autotune()
            avs = _vs_baseline(baselines,
                               f"{platform}:autotune_mlp_steps_per_sec_v1",
                               autotune["best_steps_per_sec"], base_path)
            autotune["vs_baseline"] = round(avs, 4)
        except Exception as exc:
            print(f"bench: autotune section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- reshard: cross-topology redistribution + shrink drill --------------
    reshard = None
    t_reshard = 90 if on_tpu else 60
    if os.environ.get("BENCH_RESHARD", "1") != "0" and \
            _time_left() < t_reshard:
        print(f"bench: shedding reshard section ({_time_left():.0f}s left)",
              file=sys.stderr)
    elif os.environ.get("BENCH_RESHARD", "1") != "0":
        try:
            with _section_timer("reshard"):
                reshard = _reshard()
            rvs = _vs_baseline(baselines,
                               f"{platform}:reshard_chunked_gb_per_sec_v1",
                               reshard["chunked_gb_per_sec"], base_path)
            reshard["vs_baseline"] = round(rvs, 4)
        except Exception as exc:
            print(f"bench: reshard section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- observability: telemetry overhead on the train loop ---------------
    observability = None
    t_obs = 60 if on_tpu else 45
    if os.environ.get("BENCH_OBS", "1") != "0" and _time_left() < t_obs:
        print(f"bench: shedding observability section ({_time_left():.0f}s "
              "left)", file=sys.stderr)
    elif os.environ.get("BENCH_OBS", "1") != "0":
        try:
            with _section_timer("observability"):
                observability = _observability()
            # lower is better, but _vs_baseline just ratios against the
            # first recorded value — drift either way shows up
            ovs = _vs_baseline(baselines,
                               f"{platform}:obs_overhead_fraction_v1",
                               observability["obs_overhead_fraction"],
                               base_path)
            observability["vs_baseline"] = round(ovs, 4)
            tvs = _vs_baseline(
                baselines, f"{platform}:obs_trace_overhead_fraction_v1",
                observability["trace"]["obs_trace_overhead_fraction"],
                base_path)
            observability["trace"]["vs_baseline"] = round(tvs, 4)
        except Exception as exc:
            print(f"bench: observability section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- memory model: calibrated vs analytic HBM prediction error ---------
    memory_model = None
    t_mem = 90 if on_tpu else 60
    if os.environ.get("BENCH_MEMORY", "1") != "0" and _time_left() < t_mem:
        print(f"bench: shedding memory-model section ({_time_left():.0f}s "
              "left)", file=sys.stderr)
    elif os.environ.get("BENCH_MEMORY", "1") != "0":
        try:
            with _section_timer("memory_model"):
                memory_model = _memory_model()
            merr = memory_model["calibrated_error_mean"]
            if merr is not None:
                mvs = _vs_baseline(baselines,
                                   f"{platform}:mem_model_error_v1",
                                   merr, base_path)
                memory_model["vs_baseline"] = round(mvs, 4)
        except Exception as exc:
            print(f"bench: memory-model section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    # --- collectives: quantized + ring-overlapped FSDP comm layer ----------
    collectives = None
    t_comm = 90 if on_tpu else 60
    if os.environ.get("BENCH_COMM", "1") != "0" and _time_left() < t_comm:
        print(f"bench: shedding collectives section ({_time_left():.0f}s "
              "left)", file=sys.stderr)
    elif os.environ.get("BENCH_COMM", "1") != "0":
        try:
            with _section_timer("collectives"):
                collectives = _collectives()
            cvs = _vs_baseline(baselines,
                               f"{platform}:comm_int8_bytes_reduction_v1",
                               collectives["bytes"]["int8_reduction_x"],
                               base_path)
            collectives["vs_baseline"] = round(cvs, 4)
            ofrac = collectives["overlap"]["overlap_fraction"]
            if ofrac:
                # only a nonzero fraction seeds/ratios the baseline: a
                # loaded-box zero must not pin the record at 0 forever
                collectives["overlap_vs_baseline"] = round(
                    _vs_baseline(baselines,
                                 f"{platform}:comm_overlap_fraction_v1",
                                 ofrac, base_path), 4)
        except Exception as exc:
            print(f"bench: collectives section failed "
                  f"({type(exc).__name__}: {exc})", file=sys.stderr)

    attn_speedup = None
    if on_tpu and os.environ.get("BENCH_ATTENTION", "1") != "0":
        if _time_left() < 90:
            print(f"bench: shedding attention micro ({_time_left():.0f}s "
                  "left)", file=sys.stderr)
        else:
            with _section_timer("attention"):
                attn_speedup = _attention_speedup()
    if attn_speedup is not None:
        # latest-wins decision datum: workloads' `--attention auto` gates
        # the TPU flash default on this recorded ratio (northstar.py)
        from distributed_deep_learning_tpu.utils.bench_records import (
            record_flash_speedup)

        record_flash_speedup(attn_speedup)

    line = {
        "metric": f"resnet50_224 bf16 train images/sec/chip ({platform})",
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(vs, 4),
        "mfu": round(mfu, 4) if mfu else None,
        "platform": platform,
        "device_count": n_chips,
        "flops_per_image": round(flops_per_image) if flops_per_image else None,
        "device_kind": device_kind,
        "secondary": secondary,
        "lm": lm,
        "input_pipeline": input_pipe,
        "serving": serving,
        "serving_quant": serving_quant,
        "serving_disagg": serving_disagg,
        "resilience": resilience,
        "serve_resilience": serve_resilience,
        "fleet_resilience": fleet_resilience,
        "fleet_rebalance": fleet_rebalance,
        "autotune": autotune,
        "reshard": reshard,
        "observability": observability,
        "memory_model": memory_model,
        "collectives": collectives,
        "flash_attention_speedup":
            round(attn_speedup, 3) if attn_speedup else None,
        "section_secs": section_secs,
    }
    # --- perf-regression sentry (ISSUE 11) --------------------------------
    # Every measured value is judged against its recorded baseline with a
    # noise-aware band; breaches always WARN loudly on stderr and ride
    # the JSON line.  BENCH_REGRESS=1 turns breaches into exit code 3
    # (the CI gate) — run it worker-direct (BENCH_REGRESS=1 python
    # bench.py), optionally shedding sections with the BENCH_* toggles.
    regressions = regression_sentry(baselines)
    line["regressions"] = regressions
    # every datum this run measured, flat — what BENCH_REGRESS_FROM
    # re-judges later without re-running the benches
    line["measured"] = {k: _MEASURED[k] for k in sorted(_MEASURED)}
    for r in regressions:
        print(f"bench: REGRESSION {r['key']}: {r}", file=sys.stderr)
    print(json.dumps(line))
    if regressions and os.environ.get("BENCH_REGRESS") == "1":
        print(f"bench: {len(regressions)} regression(s) vs baseline; "
              "failing (BENCH_REGRESS=1)", file=sys.stderr)
        return 3
    return 0


def orchestrate() -> int:
    """Watchdogged driver entry: probe, then accelerator attempts only.

    The parent never touches JAX (one process at a time owns the chip);
    it runs a probe child and then worker children one after another.

    1. GLOBAL wall-clock deadline (``BENCH_TIMEOUT``, default 1200 s);
       per-attempt timeouts are carved from what remains.
    2. A ~75 s watchdogged trivial-matmul probe precedes the first heavy
       attempt and reports the platform: a backend that hangs, errors or
       is not a TPU ends the run there.
    3. ANY failed attempt — nonzero rc or timeout — counts; after 2
       failures of any kind the run gives up.

    There is no CPU attempt: a bench that finds no chip, or whose every
    attempt fails, exits 1 and prints no result line.

    Workers receive the absolute deadline (``BENCH_DEADLINE``) and shed
    optional sections (DenseNet / LM / attention micro) to get the
    headline out inside it.
    """
    import subprocess
    import time as _time

    total = float(os.environ.get("BENCH_TIMEOUT", 1200))
    deadline = _time.monotonic() + total

    def remaining() -> float:
        return deadline - _time.monotonic()

    def run_attempt(extra: dict, timeout: float) -> str | None:
        env = dict(os.environ, BENCH_WORKER="1", **extra)
        # absolute soft deadline, with margin for the final print/flush
        env["BENCH_DEADLINE"] = repr(_time.time() + timeout - 10.0)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)], env=env,
                stdout=subprocess.PIPE, text=True, timeout=timeout)
        except subprocess.TimeoutExpired:
            print(f"bench: attempt {extra} timed out after {timeout:.0f}s",
                  file=sys.stderr)
            return None
        if proc.returncode == 0 and proc.stdout.strip():
            return proc.stdout
        print(f"bench: attempt {extra} failed rc={proc.returncode}",
              file=sys.stderr)
        return None

    # --- probe: is the default backend alive, and is it a TPU? -------------
    probe_budget = min(75.0, max(remaining(), 30.0))
    probe_env = dict(os.environ, BENCH_WORKER="1", BENCH_PROBE="1")
    try:
        probe = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=probe_env,
            stdout=subprocess.PIPE, text=True, timeout=probe_budget)
        probed = probe.stdout.split() if probe.returncode == 0 else []
    except subprocess.TimeoutExpired:
        probed = []
    if probed[:1] != ["probe-ok"]:
        print(f"bench: backend probe failed within {probe_budget:.0f}s; "
              "no result", file=sys.stderr)
        return 1
    if probed[1:2] != ["tpu"]:
        print(f"bench: no TPU (default backend is {' '.join(probed[1:])}); "
              "no result. To run the CPU-countable sections on purpose: "
              "JAX_PLATFORMS=cpu BENCH_WORKER=1 python bench.py",
              file=sys.stderr)
        return 1

    # --- accelerator attempts, batch backing off on failure ----------------
    pinned = "BENCH_BATCH" in os.environ or \
        "BENCH_BATCH_PER_CHIP" in os.environ
    # Retries shed the optional sections up front: after a timed-out first
    # attempt a full section set can never fit what remains, but
    # headline-only with a warm compile cache can.
    shed = {"BENCH_SECONDARY": "0", "BENCH_LM": "0", "BENCH_INPUT": "0",
            "BENCH_ATTENTION": "0", "BENCH_SERVE": "0",
            "BENCH_RESILIENCE": "0", "BENCH_SERVE_RESILIENCE": "0",
            "BENCH_FLEET_REBALANCE": "0", "BENCH_RESHARD": "0",
            "BENCH_OBS": "0", "BENCH_COMM": "0", "BENCH_MEMORY": "0"}
    plan: list[dict] = [{}] if pinned else [
        {"BENCH_BATCH_PER_CHIP": "256"},
        {"BENCH_BATCH_PER_CHIP": "128", **shed},
        # insurance against a TPU-specific s2d-stem compile failure: one
        # attempt with the plain 7x7 stem before giving up the chip
        {"BENCH_BATCH_PER_CHIP": "128", "BENCH_S2D": "0", **shed},
    ]
    failures = 0
    for extra in plan:
        budget = remaining()
        if failures >= 2 or budget < 60:
            break  # the backend is sick or time is short
        out = run_attempt(extra, budget if pinned else min(budget, total * 0.6))
        if out is not None:
            sys.stdout.write(out)
            return 0
        failures += 1
    print(f"bench: {failures} accelerator attempt(s) failed; no result",
          file=sys.stderr)
    return 1


if __name__ == "__main__":
    if os.environ.get("BENCH_PROBE") == "1":
        # minimal end-to-end device proof: init backend, one matmul, one
        # host fetch — everything a heavy attempt needs, in miniature
        import jax
        import jax.numpy as jnp

        x = jnp.ones((128, 128))
        float(jnp.sum(x @ x))
        dev = jax.devices()[0]
        print("probe-ok", dev.platform, dev.device_kind)
        sys.exit(0)
    if os.environ.get("BENCH_REGRESS_FROM"):
        # judge an existing record against the baselines — no benches run
        sys.exit(regress_from(os.environ["BENCH_REGRESS_FROM"]))
    if os.environ.get("BENCH_WORKER") == "1" or \
            os.environ.get("BENCH_NO_WATCHDOG") == "1" or \
            os.environ.get("BENCH_REGRESS") == "1":
        # BENCH_REGRESS runs worker-direct: the orchestrator would treat
        # the sentry's exit 3 as a failed attempt and retry, swallowing
        # the very signal the gate exists to surface
        sys.exit(main())
    sys.exit(orchestrate())
