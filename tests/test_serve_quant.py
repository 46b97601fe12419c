"""Quantized serving hot path (ISSUE 14): int8 weights, quantized paged
KV pools, and the block-table-aware flash-decode kernel — under the
serving layer's standing guarantees:

* full precision stays BIT-IDENTICAL (every quant shim is a no-op when
  the dtypes are unset) and bf16-KV greedy decode agrees exactly on the
  pinned trace;
* int8 is drift-BOUNDED, not exact: the calibrated per-token logprob
  bound (serve/quant.calibrate_weight_drift) is the declared gate;
* the quantized representation is what the pool machinery operates on:
  prefix reuse, copy-on-write and chain hashes work unchanged on
  QuantTensor pools, and ``kv_cache_bytes`` measures the real >= 3.5x
  shrink at the bench geometry;
* compile-once survives quantization (``decode_compiles == 1``);
* precision is never silently dropped: a float write into an integer
  slab/pool raises instead of a bare ``astype`` (the write_slot /
  scatter_span regression);
* the Pallas kernel (interpret mode on CPU) matches the lax reference
  for both fp32 and int8 pools.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_deep_learning_tpu.models.transformer import CausalLM
from distributed_deep_learning_tpu.ops.paged_decode_pallas import (
    paged_decode_reference, paged_flash_decode)
from distributed_deep_learning_tpu.serve import cache as slot_cache
from distributed_deep_learning_tpu.serve import paged, quant
from distributed_deep_learning_tpu.serve.engine import (PagedEngine,
                                                        ServeEngine)
from distributed_deep_learning_tpu.serve.quant import (QuantTensor,
                                                       is_quant)
from distributed_deep_learning_tpu.serve.scheduler import Request
from distributed_deep_learning_tpu.utils.config import parse_args

MODEL = dict(vocab_size=61, num_layers=2, d_model=32, num_heads=4,
             mlp_dim=64, max_len=48)


@functools.lru_cache(maxsize=None)
def _shared(**kw):
    model = CausalLM(**{**MODEL, **kw})
    toks = jnp.ones((1, 4), jnp.int32)
    return model, model.init(jax.random.key(1), toks)["params"]


def _engine(**kw):
    model, params = _shared()
    kw.setdefault("max_slots", 3)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return PagedEngine(model, params, **kw)


def _trace(seed=0, n=5, max_new=(1, 8), plens=(3, 16), stagger=3):
    rng = np.random.default_rng(seed)
    reqs, tick = [], 0
    for uid in range(n):
        p = int(rng.integers(*plens))
        reqs.append(Request(uid, rng.integers(1, 61, p).astype(np.int32),
                            int(rng.integers(*max_new)),
                            arrival_tick=tick))
        tick += int(rng.integers(0, stagger + 1))
    return reqs


def _agreement(a, b):
    total = same = 0
    for uid, toks in a.items():
        other = np.asarray(b[uid])
        toks = np.asarray(toks)
        total += len(toks)
        same += int(np.sum(toks == other))
    return same / total


# --- leaf quantizers: round-trip error bounds ---------------------------


def test_roundtrip_error_bounds():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(24, 16)) * 3.0, jnp.float32)
    for qt in (quant.quantize_channels(x), quant.quantize_rows(x)):
        assert is_quant(qt) and qt.q.dtype == jnp.int8
        back = quant.dequant(qt, jnp.float32)
        # symmetric int8: worst-case error is half a quantization step
        # (amax/127) per scale group; check against the global amax
        step = float(jnp.max(jnp.abs(x))) / 127.0
        assert float(jnp.max(jnp.abs(back - x))) <= step
    # scale shapes: per-channel (C,), per-row leading dims + (1,)
    assert quant.quantize_channels(x).s.shape == (16,)
    assert quant.quantize_rows(x).s.shape == (24, 1)


def test_quant_tensor_is_indexable_pytree():
    """The load-bearing shape contract: tree-mapped leading-axis indexing
    hits payload and scales coherently, so every paged pool op works on
    QuantTensor pools unchanged."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(6, 4, 8)),
                    jnp.float32)
    qt = quant.quantize_rows(x)
    picked = jax.tree.map(lambda leaf: leaf[jnp.asarray([4, 0])], qt)
    assert is_quant(picked) and picked.q.shape == (2, 4, 8)
    assert picked.s.shape == (2, 4, 1)
    np.testing.assert_array_equal(np.asarray(picked.q),
                                  np.asarray(qt.q)[[4, 0]])


def test_check_dtype_rejects_unknown():
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        quant.check_dtype("kv_dtype", "fp4")
    assert quant.check_dtype("kv_dtype", None) is None
    assert quant.check_dtype("kv_dtype", "int8") == "int8"


# --- precision contract: no silent float->int casts ---------------------


def test_write_slot_rejects_bare_float_into_int_slab():
    """The regression this PR fixes: a float update landing in an
    integer slab must go through a scale-aware quantizer, never a bare
    astype."""
    slab = {"cached_key": jnp.zeros((2, 4, 3), jnp.int8)}
    upd = {"cached_key": jnp.ones((1, 4, 3), jnp.float32)}
    with pytest.raises(TypeError, match="quantizer"):
        slot_cache.write_slot(slab, upd, 0)
    # the quantizer path produces the slab's dtype and is accepted
    out = slot_cache.write_slot(
        slab, upd, 0, quantizer=lambda x: x.astype(jnp.int8))
    assert out["cached_key"].dtype == jnp.int8
    # and a quantizer with the WRONG output dtype is also rejected
    with pytest.raises(TypeError, match="produced"):
        slot_cache.write_slot(slab, upd, 0,
                              quantizer=lambda x: x.astype(jnp.int16))


def test_scatter_span_rejects_bare_float_into_int_pool():
    pools = {"cached_key": jnp.zeros((4, 8, 2 * 3), jnp.int8)}
    span = {"cached_key": jnp.ones((1, 1, 2, 3), jnp.float32)}
    with pytest.raises(TypeError, match="quantize the span"):
        paged.scatter_span(pools, span, jnp.zeros((1, 1), jnp.int32),
                           jnp.zeros((1, 1), jnp.int32))


# --- quantized pools: CoW, chain hashes, prefix reuse -------------------


def test_int8_pools_are_quant_tensors_and_prefix_reuse_works():
    """Prefix sharing operates on the quantized representation: shared
    blocks hash/hit exactly as in full precision, CoW isolates
    divergence, and two identical int8 runs are deterministic."""
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(1, 61, 17).astype(np.int32)
    reqs = [Request(uid,
                    np.concatenate([sys_prompt,
                                    rng.integers(1, 61, 4 + uid)
                                    .astype(np.int32)]),
                    6, arrival_tick=0)
            for uid in range(4)]

    eng = _engine(max_slots=2, kv_dtype="int8")
    for leaf in jax.tree.leaves(
            {k: v for k, v in eng.pools.items() if k != "tokens"},
            is_leaf=is_quant):
        if is_quant(leaf):
            assert leaf.q.dtype == jnp.int8 and leaf.s.dtype == jnp.float32
    assert any(is_quant(leaf) for leaf in
               jax.tree.leaves(eng.pools, is_leaf=is_quant))

    out = eng.run(reqs)
    assert not out["errors"]
    st = out["stats"]
    assert st["paged"]["prefix_hit_rate"] > 0, st["paged"]
    assert st["decode_compiles"] == 1 and st["chunk_compiles"] == 1, st

    # same trace through the full-precision engine: hit rate identical
    # (chain hashes are token-derived, storage-independent)
    ref = _engine(max_slots=2).run(reqs)
    assert st["paged"]["prefix_hit_rate"] == \
        ref["stats"]["paged"]["prefix_hit_rate"]

    # determinism of the quantized path itself
    again = _engine(max_slots=2, kv_dtype="int8").run(reqs)
    assert _agreement(out["results"], again["results"]) == 1.0


def test_draft_pool_inherits_kv_dtype():
    eng = _engine(kv_dtype="int8", weight_dtype="int8", draft_layers=1,
                  max_len=40)  # leave whole-block speculative headroom
    assert eng.draft_pools is not None
    assert any(is_quant(leaf) for leaf in
               jax.tree.leaves(eng.draft_pools, is_leaf=is_quant))
    out = eng.run(_trace(n=3, max_new=(2, 6)))
    assert not out["errors"]
    assert out["stats"]["decode_compiles"] <= 1  # spec path may use verify


# --- greedy parity gates ------------------------------------------------


def test_bf16_kv_greedy_parity_exact():
    """bf16 KV storage on the pinned trace: token-exact vs full
    precision, on BOTH engines (model compute stays f32; only at-rest
    KV is cast)."""
    reqs = _trace(n=4)
    ref = _engine().run(reqs)
    bf = _engine(kv_dtype="bf16").run(reqs)
    assert _agreement(ref["results"], bf["results"]) == 1.0

    model, params = _shared()
    v1_ref = ServeEngine(model, params, max_slots=3).run(reqs)
    v1_bf = ServeEngine(model, params, max_slots=3,
                        kv_dtype="bf16").run(reqs)
    assert _agreement(v1_ref["results"], v1_bf["results"]) == 1.0
    assert v1_bf["stats"]["decode_compiles"] == 1


def test_int8_weights_drift_bounded():
    """int8 weights: the calibration pass measures the greedy logprob
    drift and declares a bound with headroom; the engine runs clean
    under it with compile-once intact."""
    model, params = _shared()
    qparams = quant.quantize_weights(params, "int8")
    probe = np.asarray(_trace(n=1, plens=(24, 25))[0].prompt)
    cal = quant.calibrate_weight_drift(model, params, qparams, probe)
    assert cal["measured_max_drift"] <= cal["declared_bound"]
    assert cal["declared_bound"] <= 0.05   # the recorded band ceiling
    assert cal["probe_argmax_agreement"] >= 0.9

    reqs = _trace(n=4)
    out = _engine(kv_dtype="int8", weight_dtype="int8").run(reqs)
    assert not out["errors"]
    assert out["stats"]["decode_compiles"] == 1
    # untrained weights sit near argmax ties, so token agreement is the
    # weak gate (drift-bounded, not exact) — most tokens still agree
    ref = _engine().run(reqs)
    assert _agreement(ref["results"], out["results"]) >= 0.5


def test_v1_engine_rejects_int8_kv():
    model, params = _shared()
    with pytest.raises(ValueError, match="requires the paged engine"):
        ServeEngine(model, params, kv_dtype="int8")
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        ServeEngine(model, params, kv_dtype="fp4")


# --- memory: the measured shrink ----------------------------------------


def test_kv_cache_bytes_shrink_at_bench_geometry():
    """At the bench model geometry (head_dim 32) int8 pools + scales cut
    the measured ``kv_cache_bytes`` gauge >= 3.5x vs full precision at
    identical slots x capacity — the acceptance number, computed from
    real allocated pools."""
    from distributed_deep_learning_tpu.obs.memory import pytree_bytes

    model, params = _shared(vocab_size=512, d_model=128, mlp_dim=256,
                            max_len=64)
    kw = dict(max_slots=2, kv_block_size=8, max_len=64)
    fp = PagedEngine(model, params, **kw)
    q8 = PagedEngine(model, params, kv_dtype="int8", **kw)
    ratio = pytree_bytes(fp.pools) / pytree_bytes(q8.pools)
    assert ratio >= 3.5, ratio
    assert q8.kv_dtype == "int8" and fp.kv_dtype is None


def test_weight_bytes_shrink():
    _, params = _shared()
    full = quant.weight_bytes(params)
    q8 = quant.weight_bytes(quant.quantize_weights(params, "int8"))
    assert q8 < full / 2.5   # matmul kernels dominate; vectors stay f32


# --- kernel parity (interpret mode on CPU) ------------------------------


def _merged_pool(rng, N, bs, Hkv, D, dtype, quantized):
    """One pool leaf as the engine rests it: ``(N, bs, Hkv*D)``, int8
    with ``(N, bs, Hkv)`` scales where `quantized`."""
    x = jnp.asarray(rng.normal(size=(N, bs, Hkv, D)), jnp.float32)
    return paged.merge_trailing(quant.quantize_rows(x) if quantized
                                else x.astype(dtype))


#: heads x width, block, blocks a slot, blocks a grid step; every case has
#: a slot of each length in `_lengths` and a table longer than it needs
KERNEL_CASES = {
    # the first two are what this test held before the pools were merged
    "small-f32": dict(H=8, Hkv=4, D=16, bs=8, Bps=3, step=1),
    "small-int8": dict(H=8, Hkv=4, D=16, bs=8, Bps=3, step=1,
                       quantized=True),
    # gpt2-xl's heads (plain MHA, 64 wide: 12.5 lane tiles a row)
    "mha-25x64-bf16": dict(H=25, Hkv=25, D=64, bs=16, Bps=5, step=2,
                           dtype=jnp.bfloat16),
    "mha-25x64-int8": dict(H=25, Hkv=25, D=64, bs=16, Bps=5, step=2,
                           quantized=True),
    # laguna's full layers: 48 query heads on 8 KV heads of 128, G = 6
    "gqa-8x128-bf16": dict(H=48, Hkv=8, D=128, bs=16, Bps=5, step=2,
                           dtype=jnp.bfloat16),
    "gqa-8x128-int8": dict(H=48, Hkv=8, D=128, bs=16, Bps=5, step=3,
                           quantized=True),
    # a table of 5 blocks in steps of 4, of 5 in one step, of 7 in steps
    # of 3: none a multiple of its step
    "step-4-of-5": dict(H=4, Hkv=4, D=8, bs=4, Bps=5, step=4),
    "one-step": dict(H=4, Hkv=2, D=8, bs=4, Bps=5, step=None),
    "step-3-of-7": dict(H=6, Hkv=2, D=8, bs=4, Bps=7, step=3),
    "window": dict(H=6, Hkv=2, D=16, bs=8, Bps=6, step=4, window=9),
}


def _lengths(bs, Bps):
    """Cached positions a slot: none, one, mid-block, a whole block, whole
    blocks, and all but the one the token's own row takes (max_len)."""
    return [0, 1, bs + bs // 2, bs, 2 * bs, Bps * bs - 1]


@pytest.mark.parametrize("new_row", [True, False], ids=["new-row", "cached"])
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_paged_flash_decode_matches_reference(case, new_row):
    """The kernel in interpret mode against the gather-then-mask reference,
    on pool leaves as they rest: every slot length, tables whose tails are
    trash, and an invalid cached position in the last slot's first block."""
    cfg = dict(KERNEL_CASES[case])
    H, Hkv, D, bs, Bps = (cfg[k] for k in ("H", "Hkv", "D", "bs", "Bps"))
    dtype = cfg.get("dtype", jnp.float32)
    quantized = cfg.get("quantized", False)
    rng = np.random.default_rng(3)
    lens = _lengths(bs, Bps)
    B, N = len(lens), len(lens) * Bps + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    kp = _merged_pool(rng, N, bs, Hkv, D, dtype, quantized)
    vp = _merged_pool(rng, N, bs, Hkv, D, dtype, quantized)
    tables = np.full((B, Bps), paged.TRASH, np.int32)
    free = iter(rng.permutation(np.arange(1, N)))
    for b, n in enumerate(lens):                # only the blocks it needs
        for j in range(-(-(n + new_row) // bs)):
            tables[b, j] = next(free)
    valid = np.ones((N, bs), bool)
    valid[tables[-1, 0], 1] = False             # a cached_valid hole
    valid[paged.TRASH] = rng.random(bs) < 0.5   # trash holds anything
    kw = dict(valid_pool=jnp.asarray(valid), window=cfg.get("window"))
    if new_row:
        kw.update(k_new=jnp.asarray(rng.normal(size=(B, Hkv, D)), dtype),
                  v_new=jnp.asarray(rng.normal(size=(B, Hkv, D)), dtype),
                  new_valid=jnp.asarray([True] * (B - 1) + [False]))
    tables, lens = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    ref = paged_decode_reference(q, kp, vp, tables, lens, **kw)
    out = paged_flash_decode(q, kp, vp, tables, lens, interpret=True,
                             blocks_per_step=cfg["step"], **kw)
    assert out.shape == q.shape and out.dtype == q.dtype
    # a slot with nothing to attend: the reference averages garbage, the
    # kernel gives zeros; both finite, both ignored by the engine
    some = slice(0 if new_row else 1, None)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[some], np.asarray(ref, np.float32)[some],
        atol=4e-2 if dtype == jnp.bfloat16 else 1e-5)
    # off-TPU dispatch (no interpret flag) routes to the reference
    disp = paged_flash_decode(q, kp, vp, tables, lens, **kw)
    np.testing.assert_array_equal(np.asarray(disp), np.asarray(ref))


def test_kernel_work_list_holds_live_blocks_only():
    """The kernel's grid as data: the slots' live steps end to end, one
    step for a slot with nothing cached, and for each of a step's tiles
    the table's block while the slot has one there, else what the tile
    held before: no entry of a table's tail is ever named."""
    from distributed_deep_learning_tpu.ops.paged_decode_pallas import (
        _work_list)

    lens = np.asarray([0, 1, 40, 64, 127, 0], np.int32)
    tail = 999                              # never to be read
    tables = np.full((6, 8), tail, np.int32)
    for b, n in enumerate(lens):
        tables[b, :-(-n // 16)] = 100 * (b + 1) + np.arange(-(-n // 16))
    total, slot, step, phys = (np.asarray(x) for x in _work_list(
        jnp.asarray(tables), jnp.asarray(lens), 2, 16))
    assert total == 1 + 1 + 2 + 2 + 4 + 1
    np.testing.assert_array_equal(slot[:total],
                                  [0, 1, 2, 2, 3, 3, 4, 4, 4, 4, 5])
    np.testing.assert_array_equal(step[:total],
                                  [0, 0, 0, 1, 0, 1, 0, 1, 2, 3, 0])
    assert tail not in phys
    np.testing.assert_array_equal(          # tile 0, then tile 1, by step
        phys[:, :total],
        [[0, 200, 300, 302, 400, 402, 500, 502, 504, 506, 506],
         [0, 0, 301, 301, 401, 403, 501, 503, 505, 507, 507]])
    # past the live steps nothing moves: a static grid would issue no DMA
    assert (phys[:, total:] == phys[:, total - 1:total]).all()


def test_paged_flash_decode_zero_length_slot_is_finite():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 2, 8)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(4, 4, 16)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(4, 4, 16)), jnp.float32)
    tables = jnp.zeros((1, 2), jnp.int32)
    out = paged_flash_decode(q, kp, vp, tables,
                             jnp.zeros((1,), jnp.int32), interpret=True)
    assert bool(jnp.all(out == 0))


def test_kernel_rejects_mismatched_quantization():
    q = jnp.zeros((1, 2, 8), jnp.float32)
    kp = jnp.zeros((4, 4, 16), jnp.float32)
    kq = QuantTensor(jnp.zeros((4, 4, 16), jnp.int8), jnp.ones((4, 4, 2)))
    with pytest.raises(ValueError, match="agree on quantization"):
        paged_flash_decode(q, kq, kp, jnp.zeros((1, 1), jnp.int32),
                           jnp.ones((1,), jnp.int32))


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_engine_decodes_through_the_interpreted_kernel(monkeypatch, kv_dtype):
    """The whole path the chip runs, with the kernel interpreted: the
    decode program hands the attention layers the pool leaves and the
    block tables, the slots' ``vmap`` folds into the kernel's slot axis,
    and the tokens are those of the gather path (the dispatcher's choice
    off a TPU)."""
    from distributed_deep_learning_tpu.ops import paged_decode_pallas as pdp

    want = _engine(kv_dtype=kv_dtype).run(_trace(seed=5))
    calls = []

    def interpreted(*args, **kw):
        calls.append(args[0].shape)
        return kernel(*args, **{**kw, "interpret": True})

    kernel = pdp.paged_flash_decode
    monkeypatch.setattr(pdp, "paged_flash_decode", interpreted)
    eng = _engine(kv_dtype=kv_dtype)
    got = eng.run(_trace(seed=5))
    # what reaches the program is one call a layer, every slot a row of it
    # (custom_vmap also traces the one-slot form it then sets aside)
    assert [c for c in calls if c[0] != 1] == \
        [(eng.max_slots, 4, 8)] * MODEL["num_layers"]
    assert got["stats"]["decode_compiles"] == 1
    assert _agreement(want["results"], got["results"]) == 1.0


# --- CLI + plan lattice -------------------------------------------------


@pytest.mark.parametrize("argv,match", [
    (["--kv-dtype", "fp4"], "unknown --kv-dtype"),
    (["--weight-dtype", "fp4"], "unknown --weight-dtype"),
    (["--kv-dtype", "int8"], "requires --paged"),
])
def test_cli_rejects_bad_quant_flags(argv, match):
    with pytest.raises(SystemExit, match=match):
        parse_args(argv)


def test_cli_accepts_quant_flags():
    cfg = parse_args(["--paged", "--kv-dtype", "int8",
                      "--weight-dtype", "int8"])
    assert cfg.kv_dtype == "int8" and cfg.weight_dtype == "int8"
    assert parse_args(["--kv-dtype", "bf16"]).kv_dtype == "bf16"
    assert parse_args([]).kv_dtype is None


def test_plan_lattice_quant_axes():
    from distributed_deep_learning_tpu.tune.space import (Plan,
                                                          enumerate_plans)

    # singleton defaults keep the training lattice unchanged
    assert all(p.kv_dtype == "none" and p.weight_dtype == "none"
               and not p.paged for p in enumerate_plans(2, 8))
    # opting the serving axes in: int8 KV exists ONLY on paged plans
    plans = enumerate_plans(
        2, 8, paged_options=(False, True),
        kv_dtype_options=("none", "bf16", "int8"),
        weight_dtype_options=("none", "int8"))
    assert any(p.kv_dtype == "int8" for p in plans)
    assert all(p.paged for p in plans if p.kv_dtype == "int8")
    # round-trip through Config overrides (replay closure)
    from distributed_deep_learning_tpu.tune.space import (apply_plan,
                                                          plan_from_config)

    p = Plan(paged=True, kv_dtype="int8", weight_dtype="bf16")
    cfg = apply_plan(parse_args([], workload="mlp"), p)
    assert cfg.paged and cfg.kv_dtype == "int8" \
        and cfg.weight_dtype == "bf16"
    assert plan_from_config(cfg, 1) == p


# --- bench record -------------------------------------------------------

