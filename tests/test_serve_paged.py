"""Paged serving engine (ISSUE 9): paged KV + prefix reuse + chunked
prefill + speculative decoding, under the same two load-bearing
guarantees as the v1 engine — compile-once and bit-identical greedy
outputs against ``generate()`` — plus the new ones this generation
adds:

* prefix reuse measurably reduces prefill compute WITHOUT changing one
  output token (shared blocks are referenced, the last prompt token is
  always recomputed, copy-on-write isolates divergence);
* chunked prefill bounds decode stalls: live streams decode EVERY tick
  while a long prompt lands chunk by chunk (timeline-asserted);
* speculative decoding preserves exact greedy parity while the target
  runs fewer forwards (verify replaces plain decode: ``decode==0``,
  ``verify==1``, ``draft==1`` compile counts);
* a burst of long prompts cannot starve a queued short request
  (round-robin chunk budget → bounded wait — the fairness regression).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_deep_learning_tpu.models.transformer import (
    CausalLM, generate, make_decode_model)
from distributed_deep_learning_tpu.serve import paged, quant
from distributed_deep_learning_tpu.serve.engine import PagedEngine
from distributed_deep_learning_tpu.serve.load import (LoadSpec, make_load,
                                                      slo_report)
from distributed_deep_learning_tpu.serve.migrate import BlockMigrator
from distributed_deep_learning_tpu.serve.paged import (TRASH, BlockManager,
                                                       chain_hash)
from distributed_deep_learning_tpu.serve.prefill import (ChunkPlan,
                                                         chunk_tokens,
                                                         plan_chunks,
                                                         write_targets)
from distributed_deep_learning_tpu.serve.scheduler import Request
from distributed_deep_learning_tpu.serve.spec import (greedy_accept,
                                                      truncated_draft)
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


def _trace(seed=0, n=6, max_new=(1, 10), plens=(3, 20), stagger=3):
    rng = np.random.default_rng(seed)
    reqs, tick = [], 0
    for uid in range(n):
        p = int(rng.integers(*plens))
        reqs.append(Request(uid, rng.integers(1, 61, p).astype(np.int32),
                            int(rng.integers(*max_new)),
                            arrival_tick=tick))
        tick += int(rng.integers(0, stagger + 1))
    return reqs


def _check_parity(out, reqs, label="", **model_kw):
    model, params = _shared(**model_kw)
    for r in reqs:
        ref = generate(model, params, jnp.asarray(r.prompt)[None],
                       max_new_tokens=r.max_new_tokens)
        np.testing.assert_array_equal(out["results"][r.uid],
                                      np.asarray(ref)[0],
                                      err_msg=f"{label} request {r.uid}")


# --- the tentpole guarantees -------------------------------------------


def test_paged_matches_generate_and_compiles_once():
    """Bit-identical greedy outputs vs generate() across a mixed trace,
    with EXACTLY one chunk-prefill, one decode, and (at most) one
    block-copy compilation for the engine's lifetime — across TWO
    run() calls (the second starts with a warm prefix index)."""
    eng = _engine()
    reqs = _trace(n=5, max_new=(1, 8), plens=(3, 16))
    out = eng.run(reqs)
    assert not out["errors"]
    _check_parity(out, reqs, label="run1")
    s = out["stats"]
    assert s["chunk_compiles"] == 1, s
    assert s["decode_compiles"] == 1, s
    assert s["verify_compiles"] == 0, s

    reqs2 = _trace(seed=9, n=3)
    out2 = eng.run(reqs2)
    _check_parity(out2, reqs2, label="run2")
    s2 = out2["stats"]
    assert s2["chunk_compiles"] == 1 and s2["decode_compiles"] == 1, s2


@pytest.mark.parametrize("kw,paths", [
    (dict(), {"block_table": 2, "gather": 0, "latent": 0}),
    (dict(kv_dtype="int8"), {"block_table": 2, "gather": 0, "latent": 0}),
    (dict(draft_layers=1, spec_k=2, max_len=80),
     {"block_table": 2, "gather": 0, "latent": 0}),
])
def test_decode_program_attends_live_blocks_through_the_table(kw, paths):
    """The decode program hands every full-kind layer its pool leaves and
    the slot's block table (the build says how many layers took which
    path, the compile log repeats it), and the tick ring counts the blocks
    it reads over the blocks the tables hold: a slot of 40 cached
    positions at block 16 reads 3 blocks a layer, not `blocks_per_slot`."""
    from distributed_deep_learning_tpu import obs

    model, params = _shared(max_len=96)
    obs.compile_log.mark("test")
    eng = PagedEngine(model, params, max_slots=2, kv_block_size=16,
                      prefill_chunk=8, **kw)
    assert eng.decode_attn_paths == paths
    assert eng.blocks_per_slot == 6
    rng = np.random.default_rng(2)
    reqs = [Request(0, rng.integers(1, 61, 40).astype(np.int32), 3),
            Request(1, rng.integers(1, 61, 5).astype(np.int32), 2,
                    arrival_tick=20)]
    out = eng.run(reqs)
    _check_parity(out, reqs, max_len=96) if not kw.get("kv_dtype") else None
    attn = out["stats"]["paged"]["decode_attn"]
    assert attn["paths"] == paths
    ticks = [t[2][2] for t in obs.last_run("serve").phases.ticks
             if t[1] == "decode"]
    if kw.get("draft_layers"):      # speculation verifies: the gather path
        assert all("attn_blocks" not in c for c in ticks)
        assert attn["blocks_read"] == 0
        return
    assert out["stats"]["decode_compiles"] == 1
    held = 2 * 2 * eng.blocks_per_slot      # layers x slots x blocks a slot
    reads = [c["attn_blocks"] for c in ticks]
    assert all(c["tables"] == held for c in reads)
    # the first token of each comes with its last chunk; request 0 then
    # decodes at 40 and 41 cached positions: 3 blocks a layer; request 1
    # (alone by then) at 5: 1 block a layer
    assert [c["read"] for c in reads] == [6, 6, 2]
    assert attn["blocks_read"] == 14 and \
        attn["blocks_in_tables"] == 3 * held
    notes = [e for e in obs.compile_log.since_mark() if e[0] == "attn_paths"]
    assert notes == [("attn_paths", "jit(paged_decode)", notes[0][2],
                      "block_table=2 gather=0 latent=0")]


def test_obs_report_prints_the_decode_attention_line():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "scripts", "obs_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    stats = _engine().run(_trace(n=3))["stats"]
    attn = stats["paged"]["decode_attn"]
    assert 0 < attn["blocks_read"] < attn["blocks_in_tables"]
    text = report.render([{"event": "obs_serve", "stats": stats}])
    assert ("decode attention: 2 layers through the block table, 0 "
            f"gathered; blocks read {attn['blocks_read']} of "
            f"{attn['blocks_in_tables']} in the tables") in text
    pg = stats["paged"]
    assert pg["indexed_total"] > 0
    assert (f"prefix index: {pg['indexed_total']} blocks registered, "
            f"{pg['indexed_total'] * 8} tokens read to hash them (1.00 a "
            "token indexed)") in text


def test_decode_view_keeps_pool_leaves_and_gathers_rings():
    """`decode_view` hands a full-kind layer the pool leaves themselves and
    the table, gathers a ring layer's leaves; `view_span` takes the
    token's own row from the first and position `pos` from the second."""
    like = {"layer_0": {"self_attn": {
                "cached_key": jax.ShapeDtypeStruct((1, 8, 2, 4), jnp.float32),
                "cached_valid": jax.ShapeDtypeStruct((1, 8), jnp.bool_),
                "cache_index": jax.ShapeDtypeStruct((), jnp.int32)}},
            "layer_1": {"self_attn": {
                "ring_key": jax.ShapeDtypeStruct((1, 4, 2, 4), jnp.float32),
                "cache_index": jax.ShapeDtypeStruct((), jnp.int32)}},
            "embed": {"pos_index": jax.ShapeDtypeStruct((), jnp.int32)}}
    assert paged.attention_paths(like) == {"block_table": 1, "gather": 1, "latent": 0}
    pools = paged.build_pools(like, 5, 2, ring_num_blocks=3)
    pools = jax.tree.map(
        lambda x: jnp.arange(x.size, dtype=jnp.float32).reshape(
            x.shape).astype(x.dtype), pools)
    table = (jnp.asarray([3, 1, 0, 0]), jnp.asarray([2, 1]))
    view = paged.decode_view(pools, table, 5, like)
    full = view["layer_0"]["self_attn"]
    assert full["cached_key"] is pools["layer_0"]["self_attn"]["cached_key"]
    np.testing.assert_array_equal(full["block_table"], table[0])
    assert int(full["cache_index"]) == 5 and \
        int(view["embed"]["pos_index"]) == 5
    ring = view["layer_1"]["self_attn"]
    assert "block_table" not in ring and ring["ring_key"].shape == (1, 4, 2, 4)
    np.testing.assert_array_equal(
        ring["ring_key"][0, :2],
        pools["layer_1"]["self_attn"]["ring_key"][2].reshape(2, 2, 4))
    # what the model hands back: the token's own row where the pool went in
    row = jnp.full((1, 1, 2, 4), 7.0)
    new = {**view, "layer_0": {"self_attn": {
        **full, "cached_key": row, "cached_valid": jnp.ones((1, 1), bool)}}}
    span = paged.view_span(new, 5)
    assert set(span["layer_0"]["self_attn"]) == {
        "cached_key", "cached_valid", "cache_index"}
    np.testing.assert_array_equal(span["layer_0"]["self_attn"]["cached_key"],
                                  row[0])
    np.testing.assert_array_equal(           # position 5 of a ring of 4
        span["layer_1"]["self_attn"]["ring_key"][0],
        ring["ring_key"][0, 1])


def test_prefix_reuse_skips_prefill_same_tokens_out():
    """Requests opening with one shared system prompt: the paged engine
    prefills the shared blocks ONCE, later requests reference them
    (hit rate > 0, fewer prefill tokens computed) — and every output
    token still matches generate() exactly."""
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(1, 61, 17).astype(np.int32)
    reqs = []
    for uid in range(4):
        tail = rng.integers(1, 61, 4 + uid).astype(np.int32)
        reqs.append(Request(uid, np.concatenate([sys_prompt, tail]),
                            6, arrival_tick=0))
    eng = _engine(max_slots=2)
    out = eng.run(reqs)
    assert not out["errors"]
    _check_parity(out, reqs, label="shared-prefix")
    pg = out["stats"]["paged"]
    # requests 0-1 are admitted together into an empty index; 2-3 admit
    # after blocks committed and reuse the two full 8-blocks each (the
    # partial 3rd block may add more via the children index)
    assert pg["shared_tokens"] >= 2 * 16, pg
    assert pg["prefix_hit_rate"] > 0.3, pg
    assert pg["prefill_tokens_computed"] < pg["prompt_tokens"] + \
        8 * len(reqs), pg

    # a SECOND trace with the same system prompt through the same
    # engine starts with a warm index: the shared prefix is never
    # recomputed
    tail = rng.integers(1, 61, 5).astype(np.int32)
    reqs2 = [Request(10, np.concatenate([sys_prompt, tail]), 4,
                     arrival_tick=0)]
    out2 = eng.run(reqs2)
    _check_parity(out2, reqs2, label="warm-index")
    assert out2["stats"]["paged"]["shared_tokens"] >= 16


def test_copy_on_write_isolates_divergence():
    """Two prompts sharing a PARTIAL block (12 tokens, block size 8):
    the second matches mid-block, gets a copy-on-write reserve block,
    and neither request's outputs are perturbed by the other."""
    rng = np.random.default_rng(7)
    shared = rng.integers(1, 61, 12).astype(np.int32)
    a = Request(0, np.concatenate([shared,
                                   rng.integers(1, 61, 6).astype(np.int32)]),
                5, arrival_tick=0)
    # B arrives once A has committed (and registered) both blocks the
    # 12-token prefix spans — the partial match on block 1 is what
    # forces the copy
    b = Request(1, np.concatenate([shared,
                                   rng.integers(1, 61, 9).astype(np.int32)]),
                5, arrival_tick=4)
    eng = _engine(max_slots=2)
    out = eng.run([a, b])
    assert not out["errors"]
    _check_parity(out, [a, b], label="cow")
    assert out["stats"]["paged"]["cow_copies"] >= 1, out["stats"]["paged"]


def test_spec_decoding_exact_parity_fewer_target_forwards():
    """Speculative decoding with a truncated 1-layer draft: outputs are
    bit-identical to generate() (greedy parity is exact, acceptance only
    changes speed), the verify and draft programs compile once each, and
    plain decode never runs (``decode_compiles == 0``)."""
    reqs = _trace(seed=3, n=4, max_new=(4, 10), plens=(3, 14))
    eng = _engine(max_len=40, draft_layers=1, spec_k=3)
    out = eng.run(reqs)
    assert not out["errors"]
    _check_parity(out, reqs, label="spec")
    s = out["stats"]
    assert s["decode_compiles"] == 0, s
    assert s["verify_compiles"] == 1, s
    assert s["draft_compiles"] == 1, s
    assert s["chunk_compiles"] == 1, s
    sp = s["spec"]
    assert sp["enabled"] and sp["rounds"] > 0
    assert sp["acceptance_rate"] is not None
    assert 0.0 <= sp["acceptance_rate"] <= 1.0
    # every accepted proposal is one target forward the engine skipped
    assert sp["proposed"] == sp["rounds"] * 3


def test_chunked_prefill_bounds_decode_stalls():
    """The stall bound, tick by tick: while a 40-token prompt lands in
    8-token chunks, the already-live short request decodes EVERY tick —
    a long arrival costs live streams at most one chunk of compute per
    tick, never a whole prompt."""
    rng = np.random.default_rng(11)
    short = Request(0, rng.integers(1, 61, 4).astype(np.int32), 20,
                    arrival_tick=0)
    long_ = Request(1, rng.integers(1, 61, 40).astype(np.int32), 3,
                    arrival_tick=2)
    eng = _engine(max_slots=2, prefill_chunk=8)
    out = eng.run([short, long_], keep_timeline=True)
    assert not out["errors"]
    _check_parity(out, [short, long_], label="stall")
    tl = out["timeline"]
    # the long prompt takes ceil(40/8) = 5 chunk ticks
    chunk_ticks = [ev["tick"] for ev in tl if 1 in ev["chunks"]]
    assert len(chunk_ticks) == 5, tl
    # budget: at most chunks_per_tick (=1) chunks ever run in one tick
    assert all(len(ev["chunks"]) <= 1 for ev in tl)
    # THE bound: on every tick the long prompt was prefilling, the
    # short request still decoded
    short_decode_ticks = {ev["tick"] for ev in tl if 0 in ev["decoded"]}
    for t in chunk_ticks:
        assert t in short_decode_ticks, \
            f"tick {t}: short stalled behind long prefill\n{tl}"


def test_burst_of_long_prompts_cannot_starve_short():
    """Fairness regression: three 40-token prompts and one short
    request all admitted at tick 0.  The round-robin chunk budget
    guarantees the short request's single chunk runs within
    ``max_slots`` ticks and it decodes every tick after — a long-prompt
    burst delays it by a bounded number of chunks, not by the burst's
    total prefill work."""
    rng = np.random.default_rng(13)
    reqs = [Request(u, rng.integers(1, 61, 40).astype(np.int32), 2,
                    arrival_tick=0) for u in range(3)]
    reqs.append(Request(3, rng.integers(1, 61, 5).astype(np.int32), 8,
                        arrival_tick=0))
    eng = _engine(max_slots=4, prefill_chunk=8)
    out = eng.run(reqs, keep_timeline=True)
    assert not out["errors"]
    tl = out["timeline"]
    first_chunk = next(ev["tick"] for ev in tl if 3 in ev["chunks"])
    assert first_chunk < 4, \
        f"short request's prefill waited {first_chunk} ticks\n{tl}"
    # once live it decodes on EVERY subsequent tick until retirement,
    # long burst or not
    decoded = [ev["tick"] for ev in tl if 3 in ev["decoded"]]
    assert len(decoded) >= 1
    assert decoded == list(range(decoded[0], decoded[0] + len(decoded))), \
        f"short request skipped decode ticks: {decoded}"
    _check_parity(out, reqs, label="fairness")


@pytest.mark.slow
def test_admission_waits_for_blocks_never_deadlocks():
    """A trace larger than the block pool: admission reserves each
    request's WHOLE budget or waits, so the pool can never deadlock
    mid-request — everything completes, with evictions or head-of-line
    waits, and outputs stay exact."""
    reqs = _trace(seed=17, n=6, plens=(10, 18), max_new=(4, 8))
    # 2 slots x 6 blocks, +2 spare: admission must throttle
    eng = _engine(max_slots=2, num_blocks=14)
    out = eng.run(reqs)
    assert not out["errors"]
    assert len(out["results"]) == len(reqs)
    _check_parity(out, reqs, label="pressure")
    pg = out["stats"]["paged"]
    assert pg["blocks_peak_in_use"] <= 14


def test_request_longer_than_capacity_rejected():
    eng = _engine(max_slots=1)
    big = Request(0, np.ones(45, np.int32), 10, arrival_tick=0)
    out = eng.run([big])
    assert 0 in out["errors"]
    assert not out["results"]


# --- unit layers --------------------------------------------------------


KV_DTYPES = pytest.mark.parametrize("kv_dtype", [None, "bf16", "int8"],
                                    ids=["f32", "bf16", "int8"])
HEADS, HEAD_DIM = MODEL["num_heads"], MODEL["d_model"] // MODEL["num_heads"]


def _written_pools(kv_dtype, blocks, offsets, seed=0):
    """Pools of the test model (6 blocks of 8) with one random span
    scattered at ``blocks``/``offsets``; returns the template, the pools
    and the span as it rests (int8: payload + scales)."""
    lm = make_decode_model(_shared()[0])
    like = paged.slot_template(lm, 48, kv_dtype=kv_dtype)
    rng = np.random.default_rng(seed)

    def draw(path, leaf):
        if paged.is_counter(path):
            return jnp.zeros((), jnp.int32)
        shape = (len(blocks),) + leaf.shape[2:]
        if leaf.dtype == jnp.bool_:
            return jnp.asarray(rng.integers(0, 2, shape), jnp.bool_)
        return jnp.asarray(rng.normal(size=shape), leaf.dtype)

    span = jax.tree_util.tree_map_with_path(
        draw, paged.slot_template(lm, 48))
    if kv_dtype is not None:
        span = quant.quantize_cache_span(span, kv_dtype)
    pools = paged.scatter_span(paged.build_pools(like, 6, 8), span,
                               jnp.asarray(blocks), jnp.asarray(offsets))
    return like, pools, span


def _assert_slot_holds(like, pools, table, positions, span):
    """The slot gathered through ``table`` comes back in the model's own
    layout and holds ``span`` at ``positions``, bit for bit."""
    def check(path, leaf, want, wrote):
        if paged.is_counter(path):
            assert int(leaf) == 7
            return
        assert leaf.shape == want.shape and leaf.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(leaf[0, positions]),
                                      np.asarray(wrote))

    got = paged.gather_slot(pools, jnp.asarray(table), 7, like)
    jax.tree_util.tree_map_with_path(check, got, like, span)


@KV_DTYPES
def test_pool_leaves_rest_merged(kv_dtype):
    """Every K/V pool leaf is ``(blocks, block, H*D)`` (int8 scales
    ``(blocks, block, H)``), validity stays ``(blocks, block)``, and the
    bytes are what the model's 4-D layout held."""
    eng = _engine(kv_dtype=kv_dtype)
    nb = eng.num_blocks + 1
    item, seen = {None: 4, "bf16": 2, "int8": 1}[kv_dtype], set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng.pools):
        if paged.is_counter(path):
            assert leaf.shape == ()
            continue
        seen.add(leaf.shape)
        assert leaf.shape[:2] == (nb, 8) and leaf.ndim <= 3
    merged = {(nb, 8, HEADS * HEAD_DIM)}
    if kv_dtype == "int8":
        merged.add((nb, 8, HEADS))
    assert merged <= seen <= merged | {(nb, 8)}, seen
    per_position = HEADS * HEAD_DIM * item + \
        (HEADS * 4 if kv_dtype == "int8" else 0)
    rest = sum(leaf.nbytes for leaf in jax.tree.leaves(eng.pools)
               if leaf.ndim < 3)          # validity masks and counters
    assert eng.kv_cache_bytes - rest == \
        2 * MODEL["num_layers"] * nb * 8 * per_position


@KV_DTYPES
def test_gather_of_scatter_returns_the_span_bit_for_bit(kv_dtype):
    blocks, offsets = [3, 3, 1, 5], [6, 7, 0, 2]
    like, pools, span = _written_pools(kv_dtype, blocks, offsets)
    # logical blocks 0, 1, 2 are physical 3, 1, 5
    _assert_slot_holds(like, pools, [3, 1, 5, TRASH, TRASH, TRASH],
                       np.asarray([6, 7, 8, 18]), span)


@KV_DTYPES
def test_copy_block_preserves_the_block(kv_dtype):
    like, pools, span = _written_pools(kv_dtype, [2, 2, 2], [0, 3, 7])
    pools = jax.jit(paged.copy_block)(pools, 2, 4)
    for table in ([2] + [TRASH] * 5, [4] + [TRASH] * 5):
        _assert_slot_holds(like, pools, table, np.asarray([0, 3, 7]), span)


@KV_DTYPES
def test_migrator_round_trip_preserves_the_span(kv_dtype):
    like, pools, span = _written_pools(kv_dtype, [1, 4, 4], [5, 0, 1])
    dst = BlockMigrator(2).migrate(
        pools, paged.build_pools(like, 6, 8), np.asarray([1, 4]),
        np.asarray([5, 2]), device=jax.local_devices()[1], verify=True)
    _assert_slot_holds(like, dst, [5, 2] + [TRASH] * 4,
                       np.asarray([5, 8, 9]), span)


def test_chain_hash_commits_to_whole_prefix():
    h1 = chain_hash(b"", [1, 2, 3])
    assert chain_hash(b"", [1, 2, 3]) == h1
    assert chain_hash(b"", [1, 2, 4]) != h1
    h2 = chain_hash(h1, [4, 5])
    # same chunk under a different parent → different chain hash
    assert chain_hash(chain_hash(b"", [9, 9, 9]), [4, 5]) != h2


def test_block_manager_refcounts_and_eviction():
    mgr = BlockManager(num_blocks=8, block_size=4, max_slots=2,
                       blocks_per_slot=4)
    prompt = list(range(1, 14))           # 13 tokens: 3 full blocks - 1
    sp = mgr.match_prefix(prompt)
    assert mgr.shared_len(sp) == 0        # cold index
    shared = mgr.admit(0, sp, 16)
    assert shared == 0 and mgr.in_use == 4
    mgr.register_committed(0, prompt, 12)
    mgr.release(0)
    # registered blocks outlive the request (index holds the ref) ...
    assert mgr.in_use == 3
    # ... and a matching prompt reuses them, capped at L-1 so the last
    # token is always recomputed for first-token sampling
    sp2 = mgr.match_prefix(prompt)
    assert mgr.shared_len(sp2) == 12      # 12 < 13 - 1 is false: 12 = L-1
    sp3 = mgr.match_prefix(prompt[:13])
    assert mgr.shared_len(sp3) <= len(prompt) - 1
    # filling the pool evicts LRU index blocks rather than failing
    shared2 = mgr.admit(0, sp2, 16)
    assert shared2 == 12
    mgr.release(0)
    sp4 = mgr.match_prefix([50, 51, 52, 53, 54])
    assert mgr.can_admit(sp4, 20) is False or mgr.in_use <= 8


def _register_whole_stream(mgr, slot, tokens, committed):
    """`BlockManager.register_committed` as it stood before it read only
    the blocks that have just filled: the whole stream becomes an array at
    every call.  The reference the incremental one is held to."""
    bs = mgr.block_size
    done, h = mgr._chain[slot]
    toks = np.asarray(tokens)
    while (done + 1) * bs <= committed:
        blk = tuple(int(t) for t in toks[done * bs:(done + 1) * bs])
        parent, h = h, chain_hash(h, blk)
        b = int(mgr.tables[slot, done])
        if b != TRASH and mgr.index.add(parent, h, b, blk):
            mgr.refs[b] += 1
        done += 1
    mgr._chain[slot] = (done, h)


def _commits(mode, prompt_len, total, chunk, rng):
    """The values `committed` takes while one slot is served: the prompt
    a chunk at a time (the last chunk ends at the prompt's end), then the
    answer a token a tick, or whole in steps of a chunk, or as the
    speculative path commits it, 1 to 4 tokens a round."""
    out = [p.commit_to for p in plan_chunks(0, prompt_len, chunk)]
    c = prompt_len
    while c < total:
        c = min(total, c + {"token": 1, "chunk": chunk,
                            "spec": int(rng.integers(1, 5))}[mode])
        out.append(c)
    return out


@pytest.mark.parametrize("mode", ["token", "chunk", "spec"])
@pytest.mark.parametrize("kind", ["list", "array"])
@pytest.mark.parametrize("bs", [4, 16])
def test_register_committed_builds_the_whole_stream_references_index(
        bs, kind, mode):
    """Three slots, two of them behind one shared prefix (so a block is
    hashed that the index already holds), fed the same commits through
    `register_committed` and through the whole-stream reference: the
    index entry for entry, the chains and the reference counts are
    equal, and each indexed block's tokens were read once."""
    rng = np.random.default_rng(bs * 7 + len(kind) + len(mode))
    shared = [int(t) for t in rng.integers(1, 1000, 3 * bs + 1)]
    streams = [shared + [int(t) for t in rng.integers(1, 1000, n)]
               for n in (5 * bs + 3, 2 * bs + 1)]
    streams.append([int(t) for t in rng.integers(1, 1000, 4 * bs + 2)])
    prompt_lens = [3 * bs + 2, 4 * bs, 2 * bs + 3]

    def give(tokens):      # the stream as the caller holds it so far
        return list(tokens) if kind == "list" else np.asarray(tokens)

    mgrs = [BlockManager(num_blocks=64, block_size=bs, max_slots=3,
                         blocks_per_slot=12) for _ in range(2)]
    registers = [BlockManager.register_committed, _register_whole_stream]
    for mgr, register in zip(mgrs, registers):
        for slot, (stream, L) in enumerate(zip(streams, prompt_lens)):
            sp = mgr.match_prefix(give(stream[:L]))
            mgr.admit(slot, sp, len(stream))
            for c in _commits(mode, L, len(stream), 2 * bs,
                              np.random.default_rng(slot)):
                # the host knows one token past what is committed: the
                # pending one (engine.py appends before it registers)
                register(mgr, slot, give(stream[:c + 1]), c)
    got, want = mgrs
    assert len(want.index) >= 10           # the reference indexed them
    assert list(got.index.entries) == list(want.index.entries)
    for h, e in want.index.entries.items():
        g = got.index.entries[h]
        assert (g.block, g.tokens, g.parent) == (e.block, e.tokens, e.parent)
        assert all(type(t) is int for t in g.tokens)
    assert got.index.children == want.index.children
    assert got.index.by_block == want.index.by_block
    assert got._chain == want._chain
    np.testing.assert_array_equal(got.refs, want.refs)
    np.testing.assert_array_equal(got.tables, want.tables)
    stats = got.stats()
    assert stats["indexed_total"] == len(want.index)
    hashed = sum(done for done, _ in got._chain.values())
    # slot 1 came in behind slot 0's indexed prefix: those blocks were
    # matched at admission, never hashed again
    assert stats["tokens_read"] == (hashed - 3) * bs


class _CountingStream(list):
    """A slot's stream that counts what is read out of it."""

    def __init__(self, tokens):
        super().__init__(tokens)
        self.reads = []

    def __getitem__(self, key):
        self.reads.append(key)
        return super().__getitem__(key)

    def __iter__(self):
        self.reads.append("iter")
        return super().__iter__()


def test_register_committed_reads_only_the_block_that_filled():
    bs, L = 16, 20_000
    mgr = BlockManager(num_blocks=1300, block_size=bs, max_slots=1,
                       blocks_per_slot=1260)
    stream = _CountingStream(int(t) for t in
                             np.random.default_rng(3).integers(1, 5000, L))
    mgr.admit(0, mgr.match_prefix(list(stream)), L + 64)
    assert mgr.register_committed(0, stream, L) == L // bs
    assert mgr.stats()["tokens_read"] == L
    stream.reads.clear()
    for c in range(L + 1, L + bs):         # 15 tokens: no block fills
        stream.append(c)
        assert mgr.register_committed(0, stream, c) == 0
    assert stream.reads == []
    assert mgr.stats()["tokens_read"] == L
    stream.append(7)
    assert mgr.register_committed(0, stream, L + bs) == 1
    assert stream.reads == [slice(L, L + bs)]
    assert mgr.stats()["tokens_read"] == L + bs
    assert mgr.stats()["indexed_total"] == L // bs + 1


@pytest.mark.parametrize("kind", ["list", "array"])
@pytest.mark.parametrize("length,plan", [
    (19, ChunkPlan(8, 16, -1)),            # a whole chunk mid-prompt
    (19, ChunkPlan(11, 19, 7)),            # the last chunk, slid back
    (5, ChunkPlan(0, 5, 4)),               # shorter than a chunk: padded
])
def test_chunk_tokens_converts_the_slice_alone_to_int32(kind, length, plan):
    tokens = [int(t) for t in
              np.random.default_rng(length).integers(1, 50_000, length)]
    stream = _CountingStream(tokens) if kind == "list" \
        else np.asarray(tokens)
    got = chunk_tokens(stream, plan, 8, 61)
    # the slice as it was taken before: of the whole stream as an array
    old = np.asarray(tokens)[plan.feed_start:plan.feed_start + 8]
    old = np.concatenate([old, np.full(8 - len(old), 61, old.dtype)])
    assert got.dtype == np.int32 and got.shape == (8,)
    np.testing.assert_array_equal(got, old)
    if kind == "list":
        assert stream.reads == [slice(plan.feed_start, plan.feed_start + 8)]


def test_no_convert_program_runs_ahead_of_a_chunk():
    """The chunk's tokens reach the device as the int32 the chunk program
    is traced for: an int64 host array made `jnp.asarray(toks, jnp.int32)`
    a device program of its own ahead of every chunk.  The compile log
    names every program the run compiled; the chunk width is one no other
    test of this process uses, so a convert program for it could not have
    been compiled before.  Served tokens are `generate()`'s."""
    from distributed_deep_learning_tpu import obs
    from distributed_deep_learning_tpu.runtime.bootstrap import (
        enable_compile_cache)

    enable_compile_cache()                 # installs the log's listeners
    eng = _engine(prefill_chunk=13)
    reqs = _trace(seed=4, n=5, max_new=(2, 10), plens=(3, 30))
    obs.compile_log.mark("test")
    out = eng.run(reqs)
    assert not out["errors"]
    compiled = [fun for event, fun, *_ in obs.compile_log.since_mark()
                if event == "compile"]
    assert "jit(paged_chunk)" in compiled and "jit(paged_decode)" in compiled
    assert not [f for f in compiled if "convert_element_type" in f], compiled
    _check_parity(out, reqs, label="int32 chunk")
    s = out["stats"]
    assert s["chunk_compiles"] == 1 and s["decode_compiles"] == 1, s
    assert s["paged"]["indexed_total"] > 0
    assert s["paged"]["tokens_read"] == s["paged"]["indexed_total"] * 8


def test_plan_chunks_tail_shift_single_width():
    # 19 unshared tokens in 8-token chunks: 0-8, 8-16, then the LAST
    # chunk slides back to keep one static width (feed 11..19)
    plans = plan_chunks(0, 19, 8)
    assert [(p.feed_start, p.commit_to) for p in plans] == \
        [(0, 8), (8, 16), (11, 19)]
    assert [p.is_last for p in plans] == [False, False, True]
    assert plans[-1].logit_index == 18 - 11
    # shared prefix shifts the start; a short remainder is one chunk
    plans = plan_chunks(12, 15, 8)
    assert [(p.feed_start, p.commit_to) for p in plans] == [(7, 15)]
    assert plans[0].logit_index == 14 - 7
    with pytest.raises(ValueError):
        plan_chunks(5, 5, 8)


def test_write_targets_route_overlap_to_trash():
    table = np.array([3, 7, 9, 2], np.int32)
    blocks, offsets, live = write_targets(
        feed_start=5, n=8, committed=8, length=11, table_row=table,
        block_size=4)
    # positions 5..7 are already committed, 11..12 beyond the prompt:
    # both land in the trash block; 8..10 write for real
    assert list(blocks[:3]) == [TRASH] * 3
    assert list(blocks[3:6]) == [9, 9, 9]
    assert list(offsets[3:6]) == [0, 1, 2]
    assert list(blocks[6:]) == [TRASH] * 2
    assert list(live) == [0, 0, 0, 1, 1, 1, 0, 0]


def test_greedy_accept_prefix_semantics():
    a, em = greedy_accept([5, 6, 7], [5, 6, 7, 8])
    assert (a, em) == (3, [5, 6, 7, 8])       # all accepted + bonus
    a, em = greedy_accept([5, 6, 7], [5, 9, 1, 2])
    assert (a, em) == (1, [5, 9])             # correction replaces d_1
    a, em = greedy_accept([5, 6, 7], [4, 1, 2, 3])
    assert (a, em) == (0, [4])                # pure fallback to target
    with pytest.raises(ValueError):
        greedy_accept([5, 6], [5, 6])


def test_truncated_draft_shares_weights():
    model, params = _shared()
    draft, dparams = truncated_draft(model.clone(decode=True), params, 1)
    assert draft.num_layers == 1
    assert dparams["embed"] is params["embed"]
    assert "layer_1" not in dparams
    with pytest.raises(ValueError):
        truncated_draft(model.clone(decode=True), params, 2)


# --- trace-driven load + SLOs ------------------------------------------


def test_make_load_shapes_and_determinism():
    spec = LoadSpec(n_requests=12, arrival="poisson", rate=1.5,
                    shared_prefix_len=6, shared_frac=1.0,
                    prompt_short=(2, 4), prompt_long=(8, 10),
                    slo_ttft_ms=100.0)
    a = make_load(spec, vocab_size=61, seed=4)
    b = make_load(spec, vocab_size=61, seed=4)
    assert [r.prompt.tolist() for r in a] == \
        [r.prompt.tolist() for r in b]
    head = a[0].prompt[:6].tolist()
    assert all(r.prompt[:6].tolist() == head for r in a)  # one sys prompt
    ticks = [r.arrival_tick for r in a]
    assert ticks == sorted(ticks)
    assert all(r.slo_ttft_ms == 100.0 for r in a)

    bursty = make_load(LoadSpec(n_requests=8, arrival="bursty",
                                burst_every=5, burst_size=4),
                       vocab_size=61, seed=0)
    assert sorted({r.arrival_tick for r in bursty}) == [0, 5]


def test_slo_report_counts_misses():
    reqs = [Request(0, np.ones(3, np.int32), 2, slo_ttft_ms=100.0),
            Request(1, np.ones(3, np.int32), 2, slo_e2e_ms=1000.0),
            Request(2, np.ones(3, np.int32), 2)]
    rep = slo_report(reqs, {0: 0.05, 1: 5.0}, {0: 0.2, 1: 0.5})
    assert rep["slo_checked"] == 2          # request 2 has no SLO
    assert rep["slo_attained"] == 2         # 1's TTFT is unconstrained
    rep = slo_report(reqs, {0: 0.25}, {0: 0.3, 1: 2.0})
    assert rep["slo_ttft_misses"] == 1      # 0 blew 100ms
    assert rep["slo_e2e_misses"] == 1       # 1 blew 1s
    assert rep["slo_attainment"] == 0.0
    # a request with an SLO but NO measurement is a miss, not a skip
    rep = slo_report(reqs, {}, {})
    assert rep["slo_checked"] == 2 and rep["slo_attained"] == 0
    assert slo_report([reqs[2]], {}, {})["slo_attainment"] is None


# --- CLI validation (satellite: parse-time, clear SystemExit) ----------


@pytest.mark.parametrize("argv,msg", [
    (["--max-slots", "0"], "--max-slots"),
    (["--max-slots", "-2"], "--max-slots"),
    (["--prefill-buckets", "8,8"], "duplicate"),
    (["--prefill-buckets", "16,8"], "ascending"),
    (["--draft", "1"], "--draft requires --paged"),
    (["--paged", "--draft", "-1"], "--draft"),
    (["--paged", "--slo-ttft-ms", "0"], "--slo-ttft-ms"),
])
def test_cli_rejects_bad_serving_flags(argv, msg):
    base = ["-l", "1", "-s", "32", "-e", "1", "-b", "16"]
    with pytest.raises(SystemExit, match=msg.replace("-", r"\-")):
        parse_args(base + argv, workload="gpt")


def test_cli_accepts_paged_flags():
    cfg = parse_args(["-l", "2", "-s", "32", "-e", "1", "-b", "16",
                      "--paged", "--kv-block-size", "8",
                      "--prefill-chunk", "16", "--draft", "1",
                      "--spec-k", "3", "--slo-ttft-ms", "500"],
                     workload="gpt")
    assert cfg.paged and cfg.kv_block_size == 8
    assert cfg.prefill_chunk == 16 and cfg.draft == 1 and cfg.spec_k == 3
    assert cfg.slo_ttft_ms == 500.0 and cfg.slo_e2e_ms is None


# --- the paged cache's capacity rule -----------------------------------


@pytest.mark.parametrize("args,want", [
    ((100, 16, False, 4), 96),      # whole blocks; spec_k idle without a draft
    ((100, 16, True, 4), 91),       # spec_k + 1 positions of verify headroom
    ((20, 16, True, 4), None),      # 16 - 5 leaves less than one block
], ids=["plain", "draft-headroom", "too-small"])
def test_paged_max_len(args, want):
    if want is None:
        with pytest.raises(ValueError, match="too small for block size 16"):
            paged.paged_max_len(*args)
    else:
        assert paged.paged_max_len(*args) == want
