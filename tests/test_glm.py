"""Latent attention and bias-corrected sigmoid routing, against their plain
reference.

The package's ``CausalLM`` built from a model description under
GLM-4.7-Flash's own key names (``glm4_moe_lite``: queries through a normed
low-rank bottleneck, keys and values through one normed latent plus a
rotated key shared by every head, a leading dense layer, then routed experts
chosen by sigmoid score + bias and weighted by the score alone, with a
shared expert) is compared on seeded random weights, in float32 on the CPU,
with ``benchmark/reference/glm4_moe_lite.py``, which imports nothing of the
package and computes attention EXPANDED: the full forward, chunked prefill
(expanded, keys walked in blocks) and decode (ABSORBED, through the paged
latent cache), one layer both ways, the decode kernel interpreted, the
router, the description, a train step, and what else runs the engine.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm4_moe_lite as ref
from benchmark.weights import glm4_moe_lite as weights
from distributed_deep_learning_tpu import obs
from distributed_deep_learning_tpu.models import describe
from distributed_deep_learning_tpu.models.moe import (ExpertSpec,
                                                      RoutedExperts,
                                                      route_top_k)
from distributed_deep_learning_tpu.models.transformer import (
    LatentAttention, LatentSpec, RopeSpec, generate,
    latent_expanded_attention)
from distributed_deep_learning_tpu.ops import paged_decode_pallas as pdp
from distributed_deep_learning_tpu.serve.engine import PagedEngine
from distributed_deep_learning_tpu.serve.scheduler import Request

VOCAB = 97


def tiny(**over) -> dict:
    """GLM-4.7-Flash's published keys (every one of them) at a width a CPU
    test affords: 4 heads of 6 + 4 / 8, ranks 12 and 16, 8 experts, 2 a
    token."""
    cfg = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 32,
        "intermediate_size": 64, "max_position_embeddings": 256,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 16,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 4, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
        "first_k_dense_replace": 1, "num_hidden_layers": 3,
        "num_key_value_heads": 4, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 12,
        "kv_lora_rank": 16, "qk_nope_head_dim": 6, "qk_rope_head_dim": 4,
        "v_head_dim": 8, "vocab_size": VOCAB,
    }
    cfg.update(over)
    return cfg


def build(cfg, seed=0, max_len=128, spread=8.0):
    """(model, program params, reference weights), float32.  The router
    is widened by `spread` over the benchmark's init, whose 0.02 is sized
    for a 2,048-wide input: at 32 wide the scores would all sit at a half
    and every choice would be the bias's."""
    model = describe.causal_lm(cfg, max_len=max_len, with_logits=True)
    flat = weights.make_weights(jax.random.key(seed), cfg, jnp.float32)
    for name in flat:
        if name.endswith(".router"):
            flat[name] = flat[name] * spread
    return model, weights.to_program_tree(flat, cfg), flat


def tokens(seed, *shape):
    return np.random.default_rng(seed).integers(1, VOCAB, size=shape)


@pytest.fixture(autouse=True)
def float32_matmuls():
    with ref.highest():
        yield


# ------------------------------------------------------- (a) the full forward

def test_full_forward_matches_reference():
    """Tolerance: float32 both sides, the same products in another order
    (the package projects all heads at once, the reference a head at a
    time): 2e-5 on logits of size ~0.5, as the laguna test has it."""
    cfg = tiny()
    model, params, flat = build(cfg)
    toks = tokens(1, 2, 40)
    got = model.apply({"params": params}, jnp.asarray(toks))
    want = ref.logits(flat, jnp.asarray(toks))
    assert got.shape == want.shape == (2, 40, VOCAB)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    # the description's tree is what the weights module pours into
    init = jax.eval_shape(model.init, jax.random.key(0), jnp.asarray(toks))
    assert jax.tree.map(lambda x: x.shape, init["params"]) == \
        jax.tree.map(lambda x: x.shape, params)
    # and the bias did change choices: without it the logits move
    nobias = {n: (jnp.zeros_like(a) if n.endswith("rbias") else a)
              for n, a in flat.items()}
    assert float(jnp.max(jnp.abs(
        ref.logits(ref.Weights(nobias, flat.hp), jnp.asarray(toks))
        - want))) > 1e-3


def test_reference_blocks_agree_with_itself(monkeypatch):
    """The reference's query blocks and head blocks (what lets a 21,000
    token row fit) change nothing: blocks of 16 and 8 against one block."""
    cfg = tiny()
    _, _, flat = build(cfg)
    toks = jnp.asarray(tokens(2, 1, 40))
    whole = ref.logits(flat, toks)
    gaps, first = ref.token_gaps(flat, toks)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 8)
    jax.clear_caches()
    np.testing.assert_allclose(ref.logits(flat, toks), whole, atol=1e-6)
    gaps2, first2 = ref.token_gaps(flat, toks)
    np.testing.assert_allclose(gaps2, gaps, atol=1e-6)
    np.testing.assert_array_equal(first2, first)
    lg = whole[0, :-1]
    np.testing.assert_allclose(
        gaps[0], jnp.max(lg, -1) - lg[jnp.arange(39), toks[0, 1:]],
        atol=1e-6)
    np.testing.assert_allclose(ref.gaps_of(flat, toks, first2[:, :1].repeat(
        40, 1))[0], jnp.max(whole[0], -1) - whole[0, :, first2[0, 0]],
        atol=1e-6)
    jax.clear_caches()


# ----------------------------- (b) chunked prefill and decode, paged and latent

def _serve(cfg, requests, **engine_kw):
    """Run `requests` [(prompt, new tokens)] through the paged engine;
    ({uid: tokens}, {uid: chosen-token log-probs}, engine, flat weights)."""
    model, params, flat = build(cfg)
    eng = PagedEngine(model, params, **engine_kw)
    logprob = {}

    def on_tick(report):
        for uid, _ in report.emitted:
            logprob.setdefault(uid, []).append(report.logprob[uid])

    out = eng.run([Request(uid=i, prompt=p, max_new_tokens=n)
                   for i, (p, n) in enumerate(requests)], on_tick=on_tick)
    assert not out["errors"]
    assert out["stats"]["chunk_compiles"] == 1
    assert out["stats"]["decode_compiles"] == 1
    return out, logprob, eng, flat


REQUESTS = [(40, 20), (7, 30), (61, 12), (23, 25), (50, 5), (16, 9)]


def _check_against_reference(reqs, served, logprob, flat, atol=5e-5):
    for uid, (prompt, new) in enumerate(reqs):
        got = np.asarray(served[uid])
        assert len(got) == new
        row = jnp.asarray(np.concatenate([prompt, got]))[None]
        lg = ref.logits(flat, row)[0, len(prompt) - 1:-1]
        lp = jax.nn.log_softmax(lg, axis=-1)
        np.testing.assert_array_equal(got, np.argmax(lg, axis=-1))
        np.testing.assert_allclose(logprob[uid],
                                   lp[np.arange(new), got], atol=atol)


def test_chunked_prefill_and_decode_match_reference_through_the_paged_latent_cache():
    """Prompts of 7 to 61 tokens against chunks of 8 and blocks of 4 (one
    prompt ends on a chunk border, one inside a block, one is shorter than
    a chunk), 5 to 30 new tokens: the chunk program attends EXPANDED over
    the gathered latent rows, the decode program ABSORBED through the block
    table.  Each served token's log-prob is the reference's full-forward
    log-prob of that token in that context, and its own greedy choice.
    Tolerance 5e-5 on log-probs: float32, two orders of products."""
    cfg = tiny()
    reqs = [(tokens(10 + i, n), k) for i, (n, k) in enumerate(REQUESTS)]
    out, logprob, eng, flat = _serve(
        cfg, reqs, max_slots=3, max_len=96, kv_block_size=4,
        prefill_chunk=8)
    _check_against_reference(reqs, out["results"], logprob, flat)
    # one leaf a layer, the 20-wide row padded to a whole lane tile, under
    # the one rule of build_pools; a latent-only model is one kind: no
    # ring, an index
    shapes = jax.tree.map(lambda x: x.shape, eng.pools)
    assert shapes["layer_1"]["self_attn"] == {
        "cache_index": (), "latent_kv": (2 * 3 * 24 + 1, 4, 128),
        "latent_valid": (2 * 3 * 24 + 1, 4)}
    assert eng.ring_blocks is None
    assert eng.decode_attn_paths == {"block_table": 0, "gather": 0,
                                     "latent": 3}
    assert out["stats"]["paged"]["decode_attn"]["latent_row_bytes"] == 512
    ticks = [t[2][2] for t in obs.last_run("serve").phases.ticks
             if t[1] == "decode" and t[2][0]]
    assert ticks and all(c["latent"]["row_bytes"] == 128 * 4
                         and c["latent"]["rows"] % 3 == 0
                         and c["latent"]["rows"] > 0 for c in ticks)
    # blocks read cover the rows read, block by block
    assert all(c["attn_blocks"]["read"] * 4 >= c["latent"]["rows"]
               for c in ticks)
    assert all("experts" in c and c["experts"]["held"] == 8 for c in ticks)
    # and the report prints the latent layers beside the others
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "scripts", "obs_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    text = report.render([{"event": "obs_serve", "stats": out["stats"]}])
    assert ("decode attention: 0 layers through the block table, 3 latent "
            "layers through it (a row read 512 bytes), 0 gathered") in text


def test_engine_decodes_through_the_interpreted_latent_kernel(monkeypatch):
    """The whole path the chip runs, with the kernel interpreted: the
    decode program hands each latent layer its pool leaf and the block
    tables, the slots' ``vmap`` folds into the kernel's slot axis, and the
    served tokens still are the reference's."""
    cfg = tiny()
    reqs = [(tokens(10 + i, n), k) for i, (n, k) in enumerate(REQUESTS[:4])]
    calls = []
    kernel = pdp.paged_latent_decode

    def interpreted(*args, **kw):
        calls.append(args[0].shape)
        return kernel(*args, **{**kw, "interpret": True})

    monkeypatch.setattr(pdp, "paged_latent_decode", interpreted)
    out, logprob, eng, flat = _serve(
        cfg, reqs, max_slots=3, max_len=96, kv_block_size=4,
        prefill_chunk=8)
    assert [c for c in calls if c[0] != 1] == [(3, 4, 128)] * 3
    _check_against_reference(reqs, out["results"], logprob, flat)


def test_a_repeated_prompt_hits_the_prefix_index_of_latent_blocks():
    cfg = tiny()
    prompt = tokens(3, 30)
    model, params, flat = build(cfg)
    eng = PagedEngine(model, params, max_slots=2, max_len=64,
                      kv_block_size=4, prefill_chunk=8)
    first = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=10)])
    again = eng.run([Request(uid=1, prompt=prompt, max_new_tokens=10)])
    np.testing.assert_array_equal(first["results"][0], again["results"][1])
    assert again["stats"]["paged"]["shared_tokens"] >= 24
    want = generate(model, params, jnp.asarray(prompt)[None],
                    max_new_tokens=10)[0]
    np.testing.assert_array_equal(first["results"][0], want)


# --------------------------------------- (c) absorbed against expanded, a layer

SPEC = LatentSpec(kv_rank=16, nope_dim=6, rope_dim=4, v_dim=8, q_rank=12)


def test_absorbed_matches_expanded_on_one_layer():
    """One LatentAttention layer: the whole sequence with no cache
    (expanded, the pluggable attention), a cached call of 13 then 11 tokens
    (expanded, keys walked in blocks over the cache), and 24 cached calls
    of one token (absorbed) give the same outputs.  Tolerance 2e-6:
    float32, W_UK and W_UV applied on the other side of the softmax."""
    layer = LatentAttention(4, SPEC, rope=RopeSpec(theta=1e6, rotary_dim=4),
                            ln_eps=1e-5)
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    params = layer.init(jax.random.key(1), x)["params"]
    whole = layer.apply({"params": params}, x)

    cached = layer.clone(decode=True)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(cached.init, jax.random.key(0),
                       jnp.zeros((2, 40, 32)))["cache"])
    assert set(cache) == {"latent_kv", "latent_valid", "cache_index"}
    assert cache["latent_kv"].shape == (2, 40, 128)     # 20 and its padding
    assert SPEC.row == 20 and SPEC.row_at_rest == 128

    def run(cache, pieces):
        outs = []
        for piece in pieces:
            y, upd = cached.apply({"params": params, "cache": cache}, piece,
                                  mutable=["cache"])
            cache = upd["cache"]
            outs.append(y)
        return jnp.concatenate(outs, axis=1), cache

    chunks, c1 = run(cache, [x[:, :13], x[:, 13:]])
    steps, c2 = run(cache, [x[:, t:t + 1] for t in range(24)])
    np.testing.assert_allclose(chunks, whole, atol=2e-6)
    np.testing.assert_allclose(steps, whole, atol=2e-6)
    # both wrote the same rows: one cache for two paths
    np.testing.assert_allclose(c1["latent_kv"], c2["latent_kv"], atol=1e-6)
    assert not np.asarray(c1["latent_kv"][..., 20:]).any()
    assert int(c1["cache_index"]) == int(c2["cache_index"]) == 24


def test_expanded_attention_walks_the_keys_in_blocks():
    """Blocks of 24 over a cache of 64 (the last block starts early to stay
    inside it and masks what it shares with the one before), queries at
    positions 30-45, an invalid position among the keys: the same as one
    block over everything; and nothing past the last query is visited
    (rows of the third block hold NaN)."""
    key = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(key[0], (16, 4, 10))
    rows = jax.random.normal(key[1], (64, 20)).at[48:].set(jnp.nan)
    kv_up = jax.random.normal(key[2], (16, 4, 14))
    valid = jnp.ones((64,), bool).at[7].set(False)
    q_pos = 30 + jnp.arange(16)
    args = (q, rows, valid, kv_up, q_pos, SPEC, jnp.float32)
    got = latent_expanded_attention(*args, block=24)
    want = latent_expanded_attention(
        q, rows.at[48:].set(0.0), valid, kv_up, q_pos, SPEC, jnp.float32,
        block=64)
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got, want, atol=2e-6)
    # by hand, one query: position 33 sees keys 0..33 but 7
    c, kr = rows[:34, :16], rows[:34, 16:]
    kvx = jnp.einsum("kc,chd->khd", c, kv_up)
    s = (jnp.einsum("hd,khd->hk", q[3, :, :6], kvx[..., :6])
         + jnp.einsum("hr,kr->hk", q[3, :, 6:], kr)) / np.sqrt(10)
    p = jax.nn.softmax(jnp.where(valid[:34][None], s, -1e30), axis=-1)
    np.testing.assert_allclose(
        got[3], jnp.einsum("hk,khd->hd", p, kvx[..., 6:]), atol=2e-6)


# -------------------------------------------------- (d) the kernel, interpreted

def _latent_case(seed, B=3, H=4, W=20, bs=4, Bps=12, N=40, lens=(0, 17, 44),
                 dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, W)), dtype)
    pool = jnp.asarray(rng.normal(size=(N, bs, W)), dtype)
    new = jnp.asarray(rng.normal(size=(B, W)), dtype)
    tables = jnp.asarray(np.stack([rng.permutation(np.arange(1, N))[:Bps]
                                   for _ in range(B)]), jnp.int32)
    valid = jnp.asarray(rng.random((N, bs)) > 0.1)
    return q, pool, tables, jnp.asarray(lens, jnp.int32), new, valid


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("blocks_per_step", [1, 4, None])
def test_latent_kernel_matches_its_reference(dtype, atol, blocks_per_step):
    """The real kernel, interpreted, on latent shapes (one 20-wide row a
    position, values its first 16 columns, scale 1/sqrt(10)), slots of 0,
    17 and 44 cached positions, some invalid, the new row beside them.
    bfloat16: probabilities round to 8 bits before P @ V on both sides, in
    another order."""
    q, pool, tables, lens, new, valid = _latent_case(0, dtype=dtype)
    kw = dict(v_width=16, sm_scale=10 ** -0.5, valid_pool=valid,
              new_valid=jnp.asarray([True, True, False]))
    want = pdp.paged_latent_reference(q, pool, tables, lens, new, **kw)
    got = pdp.paged_latent_decode(q, pool, tables, lens, new, **kw,
                                  blocks_per_step=blocks_per_step,
                                  interpret=True)
    assert got.shape == (3, 4, 16) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    # off a TPU the dispatcher IS the reference
    np.testing.assert_array_equal(
        pdp.paged_latent_decode(q, pool, tables, lens, new, **kw), want)


def test_latent_reference_is_the_per_head_reference_on_latent_shapes():
    """`paged_decode_reference` (per-head K and V: one KV head as wide as
    the row under every query head) handed the latent leaf as K and, its
    rope columns zeroed, as V, gives the latent reference's values in its
    first `kv_rank` columns, once its 1/sqrt(W) is traded for the
    layer's scale."""
    q, pool, tables, lens, new, valid = _latent_case(1)
    W, R = 20, 16
    zero_rope = jnp.concatenate([jnp.ones((R,)), jnp.zeros((W - R,))])
    want = pdp.paged_decode_reference(
        q * np.sqrt(W / 10.0), pool, pool * zero_rope, tables, lens,
        k_new=new[:, None], v_new=(new * zero_rope)[:, None],
        valid_pool=valid)
    got = pdp.paged_latent_reference(q, pool, tables, lens, new, v_width=R,
                                     sm_scale=10 ** -0.5, valid_pool=valid)
    np.testing.assert_allclose(got, want[..., :R], atol=2e-6)


def test_latent_kernel_reads_a_row_once():
    """What the engine's ``counters["latent"]["row_bytes"]`` reports is
    counted off the traced call: the ``pallas_call`` takes the pool as ONE
    operand a tile of a step, so a row costs its own bytes; a call that
    hands the pool in again for the values reads twice that."""
    from jax.experimental import pallas as pl

    q, pool, tables, lens, new, valid = _latent_case(2)
    assert pdp.latent_bytes_a_row(pool, 3, 12) == 20 * 4
    jaxpr = jax.make_jaxpr(lambda *a: pdp.paged_latent_decode(
        *a, v_width=16, sm_scale=1.0, blocks_per_step=4, interpret=True))(
            q, pool, tables, lens, new)
    assert pdp._pool_bytes_a_position(jaxpr.jaxpr, pool) == 20 * 4

    def keys_and_values_apart(pool):
        n, seen = 4, jnp.ones((3, 3, 1, 4 * 4))     # (B, steps, 1, n * bs)
        return pl.pallas_call(
            lambda *refs: None, interpret=True,
            out_shape=jax.ShapeDtypeStruct((3, 8, 16), pool.dtype))(
                seen, *([pool] * n), *([pool] * n))

    apart = jax.make_jaxpr(keys_and_values_apart)(pool)
    assert pdp._pool_bytes_a_position(apart.jaxpr, pool) == 2 * 20 * 4


# ------------------------------------------------------------- (e) the router

def test_a_bias_that_flips_a_choice_changes_which_experts_run_not_their_weights():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0], [0.1, 0.2, 0.3, 0.4]])
    s = jax.nn.sigmoid(logits)
    w0, e0 = route_top_k(logits, 2, True, 1.8, "sigmoid", jnp.zeros(4))
    np.testing.assert_array_equal(e0, [[0, 1], [3, 2]])
    np.testing.assert_allclose(
        w0[0], 1.8 * s[0, :2] / jnp.sum(s[0, :2]), rtol=1e-6)
    # expert 3 is lifted over experts 1 and 2 for the CHOICE of token 0
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.6])
    w1, e1 = route_top_k(logits, 2, True, 1.8, "sigmoid", bias)
    np.testing.assert_array_equal(e1, [[0, 3], [3, 2]])
    picked = s[0, jnp.asarray([0, 3])]
    np.testing.assert_allclose(w1[0], 1.8 * picked / jnp.sum(picked),
                               rtol=1e-6)      # no 0.6 in the weights
    np.testing.assert_allclose(w1[1], w0[1], rtol=1e-6)   # same choice: same
    with pytest.raises(ValueError, match="softmax.*sigmoid"):
        route_top_k(logits, 2, score="tanh")


def test_softmax_routing_is_what_it_was_bit_for_bit():
    """Laguna's router: no score named, no bias; the values the parent's
    three lines gave."""
    logits = jax.random.normal(jax.random.key(0), (50, 16)) * 2.0
    w, e = route_top_k(logits, 3, True, 2.5)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w_old, e_old = jax.lax.top_k(probs, 3)
    w_old = w_old / jnp.sum(w_old, axis=-1, keepdims=True) * 2.5
    np.testing.assert_array_equal(e, e_old)
    np.testing.assert_array_equal(w, w_old)
    # and a layer without a choice bias has no such parameter
    layer = RoutedExperts(ExpertSpec(4, 8, 2))
    params = layer.init(jax.random.key(0), jnp.zeros((1, 3, 16)))["params"]
    assert "router_bias" not in params


def test_the_layer_routes_by_its_bias_and_weighs_without_it():
    """An expert layer of the tiny model against the reference's, with a
    bias large enough to decide every choice: the two experts it names run
    for every token, weighted by their own sigmoid scores."""
    cfg = tiny()
    model, params, flat = build(cfg)
    u = jax.random.normal(jax.random.key(5), (1, 9, 32))
    spec = model.layers[1].experts
    assert spec.score == "sigmoid" and spec.choice_bias \
        and spec.shared_dim == 16 and spec.routed_scale == 1.8
    layer = RoutedExperts(spec)
    p = dict(params["layer_1"]["moe"])
    got = layer.apply({"params": p}, u)
    w = ref._f32(ref.layer_weights(flat, 1))
    want, ids = ref.expert_ffn(u[0], w, flat.hp)
    np.testing.assert_allclose(got[0], want, atol=2e-6)
    p["router_bias"] = jnp.zeros(8).at[jnp.asarray([2, 5])].set(10.0)
    forced = layer.apply({"params": p}, u)
    w["rbias"] = p["router_bias"]
    want, ids = ref.expert_ffn(u[0], w, flat.hp)
    np.testing.assert_array_equal(jnp.sort(ids, -1),
                                  jnp.broadcast_to(jnp.asarray([2, 5]),
                                                   (9, 2)))
    np.testing.assert_allclose(forced[0], want, atol=2e-6)


def test_the_benchmarks_bias_changes_some_choices_not_all():
    """`weights/glm4_moe_lite.py`'s init at the published router width: the
    correction bias moves the choice of a good share of tokens, and leaves
    a good share alone."""
    key = jax.random.key(7)
    u = jax.random.normal(key, (512, 2048))
    router = weights.STD * jax.random.normal(jax.random.fold_in(key, 1),
                                             (2048, 64))
    bias = weights.STD * jax.random.normal(jax.random.fold_in(key, 2), (64,))
    logits = u @ router
    _, with_b = route_top_k(logits, 4, score="sigmoid", bias=bias)
    _, without = route_top_k(logits, 4, score="sigmoid")
    moved = float(jnp.mean(jnp.any(jnp.sort(with_b, -1)
                                   != jnp.sort(without, -1), -1)))
    assert 0.1 < moved < 0.9, moved


# -------------------------------------------------------- (f) the description

PUBLISHED = {   # the catalog's `config` of GLM-4.7-Flash, as it stands
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "vocab_size": 154880,
}


def test_the_published_config_is_a_description_as_it_stands():
    specs = describe.layer_specs(PUBLISHED)
    assert len(specs) == 47
    assert specs[0].mlp == "swiglu" and specs[0].mlp_dim == 10240
    assert all(sp.mlp == "experts" for sp in specs[1:])
    assert all(sp.latent == LatentSpec(kv_rank=512, nope_dim=192,
                                       rope_dim=64, v_dim=256, q_rank=768)
               and sp.num_heads == 20 and sp.window is None
               and sp.rope == RopeSpec(theta=1e6, rotary_dim=64)
               and sp.norm == "rms" and not sp.use_bias for sp in specs)
    assert specs[1].experts == ExpertSpec(
        num_experts=64, mlp_dim=1536, top_k=4, routed_scale=1.8,
        norm_topk=True, shared_dim=1536, score="sigmoid", choice_bias=True)
    assert specs[0].latent.row == 576 and specs[0].latent.row_at_rest == 640
    assert specs[0].latent.scale == pytest.approx(1 / 16)
    model = describe.causal_lm(PUBLISHED, max_len=24576)
    assert not model.tie_head and model.pad_id is None \
        and model.ln_eps == 1e-5 and model.vocab_size == 154880
    # the benchmark's configuration: the same keys, depth and MTP cut
    from benchmark import harness

    cfg = harness.load_json(harness.ROOT, "benchmark", "configs",
                            "glm-4.7-flash-d7.json")
    assert set(cfg["reduced"]) == {"num_hidden_layers",
                                   "num_nextn_predict_layers"}
    assert {k: v for k, v in cfg.items() if k in PUBLISHED
            and k not in cfg["reduced"]} == {
        k: v for k, v in PUBLISHED.items() if k not in cfg["reduced"]}
    assert describe.layer_specs(cfg) == specs[:7]
    shapes = weights.leaf_shapes(cfg)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert n == cfg["parameters"] and 8.4 < 2 * n / 2 ** 30 < 8.5


@pytest.mark.parametrize("change, match", [
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"n_group": 8}, "n_group"),
    ({"topk_group": 4}, "topk_group"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"gating": "per-head"}, "gate on latent attention"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"layer_types": ["full_attention", "sliding_attention",
                      "full_attention"], "sliding_window": 8},
     "sliding_attention"),
])
def test_a_description_refuses_each_switch_it_cannot_compute(change, match):
    with pytest.raises(ValueError, match=match):
        describe.layer_specs(tiny(**change))


def test_a_latent_model_with_a_softmax_router_and_no_choice_bias():
    """No ``topk_method`` under the same keys: the softmax router the
    package had, no correction bias: described, built, run."""
    cfg = tiny()
    del cfg["topk_method"]
    model = describe.causal_lm(cfg, max_len=64, with_logits=True)
    assert model.layers[1].experts.score == "softmax"
    toks = jnp.asarray(tokens(4, 1, 12))
    params = model.init(jax.random.key(0), toks)["params"]
    assert "router_bias" not in params["layer_1"]["moe"]
    assert model.apply({"params": params}, toks).shape == (1, 12, VOCAB)


# ------------------------------------------------------------ (g) a train step

def test_one_cli_train_step_matches_the_references_loss_and_gradient(
        tmp_path):
    """``gpt --model-file`` builds the model, the loss and the train step
    as the CLI does; the first step's loss and the gradient it is taken
    from are the reference's (``jax.grad`` through the plain forward).
    Tolerances: float32; loss 1e-5 of ~4.6, gradient 2e-4 of its norm (the
    routed weights' gradient passes through two top-k gathers that the
    reference writes as a scatter)."""
    from distributed_deep_learning_tpu.data.tokens import TokenArrayDataset
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.utils.config import parse_args
    from distributed_deep_learning_tpu.workloads import base as wb, get_spec

    cfg = tiny()
    path = tmp_path / "tiny-glm.json"
    path.write_text(json.dumps(cfg))
    config = parse_args(["--model-file", str(path), "-b", "4", "-m",
                         "sequential", "-e", "1"], workload="gpt")
    spec = get_spec("gpt")
    rows = tokens(8, 4, 25)
    rows[0, 0] = VOCAB - 1         # the vocabulary is the largest id + 1
    ds = TokenArrayDataset(rows[:, :-1], rows[:, 1:], VOCAB)
    model = spec.build_model(config, ds)
    assert model.layers[2].latent is not None
    flat = weights.make_weights(jax.random.key(3), cfg, jnp.float32)
    params = weights.to_program_tree(flat, cfg)
    state = create_train_state(model, jax.random.key(0),
                               spec.example_input(config, ds),
                               wb.build_optimizer(spec, config, 1))
    assert jax.tree.map(lambda x: x.shape, state.params) == \
        jax.tree.map(lambda x: x.shape, params)
    state = state.replace(params=params)
    mesh = build_mesh({"data": 1}, jax.devices()[:1])
    sspec = wb.derive_state_spec(spec, config, mesh, state)
    loss_fn = spec.build_loss(config)
    train_step, _ = wb.make_train_eval_steps(config, mesh, loss_fn, sspec)
    x, y = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])

    def ref_loss(w):
        lg = ref.logits(ref.Weights(w, flat.hp), x)
        lp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.mean(jnp.take_along_axis(lp, y[..., None], -1))

    want, want_grad = jax.value_and_grad(ref_loss)(dict(flat))
    got_grad = jax.grad(lambda p: loss_fn(
        model.apply({"params": p}, x), y))(params)
    want_tree = weights.to_program_tree(want_grad, cfg)
    norm = float(jnp.sqrt(sum(jnp.sum(g * g)
                              for g in jax.tree.leaves(want_tree))))
    diff = float(jnp.sqrt(sum(
        jnp.sum((a - b) ** 2) for a, b in zip(
            jax.tree.leaves(got_grad), jax.tree.leaves(want_tree)))))
    assert norm > 0.1 and diff < 2e-4 * norm, (diff, norm)
    before = jax.tree.map(np.asarray, params)    # the step donates its state
    new_state, metrics = train_step(state, x, y)
    assert float(metrics["loss"]) == pytest.approx(float(want), abs=1e-5)
    moved = jax.tree.map(lambda a, b: float(np.max(np.abs(a - b))),
                         new_state.params, before)
    assert moved["layer_1"]["self_attn"]["kv_b"] > 0
    assert moved["layer_1"]["moe"]["router"] > 0


# ------------------------------------- who else runs the engine on this layout

def _engine(model, params, **kw):
    return PagedEngine(model, params, **{
        "max_slots": 2, "max_len": 64, "kv_block_size": 4,
        "prefill_chunk": 8, **kw})


def test_speculation_keeps_greedy_parity_on_latent_pools():
    """The draft's pools, the verify program (several queries a slot:
    expanded over the gathered rows) and the draft's one-token steps
    (absorbed over a model-layout cache) go through the one rule."""
    model, params, _ = build(tiny())
    prompt = tokens(6, 21)
    eng = _engine(model, params, draft_layers=1, spec_k=2)
    out = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=12)])
    want = generate(model, params, jnp.asarray(prompt)[None],
                    max_new_tokens=12)[0]
    np.testing.assert_array_equal(out["results"][0], want)
    assert out["stats"]["spec"]["rounds"] > 0


def test_spill_and_resume_move_latent_slots_whole():
    model, params, _ = build(tiny())
    prompts = [tokens(40 + i, 20) for i in range(3)]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=16,
                    priority=0 if i == 2 else 2, arrival_tick=4 * (i == 2))
            for i, p in enumerate(prompts)]
    eng = _engine(model, params, preempt=True, num_blocks=24)
    out = eng.run(reqs)
    assert not out["errors"]
    for i, p in enumerate(prompts):
        want = generate(model, params, jnp.asarray(p)[None],
                        max_new_tokens=16)[0]
        np.testing.assert_array_equal(out["results"][i], want)
    assert out["stats"]["preempt"]["preemptions"] >= 1
    assert out["stats"]["preempt"]["resumes"] >= 1


def test_a_bf16_cache_serves_and_int8_pools_refuse_the_latent_layout():
    model, params, flat = build(tiny())
    prompt = tokens(9, 30)
    eng = _engine(model, params, kv_dtype="bf16")
    assert eng.pools["layer_0"]["self_attn"]["latent_kv"].dtype == \
        jnp.bfloat16
    assert eng.latent_row_bytes == 128 * 2
    out = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=10)])
    row = jnp.asarray(np.concatenate([prompt, out["results"][0]]))[None]
    gaps, _ = ref.token_gaps(flat, row)
    assert float(jnp.max(gaps[0, len(prompt) - 1:])) < 0.05
    with pytest.raises(ValueError, match="latent attention layers"):
        _engine(model, params, kv_dtype="int8")


def test_canary_and_disagg_and_migration_run_on_latent_pools():
    from distributed_deep_learning_tpu.serve.disagg import DisaggEngine
    from distributed_deep_learning_tpu.serve.migrate import (BlockMigrator,
                                                             clone_prefix)

    model, params, _ = build(tiny())
    prompt = tokens(50, 30)
    want = generate(model, params, jnp.asarray(prompt)[None],
                    max_new_tokens=6)[0]
    eng = _engine(model, params)
    eng.begin_canary(params, [0])
    out = eng.run([Request(uid=i, prompt=prompt, max_new_tokens=6)
                   for i in range(2)])
    assert eng.end_canary(promote=False)["acceptance"] == 1.0
    np.testing.assert_array_equal(out["results"][0], want)
    np.testing.assert_array_equal(out["results"][1], want)
    # a prefix cloned into another engine's latent pool is hit there
    dst = _engine(model, params)
    assert clone_prefix(eng, dst, prompt, BlockMigrator(4)) >= 24
    again = dst.run([Request(uid=0, prompt=prompt, max_new_tokens=6)])
    np.testing.assert_array_equal(again["results"][0], want)
    assert again["stats"]["paged"]["shared_tokens"] >= 24
    dis = DisaggEngine(model, params, max_slots=2, max_len=64,
                       kv_block_size=4, prefill_chunk=8)
    got = dis.run([Request(uid=0, prompt=prompt, max_new_tokens=6)])
    np.testing.assert_array_equal(got["results"][0], want)


# ------------------------------------------- the benchmark's side of the cell

def _tiny_bench(root):
    """A one-cell BENCHMARK.json under `root`: the tiny model above as a
    serving cell, with the per-layer metrics the real cell lists."""
    import os

    from benchmark import harness

    real = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(root, d))
    cfg = dict(tiny(), name="tiny-glm", architecture="glm4_moe_lite",
               reference="glm4_moe_lite", n_positions=96,
               serve_param_dtype="float32",
               cli=["gpt", "--model-file",
                    os.path.join(root, "configs", "tiny-glm.json")])
    mix = {"name": "tiny-long", "kind": "serve", "prompt_len": [20, 60],
           "output_len": [5, 20], "table_len": 4, "pairing": [2, 0, 3, 1],
           "issue_order": [1, 3, 0, 2], "queue_cycles": 400,
           "engine": {"max_slots": 3, "max_len": 96, "kv_block_size": 4,
                      "prefill_chunk": 8, "prefill_chunks_per_tick": 1,
                      "temperature": 0.0, "num_blocks": 80},
           "check_requests": 4, "trace_seconds": 1}
    for path, what in (("configs/tiny-glm.json", cfg),
                       ("traffic/tiny-long.json", mix),
                       ("limits/tiny-serve.json",
                        {"served_gap_widest": 1e-3,
                         "served_gap_mean": 1e-4})):
        with open(os.path.join(root, path), "w") as f:
            json.dump(what, f)
    cell = ["tiny-serve"]
    bench = {
        "paths": ["."],
        "configs": [{"name": "tiny-glm", "file": "configs/tiny-glm.json"}],
        "workloads": [{"name": "tiny-serve", "config": "tiny-glm",
                       "traffic": "tiny-long", "chips": 1}],
        "end_to_end": [m for m in real["end_to_end"] if m["name"] in (
            "serve_total_tokens_per_s", "setup_s")],
        "per_layer": [dict(m, workloads=cell) for m in real["per_layer"]
                      if "glm-serve-long-context" in m.get("workloads", ())]}
    bench["end_to_end"][0] = dict(bench["end_to_end"][0], workloads=cell)
    return harness.Cell("tiny-serve", root=str(root), bench=bench)


def test_the_benchmark_runs_the_cell_and_reads_its_counters(tmp_path):
    """The unedited serving runner builds the described model through the
    CLI's argument, serves the mix, and the reference agrees with every
    served token; then the new per-layer readers, on the record that run
    left and a hand-made trace."""
    from benchmark import cellrun, harness
    from benchmark.readers import (latent_roofline, latent_row_reads,
                                   sample_p95_ms, tick_counters)

    real = harness.Cell("glm-serve-long-context")
    assert [m["name"] for m in real.end_to_end] == [
        "serve_total_tokens_per_s", "setup_s"]
    listed = [m["name"] for m in real.per_layer if "workloads" in m]
    assert listed == ["serve_expert_touched_pct", "serve_expert_load_skew",
                      "latent_decode_roofline", "latent_attn_roofline",
                      "serve_latent_row_reads",
                      "serve_itl_p95_ms.long_context",
                      # PR 37: what the program records of its own runs
                      "grouped_product_roofline",
                      "serve_chunk_expert_touched_pct",
                      "serve_turnaround_ms", "serve_launch_notice_ms",
                      "serve_tick_longest_ms"]
    cell = _tiny_bench(tmp_path)
    assert [m["name"] for m in cell.per_layer] == listed
    out = cellrun.run_cell("tiny-serve", 2 ** 31 + 30, 1.5, False,
                           allow_cpu=True, cell=cell)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["serve_total_tokens_per_s"]["value"] > 0

    ticks = tick_counters.tick_counters()
    assert ticks and all("experts" in c and "latent" in c for c in ticks)
    # float32 rows at this size: 20 values x 4 bytes in a 128-lane tile,
    # read once (at the published widths 640 lanes for 576 values: 1.11)
    ctx = {"config": cell.config}
    assert latent_row_reads.read(ctx) == pytest.approx(128 / 20)
    assert latent_row_reads.read({"config": real.config}) == \
        pytest.approx(512 / 1152)
    # the stutter between a request's tokens, as a per-layer metric here:
    # the runner's own pick of the 95th percentile, in ms
    gaps = {"samples": {"itl_s": [0.001 * i for i in range(100, 0, -1)]}}
    assert sample_p95_ms.read(gaps, "itl_s") == pytest.approx(96.0)
    assert sample_p95_ms.read({"samples": {}}, "itl_s") is None
    rows = tick_counters.read({}, ["latent", "rows"])
    touched = tick_counters.read({}, ["experts", "touched"])
    assert rows > 0 and 0 < touched <= 8

    ms = 1_000_000
    events = [
        ["/host:CPU", "main", "bench:window", 0, 100 * ms],
        ["/host:CPU", "main", "bench:decode_dispatch", 3 * ms, ms],
        ["/device:TPU:0", "XLA Modules", "jit_paged_decode", 2 * ms,
         10 * ms],
        ["/device:TPU:0", "XLA Ops", "fusion.7", 6 * ms, 2 * ms],
    ] + [["/device:TPU:0", "XLA Ops",
          f"custom-call.{i} [tpu_custom_call] paged_latent_decode",
          (8 + i) * ms // 2, ms // 4] for i in range(7)]
    ctx = {"trace": {"events": events}, "config": real.config,
           "traffic": real.traffic,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "counters": {"mean_decoding_slots_share": 0.5}}
    need = harness.cost_function("glm_decode_tick")(
        real.config, 8.0, touched, rows / 7)
    # outside the routed experts: 21.76 M of attention a layer (and its two
    # latent norms), layer 0's MLP, 6 x (shared expert + router + bias), 15
    # block norms, the head's 154,880 rows; 9.44 M an expert; 1,152 B a
    # live row and layer
    assert need["outside_bytes"] == 2 * (
        7 * (21_757_952 + 768 + 512) + 62_914_560
        + 6 * (9_437_184 + 131_072 + 64) + 15 * 2048 + 154_880 * 2048)
    assert need["expert_bytes"] == pytest.approx(
        2 * 6 * touched * 3 * 2048 * 1536)
    assert need["kv_bytes"] == need["attn_bytes"] == \
        pytest.approx(1152 * rows)
    assert need["attn_flops"] == pytest.approx(2 * 20 * 1088 * rows)
    got = latent_roofline.read(ctx)
    assert got == pytest.approx(100.0 * need["bytes"] / 819e9 / 0.010)
    got = latent_roofline.read(ctx, kernel="paged_latent_decode")
    assert got == pytest.approx(
        100.0 * (1152 * rows / 7) / 819e9 / 0.00025)
    # no kernel events, or a program without the counters (the parent of
    # this PR): nothing to read, and nothing raised
    assert latent_roofline.read(ctx, kernel="no_such_kernel") is None
    obs.last_run("serve").phases.ticks.clear()
    assert latent_roofline.read(ctx) is None
    assert latent_row_reads.read({"config": real.config}) is None


def test_evicting_for_one_long_request_does_not_walk_the_index_a_block():
    """A request that needs 1,200 fresh blocks of a full pool whose 12,000
    blocks are all indexed evicts them by each entry's own parent: a walk
    over every parent's list for every block was 1.3 s of host time for one
    admission (the glm cell's pool, my chip runs, PR 30).  Counted, not
    timed: sibling lists looked at, one an evicted block."""
    from distributed_deep_learning_tpu.serve.paged import BlockManager

    class Counted(dict):
        """`children`, counting the sibling lists a caller is handed."""
        looked = 0

        def get(self, *a):
            self.looked += 1
            return super().get(*a)

        def __getitem__(self, k):
            self.looked += 1
            return super().__getitem__(k)

        def values(self):
            self.looked += len(self)
            return super().values()

        def items(self):
            self.looked += len(self)
            return super().items()

    m = BlockManager(12000, 16, 10, 1200)
    rng = np.random.default_rng(0)
    for s in range(10):
        toks = rng.integers(1, 1000, size=1200 * 16)
        m.admit(s, m.match_prefix(toks), 1200 * 16)
        m.register_committed(s, toks, 1200 * 16)
        m.release(s)
    assert len(m.index) == 12000 and not m.free
    toks = rng.integers(1, 1000, size=1200 * 16)
    sp = m.match_prefix(toks)
    assert m.can_admit(sp, 1200 * 16)
    m.index.children = Counted(m.index.children)
    m.admit(0, sp, 1200 * 16)
    assert m.evictions == 1200 and len(m.index) == 10800
    assert m.index.children.looked == 1200
    # no list of siblings still names an evicted block
    assert all(h in m.index.entries for sibs in m.index.children.values()
               for h in sibs)
