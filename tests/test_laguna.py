"""A decoder whose layers differ, against its plain reference.

The package's ``CausalLM`` built from a model description (a small
Laguna-S-2.1: full and sliding layers with their own head counts and RoPE,
a per-head gate, a leading dense layer, then routed experts with a shared
one, held here as a share of the router's experts) is compared on seeded
random weights, in float32 on the CPU, with ``benchmark/reference/laguna.py``,
which imports nothing of the package: the full forward, chunked prefill and
decode through the paged cache with rings that wrap, each layer kind alone,
YaRN's frequencies, the share test and dropless routing.  And GPT-2, the
one-kind case of the same code, is held to what it was.
"""

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna as ref
from benchmark.weights import laguna as weights
from distributed_deep_learning_tpu import obs
from distributed_deep_learning_tpu.models import describe
from distributed_deep_learning_tpu.models.moe import (ExpertSpec,
                                                      RoutedExperts)
from distributed_deep_learning_tpu.models.transformer import (CausalLM,
                                                              RopeSpec,
                                                              generate)
from distributed_deep_learning_tpu.serve import paged
from distributed_deep_learning_tpu.serve.engine import PagedEngine
from distributed_deep_learning_tpu.serve.scheduler import Request

YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 64, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
PLAIN = {"rope_type": "default", "rope_theta": 10000,
         "partial_rotary_factor": 1}
FULL, SLIDING = "full_attention", "sliding_attention"


def tiny(layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL, SLIDING),
         held=(4, 4), **over) -> dict:
    """Laguna-S-2.1's keys at a width a CPU test affords: 16 experts of
    which `held` = (first, count) live here, 3 a token, window 8."""
    n = len(layer_types)
    cfg = {
        "vocab_size": 97, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": n, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "attention_bias": False, "rms_norm_eps": 1e-6,
        "num_experts": held[1], "router_experts": 16,
        "expert_offset": held[0], "num_experts_per_tok": 3,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
        "norm_topk_prob": True, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 8,
        "rope_parameters": {FULL: YARN, SLIDING: PLAIN},
        "layer_types": list(layer_types),
        "mlp_layer_types": ["dense"] + ["sparse"] * (n - 1),
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [4 if t == FULL else 6
                                          for t in layer_types],
    }
    cfg.update(over)
    return cfg


def build(cfg, seed=0, max_len=128):
    """(model, program params, reference weights), float32."""
    model = describe.causal_lm(cfg, max_len=max_len, with_logits=True)
    flat = weights.make_weights(jax.random.key(seed), cfg, jnp.float32)
    return model, weights.to_program_tree(flat, cfg), flat


def tokens(seed, *shape):
    return np.random.default_rng(seed).integers(1, 97, size=shape)


@pytest.fixture(autouse=True)
def float32_matmuls():
    with ref.highest():
        yield


@pytest.mark.parametrize("layer_types", [
    (FULL, SLIDING, SLIDING, SLIDING, FULL, SLIDING),    # the model's mix
    (FULL, FULL, FULL),                                  # YaRN layers alone
    (SLIDING, SLIDING, SLIDING),                         # window layers alone
], ids=["mixed", "full-only", "sliding-only"])
def test_full_forward_matches_reference(layer_types):
    cfg = tiny(layer_types)
    model, params, flat = build(cfg)
    toks = tokens(1, 2, 40)
    got = model.apply({"params": params}, jnp.asarray(toks))
    want = ref.logits(flat, jnp.asarray(toks))
    assert got.shape == want.shape == (2, 40, 97)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    # the description's tree is what the weights module pours into
    init = jax.eval_shape(model.init, jax.random.key(0), jnp.asarray(toks))
    assert jax.tree.map(lambda x: x.shape, init["params"]) == \
        jax.tree.map(lambda x: x.shape, params)


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
    return out["results"], logprob, eng, flat


def test_chunked_prefill_and_decode_match_reference_past_the_ring():
    """Prompts of up to 61 tokens and 30 new ones against a window of 8,
    chunks of 8 and blocks of 4: a ring of 5 blocks = 20 positions, which
    every request but the shortest wraps, some three times over.  Each
    served token's log-prob, as the engine's chunk and decode programs
    computed it through the paged cache, is the reference's full-forward
    log-prob of that token in that context; and it is the reference's own
    greedy choice (a described model has no pad id: any id may be served)."""
    cfg = tiny()
    reqs = [(tokens(10 + i, n), k) for i, (n, k) in enumerate(
        [(40, 20), (7, 30), (61, 12), (23, 25), (50, 5)])]
    served, logprob, eng, flat = _serve(
        cfg, reqs, max_slots=3, max_len=96, kv_block_size=4,
        prefill_chunk=8)
    assert eng.ring_blocks == 5 and eng.manager.ring_tables.shape == (3, 5)
    for uid, (prompt, new) in enumerate(reqs):
        got = np.asarray(served[uid])
        assert len(got) == new
        row = jnp.asarray(np.concatenate([prompt, got]))[None]
        lg = ref.logits(flat, row)[0, len(prompt) - 1:-1]
        lp = jax.nn.log_softmax(lg, axis=-1)
        np.testing.assert_array_equal(got, np.argmax(lg, axis=-1))
        np.testing.assert_allclose(
            logprob[uid], lp[np.arange(new), got], atol=5e-5)


def test_pools_by_kind_and_tick_counters():
    """Full layers pool whole sequences, window layers a ring a slot; the
    prefix index stays empty; the tick ring carries the blocks by kind and
    the experts' counters, counted on the device."""
    cfg = tiny()
    reqs = [(tokens(20 + i, 30), 8) for i in range(4)]
    reqs.append((reqs[0][0].copy(), 8))         # a prompt seen before
    served, _, eng, _ = _serve(cfg, reqs, max_slots=2, max_len=64,
                               kv_block_size=4, prefill_chunk=8,
                               num_blocks=40)
    np.testing.assert_array_equal(served[0], served[4])
    shapes = jax.tree.map(lambda x: x.shape, eng.pools)
    assert shapes["layer_0"]["self_attn"]["cached_key"] == (41, 4, 16)
    assert shapes["layer_1"]["self_attn"]["ring_key"] == (2 * 5 + 1, 4, 16)
    assert "cached_key" not in shapes["layer_1"]["self_attn"]
    stats = eng.manager.stats()
    assert stats["indexed_blocks"] == 0 and stats["cow_copies"] == 0
    assert stats["ring_blocks_per_slot"] == 5
    ticks = [t for t in obs.last_run("serve").phases.ticks
             if t[1] == "decode"]
    both = [t[2][2] for t in ticks if t[2][0] == 2]
    assert both, "no tick decoded both slots"
    for c in both:
        # 38 positions a request = 10 blocks, of which the ring holds 5
        assert c["kv_blocks"] == {"full": 20, "window": 10,
                                  "window_released": 10}
        ex = c["experts"]
        # 2 tokens x 3 choices x 5 expert layers, a quarter of them held
        # on average; never more than all of them
        assert 0 <= ex["assignments"] <= 30 and ex["held"] == 4
        assert 0.0 <= ex["touched"] <= 4.0
        assert ex["skew"] == 0.0 or 1.0 <= ex["skew"] <= 4.0
    assert sum(c["experts"]["assignments"] for c in both) > 0


@pytest.mark.parametrize("held", [(4, 4), (0, 16)])
def test_a_chunks_experts_equal_a_numpy_recount(held, monkeypatch):
    """Two expert layers on the CPU: every chunk program's record carries
    `expert_counters` of ITS OWN load, counted on the device, equal to a
    recount in numpy of the router's choices over the chunk's rows,
    padding rows included (with every expert held a chunk of 8 rows makes
    8 x 3 assignments a layer however short the prompt)."""
    from distributed_deep_learning_tpu.models import moe

    seen, real = [], moe.route_top_k

    def spy(logits, *args, **kw):
        w, experts = real(logits, *args, **kw)
        jax.debug.callback(lambda e: seen.append(np.asarray(e)), experts,
                           ordered=True)
        return w, experts

    monkeypatch.setattr(moe, "route_top_k", spy)
    cfg = tiny(layer_types=(FULL, SLIDING, FULL), held=held)
    reqs = [(tokens(60 + i, n), 3) for i, n in enumerate((5, 19, 8, 30))]
    _serve(cfg, reqs, max_slots=2, max_len=64, kv_block_size=4,
           prefill_chunk=8, num_blocks=40)
    jax.effects_barrier()
    progs = [p for t in obs.last_run("serve").phases.ticks
             if len(t[2]) > 2 for p in t[2][2]["programs"]]
    chunks = [p for p in progs if p["program"] == "paged_chunk"]
    # a chunk program routes 8 rows a layer, a decode program its 2 slots
    by_chunk = [e for e in seen if e.shape == (8, 3)]
    assert len(chunks) == 1 + 3 + 1 + 4 and len(by_chunk) == 2 * len(chunks)
    first, count = held
    for k, p in enumerate(chunks):
        load = np.zeros((2, count), np.int64)
        for layer in range(2):
            local = by_chunk[2 * k + layer].reshape(-1) - first
            local = local[(local >= 0) & (local < count)]
            load[layer] = np.bincount(local, minlength=count)
        took = load.sum(axis=1)
        busy = took > 0
        want = {"assignments": int(took.sum()),
                "touched": float((load > 0).sum(axis=1).mean()),
                "held": count, "layers": 2,
                "skew": float((load.max(axis=1)[busy] * count
                               / took[busy]).mean()) if busy.any() else 0.0}
        assert p["experts"] == pytest.approx(want), (k, p)
        if count == 16:         # all held: padding rows are counted too
            assert p["experts"]["assignments"] == 2 * 8 * 3
    # the decode program's record is the tick's own counters, unmoved
    for t in obs.last_run("serve").phases.ticks:
        for p in (t[2][2]["programs"] if len(t[2]) > 2 else ()):
            if p["program"] == "paged_decode":
                assert p["experts"] is t[2][2]["experts"]


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_reduced_precision_caches_serve_two_kinds(kv_dtype):
    """A ring rests in bf16 or int8 as a whole-sequence pool does, scales
    and all; the tokens stay near the reference's (not bit for bit: the
    cache is rounded)."""
    cfg = tiny()
    reqs = [(tokens(30 + i, 45), 10) for i in range(2)]
    served, _, eng, flat = _serve(cfg, reqs, max_slots=2, max_len=64,
                                  kv_block_size=4, prefill_chunk=8,
                                  kv_dtype=kv_dtype)
    assert eng.ring_blocks == 5
    for uid, (prompt, _) in enumerate(reqs):
        row = jnp.asarray(np.concatenate([prompt, served[uid]]))[None]
        gaps, _ = ref.token_gaps(flat, row)
        assert float(jnp.max(gaps[0, len(prompt) - 1:])) < 0.05


@pytest.mark.parametrize("what, kw", [
    ("speculative decoding", {"draft_layers": 2}),
    ("preemption", {"preempt": True}),
])
def test_what_moves_whole_slots_refuses_two_kinds(what, kw):
    model, params, _ = build(tiny())
    with pytest.raises(ValueError, match="mixes full and window layers"):
        PagedEngine(model, params, max_slots=2, max_len=64, kv_block_size=4,
                    prefill_chunk=8, **kw)


def test_canary_refuses_two_kinds():
    model, params, _ = build(tiny())
    eng = PagedEngine(model, params, max_slots=2, max_len=64,
                      kv_block_size=4, prefill_chunk=8)
    with pytest.raises(RuntimeError, match="mixes full and window layers"):
        eng.begin_canary(params, [0])


def test_yarn_frequencies_by_hand():
    """Laguna-S-2.1's full-attention RoPE: 64 rotated dims of 128, theta
    500,000, factor 128 over an original 8,192.  By hand: the ramp starts
    at floor(64 ln(8192 / (32 * 2 pi)) / (2 ln 500000)) = 9 and ends at
    ceil(64 ln(8192 / (2 pi)) / (2 ln 500000)) = 18, so pairs 0-9 keep
    theta^(-2i/64), pairs 18-31 are divided by 128, and pair 12 sits a
    third of the way: 2/3 kept + 1/3 interpolated."""
    spec = RopeSpec(theta=500000.0, rotary_dim=64, factor=128.0,
                    original_max_len=8192, beta_fast=32.0, beta_slow=1.0,
                    attention_factor=1.4852030263919618)
    got = spec.inv_freq(128)
    assert got.shape == (32,)
    assert math.floor(64 * math.log(8192 / (32 * 2 * math.pi))
                      / (2 * math.log(500000))) == 9
    assert math.ceil(64 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(500000))) == 18
    base = 500000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:10], base[:10], rtol=1e-12)
    np.testing.assert_allclose(got[18:], base[18:] / 128.0, rtol=1e-12)
    np.testing.assert_allclose(got[12], base[12] * (2 / 3 + 1 / 3 / 128),
                               rtol=1e-12)
    np.testing.assert_allclose(got[0], 1.0)
    np.testing.assert_allclose(got[31], 500000.0 ** (-31 / 32) / 128)
    # the reference computes its own, from the same published keys
    np.testing.assert_allclose(
        ref.inv_freq(ref.Rope(500000.0, 64, 128.0, 8192, 32.0, 1.0)), got,
        rtol=1e-12)
    # the plain kind: theta^(-2i/d) over the whole head
    np.testing.assert_allclose(RopeSpec(theta=10000.0).inv_freq(128),
                               10000.0 ** (-np.arange(64) / 64.0))


def _expert_layer(cfg, flat, i, u, held):
    """The package's expert layer `i` on (T, d), holding `held` =
    (first, count) of the experts in `flat` (which has them all)."""
    first, count = held
    spec = ExpertSpec(num_experts=count, mlp_dim=16, top_k=3,
                      router_experts=16, expert_offset=first,
                      routed_scale=2.5, shared_dim=16)
    lw = ref.layer_weights(flat, i)
    cut = slice(first, first + count)
    params = {"router": lw["router"], "w_gate": lw["eg"][cut],
              "w_up": lw["eu"][cut], "w_down": lw["ed"][cut],
              "shared": {"gate": {"kernel": lw["sg"]},
                         "up": {"kernel": lw["su"]},
                         "down": {"kernel": lw["sd"]}}}
    layer = RoutedExperts(spec, decode=True)
    y, upd = layer.apply({"params": params}, u[None], mutable=["moe_stats"])
    return y[0], upd["moe_stats"]["load"][0]


def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts that 8 chips' shares give
    (2 experts each of 16), with the shared expert counted once, add up to
    what the uncut reference gives for the whole layer."""
    cfg = tiny(held=(0, 16))
    flat = weights.make_weights(jax.random.key(3), cfg, jnp.float32)
    hp = flat.hp
    u = jax.random.normal(jax.random.key(4), (24, 32), jnp.float32)
    lw = {n: jnp.asarray(a) for n, a in ref.layer_weights(flat, 1).items()}
    whole, _ = ref.expert_ffn(u, lw, hp)
    shared = ref.swiglu(u, lw["sg"], lw["su"], lw["sd"])
    total, loads = jnp.zeros_like(u), []
    for chip in range(8):
        y, load = _expert_layer(cfg, flat, 1, u, (2 * chip, 2))
        total = total + (y - shared)
        loads.append(np.asarray(load))
        # and the reference, given the same share, gives the same part
        cut = {**lw, **{n: lw[n][2 * chip:2 * chip + 2]
                        for n in ("eg", "eu", "ed")}}
        part, _ = ref.expert_ffn(u, cut, hp, held=(2 * chip, 2))
        np.testing.assert_allclose(y, part, atol=2e-5)
    np.testing.assert_allclose(total + shared, whole, atol=5e-5)
    # every assignment landed on exactly one chip: nothing dropped
    assert int(np.sum(loads)) == 24 * 3


def test_dropless_under_a_skewed_router():
    """A router that sends every token to the same three experts, all
    held: each takes all 64 tokens, a capacity of 64 x 3 / 16 = 12 would
    have dropped 52 of them, and the result is still the reference's."""
    cfg = tiny(held=(0, 16))
    flat = weights.make_weights(jax.random.key(5), cfg, jnp.float32)
    u = jnp.abs(jax.random.normal(jax.random.key(6), (64, 32))) + 0.1

    def both(chosen):
        """(package, its loads, reference) for a share of experts 4-7
        under a router whose positive input picks `chosen`."""
        router = np.zeros((32, 16), np.float32)
        router[:, chosen] = 1.0
        skewed = ref.Weights({**flat, "l2.router": jnp.asarray(router)},
                             flat.hp)
        y, load = _expert_layer(cfg, skewed, 2, u, (4, 4))
        lw = {n: jnp.asarray(a)
              for n, a in ref.layer_weights(skewed, 2).items()}
        cut = {**lw, **{n: lw[n][4:8] for n in ("eg", "eu", "ed")}}
        want, ids = ref.expert_ffn(u, cut, flat.hp, held=(4, 4))
        assert set(np.unique(ids)) == set(chosen)
        return y, np.asarray(load), want, lw

    y, load, want, _ = both([5, 6, 7])
    np.testing.assert_array_equal(load, [0, 64, 64, 64])
    np.testing.assert_allclose(y, want, atol=5e-5)
    # a router that picks only absent experts leaves the shared one
    y, load, want, lw = both([12, 13, 14])
    np.testing.assert_array_equal(load, [0, 0, 0, 0])
    np.testing.assert_allclose(y, want, atol=5e-5)
    np.testing.assert_allclose(
        want, ref.swiglu(u, lw["sg"], lw["su"], lw["sd"]), atol=1e-6)


def test_a_description_refuses_what_it_cannot_compute():
    for key, value in (("moe_router_logit_softcapping", 30.0),
                       ("moe_apply_router_weight_on_input", True),
                       ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            describe.layer_specs(tiny(**{key: value}))
    with pytest.raises(ValueError, match="layer type"):
        describe.layer_specs(tiny(layer_types=(FULL, "linear_attention")))
    with pytest.raises(ValueError, match="rope_type"):
        describe.layer_specs(tiny(rope_parameters={
            FULL: {**YARN, "rope_type": "longrope"}, SLIDING: PLAIN}))
    with pytest.raises(ValueError, match="vocab_size"):
        describe.causal_lm(tiny(), max_len=32, vocab_size=50)


# ------------------------------------------------------- GPT-2, as before

#: what the parent commit (PR 25) gave for `_gpt2()` below: the parameter
#: tree's paths, and a digest of the tokens its paged engine served
GPT2_PATHS = sorted(
    [("embed", "pos"), ("embed", "tok", "embedding"),
     ("final_norm", "bias"), ("final_norm", "scale")]
    + [(f"layer_{i}",) + p for i in range(2) for p in (
        ("Dense_0", "bias"), ("Dense_0", "kernel"), ("Dense_1", "bias"),
        ("Dense_1", "kernel"), ("LayerNorm_0", "bias"),
        ("LayerNorm_0", "scale"), ("LayerNorm_1", "bias"),
        ("LayerNorm_1", "scale"))
       + tuple(("self_attn", m, leaf) for m in ("k", "out", "q", "v")
               for leaf in ("bias", "kernel"))])
GPT2_SERVED_DIGEST = (
    "729dcde4ebb5136a893096bc5a18cb2b6a3de64dc452605d30a360f8268a6903")


def _gpt2():
    model = CausalLM(vocab_size=61, num_layers=2, d_model=32, num_heads=4,
                     mlp_dim=64, max_len=64)
    params = model.init(jax.random.key(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def test_gpt2_tree_pools_and_served_tokens_are_what_they_were():
    model, params = _gpt2()
    paths = sorted(tuple(k.key for k in path) for path, _ in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    assert paths == GPT2_PATHS
    assert model.layer_specs() == (model.layer_specs()[0],) * 2
    assert dataclasses.asdict(model.layer_specs()[0]) == {
        "num_heads": 4, "mlp_dim": 64, "num_kv_heads": None,
        "head_dim": None, "window": None, "rope": False, "gate": False,
        "use_bias": True, "norm": "layer", "mlp": "gelu", "experts": None,
        "latent": None}
    eng = PagedEngine(model, params, max_slots=3, max_len=48,
                      kv_block_size=4, prefill_chunk=8)
    assert eng.ring_blocks is None and eng.manager.ring_blocks is None
    assert isinstance(eng.manager.device_tables(), np.ndarray)
    shapes = jax.tree.map(lambda x: x.shape, eng.pools)
    assert shapes["layer_1"]["self_attn"] == {
        "cache_index": (), "cached_key": (73, 4, 32),
        "cached_valid": (73, 4), "cached_value": (73, 4, 32)}
    reqs = [Request(uid=i, prompt=tokens(40 + i, n) % 61, max_new_tokens=k)
            for i, (n, k) in enumerate([(20, 12), (5, 20), (33, 9),
                                        (20, 12)])]
    reqs[3] = dataclasses.replace(reqs[3], prompt=reqs[0].prompt)
    out = eng.run(reqs)
    assert out["stats"]["paged"]["shared_tokens"] > 0     # the index works
    for r in reqs:
        want = generate(model, params, jnp.asarray(r.prompt)[None],
                        max_new_tokens=r.max_new_tokens)[0]
        np.testing.assert_array_equal(out["results"][r.uid], want)
    served = np.concatenate([out["results"][r.uid] for r in reqs])
    assert hashlib.sha256(served.astype(np.int64).tobytes()).hexdigest() \
        == GPT2_SERVED_DIGEST
    kinds = [t[2][2]["kv_blocks"] for t in
             obs.last_run("serve").phases.ticks if t[1] == "decode"]
    assert kinds and all(set(k) == {"full"} for k in kinds)


def test_ring_pool_ops_address_position_modulo_the_ring():
    """gather / extract / scatter on a two-kind tree by hand: position p
    of a ring leaf rests at block ring_table[(p // bs) % ring_blocks]."""
    like = {"a": {"cached_key": jax.ShapeDtypeStruct((1, 16, 2), jnp.float32),
                  "ring_key": jax.ShapeDtypeStruct((1, 8, 2), jnp.float32),
                  "cache_index": jax.ShapeDtypeStruct((), jnp.int32)}}
    pools = paged.build_pools(like, 5, 4, ring_num_blocks=3)
    assert pools["a"]["cached_key"].shape == (5, 4, 2)
    assert pools["a"]["ring_key"].shape == (3, 4, 2)
    mgr = paged.BlockManager(4, 4, 1, 4, ring_blocks=2)
    sp = mgr.match_prefix(np.arange(1, 13))
    assert mgr.shared_len(sp) == 0
    mgr.admit(0, sp, 14)
    pos = np.arange(9, 13)                       # blocks 2 and 3: ring 0, 1
    ring_wb = mgr.ring_targets(0, pos, np.array([True, True, True, False]))
    np.testing.assert_array_equal(ring_wb, [1, 1, 1, 0])   # 12 is masked
    np.testing.assert_array_equal(
        mgr.ring_targets(0, np.array([12]), True), [2])
    kv = {"a": {"cached_key": jnp.arange(8.0).reshape(4, 2),
                "ring_key": 10 + jnp.arange(8.0).reshape(4, 2),
                "cache_index": jnp.zeros((), jnp.int32)}}
    wb = mgr.tables[0][pos // 4]
    pools = paged.scatter_span(pools, kv, (jnp.asarray(wb),
                                           jnp.asarray(ring_wb)),
                               jnp.asarray(pos % 4))
    got = paged.gather_slot(pools, tuple(map(jnp.asarray,
                                             mgr.device_tables(0))), 13,
                            like)
    assert got["a"]["cached_key"].shape == (1, 16, 2)
    assert got["a"]["ring_key"].shape == (1, 8, 2)
    np.testing.assert_array_equal(got["a"]["cached_key"][0, 9:13],
                                  kv["a"]["cached_key"])
    np.testing.assert_array_equal(got["a"]["ring_key"][0, 1:4],
                                  kv["a"]["ring_key"][:3])   # 9 % 8 = 1
    span = paged.extract_span(got, 6, 4)       # positions 6, 7, 8, 9
    np.testing.assert_array_equal(
        span["a"]["ring_key"], got["a"]["ring_key"][0, [6, 7, 0, 1]])
    assert mgr.blocks_by_kind() == {"full": 4, "window": 2,
                                    "window_released": 2}
    mgr.release(0)
    assert mgr.blocks_by_kind() == {"full": 0, "window": 0,
                                    "window_released": 0}


# ------------------------------------------- the benchmark's side of the cell

def _tiny_bench(root):
    """A one-cell BENCHMARK.json under `root`: the tiny model above as a
    serving cell, with the new per-layer metrics as the real file has
    them."""
    import json
    import os

    from benchmark import harness

    real = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(root, d))
    cfg = dict(tiny(), name="tiny-laguna", architecture="laguna",
               reference="laguna", n_positions=96,
               serve_param_dtype="float32",
               cli=["gpt", "--model-file",
                    os.path.join(root, "configs", "tiny-laguna.json")])
    mix = {"name": "tiny-long", "kind": "serve", "prompt_len": [20, 60],
           "output_len": [5, 20], "table_len": 4, "pairing": [2, 0, 3, 1],
           "issue_order": [1, 3, 0, 2], "queue_cycles": 400,
           "engine": {"max_slots": 3, "max_len": 96, "kv_block_size": 4,
                      "prefill_chunk": 8, "prefill_chunks_per_tick": 1,
                      "temperature": 0.0, "num_blocks": 80},
           "check_requests": 4, "trace_seconds": 1}
    for path, what in (("configs/tiny-laguna.json", cfg),
                       ("traffic/tiny-long.json", mix),
                       ("limits/tiny-serve.json",
                        {"served_gap_widest": 1e-3,
                         "served_gap_mean": 1e-4})):
        with open(os.path.join(root, path), "w") as f:
            json.dump(what, f)
    cell = ["tiny-serve"]
    bench = {
        "paths": ["."],
        "configs": [{"name": "tiny-laguna",
                     "file": "configs/tiny-laguna.json"}],
        "workloads": [{"name": "tiny-serve", "config": "tiny-laguna",
                       "traffic": "tiny-long", "chips": 1}],
        "end_to_end": [m for m in real["end_to_end"] if m["name"] in (
            "serve_total_tokens_per_s", "setup_s")],
        "per_layer": [dict(m, workloads=cell) for m in real["per_layer"]
                      if "laguna-serve-long-mixed" in m.get("workloads", ())]}
    bench["end_to_end"][0] = dict(bench["end_to_end"][0], workloads=cell)
    return harness.Cell("tiny-serve", root=str(root), bench=bench)


def test_the_benchmark_runs_the_cell_and_reads_its_counters(tmp_path):
    """The unedited serving runner builds the described model through the
    CLI's argument, serves the mix, and the reference agrees with every
    served token; then the new per-layer readers, on the record that run
    left and a hand-made trace."""
    from benchmark import cellrun, harness
    from benchmark.readers import (kv_window_saved, moe_decode_roofline,
                                   tick_counters)

    cell = _tiny_bench(tmp_path)
    assert [m["name"] for m in cell.per_layer] == [
        "moe_decode_roofline", "serve_expert_touched_pct",
        "serve_expert_load_skew",
        "serve_kv_window_saved_pct",
        # PR 37: what the program records of its own runs
        "grouped_product_roofline", "serve_chunk_expert_touched_pct",
        "serve_turnaround_ms", "serve_launch_notice_ms",
        "serve_tick_longest_ms"]
    keep = {}
    out = cellrun.run_cell("tiny-serve", 2 ** 31 + 26, 1.5, False,
                           allow_cpu=True, cell=cell, keep=keep)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["serve_total_tokens_per_s"]["value"] > 0

    ticks = tick_counters.tick_counters()
    assert ticks and all("experts" in c and "kv_blocks" in c for c in ticks)
    touched = tick_counters.read({}, ["experts", "touched"],
                                 per=["experts", "held"], scale=100.0)
    assert 0.0 < touched <= 100.0
    skew = tick_counters.read({}, ["experts", "skew"],
                              where=["experts", "assignments"])
    assert 1.0 <= skew <= 4.0
    assert tick_counters.read({}, ["no", "such"]) is None
    # PR 37: the window's own record, read by the readers of the timed run
    from benchmark.readers import (tick_longest_ms, traced_run,
                                   turnaround_ms)

    progs = traced_run.programs(traced_run.timed_record())
    assert {p["program"] for p in progs} == {"paged_chunk", "paged_decode"}
    # a chunk's load is fetched behind the NEXT program: the chunk the
    # window's end cut off may lack it, no other
    assert sum("experts" not in p for p in progs) <= 1
    assert 0.0 < turnaround_ms.read({}) < 1e3
    assert tick_longest_ms.read({}) > 0.0
    # nothing listened to this window: the traced window's reader reads
    # nothing of it
    assert traced_run.timed_record().phases.listened == 0
    listened = traced_run.traced_record()
    assert listened is None or listened is not traced_run.timed_record()
    # 2 full layers and 4 sliding ones; a ring of 5 blocks against 7 to 20
    # reserved a request: something is saved, less than the sliding share
    saved = kv_window_saved.read({"config": cell.config})
    assert 0.0 < saved < 100.0 * 4 / 6

    ms = 1_000_000
    events = [
        ["/host:CPU", "main", "bench:window", 0, 100 * ms],
        ["/host:CPU", "main", "bench:decode_dispatch", 3 * ms, ms],
        ["/device:TPU:0", "XLA Modules", "jit_paged_decode", 2 * ms,
         10 * ms],
        ["/device:TPU:0", "XLA Ops", "fusion.7", 6 * ms, 5 * ms],
        ["/host:CPU", "main", "bench:chunk_dispatch", 20 * ms, ms],
        ["/device:TPU:0", "XLA Modules", "jit_paged_chunk", 19 * ms,
         30 * ms],      # begins BEFORE its dispatch annotation, as on the chip
    ]
    real = harness.Cell("laguna-serve-long-mixed")
    ctx = {"trace": {"events": events}, "config": real.config,
           "traffic": real.traffic,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "counters": {"mean_live_tokens": 16 * 4000.0,
                        "mean_decoding_slots_share": 1.0}}
    held = tick_counters.read({}, ["experts", "touched"])
    need = harness.cost_function("laguna_decode_tick")(
        real.config, 16.0, held, 16 * 4000.0, 16 * 512.0)
    # outside the routed experts: attention 44.19 M a full layer and 63.14 M
    # a sliding one, layer 0's MLP, 8 x (shared expert + router), 19 norms,
    # the head's 12,544 rows; 9.44 M an expert; 4 KiB a position and layer,
    # 3 full layers whole and 6 sliding a window
    assert need["outside_bytes"] == 2 * (
        3 * 44_187_648 + 6 * 63_135_744 + 113_246_208
        + 8 * (9_437_184 + 786_432) + 19 * 3072 + 12_544 * 3072)
    assert need["expert_bytes"] == pytest.approx(
        2 * 8 * held * 3 * 3072 * 1024)
    assert need["kv_bytes"] == 4096 * (3 * 64000 + 6 * 8192)
    got = moe_decode_roofline.read(ctx)
    assert got == pytest.approx(100.0 * need["bytes"] / 819e9 / 0.010)
    # the parent's program has no such counters: every reader gives None
    obs.last_run("serve").phases.ticks.clear()
    assert moe_decode_roofline.read(ctx) is None
    assert kv_window_saved.read({"config": real.config}) is None


def test_disagg_refuses_and_evacuation_moves_nothing_for_two_kinds():
    from distributed_deep_learning_tpu.serve.disagg import DisaggEngine
    from distributed_deep_learning_tpu.serve.migrate import (BlockMigrator,
                                                             clone_prefix)
    from distributed_deep_learning_tpu.serve.rebalance import evacuate_slot

    model, params, _ = build(tiny())
    kw = dict(max_slots=2, max_len=64, kv_block_size=4, prefill_chunk=8)
    with pytest.raises(ValueError, match="mixes full and window layers"):
        DisaggEngine(model, params, **kw)
    src, dst = (PagedEngine(model, params, **kw) for _ in range(2))
    prompt = tokens(50, 30)
    src.run([Request(uid=0, prompt=prompt, max_new_tokens=4)])
    mig = BlockMigrator(4)
    assert clone_prefix(src, dst, prompt, mig) == 0
    rec = evacuate_slot(src, dst, prompt, mig)
    assert rec["ok"] and rec["blocks"] == 0 and not rec["rolled_back"]


def test_a_described_model_of_one_kind_keeps_speculation_and_the_index():
    """Full layers only: no ring, so the draft (with the untied head) and
    the prefix index work as for GPT-2, token for token."""
    model, params, _ = build(tiny((FULL, FULL, FULL)))
    eng = PagedEngine(model, params, max_slots=2, max_len=64,
                      kv_block_size=4, prefill_chunk=8, draft_layers=1,
                      spec_k=2)
    assert eng.ring_blocks is None
    prompt = tokens(3, 20)
    first = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=10)])
    again = eng.run([Request(uid=1, prompt=prompt, max_new_tokens=10)])
    want = generate(model, params, jnp.asarray(prompt)[None],
                    max_new_tokens=10)[0]
    np.testing.assert_array_equal(first["results"][0], want)
    np.testing.assert_array_equal(again["results"][1], want)
    assert again["stats"]["paged"]["shared_tokens"] >= 16
    assert again["stats"]["spec"]["rounds"] > 0
