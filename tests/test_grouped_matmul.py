"""The grouped product kernel (``ops/grouped_matmul_pallas.py``) in
interpret mode on the CPU, against ``jax.lax.ragged_dot`` AND a plain loop
over the groups; the visit lists; the rule that picks a call's path; and
``held_experts`` / a served model through the interpreted kernel, with the
``grouped_product`` note each traced program leaves."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_deep_learning_tpu import obs
from distributed_deep_learning_tpu.models import describe, moe
from distributed_deep_learning_tpu.models.transformer import random_causal_lm
from distributed_deep_learning_tpu.ops import grouped_matmul_pallas as gm
from distributed_deep_learning_tpu.serve.engine import PagedEngine
from distributed_deep_learning_tpu.serve.scheduler import Request

HERE = os.path.dirname(os.path.abspath(__file__))


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _loop(rows, w, load):
    """Group by group in float32: ``(what the groups' rows give, how many
    rows the groups own)``."""
    out = np.zeros((rows.shape[0], w.shape[2]), np.float32)
    at = 0
    for g, n in enumerate(np.asarray(load)):
        out[at:at + n] = (np.asarray(rows[at:at + n], np.float32)
                          @ np.asarray(w[g], np.float32))
        at += n
    return out, at


def _operands(M, K, N, E, dtype, seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k[0], (M, K), dtype),
            jax.random.normal(k[1], (E, K, N), dtype) * 0.1,
            jax.random.normal(k[2], (E, K, N), dtype) * 0.1)


def _spread(total, E, seed):
    """`total` rows over `E` groups, some of them empty."""
    rng = np.random.default_rng(seed)
    p = rng.dirichlet(np.full(E, 0.5))
    p[rng.integers(E)] = 0
    return rng.multinomial(total, p / p.sum()).tolist()


# M, E, (tm, tn), load: each case one thing the kernel must get right
CASES = {
    "groups-end-inside-a-tile": (256, 4, (64, 128), [70, 50, 100, 36]),
    "empty-groups": (160, 8, (64, 128), [3, 0, 70, 0, 0, 30, 1, 20]),
    "one-group-holds-every-row": (192, 4, (64, 128), [0, 192, 0, 0]),
    "absent-experts-rows-last": (320, 4, (64, 128), [10, 0, 25, 5]),
    "no-row-at-all": (256, 4, (128, 128), [0, 0, 0, 0]),
    "one-row": (128, 3, (64, 128), [0, 0, 1]),
    "a-group-over-three-tiles": (256, 2, (64, 128), [60, 190]),
    # the cells' row counts: a decode program's 64 and 160 (one tile of 64,
    # a tile and a quarter of 128), a chunk program's 4,096 and 5,120 of
    # which an eighth is held
    "rows-64": (64, 8, (64, 128), _spread(64, 8, 1)),
    "rows-160": (160, 8, (128, 128), _spread(150, 8, 2)),
    "rows-4096": (4096, 16, (128, 128), _spread(4096, 16, 3)),
    "rows-5120-an-eighth-held": (5120, 8, (128, 128), _spread(640, 8, 4)),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_ragged_dot_and_a_loop_over_groups(case, dtype, tol):
    M, E, tiles, load = CASES[case]
    rows, w, w_up = _operands(M, 128, 256, E, dtype)
    load = jnp.asarray(load, jnp.int32)
    products = gm.Visits(load, M, interpret=True, tiles=tiles)
    got = products.product(rows, w)
    assert got.shape == (M, 256) and got.dtype == dtype
    want, owned = _loop(rows, w, load)
    got = np.asarray(got, np.float32)[:owned]
    np.testing.assert_allclose(got, want[:owned], atol=tol, rtol=tol)
    xla = np.asarray(jax.lax.ragged_dot(rows, w, load), np.float32)
    np.testing.assert_allclose(got, xla[:owned], atol=tol, rtol=tol)
    # gate and up in one call: silu(g) * u from the two f32 products
    fused = products.swiglu(rows, w, w_up)
    assert fused.shape == (M, 256) and fused.dtype == dtype
    up, _ = _loop(rows, w_up, load)
    np.testing.assert_allclose(
        np.asarray(fused, np.float32)[:owned],
        (np.asarray(jax.nn.silu(jnp.asarray(want))) * up)[:owned],
        atol=3 * tol, rtol=3 * tol)


@pytest.mark.parametrize("case", CASES)
def test_visits_cover_each_overlap_once_in_row_order(case):
    """The visit lists against a walk over every (row tile, group) pair:
    exactly the pairs that overlap, in row order, none twice; the count
    under ``ceil(M / tm) + E - 1``; the tail repeats the last live visit
    (a block index that does not change moves nothing)."""
    M, E, (tm, _), load = CASES[case]
    offsets, groups, tiles, live = map(np.asarray, gm.visits(
        jnp.asarray(load, jnp.int32), M, tm))
    most = -(-M // tm) + E - 1
    assert groups.shape == tiles.shape == (most,)
    ends = np.cumsum(load)
    np.testing.assert_array_equal(offsets, [0, *ends])
    want = [(t, g) for t in range(-(-M // tm)) for g in range(E)
            if load[g] and ends[g] - load[g] < (t + 1) * tm
            and ends[g] > t * tm]
    assert live == len(want) <= most
    assert list(zip(tiles[:live], groups[:live])) == want
    if live:
        assert set(zip(tiles[live:], groups[live:])) <= {want[-1]}
    else:
        assert not tiles.any()


def test_the_most_visits_there_can_be():
    """Every group but the first starts inside a tile another has begun:
    ``ceil(M / tm) + E - 1`` visits, all live."""
    M, tm, load = 256, 64, [63, 64, 64, 65]
    *_, live = gm.visits(jnp.asarray(load, jnp.int32), M, tm)
    assert int(live) == M // tm + len(load) - 1


# the grouped products of the two expert cells' programs, (M, K, N, E):
# gate / up and down of a chunk program and of a decode program
CELL_CALLS = {
    "glm-chunk-up": ((4096, 2048, 1536, 64), (128, 1536)),
    "glm-chunk-down": ((4096, 1536, 2048, 64), (128, 2048)),
    "laguna-chunk-up": ((5120, 3072, 1024, 32), (128, 1024)),
    "laguna-chunk-down": ((5120, 1024, 3072, 32), (128, 3072)),
    "glm-decode-up": ((64, 2048, 1536, 64), (64, 1536)),
    "glm-decode-down": ((64, 1536, 2048, 64), (64, 2048)),
    "laguna-decode-up": ((160, 3072, 1024, 32), (128, 1024)),
    "laguna-decode-down": ((160, 1024, 3072, 32), (128, 3072)),
    # one sequence decoding alone (`generate`): top_k rows, never timed
    "glm-one-token": ((4, 2048, 1536, 64), None),
    "laguna-one-token": ((10, 3072, 1024, 32), None),
}


@pytest.mark.parametrize("call", CELL_CALLS)
def test_the_path_is_picked_from_the_shapes(call):
    """A chunk program's products and a decode program's go through the
    kernel with the whole of N a tile (a row tile is read once), gate and
    up fused at the same tiles, 128 rows a visit or all 64 of them; a
    single sequence's few rows stay on ``ragged_dot``."""
    shape, want = CELL_CALLS[call]
    assert gm._tiling(*shape) == want
    if want and call.endswith("up"):
        assert gm._tiling(*shape, weights=2) == want
        tm, tn = want
        assert gm._held(tm, shape[1], tn, 2, 2) <= gm.VMEM_BLOCKS


def test_what_the_rule_refuses():
    assert gm._tiling(4096, 2048, 1000, 64) is None       # N off the lanes
    assert gm._tiling(63, 2048, 1536, 64) is None         # too few rows
    assert gm._tiling(72, 2048, 1536, 64) == (80, 1536)   # whole sublanes
    # a contraction that fits only a narrower tile of N, then none
    assert gm._tiling(4096, 16384, 1536, 64) == (128, 384)
    assert gm._tiling(4096, 1 << 18, 1536, 64) is None


def test_off_a_tpu_the_products_are_ragged_dot():
    rows, w, w_up = _operands(64, 128, 128, 4, jnp.float32)
    load = jnp.asarray([10, 0, 30, 4], jnp.int32)
    products = gm.Visits(load, 64)
    np.testing.assert_array_equal(products.product(rows, w),
                                  jax.lax.ragged_dot(rows, w, load))
    np.testing.assert_array_equal(
        products.swiglu(rows, w, w_up),
        jax.nn.silu(jax.lax.ragged_dot(rows, w, load))
        * jax.lax.ragged_dot(rows, w_up, load))


# --------------------------------------- held_experts through the kernel

@pytest.fixture
def interpreted(monkeypatch):
    """What a serving program's products find on the chip, with the kernel
    interpreted and the row floor at a CPU test's sizes."""
    monkeypatch.setattr(moe, "Visits",
                        functools.partial(gm.Visits, interpret=True))
    monkeypatch.setattr(gm, "MIN_ROWS", 16)


@pytest.mark.parametrize("held", [(0, 8), (2, 4)],
                         ids=["all-held", "an-eighth-way-share"])
def test_held_experts_give_what_they_gave(interpreted, held):
    """`grad=False` (a serving program's call) through the interpreted
    kernel against `grad=True` (``ragged_dot``): the same assignments, the
    same loads, outputs to float32 rounding; rows of absent experts past
    the last group are zeroed on the way out either way."""
    offset, E = held
    n, k, d, f = 40, 3, 128, 128
    key = jax.random.split(jax.random.key(5), 6)
    x = jax.random.normal(key[0], (n, d))
    experts = jnp.stack([jax.random.permutation(kk, 8)[:k]
                         for kk in jax.random.split(key[1], n)])
    w = jax.nn.softmax(jax.random.normal(key[2], (n, k)), -1)
    w_gate, w_up = (jax.random.normal(kk, (E, d, f)) * 0.1
                    for kk in key[3:5])
    w_down = jax.random.normal(key[5], (E, f, d)) * 0.1
    want, load = moe.held_experts(x, w, experts, w_gate, w_up, w_down,
                                  offset)
    got, load2 = moe.held_experts(x, w, experts, w_gate, w_up, w_down,
                                  offset, grad=False)
    np.testing.assert_array_equal(load, load2)
    assert int(load.sum()) == int(((experts >= offset)
                                   & (experts < offset + E)).sum())
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_the_training_path_stays_differentiable(interpreted):
    """`decode=False` never reaches the kernel, whatever its rows."""
    spec = moe.ExpertSpec(num_experts=4, mlp_dim=128, top_k=2)
    layer = moe.RoutedExperts(spec)
    x = jax.random.normal(jax.random.key(0), (2, 16, 128))
    params = layer.init(jax.random.key(1), x)
    obs.compile_log.mark("test")
    grads = jax.grad(lambda p: jnp.sum(layer.apply(p, x) ** 2))(params)
    assert all(np.isfinite(g).all() for g in jax.tree.leaves(grads))
    assert not [n for n in obs.compile_log.notes()
                if n[0] == "grouped_product"]
    served = moe.RoutedExperts(spec, decode=True)
    with obs.compile_log.notes_for("jit(served)") as said:
        got = served.apply(params, x, mutable=["moe_stats"])[0]
    np.testing.assert_allclose(got, layer.apply(params, x), atol=2e-5,
                               rtol=2e-5)
    assert said == {"grouped_product": "calls=2 rows=64 experts=4 "
                    "path=pallas tiles=64x128x128 fused_gate_up=1"}


# ------------------------------------ a served model, and what it notes

def _tiny(family):
    tests = _module(os.path.join(HERE, f"test_{family}.py"),
                    f"_tiny_{family}")
    return tests.tiny(hidden_size=128, moe_intermediate_size=128)


def _serve(model, params, n=3):
    eng = PagedEngine(model, params, max_slots=3, max_len=96,
                      kv_block_size=4, prefill_chunk=8)
    rng = np.random.default_rng(7)
    out = eng.run([Request(uid=i, prompt=rng.integers(1, 97, size=9 + 7 * i),
                           max_new_tokens=4) for i in range(n)])
    assert not out["errors"]
    return out


@pytest.mark.parametrize("family", ["glm", "laguna"])
def test_a_served_expert_model_notes_its_grouped_products(family,
                                                          interpreted):
    """A chunk of 8 tokens hands the products 16 (glm, 2 a token) or 24
    (laguna, 3) sorted rows, at or over the patched floor: the interpreted
    kernel, gate and up fused; a decode program's 6 or 9 rows are under it
    and stay on ``ragged_dot``.  The served tokens are what ``ragged_dot`` alone
    serves, and the note is in the compile log, the run's stats and the
    report."""
    cfg = _tiny(family)
    model = describe.causal_lm(cfg, max_len=96, with_logits=True)
    params = model.init(jax.random.key(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    obs.compile_log.mark("test")
    out = _serve(model, params)
    layers = len(cfg.get("mlp_layer_types", [0] * 3)) - 1   # expert layers
    k = cfg["num_experts_per_tok"]
    E = cfg.get("n_routed_experts", cfg.get("num_experts"))
    want = {
        "paged_chunk": f"calls={2 * layers} rows={8 * k} experts={E} "
                       f"path=pallas tiles={-(-8 * k // 16) * 16}x128x128 "
                       f"fused_gate_up={layers}",
        "paged_decode": f"calls={3 * layers} rows={3 * k} experts={E} "
                        f"path=ragged_dot tiles=none fused_gate_up=0",
    }
    assert out["stats"]["paged"]["grouped_product"] == want
    noted = {fun: text for event, fun, text in obs.compile_log.notes()
             if event == "grouped_product"}
    assert noted == {f"jit({p})": t for p, t in want.items()}
    report = _module(os.path.join(HERE, os.pardir, "scripts",
                                  "obs_report.py"), "obs_report")
    text = report.render([{"event": "obs_serve", "stats": out["stats"]}])
    for prog, note in want.items():
        assert f"grouped expert products, {prog}: {note}" in text
    # and the tokens: the same engine with every product on ragged_dot
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gm, "MIN_ROWS", 1 << 30)
        plain = _serve(model, params)
    assert plain["stats"]["paged"]["grouped_product"]["paged_chunk"] \
        .count("path=ragged_dot")
    assert plain["results"].keys() == out["results"].keys()
    for uid, toks in out["results"].items():
        np.testing.assert_array_equal(toks, plain["results"][uid])


def test_a_gpt2_program_notes_no_grouped_product(interpreted):
    model, params = random_causal_lm(seed=3, vocab_size=97, num_layers=2,
                                     d_model=32, num_heads=4, mlp_dim=64,
                                     max_len=96)
    obs.compile_log.mark("test")
    out = _serve(model, params)
    assert "grouped_product" not in out["stats"]["paged"]
    assert not [n for n in obs.compile_log.notes()
                if n[0] == "grouped_product"]
