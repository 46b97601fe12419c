"""The head and its cross-entropy from blocks of logits vs the logits at
rest: values, gradients, padding semantics, the Pallas kernels
(interpreted) at several tilings and tile boundaries, and the ``gpt``
workload's step on deferred logits."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_deep_learning_tpu.ops import fused_ce
from distributed_deep_learning_tpu.ops.fused_ce import (DeferredLogits,
                                                        head_cross_entropy,
                                                        head_rows)
from distributed_deep_learning_tpu.train import objectives
from distributed_deep_learning_tpu.train.objectives import (
    prediction_metrics, token_cross_entropy)

#: how a call tiles: the kernels (interpreted) at the shapes' own tiles
#: (one tile, wider than the vocabulary), at tiles that leave a row tile
#: (24 rows of 16) and a vocabulary tile at the boundary, with the rows
#: whole over narrow vocabulary tiles, and at one row tile a sublane tile
TILES = {
    "own": None,
    "16x128": (16, 128),
    "32x128": (32, 128),
    "8x256": (8, 256),
}


@pytest.fixture(params=list(TILES))
def how(request):
    return dict(interpret=True, tiles=TILES[request.param])


def _through_kernels(mp, **how):
    """The objectives take deferred logits through the kernels, whatever
    ``logits_at_rest`` would say of this CPU, their size and their dtype:
    what a TPU's step does with bf16 logits of ``REST_BYTES`` a shard and
    over."""
    mp.setattr(objectives, "logits_at_rest", lambda pred: None)
    mp.setattr(fused_ce, "head_rows", functools.partial(head_rows, **how))


@pytest.fixture
def interpreted(monkeypatch):
    _through_kernels(monkeypatch, interpret=True)


def fused(h, table, targets, ignore_id=0, **how):
    return head_cross_entropy(h, table, targets, ignore_id, **how)[0]


def _reference(h, table, targets, ignore_id=0):
    logits = h.astype(jnp.float32) @ table.astype(jnp.float32).T
    per = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    valid = targets != ignore_id
    return jnp.sum(jnp.where(valid, per, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)


def _data(N=24, d=16, V=64, seed=0, pad_tail=4):
    ks = jax.random.split(jax.random.key(seed), 3)
    h = jax.random.normal(ks[0], (N, d))
    table = jax.random.normal(ks[1], (V, d)) * 0.1
    targets = jax.random.randint(ks[2], (N,), 1, V)
    targets = targets.at[-pad_tail:].set(0)
    return h, table, targets


def _grads_close(got, want, rtol=1e-4, atol=1e-6):
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                   atol=atol)


def test_matches_reference_loss(how):
    h, table, targets = _data()
    np.testing.assert_allclose(float(fused(h, table, targets, **how)),
                               float(_reference(h, table, targets)),
                               rtol=1e-5)


@pytest.mark.parametrize("tiles", [(32, 128), (16, 256)])
def test_matches_with_single_block(tiles):
    """One vocabulary tile wider than the vocabulary (64 of 128, of 256)."""
    h, table, targets = _data(seed=1)
    got = fused(h, table, targets, interpret=True, tiles=tiles)
    np.testing.assert_allclose(float(got),
                               float(_reference(h, table, targets)),
                               rtol=1e-5)


def test_gradients_match_reference(how):
    h, table, targets = _data(seed=2)
    g_fused = jax.grad(lambda h, w: fused(h, w, targets, **how),
                       argnums=(0, 1))(h, table)
    g_ref = jax.grad(lambda h, w: _reference(h, w, targets),
                     argnums=(0, 1))(h, table)
    _grads_close(g_fused, g_ref)


def test_batched_sequence_shape(how):
    """(B, T, d) activations + (B, T) targets — the LM calling shape."""
    h, table, targets = _data(N=32, seed=3)
    got = fused(h.reshape(4, 8, -1), table, targets.reshape(4, 8), **how)
    np.testing.assert_allclose(float(got),
                               float(_reference(h, table, targets)),
                               rtol=1e-5)


def test_all_padding_is_finite(how):
    h, table, _ = _data(seed=4)
    targets = jnp.zeros((24,), jnp.int32)  # everything ignored
    assert float(fused(h, table, targets, **how)) == 0.0
    g = jax.grad(lambda h, w: fused(h, w, targets, **how),
                 argnums=(0, 1))(h, table)
    for x in g:
        assert np.isfinite(np.asarray(x)).all()
        np.testing.assert_allclose(np.asarray(x), 0.0, atol=1e-8)


@pytest.mark.parametrize("V,tiles", [(300, (16, 128)), (300, (32, 256)),
                                     (257, (16, 128)), (129, (48, 128))])
def test_indivisible_block_pads(V, tiles):
    """A vocabulary no tile divides ends in a boundary tile (of 44, of 1
    column): masked on the way in, never stored on the way out; and rows
    no tile divides (24 of 16, of 32, of 48) are padded."""
    h, table, targets = _data(V=V)
    kw = dict(interpret=True, tiles=tiles)
    np.testing.assert_allclose(float(fused(h, table, targets, **kw)),
                               float(_reference(h, table, targets)),
                               rtol=1e-5)
    _grads_close(
        jax.grad(lambda h, w: fused(h, w, targets, **kw),
                 argnums=(0, 1))(h, table),
        jax.grad(lambda h, w: _reference(h, w, targets),
                 argnums=(0, 1))(h, table))


def test_bf16_activations(how):
    """bf16 hidden states: the table goes to the product in bf16 too (as
    the MXU takes both), f32 accumulation and statistics."""
    h, table, targets = _data(seed=5)
    got = fused(h.astype(jnp.bfloat16), table, targets, **how)
    np.testing.assert_allclose(float(got),
                               float(_reference(h, table, targets)),
                               rtol=2e-2)
    rounded = _reference(h.astype(jnp.bfloat16),
                         table.astype(jnp.bfloat16), targets)
    np.testing.assert_allclose(float(got), float(rounded), rtol=1e-5)


def test_under_jit_and_grad_jit(how):
    h, table, targets = _data(seed=6)
    f = jax.jit(lambda h, w: fused(h, w, targets, **how))
    g = jax.jit(jax.grad(f, argnums=(0, 1)))
    np.testing.assert_allclose(float(f(h, table)),
                               float(_reference(h, table, targets)),
                               rtol=1e-5)
    _grads_close(g(h, table),
                 jax.grad(lambda h, w: _reference(h, w, targets),
                          argnums=(0, 1))(h, table))


def test_causal_lm_fused_loss_matches_logits_path(interpreted):
    """Model-level: the token loss of a CausalLM that defers its logits
    (the kernels) == softmax-CE over CausalLM.logits_from, pad positions
    excluded."""
    from distributed_deep_learning_tpu.models.transformer import CausalLM

    model = CausalLM(vocab_size=97, num_layers=2, d_model=32, num_heads=4,
                     mlp_dim=64, max_len=64, with_logits="deferred")
    toks = jax.random.randint(jax.random.key(0), (2, 17), 1, 97)
    toks = toks.at[1, 12:].set(0)  # padding tail
    params = model.init(jax.random.key(1), toks[:, :-1])
    pred = model.apply(params, toks[:, :-1], train=False)
    targets = toks[:, 1:]

    fused_loss = token_cross_entropy(pred, targets)
    logits = model.logits_from(params, pred.hidden)
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = targets != 0
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    ref = -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)
    np.testing.assert_allclose(np.asarray(fused_loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_causal_lm_is_causal():
    """Hidden state at position t must not depend on tokens after t."""
    from distributed_deep_learning_tpu.models.transformer import CausalLM

    model = CausalLM(vocab_size=50, num_layers=2, d_model=32, num_heads=4,
                     mlp_dim=64, max_len=32)
    t1 = jax.random.randint(jax.random.key(0), (1, 16), 1, 50)
    t2 = t1.at[0, 10:].set(1 + (t1[0, 10:] % 49))  # change the tail only
    params = model.init(jax.random.key(1), t1)
    h1 = model.apply(params, t1, train=False)
    h2 = model.apply(params, t2, train=False)
    np.testing.assert_allclose(np.asarray(h1[:, :10]),
                               np.asarray(h2[:, :10]), rtol=2e-5, atol=2e-5)


def test_prime_vocab_full_block_width(how):
    """A prime vocabulary (GPT-2's 50,257 is prime) runs at full tile
    width: values and gradients match the logits at rest."""
    h, table, targets = _data(V=97)
    np.testing.assert_allclose(float(fused(h, table, targets, **how)),
                               float(_reference(h, table, targets)),
                               rtol=1e-5)
    _grads_close(
        jax.grad(lambda h, t: fused(h, t, targets, **how),
                 argnums=(0, 1))(h, table),
        jax.grad(lambda h, t: _reference(h, t, targets),
                 argnums=(0, 1))(h, table))


def test_causal_lm_loss_threads_pad_id(interpreted):
    """The token loss of deferred logits must exclude the ``pad_id``
    positions, and with ``pad_id=None`` count EVERY position (imported
    GPT-2, where id 0 is a real token), instead of hard-coding id 0."""
    from distributed_deep_learning_tpu.models.transformer import CausalLM

    model = CausalLM(vocab_size=61, num_layers=1, d_model=16, num_heads=2,
                     mlp_dim=32, max_len=32, with_logits="deferred")
    toks = jax.random.randint(jax.random.key(0), (2, 13), 1, 61)
    toks = toks.at[1, 9:].set(0)  # tail of id-0 positions
    params = model.init(jax.random.key(1), toks[:, :-1])
    pred = model.apply(params, toks[:, :-1], train=False)
    targets = toks[:, 1:]

    def ref(ignore):
        logp = jax.nn.log_softmax(model.logits_from(params, pred.hidden),
                                  axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None],
                                     axis=-1)[..., 0]
        valid = targets != ignore
        return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)

    pad0 = float(token_cross_entropy(pred, targets))
    np.testing.assert_allclose(pad0, float(ref(0)), rtol=1e-5)
    # pad_id=None: id-0 sites now COUNT (denominator grows, value shifts)
    none = float(token_cross_entropy(pred, targets, pad_id=None))
    np.testing.assert_allclose(none, float(ref(-1)), rtol=1e-5)
    assert pad0 != pytest.approx(none)


def test_token_cross_entropy_pad_id_param():
    """objectives.token_cross_entropy: the ignored id is a parameter now
    (``pad_id=None`` scores every position)."""
    logits = jax.random.normal(jax.random.key(0), (2, 6, 11))
    targets = jnp.array([[3, 0, 5, 0, 1, 2], [4, 4, 0, 0, 0, 9]])
    default = token_cross_entropy(logits, targets)
    explicit0 = token_cross_entropy(logits, targets, pad_id=0)
    np.testing.assert_allclose(float(default), float(explicit0))

    none = token_cross_entropy(logits, targets, pad_id=None)
    per = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    np.testing.assert_allclose(float(none), float(jnp.mean(per)), rtol=1e-6)

    pad9 = token_cross_entropy(logits, targets, pad_id=9)
    valid = targets != 9
    want = jnp.sum(jnp.where(valid, per, 0.0)) / jnp.sum(valid)
    np.testing.assert_allclose(float(pad9), float(want), rtol=1e-6)


# --------------------------------------------------------------------------
# against token_cross_entropy + prediction_metrics on real f32 logits
# --------------------------------------------------------------------------

def _lm_data(B=3, T=12, d=32, V=131, seed=0, dtype=jnp.bfloat16):
    """A head whose argmax is the target at about half the positions, id 0
    among the targets, a prime vocabulary (131 = 128 + 3)."""
    ks = jax.random.split(jax.random.key(seed), 4)
    h = jax.random.normal(ks[0], (B, T, d)).astype(dtype)
    table = jax.random.normal(ks[1], (V, d)) * 0.3
    best = jnp.argmax(h.astype(jnp.float32)
                      @ table.astype(dtype).astype(jnp.float32).T, axis=-1)
    drawn = jax.random.randint(ks[2], (B, T), 0, V)
    targets = jnp.where(jax.random.bernoulli(ks[3], 0.5, (B, T)), best,
                        drawn).astype(jnp.int32)
    return h, table, targets.at[0, -3:].set(0)


def _at_rest(h, table, targets, pad_id, eps):
    """The parent's step: f32 logits at rest (from the operands as the MXU
    takes them: both in the compute dtype), then the two objectives."""
    logits = jnp.einsum("...d,vd->...v", h.astype(jnp.float32),
                        table.astype(h.dtype).astype(jnp.float32),
                        precision="highest")
    loss = token_cross_entropy(logits, targets, eps, pad_id)
    return loss, prediction_metrics(logits, targets, loss)


@pytest.mark.parametrize("eps", [0.0, 0.1])
@pytest.mark.parametrize("pad_id", [0, None])
def test_deferred_logits_score_like_logits_at_rest(how, pad_id, eps):
    """loss, correct, count, dh and dW through the objectives on deferred
    logits == on the f32 logits: prime vocabulary with a boundary tile,
    bf16 hidden states, with and without a padding id and smoothing."""
    h, table, targets = _lm_data()

    def deferred(h, table):
        with pytest.MonkeyPatch.context() as mp:
            _through_kernels(mp, **how)
            pred = DeferredLogits(h, table)
            loss = token_cross_entropy(pred, targets, eps, pad_id)
            return loss, prediction_metrics(pred, targets, loss)

    (got, got_m), got_g = jax.value_and_grad(
        deferred, argnums=(0, 1), has_aux=True)(h, table)
    (want, want_m), want_g = jax.value_and_grad(
        lambda h, w: _at_rest(h, w, targets, pad_id, eps), argnums=(0, 1),
        has_aux=True)(h, table)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    assert int(got_m["correct"]) == int(want_m["correct"]) > 0
    assert int(got_m["count"]) == int(want_m["count"]) \
        == int(jnp.sum(targets != 0))
    assert got_g[0].dtype == h.dtype and got_g[1].dtype == table.dtype
    # the cotangent of a tile is rounded to bf16 before its two products
    # (what a TPU's MXU does to the logits' f32 cotangent), dh once more
    for a, b in zip(got_g, want_g):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        assert np.linalg.norm(a - b) < 1e-2 * np.linalg.norm(b)


def test_rows_are_the_logits_statistics(how):
    """head_rows: log-sum-exp, target logit and argmax a position; a tie
    goes to the earlier id, a target outside the vocabulary reads 0."""
    h, table, targets = _lm_data(dtype=jnp.float32)
    table = table.at[7].set(table[5])          # ids 5 and 7 always tie
    targets = targets.at[1, 0].set(-1)
    lse, zt, best = head_rows(h, table, targets, **how)
    logits = h @ table.T
    np.testing.assert_allclose(np.asarray(lse), np.asarray(
        jax.nn.logsumexp(logits, axis=-1)), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(best),
                                  np.asarray(jnp.argmax(logits, axis=-1)))
    assert 7 not in np.asarray(best)
    picked = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None],
                                 axis=-1)[..., 0]
    np.testing.assert_allclose(np.asarray(zt), np.asarray(
        jnp.where(targets >= 0, picked, 0.0)), rtol=1e-5, atol=1e-6)


def _tiny_lm(with_logits, **kw):
    from distributed_deep_learning_tpu.models.transformer import CausalLM

    return CausalLM(vocab_size=131, num_layers=1, d_model=32, num_heads=2,
                    mlp_dim=64, max_len=16, with_logits=with_logits, **kw)


@pytest.mark.parametrize("tie_head", [True, False])
def test_tied_table_gets_both_contributions(tie_head, interpreted):
    """The gradient of every parameter through the deferred head == through
    the logits: the tied table's holds the lookup's rows AND the head's."""
    toks = jax.random.randint(jax.random.key(0), (2, 13), 1, 131)
    x, y = toks[:, :-1], toks[:, 1:].at[1, 8:].set(0)
    at_rest = _tiny_lm(True, tie_head=tie_head)
    deferred = _tiny_lm("deferred", tie_head=tie_head)
    params = at_rest.init(jax.random.key(1), x)

    def loss(model, params):
        return token_cross_entropy(model.apply(params, x), y)

    assert isinstance(deferred.apply(params, x), DeferredLogits)
    want = jax.grad(lambda p: loss(at_rest, p))(params)
    got = jax.grad(lambda p: loss(deferred, p))(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6, err_msg=str(path))
    table = got["params"]["embed"]["tok"]["embedding"]
    only_head = jax.grad(lambda p: head_cross_entropy(
        jax.lax.stop_gradient(at_rest.clone(with_logits=False).apply(
            params, x)), p["params"]["embed"]["tok"]["embedding"], y,
        interpret=True)[0])(
                params)["params"]["embed"]["tok"]["embedding"]
    if tie_head:
        assert float(jnp.abs(table - only_head).max()) > 1e-4  # the lookup's
    else:
        assert "head" in got["params"]


def test_clones_keep_their_meaning():
    """``with_logits=True`` still hands real logits and the decode clone
    hidden states, whatever the training model deferred."""
    from distributed_deep_learning_tpu.models.transformer import (
        make_decode_model)

    model = _tiny_lm("deferred")
    x = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), x)
    pred = model.apply(params, x)
    assert pred.hidden.shape == (1, 8, 32) and pred.table.shape == (131, 32)
    logits = model.clone(with_logits=True).apply(params, x)
    assert logits.shape == (1, 8, 131)
    # small logits rest whole: the very array the logits model computes
    np.testing.assert_array_equal(
        np.asarray(fused_ce.logits_at_rest(pred)), np.asarray(logits))
    assert make_decode_model(model).with_logits is False


def test_small_logits_rest_and_large_ones_do_not(monkeypatch):
    """The rule by a shard's shape, on a TPU (steered from outside, as the
    AOT tests steer it): bf16 hidden states whose f32 logits are under
    ``REST_BYTES`` are multiplied out (cell 4's 2 rows a chip: 0.38 GiB),
    larger ones taken a block at a time (cell 1's 16 rows: 3.07 GiB);
    under a mesh it is a SHARD's rows that count; either way the same loss
    and count."""
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    rest = fused_ce.REST_BYTES
    assert 2 * 1024 * 50257 * 4 < rest <= 16 * 1024 * 50257 * 4
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fused_ce, "head_rows",
                        functools.partial(head_rows, interpret=True))
    h, table, targets = _lm_data(B=4)
    pred = DeferredLogits(h, table)
    whole = 4 * 12 * 131 * 4
    want = _at_rest(h, table, targets, 0, 0.0)
    for limit, rests in ((whole + 1, True), (whole, False)):
        monkeypatch.setattr(fused_ce, "REST_BYTES", limit)
        assert (fused_ce.logits_at_rest(pred) is not None) == rests
        loss = token_cross_entropy(pred, targets)
        got = prediction_metrics(pred, targets, loss)
        # whole, the table goes to this CPU's product in f32 (a TPU's
        # DEFAULT precision rounds it to bf16, as the kernels do)
        np.testing.assert_allclose(float(loss), float(want[0]), rtol=2e-4)
        assert int(got["correct"]) == int(want[1]["correct"])
        assert int(got["count"]) == int(want[1]["count"])
    mesh = build_mesh({"data": 4}, jax.devices()[:4])
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        for limit, rests in ((whole // 4 + 1, True), (whole // 4, False)):
            monkeypatch.setattr(fused_ce, "REST_BYTES", limit)
            # a fresh function a limit: eval_shape keeps what it traced
            assert (jax.eval_shape(lambda p: fused_ce.logits_at_rest(p),
                                   pred) is not None) == rests


def test_where_no_kernel_runs_logits_rest_whole(monkeypatch):
    """Off a TPU, and on one from f32 hidden states or where the mesh's
    batch axes do not divide the rows, deferred logits are multiplied out
    whatever their size: the kernels are the only way to take them a block
    at a time, they were timed in bf16 alone, and ``head_rows`` says so of
    rows it cannot split."""
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    h, table, targets = _lm_data(B=3)
    pred = DeferredLogits(h, table)
    monkeypatch.setattr(fused_ce, "REST_BYTES", 0)
    logits = fused_ce.logits_at_rest(pred)          # this CPU
    np.testing.assert_allclose(np.asarray(logits), np.asarray(jnp.einsum(
        "btd,vd->btv", h.astype(jnp.float32), table)), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(token_cross_entropy(pred, targets)),
        np.asarray(token_cross_entropy(logits, targets)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fused_ce.logits_at_rest(pred) is None
    assert fused_ce.logits_at_rest(DeferredLogits(
        h.astype(jnp.float32), table)) is not None
    mesh = build_mesh({"data": 2}, jax.devices()[:2])
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):    # 3 rows / 2
        assert jax.eval_shape(fused_ce.logits_at_rest, pred) is not None
        with pytest.raises(ValueError, match="no kernel"):
            jax.eval_shape(functools.partial(head_rows, interpret=True),
                           h, table, targets)


@pytest.fixture
def gpt_steps(monkeypatch):
    """(state, x, y, steps of the at-rest model, steps of the deferred
    one, the mesh), as the CLI builds a ``gpt`` step on the devices."""
    def build(mesh_shape, rows=8, blocks=True, **step_kw):
        from jax.sharding import PartitionSpec as P

        from distributed_deep_learning_tpu.obs.runlog import compile_log
        from distributed_deep_learning_tpu.runtime.mesh import build_mesh
        from distributed_deep_learning_tpu.train.state import (
            create_train_state)
        from distributed_deep_learning_tpu.train.step import (make_step_fns,
                                                              place_state)

        n = int(np.prod(list(mesh_shape.values())))
        mesh = build_mesh(mesh_shape, jax.devices()[:n])
        if blocks:
            _through_kernels(monkeypatch, interpret=True)
        toks = jax.random.randint(jax.random.key(0), (rows, 13), 1, 131)
        x, y = toks[:, :-1], toks[:, 1:].at[1, 8:].set(0)
        out = {}
        for name, with_logits in (("rest", True), ("deferred", "deferred")):
            state = place_state(create_train_state(
                _tiny_lm(with_logits), jax.random.key(1), x[:1],
                optax.sgd(0.1)), mesh)
            compile_log.mark(name)
            train, evaluate = make_step_fns(
                mesh, lambda p, t: token_cross_entropy(p, t, 0.1),
                state_spec=P(), **step_kw)
            new, metrics = train(state, x, y)
            out[name] = (new, metrics, evaluate(new, x, y),
                         dict((e, t) for e, _, t in compile_log.notes()
                              if e == "fused_head"))
        return out
    return build


@pytest.mark.parametrize("mesh_shape", [{"data": 1}, {"data": 4},
                                        {"data": 2, "fsdp": 2}],
                         ids=["one", "data4", "data2-fsdp2"])
def test_step_on_deferred_logits_is_the_step_on_logits(gpt_steps,
                                                       mesh_shape):
    """``make_step_fns`` on a model that defers its logits: the same
    metrics and the same new parameters as on the logits, on one device
    and with a shard's rows on the shard; the ``fused_head`` note says
    which it was."""
    out = gpt_steps(mesh_shape)
    (new, metrics, evald, note), (want, want_m, want_e, want_note) = \
        out["deferred"], out["rest"]
    for got_m, ref_m in ((metrics, want_m), (evald, want_e)):
        np.testing.assert_allclose(float(got_m["loss"]),
                                   float(ref_m["loss"]), rtol=1e-5)
        assert int(got_m["correct"]) == int(ref_m["correct"])
        assert int(got_m["count"]) == int(ref_m["count"])
    for a, b in zip(jax.tree.leaves(new.params),
                    jax.tree.leaves(want.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6)
    rows = 8 * 12 // int(np.prod(list(mesh_shape.values())))
    tn, tv = fused_ce._tiling(rows, 32, 131, itemsize=4)
    assert note["fused_head"] == (
        f"calls=1 rows={rows} vocab=131 path=pallas tiles={tn}x{tv} "
        "logits_at_rest=0")
    assert want_note["fused_head"] == "calls=0 path=logits logits_at_rest=1"


def test_step_on_small_deferred_logits_takes_them_whole(gpt_steps):
    """Where the rule says the logits rest (here: off a TPU) the deferred
    step multiplies them out: the logits model's numbers to the bit, and
    the note says so."""
    out = gpt_steps({"data": 4}, blocks=False)
    (new, metrics, _, note), (want, want_m, _, _) = \
        out["deferred"], out["rest"]
    assert float(metrics["loss"]) == float(want_m["loss"])
    for a, b in zip(jax.tree.leaves(new.params),
                    jax.tree.leaves(want.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert note["fused_head"] == (
        "calls=1 rows=24 vocab=131 path=logits tiles=none logits_at_rest=1")


def test_step_under_remat_and_accumulation(gpt_steps, interpreted):
    """The deferred value crosses ``jax.checkpoint`` (a registered pytree)
    and the accumulating builder's scan."""
    from jax.sharding import PartitionSpec as P

    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from distributed_deep_learning_tpu.train.accumulate import (
        make_accum_step_fns)
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.train.step import place_state

    out = gpt_steps({"data": 1}, remat=True)
    np.testing.assert_allclose(float(out["deferred"][1]["loss"]),
                               float(out["rest"][1]["loss"]), rtol=1e-5)
    mesh = build_mesh({"data": 1}, jax.devices()[:1])
    toks = jax.random.randint(jax.random.key(0), (8, 13), 1, 131)
    x, y = toks[:, :-1], toks[:, 1:]
    losses = []
    for with_logits in (True, "deferred"):
        state = place_state(create_train_state(
            _tiny_lm(with_logits), jax.random.key(1), x[:1],
            optax.sgd(0.1)), mesh)
        train, _ = make_accum_step_fns(mesh, token_cross_entropy,
                                       accum_steps=2, state_spec=P())
        losses.append(float(train(state, x, y)[1]["loss"]))
    np.testing.assert_allclose(losses[1], losses[0], rtol=1e-5)


def test_tiling_is_a_function_of_the_shapes():
    """Cell 1's and cell 4's per-chip shapes, a small call, and VMEM's
    say: wide hidden states in f32 narrow the row tile, then the columns."""
    assert fused_ce._tiling(16384, 1024, 50257) == (512, 2048)
    assert fused_ce._tiling(2048, 1600, 50257) == (512, 2048)
    assert fused_ce._tiling(40, 32, 97) == (48, 128)
    tn, tv = fused_ce._tiling(16384, 8192, 50257, itemsize=4)
    assert (tn, tv) == (128, 256)
    assert fused_ce._held(tn, tv, 8192, 4) <= fused_ce.VMEM_BLOCKS
