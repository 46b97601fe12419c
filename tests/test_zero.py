"""ZeRO-1 / FSDP sharding rules: numerics match pure DP, state is sharded."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_deep_learning_tpu.models.mlp import MLP
from distributed_deep_learning_tpu.parallel.zero import (
    fsdp_state_spec, leaf_shard_spec, zero1_state_spec,
)
from distributed_deep_learning_tpu.runtime.mesh import build_mesh
from distributed_deep_learning_tpu.train.objectives import cross_entropy_loss
from distributed_deep_learning_tpu.train.state import create_train_state
from distributed_deep_learning_tpu.train.step import make_step_fns, place_state
from jax.sharding import PartitionSpec as P


def _setup(mesh, state_spec_fn=None):
    model = MLP(hidden_size=64, num_hidden_layers=2, num_classes=8)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (16, 32), np.float32))
    y = jax.nn.one_hot(jnp.arange(16) % 8, 8)
    state = create_train_state(model, jax.random.key(0), x[:1],
                               optax.adam(1e-2))
    spec = (state_spec_fn(state, mesh) if state_spec_fn else P())
    state = place_state(state, mesh, spec)
    train_step, _ = make_step_fns(mesh, cross_entropy_loss, state_spec=spec)
    return state, train_step, x, y


class TestLeafSpec:
    def test_shards_largest_divisible_dim(self):
        leaf = jnp.zeros((3, 256))
        assert leaf_shard_spec(leaf, 4, min_leaf_size=1) == P(None, "fsdp")

    def test_small_or_indivisible_replicated(self):
        assert leaf_shard_spec(jnp.zeros((4, 4)), 4) == P()  # too small
        assert leaf_shard_spec(jnp.zeros((3, 5)), 4, min_leaf_size=1) == P()
        assert leaf_shard_spec(jnp.zeros(()), 4, min_leaf_size=0) == P()


class TestZero1:
    def test_opt_state_is_sharded_params_replicated(self):
        mesh = build_mesh({"data": 2, "fsdp": 4})
        state, step, x, y = _setup(
            mesh, lambda s, m: zero1_state_spec(s, m, min_leaf_size=16))
        state, _ = step(state, x, y)
        # adam mu for a (64,64) kernel must live sharded over fsdp
        mu = state.opt_state[0].mu["DenseReLU_1"]["Dense_0"]["kernel"]
        assert "fsdp" in jax.tree.leaves(
            [mu.sharding.spec], is_leaf=lambda s: isinstance(s, P))[0]
        kernel = state.params["DenseReLU_1"]["Dense_0"]["kernel"]
        assert kernel.sharding.spec == P()

    def test_numerics_match_pure_dp(self):
        mesh = build_mesh({"data": 2, "fsdp": 4})
        s_dp, step_dp, x, y = _setup(mesh)
        s_z1, step_z1, _, _ = _setup(
            mesh, lambda s, m: zero1_state_spec(s, m, min_leaf_size=16))
        for _ in range(3):
            s_dp, m_dp = step_dp(s_dp, x, y)
            s_z1, m_z1 = step_z1(s_z1, x, y)
        np.testing.assert_allclose(float(m_dp["loss"]), float(m_z1["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(s_dp.params),
                        jax.tree.leaves(s_z1.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


class TestFsdp:
    def test_params_sharded_and_numerics(self):
        mesh = build_mesh({"data": 2, "fsdp": 4})
        s_dp, step_dp, x, y = _setup(mesh)
        s_fs, step_fs, _, _ = _setup(
            mesh, lambda s, m: fsdp_state_spec(s, m, min_leaf_size=16))
        kernel = s_fs.params["DenseReLU_1"]["Dense_0"]["kernel"]
        assert kernel.sharding.spec != P()
        for _ in range(2):
            s_dp, m_dp = step_dp(s_dp, x, y)
            s_fs, m_fs = step_fs(s_fs, x, y)
        np.testing.assert_allclose(float(m_dp["loss"]), float(m_fs["loss"]),
                                   rtol=1e-5)


# --- activations pinned to the batch axes (runtime.batch_pin) -------------

def _tiny_lm(attention_fn=None):
    from distributed_deep_learning_tpu.models.transformer import CausalLM

    return CausalLM(vocab_size=64, num_layers=2, d_model=32, num_heads=4,
                    mlp_dim=64, max_len=16, with_logits=True, pad_id=None,
                    attention_fn=attention_fn)


def _lm_step(mesh_shape, devices, fsdp: bool, attention_fn=None):
    """One SGD step at rate 1 of the tiny LM through the jitted step: the
    loss, and the parameters after it (start minus the gradient)."""
    from distributed_deep_learning_tpu.train.objectives import (
        token_cross_entropy)

    mesh = build_mesh(mesh_shape, devices)
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, 64, (8, 17)),
                         jnp.int32)
    state = create_train_state(_tiny_lm(attention_fn), jax.random.key(0),
                               tokens[:1, :-1], optax.sgd(1.0))
    spec = fsdp_state_spec(state, mesh, min_leaf_size=16) if fsdp else P()
    if fsdp:
        assert spec.params["layer_0"]["Dense_0"]["kernel"] != P()
    step, _ = make_step_fns(mesh, token_cross_entropy, state_spec=spec)
    state, metrics = step(place_state(state, mesh, spec), tokens[:, :-1],
                          tokens[:, 1:])
    return float(metrics["loss"]), jax.tree.map(np.asarray, state.params)


@pytest.mark.parametrize("mesh_shape", [{"fsdp": 4}, {"data": 2, "fsdp": 2}],
                         ids=["fsdp4", "data2-fsdp2"])
def test_pinned_fsdp_step_matches_the_unsharded_step(mesh_shape):
    """With every activation held to the batch axes the sharded step is
    still the same arithmetic: loss and gradient (SGD at rate 1 leaves
    start - gradient) equal one device's, by this file's tolerances."""
    from distributed_deep_learning_tpu import obs

    loss_1, after_1 = _lm_step({"data": 1}, jax.devices()[:1], fsdp=False)
    obs.compile_log.mark("test")
    loss_n, after_n = _lm_step(mesh_shape, jax.devices()[:4], fsdp=True)
    axes = ",".join(a for a in ("data", "fsdp") if a in mesh_shape)
    notes = [e[3] for e in obs.compile_log.since_mark()
             if e[:2] == ("batch_pins", "jit(train_step)")]
    assert notes == [f"axes={axes} sites=19"]
    np.testing.assert_allclose(loss_n, loss_1, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(after_n), jax.tree.leaves(after_1)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def _constraints(jaxpr) -> list:
    """The spec of every ``sharding_constraint`` equation in `jaxpr`,
    nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sharding_constraint":
            out.append(eqn.params["sharding"].spec)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _constraints(sub)
    return out


@pytest.mark.parametrize("mesh_shape, batch", [
    (None, None), ({"data": 1}, None), ({"data": 1, "model": 2}, None),
    ({"fsdp": 4}, "fsdp"), ({"data": 2, "model": 2}, "data")],
    ids=["no-mesh", "one-device", "model-axis", "fsdp4", "data2-model2"])
def test_pin_emits_nothing_where_no_batch_axis_is_split(monkeypatch,
                                                        mesh_shape, batch):
    """The helper adapts to what the ambient mesh splits: no mesh, one
    device, a ``model`` axis alone -> the jaxpr is the one the model gives
    with the helper stubbed to the identity, no ``sharding_constraint`` in
    it; a split batch axis -> every site is pinned, its batch dimension
    to that axis and every other dimension left to the partitioner."""
    import contextlib

    from distributed_deep_learning_tpu.models import transformer

    model = _tiny_lm()
    tokens = jnp.ones((8, 16), jnp.int32)
    params = model.init(jax.random.key(0), tokens[:1])

    def trace():
        ambient = contextlib.nullcontext() if mesh_shape is None else \
            jax.sharding.use_abstract_mesh(build_mesh(
                mesh_shape,
                jax.devices()[:int(np.prod(list(mesh_shape.values())))]
            ).abstract_mesh)
        with ambient:
            return jax.make_jaxpr(
                lambda p, t: model.apply(p, t))(params, tokens)

    specs = _constraints(trace().jaxpr)
    if batch is None:
        assert specs == []
        with_helper = str(trace())
        monkeypatch.setattr(transformer, "pin_batch", lambda x: x)
        assert with_helper == str(trace())
    else:
        assert len(specs) == 19
        assert all(spec[0] == batch and set(spec[1:]) == {P.UNCONSTRAINED}
                   for spec in specs), specs


def test_obs_report_prints_what_the_step_pinned(tmp_path):
    """The compile log's ``batch_pins`` note reaches the ``--obs`` stream
    (``obs_programs``) and ``scripts/obs_report.py`` prints it: the axes
    and the sites under a split batch axis, ``none`` and 0 on one device."""
    import os
    import subprocess
    import sys

    from distributed_deep_learning_tpu import obs

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for mesh_shape, devices, said in (
            ({"fsdp": 4}, jax.devices()[:4], "axes=fsdp sites=19"),
            ({"data": 1}, jax.devices()[:1], "axes=none sites=0")):
        obs.compile_log.mark("test")
        path = str(tmp_path / f"{len(devices)}.jsonl")
        telemetry = obs.RunTelemetry(path)
        _lm_step(mesh_shape, devices, fsdp=len(devices) > 1)
        telemetry.close()
        text = subprocess.run(
            [sys.executable, os.path.join(repo, "scripts", "obs_report.py"),
             path], capture_output=True, text=True, check=True).stdout
        assert f"jit(train_step): batch_pins {said}" in text, text


@pytest.mark.parametrize("mesh_shape", [{"fsdp": 4}, {"data": 1}],
                         ids=["fsdp4", "one-device"])
def test_flash_step_says_how_its_kernel_calls_tiled(tmp_path, mesh_shape):
    """The compile log's ``flash_layout`` note of a step that attends
    through the flash kernel (two layers, four heads of 8: all four in one
    32-lane block, nothing transposed; per shard under FSDP, the same
    shapes a chip): in ``obs.compile_log.notes()`` beside ``batch_pins``,
    in the ``--obs`` stream (``obs_programs``) and in what
    ``scripts/obs_report.py`` prints; and the step is the dense one's."""
    import os
    import subprocess
    import sys

    from distributed_deep_learning_tpu import obs
    from distributed_deep_learning_tpu.ops.attention_pallas import (
        make_attention_fn)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    devices = jax.devices()[:int(np.prod(list(mesh_shape.values())))]
    fsdp = len(devices) > 1
    loss_dense, _ = _lm_step(mesh_shape, devices, fsdp=fsdp)
    obs.compile_log.mark("test")
    path = str(tmp_path / "obs.jsonl")
    telemetry = obs.RunTelemetry(path)
    loss, _ = _lm_step(mesh_shape, devices, fsdp=fsdp,
                       attention_fn=make_attention_fn())
    telemetry.close()
    np.testing.assert_allclose(loss, loss_dense, rtol=1e-5)
    said = "calls=2 lanes_a_block=32 heads_a_block=4 transposed=0"
    notes = obs.compile_log.notes()
    assert ("flash_layout", "jit(train_step)", said) in notes, notes
    assert sorted(n[0] for n in notes if n[1] == "jit(train_step)") == [
        "batch_pins", "flash_layout", "fused_head"]
    text = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "obs_report.py"),
         path], capture_output=True, text=True, check=True).stdout
    assert f"jit(train_step): flash_layout {said}" in text, text
