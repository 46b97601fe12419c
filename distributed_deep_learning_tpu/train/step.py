"""Jitted train/eval steps — the whole reference hot loop as one XLA program.

The reference's hot path is eager per-op dispatch plus, when distributed, one
blocking NCCL all-reduce *per parameter* between backward and step
(``CNN/main.py:84-89,137-139``, quirk Q8).  Here forward, loss, backward,
gradient mean and optimizer update compile into a single program: the batch
arrives sharded over the ``data``/``fsdp`` mesh axes, so XLA inserts one
fused gradient all-reduce over ICI — the per-param loop and its bugs (Q1/Q2)
are impossible by construction.

Gradient sync is therefore not a bolt-on ``sync(model)`` callable but a
consequence of sharding: replicated-out params + sharded-in batch ⇒ psum.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_deep_learning_tpu.data.loader import BATCH_AXES
from distributed_deep_learning_tpu.obs.runlog import compile_log
from distributed_deep_learning_tpu.ops.fused_ce import note_text as head_text
from distributed_deep_learning_tpu.runtime.batch_pin import pins_text
from distributed_deep_learning_tpu.train.objectives import prediction_metrics
from distributed_deep_learning_tpu.train.state import TrainState
from distributed_deep_learning_tpu.utils.config import REMAT_POLICIES

LossFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


def _state_sharding(mesh: Mesh, state_spec):
    """A single PartitionSpec broadcasts over the whole state; a
    TrainState-shaped pytree of specs (e.g. from
    :func:`..parallel.zero.zero1_state_spec`) shards per leaf."""
    if isinstance(state_spec, P):
        return NamedSharding(mesh, state_spec)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), state_spec)


def under_mesh(mesh: Mesh):
    """Decorator for a step body: trace it with `mesh` as the ambient one,
    so code deep in the model can see how its inputs are split — the flash
    kernel must run per shard (``ops.attention_pallas._per_shard``) and
    the activations stay on the batch axes (``runtime.batch_pin``).  What
    either says of the program as it is traced becomes its notes in the
    compile log: ``batch_pins``, what this trace pinned, the kernel's
    ``flash_layout``, how its calls tiled their operands, and
    ``fused_head``, whether the token loss took its logits a block at a
    time (``logits_at_rest=0``) or was handed them whole."""
    def decorate(step):
        @functools.wraps(step)
        def traced(*args):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh), \
                    compile_log.notes_for(f"jit({step.__name__})",
                                          batch_pins=pins_text,
                                          fused_head=head_text):
                return step(*args)
        return traced
    return decorate


def _remat_policy(name: str):
    """Resolve a REMAT_POLICIES name (the what-may-backward-reuse table,
    shared with the CLI choices) to a jax.checkpoint policy; "nothing"
    is classic full remat, the dots policies keep MXU outputs."""
    try:
        attr = REMAT_POLICIES[name]
    except KeyError:
        raise ValueError(f"unknown remat policy {name!r}; choose from "
                         f"{sorted(REMAT_POLICIES)}") from None
    return getattr(jax.checkpoint_policies, attr) if attr else None


def make_step_fns(mesh: Mesh, loss_fn: LossFn, *,
                  state_spec=P(), batch_spec=P(BATCH_AXES),
                  remat: bool = False, remat_policy: str = "nothing",
                  sentinel=None):
    """Build (train_step, eval_step), jitted with explicit shardings.

    ``state_spec`` defaults to fully-replicated parameters/optimizer state
    (pure DP).  ZeRO-1/FSDP pass a sharded per-leaf spec pytree instead
    (:mod:`..parallel.zero`); the step body is identical — only the
    shardings change, and XLA inserts the reduce-scatter/all-gather
    dataflow those schemes describe.

    ``remat=True`` wraps the forward in ``jax.checkpoint``: backward
    recomputes activations instead of storing them — the HBM-for-FLOPs
    trade that lets batch/model sizes exceed activation memory.  Numerics
    are unchanged.  ``remat_policy`` picks what the backward may keep
    (:data:`REMAT_POLICIES`): ``"nothing"`` recomputes everything;
    ``"dots"``/``"dots_no_batch"`` save matmul outputs so only the cheap
    elementwise chains recompute — usually the better MFU trade on TPU,
    where the recomputed FLOPs would otherwise hit the MXU twice.

    ``sentinel`` (:class:`..train.sentinel.SentinelConfig`) arms the
    on-device anomaly sentinel: the step computes the global grad norm,
    checks loss/grad finiteness and spike thresholds against running means
    carried in ``state.sentinel`` (attach via
    :func:`..train.sentinel.attach_sentinel` BEFORE deriving sharding
    specs), and discards anomalous updates with a per-leaf select — one
    extra scalar in the metrics, no host sync.
    """
    # resolved eagerly (even when remat=False) so a typo'd policy name
    # fails fast at build time
    policy = _remat_policy(remat_policy)
    state_sh = _state_sharding(mesh, state_spec)
    batch_sh = NamedSharding(mesh, batch_spec)
    repl = NamedSharding(mesh, P())

    _metrics = prediction_metrics

    @under_mesh(mesh)
    def train_step(state: TrainState, x, y):
        rngs = state.step_rngs()

        def compute(params):
            fwd = state.apply_fn
            if remat:
                fwd = jax.checkpoint(
                    lambda p, ms, xx: state.apply_fn(p, ms, xx, train=True,
                                                     rngs=rngs),
                    policy=policy)
                pred, new_ms, aux = fwd(params, state.model_state, x)
            else:
                pred, new_ms, aux = fwd(params, state.model_state, x,
                                        train=True, rngs=rngs)
            # scopes name, in a profiler trace, the work no Flax module
            # names: the loss here, the optimizer update below
            with jax.named_scope("loss"):
                loss = loss_fn(pred, y)
                # gradient objective includes the model's aux losses (MoE
                # load balance etc.); logged metrics report the task loss
                return loss + aux, (_metrics(pred, y, loss), new_ms)

        grad_fn = jax.value_and_grad(compute, has_aux=True)
        (_, (metrics, new_ms)), grads = grad_fn(state.params)
        with jax.named_scope("optimizer"):
            if sentinel is not None:
                from distributed_deep_learning_tpu.train.sentinel import (
                    guarded_update)

                return guarded_update(state, grads, new_ms, metrics,
                                      sentinel)
            return state.apply_gradients(grads, model_state=new_ms), metrics

    @under_mesh(mesh)
    def eval_step(state: TrainState, x, y):
        pred, _, _ = state.apply_fn(state.params, state.model_state, x,
                                    train=False)
        return _metrics(pred, y, loss_fn(pred, y))

    train_step = jax.jit(
        train_step,
        in_shardings=(state_sh, batch_sh, batch_sh),
        out_shardings=(state_sh, repl),
        donate_argnums=(0,),
    )
    eval_step = jax.jit(
        eval_step,
        in_shardings=(state_sh, batch_sh, batch_sh),
        out_shardings=repl,
    )
    return train_step, eval_step


def place_state(state: TrainState, mesh: Mesh, state_spec=P()) -> TrainState:
    """Put freshly-initialised state onto the mesh with its sharding."""
    return jax.device_put(state, _state_sharding(mesh, state_spec))
