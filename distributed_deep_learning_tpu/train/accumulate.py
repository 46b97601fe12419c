"""Gradient accumulation: big effective batches at constant memory.

Absent from the reference (SURVEY.md §2.5) but essential on TPU: HBM bounds
the per-step microbatch while convergence recipes are written in terms of
the effective batch.  The jitted step reshapes the global batch into
``accum_steps`` microbatches and folds them through a ``lax.scan`` —
activations for only ONE microbatch are ever live, gradients accumulate in
a running mean, and a single optimizer update fires at the end.  Composes
with every sharding the plain step supports (the batch axis sharding
propagates through the reshape).

Semantics: identical to one step on the full batch for mean-reduced losses
over equal-size microbatches (asserted in tests), with the usual BatchNorm
caveat — running stats advance per microbatch, matching the reference's
per-chunk BN in its pipelined forward.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_deep_learning_tpu.data.loader import BATCH_AXES
from distributed_deep_learning_tpu.train.objectives import prediction_metrics
from distributed_deep_learning_tpu.train.state import TrainState
from distributed_deep_learning_tpu.train.step import (_state_sharding,
                                                      under_mesh)


def make_accum_step_fns(mesh: Mesh, loss_fn: Callable, *,
                        accum_steps: int, state_spec=P(),
                        batch_spec=P(BATCH_AXES)):
    """(train_step, eval_step) with `accum_steps`-way gradient accumulation.

    Drop-in replacement for :func:`..step.make_step_fns`; the global batch
    must divide by ``accum_steps`` (and each microbatch by the data-parallel
    mesh size).
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    state_sh = _state_sharding(mesh, state_spec)
    batch_sh = NamedSharding(mesh, batch_spec)
    repl = NamedSharding(mesh, P())

    def _micro(x, y):
        B = x.shape[0]
        if B % accum_steps:
            raise ValueError(f"batch {B} not divisible by accumulation "
                             f"factor {accum_steps}")
        m = B // accum_steps
        return (x.reshape(accum_steps, m, *x.shape[1:]),
                y.reshape(accum_steps, m, *y.shape[1:]))

    @under_mesh(mesh)
    def train_step(state: TrainState, x, y):
        xs, ys = _micro(x, y)
        micro_idx = jnp.arange(accum_steps)

        def micro_grad(model_state, xy):
            mx, my, i = xy
            rngs = state.step_rngs()
            if rngs is not None:  # distinct stream per microbatch
                rngs = {k: jax.random.fold_in(r, i) for k, r in rngs.items()}

            def compute(params):
                pred, new_ms, aux = state.apply_fn(params, model_state, mx,
                                                   train=True, rngs=rngs)
                loss = loss_fn(pred, my)
                return loss + aux, (prediction_metrics(pred, my, loss),
                                    new_ms)

            (_, (metrics, new_ms)), grads = jax.value_and_grad(
                compute, has_aux=True)(state.params)
            return new_ms, (grads, metrics)

        final_ms, (grads, metrics) = lax.scan(micro_grad, state.model_state,
                                              (xs, ys, micro_idx))
        mean_grads = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
        summed = {
            "loss": jnp.mean(metrics["loss"]),  # mean of microbatch means
            "correct": jnp.sum(metrics["correct"]),
            "count": jnp.sum(metrics["count"]),
        }
        new_state = state.apply_gradients(mean_grads, model_state=final_ms)
        return new_state, summed

    @under_mesh(mesh)
    def eval_step(state: TrainState, x, y):
        pred, _, _ = state.apply_fn(state.params, state.model_state, x,
                                    train=False)
        return prediction_metrics(pred, y, loss_fn(pred, y))

    train_step = jax.jit(train_step,
                         in_shardings=(state_sh, batch_sh, batch_sh),
                         out_shardings=(state_sh, repl),
                         donate_argnums=(0,))
    eval_step = jax.jit(eval_step,
                        in_shardings=(state_sh, batch_sh, batch_sh),
                        out_shardings=repl)
    return train_step, eval_step
