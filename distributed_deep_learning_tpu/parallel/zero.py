"""ZeRO-style state sharding as pjit sharding rules (no new step code).

BASELINE.json config[4] asks for a "pjit 2D mesh, ZeRO-1-style optimizer
shard".  On TPU this is not a new algorithm but a *sharding annotation*: the
train step (:mod:`..train.step`) is already one jitted program threading a
``TrainState`` pytree; handing jit a sharded spec for ``opt_state`` makes
XLA's SPMD partitioner reduce-scatter gradients into the shard, update
sharded, and all-gather updated params — the ZeRO-1 dataflow — entirely via
compiler-inserted ICI collectives.  Sharding params too (``fsdp_spec``)
gives the ZeRO-3/FSDP dataflow the same way, provided the activations stay
on the batch axes: the transformer pins them there
(:func:`..runtime.batch_pin.pin_batch`), or the partitioner trades the
batch sharding for a feature sharding over the same axis and reshards
activations with ``all-to-all`` between a layer's products.

Rules are computed per-leaf: shard the largest dimension divisible by the
``fsdp`` axis size, leave small leaves (below ``min_leaf_size`` elements)
replicated — sub-tile leaves only add collective latency.
"""

from __future__ import annotations

import math
from typing import Any

import jax
from jax.sharding import Mesh, PartitionSpec as P

from distributed_deep_learning_tpu.train.state import TrainState


def leaf_shard_spec(leaf: Any, axis_size: int, axis: str = "fsdp",
                    min_leaf_size: int = 2 ** 14) -> P:
    """Spec sharding `leaf`'s largest divisible dim over `axis`."""
    shape = getattr(leaf, "shape", ())
    if not shape or axis_size <= 1:
        return P()
    if math.prod(shape) < min_leaf_size:
        return P()
    dims = sorted(range(len(shape)), key=lambda d: -shape[d])
    for d in dims:
        if shape[d] % axis_size == 0:
            spec = [None] * len(shape)
            spec[d] = axis
            return P(*spec)
    return P()


def _tree_specs(tree: Any, axis_size: int, axis: str,
                min_leaf_size: int) -> Any:
    return jax.tree.map(
        lambda l: leaf_shard_spec(l, axis_size, axis, min_leaf_size), tree)


def _replicated(tree: Any) -> Any:
    return jax.tree.map(lambda _: P(), tree)


def _residual_specs(tree: Any) -> Any:
    # error-feedback residuals (..parallel.collectives) carry a leading
    # per-shard axis sharded over the batch axes: each device holds
    # exactly its own quantization error
    from distributed_deep_learning_tpu.data.loader import BATCH_AXES

    return jax.tree.map(lambda _: P(BATCH_AXES), tree)


def dp_state_spec(state: TrainState) -> TrainState:
    """Pure data-parallel state: everything replicated EXCEPT the
    error-feedback residual, which is per-shard by construction.  The
    ``--grad-compress int8`` path needs this instead of a bare ``P()``:
    placing the residual replicated while the compressed step returns it
    batch-sharded breaks the step's buffer donation."""
    return state.replace(
        step=P(),
        params=_replicated(state.params),
        model_state=_replicated(state.model_state),
        opt_state=_replicated(state.opt_state),
        rng=P() if state.rng is not None else None,
        sentinel=_replicated(state.sentinel),
        comm_residual=_residual_specs(state.comm_residual),
    )


def zero1_state_spec(state: TrainState, mesh: Mesh, *, axis: str = "fsdp",
                     min_leaf_size: int = 2 ** 14) -> TrainState:
    """ZeRO-1: optimizer state sharded over `axis`; params replicated.

    Returns a TrainState-shaped pytree of PartitionSpecs for
    :func:`..train.step.make_step_fns`'s ``state_spec``.
    """
    n = mesh.shape.get(axis, 1)
    return state.replace(
        step=P(),
        params=_replicated(state.params),
        model_state=_replicated(state.model_state),
        opt_state=_tree_specs(state.opt_state, n, axis, min_leaf_size),
        rng=P() if state.rng is not None else None,
        sentinel=_replicated(state.sentinel),  # four scalars, replicated
        comm_residual=_residual_specs(state.comm_residual),
    )


def fsdp_state_spec(state: TrainState, mesh: Mesh, *, axis: str = "fsdp",
                    min_leaf_size: int = 2 ** 14) -> TrainState:
    """ZeRO-3/FSDP: params AND optimizer state sharded over `axis`."""
    n = mesh.shape.get(axis, 1)
    return state.replace(
        step=P(),
        params=_tree_specs(state.params, n, axis, min_leaf_size),
        model_state=_replicated(state.model_state),
        opt_state=_tree_specs(state.opt_state, n, axis, min_leaf_size),
        rng=P() if state.rng is not None else None,
        sentinel=_replicated(state.sentinel),  # four scalars, replicated
        comm_residual=_residual_specs(state.comm_residual),
    )
