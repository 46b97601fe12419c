"""What the step's ambient mesh splits, and activations held to it.

A step builder traces its body with the mesh ambient
(:func:`..train.step.under_mesh`), so code deep in a model can see how
its inputs are split.  Two things read that here, by one rule
(:func:`split_axes`): the flash kernel, which must run per shard
(:func:`..ops.attention_pallas._per_shard`), and :func:`pin_batch`, which
holds an activation's batch dimension to the batch axes the mesh splits.

Why pin.  jit is handed the state's and the batch's shardings and nothing
else; between them the SPMD partitioner is free, and with parameters
sharded over ``fsdp`` it finds that moving an activation is cheaper than
gathering a layer: it computes the products feature-sharded over the
batch's own axis and converts between the two layouts with ``all-to-all``
and ``collective-permute``, each between two products of one layer where
nothing can hide it.  With every value a module hands on pinned to the
batch axes it has to bring the weights to the rows instead: an all-gather
of a layer's kernels ahead of use and a reduction of their gradients,
which the scheduler may start a layer early (FSDP's own dataflow).  Only
the batch dimension is named; every other dimension stays unconstrained,
so a ``model`` or a sequence axis propagates as it would without the pin.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, PartitionSpec as P

from distributed_deep_learning_tpu.data.loader import BATCH_AXES
from distributed_deep_learning_tpu.obs import runlog


def split_axes() -> set:
    """The ambient abstract mesh's axes that split something and are the
    partitioner's to use: size > 1 and not manual (inside an enclosing
    ``shard_map`` an axis is already per shard).  Empty with no mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    return {name for name, kind in zip(mesh.axis_names, mesh.axis_types)
            if kind == AxisType.Auto and mesh.shape[name] > 1}


def split_batch_axes() -> tuple:
    """Those of them a batch is split over, in ``BATCH_AXES`` order."""
    split = split_axes()
    return tuple(a for a in BATCH_AXES if a in split)


def pin_batch(x):
    """`x` (batch leading) with its batch dimension held to the batch axes
    the ambient mesh splits; `x` itself, and nothing emitted, where it
    splits none (one device, no mesh, a stage's ``shard_map``)."""
    axes = split_batch_axes()
    if not axes:
        return x
    runlog.compile_log.gather("batch_pins", axes, pins_text)
    return jax.lax.with_sharding_constraint(
        x, P(axes, *[P.UNCONSTRAINED] * (x.ndim - 1)))


def pins_text(sites) -> str:
    """The ``batch_pins`` note of a traced program (a step builder names it
    to ``obs.compile_log.notes_for``, so every step has one): which batch
    axes its activations were pinned to and at how many sites, ``axes=fsdp
    sites=23``; ``axes=none sites=0`` where the mesh splits no batch axis."""
    axes = sorted({a for site in sites for a in site}, key=BATCH_AXES.index)
    return f"axes={','.join(axes) or 'none'} sites={len(sites)}"
