"""Quantized + ring-overlapped explicit collectives for ZeRO/FSDP.

At scale the wire bill moves from the data-parallel gradient all-reduce
(compressible via ``--grad-compress``, :mod:`..train.compress`) to the
FSDP **param all-gathers and grad reduce-scatters**, which the annotation
path (:mod:`.zero`) leaves to XLA's partitioner: full fp32, no overlap
control.  This module owns that dataflow instead, three layers deep:

1. **Wire formats** — :func:`all_gather` / :func:`reduce_scatter` run
   under ``shard_map`` with an explicit ``method``: ``bf16`` (half the
   bytes, exponent range kept) or common-scale symmetric ``int8`` (one
   global ``pmax`` scale per leaf, EQuARX-style numerics — see
   PAPERS.md).  ``int8`` composes with momentum/Adam through per-leaf
   **error-feedback residuals** (:func:`ef_quantize`): the quantization
   error of step *t* is added back before quantizing step *t+1*, so the
   applied updates telescope to the true sum instead of accumulating
   bias.  As in :mod:`..train.compress`, the int8 *reduction* is
   emulated in int32 at framework level (the true wire format needs
   compiler support); the all-gather variants genuinely move int8/bf16
   buffers.
2. **Ring overlap** — ``overlap=True`` swaps each collective for a
   double-buffered ``ppermute`` ring (the decomposition idiom of arxiv
   2112.01075, same loop shape as :mod:`.ring_attention`): the transfer
   for chunk *k+1* is issued **before** chunk *k*'s consumer op, so XLA
   may pipeline the next hop's wire time under the current chunk's
   compute.  :func:`gather_matmul` is the fused consumer form — each
   arriving param chunk feeds its matmul rows immediately, never
   materialising the gathered operand.
3. **The FSDP step** — :func:`make_fsdp_step_fns` is the explicit-
   collective rendition of ZeRO-3: gather params → forward/backward →
   reduce-scatter grads → sharded optimizer update, with the residual
   threaded through ``TrainState.comm_residual``.  ``method="none"``
   reproduces the :mod:`.zero` annotation path's numerics.

In uncompressed mode every variant is value-equal to its XLA primitive
(``lax.all_gather`` / ``lax.psum_scatter``); ring reductions only
reassociate the sum, so bit-parity holds whenever the addition is exact.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_deep_learning_tpu.data.loader import BATCH_AXES
from distributed_deep_learning_tpu.runtime.shmap import shard_map
from distributed_deep_learning_tpu.train.objectives import prediction_metrics

METHODS = ("none", "bf16", "int8")

#: claimed wire bytes per element (the format a compiler-level
#: implementation would put on the ICI; the analytic accounting
#: :func:`wire_bytes` uses)
WIRE_ITEMSIZE = {"bf16": 2, "int8": 1}

#: int8 ships one f32 scale per leaf per collective
_SCALE_BYTES = 4

#: reduction accumulator per method: int32 keeps int8 sums exact up to
#: 2^24 shards; bf16 values accumulate in f32 (psum upcasts on TPU)
_ACCUM = {"bf16": jnp.float32, "int8": jnp.int32}


# --------------------------------------------------------------------------
# wire formats
# --------------------------------------------------------------------------

def quantize(x, method: str, axis=None):
    """``x`` → ``(wire, scale)``.  For int8 the scale is the GLOBAL
    max-|x| over ``axis`` (one scalar pmax) so every shard dequantizes
    identically; ``axis=None`` quantizes with the local amax (for use
    outside shard_map)."""
    if method == "none":
        return x, None
    if method == "bf16":
        return x.astype(jnp.bfloat16), None
    if method == "int8":
        amax = jnp.max(jnp.abs(x))
        if axis is not None:
            amax = lax.pmax(amax, axis)
        scale = jnp.maximum(amax / 127.0, jnp.asarray(1e-30, x.dtype))
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return q, scale
    raise ValueError(f"unknown comm method {method!r}; "
                     f"choose from {METHODS}")


def dequantize(wire, scale, method: str, dtype):
    if method == "none":
        return wire
    if method == "bf16":
        return wire.astype(dtype)
    return wire.astype(dtype) * scale


def ef_quantize(x, residual, method: str, axis=None):
    """Error-feedback quantization: ``(wire, scale, new_residual)``.

    The residual (last step's quantization error) is added back before
    quantizing, and the new error is returned to carry forward — the sum
    of dequantized outputs telescopes to the true sum of inputs, so the
    compression is unbiased in the long run instead of per step.
    ``residual=None`` (or ``method="none"``) degrades to plain
    :func:`quantize`."""
    if method == "none" or residual is None:
        wire, scale = quantize(x, method, axis)
        return wire, scale, residual
    v = x + residual.astype(x.dtype)
    wire, scale = quantize(v, method, axis)
    new_res = v - dequantize(wire, scale, method, x.dtype)
    return wire, scale, new_res


# --------------------------------------------------------------------------
# ring variants (shard_map-internal; same ppermute-in-scan shape as
# ring_attention.py)
# --------------------------------------------------------------------------

def _ring_all_gather(wire, axis: str, size: int):
    """Ring all-gather of dim-0 blocks: ``(m, ...)`` → ``(size*m, ...)``.

    Double-buffered: the ppermute for hop *r+1* is issued before hop
    *r*'s block is consumed (here the buffer write; in
    :func:`gather_matmul` the consumer matmul), so the next transfer is
    in flight while the current block is used."""
    S = size
    my = lax.axis_index(axis)
    perm = [(i, (i + 1) % S) for i in range(S)]
    out = jnp.zeros((S,) + wire.shape, wire.dtype).at[my].set(wire)
    blk = lax.ppermute(wire, axis, perm)  # hop 1, issued up front

    def hop(carry, r):   # blk = hop r's block, not yet consumed
        out, blk = carry
        nxt = lax.ppermute(blk, axis, perm)     # hop r+1 in flight...
        out = out.at[(my - r) % S].set(blk)     # ...while hop r lands
        return (out, nxt), None

    if S > 2:
        (out, blk), _ = lax.scan(hop, (out, blk), jnp.arange(1, S - 1))
    out = out.at[(my - (S - 1)) % S].set(blk)
    return out.reshape((S * wire.shape[0],) + wire.shape[1:])


def _ring_reduce_scatter(contrib, axis: str, size: int):
    """Ring reduce-scatter: ``(size*m, ...)`` per-shard contributions →
    this shard's reduced ``(m, ...)`` chunk.

    The partial sum for chunk *j* starts at shard *j+1* and travels the
    ring collecting each shard's contribution; at every hop the
    ppermute is issued before the consumer add of the next local chunk,
    so the wire and the adds pipeline."""
    S = size
    my = lax.axis_index(axis)
    perm = [(i, (i + 1) % S) for i in range(S)]
    m = contrib.shape[0] // S
    blocks = contrib.reshape((S, m) + contrib.shape[1:])
    send = blocks[(my - 1) % S]   # chunk my-1's partial: own contribution

    def hop(send, r):
        recvd = lax.ppermute(send, axis, perm)      # hop r in flight...
        return recvd + blocks[(my - 1 - r) % S], None   # ...then the add

    acc, _ = lax.scan(hop, send, jnp.arange(1, S))
    return acc   # chunk `my`, fully reduced


# --------------------------------------------------------------------------
# the collectives
# --------------------------------------------------------------------------

def all_gather(x, axis: str, *, size: int, method: str = "none",
               overlap: bool = False, residual=None):
    """Explicit all-gather of dim-0 blocks under shard_map:
    ``(m, ...)`` → ``(size*m, ...)``, quantized on the wire per
    ``method``, ring-overlapped when ``overlap``.  Every shard
    dequantizes the same wire values (common scale), so the gathered
    array is replicated-consistent.  With ``residual`` returns
    ``(gathered, new_residual)``."""
    wire, scale, new_res = ef_quantize(x, residual, method, axis)
    if size == 1:
        gathered = wire
    elif overlap:
        gathered = _ring_all_gather(wire, axis, size)
    else:
        gathered = lax.all_gather(wire, axis, tiled=True)
    out = dequantize(gathered, scale, method, x.dtype)
    return out if residual is None else (out, new_res)


def reduce_scatter(x, axis: str, *, size: int, method: str = "none",
                   overlap: bool = False, residual=None):
    """Explicit reduce-scatter under shard_map: ``(size*m, ...)`` local
    contributions → this shard's summed ``(m, ...)`` chunk.  The local
    contribution is quantized ONCE (with error feedback when
    ``residual`` is given); partials accumulate in int32/f32 so ring
    and XLA reductions agree exactly for int8.  With ``residual``
    returns ``(chunk, new_residual)``."""
    wire, scale, new_res = ef_quantize(x, residual, method, axis)
    contrib = wire if method == "none" else wire.astype(_ACCUM[method])
    if size == 1:
        acc = contrib
    elif overlap:
        acc = _ring_reduce_scatter(contrib, axis, size)
    else:
        acc = lax.psum_scatter(contrib, axis, tiled=True)
    if method == "none":
        out = acc
    elif method == "bf16":
        out = acc.astype(x.dtype)
    else:
        out = acc.astype(x.dtype) * scale
    return out if residual is None else (out, new_res)


def gather_matmul(a_block, b, axis: str, *, size: int, method: str = "none",
                  overlap: bool = False):
    """``all_gather(a) @ b`` with the ring's consumer fused in:
    ``a_block (m, k)`` per shard, ``b (k, n)`` replicated →
    ``(size*m, n)``.  With ``overlap`` each arriving chunk's matmul runs
    while the next chunk's ppermute is already issued — the gathered
    operand is never materialised."""
    wire, scale = quantize(a_block, method, axis)
    S = size
    if S == 1 or not overlap:
        full = wire if S == 1 else lax.all_gather(wire, axis, tiled=True)
        return dequantize(full, scale, method, a_block.dtype) @ b

    my = lax.axis_index(axis)
    perm = [(i, (i + 1) % S) for i in range(S)]
    m = wire.shape[0]
    out = jnp.zeros((S, m, b.shape[1]), b.dtype)
    out = out.at[my].set(dequantize(wire, scale, method, a_block.dtype) @ b)
    blk = lax.ppermute(wire, axis, perm)

    def hop(carry, r):
        out, blk = carry
        nxt = lax.ppermute(blk, axis, perm)     # chunk r+1 in flight...
        chunk = dequantize(blk, scale, method, a_block.dtype)
        out = out.at[(my - r) % S].set(chunk @ b)   # ...during chunk r's matmul
        return (out, nxt), None

    if S > 2:
        (out, blk), _ = lax.scan(hop, (out, blk), jnp.arange(1, S - 1))
    chunk = dequantize(blk, scale, method, a_block.dtype)
    out = out.at[(my - (S - 1)) % S].set(chunk @ b)
    return out.reshape((S * m, b.shape[1]))


# --------------------------------------------------------------------------
# analytic wire accounting (host-side; a jitted program cannot count its
# own bytes, and the int8 reduction is int32-emulated anyway — these are
# the bytes the CLAIMED wire format moves)
# --------------------------------------------------------------------------

def wire_bytes(op: str, method: str, shape, axis_size: int,
               itemsize: int = 4) -> int:
    """Bytes one shard SENDS for one collective.  ``shape`` is the local
    block for ``all_gather`` and the full input for ``reduce_scatter``;
    ring and bidirectional XLA schedules both move (S-1)/S of the data
    per shard.  ``kv_migrate`` is point-to-point (the serve tier's
    KV-block migration): one sender, one receiver, the payload crosses
    the fabric exactly once — no (S-1)/S schedule factor."""
    elems = int(math.prod(shape)) if shape else 1
    if op == "kv_migrate":
        size = WIRE_ITEMSIZE.get(method, itemsize)
        return elems * size + (_SCALE_BYTES if method == "int8" else 0)
    if op == "reduce_scatter":
        elems //= max(1, axis_size)
    sent = elems * (axis_size - 1)
    size = WIRE_ITEMSIZE.get(method, itemsize)
    return sent * size + (_SCALE_BYTES if method == "int8" else 0)


def tree_wire_bytes(op: str, method: str, tree, axis_size: int) -> int:
    """Sum of :func:`wire_bytes` over a pytree of arrays/shapes."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", leaf)
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
        total += wire_bytes(op, method, tuple(shape), axis_size, itemsize)
    return total


def fsdp_wire_stats(params, dims, axis_size: int, method: str) -> dict:
    """Per-step analytic wire bytes for the explicit FSDP dataflow (one
    param all-gather + one grad reduce-scatter over the leaves ``dims``
    marks as sharded), plus the fp32 bytes the same collectives would
    move."""
    gather = scatter = gather_fp32 = scatter_fp32 = 0
    for leaf, d in zip(jax.tree.leaves(params), jax.tree.leaves(dims)):
        if d < 0:
            continue
        shape = tuple(leaf.shape)
        block = tuple(s // axis_size if i == d else s
                      for i, s in enumerate(shape))
        gather += wire_bytes("all_gather", method, block, axis_size)
        scatter += wire_bytes("reduce_scatter", method, shape, axis_size)
        gather_fp32 += wire_bytes("all_gather", "none", block, axis_size)
        scatter_fp32 += wire_bytes("reduce_scatter", "none", shape,
                                   axis_size)
    return {"all_gather_bytes": gather, "reduce_scatter_bytes": scatter,
            "all_gather_fp32_bytes": gather_fp32,
            "reduce_scatter_fp32_bytes": scatter_fp32}


# --------------------------------------------------------------------------
# error-feedback state
# --------------------------------------------------------------------------

def attach_residual(state, n_shards: int):
    """Zero-init the per-shard error-feedback buffer on
    ``TrainState.comm_residual``: one params-shaped tree with a leading
    per-shard axis, sharded over the batch axes (each device carries
    exactly its own residual).  Attach BEFORE deriving sharding specs —
    :mod:`.zero`'s builders map the field alongside the rest."""
    res = jax.tree.map(
        lambda p: jnp.zeros((n_shards,) + tuple(p.shape), p.dtype),
        state.params)
    return state.replace(comm_residual=res)


def residual_spec(tree):
    """PartitionSpecs for a residual tree: leading axis over the batch
    axes, everything else replicated."""
    return jax.tree.map(lambda _: P(BATCH_AXES), tree)


# --------------------------------------------------------------------------
# the explicit-collective FSDP step
# --------------------------------------------------------------------------

def _spec_dim(spec: P, axis: str) -> int:
    """Which dim ``spec`` shards over ``axis`` (-1 = replicated)."""
    for i, entry in enumerate(spec):
        names = entry if isinstance(entry, tuple) else (entry,)
        if axis in names:
            return i
    return -1


def make_fsdp_step_fns(mesh: Mesh, loss_fn: Callable, *, state_spec,
                       method: str = "none", overlap: bool = False,
                       axis: str = "fsdp", remat: bool = False,
                       remat_policy: str = "nothing",
                       batch_spec: P = P(BATCH_AXES), registry=None):
    """(train_step, eval_step) owning the FSDP collectives explicitly.

    Where :mod:`.zero` hands XLA a sharded spec and trusts the
    partitioner, this builder writes the ZeRO-3 dataflow out: all-gather
    the sharded params (quantized per ``method``, ring-overlapped per
    ``overlap``) → forward/backward on the local batch shard →
    reduce-scatter the grads back into the shard (with error feedback
    when ``state.comm_residual`` is attached) → update params+optimizer
    shard-local.  ``state_spec`` is the same TrainState-shaped spec tree
    :func:`..parallel.zero.fsdp_state_spec` produces — leaves it left
    replicated (small/indivisible) skip the gather and psum their grads
    uncompressed.

    ``method="none"`` is loss-parity with the annotation path; the
    optimizer must be elementwise (sgd/momentum/adam —
    a global-norm clip would need its own psum, which shard-local
    ``tx.update`` does not insert).  ``registry`` (an
    ``obs.metrics.MetricsRegistry``) gets per-step ``comm_bytes{op,
    method}`` counters, incremented host-side from the analytic model.
    """
    if method not in METHODS:
        raise ValueError(f"unknown comm method {method!r}; "
                         f"choose from {METHODS}")
    from distributed_deep_learning_tpu.train.step import _remat_policy

    policy = _remat_policy(remat_policy)   # eager: fail fast on typos
    S = mesh.shape.get(axis, 1)
    if S <= 1:
        raise ValueError(f"explicit FSDP collectives need a >1 {axis!r} "
                         "mesh axis (nothing to gather/scatter)")
    batch_axes = tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    other_axes = tuple(a for a in batch_axes if a != axis)
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]

    # which dim each param leaf shards over `axis` (-1 = replicated);
    # static, precomputed from the spec tree the annotation path uses
    gdims = jax.tree.map(lambda s: _spec_dim(s, axis), state_spec.params)

    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, batch_spec)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_spec)

    def _gather_leaf(p, d):
        if d < 0:
            return p
        x0 = jnp.moveaxis(p, d, 0)
        g = all_gather(x0, axis, size=S, method=method, overlap=overlap)
        return jnp.moveaxis(g, 0, d)

    def train_step(state, x, y):
        has_rng = state.rng is not None
        has_res = state.comm_residual is not None
        key = jax.random.fold_in(state.rng, state.step) if has_rng \
            else jax.random.key(0)

        def compute(params, ms, key, x, y):
            rngs = {"dropout": key} if has_rng else None
            fwd = state.apply_fn
            if remat:
                fwd = jax.checkpoint(lambda p, m, xx: state.apply_fn(
                    p, m, xx, train=True, rngs=rngs), policy=policy)
                pred, new_ms, aux = fwd(params, ms, x)
            else:
                pred, new_ms, aux = fwd(params, ms, x, train=True, rngs=rngs)
            loss = loss_fn(pred, y)
            return loss + aux, (prediction_metrics(pred, y, loss), new_ms)

        @partial(shard_map, mesh=mesh,
                 in_specs=(state_spec, P(), batch_spec, batch_spec),
                 out_specs=(state_spec, P()), check_vma=False)
        def step(st, key, xx, yy):
            if has_rng:
                # each batch shard draws an INDEPENDENT dropout mask
                for a in batch_axes:
                    key_local = jax.random.fold_in(key, lax.axis_index(a))
                    key = key_local
            full_params = jax.tree.map(_gather_leaf, st.params, gdims)
            (_, (metrics, new_ms)), g = jax.value_and_grad(
                compute, has_aux=True)(full_params, st.model_state, key,
                                       xx, yy)
            if other_axes:
                # fold the non-shard batch axes first; the scatter below
                # finishes the reduction over `axis`
                g = jax.tree.map(lambda l: lax.psum(l, other_axes), g)

            res = st.comm_residual
            if has_res:
                res = jax.tree.map(lambda r: jnp.squeeze(r, 0), res)

            def scatter(gl, d, rl):
                if d < 0:   # replicated leaf: plain full-precision psum
                    return lax.psum(gl, (axis,)), rl
                g0 = jnp.moveaxis(gl, d, 0)
                r0 = None if rl is None else jnp.moveaxis(rl, d, 0)
                if r0 is None:
                    out = reduce_scatter(g0, axis, size=S, method=method,
                                         overlap=overlap)
                else:
                    out, r0 = reduce_scatter(g0, axis, size=S,
                                             method=method, overlap=overlap,
                                             residual=r0)
                    rl = jnp.moveaxis(r0, 0, d)
                return jnp.moveaxis(out, 0, d), rl

            if has_res:
                pairs = jax.tree.map(scatter, g, gdims, res)
            else:
                pairs = jax.tree.map(lambda gl, d: scatter(gl, d, None),
                                     g, gdims)
            is_pair = lambda t: isinstance(t, tuple)  # noqa: E731
            g = jax.tree.map(lambda t: t[0] / n_batch, pairs,
                             is_leaf=is_pair)
            new_res = st.comm_residual
            if has_res:
                new_res = jax.tree.map(lambda t: t[1][None], pairs,
                                       is_leaf=is_pair)

            metrics = {  # loss is a shard mean → average; counts sum
                "loss": lax.psum(metrics["loss"], batch_axes) / n_batch,
                "correct": lax.psum(metrics["correct"], batch_axes),
                "count": lax.psum(metrics["count"], batch_axes),
            }
            new_ms = jax.tree.map(
                lambda s: lax.psum(s.astype(jnp.float32),
                                   batch_axes) / n_batch
                if jnp.issubdtype(s.dtype, jnp.floating) else s, new_ms)

            updates, new_opt = st.tx.update(g, st.opt_state, st.params)
            new_params = optax.apply_updates(st.params, updates)
            new_state = st.replace(step=st.step + 1, params=new_params,
                                   opt_state=new_opt, model_state=new_ms,
                                   comm_residual=new_res)
            return new_state, metrics

        return step(state, key, x, y)

    def eval_step(state, x, y):
        # eval gathers via the annotation path: the partitioner inserts
        # the all-gathers from the sharded in_shardings
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

    if registry is None:
        return train_step, eval_step

    stats: dict = {}

    def train_step_counted(state, x, y):
        if not stats:
            stats.update(fsdp_wire_stats(state.params, gdims, S, method))
        registry.counter("comm_bytes", op="all_gather", method=method).inc(
            stats["all_gather_bytes"])
        registry.counter("comm_bytes", op="reduce_scatter",
                         method=method).inc(stats["reduce_scatter_bytes"])
        return train_step(state, x, y)

    # keep AOT hooks (FLOPs measurement, trial compile) working through
    # the counting wrapper
    train_step_counted.lower = train_step.lower
    return train_step_counted, eval_step
