"""The LM head and its cross-entropy from blocks of logits.

For a language model the output projection is the memory hot spot: the
logits are ``(batch·seq, vocab)``: at GPT-2's 50,257 ids and 16 rows of
1,024 tokens an f32 array of 3.07 GiB that the loss reads three times
(max, sum of exponents, argmax), whose cotangent is as large again and
feeds two more products.  Here a row's logits exist a block at a time:

  forward   a row's statistics, :func:`head_rows`: the log-sum-exp of its
            logits, its target's logit and its argmax, folded block by
            block (running max and sum of exponents, the online softmax
            of :mod:`.attention_pallas` over the vocabulary).
  backward  a ``custom_vjp`` makes each block again from the saved
            log-sum-exp and forms its cotangent, ``a·softmax + b·onehot``
            for the cotangents ``a`` of a row's log-sum-exp and ``b`` of
            its target logit: whatever the loss made of the statistics
            (a padding mask, a mean, label smoothing) is ordinary JAX on
            ``(rows,)`` vectors and ordinary autodiff.

Both passes are Pallas kernels (a TPU's; ``interpret=True`` runs them in
the CPU tests).  ``head_ce_fwd`` walks (row tile, vocabulary tile) with the
vocabulary innermost: one MXU pass a tile (operands in the compute dtype,
f32 accumulation), the f32 tile and every statistic in VMEM.
``head_ce_bwd`` makes a tile again and writes its cotangent ONCE, in the
compute dtype (what the MXU would round it to): the one ``(rows, vocab)``
value that rests, half the size of the logits; ``dh`` and ``dW`` are two
plain products over it.  The vocabulary's last tile is a boundary tile
(50,257 = 24 x 2,048 + 1,105): its columns past the table are masked on
the way in and never stored on the way out, so no padded copy of the table
is made.  Tiles come from the shapes alone (:func:`_tiling`).

Under a step's mesh (:func:`..train.step.under_mesh`) a shard's rows run
on the shard, as :func:`.attention_pallas._per_shard` runs attention, with
the table brought whole to each chip in the compute dtype and ``dW``
summed over the batch axes in f32.

:class:`DeferredLogits` is what a model hands a step in the logits' place
(:class:`..models.transformer.CausalLM` with ``with_logits="deferred"``):
the final hidden states and the table, logits not yet taken;
:func:`..train.objectives.token_cross_entropy` and
:func:`..train.objectives.prediction_metrics` recognise it and come here.
Blocks cost a fourth product and buy memory, so small logits are still
multiplied out, as are all logits where no kernel runs (off a TPU, rows
the mesh's batch axes do not divide) or none was timed (f32 hidden
states): :func:`logits_at_rest` is the rule, by the backend, the dtype and
a shard's shape.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from distributed_deep_learning_tpu.obs import runlog
from distributed_deep_learning_tpu.ops.attention_pallas import (LANES,
                                                              VMEM_DEFAULT)

NEG_INF = -1e30
#: the widest tiles, rows x vocabulary columns: of the six timed on the
#: chip at both train cells' shapes (512..2,048 x 512..2,048) the fastest,
#: by 2% over 1,024 x 1,024 (PERF.md section 6, PR 45)
ROW_TILE = 512
VOCAB_TILE = 2048
#: columns of a tile the kernels take at a time, which bounds the f32
#: values a grid step holds beside its blocks (256, 512 and 1,024 time
#: alike on the chip)
PIECE = 512
#: the VMEM a kernel's blocks and its f32 tile may take
VMEM_BLOCKS = 40 << 20
#: a shard's f32 logits of fewer bytes rest whole (:func:`logits_at_rest`)
REST_BYTES = 1 << 30


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeferredLogits:
    """Logits not yet taken: ``hidden (..., T, d)`` in the compute dtype
    and the ``(V, d)`` table they would be projected through.  What scores
    them decides how they are taken (:func:`logits_at_rest`)."""

    hidden: jax.Array
    table: jax.Array


def _round_up(a: int, b: int) -> int:
    return pl.cdiv(a, b) * b


# --------------------------------------------------------------------------
# tiles from the shapes
# --------------------------------------------------------------------------

def _held(tn: int, tv: int, d: int, itemsize: int) -> int:
    """Bytes of VMEM a program's blocks take: two buffers an operand and a
    cotangent tile, the f32 tile and two values of its size beside it."""
    return (2 * itemsize * (tn * d + tv * d + tn * tv)
            + 3 * 4 * tn * min(tv, PIECE))


def _tiling(N: int, d: int, V: int, itemsize: int = 2):
    """``(tn, tv)`` of a call from its shapes alone.  ``tv`` is
    :data:`VOCAB_TILE` (a smaller vocabulary whole, in whole lane tiles),
    ``tn`` :data:`ROW_TILE` (fewer rows whole, in whole sublane tiles);
    while the blocks take more than :data:`VMEM_BLOCKS` the rows are
    halved down to 128, then the columns: a row tile is read once and the
    table once a row tile, so the table's traffic falls with ``tn``."""
    tv = min(VOCAB_TILE, _round_up(V, LANES))
    tn = min(ROW_TILE, _round_up(N, 16))
    while _held(tn, tv, d, itemsize) > VMEM_BLOCKS:
        if tn > 128:
            tn = _round_up(tn // 2, 16)
        elif tv > LANES:
            tv //= 2
        else:
            break
    return tn, tv


def _params(held: int, semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=held + VMEM_DEFAULT if 2 * held > VMEM_DEFAULT
        else None)


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------

def _tile_logits(h, w_ref, lo: int, width: int):
    """``h (tn, d) x w[lo:lo + width] (width, d) -> (tn, width)`` f32."""
    return lax.dot_general(h, w_ref[lo:lo + width, :],
                           (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _fwd_kernel(h_ref, w_ref, t_ref, lse_ref, zt_ref, amax_ref, m_ref,
                s_ref, *, V: int, tv: int, piece: int):
    j = pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        zt_ref[...] = jnp.zeros_like(zt_ref)
        amax_ref[...] = jnp.zeros_like(amax_ref)

    def fold(masked: bool):
        # straight-line on purpose: the compiler keeps a piece's chain of
        # vector work in registers and runs it beside the next piece's
        # product; walked a strip of rows at a time in a loop the forward
        # took 27-44 ms where this takes 10.9 (PERF.md section 6, PR 45)
        h, t = h_ref[...], t_ref[...]
        m, s, zt, best = m_ref[...], s_ref[...], zt_ref[...], amax_ref[...]
        for lo in range(0, tv, piece):
            z = _tile_logits(h, w_ref, lo, piece)
            col = j * tv + lo + lax.broadcasted_iota(jnp.int32, z.shape, 1)
            if masked:      # the boundary tile: columns past the table
                z = jnp.where(col < V, z, NEG_INF)
            top = jnp.max(z, axis=1, keepdims=True)
            first = jnp.min(jnp.where(z == top, col, V), axis=1,
                            keepdims=True)
            best = jnp.where(top > m, first, best)   # a tie: the earlier id
            new_m = jnp.maximum(m, top)
            s = s * jnp.exp(m - new_m) + jnp.sum(
                jnp.exp(z - new_m), axis=1, keepdims=True)
            zt = zt + jnp.sum(jnp.where(col == t, z, 0.0), axis=1,
                              keepdims=True)
            m = new_m
        m_ref[...], s_ref[...], zt_ref[...], amax_ref[...] = m, s, zt, best

    if V % tv:
        pl.when(j < last)(functools.partial(fold, False))
        pl.when(j == last)(functools.partial(fold, True))
    else:
        fold(False)

    @pl.when(j == last)
    def _():
        lse_ref[...] = m_ref[...] + jnp.log(s_ref[...])


def _bwd_kernel(h_ref, w_ref, t_ref, lse_ref, a_ref, b_ref, delta_ref, *,
                tv: int, piece: int):
    j = pl.program_id(1)
    h, t = h_ref[...], t_ref[...]
    lse, a, b = lse_ref[...], a_ref[...], b_ref[...]
    for lo in range(0, tv, piece):
        z = _tile_logits(h, w_ref, lo, piece)
        col = j * tv + lo + lax.broadcasted_iota(jnp.int32, z.shape, 1)
        p = a * jnp.exp(z - lse)
        # the boundary tile's columns past the table are never stored
        delta_ref[:, lo:lo + piece] = jnp.where(
            col == t, p + b, p).astype(delta_ref.dtype)


def _column(tn: int):
    return pl.BlockSpec((tn, 1), lambda i, j: (i, 0))


@functools.partial(jax.jit, static_argnames=("tn", "tv", "interpret"))
def _fwd_call(h, w, t, *, tn, tv, interpret):
    N, d = h.shape
    V = w.shape[0]
    piece = min(PIECE, tv)
    stat = jax.ShapeDtypeStruct((N, 1), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, V=V, tv=tv, piece=piece),
        out_shape=(stat, stat, jax.ShapeDtypeStruct((N, 1), jnp.int32)),
        grid=(N // tn, pl.cdiv(V, tv)),
        in_specs=[pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((tv, d), lambda i, j: (j, 0)),
                  _column(tn)],
        out_specs=(_column(tn),) * 3,
        scratch_shapes=[pltpu.VMEM((tn, 1), jnp.float32)] * 2,
        compiler_params=_params(_held(tn, tv, d, h.dtype.itemsize),
                                ("parallel", "arbitrary")),
        interpret=interpret, name="head_ce_fwd",
    )(h, w, t)


@functools.partial(jax.jit, static_argnames=("tn", "tv", "interpret"))
def _bwd_call(h, w, t, lse, a, b, *, tn, tv, interpret):
    N, d = h.shape
    V = w.shape[0]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tv=tv, piece=min(PIECE, tv)),
        out_shape=jax.ShapeDtypeStruct((N, V), h.dtype),
        grid=(N // tn, pl.cdiv(V, tv)),
        in_specs=[pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((tv, d), lambda i, j: (j, 0))]
        + [_column(tn)] * 4,
        out_specs=pl.BlockSpec((tn, tv), lambda i, j: (i, j)),
        compiler_params=_params(_held(tn, tv, d, h.dtype.itemsize),
                                ("parallel", "parallel")),
        interpret=interpret, name="head_ce_bwd",
    )(h, w, t, lse, a, b)


# --------------------------------------------------------------------------
# a shard's rows: both passes
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Plan:
    """How a call runs, from its shapes and the ambient mesh: static, so
    one trace a plan."""

    tn: int                   # rows a tile
    tv: int                   # vocabulary columns a tile
    interpret: bool
    axes: tuple               # the mesh's batch axes the rows are split over
    mapped: bool              # the mesh splits something: a shard at a time
    rows: int                 # a shard's rows


def _padded(plan: _Plan, h, *columns):
    """A shard's operands as the kernels take them: ``h (n, d)`` and each
    column ``(n, 1)``, zero rows added up to a whole tile (a zero row's
    cotangent coefficients are zero, so it moves nothing)."""
    h = h.reshape(-1, h.shape[-1])
    pad = -h.shape[0] % plan.tn
    return tuple(jnp.pad(x.reshape(h.shape[0], -1), ((0, pad), (0, 0)))
                 for x in (h, *columns))


def _rows_local(plan: _Plan, h, t, w):
    """``(lse, zt, argmax)`` shaped like `t` for one shard's rows."""
    hp, tp = _padded(plan, h, t)
    out = _fwd_call(hp, w, tp, tn=plan.tn, tv=plan.tv,
                    interpret=plan.interpret)
    return tuple(x.reshape(-1)[:t.size].reshape(t.shape) for x in out)


def _grads_local(plan: _Plan, h, t, lse, a, b, w):
    """``(dh like h, dW (V, d) f32)`` of one shard's rows, ``dW`` summed
    over the shards."""
    hp, tp, lp, ap, bp = _padded(plan, h, t, lse, a, b)
    delta = _bwd_call(hp, w, tp, lp, ap, bp, tn=plan.tn, tv=plan.tv,
                      interpret=plan.interpret)
    dh = jnp.dot(delta, w, preferred_element_type=jnp.float32)
    dw = lax.dot_general(delta, hp, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    if plan.axes:
        dw = lax.psum(dw, plan.axes)
    n = math.prod(h.shape[:-1])
    return dh[:n].reshape(h.shape).astype(h.dtype), dw


def _per_shard(plan: _Plan, local, rows, w, out_like):
    """``local(*rows, w)`` once a shard of the step's mesh (XLA cannot
    partition a Mosaic kernel): each of `rows` split along its first
    dimension over the plan's batch axes, `w` whole on every shard; a
    result is split like the array `out_like` names for it, or with None
    the same on every shard."""
    if not plan.mapped:
        return local(*rows, w)

    def spec(x):
        return P() if x is None else P(plan.axes or None,
                                       *[None] * (x.ndim - 1))

    return jax.shard_map(local, in_specs=(*map(spec, rows), P()),
                         out_specs=tuple(map(spec, out_like)),
                         check_vma=False)(*rows, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _head_rows(h, table, t, plan: _Plan):
    return _head_rows_fwd(h, table, t, plan)[0]


def _head_rows_fwd(h, table, t, plan: _Plan):
    w = table.astype(h.dtype)
    lse, zt, best = _per_shard(plan, functools.partial(_rows_local, plan),
                               (h, t), w, (t, t, t))
    # the table itself for its dtype alone: a parameter, alive anyway
    return (lse, zt, best), (h, w, t, lse, table)


def _head_rows_bwd(plan: _Plan, res, cts):
    h, w, t, lse, table = res
    a, b, _ = cts
    dh, dw = _per_shard(plan, functools.partial(_grads_local, plan),
                        (h, t, lse, a, b), w, (h, None))
    return dh, dw.astype(table.dtype), None


_head_rows.defvjp(_head_rows_fwd, _head_rows_bwd)


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

def note_text(calls) -> str:
    """The ``fused_head`` note: ``calls=N rows=R vocab=V path=P tiles=TNxTV
    logits_at_rest=L``.  `calls` counts the heads the program scores (a
    step asks twice of one head, for the loss and for the argmax count,
    and holds it once: asks over the same hidden states are one call),
    `rows` a shard's rows, ``path`` how they were taken: ``pallas`` a block
    at a time, ``logits`` whole (a deferred head under
    :func:`logits_at_rest`'s rule; ``calls=0`` where the loss was handed
    arrays)."""
    heads = {c[0]: c[1:] for c in calls if c[0] is not None}
    if not heads:
        return "calls=0 path=logits logits_at_rest=1"
    rows, vocab, path, tiles = max(heads.values())
    return (f"calls={len(heads)} rows={rows} vocab={vocab} path={path} "
            f"tiles={tiles} logits_at_rest={int(path == 'logits')}")


def _note(hidden, rows: int, vocab: int, path: str, tiles: str) -> None:
    """One ask of a head, for the note of the program being traced."""
    runlog.compile_log.gather(
        "fused_head", (id(hidden), rows, vocab, path, tiles), note_text)


def note_logits() -> None:
    """A loss that was handed arrays, for the same note."""
    runlog.compile_log.gather("fused_head", (None,), note_text)


def _shards(h):
    """``(the mesh's batch axes `h`'s first dimension is split over, how
    many shards that makes, whether the mesh splits anything)``, or None
    where no kernel may run: rows the batch axes do not divide are the
    partitioner's, whole, and it cannot split a kernel."""
    from distributed_deep_learning_tpu.runtime.batch_pin import (
        split_axes, split_batch_axes)

    axes = split_batch_axes()
    mesh = jax.sharding.get_abstract_mesh()
    shards = math.prod(mesh.shape[a] for a in axes)
    if h.shape[0] % shards:
        return None
    return axes, shards, bool(split_axes())


def logits_at_rest(pred: DeferredLogits):
    """The f32 logits of `pred`, whole, or None where they are to be taken
    a block at a time: on a TPU, where a kernel may run, from 16-bit hidden
    states (the kernels were timed on bf16 operands alone; f32 ones would
    be multiplied in full precision, several MXU passes where the parent's
    DEFAULT-precision product takes one), where a shard's f32 logits are
    :data:`REST_BYTES` or more.

    Why a rule at all: blocks cost a fourth product (the backward makes a
    tile again) and buy memory.  At 16 rows of 1,024 tokens a chip the
    3.07 GiB of logits had the compiler make 14 MLP products and the
    logits twice to fit, and blocks are 9% faster; at 2 rows a chip (0.38
    GiB, nothing rematerialised, ``fsdp=4``) the logits whole are 2.5%
    faster; at 16 rows a chip under ``data=4`` blocks are 6.4% faster
    (PERF.md section 6, PR 45): the threshold lies between the shapes
    that were measured, and nothing between them was.  Whole, they are
    what the parent's model computed
    (:meth:`..models.transformer.Embed.logits`)."""
    from distributed_deep_learning_tpu.models.transformer import Embed

    hidden, table = pred.hidden, pred.table
    split = _shards(hidden)
    rows = math.prod(hidden.shape[:-1]) // (split[1] if split else 1)
    if split and jax.default_backend() == "tpu" \
            and hidden.dtype.itemsize == 2 \
            and rows * table.shape[0] * 4 >= REST_BYTES:
        return None
    _note(hidden, rows, table.shape[0], "logits", "none")
    return Embed.logits(hidden, table)


def head_rows(hidden, table, targets, *, interpret: bool = False,
              tiles: Optional[tuple] = None):
    """``(lse, target logit, argmax)``, each shaped like `targets`, of the
    logits ``hidden (..., d) @ table (V, d).T`` that are never whole:
    operands in `hidden`'s dtype, f32 accumulation and statistics.  A
    target outside ``[0, V)`` has target logit 0.  Differentiable in
    `hidden` and `table` through the log-sum-exp and the target logit.

    The kernels, so a TPU's (``interpret=True``: interpreted, anywhere);
    `tiles` overrides :func:`_tiling`."""
    split = _shards(hidden)
    if split is None:
        raise ValueError(
            f"{hidden.shape[0]} rows over the mesh's batch axes: no kernel "
            "runs on part of a row (logits_at_rest takes such logits whole)")
    axes, shards, mapped = split
    rows = math.prod(hidden.shape[:-1]) // shards
    tn, tv = tiles or _tiling(rows, hidden.shape[-1], table.shape[0],
                              hidden.dtype.itemsize)
    plan = _Plan(tn, tv, interpret, axes, mapped, rows)
    _note(hidden, rows, table.shape[0], "pallas", f"{tn}x{tv}")
    with jax.named_scope("head"):
        return _head_rows(hidden, table, targets.astype(jnp.int32), plan)


def head_cross_entropy(hidden, table, targets,
                       ignore_id: Optional[int] = 0,
                       label_smoothing: float = 0.0, **how):
    """``(loss, correct)`` of the head ``hidden (..., d) @ table (V, d).T``
    against integer `targets` ``(...,)`` without the logits at rest: the
    mean cross-entropy over the positions whose target is not `ignore_id`
    (None: every position counts), as
    :func:`..train.objectives.token_cross_entropy` defines it
    (`label_smoothing` included), and the count of argmax matches over the
    positions whose target is not id 0, as
    :func:`..train.objectives.prediction_metrics` counts them.  `how` goes
    to :func:`head_rows`."""
    lse, zt, best = head_rows(hidden, table, targets, **how)
    per_tok = lse - zt
    if label_smoothing:
        # the sum of a row's logits is linear in it: h . (the table's
        # column sums), no pass over the vocabulary
        w = table.astype(hidden.dtype).astype(jnp.float32)
        sum_z = jnp.einsum("...d,d->...", hidden.astype(jnp.float32),
                           jnp.sum(w, axis=0),
                           precision=lax.Precision.HIGHEST)
        per_tok = (lse - (1.0 - label_smoothing) * zt
                   - (label_smoothing / table.shape[0]) * sum_z)
    valid = (targets != ignore_id if ignore_id is not None
             else jnp.ones(targets.shape, bool)).astype(jnp.float32)
    loss = jnp.sum(per_tok * valid) / jnp.maximum(jnp.sum(valid), 1.0)
    correct = jnp.sum((best == targets) & (targets != 0))
    return loss, correct
