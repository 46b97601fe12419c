"""The grouped matrix product of dropless experts as a Pallas TPU kernel.

``(rows (M, K), w (E, K, N), load (E,)) -> (M, N)``: the rows are sorted by
group, group ``g`` owns the ``load[g]`` rows after those of the groups
before it, and each of its rows is multiplied by ``w[g]``.  It is what
:func:`..models.moe.held_experts` calls three times a layer, and what
:func:`jax.lax.ragged_dot` computes; XLA lowers that to a ``ragged-dot``
custom call that walks every row tile of the buffer whatever share of it
holds rows, and reads a chunk-sized call's weights at 38% of HBM's rate.

* **A grid over visits, not rows.**  A *visit* is a (row tile, group) pair
  that overlap.  From `load` the prologue (:func:`visits`, plain XLA on a
  few dozen integers, once a layer for its three products) lists them in
  row order with how many are live; the lists are scalar-prefetched into
  SMEM and the grid is ``(n tiles, live visits)`` with the visit count a
  traced value.  Rows past ``sum(load)`` (the assignments of experts this
  chip does not hold: seven eighths of the buffer in an eight-way
  expert-parallel layer) cost no grid step, no DMA and no MXU pass; they
  are left unwritten, as ``ragged_dot`` leaves them unspecified.
* **A weight tile is read once a group.**  The contraction is whole
  (``tk = K``), so a visit's product is one MXU pass into an f32 value and
  no accumulator is carried between grid steps.  Visits are in row order,
  so a group's visits are consecutive and so are a row tile's: the
  pipeline skips the copy of a block whose index did not change, which
  makes the weight traffic ``K x N`` a group that holds rows, and the row
  traffic ``tm x K`` a row tile and n tile.  A visit stores under a row
  mask (the tile's other rows belong to its other visits).
* **Gate and up in one call** (:func:`Visits.swiglu`): the same kernel with
  two weight operands, ``silu(rows @ w_gate) * (rows @ w_up)`` from the
  two f32 products, rounded once.
* **Tiles from the shapes** (:func:`_tiling`), and under 64 rows, where
  the chip has timed nothing, no kernel at all: ``ragged_dot``.

Off a TPU the entry points are ``ragged_dot``; ``interpret=True`` forces
the kernel through the Pallas interpreter (the CPU parity tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_deep_learning_tpu.obs import runlog
from distributed_deep_learning_tpu.ops.attention_pallas import (LANES,
                                                              VMEM_DEFAULT)

#: a call of fewer rows stays on XLA's ragged_dot: 64 is the smallest call
#: the chip has shown faster through the kernel (a glm decode program's 16
#: slots x top 4; PERF.md section 6, PR 36), and nothing smaller was timed
MIN_ROWS = 64
#: rows a visit multiplies, and the VMEM the kernel's blocks may take
ROW_TILE = 128
VMEM_BLOCKS = 40 << 20


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _tiling(M: int, K: int, N: int, E: int, itemsize: int = 2,
            weights: int = 1):
    """``(tm, tn)`` of a call from its shapes alone, or None where the call
    stays on ``ragged_dot``: fewer than :data:`MIN_ROWS` rows, an ``N``
    that is no multiple of 128 lanes, or a contraction so long that not
    even a 128-lane weight tile of it fits.

    ``tm`` is :data:`ROW_TILE`, or all of a smaller call's rows in whole
    sublane tiles: a visit multiplies a whole row tile whatever share of
    it its group owns, so the MXU's work is ``(M / tm + E - 1) x tm`` rows
    where the groups own ``M``; 64, 128 and 256 time alike (the weights'
    DMA is what a visit waits for) and 512 is two thirds slower.  ``tn`` is
    the widest multiple of 128 that divides ``N`` whose blocks (`weights`
    weight tiles ``K x tn``, a row tile, an output tile, two buffers each,
    and the f32 products) fit :data:`VMEM_BLOCKS`: the whole of ``N`` at
    the served shapes, so a row tile is read once."""
    if M < MIN_ROWS or N % LANES:
        return None
    tm = min(ROW_TILE, _cdiv(M, 16) * 16)
    for tn in sorted((t for t in range(LANES, N + 1, LANES) if N % t == 0),
                     reverse=True):
        if _held(tm, K, tn, itemsize, weights) <= VMEM_BLOCKS:
            return tm, tn
    return None


def _held(tm: int, K: int, tn: int, itemsize: int, weights: int) -> int:
    """Bytes of VMEM a program's blocks take: two buffers an operand, and
    the f32 products before they are rounded."""
    return (2 * itemsize * (tm * K + weights * K * tn + tm * tn)
            + 4 * weights * tm * tn)


# --------------------------------------------------------------------------
# the prologue: which (row tile, group) pairs hold rows
# --------------------------------------------------------------------------

def visits(load, M: int, tm: int):
    """``(offsets (E + 1,), groups (V,), tiles (V,), live ())`` int32 for
    `load` over ``M`` rows in tiles of ``tm``: group ``g`` owns rows
    ``[offsets[g], offsets[g + 1])``; visit ``v < live`` is the pair (row
    tile ``tiles[v]``, group ``groups[v]``), in row order; ``V = ceil(M /
    tm) + E - 1`` is the most there can be (every group but the first may
    start inside a tile another has begun).  Entries past `live` repeat the
    last live visit, so a reader that runs past it changes no block."""
    E = load.shape[0]
    V = _cdiv(M, tm) + E - 1
    load = load.astype(jnp.int32)
    ends = jnp.cumsum(load)
    starts = ends - load
    first = starts // tm
    # tiles a group touches: none where it holds no row
    count = jnp.where(load > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(count)                       # visits through group g
    live = upto[-1]
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32), jnp.maximum(live - 1, 0))
    groups = jnp.minimum(jnp.searchsorted(upto, v, side="right"),
                         E - 1).astype(jnp.int32)
    tiles = first[groups] + v - (upto - count)[groups]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, groups, jnp.where(live > 0, tiles, 0), live


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------

def _kernel(offsets_ref, groups_ref, tiles_ref, rows_ref, *refs, tm: int):
    *w_refs, out_ref = refs
    v = pl.program_id(1)
    g = groups_ref[v]
    row = tiles_ref[v] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    rows = rows_ref[...]
    prods = [lax.dot_general(rows, w[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for w in w_refs]
    val = prods[0] if len(prods) == 1 \
        else jax.nn.silu(prods[0]) * prods[1]
    # the tile's other rows are its other visits', or nobody's
    out_ref[...] = jnp.where(mine, val.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _call(offsets, groups, tiles, live, rows, *ws, tm, tn, interpret):
    M, K = rows.shape
    N = ws[0].shape[2]

    def row_block(n, v, offsets, groups, tiles):
        return tiles[v], 0

    def weight_block(n, v, offsets, groups, tiles):
        return groups[v], 0, n

    def out_block(n, v, offsets, groups, tiles):
        return tiles[v], n

    held = _held(tm, K, tn, rows.dtype.itemsize, len(ws))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((M, N), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, K), row_block)]
            + [pl.BlockSpec((None, K, tn), weight_block)] * len(ws),
            out_specs=pl.BlockSpec((tm, tn), out_block),
            grid=(N // tn, live)),
        compiler_params=_params(held), interpret=interpret,
        name="grouped_product" if len(ws) == 1 else "grouped_swiglu",
    )(offsets, groups, tiles, rows, *ws)


def _params(held: int):
    """The n tiles are independent and the visits of one are in order (an
    output tile's visits are consecutive).  VMEM as the flash kernels ask
    for it: nothing while the blocks take under half of what a kernel gets
    unasked, else the blocks on top of it."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=held + VMEM_DEFAULT if 2 * held > VMEM_DEFAULT
        else None)


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

def _note_text(calls) -> str:
    """The ``grouped_product`` note: ``calls=N rows=M experts=E path=P
    tiles=TMxKxTN fused_gate_up=F``: the grouped products the program
    holds at its largest row count (a decode program is traced a slot at a
    time first, ``top_k`` rows, before the slots' ``vmap`` folds into the
    one call that runs: those are not counted), their rows and groups, the
    path they took (``pallas``, ``ragged_dot``, or ``mixed``), the Pallas
    calls' widest tiles and how many calls are a fused gate and up."""
    rows = max(c[0] for c in calls)
    calls = [c for c in calls if c[0] == rows]
    paths = {"pallas" if c[2] else "ragged_dot" for c in calls}
    tiled = [c[2] for c in calls if c[2]]
    tiles = "x".join(map(str, max(tiled, key=lambda t: t[1] * t[2]))) \
        if tiled else "none"
    return (f"calls={len(calls)} rows={rows} experts={calls[0][1]} "
            f"path={paths.pop() if len(paths) == 1 else 'mixed'} "
            f"tiles={tiles} fused_gate_up={sum(c[3] for c in calls)}")


def _note_call(M: int, E: int, tiles, fused: bool) -> None:
    """One call, for the note of the program being traced (the engine's
    ``CountingJit`` opens ``obs.compile_log.notes_for``).  Traced outside
    any (the engine sizing its cache with ``eval_shape``) it says nothing:
    no program holds that call."""
    runlog.compile_log.gather("grouped_product", (M, E, tiles, fused),
                              _note_text)


class Visits:
    """A layer's grouped products over ONE sorted batch of ``M`` rows with
    `load` rows a group: the visit lists are made once (a row tile size)
    and every product of the layer reads them."""

    def __init__(self, load, M: int, interpret: Optional[bool] = None,
                 tiles: Optional[tuple] = None):
        self.load, self.M, self._forced = load, M, tiles
        if interpret is None:
            self._kernel, self._interpret = \
                jax.default_backend() == "tpu", False
        else:
            self._kernel, self._interpret = True, interpret
        self._lists = {}

    def _path(self, K: int, N: int, itemsize: int, weights: int):
        if not self._kernel:
            return None
        return self._forced or _tiling(self.M, K, N, self.load.shape[0],
                                       itemsize, weights)

    def _run(self, rows, ws, tiling):
        tm, tn = tiling
        if tm not in self._lists:
            self._lists[tm] = visits(self.load, self.M, tm)
        return _call(*self._lists[tm], rows, *ws, tm=tm, tn=tn,
                     interpret=self._interpret)

    def product(self, rows, w):
        """``rows (M, K) @ w[group of the row] (E, K, N) -> (M, N)`` in the
        operands' dtype (f32 accumulation), as ``jax.lax.ragged_dot(rows,
        w, load)`` gives it; rows past ``sum(load)`` are unspecified."""
        E, K, N = w.shape
        tiling = self._path(K, N, rows.dtype.itemsize, 1)
        _note_call(self.M, E, tiling and (tiling[0], K, tiling[1]), False)
        if tiling is None:
            return lax.ragged_dot(rows, w, self.load)
        return self._run(rows, (w,), tiling)

    def swiglu(self, rows, w_gate, w_up):
        """``silu(product(rows, w_gate)) * product(rows, w_up)`` in the
        operands' dtype: where the kernel runs, ONE call that reads a row
        tile once and rounds once."""
        E, K, N = w_gate.shape
        tiling = self._path(K, N, rows.dtype.itemsize, 2)
        if tiling is None:
            return jax.nn.silu(self.product(rows, w_gate)) \
                * self.product(rows, w_up)
        _note_call(self.M, E, (tiling[0], K, tiling[1]), True)
        return self._run(rows, (w_gate, w_up), tiling)
