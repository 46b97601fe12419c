"""Fused (flash) attention as Pallas TPU kernels — forward AND backward.

The reference leans on cuDNN/Triton for its fused kernels
(``torch.compile``, ``WrapperTriton``, SURVEY.md §2.4); the TPU-native
counterpart is a Pallas kernel.  Attention is *the* op worth fusing: naive
attention materialises the (T×T) score matrix in HBM, while these kernels
stream K/V blocks through VMEM and keep the online-softmax running
statistics (max ``m``, denominator ``l``, accumulator ``acc``) in
registers — O(T·D) memory, MXU-shaped contractions, no HBM round-trip for
the scores.

Performance rules the kernels obey (each learned from a measured regression
— the first revision cast everything to f32 and rematerialised a *dense*
backward, and benched 0.54× dense on a v5e):

* **Matmuls stay in the input dtype** (bf16 on TPU) with
  ``preferred_element_type=f32`` — the MXU's native bf16×bf16→f32 mode.
  Only the softmax statistics run in f32 on the VPU.  (When callers pass
  f32 — the CPU parity tests — the contractions stay f32 and results match
  the dense path to tight tolerances.)
* **Causal block skipping**: a query block at offset ``q_off`` stops its
  key loop at the diagonal (``ceil((q_off+bq)/bk)`` blocks) instead of
  scanning all of K — half the work, and the dominant win at long T.
* **A real flash backward**: one Pallas kernel recomputes scores blockwise
  from the forward's saved LSE — O(T·D) HBM traffic in backward too — and
  takes dQ, dK and dV from that ONE recomputation (as two kernels, dQ over
  query blocks and dK/dV over key blocks, each block of scores was made
  twice).  The forward emits LSE precisely to enable this (the standard
  flash-attention-2 decomposition: ``delta = rowsum(dO·O)`` then
  ``ds = p·(dO·Vᵀ − delta)``).
* **Operands where they rest**: q, k and v are read, and o, dq, dk and dv
  written, as the projections lay them out, ``(B, T, H·D)``: a program takes
  a block of 128 lanes — two 64-wide heads, walked as separate problems over
  static lane slices and stored as one full-lane value — so no transpose to
  ``(B·H, T, D)`` and back surrounds a call (seven copies of an activation
  a layer, a third of attention's time in a GPT-2 medium step).  Which lanes
  a program takes is a function of ``(D, H, Hkv)`` alone (:func:`_tiling`);
  shapes that cannot be tiled in whole heads transpose as before, through
  the same kernels.

Grid: one program per (batch row, lane block, query-block) forward /
(batch row, lane block, key-block) backward; inner loops are ``fori_loop``
with *dynamic* (diagonal-bounded) trip counts — uniform control flow,
nothing shape-dependent.

On non-TPU platforms the kernels run in interpreter mode so the identical
code path is testable on the CPU mesh.

The same online-softmax recurrence drives :mod:`..parallel.ring_attention`
at the inter-chip level — this kernel is the intra-chip member of that
family.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_deep_learning_tpu.obs import runlog

NEG_INF = -1e30


def _dot(a, b, dims, out_dtype=jnp.float32):
    """dot_general with f32 accumulation, operands kept in their own dtype
    (bf16 operands hit the MXU's native mixed-precision mode)."""
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=out_dtype)


def _causal_mask(s, q_off, k_off, bq, bk, window=None):
    q_pos = q_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = q_pos >= k_pos
    if window is not None:
        # sliding window: each query sees its last `window` positions
        ok = jnp.logical_and(ok, q_pos - k_pos < window)
    return jnp.where(ok, s, NEG_INF)


def _window_lo(q_off, window, block_k):
    """First key block a windowed query block can touch."""
    return jnp.maximum(0, q_off - (window - 1)) // block_k


# --------------------------------------------------------------------------
# layout: which lanes a program takes
# --------------------------------------------------------------------------

LANES = 128
#: the VMEM a kernel may use unless it asks for more (of 128 MiB on a v5e)
VMEM_DEFAULT = 16 << 20


def _vmem(held: int):
    """Compiler parameters of a kernel that holds `held` bytes of operands
    whole, as long as the sequence is: none (it compiles as it always did)
    while they take under half of what a kernel gets unasked, T = 2,048 in
    the backward at 64-wide heads; past that the kernel asks for them on
    top of it, and the limit to T is the chip's VMEM, not the default."""
    if 2 * held <= VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=held + VMEM_DEFAULT)


def _tiling(D: int, H: int, Hkv: int):
    """``(lanes a block, heads a block)`` of the kernels over q, k and v as
    the projections write them, ``(B, T, H·D)``, from the shapes alone; None
    where no whole block of lanes holds whole heads of q AND of their k / v,
    and the call transposes to ``(B·H, T, D)`` first.

    * ``D`` divides 128 and every query head has its own K/V head: ``128 //
      D`` heads a block (all of them where ``H·D`` is under 128).  An ``H·D``
      that is no multiple of 128 ends in a boundary block with fewer heads
      (gpt2-xl: 25 x 64 = 12 blocks of two and one of one): the lanes past
      the array are padding on the way in and never stored on the way out.
    * ``D`` a multiple of 128: a head a block; query block ``j`` reads K/V
      block ``j // group`` (GQA, as the transposing path maps rows).
    * ``D`` under 128 with grouped K/V would split a K/V head over query
      blocks, and a ``D`` such as 96 or 192 a head over blocks: transposed.
    """
    if D < LANES and LANES % D == 0 and H == Hkv:
        return (LANES, LANES // D) if H * D > LANES else (H * D, H)
    if D % LANES == 0:
        return D, 1
    return None


def _layout_text(calls) -> str:
    """The ``flash_layout`` note: ``calls=N lanes_a_block=L heads_a_block=G
    transposed=M``, the lanes and heads of the calls that read q, k and v
    where they rest (the widest, should a program hold several shapes), and
    how many calls did not."""
    rest = [c for c in calls if not c[2]] or calls
    return (f"calls={len(calls)} lanes_a_block={max(c[0] for c in rest)} "
            f"heads_a_block={max(c[1] for c in rest)} "
            f"transposed={sum(c[2] for c in calls)}")


def _note_call(lanes: int, heads: int, transposed: bool) -> None:
    """One call, for the note of the program being traced (a step builder's
    ``obs.compile_log.notes_for``); traced outside any, the call leaves its
    own note under ``flash_attention``."""
    call = (lanes, heads, transposed)
    if not runlog.compile_log.gather("flash_layout", call, _layout_text):
        runlog.compile_log.note("flash_layout", "flash_attention",
                                _layout_text([call]))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

# Operands are (rows, T, W): W = H·D lanes of `rows` = B batch rows where
# the call reads the projections' own layout, W = D and `rows` = B·H on the
# transposing path.  One kernel family serves both: a program takes `lanes`
# of W (a block of whole heads, each `d` wide) and walks its heads as
# separate problems over static lane slices.
#
# The row statistic (LSE) rests (rows, lane blocks, T, G): a column a head
# of the block, so a program takes its block's, (1, 1, block_q, G), and a
# head's (block_q, 1) column is a one-lane slice of it.  Mosaic requires a
# block's last two dims (8, 128)-aligned or array-sized, and G equals its
# array dim.  (In HBM and VMEM a row of G values still pads to a tile's 128
# lanes; one column an array, (rows, H, T, 1), would pad twice as much.)


def drop_kv(kern, n_fixed):
    """Adapt a kernel taking ``kv_ref`` at position ``n_fixed`` to the
    no-padding-mask call, where that ref is absent from the grid."""
    def wrapped(*refs, **kw):
        return kern(*refs[:n_fixed], None, *refs[n_fixed:], **kw)
    return wrapped


def _join(heads):
    """A block's heads side by side: one full-lane value to store."""
    return heads[0] if len(heads) == 1 else jnp.concatenate(heads, axis=-1)


def _fwd_kernel(q_ref, k_ref, v_ref, kv_ref, o_ref, lse_ref, *, d: int,
                sm_scale: float, causal: bool, block_k: int, k_len: int,
                window: int | None = None):
    bq, lanes = q_ref.shape[1:]
    q_off = pl.program_id(2) * bq

    n_blocks = k_len // block_k
    lo = 0
    if causal:
        # stop at the diagonal: key blocks fully above it are all-masked
        n_blocks = jnp.minimum(n_blocks,
                               (q_off + bq + block_k - 1) // block_k)
        if window is not None:
            # sliding window: skip key blocks fully below it too
            lo = _window_lo(q_off, window, block_k)

    outs, lses = [], []
    for h in range(lanes // d):                      # the block's heads
        at = slice(h * d, (h + 1) * d)
        q = q_ref[0, :, at]                          # (bq, d), input dtype

        def body(i, carry, q=q, at=at):
            m, l, acc = carry
            k = k_ref[0, pl.ds(i * block_k, block_k), at]
            v = v_ref[0, pl.ds(i * block_k, block_k), at]
            s = _dot(q, k, ((1,), (1,))) * sm_scale  # (bq, bk) f32
            if causal:
                s = _causal_mask(s, q_off, i * block_k, bq, block_k, window)
            if kv_ref is not None:
                valid = kv_ref[0, i]                 # (1, bk) f32
                s = jnp.where(valid > 0, s, NEG_INF)
            blk_max = jnp.max(s, axis=-1, keepdims=True)
            new_m = jnp.maximum(m, blk_max)
            corr = jnp.exp(m - new_m)
            p = jnp.exp(s - new_m)
            new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            pv = _dot(p.astype(v.dtype), v, ((1,), (0,)))
            return new_m, new_l, acc * corr + pv

        m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq, 1), jnp.float32)
        acc0 = jnp.zeros((bq, d), jnp.float32)
        m, l, acc = lax.fori_loop(lo, n_blocks, body, (m0, l0, acc0))
        # all-keys-masked rows (fully-padded sequence) degrade to uniform
        # attention over the visited key blocks (the dense path averages
        # over all Tk; same spirit, padded-row values are garbage either
        # way) — never NaN, and backward treats such rows as zero-gradient
        l = jnp.maximum(l, 1e-30)
        outs.append((acc / l).astype(o_ref.dtype))
        # clamp m before adding log(l): with m = NEG_INF (fully-masked row)
        # f32 absorbs log(l) entirely and the backward's exp(s - lse) would
        # evaluate to 1 per masked key instead of ~0.  Clamped, backward
        # gradients for fully-padded rows are exactly zero (the dense path
        # gives dq = dk = 0 via the mask's where-grad and a ~1/Tk·dO dv; we
        # zero dv too — padded rows contribute no update either way).
        lses.append(jnp.maximum(m, -1e20) + jnp.log(l))
    o_ref[0] = _join(outs)
    lse_ref[0, 0] = _join(lses)


def _fit_block(length: int, requested: int) -> int:
    """Largest divisor of ``length`` not exceeding ``requested`` — block
    sizes adapt to the data's sequence length (user-controlled via real
    token files) instead of hard-failing on indivisible shapes."""
    return max(b for b in range(1, min(requested, length) + 1)
               if length % b == 0)


def _mask_blocks(kvalid, block_k):
    """Padding mask ``(B, 1, Tk)`` → ``(B, Tk // block_k, 1, block_k)``: one
    key block per leading index, which a kernel picks with an untiled
    index.  Slicing the mask along its lane dimension instead must start
    at a multiple of 128, which the chip's compiler cannot prove for
    blocks of 64 or 96 keys (any T below 128 or without a 128-multiple
    divisor) and refuses — invisible in interpret mode."""
    B, _, Tk = kvalid.shape
    return kvalid.reshape(B, Tk // block_k, 1, block_k)


class _Grid:
    """The index maps of one call, grid ``(rows, lane blocks, T blocks)``:
    which block of each operand a program takes."""

    def __init__(self, q, k, kvalid, d, lanes, block_q, block_k):
        self.rows, self.Tq, width = q.shape
        rows = self.rows
        self.Tk = k.shape[1]
        self.lanes, self.heads = lanes, lanes // d
        self.lane_blocks = pl.cdiv(width, lanes)
        self.block_q = _fit_block(self.Tq, block_q)
        self.block_k = _fit_block(self.Tk, block_k)
        # GQA-native (round 5): the kernels stream the TRUE K/V, never a
        # head-expanded copy (the group× HBM saving is the whole point of
        # grouped-query attention).  Transposed, q rows are (batch,
        # kv_head, group_member)-ordered and query row b reads K/V row
        # b // kv_rows; where they rest, query lane block j reads K/V lane
        # block j // kv_lanes.  kvalid is per-batch, shared by every head.
        self.kv_rows = rows // k.shape[0]
        self.kv_lanes = self.lane_blocks // pl.cdiv(k.shape[2], lanes)
        self.valid_rows = rows // kvalid.shape[0] if kvalid is not None else 1

    @staticmethod
    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    def grid(self, t_blocks):
        return self.rows, self.lane_blocks, t_blocks

    def q_block(self):
        return self.spec((1, self.block_q, self.lanes),
                         lambda b, j, i: (b, i, j))

    def q_whole(self):
        return self.spec((1, self.Tq, self.lanes), lambda b, j, i: (b, 0, j))

    def kv_block(self, shared=True):
        r, g = (self.kv_rows, self.kv_lanes) if shared else (1, 1)
        return self.spec((1, self.block_k, self.lanes),
                         lambda b, j, i: (b // r, i, j // g))

    def kv_whole(self):
        r, g = self.kv_rows, self.kv_lanes
        return self.spec((1, self.Tk, self.lanes),
                         lambda b, j, i: (b // r, 0, j // g))

    def stat_block(self):
        return self.spec((1, 1, self.block_q, self.heads),
                         lambda b, j, i: (b, j, i, 0))

    def stat_whole(self):
        return self.spec((1, 1, self.Tq, self.heads),
                         lambda b, j, i: (b, j, 0, 0))

    def mask_whole(self):
        # every key block of this batch row; the size-1 sublane dim keeps
        # the block Mosaic-legal (a (1, bk) block over a 2D mask is not)
        r = self.valid_rows
        return self.spec((1, self.Tk // self.block_k, 1, self.block_k),
                         lambda b, j, i: (b // r, 0, 0, 0))

    def mask_block(self):
        r = self.valid_rows
        return self.spec((1, 1, 1, self.block_k),
                         lambda b, j, i: (b // r, i, 0, 0))

    def stat_shape(self):
        return jax.ShapeDtypeStruct(
            (self.rows, self.lane_blocks, self.Tq, self.heads), jnp.float32)


# jitted, so that a model's layers trace and lower each kernel once between
# them (a 48-layer step traced 96 kernel bodies, a fifth of its set-up)
_STATIC = tuple(range(4, 12))


@functools.partial(jax.jit, static_argnums=_STATIC)
def _flash_fwd(q, k, v, kvalid, sm_scale, causal, block_q, block_k,
               interpret, window, d, lanes):
    g = _Grid(q, k, kvalid, d, lanes, block_q, block_k)
    kernel = functools.partial(
        _fwd_kernel if kvalid is not None else drop_kv(_fwd_kernel, 3),
        d=d, sm_scale=sm_scale, causal=causal, block_k=g.block_k,
        k_len=g.Tk, window=window)
    in_specs = [g.q_block(), g.kv_whole(), g.kv_whole()]
    args = [q, k, v]
    if kvalid is not None:
        in_specs.append(g.mask_whole())
        args.append(_mask_blocks(kvalid, g.block_k))
    out, lse = pl.pallas_call(
        kernel,
        grid=g.grid(g.Tq // g.block_q),
        in_specs=in_specs,
        out_specs=[g.q_block(), g.stat_block()],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   g.stat_shape()],
        # held as long as T is: K and V whole, two buffers each
        compiler_params=_vmem(g.Tk * 4 * lanes * k.dtype.itemsize),
        interpret=interpret, name="flash_fwd",
    )(*args)
    return out, lse


# --------------------------------------------------------------------------
# backward (flash-attention-2 decomposition, one kernel)
# --------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, kv_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *, d: int, sm_scale: float,
                causal: bool, block_q: int, q_len: int,
                window: int | None = None):
    """dQ, dK and dV of one key block of a lane block's heads: a program
    walks the query blocks that attend to its keys and recomputes each
    block of scores ONCE for all three (as two kernels, dQ over query
    blocks and dK/dV over key blocks, every block of scores, its mask, its
    exponentials and ``dP`` were computed twice, and the VPU is what these
    kernels wait for at 64-wide heads).  dK and dV are the program's own;
    dQ sums over the key blocks, which are the grid's innermost axis: it
    accumulates in an f32 scratch that the first key block clears and the
    last one casts into the output block, resident meanwhile."""
    bk, lanes = k_ref.shape[1:]
    j = pl.program_id(2)
    k_off = j * bk
    valid = kv_ref[0, 0] if kv_ref is not None else None     # (1, bk)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    # causal: query blocks strictly above this key block's row range never
    # attend to it — start the loop at the diagonal
    lo = k_off // block_q if causal else 0
    hi = q_len // block_q
    if causal and window is not None:
        # windowed: queries beyond k_pos + window - 1 never attend either
        hi = jnp.minimum(hi,
                         (k_off + bk + window - 2) // block_q + 1)

    dks, dvs = [], []
    for h in range(lanes // d):
        at = slice(h * d, (h + 1) * d)
        k = k_ref[0, :, at]                          # (bk, d)
        v = v_ref[0, :, at]

        def body(i, carry, k=k, v=v, at=at, h=h):
            dk, dv = carry
            rows = pl.ds(i * block_q, block_q)
            q = q_ref[0, rows, at]
            do = do_ref[0, rows, at]
            lse = lse_ref[0, 0, rows, h:h + 1]       # (bq, 1) f32
            # delta = rowsum(dO ⊙ O), here where both blocks are in VMEM
            # anyway (as a pass of XLA's over (B, T, H·D) it cost two
            # layout copies beside; a block of it is 1/bk of a block of
            # scores, so once a key block is as good as once)
            delta = jnp.sum(do.astype(jnp.float32)
                            * o_ref[0, rows, at].astype(jnp.float32),
                            axis=-1, keepdims=True)  # (bq, 1) f32
            s = _dot(q, k, ((1,), (1,))) * sm_scale  # (bq, bk) f32
            if causal:
                s = _causal_mask(s, i * block_q, k_off, block_q, bk, window)
            if valid is not None:
                s = jnp.where(valid > 0, s, NEG_INF)
            p = jnp.exp(s - lse)
            dv = dv + _dot(p.astype(do.dtype), do, ((0,), (0,)))  # (bk, d)
            dp = _dot(do, v, ((1,), (1,)))           # (bq, bk) f32
            ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
            dk = dk + _dot(ds, q, ((0,), (0,)))      # (bk, d)
            dq_acc[h, rows, :] += _dot(ds, k, ((1,), (0,)))       # (bq, d)
            return dk, dv

        zeros = jnp.zeros((bk, d), jnp.float32)
        dk, dv = lax.fori_loop(lo, hi, body, (zeros, zeros))
        dks.append(dk.astype(dk_ref.dtype))
        dvs.append(dv.astype(dv_ref.dtype))
    dk_ref[0] = _join(dks)
    dv_ref[0] = _join(dvs)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = _join([dq_acc[h].astype(dq_ref.dtype)
                           for h in range(lanes // d)])


@functools.partial(jax.jit, static_argnums=tuple(range(7, 15)))
def _flash_bwd(q, k, v, kvalid, out, lse, do, sm_scale, causal, block_q,
               block_k, interpret, window, d, lanes):
    g = _Grid(q, k, kvalid, d, lanes, block_q, block_k)
    rows, Tq, width = q.shape
    # GQA: each query-head program computes ITS contribution to the shared
    # K/V heads' gradients (partials the shape of q's heads); the group-sum
    # reduction to k's shape happens outside in f32 — a group's heads are
    # adjacent by construction (head = kv_head·group + member), rows when
    # transposed and lanes where they rest, so it is one reshape.
    kernel = functools.partial(
        _bwd_kernel if kvalid is not None else drop_kv(_bwd_kernel, 6),
        d=d, sm_scale=sm_scale, causal=causal, block_q=g.block_q, q_len=Tq,
        window=window)
    in_specs = [g.q_whole(), g.kv_block(), g.kv_block(), g.q_whole(),
                g.q_whole(), g.stat_whole()]
    args = [q, k, v, out, do, lse]
    if kvalid is not None:
        in_specs.append(g.mask_block())
        args.append(_mask_blocks(kvalid, g.block_k))
    partial_shape = (rows, g.Tk, width)
    # held as long as T is: q, o, dO and dq whole (two buffers each), the
    # statistic and the f32 dQ scratch, whose rows pad to a tile's lanes
    held = Tq * (8 * lanes * q.dtype.itemsize + 2 * LANES * 4
                 + g.heads * max(d, LANES) * 4)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=g.grid(g.Tk // g.block_k),
        in_specs=in_specs,
        out_specs=[g.q_whole(), g.kv_block(shared=False),
                   g.kv_block(shared=False)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(partial_shape, k.dtype),
                   jax.ShapeDtypeStruct(partial_shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((g.heads, Tq, d), jnp.float32)],
        compiler_params=_vmem(held), interpret=interpret,
        name="flash_bwd",
    )(*args)
    if g.kv_rows > 1 or g.kv_lanes > 1:
        def reduce_group(a, like):
            a = a.reshape(like.shape[0], g.kv_rows, g.Tk,
                          like.shape[2] // d, g.kv_lanes, d)
            return jnp.sum(a.astype(jnp.float32), axis=(1, 4)) \
                .reshape(like.shape).astype(like.dtype)

        dk, dv = reduce_group(dk, k), reduce_group(dv, v)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom_vjp plumbing + public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=_STATIC)
def _flash_rows(q, k, v, kvalid, sm_scale, causal, block_q, block_k,
                interpret, window, d, lanes):
    """Attention over ``(rows, T, W)`` operands, heads `d` wide side by side
    in W, `lanes` of them a program."""
    out, _ = _flash_fwd(q, k, v, kvalid, sm_scale, causal, block_q, block_k,
                        interpret, window, d, lanes)
    return out


def _flash_vjp_fwd(q, k, v, kvalid, *static):
    out, lse = _flash_fwd(q, k, v, kvalid, *static)
    return out, (q, k, v, kvalid, out, lse)


def _flash_vjp_bwd(*args):
    *static, res, g = args
    q, k, v, kvalid, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, kvalid, out, lse, g, *static)
    dkv = None if kvalid is None else jnp.zeros_like(kvalid)
    return dq, dk, dv, dkv


_flash_rows.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


#: default (block_q, block_k) on a TPU backend, and on any other (where the
#: kernels run interpreted).  Constants, not a claim: 512 x 512 is what both
#: train cells have compiled since PR 23, and the ledger reads
#: ``flash_roofline`` 12.07% / 13.33% at it; re-deciding it from traced runs
#: is ROADMAP S3's.
DEFAULT_BLOCKS_TPU = (512, 512)
DEFAULT_BLOCKS_ELSEWHERE = (128, 128)


@functools.cache
def _warn_dense_mask_fallback() -> None:
    import warnings

    warnings.warn(
        "flash attention_fn received a dense mask tensor; routing this "
        "call to the dense path (key_valid/causal stay on the kernel)",
        stacklevel=3)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = False, key_valid: jnp.ndarray | None = None,
                    sm_scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    window: int | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Fused attention on ``(B, T, H, D)`` q — with ``(B, Tk, Hkv, D)``
    k/v where ``Hkv`` divides H (GQA/MQA NATIVE, round 5: the kernel maps
    each query head onto its shared K/V head via the block index maps, so
    the group×-smaller K/V is what streams from HBM; head-expanded copies
    are never materialised).  ``Hkv == H`` is ordinary multi-head (same
    layout as :func:`..models.transformer.dot_product_attention`).

    ``key_valid`` is an optional ``(B, Tk)`` boolean padding mask (True =
    attend); invalid keys are masked in-kernel with the same NEG_INF
    semantics as the dense path.  ``interpret=None`` auto-selects: compiled
    on TPU, interpreter elsewhere (so CPU tests exercise the identical
    kernel code).  Forward and backward are both flash kernels.  What a
    program holds grows with T: the forward K and V a lane block whole,
    the backward q, o, dO and dq whole plus the row statistic and an f32
    dQ scratch, whose rows pad to 128 lanes (4 KiB a position at 64-wide
    heads: 4 MiB at the train cells' 1,024).  Up to T = 2,048 that fits the
    16 MiB of VMEM a kernel gets unasked; past it the kernels ask for what
    they hold (:func:`_vmem`), and both compile for a v5e through T =
    16,384 at D = 64 and 128 and 4,096 at D = 256
    (``tests/test_chip_compile.py``; compiled, not timed).  Beyond that,
    shard ``seq`` (ring attention / Ulysses) first.

    The operands are viewed ``(B, T, H·D)`` and tiled in blocks of lanes
    by :func:`_tiling`'s rule; a shape it cannot tile in whole heads
    (``D`` under 128 with grouped K/V, a ``D`` such as 96) is transposed
    to ``(B·H, T, D)`` first.  Either way the compile log of the program
    being traced gets a ``flash_layout`` note (:func:`_note_call`).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_q is None or block_k is None:
        default_q, default_k = (
            DEFAULT_BLOCKS_TPU if jax.default_backend() == "tpu"
            else DEFAULT_BLOCKS_ELSEWHERE)
        block_q = block_q or default_q
        block_k = block_k or default_k
    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window attention) requires "
                             "causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"{H} query heads not a multiple of {Hkv} KV "
                         "heads (GQA groups must be uniform)")

    kvalid = None
    if key_valid is not None:
        # per-BATCH mask shaped (B, 1, Tk) — the kernels index it by the
        # batch row, so no head expansion is ever materialised; the size-1
        # sublane dim keeps kernel blocks Mosaic-legal; float so the
        # custom_vjp can hand back an ordinary zero cotangent
        kvalid = key_valid.astype(jnp.float32)[:, None, :]
    tiling = _tiling(D, H, Hkv)
    if tiling is None:
        # no block of 128 lanes holds whole heads of q and of their k / v:
        # heads become rows, (B·H, T, D), and a program takes a head
        def rows(x):
            return jnp.swapaxes(x, 1, 2).reshape(-1, x.shape[1], D)

        _note_call(D, 1, True)
        out = _flash_rows(rows(q), rows(k), rows(v), kvalid, sm_scale,
                          causal, block_q, block_k, interpret, window, D, D)
        return jnp.swapaxes(out.reshape(B, H, Tq, D), 1, 2)
    # where the projections wrote them: (B, T, H·D) is a view of a
    # contiguous minor pair, and so is the way back

    def merged(x):
        return x.reshape(B, x.shape[1], -1)

    lanes, heads = tiling
    _note_call(lanes, heads, False)
    out = _flash_rows(merged(q), merged(k), merged(v), kvalid, sm_scale,
                      causal, block_q, block_k, interpret, window, D, lanes)
    return out.reshape(B, Tq, H, D)


def make_attention_fn(causal: bool = False, **kw):
    """Adapter: flash attention as a ``MultiHeadAttention.attention_fn``
    (mirrors :func:`..parallel.ring_attention.make_attention_fn`).

    Supports the structured mask convention (``key_valid`` padding masks +
    a ``causal`` flag) and NATIVE GQA (``attn.supports_gqa``: the layer
    hands over unexpanded ``Hkv``-headed K/V and the kernel maps query
    heads onto shared K/V heads — no head-expanded copy in HBM).  A
    pre-built dense ``mask`` tensor — whose (T×T) materialisation is
    exactly what the kernel avoids — falls back to the dense path for
    THAT call with a one-time warning (VERDICT r4 item 9), so any
    ``MultiHeadAttention(mask=...)`` config still trains under
    ``--attention auto`` instead of crashing.
    """

    forced_causal = causal

    def attn(q, k, v, *, mask=None, key_valid=None, causal=False,
             window=None, dtype=jnp.float32):
        if mask is not None:
            _warn_dense_mask_fallback()
            from distributed_deep_learning_tpu.models.transformer import (
                dot_product_attention)

            # honour maker-baked kernel options on the dense path too:
            # call-time window wins over the maker's; a maker sm_scale is
            # folded into q (dense hardcodes 1/sqrt(d))
            eff_window = window if window is not None else kw.get("window")
            if eff_window is not None and not (causal or forced_causal):
                raise ValueError("window (sliding-window attention) "
                                 "requires causal=True")  # kernel parity
            sm = kw.get("sm_scale")
            if sm is not None:
                q = q * (sm * (q.shape[-1] ** 0.5))
            if k.shape[2] != q.shape[2]:
                # the layer skipped GQA expansion for us; dense needs it
                group = q.shape[2] // k.shape[2]
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            return dot_product_attention(
                q, k, v, mask=mask, key_valid=key_valid,
                causal=causal or forced_causal, window=eff_window,
                dtype=dtype)
        call_kw = dict(kw)
        if window is not None:  # call-time window wins over the maker's
            call_kw["window"] = window

        def kernel(q, k, v, key_valid=None):
            return flash_attention(q, k, v, causal=causal or forced_causal,
                                   key_valid=key_valid, **call_kw)

        return _per_shard(kernel, q, k, v, key_valid).astype(dtype)

    attn.supports_gqa = True
    # the kernels read q, k and v as (B, T, H·D): the layer projects so
    # that this view, and the one back, move nothing
    attn.reads_heads_merged = True
    return attn


def _per_shard(kernel, q, k, v, key_valid):
    """Run ``kernel(q, k, v[, key_valid])`` once per shard of the step's mesh.

    XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so a step whose batch or heads are sharded
    must hand each device its own slice through ``shard_map``: attention is
    independent per (batch row, head), so batch rides the data-parallel
    axes and heads the ``model`` axis, with no collective.  The mesh is the
    one the step builder traced under (``jax.sharding.use_abstract_mesh`` in
    :func:`..train.step.make_step_fns`); axes already manual (inside an
    enclosing ``shard_map``) or of size 1 need nothing, and with none left
    the kernel is called directly — one device, or no mesh at all.
    """
    from jax.sharding import PartitionSpec as P

    from distributed_deep_learning_tpu.runtime.batch_pin import (
        split_axes, split_batch_axes)

    batch = split_batch_axes() or None
    heads = "model" if "model" in split_axes() else None
    args = (q, k, v) if key_valid is None else (q, k, v, key_valid)
    if batch is None and heads is None:
        return kernel(*args)
    qkv = P(batch, None, heads, None)
    specs = (qkv, qkv, qkv) + (() if key_valid is None else (P(batch, None),))
    return jax.shard_map(kernel, in_specs=specs, out_specs=qkv,
                         check_vma=False)(*args)
