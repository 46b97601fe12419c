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
* **A real flash backward**: two Pallas kernels (dQ; dK/dV fused) recompute
  scores blockwise from the forward's saved LSE — O(T·D) HBM traffic in
  backward too.  The forward emits LSE precisely to enable this (the
  standard flash-attention-2 decomposition: ``delta = rowsum(dO·O)`` then
  ``ds = p·(dO·Vᵀ − delta)``).

Grid: one program per (batch·head, query-block) forward / (batch·head,
query-block) for dQ / (batch·head, key-block) for dK/dV; inner loops are
``fori_loop`` with *dynamic* (diagonal-bounded) trip counts — uniform
control flow, nothing shape-dependent.

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
# forward
# --------------------------------------------------------------------------

# The row-statistic (LSE) tensor is stored (BH, T, 1): Mosaic requires block
# shapes' last two dims to be (8, 128)-aligned or array-sized, which a
# (1, block_q) spec over a 2D (BH, T) array violates — but a trailing
# size-1 dim equals its array dim, so (1, block_q, 1) blocks are legal and
# cost 4 bytes/row instead of the official kernel's 128-lane broadcast.


def drop_kv(kern, n_fixed):
    """Adapt a kernel taking ``kv_ref`` at position ``n_fixed`` to the
    no-padding-mask call, where that ref is absent from the grid."""
    def wrapped(*refs, **kw):
        return kern(*refs[:n_fixed], None, *refs[n_fixed:], **kw)
    return wrapped


def _fwd_kernel(q_ref, k_ref, v_ref, kv_ref, o_ref, lse_ref, *,
                sm_scale: float, causal: bool, block_k: int, k_len: int,
                window: int | None = None):
    q = q_ref[0]                                     # (bq, D), input dtype
    bq, d = q.shape
    q_off = pl.program_id(1) * bq

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :]
        v = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _dot(q, k, ((1,), (1,))) * sm_scale      # (bq, bk) f32
        if causal:
            s = _causal_mask(s, q_off, i * block_k, bq, block_k, window)
        if kv_ref is not None:
            valid = kv_ref[0, i]                     # (1, bk) f32
            s = jnp.where(valid > 0, s, NEG_INF)
        blk_max = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = _dot(p.astype(v.dtype), v, ((1,), (0,)))
        return new_m, new_l, acc * corr + pv

    n_blocks = k_len // block_k
    lo = 0
    if causal:
        # stop at the diagonal: key blocks fully above it are all-masked
        n_blocks = jnp.minimum(n_blocks,
                               (q_off + bq + block_k - 1) // block_k)
        if window is not None:
            # sliding window: skip key blocks fully below it too
            lo = _window_lo(q_off, window, block_k)
    m, l, acc = lax.fori_loop(lo, n_blocks, body, (m0, l0, acc0))
    # all-keys-masked rows (fully-padded sequence) degrade to uniform
    # attention over the visited key blocks (the dense path averages over
    # all Tk; same spirit, padded-row values are garbage either way) —
    # never NaN, and backward treats such rows as zero-gradient
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # clamp m before adding log(l): with m = NEG_INF (fully-masked row)
    # f32 absorbs log(l) entirely and the backward's exp(s - lse) would
    # evaluate to 1 per masked key instead of ~0.  Clamped, backward
    # gradients for fully-padded rows are exactly zero (the dense path
    # gives dq = dk = 0 via the mask's where-grad and a ~1/Tk·dO dv; we
    # zero dv too — padded rows contribute no update either way).
    lse_ref[0] = jnp.maximum(m, -1e20) + jnp.log(l)


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


def _flash_fwd(q, k, v, kvalid, sm_scale, causal, block_q, block_k,
               interpret, window=None):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    block_q = _fit_block(Tq, block_q)
    block_k = _fit_block(Tk, block_k)
    # GQA-native (round 5): q rows are (batch, kv_head, group_member)-
    # ordered, so query program b reads K/V row b // kv_group — the kernel
    # streams the TRUE (B·Hkv) K/V, never a (B·H) head-expanded copy (the
    # group× HBM saving is the whole point of grouped-query attention).
    # kvalid is per-batch, shared by every head: row b // valid_group.
    kv_group = BH // k.shape[0]
    valid_group = BH // kvalid.shape[0] if kvalid is not None else 1
    kernel = functools.partial(
        _fwd_kernel if kvalid is not None else drop_kv(_fwd_kernel, 3),
        sm_scale=sm_scale, causal=causal, block_k=block_k, k_len=Tk,
        window=window)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, qi: (b, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, Tk, D), lambda b, qi: (b // kv_group, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, Tk, D), lambda b, qi: (b // kv_group, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q, k, v]
    if kvalid is not None:
        # every key block of this batch row; the size-1 sublane dim keeps
        # the block Mosaic-legal (a (1, bk) block over a 2D mask is not)
        in_specs.append(pl.BlockSpec(
            (1, Tk // block_k, 1, block_k),
            lambda b, qi: (b // valid_group, 0, 0, 0),
            memory_space=pltpu.VMEM))
        args.append(_mask_blocks(kvalid, block_k))
    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, Tq // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi: (b, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, qi: (b, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32)],
        interpret=interpret,
    )(*args)
    return out, lse


# --------------------------------------------------------------------------
# backward (flash-attention-2 decomposition, two kernels)
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kv_ref,
               dq_ref, *, sm_scale: float, causal: bool, block_k: int,
               k_len: int, window: int | None = None):
    q = q_ref[0]                                     # (bq, D)
    do = do_ref[0]
    bq, d = q.shape
    q_off = pl.program_id(1) * bq
    lse = lse_ref[0]                                 # (bq, 1) f32
    delta = delta_ref[0]                             # (bq, 1) f32

    def body(i, acc):
        k = k_ref[0, pl.ds(i * block_k, block_k), :]
        v = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _dot(q, k, ((1,), (1,))) * sm_scale
        if causal:
            s = _causal_mask(s, q_off, i * block_k, bq, block_k, window)
        if kv_ref is not None:
            valid = kv_ref[0, i]                     # (1, bk)
            s = jnp.where(valid > 0, s, NEG_INF)
        p = jnp.exp(s - lse)                         # (bq, bk) f32
        dp = _dot(do, v, ((1,), (1,)))               # (bq, bk) f32
        ds = p * (dp - delta) * sm_scale
        return acc + _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    n_blocks = k_len // block_k
    lo = 0
    if causal:
        n_blocks = jnp.minimum(n_blocks,
                               (q_off + bq + block_k - 1) // block_k)
        if window is not None:
            lo = _window_lo(q_off, window, block_k)
    acc = lax.fori_loop(lo, n_blocks, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kv_ref,
                dk_ref, dv_ref, *, sm_scale: float, causal: bool,
                block_q: int, q_len: int, window: int | None = None):
    k = k_ref[0]                                     # (bk, D)
    v = v_ref[0]
    bk, d = k.shape
    k_off = pl.program_id(1) * bk
    valid = kv_ref[0, 0] if kv_ref is not None else None     # (1, bk)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]     # (bq, 1)
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = _dot(q, k, ((1,), (1,))) * sm_scale      # (bq, bk) f32
        if causal:
            s = _causal_mask(s, i * block_q, k_off, block_q, bk, window)
        if valid is not None:
            s = jnp.where(valid > 0, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + _dot(p.astype(do.dtype), do, ((0,), (0,)))   # (bk, D)
        dp = _dot(do, v, ((1,), (1,)))               # (bq, bk) f32
        ds = p * (dp - delta) * sm_scale
        dk = dk + _dot(ds.astype(q.dtype), q, ((0,), (0,)))    # (bk, D)
        return dk, dv

    zeros = jnp.zeros((bk, d), jnp.float32)
    # causal: query blocks strictly above this key block's row range never
    # attend to it — start the loop at the diagonal
    lo = k_off // block_q if causal else 0
    hi = q_len // block_q
    if causal and window is not None:
        # windowed: queries beyond k_pos + window - 1 never attend either
        hi = jnp.minimum(hi,
                         (k_off + bk + window - 2) // block_q + 1)
    dk, dv = lax.fori_loop(lo, hi, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, kvalid, out, lse, g, sm_scale, causal, block_q,
               block_k, interpret, window=None):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    block_q = _fit_block(Tq, block_q)
    block_k = _fit_block(Tk, block_k)
    kv_group = BH // k.shape[0]  # GQA: K/V rows shared by `group` q heads
    valid_group = BH // kvalid.shape[0] if kvalid is not None else 1
    # delta = rowsum(dO ⊙ O), precomputed ONCE (plain XLA, fuses with the
    # surrounding graph) and threaded to both kernels like lse — cheaper
    # than streaming O into the kernels and recomputing per key block
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    qspec = pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM)
    qfull = pl.BlockSpec((1, Tq, D), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kfull = pl.BlockSpec((1, Tk, D), lambda b, i: (b // kv_group, 0, 0),
                         memory_space=pltpu.VMEM)
    kblk_shared = pl.BlockSpec((1, block_k, D),
                               lambda b, i: (b // kv_group, i, 0),
                               memory_space=pltpu.VMEM)
    lseblk = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    lsefull = pl.BlockSpec((1, Tq, 1), lambda b, i: (b, 0, 0),
                           memory_space=pltpu.VMEM)
    kvfull = pl.BlockSpec((1, Tk // block_k, 1, block_k),
                          lambda b, i: (b // valid_group, 0, 0, 0),
                          memory_space=pltpu.VMEM)
    kvblk = pl.BlockSpec((1, 1, 1, block_k),
                         lambda b, i: (b // valid_group, i, 0, 0),
                         memory_space=pltpu.VMEM)
    if kvalid is not None:
        kvalid = _mask_blocks(kvalid, block_k)

    # ---- dQ: grid over query blocks -------------------------------------
    dq_kernel = functools.partial(
        _dq_kernel if kvalid is not None else drop_kv(_dq_kernel, 6),
        sm_scale=sm_scale, causal=causal, block_k=block_k, k_len=Tk,
        window=window)
    dq_specs = [qspec, kfull, kfull, qspec, lseblk, lseblk]
    dq_args = [q, k, v, g, lse, delta]
    if kvalid is not None:
        dq_specs.append(kvfull)
        dq_args.append(kvalid)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, Tq // block_q),
        in_specs=dq_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*dq_args)

    # ---- dK/dV (fused): grid over key blocks ----------------------------
    # GQA: each query-head program computes ITS contribution to the shared
    # K/V rows' gradients ((BH, Tk, D) partials); the group-sum reduction
    # to (B·Hkv, Tk, D) happens outside in f32 — group rows are adjacent
    # by construction (b = kv_row·group + member), so it is one reshape.
    dkv_kernel = functools.partial(
        _dkv_kernel if kvalid is not None else drop_kv(_dkv_kernel, 6),
        sm_scale=sm_scale, causal=causal, block_q=block_q, q_len=Tq,
        window=window)
    dkv_specs = [qfull, kblk_shared, kblk_shared, qfull, lsefull, lsefull]
    dkv_args = [q, k, v, g, lse, delta]
    if kvalid is not None:
        dkv_specs.append(kvblk)
        dkv_args.append(kvalid)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, Tk // block_k),
        in_specs=dkv_specs,
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, D), v.dtype)],
        interpret=interpret,
    )(*dkv_args)
    if kv_group > 1:
        def reduce_group(a, dtype):
            a = a.reshape(k.shape[0], kv_group, Tk, D)
            return jnp.sum(a.astype(jnp.float32), axis=1).astype(dtype)

        dk = reduce_group(dk, k.dtype)
        dv = reduce_group(dv, v.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom_vjp plumbing + public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_bhtd(q, k, v, kvalid, sm_scale, causal, block_q, block_k,
                interpret, window):
    out, _ = _flash_fwd(q, k, v, kvalid, sm_scale, causal, block_q, block_k,
                        interpret, window)
    return out


def _flash_vjp_fwd(q, k, v, kvalid, sm_scale, causal, block_q, block_k,
                   interpret, window):
    out, lse = _flash_fwd(q, k, v, kvalid, sm_scale, causal, block_q,
                          block_k, interpret, window)
    return out, (q, k, v, kvalid, out, lse)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, window,
                   res, g):
    q, k, v, kvalid, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, kvalid, out, lse, g, sm_scale, causal,
                            block_q, block_k, interpret, window)
    dkv = None if kvalid is None else jnp.zeros_like(kvalid)
    return dq, dk, dv, dkv


_flash_bhtd.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


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
    kernel code).  Forward and backward are both flash kernels; the
    largest per-program VMEM residency (dK/dV kernel: Q and dO full plus
    K/V blocks and the (T, 1) lse/delta rows) stays under ~5 MB of the
    ~16 MB budget through T ≈ 16k at D=64 — beyond that, shard ``seq``
    (ring attention / Ulysses) first.
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

    def to_bhtd(x):
        return jnp.swapaxes(x, 1, 2).reshape(B * x.shape[2], x.shape[1], D)

    kvalid = None
    if key_valid is not None:
        # per-BATCH mask shaped (B, 1, Tk) — the kernels index it with
        # b // valid_group, so no head expansion is ever materialised; the
        # size-1 sublane dim keeps kernel blocks Mosaic-legal; float so
        # the custom_vjp can hand back an ordinary zero cotangent
        kvalid = key_valid.astype(jnp.float32)[:, None, :]
    out = _flash_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v), kvalid, sm_scale,
                      causal, block_q, block_k, interpret, window)
    return jnp.swapaxes(out.reshape(B, H, Tq, D), 1, 2)


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
