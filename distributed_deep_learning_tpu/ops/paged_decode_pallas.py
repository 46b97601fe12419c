"""Paged flash-decode Pallas kernel: K and V are read where they rest.

The paged engine's one-token decode program (``jit_paged_decode``) hands
each full-attention layer its K and V POOL leaves as they rest,
``(num_blocks, block, Hkv*D)`` with the trailing dims merged
(:mod:`..serve.paged`), and the slot's block table beside them; this kernel
attends over them in place, over the blocks that hold live positions only.
Who still gathers (``paged.gather_slot``: ``leaf[table]`` then reshape) and
why: the programs with several queries a slot (``paged_chunk``,
``paged_verify``, ``paged_draft*``), which run the model's own multi-token
cached forward; ``paged_spill``, which wants the at-rest image; and a
window layer's ring in the decode program itself, 65 blocks a slot and
bounded by the window already.

The grid is DATA: the slots' live steps laid end to end (``_work_list``),
a step ``blocks_per_step`` logical blocks of one slot, the grid's length a
traced value (a slot of 40 positions at block 16 and 16 blocks a step has
one step; one of length 0, free or still prefilling, has one too, for its
result, and reads nothing).  What a grid step is rides in scalar-prefetch
memory (SMEM): its slot, its step within the slot, and the physical block
of each of its tiles.  Each of a step's blocks is an operand of its own
whose index map points Pallas' pipeline DMA at that block of the resident
pool: K/V stream straight from where they live.  Past a slot's last live
block a tile's entry repeats what the tile already holds, so no DMA is
issued, and the table's trash-padded tail is never touched.  Why not a
``(slot, step)`` grid over the whole table: the pipeline's bookkeeping
costs the scalar core ~0.09 us an operand and grid step, more than a
block's DMA takes, dead steps included (my chip runs, PR 27: 1.4 ms a call
on a table of 512 blocks with every slot empty).  The online-softmax
running statistics (max ``m``, denominator ``l``, accumulator ``acc``)
carry across a slot's steps in VMEM scratch like the training flash kernel
(:mod:`.attention_pallas`).

The leaf is never relaid into ``(Hkv, D)`` tiles, in HBM or in VMEM (64-wide
heads in a 1,600-wide minor dim are 12.5 lane tiles).  The query is made
BLOCK-DIAGONAL instead: row ``h`` of ``q_bd (H, Hkv*D)`` holds ``q[h]`` in
the columns of KV head ``h // G`` and zero elsewhere, so ``q_bd @ K^T`` is
every head's scores in one matmul a step, ``P @ V`` one more, and the
head's own ``D`` columns are picked off the ``(H, Hkv*D)`` result by the
same mask.  That wastes ``Hkv`` x the FLOPs on a program that is bound by
bytes, and serves grouped queries (``G > 1``) unchanged.

Precision: scores, running max and denominator in float32; ``P`` in the
query's dtype for ``P @ V`` with float32 accumulation, as
:func:`..models.transformer.dot_product_attention` does.  int8 pools
(:class:`..serve.quant.QuantTensor`: payload ``(N, bs, Hkv*D)`` + f32
scales ``(N, bs, Hkv)``) dequantise in register: the scale tile rides the
same block index map as its payload tile and multiplies the scores (K) and
the probabilities (V), a head's scale being constant over its ``D`` columns.

Masking: cached position ``p`` attends iff ``p < seq_lens[b]``, it is valid
(``valid_pool``, gathered through the same table: a byte a position) and
inside the window.  The new token's own K/V row, not yet in the pool, comes
beside it (``k_new`` / ``v_new``) and is the softmax's last term.  A slot
with nothing to attend gives zeros, never NaN; callers ignore those rows.

Off-TPU the dispatcher (:func:`paged_flash_decode`) routes to
:func:`paged_decode_reference`: gather, insert the new row, mask, and the
model's own dense attention, which is what the engine compiled before this
kernel was on its path.  The CPU parity tests run the REAL kernel in
interpreter mode against it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: positions a grid step attends (the default ``blocks_per_step`` covers
#: them): fewer, larger steps than a block a step, each one DMA a block
STEP_POSITIONS = 256


def _nt(a, b):
    """``a (M, K) @ b (N, K)^T`` -> ``(M, N)``, f32 accumulate."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _nn(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _exact(x, onehot, dot=_nn):
    """``dot(x, onehot)`` for a 0/1 bf16 matrix, exact in `x`'s dtype at
    one MXU pass a bf16 term of `x` (one for bf16, three for float32):
    a float32 product at full precision costs six."""
    if x.dtype == jnp.bfloat16:
        return dot(x, onehot)
    rest, out = x.astype(jnp.float32), 0.0
    for _ in range(3):
        term = rest.astype(jnp.bfloat16)
        out = out + dot(term, onehot)
        rest = rest - term.astype(jnp.float32)
    return out


def _rows(refs, dtype):
    """A step's block tiles ``(1, bs, W)`` as one ``(n * bs, W)`` array in
    `dtype`.  Tiles that do not fill a packed sublane tile of their own
    type (bf16: 16 rows, int8: 32) go through float32, whose 8 rows they
    do fill."""
    tiles = [r[0] for r in refs]
    packed = 32 // tiles[0].dtype.itemsize
    if tiles[0].shape[0] % packed or tiles[0].dtype == jnp.int8:
        tiles = [t.astype(jnp.float32) for t in tiles]
    got = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=0)
    return got.astype(dtype)


def _decode_kernel(slot_ref, step_ref, phys_ref, lens_ref, newv_ref, q_ref,
                   valid_ref, pick_ref, tile_ref, fold_ref, *rest, n: int,
                   block_size: int, sm_scale: float, window,
                   quantized: bool, has_new: bool):
    """One step of one slot of the online softmax: grid step ``t`` is step
    ``step_ref[t]`` of slot ``slot_ref[t]``, the slots' live steps laid
    end to end (a slot with nothing cached has one, for its result).

    The BlockSpec index maps below already used ``phys_ref`` to land the
    step's `n` K and `n` V tiles (then their scale tiles) on their
    physical blocks, so the body never sees a physical id.  ``rest``: the
    new row's K and V (with `has_new`), the head-to-scale matrix (with
    `quantized`), those tiles, the output, and the scratch: the
    block-diagonal query, ``m``, ``l``, ``acc``."""
    rest = list(rest)
    kn_ref, vn_ref = (rest.pop(0), rest.pop(0)) if has_new else (None, None)
    spread_ref = rest.pop(0) if quantized else None
    k_refs, v_refs = rest[:n], rest[n:2 * n]
    rest = rest[2 * n:]
    if quantized:
        ks_refs, vs_refs = rest[:n], rest[n:2 * n]
        rest = rest[2 * n:]
    o_ref, qbd_ref, m_ref, l_ref, acc_ref = rest
    t = pl.program_id(0)
    b, j = slot_ref[t], step_ref[t]
    length = lens_ref[b]
    step = n * block_size
    cdt = q_ref.dtype

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # q (H, D) tiled Hkv times along the columns, its own head's kept
        qbd_ref[...] = (_exact(q_ref[0], tile_ref[...])
                        * pick_ref[...].astype(jnp.float32)).astype(cdt)

    @pl.when(j * step < length)
    def _attend():
        s = _nt(qbd_ref[...], _rows(k_refs, cdt)) * sm_scale     # (H, step)
        if quantized:
            s = s * _exact(_rows(ks_refs, jnp.float32), spread_ref[...],
                           lambda scales, heads: _nt(heads, scales))
        kpos = j * step + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = jnp.logical_and(kpos < length, valid_ref[0, 0] > 0)
        if window is not None:
            live = jnp.logical_and(live, length - kpos < window)
        s = jnp.where(live, s, NEG_INF)
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m - new_m)
        p = jnp.where(live, jnp.exp(s - new_m), 0.0)
        m_ref[...] = new_m
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * _exact(_rows(vs_refs, jnp.float32), spread_ref[...],
                           lambda scales, heads: _nt(heads, scales))
        acc_ref[...] = acc_ref[...] * corr + _nn(p.astype(cdt),
                                                 _rows(v_refs, cdt))

    @pl.when((j + 1) * step >= length)
    def _writeout():
        m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
        if has_new:
            # the token's own row: the last term of the softmax
            s = jnp.sum(qbd_ref[...].astype(jnp.float32)
                        * kn_ref[0].astype(jnp.float32),
                        axis=1, keepdims=True) * sm_scale        # (H, 1)
            ok = newv_ref[b] > 0
            s = jnp.where(ok, s, NEG_INF)
            new_m = jnp.maximum(m, s)
            corr = jnp.exp(m - new_m)
            p = jnp.where(ok, jnp.exp(s - new_m), 0.0)
            l = l * corr + p
            acc = acc * corr + (p.astype(cdt).astype(jnp.float32)
                                * vn_ref[0].astype(jnp.float32))
        full = acc / jnp.maximum(l, 1e-30)                       # (H, Hkv*D)
        own = (full * pick_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
        o_ref[0] = _exact(own, fold_ref[...]).astype(o_ref.dtype)


def _work_list(tables, lens, n: int, block_size: int):
    """The grid as data: the slots' live steps laid end to end.

    ``(total, slot, step, phys)``: `total` steps in all (a slot's share is
    its cached positions in steps of ``n * block_size``, and one where it
    has none); for grid step ``t`` its slot and its step within the slot;
    ``phys[i, t]`` the physical block the step's `i`-th tile holds: the
    table's entry while the slot has a block there, else what that tile
    held the step before (an index map that repeats itself issues no
    DMA), so a table's tail is never read."""
    B, Bps = tables.shape
    span = n * block_size
    n_steps = -(-Bps // n)
    steps = jnp.maximum(1, (lens + span - 1) // span)
    ends = jnp.cumsum(steps)
    t = jnp.arange(B * n_steps, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, t, side="right"),
                       B - 1).astype(jnp.int32)
    step = jnp.clip(t - (ends - steps)[slot], 0, n_steps - 1)
    at = step[None] * n + jnp.arange(n, dtype=jnp.int32)[:, None]   # (n, T)
    held = jnp.logical_and(at * block_size < lens[slot][None],
                           (t < ends[-1])[None])
    since = lax.cummax(jnp.where(held, t[None], -1), axis=1)
    phys = tables[slot[None], jnp.minimum(at, Bps - 1)]
    phys = jnp.where(since < 0, 0, jnp.take_along_axis(
        phys, jnp.maximum(since, 0), axis=1))
    return ends[-1], slot, step, phys.astype(jnp.int32)


def _scalars_and_validity(block_tables, seq_lens, new_valid, valid_pool,
                          bs: int, n_steps: int, step: int):
    """What both kernels prefetch and mask by: the tables and lengths as
    int32, the new rows' validity (default: all), and the cached validity
    gathered through the tables, a ``(1, step)`` float tile a slot and
    step (all ones without a `valid_pool`)."""
    B, Bps = block_tables.shape
    tables = block_tables.astype(jnp.int32)
    newv = (jnp.ones((B,), jnp.int32) if new_valid is None
            else new_valid.astype(jnp.int32))
    if valid_pool is None:
        valid = jnp.ones((B, n_steps, 1, step), jnp.float32)
    else:
        valid = valid_pool[tables].reshape(B, Bps * bs).astype(jnp.float32)
        valid = jnp.pad(valid, ((0, 0), (0, n_steps * step - Bps * bs)))
        valid = valid.reshape(B, n_steps, 1, step)
    return tables, seq_lens.astype(jnp.int32), newv, valid


def _split_quant(pool):
    """``(payload, scales or None)`` of a pool leaf."""
    from distributed_deep_learning_tpu.serve.quant import is_quant

    return (pool.q, pool.s) if is_quant(pool) else (pool, None)


def _head_matrices(H: int, Hp: int, Hkv: int, D: int):
    """The 0/1 matrices that stand in for a relayout: `pick` ``(Hp, Hkv*D)``
    row ``h`` marks the columns of KV head ``h // G`` (padding rows none),
    `tile` ``(D, Hkv*D)`` is ``Hkv`` identities side by side, `spread`
    ``(Hp, Hkv)`` row ``h`` marks KV head ``h // G``."""
    G = H // Hkv
    head = np.arange(Hp) // G
    live = (np.arange(Hp) < H)[:, None]
    cols = np.arange(Hkv * D)
    pick = (head[:, None] == cols[None] // D) & live
    tile = np.arange(D)[:, None] == cols[None] % D
    spread = (head[:, None] == np.arange(Hkv)[None]) & live
    return pick, tile, spread


def paged_flash_decode(q, k_pool, v_pool, block_tables, seq_lens, *,
                       k_new=None, v_new=None, new_valid=None,
                       valid_pool=None, window=None, blocks_per_step=None,
                       interpret: bool | None = None):
    """One token's attention for every slot, straight off the paged pools.

    ``q``: ``(B, H, D)``, one query a slot.  ``k_pool`` / ``v_pool``: the
    engine's resident ``(N, bs, Hkv*D)`` pool leaves as they rest (``H``
    a multiple of ``Hkv``), floating, or int8 as a
    :class:`..serve.quant.QuantTensor` with ``(N, bs, Hkv)`` f32 scales.
    ``block_tables``: ``(B, Bps)`` int32 physical ids (trash-padded tails
    fine); ``seq_lens``: ``(B,)`` int32 cached positions a slot attends.
    ``valid_pool``: the ``(N, bs)`` bool leaf of cached validity, or None
    (all valid).  ``k_new`` / ``v_new`` ``(B, Hkv, D)``: the token's own
    row, attended as position ``seq_lens[b]`` (then ``seq_lens < Bps *
    bs``) where ``new_valid[b]`` (default: everywhere).  `window`: a
    causal sliding window in positions.  Returns ``(B, H, D)`` in
    ``q``'s dtype.

    On TPU this is the scalar-prefetch Pallas kernel; elsewhere it is
    :func:`paged_decode_reference`.  ``interpret=True`` forces the kernel
    through the Pallas interpreter (the CPU parity tests).
    """
    if (k_new is None) != (v_new is None):
        raise ValueError("k_new and v_new come together")
    kq, ks = _split_quant(k_pool)
    vq, vs = _split_quant(v_pool)
    if (ks is None) != (vs is None):
        raise ValueError("k and v pools must agree on quantization")
    kw = dict(k_new=k_new, v_new=v_new, new_valid=new_valid,
              valid_pool=valid_pool, window=window)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return paged_decode_reference(q, k_pool, v_pool, block_tables,
                                          seq_lens, **kw)
        interpret = False
    return _kernel_call(q, k_pool, v_pool, block_tables, seq_lens, **kw,
                        blocks_per_step=blocks_per_step, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "blocks_per_step",
                                             "interpret"))
def _kernel_call(q, k_pool, v_pool, block_tables, seq_lens, *, k_new, v_new,
                 new_valid, valid_pool, window, blocks_per_step, interpret):
    """The kernel's call, jitted: every layer of a model calls it with the
    same shapes, so it is traced and lowered once a program, not once a
    layer (48 Mosaic modules cost half a minute of set-up)."""
    kq, ks = _split_quant(k_pool)
    vq, vs = _split_quant(v_pool)
    B, H, D = q.shape
    bs, HD = kq.shape[1:]
    Hkv, Bps = HD // D, block_tables.shape[1]
    if Hkv * D != HD or H % Hkv:
        raise ValueError(f"{H} query heads of {D} against a pool leaf "
                         f"{HD} wide")
    n = blocks_per_step or max(1, STEP_POSITIONS // bs)
    n = min(n, Bps)
    n_steps = -(-Bps // n)
    step = n * bs
    quantized, has_new = ks is not None, k_new is not None
    Hp = -(-H // 8) * 8                      # whole sublane tiles of rows

    tables, lens, newv, valid = _scalars_and_validity(
        block_tables, seq_lens, new_valid, valid_pool, bs, n_steps, step)
    pick, tile, spread = _head_matrices(H, Hp, Hkv, D)
    qp = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))

    total, slot, step_of, phys = _work_list(tables, lens, n, bs)

    def slot_map(t, slot_ref, *_):
        return (slot_ref[t], 0, 0)

    def const_map(t, *_):
        return (0, 0)

    def valid_map(t, slot_ref, step_ref, *_):
        return (slot_ref[t], step_ref[t], 0, 0)

    def pool_map(i):
        return lambda t, slot_ref, step_ref, phys_ref, *_: (
            phys_ref[i, t], 0, 0)

    in_specs = [pl.BlockSpec((1, Hp, D), slot_map),
                pl.BlockSpec((1, 1, 1, step), valid_map),
                pl.BlockSpec((Hp, HD), const_map),
                pl.BlockSpec((D, HD), const_map),
                pl.BlockSpec((HD, D), const_map)]
    args = [qp, valid] + [jnp.asarray(m, jnp.bfloat16)
                          for m in (pick, tile, tile.T)]
    if has_new:
        in_specs += [pl.BlockSpec((1, 1, HD), slot_map)] * 2
        args += [k_new.reshape(B, 1, HD), v_new.reshape(B, 1, HD)]
    if quantized:
        in_specs.append(pl.BlockSpec((Hp, Hkv), const_map))
        args.append(jnp.asarray(spread, jnp.bfloat16))
    for pool, width in ((kq, HD), (vq, HD)) + (
            ((ks, Hkv), (vs, Hkv)) if quantized else ()):
        in_specs += [pl.BlockSpec((1, bs, width), pool_map(i))
                     for i in range(n)]
        args += [pool] * n

    kern = functools.partial(
        _decode_kernel, n=n, block_size=bs, sm_scale=1.0 / (D ** 0.5),
        window=window, quantized=quantized, has_new=has_new)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(total,),          # as many steps as the slots hold, no more
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hp, D), slot_map),
        scratch_shapes=[pltpu.VMEM((Hp, HD), q.dtype),
                        pltpu.VMEM((Hp, 1), jnp.float32),
                        pltpu.VMEM((Hp, 1), jnp.float32),
                        pltpu.VMEM((Hp, HD), jnp.float32)],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_flash_decode",
    )(slot, step_of, phys, lens, newv, *args)
    return out[:, :H]


def paged_slot_attention(q, k_new, v_new, new_valid, k_pool, v_pool,
                         valid_pool, table, length, *, window=None):
    """:func:`paged_flash_decode` for ONE slot (``q (H, D)``, ``k_new`` /
    ``v_new (Hkv, D)``, scalars `new_valid` and `length`, ``table
    (Bps,)``), for use under ``vmap`` over slots that share the pools (the
    paged engine's decode program maps the model over its slots): the
    mapped axis becomes the kernel's slot axis, so the pools stay one
    resident copy and the block tables reach the kernel as scalars, which
    a plain ``vmap`` of the kernel cannot give."""
    def attend(q, k_new, v_new, new_valid, table, length, *pools):
        return paged_flash_decode(
            q, pools[0], pools[1], table, length, k_new=k_new, v_new=v_new,
            new_valid=new_valid, valid_pool=pools[2], window=window)

    @jax.custom_batching.custom_vmap
    def one(*args):
        return attend(*(x[None] for x in args[:6]), *args[6:])[0]

    @one.def_vmap
    def rule(axis_size, in_batched, *args):
        if any(jax.tree.leaves(in_batched[6:])):
            raise NotImplementedError(
                "paged attention under vmap: only the slots' queries, rows, "
                "tables and lengths may be mapped (one pool for all)")
        rows = [x if mapped else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, mapped in zip(args[:6], in_batched[:6])]
        return attend(*rows, *args[6:]), True

    return one(q, k_new, v_new, jnp.asarray(new_valid),
               table, jnp.asarray(length), k_pool, v_pool, valid_pool)


def paged_decode_reference(q, k_pool, v_pool, block_tables, seq_lens, *,
                           k_new=None, v_new=None, new_valid=None,
                           valid_pool=None, window=None):
    """The gather path's arithmetic, step for step: gather each slot's
    logical K/V (``leaf[table]``, :func:`..serve.paged.gather_slot`'s
    move), lift it to the query's dtype, put the new row at its position,
    mask to the causal prefix (and window, and validity) and run the
    model's dense attention.  The semantics the kernel must reproduce,
    and the off-TPU execution path."""
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)
    from distributed_deep_learning_tpu.serve import quant

    B, H, D = q.shape
    bs = _split_quant(k_pool)[0].shape[1]
    T = block_tables.shape[1] * bs
    lens = seq_lens.astype(jnp.int32)

    def logical(pool):
        payload, scale = _split_quant(pool)
        got = payload[block_tables].reshape(B, T, -1, D)
        if scale is None:
            return got.astype(q.dtype)
        scale = scale[block_tables].reshape(B, T, -1, 1)
        return quant.dequant(quant.QuantTensor(got, scale), q.dtype)

    k, v = logical(k_pool), logical(v_pool)
    valid = jnp.ones((B, T), jnp.bool_) if valid_pool is None \
        else valid_pool[block_tables].reshape(B, T)
    kpos = jnp.arange(T)[None]
    if k_new is None:
        mask = kpos < lens[:, None]
    else:
        def put(row, new, at):
            return lax.dynamic_update_slice_in_dim(row, new[None], at, 0)

        k = jax.vmap(put)(k, k_new.astype(q.dtype), lens)
        v = jax.vmap(put)(v, v_new.astype(q.dtype), lens)
        valid = jax.vmap(put)(
            valid, jnp.ones((B,), jnp.bool_) if new_valid is None
            else new_valid.astype(jnp.bool_), lens)
        mask = kpos <= lens[:, None]
    if window is not None:
        mask = jnp.logical_and(mask, lens[:, None] - kpos < window)
    return dot_product_attention(q[:, None], k, v,
                                 mask=mask[:, None, None, :],
                                 key_valid=valid, dtype=q.dtype)[:, 0]


# --- the latent layout: one row a position, read once ----------------------
#
# A latent-attention layer (:class:`..models.transformer.LatentSpec`) caches
# ONE row ``[c | k_r]`` a position, ``kv_rank + rope`` values zero-padded to
# whole lane tiles (512 + 64 -> 640), and in its absorbed form a head's key
# IS that row and its value the row's first ``kv_rank`` columns: one "KV
# head" under every query head.  Handing :func:`paged_flash_decode` the leaf
# as its K pool and again as its V pool would land every live row in VMEM
# twice, doubling the only traffic the latent was made to shrink.  The
# sibling below takes the leaf ONCE: a tile is one operand, scored whole (the
# absorbed query is zero where the row is padding) and sliced, at a
# lane-tile boundary, for the values.  Same grid-as-data (`_work_list`),
# same running softmax; no head matrices, since with one KV head the query
# needs no block-diagonal form.

def latent_bytes_a_row(pool, slots: int, blocks_per_slot: int) -> int:
    """Bytes :func:`paged_latent_decode`'s kernel lands in VMEM a live
    position, read off the call as it is TRACED for these shapes, not
    declared: the ``pallas_call``'s operands that are `pool` (the layer's
    ``(N, bs, W)`` leaf; each is one ``(1, bs, W)`` tile a grid step, a
    row's padding included: a tile is moved whole) against the positions
    a grid step covers (the last dim of its validity tile).  One operand a
    tile gives the row's own bytes; K and V handed apart would give twice
    them."""
    B, W = slots, pool.shape[-1]
    like = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(functools.partial(
        _latent_call, v_width=W, sm_scale=1.0, new_valid=None,
        valid_pool=None, blocks_per_step=None, interpret=False))(
        like((B, 8, W), pool.dtype), like(pool.shape, pool.dtype),
        like((B, blocks_per_slot), jnp.int32), like((B,), jnp.int32),
        like((B, W), pool.dtype))
    return _pool_bytes_a_position(jaxpr.jaxpr, pool)


def _pool_bytes_a_position(jaxpr, pool) -> int:
    """Over the ``pallas_call`` s of `jaxpr` (inner jits included)."""
    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    total = 0
    for call in calls(jaxpr):
        shapes = [v.aval.shape for v in call.invars]
        (step,) = {s[-1] for s in shapes if len(s) == 4}     # validity tile
        tiles = sum(s == tuple(pool.shape) for s in shapes)
        total += (tiles * pool.shape[1] * pool.shape[2]
                  * pool.dtype.itemsize) // step
    return total


def _latent_kernel(slot_ref, step_ref, phys_ref, lens_ref, newv_ref, q_ref,
                   valid_ref, new_ref, *rest, n: int, block_size: int,
                   sm_scale: float, v_width: int):
    """One step of one slot, as `_decode_kernel`: ``rest`` is the step's
    `n` row tiles ``(1, bs, W)``, the output ``(1, Hp, v_width)`` and the
    scratch ``m``, ``l``, ``acc``."""
    tiles, (o_ref, m_ref, l_ref, acc_ref) = rest[:n], rest[n:]
    t = pl.program_id(0)
    b, j = slot_ref[t], step_ref[t]
    length = lens_ref[b]
    step = n * block_size
    cdt = q_ref.dtype

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * step < length)
    def _attend():
        rows = _rows(tiles, cdt)                                 # (step, W)
        s = _nt(q_ref[0], rows) * sm_scale                       # (Hp, step)
        kpos = j * step + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = jnp.logical_and(kpos < length, valid_ref[0, 0] > 0)
        s = jnp.where(live, s, NEG_INF)
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m - new_m)
        p = jnp.where(live, jnp.exp(s - new_m), 0.0)
        m_ref[...] = new_m
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _nn(p.astype(cdt),
                                                 rows[:, :v_width])

    @pl.when((j + 1) * step >= length)
    def _writeout():
        m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
        new = new_ref[0].astype(jnp.float32)                     # (1, W)
        s = jnp.sum(q_ref[0].astype(jnp.float32) * new, axis=1,
                    keepdims=True) * sm_scale                    # (Hp, 1)
        ok = newv_ref[b] > 0
        s = jnp.where(ok, s, NEG_INF)
        new_m = jnp.maximum(m, s)
        corr = jnp.exp(m - new_m)
        p = jnp.where(ok, jnp.exp(s - new_m), 0.0)
        l = l * corr + p
        acc = acc * corr + (p.astype(cdt).astype(jnp.float32)
                            * new[:, :v_width])
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_latent_decode(q, pool, block_tables, seq_lens, row_new, *,
                        v_width: int, sm_scale: float, new_valid=None,
                        valid_pool=None, blocks_per_step=None,
                        interpret: bool | None = None):
    """One token's ABSORBED latent attention for every slot, straight off
    the latent pool, each live row read once.

    ``q``: ``(B, H, W)`` absorbed queries, `W` the leaf's width (``kv_rank
    + rope`` and its padding, where the query is zero).  `pool`:
    the resident ``(N, bs, W)`` leaf.  ``block_tables`` ``(B, Bps)``,
    ``seq_lens`` ``(B,)``, ``valid_pool`` ``(N, bs)`` or None as for
    :func:`paged_flash_decode`.  ``row_new`` ``(B, W)``: the token's own
    row, attended as position ``seq_lens[b]`` where ``new_valid[b]``.
    Scores are ``q . row * sm_scale``; the values are a row's first
    `v_width` columns.  Returns ``(B, H, v_width)`` in ``q``'s dtype.

    On TPU the Pallas kernel; elsewhere :func:`paged_latent_reference`;
    ``interpret=True`` forces the kernel through the interpreter."""
    kw = dict(v_width=v_width, sm_scale=sm_scale, new_valid=new_valid,
              valid_pool=valid_pool)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return paged_latent_reference(q, pool, block_tables, seq_lens,
                                          row_new, **kw)
        interpret = False
    return _latent_call(q, pool, block_tables, seq_lens, row_new, **kw,
                        blocks_per_step=blocks_per_step, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "v_width", "sm_scale", "blocks_per_step", "interpret"))
def _latent_call(q, pool, block_tables, seq_lens, row_new, *, v_width,
                 sm_scale, new_valid, valid_pool, blocks_per_step,
                 interpret):
    """The latent kernel's call, jitted so that every layer of a program
    shares one trace and one lowering."""
    B, H, W = q.shape
    bs = pool.shape[1]
    Bps = block_tables.shape[1]
    if pool.shape[2] != W or not 0 < v_width <= W:
        raise ValueError(f"absorbed queries {W} wide, values {v_width}, "
                         f"against a pool leaf {pool.shape[2]} wide")
    n = min(blocks_per_step or max(1, STEP_POSITIONS // bs), Bps)
    n_steps = -(-Bps // n)
    step = n * bs
    Hp = -(-H // 8) * 8

    tables, lens, newv, valid = _scalars_and_validity(
        block_tables, seq_lens, new_valid, valid_pool, bs, n_steps, step)
    total, slot, step_of, phys = _work_list(tables, lens, n, bs)

    def slot_map(t, slot_ref, *_):
        return (slot_ref[t], 0, 0)

    def pool_map(i):
        return lambda t, slot_ref, step_ref, phys_ref, *_: (
            phys_ref[i, t], 0, 0)

    in_specs = [pl.BlockSpec((1, Hp, W), slot_map),
                pl.BlockSpec((1, 1, 1, step),
                             lambda t, slot_ref, step_ref, *_: (
                                 slot_ref[t], step_ref[t], 0, 0)),
                pl.BlockSpec((1, 1, W), slot_map)]
    in_specs += [pl.BlockSpec((1, bs, W), pool_map(i)) for i in range(n)]
    kern = functools.partial(_latent_kernel, n=n, block_size=bs,
                             sm_scale=sm_scale, v_width=v_width)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(total,), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hp, v_width), slot_map),
            scratch_shapes=[pltpu.VMEM((Hp, 1), jnp.float32),
                            pltpu.VMEM((Hp, 1), jnp.float32),
                            pltpu.VMEM((Hp, v_width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hp, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_latent_decode",
    )(slot, step_of, phys, lens, newv,
      jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0))), valid,
      row_new.reshape(B, 1, W), *([pool] * n))   # ONE operand a tile
    return out[:, :H]


def paged_latent_slot_attention(q, row_new, new_valid, pool, valid_pool,
                                table, length, *, spec):
    """:func:`paged_latent_decode` for ONE slot (``q (H, W)``, ``row_new
    (W,)``, scalars `new_valid` and `length`, ``table (Bps,)``) under
    ``vmap`` over slots that share the pool, as
    :func:`paged_slot_attention`; `spec` is the layer's
    :class:`..models.transformer.LatentSpec`."""
    def attend(q, row_new, new_valid, table, length, pool, valid_pool):
        return paged_latent_decode(
            q, pool, table, length, row_new, v_width=spec.kv_rank,
            sm_scale=spec.scale, new_valid=new_valid, valid_pool=valid_pool)

    @jax.custom_batching.custom_vmap
    def one(*args):
        return attend(*(x[None] for x in args[:5]), *args[5:])[0]

    @one.def_vmap
    def rule(axis_size, in_batched, *args):
        if any(jax.tree.leaves(in_batched[5:])):
            raise NotImplementedError(
                "paged latent attention under vmap: only the slots' "
                "queries, rows, tables and lengths may be mapped (one pool "
                "for all)")
        rows = [x if mapped else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, mapped in zip(args[:5], in_batched[:5])]
        return attend(*rows, *args[5:]), True

    return one(q, row_new, jnp.asarray(new_valid), table,
               jnp.asarray(length), pool, valid_pool)


def paged_latent_reference(q, pool, block_tables, seq_lens, row_new, *,
                           v_width: int, sm_scale: float, new_valid=None,
                           valid_pool=None):
    """What :func:`paged_latent_decode` must reproduce, and the off-TPU
    path: gather each slot's rows (``leaf[table]``), put the new row at
    its position, mask to the causal prefix and validity, and the model's
    own plain absorbed attention."""
    from distributed_deep_learning_tpu.models.transformer import (
        latent_absorbed_attention)

    B, H, W = q.shape
    T = block_tables.shape[1] * pool.shape[1]
    lens = seq_lens.astype(jnp.int32)
    rows = pool[block_tables].reshape(B, T, W).astype(q.dtype)
    valid = jnp.ones((B, T), jnp.bool_) if valid_pool is None \
        else valid_pool[block_tables].reshape(B, T)

    def put(row, new, at):
        return lax.dynamic_update_slice_in_dim(row, new[None], at, 0)

    rows = jax.vmap(put)(rows, row_new.astype(q.dtype), lens)
    valid = jax.vmap(put)(
        valid, jnp.ones((B,), jnp.bool_) if new_valid is None
        else new_valid.astype(jnp.bool_), lens)
    seen = jnp.logical_and(jnp.arange(T)[None] <= lens[:, None], valid)
    return latent_absorbed_attention(q, rows, seen, kv_rank=v_width,
                                     scale=sm_scale, dtype=q.dtype)
