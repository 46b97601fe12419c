"""Slot-based static KV cache: one allocation, any request mix.

The model's decode cache (:class:`..models.transformer.MultiHeadAttention`
``decode=True``) is a per-call pytree shaped ``(B, total_len, Hkv, D)``
with ONE scalar ``cache_index`` shared by all rows — correct for
batch-synchronous :func:`..models.transformer.generate`, useless for
continuous batching where every sequence sits at its own position.

This module re-hosts that exact cache as a SLOT TABLE: each array leaf
gains a leading ``max_slots`` axis and loses the per-call batch axis
(``cached_key``: ``(max_slots, max_len, Hkv, D)`` per layer), and each
scalar counter (``cache_index``, ``pos_index``) becomes a ``(max_slots,)``
vector — per-slot positions, the whole point.  Nothing about the model's
cache semantics is reimplemented: the engine vmaps the model's own
single-sequence decode over the slot axis (:func:`lift` / :func:`unlift`
move one slot between table layout and the model's ``B=1`` layout), so
slot decode is correct BY CONSTRUCTION — it is literally the tested
decode path, batched over slots.

All shapes here are static: requests enter and leave slots by writing
into this table (:func:`write_slot`), never by changing an array shape,
which is what lets the decode step compile once and be reused for the
engine's lifetime.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_deep_learning_tpu.models.transformer import init_cache

#: cache-collection leaf names that are sequence-position counters; these
#: are the leaves prefill must pin to the TRUE prompt length after a
#: bucket-padded forward (fix_counters) and that become (max_slots,)
#: vectors in the slot table.
COUNTER_LEAVES = ("cache_index", "pos_index")

#: cache-collection leaf names that hold actual key/value tensors — the
#: leaves the serving quantization path (:mod:`.quant`) stores in reduced
#: precision (``ring_*``: a window layer's ring under the paged engine;
#: ``latent_kv``: a latent-attention layer's one row a position).
#: ``cached_valid`` / ``ring_valid`` (bool) and the counters stay exact.
KV_LEAVES = ("cached_key", "cached_value", "ring_key", "ring_value",
             "latent_kv")


def _leaf_name(path) -> str:
    last = path[-1]
    return last.key if hasattr(last, "key") else str(last)


def allocate_slots(lm, max_slots: int, max_len: int,
                   token_dtype=jnp.int32):
    """Zeroed slot table for ``max_slots`` sequences of up to ``max_len``.

    Built from the decode model's own cache shapes (``eval_shape`` of a
    ``(1, max_len)`` init — no forward, no parameter init): array leaves
    swap their ``B=1`` axis for a ``max_slots`` axis, scalar counters
    become ``(max_slots,)``.
    """
    per_slot = init_cache(lm, 1, max_len, token_dtype)

    def alloc(leaf):
        if leaf.ndim == 0:                      # scalar counter
            return jnp.zeros((max_slots,), leaf.dtype)
        return jnp.zeros((max_slots,) + leaf.shape[1:], leaf.dtype)

    return jax.tree.map(alloc, per_slot)


def fresh_slot(slots):
    """A zeroed model-layout (``B=1``) cache matching one slot of
    ``slots`` — the blank cache a prefill forward fills in.  Pure shape
    work, so it is free inside a jitted prefill program."""
    def one(leaf):
        if leaf.ndim == 1:                      # (max_slots,) counter
            return jnp.zeros((), leaf.dtype)
        return jnp.zeros((1,) + leaf.shape[1:], leaf.dtype)

    return jax.tree.map(one, slots)


def lift(slot_cache):
    """One slot's leaves (no batch axis, scalar counters) -> the model's
    ``B=1`` cache layout.  Used under ``vmap`` over the slot axis."""
    return jax.tree.map(lambda x: x[None] if jnp.ndim(x) else x,
                        slot_cache)


def unlift(cache):
    """Inverse of :func:`lift`: drop the ``B=1`` axis, keep scalars."""
    return jax.tree.map(lambda x: x[0] if jnp.ndim(x) else x, cache)


def write_slot(slots, cache, slot, quantizer=None):
    """Write a model-layout (``B=1``) ``cache`` into row ``slot`` of the
    table.  ``slot`` may be traced (an int32 scalar), so one compiled
    prefill program serves every slot.

    Precision contract: a floating-point update may land in a LOWER
    floating precision slab (bf16 — the cast IS the quantization), but
    writing it into an INTEGER slab through a bare ``astype`` would
    silently round-and-wrap with no scale.  Integer slabs therefore
    require ``quantizer`` (a leaf map producing the slab's exact dtype,
    normally built on :mod:`.quant`'s scale-aware path); without one the
    write raises instead of corrupting the cache.
    """
    def wr(slab, upd):
        if slab.ndim == 1:                      # counter vector <- scalar
            upd = jnp.reshape(upd, (1,)).astype(slab.dtype)
            return jax.lax.dynamic_update_slice(slab, upd, (slot,))
        if jnp.issubdtype(slab.dtype, jnp.integer) and \
                jnp.issubdtype(upd.dtype, jnp.floating):
            if quantizer is None:
                raise TypeError(
                    f"write_slot: float {upd.dtype} update into an "
                    f"integer {slab.dtype} slab — a bare astype would "
                    "truncate without a scale; pass quantizer= (the "
                    "scale-aware serve.quant path)")
            upd = quantizer(upd)
            if upd.dtype != slab.dtype:
                raise TypeError(
                    f"write_slot: quantizer produced {upd.dtype}, "
                    f"slab holds {slab.dtype}")
        starts = (slot,) + (0,) * (slab.ndim - 1)
        return jax.lax.dynamic_update_slice(slab, upd.astype(slab.dtype),
                                            starts)

    return jax.tree.map(wr, slots, cache)


def fix_counters(cache, value):
    """Pin every position counter in a model-layout cache to ``value``.

    A bucket-padded prefill advances ``cache_index``/``pos_index`` by the
    PADDED length; resetting them to the true prompt length makes the
    next decode token overwrite the first pad position and take the
    correct (learned or rotary) position — bucket padding then has no
    numerical trace at all (the tail garbage K/V sit at positions the
    causal prefix mask can never reach before they are overwritten).
    """
    def fix(path, leaf):
        if _leaf_name(path) in COUNTER_LEAVES:
            return jnp.broadcast_to(jnp.asarray(value, leaf.dtype),
                                    leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)
