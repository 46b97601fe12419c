"""Paged KV cache: fixed-size blocks, refcounts, hash-keyed prefix reuse.

The slot table (:mod:`.cache`) gives every slot a private ``max_len``
stripe of KV — correct, but at planet scale fatally wasteful: a million
requests sharing one system prompt each re-prefill it, and each holds a
private copy of identical KV.  This module re-hosts the cache one level
lower, as vLLM-style PAGES:

* **Device side** — each sequence-axis cache leaf ``(1, T, *trailing)``
  becomes a pool ``(num_blocks, block_size, prod(trailing))``: K/V
  ``(nb, bs, H*D)``, int8 scales ``(nb, bs, H)``, validity ``(nb, bs)``.
  A slot's logical cache is the concatenation of the physical blocks
  its BLOCK TABLE names.  :func:`gather_slot` materialises one slot
  back into the model's ``B=1`` cache layout, trailing dims restored
  from the slot's template (:func:`slot_template`) — so the programs
  with several queries a slot (chunk prefill, verify, the draft's) run
  the model's own tested cached forward, paging invisible to the model —
  and :func:`scatter_span` writes freshly-computed KV positions back
  into their blocks.  The one-token decode program does not gather
  full-kind leaves: :func:`decode_view` hands the model the pool leaves
  themselves and the slot's block table, and attention reads the live
  blocks in place (:mod:`..ops.paged_decode_pallas`).  All shapes are
  static; tables/positions are data, so the compile-once contract
  survives intact.

  Why the trailing dims are merged AT REST: a TPU tiles the two minor
  dims of a buffer (8 x 128 for bf16 pairs), and for a ``(H, D)`` minor
  pair like GPT-2 XL's 25 x 64 it would pad 2.56x, so it rests a 4-D
  pool leaf block-index-minor instead — while the gather and the
  scatter compute row-major.  Every program then copied every whole
  pool leaf into the computing layout and back to write a handful of
  positions: 73-91% of the device time of a serving tick (PERF.md,
  PR 24 / PR 25).  ``H*D`` minor (1600, 1024, ...) is lane-dense as it
  is, rests row-major, and the scatter updates the donated pool in
  place; ``tests/test_chip_compile.py`` holds the compiler to it.
* **Host side** — :class:`BlockManager` owns the free list, per-block
  refcounts, per-slot tables, and a :class:`PrefixIndex` keyed by a
  ROLLING CHAIN HASH of token-prefix chunks: ``h_i = H(h_{i-1} ||
  tokens_i)`` identifies the entire prefix through block *i*, not just
  the block's own tokens, so a hash hit means the whole prefix matches
  (token equality is re-verified — a collision can never corrupt).
  Matching blocks are attached to the new slot's table by REFERENCE
  (refcount++), the prefill computes only the unshared tail, and a
  shared block is copied (:func:`copy_block`, copy-on-write) the moment
  a slot needs to write into it.

KV at position ``p`` depends only on tokens ``0..p`` (causal), so a
block whose prefix-chain matches holds bit-identical KV to what a fresh
prefill would compute — prefix reuse cannot change a single output
token, which is what lets the parity tests assert exact equality
against ``generate()``.

Physical block 0 is a TRASH block: gathers may read it (garbage in,
discarded out — free slots, tail padding) and masked writes are routed
to it, so real blocks only ever receive committed positions.

**A latent layer's row is one more leaf under the one rule.**  A
latent-attention layer (:class:`..models.transformer.LatentSpec`) caches a
single ``(1, T, row_at_rest)`` leaf, ``latent_kv``, and its validity
(:data:`..models.transformer.LATENT_LEAVES`); :func:`build_pools` folds it
like any other whole-sequence leaf, into ``(num_blocks, bs, row_at_rest)``.
ONE leaf, PADDED by the model to whole lane tiles (576 -> 640), not split:
left 576 wide, XLA rests the pool leaf block-index minor to save the 64
lanes a row-major tile would waste, and every program copies every whole
leaf into the computing layout and back (two copies of 478 MB a layer and
program, 7.7 s of a 20 s window: my chip trace, PR 30; the same fault PR 25
cured for per-head K and V); a 512 and a 64 leaf would be two DMAs a block
where the decode kernel's time is its DMA count, and the 64-wide one would
itself rest padded to 128 lanes or block-index minor.  The 64 zero columns
are what a row-major (8, 128)-tiled buffer holds anyway.  The leaf is of
the full kind: addressed by the slot's block table, shared through the
prefix index, copied on write, spilled and migrated by the ops below as
they stand.  :func:`decode_view` hands the layer the pool leaf itself and
the absorbed attention reads each live row once
(:func:`..ops.paged_decode_pallas.paged_latent_decode`).

**Two kinds of layer, two kinds of pool.**  A full-attention layer needs
every position of a sequence; a sliding-window layer only its last
``window``.  A model that mixes them (the decode model names a window
layer's cache leaves ``ring_*``, :data:`..models.transformer.RING_LEAVES`)
gets a pool family a kind: FULL leaves ``(num_blocks, bs, ...)`` addressed
by the slot's block table, as ever, and RING leaves ``(max_slots *
ring_blocks, bs, ...)`` addressed by the slot's ring table, logical block
``j`` at ring entry ``j % ring_blocks``: position ``p`` rests at ring
index ``p % (ring_blocks * bs)``, which is where the model's ring cache
expects it.  Where a program argument is one array for a one-kind model
(a block table, the scatter's block ids) it is a ``(full, ring)`` pair
for a two-kind one; a uniform model never sees a pair, and its programs
are what they were.  Each slot OWNS its ring (nothing to allocate, nothing
to share), so a model with window layers indexes no prefix: the blocks
behind an indexed boundary would have to keep every window layer's last
``window`` positions alive as well, and no user of this engine has asked
for it yet.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributed_deep_learning_tpu.models.transformer import (BLOCK_TABLE,
                                                              FULL_LEAVES,
                                                              LATENT_LEAVES,
                                                              RING_LEAVES,
                                                              init_cache)
from distributed_deep_learning_tpu.serve import quant
from distributed_deep_learning_tpu.serve.cache import (COUNTER_LEAVES,
                                                       _leaf_name)

#: physical id of the write-discard / read-garbage block (never allocated)
TRASH = 0


def is_counter(path) -> bool:
    return _leaf_name(path) in COUNTER_LEAVES


def is_ring(path) -> bool:
    """Whether the cache leaf at `path` is (part of) a window layer's
    ring: an int8 leaf's payload and scales lie one key below its name."""
    return any(getattr(k, "key", None) in RING_LEAVES for k in path)


def _of_kind(x, path):
    """`x` for the leaf at `path`: the member of a ``(full, ring)`` pair
    its kind picks, or `x` itself where one array serves every leaf."""
    return x[is_ring(path)] if isinstance(x, tuple) else x


def chain_hash(prev: bytes, tokens: Sequence[int]) -> bytes:
    """Rolling prefix hash: digest of the previous chain digest plus this
    block's token ids.  ``h_i`` therefore commits to the ENTIRE token
    prefix through block *i* — equal hashes (plus the token-equality
    re-check) mean equal prefixes, hence bit-equal KV."""
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.digest()


# --- device-side pool ops (pure functions of pytrees) ---------------------


def slot_template(lm, padded_len: int, token_dtype=jnp.int32,
                  kv_dtype: Optional[str] = None):
    """Shapes and dtypes of ONE slot's cache in the model's ``B=1``
    layout, KV payload leaves in their at-rest form — what
    :func:`gather_slot` hands back and what :func:`build_pools` folds
    into blocks.  ``eval_shape`` of a ``(1, padded_len)`` cache init: no
    forward, no arrays.

    ``kv_dtype`` picks the at-rest precision of the KV payload leaves:
    ``None`` keeps the model's own dtype, ``"bf16"`` halves it, and
    ``"int8"`` stores each KV leaf as a :class:`.quant.QuantTensor`
    (int8 payload ``(1, T, H, D)`` + f32 per-position-per-head scales
    ``(1, T, H, 1)``).  Bool validity and counters are exact regardless."""
    def at_rest():
        cache = init_cache(lm, 1, padded_len, token_dtype)
        if kv_dtype is None:
            return cache
        return quant.quantize_cache_span(cache, kv_dtype)

    return jax.eval_shape(at_rest)


def build_pools(like, num_blocks: int, block_size: int,
                ring_num_blocks: Optional[int] = None):
    """Zeroed block pools for slots shaped `like` (:func:`slot_template`);
    ring leaves, where the model has them, get `ring_num_blocks` blocks.

    Every sequence-axis leaf ``(1, T, *trailing)`` becomes a pool
    ``(num_blocks, block_size, prod(trailing))`` — K/V ``(nb, bs, H*D)``,
    int8 scales ``(nb, bs, H)``, ``cached_valid`` ``(nb, bs)`` — one rule,
    read off the leaf's own shape (why merged: the module docstring).
    Every pool op below indexes the two leading dims only, so a
    :class:`.quant.QuantTensor`'s payload and scales move coherently.
    Counter leaves shrink to a placeholder (positions are host-owned —
    the host scheduler must know every slot's position anyway, so the
    device copy would only mirror it; :func:`gather_slot` injects the
    host value instead)."""
    def alloc(path, leaf):
        if is_counter(path):
            return jnp.zeros((), leaf.dtype)          # unused placeholder
        if leaf.shape[1] % block_size:
            raise ValueError(f"padded_len {leaf.shape[1]} must be a "
                             f"multiple of block_size {block_size}")
        merged = (math.prod(leaf.shape[2:]),) if leaf.ndim > 2 else ()
        n = ring_num_blocks if is_ring(path) else num_blocks
        return jnp.zeros((n, block_size) + merged, leaf.dtype)

    return jax.tree_util.tree_map_with_path(alloc, like)


def merge_trailing(blocks):
    """``(N, block, *trailing)`` blocks, an array or a
    :class:`.quant.QuantTensor`, as a pool leaf rests them: the trailing
    dims merged into one (:func:`build_pools` has why)."""
    return jax.tree.map(lambda a: a.reshape(a.shape[:2] + (-1,)), blocks)


def gather_slot(pools, table, pos, like):
    """One slot's logical cache in the model's ``B=1`` layout.

    ``table`` is the slot's ``(blocks_per_slot,)`` physical block ids
    (with ring leaves, the pair of that and its ``(ring_blocks,)`` ring
    table) and ``pos`` its position counter — all traced, so one compiled
    program serves every slot, table and position.  `like` (the pools'
    :func:`slot_template`) gives each leaf its trailing dims back, on
    the gathered slot and never on the pool.  Trash entries gather
    garbage that the decode-path causal prefix mask (``kpos <= qpos``)
    keeps causally unreachable.

    It copies ``blocks_per_slot`` blocks of every leaf whatever the slot
    holds, so the one-token decode program does not use it for full-kind
    leaves (:func:`decode_view`).  Who still gathers: the programs with
    several queries a slot (chunk prefill, verify, the draft's), which run
    the model's multi-token cached forward over a slot-shaped cache; spill,
    which wants the slot's at-rest image; and the decode program for ring
    leaves, bounded by the window as they are."""
    def g(path, leaf, want):
        if is_counter(path):
            return jnp.asarray(pos, leaf.dtype)
        return leaf[_of_kind(table, path)].reshape((1, -1) + want.shape[2:])

    return jax.tree_util.tree_map_with_path(g, pools, like)


def _by_layer(tree, layer, other, *rest):
    """`tree` rebuilt: `layer` on each dict that holds one attention
    layer's cache leaves, `other` on every other innermost dict (the
    embedding's counter), each with the matching nodes of `rest`."""
    def walk(node, *more):
        if any(kind[0] in node
               for kind in (FULL_LEAVES, RING_LEAVES, LATENT_LEAVES)):
            return layer(node, *more)
        if any(isinstance(v, dict) for v in node.values()):
            return {k: walk(v, *(m[k] for m in more))
                    for k, v in node.items()}
        return other(node, *more)

    return walk(tree, *rest)


def decode_view(pools, table, pos, like, lift=lambda cache: cache):
    """One slot's cache for the ONE-TOKEN decode program.

    A full-kind layer (per-head K and V, or a latent row) gets its pool
    leaves THEMSELVES, as they rest, with the slot's block table beside
    them (``block_table``): the layer attends
    over the blocks that hold live positions in place
    (:mod:`..ops.paged_decode_pallas`) and nothing of ``blocks_per_slot x
    block`` is copied.  A ring layer's leaves are gathered as
    :func:`gather_slot` gathers them, and lifted by `lift` (the engine's
    dequantisation)."""
    full = table[0] if isinstance(table, tuple) else table

    def layer(node, want):
        if RING_LEAVES[0] in node:
            return lift(gather_slot(node, table, pos, want))
        return {**{k: jnp.asarray(pos, v.dtype) if k in COUNTER_LEAVES else v
                   for k, v in node.items()}, BLOCK_TABLE: full}

    return _by_layer(pools, layer,
                     lambda node, want: gather_slot(node, table, pos, want),
                     like)


def view_span(cache, pos):
    """What :func:`extract_span` gives for one position, of a cache that
    went into the model as a :func:`decode_view`: a full-kind layer's
    leaves came back as the token's own row."""
    def layer(node):
        if BLOCK_TABLE not in node:
            return extract_span(node, pos, 1)
        return {k: jnp.zeros((), jnp.int32) if k in COUNTER_LEAVES else v[0]
                for k, v in node.items() if k != BLOCK_TABLE}

    return _by_layer(cache, layer, lambda node: extract_span(node, pos, 1))


def attention_paths(like) -> dict:
    """How many attention layers of slots shaped `like` the one-token
    decode program serves in place through the block table (per-head K
    and V: ``block_table``; a latent row: ``latent``), and how many by
    gathering (:func:`decode_view`)."""
    paths = {"block_table": 0, "gather": 0, "latent": 0}

    def layer(node):
        paths["gather" if RING_LEAVES[0] in node else
              "latent" if LATENT_LEAVES[0] in node else "block_table"] += 1

    _by_layer(like, layer, lambda node: None)
    return paths


def latent_leaf(like):
    """The latent row leaf of slots shaped `like` (every latent layer's is
    alike), or None where no layer has one."""
    found = []
    _by_layer(like, lambda node: found.append(node.get(LATENT_LEAVES[0])),
              lambda node: None)
    return next((leaf for leaf in found if leaf is not None), None)


def extract_span(cache, pos, n: int):
    """Positions ``[pos, pos+n)`` of a model-layout cache — the freshly
    written KV a program hands to :func:`scatter_span`.  ``n`` is static
    (the program's chunk width); ``pos`` is traced.  A ring leaf holds
    position ``p`` at index ``p`` modulo its length."""
    def e(path, leaf):
        if is_counter(path):
            return jnp.zeros((), jnp.int32)            # placeholder
        if is_ring(path):
            return leaf[0][(pos + jnp.arange(n)) % leaf.shape[1]]
        return jax.lax.dynamic_slice_in_dim(leaf[0], pos, n, axis=0)

    return jax.tree_util.tree_map_with_path(e, cache)


def scatter_span(pools, kv, blocks, offsets):
    """Write per-position KV back into the pools.

    ``blocks``/``offsets`` have shape ``(..., n)`` matching the leading
    dims of the ``kv`` leaves, whose trailing dims (the model's) are
    merged into the pool's one (``blocks`` a ``(full, ring)`` pair where
    the pools hold both kinds; the offsets are the same for both);
    entries routed to :data:`TRASH` discard their write (pad tails,
    inactive slots).  The host guarantees no two
    REAL (block, offset) pairs collide in one call — only trash may be
    written more than once, and trash is never read as truth."""
    def s(path, pool, upd):
        if is_counter(path):
            return pool
        if jnp.issubdtype(pool.dtype, jnp.integer) and \
                jnp.issubdtype(upd.dtype, jnp.floating):
            raise TypeError(
                f"scatter_span: float {upd.dtype} span into an integer "
                f"{pool.dtype} pool — a bare astype would truncate "
                "without a scale; quantize the span first "
                "(serve.quant.quantize_cache_span)")
        to = _of_kind(blocks, path)
        upd = upd.astype(pool.dtype).reshape(to.shape + pool.shape[2:])
        return pool.at[to, offsets].set(upd)

    return jax.tree_util.tree_map_with_path(s, pools, kv)


def copy_block(pools, src, dst):
    """Physical block copy ``dst <- src`` — the copy half of
    copy-on-write.  ``src``/``dst`` are traced scalars: one compiled
    program covers every COW for the engine's lifetime.  They name blocks
    of the full kind; rings are never shared, so never copied."""
    def c(path, pool):
        if is_counter(path) or is_ring(path):
            return pool
        return jax.lax.dynamic_update_slice_in_dim(
            pool, jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=0),
            dst, axis=0)

    return jax.tree_util.tree_map_with_path(c, pools)


# --- host-side block manager ---------------------------------------------


@dataclasses.dataclass
class SharedPrefix:
    """Outcome of a prefix-index match for one prompt."""

    full_blocks: list       # physical ids of fully-matched blocks
    partial_block: Optional[int]   # physical id matched up to partial_len
    partial_len: int               # tokens matched inside partial_block
    chain: bytes                   # chain hash after the full blocks


@dataclasses.dataclass
class _IndexEntry:
    block: int
    tokens: tuple
    last_used: int
    parent: bytes       # the chain hash this entry extends


class PrefixIndex:
    """Chain-hash → block map with LRU bookkeeping.

    ``children`` maps a prefix chain hash to the hashes that extend it by
    one block — the partial-tail lookup (copy-on-write's entry point)
    walks it to find a cached block whose FIRST ``m`` tokens match the
    prompt's next tokens."""

    def __init__(self):
        self.entries: dict[bytes, _IndexEntry] = {}
        self.children: dict[bytes, list[bytes]] = {}
        self.by_block: dict[int, bytes] = {}
        self._clock = 0

    def __len__(self):
        return len(self.entries)

    def touch(self, h: bytes) -> None:
        self._clock += 1
        self.entries[h].last_used = self._clock

    def get(self, h: bytes):
        return self.entries.get(h)

    def add(self, parent: bytes, h: bytes, block: int,
            tokens: tuple) -> bool:
        """Register ``block`` as the completion of prefix ``parent`` with
        ``tokens``.  First registration wins (a concurrent slot that
        filled an identical block keeps its private copy)."""
        if h in self.entries or block in self.by_block:
            return False
        self._clock += 1
        self.entries[h] = _IndexEntry(block, tokens, self._clock, parent)
        self.children.setdefault(parent, []).append(h)
        self.by_block[block] = h
        return True

    def remove(self, h: bytes) -> int:
        e = self.entries.pop(h)
        del self.by_block[e.block]
        # by the entry's own parent, not by a walk over every parent's
        # list: evicting 1,200 of 20,000 indexed blocks for one admission
        # took the host 1.3 s (a stall in every window of the glm cell,
        # my chip runs, PR 30)
        sibs = self.children.get(e.parent)
        if sibs is not None and h in sibs:
            sibs.remove(h)
            if not sibs:
                del self.children[e.parent]
        self.children.pop(h, None)
        return e.block

    def lru(self):
        """Hashes in least-recently-used-first order."""
        return sorted(self.entries, key=lambda h: self.entries[h].last_used)


class BlockPoolExhausted(RuntimeError):
    """A single request needs more blocks than the pool will ever hold."""


class BlockManager:
    """Host truth for the paged pool: free list, refcounts, tables, index.

    Pure Python — no JAX.  The engine asks it three questions (can this
    request be admitted?  which physical blocks back slot *s*?  is this
    block writable, or must it be COW-copied first?) and tells it two
    facts (these positions are now committed; this slot retired)."""

    def __init__(self, num_blocks: int, block_size: int, max_slots: int,
                 blocks_per_slot: int, ring_blocks: Optional[int] = None):
        """`ring_blocks`: the model has window layers and each slot owns
        a ring of that many blocks of the ring pools (module docstring);
        such a manager indexes no prefix."""
        if num_blocks < blocks_per_slot:
            raise ValueError(
                f"num_blocks {num_blocks} cannot hold even one slot "
                f"({blocks_per_slot} blocks)")
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.blocks_per_slot = int(blocks_per_slot)
        # physical ids 1..num_blocks; 0 is TRASH
        self.free: list[int] = list(range(num_blocks, 0, -1))
        self.refs = np.zeros(num_blocks + 1, np.int32)
        self.tables = np.full((max_slots, blocks_per_slot), TRASH, np.int32)
        self.ring_blocks = ring_blocks
        if ring_blocks is not None:
            # slot s owns ring-pool blocks 1 + s * ring_blocks ..., for good
            self.ring_tables = 1 + np.arange(
                max_slots * ring_blocks, dtype=np.int32).reshape(
                    max_slots, ring_blocks)
        self._logical: dict[int, int] = {}     # slot -> blocks reserved
        self.index = PrefixIndex()
        self._reserve: dict[int, int] = {}     # slot -> COW reserve block
        # slot -> (blocks hashed so far, chain hash after them)
        self._chain: dict[int, tuple[int, bytes]] = {}
        self.copies = 0
        self.evictions = 0
        #: what `register_committed` has done so far: tokens it read out
        #: of the streams it was given, and blocks it added to the index
        #: (evicted since or not).  tokens_read / (indexed x block_size)
        #: is 1.0 where nothing is shared and each block is read once
        self.tokens_read = 0
        self.indexed = 0
        self.peak_in_use = 0
        # optional observability hook: ``on_event(kind, **fields)`` fires
        # on evictions and COW detaches (the engine wires it to the
        # tracer/flight recorder; None costs nothing)
        self.on_event = None

    # --- accounting -------------------------------------------------------
    @property
    def in_use(self) -> int:
        return self.num_blocks - len(self.free)

    def blocks_by_kind(self) -> dict:
        """Blocks holding live slots' positions, a count a kind: the
        table's for full layers; for window layers what of each slot's
        reservation its ring holds, and under ``window_released`` what it
        does not: the blocks one shape for every layer would also keep."""
        out = {"full": self.in_use}
        if self.ring_blocks is not None:
            out["window"] = sum(min(n, self.ring_blocks)
                                for n in self._logical.values())
            out["window_released"] = sum(max(0, n - self.ring_blocks)
                                         for n in self._logical.values())
        return out

    def device_tables(self, slot: Optional[int] = None):
        """What a program takes as its block table: every slot's (or
        `slot`'s) table, paired with the ring table where there is one."""
        pick = (lambda t: t) if slot is None else (lambda t: t[slot])
        if self.ring_blocks is None:
            return pick(self.tables)
        return pick(self.tables), pick(self.ring_tables)

    def ring_targets(self, slot: int, positions, live):
        """The ring blocks that `positions` of `slot` are written to
        (TRASH where not `live`), or None for a one-kind model; the
        offsets are the full kind's."""
        if self.ring_blocks is None:
            return None
        entry = (np.asarray(positions) // self.block_size) % self.ring_blocks
        return np.where(live, self.ring_tables[slot][entry],
                        TRASH).astype(np.int32)

    def _evictable(self) -> int:
        return int(sum(1 for h, e in self.index.entries.items()
                       if self.refs[e.block] == 1))

    def _alloc(self) -> int:
        b = self.free.pop()
        self.refs[b] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return b

    def _deref(self, b: int) -> None:
        if b == TRASH:
            return
        self.refs[b] -= 1
        if self.refs[b] < 0:
            raise AssertionError(f"block {b} refcount underflow")
        if self.refs[b] == 0:
            self.free.append(b)

    def evict(self, need: int) -> int:
        """Drop LRU index-only blocks until ``need`` are free (or no more
        are evictable).  Returns how many blocks were freed."""
        freed = 0
        for h in self.index.lru():
            if len(self.free) >= need:
                break
            b = self.index.entries[h].block
            if self.refs[b] != 1:       # some slot still references it
                continue
            self.index.remove(h)
            self._deref(b)
            self.evictions += 1
            freed += 1
        if freed and self.on_event is not None:
            self.on_event("evict", freed=freed, need=need)
        return freed

    def flush_index(self) -> int:
        """Drop EVERY prefix-index entry and the reference each holds.

        Blocks still owned by live slots stay alive (the slots' own
        refs remain); blocks the index alone retained return to the
        free list.  Hot weight swap calls this: indexed KV was computed
        under the OLD weights, so matching it as a prefix under the new
        weights would silently mix generations."""
        dropped = 0
        for h in list(self.index.entries):
            b = self.index.remove(h)
            self._deref(b)
            dropped += 1
        if dropped and self.on_event is not None:
            self.on_event("index_flush", dropped=dropped)
        return dropped

    # --- prefix matching --------------------------------------------------
    def match_prefix(self, prompt: np.ndarray) -> SharedPrefix:
        """Longest reusable prefix of ``prompt`` present in the index:
        a chain of fully-matched blocks plus at most one partially-
        matched tail block.  Capped at ``len(prompt) - 1`` — the final
        prompt token is always recomputed, because sampling the first
        output token needs its hidden state, which no KV cache stores."""
        if self.ring_blocks is not None:     # nothing is ever indexed
            return SharedPrefix([], None, 0, b"")
        bs = self.block_size
        toks = np.asarray(prompt)
        L = len(toks)
        h = b""
        full: list[int] = []
        i = 0
        while (i + 1) * bs <= L - 1:    # cap: never cover the last token
            blk = tuple(int(t) for t in toks[i * bs:(i + 1) * bs])
            h2 = chain_hash(h, blk)
            e = self.index.get(h2)
            if e is None or e.tokens != blk:
                break
            full.append(e.block)
            self.index.touch(h2)
            h = h2
            i += 1
        partial, m = None, 0
        rest = toks[i * bs:]
        cap = L - 1 - i * bs            # last token stays uncached
        if cap > 0:
            best = 0
            for ch in self.index.children.get(h, []):
                e = self.index.entries[ch]
                ct = np.asarray(e.tokens)
                n = int(min(len(ct), len(rest), cap))
                eq = ct[:n] == rest[:n]
                k = int(eq.argmin()) if not eq.all() else n
                if k > best:
                    best, partial = k, e.block
                    self.index.touch(ch)
            m = best
            if m == 0:
                partial = None
        sp = SharedPrefix(full, partial, m, h)
        return sp

    def shared_len(self, sp: SharedPrefix) -> int:
        return len(sp.full_blocks) * self.block_size + sp.partial_len

    # --- admission / release ----------------------------------------------
    def owned_needed(self, sp: SharedPrefix, total_len: int) -> int:
        """Fresh blocks a request needs: capacity for its whole stream
        minus the fully-shared blocks (a partially-shared block cancels
        against its COW reserve — referenced now, copied at first
        write)."""
        logical = -(-total_len // self.block_size)   # ceil
        logical = min(logical, self.blocks_per_slot)
        need = logical - len(sp.full_blocks)
        if need < 0:
            raise AssertionError("shared prefix longer than the request")
        return need

    def can_admit(self, sp: SharedPrefix, total_len: int) -> bool:
        need = self.owned_needed(sp, total_len)
        if need > self.num_blocks:
            raise BlockPoolExhausted(
                f"request needs {need} blocks; the pool holds "
                f"{self.num_blocks}")
        return len(self.free) + self._evictable() >= need

    def admit(self, slot: int, sp: SharedPrefix, total_len: int) -> int:
        """Build slot ``slot``'s block table: shared blocks by reference,
        fresh blocks for the rest, one fresh block held aside as the COW
        reserve when a partial block is referenced.  Returns the shared
        prefix length in tokens."""
        need = self.owned_needed(sp, total_len)
        if len(self.free) < need:
            self.evict(need)
        if len(self.free) < need:
            raise AssertionError("admit() called without can_admit()")
        row = self.tables[slot]
        row[:] = TRASH
        for j, b in enumerate(sp.full_blocks):
            row[j] = b
            self.refs[b] += 1
        logical = min(-(-total_len // self.block_size),
                      self.blocks_per_slot)
        j = len(sp.full_blocks)
        if sp.partial_block is not None:
            row[j] = sp.partial_block
            self.refs[sp.partial_block] += 1
            self._reserve[slot] = self._alloc()
            j += 1
            need -= 1
        while j < logical:
            row[j] = self._alloc()
            j += 1
        self._chain[slot] = (len(sp.full_blocks), sp.chain)
        self._logical[slot] = logical
        return self.shared_len(sp)

    def release(self, slot: int) -> None:
        row = self.tables[slot]
        for b in row:
            self._deref(int(b))
        row[:] = TRASH
        r = self._reserve.pop(slot, None)
        if r is not None:
            self._deref(r)
        self._chain.pop(slot, None)
        self._logical.pop(slot, None)

    # --- copy-on-write ----------------------------------------------------
    def writable(self, slot: int, logical: int) -> Optional[tuple[int, int]]:
        """Make logical block ``logical`` of ``slot`` safe to write.

        Exclusive blocks pass through (None).  A shared block (refcount
        > 1 — other slots and/or the prefix index still read it) is
        detached: a fresh physical block takes its table entry and the
        caller must device-copy ``src -> dst`` before writing.  This is
        the write fault of classic copy-on-write, reached whenever a
        prompt's shared prefix ends mid-block."""
        b = int(self.tables[slot, logical])
        if b == TRASH:
            raise AssertionError(
                f"slot {slot} writing unallocated logical block {logical}")
        if self.refs[b] == 1:
            # the slot's own reference is the only one: exclusive, and
            # (since the index always holds a reference to indexed
            # blocks) guaranteed unindexed
            return None
        dst = self._reserve.pop(slot, None)
        if dst is None:
            if not self.free:
                self.evict(1)
            dst = self._alloc()
        self.tables[slot, logical] = dst
        self._deref(b)
        self.copies += 1
        if self.on_event is not None:
            self.on_event("cow", slot=slot, logical=logical,
                          src=b, dst=dst)
        return b, dst

    # --- registration -----------------------------------------------------
    def register_committed(self, slot: int, tokens, committed: int) -> int:
        """Index every full block of ``slot`` whose tokens are final
        (all positions < ``committed``; committed positions are never
        rewritten, so the block's content is frozen).  ``tokens`` is the
        slot's whole stream (prompt + generated) as known to the host, a
        list or an array: only the blocks that have just filled are read
        out of it (`tokens_read`), so a commit that completes no block
        costs nothing that grows with the stream (converted whole, once a
        decoding slot a token, it was 5.5-6.9 ms of the glm cell's tick:
        ledger, PR 37).
        The chain hash is a pure function of the token stream, so a
        COW-copied private block registers under its true prefix hash
        like any other.  Returns how many new blocks were indexed (none,
        ever, for a model with window layers)."""
        if self.ring_blocks is not None:
            return 0
        bs = self.block_size
        done, h = self._chain[slot]
        added = 0
        while (done + 1) * bs <= committed:
            blk = tuple(int(t) for t in tokens[done * bs:(done + 1) * bs])
            self.tokens_read += len(blk)
            parent = h
            h = chain_hash(h, blk)
            b = int(self.tables[slot, done])
            if b != TRASH and self.index.add(parent, h, b, blk):
                self.refs[b] += 1          # the index holds a reference
                added += 1
            done += 1
        self._chain[slot] = (done, h)
        self.indexed += added
        return added

    def adopt_prefix(self, tokens, n_blocks: int):
        """Register the first ``n_blocks`` full blocks of ``tokens`` as
        if a local slot had prefilled them, allocating fresh physical
        blocks for the chain links not already indexed — the
        destination half of cross-engine prefix cloning
        (:func:`..serve.migrate.clone_prefix`).

        Returns ``(start, new_block_ids)``: ``start`` chain links were
        already indexed here (nothing to copy), and ``new_block_ids``
        are freshly-allocated blocks for links ``start..`` — held ONLY
        by the index (refcount 1), so they age out under LRU eviction
        like any locally-prefilled prefix.  The caller MUST fill every
        returned block with the exact at-rest KV for its positions
        before anything admits against the chain.  Returns None when
        the pool cannot free enough blocks (sharing is best-effort and
        never steals from live slots), or the model has window layers."""
        if self.ring_blocks is not None:
            return None
        bs = self.block_size
        toks = np.asarray(tokens)
        chain = []
        h = b""
        for i in range(int(n_blocks)):
            blk = tuple(int(t) for t in toks[i * bs:(i + 1) * bs])
            if len(blk) < bs:
                break
            parent = h
            h = chain_hash(h, blk)
            chain.append((parent, h, blk))
        start = 0
        for parent, h2, blk in chain:
            e = self.index.get(h2)
            if e is None:
                break
            if e.tokens != blk:     # hash collision: never adopt over it
                return None
            self.index.touch(h2)    # protect the stem from our own evict
            start += 1
        todo = chain[start:]
        if not todo:
            return start, []
        if len(self.free) < len(todo):
            self.evict(len(todo))
        if len(self.free) < len(todo):
            return None
        ids = []
        for parent, h2, blk in todo:
            b = self._alloc()       # refcount 1: the index's reference
            if not self.index.add(parent, h2, b, blk):
                self._deref(b)
                return None
            ids.append(b)
        if self.on_event is not None:
            self.on_event("adopt", blocks=len(ids))
        return start, ids

    def unadopt(self, block_ids) -> int:
        """Roll back a failed adoption: drop the index entries holding
        the given freshly-adopted blocks and release the blocks back to
        the free list.  The inverse of :meth:`adopt_prefix` for blocks
        whose payload never arrived (a migration that tripped its
        digest) — adopted blocks are held ONLY by the index (refcount
        1), so removing the entry frees them and nothing downstream can
        ever admit against the half-filled chain.  Returns how many
        blocks were released."""
        dropped = 0
        for b in block_ids:
            h = self.index.by_block.get(int(b))
            if h is None:
                continue
            self.index.remove(h)
            self._deref(int(b))
            dropped += 1
        if dropped and self.on_event is not None:
            self.on_event("unadopt", blocks=dropped)
        return dropped

    def prefix_summary(self) -> frozenset:
        """Cheap export of this manager's prefix-index coverage: the set
        of chain hashes currently indexed.  Each hash commits to an
        entire token prefix (see :func:`chain_hash`), so a router can
        predict how many prompt tokens would hit this replica's cache
        without seeing any cached tokens — hand the summary to
        :func:`predict_shared_len`."""
        return frozenset(self.index.entries)

    def stats(self) -> dict:
        return {
            **({} if self.ring_blocks is None else
               {"ring_blocks_per_slot": self.ring_blocks,
                "blocks_by_kind": self.blocks_by_kind()}),
            "blocks_total": self.num_blocks,
            "blocks_in_use": self.in_use,
            "blocks_peak_in_use": self.peak_in_use,
            "indexed_blocks": len(self.index),
            "indexed_total": self.indexed,
            "tokens_read": self.tokens_read,
            "cow_copies": self.copies,
            "evictions": self.evictions,
        }


def paged_max_len(model_max_len: int, kv_block_size: int,
                  draft: bool, spec_k: int) -> int:
    """Largest engine ``max_len`` a model geometry supports: the paged
    cache rounds capacity up to whole blocks and, with speculation on,
    needs ``spec_k + 1`` positions of verify headroom — all of which
    must still fit the model's learned position range."""
    head = (spec_k + 1) if draft else 0
    cap = (model_max_len // kv_block_size) * kv_block_size - head
    if cap < kv_block_size:
        raise ValueError(
            f"model max_len {model_max_len} too small for block size "
            f"{kv_block_size} (+{head} speculative headroom)")
    return cap


def predict_shared_len(summary, prompt, block_size: int) -> int:
    """Predicted prefix-cache hit for ``prompt`` against a replica's
    :meth:`BlockManager.prefix_summary`: tokens covered by the longest
    chain of fully-matched blocks.  Mirrors the full-block walk of
    :meth:`BlockManager.match_prefix` but skips the token-equality
    re-check and the partial-tail search — the summary carries hashes
    only, so this is a *prediction* (collision-safe in practice: the
    chain digest commits to the whole prefix).  Partial-block hits are
    deliberately ignored; they are at most ``block_size - 1`` tokens."""
    bs = block_size
    toks = np.asarray(prompt)
    L = len(toks)
    h = b""
    i = 0
    while (i + 1) * bs <= L - 1:    # same cap as match_prefix
        h2 = chain_hash(h, tuple(int(t) for t in toks[i * bs:(i + 1) * bs]))
        if h2 not in summary:
            break
        h = h2
        i += 1
    return i * bs
