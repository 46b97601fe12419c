"""Device-to-device migration of committed paged-KV blocks.

The serve tier's slowest data paths — preemption spill/resume,
prefill→decode handoff, cross-replica prefix sharing — all reduce to
the same primitive: move N committed pool blocks from one device's
paged-KV pools into another's, bit-exactly, without a host round trip.
:class:`BlockMigrator` is that primitive, built from the two schedule
ideas this repo already carries:

* **Per-shard placement** (arxiv 2112.01075, via
  :func:`..reshard.redistribute.chunked_device_put`): the hop is a
  bounded-size chunked ``device_put`` schedule, never a monolithic
  transfer, so a migration can overlap the next prefill chunk instead
  of parking the pipeline behind one giant copy.
* **Quantized wire formats** (EQuARX, arxiv 2506.17615, via
  :mod:`..parallel.collectives`): the optional ``wire="int8"`` mode
  carries bf16 KV as int8 + per-block-row f32 scales — the exact
  ``quantize``/``dequantize`` pair the gradient collectives use —
  halving (or better) the bytes on the fabric.  ``wire="at_rest"``
  (default) moves the pools' own representation verbatim, so bf16 AND
  int8+scales (:class:`..serve.quant.QuantTensor`) pools round-trip
  **bit-exactly** — the property preemption and failover replay gate
  on.

Two compiled programs, compile-once per (pool geometry, device):

* **gather** — ``leaf[ids]`` every non-counter pool leaf for a fixed
  ``width`` of block ids (short moves pad with :data:`~.paged.TRASH`:
  reading the trash block is harmless, writing to it is discarded — the
  same garbage-routing trick chunked prefill uses), then PACK the
  blocks into one flat buffer per wire dtype.  Packing matters: a pool
  tree is ~20 leaves, and per-leaf transfers pay per-transfer dispatch
  ~20×; the packed payload is 2-3 arrays however deep the model is.
* **scatter** — slice each leaf's span back out of the flat buffers
  (all offsets static, derived from the pool treedef) and
  ``leaf.at[ids].set(...)`` into the destination pools.

Integrity is end-to-end, not per-hop: ``verify=True`` takes a blake2b
digest of the payload before the hop and re-checks it after; a mismatch
(lost or corrupted transfer — the ``migrate_drop`` chaos kind) raises
:class:`MigrationError` BEFORE anything is scattered, so the
destination pools are never poisoned and the supervisor's ledger replay
recovers bit-identically.

Accounting lands in the shared observability surfaces: wire bytes in
``comm_bytes{op="kv_migrate"}`` (beside the gradient collectives) and
``serve_migration_bytes``, wall time in the ``serve_migration_s``
histogram, and a ``kv_migrate`` tracer span per move.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Optional

import numpy as np

from distributed_deep_learning_tpu.parallel import collectives
from distributed_deep_learning_tpu.reshard.redistribute import (
    CHUNK_THRESHOLD_BYTES, chunked_device_put)
from distributed_deep_learning_tpu.serve import paged

#: wire formats: ``at_rest`` moves the pools' own representation
#: (bit-exact round trips), ``int8`` re-quantizes floating KV payload
#: with the collectives' int8+scales format (lossy like any quantized
#: collective; ~2x fewer bytes over bf16 pools).
WIRES = ("at_rest", "int8")


class MigrationError(RuntimeError):
    """A KV block transfer failed its end-to-end integrity check — the
    payload was lost or corrupted in flight.  Nothing was scattered;
    the caller replays the affected requests from its ledger (the
    supervisor contains this exactly like a KV-corruption fault)."""


@dataclasses.dataclass
class MigrationStats:
    """Cumulative accounting for one :class:`BlockMigrator`."""

    moves: int = 0
    blocks: int = 0
    wire_bytes: int = 0       # bytes actually carried (padded payload)
    seconds: float = 0.0      # wall time inside migrate() calls
    hops: int = 0             # moves that crossed a device boundary
    verified: int = 0
    failed: int = 0

    def gb_per_s(self) -> float:
        return self.wire_bytes / max(self.seconds, 1e-9) / (1 << 30)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["gb_per_s"] = round(self.gb_per_s(), 4)
        return d


def _is_quant_scale(path) -> bool:
    """True for a :class:`..serve.quant.QuantTensor` ``s`` leaf — the
    f32 scales must always travel raw (re-quantizing scales would
    corrupt every value they calibrate)."""
    import jax

    return bool(path) and isinstance(path[-1], jax.tree_util.GetAttrKey) \
        and path[-1].name == "s"


def tree_digest(tree) -> bytes:
    """Host blake2b-128 over every leaf's bytes, in tree order — the
    end-to-end integrity check for a migration payload (and the audit
    digest device-path spill records beside the npz fallback)."""
    import jax

    h = hashlib.blake2b(digest_size=16)
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.digest()


def offload(tree, device, chunk_bytes: int = CHUNK_THRESHOLD_BYTES):
    """Move every leaf of a pytree onto ``device`` with the chunked
    per-shard schedule.  Used for migration payload hops and for the
    engine's device-path preemption spill (KV parked on a spill device
    instead of host npz)."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: chunked_device_put(x, device, chunk_bytes), tree)


def tree_bytes(tree) -> int:
    return sum(int(getattr(leaf, "nbytes", 0) or 0)
               for leaf in __import__("jax").tree_util.tree_leaves(tree))


class BlockMigrator:
    """Compile-once mover of paged-KV blocks between pool trees.

    ``width`` fixes the gather/scatter program shape (one program per
    pool geometry per device — moves shorter than ``width`` pad with
    TRASH ids).  Use the source engine's ``blocks_per_slot``: one
    slot's worth of blocks is the natural migration unit.

    The migrator is stateless w.r.t. the pools — ``migrate`` is
    functional (returns the new destination pools), same discipline as
    every compiled pool op in :mod:`.paged`.
    """

    def __init__(self, width: int, *, wire: str = "at_rest",
                 registry=None, tracer=None,
                 chunk_bytes: int = CHUNK_THRESHOLD_BYTES):
        from distributed_deep_learning_tpu.serve.engine import CountingJit

        if width < 1:
            raise ValueError(f"migrator width must be >= 1, got {width}")
        if wire not in WIRES:
            raise ValueError(f"wire must be one of {WIRES}, got {wire!r}")
        self.width = int(width)
        self.wire = wire
        self.chunk_bytes = int(chunk_bytes)
        self.stats = MigrationStats()
        self.tracer = tracer
        self._gather = CountingJit(self._gather_impl, "migrate_gather")
        self._scatter = CountingJit(self._scatter_impl, "migrate_scatter")
        if registry is not None:
            self._c_bytes = registry.counter("serve_migration_bytes",
                                             wire=wire)
            self._c_comm = registry.counter(
                "comm_bytes", op="kv_migrate",
                method="int8" if wire == "int8" else "none")
            self._h_s = registry.histogram("serve_migration_s")
        else:
            self._c_bytes = self._c_comm = self._h_s = None

    # --- wire predicates (host-side, on static leaf metadata) ----------
    def _quantizes(self, path, leaf) -> bool:
        import jax.numpy as jnp

        return (self.wire == "int8"
                and jnp.issubdtype(leaf.dtype, jnp.floating)
                and not _is_quant_scale(path))

    # --- compiled programs ---------------------------------------------
    def _gather_impl(self, pools, ids):
        """(pools, int32[width]) -> packed payload dict: one flat buffer
        per wire dtype (keys static from the pool treedef) plus the
        per-block-row f32 scales when the wire quantizes."""
        import jax
        import jax.numpy as jnp

        bufs: dict = {}
        scales: list = []

        def take(path, leaf):
            if paged.is_counter(path):
                return
            x = leaf[ids]                          # (width, bs, ...)
            if self._quantizes(path, leaf):
                q, s = jax.vmap(
                    lambda row: collectives.quantize(row, "int8"))(
                        x.reshape((x.shape[0], -1)))
                bufs.setdefault("int8", []).append(q.reshape(-1))
                scales.append(s.astype(jnp.float32).reshape(-1))
            else:
                bufs.setdefault(jnp.dtype(x.dtype).name,
                                []).append(x.reshape(-1))

        jax.tree_util.tree_map_with_path(take, pools)
        payload = {f"b_{k}": (v[0] if len(v) == 1 else jnp.concatenate(v))
                   for k, v in bufs.items()}
        if scales:
            payload["scales"] = jnp.concatenate(scales)
        return payload

    def _scatter_impl(self, pools, payload, ids):
        """Unpack the payload (static offsets, same walk as gather) and
        write each leaf's blocks at ``ids``; rows aimed at TRASH are
        writes to the trash block — discarded by contract."""
        import jax
        import jax.numpy as jnp

        offs = {k: 0 for k in payload}
        srow = {"i": 0}

        def put(path, leaf):
            if paged.is_counter(path):
                return leaf
            shape = (int(ids.shape[0]),) + tuple(leaf.shape[1:])
            n = int(np.prod(shape))
            if self._quantizes(path, leaf):
                flat = payload["b_int8"][offs["b_int8"]:
                                         offs["b_int8"] + n]
                offs["b_int8"] += n
                s = payload["scales"][srow["i"]:srow["i"] + shape[0]]
                srow["i"] += shape[0]
                x = jax.vmap(
                    lambda qr, sr: collectives.dequantize(
                        qr, sr, "int8", leaf.dtype))(
                            flat.reshape((shape[0], -1)), s)
            else:
                key = f"b_{jnp.dtype(leaf.dtype).name}"
                flat = payload[key][offs[key]:offs[key] + n]
                offs[key] += n
                x = flat
            x = x.reshape(shape).astype(leaf.dtype)
            # width-unrolled row updates: each lowers to a memcpy-like
            # dynamic-update-slice (XLA scatter is element-wise on CPU
            # and ~50x slower for block-sized rows); duplicate TRASH
            # rows just overwrite the trash block
            out = leaf
            for i in range(shape[0]):
                out = jax.lax.dynamic_update_index_in_dim(
                    out, x[i], ids[i], axis=0)
            return out

        return jax.tree_util.tree_map_with_path(put, pools)

    # --- host API -------------------------------------------------------
    def _pad(self, ids) -> np.ndarray:
        out = np.full(self.width, paged.TRASH, np.int32)
        out[:len(ids)] = np.asarray(ids, np.int32)
        return out

    def migrate(self, src_pools, dst_pools, src_ids, dst_ids, *,
                device=None, verify: bool = False, chaos=None,
                sync: bool = False, trace_id: str = "kv"):
        """Move ``src_pools``' blocks ``src_ids`` into ``dst_pools`` at
        ``dst_ids``; returns the NEW destination pools.

        ``device`` — hop the packed payload there first (the
        destination pools' device); ``None`` scatters in place (same
        device — prefix sharing between co-located replicas).
        ``verify`` — digest the payload before and after the hop and
        raise :class:`MigrationError` on mismatch, scattering nothing.
        ``chaos`` — fault-injection seam: a callable payload→payload
        applied between digest and hop (the ``migrate_drop`` drill).
        ``sync`` — block until the scatter lands (benchmarks); the
        engine leaves this False so migration overlaps the next prefill
        chunk.
        """
        import jax

        src_ids = [int(b) for b in src_ids]
        dst_ids = [int(b) for b in dst_ids]
        if len(src_ids) != len(dst_ids):
            raise ValueError(f"src/dst id count mismatch: "
                             f"{len(src_ids)} vs {len(dst_ids)}")
        if len(src_ids) > self.width:
            raise ValueError(f"move of {len(src_ids)} blocks exceeds "
                             f"migrator width {self.width}")
        if not src_ids:
            return dst_pools
        t0 = time.perf_counter()
        payload = self._gather(src_pools, self._pad(src_ids))
        digest = tree_digest(payload) if verify else None
        if chaos is not None:
            payload = chaos(payload)
        hop = device is not None
        if hop:
            payload = offload(payload, device, self.chunk_bytes)
        if digest is not None:
            self.stats.verified += 1
            if tree_digest(payload) != digest:
                self.stats.failed += 1
                raise MigrationError(
                    f"kv migrate: payload digest mismatch after "
                    f"{'device hop' if hop else 'copy'} of "
                    f"{len(src_ids)} block(s) — transfer lost or "
                    f"corrupted; nothing scattered, replay from ledger")
        out = self._scatter(dst_pools, payload, self._pad(dst_ids))
        if sync:
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        wire_b = tree_bytes(payload)
        self.stats.moves += 1
        self.stats.blocks += len(src_ids)
        self.stats.wire_bytes += wire_b
        self.stats.seconds += dt
        self.stats.hops += int(hop)
        if self._c_bytes is not None:
            self._c_bytes.inc(wire_b)
            self._c_comm.inc(wire_b)
            self._h_s.observe(dt)
        if self.tracer is not None:
            self.tracer.add("kv_migrate", t0, t0 + dt, trace_id,
                            track="migrate", blocks=len(src_ids),
                            bytes=wire_b, hop=hop, wire=self.wire)
        return out

    @property
    def compiles(self) -> int:
        """Total migrate program traces (gather + scatter).  One each
        per (pool geometry, device) — the compile-once guard."""
        return self._gather.traces + self._scatter.traces


def clone_prefix(src_engine, dst_engine, prompt, migrator: BlockMigrator,
                 *, device=None, sync: bool = False) -> int:
    """Copy the longest committed full-block prefix of ``prompt`` from
    one engine's pools into another's — prefix blocks prefilled once
    serve the fleet.

    Matches on the source's real index (``match_prefix``), registers
    the chain on the destination (``BlockManager.adopt_prefix``), and
    migrates only the blocks the destination doesn't already hold.
    Returns the number of prompt tokens made shareable (0 when the
    source has nothing, the destination already has it all, or the
    destination can't free enough blocks — sharing is best-effort and
    never required for correctness)."""
    prompt = np.asarray(prompt)
    sp = src_engine.manager.match_prefix(prompt)
    if not sp.full_blocks:
        return 0
    adopted = dst_engine.manager.adopt_prefix(prompt, len(sp.full_blocks))
    if adopted is None:
        return 0
    start, dst_ids = adopted
    if not dst_ids:
        return 0
    src_ids = list(sp.full_blocks[start:start + len(dst_ids)])
    moved = 0
    for i in range(0, len(dst_ids), migrator.width):
        dst_engine.pools = migrator.migrate(
            src_engine.pools, dst_engine.pools,
            src_ids[i:i + migrator.width],
            dst_ids[i:i + migrator.width],
            device=device, sync=sync)
        moved += len(dst_ids[i:i + migrator.width])
    return moved * dst_engine.block_size
