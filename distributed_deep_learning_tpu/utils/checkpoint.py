"""Checkpoint / resume on orbax, sharding-aware.

The reference has NO checkpointing — no ``torch.save`` anywhere; every run
is train-from-scratch (SURVEY.md §5).  A TPU framework can't ship without
it: pod jobs get preempted, and elastic resume is the failure-recovery
mechanism.  Because :class:`~..train.state.TrainState` is one pytree, a
checkpoint is one atomic orbax save; restore takes an *abstract* target
built from the live state, so arrays come back with the same shardings
they were saved under (each host restores only its addressable shards —
multi-host safe by construction).

Only pytree leaves (step/params/model_state/opt_state) are persisted;
``apply_fn``/``tx`` are code, re-supplied by the target state at restore.

**Integrity** (ISSUE 3): every save writes a ``manifest-<step>.json``
sidecar — per-leaf CRC32 checksums plus a finiteness summary, computed
from the in-memory state and written atomically.  Restores verify the
restored leaves against the manifest; :meth:`Checkpointer.restore_verified`
additionally falls back to the newest *verified-good* checkpoint when the
latest is torn, bit-flipped or non-finite, QUARANTINING (renaming, never
deleting) the bad step so recovery proceeds and the evidence survives for
forensics.  Pre-manifest checkpoints restore unverified (logged), keeping
old run directories resumable.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import zlib

import jax
import numpy as np
import orbax.checkpoint as ocp

from distributed_deep_learning_tpu.train.state import TrainState

# works for TrainState AND any state holder exposing these fields (e.g. the
# staged trainer's StagedState)
_FIELDS = ("step", "params", "model_state", "opt_state")

# Format 2 adds the topology block (mesh shape + per-leaf PartitionSpec,
# see reshard/manifest.py).  Readers treat a missing block — format 1 or
# any pre-integrity checkpoint — as legacy-same-topology: warn, restore,
# never quarantine, so every pre-reshard run directory stays resumable.
MANIFEST_FORMAT = 2


class CheckpointCorruption(RuntimeError):
    """A restored checkpoint failed manifest verification."""

    def __init__(self, step: int, detail: str):
        self.step = step
        super().__init__(f"checkpoint step {step} failed integrity "
                         f"verification: {detail}")


def _leaf_records(tree) -> dict:
    """Per-leaf integrity records keyed by pytree path.

    CRC32 over the raw bytes plus shape/dtype and (for float leaves) an
    all-finite flag.  Leaves that are not fully addressable on this host
    (multi-host shards) record ``crc32: None`` — shard-local checksums
    would differ per host, so those leaves are exempt from verification."""
    records = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            records[key] = {"crc32": None}
            continue
        arr = np.asarray(jax.device_get(leaf))
        rec = {"crc32": zlib.crc32(arr.tobytes()),
               "shape": list(arr.shape), "dtype": str(arr.dtype)}
        try:
            finite = bool(np.isfinite(arr.astype(np.float32)).all()) \
                if arr.dtype.kind == "f" or arr.dtype.name == "bfloat16" \
                else True
        except (TypeError, ValueError):  # exotic dtype: skip the check
            finite = True
        rec["finite"] = finite
        records[key] = rec
    return records


def _as_pytree(state) -> dict:
    return {f: getattr(state, f) for f in _FIELDS}


def _with_fields(state, fields: dict):
    if hasattr(state, "replace"):  # flax.struct dataclass
        return state.replace(**fields)
    return dataclasses.replace(state, **fields)


class Checkpointer:
    """Thin orbax CheckpointManager wrapper bound to one run directory."""

    def __init__(self, directory: str | os.PathLike, *, keep: int = 3):
        self._dir = os.path.abspath(os.fspath(directory))
        os.makedirs(self._dir, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(max_to_keep=keep,
                                                 create=True),
        )

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state: TrainState, *, force: bool = False,
             wait: bool = False, extra: dict | None = None,
             manifest: bool = True,
             publish_dir: str | None = None) -> bool:
        """Persist `state` under `step`.  Async by default (the save runs
        while training continues); `wait` blocks until durable.

        ``extra`` is an optional small JSON-serialisable dict saved as a
        sidecar next to the orbax step (loader position, partial-phase
        totals — the mid-epoch resume metadata).  Only the coordinator
        writes it (process 0); every process reads it back identically
        from the shared run directory.  The sidecar is written BEFORE the
        orbax save so a finalised step always has its sidecar (a kill in
        between leaves a harmless orphan, collected below); an already-
        finalised ``step`` is skipped, not re-saved — ONLY safe because a
        run never reuses a dirty directory without ``--resume`` or
        ``--elastic`` (:func:`..workloads.base._maybe_checkpointer`
        rejects that, and elastic restores-then-continues, logging what it
        restored), so a replayed id within a run carries bit-identical
        state (the elastic retry).  ``force=True`` really overwrites
        (delete + save, sidecar included).

        ``manifest=True`` (default) also writes the per-leaf
        checksum/finiteness manifest sidecar — the integrity record
        restores verify against.  Like ``extra`` it is written BEFORE the
        orbax save (a finalised step always has its manifest; a kill in
        between leaves an orphan the GC collects).

        ``publish_dir`` (``--publish-weights``) additionally publishes
        the state's params to that directory in the
        :func:`..serve.reload.publish_weights` manifest format, for
        serving fleets watching it (``--reload-watch``) to hot-swap.
        Publishing happens AFTER the orbax save is durable (it forces a
        ``wait_until_finished``): only weights that a restart could also
        restore are ever offered to live engines."""
        if step in set(self._mgr.all_steps()):
            if not force:
                if wait:
                    self._mgr.wait_until_finished()
                return False
            self._mgr.delete(step)
            if jax.process_index() == 0:
                for path in (self._extra_path(step),
                             self._manifest_path(step)):
                    try:  # the old step's sidecars must not outlive it
                        os.remove(path)
                    except FileNotFoundError:
                        pass
        if extra is not None and jax.process_index() == 0:
            self._write_json(self._extra_path(step), extra)
        if manifest and jax.process_index() == 0:
            from distributed_deep_learning_tpu.reshard.manifest import capture

            tree = _as_pytree(state)
            records = _leaf_records(tree)
            self._write_json(self._manifest_path(step), {
                "format": MANIFEST_FORMAT,
                "all_finite": all(r.get("finite", True)
                                  for r in records.values()),
                "leaves": records,
                # metadata-only placement fingerprint: lets a restore on a
                # different topology know it must reshard
                "topology": capture(tree).to_json(),
            })
        saved = self._mgr.save(
            step, args=ocp.args.StandardSave(_as_pytree(state)), force=force)
        if jax.process_index() == 0:
            self._gc_sidecars(protect=step)
        if saved and publish_dir is not None:
            # durability gate: never offer weights to live engines that a
            # restart could not also restore
            self._mgr.wait_until_finished()
            if jax.process_index() == 0:
                from distributed_deep_learning_tpu.serve import reload

                reload.publish_weights(publish_dir, step, state.params)
        if wait:
            self._mgr.wait_until_finished()
        return saved

    def _extra_path(self, step: int) -> str:
        return os.path.join(self._dir, f"extra-{step}.json")

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self._dir, f"manifest-{step}.json")

    @staticmethod
    def _write_json(path: str, payload: dict) -> None:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)  # atomic on POSIX

    def _gc_sidecars(self, protect: int | None = None) -> None:
        """Drop sidecars whose checkpoint orbax has pruned (max_to_keep).

        Only steps BELOW the newest finalised one are candidates: steps are
        saved in increasing order, so anything above it is still in flight
        and must keep its (pre-written) sidecar.  ``protect`` exempts the
        step whose save is in flight RIGHT NOW — a ``force=True``
        re-save of a non-latest step sits below the newest finalised id
        and would otherwise lose its fresh sidecar (review finding)."""
        import glob

        finalised = set(self._mgr.all_steps())
        if not finalised:
            return
        newest = max(finalised)
        for kind in ("extra", "manifest"):
            for path in glob.glob(os.path.join(self._dir,
                                               f"{kind}-*.json")):
                name = os.path.basename(path)
                try:
                    step = int(name[len(kind) + 1:-len(".json")])
                except ValueError:
                    continue
                if step < newest and step not in finalised \
                        and step != protect:
                    try:
                        os.remove(path)
                    except OSError:  # pragma: no cover - concurrent cleanup
                        pass

    def read_extra(self, step: int | None = None) -> dict | None:
        """The `extra` sidecar saved with `step` (default: latest), or None
        (pre-sidecar checkpoints / never saved with extra)."""
        step = self.latest_step() if step is None else step
        if step is None:
            return None
        import json

        try:
            with open(self._extra_path(step)) as f:
                return json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            return None

    def read_manifest(self, step: int | None = None) -> dict | None:
        """The integrity manifest sidecar for `step` (default: latest), or
        None (legacy checkpoint / unreadable sidecar)."""
        step = self.latest_step() if step is None else step
        if step is None:
            return None
        try:
            with open(self._manifest_path(step)) as f:
                return json.load(f)
        except (FileNotFoundError, OSError, json.JSONDecodeError):
            return None

    def read_topology(self, step: int | None = None):
        """The saved :class:`~...reshard.manifest.Topology` for `step`, or
        None for a legacy checkpoint (format-1 manifest, no manifest at
        all, or a malformed block) — callers treat None as "same topology
        as the writer", warn, and never quarantine."""
        from distributed_deep_learning_tpu.reshard.manifest import Topology

        manifest = self.read_manifest(step)
        if not manifest:
            return None
        return Topology.from_json(manifest.get("topology"))

    # -- restore ------------------------------------------------------------
    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def all_steps(self) -> list[int]:
        """Finalised step ids, ascending."""
        return sorted(self._mgr.all_steps())

    def restore(self, target: TrainState, step: int | None = None, *,
                verify: bool = True, shardings=None) -> TrainState | None:
        """Restore into the structure/shardings of `target`.

        Returns None when the directory holds no checkpoint (caller starts
        fresh) — the preemption-resume idiom::

            state = ckpt.restore(state) or state

        With ``verify`` (default) the restored leaves are checked against
        the step's manifest sidecar; a mismatch (bit-flip, torn write,
        non-finite values) raises :class:`CheckpointCorruption`.  Steps
        saved without a manifest (pre-integrity run dirs) restore
        unverified.  Use :meth:`restore_verified` for the full
        fallback-and-quarantine recovery path.

        ``shardings`` (a pytree of per-leaf Shardings shaped like the
        saved fields) overrides the abstract target's placement: orbax
        then reads only the slices each target shard needs — the on-disk
        chunked half of cross-topology resume (reshard/).  Verification
        still applies: the CRC is over the global array, placement-
        independent.
        """
        step = self.latest_step() if step is None else step
        if step is None:
            return None
        # abstract target: arrays → ShapeDtypeStruct carrying their sharding
        # (so each host restores its addressable shards); python scalars
        # (e.g. a plain int step) pass through as-is
        if shardings is None:
            abstract = jax.tree.map(
                lambda x: ocp.utils.to_shape_dtype_struct(x)
                if isinstance(x, jax.Array) else x,
                _as_pytree(target))
        else:
            abstract = jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                  sharding=s)
                if isinstance(x, jax.Array) else x,
                _as_pytree(target), shardings)
        restored = self._mgr.restore(
            step, args=ocp.args.StandardRestore(abstract))
        if verify:
            self._verify(step, restored)
        return _with_fields(target, restored)

    def _verify(self, step: int, restored_tree) -> None:
        """Raise :class:`CheckpointCorruption` unless `restored_tree`
        matches `step`'s manifest (no manifest = legacy, passes)."""
        try:
            with open(self._manifest_path(step)) as f:
                manifest = json.load(f)
        except FileNotFoundError:
            return  # pre-integrity checkpoint: nothing to verify against
        except (OSError, json.JSONDecodeError) as e:
            raise CheckpointCorruption(step, f"unreadable manifest ({e})")
        if not manifest.get("all_finite", True):
            raise CheckpointCorruption(
                step, "manifest records non-finite values at save time")
        expected = manifest.get("leaves", {})
        actual = _leaf_records(restored_tree)
        if set(expected) != set(actual):
            raise CheckpointCorruption(
                step, f"leaf set changed: manifest has {len(expected)} "
                f"leaves, restore produced {len(actual)}")
        for key, rec in expected.items():
            got = actual[key]
            if rec.get("crc32") is None or got.get("crc32") is None:
                continue  # multi-host shard: exempt (see _leaf_records)
            if rec["crc32"] != got["crc32"]:
                raise CheckpointCorruption(
                    step, f"checksum mismatch at leaf {key!r}")
            if not got.get("finite", True):
                raise CheckpointCorruption(
                    step, f"non-finite values restored at leaf {key!r}")

    def restore_verified(self, target: TrainState,
                         step: int | None = None
                         ) -> tuple[TrainState | None, int | None]:
        """Restore the newest VERIFIED-GOOD checkpoint at or below `step`.

        The recovery-chain entry point: tries the newest candidate first;
        a step that fails to restore (torn orbax files) or fails manifest
        verification (bit-flip, non-finite save) is QUARANTINED — renamed
        under ``<dir>/quarantine/``, sidecars included, never deleted —
        and the next-newest step is tried.  Returns ``(state, step)``, or
        ``(None, None)`` when no checkpoint survives (caller starts
        fresh).  Every process must call this collectively (orbax restores
        are collective); quarantine renames happen on process 0."""
        self._mgr.wait_until_finished()
        candidates = sorted(self._mgr.all_steps(), reverse=True)
        if step is not None:
            candidates = [s for s in candidates if s <= step]
        for s in candidates:
            try:
                return self.restore(target, step=s, verify=True), s
            except Exception as e:
                # CheckpointCorruption, or backend-specific errors from a
                # torn orbax step: ANY restore failure here means "this
                # step is unusable", which is exactly what
                # quarantine-and-fall-back is for
                print(f"checkpoint: step {s} unusable "
                      f"({type(e).__name__}: {e}); quarantining and "
                      "falling back", file=sys.stderr, flush=True)
                self.quarantine(s, reason=f"{type(e).__name__}: {e}")
        return None, None

    # -- quarantine ---------------------------------------------------------
    def _step_path(self, step: int) -> str | None:
        """The directory orbax stores `step` under (name formats vary)."""
        direct = os.path.join(self._dir, str(step))
        if os.path.isdir(direct):
            return direct
        for name in os.listdir(self._dir):
            full = os.path.join(self._dir, name)
            if not os.path.isdir(full) or name == "quarantine":
                continue
            m = re.fullmatch(r"\D*?0*(\d+)", name)
            if m and int(m.group(1)) == step:
                return full
        return None

    def quarantine(self, step: int, reason: str = "") -> str | None:
        """Move `step`'s directory + sidecars under ``<dir>/quarantine/``.

        Rename, never delete: the corrupt artifact is evidence (what broke
        — storage, a torn write, a bad host?) and rename keeps it off the
        recovery path atomically.  Returns the quarantine path (None when
        the step has no directory).  Refreshes the orbax manager so
        ``latest_step``/``all_steps`` immediately reflect the removal."""
        dst = None
        if jax.process_index() == 0:
            src = self._step_path(step)
            if src is not None:
                qdir = os.path.join(self._dir, "quarantine")
                os.makedirs(qdir, exist_ok=True)
                dst = os.path.join(qdir, os.path.basename(src))
                n = 0
                while os.path.exists(dst):  # repeated corruption of one id
                    n += 1
                    dst = os.path.join(qdir, f"{os.path.basename(src)}.{n}")
                os.rename(src, dst)
                for side in (self._extra_path(step),
                             self._manifest_path(step)):
                    if os.path.exists(side):
                        os.rename(side, os.path.join(
                            qdir, os.path.basename(dst) + "-" +
                            os.path.basename(side)))
                if reason:
                    self._write_json(f"{dst}.reason.json",
                                     {"step": step, "reason": reason})
        self._reload_manager()
        return dst

    def _reload_manager(self) -> None:
        """Make the orbax manager re-scan the directory after an external
        change (quarantine rename)."""
        self._mgr.reload()

    def wait_until_finished(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()

    def __enter__(self) -> "Checkpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
