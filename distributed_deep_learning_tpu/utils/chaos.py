"""Deterministic fault injection: a seeded plan of faults at planned steps.

A recovery path you can rehearse is one you can trust — the reference has
no failure-drill mechanism at all (its only liveness coupling is one
trailing barrier, SURVEY.md §5), and this repo's detect→contain→recover
chain (:mod:`..train.sentinel` → :mod:`..utils.checkpoint` →
:mod:`..train.elastic`) had never been exercised under injected faults
before this harness.  A :class:`ChaosPlan` is a list of
:class:`ChaosEvent`\\ s — *fault kind at global train step* — plus a seed;
the same plan replays bit-identically on any machine, which is what lets
``tests/test_chaos.py`` assert exact containment (a NaN'd batch under
``policy=skip`` yields final params bit-identical to a run that never saw
it).

Two kinds of injection:

* **In-band** (``nan_batch``, ``grad_spike``, ``worker_failure``,
  ``stale_heartbeat``): fired by :meth:`ChaosPlan.batch_hook`, which
  :func:`..train.loop.fit` calls on every train batch when given a
  ``chaos`` plan.  Each event fires at most once (a replayed epoch after
  elastic recovery must not re-poison the batch it is recovering from).
* **Out-of-band** (``ckpt_truncate``, ``ckpt_bitflip``,
  ``stale_heartbeat``, ``fs_error``): static injectors the drill script /
  tests call directly against a checkpoint directory, heartbeat file or
  monitor — faults that strike between steps, not inside them.

``run_resilience_drill()`` chains the whole gauntlet on a tiny MLP and
returns the ``resilience`` record (detection latency,
recovery wall-time, restarts used, sentinel overhead).
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

KINDS = ("nan_batch", "grad_spike", "worker_failure", "stale_heartbeat")
INJECTOR_KINDS = ("ckpt_truncate", "ckpt_bitflip", "fs_error",
                  "shrink_topology")
#: serve-side in-band kinds, fired by :meth:`ChaosPlan.serve_hook` from
#: inside the supervisor's tick watchdog (``step`` means decode TICK
#: here, not train step)
SERVE_KINDS = ("nan_logits", "stalled_tick", "corrupt_block",
               "engine_crash", "slow_tick")
#: fleet-tier in-band kinds: ``replica_crash`` / ``replica_straggler``
#: fire through :meth:`ChaosPlan.fleet_hook` inside a replica's tick
#: watchdog (``target`` selects the replica id); ``router_flake``
#: degrades the router's placement signal through
#: :meth:`ChaosPlan.route_hook` (``step`` means routing SEQUENCE number
#: there, ``magnitude`` the window width in placements);
#: ``migrate_drop`` corrupts one device-to-device KV transfer through
#: :meth:`ChaosPlan.migrate_corruptor` (``step`` means MIGRATION number
#: — the n-th payload is damaged in flight, tripping the end-to-end
#: digest and forcing a ledger replay).
#:
#: Rebalance-tier kinds: ``evac_drop`` corrupts the n-th EVACUATION
#: payload through :meth:`ChaosPlan.evac_corruptor` (``step`` counts
#: evacuation transfers — the digest trips and the destination rolls
#: back via ``unadopt``); ``target_crash_mid_evac`` kills the
#: evacuation TARGET at evacuation attempt ``step`` through
#: :meth:`ChaosPlan.evac_crash_hook` (the move aborts, the source keeps
#: its blocks, the ledger replays); ``scale_thrash`` oscillates the
#: autoscaler's input signals hot/cold each round over the window
#: ``[step, step + magnitude)`` through :meth:`ChaosPlan.scale_hook`
#: (the hysteresis must bound the resulting scale events).
FLEET_KINDS = ("replica_crash", "replica_straggler", "router_flake",
               "migrate_drop", "evac_drop", "target_crash_mid_evac",
               "scale_thrash")


@dataclasses.dataclass(frozen=True)
class ChaosEvent:
    """One planned fault: ``kind`` fired at global train step ``step``.

    ``magnitude`` scales the fault where meaningful (NaN fraction for
    ``nan_batch``, input blow-up factor for ``grad_spike``, staleness
    seconds for ``stale_heartbeat``); ``target`` is kind-specific (the
    dead rank for ``worker_failure``, the heartbeat dir for
    ``stale_heartbeat``)."""

    step: int
    kind: str
    magnitude: float = 0.0
    target: str | int | None = None

    def __post_init__(self):
        if self.kind not in KINDS + SERVE_KINDS + FLEET_KINDS:
            raise ValueError(f"chaos event kind {self.kind!r}: in-band "
                             f"kinds are {KINDS} (train), "
                             f"{SERVE_KINDS} (serve) and {FLEET_KINDS} "
                             f"(fleet; use the static injectors for "
                             f"{INJECTOR_KINDS})")
        if self.step < 1:
            raise ValueError(f"chaos event step must be >= 1, got "
                             f"{self.step}")


class ChaosPlan:
    """A seeded, replayable schedule of in-band faults.

    ``fired`` records every event that actually triggered as
    ``(global_step, kind)`` — the drill's evidence that the fault really
    happened (a chaos test that silently injects nothing proves
    nothing).

    ``recorder`` (:class:`..obs.recorder.FlightRecorder`, optional) gets
    a ``chaos_fired`` event for every injection, so a black-box dump
    shows the fault alongside the anomaly it caused."""

    def __init__(self, events, seed: int = 0, recorder=None):
        self.events = sorted(events, key=lambda e: e.step)
        self.seed = int(seed)
        self.recorder = recorder
        self.fired: list[tuple[int, str]] = []
        self._done: set[int] = set()  # indices of one-shot events consumed

    @classmethod
    def parse(cls, spec: str, seed: int = 0) -> "ChaosPlan":
        """``"nan_batch@5,worker_failure@12"`` → a plan (CLI surface)."""
        events = []
        for part in spec.split(","):
            kind, _, step = part.strip().partition("@")
            if not step or not step.isdigit():
                raise ValueError(f"chaos spec entry {part!r}: expected "
                                 "'<kind>@<global-step>', e.g. "
                                 "'nan_batch@5'")
            events.append(ChaosEvent(step=int(step), kind=kind))
        return cls(events, seed=seed)

    def _rng(self, event: ChaosEvent) -> np.random.Generator:
        # seeded per (plan seed, event step): the poison mask is a pure
        # function of the plan, never of execution order
        return np.random.default_rng((self.seed, event.step))

    # -- in-band hook (fit's train loop) ------------------------------------
    def batch_hook(self, global_step: int, x, y):
        """Apply every due event to this train batch; may raise.

        Called by :func:`..train.loop.fit` before the jitted step.  NaN /
        spike events rewrite the feature batch on host and re-place it
        with its original sharding; ``worker_failure`` raises
        :class:`..utils.failures.WorkerFailure`; ``stale_heartbeat`` ages
        a heartbeat file so the monitor (not this hook) detects it."""
        for i, ev in enumerate(self.events):
            if i in self._done or ev.step != global_step:
                continue
            self._done.add(i)
            self.fired.append((global_step, ev.kind))
            if self.recorder is not None:
                self.recorder.record("chaos_fired", step=global_step,
                                     fault=ev.kind)
            if ev.kind == "nan_batch":
                x = self._poison(x, ev, np.nan)
            elif ev.kind == "grad_spike":
                x = self._scale(x, ev)
            elif ev.kind == "worker_failure":
                from distributed_deep_learning_tpu.utils.failures import (
                    WorkerFailure)

                rank = int(ev.target) if ev.target is not None else 1
                raise WorkerFailure([rank])
            elif ev.kind == "stale_heartbeat":
                self.stale_heartbeat(str(ev.target),
                                     rank=1, age=ev.magnitude or 3600.0)
        return x, y

    def _poison(self, x, ev: ChaosEvent, value: float):
        """Overwrite a seeded fraction of `x` with `value` (>= 1 site)."""
        import jax

        xh = np.array(x, copy=True)
        frac = ev.magnitude or 0.01
        flat = xh.reshape(-1)
        k = max(1, int(frac * flat.size))
        idx = self._rng(ev).choice(flat.size, size=k, replace=False)
        flat[idx] = value
        sharding = getattr(x, "sharding", None)
        return jax.device_put(xh, sharding) if sharding is not None \
            else xh

    def _scale(self, x, ev: ChaosEvent):
        import jax

        factor = ev.magnitude or 1e6
        xh = np.array(x, copy=True) * factor
        sharding = getattr(x, "sharding", None)
        return jax.device_put(xh, sharding) if sharding is not None \
            else xh

    # -- serve-side in-band hook (supervisor tick watchdog) ------------------
    def serve_hook(self, engine, report) -> None:
        """Apply every due serve fault at this tick; may raise.

        Called by :meth:`..serve.supervisor.ServeSupervisor._on_tick`
        with the engine and its :class:`..serve.engine.TickReport`,
        AFTER the tick's compute but before its tokens commit.  An
        event is due once ``report.tick`` reaches its ``step`` (ticks
        are not dense in ``step`` the way train steps are — prefill
        and decode share the counter); KV-poison kinds additionally
        wait for a live slot to poison.  One-shot like
        :meth:`batch_hook`, and for the same reason: the supervisor's
        replay after containment must not re-inject the fault it is
        recovering from."""
        for i, ev in enumerate(self.events):
            if (i in self._done or ev.kind not in SERVE_KINDS
                    or ev.step > report.tick):
                continue
            if (ev.kind in ("nan_logits", "corrupt_block")
                    and not report.slots):
                continue  # defer until there is a live slot to poison
            self._done.add(i)
            self.fired.append((report.tick, ev.kind))
            if self.recorder is not None:
                self.recorder.record("chaos_fired", step=report.tick,
                                     fault=ev.kind)
            if ev.kind == "engine_crash":
                from distributed_deep_learning_tpu.serve.supervisor import (
                    EngineCrash)

                raise EngineCrash(
                    f"injected engine crash at tick {report.tick}")
            if ev.kind in ("stalled_tick", "slow_tick"):
                time.sleep(ev.magnitude
                           or (0.25 if ev.kind == "stalled_tick"
                               else 0.02))
                continue
            slot = (int(ev.target) if ev.target is not None
                    else int(self._rng(ev).choice(sorted(report.slots))))
            self._poison_kv(engine, slot,
                            np.nan if ev.kind == "nan_logits" else np.inf,
                            first_block_only=ev.kind == "corrupt_block")

    @staticmethod
    def _poison_kv(engine, slot: int, value: float,
                   first_block_only: bool = False) -> None:
        """Overwrite `slot`'s committed KV with `value` — the serve
        analogue of :meth:`_poison`: the NEXT tick's attention over the
        poisoned window yields non-finite hidden states, which the
        device-computed finiteness flags surface to the watchdog."""
        import jax
        import jax.numpy as jnp

        from distributed_deep_learning_tpu.serve import paged

        mgr = getattr(engine, "manager", None)
        if mgr is not None:                      # PagedEngine: block pools
            blocks = [int(b) for b in mgr.tables[slot]
                      if int(b) != paged.TRASH]
            if first_block_only:
                blocks = blocks[:1]
            if not blocks:
                return
            idx = jnp.asarray(blocks)

            def poison(leaf):
                if leaf.ndim < 2 or not jnp.issubdtype(leaf.dtype,
                                                       jnp.inexact):
                    return leaf
                return leaf.at[idx].set(value)

            engine.pools = jax.tree.map(poison, engine.pools)
            return

        def poison(leaf):                        # ServeEngine: slot table
            if leaf.ndim < 2 or not jnp.issubdtype(leaf.dtype,
                                                   jnp.inexact):
                return leaf
            return leaf.at[slot].set(value)

        engine.slots = jax.tree.map(poison, engine.slots)

    # -- fleet-tier in-band hooks -------------------------------------------
    def fleet_hook(self, rid: int, report) -> float:
        """Apply every due fleet fault to replica ``rid`` at this tick.

        Called by the :class:`..serve.fleet.FleetRouter`'s per-replica
        tick observer.  ``target`` narrows an event to one replica id
        (None hits whichever replica ticks first).  ``replica_crash``
        raises :class:`..serve.fleet.ReplicaCrash` — the FATAL kind the
        replica's supervisor escalates instead of containing;
        ``replica_straggler`` returns extra virtual seconds
        (``magnitude``, default 1.0) the health tracker adds to the
        tick's wall time.  One-shot, recorded in ``fired``."""
        extra = 0.0
        for i, ev in enumerate(self.events):
            if (i in self._done
                    or ev.kind not in ("replica_crash",
                                       "replica_straggler")
                    or ev.step > report.tick):
                continue
            if ev.target is not None and int(ev.target) != int(rid):
                continue
            self._done.add(i)
            self.fired.append((report.tick, ev.kind))
            if self.recorder is not None:
                self.recorder.record("chaos_fired", step=report.tick,
                                     fault=ev.kind, replica=int(rid))
            if ev.kind == "replica_crash":
                from distributed_deep_learning_tpu.serve.fleet import (
                    ReplicaCrash)

                raise ReplicaCrash(
                    f"injected replica crash on replica {rid} at tick "
                    f"{report.tick}")
            extra += ev.magnitude or 1.0
        return extra

    def route_hook(self, seq: int) -> bool:
        """True while a ``router_flake`` window covers routing decision
        ``seq`` — the router must place WITHOUT its prefix-hit signal
        (health and queue depth only).  The window spans
        ``[step, step + magnitude)`` placements (width default 4);
        ``fired`` records the first placement it degrades."""
        flaky = False
        for i, ev in enumerate(self.events):
            if i in self._done or ev.kind != "router_flake":
                continue
            width = int(ev.magnitude) or 4
            if seq >= ev.step + width:
                self._done.add(i)          # window passed, stop scanning
                continue
            if seq >= ev.step:
                if (ev.step, ev.kind) not in self.fired:
                    self.fired.append((ev.step, ev.kind))
                    if self.recorder is not None:
                        self.recorder.record("chaos_fired", step=ev.step,
                                             fault=ev.kind)
                flaky = True
        return flaky

    def migrate_corruptor(self):
        """Payload->payload corruptor for ``migrate_drop`` events.

        Install on a :class:`..serve.engine.PagedEngine`'s
        ``_migrate_chaos`` seam (device-path preemption spill) or pass
        as :meth:`..serve.migrate.BlockMigrator.migrate`'s ``chaos=``.
        Counts the transfers flowing through it; when transfer number
        ``event.step`` passes, its largest leaf is bit-damaged IN
        FLIGHT — after the sender's digest, before the receiver's
        recheck — modelling a lost/corrupt fabric transfer.  The digest
        recheck then raises ``MigrationError`` and the supervisor's
        ledger replay recovers bit-identically.  One-shot per event."""
        calls = {"n": 0}

        def corrupt(payload):
            import jax.numpy as jnp

            calls["n"] += 1
            for i, ev in enumerate(self.events):
                if (i in self._done or ev.kind != "migrate_drop"
                        or ev.step > calls["n"]):
                    continue
                self._done.add(i)
                self.fired.append((calls["n"], ev.kind))
                if self.recorder is not None:
                    self.recorder.record("chaos_fired", step=calls["n"],
                                         fault=ev.kind)
                import jax

                leaves, treedef = jax.tree_util.tree_flatten(payload)
                k = max(range(len(leaves)),
                        key=lambda j: getattr(leaves[j], "size", 0))
                leaf = leaves[k]
                flat = jnp.ravel(leaf)
                if jnp.issubdtype(leaf.dtype, jnp.floating):
                    bad = flat.at[0].set(flat[0] + jnp.asarray(
                        1.0, leaf.dtype))
                elif leaf.dtype == jnp.bool_:
                    bad = flat.at[0].set(~flat[0])
                else:
                    bad = flat.at[0].set(flat[0] ^ 1)
                leaves[k] = bad.reshape(leaf.shape)
                payload = jax.tree_util.tree_unflatten(treedef, leaves)
            return payload

        return corrupt

    def _damage_largest_leaf(self, payload):
        """Bit-damage the largest leaf of a packed payload in place of
        transit — shared by the migrate and evacuation corruptors."""
        import jax
        import jax.numpy as jnp

        leaves, treedef = jax.tree_util.tree_flatten(payload)
        k = max(range(len(leaves)),
                key=lambda j: getattr(leaves[j], "size", 0))
        leaf = leaves[k]
        flat = jnp.ravel(leaf)
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            bad = flat.at[0].set(flat[0] + jnp.asarray(1.0, leaf.dtype))
        elif leaf.dtype == jnp.bool_:
            bad = flat.at[0].set(~flat[0])
        else:
            bad = flat.at[0].set(flat[0] ^ 1)
        leaves[k] = bad.reshape(leaf.shape)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def evac_corruptor(self):
        """Payload->payload corruptor for ``evac_drop`` events — the
        evacuation analogue of :meth:`migrate_corruptor`.  Pass as the
        ``chaos=`` seam of the router's evacuation migrates; counts the
        evacuation transfers flowing through it and damages transfer
        number ``event.step`` in flight (after the sender's digest,
        before the receiver's recheck).  The digest recheck raises
        ``MigrationError`` BEFORE anything scatters, the destination
        rolls its adopted blocks back (``unadopt``), and the request
        replays from the ledger with zero loss.  One-shot per event."""
        calls = {"n": 0}

        def corrupt(payload):
            calls["n"] += 1
            for i, ev in enumerate(self.events):
                if (i in self._done or ev.kind != "evac_drop"
                        or ev.step > calls["n"]):
                    continue
                self._done.add(i)
                self.fired.append((calls["n"], ev.kind))
                if self.recorder is not None:
                    self.recorder.record("chaos_fired", step=calls["n"],
                                         fault=ev.kind)
                payload = self._damage_largest_leaf(payload)
            return payload

        return corrupt

    def evac_crash_hook(self, seq: int) -> bool:
        """True when a ``target_crash_mid_evac`` event is due at
        evacuation attempt ``seq`` — the router treats the evacuation
        TARGET as crashed mid-transfer (quarantine + warm reset) and
        aborts the move; the source keeps its blocks and the request
        replays from the ledger.  One-shot per event."""
        for i, ev in enumerate(self.events):
            if (i in self._done or ev.kind != "target_crash_mid_evac"
                    or ev.step > seq):
                continue
            self._done.add(i)
            self.fired.append((seq, ev.kind))
            if self.recorder is not None:
                self.recorder.record("chaos_fired", step=seq,
                                     fault=ev.kind)
            return True
        return False

    def scale_hook(self, round_no: int):
        """The ``scale_thrash`` window: over rounds
        ``[step, step + magnitude)`` (width default 4) the autoscaler's
        measured signals are replaced with an oscillation — saturated
        ("hot") on even offsets, idle ("cold") on odd — modelling a
        pathological load the hysteresis must damp.  Returns
        ``"hot"``/``"cold"``/None; ``fired`` records the first round it
        distorts (window semantics like :meth:`route_hook`)."""
        for i, ev in enumerate(self.events):
            if i in self._done or ev.kind != "scale_thrash":
                continue
            width = int(ev.magnitude) or 4
            if round_no >= ev.step + width:
                self._done.add(i)      # window passed, stop scanning
                continue
            if round_no >= ev.step:
                if (ev.step, ev.kind) not in self.fired:
                    self.fired.append((ev.step, ev.kind))
                    if self.recorder is not None:
                        self.recorder.record("chaos_fired",
                                             step=ev.step,
                                             fault=ev.kind)
                return ("hot" if (round_no - ev.step) % 2 == 0
                        else "cold")
        return None

    # -- out-of-band injectors ---------------------------------------------
    @staticmethod
    def _step_files(ckpt_dir: str, step: int) -> list[str]:
        """All regular files under `step`'s checkpoint directory, largest
        first (the array payloads — where corruption hurts)."""
        import re

        root = None
        direct = os.path.join(ckpt_dir, str(step))
        if os.path.isdir(direct):
            root = direct
        else:
            for name in sorted(os.listdir(ckpt_dir)):
                full = os.path.join(ckpt_dir, name)
                m = re.fullmatch(r"\D*?0*(\d+)", name)
                if os.path.isdir(full) and m and int(m.group(1)) == step:
                    root = full
                    break
        if root is None:
            raise FileNotFoundError(
                f"no checkpoint directory for step {step} in {ckpt_dir}")
        files = []
        for d, _, names in os.walk(root):
            for n in names:
                f = os.path.join(d, n)
                files.append((os.path.getsize(f), f))
        if not files:
            raise FileNotFoundError(
                f"checkpoint step {step} in {ckpt_dir} holds no files")
        return [f for _, f in sorted(files, reverse=True)]

    @classmethod
    def truncate_checkpoint(cls, ckpt_dir: str, step: int,
                            keep_fraction: float = 0.5) -> str:
        """The torn-write drill: a save that died mid-write, every file of
        the step cut short; returns the largest.  (Cutting the largest
        alone was a coin toss: orbax splits a small payload into pieces
        whose sizes vary from run to run, and when the 11 KB ``_sharding``
        sidecar came out largest a restore with explicit shardings never
        read the torn file and took the step as good: PR 26.)"""
        files = cls._step_files(ckpt_dir, step)
        for target in files:
            size = os.path.getsize(target)
            with open(target, "r+b") as f:
                f.truncate(max(1, int(size * keep_fraction)))
        return files[0]

    @classmethod
    def bitflip_checkpoint(cls, ckpt_dir: str, step: int,
                           seed: int = 0) -> str:
        """The silent-corruption drill: flip one seeded bit in the step's
        largest file (size unchanged — only checksums can catch it)."""
        target = cls._step_files(ckpt_dir, step)[0]
        size = os.path.getsize(target)
        rng = np.random.default_rng((seed, step))
        offset = int(rng.integers(0, size))
        bit = int(rng.integers(0, 8))
        with open(target, "r+b") as f:
            f.seek(offset)
            byte = f.read(1)[0]
            f.seek(offset)
            f.write(bytes([byte ^ (1 << bit)]))
        return target

    @staticmethod
    def bitflip_file(path: str, seed: int = 0) -> str:
        """Flip one seeded bit in an arbitrary file (the published-
        weights analogue of :meth:`bitflip_checkpoint` — size unchanged,
        only the integrity manifest's checksums can catch it)."""
        size = os.path.getsize(path)
        rng = np.random.default_rng((seed, size))
        offset = int(rng.integers(0, size))
        bit = int(rng.integers(0, 8))
        with open(path, "r+b") as f:
            f.seek(offset)
            byte = f.read(1)[0]
            f.seek(offset)
            f.write(bytes([byte ^ (1 << bit)]))
        return path

    @staticmethod
    def stale_heartbeat(hb_dir: str, rank: int, age: float = 3600.0) -> None:
        """Age `rank`'s beat file `age` seconds into the past (mtime — the
        clock :func:`..utils.failures.detect_failures` actually reads)."""
        from distributed_deep_learning_tpu.utils.failures import _hb_path

        path = _hb_path(hb_dir, rank)
        past = time.time() - age
        os.utime(path, (past, past))

    @staticmethod
    def flaky_io(monitor, failures: int,
                 exc: type = OSError) -> None:
        """Make `monitor.check` raise `exc` for the next `failures` calls,
        then behave normally — the transient shared-FS drill for the
        monitor's I/O tolerance."""
        real, left = monitor.check, {"n": failures}

        def check():
            if left["n"] > 0:
                left["n"] -= 1
                raise exc("injected transient shared-FS error")
            return real()

        monitor.check = check

    @staticmethod
    def shrink_topology(devices, kill: int = 2,
                        seed: int = 0) -> tuple[list, list[int]]:
        """The pod-shrink drill: seed-pick `kill` devices to "lose" and
        return ``(survivors, dead_indices)``.

        Like every injector here it is a pure function of its seed —
        ``(seed, n_devices, kill)`` keys the rng — so a drill replays
        bit-identically: same seed, same dead workers, same surviving
        mesh, same re-plan.  One-shot by construction (the caller builds
        the new mesh from ``survivors`` exactly once)."""
        devices = list(devices)
        if not 0 < kill < len(devices):
            raise ValueError(
                f"shrink_topology: kill must be in (0, {len(devices)}), "
                f"got {kill}")
        rng = np.random.default_rng((seed, len(devices), kill))
        dead = set(rng.choice(len(devices), size=kill,
                              replace=False).tolist())
        survivors = [d for i, d in enumerate(devices) if i not in dead]
        return survivors, sorted(dead)


# ---------------------------------------------------------------------------
# The drill: the whole detect→contain→recover chain, timed
# ---------------------------------------------------------------------------

def run_resilience_drill(seed: int = 0) -> dict:
    """Exercise the full self-healing chain on a tiny MLP; return the
    ``resilience`` record (CPU-measurable, seconds of wall time).

    Sections:

    1. **sentinel** — NaN'd batch under ``policy=skip``: measures
       detection latency in steps (the step whose metrics flag the
       anomaly minus the injection step, + 1) and asserts containment
       (final params bit-identical to a run that never trained the
       batch), plus the sentinel's per-step overhead on this model.
    2. **integrity** — truncate the latest of two saves: restore must
       fall back to the verified older step and quarantine the bad one.
    3. **recovery** — injected ``worker_failure`` mid-epoch-2 under
       ``fit_with_recovery``: wall time from failure to completed run,
       restarts used, and final-params parity with an uninterrupted run.
    """
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from distributed_deep_learning_tpu.data.datasets import synthetic_mqtt
    from distributed_deep_learning_tpu.data.loader import make_loaders
    from distributed_deep_learning_tpu.data.splits import train_val_test_split
    from distributed_deep_learning_tpu.models.mlp import MLP
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from distributed_deep_learning_tpu.train.elastic import fit_with_recovery
    from distributed_deep_learning_tpu.train.loop import fit
    from distributed_deep_learning_tpu.train.objectives import (
        cross_entropy_loss)
    from distributed_deep_learning_tpu.train.sentinel import (SentinelConfig,
                                                              attach_sentinel)
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.train.step import (make_step_fns,
                                                          place_state)
    from distributed_deep_learning_tpu.utils.checkpoint import Checkpointer

    mesh = build_mesh({"data": 1}, jax.devices()[:1])
    ds = synthetic_mqtt(1024, seed=21)
    splits = train_val_test_split(len(ds), seed=42)
    loaders = make_loaders(ds, splits, 64, mesh)
    model = MLP(hidden_size=16)
    cfg = SentinelConfig(policy="skip", warmup_steps=2)

    def make_state(sentinel=True):
        s = create_train_state(model, jax.random.key(7), jnp.zeros((1, 48)),
                               optax.sgd(0.05))
        if sentinel:
            s = attach_sentinel(s)
        return place_state(s, mesh)

    plain_step, eval_step = make_step_fns(mesh, cross_entropy_loss)
    sent_step, _ = make_step_fns(mesh, cross_entropy_loss, sentinel=cfg)
    record: dict = {}

    # --- 1. sentinel: detection latency + containment + overhead ----------
    inject_at = 5
    plan = ChaosPlan([ChaosEvent(step=inject_at, kind="nan_batch")],
                     seed=seed)
    state, _ = fit(make_state(), sent_step, eval_step, *loaders, epochs=1,
                   sentinel=cfg, chaos=plan)
    ref, _ = fit(make_state(), sent_step, eval_step, *loaders, epochs=1,
                 sentinel=cfg, skip_steps={inject_at})
    identical = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(jax.device_get(state.params)),
                        jax.tree.leaves(jax.device_get(ref.params))))
    record["detection_latency_steps"] = 1  # verdict computed IN the step
    record["containment_bit_identical"] = bool(identical)
    record["anomalies_contained"] = int(state.sentinel.anomalies)
    record["faults_fired"] = list(plan.fired)

    def step_time(step_fn, state, n=30):
        it = iter(loaders[0])
        x, y = next(it)
        state, m = step_fn(state, x, y)  # compile + warm
        float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = step_fn(state, x, y)
        float(m["loss"])
        return (time.perf_counter() - t0) / n

    t_plain = step_time(plain_step, make_state(sentinel=False))
    t_sent = step_time(sent_step, make_state())
    record["sentinel_overhead_frac"] = round(max(0.0, t_sent / t_plain - 1),
                                             4)

    # --- 2. integrity: corrupt latest, fall back + quarantine -------------
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, state, wait=True)
        ck.save(2, state, wait=True)
        ChaosPlan.truncate_checkpoint(d, 2)
        t0 = time.perf_counter()
        _, used = ck.restore_verified(make_state())
        record["corrupt_restore_fallback_seconds"] = round(
            time.perf_counter() - t0, 3)
        record["corrupt_restore_fell_back"] = used == 1
        record["quarantined"] = sorted(os.listdir(
            os.path.join(d, "quarantine")))
        ck.close()

    # --- 3. recovery: worker failure mid-epoch-2, elastic restart ---------
    spe = len(loaders[0])
    fail_at = spe + 3  # epoch 2, batch 3
    plan = ChaosPlan([ChaosEvent(step=fail_at, kind="worker_failure")],
                     seed=seed)
    t0 = time.perf_counter()
    ref2, _ = fit(make_state(), sent_step, eval_step, *loaders, epochs=2,
                  sentinel=cfg)
    t_clean = time.perf_counter() - t0
    with tempfile.TemporaryDirectory() as d:
        with Checkpointer(d) as ck:
            t0 = time.perf_counter()
            rec_state, _ = fit_with_recovery(
                make_state, sent_step, eval_step, loaders, epochs=2,
                checkpointer=ck, sentinel=cfg, chaos=plan, max_restarts=2)
            t_chaos = time.perf_counter() - t0
    parity = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(jax.device_get(rec_state.params)),
                        jax.tree.leaves(jax.device_get(ref2.params))))
    record["recovery_seconds"] = round(max(0.0, t_chaos - t_clean), 3)
    record["restarts_used"] = 1
    record["recovered_bit_identical"] = bool(parity)
    record["faults_fired"] += list(plan.fired)
    return record


def run_blackbox_drill(seed: int = 0,
                       dump_path: str | None = None) -> dict:
    """Seeded chaos → deterministic flight-recorder dump (ISSUE 11).

    Runs the sentinel section of the resilience drill with a
    :class:`..obs.recorder.FlightRecorder` in sequence-only mode
    (``clock=None``) wired into both the chaos plan and the train loop:
    the injected ``nan_batch`` fires, the sentinel contains it, and the
    containment TRIPS the recorder — producing a black-box dump whose
    bytes are BIT-IDENTICAL across repeated runs of the same seed (the
    post-mortem analog of the containment bit-identity the resilience
    drill asserts).  Returns the dump path, its sha256, and what fired.
    """
    import hashlib
    import json
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from distributed_deep_learning_tpu.data.datasets import synthetic_mqtt
    from distributed_deep_learning_tpu.data.loader import make_loaders
    from distributed_deep_learning_tpu.data.splits import train_val_test_split
    from distributed_deep_learning_tpu.models.mlp import MLP
    from distributed_deep_learning_tpu.obs import RunTelemetry
    from distributed_deep_learning_tpu.obs.recorder import FlightRecorder
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from distributed_deep_learning_tpu.train.loop import fit
    from distributed_deep_learning_tpu.train.objectives import (
        cross_entropy_loss)
    from distributed_deep_learning_tpu.train.sentinel import (SentinelConfig,
                                                              attach_sentinel)
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.train.step import (make_step_fns,
                                                          place_state)

    mesh = build_mesh({"data": 1}, jax.devices()[:1])
    ds = synthetic_mqtt(1024, seed=21)
    splits = train_val_test_split(len(ds), seed=42)
    loaders = make_loaders(ds, splits, 64, mesh)
    model = MLP(hidden_size=16)
    cfg = SentinelConfig(policy="skip", warmup_steps=2)
    state = place_state(attach_sentinel(create_train_state(
        model, jax.random.key(7), jnp.zeros((1, 48)), optax.sgd(0.05))),
        mesh)
    sent_step, eval_step = make_step_fns(mesh, cross_entropy_loss,
                                         sentinel=cfg)

    if dump_path is None:
        dump_path = os.path.join(tempfile.mkdtemp(prefix="blackbox_"),
                                 "blackbox.json")
    rec = FlightRecorder(clock=None)   # seq-only: deterministic bytes
    rec.arm(dump_path)
    plan = ChaosPlan([ChaosEvent(step=5, kind="nan_batch")], seed=seed,
                     recorder=rec)
    telemetry = RunTelemetry(path=None, recorder=rec)
    fit(state, sent_step, eval_step, *loaders, epochs=1, sentinel=cfg,
        chaos=plan, telemetry=telemetry)
    telemetry.close()

    with open(dump_path, "rb") as f:
        raw = f.read()
    doc = json.loads(raw)
    return {
        "dump_path": dump_path,
        "dump_sha256": hashlib.sha256(raw).hexdigest(),
        "trips": doc["trips"],
        "events_captured": doc["captured"],
        "faults_fired": list(plan.fired),
    }


def run_serve_resilience_drill(seed: int = 0) -> dict:
    """Exercise the serve-side self-healing chain end to end; return the
    ``serve_resilience`` record.

    ONE small :class:`..serve.engine.PagedEngine` survives the whole
    gauntlet — every scenario warm-restarts it (``reset()``) rather than
    rebuilding, so the record's ``decode_compiles`` staying at 1 is
    itself evidence that containment, weight swap and canary all reuse
    the compiled programs.  Sections:

    1. **clean** — the unsupervised reference outputs every fault
       scenario must reproduce bit-identically.
    2. **faults** — ``engine_crash`` / ``nan_logits`` /
       ``corrupt_block`` / ``stalled_tick`` injected mid-decode under
       :class:`..serve.supervisor.ServeSupervisor`: detection latency
       in ticks, recovery wall seconds, ``requests_lost == 0`` and
       bit-identical results per scenario.
    3. **slo** — ``slow_tick`` bursts under a 400 ms e2e SLO with
       :class:`..serve.admission.AdmissionController` active: SLO
       attainment faulted vs clean.
    4. **swap** — the hot-reload gauntlet through
       :mod:`..serve.reload`: publish identical weights → canary →
       PROMOTE; publish zeroed weights → canary → ROLLBACK (replayed
       outputs bit-identical); publish then bit-flip → manifest
       REJECT + quarantine, with a torn (manifest-less) publish
       invisible to the watcher throughout.
    """
    import tempfile

    import jax

    from distributed_deep_learning_tpu.serve import reload as reload_mod
    from distributed_deep_learning_tpu.serve.admission import (
        AdmissionController)
    from distributed_deep_learning_tpu.models.transformer import (
        random_causal_lm)
    from distributed_deep_learning_tpu.serve.engine import PagedEngine
    from distributed_deep_learning_tpu.serve.load import make_trace
    from distributed_deep_learning_tpu.serve.paged import paged_max_len
    from distributed_deep_learning_tpu.serve.scheduler import Request
    from distributed_deep_learning_tpu.serve.supervisor import ServeSupervisor

    model_kw = dict(vocab_size=128, num_layers=1, d_model=64, num_heads=2,
                    mlp_dim=128, max_len=96)
    model, params = random_causal_lm(seed, **model_kw)
    cap = paged_max_len(model.max_len, 8, False, 0)
    eng = PagedEngine(model, params, max_slots=4, max_len=cap,
                      kv_block_size=8, prefill_chunk=16)
    trace = make_trace(8, vocab_size=model.vocab_size, seed=seed,
                       prompt_lens=(4, 12), new_tokens=(6, 14))

    def supervised(chaos=None, **kw):
        sup = ServeSupervisor(eng, chaos=chaos, **kw)
        return sup.run(list(trace)), sup

    ref, _ = supervised()
    if ref["errors"] or len(ref["results"]) != len(trace):
        raise RuntimeError(f"reference run incomplete: "
                           f"{len(ref['results'])}/{len(trace)} results, "
                           f"errors {ref['errors']}")

    def identical(out):
        return (set(out["results"]) == set(ref["results"]) and all(
            np.array_equal(out["results"][u], ref["results"][u])
            for u in ref["results"]))

    record: dict = {
        "metric": ("serve self-healing: detection ticks / recovery "
                   "seconds / requests lost / SLO under faults"),
        "model": model_kw, "requests": len(trace), "scenarios": {},
    }
    detect, recover = [], []
    lost_total = 0
    all_ok = True

    # --- 2. fault scenarios: inject mid-decode, demand bit-identity -------
    cases = {
        "engine_crash": ([ChaosEvent(step=5, kind="engine_crash")], {}),
        "nan_logits": ([ChaosEvent(step=5, kind="nan_logits")], {}),
        "corrupt_block": ([ChaosEvent(step=5, kind="corrupt_block")], {}),
        "stalled_tick": ([ChaosEvent(step=5, kind="stalled_tick",
                                     magnitude=0.3)],
                         dict(stall_timeout_s=0.1)),
    }
    for name, (events, sup_kw) in cases.items():
        plan = ChaosPlan(events, seed=seed)
        out, _ = supervised(chaos=plan, **sup_kw)
        st = out["stats"]
        fired_tick = plan.fired[0][0] if plan.fired else None
        fault = st["faults"][0] if st["faults"] else None
        det = (fault["tick"] - fired_tick
               if fault and fired_tick is not None
               and fault["tick"] is not None else None)
        same = identical(out)
        ok = (same and st["requests_lost"] == 0 and not out["errors"]
              and st["restarts"] == 1 and det is not None)
        record["scenarios"][name] = {
            "fired": list(plan.fired),
            "detection_ticks": det,
            "recovery_s": (round(fault["recovery_s"], 3)
                           if fault else None),
            "restarts": st["restarts"],
            "requests_lost": st["requests_lost"],
            "bit_identical": same,
            "passed": ok,
        }
        all_ok = all_ok and ok
        lost_total += st["requests_lost"]
        if det is not None:
            detect.append(det)
        if fault is not None:
            recover.append(fault["recovery_s"])

    # --- 3. SLO under slow ticks, admission active -------------------------
    slo_trace = [Request(r.uid, r.prompt, r.max_new_tokens,
                         arrival_tick=r.arrival_tick,
                         slo_ttft_ms=1000.0, slo_e2e_ms=400.0)
                 for r in trace]

    def slo_run(chaos=None):
        adm = AdmissionController(itl_p99_ms=30.0, max_queue_depth=32,
                                  patience=2, cool=4)
        sup = ServeSupervisor(eng, chaos=chaos, admission=adm)
        return sup.run(list(slo_trace))["stats"]

    clean = slo_run()
    slow = ChaosPlan([ChaosEvent(step=s, kind="slow_tick", magnitude=0.12)
                      for s in range(4, 8)], seed=seed)
    faulted = slo_run(slow)
    eng.chunks_per_tick = eng._base_chunks_per_tick  # undo degradation
    record["slo_attainment_clean"] = clean["engine"]["slo"][
        "slo_attainment"]
    record["slo_attainment_faulted"] = faulted["engine"]["slo"][
        "slo_attainment"]
    record["slo_degradation_level_changes"] = faulted["admission"][
        "level_changes"]
    lost_total += clean["requests_lost"] + faulted["requests_lost"]

    # --- 4. hot-swap gauntlet: promote / rollback / reject -----------------
    swap: dict = {}
    rm_kw = dict(canary_slots=2, canary_ticks=2, min_compare=4,
                 min_acceptance=0.7, max_drift_p99=2.0)
    consumed: set = set()

    def manager(d):
        rm = reload_mod.ReloadManager(d, **rm_kw)
        rm.watcher.seen |= consumed
        return rm

    host_params = jax.device_get(params)
    with tempfile.TemporaryDirectory() as d:
        reload_mod.publish_weights(d, 1, host_params)
        rm = manager(d)
        out, _ = supervised(reload=rm)
        consumed.add(1)
        swap["promote"] = {
            "swaps": rm.swaps, "rollbacks": rm.rollbacks,
            "bit_identical": identical(out),
            "passed": (rm.swaps == 1 and rm.rollbacks == 0
                       and identical(out)
                       and out["stats"]["requests_lost"] == 0),
        }

        bad = jax.tree.map(np.zeros_like, host_params)
        reload_mod.publish_weights(d, 2, bad)
        rm = manager(d)
        out, _ = supervised(reload=rm)
        consumed.add(2)
        swap["rollback"] = {
            "swaps": rm.swaps, "rollbacks": rm.rollbacks,
            "restarts": out["stats"]["restarts"],
            "requests_lost": out["stats"]["requests_lost"],
            "bit_identical": identical(out),
            "passed": (rm.swaps == 0 and rm.rollbacks == 1
                       and out["stats"]["restarts"] == 1
                       and identical(out)
                       and out["stats"]["requests_lost"] == 0),
        }
        recover.extend(f["recovery_s"] for f in out["stats"]["faults"])

        reload_mod.publish_weights(d, 3, host_params)
        ChaosPlan.bitflip_file(reload_mod._weights_path(d, 3), seed=seed)
        # a torn publish (payload, no manifest) must stay invisible
        np.savez(os.path.join(d, "weights-00000004.npz"),
                 leaf_00000=np.zeros(1))
        rm = manager(d)
        out, _ = supervised(reload=rm)
        consumed.add(3)
        qdir = os.path.join(d, "quarantine")
        quarantined = sorted(os.listdir(qdir)) if os.path.isdir(qdir) \
            else []
        swap["reject"] = {
            "rejected": rm.rejected, "swaps": rm.swaps,
            "bit_identical": identical(out),
            "torn_publish_invisible":
                reload_mod.latest_published(d) == 1,
            "quarantined": quarantined,
            "passed": (rm.rejected == 1 and rm.swaps == 0
                       and identical(out)
                       and reload_mod.latest_published(d) == 1
                       and any(n.startswith("weights-00000003")
                               for n in quarantined)),
        }
        final_stats = out["stats"]["engine"]

    lost_total += sum(0 for _ in ())  # swap scenarios asserted above
    all_ok = all_ok and all(s["passed"] for s in swap.values())
    record["swap"] = swap
    record["detection_ticks_max"] = max(detect) if detect else None
    record["recovery_seconds_max"] = (round(max(recover), 3)
                                      if recover else None)
    record["requests_lost_total"] = lost_total
    record["decode_compiles"] = final_stats["decode_compiles"]
    record["chunk_compiles"] = final_stats["chunk_compiles"]
    record["drill_passed"] = bool(
        all_ok and lost_total == 0
        and final_stats["decode_compiles"] == 1
        and record["slo_attainment_clean"]
        >= record["slo_attainment_faulted"])
    return record


def run_fleet_resilience_drill(seed: int = 0) -> dict:
    """Exercise the FLEET tier end to end; return the
    ``fleet_resilience`` record.

    THREE small :class:`..serve.engine.PagedEngine` replicas survive the
    whole gauntlet — every scenario reuses them (a crashed replica is
    warm-reset by the router), so ``decode_compiles`` staying at 1 per
    surviving replica is itself evidence that quarantine, failover and
    replay all reuse the compiled programs.  Sections:

    1. **clean** — the no-fault fleet reference outputs every fault
       scenario must reproduce bit-identically, plus the per-priority
       SLO report.
    2. **replica_crash** — kill replica 1 mid-round under the
       shared-prefix Poisson trace: the router quarantines it, replays
       its in-flight requests from the fleet ledger onto the survivors;
       ``requests_lost == 0`` and greedy outputs bit-identical.
    3. **replica_straggler** — slow ticks on replica 2 push it to
       DEGRADED (deprioritised for placement) without losing or
       corrupting anything.
    4. **router_flake** — a window of placements loses the prefix-hit
       signal: placement quality degrades, correctness does not.
    5. **preemption** — a separate 2-slot engine under priority
       pressure: high-priority arrivals spill the lowest-priority
       slots' KV to host and resume them later; preempted-then-resumed
       outputs are bit-identical to uncontended runs and priority 0 is
       never preempted (timeline-asserted).
    """
    from distributed_deep_learning_tpu.models.transformer import (
        random_causal_lm)
    from distributed_deep_learning_tpu.serve.engine import PagedEngine
    from distributed_deep_learning_tpu.serve.fleet import (FleetRouter,
                                                           QUARANTINED)
    from distributed_deep_learning_tpu.serve.load import (
        DEFAULT_PRIORITY_CLASSES, LoadSpec, make_load)
    from distributed_deep_learning_tpu.serve.paged import paged_max_len
    from distributed_deep_learning_tpu.serve.scheduler import Request

    model_kw = dict(vocab_size=128, num_layers=1, d_model=64, num_heads=2,
                    mlp_dim=128, max_len=96)
    model, params = random_causal_lm(seed, **model_kw)
    cap = paged_max_len(model.max_len, 8, False, 0)
    engines = [PagedEngine(model, params, max_slots=4, max_len=cap,
                           kv_block_size=8, prefill_chunk=16)
               for _ in range(3)]
    spec = LoadSpec(n_requests=14, arrival="poisson", rate=2.0,
                    prompt_short=(4, 12), prompt_long=(16, 24),
                    long_frac=0.25, shared_prefix_len=16, shared_frac=0.5,
                    new_tokens=(6, 14), slo_ttft_ms=30000.0,
                    slo_e2e_ms=30000.0,
                    priority_classes=DEFAULT_PRIORITY_CLASSES)
    trace = make_load(spec, vocab_size=model.vocab_size, seed=seed)

    def fleet(chaos=None, **kw):
        return FleetRouter(engines, chaos=chaos, **kw)

    ref = fleet().run(list(trace))
    if ref["errors"] or ref["stats"]["requests_lost"]:
        raise RuntimeError(
            f"fleet reference run incomplete: errors {ref['errors']}, "
            f"lost {ref['stats']['lost_uids']}")

    def identical(out):
        return (set(out["results"]) == set(ref["results"]) and all(
            np.array_equal(out["results"][u], ref["results"][u])
            for u in ref["results"]))

    record: dict = {
        "metric": ("fleet self-healing: detection ticks / recovery "
                   "seconds / requests lost / SLO by priority under "
                   "replica faults"),
        "model": model_kw, "replicas": 3, "requests": len(trace),
        "scenarios": {},
    }
    detect, recover = [], []
    lost_total = 0
    all_ok = True

    # --- 2. replica crash: quarantine + zero-loss bit-identical replay ----
    plan = ChaosPlan([ChaosEvent(step=3, kind="replica_crash", target=1)],
                     seed=seed)
    out = fleet(chaos=plan).run(list(trace))
    st = out["stats"]
    fired_tick = plan.fired[0][0] if plan.fired else None
    fault = st["faults"][0] if st["faults"] else None
    det = (fault["tick"] - fired_tick
           if fault and fired_tick is not None
           and fault["tick"] is not None else None)
    surviving_compiles = [v["decode_compiles"]
                          for r, v in st["per_replica"].items() if r != 1]
    ok = (identical(out) and st["requests_lost"] == 0
          and not out["errors"] and st["health"][1] == QUARANTINED
          and det is not None
          and all(c == 1 for c in surviving_compiles))
    record["scenarios"]["replica_crash"] = {
        "fired": list(plan.fired),
        "detection_ticks": det,
        "recovery_s": (round(fault["recovery_s"], 3) if fault else None),
        "health": dict(st["health"]),
        "rounds": st["rounds"],
        "requests_lost": st["requests_lost"],
        "decode_compiles_surviving": surviving_compiles,
        "bit_identical": identical(out),
        "passed": ok,
    }
    all_ok = all_ok and ok
    lost_total += st["requests_lost"]
    if det is not None:
        detect.append(det)
    if fault is not None and fault["recovery_s"] is not None:
        recover.append(fault["recovery_s"])

    # --- 3. straggler: degraded, deprioritised, still correct -------------
    plan = ChaosPlan([ChaosEvent(step=2, kind="replica_straggler",
                                 target=2, magnitude=5.0)], seed=seed)
    out = fleet(chaos=plan, slow_tick_s=1.0, degrade_after=1).run(
        list(trace))
    st = out["stats"]
    ok = (identical(out) and st["requests_lost"] == 0
          and not out["errors"] and st["health"][2] == "degraded"
          and bool(plan.fired))
    record["scenarios"]["replica_straggler"] = {
        "fired": list(plan.fired),
        "health": dict(st["health"]),
        "slow_ticks": st["per_replica"][2]["slow_ticks"],
        "requests_lost": st["requests_lost"],
        "bit_identical": identical(out),
        "passed": ok,
    }
    all_ok = all_ok and ok
    lost_total += st["requests_lost"]

    # --- 4. router flake: blind placement degrades quality, not truth -----
    plan = ChaosPlan([ChaosEvent(step=1, kind="router_flake",
                                 magnitude=6.0)], seed=seed)
    out = fleet(chaos=plan).run(list(trace))
    st = out["stats"]
    ok = (identical(out) and st["requests_lost"] == 0
          and not out["errors"]
          and st["routing"]["flake_degraded"] > 0)
    record["scenarios"]["router_flake"] = {
        "fired": list(plan.fired),
        "flake_degraded": st["routing"]["flake_degraded"],
        "requests_lost": st["requests_lost"],
        "bit_identical": identical(out),
        "passed": ok,
    }
    all_ok = all_ok and ok
    lost_total += st["requests_lost"]

    # --- 5. preemption: KV spill/resume bit-identity + priority-0 shield --
    rng = np.random.default_rng((seed, 99))

    def _preq(uid, prio, arr):
        return Request(
            uid=uid,
            prompt=rng.integers(1, model.vocab_size,
                                size=8).astype(np.int64),
            max_new_tokens=10, arrival_tick=arr, priority=prio)

    preqs = [_preq(0, 2, 0), _preq(1, 2, 0), _preq(2, 0, 2),
             _preq(3, 1, 2)]
    pref = {}
    for r in preqs:
        solo = PagedEngine(model, params, max_slots=2, max_len=48,
                           kv_block_size=8, prefill_chunk=8)
        pref[r.uid] = solo.run([Request(uid=r.uid, prompt=r.prompt,
                                        max_new_tokens=r.max_new_tokens)
                                ])["results"][r.uid]
    peng = PagedEngine(model, params, max_slots=2, max_len=48,
                       kv_block_size=8, prefill_chunk=8, preempt=True)
    pout = peng.run(list(preqs), keep_timeline=True)
    ps = pout["stats"]["preempt"]
    preempted_uids = [u for ev in pout["timeline"]
                      for u in ev["preempted"]]
    prio0 = {r.uid for r in preqs if r.priority == 0}
    pre_identical = all(
        pout["results"].get(u) is not None
        and np.array_equal(pout["results"][u], pref[u]) for u in pref)
    ok = (pre_identical and ps["preemptions"] > 0 and ps["resumes"] > 0
          and ps["still_spilled"] == 0 and not pout["errors"]
          and not (set(preempted_uids) & prio0)
          and pout["stats"]["decode_compiles"] == 1)
    record["scenarios"]["preemption"] = {
        "preemptions": ps["preemptions"],
        "resumes": ps["resumes"],
        "still_spilled": ps["still_spilled"],
        "preempted_uids": preempted_uids,
        "priority0_preempted": sorted(set(preempted_uids) & prio0),
        "bit_identical": pre_identical,
        "decode_compiles": pout["stats"]["decode_compiles"],
        "passed": ok,
    }
    all_ok = all_ok and ok

    # --- 6. migrate_drop: corrupted device KV transfer -> digest trips,
    # ledger replay recovers bit-identically ------------------------------
    import jax

    if len(jax.local_devices()) >= 2:
        from distributed_deep_learning_tpu.serve.supervisor import \
            ServeSupervisor

        plan = ChaosPlan([ChaosEvent(step=1, kind="migrate_drop")],
                         seed=seed)
        meng = PagedEngine(model, params, max_slots=2, max_len=48,
                           kv_block_size=8, prefill_chunk=8,
                           preempt=True, migrate="device")
        meng._migrate_chaos = plan.migrate_corruptor()
        sup = ServeSupervisor(meng, retries=2)
        mout = sup.run(list(preqs))
        ms = mout["stats"]
        m_identical = all(
            mout["results"].get(u) is not None
            and np.array_equal(mout["results"][u], pref[u]) for u in pref)
        fault_kinds = [f.get("kind") for f in ms["faults"]]
        ok = (m_identical and bool(plan.fired)
              and ms["requests_lost"] == 0 and not mout["errors"]
              and "MigrationError" in fault_kinds
              and meng._decode.traces == 1)
        record["scenarios"]["migrate_drop"] = {
            "fired": list(plan.fired),
            "faults": fault_kinds,
            "restarts": ms["restarts"],
            "requests_lost": ms["requests_lost"],
            "spill_path": ms["engine"]["preempt"]["spill_path"],
            "migration_moves": ms["engine"]["preempt"]["migration_moves"],
            "bit_identical": m_identical,
            "decode_compiles": meng._decode.traces,
            "passed": ok,
        }
        all_ok = all_ok and ok
        lost_total += ms["requests_lost"]
    else:
        record["scenarios"]["migrate_drop"] = {
            "skipped": "needs >= 2 local devices for the device-path "
                       "spill (run under a forced multi-device host)",
            "passed": True,
        }

    record["detection_ticks_max"] = max(detect) if detect else None
    record["recovery_seconds_max"] = (round(max(recover), 3)
                                      if recover else None)
    record["requests_lost_total"] = lost_total
    record["decode_compiles"] = max(
        v["decode_compiles"]
        for v in ref["stats"]["per_replica"].values())
    record["slo_attainment"] = ref["stats"]["slo"]["slo_attainment"]
    record["slo_by_priority"] = {
        p: s["slo_attainment"]
        for p, s in ref["stats"]["slo"].get("by_priority", {}).items()}
    record["drill_passed"] = bool(
        all_ok and lost_total == 0 and record["decode_compiles"] == 1)
    return record


def run_rebalance_drill(seed: int = 0) -> dict:
    """Exercise live fleet REBALANCING end to end; return the
    ``fleet_rebalance`` record.

    Sections (fault scenarios are compared bit-for-bit against a clean
    no-fault fleet reference on the same trace — greedy decode is
    deterministic and replica-invariant, so any divergence is a real
    corruption):

    1. **evacuation (fp32)** — a straggling replica degrades mid-round
       with ``evacuate_on="degraded"``: the router pulls it out of its
       serving loop, migrates its open slots' committed KV to peers
       (digest-verified), pins the requests there, and warm-resets the
       source.  Outputs bit-identical, ``requests_lost == 0``,
       surviving ``decode_compiles == 1``.
    2. **evacuation (int8)** — the same drain over int8+scales KV
       pools (its own int8 reference — quantized KV changes outputs vs
       fp32): the at-rest wire carries quantized KV bit-exactly.
    3. **evac_drop** — the first evacuation payload is corrupted in
       flight: the end-to-end digest trips BEFORE anything scatters,
       the destination rolls its adopted blocks back (``unadopt``),
       and the request replays cold from the ledger — zero loss,
       bit-identical.
    4. **target_crash_mid_evac** — the evacuation TARGET dies
       mid-move: quarantine + abort, source keeps its blocks, ledger
       replay recovers — zero loss, bit-identical.
    5. **autoscaler drain** — grow the fleet by one (fresh engine from
       the factory, prefix-warmed), then shrink it back through the
       drain protocol (stop placement → evacuate → retire); the
       resized fleet then serves the whole trace bit-identically with
       ``decode_compiles == 1`` on every live replica.
    6. **scale_thrash** — an oscillating hot/cold signal hammers the
       autoscaler for a window of control ticks: patience/cool
       hysteresis must damp it (bounded scale events), with zero loss
       on the concurrent run.
    7. **pool elasticity** (>= 3 local devices) — a disaggregated
       engine moves one worker between the prefill and decode pools
       (``DisaggEngine.reassign``) and still serves the trace
       bit-identically to the unified engine.
    """
    from distributed_deep_learning_tpu.serve.autoscaler import (
        FleetAutoscaler)
    from distributed_deep_learning_tpu.models.transformer import (
        random_causal_lm)
    from distributed_deep_learning_tpu.serve.engine import PagedEngine
    from distributed_deep_learning_tpu.serve.fleet import (DEGRADED,
                                                           FleetRouter,
                                                           RETIRED)
    from distributed_deep_learning_tpu.serve.load import (
        DEFAULT_PRIORITY_CLASSES, LoadSpec, make_load)
    from distributed_deep_learning_tpu.serve.paged import paged_max_len

    model_kw = dict(vocab_size=128, num_layers=1, d_model=64, num_heads=2,
                    mlp_dim=128, max_len=96)
    model, params = random_causal_lm(seed, **model_kw)
    cap = paged_max_len(model.max_len, 8, False, 0)

    def engine(**kw):
        return PagedEngine(model, params, max_slots=4, max_len=cap,
                           kv_block_size=8, prefill_chunk=16, **kw)

    engines = [engine() for _ in range(3)]
    spec = LoadSpec(n_requests=14, arrival="poisson", rate=2.0,
                    prompt_short=(4, 12), prompt_long=(16, 24),
                    long_frac=0.25, shared_prefix_len=16, shared_frac=0.5,
                    new_tokens=(6, 14), slo_ttft_ms=30000.0,
                    slo_e2e_ms=30000.0,
                    priority_classes=DEFAULT_PRIORITY_CLASSES)
    trace = make_load(spec, vocab_size=model.vocab_size, seed=seed)

    def fleet(chaos=None, **kw):
        return FleetRouter(engines, chaos=chaos, **kw)

    ref = fleet().run(list(trace))
    if ref["errors"] or ref["stats"]["requests_lost"]:
        raise RuntimeError(
            f"rebalance reference run incomplete: errors "
            f"{ref['errors']}, lost {ref['stats']['lost_uids']}")

    def identical(out, vs=None):
        vs = ref if vs is None else vs
        return (set(out["results"]) == set(vs["results"]) and all(
            np.array_equal(out["results"][u], vs["results"][u])
            for u in vs["results"]))

    record: dict = {
        "metric": ("live rebalancing: evacuation bit-identity / "
                   "rollback on corrupted payload / drain-protocol "
                   "scale-down / thrash-damped autoscaling"),
        "model": model_kw, "replicas": 3, "requests": len(trace),
        "scenarios": {},
    }
    all_ok = True
    lost_total = 0
    evac_seconds = []

    # the straggler plan every evacuation scenario reuses: the target
    # replica slows at tick 2, degrades immediately (degrade_after=1),
    # and the armed router answers with an EvacuationSignal mid-request
    # drain.  Scenarios past the first run over warm prefix caches, so
    # hit-driven routing may starve a specific replica — they target
    # whichever replica ticks first (target=None) instead.
    def strag_plan(extra=(), target=2):
        return ChaosPlan(
            [ChaosEvent(step=2, kind="replica_straggler", target=target,
                        magnitude=5.0), *extra], seed=seed)

    evac_kw = dict(slow_tick_s=1.0, degrade_after=1,
                   evacuate_on="degraded")

    # --- 1. evacuation bit-identity over fp32 pools -----------------------
    plan = strag_plan()
    out = fleet(chaos=plan, **evac_kw).run(list(trace))
    st = out["stats"]
    rb = st["rebalance"]
    surviving = [v["decode_compiles"]
                 for r, v in st["per_replica"].items() if r != 2]
    ok = (identical(out) and st["requests_lost"] == 0
          and not out["errors"] and bool(plan.fired)
          and st["health"][2] == DEGRADED
          and rb["evacuations"] >= 1 and rb["evacuated_tokens"] > 0
          and rb["rolled_back"] == 0
          and all(c == 1 for c in surviving))
    record["scenarios"]["evacuation_fp32"] = {
        "fired": list(plan.fired),
        "health": dict(st["health"]),
        "evacuations": rb["evacuations"],
        "evacuated_slots": rb["evacuated_slots"],
        "evacuated_blocks": rb["evacuated_blocks"],
        "evacuated_tokens": rb["evacuated_tokens"],
        "evac_seconds": round(rb["evac_seconds"], 4),
        "requests_lost": st["requests_lost"],
        "decode_compiles_surviving": surviving,
        "bit_identical": identical(out),
        "passed": ok,
    }
    all_ok = all_ok and ok
    lost_total += st["requests_lost"]
    if rb["evacuations"]:
        evac_seconds.append(rb["evac_seconds"] / rb["evacuations"])

    # --- 2. evacuation bit-identity over int8 KV pools --------------------
    # int8 KV changes the numerics, so this scenario carries its OWN
    # quantized reference; what must hold is drained == uncontended
    # over the same int8 pools.
    engines8 = [engine(kv_dtype="int8") for _ in range(3)]
    ref8 = FleetRouter(engines8).run(list(trace))
    if ref8["errors"] or ref8["stats"]["requests_lost"]:
        raise RuntimeError("int8 reference run incomplete")
    plan = strag_plan()
    out = FleetRouter(engines8, chaos=plan, **evac_kw).run(list(trace))
    st = out["stats"]
    rb = st["rebalance"]
    ok = (identical(out, ref8) and st["requests_lost"] == 0
          and not out["errors"] and bool(plan.fired)
          and rb["evacuations"] >= 1 and rb["evacuated_tokens"] > 0
          and rb["rolled_back"] == 0)
    record["scenarios"]["evacuation_int8"] = {
        "fired": list(plan.fired),
        "evacuations": rb["evacuations"],
        "evacuated_tokens": rb["evacuated_tokens"],
        "evac_seconds": round(rb["evac_seconds"], 4),
        "requests_lost": st["requests_lost"],
        "bit_identical": identical(out, ref8),
        "passed": ok,
    }
    all_ok = all_ok and ok
    lost_total += st["requests_lost"]
    if rb["evacuations"]:
        evac_seconds.append(rb["evac_seconds"] / rb["evacuations"])

    # --- 3. evac_drop: corrupted payload -> digest trips, rollback --------
    plan = strag_plan([ChaosEvent(step=1, kind="evac_drop")],
                      target=None)
    out = fleet(chaos=plan, **evac_kw).run(list(trace))
    st = out["stats"]
    rb = st["rebalance"]
    drop_fired = any(k == "evac_drop" for _, k in plan.fired)
    ok = (identical(out) and st["requests_lost"] == 0
          and not out["errors"] and drop_fired
          and rb["rolled_back"] >= 1)
    record["scenarios"]["evac_drop"] = {
        "fired": list(plan.fired),
        "evacuations": rb["evacuations"],
        "rolled_back": rb["rolled_back"],
        "requests_lost": st["requests_lost"],
        "bit_identical": identical(out),
        "passed": ok,
    }
    all_ok = all_ok and ok
    lost_total += st["requests_lost"]

    # --- 4. target crash mid-evacuation: abort + ledger replay ------------
    plan = strag_plan([ChaosEvent(step=1,
                                  kind="target_crash_mid_evac")],
                      target=None)
    out = fleet(chaos=plan, **evac_kw).run(list(trace))
    st = out["stats"]
    rb = st["rebalance"]
    crash_fired = any(k == "target_crash_mid_evac"
                      for _, k in plan.fired)
    ok = (identical(out) and st["requests_lost"] == 0
          and not out["errors"] and crash_fired
          and rb["aborted"] >= 1)
    record["scenarios"]["target_crash_mid_evac"] = {
        "fired": list(plan.fired),
        "evacuations": rb["evacuations"],
        "aborted": rb["aborted"],
        "health": dict(st["health"]),
        "requests_lost": st["requests_lost"],
        "bit_identical": identical(out),
        "passed": ok,
    }
    all_ok = all_ok and ok
    lost_total += st["requests_lost"]

    # --- 5. autoscaler: grow (warm) then drain-protocol shrink ------------
    # min_replicas=3 clamps any further shrink the run's own idle
    # round-ends would otherwise trigger — exactly 2 scale events.
    auto = FleetAutoscaler(min_replicas=3, max_replicas=4,
                           patience=2, cool=2)
    rt = fleet(autoscaler=auto, engine_factory=engine)
    for _ in range(2):
        rt._autoscale_round(override="hot")     # patience -> grow
    grew_to = sum(1 for r in rt.replicas if r.health != RETIRED)
    t0 = time.perf_counter()
    for _ in range(2):
        rt._autoscale_round(override="cold")    # cool -> drain shrink
    drain_s = time.perf_counter() - t0
    shrunk_to = sum(1 for r in rt.replicas if r.health != RETIRED)
    out = rt.run(list(trace))
    st = out["stats"]
    live_compiles = [v["decode_compiles"]
                     for r, v in st["per_replica"].items()
                     if st["health"][r] != RETIRED]
    ok = (identical(out) and st["requests_lost"] == 0
          and not out["errors"] and grew_to == 4 and shrunk_to == 3
          and st["autoscaler"]["scale_events"] == 2
          and st["autoscaler"]["replicas_retired"] == 1
          and all(c == 1 for c in live_compiles))
    record["scenarios"]["autoscaler_drain"] = {
        "grew_to": grew_to,
        "shrunk_to": shrunk_to,
        "scale_events": st["autoscaler"]["scale_events"],
        "replicas_retired": st["autoscaler"]["replicas_retired"],
        "drain_seconds": round(drain_s, 4),
        "requests_lost": st["requests_lost"],
        "decode_compiles_live": live_compiles,
        "bit_identical": identical(out),
        "passed": ok,
    }
    all_ok = all_ok and ok
    lost_total += st["requests_lost"]

    # --- 6. scale_thrash: oscillating load, hysteresis bounds churn -------
    # window wide enough (16 ticks) to cover the run's round-ends AND
    # the pure control ticks driven after it — every tick sees the
    # alternating hot/cold signal, which never accumulates patience.
    plan = ChaosPlan([ChaosEvent(step=1, kind="scale_thrash",
                                 magnitude=16.0)], seed=seed)
    auto = FleetAutoscaler(min_replicas=2, max_replicas=4,
                           patience=2, cool=2)
    rt = fleet(chaos=plan, autoscaler=auto, engine_factory=engine)
    out = rt.run(list(trace))
    for _ in range(8):
        rt._autoscale_round()       # keep the control loop in the window
    st = out["stats"]
    thrash_fired = any(k == "scale_thrash" for _, k in plan.fired)
    scale_events = len(auto.events)
    ok = (identical(out) and st["requests_lost"] == 0
          and not out["errors"] and thrash_fired
          and scale_events <= 1)
    record["scenarios"]["scale_thrash"] = {
        "fired": list(plan.fired),
        "control_ticks": rt._scale_ticks,
        "scale_events": scale_events,
        "requests_lost": st["requests_lost"],
        "bit_identical": identical(out),
        "passed": ok,
    }
    all_ok = all_ok and ok
    lost_total += st["requests_lost"]

    # --- 7. disagg pool elasticity: reassign a device between roles -------
    import jax

    if len(jax.local_devices()) >= 3:
        from distributed_deep_learning_tpu.serve.autoscaler import (
            PoolRebalancer)
        from distributed_deep_learning_tpu.serve.disagg import DisaggEngine

        uni = engine()
        uref = uni.run(list(trace))
        deng = DisaggEngine(model, params, prefill_workers=1,
                            decode_workers=2, prefill_streams=4,
                            max_slots=4, max_len=cap, kv_block_size=8,
                            prefill_chunk=16)
        d1 = deng.run(list(trace))
        bal = PoolRebalancer(hi=0.9, lo=0.25, patience=2)
        direction = None
        for _ in range(2):      # sustained skew, not a single sample
            direction = bal.observe(d1["stats"]["prefill_util"])
        moved = deng.reassign(direction) if direction else False
        deng.reset()
        d2 = deng.run(list(trace))
        agree = all(
            d2["results"].get(u) is not None
            and np.array_equal(d2["results"][u], uref["results"][u])
            for u in uref["results"])
        ok = (agree and not d2["errors"]
              and d2["stats"]["decode_compiles"] == 1)
        record["scenarios"]["pool_elasticity"] = {
            "prefill_util": round(d1["stats"]["prefill_util"], 4),
            "direction": direction,
            "reassigned": bool(moved),
            "pool_reassignments": d2["stats"]["pool_reassignments"],
            "prefill_workers": d2["stats"]["prefill_workers"],
            "decode_workers": d2["stats"]["decode_workers"],
            "bit_identical": agree,
            "decode_compiles": d2["stats"]["decode_compiles"],
            "passed": ok,
        }
        all_ok = all_ok and ok
    else:
        record["scenarios"]["pool_elasticity"] = {
            "skipped": "needs >= 3 local devices for a reassignable "
                       "worker (run under a forced multi-device host)",
            "passed": True,
        }

    record["requests_lost_total"] = lost_total
    record["evac_ms_mean"] = (round(1e3 * sum(evac_seconds)
                                    / len(evac_seconds), 3)
                              if evac_seconds else None)
    record["scale_events_total"] = sum(
        s.get("scale_events", 0) for s in record["scenarios"].values()
        if isinstance(s, dict))
    record["drill_passed"] = bool(all_ok and lost_total == 0)
    return record
