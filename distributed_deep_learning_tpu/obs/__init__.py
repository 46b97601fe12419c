"""Unified run telemetry: goodput/MFU accounting, stall attribution,
and latency histograms.

One :class:`RunTelemetry` object per run threads through the train loop,
elastic recovery, and the workload runner; the serve engine builds its
own :class:`~.metrics.MetricsRegistry` per ``run()`` (serving latency is
meaningful even without a run-level stream).  Everything is pure host
Python — nothing here touches jax until/unless ``measure_flops`` is
asked to lower a step.

Layout:

* :mod:`obs.metrics`  — counters / gauges / log-bucketed histograms.
* :mod:`obs.timeline` — per-step spans → goodput breakdown.
* :mod:`obs.mfu`      — model-FLOP accounting + chip peak table.
* :mod:`obs.export`   — JSONL event stream + Prometheus exposition.
* :mod:`obs.trace`    — spans: the ``span()`` front door (profiler +
  Tracer), the Tracer ring and its Chrome export, ``PhaseClock``.
* :mod:`obs.runlog`   — ``last_run(kind)`` / ``runs(kind)``: the record
  a run publishes as it starts (it outlives a run that raises; the newest
  four a kind are kept), and the compile log.
"""

from __future__ import annotations

import time
from typing import Any, Callable

from .export import EventWriter
from .memory import MemoryTracker
from .metrics import MetricsRegistry
from .mfu import chip_peak_flops, measure_step_flops, mfu_record
from .recorder import FlightRecorder
from .runlog import compile_log, last_run, runs
from .timeline import Timeline
from .trace import Tracer, span

__all__ = ["RunTelemetry", "MetricsRegistry", "Timeline", "EventWriter",
           "Tracer", "FlightRecorder", "MemoryTracker", "chip_peak_flops",
           "span", "last_run", "runs", "compile_log"]


class RunTelemetry:
    """The per-run telemetry hub every layer reports into.

    ``path=None`` keeps the full accounting in memory without a sidecar
    (tests, the overhead harness); instruments stay live either way.

    Generation 2 (ISSUE 11): ``trace_path`` turns on the per-request /
    per-step span :class:`~.trace.Tracer` (exported as a Chrome/Perfetto
    trace on :meth:`close`); ``recorder`` attaches a
    :class:`~.recorder.FlightRecorder` the train loop and serve engines
    feed (sentinel anomalies, SLO breaches) so a dying run leaves a
    black box.  ``rotate_mb`` size-caps the JSONL sidecar (see
    :class:`~.export.EventWriter`).
    """

    def __init__(self, path: str | None = None,
                 clock=time.perf_counter, *,
                 trace_path: str | None = None,
                 tracer: "Tracer | None" = None,
                 recorder: "FlightRecorder | None" = None,
                 rotate_mb: float | None = None,
                 fsync_on_rollover: bool = False) -> None:
        self.registry = MetricsRegistry()
        self.tracer = tracer if tracer is not None else (
            Tracer(clock=clock) if trace_path else None)
        self.trace_path = trace_path
        self.recorder = recorder
        self.timeline = Timeline(clock=clock, tracer=self.tracer)
        self.writer = EventWriter(
            path, clock=clock,
            max_bytes=int(rotate_mb * 1e6) if rotate_mb else None,
            fsync_on_rollover=fsync_on_rollover)
        self.clock = clock
        # live memory gauges; resolves its device lazily on first sample,
        # so constructing it here keeps the "no jax until asked" contract
        self.memory = MemoryTracker(self.registry)
        # model-FLOP state (filled by measure_flops / note_train)
        self.step_flops: float | None = None
        self.n_devices: int | None = None
        self.train_steps = 0.0
        self.train_seconds = 0.0
        self.train_examples = 0.0
        self._dispatched_fns: set[int] = set()
        self._closed = False

    # -- compile attribution ------------------------------------------
    def dispatch_kind(self, fn: Any) -> str:
        """First dispatch of a given jitted fn is trace+XLA-build time:
        attribute it to "compile"; every later one is "dispatch"."""
        key = id(fn)
        if key in self._dispatched_fns:
            return "dispatch"
        self._dispatched_fns.add(key)
        return "compile"

    # -- model-FLOP accounting ----------------------------------------
    def measure_flops(self, step_fn: Callable, *args,
                      n_devices: int | None = None, **kwargs) -> None:
        """Record the global per-step FLOPs of the run's train step
        (costs one extra compile, charged to the compile span).
        ``n_devices`` is the device count the step's mesh spans (MFU
        denominator too); default: every visible device.  Failure
        degrades to step_flops=None rather than killing the run."""
        self.n_devices = n_devices
        with self.timeline.span("compile"):
            try:
                self.step_flops = measure_step_flops(
                    step_fn, *args, n_devices=n_devices, **kwargs)
            except Exception:
                self.step_flops = None

    def note_train(self, steps: float, seconds: float,
                   examples: float = 0.0) -> None:
        """Accumulate productive-phase totals for the run MFU number."""
        self.train_steps += steps
        self.train_seconds += seconds
        self.train_examples += examples

    def mfu(self) -> dict:
        import jax

        devs = jax.devices()
        return mfu_record(self.step_flops, self.train_steps,
                          self.train_seconds,
                          self.n_devices or len(devs),
                          devs[0].device_kind)

    # -- rollups -------------------------------------------------------
    def phase_rollup(self, scope: str, since: dict | None = None) -> dict:
        """Emit (and return) a goodput breakdown for a phase delta."""
        gp = self.timeline.goodput(since=since)
        self.writer.emit("obs_goodput", scope=scope, **gp)
        return gp

    def close(self) -> dict:
        """Run-level rollup: whole-timeline goodput, MFU, and the full
        metrics snapshot, then close the sidecar.  Idempotent; returns
        the summary dict (also what obs_report renders)."""
        if self._closed:
            return {}
        self._closed = True
        gp = self.timeline.goodput()
        rec = self.mfu()
        snap = self.registry.snapshot()
        self.writer.emit("obs_goodput", scope="run", **gp)
        self.writer.emit("obs_mfu", **rec)
        self.writer.emit("obs_snapshot", snapshot=snap)
        summary = {"goodput": gp, "mfu": rec, "snapshot": snap}
        # what the run's programs said of themselves as they were traced
        # (batch_pins and flash_layout of a train step, attn_paths of a
        # decode program)
        notes = [{"program": fun, "note": event, "text": text}
                 for event, fun, text in compile_log.notes()]
        if notes:
            self.writer.emit("obs_programs", notes=notes)
            summary["programs"] = notes
        if self.memory.samples or self.memory.steps:
            mem = self.memory.summary()
            self.writer.emit("obs_memory", **mem)
            summary["memory"] = mem
        if self.tracer is not None and self.trace_path:
            n = self.tracer.export(self.trace_path)
            self.writer.emit("obs_trace", path=self.trace_path, spans=n,
                             dropped=self.tracer.dropped)
            summary["trace"] = {"path": self.trace_path, "spans": n}
        self.writer.close()
        return summary
