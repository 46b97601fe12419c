"""Per-request distributed tracing: causally-linked spans, Chrome export.

Observability generation 2 (ISSUE 11).  The gen-1 ``obs/`` layer answers
"how did the run do on average"; this module answers "why was THIS
request slow".  Every unit of work the serving engine performs for a
request — admission, prefix match, copy-on-write, each prefill chunk,
each decode tick, retirement — becomes a :class:`Span` carrying the
request's trace id and a parent link to the span that caused it, so the
whole life of a request reads as a tree.  Train-side spans
(data-wait / dispatch / compile / checkpoint, via
:class:`..obs.timeline.Timeline`) land in the same tracer under the
``train`` trace id.

Export is the Chrome trace-event JSON format (``ph: "X"`` complete
events with microsecond ``ts``/``dur``), which both ``chrome://tracing``
and https://ui.perfetto.dev load directly — a ``--obs --obs-trace`` run
produces a file you drop into a real trace viewer.  Causality that the
viewer's (pid, tid) nesting cannot express (a request's decode span is
*caused by* its admit, but *timed inside* the engine's batched tick) is
preserved in every event's ``args``: ``trace_id`` / ``span_id`` /
``parent_id`` round-trip losslessly through :func:`read_chrome_trace`.

Hot-path contract (same bar as :mod:`..obs.metrics`): :meth:`Tracer.add`
is one list append of a tuple-backed :class:`Span` plus one integer
increment — no string formatting, no dict merging unless the caller
passes attrs.  The span ring is bounded (``capacity``); old spans fall
off rather than growing a multi-hour run without bound, and ``dropped``
reports how many did.

The front door for a region of host code is :func:`span`: one call site
feeds JAX's profiler (a ``ddl:<name>`` ``TraceAnnotation`` on the host
plane of the same xplane as the device ops, so on the profiler's clock)
while a profiler session is open, and the run's installed :class:`Tracer`
(:func:`use_tracer`) while there is one; with neither it hands back one
shared null context.  :class:`PhaseClock` adds the always-on half: summed
seconds and a count per phase and a bounded ring of per-tick records,
which the engine and the loader publish through :mod:`..obs.runlog`.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Iterable, Optional

__all__ = ["Span", "Tracer", "chrome_trace_events", "write_chrome_trace",
           "read_chrome_trace", "request_trace_id", "span", "use_tracer",
           "installed_tracer", "PhaseClock", "SPAN_PREFIX"]


def request_trace_id(uid: int) -> str:
    """The canonical trace id for serving request ``uid`` — shared by
    every layer (scheduler, block manager, engine) that reports spans
    about it."""
    return f"req-{uid}"


class Span:
    """One traced unit of work: ``[t0, t1]`` seconds on the tracer's
    clock, a ``trace_id`` naming the causal chain it belongs to, and a
    ``parent_id`` linking to the span that caused it (None = root)."""

    __slots__ = ("name", "t0", "t1", "trace_id", "span_id", "parent_id",
                 "track", "attrs")

    def __init__(self, name: str, t0: float, t1: float, trace_id: str,
                 span_id: int, parent_id: Optional[int],
                 track: str, attrs: Optional[dict]) -> None:
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.track = track
        self.attrs = attrs

    def to_dict(self) -> dict:
        d = {"name": self.name, "t0": self.t0, "t1": self.t1,
             "trace_id": self.trace_id, "span_id": self.span_id,
             "parent_id": self.parent_id, "track": self.track}
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class Tracer:
    """Bounded span collector with an injectable clock.

    ``capacity`` bounds memory (a span is ~200 bytes; the default ring
    holds the last 64k spans ≈ a few minutes of busy serving).
    ``on_span`` — optional callback fired with every COMPLETED span
    (the flight-recorder wiring point); it must be cheap.
    """

    def __init__(self, clock=time.perf_counter, capacity: int = 65536,
                 on_span=None) -> None:
        self.clock = clock
        self.spans: deque[Span] = deque(maxlen=capacity)
        self.capacity = capacity
        self.emitted = 0                 # total ever completed
        self.on_span = on_span
        # next(count) is one bytecode-atomic call: the loader's prefetch
        # thread and the main loop may both record into one Tracer
        self._ids = itertools.count(1)
        self._open: dict[int, Span] = {}

    @property
    def dropped(self) -> int:
        """Completed spans that have fallen off the ring."""
        return self.emitted - len(self.spans)

    # -- hot path ------------------------------------------------------
    def add(self, name: str, t0: float, t1: float, trace_id: str,
            parent: Optional[int] = None, track: str = "main",
            **attrs: Any) -> int:
        """Record a completed span; returns its span id (usable as a
        later span's ``parent``)."""
        sid = next(self._ids)
        sp = Span(name, t0, t1, trace_id, sid, parent, track,
                  attrs or None)
        self.spans.append(sp)
        self.emitted += 1
        if self.on_span is not None:
            self.on_span(sp)
        return sid

    # -- open/close (long-lived spans, e.g. a whole request) -----------
    def begin(self, name: str, trace_id: str, parent: Optional[int] = None,
              track: str = "main", t0: Optional[float] = None,
              **attrs: Any) -> int:
        """Open a span whose end is not yet known (a request's root span
        opens at arrival and closes at retire)."""
        sid = next(self._ids)
        self._open[sid] = Span(name, t0 if t0 is not None else self.clock(),
                               -1.0, trace_id, sid, parent, track,
                               attrs or None)
        return sid

    def end(self, span_id: int, t1: Optional[float] = None,
            **attrs: Any) -> Optional[Span]:
        """Close an open span (no-op on an unknown id — a retire racing
        a ring overflow must not raise)."""
        sp = self._open.pop(span_id, None)
        if sp is None:
            return None
        sp.t1 = t1 if t1 is not None else self.clock()
        if attrs:
            sp.attrs = {**(sp.attrs or {}), **attrs}
        self.spans.append(sp)
        self.emitted += 1
        if self.on_span is not None:
            self.on_span(sp)
        return sp

    @contextmanager
    def span(self, name: str, trace_id: str, parent: Optional[int] = None,
             track: str = "main", **attrs: Any):
        """Cold-path convenience; hot loops should call :meth:`add` with
        their own clock arithmetic (same contract as Timeline.span)."""
        t0 = self.clock()
        try:
            yield
        finally:
            self.add(name, t0, self.clock(), trace_id, parent=parent,
                     track=track, **attrs)

    def drain_open(self) -> None:
        """Close every still-open span at the current clock (end-of-run
        flush so an aborted request still shows in the trace)."""
        now = self.clock()
        for sid in list(self._open):
            self.end(sid, t1=now, truncated=True)

    # -- export --------------------------------------------------------
    def export(self, path: str) -> int:
        """Atomically write the ring as a Chrome/Perfetto trace JSON;
        returns the number of spans written."""
        self.drain_open()
        spans = list(self.spans)
        write_chrome_trace(path, spans)
        return len(spans)


def chrome_trace_events(spans: Iterable[Span],
                        process_name: str = "ddl") -> list[dict]:
    """Spans → Chrome trace-event dicts.

    Each track becomes a tid with a ``thread_name`` metadata event;
    every event is a ``ph: "X"`` complete event with microsecond
    ``ts``/``dur`` and the causal links in ``args``.  Zero-duration
    spans get a 1 µs floor so viewers render them.
    """
    events: list[dict] = [{
        "ph": "M", "pid": 0, "tid": 0, "name": "process_name",
        "args": {"name": process_name},
    }]
    tids: dict[str, int] = {}
    for sp in spans:
        tid = tids.get(sp.track)
        if tid is None:
            tid = tids[sp.track] = len(tids) + 1
            events.append({"ph": "M", "pid": 0, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": sp.track}})
        args = {"trace_id": sp.trace_id, "span_id": sp.span_id,
                "parent_id": sp.parent_id}
        if sp.attrs:
            args.update(sp.attrs)
        events.append({
            "ph": "X", "pid": 0, "tid": tid, "name": sp.name,
            "ts": sp.t0 * 1e6,
            "dur": max((sp.t1 - sp.t0) * 1e6, 1.0),
            "cat": sp.trace_id,
            "args": args,
        })
    return events


def write_chrome_trace(path: str, spans: Iterable[Span],
                       process_name: str = "ddl") -> None:
    """Atomic write (the checkpoint-sidecar tmp+rename pattern — a
    killed run leaves the previous complete trace, never a torn one)."""
    doc = {"traceEvents": chrome_trace_events(spans, process_name),
           "displayTimeUnit": "ms"}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)  # atomic on POSIX


def read_chrome_trace(path: str) -> list[dict]:
    """Load a trace file back as the list of ``ph: "X"`` span events
    (metadata events filtered out) — what the causality tests and
    ``obs_report --trace`` consume."""
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    return [e for e in events if e.get("ph") == "X"]


# ------------------------------------------------------------ front door

#: every span this package writes into the profiler's trace starts with
#: it.  Never ``bench:``: the benchmark labels each program run by the
#: latest ``bench:`` annotation begun before it.
SPAN_PREFIX = "ddl:"

_NULL = nullcontext()
_TRACER: Optional[Tracer] = None      # the run's store, see use_tracer
_OPEN = threading.local()             # per thread: open (id, trace, track)
_ANNOTATION = None                    # jax.profiler.TraceAnnotation, lazily


def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use so that
    importing :mod:`..obs` stays free of JAX."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def installed_tracer() -> Optional[Tracer]:
    return _TRACER


@contextmanager
def use_tracer(tracer: Optional[Tracer]):
    """Make `tracer` the store :func:`span` records into for the enclosed
    run.  ``None`` leaves whatever an outer caller installed."""
    global _TRACER
    if tracer is None:
        yield
        return
    prev, _TRACER = _TRACER, tracer
    try:
        yield
    finally:
        _TRACER = prev


def _active() -> bool:
    return _TRACER is not None or (_ANNOTATION or _annotation()).is_enabled()


def span(name: str, *, trace_id: Optional[str] = None,
         track: Optional[str] = None, parent: Optional[int] = None,
         **attrs: Any):
    """Context manager around one named region of host code.

    While a profiler session is open the region is a
    ``TraceAnnotation("ddl:" + name, **attrs)``: it lands on the host
    plane of the xplane that holds the device ops, on the profiler's
    clock.  While a :class:`Tracer` is installed (:func:`use_tracer`) the
    same call records into its ring; `parent`, `trace_id` and `track`
    default to the enclosing span's (of this thread), so nesting in the
    code is nesting in the export.  With neither, the one shared null
    context comes back and nothing is recorded.
    """
    ann = _ANNOTATION or _annotation()
    profiling = ann.is_enabled()
    if _TRACER is None and not profiling:
        return _NULL
    return _Span(name, attrs, ann if profiling else None, _TRACER,
                 trace_id, track, parent)


class _Span:
    __slots__ = ("_name", "_attrs", "_ann", "_tracer", "_trace_id",
                 "_track", "_parent", "_sid")

    def __init__(self, name, attrs, ann, tracer, trace_id, track, parent):
        self._name, self._attrs = name, attrs
        self._ann, self._tracer = ann, tracer
        self._trace_id, self._track, self._parent = trace_id, track, parent

    def __enter__(self):
        if self._ann is not None:
            self._ann = self._ann(SPAN_PREFIX + self._name, **self._attrs)
            self._ann.__enter__()
        if self._tracer is not None:
            stack = _OPEN.__dict__.setdefault("stack", [])
            _, up_trace, up_track = stack[-1] if stack else (None, "run",
                                                             "main")
            trace_id = self._trace_id or up_trace
            track = self._track or up_track
            parent = self._parent
            if parent is None and stack:
                parent = stack[-1][0]
            self._sid = self._tracer.begin(self._name, trace_id,
                                           parent=parent, track=track,
                                           **self._attrs)
            stack.append((self._sid, trace_id, track))
        return self

    def __exit__(self, *exc):
        if self._tracer is not None:
            _OPEN.stack.pop()
            self._tracer.end(self._sid)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


# --------------------------------------------------- always-on phase sums

class PhaseClock:
    """What one run's loop spent in each of its named phases, always on.

    The loop wraps each iteration in :meth:`tick` and each region in
    :meth:`phase`; both cost two ``perf_counter`` reads and a few list
    writes, and both also go through :func:`span` while something listens
    (checked once a tick).  Kept per run: summed seconds and a count per
    phase, and a bounded ring of per-tick records ``(index, kind, meta,
    wall seconds, seconds per phase in `names` order)``.  A tick left by
    an exception is recorded with kind ``"aborted"``.

    Beside the ring, record for record: `started`, the clock reading each
    tick began at, so ``started[k + 1] - started[k] - wall(k)`` is the
    time between two ticks, which no phase owns.  `listened` counts the
    ticks that ran with something listening: a run whose `listened` is
    positive is one a profiler session or a Tracer saw.  A phase keeps its
    last two clock readings as ``t0`` (entered) and ``t1`` (left), for a
    caller that records instants and must not read the clock again.
    """

    def __init__(self, names, spanless=(), ring: int = 4096,
                 clock=time.perf_counter) -> None:
        """`spanless` phases are clocked only: their span is opened by
        the callee (a program object naming its own dispatch)."""
        self.names = tuple(names)
        self.clock = clock
        self.seconds = [0.0] * len(self.names)
        self.counts = [0] * len(self.names)
        self.ticks: deque = deque(maxlen=ring)
        self.started: deque = deque(maxlen=ring)
        self.n_ticks = 0
        self.listened = 0
        self._row = [0.0] * len(self.names)
        self._listening = False
        self._phases = {n: _Phase(self, i, n, n not in spanless)
                        for i, n in enumerate(self.names)}
        self._tick = _Tick(self)

    def phase(self, name: str) -> "_Phase":
        return self._phases[name]

    def tick(self, index: int, span_name: str = "tick", **attrs) -> "_Tick":
        """The context manager of one iteration (one object, reused);
        set its ``kind`` and ``meta`` before it closes."""
        tk = self._tick
        tk.index, tk.kind, tk.meta = index, "idle", ()
        tk._span_name, tk._attrs = span_name, attrs
        return tk

    def summary(self) -> dict:
        """``{phase: {"seconds", "count"}}`` of the phases that ran."""
        return {n: {"seconds": s, "count": c}
                for n, s, c in zip(self.names, self.seconds, self.counts)
                if c}


class _Phase:
    __slots__ = ("_pc", "_i", "name", "_spanned", "t0", "t1", "_span")

    def __init__(self, pc: PhaseClock, i: int, name: str,
                 spanned: bool) -> None:
        self._pc, self._i, self.name, self._spanned = pc, i, name, spanned
        self._span = None

    def __enter__(self):
        if self._spanned and self._pc._listening:
            self._span = span(self.name)
            self._span.__enter__()
        self.t0 = self._pc.clock()
        return self

    def __exit__(self, *exc):
        pc, i = self._pc, self._i
        self.t1 = pc.clock()
        dt = self.t1 - self.t0
        pc.seconds[i] += dt
        pc.counts[i] += 1
        pc._row[i] += dt
        if self._span is not None:
            self._span.__exit__(*exc)
            self._span = None
        return False


class _Tick:
    __slots__ = ("_pc", "index", "kind", "meta", "_span_name", "_attrs",
                 "_span", "_t0")

    def __init__(self, pc: PhaseClock) -> None:
        self._pc = pc
        self._span = None

    def __enter__(self):
        pc = self._pc
        pc._listening = _active()
        if pc._listening:
            pc.listened += 1
            self._span = span(self._span_name, **self._attrs)
            self._span.__enter__()
        self._t0 = pc.clock()
        return self

    def __exit__(self, et, ev, tb):
        pc = self._pc
        wall = pc.clock() - self._t0
        pc.ticks.append((self.index, "aborted" if et is not None
                         else self.kind, self.meta, wall, tuple(pc._row)))
        pc.started.append(self._t0)
        pc.n_ticks += 1
        pc._row = [0.0] * len(pc.names)
        if self._span is not None:
            self._span.__exit__(et, ev, tb)
            self._span = None
        return False
