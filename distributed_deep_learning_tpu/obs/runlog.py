"""What the last run of each kind left behind, and the compile log:
the two process-wide records a post-mortem can read with no help from
the caller.

A serving run that ends by an exception (a crashing supervisor hook, the
benchmark's window closing) returns nothing, so everything it counted
would be lost with it.  :class:`RunRecord` is therefore *published* at
the top of the run (:func:`publish`) and filled in place as the run goes:
``last_run("serve")`` is the paged engine's last ``run()``,
``last_run("loader")`` the last :class:`~..data.loader.DeviceLoader` that
was iterated.  The newest :data:`KEPT` records of a kind stay
(:func:`runs`, oldest first), so a run that something listened to (a
profiler's traced window: ``record.phases.listened > 0``) is still there
after the unlistened run that followed it.

The compile log answers "which program was traced, lowered, compiled or
fetched from the cache, when, and for how long" by program name, from
``jax.monitoring``'s compile-path events.  :func:`install_compile_log`
(called by :func:`~..runtime.bootstrap.enable_compile_cache`, which every
entry point calls before it builds a program) registers the listeners
once a process and marks the log each time, so a reader can take the
entries of the current start alone.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Iterable, Optional

from .metrics import MetricsRegistry
from .trace import PhaseClock

__all__ = ["RunRecord", "publish", "last_run", "runs", "CompileLog",
           "compile_log", "install_compile_log"]


class RunRecord:
    """One run's in-memory record: its phase clock (sums and the tick
    ring), its metrics registry where it has one, and a few facts about
    the run (`meta`)."""

    __slots__ = ("kind", "phases", "registry", "meta")

    def __init__(self, kind: str, phases: PhaseClock,
                 registry: Optional[MetricsRegistry] = None,
                 **meta) -> None:
        self.kind, self.phases, self.registry = kind, phases, registry
        self.meta = meta


#: records kept a kind.  A record holds host numbers only (no device
#: array): a serving run's full ring is about a megabyte.
KEPT = 4
_LAST: dict[str, RunRecord] = {}
_RUNS: dict[str, deque] = {}


def publish(record: RunRecord) -> RunRecord:
    """Make `record` what :func:`last_run` returns for its kind, and the
    newest of :func:`runs`; one published again (a loader publishes its
    one record every epoch) is moved there, not kept twice."""
    _LAST[record.kind] = record
    kept = _RUNS.setdefault(record.kind, deque(maxlen=KEPT))
    if record in kept:          # by identity: a record defines no equality
        kept.remove(record)
    kept.append(record)
    return record


def last_run(kind: str) -> Optional[RunRecord]:
    return _LAST.get(kind)


def runs(kind: str) -> list:
    """The newest :data:`KEPT` records published of `kind`, oldest first."""
    return list(_RUNS.get(kind, ()))


# ------------------------------------------------------------ compile log

#: jax.monitoring's compile-path events, under the short names the log uses
_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
MARK = "mark"


class CompileLog:
    """Bounded log of ``(event, fun_name, start, seconds)``: event is
    ``trace`` / ``lower`` / ``compile`` (the backend compile, a cache
    fetch included) / ``retrieve`` (the fetch alone; JAX gives it no
    name) / ``mark`` / a program's own :meth:`note`; `start` is
    ``time.time()``.  ``trace`` carries the
    Python function's name, ``lower`` and ``compile`` the module's
    (``jit(<name>)``; the profiler writes it ``jit_<name>``).  Only a
    program's own trace is kept, not those of the functions it calls."""

    def __init__(self, capacity: int = 8192) -> None:
        self.entries: deque = deque(maxlen=capacity)
        #: what the program being traced has said of itself so far, an
        #: ``event -> (items, render)`` (:meth:`notes_for`); None outside
        self._gathered: Optional[dict] = None

    def mark(self, label: str) -> None:
        self.entries.append((MARK, label, time.time(), 0.0))

    def note(self, event: str, fun_name: str, text: str) -> None:
        """A fact a program states about itself as it is traced (which of
        its paths each layer took), in the log beside its compile events:
        `text` where they carry seconds."""
        self.entries.append((event, fun_name, time.time(), text))

    @contextlib.contextmanager
    def notes_for(self, program: str, **always):
        """Around the trace of `program` (a step builder opens it): what
        code deep inside says of the program one item at a time
        (:meth:`gather`: a pinned activation, a kernel call) becomes one
        note an event at the end, ``render(items)``, since a count is known
        only then.  `always` names the renderers of events noted even where
        nothing was gathered (``batch_pins``: ``axes=none sites=0``).
        Yields a dict that holds the notes, ``event -> text``, once the
        block is left."""
        outer = self._gathered
        self._gathered = {e: ([], render) for e, render in always.items()}
        said = {}
        try:
            yield said
        finally:
            gathered, self._gathered = self._gathered, outer
            for event, (items, render) in gathered.items():
                said[event] = render(items)
                self.note(event, program, said[event])

    def gather(self, event: str, item, render) -> bool:
        """`item` for the `event` note of the program being traced; False,
        and nothing kept, outside any :meth:`notes_for`."""
        if self._gathered is None:
            return False
        self._gathered.setdefault(event, ([], render))[0].append(item)
        return True

    def _span(self, event, start, end, fun_name=None, **_):
        short = _SPANS.get(event)
        if short is None:
            return
        if short == "trace":
            # JAX reports the trace of every jitted function a program
            # calls (thousands in a 48-layer model), each before the
            # program's own: keep the outermost, which contains them, and
            # what the program noted about itself while it was traced
            entries, kept = self.entries, []
            while entries and entries[-1][0] != MARK \
                    and entries[-1][2] >= start:
                inner = entries.pop()
                if inner[0] != "trace":
                    kept.append(inner)
            entries.extend(reversed(kept))
        self.entries.append((short, fun_name, start, end - start))

    def _duration(self, event, seconds, **_):
        if event == _RETRIEVAL:
            self.entries.append(("retrieve", None, time.time() - seconds,
                                 seconds))

    def since_mark(self) -> list:
        """The entries after the newest mark (all of them without one)."""
        out = []
        for e in reversed(self.entries):
            if e[0] == MARK:
                break
            out.append(e)
        return out[::-1]

    def notes(self) -> list:
        """``(event, fun_name, text)`` of every :meth:`note` since the
        newest mark."""
        return [(event, fun, text) for event, fun, _, text
                in self.since_mark() if isinstance(text, str)]

    def seconds(self, names: Iterable[str],
                events: Iterable[str] = ("trace", "lower"),
                entries: Optional[list] = None) -> dict:
        """Summed seconds by event of the programs called `names` (a name
        matches the function's and the module's form of it), in `entries`
        (default: since the newest mark)."""
        want = set(names) | {f"jit({n})" for n in names}
        out = {e: 0.0 for e in events}
        for event, fun, _, secs in (self.since_mark() if entries is None
                                    else entries):
            if event in out and fun in want:
                out[event] += secs
        return out


compile_log = CompileLog()
_installed = False


def install_compile_log(label: str = "start") -> CompileLog:
    """Register the listeners (once a process; JAX keeps them for good)
    and mark the log."""
    global _installed
    if not _installed:
        from jax import monitoring

        monitoring.register_event_time_span_listener(compile_log._span)
        monitoring.register_event_duration_secs_listener(
            compile_log._duration)
        _installed = True
    compile_log.mark(label)
    return compile_log
