"""Profiling and compiler diagnostics.

The reference's observability is wall-clock print lines plus
``torch._dynamo.explain`` graph-break dumps (``CNN/model.py:289``,
SURVEY.md §5).  The TPU-native equivalents are strictly stronger and live
here:

* :func:`trace` — ``jax.profiler`` device traces (TensorBoard/XProf
  format): per-op device timelines, HBM usage, ICI collectives.
  Named host-side regions in that trace come from one door,
  :func:`..obs.trace.span`.
* :func:`hlo_text` / :func:`compiled_text` — the compiler's view of a
  jitted function before/after XLA optimisation (the ``dynamo.explain``
  analogue; there are no "graph breaks" to hunt — if it traced, it's one
  program — but fusion/layout decisions live in the optimised HLO).
* :func:`cost_analysis` — XLA's FLOP/byte estimates for a jitted call.
* :func:`memory_analysis` — XLA's compiled-memory breakdown (argument /
  output / temp / code bytes); the tune/ planner cross-checks its analytic
  HBM model against this.
* :class:`StepTimer` — steps/sec / examples/sec meter with warmup skip.
* :func:`measure_async_overlap` — dispatch-vs-completion split for a
  staged/pipelined callable: evidence that the host enqueues the whole
  schedule ahead of device execution (the mechanism behind
  ``StagedTrainer``'s cross-stage overlap).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str | None) -> Iterator[None]:
    """Capture a device trace under ``log_dir`` (no-op when None) —
    view with TensorBoard's profile plugin or xprof."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _lowered(fn: Callable, *args, **kwargs):
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jitted.lower(*args, **kwargs)


def hlo_text(fn: Callable, *args, **kwargs) -> str:
    """StableHLO for `fn` at these abstract shapes (pre-optimisation)."""
    return _lowered(fn, *args, **kwargs).as_text()


def compiled_text(fn: Callable, *args, **kwargs) -> str:
    """Post-XLA-optimisation HLO — where fusion and layout decisions are
    visible (the thing to read when perf surprises)."""
    return _lowered(fn, *args, **kwargs).compile().as_text()


def normalize_cost_analysis(analysis: Any) -> dict[str, Any]:
    """``Compiled.cost_analysis()`` output → plain dict (some backends wrap
    the dict in a single-element list)."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    return dict(analysis) if analysis else {}


def cost_analysis(fn: Callable, *args, **kwargs) -> dict[str, Any]:
    """XLA's cost model for one call: flops, bytes accessed, etc."""
    compiled = _lowered(fn, *args, **kwargs).compile()
    return normalize_cost_analysis(compiled.cost_analysis())


#: the stable integer fields of XLA's CompiledMemoryStats (the proto also
#: carries a serialized HLO blob — never surfaced here)
_MEMORY_FIELDS = (
    "generated_code_size_in_bytes", "argument_size_in_bytes",
    "output_size_in_bytes", "alias_size_in_bytes", "temp_size_in_bytes",
    "host_generated_code_size_in_bytes", "host_argument_size_in_bytes",
    "host_output_size_in_bytes", "host_alias_size_in_bytes",
    "host_temp_size_in_bytes",
)


def normalize_memory_analysis(stats: Any) -> dict[str, int]:
    """``Compiled.memory_analysis()`` output → dict of its stable integer
    fields, ``{}`` when the backend reports nothing at all.
    ``generated_code_size_in_bytes`` rides along (program size is part of
    the device footprint)."""
    if stats is None:
        return {}
    out: dict[str, int] = {}
    for field in _MEMORY_FIELDS:
        value = getattr(stats, field, None)
        if isinstance(value, int):
            out[field] = value
    return out


def memory_analysis(fn: Callable, *args, **kwargs) -> dict[str, int]:
    """XLA's compiled-memory breakdown for one call — argument / output /
    temp / generated-code bytes on device (plus host_* variants where the
    backend offloads).  The static sibling of a profiler HBM trace: it is
    known the moment compilation finishes, before anything runs.  Returns
    ``{}`` on backends that don't report memory stats."""
    try:
        stats = _lowered(fn, *args, **kwargs).compile().memory_analysis()
    except Exception:
        return {}
    return normalize_memory_analysis(stats)


class StepTimer:
    """Steps/sec + examples/sec with compile-step warmup exclusion.

    ``tick(examples)`` after each step; the first `warmup` ticks (compile,
    cache population) are excluded from rates.  Rates use a device sync at
    read time (`summary`) so async dispatch doesn't flatter the numbers.
    """

    def __init__(self, warmup: int = 1, clock=time.perf_counter):
        self.warmup = warmup
        self.clock = clock
        self.reset()

    def reset(self) -> None:
        self._ticks = 0
        self._examples = 0
        self._t0: float | None = None
        self._last: float | None = None

    def tick(self, examples: int = 0) -> None:
        now = self.clock()
        self._ticks += 1
        if self._ticks == self.warmup:
            self._t0 = now
            self._examples = 0
        elif self._ticks > self.warmup:
            self._examples += examples
        self._last = now

    @property
    def measured_steps(self) -> int:
        return max(0, self._ticks - self.warmup)

    def summary(self, sync: Any = None) -> dict[str, float]:
        """Rates over the post-warmup window.  Pass a jax.Array as `sync`
        to block on it first (honest step timing)."""
        if sync is not None:
            jax.block_until_ready(sync)
            # Only fold the sync time into the window when a window is
            # open: after reset() (no _t0 yet) a sync'd summary must not
            # plant a _last that would precede the next window's _t0.
            if self._t0 is not None:
                self._last = max(self.clock(), self._t0)
        if self._t0 is None or self._last is None or self.measured_steps == 0:
            return {"steps_per_sec": 0.0, "examples_per_sec": 0.0,
                    "seconds": 0.0}
        dt = max(self._last - self._t0, 1e-9)
        return {
            "steps_per_sec": self.measured_steps / dt,
            "examples_per_sec": self._examples / dt,
            "seconds": dt,
        }


def measure_async_overlap(fn: Callable, *args, warmup: bool = True,
                          **kwargs) -> dict[str, float]:
    """Measure how far ahead of device execution the host can run ``fn``.

    Returns ``{"dispatch_s", "total_s", "overlap_fraction"}`` where
    ``dispatch_s`` is the time for ``fn(*args, **kwargs)`` to *return*
    (all work
    enqueued on the devices' async streams) and ``total_s`` the time until
    every array in its result is actually ready.  ``overlap_fraction`` =
    ``1 - dispatch_s / total_s``: close to 1 means the host handed the
    whole schedule to the runtime and device execution proceeds behind it.

    This is the property that makes :class:`..workloads.base.StagedTrainer`
    a *pipeline* rather than a lock-step stage walk: its per-stage jitted
    applies and ``device_put`` transfers are all async, so microbatch *k*
    on stage *s* runs concurrently with *k+1* on stage *s-1* whenever the
    stages sit on distinct hardware.  (The reference's scheduler claims the
    same overlap from eager CUDA streams but never measured it —
    ``MLP/model.py:81-130``.)  On shared-core CPU test meshes the devices
    contend for the same silicon, so wall-clock speedup is not asserted —
    dispatch asynchrony is.
    """
    if warmup:
        jax.block_until_ready(fn(*args, **kwargs))
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    t1 = time.perf_counter()
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    dispatch, total = t1 - t0, max(t2 - t0, 1e-9)
    return {"dispatch_s": dispatch, "total_s": total,
            "overlap_fraction": 1.0 - dispatch / total}
