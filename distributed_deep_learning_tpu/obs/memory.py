"""Memory observability: live HBM tracking, buffer attribution, OOM
postmortems.

The obs/ layer measures *time* everywhere (spans, goodput, MFU, traces);
this module is the matching *memory* ledger.  Three surfaces:

* :class:`MemoryTracker` — polls ``device.memory_stats()`` into
  watermark / in-use gauges with a bounded per-step peak-delta timeline,
  plus host RSS.  TPU runtimes report the stats dict; the CPU backend
  reports nothing, so the tracker disarms itself after the first empty
  sample and the per-step hook degrades to one attribute read (the
  <2% hot-loop bar stays intact on every backend).
* :func:`buffer_attribution` / :func:`top_leaves` /
  :func:`donation_audit` — the static view from the compiled step's
  ``memory_analysis()``: argument/output/temp/alias breakdown, the
  largest pytree leaves by shape, and a donation audit that flags
  donated bytes that failed to alias (donated-but-copied inputs double
  their footprint — the exact crash class the bare-``P()`` placement
  bug in the ``--grad-compress int8`` path hit).
* :func:`record_oom_postmortem` — dumps watermark timeline + top
  buffers + active plan into a :class:`~.recorder.FlightRecorder` when
  ``RESOURCE_EXHAUSTED`` surfaces, so an OOM leaves an attributed black
  box instead of a bare stack trace.  With a seq-only recorder clock
  the dump bytes are bit-identical across runs.

Everything here is host Python; jax is imported lazily and only when a
device is actually polled.
"""

from __future__ import annotations

from typing import Any, Iterable, Sequence

#: gauge names the tracker maintains (the JSONL/Prometheus surface)
GAUGE_IN_USE = "mem_hbm_bytes_in_use"
GAUGE_LIMIT = "mem_hbm_bytes_limit"
GAUGE_PEAK = "mem_hbm_peak_bytes"
GAUGE_HOST_RSS = "mem_host_rss_bytes"


def is_oom_error(err: BaseException) -> bool:
    """Does this exception smell like device memory exhaustion?  XLA
    surfaces OOM as ``XlaRuntimeError`` with RESOURCE_EXHAUSTED status —
    matched on the message because the exception class moved across
    jaxlib versions."""
    msg = str(err)
    return ("RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()
            or "OOM" in msg)


def host_rss_bytes() -> int | None:
    """Resident set size of this process, from ``/proc/self/status``
    (exact, linux) falling back to ``resource.getrusage`` (portable);
    None when neither source works."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # linux reports KiB, macOS bytes; linux is the deployed target
        return int(ru) * 1024
    except Exception:
        return None


def device_memory_stats(device: Any) -> dict[str, int]:
    """``device.memory_stats()`` as a plain dict, ``{}`` when the backend
    reports nothing (CPU) or the call itself raises."""
    try:
        stats = device.memory_stats()
    except Exception:
        return {}
    return dict(stats) if stats else {}


def pytree_bytes(tree: Any) -> int:
    """Exact byte footprint of a pytree of arrays: Σ size × itemsize over
    leaves that carry shape/dtype (ShapeDtypeStructs count too — the
    analytic and allocated views agree by construction)."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * int(dtype.itemsize)
    return total


def top_leaves(tree: Any, n: int = 10) -> list[dict[str, Any]]:
    """The ``n`` largest leaves of a pytree by bytes, with their tree
    paths — "which buffer is eating HBM" by name.  Deterministic order:
    bytes descending, then path (ties can't reshuffle a postmortem)."""
    import jax

    rows = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        size = 1
        for d in shape:
            size *= int(d)
        rows.append({
            "path": jax.tree_util.keystr(path),
            "shape": list(shape),
            "dtype": str(dtype),
            "bytes": size * int(dtype.itemsize),
        })
    rows.sort(key=lambda r: (-r["bytes"], r["path"]))
    return rows[:n]


class MemoryTracker:
    """Live device-memory gauges + a bounded per-step timeline.

    Construct once per run (``RunTelemetry`` owns one), then call
    :meth:`sample` at span boundaries and :meth:`on_step` from the hot
    loop.  The first sample decides whether the backend reports memory
    at all; when it doesn't (CPU), ``on_step`` collapses to a single
    attribute read and only explicit :meth:`sample` calls refresh host
    RSS.

    ``every`` subsamples the hot loop (a ``memory_stats()`` call is a
    runtime round-trip; once every N steps bounds the cost while the
    peak-delta per sample still covers the window since the last one).
    """

    def __init__(self, registry, *, device: Any = None, every: int = 8,
                 capacity: int = 256) -> None:
        self.registry = registry
        self.device = device
        self.every = max(1, int(every))
        self.capacity = max(1, int(capacity))
        self.timeline: list[dict[str, Any]] = []
        self.samples = 0
        self.steps = 0
        self.peak_bytes = 0
        self._last_peak: int | None = None
        self._armed: bool | None = None   # unknown until the first sample

    @property
    def enabled(self) -> bool:
        """True until the backend proves it reports nothing."""
        return self._armed is not False

    def _resolve_device(self) -> Any:
        if self.device is None:
            import jax

            self.device = jax.devices()[0]
        return self.device

    def on_step(self) -> None:
        """Hot-loop hook: sample every ``self.every`` trained steps.
        One int increment + compare when disarmed or off-cadence."""
        self.steps += 1
        if self._armed is False or self.steps % self.every:
            return
        self.sample(step=self.steps)

    def sample(self, step: int | None = None) -> dict[str, Any] | None:
        """Poll the device once; update gauges and the timeline.

        Returns the sample dict, or None when the backend reports no
        memory stats (host RSS is still gauged on the FIRST empty
        sample, so CPU runs export it once without paying per step)."""
        stats = device_memory_stats(self._resolve_device())
        if not stats:
            if self._armed is None:
                self._armed = False
                rss = host_rss_bytes()
                if rss is not None:
                    self.registry.gauge(GAUGE_HOST_RSS).set(rss)
            return None
        self._armed = True
        in_use = int(stats.get("bytes_in_use", 0))
        limit = int(stats.get("bytes_limit", 0))
        peak = int(stats.get("peak_bytes_in_use", in_use))
        self.peak_bytes = max(self.peak_bytes, peak)
        delta = peak - self._last_peak if self._last_peak is not None else 0
        self._last_peak = peak
        self.registry.gauge(GAUGE_IN_USE).set(in_use)
        self.registry.gauge(GAUGE_PEAK).set(self.peak_bytes)
        if limit:
            self.registry.gauge(GAUGE_LIMIT).set(limit)
        rss = host_rss_bytes()
        if rss is not None:
            self.registry.gauge(GAUGE_HOST_RSS).set(rss)
        sample = {"step": step if step is not None else self.steps,
                  "bytes_in_use": in_use, "peak_bytes": peak,
                  "peak_delta": delta, "host_rss_bytes": rss}
        self.timeline.append(sample)
        if len(self.timeline) > self.capacity:
            del self.timeline[:len(self.timeline) - self.capacity]
        self.samples += 1
        return sample

    def summary(self) -> dict[str, Any]:
        """The run-level memory rollup (the ``obs_memory`` event body)."""
        return {
            "samples": self.samples,
            "steps": self.steps,
            "device_reports_memory": bool(self._armed),
            "peak_bytes": self.peak_bytes or None,
            "host_rss_bytes": host_rss_bytes(),
            "timeline_tail": self.timeline[-16:],
        }


def donation_audit(memory: dict[str, int],
                   donated_bytes: int | None) -> dict[str, Any]:
    """Flag donated input bytes that failed to alias an output.

    ``memory`` is a :func:`~..utils.profiling.normalize_memory_analysis`
    dict; ``donated_bytes`` the byte size of the arguments the caller
    donated (e.g. the train state).  When XLA honours a donation the
    bytes show up in ``alias_size_in_bytes``; donated bytes above the
    aliased count were silently copied — the program holds BOTH the old
    and new buffer, which is exactly how a "should fit" step OOMs.
    """
    aliased = int(memory.get("alias_size_in_bytes", 0))
    out: dict[str, Any] = {"aliased_bytes": aliased,
                           "donated_bytes": donated_bytes}
    if donated_bytes is None:
        out["unaliased_donated_bytes"] = None
        out["ok"] = None
        return out
    unaliased = max(0, int(donated_bytes) - aliased)
    out["unaliased_donated_bytes"] = unaliased
    # tolerate counter-sized slack: tiny scalar leaves are often folded
    # into the program rather than aliased, and that is not a leak
    out["ok"] = unaliased <= max(4096, int(donated_bytes) * 0.01)
    return out


def buffer_attribution(memory: dict[str, int], *, state: Any = None,
                       donated_bytes: int | None = None,
                       top_n: int = 10) -> dict[str, Any]:
    """The static memory story of one compiled step.

    ``memory`` — normalized ``memory_analysis()`` fields; ``state`` — an
    optional pytree (train state, KV cache) whose largest leaves get
    named; ``donated_bytes`` — what the caller donated, for the audit.
    """
    breakdown = {k: memory.get(k, 0) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    if donated_bytes is None and state is not None:
        donated_bytes = pytree_bytes(state)
    return {
        "breakdown": breakdown,
        "total_bytes": sum(v for v in breakdown.values()
                           if isinstance(v, int)),
        "top_leaves": top_leaves(state, top_n) if state is not None else [],
        "donation": donation_audit(memory, donated_bytes),
    }


def record_oom_postmortem(recorder, *, error: BaseException | str,
                          plan: dict | None = None,
                          top_buffers: Sequence[dict] | None = None,
                          watermarks: Iterable[dict] | None = None,
                          attribution: dict | None = None,
                          context: str = "train") -> bool:
    """Write the OOM story into a flight recorder and trip it.

    Returns True when a postmortem was recorded (the error actually was
    an OOM and a recorder exists).  Every field is JSON-plain and
    deterministically ordered, so a seq-clock recorder dumps
    bit-identical bytes for identical failures."""
    if recorder is None:
        return False
    if isinstance(error, BaseException):
        if not is_oom_error(error):
            return False
        error = f"{type(error).__name__}: {error}"[:500]
    elif "RESOURCE_EXHAUSTED" not in error and "OOM" not in error \
            and "out of memory" not in error.lower():
        return False
    recorder.record(
        "oom_postmortem",
        context=context,
        error=error,
        plan=plan,
        top_buffers=list(top_buffers or ()),
        watermark_timeline=list(watermarks or ()),
        attribution=attribution,
    )
    recorder.trip("oom_postmortem")
    return True
