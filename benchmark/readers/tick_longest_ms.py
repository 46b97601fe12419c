"""The TIMED (last) run's longest tick in milliseconds, counted from the
end of the tick before it, so that time between two ticks, which no phase
owns, is in it.  A median over runs hides a rare stall; the line printed
in the run that has one is the point: the tick's index, kind, slots and
chunks, its phase row, its program records, the unowned time before it,
and how many ticks ran over ten times the median."""
import statistics

from benchmark.harness import say
from benchmark.readers import traced_run


def spans_of(ticks: list, starts: list) -> list:
    """Seconds from the end of tick k-1 to the end of tick k (the first:
    its wall time)."""
    ends = [s + t[3] for s, t in zip(starts, ticks)]
    return [ticks[0][3]] + [b - a for a, b in zip(ends, ends[1:])]


def _read():
    record = traced_run.timed_record()
    if record is None:
        return None
    index, ticks, starts = traced_run.ring_rows(record)
    if not ticks or starts is None:
        return None
    spans = spans_of(ticks, starts)
    k = max(range(len(spans)), key=spans.__getitem__)
    median = statistics.median(spans)
    i, kind, meta, wall, row = ticks[k]
    before = spans[k] - wall
    progs = meta[2].get("programs", ()) if len(meta) > 2 else ()
    t0 = starts[k]
    say(f"longest tick: {spans[k] * 1e3:.3f}ms (median {median * 1e3:.3f}; "
        f"{sum(s > 10 * median for s in spans)} of {len(spans)} over ten "
        f"times it), tick {i} ({kind}), {meta[0] if meta else 0} slots "
        f"decoding, {meta[1] if len(meta) > 1 else 0} chunks; "
        f"{before * 1e3:.3f}ms before it that no tick owns; phases ms: "
        + ", ".join(f"{n} {row[j] * 1e3:.3f}" for n, j in index.items()
                    if row[j])
        + "; programs (ms from the tick's start: dispatch, returned, "
        "ready): " + ("; ".join(
            f"{p['program']} " + "/".join(
                "-" if t is None else f"{(t - t0) * 1e3:.3f}"
                for t in p["at"]) for p in progs) or "none"))
    return spans[k] * 1e3


def read(ctx):
    return traced_run.guarded("longest tick", _read)
