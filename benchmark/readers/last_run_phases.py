"""Median host milliseconds an iteration of the program's own loop spent
in some of its phases, from the record the program publishes as a run
starts (``obs.last_run(kind)``: the paged engine's per-tick ring, the
loader's per-batch ring).  In a traced run that is the last run the
process made: the untraced rest of the window.

`phases`: the phases summed; without it, the iteration's wall time less
the phases in `less`.  `ticks`: the kind or kinds of iteration counted
(the engine's ``decode`` and ``prefill`` ticks, the loader's ``batch``).
`per_chunk`: count only iterations that ran a chunk, each divided by its
chunks.  `split`: print the median of every phase, the hook's apart.

A program that publishes no such record (the parent of the PR that added
it), or one whose record has another shape, gives nothing to read: None,
and the metric is left out.  A reader of the program's own records never
ends a run."""
import statistics

from benchmark.harness import say


def read(ctx, kind: str, ticks, phases=None, less=(),
         per_chunk: bool = False, split: bool = False):
    try:
        return _read(kind, ticks, phases, less, per_chunk, split)
    except Exception as e:  # noqa: BLE001 -- absent or foreign: no metric
        say(f"{kind} record: nothing to read ({type(e).__name__}: {e})")
        return None


def _read(kind, ticks, phases, less, per_chunk, split):
    from distributed_deep_learning_tpu.obs import last_run

    record = last_run(kind)
    if record is None:
        return None
    names = record.phases.names
    kinds = {ticks} if isinstance(ticks, str) else set(ticks)
    rows = [t for t in record.phases.ticks if t[1] in kinds]
    if per_chunk:
        rows = [t for t in rows if t[2][1] > 0]
    if not rows:
        return None
    index = {n: i for i, n in enumerate(names)}
    if any(p not in index for p in (*(phases or ()), *less)):
        return None

    def value(t):
        _, _, meta, wall, row = t
        got = (sum(row[index[p]] for p in phases) if phases
               else wall - sum(row[index[p]] for p in less))
        return got / meta[1] if per_chunk else got

    if split:
        med = {n: statistics.median(t[4][i] for t in rows) * 1e3
               for n, i in index.items()}
        wall = statistics.median(t[3] for t in rows) * 1e3
        say(f"{kind} {'/'.join(sorted(kinds))} ticks ({len(rows)} in the "
            f"ring): median wall {wall:.3f}ms = " + ", ".join(
                f"{n} {v:.3f}" for n, v in med.items() if n != "hook")
            + f"; hook (the caller's on_tick) {med.get('hook', 0.0):.3f}; "
            f"unattributed "
            f"{wall - sum(med.values()):.3f} (medians do not add exactly)")
    return statistics.median(value(t) for t in rows) * 1e3
