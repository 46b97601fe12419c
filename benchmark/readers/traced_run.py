"""What the program recorded about the TRACED window, and its join with the
device's own events: the helper of the readers that set a program's device
time against what that same program held.

The paged engine publishes a record a run (``obs.runs("serve")``, the
newest few, oldest first) and says of each how many of its ticks ran with
something listening (``record.phases.listened``): the traced window's
record is the newest that was listened to, whatever the runner's order of
windows.  A tick's ``counters["programs"]`` lists the programs it
dispatched, in order, each with the instants ``at = (t_dispatch,
t_returned, t_ready)`` on the host's clock, and for a model with expert
layers what THAT program's experts took (``experts``).

The join is BY ORDER: the k-th recorded ``paged_decode`` is the k-th
``jit_paged_decode`` event on the first chip's ``XLA Modules`` line from
the window's start.  No clock is mapped onto another: device intervals come
from the trace, host intervals from the ring, and only differences of each
are combined (the profiler's device and host planes lie 0.2-0.4 ms apart,
chip traces of PR 26).  The window ends by raising out of a dispatch, so the
last record or event of a name may lack its partner.

A program that keeps no such records (the parent of the PR that added
them) gives nothing to read: None or [], never an exception.
"""
from benchmark import trace_reduce
from benchmark.harness import say

PROGRAMS = ("paged_chunk", "paged_decode")


def guarded(what: str, fn, *args, **kw):
    """`fn`'s value; None, with a line, where the program's records are
    absent or of another shape.  A reader never ends a run."""
    try:
        return fn(*args, **kw)
    except Exception as e:  # noqa: BLE001 -- absent or foreign: no metric
        say(f"{what}: nothing to read ({type(e).__name__}: {e})")
        return None


def traced_record():
    """The newest serving run something listened to; None without one."""
    from distributed_deep_learning_tpu import obs

    for record in reversed(obs.runs("serve")):
        if record.phases.listened > 0:
            return record
    return None


def timed_record():
    """The last serving run the process made: the timed window."""
    from distributed_deep_learning_tpu import obs

    return obs.last_run("serve")


def programs(record, whole: bool = True) -> list:
    """The record's program records, flattened in dispatch order, each a
    copy with ``tick`` = its tick's place in the ring; `whole`: [] where
    the ring has lost ticks (an order from the run's start is then no
    order; a reader of consecutive pairs alone does not mind)."""
    pc = record.phases
    if whole and pc.n_ticks != len(pc.ticks):
        say(f"program records: the ring kept {len(pc.ticks)} of "
            f"{pc.n_ticks} ticks; no order from the run's start")
        return []
    out = []
    for place, t in enumerate(pc.ticks):
        meta = t[2]
        if len(meta) > 2:
            out.extend(dict(p, tick=place)
                       for p in meta[2].get("programs", ()))
    return out


def module_events(events: list) -> list:
    """``(start_ns, end_ns, name)`` of the first chip's program runs that
    began inside the traced window, by start; a name is the module's,
    ``jit_<program>``, without its run id."""
    lo, hi = trace_reduce.window_of(events)
    runs = [e for e in events if e[1] == trace_reduce.MODULES_LINE
            and e[0].startswith("/device:") and lo <= e[3] < hi]
    first = min((e[0] for e in runs), default=None)
    return sorted((e[3], e[3] + e[4], e[2].split("(")[0])
                  for e in runs if e[0] == first)


def join(records: list, events: list):
    """The flattened `records`, each paired BY ORDER with the device event
    of its program: ``[(record, (start_ns, end_ns))]`` in dispatch order,
    the first min(records, events) of each name.  None, with a line, where
    the two counts of a name differ by more than one (the window's end may
    cut one; more is a program run nobody recorded, or the reverse)."""
    ends, runs = {}, module_events(events)
    for name in PROGRAMS:
        mine = [r for r in records if r["program"] == name]
        dev = [e for e in runs if e[2] == "jit_" + name]
        if abs(len(mine) - len(dev)) > 1:
            say(f"join: {len(mine)} recorded {name} against {len(dev)} "
                f"jit_{name} events in the window; no join by order")
            return None
        ends[name] = iter(dev[:len(mine)])
    out = []
    for r in records:
        ev = next(ends.get(r["program"], iter(())), None)
        if ev is not None:
            out.append((r, ev[:2]))
    return out


def joined(ctx):
    """The traced window's programs joined with its trace; None where the
    program kept no record of it or the counts do not fit."""
    if not ctx.get("trace"):
        return None
    record = traced_record()
    if record is None:
        return None
    records = programs(record)
    if not records:
        return None
    return join(records, ctx["trace"]["events"])


def ring_rows(record):
    """``(names index, ticks, starts)``: the ring as lists, and each tick's
    start beside it (None where the program keeps none)."""
    pc = record.phases
    ticks = list(pc.ticks)
    starts = list(getattr(pc, "started", ()))
    return ({n: i for i, n in enumerate(pc.names)}, ticks,
            starts if len(starts) == len(ticks) else None)
