"""Host milliseconds from holding one program's result to entering the
next program's dispatch: the median over consecutive recorded programs A,
B of ``B.at[0] - A.at[2]`` in the TIMED (last) run's record.  The host's
own bookkeeping between two programs, the part of the device's gap that
work on the host's code removes; launch and notice latency are not in it.

Printed: the median by kind of pair and the phases that lie between the
two instants, each as the median over the pairs' ticks of the ring's row
(the hook is the caller's ``on_tick``; "between ticks" is time no phase
owns, from the ring's starts)."""
import statistics

from benchmark.harness import say
from benchmark.readers import traced_run

SHORT = {"paged_chunk": "chunk", "paged_decode": "decode"}
#: phases between A's ready and B's dispatch: (in A's tick after A, in
#: B's tick before B); within one tick the two lists are one
AFTER = {"paged_chunk": ("chunk_commit", "hook"),
         "paged_decode": ("hook", "decode_commit")}
BEFORE = {"paged_chunk": ("chunk_prepare",),
          "paged_decode": ("decode_prepare",)}


def pairs_of(records: list) -> list:
    """Consecutive (A, B) with both instants known."""
    return [(a, b) for a, b in zip(records, records[1:])
            if a["at"][2] is not None and b["at"][0] is not None]


def _fill(record, pairs):
    """Median ms of each phase between A and B over the pairs' ticks."""
    index, ticks, starts = traced_run.ring_rows(record)
    sums: dict = {}

    def add(name, value):
        sums.setdefault(name, []).append(value * 1e3)

    for a, b in pairs:
        ta, tb = ticks[a["tick"]], ticks[b["tick"]]
        for n in AFTER[a["program"]]:
            add(n, ta[4][index[n]])
        if a["tick"] != b["tick"]:
            add("tick_end", ta[4][index["tick_end"]])
            add("admit", tb[4][index["admit"]])
            if starts is not None:
                add("between ticks", starts[b["tick"]]
                    - starts[a["tick"]] - ta[3])
        for n in BEFORE[b["program"]]:
            add(n, tb[4][index[n]])
    return ", ".join(f"{n} {statistics.median(v):.3f}"
                     for n, v in sums.items())


def _read():
    record = traced_run.timed_record()
    if record is None:
        return None
    pairs = pairs_of(traced_run.programs(record, whole=False))
    if not pairs:
        return None
    by_kind: dict = {}
    for a, b in pairs:
        by_kind.setdefault((a["program"], b["program"]), []).append((a, b))
    for (ka, kb), got in sorted(by_kind.items()):
        ms = statistics.median(b["at"][0] - a["at"][2] for a, b in got) * 1e3
        say(f"turnaround {SHORT.get(ka, ka)}->{SHORT.get(kb, kb)}: "
            f"{len(got)} pairs, median {ms:.3f}ms; phases between (each a "
            f"tick's whole sum, median): {_fill(record, got)}")
    return statistics.median(b["at"][0] - a["at"][2] for a, b in pairs) * 1e3


def read(ctx):
    return traced_run.guarded("turnaround", _read)
