"""What of the device's gap between two programs is NOT the host's
bookkeeping: the median over joined pairs A, B of the TRACED window, with
no other program run between them, of

    (device start of B - device end of A) - (B.at[0] - A.at[2])

that is launch latency plus the time the host takes to notice A's end
(the result's way back).  Device instants from the trace, host instants
from the program's record, joined by order (``traced_run.py``); each
difference is taken on one clock.

It cannot be negative in truth (the host holds A's result after A ended,
and B starts after its dispatch was entered): the share of pairs that read
negative is printed, and over 1% of them means a join fault: None.  The
means of gap, turnaround and remainder over the same pairs are printed;
they add exactly."""
import bisect
import statistics

from benchmark.harness import say
from benchmark.readers import traced_run


def clean_pairs(joined: list, events: list) -> list:
    """``(gap_s, turnaround_s)`` of consecutive joined programs with both
    instants known and no other program run starting in the gap."""
    others = [s for s, _, _ in traced_run.module_events(events)]
    out = []
    for (a, ea), (b, eb) in zip(joined, joined[1:]):
        if a["at"][2] is None or b["at"][0] is None or eb[0] < ea[1]:
            continue
        # module events that start after A's start and before B's start:
        # only A itself may
        lo = bisect.bisect_right(others, ea[0])
        hi = bisect.bisect_left(others, eb[0])
        if hi > lo:
            continue
        out.append(((eb[0] - ea[1]) / 1e9, b["at"][0] - a["at"][2]))
    return out


def _read(ctx):
    joined = traced_run.joined(ctx)
    if not joined:
        return None
    pairs = clean_pairs(joined, ctx["trace"]["events"])
    if not pairs:
        return None
    rest = [g - t for g, t in pairs]
    negative = sum(r < 0 for r in rest) / len(rest)
    gap, turn = (statistics.fmean(p[i] for p in pairs) for i in (0, 1))
    say(f"launch + notice: {len(pairs)} of {len(joined) - 1} pairs with "
        f"nothing between; mean gap {gap * 1e3:.3f}ms = turnaround "
        f"{turn * 1e3:.3f} + remainder {statistics.fmean(rest) * 1e3:.3f}; "
        f"{100 * negative:.2f}% of pairs read a negative remainder")
    if negative > 0.01:
        say("launch + notice: over 1% negative: the join is at fault, "
            "nothing read")
        return None
    return statistics.median(rest) * 1e3


def read(ctx):
    if not ctx.get("trace"):
        return None
    return traced_run.guarded("launch + notice", _read, ctx)
