"""The grouped expert kernels' share of their roofline, from the TRACED
window alone: the least time for the grouped calls of the programs the
trace and the program's own records both hold (joined by order,
``traced_run.py``) over the device time of those programs' kernel events.

What a program's calls had to move comes from ITS OWN record
(``experts.touched`` x ``experts.layers`` touched experts, ``experts.
assignments`` held rows; chunk and decode programs alike), through
``costs/<cost>.py``; the least time is the larger of the summed bytes over
the chip's bytes/s and the summed operations over its FLOP/s.  An event
belongs to the joined program whose device interval it starts in.  None
where the events a program do not come to two an expert layer (a program
whose calls went another way), or there is nothing to join."""
import re

from benchmark import harness, trace_reduce
from benchmark.harness import say
from benchmark.readers import traced_run


def kernel_time(events: list, pairs: list, pattern: str):
    """``(seconds, events)`` of the first chip's ops matching `pattern`
    that start inside a joined program's device interval."""
    import bisect

    rx = re.compile(pattern)
    lo, hi = trace_reduce.window_of(events)
    spans = sorted(ev for _, ev in pairs)
    starts = [s for s, _ in spans]
    ns = n = 0
    for s, t in trace_reduce.op_intervals(
            events, trace_reduce.device_planes(events)[0], lo,
            max(hi, spans[-1][1]), lambda name: bool(rx.search(name))):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            ns += t - s
            n += 1
    return ns / 1e9, n


def _read(ctx, cost, pattern):
    pairs = traced_run.joined(ctx)
    if not pairs:
        return None
    pairs = [(r, ev) for r, ev in pairs if "experts" in r]
    if not pairs:
        return None
    took, n_events = kernel_time(ctx["trace"]["events"], pairs, pattern)
    layers = {r["experts"]["layers"] for r, _ in pairs}
    if len(layers) != 1 or n_events != 2 * layers.pop() * len(pairs):
        say(f"roofline {cost}: {n_events} kernel events in {len(pairs)} "
            f"joined programs of {sorted(layers) or 'one count of'} expert "
            f"layers: not two a layer, nothing read")
        return None
    touched = sum(r["experts"]["touched"] * r["experts"]["layers"]
                  for r, _ in pairs)
    rows = sum(r["experts"]["assignments"] for r, _ in pairs)
    need = harness.cost_function(cost)(ctx["config"], touched, rows)
    by_bytes = need["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = need["flops"] / ctx["peaks"]["bf16_flops"]
    kinds = {n: sum(r["program"] == n for r, _ in pairs)
             for n in traced_run.PROGRAMS}
    say(f"roofline {cost}: {len(pairs)} programs joined ("
        + ", ".join(f"{k} {n[len('paged_'):]}" for n, k in kinds.items())
        + f"), {n_events / len(pairs):.0f} kernel events a program, "
        f"{took:.4f}s of kernels; {touched:.0f} touched experts and "
        f"{rows} held rows summed; least {by_bytes:.4f}s by bytes "
        f"({need['weight_bytes'] / 1e9:.2f} GB of weights + "
        f"{need['row_bytes'] / 1e9:.2f} GB of rows), {by_flops:.4f}s by "
        f"operations: bound by "
        f"{'bytes' if by_bytes >= by_flops else 'operations'}")
    return 100.0 * max(by_bytes, by_flops) / took if took else None


def read(ctx, cost: str = "grouped_product",
         pattern: str = "grouped_swiglu|grouped_product"):
    if not ctx.get("trace") or ctx.get("peaks") is None:
        return None
    return traced_run.guarded(f"roofline {cost}", _read, ctx, cost, pattern)
