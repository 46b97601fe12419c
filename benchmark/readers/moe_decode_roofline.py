"""The decode program's share of its roofline for a model with routed
experts: the time the chip needs for what a tick must read (the weights
outside the experts once, the held experts the tick's tokens TOUCHED, from
the program's own counter, the live keys and values by layer kind) over
the median device time of one decode program run in the trace.

The runs are picked by the program's own name on the device's ``XLA
Modules`` line (`program`), not by the host annotation that launched them
(``trace_reduce.program_seconds``): in this cell's traces a program's
first device event lies 0.2-0.4 ms BEFORE its dispatch annotation begins
on the profiler's clocks (my chip trace, PR 26), so the label by "latest
annotation begun" names the dispatch before."""
import statistics

from benchmark import harness, trace_reduce
from benchmark.harness import say
from benchmark.readers import tick_counters


def runs_of(events: list, program: str) -> list:
    """(start, end) of each run of the jitted `program` on the first
    chip, whole inside the traced window."""
    lo, hi = trace_reduce.window_of(events)
    plane = trace_reduce.device_planes(events)[0]
    return sorted((e[3], e[3] + e[4]) for e in events
                  if e[0] == plane and e[1] == trace_reduce.MODULES_LINE
                  and e[2].startswith(program)
                  and e[3] >= lo and e[3] + e[4] <= hi)


def read(ctx, cost: str = "laguna_decode_tick",
         program: str = "jit_paged_decode"):
    if not ctx["trace"] or ctx["peaks"] is None:
        return None
    runs = [(t - s) / 1e9 for s, t in runs_of(ctx["trace"]["events"],
                                              program)]
    touched = tick_counters.read(ctx, ["experts", "touched"])
    live = ctx["counters"].get("mean_live_tokens")
    share = ctx["counters"].get("mean_decoding_slots_share")
    if not runs or touched is None or live is None or share is None:
        return None
    slots = share * ctx["traffic"]["engine"]["max_slots"]
    need = harness.cost_function(cost)(
        ctx["config"], slots, touched, live,
        slots * ctx["config"]["sliding_window"])
    by_bytes = need["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = need["flops"] / ctx["peaks"]["bf16_flops"]
    took = statistics.median(runs)
    say(f"roofline {cost}: {len(runs)} decode programs, median "
        f"{took * 1e3:.3f}ms; least {by_bytes * 1e3:.3f}ms by bytes "
        f"({need['outside_bytes'] / 1e9:.2f} GB weights outside experts + "
        f"{need['expert_bytes'] / 1e9:.2f} GB of {touched:.1f} touched "
        f"experts a layer + {need['kv_bytes'] / 1e9:.2f} GB live KV), "
        f"{by_flops * 1e3:.3f}ms by operations: bound by "
        f"{'bytes' if by_bytes >= by_flops else 'operations'}")
    return 100.0 * max(by_bytes, by_flops) / took
