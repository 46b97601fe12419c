"""The decode program's share of its roofline: the time the chip needs
to read what a tick must read (weights once, live keys and values once)
over the median device time of one decode program run in the trace."""
import statistics

from benchmark import harness, trace_reduce
from benchmark.harness import say


def read(ctx, cost: str = "decode_tick", label: str = "decode_dispatch"):
    if not ctx["trace"] or ctx["peaks"] is None:
        return None
    runs = trace_reduce.program_seconds(ctx["trace"]["events"], label)
    live = ctx["counters"].get("mean_live_tokens")
    if not runs or live is None:
        return None
    need = harness.cost_function(cost)(
        ctx["config"], ctx["traffic"]["engine"]["max_slots"], live)
    by_bytes = need["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = need["flops"] / ctx["peaks"]["bf16_flops"]
    took = statistics.median(runs)
    say(f"roofline {cost}: {len(runs)} decode programs, median "
        f"{took * 1e3:.3f}ms; least {by_bytes * 1e3:.3f}ms by bytes "
        f"({need['weight_bytes'] / 1e9:.2f} GB weights + "
        f"{need['kv_bytes'] / 1e9:.2f} GB live KV), "
        f"{by_flops * 1e3:.3f}ms by operations: bound by "
        f"{'bytes' if by_bytes >= by_flops else 'operations'}")
    return 100.0 * max(by_bytes, by_flops) / took
