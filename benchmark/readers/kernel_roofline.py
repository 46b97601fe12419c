"""A kernel's share of its roofline: the least time the chip could take
for the calls the trace holds (the larger of operations over peak FLOP/s
and bytes over peak bytes/s, from the cost function) over the device
time of the kernel's events in the trace."""
from benchmark import harness, trace_reduce
from benchmark.harness import say


def read(ctx, pattern: str, cost: str, per: str):
    if not ctx["trace"] or ctx["peaks"] is None:
        return None
    events = ctx["trace"]["events"]
    got = trace_reduce.op_seconds(events, pattern)
    runs = len(trace_reduce.program_seconds(events, per))
    if not got["count"] or not runs:
        return None
    mix = ctx["traffic"]
    need = harness.cost_function(cost)(
        ctx["config"], int(mix["rows_per_chip"]), int(mix["seq_len"]))
    by_flops = need["flops"] / ctx["peaks"]["bf16_flops"]
    by_bytes = need["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    say(f"roofline {cost}: {got['count']} kernel events, "
        f"{got['seconds'] / runs * 1e3:.3f}ms a step over {runs} steps; "
        f"least {by_flops * 1e3:.3f}ms by operations, "
        f"{by_bytes * 1e3:.3f}ms by bytes: bound by "
        f"{'operations' if by_flops >= by_bytes else 'bytes'}")
    return 100.0 * max(by_flops, by_bytes) * runs / got["seconds"]
