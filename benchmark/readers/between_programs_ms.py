"""Median time the device waits between the end of one program run and
the start of the next, from the trace."""
import statistics

from benchmark import trace_reduce


def read(ctx):
    if not ctx["trace"]:
        return None
    gaps = trace_reduce.between_programs(ctx["trace"]["events"])
    return statistics.median(gaps) * 1e3 if gaps else None
