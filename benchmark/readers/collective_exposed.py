"""Collective-op time during which no other op ran on that chip, as a
share of the traced window.  Nothing to read on one chip."""
from benchmark import trace_reduce


def read(ctx):
    if not ctx["trace"] or ctx["chips"] < 2:
        return None
    got = trace_reduce.collective_exposed(ctx["trace"]["events"])
    if got["collective_s"] == 0:
        return None
    return 100.0 * got["exposed_s"] / got["window_s"]
