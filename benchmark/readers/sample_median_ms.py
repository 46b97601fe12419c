"""Median of one of the run's host-clock samples, in milliseconds."""
import statistics


def read(ctx, sample: str):
    values = ctx["samples"].get(sample)
    return statistics.median(values) * 1e3 if values else None
