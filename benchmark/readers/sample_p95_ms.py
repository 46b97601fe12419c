"""95th percentile of one of the run's host-clock samples, in
milliseconds, picked as the serve runner picks its own (sorted, the value
at ``int(0.95 * n)``)."""


def read(ctx, sample: str):
    values = sorted(ctx["samples"].get(sample) or ())
    if not values:
        return None
    return values[min(len(values) - 1, int(0.95 * len(values)))] * 1e3
