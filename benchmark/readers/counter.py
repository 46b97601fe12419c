"""One of the run's counters, scaled."""


def read(ctx, counter: str, scale: float = 1.0):
    value = ctx["counters"].get(counter)
    return None if value is None else value * scale
