"""The mean over the engine's decode ticks of a counter the program wrote
into its own tick ring (``obs.last_run("serve")``: each tick's
``meta[2]``, a dict of dicts), scaled; `per` names another counter of the
same tick to divide by first, `where` one that must be positive for the
tick to count.  Counters are paths like ``["experts", "touched"]``.

A program that writes no such counters (the parent of the PR that added
them, or a model without the layer) gives nothing to read: None, and the
metric is left out.  A reader of the program's own records never ends a
run."""
from benchmark.harness import say


def tick_counters(kind: str = "decode") -> list:
    """The counters dict of each `kind` tick in the last serving run's
    ring; [] where the program publishes none."""
    try:
        from distributed_deep_learning_tpu.obs import last_run

        record = last_run("serve")
        return [t[2][2] for t in record.phases.ticks
                if t[1] == kind and len(t[2]) > 2]
    except Exception as e:  # noqa: BLE001 -- absent or foreign: no metric
        say(f"serve tick counters: nothing to read "
            f"({type(e).__name__}: {e})")
        return []


def at(counters: dict, path):
    for key in path:
        if not isinstance(counters, dict) or key not in counters:
            return None
        counters = counters[key]
    return counters


def read(ctx, path, per=None, where=None, scale: float = 1.0):
    values = []
    for c in tick_counters():
        v = at(c, path)
        over = 1.0 if per is None else at(c, per)
        if v is None or not over or (where and not at(c, where)):
            continue
        values.append(v / over)
    if not values:
        return None
    return scale * sum(values) / len(values)
