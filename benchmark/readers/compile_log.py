"""Seconds the program's compile log holds, since this run's start, for
the programs called `names`, summed over `events` (``trace``: Python to
jaxpr; ``lower``: jaxpr to an MLIR module; ``compile``: the backend
compile or the fetch from the cache).  The log is the program's own
(``obs.compile_log``, fed by JAX's compile-path events); the benchmark's
reference and helpers are left out by name.

A program with no such log (the parent of the PR that added it), or one
whose log has another shape, gives nothing to read: None, and the metric
is left out.  A reader of the program's own records never ends a run."""
from benchmark.harness import say


def read(ctx, names, events=("trace", "lower")):
    try:
        from distributed_deep_learning_tpu.obs import compile_log

        entries = compile_log.since_mark()
        each = {n: compile_log.seconds([n], events, entries) for n in names}
    except Exception as e:  # noqa: BLE001 -- absent or foreign: no metric
        say(f"compile log: nothing to read ({type(e).__name__}: {e})")
        return None
    ran = {n: v for n, v in each.items() if any(v.values())}
    if not ran:
        return None
    say("compile log: " + "; ".join(
        f"{n} " + " ".join(f"{e} {s:.2f}s" for e, s in v.items())
        for n, v in ran.items())
        + f" ({len(entries)} entries since the run began)")
    return sum(s for v in ran.values() for s in v.values())
