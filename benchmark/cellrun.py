"""One run of one cell, importable: ``run.py`` calls it once; a loop over
seeds in one process (the dozen seeds a limit is set from, the control)
calls it again and again."""

from __future__ import annotations

import contextlib
import os
import shutil
import time

from benchmark import harness, trace_reduce
from benchmark.harness import say


class Tracer:
    """JAX's profiler around a short traced window, and host annotations
    on its clock.  Off (``active`` false) it hands out null contexts, so
    the runners call it either way."""

    def __init__(self, active: bool, root: str = harness.ROOT):
        self.active = active
        self.dir = os.path.join(root, ".bench_trace")

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)

    def annotate(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def window(self):
        return self.annotate(trace_reduce.WINDOW)

    def stop(self) -> dict:
        """Stop, reduce, delete the files; the reduced trace."""
        import jax

        t = time.perf_counter()
        jax.profiler.stop_trace()
        events = trace_reduce.load_xplane(
            trace_reduce.newest_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        out = dict(trace_reduce.busy(events), events=events)
        say(f"trace: {len(events)} events, busy {out['busy_s']:.3f}s of "
            f"{out['window_s']:.3f}s on {out['chips']} chip(s), read in "
            f"{time.perf_counter() - t:.1f}s")
        return out


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_process_start: float | None = None, allow_cpu: bool = False,
             cell: harness.Cell | None = None,
             keep: dict | None = None) -> dict:
    """Set up, measure, compare; the dict of the result line.  `keep`,
    where given, receives what the runner handed back (trace events,
    samples, counters, checks) for tools and tests."""
    clock = harness.SetupClock(time.perf_counter()
                               if t_process_start is None
                               else t_process_start)
    cell = cell or harness.Cell(name)
    with clock.phase("import_jax"):
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with clock.phase("device_init"):
        devices = harness.claim_devices(cell.chips, allow_cpu)
    peaks = (harness.peaks_for(devices[0].device_kind)
             if devices[0].platform != "cpu" else None)
    with clock.phase("import_program"):
        from distributed_deep_learning_tpu.runtime.bootstrap import (
            enable_compile_cache)

        cache = enable_compile_cache()
    say(f"cell {cell.name}: config {cell.config['name']}, traffic "
        f"{cell.traffic['name']}, seed {seed}, {seconds}s, trace "
        f"{int(trace)}; {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); compile cache {cache}")
    meter = compile_meter()
    tracer = Tracer(trace)
    out = harness.runner_for(cell).run(cell, seed, seconds, trace, clock,
                                       meter, devices, tracer)
    if keep is not None:
        keep.update(out)
    correct = harness.print_checks(out["checks"])
    if out["compiles_in_window"]:
        say(f"check compiles_in_window: {out['compiles_in_window']} "
            f"(limit 0) FAILED")
        correct = False
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        ctx = {"cell": cell, "config": cell.config, "traffic": cell.traffic,
               "chips": cell.chips, "peaks": peaks, "meter": meter,
               "setup": dict(clock.phases, total=clock.total,
                             compile_s=clock.compile_s),
               "trace": out["trace"], "samples": out["samples"],
               "counters": out["counters"],
               "memory_peak_bytes": out["memory_peak_bytes"]}
        result["metrics"] = harness.read_per_layer(cell, ctx)
        events = out["trace"]["events"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(events),
            "idle_gaps": trace_reduce.idle_gaps(events)}
    else:
        result["metrics"] = harness.select_end_to_end(cell,
                                                      out["end_to_end"])
    result["device"] = harness.device_record(
        devices, out["memory_peak_bytes"], out["trace"] if trace else None)
    return result


_METER = None


def compile_meter() -> harness.CompileMeter:
    """One set of listeners a process (JAX keeps them for good)."""
    global _METER
    if _METER is None:
        _METER = harness.CompileMeter()
    return _METER
