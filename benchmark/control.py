"""The readings a limit is set from: sound runs of the program and the
lower-precision control, over several seeds in ONE process (set-up is
long; a limit wants a dozen seeds).

    python3 benchmark/control.py --workload <name>[,<name>] --seeds 1,2,3 --seconds 40 --control fp8

Several cells of one machine share the process (the serving package alone
takes 36 s to import there); ``--control`` is any precision the cell's
plain reference knows (``fp8``, ``int8``).  For each seed it prints every number compared, for the program and for
the control (the plain reference computed in the next precision down, put
in the program's place), and at the end the largest the sound runs gave
and the smallest the control gave.  A benchmark run never calls this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--control", default="fp8",
                   help="'none' reads the sound runs only")
    p.add_argument("--control-seeds", type=int, default=3,
                   help="the control follows the first N seeds")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _env

    _env.prepare(ROOT)
    from benchmark import harness

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from distributed_deep_learning_tpu.runtime.bootstrap import (
        enable_compile_cache)

    enable_compile_cache()
    found = {}
    for name in args.workload.split(","):
        found[name] = read_cell(harness.Cell(name), args)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(found, f, indent=1)
    return 0


def read_cell(cell, args) -> dict:
    from benchmark import harness
    from benchmark.harness import say

    devices = harness.claim_devices(cell.chips)
    runner = harness.runner_for(cell)
    sound, control = {}, {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        quant = (args.control if args.control != "none"
                 and i < args.control_seeds else None)
        clock = harness.SetupClock(time.perf_counter())
        got = runner.readings(cell, seed, args.seconds, devices, clock,
                              quant)
        pair = got if quant is not None else (got, [])
        for side, checks in zip((sound, control), pair):
            for c in checks:
                side.setdefault(c["name"], {})[seed] = c["value"]
        say(f"{cell.name} seed {seed}: program "
            + ", ".join(f"{c['name']} {c['value']:.6g}" for c in pair[0])
            + (f" | {quant} control " + ", ".join(
                f"{c['name']} {c['value']:.6g}" for c in pair[1])
               if pair[1] else ""))
    for name in sound:
        hi = max(sound[name].values())
        lo = min(control[name].values()) if control.get(name) else None
        say(f"{cell.name} {name}: sound runs' largest {hi:.6g} over "
            f"{len(sound[name])} seeds"
            + (f"; {args.control} control's smallest {lo:.6g} over "
               f"{len(control[name])} seeds; ratio {lo / hi:.2f}"
               if lo is not None and hi > 0 else ""))
    return {"control": args.control, "sound": sound, "control_read": control}


if __name__ == "__main__":
    sys.exit(main())
