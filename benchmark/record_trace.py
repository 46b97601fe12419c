"""Run one cell with the profiler on and keep what the reduction reads.

    python3 benchmark/record_trace.py --workload <name> --seed <n> --seconds <s> --out <dir>

Writes ``<dir>/<workload>.describe.txt`` (planes, lines, event counts and
the heaviest names: read it before trusting code written against a trace)
and ``<dir>/<workload>.events.json``, the neutral events of the first
`--keep-ms` milliseconds of the traced window.  ``testdata/`` was made so.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--out", required=True)
    p.add_argument("--keep-ms", type=float, default=400.0)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _env

    _env.prepare(ROOT)
    from benchmark import cellrun, harness, trace_reduce

    keep: dict = {}
    result = cellrun.run_cell(args.workload, args.seed, args.seconds, True,
                              keep=keep)
    events = keep["trace"]["events"]
    os.makedirs(args.out, exist_ok=True)
    base = os.path.join(args.out, args.workload)
    with open(base + ".describe.txt", "w") as f:
        f.write("\n".join(trace_reduce.describe(events, 30)) + "\n")
    lo, hi = trace_reduce.window_of(events)
    cut = lo + int(args.keep_ms * 1e6)
    small = [e for e in events
             if e[2] == trace_reduce.WINDOW or (e[3] >= lo and e[3] + e[4] <= cut)]
    for e in small:
        if e[2] == trace_reduce.WINDOW:
            e[4] = min(e[4], cut - e[3])
    with open(base + ".events.json", "w") as f:
        json.dump(small, f)
    harness.result_line(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
