"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints what set-up was made of, each number compared beside its limit,
and as its last line the result object.  Exits non-zero, with no result
line, where JAX finds no accelerator or too few chips.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "distributed_deep_learning_tpu")):
        print(f"bench: no system under test beside {ROOT}/benchmark: the "
              "package distributed_deep_learning_tpu is not in this "
              "directory", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _env

    _env.prepare(ROOT)
    from benchmark import cellrun, harness

    try:
        result = cellrun.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS_START)
    except harness.BenchFailure as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.result_line(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
