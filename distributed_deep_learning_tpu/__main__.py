"""Package entry point: ``python -m distributed_deep_learning_tpu <workload>``.

The reference is launched per-workload (``python CNN/main.py -m data ...``);
the equivalent here is ``python -m distributed_deep_learning_tpu cnn -m data
...`` with the identical flag surface (``-l -s -e -b -d -w -m -p -r``).
"""

from __future__ import annotations

import sys


def main(argv: list[str] | None = None):
    """Run one workload; returns ``run_workload``'s ``(state, history)``
    (None for ``--help`` and ``--spawn``, whose ranks own their states)."""
    argv = sys.argv[1:] if argv is None else list(argv)
    from distributed_deep_learning_tpu.workloads import WORKLOADS

    if not argv or argv[0] in ("-h", "--help"):
        print(f"usage: python -m distributed_deep_learning_tpu "
              f"{{{'|'.join(WORKLOADS)}}} [flags]\n"
              f"Run '<workload> -h' for the per-workload flag reference.")
        return
    name, rest = argv[0], argv[1:]
    if "--spawn" in rest:
        # reference -r semantics, process edition: fork -r local ranks that
        # rendezvous via jax.distributed (CNN/main.py:202's
        # torch.multiprocessing.spawn analogue; CPU — one chip can't be
        # shared, pods launch ranks via the scheduler instead)
        rest = [a for a in rest if a != "--spawn"]
        from distributed_deep_learning_tpu.runtime.launch import launch_local
        from distributed_deep_learning_tpu.utils.config import (Device,
                                                                parse_args)

        config = parse_args(rest, workload=name)
        n = config.world_size
        if n < 2:
            raise SystemExit("--spawn needs -r N with N >= 2")
        if config.device not in (None, Device.CPU):
            raise SystemExit(
                f"--spawn runs its ranks on the CPU (processes cannot "
                f"share a chip); it cannot honour -d "
                f"{config.device.value} — drop -d or pass -d cpu")
        for res in launch_local(n, [name, *rest]):
            sys.stdout.write(res.stdout)
        return
    from distributed_deep_learning_tpu.utils.config import parse_args
    from distributed_deep_learning_tpu.workloads import get_spec, run_workload

    spec = get_spec(name)
    return run_workload(spec, parse_args(rest, workload=name))


if __name__ == "__main__":
    main()
