"""Local multi-process launcher — the ``torch.multiprocessing.spawn``
analogue.

The reference's ``-r N`` forks N local trainer processes over
``torch.multiprocessing.spawn`` (reference ``CNN/main.py:202``).  The JAX
equivalent launches N OS processes that rendezvous through
``jax.distributed.initialize`` (:mod:`.bootstrap`); each process owns its
local devices and the mesh spans all of them.  On a laptop/CI this runs the
REAL multi-process code paths — global device lists, the
``process_count() > 1`` loader branch, cross-process collectives over the
distributed service — on CPU (``force_cpu=True``), since a single TPU chip
cannot be shared by processes; on a pod the scheduler launches the
processes and this module is not involved.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Sequence


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_local(n_processes: int, argv: Sequence[str], *,
                 module: str = "distributed_deep_learning_tpu",
                 force_cpu: bool = True, devices_per_process: int = 1,
                 timeout: float | None = 600.0,
                 extra_env: dict[str, str] | None = None
                 ) -> list[subprocess.CompletedProcess]:
    """Run ``python -m <module> <argv>`` in ``n_processes`` rendezvousing
    processes; returns their CompletedProcess list (rank order).

    Raises ``RuntimeError`` if any rank exits nonzero (with its tail of
    output, stdout+stderr combined per rank).
    """
    import re

    port = free_port()
    procs: list[subprocess.Popen] = []
    for rank in range(n_processes):
        env = dict(os.environ)
        env.update(extra_env or {})
        env.update({
            "DDL_NUM_PROCESSES": str(n_processes),
            "DDL_PROCESS_ID": str(rank),
            "DDL_LOCAL_PROCESS_ID": str(rank),
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
        })
        if force_cpu:
            env["JAX_PLATFORMS"] = "cpu"
            # pin the child's own device count (a pytest parent's forced
            # 8-device flag must not leak into every rank)
            flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                           "", env.get("XLA_FLAGS", ""))
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{devices_per_process}").strip()
        procs.append(subprocess.Popen(
            [sys.executable, "-m", module, *argv], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    # drain every rank's pipe CONCURRENTLY: a crashing rank that fills its
    # 64KB pipe buffer would otherwise block, stall the collective its
    # peers wait on, and turn one rank's failure into a timeout that
    # discards the very log that explains it
    import threading
    import time as _time

    outputs = [""] * n_processes

    def drain(i: int, p: subprocess.Popen):
        outputs[i] = p.stdout.read()

    drainers = [threading.Thread(target=drain, args=(i, p), daemon=True)
                for i, p in enumerate(procs)]
    for t in drainers:
        t.start()
    deadline = None if timeout is None else _time.monotonic() + timeout
    results = []
    for rank, p in enumerate(procs):
        left = None if deadline is None else max(0.0,
                                                 deadline - _time.monotonic())
        try:
            p.wait(timeout=left)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
    for t in drainers:
        t.join(timeout=10)
    for rank, p in enumerate(procs):
        results.append(subprocess.CompletedProcess(p.args, p.returncode,
                                                   stdout=outputs[rank]))
    bad = [r for r in results if r.returncode != 0]
    if bad:
        tails = "\n---\n".join(r.stdout[-2000:] for r in bad)
        raise RuntimeError(f"{len(bad)}/{n_processes} ranks failed:\n{tails}")
    return results
