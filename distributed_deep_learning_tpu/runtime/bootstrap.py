"""Multi-host bootstrap: `jax.distributed` in place of MPI env sniffing.

The reference detects a distributed launch by scanning the environment for
``MPI_`` variables and reading ``OMPI_COMM_WORLD_*`` (``CNN/main.py:62-67``),
then calls ``torch.distributed.init_process_group`` with a backend chosen
from a hard-coded matrix — including a hard-coded head node
(``rtx2080-1.mit``) and NIC (``enp3s0``) at ``CNN/main.py:192-193``.

Here a single call covers every topology: on multi-host TPU pods,
``jax.distributed.initialize()`` picks coordinator/process-id from the TPU
runtime automatically; for MPI/SLURM launches we forward what
:class:`DistributedEnv` discovered.  Nothing is hard-coded; everything comes
from flags or the environment.
"""

from __future__ import annotations

import os

import jax

from distributed_deep_learning_tpu.obs.runlog import install_compile_log
from distributed_deep_learning_tpu.utils.config import Config, DistributedEnv

_INITIALIZED = False


def initialize_runtime(config: Config | None = None) -> DistributedEnv:
    """Idempotently initialise the distributed JAX runtime.

    Returns the effective process topology.  Safe to call in single-process
    runs (no-op).  Must run before the first device access on multi-host.
    """
    global _INITIALIZED
    dist = config.distributed if config is not None else DistributedEnv.from_environ()
    # Only latch once jax.distributed has actually been initialised — an
    # early single-process call must not turn a later multi-host call into
    # a silent no-op.
    if _INITIALIZED or not dist.is_distributed:
        return _effective_env(dist)

    kwargs = {}
    if dist.coordinator:
        kwargs = dict(
            coordinator_address=dist.coordinator,
            num_processes=dist.num_processes,
            process_id=dist.process_id,
        )
    # else: TPU pod — jax.distributed.initialize() autodetects everything.
    jax.distributed.initialize(**kwargs)
    _INITIALIZED = True
    return _effective_env(dist)


#: the checkout root (the directory holding the package)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places it from outside: when set, JAX
    reads it itself and no code here names a directory.  Otherwise the
    cache lives at ``<checkout>/.jax_cache`` — a fixed path, because the
    path is part of the cache key and a directory that moves never hits —
    unless the default backend is the CPU, which gets none (returns None):
    its compiles are cheap, and XLA:CPU logs two screens of machine-feature
    errors for every executable it loads back.  Call before the first
    compile; safe to call more than once.

    Every entry point comes through here before it builds a program, so
    this is also where the compile log starts listening
    (:func:`..obs.runlog.install_compile_log`: which program was traced,
    lowered, compiled or fetched, and for how long, by name).
    """
    install_compile_log("enable_compile_cache")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        if jax.default_backend() == "cpu":
            return None
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def describe_devices(devices=None) -> dict:
    """``{"platform", "device_kind", "device_count"}`` of `devices` (JAX's
    default backend when None) — the three keys every run log and every
    script's JSON line carries, so no number is read without its device."""
    devices = jax.devices() if devices is None else devices
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def require_devices(n: int) -> list:
    """JAX's default-backend devices, or a ``SystemExit`` naming the CPU
    rehearsal recipe when there are fewer than `n` — a script that needs
    a mesh says so instead of choosing a platform for its user."""
    devices = jax.devices()
    if len(devices) < n:
        raise SystemExit(
            f"needs {n} devices; the default backend "
            f"({devices[0].platform}, {devices[0].device_kind}) has "
            f"{len(devices)}. Rehearse on the CPU with: JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return devices


def _effective_env(dist: DistributedEnv) -> DistributedEnv:
    return DistributedEnv(
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        local_process_id=dist.local_process_id,
        coordinator=dist.coordinator,
    )


def is_coordinator() -> bool:
    """Rank-0 gate for logging (reference: ``verbose=rank==0``)."""
    return jax.process_index() == 0


def force_host_device_count(n: int) -> None:
    """Test helper: emulate an `n`-device host platform (the JAX analogue of
    the reference's fake CPU device list, ``LSTM/model.py:183``).

    Must be called before JAX initialises its backends — typically from a
    pytest conftest.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
