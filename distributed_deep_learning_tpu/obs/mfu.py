"""Model-FLOP utilization accounting.

MFU = achieved model FLOP/s ÷ (n_chips × chip peak bf16 FLOP/s).  The
numerator comes from XLA's own cost model on the *exact compiled train
step* (``utils.profiling.cost_analysis``), not an analytic 6ND guess —
so remat recompute, fused losses, and optimizer math are all counted the
way the compiler actually scheduled them.

The chip-peak table lives here.  On CPU there is no meaningful peak, so ``peak_flops``
is None and MFU is reported as None — unless ``DDL_OBS_PEAK_FLOPS`` is
set, which tests and CPU smoke runs use to exercise the full path.
"""

from __future__ import annotations

import os
from typing import Any, Callable

# Chip peak dense-bf16 FLOP/s by device_kind substring (ordered: first
# match wins; "lite" variants checked before their full-size siblings).
PEAK_BF16_FLOPS = (
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v5", 459e12),
    ("v4 lite", 138e12), ("v4i", 138e12), ("v4", 275e12),
    ("v3", 123e12), ("v2", 45e12),
)


def chip_peak_flops(device_kind: str) -> float | None:
    """Peak dense-bf16 FLOP/s for a device kind, None when unknown
    (CPU, GPU kinds not in the table).  ``DDL_OBS_PEAK_FLOPS`` overrides
    for CPU smoke runs and tests."""
    return chip_peak_flops_sourced(device_kind)[0]


def chip_peak_flops_sourced(device_kind: str
                            ) -> tuple[float | None, str | None]:
    """(peak, source) where source says where the number came from:
    ``"env_override"`` (``DDL_OBS_PEAK_FLOPS``) or ``"table"`` — the
    label that keeps a CPU-box MFU record (synthetic peak) from being
    read as a TPU-measured one."""
    env = os.environ.get("DDL_OBS_PEAK_FLOPS")
    if env:
        return float(env), "env_override"
    kind = device_kind.lower()
    for sub, peak in PEAK_BF16_FLOPS:
        if sub in kind:
            return peak, "table"
    return None, None


def measure_step_flops(step_fn: Callable, *args, n_devices: int | None = None,
                       **kwargs) -> float | None:
    """Total model FLOPs of one call of ``step_fn`` at these arguments,
    summed across devices.

    ``cost_analysis`` reports the per-executable flops of the SPMD
    program — i.e. one device's share — so the global number is
    flops × n_devices (the devices the step's mesh actually spans, which
    on a partial-mesh run is fewer than ``jax.device_count()``).  Returns None when the
    backend reports no flops key (some CPU builds).  NOTE: this
    lowers+compiles the step once; jit keeps its own dispatch cache, so
    the training run pays one extra compile when flop accounting is
    enabled (one-time, attributed to the run's compile span, excluded
    from steady-state overhead).
    """
    import jax

    from ..utils import profiling

    if n_devices is None:
        n_devices = jax.device_count()
    cost = profiling.cost_analysis(step_fn, *args, **kwargs)
    flops = cost.get("flops")
    if flops is None or flops <= 0:
        return None
    return float(flops) * n_devices


def mfu_record(step_flops: float | None, steps: float, seconds: float,
               n_devices: int, device_kind: str,
               peak_flops: float | None = None) -> dict[str, Any]:
    """Assemble the MFU report dict from measured pieces.

    ``step_flops`` is the GLOBAL (all-device) FLOPs of one step.  Any
    piece may be missing (None flops on odd backends, unknown peak on
    CPU); the record degrades field-by-field instead of failing.  Every
    record carries ``peak_flops_source`` (``table`` / ``env_override`` /
    ``caller`` / None) so readers can tell measured-hardware MFU from
    synthetic-peak smoke numbers.
    """
    source: str | None = "caller" if peak_flops is not None else None
    if peak_flops is None:
        peak_flops, source = chip_peak_flops_sourced(device_kind)
    steps_per_sec = steps / seconds if seconds > 0 else None
    achieved = (step_flops * steps_per_sec
                if step_flops and steps_per_sec else None)
    mfu = None
    if achieved and peak_flops and n_devices > 0:
        mfu = achieved / (n_devices * peak_flops)
    return {
        "step_flops": step_flops,
        "steps": steps,
        "seconds": seconds,
        "steps_per_sec": steps_per_sec,
        "achieved_flops_per_sec": achieved,
        "n_devices": n_devices,
        "device_kind": device_kind,
        "peak_flops_per_chip": peak_flops,
        "peak_flops_source": source,
        "mfu": mfu,
    }
