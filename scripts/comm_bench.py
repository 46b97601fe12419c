"""Microbench for the quantized + ring-overlapped FSDP collectives.

Measures the three claims ``parallel/collectives.py`` makes, on whatever
devices are present (8 fake CPU devices when run standalone):

* **wire bytes** — analytic per-step bytes for the explicit FSDP
  dataflow (param all-gather + grad reduce-scatter) under each wire
  format, and the int8/bf16 reduction vs fp32 (the >= 3x acceptance
  gate for int8);
* **overlap** — wall time of the fused ring ``gather_matmul`` (one
  program, transfer k+1 in flight during matmul k) vs the sum of a
  blocking all-gather and the consumer matmul run separately; the
  overlap fraction is how much of the gather's wire time the fused
  schedule hides, recorded through :class:`..obs.timeline.Timeline`
  spans and a ``comm_overlap_fraction`` gauge;
* **parity** — the explicit FSDP step with ``method="none"`` against
  the :mod:`..parallel.zero` annotation path (same mesh, same model,
  same optimizer — losses must agree), plus the int8+error-feedback
  loss drift against that reference.

    python scripts/comm_bench.py            # JSON record to stdout

``bench.py`` embeds the same :func:`run` as its ``collectives``
sub-record; ``scripts/tpu_validation.py`` re-runs it on real chips.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _script_env() -> None:
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    # only shapes the CPU backend, should that be the one JAX picks
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def _timed(fn, *args, steps: int, reps: int = 3) -> float:
    """Best-of-``reps`` mean seconds/call after one warm (compile) call,
    sync-honest; the min over repeats rejects scheduler-noise outliers."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


def run(rows: int = 512, cols: int = 2048, inner: int = 256,
        steps: int = 5, parity_steps: int = 3, registry=None) -> dict:
    """The collectives microbench record (see module docstring).

    ``rows`` is the per-shard block height for the overlap timing;
    ``registry`` (an ``obs.metrics.MetricsRegistry``) receives the
    ``comm_bytes{op,method}`` counters and the overlap gauge.
    """
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from distributed_deep_learning_tpu.models.mlp import MLP
    from distributed_deep_learning_tpu.obs.timeline import Timeline
    from distributed_deep_learning_tpu.parallel import collectives as coll
    from distributed_deep_learning_tpu.parallel.zero import fsdp_state_spec
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from distributed_deep_learning_tpu.runtime.shmap import shard_map
    from distributed_deep_learning_tpu.train.objectives import (
        cross_entropy_loss)
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.train.step import (make_step_fns,
                                                          place_state)

    from distributed_deep_learning_tpu.runtime.bootstrap import (
        require_devices)

    devices = require_devices(2)
    S = len(devices)
    mesh1d = build_mesh({"data": S})
    axis = "data"
    rng = np.random.default_rng(7)

    # ---- wire bytes: the explicit FSDP dataflow on an MLP's params ------
    geom_state = create_train_state(
        MLP(hidden_size=256, num_hidden_layers=2, num_classes=8),
        jax.random.key(0), jnp.zeros((1, 64)), optax.sgd(0.1))
    geom_spec = fsdp_state_spec(geom_state, mesh1d, axis=axis,
                                min_leaf_size=16)
    gdims = jax.tree.map(lambda s: coll._spec_dim(s, axis),
                         geom_spec.params)
    bytes_rec: dict = {}
    for method in coll.METHODS:
        st = coll.fsdp_wire_stats(geom_state.params, gdims, S, method)
        key = "fp32" if method == "none" else method
        bytes_rec[key] = {
            "all_gather": st["all_gather_bytes"],
            "reduce_scatter": st["reduce_scatter_bytes"],
        }
        if registry is not None and method != "none":
            registry.counter("comm_bytes", op="all_gather",
                             method=method).inc(st["all_gather_bytes"])
            registry.counter("comm_bytes", op="reduce_scatter",
                             method=method).inc(st["reduce_scatter_bytes"])
    total = {k: v["all_gather"] + v["reduce_scatter"]
             for k, v in bytes_rec.items()}
    bytes_rec["int8_reduction_x"] = round(total["fp32"] / total["int8"], 2)
    bytes_rec["bf16_reduction_x"] = round(total["fp32"] / total["bf16"], 2)

    # ---- numerics: quantized ring collectives vs the fp32 primitives ----
    # integer-valued floats: sums are exact, so the ring's different
    # reduction order must be BIT-equal to XLA's (the exactness gate);
    # the quantized rel-errs measure the wire format, not float reassoc
    blk = jnp.asarray(rng.integers(-8, 9, (S * 4, 32)), jnp.float32)

    def gathered(method, overlap):
        @partial(shard_map, mesh=mesh1d, in_specs=P(axis), out_specs=P(),
                 check_vma=False)
        def f(b):
            return coll.all_gather(b, axis, size=S, method=method,
                                   overlap=overlap)
        return np.asarray(f(blk))

    def scattered(method, overlap):
        @partial(shard_map, mesh=mesh1d, in_specs=P(), out_specs=P(axis),
                 check_vma=False)
        def f(b):
            c = b * (1.0 + jax.lax.axis_index(axis))
            return coll.reduce_scatter(c, axis, size=S, method=method,
                                       overlap=overlap)
        return np.asarray(f(blk))

    ref_g, ref_s = gathered("none", False), scattered("none", False)
    scale_g = float(np.max(np.abs(ref_g))) or 1.0
    scale_s = float(np.max(np.abs(ref_s))) or 1.0
    numerics = {
        "ring_all_gather_exact":
            bool((gathered("none", True) == ref_g).all()),
        "ring_reduce_scatter_exact":
            bool((scattered("none", True) == ref_s).all()),
    }
    for method in ("bf16", "int8"):
        numerics[f"{method}_all_gather_rel_err"] = round(float(
            np.max(np.abs(gathered(method, True) - ref_g))) / scale_g, 5)
        numerics[f"{method}_reduce_scatter_rel_err"] = round(float(
            np.max(np.abs(scattered(method, True) - ref_s))) / scale_s, 5)

    # ---- overlap: fused ring gather_matmul vs gather-then-matmul --------
    a = jnp.asarray(rng.standard_normal((S * rows, cols)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((cols, inner)), jnp.float32)

    gather_only = jax.jit(partial(
        shard_map, mesh=mesh1d, in_specs=P(axis), out_specs=P(),
        check_vma=False)(
            lambda x: coll.all_gather(x, axis, size=S, method="none")))
    matmul_only = jax.jit(lambda x, y: x @ y)

    def fused(overlap):
        return jax.jit(partial(
            shard_map, mesh=mesh1d, in_specs=(P(axis), P()), out_specs=P(),
            check_vma=False)(
                lambda x, y: coll.gather_matmul(x, y, axis, size=S,
                                                method="none",
                                                overlap=overlap)))

    tl = Timeline()
    with tl.span("comm_gather"):
        t_comm = _timed(gather_only, a, steps=steps)
    full = gather_only(a)
    with tl.span("comm_matmul"):
        t_mm = _timed(matmul_only, full, b, steps=steps)
    with tl.span("comm_ring"):
        t_ring = _timed(fused(True), a, b, steps=steps)
    with tl.span("comm_sequential"):
        t_seq = _timed(fused(False), a, b, steps=steps)
    # how much of the gather's time the ring schedule hides, measured
    # against the like-for-like sequential program (full all-gather, then
    # one matmul over the materialised operand): same bytes moved, same
    # FLOPs, only the schedule differs.  1.0 = the whole transfer fits
    # under the matmuls.  On CPU (sync collectives) the win comes from
    # consuming each chunk while hot instead of materialising the
    # (size*rows, cols) gathered operand; on TPU the double-buffered
    # ppermutes also pipeline the actual wire time
    fraction = max(0.0, min(1.0, (t_seq - t_ring) / t_comm)) \
        if t_comm > 0 else 0.0
    if registry is not None:
        registry.gauge("comm_overlap_fraction").set(fraction)
    overlap_rec = {
        "gather_seconds": round(t_comm, 6),
        "matmul_seconds": round(t_mm, 6),
        "ring_fused_seconds": round(t_ring, 6),
        "sequential_fused_seconds": round(t_seq, 6),
        "overlap_fraction": round(fraction, 4),
        "timeline_seconds": {k: round(v, 6)
                             for k, v in tl.seconds.items()},
    }

    # ---- parity: explicit FSDP step vs the zero.py annotation path ------
    shape = {"data": 2, "fsdp": S // 2} if S >= 4 and S % 2 == 0 \
        else {"data": 1, "fsdp": S}
    mesh = build_mesh(shape)
    model = MLP(hidden_size=64, num_hidden_layers=2, num_classes=8)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    y = jax.nn.one_hot(jnp.arange(16) % 8, 8)
    sh_axis = "fsdp" if mesh.shape.get("fsdp", 1) > 1 else "data"

    def fresh(attach=False):
        st = create_train_state(model, jax.random.key(0), x[:1],
                                optax.adam(1e-2))
        if attach:
            n = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
            st = coll.attach_residual(st, n)
        spec = fsdp_state_spec(st, mesh, axis=sh_axis, min_leaf_size=16)
        return place_state(st, mesh, spec), spec

    s_ann, spec_ann = fresh()
    step_ann, _ = make_step_fns(mesh, cross_entropy_loss,
                                state_spec=spec_ann)
    losses = {"annotation": [], "explicit_none": [], "explicit_int8_ef": []}
    for _ in range(parity_steps):
        s_ann, m = step_ann(s_ann, x, y)
        losses["annotation"].append(float(m["loss"]))
    for name, method, overlap, attach in (
            ("explicit_none", "none", False, False),
            ("explicit_int8_ef", "int8", True, True)):
        st, spec = fresh(attach=attach)
        step, _ = coll.make_fsdp_step_fns(
            mesh, cross_entropy_loss, state_spec=spec, method=method,
            overlap=overlap, axis=sh_axis)
        for _ in range(parity_steps):
            st, m = step(st, x, y)
            losses[name].append(float(m["loss"]))
    ref = losses["annotation"]
    parity = {
        "steps": parity_steps,
        "losses": {k: [round(v, 6) for v in vs] for k, vs in losses.items()},
        "explicit_none_max_abs_delta": round(max(
            abs(a - b) for a, b in zip(ref, losses["explicit_none"])), 8),
        "int8_ef_max_abs_delta": round(max(
            abs(a - b) for a, b in zip(ref, losses["explicit_int8_ef"])), 6),
    }

    return {
        "metric": "quantized + ring-overlapped FSDP collectives",
        "n_devices": S,
        "bytes": bytes_rec,
        "numerics": numerics,
        "overlap": overlap_rec,
        "parity": parity,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="microbench the quantized/ring FSDP collectives")
    p.add_argument("--rows", type=int, default=512,
                   help="per-shard block rows for the overlap timing")
    p.add_argument("--cols", type=int, default=2048)
    p.add_argument("--inner", type=int, default=256,
                   help="matmul output width")
    p.add_argument("--steps", type=int, default=5,
                   help="timed iterations per variant")
    p.add_argument("--parity-steps", type=int, default=3,
                   help="train steps for the loss-parity gate")
    args = p.parse_args(argv)
    from distributed_deep_learning_tpu.runtime.bootstrap import (
        describe_devices, enable_compile_cache)

    enable_compile_cache()
    rec = run(rows=args.rows, cols=args.cols, inner=args.inner,
              steps=args.steps, parity_steps=args.parity_steps)
    rec["device"] = describe_devices()
    print(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    _script_env()
    sys.exit(main())
