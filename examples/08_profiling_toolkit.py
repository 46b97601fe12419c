"""The profiling toolkit: see what XLA does with your model.

The reference's observability was print lines plus torch._dynamo graph
dumps; `utils/profiling` is the TPU-native equivalent.  This example
runs each diagnostic on a small train step:

* `cost_analysis` — XLA's FLOPs / bytes-accessed estimates, the inputs
  to a roofline model (`flops / bytes >= peak_flops / hbm_bw` means
  compute-bound).
* `hlo_text` / `compiled_text` — the program before and after XLA
  optimisation; fusion and layout decisions are visible in the latter.
* `StepTimer` — steps/sec with compile-step skip.
* `trace` — a TensorBoard/XProf device trace directory (inspect with
  `tensorboard --logdir`).

    python examples/08_profiling_toolkit.py          # 8 emulated devices
    python examples/08_profiling_toolkit.py --tpu    # the machine's chips
"""

import tempfile

import _bootstrap  # noqa: F401  (must precede jax import)
import jax
import numpy as np
import optax

from distributed_deep_learning_tpu.models.mlp import MLP
from distributed_deep_learning_tpu.obs import trace as obs_trace
from distributed_deep_learning_tpu.runtime.mesh import build_mesh
from distributed_deep_learning_tpu.train.objectives import cross_entropy_loss
from distributed_deep_learning_tpu.train.state import create_train_state
from distributed_deep_learning_tpu.train.step import make_step_fns, place_state
from distributed_deep_learning_tpu.utils import profiling


def main():
    mesh = build_mesh({"data": len(jax.devices())})
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 48)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 64)]

    model = MLP(hidden_size=256, num_hidden_layers=2, num_classes=5)
    state = create_train_state(model, jax.random.key(0), x[:1],
                               optax.sgd(0.05, momentum=0.9))
    state = place_state(state, mesh)
    train_step, _ = make_step_fns(mesh, cross_entropy_loss)

    # 1. the compiler's cost model for this exact step
    cost = profiling.cost_analysis(train_step, state, x, y)
    flops, byts = cost.get("flops", 0), cost.get("bytes accessed", 0)
    print(f"cost_analysis: {flops:.3g} FLOPs, {byts:.3g} bytes, "
          f"arithmetic intensity {flops / max(byts, 1):.1f} FLOPs/byte")

    # 2. before/after-optimisation HLO (fusion decisions live in the latter)
    pre = profiling.hlo_text(train_step, state, x, y)
    post = profiling.compiled_text(train_step, state, x, y)
    print(f"hlo_text: {len(pre.splitlines())} lines; compiled_text: "
          f"{len(post.splitlines())} lines, "
          f"{post.count('fusion')} fusion mentions")

    # 3. throughput meter (skips the compile step automatically)
    timer = profiling.StepTimer(warmup=1)
    for _ in range(6):
        state, m = train_step(state, x, y)
        float(m["loss"])                 # host fetch = device barrier
        timer.tick(examples=len(x))
    rates = timer.summary()
    print(f"StepTimer: {rates['steps_per_sec']:.1f} steps/s, "
          f"{rates['examples_per_sec']:.0f} examples/s")

    # 4. device trace for TensorBoard/XProf
    trace_dir = tempfile.mkdtemp()
    with profiling.trace(trace_dir):
        with obs_trace.span("profiled-step"):   # "ddl:profiled-step"
            state, m = train_step(state, x, y)
            float(m["loss"])
    import os
    n_files = sum(len(fs) for _, _, fs in os.walk(trace_dir))
    print(f"trace: wrote {n_files} file(s) under {trace_dir} "
          "(view: tensorboard --logdir <dir>)")
    assert flops > 0 and n_files > 0


if __name__ == "__main__":
    main()
