"""Shared workload runner: config → mesh → data → model → mode → train.

This is the TPU-native replacement for the reference's per-workload ``main``
modules, which copy-pasted CLI parsing, process setup, mode dispatch and the
training loop three times (``CNN/main.py:129-204``, ``LSTM/main.py:133-210``,
``MLP/main.py:41-140``).  Here each workload is a declarative
:class:`WorkloadSpec`; one :func:`run_workload` drives every mode:

=============  ==========================================================
mode           execution
=============  ==========================================================
sequential     1-device mesh, whole model, one jitted step
data           ``{"data": N}`` mesh, batch sharded, fused psum gradients
model          staged layers over N devices, activation transfers between
               stages (reference ``modelParallelismForward``)
pipeline       staged + microbatched (reference ``-p`` = microbatch SIZE)
=============  ==========================================================

``data`` mode fixes quirks Q1/Q2 by construction (gradient sync is a
consequence of sharding, not a bolt-on callable) unless the user opts back
into the reference behaviour with ``--no-sync``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from distributed_deep_learning_tpu.data.datasets import ArrayDataset
from distributed_deep_learning_tpu.data.loader import make_loaders
from distributed_deep_learning_tpu.data.splits import train_val_test_split
from distributed_deep_learning_tpu.obs import trace as obs_trace
from distributed_deep_learning_tpu.parallel.partition import validate_assignment
from distributed_deep_learning_tpu.parallel.staging import StagedModel
from distributed_deep_learning_tpu.runtime.bootstrap import (
    describe_devices, enable_compile_cache, initialize_runtime,
    is_coordinator)
from distributed_deep_learning_tpu.runtime.mesh import build_mesh
from distributed_deep_learning_tpu.train.loop import EpochResult, fit
from distributed_deep_learning_tpu.train.objectives import prediction_metrics
from distributed_deep_learning_tpu.train.state import create_train_state
from distributed_deep_learning_tpu.train.step import make_step_fns, place_state
from distributed_deep_learning_tpu.utils import profiling
from distributed_deep_learning_tpu.utils.config import Config, Device, Mode
from distributed_deep_learning_tpu.utils.logging import PhaseLogger


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """Everything that differs between the three reference workloads."""

    name: str
    # dataset: returns (features, targets) batches; config decides real vs
    # synthetic (real paths fall back to synthetic twins when /data is absent)
    build_dataset: Callable[[Config], Any]
    # the whole model (sequential/data modes)
    build_model: Callable[[Config, Any], Any]
    # the partitionable layer list (model/pipeline modes)
    build_layers: Callable[[Config, Any], Sequence[Any]]
    # layer→stage assignment (the reference's three partition algorithms)
    partitioner: Callable[[int, int], np.ndarray]
    # loss over (pred, target)
    build_loss: Callable[[Config], Callable]
    # optax transformation (the reference's per-workload optimizer/schedule)
    build_optimizer: Callable[[Config, int], optax.GradientTransformation]
    # (1, ...) example input for init, derived from the dataset
    example_input: Callable[[Config, Any], jnp.ndarray]
    # optional: tensor-parallel sharding rules (enables --mesh model=K)
    tp_rules: Callable[[Config], Any] | None = None
    # optional: (config, dataset, mesh) -> PipelinedLM-like model; when set,
    # `-m pipeline` runs the SPMD pipeline (stage mesh axis, one XLA
    # program) instead of MPMD staging
    build_pipelined: Callable[[Config, Any, Any], Any] | None = None
    # optional: (config, final_state, logger, dataset) hook after
    # training — e.g. the gpt workload's --generate sample printer
    post_train: Callable[[Config, Any, Any, Any], None] | None = None
    # optional: (config, dataset) validation BEFORE training starts —
    # rejects configs whose post_train hook would fail only after the
    # expensive part has already run (e.g. --generate N > what the
    # dataset-derived max_len admits)
    pre_train_check: Callable[[Config, Any], None] | None = None


def config_dtype(config: Config) -> jnp.dtype:
    """The compute dtype the ``--dtype`` flag selects."""
    return jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32


def _decay_mask(params):
    """Standard AdamW/LAMB recipe: weight decay applies to matrices and
    conv kernels only — biases, LayerNorm/BatchNorm scales and other
    vectors (ndim < 2) are exempt."""
    return jax.tree.map(lambda p: jnp.ndim(p) >= 2, params)


def adamw(learning_rate, weight_decay: float = 1e-4):
    """``optax.adamw`` with the bias/norm decay exemption applied."""
    return optax.adamw(learning_rate, weight_decay=weight_decay,
                       mask=_decay_mask)


def build_optimizer(spec: "WorkloadSpec", config: Config, epoch_steps: int
                    ) -> optax.GradientTransformation:
    """The workload's optimizer recipe, overridable by ``--optimizer``.

    ``auto`` keeps the per-workload default (sgd+momentum for vision,
    adamw for the LM families — matching each reference main's choice);
    anything else builds that optax transform at ``--lr`` with the
    ``--schedule`` machinery applied.  ``adafactor`` is the TPU big-model
    staple: factored second moments give sublinear optimizer memory, and
    its state composes with ``--zero`` (the sharding specs are derived by
    walking the actual state pytree, whatever its structure).
    """
    if config.optimizer == "auto":
        return spec.build_optimizer(config, epoch_steps)
    lr = resolve_lr(config, epoch_steps, config.learning_rate)
    return {
        "sgd": lambda: optax.sgd(lr),
        "momentum": lambda: optax.sgd(lr, momentum=0.9),
        "adam": lambda: optax.adam(lr),
        "adamw": lambda: adamw(lr),
        "adafactor": lambda: optax.adafactor(learning_rate=lr),
        # optax.lamb defaults weight_decay to 0.0 — pass the canonical
        # LAMB decay explicitly or the mask would exempt nothing
        "lamb": lambda: optax.lamb(lr, weight_decay=1e-2, mask=_decay_mask),
    }[config.optimizer]()


def resolve_lr(config: Config, epoch_steps: int, base_lr: float):
    """``--schedule``/``--warmup`` → a scalar LR or an optax schedule.

    ``cosine`` peaks at ``base_lr`` and decays over the whole run (the
    ResNet/BERT recipe); ``rsqrt`` is the transformer-base Noam schedule
    (its absolute scale comes from d_model/warmup, not ``--lr``); ``step``
    is the reference's StepLR(7 epochs, x0.1) generalised.  Default warmup
    when unset: 5% of total steps.
    """
    if config.lr_schedule == "none":
        return base_lr
    total = max(2, config.epochs * max(1, epoch_steps))
    # None = auto (5% of total); an EXPLICIT --warmup 0 disables warmup
    warm = config.warmup_steps if config.warmup_steps is not None \
        else max(1, total // 20)
    warm = min(warm, total - 1)
    from distributed_deep_learning_tpu.train import schedules

    if config.lr_schedule == "cosine":
        return schedules.warmup_cosine(base_lr, warm, total)
    if config.lr_schedule == "rsqrt":
        return schedules.warmup_rsqrt(config.size, warm)
    if config.lr_schedule == "step":
        return schedules.step_decay(base_lr,
                                    steps_per_drop=7 * max(1, epoch_steps))
    raise ValueError(f"unknown --schedule {config.lr_schedule!r}")


def example_from_dataset(config: Config, dataset) -> jnp.ndarray:
    """A (1, ...) zero example with the dataset's feature shape — keeps
    input widths data-driven (fixes reference quirk Q6)."""
    x, _ = dataset.batch(np.arange(1))
    return jnp.zeros((1,) + x.shape[1:], jnp.float32)


def _devices(config: Config) -> list[jax.Device]:
    """The devices ``-d`` names: ``cpu`` even when an accelerator is
    present, JAX's default backend when the flag is unset — and an
    explicit ``tpu`` (``gpu`` aliases it) only where that default backend
    IS a TPU, so a run that asked for the chip never trains on the host
    and exits 0."""
    if config.device is Device.CPU:
        return jax.devices("cpu")
    devices = jax.devices()
    if config.device is not None and devices[0].platform != "tpu":
        raise ValueError(
            f"-d {config.device.value} asked for a TPU but JAX's default "
            f"backend is {devices[0].platform!r} "
            f"({devices[0].device_kind}); drop -d to run on the default "
            "backend, or pass -d cpu")
    return devices


# ---------------------------------------------------------------------------
# MP / PP: staged training over explicit devices (MPMD)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StagedState:
    """Mutable-by-replacement state for staged training: per-stage params,
    model-state and optimizer-state lists (each co-located with its stage's
    device; per-stage optimizer updates are equivalent to a global update
    because optax transforms are element-wise per leaf)."""

    step: int
    params: list[Any]          # per-stage params pytrees
    model_state: list[Any]     # per-stage non-param collections (batch stats)
    opt_state: list[optax.OptState]  # per-stage, co-located with params


class StagedTrainer:
    """Trains a :class:`StagedModel` with per-stage device placement.

    The reference's `model`/`pipeline` modes train straight through the
    staged forward (autograd replays across the ``.to(device)`` boundaries,
    ``MLP/model.py:77-130``); this does the same with ``jax.grad`` through
    ``jax.device_put`` stage transfers.  Per-stage applies are jitted;
    JAX's async dispatch overlaps microbatch *k* on stage *s* with *k+1* on
    stage *s-1* — fill/drain emerges from the dependency graph.
    """

    def __init__(self, staged: StagedModel, devices: Sequence[jax.Device],
                 loss_fn: Callable, tx: optax.GradientTransformation,
                 microbatch_size: int | None = None):
        if len(devices) != len(staged.stages):
            raise ValueError(f"{len(staged.stages)} stages need as many "
                             f"devices, got {len(devices)}")
        self.staged = staged
        self.devices = list(devices)
        self.loss_fn = loss_fn
        self.tx = tx
        self.microbatch_size = microbatch_size
        self._update = jax.jit(self.tx.update)
        # per-stage jitted applies; the train variant is keyed by its
        # mutable-collection tuple (known only once variables exist)
        self._eval_fns = [
            jax.jit(partial(stage.apply, train=False))
            for stage in staged.stages]
        self._train_fns: dict[tuple[int, tuple[str, ...]], Callable] = {}

    def _train_fn(self, i: int, mutable: tuple[str, ...]) -> Callable:
        key = (i, mutable)
        if key not in self._train_fns:
            stage = self.staged.stages[i]
            if mutable:
                fn = partial(stage.apply, train=True, mutable=list(mutable))
            else:
                fn = partial(stage.apply, train=True)
            self._train_fns[key] = jax.jit(fn)
        return self._train_fns[key]

    def init(self, rng: jax.Array, example: jnp.ndarray) -> StagedState:
        variables = self.staged.init(rng, example)
        params = [dict(v)["params"] for v in variables]
        model_state = [{k: v for k, v in dict(vs).items() if k != "params"}
                       for vs in variables]
        params = [jax.device_put(p, d) for p, d in zip(params, self.devices)]
        model_state = [jax.device_put(ms, d)
                       for ms, d in zip(model_state, self.devices)]
        # one optimizer state PER STAGE, co-located with its params — the
        # element-wise optax transforms make per-stage updates identical to
        # a global update, and each stage's update runs on its own device
        opt_state = [self.tx.init(p) for p in params]
        return StagedState(step=0, params=params, model_state=model_state,
                           opt_state=opt_state)

    # -- forward walks -------------------------------------------------------
    def _walk(self, params: list[Any], model_state: list[Any],
              x: jnp.ndarray, train: bool) -> tuple[jnp.ndarray, list[Any]]:
        new_ms = []
        for i, (p, ms, d) in enumerate(zip(params, model_state, self.devices)):
            x = jax.device_put(x, d)
            v = {"params": p, **ms}
            mutable = tuple(ms)
            if train and mutable:
                x, upd = self._train_fn(i, mutable)(v, x)
                new_ms.append({**ms, **upd})
            elif train:
                x = self._train_fn(i, ())(v, x)
                new_ms.append(ms)
            else:
                x = self._eval_fns[i](v, x)
                new_ms.append(ms)
        return x, new_ms

    def _chunks(self, x: jnp.ndarray) -> list[jnp.ndarray]:
        mb = self.microbatch_size
        if not mb or mb >= len(x):
            return [x]
        # reference -p semantics: fixed SIZE, ragged tail kept
        return [x[i:i + mb] for i in range(0, len(x), mb)]

    def forward(self, params, model_state, x, train=False):
        """Microbatched (pipeline) or whole-batch (model) staged forward."""
        outs, ms = [], model_state
        for chunk in self._chunks(x):
            y, ms = self._walk(params, ms, chunk, train)
            outs.append(y)
        return (outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)), ms

    # -- steps ---------------------------------------------------------------
    def train_step(self, state: StagedState, x, y):
        # targets meet the prediction on the final stage's device (the
        # reference computes loss where the last stage's output lands too)
        y = jax.device_put(y, self.devices[-1])

        def compute(params):
            pred, new_ms = self.forward(params, state.model_state, x, train=True)
            loss = self.loss_fn(pred, y)
            return loss, (pred, new_ms)

        (loss, (pred, new_ms)), grads = jax.value_and_grad(
            compute, has_aux=True)(state.params)
        params, opt_state = [], []
        for g, o, p in zip(grads, state.opt_state, state.params):
            upd, new_o = self._update(g, o, p)
            params.append(optax.apply_updates(p, upd))
            opt_state.append(new_o)
        metrics = prediction_metrics(pred, y, loss)
        return StagedState(state.step + 1, params, new_ms, opt_state), metrics

    def eval_step(self, state: StagedState, x, y):
        y = jax.device_put(y, self.devices[-1])
        pred, _ = self.forward(state.params, state.model_state, x, train=False)
        return prediction_metrics(pred, y, self.loss_fn(pred, y))


def _maybe_checkpointer(config: Config):
    """(checkpointer, resume point) from config.

    Returns ``(ckpt, ckpt_step, start_epoch, resume_batch, resume_totals)``
    — ``resume_batch > 0`` means mid-epoch resume at that batch of
    ``start_epoch`` (``--checkpoint-every`` step saves record the loader
    position in the sidecar)."""
    if not config.checkpoint_dir:
        return None, None, 1, 0, None
    from distributed_deep_learning_tpu.train.elastic import resume_point
    from distributed_deep_learning_tpu.utils.checkpoint import Checkpointer

    ckpt = Checkpointer(config.checkpoint_dir)
    if not config.resume:
        last = ckpt.latest_step()
        if not config.elastic and last is not None:
            # a dirty dir without --resume would let this run's saves be
            # silently skipped in favour of the OLD run's steps (save()
            # skips already-finalised ids) — refuse up front.  --elastic
            # is exempt: its whole contract is resume-on-restart (and it
            # logs what it restored).
            ckpt.close()
            raise ValueError(
                f"--checkpoint-dir {config.checkpoint_dir} already holds "
                f"checkpoints (latest step {last}): pass --resume to "
                "continue it, or point at a fresh directory")
        return ckpt, None, 1, 0, None
    return (ckpt, *resume_point(ckpt))


def _restore_resume(ckpt, state, ckpt_step, start_epoch, resume_batch,
                    resume_totals, logger, restore_fn=None, telemetry=None):
    """Verified restore for non-elastic ``--resume``.

    Integrity fallback: when the requested step is torn/corrupt it is
    quarantined and the newest verified-good step restores instead — the
    resume point is then re-decoded from the step ACTUALLY restored, so
    the loader replay and phase totals stay consistent with the params.
    ``restore_fn`` (same contract as ``restore_verified``) swaps in the
    resharding restore under ``--reshard``; with ``telemetry`` that case
    lands in the ``reshard`` span, a plain verified restore in
    ``recovery`` (the elastic path records its own recovery spans)."""
    from distributed_deep_learning_tpu.train.elastic import resume_point

    if telemetry is None:
        restored, used = (restore_fn or ckpt.restore_verified)(state,
                                                               step=ckpt_step)
    else:
        kind = "reshard" if restore_fn is not None else "recovery"
        with telemetry.timeline.span(kind):
            restored, used = (restore_fn or
                              ckpt.restore_verified)(state, step=ckpt_step)
    if used is None:
        logger.info("checkpoint integrity: no verifiable checkpoint "
                    "survives; starting fresh")
        return state, 1, 0, None
    if used != ckpt_step:
        logger.info(f"checkpoint integrity: step {ckpt_step} failed "
                    f"verification (quarantined); resuming from verified "
                    f"step {used}")
        _, start_epoch, resume_batch, resume_totals = \
            resume_point(ckpt, step=used)
    logger.info(f"resumed mid-epoch {start_epoch} at step {resume_batch}"
                if resume_batch else
                f"resumed from epoch {start_epoch - 1}")
    return restored, start_epoch, resume_batch, resume_totals


def derive_state_spec(spec: WorkloadSpec, config: Config, mesh, state):
    """Sharding spec for the train state under (``--mesh``, ``--zero``):
    tensor-parallel rules when the mesh has model/expert axes, ZeRO-1/fsdp
    sharding otherwise, replicated by default.  Shared by the trainer and
    the tune/ trial harness so a measured trial exercises the exact specs
    training would use."""
    state_spec = P()
    if mesh.shape.get("model", 1) > 1 or mesh.shape.get("expert", 1) > 1:
        if spec.tp_rules is None:
            raise ValueError(f"workload {spec.name!r} has no "
                             "tensor-parallel sharding rules")
        if config.zero != "none":
            raise ValueError("--zero with a model axis is not supported "
                             "yet; use fsdp_axis in the TP rules instead")
        from distributed_deep_learning_tpu.parallel.tensor_parallel import (
            tp_state_spec, validate_divisibility)

        rules = spec.tp_rules(config)
        validate_divisibility(state.params, mesh, rules)
        state_spec = tp_state_spec(state, rules)
    elif config.zero != "none":
        from distributed_deep_learning_tpu.parallel.zero import (
            fsdp_state_spec, zero1_state_spec)

        axis = "fsdp" if mesh.shape.get("fsdp", 1) > 1 else "data"
        make_spec = zero1_state_spec if config.zero == "1" \
            else fsdp_state_spec
        state_spec = make_spec(state, mesh, axis=axis)
    elif getattr(state, "comm_residual", None) is not None:
        # pure DP with an int8 error-feedback residual (--grad-compress
        # int8): replicated state, but the residual is per-shard and must
        # be PLACED that way or the compressed step's donation breaks
        from distributed_deep_learning_tpu.parallel.zero import (
            dp_state_spec)

        state_spec = dp_state_spec(state)
    return state_spec


def attach_comm_residual(config: Config, mesh, state):
    """Zero-init the error-feedback residual on ``state`` when an int8
    communication path is active (``--comm int8`` or ``--grad-compress
    int8``).  Must run BEFORE deriving sharding specs — the zero/
    spec builders map ``comm_residual`` alongside the other fields."""
    if config.comm != "int8" and config.grad_compress != "int8":
        return state
    from distributed_deep_learning_tpu.parallel.collectives import (
        attach_residual)

    n = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
    if n <= 1:
        return state   # single shard: nothing crosses the wire
    return attach_residual(state, n)


def make_train_eval_steps(config: Config, mesh, loss_fn, state_spec,
                          sentinel=None, registry=None):
    """(train_step, eval_step) for the SEQUENTIAL/DATA family, dispatching
    to the compressed / accumulating / plain step builders exactly as the
    trainer does (flag combinations the builders cannot honour are
    rejected, not silently dropped).  Shared with the tune/ trial harness.
    """
    if config.comm != "none":
        if config.zero != "fsdp" or config.grad_accum > 1 \
                or config.grad_compress != "none" \
                or mesh.shape.get("model", 1) > 1 \
                or mesh.shape.get("expert", 1) > 1:
            raise ValueError(
                "--comm quantizes the explicit FSDP collectives "
                "(parallel/collectives.py); it requires --zero fsdp and "
                "does not compose with --grad-accum/--grad-compress/"
                "--mesh model/expert axes")
        from distributed_deep_learning_tpu.parallel.collectives import (
            make_fsdp_step_fns)

        axis = "fsdp" if mesh.shape.get("fsdp", 1) > 1 else "data"
        return make_fsdp_step_fns(
            mesh, loss_fn, state_spec=state_spec, method=config.comm,
            overlap=config.comm_overlap, axis=axis, remat=config.remat,
            remat_policy=config.remat_policy, registry=registry)
    if config.grad_compress != "none":
        if config.zero != "none" or config.grad_accum > 1 \
                or mesh.shape.get("model", 1) > 1 \
                or mesh.shape.get("expert", 1) > 1:
            raise ValueError(
                "--grad-compress applies to the pure data-parallel "
                "gradient all-reduce; it does not compose with "
                "--zero/--grad-accum/--mesh model/expert axes (for "
                "compressed ZeRO/FSDP collectives use --comm bf16|int8, "
                "parallel/collectives.py)")
        from distributed_deep_learning_tpu.train.compress import (
            make_compressed_step_fns)

        return make_compressed_step_fns(
            mesh, loss_fn, method=config.grad_compress,
            remat=config.remat, remat_policy=config.remat_policy)
    if config.grad_accum > 1:
        if config.remat:
            # rejected, not silently dropped (round-1 advisor
            # principle): the accumulation scan has no remat wiring
            raise ValueError("--remat with --grad-accum is not "
                             "implemented; drop one of the two")
        from distributed_deep_learning_tpu.train.accumulate import (
            make_accum_step_fns)

        return make_accum_step_fns(
            mesh, loss_fn, accum_steps=config.grad_accum,
            state_spec=state_spec)
    return make_step_fns(
        mesh, loss_fn, state_spec=state_spec, remat=config.remat,
        remat_policy=config.remat_policy, sentinel=sentinel)


def mesh_devices(shape: dict[str, int], devices):
    """The device prefix an explicit mesh shape occupies: a plan's
    1-device corner must run on an 8-device box (axis product < device
    count), while a -1 fill keeps every device."""
    n = 1
    for s in shape.values():
        if s == -1:
            return devices
        n *= s
    return devices[:n] if n <= len(devices) else devices


def _sentinel_config(config: Config):
    """``--sentinel`` → a :class:`..train.sentinel.SentinelConfig` (or
    None), validated against flags whose step builders have no sentinel
    wiring — rejected, not silently dropped."""
    if config.sentinel == "off":
        return None
    from distributed_deep_learning_tpu.train.sentinel import SentinelConfig

    unsupported = [(config.grad_accum > 1, "--grad-accum"),
                   (config.grad_compress != "none", "--grad-compress"),
                   (config.comm != "none", "--comm")]
    bad = [flag for cond, flag in unsupported if cond]
    if bad:
        raise ValueError(f"--sentinel does not compose with "
                         f"{', '.join(bad)} (those flags build their own "
                         "train step without the sentinel's in-step "
                         "containment)")
    return SentinelConfig(policy=config.sentinel,
                          window=config.sentinel_window,
                          spike_factor=config.sentinel_factor,
                          loss_spike_factor=config.sentinel_factor)


def _fit_elastic(config: Config, logger, make_state, train_step, eval_step,
                 loaders, ckpt, sentinel=None, restore_fn=None,
                 telemetry=None):
    """``--elastic``: checkpointed restart on worker failure or runtime
    error, with optional heartbeat-based liveness detection
    (``--heartbeat-dir``) polled before every step."""
    from distributed_deep_learning_tpu.train.elastic import fit_with_recovery

    if ckpt is None:
        raise ValueError("--elastic requires --checkpoint-dir (recovery "
                         "restores from the epoch checkpoints)")
    hb = monitor = None
    if config.heartbeat_dir:
        from distributed_deep_learning_tpu.utils.failures import (
            FailureMonitor, Heartbeat)

        rank = config.distributed.process_id
        hb = Heartbeat(config.heartbeat_dir, rank).start()
        monitor = FailureMonitor(
            config.heartbeat_dir, config.distributed.num_processes,
            timeout=config.heartbeat_timeout, self_rank=rank).start()
    try:
        with profiling.trace(config.profile_dir):
            return fit_with_recovery(make_state, train_step, eval_step,
                                     loaders, epochs=config.epochs,
                                     checkpointer=ckpt, logger=logger,
                                     monitor=monitor,
                                     checkpoint_every=config.checkpoint_every,
                                     sentinel=sentinel,
                                     restore_fn=restore_fn,
                                     telemetry=telemetry)
    finally:
        if monitor is not None:
            monitor.stop()
        if hb is not None:
            hb.stop()
        ckpt.close()


def _make_1f1b_train_step(mesh, model, loss_fn, state_spec, microbatch,
                          interleaved: bool = False):
    """Train step for a :class:`..models.pipelined_lm.PipelinedLM` under the
    1F1B schedule (:func:`..parallel.spmd_pipeline.spmd_pipeline_1f1b`) or
    its interleaved variant (``--virtual-stages`` chunks per device,
    :func:`..parallel.spmd_pipeline.spmd_pipeline_interleaved`):
    embed runs outside (its backward fed by the pipeline's dx), the LM head
    + loss run on the last stage inside the pipeline (the cotangent seed
    must exist the moment a microbatch leaves the last stage)."""
    from jax.sharding import NamedSharding

    from distributed_deep_learning_tpu.data.loader import BATCH_AXES
    from distributed_deep_learning_tpu.parallel.spmd_pipeline import (
        spmd_pipeline_1f1b, spmd_pipeline_interleaved)
    from distributed_deep_learning_tpu.train.step import _state_sharding

    state_sh = _state_sharding(mesh, state_spec)
    batch_sh = NamedSharding(mesh, P(BATCH_AXES))
    repl = NamedSharding(mesh, P())
    stage_fn = model.trunk.stage_fn()

    def head_loss(hp, h_mb, y_mb):
        logits = model.head.apply({"params": hp}, h_mb)
        loss = loss_fn(logits, y_mb)
        from distributed_deep_learning_tpu.train.objectives import (
            prediction_metrics)
        return loss, prediction_metrics(logits, y_mb, loss)

    def train_step(state, x, y):
        h, embed_vjp = jax.vjp(
            lambda ep: model.embed.apply({"params": ep}, x),
            state.params["embed"])
        pipeline = (spmd_pipeline_interleaved if interleaved
                    else spmd_pipeline_1f1b)
        # --dropout: per-(stage, microbatch) keys derived inside the
        # pipeline; the rematerialised backward replays the same keys, so
        # the hand-rolled schedules stay exact (previously gpipe-only)
        rngs = state.step_rngs()
        fn = stage_fn if rngs is None else model.trunk.stage_fn_train()
        loss, tg, hg, dh, aux = pipeline(
            fn, head_loss, state.params["trunk"],
            state.params["head"], h, y, mesh=mesh,
            microbatch_size=microbatch, has_aux=True,
            rng=None if rngs is None else rngs["dropout"])
        (de,) = embed_vjp(dh.astype(h.dtype))
        grads = {"embed": de,
                 "trunk": jax.tree.map(lambda g, p: g.astype(p.dtype), tg,
                                       state.params["trunk"]),
                 "head": jax.tree.map(lambda g, p: g.astype(p.dtype), hg,
                                      state.params["head"])}
        metrics = dict(aux)
        metrics["loss"] = loss  # batch-mean (Q9 convention), not the Σ aux
        return state.apply_gradients(grads), metrics

    return jax.jit(train_step,
                   in_shardings=(state_sh, batch_sh, batch_sh),
                   out_shardings=(state_sh, repl),
                   donate_argnums=(0,))


def _run_spmd_pipelined(spec: WorkloadSpec, config: Config, devices, logger,
                        dataset, splits, example, loss_fn, tx, rng,
                        telemetry=None) -> tuple[Any, list[EpochResult]]:
    """`-m pipeline` over the SPMD `stage` axis: one jitted step, stacked
    stage params sharded over `stage`, activations rotated with ppermute —
    replaces MPMD staging for workloads that declare ``build_pipelined``.

    Composes with data parallelism: leftover devices form the `data` axis,
    so ``--nstages 4`` on 8 devices runs a 2-way-DP 4-stage pipeline.
    """
    from distributed_deep_learning_tpu.parallel.tensor_parallel import (
        tp_state_spec)
    from distributed_deep_learning_tpu.train.state import TrainState

    n_dev = len(devices)
    n_layers = config.num_layers
    if config.num_stages:
        n_stages = config.num_stages
    else:
        # largest stage count that divides both the trunk depth and the
        # device count (so the remainder forms a whole `data` axis)
        n_stages = max((s for s in range(1, n_dev + 1)
                        if n_layers % s == 0 and n_dev % s == 0), default=1)
    if n_stages > n_dev:
        raise ValueError(f"--nstages {n_stages} exceeds {n_dev} devices")
    if n_dev % n_stages:
        raise ValueError(f"--nstages {n_stages} must divide the device "
                         f"count {n_dev} (the rest becomes the data axis)")
    if config.pipeline_schedule == "interleaved" and \
            config.virtual_stages < 2:
        raise ValueError(f"--pipeline-schedule interleaved needs "
                         f"--virtual-stages >= 2 (got "
                         f"{config.virtual_stages}); with one chunk per "
                         "device use --pipeline-schedule 1f1b")
    if config.grad_compress != "none":
        raise ValueError("--grad-compress targets the pure data-parallel "
                         "gradient all-reduce; the SPMD pipeline's gradient "
                         "dataflow is stage-sharded (use -m data)")
    if config.remat_policy != "nothing" and \
            config.pipeline_schedule in ("1f1b", "interleaved"):
        # rejected BEFORE model build: the hand-scheduled pipeline
        # backward hard-codes its own block remat, so a policy here
        # would be a silent no-op
        raise ValueError("--remat-policy has no effect under "
                         "--pipeline-schedule 1f1b/interleaved")
    if config.sentinel != "off":
        raise ValueError("--sentinel supports -m sequential/data (the "
                         "fused train step); the SPMD pipeline's staged "
                         "step has no sentinel wiring yet")
    dp = n_dev // n_stages
    mesh = build_mesh({"data": dp, "stage": n_stages},
                      devices[:dp * n_stages])
    logger.info(f"SPMD pipeline: {n_stages} stages x {dp}-way data parallel")

    # the microbatch (reference -p SIZE) must divide the global batch and be
    # divisible by the data-parallel degree; snap to the nearest valid size
    # (B itself is always valid: the loader guarantees B % dp == 0)
    B, mb = config.batch_size, config.microbatch or dp
    if mb % dp or B % mb:
        valid = [d for d in range(dp, B + 1, dp) if B % d == 0]
        snapped = min(valid, key=lambda d: (abs(d - mb), d))
        logger.info(f"microbatch {mb} incompatible with batch {B} / "
                    f"dp {dp}; using {snapped}")
        config = config.replace(microbatch=snapped)

    model = spec.build_pipelined(config, dataset, mesh)
    train_rng = (jax.random.key(config.seed + 1)
                 if config.dropout > 0 else None)
    state = TrainState.create(apply_fn=model.apply_fn,
                              params=model.init(rng, example), tx=tx,
                              rng=train_rng)
    state_spec = tp_state_spec(state, model.shard_rules)
    state = place_state(state, mesh, state_spec)
    train_step, eval_step = make_step_fns(mesh, loss_fn,
                                          state_spec=state_spec,
                                          remat=config.remat,
                                          remat_policy=config.remat_policy)
    if config.pipeline_schedule in ("1f1b", "interleaved"):
        # hand-scheduled backward: O(stages) activation residency instead
        # of the scan-transpose's O(microbatches); interleaved additionally
        # fills the bubble with --virtual-stages chunks per device
        train_step = _make_1f1b_train_step(
            mesh, model, loss_fn, state_spec, config.microbatch,
            interleaved=config.pipeline_schedule == "interleaved")
    loaders = make_loaders(dataset, splits, config.batch_size, mesh,
                           seed=config.seed)
    if telemetry is not None:
        _measure_train_flops(telemetry, train_step, state, loaders[0],
                             n_devices=mesh.size)
    ckpt, ckpt_step, start_epoch, resume_batch, resume_totals = \
        _maybe_checkpointer(config)
    if config.elastic:
        def make_state():
            s = TrainState.create(apply_fn=model.apply_fn,
                                  params=model.init(rng, example), tx=tx,
                                  rng=train_rng)
            return place_state(s, mesh, state_spec)

        return _fit_elastic(config, logger, make_state, train_step,
                            eval_step, loaders, ckpt, telemetry=telemetry)
    if ckpt is not None and ckpt_step is not None:
        state, start_epoch, resume_batch, resume_totals = _restore_resume(
            ckpt, state, ckpt_step, start_epoch, resume_batch,
            resume_totals, logger, telemetry=telemetry)
    try:
        with profiling.trace(config.profile_dir):
            return fit(state, train_step, eval_step, *loaders,
                       epochs=config.epochs, logger=logger,
                       checkpointer=ckpt, start_epoch=start_epoch,
                       checkpoint_every=config.checkpoint_every,
                       resume_batch=resume_batch,
                       resume_totals=resume_totals,
                       publish_dir=config.publish_weights,
                       telemetry=telemetry)
    finally:
        if ckpt is not None:
            ckpt.close()


# ---------------------------------------------------------------------------
# Telemetry (obs/) wiring
# ---------------------------------------------------------------------------

def _maybe_telemetry(config: Config):
    """``--obs`` → a :class:`..obs.RunTelemetry` for this process.

    Every process records (structured history must survive on every
    rank, same principle as the PhaseLogger JSONL fix); non-coordinator
    sidecars get a ``.rankN`` suffix so a shared filesystem holds one
    stream per process, mergeable offline via
    ``obs.metrics.merge_snapshots``."""
    if not config.obs:
        return None
    from distributed_deep_learning_tpu.obs import (FlightRecorder,
                                                   RunTelemetry)

    def _rank(p: str | None) -> str | None:
        if p is None or is_coordinator():
            return p
        return f"{p}.rank{config.distributed.process_id}"

    recorder = None
    if config.obs_blackbox:
        # real-clocked outside drills (utils/chaos.py owns the
        # clock=None deterministic mode); install() registers the
        # atexit + SIGTERM dump hooks so preemption leaves a black box
        import time as _time

        recorder = FlightRecorder(clock=_time.perf_counter)
        recorder.install(path=_rank(config.obs_blackbox))
    return RunTelemetry(_rank(config.obs_file or "obs_events.jsonl"),
                        trace_path=_rank(config.obs_trace),
                        recorder=recorder,
                        rotate_mb=config.obs_rotate_mb,
                        fsync_on_rollover=config.obs_rotate_mb is not None)


def _log_obs_summary(logger, summary: dict) -> None:
    """One human-readable goodput/MFU line at run end (the full detail
    lives in the JSONL stream for scripts/obs_report.py)."""
    gp = summary.get("goodput")
    if not gp:
        return
    fr = gp["fractions"]
    parts = " ".join(f"{c}={fr[c]:.3f}" for c in
                     ("productive", "input_stall", "checkpoint",
                      "recovery", "compile"))
    mfu = (summary.get("mfu") or {}).get("mfu")
    mfu_txt = f" mfu={mfu:.4f}" if mfu is not None else ""
    logger.info(f"obs: goodput {parts} over {gp['wall_seconds']:.1f}s "
                f"({gp['steps']} steps){mfu_txt}")


def _measure_train_flops(telemetry, train_step, state, train_loader,
                         n_devices: int) -> None:
    """Peek one batch (the seeded loader replays each epoch's order from
    ``set_epoch``, so training sees the identical stream afterwards) and
    record the train step's global per-step FLOPs for MFU."""
    try:
        train_loader.set_epoch(1)
        x, y = next(iter(train_loader))
    except Exception:
        return
    telemetry.measure_flops(train_step, state, x, y, n_devices=n_devices)


# ---------------------------------------------------------------------------
# The runner
# ---------------------------------------------------------------------------

def run_workload(spec: WorkloadSpec, config: Config
                 ) -> tuple[Any, list[EpochResult]]:
    """Train `spec` under `config`; returns (final state, phase history)."""
    initialize_runtime(config)
    enable_compile_cache()
    devices = _devices(config)
    logger = PhaseLogger(verbose=is_coordinator(),
                         jsonl_path=config.metrics_file)
    dev = describe_devices(devices)
    logger.info(f"devices: platform={dev['platform']} "
                f"device_kind={dev['device_kind']!r} "
                f"count={dev['device_count']}")
    telemetry = _maybe_telemetry(config)
    if (config.generate_tokens or config.serve) and spec.post_train is None:
        # rejected, not silently dropped (same principle as staged-mode
        # flag validation below)
        flag = "--generate" if config.generate_tokens else "--serve"
        raise ValueError(f"{flag} is not supported by workload "
                         f"{spec.name!r} (gpt only)")
    if config.pos_embedding != "learned" and spec.name != "gpt":
        raise ValueError(f"--pos {config.pos_embedding} is a gpt option; "
                         f"workload {spec.name!r} uses its own position "
                         "scheme")
    if config.attention_window is not None:
        if config.attention_window < 1:
            raise ValueError(f"--window must be >= 1, got "
                             f"{config.attention_window}")
        if spec.name != "gpt":
            raise ValueError(f"--window needs a causal decoder-only model; "
                             f"workload {spec.name!r} has bidirectional or "
                             "cross attention sites")
    if config.label_smoothing:
        if not 0.0 < config.label_smoothing < 1.0:
            raise ValueError(f"--label-smoothing must be in (0, 1), got "
                             f"{config.label_smoothing}")
        if spec.name not in ("transformer", "bert", "moe", "gpt"):
            raise ValueError("--label-smoothing applies to the token-CE "
                             f"workloads (transformer/bert/moe/gpt), not "
                             f"{spec.name!r}")
    if config.num_kv_heads is not None:
        if config.num_kv_heads < 1:
            raise ValueError(f"--kv-heads must be >= 1, got "
                             f"{config.num_kv_heads}")
        if spec.name != "gpt":
            raise ValueError("--kv-heads (grouped-query attention) is a "
                             f"gpt option; workload {spec.name!r} models "
                             "define their own head layout")
    try:
        # --obs-trace: the run's Tracer is the store of every
        # obs.trace.span() below (the loader's batches, the engine's tick
        # tree), whoever calls them and with or without a telemetry handle
        with obs_trace.use_tracer(getattr(telemetry, "tracer", None)):
            dataset = _build_dataset(spec, config)
            if spec.pre_train_check is not None:
                spec.pre_train_check(config, dataset)
            if config.autotune or config.plan_file:
                # plan fields never affect dataset construction, so the
                # built dataset is reused by the search's measured trials
                config = _resolve_plan(spec, config, devices, logger,
                                       dataset)
            state, history = _run_workload(spec, config, devices, logger,
                                           dataset, telemetry=telemetry)
            if (config.generate_tokens or config.serve) and \
                    spec.post_train is not None:
                # --profile-dir covers serving too (a second session
                # beside the training one, under the same directory)
                with profiling.trace(config.profile_dir):
                    spec.post_train(config, state, logger, dataset)
            return state, history
    finally:
        if telemetry is not None:
            summary = telemetry.close()
            _log_obs_summary(logger, summary)
        logger.close()


def _resolve_plan(spec: WorkloadSpec, config: Config, devices, logger,
                  dataset) -> Config:
    """``--autotune`` / ``--plan FILE`` → the config the run actually uses.

    Autotune searches the plan lattice with measured trials (reusing the
    already-built dataset), writes the artifact, and applies the winner;
    ``--plan`` alone loads an artifact, verifies its key against this
    run's (workload, geometry, topology), and applies it.  Either way the
    result is plain ``Config`` field overrides — every downstream code
    path is unchanged."""
    from distributed_deep_learning_tpu.tune import artifact as plan_artifact
    from distributed_deep_learning_tpu.tune.space import apply_plan

    platform = devices[0].platform
    device_kind = getattr(devices[0], "device_kind", "")
    key = plan_artifact.plan_key(spec.name, config, len(devices),
                                 platform, device_kind)
    if config.autotune:
        from distributed_deep_learning_tpu.tune.search import run_search

        result = run_search(spec, config, devices=devices, dataset=dataset,
                            logger=logger)
        path = config.plan_file or f"autotune_{spec.name}.plan.json"
        plan_artifact.save_plan(
            path, result.best, key=key, workload=spec.name,
            topology={"n_devices": len(devices), "platform": platform,
                      "device_kind": device_kind},
            search=result.record())
        logger.info(
            f"autotune: best plan {plan_artifact.plan_hash(result.best)} "
            f"[{result.best.describe()}] "
            f"{result.best_sps:.2f} steps/s vs baseline "
            f"{result.baseline_sps:.2f}; artifact -> {path}")
        return apply_plan(config, result.best)
    plan, record = plan_artifact.load_plan(config.plan_file,
                                           expected_key=key)
    logger.info(f"plan {record['plan_hash']} [{plan.describe()}] applied "
                f"from {config.plan_file}")
    return apply_plan(config, plan)


def _build_dataset(spec: WorkloadSpec, config: Config):
    """``--packed-cache`` replaces the workload's dataset builder with the
    mmap'd :class:`..data.packed.PackedDataset` — batches come straight
    off the page cache instead of the per-epoch decode path.  The cache
    carries the source's geometry metadata (classes / vocab / shapes), so
    downstream model sizing is unchanged; it must have been packed from
    the same workload's dataset (``scripts/pack_dataset.py``)."""
    if config.packed_cache:
        from distributed_deep_learning_tpu.data.packed import PackedDataset

        return PackedDataset(config.packed_cache)
    return spec.build_dataset(config)


def _run_workload(spec: WorkloadSpec, config: Config, devices, logger,
                  dataset, telemetry=None
                  ) -> tuple[Any, list[EpochResult]]:
    # DDL_DATA_LIMIT caps the examples considered (CI / smoke runs)
    import os
    limit = int(os.environ.get("DDL_DATA_LIMIT", "0"))
    n = min(len(dataset), limit) if limit else len(dataset)
    splits = train_val_test_split(n, seed=config.seed)
    example = spec.example_input(config, dataset)
    loss_fn = spec.build_loss(config)
    epoch_steps = max(1, len(splits.train) // config.batch_size)
    tx = build_optimizer(spec, config, epoch_steps)
    if config.clip_norm:
        # applied before the optimizer transform; in staged MPMD modes the
        # per-stage updates make this a per-stage norm (documented on the
        # flag) — global-norm semantics hold for every sharded-step path
        tx = optax.chain(optax.clip_by_global_norm(config.clip_norm), tx)
    rng = jax.random.key(config.seed)

    if config.mode is Mode.PIPELINE and spec.build_pipelined is not None:
        return _run_spmd_pipelined(spec, config, devices, logger, dataset,
                                   splits, example, loss_fn, tx, rng,
                                   telemetry=telemetry)

    if config.mode in (Mode.SEQUENTIAL, Mode.DATA):
        if config.reshard and config.mode is Mode.DATA:
            # cross-topology resume: BEFORE any mesh exists, peek the saved
            # topology manifest and — when it no longer matches the
            # surviving devices — let tune/ re-plan this restart's mesh
            # (reshard/replan.py; --target-mesh overrides the search)
            from distributed_deep_learning_tpu.reshard.replan import (
                resolve_restart_topology)

            config = resolve_restart_topology(spec, config, devices, logger,
                                              dataset=dataset)
        if config.mode is Mode.SEQUENTIAL:
            mesh = build_mesh({"data": 1}, devices[:1])
        else:
            if jax.process_count() > 1:
                # multi-process launch: -r counted PROCESSES; the mesh spans
                # every process's devices (devices[:r] would strand ranks
                # whose devices hold no addressable shard)
                n = len(devices)
            else:
                n = config.world_size if config.world_size > 1 \
                    else len(devices)
            if config.mesh_shape:
                mesh = build_mesh(config.mesh_shape,
                                  mesh_devices(config.mesh_shape, devices))
            elif not config.sync_in_local_data_mode:
                # reference quirk Q1 replication: local `data` mode trained N
                # INDEPENDENT replicas and printed rank 0's metrics.  The
                # observable behaviour is rank 0 training alone on its 1/N
                # data shard — reproduce exactly that.
                logger.info(f"quirk Q1 mode: no gradient sync; training "
                            f"rank 0's 1/{n} shard only")
                mesh = build_mesh({"data": 1}, devices[:1])
                from distributed_deep_learning_tpu.data.splits import (
                    shard_indices)
                splits = dataclasses.replace(
                    splits,
                    train=shard_indices(splits.train, n, 0),
                    val=shard_indices(splits.val, n, 0),
                    test=shard_indices(splits.test, n, 0))
                epoch_steps = max(1, len(splits.train) // config.batch_size)
                tx = build_optimizer(spec, config, epoch_steps)
            else:
                mesh = build_mesh({"data": n}, devices[:n])
        loaders = make_loaders(dataset, splits, config.batch_size, mesh,
                               seed=config.seed)
        model = spec.build_model(config, dataset)
        train_rng = (jax.random.key(config.seed + 1)
                     if config.dropout > 0 else None)
        sentinel = _sentinel_config(config)
        state = create_train_state(model, rng, example, tx,
                                   train_rng=train_rng)
        if sentinel is not None:
            from distributed_deep_learning_tpu.train.sentinel import (
                attach_sentinel)

            # attach BEFORE deriving sharding specs: the spec builders map
            # the sentinel scalars to replicated specs alongside the rest
            state = attach_sentinel(state)
        state = attach_comm_residual(config, mesh, state)
        state_spec = derive_state_spec(spec, config, mesh, state)
        state = place_state(state, mesh, state_spec)
        train_step, eval_step = make_train_eval_steps(
            config, mesh, loss_fn, state_spec, sentinel=sentinel,
            registry=telemetry.registry if telemetry is not None else None)
        if telemetry is not None:
            _measure_train_flops(telemetry, train_step, state, loaders[0],
                                 n_devices=mesh.size)
        ckpt, ckpt_step, start_epoch, resume_batch, resume_totals = \
            _maybe_checkpointer(config)
        restore_fn = None
        if config.reshard and ckpt is not None:
            # restores go through the resharding path: same-topology and
            # legacy checkpoints restore plainly, anything else is
            # redistributed onto THIS run's mesh/spec
            from distributed_deep_learning_tpu.reshard.restore import (
                make_restore_fn)

            restore_fn = make_restore_fn(ckpt, mesh, state_spec,
                                         logger=logger)
        if config.elastic:
            def make_state():
                s = create_train_state(model, rng, example, tx,
                                       train_rng=train_rng)
                if sentinel is not None:
                    from distributed_deep_learning_tpu.train.sentinel import (
                        attach_sentinel)

                    s = attach_sentinel(s)
                s = attach_comm_residual(config, mesh, s)
                return place_state(s, mesh, state_spec)

            return _fit_elastic(config, logger, make_state, train_step,
                                eval_step, loaders, ckpt, sentinel=sentinel,
                                restore_fn=restore_fn, telemetry=telemetry)
        if ckpt is not None and ckpt_step is not None:
            state, start_epoch, resume_batch, resume_totals = \
                _restore_resume(ckpt, state, ckpt_step, start_epoch,
                                resume_batch, resume_totals, logger,
                                restore_fn=restore_fn, telemetry=telemetry)
        try:
            with profiling.trace(config.profile_dir):
                return fit(state, train_step, eval_step, *loaders,
                           epochs=config.epochs, logger=logger,
                           checkpointer=ckpt, start_epoch=start_epoch,
                           checkpoint_every=config.checkpoint_every,
                           resume_batch=resume_batch,
                           resume_totals=resume_totals, sentinel=sentinel,
                           publish_dir=config.publish_weights,
                           telemetry=telemetry)
        finally:
            if ckpt is not None:
                ckpt.close()

    # model / pipeline: staged MPMD over explicit devices.  Flags this path
    # does not implement are rejected, not silently dropped — a run that
    # quietly skips checkpointing or gradient accumulation is worse than an
    # error (round-1 advisor finding).
    unsupported = [(config.checkpoint_dir, "--checkpoint-dir"),
                   (config.resume, "--resume"),
                   (config.grad_accum > 1, "--grad-accum"),
                   (config.remat, "--remat"),
                   (config.zero != "none", "--zero"),
                   (config.dropout > 0, "--dropout"),
                   (config.elastic, "--elastic"),
                   (config.heartbeat_dir, "--heartbeat-dir"),
                   (config.grad_compress != "none", "--grad-compress"),
                   (config.sentinel != "off", "--sentinel")]
    bad = [flag for cond, flag in unsupported if cond]
    if bad:
        raise ValueError(
            f"staged MPMD mode {config.mode.value!r} does not support "
            f"{', '.join(bad)}; use -m data (or -m pipeline for workloads "
            "with an SPMD pipeline, which supports checkpointing and remat)")
    layers = list(spec.build_layers(config, dataset))
    n_stages = config.num_stages or min(len(devices), len(layers))
    assignment = validate_assignment(
        spec.partitioner(len(layers), n_stages), n_stages)
    staged = StagedModel.from_layers(layers, assignment, n_stages)
    stage_devices = (devices * n_stages)[:n_stages]  # cycle if too few
    microbatch = config.microbatch if config.mode is Mode.PIPELINE else None
    trainer = StagedTrainer(staged, stage_devices, loss_fn, tx,
                            microbatch_size=microbatch)
    state = trainer.init(rng, example)

    # loaders feed device 0; stage walk moves activations onward
    mesh = build_mesh({"data": 1}, stage_devices[:1])
    loaders = make_loaders(dataset, splits, config.batch_size, mesh,
                           seed=config.seed)
    with profiling.trace(config.profile_dir):
        return fit(state, trainer.train_step, trainer.eval_step, *loaders,
                   epochs=config.epochs, logger=logger,
                   telemetry=telemetry)
