"""Typed run configuration + the reference-compatible CLI.

The reference exposes per-workload argparse flags (``getConfiguration``,
reference ``src/pytorch/CNN/main.py:47-68`` and ``LSTM/main.py:53-74``):
``-l/--nlayers -s/--size -e/--epochs -b/--batch -d/--device -w/--nworkers
-m/--mode -p/--pipeline -r/--run``.  We keep that exact surface (so a user of
the reference can switch CLIs unchanged) but parse into one frozen dataclass
instead of a loose dict / module-globals injection (reference
``MLP/main.py:52-55``).

Multi-host rank/world detection generalises the reference's MPI-env sniffing
(``CNN/main.py:62-67``): we look at JAX/TPU-standard coordinator variables as
well as OMPI/SLURM ones, and feed ``jax.distributed.initialize`` instead of
``torch.distributed.init_process_group``.
"""

from __future__ import annotations

import argparse
import dataclasses
import enum
import os
from typing import Sequence


#: --remat-policy name -> jax.checkpoint_policies attribute (None = the
#: jax.checkpoint default: recompute everything).  Lives here (jax-free)
#: so the CLI choices and train/step.py's resolver share one table.
REMAT_POLICIES = {
    "nothing": None,
    "dots": "dots_saveable",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
}

#: Canonical mesh axis order.  Lives here (jax-free, same reasoning as
#: REMAT_POLICIES) so ``--mesh`` can be validated at parse time;
#: ``runtime/mesh.py`` re-exports it as ``AXES`` and builds the actual
#: ``jax.sharding.Mesh`` in this order.
MESH_AXES = ("data", "fsdp", "stage", "model", "seq", "expert")


class Mode(str, enum.Enum):
    """Execution mode, 1:1 with the reference CLI (`-m`)."""

    SEQUENTIAL = "sequential"  # single device, plain jitted step
    MODEL = "model"            # layer-wise model parallelism over `stage` axis
    PIPELINE = "pipeline"      # GPipe-style microbatched pipeline over `stage`
    DATA = "data"              # data parallelism over `data` axis

    def __str__(self) -> str:  # argparse help rendering
        return self.value


class Device(str, enum.Enum):
    CPU = "cpu"
    GPU = "gpu"  # accepted for CLI parity with the reference; mapped to tpu
    TPU = "tpu"

    def __str__(self) -> str:
        return self.value


@dataclasses.dataclass(frozen=True)
class DistributedEnv:
    """Process topology discovered from the environment.

    Replaces the reference's `DISTRIBUTED`/rank/world env sniffing
    (``CNN/main.py:62-67``).  `coordinator` feeds
    ``jax.distributed.initialize``.
    """

    process_id: int = 0
    num_processes: int = 1
    local_process_id: int = 0
    coordinator: str | None = None

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    @staticmethod
    def from_environ(env: dict[str, str] | None = None) -> "DistributedEnv":
        env = dict(os.environ) if env is None else env

        def geti(*names: str, default: int | None = None) -> int | None:
            for n in names:
                if n in env:
                    try:
                        return int(env[n])
                    except ValueError:
                        pass
            return default

        num = geti("DDL_NUM_PROCESSES", "OMPI_COMM_WORLD_SIZE", "SLURM_NTASKS",
                   "PMI_SIZE", default=1)
        pid = geti("DDL_PROCESS_ID", "OMPI_COMM_WORLD_RANK", "SLURM_PROCID",
                   "PMI_RANK", default=0)
        local = geti("DDL_LOCAL_PROCESS_ID", "OMPI_COMM_WORLD_LOCAL_RANK",
                     "SLURM_LOCALID", default=0)
        coord = env.get("DDL_COORDINATOR") or env.get("MASTER_ADDR")
        if coord is not None and ":" not in coord:
            coord = f"{coord}:{env.get('MASTER_PORT', '29500')}"
        return DistributedEnv(
            process_id=pid or 0,
            num_processes=num or 1,
            local_process_id=local or 0,
            coordinator=coord,
        )


@dataclasses.dataclass(frozen=True)
class Config:
    """One run's full configuration.

    Field-to-flag mapping follows the reference exactly (``CNN/main.py:49-57``):

    ==============  ====  =========================================
    field           flag  reference meaning
    ==============  ====  =========================================
    num_layers      -l    hidden/dense/LSTM layer count
    size            -s    hidden width / bn_size
    epochs          -e    training epochs
    batch_size      -b    global batch size
    device          -d    cpu | gpu (we add tpu; gpu aliases tpu);
                          unset = JAX's default backend
    num_workers     -w    host-side data-loader worker threads
    mode            -m    sequential | model | pipeline | data
    microbatch      -p    pipeline microbatch SIZE (not count) —
                          preserves the reference's `-p` semantics
                          (``CNN/model.py:212`` splits by size)
    world_size      -r    local device/process fan-out for `data`
    ==============  ====  =========================================
    """

    num_layers: int = 1
    size: int = 38
    epochs: int = 10    # reference default (CNN/main.py:51)
    batch_size: int = 32  # reference default (CNN/main.py:52)
    device: Device | None = None    # None: JAX's default backend
    num_workers: int = 0
    mode: Mode = Mode.SEQUENTIAL
    microbatch: int | None = 2  # reference -p default; used only in pipeline mode
    world_size: int = 1

    # --- beyond-reference knobs (all default to reference behaviour) ---
    seed: int = 42                      # reference pins torch.manual_seed(42)
    learning_rate: float = 1e-3
    dtype: str = "float32"              # "bfloat16" for the TPU fast path
    num_stages: int | None = None       # MP/PP stage count (default: #devices)
    mesh_shape: dict[str, int] | None = None  # explicit mesh, e.g. {"data":4,"stage":2}
    double_softmax: bool = False        # reference quirk Q4 (Softmax + CE); off → logits+CE
    sync_in_local_data_mode: bool = True  # reference quirk Q1 fixed by default
    zero: str = "none"                  # optimizer/param sharding: none|1|fsdp
    grad_compress: str = "none"         # gradient all-reduce wire format:
                                        #   none|bf16|int8 (train/compress.py)
    comm: str = "none"                  # FSDP collective wire format:
                                        #   none|bf16|int8 (parallel/collectives.py)
    comm_overlap: bool = False          # ring-overlapped FSDP collectives
    grad_accum: int = 1                 # gradient-accumulation microsteps
    dropout: float = 0.0                # train-time dropout rate (north-star models)
    remat: bool = False                 # rematerialise activations in backward
    remat_policy: str = "nothing"       # what backward may keep (train/step.py)
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0           # also save every N train steps (0 = epoch-only)
    resume: bool = False
    profile_dir: str | None = None
    data_dir: str | None = None         # real-data root (ImageFolder layout)
    packed_cache: str | None = None     # packed sample-cache artifact
                                        #   (data/packed.py; overrides the
                                        #   workload's dataset builder)
    image_size: int = 224               # decode size for --data-dir images
    stem_s2d: bool = False              # space-to-depth ResNet stem (TPU opt)
    attention: str = "auto"             # auto|dense|flash (transformer family)
    attention_window: int | None = None  # sliding-window size (flash, causal)
    optimizer: str = "auto"             # auto|sgd|momentum|adam|adamw|...
    generate_tokens: int = 0            # gpt: sample N tokens post-train
    serve: bool = False                 # gpt: post-train continuous-batching
                                        #   serving demo (serve/engine.py)
    max_slots: int = 8                  # serving: concurrent decode slots
    prefill_buckets: tuple[int, ...] | None = None  # serving: prefill pad
                                        #   lengths (None = powers of two)
    paged: bool = False                 # serving: paged-KV engine with
                                        #   prefix reuse + chunked prefill
                                        #   (serve/paged.py, PagedEngine)
    kv_block_size: int = 16             # serving: paged-KV block tokens
    prefill_chunk: int = 32             # serving: chunked-prefill width
    draft: int = 0                      # serving: truncated-draft layers
                                        #   for speculative decoding (0=off)
    spec_k: int = 4                     # serving: draft tokens per round
    slo_ttft_ms: float | None = None    # serving: per-request TTFT SLO
    slo_e2e_ms: float | None = None     # serving: per-request e2e SLO
    serve_deadline_ms: float | None = None  # supervised serving: hard
                                        #   per-request wall deadline
                                        #   (serve/supervisor.py)
    serve_retries: int = 2              # supervised serving: engine-fault
                                        #   survivals allowed per request
    reload_watch: str | None = None     # supervised serving: hot weight-
                                        #   reload watch directory
                                        #   (serve/reload.py)
    canary_slots: int = 2               # supervised serving: slots routed
                                        #   to candidate weights before
                                        #   promote/rollback
    admission: dict | None = None       # supervised serving: admission-
                                        #   control knobs (--admission
                                        #   "depth=16,itl-p99-ms=200")
    kv_dtype: str | None = None         # serving: KV-cache storage dtype
                                        #   bf16|int8 (int8 = per-position
                                        #   scales in the block pools,
                                        #   paged engine only; serve/quant)
    weight_dtype: str | None = None     # serving: decode weight storage
                                        #   dtype bf16|int8 (per-channel
                                        #   scales, dequant fused into the
                                        #   compiled decode matmuls)
    replicas: int = 1                   # fleet serving: paged-engine
                                        #   replicas behind the prefix-
                                        #   affinity router (serve/fleet.py)
    priority_classes: tuple | None = None  # fleet serving: priority mix
                                        #   ((prio, frac), ...) parsed from
                                        #   --priority-classes "0=0.25,..."
    spill_dir: str | None = None        # fleet serving: host directory for
                                        #   preempted-slot KV spill files
                                        #   (engine preemption audit trail)
    autoscale: dict | None = None       # fleet serving: elastic replica-
                                        #   count knobs (--autoscale
                                        #   "min=1,max=4,patience=2")
    evacuate_on: str = "off"            # fleet serving: live mid-request
                                        #   slot evacuation trigger —
                                        #   off | degraded | hotspot
                                        #   (serve/rebalance.py)
    disagg: bool = False                # serving: disaggregate the replica
                                        #   into prefill + decode device
                                        #   pools joined by KV-block
                                        #   migration (serve/disagg.py)
    pool_elastic: bool = False          # disagg serving: move a worker
                                        #   between prefill/decode pools
                                        #   on sustained prefill_util skew
    prefill_workers: int = 1            # serving: devices in the disagg
                                        #   prefill pool (the rest decode)
    migrate: str = "host"               # serving: where preempted KV
                                        #   parks — host (npz-auditable
                                        #   arrays) or device (device-to-
                                        #   device, digest-audited)
    publish_weights: str | None = None  # checkpointing: atomically publish
                                        #   verified saves for serving hot
                                        #   reload (serve/reload.py)
    pos_embedding: str = "learned"      # learned | rope (gpt)
    num_kv_heads: int | None = None     # grouped-query attention (gpt)
    model_file: str | None = None       # gpt: model description (JSON)
    label_smoothing: float = 0.0        # token-CE smoothing (LM families)
    pipeline_schedule: str = "gpipe"    # gpipe | 1f1b | interleaved
    virtual_stages: int = 2             # chunks/device (interleaved)
    lr_schedule: str = "none"           # none|cosine|rsqrt|step (north stars)
    warmup_steps: int | None = None     # cosine/rsqrt warmup; None = 5% auto
    clip_norm: float | None = None      # global-norm gradient clipping
    metrics_file: str | None = None     # JSONL event sink (rank 0)
    obs: bool = False                   # unified run telemetry (obs/):
                                        #   goodput/MFU accounting + JSONL
                                        #   event stream
    obs_file: str | None = None         # telemetry sidecar path (default
                                        #   obs_events.jsonl; non-rank-0
                                        #   processes get .rankN suffix)
    obs_trace: str | None = None        # span-trace export path (Chrome/
                                        #   Perfetto JSON; implies the
                                        #   per-step/request Tracer)
    obs_rotate_mb: float | None = None  # size-cap the JSONL sidecar:
                                        #   rotate at N MB, fsync on
                                        #   rollover (obs/export.py)
    obs_blackbox: str | None = None     # arm a crash flight recorder:
                                        #   bounded event ring dumped
                                        #   here on sentinel trip /
                                        #   fatal signal / exit
    sentinel: str = "off"               # anomaly sentinel policy:
                                        #   off|skip|rollback|halt
                                        #   (train/sentinel.py)
    sentinel_window: int = 32           # EMA horizon for spike detection
    sentinel_factor: float = 10.0       # spike threshold (x running mean)
    elastic: bool = False               # checkpointed restart on failure
    reshard: bool = False               # cross-topology resume: restore a
                                        #   checkpoint saved on a different
                                        #   mesh, re-planning via tune/
                                        #   (reshard/)
    target_mesh: dict[str, int] | None = None  # --target-mesh: pin the
                                        #   restart mesh instead of
                                        #   re-planning
    heartbeat_dir: str | None = None    # shared dir for liveness heartbeats
    heartbeat_timeout: float = 30.0     # seconds before a peer counts as dead
    autotune: bool = False              # search the plan lattice (tune/)
                                        #   before training and train under
                                        #   the best measured plan
    plan_file: str | None = None        # plan artifact path: --plan loads and
                                        #   applies it; with --autotune the
                                        #   search result is written here
    distributed: DistributedEnv = dataclasses.field(default_factory=DistributedEnv)

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)

    @property
    def pipeline_enabled(self) -> bool:
        return self.mode is Mode.PIPELINE


# Per-workload -l/-s defaults, matching each reference main
# (CNN/main.py:49-50 → 2 dense blocks, bn_size 4; LSTM/main.py:55-56 →
# 1 hidden LSTM layer, width 128; MLP/main.py:42 → 1 hidden layer, the MLP
# has no -s flag and a fixed width of 38).
WORKLOAD_DEFAULTS: dict[str, dict[str, int]] = {
    "cnn": {"nlayers": 2, "size": 4},
    "lstm": {"nlayers": 1, "size": 128},
    "mlp": {"nlayers": 1, "size": 38},
    "mnist": {"nlayers": 2, "size": 32},
    # north-star families (BASELINE.json): -s is depth (resnet) / width
    "resnet": {"nlayers": 4, "size": 18},
    "transformer": {"nlayers": 6, "size": 512},
    "bert": {"nlayers": 12, "size": 768},
    "moe": {"nlayers": 4, "size": 256},
    "gpt": {"nlayers": 12, "size": 768},
}


def build_parser(workload: str = "") -> argparse.ArgumentParser:
    """The reference CLI (``getConfiguration``), plus framework extensions.

    Shared defaults match the reference exactly (``CNN/main.py:49-57``):
    ``-e 10 -b 32 -p 2 -r 1 -m sequential``.  ``-d`` defaults to ``tpu``
    (documented divergence: this *is* the TPU backend; ``gpu`` is accepted
    and aliased to tpu).
    """
    wd = WORKLOAD_DEFAULTS.get(workload.lower(), WORKLOAD_DEFAULTS["mlp"])
    p = argparse.ArgumentParser(
        prog=workload or "ddl-tpu",
        description="TPU-native distributed deep learning trainer",
    )
    p.add_argument("-l", "--nlayers", type=int, default=wd["nlayers"],
                   help="number of hidden/dense/LSTM layers")
    p.add_argument("-s", "--size", type=int, default=wd["size"],
                   help="hidden size / bottleneck size")
    p.add_argument("-e", "--epochs", type=int, default=10)
    p.add_argument("-b", "--batch", type=int, default=32,
                   help="global batch size")
    p.add_argument("-d", "--device", choices=[d.value for d in Device],
                   default=None,
                   help="cpu, or tpu (gpu aliases tpu): an explicit tpu "
                        "is an error when JAX's default backend is not a "
                        "TPU (default: whatever JAX's default backend is)")
    p.add_argument("-w", "--nworkers", type=int, default=0,
                   help="host-side data loading workers")
    p.add_argument("-m", "--mode", choices=[m.value for m in Mode],
                   default="sequential")
    p.add_argument("-p", "--pipeline", type=int, default=2,
                   help="pipeline microbatch size (reference -p semantics; "
                        "ignored unless -m pipeline)")
    p.add_argument("-r", "--run", type=int, default=1,
                   help="world size for local data-parallel fan-out")
    # framework extensions
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    p.add_argument("--nstages", type=int, default=None,
                   help="number of model/pipeline stages (default: all devices)")
    p.add_argument("--mesh", type=str, default=None,
                   help="explicit mesh, e.g. 'data=4,stage=2'")
    p.add_argument("--double-softmax", action="store_true",
                   help="replicate reference quirk Q4 (Softmax into CE loss)")
    p.add_argument("--no-sync", dest="sync", action="store_false",
                   help="replicate reference quirk Q1 (local data mode trains "
                        "independent replicas)")
    p.add_argument("--remat", action="store_true",
                   help="recompute activations in backward (jax.checkpoint) "
                        "— trades FLOPs for HBM")
    p.add_argument("--remat-policy", dest="remat_policy", default="nothing",
                   choices=sorted(REMAT_POLICIES),
                   help="with --remat: what backward may reuse — 'nothing' "
                        "recomputes all; 'dots'/'dots_no_batch' keep matmul "
                        "outputs so only elementwise chains recompute")
    p.add_argument("--dropout", type=float, default=0.0,
                   help="dropout rate for transformer/bert workloads "
                        "(seeded per-step PRNG streams; 0 = deterministic)")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="split each batch into this many sequential "
                        "microbatches, accumulating gradients")
    p.add_argument("--zero", choices=["none", "1", "fsdp"], default="none",
                   help="shard optimizer state (ZeRO-1) or params+optimizer "
                        "(fsdp) over the fsdp/data mesh axes")
    p.add_argument("--grad-compress", choices=["none", "bf16", "int8"],
                   default="none",
                   help="compress the data-parallel gradient all-reduce: "
                        "bf16 halves wire bytes; int8 is common-scale "
                        "quantization with int32 reduction (EQuARX-style "
                        "numerics)")
    p.add_argument("--comm", choices=["none", "bf16", "int8"],
                   default="none",
                   help="with --zero fsdp: quantize the explicit param "
                        "all-gather / grad reduce-scatter collectives "
                        "(bf16 halves wire bytes; int8 quarters them with "
                        "per-leaf error-feedback residuals; "
                        "parallel/collectives.py)")
    p.add_argument("--comm-overlap", action="store_true",
                   help="with --comm: run the FSDP collectives as "
                        "double-buffered ppermute rings so each chunk's "
                        "transfer overlaps the previous chunk's compute")
    p.add_argument("--checkpoint-dir", type=str, default=None)
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="also checkpoint every N train steps (0 = per "
                        "epoch only); a preemption then costs at most N "
                        "steps — resume replays the loader to the exact "
                        "batch")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--profile-dir", type=str, default=None)
    p.add_argument("--data-dir", type=str, default=None,
                   help="train on a real ImageFolder-layout dataset "
                        "(root/<class>/*.jpg) instead of the synthetic twin; "
                        "-w sets the decode thread count")
    p.add_argument("--packed-cache", type=str, default=None, metavar="FILE",
                   help="train from a packed pre-decoded sample cache "
                        "(scripts/pack_dataset.py artifact): the cache is "
                        "memory-mapped and batches assemble with zero "
                        "per-sample Python work, instead of re-decoding "
                        "--data-dir files every epoch")
    p.add_argument("--image-size", type=int, default=224,
                   help="square decode size for --data-dir images")
    p.add_argument("--window", dest="attention_window", type=int,
                   default=None, metavar="W",
                   help="sliding-window attention: each position attends "
                        "its last W tokens only (flash kernel, causal "
                        "models; O(T*W) instead of O(T^2))")
    p.add_argument("--stem-s2d", action="store_true",
                   help="space-to-depth ResNet stem: pack 2x2 input patches "
                        "into channels and run the mathematically equivalent "
                        "4x4-s1 stem conv (MXU-friendly; ImageNet-size "
                        "stems only)")
    p.add_argument("--attention", choices=["auto", "dense", "flash"],
                   default="auto",
                   help="attention implementation for transformer-family "
                        "models: auto = Pallas flash kernel on TPU, dense "
                        "elsewhere")
    p.add_argument("--optimizer",
                   choices=["auto", "sgd", "momentum", "adam", "adamw",
                            "adafactor", "lamb"],
                   default="auto",
                   help="override the workload's default optimizer: "
                        "adafactor = sublinear-memory factored second "
                        "moments (the TPU big-model staple), lamb = "
                        "layerwise-adaptive large-batch; auto keeps the "
                        "per-workload recipe (sgd+momentum for vision, "
                        "adamw for LMs)")
    p.add_argument("--label-smoothing", type=float, default=0.0,
                   metavar="EPS",
                   help="label smoothing for the token cross-entropy "
                        "(transformer/bert/moe/gpt; 0.1 = the "
                        "transformer-base recipe)")
    p.add_argument("--kv-heads", dest="num_kv_heads", type=int,
                   default=None, metavar="K",
                   help="gpt grouped-query attention: K key/value heads "
                        "shared by the query heads (must divide them; "
                        "shrinks the KV cache by heads/K)")
    p.add_argument("--model-file", default=None, metavar="JSON",
                   help="gpt: build the decoder a model description file "
                        "gives, layer by layer, under the key names "
                        "published config.json files use "
                        "(models/describe.py); replaces -l, -s, "
                        "--kv-heads, --pos and --window")
    p.add_argument("--pos", dest="pos_embedding",
                   choices=["learned", "rope"], default="learned",
                   help="gpt position encoding: learned absolute table or "
                        "parameter-free rotary (RoPE, relative positions)")
    p.add_argument("--generate", dest="generate_tokens", type=int,
                   default=0, metavar="N",
                   help="gpt: after training, print N-token greedy "
                        "continuations of two dataset prompts (KV-cached "
                        "decode; a smoke sample — the prompts are usually "
                        "training rows, not held-out data)")
    p.add_argument("--serve", action="store_true",
                   help="gpt: after training, serve a mixed-length batch "
                        "of dataset prompts through the continuous-"
                        "batching engine (slot-based KV cache, compile-"
                        "once decode) and log tokens/sec + occupancy — "
                        "the serving sibling of --generate")
    p.add_argument("--max-slots", dest="max_slots", type=int, default=8,
                   metavar="S",
                   help="serving: concurrent decode slots (the engine's "
                        "static batch dimension; throughput tracks slot "
                        "occupancy)")
    p.add_argument("--prefill-buckets", dest="prefill_buckets", type=str,
                   default=None, metavar="L1,L2,...",
                   help="serving: comma-separated prompt-padding bucket "
                        "lengths — one compiled prefill program each "
                        "(default: powers of two up to the cache length)")
    p.add_argument("--paged", action="store_true",
                   help="serving: use the paged-KV engine — block pools "
                        "with rolling-hash prefix reuse (shared prompt "
                        "prefixes prefill once), chunked prefill "
                        "interleaved with decode, optional speculative "
                        "decoding via --draft")
    p.add_argument("--kv-block-size", dest="kv_block_size", type=int,
                   default=16, metavar="B",
                   help="paged serving: tokens per KV block (prefix "
                        "sharing granularity; smaller = more sharing, "
                        "more gather work)")
    p.add_argument("--prefill-chunk", dest="prefill_chunk", type=int,
                   default=32, metavar="C",
                   help="paged serving: prefill slice width — in-flight "
                        "decode streams stall at most ~one chunk of "
                        "compute per token, whatever the prompt length")
    p.add_argument("--draft", type=int, default=0, metavar="N",
                   help="paged serving: speculative decoding with a "
                        "draft built from the target's first N layers "
                        "(shared weights; greedy outputs stay "
                        "bit-identical); 0 disables")
    p.add_argument("--spec-k", dest="spec_k", type=int, default=4,
                   metavar="K",
                   help="paged serving: draft tokens proposed per round "
                        "(verified in one batched target forward)")
    p.add_argument("--slo-ttft-ms", dest="slo_ttft_ms", type=float,
                   default=None, metavar="MS",
                   help="serving: per-request time-to-first-token SLO; "
                        "attainment is reported in the serve stats")
    p.add_argument("--slo-e2e-ms", dest="slo_e2e_ms", type=float,
                   default=None, metavar="MS",
                   help="serving: per-request end-to-end latency SLO")
    p.add_argument("--serve-deadline-ms", dest="serve_deadline_ms",
                   type=float, default=None, metavar="MS",
                   help="supervised serving: hard per-request wall "
                        "deadline — a request a fault loop holds past "
                        "this errors out instead of replaying forever "
                        "(serve/supervisor.py; implies supervision)")
    p.add_argument("--serve-retries", dest="serve_retries", type=int,
                   default=2, metavar="N",
                   help="supervised serving: engine faults a request may "
                        "survive (with zero-loss replay) before it is "
                        "errored out")
    p.add_argument("--reload-watch", dest="reload_watch", type=str,
                   default=None, metavar="DIR",
                   help="supervised serving: watch DIR for atomically "
                        "published weights (serve/reload.py) and hot-swap "
                        "them between ticks — canary first, integrity-"
                        "manifest verified, corrupt saves quarantined")
    p.add_argument("--canary-slots", dest="canary_slots", type=int,
                   default=2, metavar="N",
                   help="supervised serving: decode slots routed to "
                        "candidate weights while old/new agreement and "
                        "logprob drift decide promote vs rollback "
                        "(0 = swap verified weights directly)")
    p.add_argument("--admission", type=str, default=None,
                   metavar="K=V,...",
                   help="supervised serving: SLO-aware admission control "
                        "— 'depth=16,itl-p99-ms=200,shed-priority=2' "
                        "(keys: depth, itl-p99-ms, shed-priority, "
                        "patience, cool); degrades quality (spec decode "
                        "off, chunk budget down) before shedding, and "
                        "never sheds priority-0 requests")
    p.add_argument("--kv-dtype", dest="kv_dtype", type=str, default=None,
                   metavar="DT",
                   help="serving: KV-cache storage dtype, bf16 or int8 "
                        "(int8 keeps per-position scales in the block "
                        "pools — requires --paged; the spec-decode draft "
                        "pool inherits it; unset = full precision)")
    p.add_argument("--weight-dtype", dest="weight_dtype", type=str,
                   default=None, metavar="DT",
                   help="serving: decode weight storage dtype, bf16 or "
                        "int8 (per-output-channel scales; dequantization "
                        "fuses into the compiled decode matmuls, so no "
                        "full-precision copy exists at rest)")
    p.add_argument("--replicas", type=int, default=1, metavar="N",
                   help="fleet serving: run N paged-engine replicas "
                        "behind the health-checked prefix-affinity "
                        "router (serve/fleet.py) — crash-quarantine with "
                        "zero-loss cross-replica replay; requires "
                        "--paged when N > 1")
    p.add_argument("--priority-classes", dest="priority_classes",
                   type=str, default=None, metavar="P=F,...",
                   help="fleet serving: request priority mix, e.g. "
                        "'0=0.25,1=0.5,2=0.25' (priority=fraction, "
                        "fractions sum to 1); under slot/block pressure "
                        "higher-priority arrivals preempt the lowest-"
                        "priority slots (KV spilled to host, resumed "
                        "bit-identically); priority 0 is never "
                        "preempted or shed; requires --paged")
    p.add_argument("--spill-dir", dest="spill_dir", type=str,
                   default=None, metavar="DIR",
                   help="fleet serving: also write each preempted "
                        "slot's spilled KV to DIR as an npz audit "
                        "trail (resume itself stays in host memory); "
                        "requires --priority-classes")
    p.add_argument("--autoscale", type=str, default=None,
                   metavar="K=V,...",
                   help="fleet serving: elastic replica autoscaling, "
                        "e.g. 'min=1,max=4,patience=2,cool=2' — "
                        "patience consecutive hot rounds warm one new "
                        "replica from the published weights (prefix-"
                        "warmed via clone_prefix), cool consecutive "
                        "cold rounds retire one through the drain "
                        "protocol (stop placement, evacuate open "
                        "slots, retire); requires --replicas > 1")
    p.add_argument("--evacuate-on", dest="evacuate_on",
                   choices=["off", "degraded", "hotspot"],
                   default="off",
                   help="fleet serving: live mid-request slot "
                        "evacuation — on 'degraded' a health-degraded "
                        "replica's open slots migrate (digest-verified "
                        "committed KV) to healthy peers and resume "
                        "bit-identically; 'hotspot' also evacuates on "
                        "sustained per-replica latency skew; requires "
                        "--replicas > 1")
    p.add_argument("--disagg", action="store_true",
                   help="serving: disaggregate the replica into a "
                        "prefill worker pool (chunked, compile-once per "
                        "chunk width) and decode workers on separate "
                        "devices, joined by device-to-device KV-block "
                        "migration (serve/disagg.py); requires --paged "
                        "and at least 2 local devices")
    p.add_argument("--prefill-workers", dest="prefill_workers", type=int,
                   default=1, metavar="N",
                   help="disaggregated serving: devices in the prefill "
                        "pool; the remaining visible devices become "
                        "decode workers, so N must leave at least one "
                        "(requires --disagg)")
    p.add_argument("--pool-elastic", dest="pool_elastic",
                   action="store_true",
                   help="disaggregated serving: after the run, judge "
                        "the measured prefill_util against the pool "
                        "rebalancer's hysteresis and reassign one idle "
                        "worker between the prefill and decode pools "
                        "when the skew is sustained (serve/autoscaler."
                        "PoolRebalancer); requires --disagg")
    p.add_argument("--migrate", choices=["host", "device"],
                   default="host",
                   help="serving preemption: where a preempted slot's "
                        "KV parks — host (npz-auditable arrays, the "
                        "default) or device (chunked device-to-device "
                        "block migration with end-to-end digest audit; "
                        "needs a second local device)")
    p.add_argument("--publish-weights", dest="publish_weights", type=str,
                   default=None, metavar="DIR",
                   help="checkpointing: after each verified save, "
                        "atomically publish the params to DIR in the "
                        "serve/reload.py manifest format, so serving "
                        "fleets watching it (--reload-watch) hot-swap "
                        "the new weights; requires --checkpoint-dir")
    p.add_argument("--schedule", dest="lr_schedule",
                   choices=["none", "cosine", "rsqrt", "step"],
                   default="none",
                   help="learning-rate schedule: cosine (ResNet/BERT "
                        "recipe), rsqrt (transformer-base Noam), step "
                        "(the reference's StepLR)")
    p.add_argument("--warmup", dest="warmup_steps", type=int, default=None,
                   help="warmup steps for --schedule cosine/rsqrt "
                        "(default: 5%% of total steps; 0 disables warmup)")
    p.add_argument("--clip-norm", type=float, default=None,
                   help="clip gradients to this global norm before the "
                        "optimizer update (per-stage norm in staged MPMD "
                        "modes)")
    p.add_argument("--metrics-file", type=str, default=None,
                   help="append one JSON object per phase/metric event "
                        "(structured sibling of the reference log stream)")
    p.add_argument("--obs", action="store_true",
                   help="unified run telemetry (obs/): per-step span "
                        "recording rolled up into a goodput breakdown "
                        "(productive/input-stall/checkpoint/recovery/"
                        "compile), MFU from the compiled step's cost "
                        "model, and a JSONL event stream readable by "
                        "scripts/obs_report.py")
    p.add_argument("--obs-file", type=str, default=None, metavar="PATH",
                   help="telemetry event-stream path (default "
                        "obs_events.jsonl; requires --obs)")
    p.add_argument("--obs-trace", type=str, default=None, metavar="PATH",
                   help="also record per-step causal spans and export "
                        "them here as Chrome/Perfetto trace JSON "
                        "(load in ui.perfetto.dev; requires --obs)")
    p.add_argument("--obs-rotate-mb", type=float, default=None,
                   metavar="MB",
                   help="size-cap the telemetry stream: rotate the "
                        "JSONL sidecar at this many MB, fsyncing each "
                        "closed segment (requires --obs)")
    p.add_argument("--obs-blackbox", type=str, default=None,
                   metavar="PATH",
                   help="arm a crash flight recorder: keep a bounded "
                        "in-memory ring of recent events and dump it "
                        "here on sentinel anomaly, SLO breach, fatal "
                        "signal or process exit (requires --obs)")
    p.add_argument("--pipeline-schedule",
                   choices=["gpipe", "1f1b", "interleaved"],
                   default="gpipe",
                   help="SPMD pipeline schedule (-m pipeline, transformer/"
                        "bert/gpt): gpipe = fill-drain with scan-transpose "
                        "backward; 1f1b = one-forward-one-backward with "
                        "O(stages) activation residency; interleaved = "
                        "1f1b with --virtual-stages model chunks per "
                        "device (Megatron-style, ~V x smaller bubble)")
    p.add_argument("--virtual-stages", type=int, default=2,
                   help="model chunks per device for --pipeline-schedule "
                        "interleaved (layers must divide nstages x this)")
    p.add_argument("--sentinel", choices=["off", "skip", "rollback", "halt"],
                   default="off",
                   help="on-device anomaly sentinel: detect non-finite "
                        "loss/grads and grad-norm/loss spikes inside the "
                        "jitted step and contain the update before it can "
                        "poison params — 'skip' drops the bad batch and "
                        "continues, 'rollback' restores the last checkpoint "
                        "with the bad step skipped (needs --elastic), "
                        "'halt' stops the run with clean state")
    p.add_argument("--sentinel-window", type=int, default=32, metavar="N",
                   help="sentinel EMA horizon in steps for the running "
                        "grad-norm/loss means spike detection compares "
                        "against")
    p.add_argument("--sentinel-factor", type=float, default=10.0,
                   metavar="X",
                   help="sentinel spike threshold: a step whose grad norm "
                        "or loss exceeds X times its running mean is "
                        "anomalous")
    p.add_argument("--elastic", action="store_true",
                   help="restart from the last checkpoint on worker failure "
                        "or runtime error (requires --checkpoint-dir)")
    p.add_argument("--reshard", action="store_true",
                   help="cross-topology resume: restore the checkpoint even "
                        "if it was saved on a different mesh, re-planning "
                        "for the surviving devices via tune/ (requires "
                        "--resume or --elastic)")
    p.add_argument("--target-mesh", type=str, default=None, metavar="SHAPE",
                   help="with --reshard: restore onto exactly this mesh "
                        "(same axis=N syntax as --mesh) instead of "
                        "re-planning")
    p.add_argument("--heartbeat-dir", type=str, default=None,
                   help="shared directory for liveness heartbeats; with "
                        "--elastic, dead peers abort the step promptly "
                        "instead of hanging the collective")
    p.add_argument("--heartbeat-timeout", type=float, default=30.0)
    p.add_argument("--autotune", action="store_true",
                   help="search the mesh x microbatch x remat x ZeRO plan "
                        "lattice (tune/) with memory-model pruning and "
                        "measured trials, write the winning plan artifact "
                        "(--plan sets the path), then train under it")
    p.add_argument("--plan", dest="plan_file", type=str, default=None,
                   metavar="FILE",
                   help="apply a plan artifact from a previous --autotune "
                        "run (rejected if its key does not match this "
                        "workload/geometry/topology); with --autotune, "
                        "where to write the search result")
    return p


def parse_buckets_arg(text: str | None) -> tuple[int, ...] | None:
    """``--prefill-buckets`` string → ascending lengths, validated at
    parse time (mirrors :func:`parse_mesh_arg`: a bad flag is an
    argparse-style error at the CLI boundary, not a traceback from the
    engine mid-run)."""
    if not text:
        return None
    try:
        buckets = tuple(int(b) for b in text.split(","))
    except ValueError:
        raise SystemExit(f"--prefill-buckets {text!r}: expected "
                         "comma-separated integers") from None
    if any(b < 1 for b in buckets):
        raise SystemExit(f"--prefill-buckets {text!r}: lengths must be "
                         ">= 1")
    for a, b in zip(buckets, buckets[1:]):
        if b == a:
            raise SystemExit(f"--prefill-buckets {text!r}: duplicate "
                             f"bucket {a} (each bucket is one compiled "
                             "prefill program; listing it twice is "
                             "always a mistake)")
        if b < a:
            raise SystemExit(f"--prefill-buckets {text!r}: lengths must "
                             f"be strictly ascending, got {b} after {a}")
    return buckets


#: ``--admission`` spec keys → (AdmissionController kwarg, converter,
#: minimum).  Kept here so a typo'd knob dies at the CLI boundary with
#: the full key list, not as a TypeError from the controller mid-serve.
_ADMISSION_KEYS = {
    "depth": ("max_queue_depth", int, 1),
    "itl-p99-ms": ("itl_p99_ms", float, 1e-9),
    "shed-priority": ("shed_priority", int, 1),
    "patience": ("patience", int, 1),
    "cool": ("cool", int, 1),
}


def parse_admission_arg(text: str | None,
                        flag: str = "--admission") -> dict | None:
    """``--admission`` string → :class:`..serve.admission.
    AdmissionController` kwargs, validated at parse time (mirrors
    :func:`parse_mesh_arg`).  Example:
    ``"depth=16,itl-p99-ms=200,shed-priority=2"``."""
    if not text:
        return None
    out: dict = {}
    for part in text.split(","):
        key, _, val = part.strip().partition("=")
        if key not in _ADMISSION_KEYS:
            raise SystemExit(
                f"{flag}: unknown key {key!r} in entry {part!r}; known "
                f"keys: {', '.join(sorted(_ADMISSION_KEYS))}")
        name, conv, lo = _ADMISSION_KEYS[key]
        if name in out:
            raise SystemExit(f"{flag}: key {key!r} given twice")
        try:
            v = conv(val)
        except ValueError:
            raise SystemExit(f"{flag}: {key}={val!r} is not a valid "
                             f"{conv.__name__}") from None
        if v < lo:
            raise SystemExit(f"{flag}: {key}={val!r} must be >= {lo}")
        out[name] = v
    return out


#: ``--autoscale`` spec keys → (FleetAutoscaler kwarg, converter,
#: minimum).  Same contract as ``_ADMISSION_KEYS``: a typo'd knob dies
#: at the CLI boundary with the full key list, not as a TypeError from
#: the autoscaler mid-serve.
_AUTOSCALE_KEYS = {
    "min": ("min_replicas", int, 1),
    "max": ("max_replicas", int, 1),
    "patience": ("patience", int, 1),
    "cool": ("cool", int, 1),
}


def parse_autoscale_arg(text: str | None,
                        flag: str = "--autoscale") -> dict | None:
    """``--autoscale`` string → :class:`..serve.autoscaler.
    FleetAutoscaler` kwargs, validated at parse time (mirrors
    :func:`parse_admission_arg`).  Example:
    ``"min=1,max=4,patience=2,cool=2"``."""
    if not text:
        return None
    out: dict = {}
    for part in text.split(","):
        key, _, val = part.strip().partition("=")
        if key not in _AUTOSCALE_KEYS:
            raise SystemExit(
                f"{flag}: unknown key {key!r} in entry {part!r}; known "
                f"keys: {', '.join(sorted(_AUTOSCALE_KEYS))}")
        name, conv, lo = _AUTOSCALE_KEYS[key]
        if name in out:
            raise SystemExit(f"{flag}: key {key!r} given twice")
        try:
            v = conv(val)
        except ValueError:
            raise SystemExit(f"{flag}: {key}={val!r} is not a valid "
                             f"{conv.__name__}") from None
        if v < lo:
            raise SystemExit(f"{flag}: {key}={val!r} must be >= {lo}")
        out[name] = v
    if ("min_replicas" in out and "max_replicas" in out
            and out["max_replicas"] < out["min_replicas"]):
        raise SystemExit(f"{flag}: max={out['max_replicas']} < "
                         f"min={out['min_replicas']} (the fleet cannot "
                         "be smaller than its floor)")
    return out


def parse_priority_classes(text: str | None,
                           flag: str = "--priority-classes"
                           ) -> tuple | None:
    """``--priority-classes`` string → ``LoadSpec.priority_classes``
    tuple, validated at parse time (mirrors :func:`parse_admission_arg`).
    Example: ``"0=0.25,1=0.5,2=0.25"`` → ``((0, 0.25), (1, 0.5),
    (2, 0.25))``."""
    if not text:
        return None
    out: list[tuple[int, float]] = []
    seen: set[int] = set()
    for part in text.split(","):
        key, _, val = part.strip().partition("=")
        if not val:
            raise SystemExit(f"{flag}: bad entry {part!r}; expected "
                             "'<priority>=<fraction>', e.g. '0=0.25'")
        try:
            prio = int(key)
        except ValueError:
            raise SystemExit(f"{flag}: priority {key!r} is not an "
                             "integer") from None
        if prio < 0:
            raise SystemExit(f"{flag}: priority {prio} must be >= 0 "
                             "(0 is the most-protected class)")
        if prio in seen:
            raise SystemExit(f"{flag}: priority {prio} given twice")
        seen.add(prio)
        try:
            frac = float(val)
        except ValueError:
            raise SystemExit(f"{flag}: fraction {val!r} for priority "
                             f"{prio} is not a number") from None
        if frac < 0:
            raise SystemExit(f"{flag}: fraction {frac} for priority "
                             f"{prio} must be >= 0")
        out.append((prio, frac))
    total = sum(f for _, f in out)
    if abs(total - 1.0) > 1e-6:
        raise SystemExit(f"{flag}: fractions must sum to 1, got "
                         f"{total:g}")
    return tuple(out)


def parse_mesh_arg(text: str | None,
                   flag: str = "--mesh") -> dict[str, int] | None:
    """``--mesh`` string → shape dict, validated at parse time.

    A bad mesh string is an argparse-style error naming the known axes —
    not a ``ValueError`` traceback from ``MeshSpec`` deep inside startup.
    The device-count constraint (axis product vs. available devices) is
    checked later by ``MeshSpec.resolve``, which knows the topology.
    ``flag`` names the offending option in the error (``--target-mesh``
    reuses this exact validation).
    """
    if not text:
        return None
    shape: dict[str, int] = {}
    for part in text.split(","):
        axis, _, n = part.partition("=")
        axis = axis.strip()
        if not n:
            raise SystemExit(f"{flag}: bad entry {part!r}; expected axis=N "
                             f"with axis one of {', '.join(MESH_AXES)}")
        if axis not in MESH_AXES:
            raise SystemExit(f"{flag}: unknown axis {axis!r}; known axes: "
                             f"{', '.join(MESH_AXES)}")
        if axis in shape:
            raise SystemExit(f"{flag}: axis {axis!r} given twice")
        try:
            size = int(n)
        except ValueError:
            raise SystemExit(f"{flag}: size for axis {axis!r} must be an "
                             f"integer (-1 = fill remaining devices), got "
                             f"{n.strip()!r}") from None
        if size == 0 or size < -1:
            raise SystemExit(f"{flag}: size for axis {axis!r} must be >= 1 "
                             "(or -1 to fill with the remaining devices)")
        shape[axis] = size
    if sum(1 for v in shape.values() if v == -1) > 1:
        raise SystemExit(f"{flag}: at most one axis may be -1")
    return shape


def parse_args(argv: Sequence[str] | None = None, workload: str = "",
               env: dict[str, str] | None = None) -> Config:
    args = build_parser(workload).parse_args(argv)
    dist = DistributedEnv.from_environ(env)
    if args.checkpoint_every and not args.checkpoint_dir:
        raise SystemExit("--checkpoint-every requires --checkpoint-dir "
                         "(silently dropping the cadence would be worse "
                         "than an error)")
    if args.checkpoint_every < 0:
        raise SystemExit(f"--checkpoint-every {args.checkpoint_every}: "
                         "must be >= 0")
    if args.remat_policy != "nothing" and not args.remat:
        raise SystemExit("--remat-policy requires --remat (a policy "
                         "without rematerialisation would be a silent "
                         "no-op)")
    if args.sentinel == "rollback" and not args.elastic:
        raise SystemExit("--sentinel rollback requires --elastic (rollback "
                         "restores the last checkpoint and replays with "
                         "the bad step skipped — that machinery IS the "
                         "elastic restart loop)")
    if args.sentinel != "off" and (args.sentinel_window < 1
                                   or args.sentinel_factor <= 1.0):
        raise SystemExit("--sentinel-window must be >= 1 and "
                         "--sentinel-factor > 1")
    if args.reshard and not (args.resume or args.elastic):
        raise SystemExit("--reshard requires --resume or --elastic (it "
                         "changes how an existing checkpoint is restored; "
                         "a fresh run has nothing to reshard)")
    if args.reshard and not args.checkpoint_dir:
        raise SystemExit("--reshard requires --checkpoint-dir (the "
                         "topology manifest lives next to the checkpoint)")
    if args.target_mesh and not args.reshard:
        raise SystemExit("--target-mesh requires --reshard (without the "
                         "resharding restore a mesh change would restore "
                         "garbage; use --mesh to shape a fresh run)")
    mesh_shape = parse_mesh_arg(args.mesh)
    if mesh_shape and args.nstages and \
            mesh_shape.get("stage", args.nstages) != args.nstages:
        raise SystemExit(f"--mesh stage={mesh_shape['stage']} conflicts "
                         f"with --nstages {args.nstages}; drop one (--mesh "
                         "wins over the mode-derived stage count)")
    if args.comm != "none":
        if args.zero != "fsdp":
            raise SystemExit("--comm quantizes the explicit FSDP param "
                             "all-gather / grad reduce-scatter; it requires "
                             "--zero fsdp (with no sharded params there is "
                             "no such collective to compress)")
        if args.grad_compress != "none":
            raise SystemExit("--comm and --grad-compress are mutually "
                             "exclusive: the FSDP dataflow has no pure "
                             "gradient all-reduce for --grad-compress to "
                             "act on")
        if args.grad_accum > 1:
            raise SystemExit("--comm does not compose with --grad-accum "
                             "(the accumulation loop re-gathers params per "
                             "microstep; quantizing those repeats is not "
                             "implemented)")
        bad = [a for a in ("model", "expert", "stage", "seq")
               if (mesh_shape or {}).get(a, 0) > 1]
        if bad:
            raise SystemExit(f"--comm requires a data/fsdp-only mesh; got "
                             f"{'/'.join(bad)} axes (the explicit FSDP "
                             "step owns the whole dataflow and does not "
                             "compose with model/expert/stage/seq "
                             "sharding)")
    if args.comm_overlap and args.comm == "none":
        raise SystemExit("--comm-overlap requires --comm bf16|int8 (it "
                         "selects the ring schedule for the explicit "
                         "collectives --comm turns on)")
    if args.plan_file and not args.autotune and not os.path.exists(args.plan_file):
        raise SystemExit(f"--plan {args.plan_file}: no such file (run "
                         "--autotune to produce one)")
    if args.obs_file and not args.obs:
        raise SystemExit("--obs-file requires --obs (the path names the "
                         "telemetry stream --obs records)")
    for flag, val in (("--obs-trace", args.obs_trace),
                      ("--obs-rotate-mb", args.obs_rotate_mb),
                      ("--obs-blackbox", args.obs_blackbox)):
        if val and not args.obs:
            raise SystemExit(f"{flag} requires --obs (it extends the "
                             "telemetry --obs turns on)")
    if args.obs_rotate_mb is not None and args.obs_rotate_mb <= 0:
        raise SystemExit(f"--obs-rotate-mb {args.obs_rotate_mb}: must "
                         "be > 0")
    if args.max_slots <= 0:
        raise SystemExit(f"--max-slots {args.max_slots}: must be >= 1 "
                         "(the engine's static batch dimension)")
    if args.kv_block_size < 1:
        raise SystemExit(f"--kv-block-size {args.kv_block_size}: must "
                         "be >= 1")
    if args.prefill_chunk < 1:
        raise SystemExit(f"--prefill-chunk {args.prefill_chunk}: must "
                         "be >= 1")
    if args.draft < 0:
        raise SystemExit(f"--draft {args.draft}: must be >= 0 (0 turns "
                         "speculative decoding off)")
    if args.draft and not args.paged:
        raise SystemExit("--draft requires --paged (speculation runs "
                         "inside the paged engine)")
    if args.spec_k < 1:
        raise SystemExit(f"--spec-k {args.spec_k}: must be >= 1")
    for flag, v in (("--slo-ttft-ms", args.slo_ttft_ms),
                    ("--slo-e2e-ms", args.slo_e2e_ms),
                    ("--serve-deadline-ms", args.serve_deadline_ms)):
        if v is not None and v <= 0:
            raise SystemExit(f"{flag} {v}: must be positive milliseconds")
    if args.serve_retries < 0:
        raise SystemExit(f"--serve-retries {args.serve_retries}: must be "
                         ">= 0 (0 = error a request on its first engine "
                         "fault)")
    if args.canary_slots < 0:
        raise SystemExit(f"--canary-slots {args.canary_slots}: must be "
                         ">= 0 (0 swaps verified weights without a "
                         "canary)")
    # the cap only binds when a reload watch will actually canary: the
    # default canary_slots must not invalidate small --max-slots runs
    if args.reload_watch and args.canary_slots >= args.max_slots:
        raise SystemExit(f"--canary-slots {args.canary_slots}: must be "
                         f"< --max-slots {args.max_slots} (at least one "
                         "slot must keep serving the stable weights)")
    for flag, v in (("--reload-watch", args.reload_watch),
                    ("--admission", args.admission)):
        if v and not args.serve:
            raise SystemExit(f"{flag} requires --serve (it extends the "
                             "post-train serving demo)")
    # serving quantization legality mirrors the engine constructors
    # (serve/quant.check_dtype + the PagedEngine-only int8 KV rule) so a
    # bad flag dies at parse time with the flag name, not inside a jit
    for flag, v in (("--kv-dtype", args.kv_dtype),
                    ("--weight-dtype", args.weight_dtype)):
        if v is not None and v not in ("bf16", "int8"):
            raise SystemExit(f"unknown {flag} {v!r}; choose bf16 or int8 "
                             "(or leave unset for full precision)")
    if args.replicas < 1:
        raise SystemExit(f"--replicas {args.replicas}: must be >= 1 "
                         "(1 = a single un-routed engine)")
    if args.replicas > 1 and not args.paged:
        raise SystemExit("--replicas > 1 requires --paged (the fleet "
                         "router's prefix-affinity placement and "
                         "zero-loss failover replay are built on the "
                         "paged engine's prefix index and ledger)")
    # the rebalance tier (evacuation + autoscaling) lives in the fleet
    # router: both flags are meaningless without a routed replica set
    if args.autoscale and args.replicas < 2:
        raise SystemExit("--autoscale requires --replicas > 1 (elastic "
                         "sizing grows/shrinks the fleet router's "
                         "replica set; a single un-routed engine has "
                         "nothing to scale)")
    if args.evacuate_on != "off" and args.replicas < 2:
        raise SystemExit(f"--evacuate-on {args.evacuate_on} requires "
                         "--replicas > 1 (a mid-request evacuation "
                         "needs a healthy peer to migrate the open "
                         "slots' committed KV to)")
    if args.priority_classes and not args.paged:
        raise SystemExit("--priority-classes requires --paged "
                         "(priority preemption spills and resumes "
                         "paged KV blocks)")
    if args.spill_dir and not args.priority_classes:
        raise SystemExit("--spill-dir requires --priority-classes "
                         "(spill files are only written when "
                         "preemption can fire)")
    if args.disagg and not args.paged:
        raise SystemExit("--disagg requires --paged (the prefill and "
                         "decode pools exchange committed paged-KV "
                         "blocks; the dense slot cache has no block "
                         "table to migrate)")
    if args.prefill_workers < 1:
        raise SystemExit(f"--prefill-workers {args.prefill_workers}: "
                         "must be >= 1")
    if args.prefill_workers != 1 and not args.disagg:
        raise SystemExit("--prefill-workers requires --disagg (worker "
                         "pools only exist in disaggregated serving)")
    if args.pool_elastic and not args.disagg:
        raise SystemExit("--pool-elastic requires --disagg (role "
                         "reassignment moves a worker between the "
                         "prefill and decode pools, which only exist "
                         "in disaggregated serving)")
    if args.disagg or args.migrate == "device":
        # these paths hard-require a device split, so resolve the
        # visible topology now and fail with the flag name instead of
        # deep inside engine construction (jax is imported lazily:
        # plain parses must not initialize a backend)
        import jax

        ndev = len(jax.local_devices())
        if args.migrate == "device" and ndev < 2:
            raise SystemExit("--migrate device: needs a second local "
                             f"device to park spilled KV on; only "
                             f"{ndev} visible — use --migrate host, or "
                             "run under a multi-device mesh (e.g. "
                             "XLA_FLAGS=--xla_force_host_platform_"
                             "device_count=2)")
        if args.disagg and ndev < 2:
            raise SystemExit("--disagg: disaggregated serving needs "
                             ">= 2 local devices (one per pool); only "
                             f"{ndev} visible — drop --disagg for the "
                             "unified paged engine, or run under a "
                             "multi-device mesh")
        if args.disagg and args.prefill_workers >= ndev:
            raise SystemExit(f"--prefill-workers {args.prefill_workers}"
                             f": the {ndev} visible devices must "
                             "partition into prefill + decode pools "
                             "with at least one decode worker — use "
                             f"1..{ndev - 1}")
    if args.publish_weights and not args.checkpoint_dir:
        raise SystemExit("--publish-weights requires --checkpoint-dir "
                         "(only verified checkpoint saves are "
                         "published for serving reload)")
    if args.kv_dtype == "int8" and not args.paged:
        raise SystemExit("--kv-dtype int8 requires --paged: int8 KV "
                         "stores per-position scales alongside the block "
                         "pools; the v1 slot table supports bf16 only "
                         "(the spec-decode draft pool inherits --kv-dtype "
                         "automatically)")
    return Config(
        num_layers=args.nlayers,
        size=args.size,
        epochs=args.epochs,
        batch_size=args.batch,
        device=Device(args.device) if args.device else None,
        num_workers=args.nworkers,
        mode=Mode(args.mode),
        microbatch=args.pipeline,
        world_size=args.run,
        seed=args.seed,
        learning_rate=args.lr,
        dtype=args.dtype,
        num_stages=args.nstages,
        mesh_shape=mesh_shape,
        double_softmax=args.double_softmax,
        sync_in_local_data_mode=args.sync,
        zero=args.zero,
        grad_compress=args.grad_compress,
        comm=args.comm,
        comm_overlap=args.comm_overlap,
        grad_accum=args.grad_accum,
        dropout=args.dropout,
        remat=args.remat,
        remat_policy=args.remat_policy,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=args.resume,
        profile_dir=args.profile_dir,
        data_dir=args.data_dir,
        packed_cache=args.packed_cache,
        image_size=args.image_size,
        stem_s2d=args.stem_s2d,
        attention=args.attention,
        attention_window=args.attention_window,
        optimizer=args.optimizer,
        generate_tokens=args.generate_tokens,
        serve=args.serve,
        max_slots=args.max_slots,
        prefill_buckets=parse_buckets_arg(args.prefill_buckets),
        paged=args.paged,
        kv_block_size=args.kv_block_size,
        prefill_chunk=args.prefill_chunk,
        draft=args.draft,
        spec_k=args.spec_k,
        slo_ttft_ms=args.slo_ttft_ms,
        slo_e2e_ms=args.slo_e2e_ms,
        serve_deadline_ms=args.serve_deadline_ms,
        serve_retries=args.serve_retries,
        reload_watch=args.reload_watch,
        canary_slots=args.canary_slots,
        admission=parse_admission_arg(args.admission),
        kv_dtype=args.kv_dtype,
        weight_dtype=args.weight_dtype,
        replicas=args.replicas,
        priority_classes=parse_priority_classes(args.priority_classes),
        spill_dir=args.spill_dir,
        autoscale=parse_autoscale_arg(args.autoscale),
        evacuate_on=args.evacuate_on,
        disagg=args.disagg,
        pool_elastic=args.pool_elastic,
        prefill_workers=args.prefill_workers,
        migrate=args.migrate,
        publish_weights=args.publish_weights,
        pos_embedding=args.pos_embedding,
        num_kv_heads=args.num_kv_heads,
        model_file=args.model_file,
        label_smoothing=args.label_smoothing,
        pipeline_schedule=args.pipeline_schedule,
        virtual_stages=args.virtual_stages,
        lr_schedule=args.lr_schedule,
        warmup_steps=args.warmup_steps,
        clip_norm=args.clip_norm,
        metrics_file=args.metrics_file,
        obs=args.obs,
        obs_file=args.obs_file,
        obs_trace=args.obs_trace,
        obs_rotate_mb=args.obs_rotate_mb,
        obs_blackbox=args.obs_blackbox,
        sentinel=args.sentinel,
        sentinel_window=args.sentinel_window,
        sentinel_factor=args.sentinel_factor,
        elastic=args.elastic,
        reshard=args.reshard,
        target_mesh=parse_mesh_arg(args.target_mesh, flag="--target-mesh"),
        heartbeat_dir=args.heartbeat_dir,
        heartbeat_timeout=args.heartbeat_timeout,
        autotune=args.autotune,
        plan_file=args.plan_file,
        distributed=dist,
    )
