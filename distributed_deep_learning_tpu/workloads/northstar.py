"""North-star workloads behind the same CLI: resnet, transformer, bert.

These are the BASELINE.json configs (MNIST/CIFAR/ImageNet CNNs, WMT
seq2seq, C4 MLM) — scope beyond the reference, exposed exactly like its
workloads so one command line covers the whole model zoo::

    python -m distributed_deep_learning_tpu resnet -s 18 -e 5 -b 256 -m data
    python -m distributed_deep_learning_tpu transformer -l 6 -s 512 --zero 1
    python -m distributed_deep_learning_tpu bert -l 12 -s 768 --dtype bfloat16

Flag mapping: ``-l`` = layer count (transformer/bert), ``-s`` = ResNet
depth (18/34/50) or model width.  All run on synthetic shape-twins of the
real datasets (``data.datasets``) unless ``--data-dir`` points at real
files; the loaders' contract means pointing them at real data is a
dataset-constructor swap.

Parallel modes: ``-m data`` (+ ``--zero`` / ``--mesh model=K``) is the
primary path.  ``-m pipeline`` runs the SPMD pipeline for transformer/bert
(``build_pipelined`` → :mod:`..models.pipelined_lm`: ``stage`` mesh axis,
forward+backward in one XLA program) and MPMD staging for resnet;
``-m model`` stages the layer sequences over explicit devices.  moe rejects
staged modes (experts shard over the ``expert`` axis instead).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

from distributed_deep_learning_tpu.data.datasets import (ArrayDataset,
                                                         synthetic_c4_mlm,
                                                         synthetic_cifar10,
                                                         synthetic_wmt)
from distributed_deep_learning_tpu.models.resnet import (BasicBlock,
                                                         BottleneckBlock,
                                                         ResNet)
from distributed_deep_learning_tpu.models.transformer import (BertEncoder,
                                                              TransformerSeq2Seq)
from distributed_deep_learning_tpu.parallel.partition import balanced_partition
from distributed_deep_learning_tpu.parallel.tensor_parallel import (
    transformer_tp_rules)
from distributed_deep_learning_tpu.train.objectives import (
    cross_entropy_loss, token_cross_entropy)
from distributed_deep_learning_tpu.utils.config import Config, parse_args
from distributed_deep_learning_tpu.workloads.base import (WorkloadSpec,
                                                          adamw,
                                                          config_dtype,
                                                          example_from_dataset,
                                                          resolve_lr,
                                                          run_workload)

_RESNET_LAYERS = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3)}


# --- resnet ----------------------------------------------------------------

def _resnet_dataset(config: Config):
    """Real ImageFolder data when ``--data-dir`` is given (decode threads
    driven by ``-w``), the synthetic CIFAR twin otherwise."""
    if config.data_dir:
        from distributed_deep_learning_tpu.data.imagefolder import (
            ImageFolderDataset)

        return ImageFolderDataset(config.data_dir,
                                  image_size=config.image_size,
                                  num_workers=config.num_workers or 8)
    return synthetic_cifar10(seed=config.seed)


def _resnet_geometry(config: Config, dataset):
    depth = config.size if config.size in _RESNET_LAYERS else 18
    num_classes = len(getattr(dataset, "classes", ())) or 10
    # Decoded image size decides the stem: small inputs (<=64 px, the
    # CIFAR twin included) use the 3x3-s1 stem, ImageNet-size the 7x7-s2.
    # Materialised datasets (synthetic twins, --packed-cache) carry their
    # size in the feature array; the lazy ImageFolder path decodes at
    # --image-size.
    feats = getattr(dataset, "features", None)
    if feats is not None and feats.ndim == 4:
        small = feats.shape[1] <= 64
    else:
        small = config.image_size <= 64 if config.data_dir else True
    return depth, num_classes, small


def _resnet_model(config: Config, dataset):
    depth, num_classes, small = _resnet_geometry(config, dataset)
    return ResNet(stage_sizes=_RESNET_LAYERS[depth],
                  block_cls=BottleneckBlock if depth >= 50 else BasicBlock,
                  num_classes=num_classes, small_inputs=small,
                  stem_s2d=config.stem_s2d and not small,
                  dtype=config_dtype(config))


def _resnet_layers(config: Config, dataset):
    from distributed_deep_learning_tpu.models.resnet import (
        resnet_layer_sequence)

    depth, num_classes, small = _resnet_geometry(config, dataset)
    return resnet_layer_sequence(
        stage_sizes=_RESNET_LAYERS[depth],
        block_cls=BottleneckBlock if depth >= 50 else BasicBlock,
        num_classes=num_classes, width=64, small_inputs=small,
        dtype=config_dtype(config))


RESNET_SPEC = WorkloadSpec(
    name="resnet",
    build_dataset=_resnet_dataset,
    build_model=_resnet_model,
    build_layers=_resnet_layers,
    partitioner=balanced_partition,
    build_loss=lambda c: cross_entropy_loss,
    build_optimizer=lambda c, steps: optax.sgd(
        resolve_lr(c, steps,
                   c.learning_rate if c.learning_rate != 1e-3 else 0.1),
        momentum=0.9),
    example_input=example_from_dataset,
)




def _token_ce_loss(c: Config):
    """Per-config token cross-entropy (single definition for the four LM
    specs — --label-smoothing rides through here)."""
    from functools import partial

    return partial(token_cross_entropy, label_smoothing=c.label_smoothing)


def _n_chunks(config: Config) -> int:
    """Chunks per device for the interleaved pipeline schedule (1 = plain
    stacking for gpipe/1f1b)."""
    return (config.virtual_stages
            if config.pipeline_schedule == "interleaved" else 1)

# --- transformer (WMT seq2seq) --------------------------------------------

class Seq2SeqAdapter(nn.Module):
    """Adapts ``TransformerSeq2Seq``'s batch-dict interface to the runner's
    ``model(x, train)`` convention: ``x`` is source and target token ids
    concatenated along the sequence axis (``src_len`` is static)."""

    model: TransformerSeq2Seq
    src_len: int

    @nn.compact
    def __call__(self, x, train: bool = False):
        batch = {"inputs": x[:, :self.src_len],
                 "targets": x[:, self.src_len:]}
        return self.model(batch, train=train)


def _wmt_dataset(config: Config, src_len: int = 32, tgt_len: int = 32,
                 vocab: int = 1024):
    if config.data_dir:
        from distributed_deep_learning_tpu.data.tokens import (load_tokens,
                                                               seq2seq_dataset)

        tokens = load_tokens(config.data_dir)
        if tokens is not None:
            return seq2seq_dataset(tokens)
    ds = synthetic_wmt(src_len=src_len, tgt_len=tgt_len, vocab_size=vocab,
                       seed=config.seed)
    feats = np.concatenate([ds.features, ds.targets], axis=1)
    return ArrayDataset(feats, ds.targets)


def _transformer_model(config: Config, dataset):
    d = config.size
    # --dropout seeds per-step PRNG streams through TrainState.rng;
    # the default 0.0 keeps steps deterministic (reference seed-42 contract)
    inner = TransformerSeq2Seq(
        vocab_size=_vocab(dataset), num_layers=config.num_layers, d_model=d,
        num_heads=max(2, d // 64), mlp_dim=4 * d,
        dropout_rate=config.dropout, dtype=config_dtype(config),
        attention_fn=_attention_fn(config))
    src_len = dataset.features.shape[1] - dataset.targets.shape[1]
    return Seq2SeqAdapter(inner, src_len)


def _attention_fn(config: Config):
    """Resolve ``--attention``: the Pallas flash kernel is the TPU default
    for the transformer family (in-kernel causal + padding masks, no (T×T)
    score materialisation); dense elsewhere, and either can be forced.
    ``auto`` is a function of the backend alone.
    """
    choice = config.attention
    if choice == "auto":
        import jax

        choice = "flash" if jax.default_backend() == "tpu" else "dense"
    if choice == "flash":
        from distributed_deep_learning_tpu.ops.attention_pallas import (
            make_attention_fn)

        return make_attention_fn()
    return None  # models fall back to dense dot_product_attention
    # (--window rides as a MODEL attribute — CausalLM.attention_window —
    # so the flash kernel, the dense fallback and the KV-cache decode all
    # apply the same band; see models/transformer.py)


def _vocab(dataset) -> int:
    """Vocabulary size: carried by file-based token datasets, 1024 for the
    synthetic twins."""
    return int(getattr(dataset, "vocab_size", 1024))


def _lm_geometry(config: Config, dataset):
    """(d_model, heads, mlp_dim, src_len, tgt_len) for the LM variants."""
    d = config.size
    tgt_len = dataset.targets.shape[1]
    src_len = dataset.features.shape[1] - tgt_len
    return d, max(2, d // 64), 4 * d, src_len, tgt_len


def _transformer_pipelined(config: Config, dataset, mesh):
    """``-m pipeline``: decoder-only causal LM over src⊕tgt tokens, logits
    read at the target positions (see :mod:`..models.pipelined_lm` for the
    divergence rationale — SPMD pipelining needs a homogeneous trunk)."""
    from distributed_deep_learning_tpu.models.pipelined_lm import PipelinedLM

    d, heads, mlp, src_len, tgt_len = _lm_geometry(config, dataset)
    return PipelinedLM(vocab_size=_vocab(dataset),
                       num_layers=config.num_layers,
                       d_model=d, num_heads=heads, mlp_dim=mlp, mesh=mesh,
                       causal=True, head_take=(src_len - 1, tgt_len),
                       microbatch_size=config.microbatch,
                       dtype=config_dtype(config),
                       attention_fn=_attention_fn(config),
                       dropout_rate=config.dropout,
                       n_chunks=_n_chunks(config))


def _transformer_layers(config: Config, dataset):
    """``-m model``: the same decoder-only LM as a partitionable layer list
    (embed / causal blocks / sliced head) for MPMD staging."""
    from distributed_deep_learning_tpu.models.pipelined_lm import (LMEmbed,
                                                                   LMHead)
    from distributed_deep_learning_tpu.models.transformer import (
        TransformerLayer)

    d, heads, mlp, src_len, tgt_len = _lm_geometry(config, dataset)
    dtype = config_dtype(config)
    vocab = _vocab(dataset)
    return [LMEmbed(vocab, d, dtype=dtype)] + [
        TransformerLayer(heads, mlp, dropout_rate=0.0, causal=True,
                         dtype=dtype)
        for _ in range(config.num_layers)
    ] + [LMHead(vocab, take=(src_len - 1, tgt_len), dtype=dtype)]


TRANSFORMER_SPEC = WorkloadSpec(
    name="transformer",
    build_dataset=_wmt_dataset,
    build_model=_transformer_model,
    build_layers=_transformer_layers,
    partitioner=balanced_partition,
    build_loss=_token_ce_loss,
    build_optimizer=lambda c, steps: adamw(
        resolve_lr(c, steps, c.learning_rate)),
    example_input=lambda c, ds: jnp.zeros((1, ds.features.shape[1]),
                                          jnp.int32),
    tp_rules=lambda c: transformer_tp_rules(),
    build_pipelined=_transformer_pipelined,
)


# --- bert (C4 MLM) ---------------------------------------------------------

def _mlm_dataset(config: Config, vocab: int = 1024, mask_id: int = 103):
    if config.data_dir:
        from distributed_deep_learning_tpu.data.tokens import (load_tokens,
                                                               mlm_dataset)

        tokens = load_tokens(config.data_dir)
        if tokens is not None:
            return mlm_dataset(tokens, mask_id=mask_id, seed=config.seed)
    ds = synthetic_c4_mlm(vocab_size=vocab, mask_id=mask_id, seed=config.seed)
    # loss/metric sites are exactly the masked positions: keep the original
    # id there and 0 (= ignore) everywhere else, matching the pad-exclusion
    # convention of token_cross_entropy / prediction_metrics
    targets = np.where(ds.features == mask_id, ds.targets, 0)
    return ArrayDataset(ds.features, targets.astype(np.int32))


def _bert_model(config: Config, dataset):
    d = config.size
    return BertEncoder(vocab_size=_vocab(dataset),
                       num_layers=config.num_layers,
                       d_model=d, num_heads=max(2, d // 64), mlp_dim=4 * d,
                       dropout_rate=config.dropout,
                       dtype=config_dtype(config),
                       attention_fn=_attention_fn(config))


def _bert_pipelined(config: Config, dataset, mesh):
    """``-m pipeline``: bidirectional trunk + untied MLM head over the
    ``stage`` axis (the full BertEncoder's tied head stays in ``-m data``)."""
    from distributed_deep_learning_tpu.models.pipelined_lm import PipelinedLM

    d = config.size
    return PipelinedLM(vocab_size=_vocab(dataset),
                       num_layers=config.num_layers,
                       d_model=d, num_heads=max(2, d // 64), mlp_dim=4 * d,
                       mesh=mesh, causal=False,
                       microbatch_size=config.microbatch,
                       dtype=config_dtype(config),
                       attention_fn=_attention_fn(config),
                       dropout_rate=config.dropout,
                       n_chunks=_n_chunks(config))


def _bert_layers(config: Config, dataset):
    from distributed_deep_learning_tpu.models.pipelined_lm import (LMEmbed,
                                                                   LMHead)
    from distributed_deep_learning_tpu.models.transformer import (
        TransformerLayer)

    d = config.size
    dtype = config_dtype(config)
    vocab = _vocab(dataset)
    return [LMEmbed(vocab, d, dtype=dtype)] + [
        TransformerLayer(max(2, d // 64), 4 * d, dropout_rate=0.0,
                         dtype=dtype)
        for _ in range(config.num_layers)
    ] + [LMHead(vocab, dtype=dtype)]


BERT_SPEC = WorkloadSpec(
    name="bert",
    build_dataset=_mlm_dataset,
    build_model=_bert_model,
    build_layers=_bert_layers,
    partitioner=balanced_partition,
    build_loss=_token_ce_loss,
    build_optimizer=lambda c, steps: adamw(
        resolve_lr(c, steps, c.learning_rate)),
    example_input=lambda c, ds: jnp.zeros((1, ds.features.shape[1]),
                                          jnp.int32),
    tp_rules=lambda c: transformer_tp_rules(),
    build_pipelined=_bert_pipelined,
)

# --- moe (sparse-expert MLM) -----------------------------------------------

def _moe_model(config: Config, dataset):
    from distributed_deep_learning_tpu.models.moe import MoELM

    d = config.size
    return MoELM(vocab_size=_vocab(dataset),
                 num_layers=config.num_layers, d_model=d,
                 num_heads=max(2, d // 64), mlp_dim=4 * d,
                 num_experts=8, dropout_rate=config.dropout,
                 dtype=config_dtype(config),
                 attention_fn=_attention_fn(config))


def _moe_rules(config: Config):
    """Expert weights over `expert`; everything else replicated (dense
    blocks could add the Megatron rules, kept replicated for clarity)."""
    from distributed_deep_learning_tpu.models.moe import moe_param_rules

    return moe_param_rules()


def _moe_no_staging(config, dataset):
    raise ValueError(
        "moe parallelises over experts, not stages: use -m data with "
        "--mesh expert=K (staged modes would drop the router's "
        "load-balance aux loss)")


MOE_SPEC = WorkloadSpec(
    name="moe",
    build_dataset=_mlm_dataset,
    build_model=_moe_model,
    build_layers=_moe_no_staging,
    partitioner=lambda n, s: np.zeros(n, np.int64),
    build_loss=_token_ce_loss,
    build_optimizer=lambda c, steps: adamw(
        resolve_lr(c, steps, c.learning_rate)),
    example_input=lambda c, ds: jnp.zeros((1, ds.features.shape[1]),
                                          jnp.int32),
    tp_rules=_moe_rules,
)

# --- gpt (decoder-only causal LM) ------------------------------------------

def _gpt_dataset(config: Config, seq_len: int = 64, vocab: int = 1024):
    if config.data_dir:
        from distributed_deep_learning_tpu.data.tokens import (lm_dataset,
                                                               load_tokens)

        tokens = load_tokens(config.data_dir)
        if tokens is not None:
            return lm_dataset(tokens)
    from distributed_deep_learning_tpu.data.datasets import synthetic_lm

    if config.model_file:       # the described model's own vocabulary
        from distributed_deep_learning_tpu.data.tokens import (
            TokenArrayDataset)
        from distributed_deep_learning_tpu.models import describe

        vocab = int(describe.read(config.model_file)["vocab_size"])
        ds = synthetic_lm(seq_len=seq_len, vocab_size=vocab,
                          seed=config.seed)
        return TokenArrayDataset(ds.features, ds.targets, vocab)
    # vocab matches _vocab()'s synthetic default (1024)
    return synthetic_lm(seq_len=seq_len, vocab_size=vocab, seed=config.seed)


def _gpt_model(config: Config, dataset):
    from distributed_deep_learning_tpu.models.transformer import CausalLM

    if config.model_file:
        from distributed_deep_learning_tpu.models import describe

        return describe.causal_lm(
            describe.read(config.model_file), vocab_size=_vocab(dataset),
            max_len=max(dataset.features.shape[1], 8),
            dropout_rate=config.dropout, with_logits="deferred",
            dtype=config_dtype(config),
            attention_fn=_attention_fn(config))
    d = config.size
    return CausalLM(vocab_size=_vocab(dataset),
                    num_layers=config.num_layers, d_model=d,
                    num_heads=max(2, d // 64), mlp_dim=4 * d,
                    dropout_rate=config.dropout, with_logits="deferred",
                    max_len=max(dataset.features.shape[1], 8),
                    pos_embedding=config.pos_embedding,
                    attention_window=config.attention_window,
                    num_kv_heads=config.num_kv_heads,
                    dtype=config_dtype(config),
                    attention_fn=_attention_fn(config))


def _no_staged_description(config: Config) -> None:
    if config.model_file:
        raise ValueError(
            f"--model-file describes a whole decoder, layer by layer; -m "
            f"{config.mode.value} builds uniform stages of its own (use -m "
            "data or sequential)")


def _gpt_layers(config: Config, dataset):
    """``-m model``: embed / causal blocks / full-sequence head."""
    _no_staged_description(config)
    from distributed_deep_learning_tpu.models.pipelined_lm import (LMEmbed,
                                                                   LMHead)
    from distributed_deep_learning_tpu.models.transformer import (
        TransformerLayer)

    d = config.size
    dtype = config_dtype(config)
    max_len = max(dataset.features.shape[1], 8)
    return [LMEmbed(_vocab(dataset), d, max_len=max_len, dtype=dtype,
                    pos_embedding=config.pos_embedding)] + [
        TransformerLayer(max(2, d // 64), 4 * d, dropout_rate=0.0,
                         causal=True, dtype=dtype,
                         rope=config.pos_embedding == "rope",
                         window=config.attention_window,
                         num_kv_heads=config.num_kv_heads)
        for _ in range(config.num_layers)
    ] + [LMHead(_vocab(dataset), dtype=dtype)]  # predict at every position


def _gpt_pipelined(config: Config, dataset, mesh):
    from distributed_deep_learning_tpu.models.pipelined_lm import PipelinedLM

    _no_staged_description(config)
    d = config.size
    return PipelinedLM(vocab_size=_vocab(dataset),
                       num_layers=config.num_layers, d_model=d,
                       num_heads=max(2, d // 64), mlp_dim=4 * d, mesh=mesh,
                       causal=True,  # head_take None: every position
                       microbatch_size=config.microbatch,
                       max_len=max(dataset.features.shape[1], 4096),
                       dtype=config_dtype(config),
                       attention_fn=_attention_fn(config),
                       dropout_rate=config.dropout,
                       n_chunks=_n_chunks(config),
                       pos_embedding=config.pos_embedding,
                       attention_window=config.attention_window,
                       num_kv_heads=config.num_kv_heads)


#: prompt length _gpt_generate slices from the dataset (rows 0-1)
_GENERATE_PROMPT_LEN = 8


def _gpt_pre_check(config: Config, dataset) -> None:
    """Reject, BEFORE training, what the post-train hooks could only
    refuse after the expensive part has finished (ADVICE r3): an explicit
    ``--serve`` under a staged/pipelined mode (the engines need the
    whole-model parameter tree) and an impossible ``--generate N``
    (generate() checks prompt + N <= max_len itself, but too late).
    ``--generate`` alone stays exempt under staged/pipelined modes —
    :func:`_gpt_generate` skips there with a notice, so the length can
    never be exercised."""
    from distributed_deep_learning_tpu.utils.config import Mode

    staged = config.mode in (Mode.MODEL, Mode.PIPELINE)
    if config.serve and staged:
        raise ValueError(
            f"--serve needs the whole-model parameter tree; -m "
            f"{config.mode.value} trains per-stage parameters (use -m "
            "data or sequential)")
    if not config.generate_tokens or staged:
        return
    max_len = max(dataset.features.shape[1], 8)  # mirrors _gpt_model
    prompt = min(_GENERATE_PROMPT_LEN, dataset.features.shape[1])
    if prompt + config.generate_tokens > max_len:
        raise ValueError(
            f"--generate {config.generate_tokens}: prompt {prompt} + new "
            f"tokens exceeds the model's max_len {max_len} (the dataset "
            f"sequence length); at most {max_len - prompt} tokens fit")


def _gpt_generate(config: Config, state, logger, dataset) -> None:
    """``--generate N``: print KV-cached greedy continuations of two
    dataset prompts (rows 0-1 — typically TRAINING rows after the
    shuffled split, so treat the output as a smoke sample, not held-out
    evaluation) in the reference's quote-delimited log style."""
    from distributed_deep_learning_tpu.models.transformer import generate

    params = getattr(state, "params", None)
    if isinstance(params, dict) and "params" in params:
        params = params["params"]
    if not isinstance(params, dict) or "embed" not in params:
        # staged/pipelined states carry per-stage param lists, not the
        # CausalLM tree — a notice, not a crash, after a finished run
        logger.info("generate skipped: --generate needs the whole-model "
                    "parameter tree (-m data or sequential)")
        return
    model = _gpt_model(config, dataset)
    prompts = jnp.asarray(dataset.features[:2, :_GENERATE_PROMPT_LEN],
                          jnp.int32)
    out = generate(model, params, prompts,
                   max_new_tokens=config.generate_tokens)
    for row_p, row_o in zip(prompts.tolist(), out.tolist()):
        logger.info(f"generate prompt={row_p} continuation={row_o}")


def _serve_supervision_kw(config: Config) -> dict | None:
    """Supervisor kwargs when any serve-resilience knob is on the CLI
    (``--serve-deadline-ms`` / ``--reload-watch`` / ``--admission``);
    ``None`` means run the engine bare, exactly as before the
    supervisor existed.  ``--serve-retries`` and ``--canary-slots``
    only shape behaviour once one of the trigger knobs is set."""
    if (config.serve_deadline_ms is None and not config.reload_watch
            and config.admission is None):
        return None
    return dict(deadline_ms=config.serve_deadline_ms,
                retries=config.serve_retries,
                reload_watch=config.reload_watch,
                canary_slots=config.canary_slots,
                admission=config.admission)


def _log_supervision(logger, sv: dict) -> None:
    """One log line for the supervisor-level outcome (the engine-level
    tokens/sec line still follows from ``stats["engine"]``)."""
    line = (f"serve(supervised): restarts={sv['restarts']}, lost="
            f"{sv['requests_lost']}, deadline_misses="
            f"{sv['deadline_misses']}, ticks={sv['ticks']}")
    r = sv.get("reload")
    if r:
        line += (f", reload swaps={r['swaps']} rollbacks={r['rollbacks']}"
                 f" rejected={r['rejected']}")
    a = sv.get("admission")
    if a:
        line += f", admission level={a['level']} shed={a['shed_total']}"
    logger.info(line)


def _gpt_serve(config: Config, state, logger, dataset) -> None:
    """``--serve``: push a seeded mixed-length request trace (prompts
    drawn over the dataset's vocabulary) through the continuous-batching
    engine (serve/engine.py) on the just-trained weights and log
    tokens/sec, mean slot occupancy and compile counts — the
    serving-path sibling of ``--generate``'s batch-synchronous smoke
    sample.  With ``--paged`` the trace goes through the paged engine
    instead (block KV + prefix reuse + chunked prefill, ``--draft N``
    speculation) and the log line adds hit rate / acceptance / SLOs."""
    from distributed_deep_learning_tpu.serve.engine import ServeEngine
    from distributed_deep_learning_tpu.serve.load import make_trace
    from distributed_deep_learning_tpu.serve.supervisor import run_supervised

    params = getattr(state, "params", None)
    if isinstance(params, dict) and "params" in params:
        params = params["params"]
    if not isinstance(params, dict) or "embed" not in params:
        # _gpt_pre_check rejects the staged modes before training; an
        # explicit --serve that cannot serve is an error, never a notice
        raise ValueError("--serve needs the whole-model parameter tree "
                         "(-m data or sequential)")
    model = _gpt_model(config, dataset)
    seq = dataset.features.shape[1]
    # prompt + budget must fit the slot capacity (the model's max_len,
    # dataset-derived and possibly tiny in smoke runs)
    p_hi = max(2, min(_GENERATE_PROMPT_LEN, seq, model.max_len - 1))
    new_hi = max(1, min(config.generate_tokens or 16,
                        model.max_len - p_hi))
    if config.paged:
        _gpt_serve_paged(config, model, params, logger, dataset,
                         p_hi, new_hi)
        return
    trace = make_trace(max(2 * config.max_slots, 8),
                       vocab_size=_vocab(dataset), seed=config.seed,
                       prompt_lens=(2, p_hi), new_tokens=(1, new_hi))
    sup_kw = _serve_supervision_kw(config)
    quant_kw = dict(kv_dtype=config.kv_dtype,
                    weight_dtype=config.weight_dtype)
    if sup_kw is None:
        out = ServeEngine(model, params, max_slots=config.max_slots,
                          prefill_buckets=config.prefill_buckets,
                          **quant_kw).run(trace)
        s = out["stats"]
    else:
        out = run_supervised(model, params, trace,
                             max_slots=config.max_slots,
                             prefill_buckets=config.prefill_buckets,
                             **quant_kw, **sup_kw)
        _log_supervision(logger, out["stats"])
        s = out["stats"]["engine"]
        if s is None:
            return
    logger.info(
        f"serve: {s['requests']} requests, {s['generated_tokens']} tokens "
        f"at {s['tokens_per_sec']:.1f} tok/s, occupancy "
        f"{s['mean_slot_occupancy']:.2f}/{s['max_slots']}, compiles "
        f"prefill={s['prefill_compiles']} decode={s['decode_compiles']}")


def _gpt_serve_paged(config: Config, model, params, logger, dataset,
                     p_hi: int, new_hi: int) -> None:
    """``--serve --paged``: the same trace shape through the paged
    engine, with the config's block/chunk/draft/SLO knobs applied."""
    import dataclasses

    from distributed_deep_learning_tpu.serve.engine import PagedEngine
    from distributed_deep_learning_tpu.serve.load import make_trace
    from distributed_deep_learning_tpu.serve.paged import paged_max_len
    from distributed_deep_learning_tpu.serve.supervisor import run_supervised

    draft = config.draft or None
    if draft is not None and not 1 <= draft < model.num_layers:
        logger.info(f"serve: --draft {draft} needs 1 <= draft < "
                    f"{model.num_layers} (the model's layer count); "
                    "speculation disabled")
        draft = None
    block = min(config.kv_block_size, model.max_len)
    cap = paged_max_len(model.max_len, block, draft is not None,
                        config.spec_k)
    p_hi = max(2, min(p_hi, cap - 1))
    new_hi = max(1, min(new_hi, cap - p_hi))
    trace = make_trace(max(2 * config.max_slots, 8),
                       vocab_size=_vocab(dataset), seed=config.seed,
                       prompt_lens=(2, p_hi), new_tokens=(1, new_hi))
    if config.slo_ttft_ms or config.slo_e2e_ms:
        trace = [dataclasses.replace(r, slo_ttft_ms=config.slo_ttft_ms,
                                     slo_e2e_ms=config.slo_e2e_ms)
                 for r in trace]
    engine_kw = dict(max_slots=config.max_slots, max_len=cap,
                     kv_block_size=block,
                     prefill_chunk=min(config.prefill_chunk, cap),
                     draft_layers=draft, spec_k=config.spec_k,
                     kv_dtype=config.kv_dtype,
                     weight_dtype=config.weight_dtype)
    if config.priority_classes:
        # seeded priority mix over the same trace (mirrors
        # LoadSpec.priority_classes) + engine-side preemption so the
        # mix has teeth: low-priority slots spill under pressure
        pcs = config.priority_classes
        rng = np.random.default_rng(config.seed)
        fr = np.asarray([f for _, f in pcs]) / sum(f for _, f in pcs)
        trace = [dataclasses.replace(r, priority=int(
            rng.choice([p for p, _ in pcs], p=fr))) for r in trace]
        engine_kw.update(preempt=True, spill_dir=config.spill_dir,
                         migrate=config.migrate)
    if config.disagg:
        _gpt_serve_disagg(config, model, params, logger, trace, engine_kw)
        return
    if config.replicas > 1:
        _gpt_serve_fleet(config, model, params, logger, trace, engine_kw)
        return
    sup_kw = _serve_supervision_kw(config)
    if sup_kw is None:
        out = PagedEngine(model, params, **engine_kw).run(trace)
        s = out["stats"]
    else:
        out = run_supervised(model, params, trace, paged=True,
                             **engine_kw, **sup_kw)
        _log_supervision(logger, out["stats"])
        s = out["stats"]["engine"]
        if s is None:
            return
    pg, sp, slo = s["paged"], s["spec"], s["slo"]
    line = (f"serve(paged): {s['requests']} requests "
            f"({len(out['results'])} completed, {len(out['errors'])} "
            f"errors), {s['generated_tokens']} tokens at "
            f"{s['tokens_per_sec']:.1f} tok/s, prefix hit "
            f"{pg['prefix_hit_rate']:.3f}, cow {pg['cow_copies']}, "
            f"compiles chunk={s['chunk_compiles']} "
            f"decode={s['decode_compiles']} "
            f"verify={s['verify_compiles']}")
    if sp["enabled"] and sp["acceptance_rate"] is not None:
        line += f", spec acceptance {sp['acceptance_rate']:.3f}"
    if slo["slo_attainment"] is not None:
        line += f", slo attainment {slo['slo_attainment']:.2f}"
    logger.info(line)


def _gpt_serve_fleet(config: Config, model, params, logger, trace,
                     engine_kw: dict) -> None:
    """``--serve --paged --replicas N``: the same trace through N
    supervised paged replicas behind the prefix-affinity fleet router
    (serve/fleet.py) — crash quarantine, zero-loss replay, per-priority
    SLO rollup."""
    from distributed_deep_learning_tpu.serve.admission import (
        AdmissionController)
    from distributed_deep_learning_tpu.serve.engine import PagedEngine
    from distributed_deep_learning_tpu.serve.fleet import FleetRouter

    engines = [PagedEngine(model, params, **engine_kw)
               for _ in range(config.replicas)]
    admissions = None
    if config.admission is not None:
        admissions = {i: AdmissionController(**config.admission)
                      for i in range(config.replicas)}
    autoscaler = engine_factory = None
    if config.autoscale is not None:
        from distributed_deep_learning_tpu.serve.autoscaler import (
            FleetAutoscaler)

        autoscaler = FleetAutoscaler(**config.autoscale)
        # the published-weights seam: every grown replica serves the
        # same params the fleet was launched with
        engine_factory = lambda: PagedEngine(model, params, **engine_kw)  # noqa: E731
    flt = FleetRouter(engines, deadline_ms=config.serve_deadline_ms,
                      retries=config.serve_retries, admissions=admissions,
                      evacuate_on=config.evacuate_on,
                      autoscaler=autoscaler, engine_factory=engine_factory)
    out = flt.run(list(trace))
    st = out["stats"]
    tokens = sum(len(v) for v in out["results"].values())
    line = (f"serve(fleet): {st['requests']} requests over "
            f"{len(engines)} replicas, {tokens} tokens, rounds="
            f"{st['rounds']}, lost={st['requests_lost']}, predicted hit "
            f"tokens {st['routing']['predicted_hit_tokens']}, compiles "
            f"decode={max(v['decode_compiles'] for v in st['per_replica'].values())}")
    slo = st["slo"]
    if slo.get("slo_attainment") is not None:
        line += f", slo attainment {slo['slo_attainment']:.2f}"
        bp = slo.get("by_priority") or {}
        if bp:
            line += " (" + ", ".join(
                f"p{p}={s['slo_attainment']:.2f}" for p, s in
                sorted(bp.items()) if s["slo_attainment"] is not None) + ")"
    rb = st.get("rebalance")
    if rb and rb["evacuate_on"] != "off":
        line += (f", evacuated {rb['evacuated_slots']} slots "
                 f"({rb['evacuated_tokens']} tokens, "
                 f"{rb['rolled_back']} rolled back)")
    asc = st.get("autoscaler")
    if asc:
        line += (f", scale events {asc['scale_events']} "
                 f"(+{asc['grows']}/-{asc['shrinks']}, "
                 f"{asc['replicas_final']} final)")
    logger.info(line)


def _gpt_serve_disagg(config: Config, model, params, logger, trace,
                      engine_kw: dict) -> None:
    """``--serve --paged --disagg``: the same trace through the
    disaggregated engine (serve/disagg.py) — prefill worker pool +
    decode worker pool on disjoint devices, per-prompt KV-block
    migration handoff, greedy outputs bit-identical to the unified
    engine."""
    import jax

    from distributed_deep_learning_tpu.serve.disagg import DisaggEngine

    if config.draft:
        logger.info("serve(disagg): --draft ignored (speculation runs "
                    "on the unified engine only)")
    ndev = len(jax.local_devices())
    eng = DisaggEngine(
        model, params,
        prefill_workers=config.prefill_workers,
        decode_workers=max(1, ndev - config.prefill_workers),
        max_slots=engine_kw["max_slots"], max_len=engine_kw["max_len"],
        kv_block_size=engine_kw["kv_block_size"],
        prefill_chunk=engine_kw["prefill_chunk"],
        kv_dtype=engine_kw["kv_dtype"],
        weight_dtype=engine_kw["weight_dtype"])
    out = eng.run(list(trace))
    s = out["stats"]
    mig = s["migration"]
    logger.info(
        f"serve(disagg): {s['requests']} requests, "
        f"{s['generated_tokens']} tokens at "
        f"{s['tokens_per_sec']:.1f} tok/s over "
        f"{config.prefill_workers}P+{max(1, ndev - config.prefill_workers)}D, "
        f"prefill util {s['prefill_util']:.2f}, migrated "
        f"{mig['moves']} handoffs ({mig['wire_bytes']} B), compiles "
        f"chunk={s['chunk_compiles']} decode={s['decode_compiles']}")
    if config.pool_elastic:
        from distributed_deep_learning_tpu.serve.autoscaler import (
            PoolRebalancer)

        # judge the measured utilisation as a sustained signal: the
        # run-level prefill_util IS the whole run's average, so feed it
        # through the full patience window before actuating
        bal = PoolRebalancer()
        direction = None
        for _ in range(bal.patience):
            direction = bal.observe(s["prefill_util"])
        if direction and eng.reassign(direction):
            logger.info(
                f"serve(disagg): pool-elastic moved one worker "
                f"{direction.replace('_', ' ')} (prefill util "
                f"{s['prefill_util']:.2f}); pools now "
                f"{len(eng.prefill)}P+{len(eng.decode)}D")
        else:
            logger.info(
                f"serve(disagg): pool-elastic held the split "
                f"(prefill util {s['prefill_util']:.2f} inside the "
                f"hysteresis band, or no idle worker to move)")


def _gpt_post(config: Config, state, logger, dataset) -> None:
    if config.generate_tokens:
        _gpt_generate(config, state, logger, dataset)
    if config.serve:
        _gpt_serve(config, state, logger, dataset)


GPT_SPEC = WorkloadSpec(
    name="gpt",
    build_dataset=_gpt_dataset,
    build_model=_gpt_model,
    build_layers=_gpt_layers,
    partitioner=balanced_partition,
    build_loss=_token_ce_loss,
    build_optimizer=lambda c, steps: adamw(
        resolve_lr(c, steps, c.learning_rate)),
    example_input=lambda c, ds: jnp.zeros((1, ds.features.shape[1]),
                                          jnp.int32),
    tp_rules=lambda c: transformer_tp_rules(),
    build_pipelined=_gpt_pipelined,
    post_train=_gpt_post,
    pre_train_check=_gpt_pre_check,
)

SPECS = {"resnet": RESNET_SPEC, "transformer": TRANSFORMER_SPEC,
         "bert": BERT_SPEC, "moe": MOE_SPEC, "gpt": GPT_SPEC}


def main(argv=None, workload: str = "resnet"):
    config = parse_args(argv, workload=workload)
    return run_workload(SPECS[workload], config)
