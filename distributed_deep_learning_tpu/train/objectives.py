"""Losses and metrics matching the reference's definitions.

* accuracy: ``argmax(pred) == argmax(target)`` count × 100 / samples
  (``CNN/main.py:90-94``) — targets are one-hot/one-hot-ish rows.
* loss stream: the reference accumulates Σ(batch-mean loss) / Σ samples
  (quirk Q9 — a ÷batch_size skew vs the true mean).  The loop replicates
  that formula for log parity; the losses here are ordinary batch means.
* CE: the reference feeds Softmax outputs into ``CrossEntropyLoss``
  (quirk Q4), which re-softmaxes them — softmax CE applied to probabilities
  *is* that quirk; see :func:`cross_entropy_loss`.
* L1: the LSTM workload regresses 5 raw sensor targets with L1 while
  logging argmax "accuracy" (quirk Q5) — both definitions kept.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from distributed_deep_learning_tpu.ops.fused_ce import (DeferredLogits,
                                                        head_cross_entropy,
                                                        logits_at_rest,
                                                        note_logits)


def cross_entropy_loss(logits: jnp.ndarray, targets: jnp.ndarray,
                       from_probabilities: bool = False) -> jnp.ndarray:
    """Mean CE against one-hot(ish) targets.

    ``from_probabilities=True`` replicates reference quirk Q4 exactly:
    ``CrossEntropyLoss`` applied to softmax *outputs* re-softmaxes them —
    i.e. the probabilities are treated as logits, which is precisely what
    ``optax.softmax_cross_entropy`` does to its input.  The flag therefore
    changes nothing numerically; it exists to make call sites say which
    behaviour they mean (and to keep the quirk documented at the one place
    it acts).
    """
    del from_probabilities  # same math either way — see docstring
    losses = optax.softmax_cross_entropy(logits, targets)
    return jnp.mean(losses)


def l1_loss(pred: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(jnp.abs(pred - targets))


def token_cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray,
                        label_smoothing: float = 0.0,
                        pad_id: int | None = 0) -> jnp.ndarray:
    """Mean CE over non-pad token positions: ``logits`` (..., T, V) vs
    integer ids ``targets`` (..., T) where ``pad_id`` positions are
    ignored — the loss convention for the seq2seq and MLM north-star
    workloads (matching :func:`prediction_metrics`' pad exclusion).
    ``pad_id`` defaults to the package's reserved id 0; ``None`` means no
    padding id and every position counts (the :class:`..models.
    transformer.CausalLM` ``pad_id=None`` convention, e.g. imported
    GPT-2 where id 0 is a real token).

    ``label_smoothing`` ε spreads (1−ε) on the target id and ε/V on the
    rest (the transformer-base recipe, ε = 0.1 in the paper).

    `logits` not yet taken (:class:`..ops.fused_ce.DeferredLogits`, what
    the ``gpt`` workload's model hands a step) are scored a block at a
    time where they are large and a TPU's kernels run, the same loss with
    no ``(..., T, V)`` array at rest, and taken whole elsewhere
    (:func:`..ops.fused_ce.logits_at_rest`)."""
    logits, whole = _taken(logits)
    if not whole:
        return head_cross_entropy(logits.hidden, logits.table, targets,
                                  pad_id, label_smoothing)[0]
    valid = (targets != pad_id if pad_id is not None
             else jnp.ones(targets.shape, bool)).astype(jnp.float32)
    tgt = jnp.maximum(targets, 0)
    if label_smoothing:
        V = logits.shape[-1]
        eps = label_smoothing
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        per_tok = -(1.0 - eps) * picked - (eps / V) * jnp.sum(logp, axis=-1)
    else:
        per_tok = optax.softmax_cross_entropy_with_integer_labels(logits,
                                                                  tgt)
    return jnp.sum(per_tok * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def _taken(pred):
    """``(pred, whether it holds logits whole)``: arrays as they come, and
    logits not yet taken multiplied out where the rule says they rest."""
    if not isinstance(pred, DeferredLogits):
        note_logits()
        return pred, True
    logits = logits_at_rest(pred)
    return (pred, False) if logits is None else (logits, True)


def argmax_correct(pred: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Count of argmax matches in the batch (reference accuracy numerator).

    ``targets`` may be one-hot(ish) vectors (reference style) or integer
    class ids of one fewer dimension (token-level models, e.g. MLM).
    Integer targets equal to 0 are treated as padding and excluded
    (matching :func:`prediction_metrics`' count)."""
    correct, _ = _correct_and_count(pred, targets)
    return correct


def _correct_and_count(pred: jnp.ndarray, targets: jnp.ndarray
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    pred_cls = jnp.argmax(pred, axis=-1)
    if (targets.ndim == pred_cls.ndim
            and jnp.issubdtype(targets.dtype, jnp.integer)):
        # token-level: id 0 is pad — pad sites are neither correct nor counted
        valid = targets != 0
        correct = jnp.sum((pred_cls == targets) & valid)
        return correct, jnp.sum(valid).astype(jnp.int32)
    tgt_cls = jnp.argmax(targets, axis=-1)
    import math
    n_sites = math.prod(pred.shape[:-1])
    return jnp.sum(pred_cls == tgt_cls), jnp.asarray(n_sites, jnp.int32)


def prediction_metrics(pred: jnp.ndarray, targets: jnp.ndarray,
                       loss: jnp.ndarray) -> dict:
    """The phase-metric triple every step builder emits: batch loss, argmax
    matches, and prediction-site count (per-sample for (B,C) classifiers —
    the reference's denominator, ``CNN/main.py:90-94`` — per non-pad token
    for token-level models; logits not yet taken are counted through the
    same pass over the vocabulary as their loss)."""
    pred, whole = _taken(pred)
    if whole:
        correct, count = _correct_and_count(pred, targets)
    else:
        correct = head_cross_entropy(pred.hidden, pred.table, targets)[1]
        count = jnp.sum(targets != 0).astype(jnp.int32)
    return {"loss": loss, "correct": correct.astype(jnp.int32),
            "count": count}
