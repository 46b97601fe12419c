"""Mixture-of-Experts MLP with expert parallelism over the ``expert`` axis.

Beyond-reference capability (SURVEY.md §2.5 lists EP as absent): a
GShard-style top-2 routed MLP whose expert weights are stacked along a
leading E axis.  Under pjit, sharding that axis with
``PartitionSpec("expert", ...)`` places one expert group per device and the
dispatch/combine einsums lower to all-to-alls over ICI — expert parallelism
is, like tensor parallelism, a sharding annotation rather than an engine.

Dispatch is the dense one-hot formulation: a (tokens, E, C) dispatch mask
and combine weights, contracted with the token stream.  O(T·E·C) memory but
fully static shapes (XLA-friendly; no sorting, no dynamic slots), the
standard TPU formulation.

:class:`RoutedExperts` is the other kind, the one today's large decoders
ship: many narrow SwiGLU experts (256 of width 1,024), ten a token, no
capacity and no dropped token, one shared expert beside them.  At 256
experts the one-hot dispatch cannot be afforded; it sorts the (token,
expert) assignments by expert and runs one grouped matrix product over the
experts it HOLDS (:func:`jax.lax.ragged_dot`, or in a serving program the
package's own kernel, :mod:`..ops.grouped_matmul_pallas`), which is also
how one chip of an expert-parallel deployment computes its share of a
layer.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_deep_learning_tpu.ops.grouped_matmul_pallas import Visits

dense_init = nn.initializers.xavier_uniform()


def top2_gating(logits: jnp.ndarray, capacity: int):
    """GShard top-2 gating with capacity-limited dispatch.

    Args:
      logits: (G, E) router logits for G tokens (a flattened group).
      capacity: per-expert slot count C.

    Returns (dispatch (G, E, C) bool-ish float, combine (G, E, C) float,
    aux_loss scalar).  Tokens overflowing an expert's capacity are dropped
    for that expert (their combine weight is 0) — standard GShard semantics.
    """
    G, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    # top-1 and top-2 expert per token
    idx1 = jnp.argmax(probs, axis=-1)                       # (G,)
    mask1 = jax.nn.one_hot(idx1, E)
    probs_wo1 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs_wo1, axis=-1)
    mask2 = jax.nn.one_hot(idx2, E)

    # load-balancing auxiliary loss (Shazeer/GShard: E * Σ fraction·prob)
    density = jnp.mean(mask1, axis=0)                       # fraction routed
    density_proxy = jnp.mean(probs, axis=0)                 # mean router prob
    aux_loss = jnp.sum(density * density_proxy) * (E ** 2) / E

    # position of each token within its expert's queue (capacity slots)
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1        # 0-based
    # expert-2 queue continues after expert-1 assignments
    pos2 = (jnp.cumsum(mask2, axis=0) - mask2
            + jnp.sum(mask1, axis=0, keepdims=True)) * mask2
    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    g1 = jnp.sum(probs * keep1, axis=-1)                    # (G,)
    g2 = jnp.sum(probs * keep2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    slot1 = jax.nn.one_hot(jnp.sum(pos1, axis=-1).astype(jnp.int32),
                           capacity)                        # (G, C)
    slot2 = jax.nn.one_hot(jnp.sum(pos2, axis=-1).astype(jnp.int32),
                           capacity)
    dispatch = (keep1[..., None] * slot1[:, None, :]
                + keep2[..., None] * slot2[:, None, :])      # (G, E, C)
    combine = (g1[:, None, None] * keep1[..., None] * slot1[:, None, :]
               + g2[:, None, None] * keep2[..., None] * slot2[:, None, :])
    return dispatch, combine, aux_loss


class MoEMLP(nn.Module):
    """Top-2 routed MLP: ``x → router → all-to-all → expert FFN →
    all-to-all → combine``.

    Expert weights have shape (E, d_model, mlp_dim)/(E, mlp_dim, d_model);
    shard the leading axis over ``expert`` (see
    :func:`moe_param_rules`).  The auxiliary load-balance loss is sown into
    the ``losses`` collection under ``moe_aux_loss``.
    """

    num_experts: int = 8
    mlp_dim: int = 2048
    capacity_factor: float = 2.0
    dtype: jnp.dtype = jnp.float32
    aux_loss_weight: float = 1.0  # scales the sown load-balance loss

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        B, T, d = x.shape
        E = self.num_experts
        G = B * T
        capacity = max(1, int(self.capacity_factor * G / E))

        tokens = x.reshape(G, d)
        router = nn.Dense(E, dtype=jnp.float32, kernel_init=dense_init,
                          name="router")
        logits = router(tokens.astype(jnp.float32))
        dispatch, combine, aux_loss = top2_gating(logits, capacity)
        self.sow("losses", "moe_aux_loss", self.aux_loss_weight * aux_loss)

        w_in = self.param("w_in", dense_init, (E, d, self.mlp_dim),
                          jnp.float32).astype(self.dtype)
        w_out = self.param("w_out", dense_init, (E, self.mlp_dim, d),
                           jnp.float32).astype(self.dtype)

        # dispatch: (G,E,C)×(G,d) → (E,C,d)  [all-to-all under EP sharding]
        expert_in = jnp.einsum("gec,gd->ecd", dispatch.astype(self.dtype),
                               tokens.astype(self.dtype))
        h = nn.gelu(jnp.einsum("ecd,edm->ecm", expert_in, w_in))
        expert_out = jnp.einsum("ecm,emd->ecd", h, w_out)
        # combine: (G,E,C)×(E,C,d) → (G,d)   [second all-to-all]
        out = jnp.einsum("gec,ecd->gd", combine.astype(self.dtype),
                         expert_out)
        return out.reshape(B, T, d).astype(jnp.float32)


def moe_param_rules(axis: str = "expert"):
    """Sharding rules for :func:`..parallel.tensor_parallel.param_specs`:
    expert-stacked weights shard their leading E axis; the router stays
    replicated (every device routes its own tokens)."""
    from jax.sharding import PartitionSpec as P

    return (
        (r"(^|.*/)w_in$", P(axis, None, None)),
        (r"(^|.*/)w_out$", P(axis, None, None)),
    )


class MoETransformerLayer(nn.Module):
    """Pre-LN transformer block whose MLP is a routed :class:`MoEMLP` —
    the standard every-other-layer MoE substitution unit."""

    num_heads: int = 8
    num_experts: int = 8
    mlp_dim: int = 2048
    capacity_factor: float = 2.0
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.float32
    aux_loss_weight: float = 1.0
    attention_fn: object = None

    @nn.compact
    def __call__(self, x, *, self_valid=None, train: bool = False):
        from distributed_deep_learning_tpu.models.transformer import (
            MultiHeadAttention)

        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = MultiHeadAttention(self.num_heads, self.dtype, self.attention_fn,
                               name="self_attn")(h, h, self_valid)
        h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        x = x + h
        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = MoEMLP(self.num_experts, self.mlp_dim, self.capacity_factor,
                   self.dtype, self.aux_loss_weight,
                   name="moe")(h, train=train)
        h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        return x + h


class MoELM(nn.Module):
    """Masked-LM encoder with routed-MoE MLPs in every other block — the
    sparse-expert member of the north-star family.  Dense blocks carry the
    odd layers; even layers route through ``num_experts`` experts whose
    weights shard over the ``expert`` mesh axis
    (:func:`moe_param_rules`).  The load-balance losses are sown and picked
    up by the training state's aux-loss convention."""

    vocab_size: int = 1024
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    mlp_dim: int = 1024
    num_experts: int = 8
    capacity_factor: float = 2.0
    aux_loss_weight: float = 1e-2
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attention_fn: object = None

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        from distributed_deep_learning_tpu.models.transformer import (
            Embed, TransformerLayer)

        valid = tokens != 0  # (B, T)
        x, emb = Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                       name="embed")(tokens)
        for i in range(self.num_layers):
            if i % 2 == 1:
                x = MoETransformerLayer(
                    self.num_heads, self.num_experts, self.mlp_dim,
                    self.capacity_factor, self.dropout_rate, self.dtype,
                    self.aux_loss_weight, self.attention_fn,
                    name=f"moe_layer_{i}")(
                        x, self_valid=valid, train=train)
            else:
                x = TransformerLayer(self.num_heads, self.mlp_dim,
                                     self.dropout_rate, dtype=self.dtype,
                                     attention_fn=self.attention_fn,
                                     name=f"layer_{i}")(x, self_valid=valid,
                                                        train=train)
        x = nn.LayerNorm(dtype=self.dtype, name="final_norm")(x)
        return Embed.logits(x, emb.embedding)


# --- dropless top-k experts, the chip's share of them ----------------------


@dataclasses.dataclass(frozen=True)
class ExpertSpec:
    """The routed-expert MLP of one decoder layer, by what the published
    configs call these numbers.  `num_experts` is how many experts are
    HELD here, ids ``[expert_offset, expert_offset + num_experts)`` of the
    `router_experts` the router scores (None: all are held)."""

    num_experts: int
    mlp_dim: int                      # moe_intermediate_size
    top_k: int                        # num_experts_per_tok
    router_experts: Optional[int] = None
    expert_offset: int = 0
    routed_scale: float = 1.0         # moe_routed_scaling_factor
    norm_topk: bool = True            # norm_topk_prob
    shared_dim: int = 0               # shared_expert_intermediate_size
    #: how a router logit becomes a score: ``softmax`` over all the
    #: router's experts, or ``sigmoid`` of each alone
    score: str = "softmax"
    #: the router holds a learned per-expert bias (``router_bias``) that
    #: is added to the scores for the CHOICE only, never to the weights
    #: (``topk_method: noaux_tc``, arXiv:2408.15664)
    choice_bias: bool = False


def route_top_k(logits, top_k: int, norm_topk: bool = True,
                routed_scale: float = 1.0, score: str = "softmax",
                bias=None):
    """``(weights, experts)``, each (N, top_k): a score for ALL the
    router's experts in float32 (`score`: their softmax, or each one's
    sigmoid), the `top_k` largest of score + `bias` (where the router has
    one), and as weights the chosen experts' scores WITHOUT the bias,
    renormalised over the chosen (`norm_topk`) and scaled."""
    logits = logits.astype(jnp.float32)
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"router score must be 'softmax' or 'sigmoid', "
                         f"got {score!r}")
    if bias is None:
        w, experts = jax.lax.top_k(scores, top_k)
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * routed_scale, experts


def held_experts(x, w, experts, w_gate, w_up, w_down, offset: int = 0,
                 grad: bool = True):
    """What the held experts give: ``sum_e w[n, e] * E_e(x[n])`` over the
    assignments ``experts[n, j]`` that fall in ``[offset, offset + E)``,
    and how many assignments each held expert took, ``(E,)``.

    No capacity, nothing dropped: the N * top_k assignments are sorted by
    expert (those of absent experts last) and every held expert's rows go
    through ONE grouped product per matrix, SwiGLU in between.  Rows of
    absent experts lie past the last group; a grouped product leaves them
    unspecified, so they are zeroed on the way out.

    `grad`: the caller may differentiate the result, so the products are
    XLA's ``ragged_dot``, which has a transpose rule.  A serving program
    says False, and its products go by their shapes
    (:class:`..ops.grouped_matmul_pallas.Visits`): on a TPU, from 64 rows
    up, through the kernel that visits only the row tiles that hold
    rows."""
    n, k = experts.shape
    E = w_gate.shape[0]
    local = experts.reshape(-1) - offset
    held = (local >= 0) & (local < E)
    group = jnp.where(held, local, E)
    order = jnp.argsort(group, stable=True)
    load = jnp.zeros((E + 1,), jnp.int32).at[group].add(1)[:E]
    rows = x[order // k]
    if grad:
        h = jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, load)) \
            * jax.lax.ragged_dot(rows, w_up, load)
        out = jax.lax.ragged_dot(h.astype(x.dtype), w_down, load)
    else:
        products = Visits(load, n * k)
        h = products.swiglu(rows, w_gate, w_up)
        out = products.product(h.astype(x.dtype), w_down)
    taken = (jnp.arange(n * k) < jnp.sum(load))[:, None]
    out = jnp.where(taken, out, 0).astype(jnp.float32)
    # back to assignment order (a gather, not a scatter-add), then each
    # token sums its top_k rows under their weights
    back = jnp.zeros((n * k,), jnp.int32).at[order].set(jnp.arange(n * k))
    y = jnp.einsum("nkd,nk->nd", out[back].reshape(n, k, -1),
                   jnp.where(held, w.reshape(-1), 0.0).reshape(n, k))
    return y.astype(x.dtype), load


def _route_and_run(x, router, w_gate, w_up, w_down, spec: ExpertSpec,
                   bias=None, grad: bool = True):
    """Tokens (N, d) through router and held experts: ``(y, load)``."""
    with jax.named_scope("moe_route"):
        logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        w, experts = route_top_k(logits, spec.top_k, spec.norm_topk,
                                 spec.routed_scale, spec.score, bias)
    with jax.named_scope("moe_experts"):
        return held_experts(x, w, experts, w_gate, w_up, w_down,
                            spec.expert_offset, grad)


def _tokens_of_all_rows(spec: ExpertSpec):
    """`_route_and_run` for use under ``vmap`` over sequences that share
    the weights (the paged engine's decode program maps the model over
    its slots): the mapped axis is folded into the token axis, so the
    experts see ONE batch of tokens and read each weight once, and the
    load comes out as the batch's total, unmapped."""
    @jax.custom_batching.custom_vmap
    def run(x, router, w_gate, w_up, w_down, bias=None):
        return _route_and_run(x, router, w_gate, w_up, w_down, spec, bias,
                              grad=False)     # a serving program's call

    @run.def_vmap
    def rule(axis_size, in_batched, x, *weights):
        if not in_batched[0] or any(jax.tree.leaves(in_batched[1:])):
            raise NotImplementedError(
                "RoutedExperts under vmap: only the tokens may be mapped "
                "(weights shared by every row)")
        y, load = run(x.reshape((-1, x.shape[-1])), *weights)
        return (y.reshape(x.shape), load), (True, False)

    return run


class RoutedExperts(nn.Module):
    """Router over all experts, the held experts' part of the result, and
    the shared expert: ``sum_{e in top-k, e held} w_e E_e(x) + E_s(x)``.

    Parameters: ``router`` (d, router_experts), with `choice_bias`
    ``router_bias`` (router_experts,), ``w_gate`` / ``w_up`` (E, d, f),
    ``w_down`` (E, f, d), and ``shared``'s three matrices.  In
    decode mode the held experts' loads of this call are sown as
    ``moe_stats/load`` (E,) for whoever asks for that collection."""

    spec: ExpertSpec
    dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x):
        sp = self.spec
        d, E, f = x.shape[-1], sp.num_experts, sp.mlp_dim
        router = self.param("router", dense_init,
                            (d, sp.router_experts or E), jnp.float32)
        w_gate, w_up = (self.param(n, dense_init, (E, d, f), jnp.float32)
                        .astype(self.dtype) for n in ("w_gate", "w_up"))
        w_down = self.param("w_down", dense_init, (E, f, d),
                            jnp.float32).astype(self.dtype)
        # a router with a choice bias passes it on; one without passes
        # nothing, and its call is what it was
        bias = (self.param("router_bias", nn.initializers.zeros,
                           (sp.router_experts or E,), jnp.float32),
                ) if sp.choice_bias else ()
        tokens = x.reshape(-1, d).astype(self.dtype)
        if self.decode:
            y, load = _tokens_of_all_rows(sp)(tokens, router, w_gate, w_up,
                                              w_down, *bias)
            self.sow("moe_stats", "load", load)
        else:       # the training path: differentiable as it stands
            y, _ = _route_and_run(tokens, router, w_gate, w_up, w_down, sp,
                                  *bias)
        y = y.reshape(x.shape)
        if sp.shared_dim:
            with jax.named_scope("moe_shared"):
                y = y + GatedMLP(sp.shared_dim, self.dtype,
                                 name="shared")(x)
        return y


class GatedMLP(nn.Module):
    """SwiGLU: ``(silu(x W_gate) * (x W_up)) W_down``, no biases."""

    mlp_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, f: nn.Dense(  # noqa: E731
            f, dtype=self.dtype, use_bias=False, kernel_init=dense_init,
            name=n)
        h = nn.silu(dense("gate", self.mlp_dim)(x)) \
            * dense("up", self.mlp_dim)(x)
        return dense("down", x.shape[-1])(h)
