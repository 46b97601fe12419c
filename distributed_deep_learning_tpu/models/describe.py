"""A decoder read from a model description file.

The file is JSON under the key names published ``config.json`` files use
(``hidden_size``, ``num_attention_heads_per_layer``, ``layer_types``,
``rope_parameters``, ``num_experts`` ...), so a published config, or a
cut of one, is a description as it stands.  :func:`causal_lm` turns it
into the :class:`~.transformer.CausalLM` the ``gpt`` workload trains and
serves, one :class:`~.transformer.LayerSpec` a layer.

What is read, by mechanism: RMSNorm pre-norm blocks without biases;
grouped-query attention with a head size of its own and a head count,
window and RoPE by layer kind (``full_attention`` / ``sliding_attention``;
``default`` and ``yarn`` RoPE, partial rotary width), the RoPE under
``rope_parameters`` or as a bare ``rope_theta``; latent attention
(``kv_lora_rank`` with ``q_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``: every layer, whole sequences); the
per-head output gate (``gating``); a dense SwiGLU MLP on
``mlp_only_layers`` or the first ``first_k_dense_replace`` layers and
routed experts elsewhere, counted by ``num_experts`` or
``n_routed_experts``: top-k, renormalised, scaled, with
``shared_expert_intermediate_size`` or ``n_shared_experts`` shared
width; the router a softmax, or with ``topk_method: noaux_tc`` a sigmoid
whose choice adds a learned bias; a tied or untied head.  Per-layer lists
may be longer than ``num_hidden_layers`` (a cut in depth keeps the
leading layers).  ``num_nextn_predict_layers`` counts multi-token
prediction modules behind the last layer, which the next-token forward
never runs: none is built, whatever the count.

Three keys are this package's own, for a chip's share of a layer:
``num_experts`` counts the experts HELD, ``router_experts`` the experts
the router scores (default: all held), ``expert_offset`` the first held
id.  A switch the package does not compute is an error, never ignored.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import jax.numpy as jnp

from distributed_deep_learning_tpu.models.moe import ExpertSpec
from distributed_deep_learning_tpu.models.transformer import (CausalLM,
                                                              LatentSpec,
                                                              LayerSpec,
                                                              RopeSpec)

#: keys whose only supported value is the one given: each names a
#: departure in the mathematics that nothing here computes
_ONLY = {"moe_router_logit_softcapping": 0,
         "moe_apply_router_weight_on_input": False,
         "decoder_sparse_step": 1, "attention_bias": False,
         "hidden_act": "silu",
         # position scaling comes as rope_parameters' rope_type, or not at
         # all; grouped (node-limited) top-k is not computed
         "rope_scaling": None, "n_group": 1, "topk_group": 1}


def read(path: str) -> dict:
    """The description at `path` (relative: to the working directory,
    else to the checkout that holds the package)."""
    if not os.path.isabs(path) and not os.path.exists(path):
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(root, path)
    with open(path) as f:
        return json.load(f)


def _rope(params: dict, head_dim: int) -> RopeSpec:
    kind = params.get("rope_type", "default")
    rotary = int(round(head_dim * params.get("partial_rotary_factor", 1)))
    if kind == "default":
        return RopeSpec(theta=float(params["rope_theta"]),
                        rotary_dim=rotary)
    if kind == "yarn":
        return RopeSpec(
            theta=float(params["rope_theta"]), rotary_dim=rotary,
            factor=float(params["factor"]),
            original_max_len=int(params["original_max_position_embeddings"]),
            beta_fast=float(params.get("beta_fast", 32)),
            beta_slow=float(params.get("beta_slow", 1)),
            attention_factor=float(params["attention_factor"]))
    raise ValueError(f"rope_type {kind!r}: only 'default' and 'yarn' are "
                     "computed")


def layer_specs(desc: dict) -> tuple:
    """One LayerSpec for each of the description's first
    ``num_hidden_layers`` layers."""
    for key, only in _ONLY.items():
        if desc.get(key, only) != only:
            raise ValueError(f"model description: {key}={desc[key]!r} is "
                             f"not computed here (only {only!r})")
    n = int(desc["num_hidden_layers"])
    head_dim = int(desc.get("head_dim")
                   or desc["hidden_size"] // desc["num_attention_heads"])
    heads = desc.get("num_attention_heads_per_layer") \
        or [desc["num_attention_heads"]] * n
    kinds = desc.get("layer_types") or ["full_attention"] * n
    gates = desc.get("gating_types") or [
        "per_head" if desc.get("gating") == "per-head" else None] * n
    dense = set(desc.get("mlp_only_layers", ()))
    if "mlp_layer_types" in desc:
        dense = {i for i, t in enumerate(desc["mlp_layer_types"][:n])
                 if t == "dense"}
    if "first_k_dense_replace" in desc:
        dense = set(range(int(desc["first_k_dense_replace"])))
    ropes = desc.get("rope_parameters") or {
        "rope_theta": desc["rope_theta"],
        "partial_rotary_factor": desc.get("partial_rotary_factor", 1)}
    if "rope_theta" in ropes:           # one RoPE for every layer kind
        ropes = {"full_attention": ropes, "sliding_attention": ropes}
    latent = _latent(desc)
    if latent is not None:
        # the rotated part of a latent layer's head is a head of its own
        if "sliding_attention" in kinds[:n]:
            raise ValueError("latent attention (kv_lora_rank) with "
                             "sliding_attention layers is not computed "
                             "here: the latent cache keeps whole sequences")
        head_dim = latent.rope_dim
    experts = _experts(desc)
    out = []
    for i in range(n):
        if kinds[i] not in ("full_attention", "sliding_attention"):
            raise ValueError(f"layer {i}: layer type {kinds[i]!r} is not "
                             "computed here")
        if gates[i] not in (None, "per_head"):
            raise ValueError(f"layer {i}: gating {gates[i]!r} is not "
                             "computed here (only per-head)")
        routed = experts is not None and i not in dense
        out.append(LayerSpec(
            num_heads=int(heads[i]),
            num_kv_heads=int(desc.get("num_key_value_heads", heads[i])),
            head_dim=head_dim,
            window=(int(desc["sliding_window"])
                    if kinds[i] == "sliding_attention" else None),
            rope=_rope(ropes[kinds[i]], head_dim),
            gate=gates[i] == "per_head", use_bias=False, norm="rms",
            mlp="experts" if routed else "swiglu",
            mlp_dim=int(desc["intermediate_size"]),
            experts=experts if routed else None, latent=latent))
    return tuple(out)


def _latent(desc: dict) -> Optional[LatentSpec]:
    """The description's latent attention, where it has one."""
    if not desc.get("kv_lora_rank"):
        return None
    if desc.get("gating") or desc.get("gating_types"):
        raise ValueError("an output gate on latent attention is not "
                         "computed here")
    if desc.get("q_lora_rank") is None:
        raise ValueError("model description: q_lora_rank null (queries "
                         "projected without a bottleneck) is not computed "
                         "here")
    return LatentSpec(kv_rank=int(desc["kv_lora_rank"]),
                      nope_dim=int(desc["qk_nope_head_dim"]),
                      rope_dim=int(desc["qk_rope_head_dim"]),
                      v_dim=int(desc["v_head_dim"]),
                      q_rank=int(desc["q_lora_rank"]))


def _experts(desc: dict) -> Optional[ExpertSpec]:
    """The routed-expert MLP of the description's expert layers, under
    either family's key names; None for a dense model."""
    held = desc.get("num_experts") or desc.get("n_routed_experts")
    if not held:
        return None
    method = desc.get("topk_method")
    if method not in (None, "noaux_tc"):
        raise ValueError(f"model description: topk_method={method!r} is not "
                         "computed here (only 'noaux_tc', or none: a "
                         "softmax router)")
    width = int(desc["moe_intermediate_size"])
    return ExpertSpec(
        num_experts=int(held), mlp_dim=width,
        top_k=int(desc["num_experts_per_tok"]),
        router_experts=desc.get("router_experts"),
        expert_offset=int(desc.get("expert_offset", 0)),
        routed_scale=float(desc.get("moe_routed_scaling_factor",
                                    desc.get("routed_scaling_factor", 1.0))),
        norm_topk=bool(desc.get("norm_topk_prob", True)),
        shared_dim=int(desc.get("shared_expert_intermediate_size",
                                width * int(desc.get("n_shared_experts",
                                                     0)))),
        score="sigmoid" if method == "noaux_tc" else "softmax",
        choice_bias=method == "noaux_tc")


def causal_lm(desc: dict, *, max_len: int, vocab_size: Optional[int] = None,
              dtype=jnp.float32, **kw) -> CausalLM:
    """The CausalLM `desc` describes; `kw` are the fields a description
    does not hold (dropout, attention_fn, with_logits)."""
    vocab = int(desc["vocab_size"])
    if vocab_size is not None and vocab_size != vocab:
        raise ValueError(f"the model description's vocab_size {vocab} is "
                         f"not the data's {vocab_size}")
    specs = layer_specs(desc)
    return CausalLM(vocab_size=vocab, num_layers=len(specs),
                    d_model=int(desc["hidden_size"]),
                    num_heads=specs[0].num_heads,
                    mlp_dim=specs[0].mlp_dim, max_len=max_len,
                    pos_embedding="rope", layers=specs,
                    tie_head=bool(desc.get("tie_word_embeddings", False)),
                    ln_eps=float(desc.get("rms_norm_eps", 1e-6)),
                    # no id is padding unless the description names one:
                    # nothing is masked and any id may be emitted
                    pad_id=desc.get("pad_token_id"), dtype=dtype, **kw)
