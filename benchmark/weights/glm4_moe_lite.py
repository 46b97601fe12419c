"""Seeded weights of the ``glm4_moe_lite`` architecture, made by the
benchmark and handed to both sides (`harness.weights_for`; the serving
runner needs `make_weights` and `to_program_tree`).

One jitted call makes every array on the device from ``--seed``.  The plain
reference takes the flat dict as it is (``l<i>.<name>`` a layer's arrays; a
layer's experts stacked on a leading axis); the program gets the arrays
poured into its parameter tree.  Neither side's initialiser is used.

Column order, as the published checkpoints have it: ``wkva`` gives ``[c_kv
(kv_lora_rank) | k_r (qk_rope_head_dim)]``, a head of ``wqb`` ``[q_nope |
q_rope]``, a head of ``wkvb`` ``[k_nope | v]``.

Init (the configuration file's ``assumed.init``): every matrix and the
embedding N(0, 0.02) (`STD`; a configuration of another width may give its
own ``init_std``), RMSNorm scales 1 + N(0, 0.02), the router N(0, 0.02)
(on a unit-RMS input of 2,048 the logits spread by 0.9, so the sigmoids are
not saturated and a token's fourth and fifth scores seldom tie), the
correction bias N(0, 0.02): about the gap between a token's fourth and
fifth score, so it changes the choice of a good share of the tokens and
of no token's weights.  Three matrices are drawn WIDER (`WIDER`), so that
attention does work the comparison can see: under 0.02 alone the scores
of a query spread by 0.33, attention is uniform over thousands of positions
and its output is 1% of the MLP's, and the served logits cannot tell a
sound latent path from a broken one.  ``wqb`` x 5 and ``wkvb`` x 2: scores
spread by 2.5, a query's weight lies on some 50 (of 4,096) to 170 (of
16,384) positions, the largest holding 4-10%; ``wkvb`` x 2 and ``wo`` x 2:
the attention output's RMS is 0.40-0.55 where an expert layer's MLP gives
0.47 and the dense layer's 0.97 (CPU readings of the plain reference at
the published widths, PR 30: they are properties of the init, not times).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.glm4_moe_lite import Weights, hyper

STD = 0.02
#: a layer's matrices drawn N(0, (STD x this)^2), and why: the docstring
WIDER = {"wqb": 5.0, "wkvb": 2.0, "wo": 2.0}

# flat name -> where it lives in one layer of the package's CausalLM tree
_ATTN = {"norm1": ("RMSNorm_0", "scale"), "norm2": ("RMSNorm_1", "scale"),
         "wqa": ("self_attn", "q_a", "kernel"),
         "qnorm": ("self_attn", "q_norm", "scale"),
         "wqb": ("self_attn", "q_b", "kernel"),
         "wkva": ("self_attn", "kv_a", "kernel"),
         "kvnorm": ("self_attn", "kv_norm", "scale"),
         "wkvb": ("self_attn", "kv_b"),
         "wo": ("self_attn", "out", "kernel")}
_DENSE = {"wg": ("mlp", "gate", "kernel"), "wu": ("mlp", "up", "kernel"),
          "wd": ("mlp", "down", "kernel")}
_EXPERTS = {"router": ("moe", "router"), "rbias": ("moe", "router_bias"),
            "eg": ("moe", "w_gate"), "eu": ("moe", "w_up"),
            "ed": ("moe", "w_down"),
            "sg": ("moe", "shared", "gate", "kernel"),
            "su": ("moe", "shared", "up", "kernel"),
            "sd": ("moe", "shared", "down", "kernel")}
_TOP = {"embed": ("embed", "tok", "embedding"), "head": ("head",),
        "norm_f": ("final_norm", "scale")}


def _layer_paths(cfg: dict, i: int) -> dict:
    dense = i < cfg["first_k_dense_replace"]
    return {**_ATTN, **(_DENSE if dense else _EXPERTS)}


def leaf_shapes(cfg: dict) -> dict:
    """Flat name -> shape."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    E, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    one = {"norm1": (d,), "norm2": (d,), "wqa": (d, rq), "qnorm": (rq,),
           "wqb": (rq, H, nope + rope), "wkva": (d, rkv + rope),
           "kvnorm": (rkv,), "wkvb": (rkv, H, nope + dv), "wo": (H, dv, d),
           "wg": (d, F), "wu": (d, F), "wd": (F, d),
           "router": (d, E), "rbias": (E,),
           "eg": (E, d, f), "eu": (E, d, f), "ed": (E, f, d),
           "sg": (d, fs), "su": (d, fs), "sd": (fs, d)}
    shapes = {"embed": (V, d), "head": (V, d), "norm_f": (d,)}
    for i in range(cfg["num_hidden_layers"]):
        shapes.update({f"l{i}.{n}": one[n] for n in _layer_paths(cfg, i)})
    return shapes


def make_weights(key, cfg: dict, dtype=jnp.float32) -> Weights:
    """The flat dict of weights for `cfg`, drawn in float32 and rounded
    to `dtype` (bfloat16 for serving).  Trace it inside a jit."""
    out, std = {}, float(cfg.get("init_std", STD))
    for i, (name, shape) in enumerate(sorted(leaf_shapes(cfg).items())):
        kind = name.split(".")[-1]
        x = std * WIDER.get(kind, 1.0) * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        if "norm" in kind:
            x = x + 1.0
        out[name] = x.astype(dtype)
    return Weights(out, hyper(cfg))


def to_program_tree(flat: dict, cfg: dict) -> dict:
    """Pour the flat dict into the package's CausalLM parameter tree."""
    tree: dict = {}

    def put(path, value):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = value

    for name, path in _TOP.items():
        put(path, flat[name])
    for i in range(cfg["num_hidden_layers"]):
        for name, path in _layer_paths(cfg, i).items():
            put((f"layer_{i}",) + path, flat[f"l{i}.{name}"])
    return tree
