"""Seeded weights of the ``laguna`` architecture, made by the benchmark
and handed to both sides (`harness.weights_for`; the serving runner needs
`make_weights` and `to_program_tree`).

One jitted call makes every array on the device from ``--seed``.  The
plain reference takes the flat dict as it is (``l<i>.<name>`` a layer's
arrays: the layers differ in shape, so they are not stacked; a layer's
experts are, on a leading axis); the program gets the arrays poured into
its parameter tree.  Only the experts HELD here and the vocabulary rows
held here are made: what the configuration's ``num_experts`` and
``vocab_size`` say.  Neither side's initialiser is used.

Init (the configuration file's ``assumed.init``): every matrix and the
embedding N(0, 0.02), RMSNorm scales 1 + N(0, 0.02), the router N(0, 0.05):
wide enough that a token's tenth and eleventh experts seldom weigh alike
(top-10 of 256 flips on rounding where they do) and that the choices the
rounding does move carry little weight.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.laguna import Weights, hyper

STD, ROUTER_STD = 0.02, 0.05

# flat name -> where it lives in one layer of the package's CausalLM tree
_ATTN = {"norm1": ("RMSNorm_0", "scale"), "norm2": ("RMSNorm_1", "scale"),
         "wq": ("self_attn", "q", "kernel"),
         "wk": ("self_attn", "k", "kernel"),
         "wv": ("self_attn", "v", "kernel"),
         "wa": ("self_attn", "gate", "kernel"),
         "wo": ("self_attn", "out", "kernel")}
_DENSE = {"wg": ("mlp", "gate", "kernel"), "wu": ("mlp", "up", "kernel"),
          "wd": ("mlp", "down", "kernel")}
_EXPERTS = {"router": ("moe", "router"), "eg": ("moe", "w_gate"),
            "eu": ("moe", "w_up"), "ed": ("moe", "w_down"),
            "sg": ("moe", "shared", "gate", "kernel"),
            "su": ("moe", "shared", "up", "kernel"),
            "sd": ("moe", "shared", "down", "kernel")}
_TOP = {"embed": ("embed", "tok", "embedding"), "head": ("head",),
        "norm_f": ("final_norm", "scale")}


def _layer_paths(cfg: dict, i: int) -> dict:
    dense = cfg["mlp_layer_types"][i] == "dense"
    return {**_ATTN, **(_DENSE if dense else _EXPERTS)}


def leaf_shapes(cfg: dict) -> dict:
    """Flat name -> shape."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    kv, E = cfg["num_key_value_heads"], cfg["num_experts"]
    f, fs = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    shapes = {"embed": (V, d), "head": (V, d), "norm_f": (d,)}
    for i in range(cfg["num_hidden_layers"]):
        H = cfg["num_attention_heads_per_layer"][i]
        one = {"norm1": (d,), "norm2": (d,), "wq": (d, H, D),
               "wk": (d, kv, D), "wv": (d, kv, D), "wa": (d, H),
               "wo": (H, D, d), "wg": (d, F), "wu": (d, F), "wd": (F, d),
               "router": (d, cfg.get("router_experts", E)),
               "eg": (E, d, f), "eu": (E, d, f), "ed": (E, f, d),
               "sg": (d, fs), "su": (d, fs), "sd": (fs, d)}
        shapes.update({f"l{i}.{n}": one[n] for n in _layer_paths(cfg, i)})
    return shapes


def make_weights(key, cfg: dict, dtype=jnp.float32) -> Weights:
    """The flat dict of weights for `cfg`, drawn in float32 and rounded
    to `dtype` (bfloat16 for serving).  Trace it inside a jit."""
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(cfg).items())):
        leaf = name.split(".")[-1]
        std = ROUTER_STD if leaf == "router" else STD
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        if leaf.startswith("norm"):
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
