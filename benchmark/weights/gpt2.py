"""Seeded weights of the ``gpt2`` architecture, made by the benchmark and
handed to both sides.  A configuration file's ``architecture`` names the
module under ``benchmark/weights/`` that lays out its arrays
(`harness.weights_for`); a new family adds a module beside this one with
the same functions: `leaf_shapes`, `is_stacked`, `make_weights`,
`to_program_tree`, `from_program_tree`, `program_leaf_norms`,
`flat_leaf_norms`, `host_leaf_norms`, `decay_mask`.

One jitted call makes every array on the device from ``--seed``, layer
arrays stacked on a leading axis (the plain reference's own layout).  The
program gets them poured into its parameter tree (`to_program_tree`); the
reference takes the flat dict as it is.  Neither side's initialiser is
used, so the reference depends on nothing the program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# name -> (shape builder, kind); kind: "n" N(0, 0.02), "g" 1 + N(0, 0.02).
LAYER_LEAVES = {
    "ln1_g": (lambda c: (c["n_embd"],), "g"),
    "ln1_b": (lambda c: (c["n_embd"],), "n"),
    "wq": (lambda c: (c["n_embd"], c["n_head"], c["head_dim"]), "n"),
    "bq": (lambda c: (c["n_head"], c["head_dim"]), "n"),
    "wk": (lambda c: (c["n_embd"], c["n_head"], c["head_dim"]), "n"),
    "bk": (lambda c: (c["n_head"], c["head_dim"]), "n"),
    "wv": (lambda c: (c["n_embd"], c["n_head"], c["head_dim"]), "n"),
    "bv": (lambda c: (c["n_head"], c["head_dim"]), "n"),
    "wo": (lambda c: (c["n_head"], c["head_dim"], c["n_embd"]), "n"),
    "bo": (lambda c: (c["n_embd"],), "n"),
    "ln2_g": (lambda c: (c["n_embd"],), "g"),
    "ln2_b": (lambda c: (c["n_embd"],), "n"),
    "w1": (lambda c: (c["n_embd"], c["n_inner"]), "n"),
    "b1": (lambda c: (c["n_inner"],), "n"),
    "w2": (lambda c: (c["n_inner"], c["n_embd"]), "n"),
    "b2": (lambda c: (c["n_embd"],), "n"),
}
TOP_LEAVES = {
    "wte": (lambda c: (c["vocab_size"], c["n_embd"]), "n"),
    "wpe": (lambda c: (c["n_positions"], c["n_embd"]), "n"),
    "lnf_g": (lambda c: (c["n_embd"],), "g"),
    "lnf_b": (lambda c: (c["n_embd"],), "n"),
}
STD = 0.02

# where each flat name lives in one layer of the package's CausalLM tree
_LAYER_PATHS = {
    "ln1_g": ("LayerNorm_0", "scale"), "ln1_b": ("LayerNorm_0", "bias"),
    "wq": ("self_attn", "q", "kernel"), "bq": ("self_attn", "q", "bias"),
    "wk": ("self_attn", "k", "kernel"), "bk": ("self_attn", "k", "bias"),
    "wv": ("self_attn", "v", "kernel"), "bv": ("self_attn", "v", "bias"),
    "wo": ("self_attn", "out", "kernel"), "bo": ("self_attn", "out", "bias"),
    "ln2_g": ("LayerNorm_1", "scale"), "ln2_b": ("LayerNorm_1", "bias"),
    "w1": ("Dense_0", "kernel"), "b1": ("Dense_0", "bias"),
    "w2": ("Dense_1", "kernel"), "b2": ("Dense_1", "bias"),
}
_TOP_PATHS = {
    "wte": ("embed", "tok", "embedding"), "wpe": ("embed", "pos"),
    "lnf_g": ("final_norm", "scale"), "lnf_b": ("final_norm", "bias"),
}


def leaf_shapes(cfg: dict) -> dict:
    """Flat name -> shape, layer arrays with the leading layer axis."""
    shapes = {n: f(cfg) for n, (f, _) in TOP_LEAVES.items()}
    shapes.update({n: (cfg["n_layer"],) + f(cfg)
                   for n, (f, _) in LAYER_LEAVES.items()})
    return shapes


def is_stacked(name: str) -> bool:
    """Whether the flat array `name` carries the leading layer axis."""
    return name not in TOP_LEAVES


def make_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """The flat dict of weights for `cfg`, drawn in float32 and rounded
    to `dtype` (the type they are stored in: float32 master weights for
    training, bfloat16 for serving).  Trace it inside a jit."""
    kinds = {**{n: k for n, (_, k) in TOP_LEAVES.items()},
             **{n: k for n, (_, k) in LAYER_LEAVES.items()}}
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(cfg).items())):
        x = STD * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        if kinds[name] == "g":
            x = x + 1.0
        out[name] = x.astype(dtype)
    return out


def to_program_tree(flat: dict, cfg: dict) -> dict:
    """Pour the flat dict into the package's CausalLM parameter tree."""
    tree: dict = {}

    def put(path, value):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = value

    for name, path in _TOP_PATHS.items():
        put(path, flat[name])
    for i in range(cfg["n_layer"]):
        for name, path in _LAYER_PATHS.items():
            put((f"layer_{i}",) + path, flat[name][i])
    return tree


def _at(tree: dict, path: tuple):
    for p in path:
        tree = tree[p]
    return tree


def from_program_tree(tree: dict, cfg: dict, only: str | None = None):
    """The flat dict (layers stacked) read back out of a tree shaped like
    the package's parameters: a gradient or a moment of the optimizer.
    `only`: just that one flat array."""
    def one(name):
        if name in _TOP_PATHS:
            return _at(tree, _TOP_PATHS[name])
        return jnp.stack([_at(tree, (f"layer_{i}",) + _LAYER_PATHS[name])
                          for i in range(cfg["n_layer"])])

    if only is not None:
        return one(only)
    return {n: one(n) for n in (*_TOP_PATHS, *_LAYER_PATHS)}


def program_leaf_norms(tree: dict, cfg: dict) -> dict:
    """Flat name -> vector of L2 norms, one per layer (one for a top
    leaf), read from a tree shaped like the package's parameters."""
    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    out = {n: norm(_at(tree, p))[None] for n, p in _TOP_PATHS.items()}
    for name, path in _LAYER_PATHS.items():
        out[name] = jnp.stack([norm(_at(tree, (f"layer_{i}",) + path))
                               for i in range(cfg["n_layer"])])
    return out


def flat_leaf_norms(flat: dict) -> dict:
    """The same vectors from a flat dict (the reference's side)."""
    out = {}
    for name, x in flat.items():
        x = x.astype(jnp.float32)
        if name in TOP_LEAVES:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x),
                                         axis=tuple(range(1, x.ndim))))
    return out


def host_leaf_norms(flat: dict) -> dict:
    """`flat_leaf_norms` for host (numpy) arrays, leaf by leaf."""
    import numpy as np

    out = {}
    for name, x in flat.items():
        x = np.asarray(x, np.float32)
        rows = x.reshape(1 if name in TOP_LEAVES else x.shape[0], -1)
        out[name] = np.linalg.norm(rows, axis=1).astype(np.float32)
    return out


def decay_mask(flat: dict) -> dict:
    """AdamW's decay mask as the package applies it: arrays of rank >= 2
    in ITS tree (so the rank without the stacked layer axis)."""
    return {n: (x.ndim - (0 if n in TOP_LEAVES else 1)) >= 2
            for n, x in flat.items()}
