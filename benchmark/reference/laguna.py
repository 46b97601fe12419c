"""Plain reference for the Laguna configurations (poolside/Laguna-S-2.1):
the forward pass in straightforward ``jax.numpy`` and float32, a token row
at a time.

No kernels, no cache, no sorting, nothing imported from the package under
test.  The weights are the flat dict `benchmark/weights/laguna.py` draws
from the seed; the numbers of the configuration that are no array's shape
(window, RoPE, experts a token ...) ride on it as static data (`Weights`,
`hyper`).  Call it under ``jax.default_matmul_precision("highest")``
(`highest()`).

The equations, layer ``l`` of a block stack ``h = x + Attn_l(RMSNorm(x))``,
``y = h + FFN_l(RMSNorm(h))``:

* ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.
* ``Attn_l`` on normed input ``u``: ``H_l`` query heads (by layer), 8 KV
  heads, head size 128, no biases; RoPE by layer kind on q and k, rotate-
  half layout: sliding layers theta 10,000 over the whole head, full layers
  YaRN frequencies over the first half of each head with cos / sin times
  the attention factor; scores ``q k^T / sqrt(head size)``, causal, on
  sliding layers also ``i - j < window``; softmax; query head ``h`` reads
  KV head ``h // (H_l / 8)``; a per-head gate ``a = sigmoid(u W_a)`` scales
  each head's output before ``W_o``.
* ``FFN`` of a dense layer: ``(silu(u W_g) * (u W_u)) W_d``.
* ``FFN`` of an expert layer: ``p = softmax(u W_r)`` over ALL the router's
  experts, ``S`` the `top_k` largest, ``w_e = scale * p_e / sum_S p``,
  ``y = sum_{e in S, e held} w_e E_e(u) + E_shared(u)``, every ``E`` a
  SwiGLU.  `held` is the chip's share ``(first id, count)``: what absent
  experts would add is left out.  Every held expert is computed for every
  token and weighted by ``w_e`` or 0: nothing is gathered or dropped.
* final RMSNorm, logits through the untied head over the rows held.

What the published config leaves open is listed under ``assumed`` in the
configuration file, decided alike here and in the package.

``quant`` is the control of `benchmark/reference/gpt2.py`, not a feature:
every weight matrix product first rounds both operands a precision step
below bfloat16 (``fp8``, ``int8``).  The router's product stays float32
under the control too (the configuration states it so), which makes the
control gentler and the limits that hold it off tighter.

Attention is computed a query head at a time and the experts one after
the other, so that a 6,500-token row fits a 16 GB chip beside the weights.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt2 import _mm, highest  # noqa: F401 -- the control


@dataclasses.dataclass(frozen=True)
class Rope:
    theta: float
    rotary_dim: int
    factor: float | None = None         # set: YaRN
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class Hyper:
    """The configuration's numbers that are no array's shape."""

    windows: tuple          # a layer: window size, or None for full
    ropes: tuple            # a layer: its Rope
    dense: tuple            # a layer: True where the FFN is the dense MLP
    eps: float
    top_k: int
    routed_scale: float
    norm_topk: bool
    held: tuple             # (first held expert id, how many)


def hyper(cfg: dict) -> Hyper:
    """From a configuration under the published key names."""
    n, d = int(cfg["num_hidden_layers"]), int(cfg["head_dim"])
    kinds = cfg["layer_types"][:n]

    def rope(p):
        return Rope(theta=float(p["rope_theta"]),
                    rotary_dim=int(round(d * p.get("partial_rotary_factor",
                                                   1))),
                    factor=(float(p["factor"])
                            if p.get("rope_type") == "yarn" else None),
                    original_max=int(p.get(
                        "original_max_position_embeddings", 0)),
                    beta_fast=float(p.get("beta_fast", 32)),
                    beta_slow=float(p.get("beta_slow", 1)),
                    attention_factor=float(p.get("attention_factor", 1.0)))

    return Hyper(
        windows=tuple(int(cfg["sliding_window"])
                      if k == "sliding_attention" else None for k in kinds),
        ropes=tuple(rope(cfg["rope_parameters"][k]) for k in kinds),
        dense=tuple(t == "dense" for t in cfg["mlp_layer_types"][:n]),
        eps=float(cfg["rms_norm_eps"]),
        top_k=int(cfg["num_experts_per_tok"]),
        routed_scale=float(cfg["moe_routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        held=(int(cfg.get("expert_offset", 0)), int(cfg["num_experts"])))


@jax.tree_util.register_pytree_node_class
class Weights(dict):
    """The flat dict of arrays; `hp` (a :class:`Hyper`) rides as static
    data, so a jitted function of the weights sees it as a constant."""

    def __init__(self, arrays, hp: Hyper):
        super().__init__(arrays)
        self.hp = hp

    def tree_flatten(self):
        names = sorted(self)
        return [self[n] for n in names], (tuple(names), self.hp)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(dict(zip(aux[0], children)), aux[1])


def inv_freq(r: Rope) -> np.ndarray:
    """``rotary_dim / 2`` inverse frequencies.  YaRN (arXiv:2309.00071,
    as the published ``rope_type: yarn`` computes it): a frequency whose
    wavelength fits the original context more than `beta_fast` times is
    kept, one that fits it fewer than `beta_slow` times is divided by
    `factor`, a linear ramp over the dimensions in between."""
    dim = r.rotary_dim
    pos = r.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if r.factor is None:
        return 1.0 / pos

    def dim_of(rotations):
        return (dim * math.log(r.original_max / (rotations * 2 * math.pi))
                / (2 * math.log(r.theta)))

    low = max(math.floor(dim_of(r.beta_fast)), 0)
    high = min(math.ceil(dim_of(r.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (1.0 / (r.factor * pos)) * (1.0 - keep) + (1.0 / pos) * keep


def rope(x, r: Rope):
    """Rotate the first ``rotary_dim`` dims of each head of (T, H, D),
    position t by t (rotate-half layout); the rest pass through."""
    f = jnp.asarray(inv_freq(r), jnp.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * f[None]
    cos = (jnp.cos(ang) * r.attention_factor)[:, None, :]
    sin = (jnp.sin(ang) * r.attention_factor)[:, None, :]
    half = r.rotary_dim // 2
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest], -1)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def attention(u, w, window, r: Rope, quant=None):
    """(T, d) -> (T, d); `w` holds one layer's wq (d, H, D), wk / wv
    (d, Hkv, D), wa (d, H), wo (H, D, d)."""
    T = u.shape[0]
    q = rope(_mm("td,dhk->thk", u, w["wq"], quant, (0,)), r)
    k = rope(_mm("td,dhk->thk", u, w["wk"], quant, (0,)), r)
    v = _mm("td,dhk->thk", u, w["wv"], quant, (0,))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    group = q.shape[1] // k.shape[1]

    def one_head(args):             # query head h reads KV head h // group
        qh, h = args                # (T, D)
        kh, vh = k[:, h // group], v[:, h // group]
        s = (qh @ kh.T) / math.sqrt(qh.shape[-1])
        return jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1) @ vh

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.arange(q.shape[1]))).transpose(1, 0, 2)
    a = jax.nn.sigmoid(_mm("td,dh->th", u, w["wa"], quant, (0,)))
    return _mm("thk,hkd->td", o * a[..., None], w["wo"], quant, (0, 1))


def swiglu(u, wg, wu, wd, quant=None):
    h = jax.nn.silu(_mm("td,df->tf", u, wg, quant, (0,))) \
        * _mm("td,df->tf", u, wu, quant, (0,))
    return _mm("tf,fd->td", h, wd, quant, (0,))


def route(u, router, hp: Hyper):
    """(weights (T, E_all), chosen ids (T, top_k)): each token's weight
    on every expert the router scores, 0 off its `top_k`."""
    p = jax.nn.softmax(u @ router, axis=-1)
    top, ids = jax.lax.top_k(p, hp.top_k)
    if hp.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    w = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], ids].set(
        top * hp.routed_scale)
    return w, ids


def expert_ffn(u, w, hp: Hyper, quant=None, held=None):
    """The held experts' part plus the shared expert, and the router's
    choices.  `w`: router (d, E_all), eg / eu (E, d, f), ed (E, f, d) of
    the HELD experts, sg / su / sd the shared one.  `held` = (first id,
    count) overrides the configuration's."""
    first, count = held or hp.held
    weight, ids = route(u, w["router"].astype(jnp.float32), hp)
    weight = weight[:, first:first + count]               # (T, E held)

    def add(y, e):
        eg, eu, ed, we = e
        return y + we[:, None] * swiglu(u, eg.astype(jnp.float32),
                                        eu.astype(jnp.float32),
                                        ed.astype(jnp.float32), quant), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(u),
                        (w["eg"], w["eu"], w["ed"], weight.T))
    return y + swiglu(u, w["sg"], w["su"], w["sd"], quant), ids


def _f32(w: dict, but=("eg", "eu", "ed")) -> dict:
    """A layer's arrays in float32; the experts are cast one at a time."""
    return {n: a if n in but else a.astype(jnp.float32)
            for n, a in w.items()}


@functools.partial(jax.jit,
                   static_argnames=("hp", "window", "r", "dense", "quant"))
def block(x, w: dict, hp: Hyper, window, r: Rope, dense: bool, quant=None):
    """One layer on (T, d): `window`, `r` and `dense` are the layer's own
    (a compile a kind of layer).  Also, for an expert layer, the router's
    choices (T, top_k) and how many tokens' set of choices changes when
    the router is given this layer's input rounded to bfloat16 (what the
    program's arithmetic hands its router); None, 0 for a dense layer."""
    w = _f32(w)
    u = rms_norm(x, w["norm1"], hp.eps)
    h = x + attention(u, w, window, r, quant)
    u = rms_norm(h, w["norm2"], hp.eps)
    if dense:
        return h + swiglu(u, w["wg"], w["wu"], w["wd"], quant), None, 0
    y, ids = expert_ffn(u, w, hp, quant)
    _, rounded = route(u.astype(jnp.bfloat16).astype(jnp.float32),
                       w["router"], hp)
    moved = jnp.sum(jnp.any(jnp.sort(ids, -1) != jnp.sort(rounded, -1), -1))
    return h + y, ids, moved


def layer_weights(w: dict, i: int) -> dict:
    """Layer `i`'s arrays out of the flat dict (``l<i>.<name>``)."""
    tag = f"l{i}."
    return {n[len(tag):]: a for n, a in w.items() if n.startswith(tag)}


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm_f, head, eps, quant=None):
    h = rms_norm(x, norm_f.astype(jnp.float32), eps)
    return _mm("td,vd->tv", h, head.astype(jnp.float32), quant, (1,))


#: running count over the rows a process has checked, for `token_gaps`'
#: printed line: [(token, expert layer) pairs, pairs whose choices moved]
_ROUTING = [0, 0]


def forward(w: Weights, row, quant=None):
    """Logits (T, V) of ONE row of token ids (T,), the routers' choices
    [(T, top_k) an expert layer], and the rounding count (see `block`)."""
    hp = w.hp
    x = w["embed"][row].astype(jnp.float32)
    chosen, moved = [], 0
    for i in range(len(hp.windows)):
        x, ids, m = block(x, layer_weights(w, i), hp, hp.windows[i],
                          hp.ropes[i], hp.dense[i], quant)
        if ids is not None:
            chosen.append(ids)
            moved = moved + m
    return _head(x, w["norm_f"], w["head"], hp.eps, quant), chosen, moved


def logits(w: Weights, tokens, quant=None):
    """(B, T, V) float32 logits, a row at a time."""
    return jnp.stack([forward(w, row, quant)[0] for row in tokens])


def token_gaps(w: Weights, tokens, quant=None):
    """For tokens (B, T): at each position t, how far the logit of the
    token that FOLLOWS lies below the best logit, shape (B, T - 1), and
    the reference's greedy token at each position.  A sound pass
    (`quant` None) also prints how many routing choices rounding to the
    program's precision moves, over the rows checked so far."""
    gaps, firsts = [], []
    for row in tokens:
        lg, chosen, moved = forward(w, row, quant)
        lg = lg[:-1]
        got = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
        gaps.append(jnp.max(lg, axis=-1) - got)
        firsts.append(jnp.argmax(lg, axis=-1))
        if quant is None and chosen:
            _ROUTING[0] += len(chosen) * int(row.shape[0])
            _ROUTING[1] += int(moved)
            print(f"bench: routing: {_ROUTING[1]} of {_ROUTING[0]} (token, "
                  f"expert layer) choices ("
                  f"{100.0 * _ROUTING[1] / _ROUTING[0]:.3f}%) differ "
                  f"between the reference's float32 router input and the "
                  f"same input rounded to bfloat16, the program's "
                  f"precision; rows checked so far", flush=True)
    return jnp.stack(gaps), jnp.stack(firsts)


def gaps_of(w: Weights, tokens, chosen):
    """Float32 logit gap of `chosen` (B, T) tokens, position by position,
    in the context `tokens` (B, T): best logit minus chosen's logit."""
    out = []
    for row, pick in zip(tokens, chosen):
        lg = forward(w, row)[0]
        got = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        out.append(jnp.max(lg, axis=-1) - got)
    return jnp.stack(out)
