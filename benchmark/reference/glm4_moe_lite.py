"""Plain reference for the ``glm4_moe_lite`` configurations
(zai-org/GLM-4.7-Flash): the forward pass in straightforward ``jax.numpy``
and float32, a token row at a time, attention in its EXPANDED form.

No kernels, no cache, no sorting, no absorbed products, nothing imported
from the package under test.  The weights are the flat dict
`benchmark/weights/glm4_moe_lite.py` draws from the seed; the numbers of the
configuration that are no array's shape ride on it as static data
(`Weights`, `hyper`).  Call it under
``jax.default_matmul_precision("highest")`` (`highest()`).

The equations, layer ``l`` of a block stack ``h = x + Attn_l(RMSNorm(x))``,
``y = h + FFN_l(RMSNorm(h))``, ``u`` a block's normed input at position t:

* ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.
* ``Attn`` (multi-head latent attention, arXiv:2405.04434, as the published
  ``q_lora_rank`` / ``kv_lora_rank`` / ``qk_nope_head_dim`` /
  ``qk_rope_head_dim`` / ``v_head_dim`` give it): ``c_q = RMSNorm(u W_qa)``;
  ``[q_nope_h | q_rope_h] = c_q W_qb`` a head; ``[c_kv | k_r] = u W_kva``;
  ``c = RMSNorm(c_kv)``; RoPE (theta, every dim of the rope part,
  rotate-half pairs) on ``q_rope_h`` and on ``k_r``, ONE key part shared by
  all heads; ``[k_nope_h | v_h] = c W_kvb`` a head; scores ``q_h . [k_nope_h
  | k_r] / sqrt(nope + rope)``, causal, softmax; ``o_h = sum p v_h``; output
  ``concat_h(o_h) W_o``.  No biases.
* ``FFN`` of a dense layer (the first ``first_k_dense_replace``):
  ``(silu(u W_g) * (u W_u)) W_d``.
* ``FFN`` of an expert layer: ``s = sigmoid(u W_r)`` over all routed experts
  in float32; ``S`` the `top_k` largest of ``s + b`` (`b` the correction
  bias of ``topk_method: noaux_tc``; ``n_group = topk_group = 1``: no group
  limit); ``w_e = scale * s_e / sum_S s`` (the bias is NOT in the weights);
  ``y = sum_{e in S} w_e E_e(u) + E_shared(u)``, every ``E`` a SwiGLU, the
  shared one ``n_shared_experts`` x the routed width and unscaled.  Every
  expert is computed for every token and weighted by ``w_e`` or 0: nothing
  is gathered or dropped.
* final RMSNorm, logits through the untied head.

Departures from the published model, each also in the configuration file:
the multi-token-prediction module (``num_nextn_predict_layers``) sits behind
the last layer, is never run by the next-token forward and is left out;
what the config leaves open is under ``assumed``, decided alike here and in
the package.

``quant`` is the control of `benchmark/reference/gpt2.py`, not a feature:
every weight matrix product first rounds both operands a precision step
below bfloat16 (``fp8``, ``int8``).  The router's product stays float32
under the control too (the configuration states it so).  A third control is
this architecture's own, ``fp8_cache`` / ``int8_cache``: every product
stays whole and ONLY what the cache holds a position, the row ``[c | k_r]``
as it would rest (normed, rotated), is rounded before keys and values are
rebuilt from it: a latent cache a precision step below the bfloat16 the
configuration states, and nothing else.  It is what says whether the
comparison sees the attention path at all.

So that a 21,000-token row fits a 16 GB chip beside 8.4 GiB of bfloat16
weights: the weights stay in the type they were seeded in and are lifted
to float32 a layer (an expert) at a time; attention is computed a query
head and a block of `QUERY_BLOCK` queries at a time; the head is applied a
block of `HEAD_BLOCK` positions at a time and only the best logit, its id
and the judged token's logit leave the block (`logits` returns them whole,
for the small sizes of the tests).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt2 import (_fake_fp8, _fake_int8,  # the control
                                      _mm as _mm_rounded, highest)  # noqa: F401
# the flat dict with the numbers riding on it, a layer's arrays out of it,
# and the cast that leaves the experts for later: plumbing, no arithmetic
from benchmark.reference.laguna import Weights, _f32, layer_weights  # noqa: F401

QUERY_BLOCK = 1024
HEAD_BLOCK = 512
#: the controls that round the cached rows alone, and how (fp8: one scale
#: for the rows of a sequence; int8: a scale a row)
CACHE_CONTROLS = {"fp8_cache": _fake_fp8,
                  "int8_cache": lambda rows: _fake_int8(rows, -1)}


def _mm(spec: str, x, w, quant, w_axes):
    """einsum(spec, x, w), operands rounded under the weight-product
    controls (`gpt2._mm`), whole under the cache controls."""
    return _mm_rounded(spec, x, w,
                       None if quant in CACHE_CONTROLS else quant, w_axes)


@dataclasses.dataclass(frozen=True)
class Hyper:
    """The configuration's numbers that are no array's shape."""

    dense: tuple            # a layer: True where the FFN is the dense MLP
    eps: float
    theta: float
    kv_rank: int
    nope: int
    rope: int
    top_k: int
    routed_scale: float
    norm_topk: bool


def hyper(cfg: dict) -> Hyper:
    """From a configuration under the published key names."""
    n = int(cfg["num_hidden_layers"])
    return Hyper(
        dense=tuple(i < int(cfg["first_k_dense_replace"]) for i in range(n)),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        kv_rank=int(cfg["kv_lora_rank"]), nope=int(cfg["qk_nope_head_dim"]),
        rope=int(cfg["qk_rope_head_dim"]),
        top_k=int(cfg["num_experts_per_tok"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]))


def rope(x, theta: float):
    """Rotate every dim of the last axis of (T, ..., D), position t by t:
    pairs ``(x[i], x[i + D/2])`` by ``t * theta^(-2i/D)`` (rotate-half)."""
    half = x.shape[-1] // 2
    f = 1.0 / theta ** (np.arange(0, x.shape[-1], 2, dtype=np.float64)
                        / x.shape[-1])
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(f, jnp.float32)[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def attention(u, w, hp: Hyper, quant=None):
    """(T, d) -> (T, d), expanded; `w` holds one layer's wqa (d, rq), qnorm
    (rq,), wqb (rq, H, nope + rope), wkva (d, rkv + rope), kvnorm (rkv,),
    wkvb (rkv, H, nope + v), wo (H, v, d)."""
    T = u.shape[0]
    c_q = rms_norm(_mm("td,dr->tr", u, w["wqa"], quant, (0,)), w["qnorm"],
                   hp.eps)
    q = _mm("tr,rhk->thk", c_q, w["wqb"], quant, (0,))
    q = jnp.concatenate([q[..., :hp.nope], rope(q[..., hp.nope:], hp.theta)],
                        -1)
    kva = _mm("td,dr->tr", u, w["wkva"], quant, (0,))
    c = rms_norm(kva[:, :hp.kv_rank], w["kvnorm"], hp.eps)
    k_r = rope(kva[:, hp.kv_rank:], hp.theta)                  # (T, rope)
    if quant in CACHE_CONTROLS:         # the row as a lesser cache holds it
        row = CACHE_CONTROLS[quant](jnp.concatenate([c, k_r], -1))
        c, k_r = row[:, :hp.kv_rank], row[:, hp.kv_rank:]
    kv = _mm("tr,rhk->thk", c, w["wkvb"], quant, (0,))
    k = jnp.concatenate(
        [kv[..., :hp.nope],
         jnp.broadcast_to(k_r[:, None], kv.shape[:2] + (hp.rope,))], -1)
    v = kv[..., hp.nope:]
    blk = min(QUERY_BLOCK, T)
    pad = -T % blk
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, blk, q.shape[1], q.shape[2])
    at = jnp.arange(T + pad).reshape(-1, blk)
    j = jnp.arange(T)[None, :]

    def one_head(args):
        qh, kh, vh = args               # (blocks, blk, D), (T, D), (T, Dv)

        def one_block(args):
            qs, i = args
            s = (qs @ kh.T) / math.sqrt(qs.shape[-1])
            return jax.nn.softmax(jnp.where(j <= i[:, None], s, -1e30),
                                  axis=-1) @ vh

        return jax.lax.map(one_block, (qh, at))

    o = jax.lax.map(one_head, (qb.transpose(2, 0, 1, 3),
                               k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.reshape(o.shape[0], -1, o.shape[-1])[:, :T].transpose(1, 0, 2)
    return _mm("thk,hkd->td", o, w["wo"], quant, (0, 1))


def swiglu(u, wg, wu, wd, quant=None):
    h = jax.nn.silu(_mm("td,df->tf", u, wg, quant, (0,))) \
        * _mm("td,df->tf", u, wu, quant, (0,))
    return _mm("tf,fd->td", h, wd, quant, (0,))


def route(u, router, bias, hp: Hyper):
    """(weights (T, E), chosen ids (T, top_k)): each token's weight on
    every routed expert, 0 off its `top_k`.  The choice is by score +
    `bias`, the weight by the score alone."""
    s = jax.nn.sigmoid(u @ router)
    _, ids = jax.lax.top_k(s + bias, hp.top_k)
    rows = jnp.arange(s.shape[0])[:, None]
    top = s[rows, ids]
    if hp.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    return jnp.zeros_like(s).at[rows, ids].set(top * hp.routed_scale), ids


def expert_ffn(u, w, hp: Hyper, quant=None):
    """The routed experts' part plus the shared expert, and the router's
    choices.  `w`: router (d, E), rbias (E,), eg / eu (E, d, f), ed
    (E, f, d), sg / su / sd the shared one."""
    weight, ids = route(u, w["router"], w["rbias"], hp)

    def add(y, e):
        eg, eu, ed, we = e
        return y + we[:, None] * swiglu(u, eg.astype(jnp.float32),
                                        eu.astype(jnp.float32),
                                        ed.astype(jnp.float32), quant), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(u),
                        (w["eg"], w["eu"], w["ed"], weight.T))
    return y + swiglu(u, w["sg"], w["su"], w["sd"], quant), ids


@functools.partial(jax.jit, static_argnames=("hp", "dense", "quant"))
def block(x, w: dict, hp: Hyper, dense: bool, quant=None):
    """One layer on (T, d).  Also, for an expert layer, the router's
    choices (T, top_k) and how many tokens' set of choices changes when
    the router is given this layer's input rounded to bfloat16 (what the
    program's arithmetic hands its router); None, 0 for a dense layer."""
    w = _f32(w)
    h = x + attention(rms_norm(x, w["norm1"], hp.eps), w, hp, quant)
    u = rms_norm(h, w["norm2"], hp.eps)
    if dense:
        return h + swiglu(u, w["wg"], w["wu"], w["wd"], quant), None, 0
    y, ids = expert_ffn(u, w, hp, quant)
    _, rounded = route(u.astype(jnp.bfloat16).astype(jnp.float32),
                       w["router"], w["rbias"], hp)
    moved = jnp.sum(jnp.any(jnp.sort(ids, -1) != jnp.sort(rounded, -1), -1))
    return h + y, ids, moved


def hidden(w: Weights, row, quant=None):
    """The final-normed hidden states (T, d) of ONE row of token ids (T,),
    the routers' choices [(T, top_k) an expert layer], and the rounding
    count (see `block`)."""
    hp = w.hp
    x = w["embed"][row].astype(jnp.float32)
    chosen, moved = [], 0
    for i, dense in enumerate(hp.dense):
        x, ids, m = block(x, layer_weights(w, i), hp, dense, quant)
        if ids is not None:
            chosen.append(ids)
            moved = moved + m
    return rms_norm(x, w["norm_f"].astype(jnp.float32), hp.eps), chosen, moved


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(h, head, quant=None):
    return _mm("td,vd->tv", h, head.astype(jnp.float32), quant, (1,))


@functools.partial(jax.jit, static_argnames=("quant",))
def _judged(h, head, picks, quant=None):
    """Of the logits of hidden states (T, d), a block of positions at a
    time: the best logit, its id, and the logit of ``picks[t]``, each
    (T,)."""
    T = h.shape[0]
    blk = min(HEAD_BLOCK, T)
    pad = -T % blk
    head = head.astype(jnp.float32)

    def one(args):
        hb, pb = args
        lg = _mm("td,vd->tv", hb, head, quant, (1,))
        return (jnp.max(lg, -1), jnp.argmax(lg, -1),
                jnp.take_along_axis(lg, pb[:, None], axis=-1)[:, 0])

    best, first, got = jax.lax.map(one, (
        jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, blk, h.shape[1]),
        jnp.pad(picks, (0, pad)).reshape(-1, blk)))
    return tuple(a.reshape(-1)[:T] for a in (best, first, got))


def forward(w: Weights, row, quant=None):
    """Logits (T, V) of ONE row, whole (small sizes: the tests), with the
    routers' choices and the rounding count."""
    h, chosen, moved = hidden(w, row, quant)
    return _head(h, w["head"], quant), chosen, moved


def logits(w: Weights, tokens, quant=None):
    """(B, T, V) float32 logits, a row at a time."""
    return jnp.stack([forward(w, row, quant)[0] for row in tokens])


#: running count over the rows a process has checked, for `token_gaps`'
#: printed line: [(token, expert layer) pairs, pairs whose choices moved]
_ROUTING = [0, 0]


def token_gaps(w: Weights, tokens, quant=None):
    """For tokens (B, T): at each position t, how far the logit of the
    token that FOLLOWS lies below the best logit, shape (B, T - 1), and
    the reference's greedy token at each position.  A sound pass
    (`quant` None) also prints how many routing choices rounding to the
    program's precision moves, over the rows checked so far."""
    gaps, firsts = [], []
    for row in tokens:
        h, chosen, moved = hidden(w, row, quant)
        best, first, got = _judged(h[:-1], w["head"], row[1:], quant)
        gaps.append(best - got)
        firsts.append(first)
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
        best, _, got = _judged(hidden(w, row)[0], w["head"], pick)
        out.append(best - got)
    return jnp.stack(out)
