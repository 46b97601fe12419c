"""Transformer models — BASELINE configs[3,4] (WMT seq2seq, BERT MLM).

TPU-first design decisions:

* One :class:`TransformerLayer` definition serves encoder (bidirectional),
  decoder (causal + cross-attention) and BERT (bidirectional) — the
  homogeneous-stack shape that the SPMD pipeline
  (:mod:`..parallel.spmd_pipeline`) and tensor-parallel sharding rules
  (:mod:`..parallel.tp`) both want.
* ``attention_fn`` is pluggable: dense softmax attention by default;
  :mod:`..ops.ring_attention` (sequence-parallel ppermute ring) or the
  Pallas flash kernel slot in without touching the model.
* bf16 compute / f32 params via ``dtype``; logits always f32.
* Fixed shapes, no data-dependent control flow: causal masking is a static
  triangular mask, padding via additive masks — everything jit-tileable.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.linen.dtypes import promote_dtype

from distributed_deep_learning_tpu.models.moe import (ExpertSpec, GatedMLP,
                                                      RoutedExperts)
from distributed_deep_learning_tpu.runtime.batch_pin import pin_batch

AttentionFn = Callable[..., jnp.ndarray]
dense_init = nn.initializers.xavier_uniform()


class MergedHeadsDense(nn.Module):
    """``nn.DenseGeneral`` into heads (``features=(H, D)``) or out of them
    (``features=d_model`` over the input's last two axes): the same
    parameters under the same names, drawn the same way, but computed on
    the merged ``H·D``: ONE 2-D product, the bias added to it, and only
    then the view as heads.  A product or a sum whose result is 4-D, 64
    wide, XLA:TPU rests sequence-minor (64 of a tile's 128 lanes would be
    padding), and every reshape to or from ``(B, T, H·D)`` around a kernel
    that reads that layout is then a copy of the whole array; with no 4-D
    value computed the reshapes are views.  What an attention function
    that ``reads_heads_merged`` gets its q, k and v from, and hands to."""

    features: Union[int, tuple]
    dtype: jnp.dtype = jnp.float32
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        into = isinstance(self.features, tuple)
        if into:
            shape = (x.shape[-1],) + self.features
            flat = (x.shape[-1], math.prod(self.features))
        else:
            shape = x.shape[-2:] + (self.features,)
            flat = (math.prod(x.shape[-2:]), self.features)
            x = x.reshape(x.shape[:-2] + flat[:1])
        kernel = self.param(
            "kernel", lambda rng, shape, dtype: dense_init(
                rng, flat, dtype).reshape(shape), shape, jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), shape[1:]
                          if into else shape[2:], jnp.float32) \
            if self.use_bias else None
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        y = x @ kernel.reshape(flat)
        if bias is not None:
            y = y + bias.reshape(-1)
        return y.reshape(y.shape[:-1] + self.features) if into else y


def dot_product_attention(q, k, v, *, mask=None, key_valid=None,
                          causal=False, window=None, dtype=jnp.float32):
    """Plain softmax attention; q/k/v are (B, T, H, D).

    Masking follows the structured convention shared with the flash and
    ring implementations: ``key_valid`` is a (B, Tk) boolean padding mask,
    ``causal`` a flag, ``window`` an optional causal sliding-window size
    (each query sees its last ``window`` positions); a pre-built dense
    ``mask`` (broadcastable to (B, H, Tq, Tk)) is also accepted and
    combined.  K/V may carry FEWER heads than q (grouped-query
    attention): query head ``h`` reads KV head ``h // (H / Hkv)``, by a
    grouped contraction: K/V are never repeated to the full head count.
    """
    depth = q.shape[-1]
    B, Tq, H, _ = q.shape
    group = H // k.shape[2]
    if group > 1:
        qg = q.reshape(B, Tq, k.shape[2], group, depth)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).reshape(
            B, H, Tq, k.shape[1]) / np.sqrt(depth)
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(depth)
    if key_valid is not None:
        kv = key_valid[:, None, None, :]
        mask = kv if mask is None else jnp.logical_and(mask, kv)
    if causal:
        tril = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))[None, None]
        mask = tril if mask is None else jnp.logical_and(mask, tril)
    if window is not None:
        if not causal and mask is None:
            raise ValueError("window requires causal attention")
        qp = jnp.arange(q.shape[1])[:, None]
        kp = jnp.arange(k.shape[1])[None, :]
        band = ((qp - kp) < window)[None, None]
        mask = band if mask is None else jnp.logical_and(mask, band)
    if mask is not None:
        # -1e9, not finfo(f32).min: the latter overflows to -inf in bf16
        # (same exponent range, smaller mantissa → rounds past bf16 max) and
        # a fully-padded row would softmax to NaN; -1e9 degrades to uniform
        # attention on such rows, which the loss masks out anyway.
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
    weights = nn.softmax(logits.astype(jnp.float32)).astype(dtype)
    if group > 1:
        wg = weights.reshape(B, k.shape[2], group, Tq, k.shape[1])
        return jnp.einsum("bhgqk,bkhd->bqhgd", wg, v).reshape(
            B, Tq, H, v.shape[-1])
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


dot_product_attention.supports_gqa = True


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """Rotary positions of one kind of layer, by the published key names
    (``rope_parameters``): `theta` the base, `rotary_dim` how many leading
    dims of each head are rotated (None: the whole head), and for
    ``rope_type: yarn`` the YaRN blend (arXiv:2309.00071): frequencies
    whose wavelength fits `original_max_len` fewer than `beta_slow` times
    are interpolated by `factor`, those that fit it more than `beta_fast`
    times are kept, a linear ramp in between; `attention_factor`
    multiplies cos and sin."""

    theta: float = 10000.0
    rotary_dim: Optional[int] = None
    factor: Optional[float] = None      # set: YaRN
    original_max_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self, head_dim: int) -> np.ndarray:
        """The ``rotary_dim / 2`` inverse frequencies, float64 on the
        host (a compile-time constant of the program)."""
        dim = self.rotary_dim or head_dim
        pos_freqs = self.theta ** (np.arange(0, dim, 2, dtype=np.float64)
                                   / dim)
        if self.factor is None:
            return 1.0 / pos_freqs

        def correction_dim(rotations):
            return (dim * math.log(self.original_max_len
                                   / (rotations * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / ((high if high != low else high + 0.001) - low),
                       0.0, 1.0)
        return ((1.0 / (self.factor * pos_freqs)) * ramp
                + (1.0 / pos_freqs) * (1.0 - ramp))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               base: float = 10000.0,
               spec: Optional[RopeSpec] = None) -> jnp.ndarray:
    """Rotary position embedding on ``(B, T, H, D)`` (D even).

    Rotates feature pairs ``(x[..., :D/2], x[..., D/2:])`` by
    ``position · base^(-2i/D)`` — attention then depends on RELATIVE
    positions only.  Parameter-free, so tensor-parallel sharding rules
    and the weight-tied head are untouched; the KV-cache decode path
    passes ``positions = cache_index + arange(T)`` so cached keys carry
    their absolute rotation.  With a `spec` its frequencies, scale
    and partial width replace the default's; dims past ``rotary_dim``
    pass through unrotated.
    """
    if spec is not None:
        rot = spec.rotary_dim or x.shape[-1]
        freqs = jnp.asarray(spec.inv_freq(x.shape[-1]), jnp.float32)
        ang = positions.astype(jnp.float32)[:, None] * freqs[None]
        cos = (jnp.cos(ang) * spec.attention_factor)[None, :, None, :]
        sin = (jnp.sin(ang) * spec.attention_factor)[None, :, None, :]
        x1 = x[..., :rot // 2].astype(jnp.float32)
        x2 = x[..., rot // 2:rot].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                                x[..., rot:].astype(jnp.float32)],
                               -1).astype(x.dtype)
    if x.shape[-1] % 2:
        raise ValueError(f"RoPE requires an even head_dim, got "
                         f"{x.shape[-1]} (pick num_heads so that "
                         "d_model/num_heads is even)")
    d2 = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(d2, dtype=jnp.float32) / d2)    # (d2,)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None]   # (T, d2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d2].astype(jnp.float32), x[..., d2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], -1).astype(x.dtype)


#: names of a layer's cache leaves: the whole sequence, or a window
#: layer's ring (:mod:`..serve.paged` pools the two kinds apart by name)
FULL_LEAVES = ("cached_key", "cached_value", "cached_valid")
RING_LEAVES = ("ring_key", "ring_value", "ring_valid")
#: a latent-attention layer's cache: ONE row ``[c | k_r]`` a position
#: (``kv_rank + rope_dim`` values: the normed latent and the rotated key
#: every head shares; zero-padded to whole lane tiles,
#: :attr:`LatentSpec.row_at_rest`) and its validity; a whole-sequence kind
#: like FULL_LEAVES, told apart by name as the rings are
LATENT_LEAVES = ("latent_kv", "latent_valid")
#: beside FULL_LEAVES that are the paged engine's POOL leaves themselves
#: (``(num_blocks, block, Hkv*D)``, not a slot's ``(1, T, Hkv, D)``): the
#: slot's block table, ``(blocks_per_slot,)`` physical ids
BLOCK_TABLE = "block_table"


class MultiHeadAttention(nn.Module):
    """Projections + pluggable attention; ``decode=True`` adds a KV cache.

    The cache is created at init time (full-length call shapes the
    ``cached_key``/``cached_value`` buffers); each subsequent 1-token call
    appends its K/V at ``cache_index`` and attends the single query
    against the filled prefix — autoregressive decode costs O(T) per
    token instead of O(T²) recompute.

    A window layer given `cache_ring` keeps a RING instead: ``ring_key`` /
    ``ring_value`` / ``ring_valid`` of `cache_ring` positions, position
    ``p`` at index ``p % cache_ring``.  A call of ``T`` tokens overwrites
    the ``T`` oldest entries, so the ring must hold ``window + T - 1``
    positions for the call's first query to still find its whole window;
    :class:`..serve.engine.PagedEngine` sizes it for its prefill chunk.

    A cache that holds a ``block_table`` beside its leaves is the paged
    engine's one-token decode program: the leaves are the engine's pools as
    they rest and the layer attends over them in place
    (:func:`..ops.paged_decode_pallas.paged_slot_attention`), handing back
    the token's own K/V row, not an updated cache.
    """

    num_heads: int
    dtype: jnp.dtype = jnp.float32
    attention_fn: Optional[AttentionFn] = None
    decode: bool = False
    rope: Union[bool, RopeSpec] = False   # True: the default RopeSpec()
    window: Optional[int] = None   # causal sliding-window size
    num_kv_heads: Optional[int] = None  # < num_heads = grouped-query attn
    head_dim: Optional[int] = None      # None: d_model // num_heads
    use_bias: bool = True
    gate: bool = False   # per-head sigmoid gate on the attention output
    cache_ring: Optional[int] = None    # window layers: ring length

    @nn.compact
    def __call__(self, x_q, x_kv, key_valid=None, *, causal: bool = False,
                 mask=None):
        d_model = x_q.shape[-1]
        head_dim = self.head_dim or d_model // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        if self.num_heads % kv_heads:
            raise ValueError(f"num_kv_heads {kv_heads} must divide "
                             f"num_heads {self.num_heads}")
        attn = self.attention_fn or dot_product_attention
        # a kernel that reads q, k and v as (B, T, H·D) gets them from
        # projections that never compute a 4-D value (training only: a
        # cached call attends densely or through the block table)
        merged = (not self.decode
                  and getattr(attn, "reads_heads_merged", False))
        proj = lambda name, h: (  # noqa: E731
            MergedHeadsDense((h, head_dim), dtype=self.dtype,
                             use_bias=self.use_bias, name=name) if merged
            else nn.DenseGeneral(
                (h, head_dim), dtype=self.dtype, use_bias=self.use_bias,
                kernel_init=dense_init, name=name))
        q = proj("q", self.num_heads)(x_q)
        k = proj("k", kv_heads)(x_kv)
        v = proj("v", kv_heads)(x_kv)
        if self.rope:
            start = jnp.zeros((), jnp.int32)
            if self.decode and self.has_variable("cache", "cache_index"):
                start = self.get_variable("cache", "cache_index")
            positions = start + jnp.arange(q.shape[1])
            spec = None if self.rope is True else self.rope
            q = apply_rope(q, positions, spec=spec)
            k = apply_rope(k, positions, spec=spec)  # cached K stay rotated
        # under a sharded step every value a module hands on stays on the
        # batch axes (runtime.batch_pin): the weights come to the rows
        q, k, v = pin_batch(q), pin_batch(k), pin_batch(v)
        y = None
        if self.decode:
            is_init = not self.has_variable("cache", "cache_index")
            # the init call is full-length and decides whether a ring pays;
            # later calls find which cache they were given
            ring = (self.window is not None and self.cache_ring is not None
                    and self.cache_ring < k.shape[1]) if is_init \
                else self.has_variable("cache", RING_LEAVES[0])
            held = (k.shape[0], self.cache_ring) + k.shape[2:] if ring \
                else k.shape
            names = RING_LEAVES if ring else FULL_LEAVES
            ck = self.variable("cache", names[0], jnp.zeros, held, k.dtype)
            cv = self.variable("cache", names[1], jnp.zeros, held, v.dtype)
            # remember each cached position's padding validity too — the
            # full forward masks pad tokens, so decode must as well
            cvalid = self.variable(
                "cache", names[2],
                lambda: jnp.zeros(held[:2], jnp.bool_))
            idx = self.variable("cache", "cache_index",
                                lambda: jnp.zeros((), jnp.int32))
            if not is_init and self.has_variable("cache", BLOCK_TABLE):
                y = self._attend_in_place(q, k, v, key_valid, ck, cv, cvalid,
                                          idx)
            elif not is_init:
                T = q.shape[1]
                max_len = ck.value.shape[1]
                step_valid = (key_valid if key_valid is not None
                              else jnp.ones(k.shape[:2], jnp.bool_))
                qpos = idx.value + jnp.arange(T)
                if ring:
                    at = qpos % max_len
                    ck.value = ck.value.at[:, at].set(k)
                    cv.value = cv.value.at[:, at].set(v)
                    cvalid.value = cvalid.value.at[:, at].set(step_valid)
                    # entry r holds the newest position <= the call's
                    # last that is r modulo the ring; below 0: never
                    # written (a former tenant's leftovers)
                    last = idx.value + T - 1
                    kpos = last - (last - jnp.arange(max_len)) % max_len
                    kpos = kpos[None, None, None, :]
                    mask = jnp.logical_and(
                        kpos <= qpos[None, None, :, None], kpos >= 0)
                else:
                    ck.value = jax.lax.dynamic_update_slice(
                        ck.value, k, (0, idx.value, 0, 0))
                    cv.value = jax.lax.dynamic_update_slice(
                        cv.value, v, (0, idx.value, 0, 0))
                    cvalid.value = jax.lax.dynamic_update_slice(
                        cvalid.value, step_valid, (0, idx.value))
                    # causal prefix: query j (global position idx+j) sees
                    # key positions <= idx+j — correct for 1-token steps
                    # AND multi-token prefill chunks
                    kpos = jnp.arange(max_len)[None, None, None, :]
                    mask = kpos <= qpos[None, None, :, None]
                k, v = ck.value, cv.value
                key_valid = cvalid.value
                if self.window is not None:
                    # the trained model never attends beyond its window —
                    # decode must not either (train/inference parity)
                    mask = jnp.logical_and(
                        mask,
                        qpos[None, None, :, None] - kpos < self.window)
                idx.value = idx.value + T
                causal = False
                # dense direct: the flash adapter would route this dense
                # mask to the same path anyway, minus a spurious warning
                attn = dot_product_attention
        if y is None and kv_heads != self.num_heads and \
                not getattr(attn, "supports_gqa", False):
            # an attention_fn that wants full heads gets K/V expanded;
            # dot_product_attention and the flash kernel take the
            # kv_heads K/V as they are and map heads inside
            group = self.num_heads // kv_heads
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        kw = {}
        if self.window is not None and mask is None:
            # structured convention: window rides alongside causal so the
            # flash kernel can bound its key loops instead of masking
            kw["window"] = self.window
        if y is None:
            with jax.named_scope("attn_window" if self.window is not None
                                 else "attn_full"):
                y = pin_batch(attn(q, k, v, mask=mask, key_valid=key_valid,
                                   causal=causal, dtype=self.dtype, **kw))
        if self.gate:
            # headwise gate of arXiv:2505.06708: a sigmoid of a per-head
            # projection of the layer's input scales each head's output
            a = nn.Dense(self.num_heads, dtype=self.dtype, use_bias=False,
                         kernel_init=dense_init, name="gate")(x_q)
            y = y * nn.sigmoid(a.astype(jnp.float32)).astype(
                self.dtype)[..., None]
        if merged:
            return MergedHeadsDense(d_model, dtype=self.dtype,
                                    use_bias=self.use_bias, name="out")(y)
        return nn.DenseGeneral(d_model, axis=(-2, -1), dtype=self.dtype,
                               use_bias=self.use_bias,
                               kernel_init=dense_init, name="out")(y)

    def _attend_in_place(self, q, k, v, key_valid, ck, cv, cvalid, idx):
        """One token of one slot against pool leaves where they rest: the
        cached positions below ``cache_index`` through the slot's block
        table, the token's own row beside them.  The cache variables leave
        holding that row (what the engine scatters), not a cache."""
        from distributed_deep_learning_tpu.ops.paged_decode_pallas import (
            paged_slot_attention)

        if q.shape[:2] != (1, 1):
            raise ValueError(
                f"a cache of pool leaves serves one token of one slot, got "
                f"queries {q.shape[:2]}; programs with more gather the slot")
        step_valid = (key_valid if key_valid is not None
                      else jnp.ones((1, 1), jnp.bool_))
        with jax.named_scope("attn_window" if self.window is not None
                             else "attn_full"), \
                jax.named_scope("kv_paged_attn"):
            y = paged_slot_attention(
                q[0, 0], k[0, 0], v[0, 0], step_valid[0, 0], ck.value,
                cv.value, cvalid.value,
                self.get_variable("cache", BLOCK_TABLE), idx.value,
                window=self.window)
        ck.value, cv.value, cvalid.value = k, v, step_valid
        idx.value = idx.value + 1
        return y[None, None]


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """Multi-head latent attention (arXiv:2405.04434), by the published
    key names: queries through a `q_rank`-wide normed bottleneck
    (``q_lora_rank``), keys and values through a
    shared `kv_rank`-wide normed latent (``kv_lora_rank``) plus ONE
    `rope_dim`-wide rotated key for all heads (``qk_rope_head_dim``); a
    head's query / key is `nope_dim` + `rope_dim` wide
    (``qk_nope_head_dim``), its value `v_dim` (``v_head_dim``)."""

    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    q_rank: int

    @property
    def row(self) -> int:
        """Values cached a position and layer: ``[c | k_r]``."""
        return self.kv_rank + self.rope_dim

    @property
    def row_at_rest(self) -> int:
        """Width of the cache leaf: the row, zero-padded to whole lane
        tiles of 128 (576 -> 640).  A TPU tiles a buffer's two minor dims
        8 x 128, so a 576-wide row occupies 640 lanes wherever it rests
        row-major; left 576 wide, XLA rests the pool leaf block-index
        minor to save those lanes and every program that touches it copies
        the whole leaf into the computing layout and back (7.7 s of a 20 s
        window, my chip trace, PR 30; :mod:`..serve.paged` has PR 25's
        account of the same for per-head K and V)."""
        return -(-self.row // 128) * 128

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.nope_dim + self.rope_dim)


#: keys a step of :func:`latent_expanded_attention` rebuilds and scores
LATENT_KEY_BLOCK = 512
_NEG = -1e30


def latent_expanded_attention(q, rows, valid, kv_up, q_pos, spec: LatentSpec,
                              dtype, block: int = LATENT_KEY_BLOCK):
    """EXPANDED latent attention of one sequence over its cache rows, the
    keys walked in blocks: ``q (Tq, H, nope + rope)`` at absolute
    positions ``q_pos (Tq,)`` (ascending) against ``rows (Tk, >= kv_rank +
    rope)`` (position ``p`` at index ``p``; columns past the row are the
    cache's padding and are not read), ``valid (Tk,)``, ``kv_up
    (kv_rank, H, nope + v)``.  Each block's per-head keys and values are
    rebuilt from its latents (shared by all `Tq` queries), scored, and
    folded into a running softmax (float32 max, denominator and
    accumulator), so no more than ``H x Tq x block`` scores ever exist;
    blocks past the last query's position are never visited.  Returns
    ``(Tq, H, v)`` in `dtype`."""
    Tq, H, _ = q.shape
    Tk = rows.shape[0]
    blk = min(block, Tk)
    rank, nope = spec.kv_rank, spec.nope_dim
    f32 = jnp.float32
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    def step(j, carry):
        m, l, acc = carry
        # the last block may start early to stay inside the cache; the
        # positions it shares with the one before are masked out
        start = jnp.minimum(j * blk, Tk - blk)
        r = jax.lax.dynamic_slice_in_dim(rows, start, blk)
        kpos = start + jnp.arange(blk)
        live = jnp.logical_and(
            jax.lax.dynamic_slice_in_dim(valid, start, blk),
            kpos >= j * blk)
        kv = jnp.einsum("kc,chd->khd", r[:, :rank], kv_up,
                        preferred_element_type=f32).astype(dtype)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :nope],
                        preferred_element_type=f32)
             + jnp.einsum("qhr,kr->hqk", q_rope, r[:, rank:spec.row],
                          preferred_element_type=f32)) * spec.scale
        seen = jnp.logical_and(kpos[None, :] <= q_pos[:, None],
                               live[None, :])[None]
        s = jnp.where(seen, s, _NEG)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - new_m)
        p = jnp.where(seen, jnp.exp(s - new_m[..., None]), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "hqk,khd->hqd", p.astype(dtype), kv[..., nope:],
            preferred_element_type=f32)
        return new_m, l, acc

    init = (jnp.full((H, Tq), _NEG, f32), jnp.zeros((H, Tq), f32),
            jnp.zeros((H, Tq, spec.v_dim), f32))
    n_live = (q_pos[-1] + blk) // blk       # blocks up to the last query
    _, l, acc = jax.lax.fori_loop(0, jnp.minimum(n_live, -(-Tk // blk)),
                                  step, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(1, 0, 2).astype(dtype)


def latent_absorbed_attention(q_abs, rows, seen, *, kv_rank: int,
                              scale: float, dtype):
    """ABSORBED latent attention, plainly: ``q_abs (..., H, W)`` (the key
    up-projection folded into the query; ``W >= kv_rank + rope``, zero
    past the row) against ``rows (..., Tk, W)`` where ``seen (..., Tk)``; scores (times
    `scale`) and softmax in float32, probabilities in `dtype` as
    :func:`dot_product_attention` has them; the values ARE the rows'
    first `kv_rank` columns.  Returns ``(..., H, kv_rank)``: the value
    up-projection is the caller's."""
    s = jnp.einsum("...hc,...kc->...hk", q_abs, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(seen[..., None, :], s, -1e9)
    p = nn.softmax(s.astype(jnp.float32)).astype(dtype)
    return jnp.einsum("...hk,...kc->...hc", p, rows[..., :kv_rank])


class LatentAttention(nn.Module):
    """Multi-head latent attention (:class:`LatentSpec`), computed two
    ways over ONE cache of ``[c | k_r]`` rows:

    * EXPANDED (no cache; a cached call of several tokens, the prefill
      chunk): per-head keys and values are rebuilt from the latents,
      ``s = q_h . [k_nope_h | k_r]``, ``o_h = sum p v_h``.  Cheaper when
      many queries share the rebuilt keys.
    * ABSORBED (a cached call of one token): ``W_kvb``'s key columns are
      folded into the query (``qt_h = W_UK_h q_nope_h``) and its value
      columns into the output (``o_h = (sum p c) W_UV_h``), so nothing is
      expanded a cached position and only the rows are read.  A cache
      that holds a ``block_table`` is the paged engine's decode program:
      the rows are read where they rest, through the table
      (:func:`..ops.paged_decode_pallas.paged_latent_slot_attention`).

    The same function both ways, up to rounding."""

    num_heads: int
    latent: LatentSpec
    dtype: jnp.dtype = jnp.float32
    attention_fn: Optional[AttentionFn] = None
    decode: bool = False
    rope: Union[bool, RopeSpec] = True
    ln_eps: float = 1e-6

    @nn.compact
    def __call__(self, x, key_valid=None, *, causal: bool = True):
        sp, H = self.latent, self.num_heads
        B, T, d_model = x.shape
        dense = lambda name, f: nn.DenseGeneral(  # noqa: E731
            f, dtype=self.dtype, use_bias=False, kernel_init=dense_init,
            name=name)
        rope = None if self.rope is True else self.rope
        start = jnp.zeros((), jnp.int32)
        if self.decode and self.has_variable("cache", "cache_index"):
            start = self.get_variable("cache", "cache_index")
        positions = start + jnp.arange(T)
        with jax.named_scope("attn_latent"):
            with jax.named_scope("mla_q"):
                c_q = nn.RMSNorm(dtype=self.dtype, epsilon=self.ln_eps,
                                 name="q_norm")(dense("q_a", sp.q_rank)(x))
                q = dense("q_b", (H, sp.nope_dim + sp.rope_dim))(c_q)
                q = jnp.concatenate(
                    [q[..., :sp.nope_dim],
                     apply_rope(q[..., sp.nope_dim:], positions, spec=rope)],
                    axis=-1)
            with jax.named_scope("mla_kv_down"):
                kv = dense("kv_a", sp.row)(x)
                c = nn.RMSNorm(dtype=self.dtype, epsilon=self.ln_eps,
                               name="kv_norm")(kv[..., :sp.kv_rank])
                k_r = apply_rope(kv[..., None, sp.kv_rank:], positions,
                                 spec=rope)[..., 0, :]
                # (B, T, row_at_rest): [c | k_r | zeros to whole lane tiles]
                rows = jnp.concatenate(
                    [c, k_r, jnp.zeros(c.shape[:2] + (sp.row_at_rest
                                                      - sp.row,), c.dtype)],
                    axis=-1)
            kv_up = self.param("kv_b", dense_init,
                               (sp.kv_rank, H, sp.nope_dim + sp.v_dim),
                               jnp.float32).astype(self.dtype)
            q, rows = pin_batch(q), pin_batch(rows)
            is_init = self.decode and \
                not self.has_variable("cache", "cache_index")
            if self.decode:
                ckv = self.variable("cache", LATENT_LEAVES[0], jnp.zeros,
                                    rows.shape, rows.dtype)
                cvalid = self.variable(
                    "cache", LATENT_LEAVES[1],
                    lambda: jnp.zeros(rows.shape[:2], jnp.bool_))
                idx = self.variable("cache", "cache_index",
                                    lambda: jnp.zeros((), jnp.int32))
            step_valid = (key_valid if key_valid is not None
                          else jnp.ones((B, T), jnp.bool_))
            if not self.decode or is_init:
                y = self._expanded_whole(q, rows, kv_up, key_valid, causal)
            elif self.has_variable("cache", BLOCK_TABLE):
                y = self._absorbed_in_place(q, rows, kv_up, step_valid,
                                            ckv, cvalid, idx)
            else:
                ckv.value = jax.lax.dynamic_update_slice(
                    ckv.value, rows, (0, idx.value, 0))
                cvalid.value = jax.lax.dynamic_update_slice(
                    cvalid.value, step_valid, (0, idx.value))
                if T == 1:
                    with jax.named_scope("mla_absorb"):
                        seen = jnp.logical_and(
                            jnp.arange(ckv.value.shape[1])[None]
                            <= idx.value, cvalid.value)
                        y = self._absorbed(
                            q[:, 0], kv_up,
                            lambda q_abs: latent_absorbed_attention(
                                q_abs, ckv.value, seen, kv_rank=sp.kv_rank,
                                scale=sp.scale, dtype=self.dtype)
                        )[:, None]
                else:
                    with jax.named_scope("mla_expand"):
                        y = jax.vmap(
                            lambda q1, rows1, valid1:
                            latent_expanded_attention(
                                q1, rows1, valid1, kv_up, positions, sp,
                                self.dtype))(q, ckv.value, cvalid.value)
                idx.value = idx.value + T
        y = pin_batch(y)
        return nn.DenseGeneral(d_model, axis=(-2, -1), dtype=self.dtype,
                               use_bias=False, kernel_init=dense_init,
                               name="out")(y)

    def _expanded_whole(self, q, rows, kv_up, key_valid, causal):
        """No cache: every head's keys and values of the whole call, then
        the pluggable attention (the training path: differentiable)."""
        sp = self.latent
        with jax.named_scope("mla_expand"):
            kv = jnp.einsum("btc,chd->bthd", rows[..., :sp.kv_rank], kv_up)
            k = jnp.concatenate(
                [kv[..., :sp.nope_dim],
                 jnp.broadcast_to(rows[..., None, sp.kv_rank:sp.row],
                                  kv.shape[:3] + (sp.rope_dim,))], axis=-1)
            k, v = pin_batch(k), pin_batch(kv[..., sp.nope_dim:])
        attn = self.attention_fn or dot_product_attention
        return attn(q, k, v, mask=None, key_valid=key_valid, causal=causal,
                    dtype=self.dtype)

    def _absorbed(self, q, kv_up, attend):
        """``q (..., H, nope + rope)`` through `attend` in the latent's
        own space: the key columns of `kv_up` folded into the query, the
        value columns applied to what comes back."""
        sp = self.latent
        qt = jnp.einsum("...hd,chd->...hc", q[..., :sp.nope_dim],
                        kv_up[..., :sp.nope_dim])
        pad = jnp.zeros(qt.shape[:-1] + (sp.row_at_rest - sp.row,), qt.dtype)
        ot = attend(jnp.concatenate([qt, q[..., sp.nope_dim:], pad], axis=-1))
        return jnp.einsum("...hc,chd->...hd", ot, kv_up[..., sp.nope_dim:])

    def _absorbed_in_place(self, q, rows, kv_up, step_valid, ckv, cvalid,
                           idx):
        """One token of one slot against the latent POOL leaf where it
        rests: the rows below ``cache_index`` through the slot's block
        table, each read once for its score and its value, the token's
        own row beside them.  The cache variables leave holding that row
        (what the engine scatters), not a cache."""
        from distributed_deep_learning_tpu.ops.paged_decode_pallas import (
            paged_latent_slot_attention)

        if q.shape[:2] != (1, 1):
            raise ValueError(
                f"a cache of pool leaves serves one token of one slot, got "
                f"queries {q.shape[:2]}; programs with more gather the slot")
        sp = self.latent
        with jax.named_scope("mla_absorb"):
            def attend(q_abs):
                with jax.named_scope("kv_paged_attn"):
                    return paged_latent_slot_attention(
                        q_abs, rows[0, 0], step_valid[0, 0], ckv.value,
                        cvalid.value,
                        self.get_variable("cache", BLOCK_TABLE), idx.value,
                        spec=sp)

            y = self._absorbed(q[0, 0], kv_up, attend)
        ckv.value, cvalid.value = rows, step_valid
        idx.value = idx.value + 1
        return y[None, None]


def _norm(kind: str, dtype, eps: float, name: Optional[str] = None):
    """``layer``: LayerNorm (scale and bias); ``rms``: RMSNorm (scale)."""
    if kind == "rms":
        return nn.RMSNorm(dtype=dtype, epsilon=eps, name=name)
    if kind != "layer":
        raise ValueError(f"norm must be 'layer' or 'rms', got {kind!r}")
    return nn.LayerNorm(dtype=dtype, epsilon=eps, name=name)


class TransformerLayer(nn.Module):
    """Pre-LN block: [self-attn] → [cross-attn]? → [MLP], residuals.

    ``self_valid``/``cross_valid`` are (B, T) boolean padding masks handed
    to the attention implementation in structured form (never as a dense
    (T×T) tensor) so fused kernels can apply them in-block.

    The defaults are GPT-2's block (LayerNorm, biases, a two-matrix GELU
    MLP); `norm`, `use_bias`, `head_dim`, `gate`, `latent` (a
    :class:`LatentSpec`: latent attention in the place of per-head K and V)
    and `mlp` (``gelu`` | ``swiglu`` | ``experts``, the last with an
    :class:`..moe.ExpertSpec`) describe the others, one :class:`LayerSpec`
    a layer.
    """

    num_heads: int = 8
    mlp_dim: int = 2048
    dropout_rate: float = 0.1
    causal: bool = False
    cross_attention: bool = False
    dtype: jnp.dtype = jnp.float32
    attention_fn: Optional[AttentionFn] = None
    decode: bool = False
    rope: Union[bool, RopeSpec] = False
    window: Optional[int] = None
    num_kv_heads: Optional[int] = None
    ln_eps: float = 1e-6   # 1e-5 matches torch/HF LayerNorm (GPT-2 import)
    head_dim: Optional[int] = None
    gate: bool = False
    use_bias: bool = True
    norm: str = "layer"
    mlp: str = "gelu"
    experts: Optional[ExpertSpec] = None
    cache_ring: Optional[int] = None
    latent: Optional[LatentSpec] = None     # set: latent attention

    @nn.compact
    def __call__(self, x, encoded=None, *, self_valid=None, cross_valid=None,
                 train: bool = False):
        # x comes in pinned to the batch axes: the embedding and every
        # layer pin what they hand on (runtime.batch_pin)
        h = _norm(self.norm, self.dtype, self.ln_eps)(x)
        if self.latent is not None:
            h = LatentAttention(self.num_heads, self.latent, self.dtype,
                                self.attention_fn, decode=self.decode,
                                rope=self.rope, ln_eps=self.ln_eps,
                                name="self_attn")(h, self_valid,
                                                  causal=self.causal)
        else:
            h = MultiHeadAttention(
                self.num_heads, self.dtype, self.attention_fn,
                decode=self.decode, rope=self.rope, window=self.window,
                num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                use_bias=self.use_bias, gate=self.gate,
                cache_ring=self.cache_ring,
                name="self_attn")(h, h, self_valid, causal=self.causal)
        h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        x = pin_batch(x + h)
        if self.cross_attention:
            h = _norm(self.norm, self.dtype, self.ln_eps)(x)
            h = MultiHeadAttention(self.num_heads, self.dtype,
                                   self.attention_fn,
                                   name="cross_attn")(h, encoded, cross_valid)
            h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
            x = pin_batch(x + h)
        h = _norm(self.norm, self.dtype, self.ln_eps)(x)
        if self.mlp == "gelu":
            h = nn.Dense(self.mlp_dim, dtype=self.dtype,
                         use_bias=self.use_bias, kernel_init=dense_init)(h)
            h = nn.gelu(pin_batch(h))
            h = nn.Dense(x.shape[-1], dtype=self.dtype,
                         use_bias=self.use_bias, kernel_init=dense_init)(h)
        elif self.mlp == "swiglu":
            h = GatedMLP(self.mlp_dim, self.dtype, name="mlp")(h)
        elif self.mlp == "experts":
            h = RoutedExperts(self.experts, self.dtype, self.decode,
                              name="moe")(h)
        else:
            raise ValueError(f"mlp must be 'gelu', 'swiglu' or 'experts', "
                             f"got {self.mlp!r}")
        h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        return pin_batch(x + pin_batch(h))


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one decoder layer of a :class:`CausalLM` is made of: the
    fields of :class:`TransformerLayer` that a published config gives
    layer by layer (head counts, window, RoPE and MLP by layer kind)."""

    num_heads: int
    mlp_dim: int
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    window: Optional[int] = None
    rope: Union[bool, RopeSpec] = False
    gate: bool = False
    use_bias: bool = True
    norm: str = "layer"
    mlp: str = "gelu"
    experts: Optional[ExpertSpec] = None
    latent: Optional[LatentSpec] = None


class Embed(nn.Module):
    vocab_size: int
    d_model: int
    max_len: int = 4096
    dtype: jnp.dtype = jnp.float32
    decode: bool = False
    use_pos: bool = True   # False: no learned positions (RoPE models)

    @nn.compact
    def __call__(self, tokens):
        emb = nn.Embed(self.vocab_size, self.d_model,
                       embedding_init=nn.initializers.normal(0.02),
                       dtype=self.dtype, name="tok")
        if not self.use_pos:
            return pin_batch(emb(tokens)), emb
        pos = self.param("pos", nn.initializers.normal(0.02),
                         (self.max_len, self.d_model))
        T = tokens.shape[1]
        if self.decode and self.has_variable("cache", "pos_index"):
            # single-token decode: position = running cache index
            idx = self.variable("cache", "pos_index",
                                lambda: jnp.zeros((), jnp.int32))
            p = jax.lax.dynamic_slice_in_dim(pos, idx.value, T)
            idx.value = idx.value + T
        else:
            if self.decode:  # init pass: create the counter
                self.variable("cache", "pos_index",
                              lambda: jnp.zeros((), jnp.int32))
            p = pos[:T]
        x = emb(tokens) + p[None].astype(self.dtype)
        return pin_batch(x), emb

    @staticmethod
    def logits(x, table):
        """Output projection through a (V, d) table, the token embedding
        where the head is tied, accumulated in f32.

        Not ``emb.attend``: Flax's attend re-casts both operands to the
        module dtype, so under bf16 the vocab-wide matmul would accumulate
        in bf16 — here the cast to f32 happens *before* the contraction.
        """
        with jax.named_scope("head"):
            table = jnp.asarray(table, jnp.float32)
            return pin_batch(jnp.einsum(
                "...d,vd->...v", pin_batch(x).astype(jnp.float32), table))


class TransformerSeq2Seq(nn.Module):
    """Transformer-base encoder-decoder (WMT14 en-de shape).

    ``__call__(batch)`` with ``batch = {"inputs": (B,S), "targets": (B,T)}``
    (token ids, 0 = pad) does teacher-forced training: returns logits over
    the target vocabulary at every target position.
    """

    vocab_size: int = 32000
    num_layers: int = 6
    d_model: int = 512
    num_heads: int = 8
    mlp_dim: int = 2048
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.float32
    attention_fn: Optional[AttentionFn] = None

    @nn.compact
    def __call__(self, batch, train: bool = False):
        inputs, targets = batch["inputs"], batch["targets"]
        src_valid = inputs != 0    # (B, S)
        tgt_valid = targets != 0   # (B, T)

        # one shared-vocabulary embedding for source, target and the
        # (weight-tied) output projection — the transformer-base recipe
        embed = Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                      name="embed")
        x, emb = embed(inputs)
        for i in range(self.num_layers):
            x = TransformerLayer(self.num_heads, self.mlp_dim,
                                 self.dropout_rate, dtype=self.dtype,
                                 attention_fn=self.attention_fn,
                                 name=f"enc_{i}")(x, self_valid=src_valid,
                                                  train=train)
        encoded = nn.LayerNorm(dtype=self.dtype, name="enc_norm")(x)

        # shift right: BOS-from-zero teacher forcing
        y_in = jnp.pad(targets, ((0, 0), (1, 0)))[:, :-1]
        y, _ = embed(y_in)
        for i in range(self.num_layers):
            y = TransformerLayer(self.num_heads, self.mlp_dim,
                                 self.dropout_rate, causal=True,
                                 cross_attention=True, dtype=self.dtype,
                                 attention_fn=self.attention_fn,
                                 name=f"dec_{i}")(y, encoded,
                                                  self_valid=tgt_valid,
                                                  cross_valid=src_valid,
                                                  train=train)
        y = nn.LayerNorm(dtype=self.dtype, name="dec_norm")(y)
        return Embed.logits(y, emb.embedding)


class CausalLM(nn.Module):
    """GPT-style decoder-only LM — the long-context flagship shape.

    ``__call__(tokens)`` returns the final hidden states ``(B, T, d)``
    (``with_logits=False``), the ``(B, T, V)`` logits (``True``) or, for a
    training step, both halves of them not yet multiplied
    (``"deferred"``: :class:`..ops.fused_ce.DeferredLogits`, which the
    token loss and the prediction metrics take a block of logits at a
    time); ``logits_from(params, hidden)`` is the explicit projection
    serving samples through.  The reference has no autoregressive model
    at all (its only sequence model consumes 10-step windows,
    ``LSTM/dataset.py:25``); this is the shape ring attention / Ulysses /
    the SPMD pipeline and the flash kernels are built to scale.
    """

    vocab_size: int = 32000
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    dropout_rate: float = 0.0
    max_len: int = 8192
    #: what ``__call__`` returns: False the hidden states, True the
    #: (B, T, V) logits, "deferred" a DeferredLogits (hidden, table)
    with_logits: Union[bool, str] = False
    decode: bool = False        # KV-cached autoregressive decode mode
    pos_embedding: str = "learned"   # learned | rope
    attention_window: Optional[int] = None  # causal sliding window
    num_kv_heads: Optional[int] = None      # grouped-query attention
    dtype: jnp.dtype = jnp.float32
    attention_fn: Optional[AttentionFn] = None
    ln_eps: float = 1e-6   # 1e-5 matches torch/HF LayerNorm (GPT-2 import)
    pad_id: Optional[int] = 0   # None: no padding id (GPT-2's id 0 is "!")
    #: one LayerSpec a layer, for a decoder whose layers differ or are not
    #: GPT-2's block (``num_layers`` must be their count); None: every
    #: layer is the block the fields above describe
    layers: Optional[tuple] = None
    tie_head: bool = True       # False: an output table of its own
    #: serving only: window layers cache a ring of this many positions
    #: (:class:`MultiHeadAttention`); set by the paged engine
    cache_ring: Optional[int] = None

    def layer_specs(self) -> tuple:
        """One :class:`LayerSpec` a layer, GPT-2's block spelled out the
        same way as any other."""
        if self.layers is not None:
            if len(self.layers) != self.num_layers:
                raise ValueError(f"{len(self.layers)} layer specs for "
                                 f"num_layers {self.num_layers}")
            return self.layers
        return (LayerSpec(self.num_heads, self.mlp_dim,
                          num_kv_heads=self.num_kv_heads,
                          window=self.attention_window,
                          rope=self.pos_embedding == "rope"),
                ) * self.num_layers

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        valid = tokens != self.pad_id if self.pad_id is not None else None
        specs = self.layer_specs()
        x, emb = Embed(self.vocab_size, self.d_model, max_len=self.max_len,
                       dtype=self.dtype, decode=self.decode,
                       use_pos=self.pos_embedding != "rope",
                       name="embed")(tokens)
        for i, spec in enumerate(specs):
            x = TransformerLayer(dropout_rate=self.dropout_rate,
                                 causal=True, dtype=self.dtype,
                                 attention_fn=self.attention_fn,
                                 decode=self.decode, ln_eps=self.ln_eps,
                                 cache_ring=self.cache_ring, **vars(spec),
                                 name=f"layer_{i}")(x, self_valid=valid,
                                                    train=train)
        x = _norm(specs[-1].norm, self.dtype, self.ln_eps,   # as the layers'
                  "final_norm")(x)
        head = emb.embedding if self.tie_head else self.param(
            "head", nn.initializers.normal(0.02),
            (self.vocab_size, self.d_model))
        # the CLI's step scores logits (token_cross_entropy + argmax
        # metrics) and takes them deferred, a block at a time, so (B·T, V)
        # never rests; serving keeps the hidden states and projects the
        # positions it samples
        if self.with_logits == "deferred":
            from distributed_deep_learning_tpu.ops.fused_ce import (
                DeferredLogits)

            return DeferredLogits(x, head)
        return Embed.logits(x, head) if self.with_logits else pin_batch(x)

    def _table(self, params):
        if not self.tie_head:
            return params["params"]["head"]
        return params["params"]["embed"]["tok"]["embedding"]

    def logits_from(self, params, hidden):
        return Embed.logits(hidden, self._table(params))


class BertEncoder(nn.Module):
    """BERT-base-shaped bidirectional encoder with an MLM head
    (BASELINE config[4]: MLM pretrain, pjit 2D mesh + ZeRO-1)."""

    vocab_size: int = 30522
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.float32
    attention_fn: Optional[AttentionFn] = None
    ln_eps: float = 1e-6   # HF BERT checkpoints use 1e-12

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        valid = tokens != 0  # (B, T)
        x, emb = Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                       name="embed")(tokens)
        for i in range(self.num_layers):
            x = TransformerLayer(self.num_heads, self.mlp_dim,
                                 self.dropout_rate, dtype=self.dtype,
                                 attention_fn=self.attention_fn,
                                 ln_eps=self.ln_eps,
                                 name=f"layer_{i}")(x, self_valid=valid,
                                                    train=train)
        x = nn.LayerNorm(dtype=self.dtype, epsilon=self.ln_eps,
                         name="final_norm")(x)
        # MLM head: dense + gelu + norm, weight-tied vocab projection
        h = nn.Dense(self.d_model, dtype=self.dtype, name="mlm_dense")(x)
        h = nn.gelu(h)
        h = nn.LayerNorm(dtype=self.dtype, epsilon=self.ln_eps,
                         name="mlm_norm")(h)
        return Embed.logits(h, emb.embedding)


def transformer_base(**kw) -> TransformerSeq2Seq:
    return TransformerSeq2Seq(**kw)


def bert_base(**kw) -> BertEncoder:
    return BertEncoder(**kw)


def random_causal_lm(seed: int = 0, **geometry):
    """A randomly initialised :class:`CausalLM` and its params: what the
    serve drills and engine tests drive when no trained weights exist
    (scheduling, recovery and compile counts do not care)."""
    model = CausalLM(**geometry)
    toks = jnp.ones((1, 8), jnp.int32)
    return model, model.init(jax.random.key(seed), toks)["params"]


def make_decode_model(model: "CausalLM") -> "CausalLM":
    """The KV-cached inference twin of a trained :class:`CausalLM`:
    decode mode on, hidden-state output (the weight-tied head projects
    only the positions that are sampled), dropout off.  Both
    :func:`generate` and the continuous-batching engine
    (:mod:`..serve.engine`) decode through this one clone recipe."""
    return model.clone(decode=True, with_logits=False, dropout_rate=0.0)


def init_cache(lm: "CausalLM", batch: int, total_len: int,
               token_dtype=jnp.int32):
    """Zeroed decode-cache pytree for ``batch`` rows of ``total_len``.

    Cache buffers are zeros by construction, so they are shaped via
    ``eval_shape`` — no full-length forward, no throwaway parameter
    init.  ``lm`` must be a decode-mode model (:func:`make_decode_model`).
    """
    shapes = jax.eval_shape(lm.init, jax.random.key(0),
                            jax.ShapeDtypeStruct((batch, total_len),
                                                 token_dtype))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


def cached_apply(lm: "CausalLM", params, cache, tokens):
    """One cached forward — a multi-token prefill chunk or a 1-token
    decode step (the decode-mode causal prefix mask keeps in-chunk
    attention causal either way).  Returns ``(hidden, new_cache)``.
    The single implementation under both :func:`generate` and the
    serving engine's prefill/decode programs."""
    hidden, upd = lm.apply({"params": params, "cache": cache}, tokens,
                           mutable=["cache"])
    return hidden, upd["cache"]


def cached_apply_counting(lm: "CausalLM", params, cache, tokens):
    """:func:`cached_apply` that also hands back what the expert layers
    counted: ``(hidden, new_cache, load)``, `load` the call's assignments
    to each held expert, a row an expert layer in layer order (None for a
    model without expert layers, whose program is cached_apply's own)."""
    if not any(sp.experts for sp in lm.layer_specs()):
        return cached_apply(lm, params, cache, tokens) + (None,)
    hidden, upd = lm.apply({"params": params, "cache": cache}, tokens,
                           mutable=["cache", "moe_stats"])
    load = jnp.stack([upd["moe_stats"][f"layer_{i}"]["moe"]["load"][0]
                      for i, sp in enumerate(lm.layer_specs())
                      if sp.experts])
    return hidden, upd["cache"], load


def validate_sampling(top_k: int | None, top_p: float | None) -> None:
    """Host-side bounds check shared by every sampling entry point."""
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def sample_tokens(model: "CausalLM", params, hidden_last, key, *,
                  temperature: float = 0.0, top_k: int | None = None,
                  top_p: float | None = None):
    """Project final hidden states ``(B, d)`` through the weight-tied
    head and pick one token per row; returns ``(tokens (B,), key)``.

    THE sampler — :func:`generate` and the serving engine both call it,
    so greedy/top-k/top-p semantics cannot drift between the batch and
    continuous-batching paths.  Greedy at ``temperature == 0.0``, else
    samples from ``softmax(logits / temperature)``; top-k and top-p
    (nucleus) filters compose, k first then p, as in the common HF
    semantics.  Top-k selection is ``jax.lax.top_k`` — O(V·k) partial
    selection instead of a full per-step vocab sort.
    """
    nl = model.logits_from({"params": params}, hidden_last)  # (B, V)
    if model.pad_id is not None:
        # never emit the pad id: the cache records a generated pad as
        # invalid (valid = tokens != pad_id), silently dropping that
        # position from all subsequent attention and skewing the
        # continuation (ADVICE r3).  pad_id=None (e.g. imported
        # GPT-2, whose id 0 is a real token) has no such hazard.
        nl = nl.at[:, model.pad_id].set(-jnp.inf)
    if top_k is not None and top_k < nl.shape[-1]:
        # mask everything below the k-th logit (static k — jit-safe)
        kth = jax.lax.top_k(nl, top_k)[0][:, -1][:, None]
        nl = jnp.where(nl >= kth, nl, -jnp.inf)
    if temperature == 0.0:
        return jnp.argmax(nl, axis=-1), key
    scaled = nl / temperature
    if top_p is not None and top_p < 1.0:
        # nucleus: keep the smallest prefix of the sorted distribution
        # whose mass reaches top_p (the crossing token included)
        order = jnp.argsort(-scaled, axis=-1)
        sp = jnp.take_along_axis(jax.nn.softmax(scaled, axis=-1),
                                 order, axis=-1)
        drop_sorted = jnp.cumsum(sp, axis=-1) - sp > top_p
        drop = jnp.zeros_like(drop_sorted).at[
            jnp.arange(nl.shape[0])[:, None], order].set(drop_sorted)
        scaled = jnp.where(drop, -jnp.inf, scaled)
    key, sub = jax.random.split(key)
    return jax.random.categorical(sub, scaled), key


def generate(model: "CausalLM", params, prompt: jnp.ndarray, *,
             max_new_tokens: int, temperature: float = 0.0,
             top_k: int | None = None, top_p: float | None = None,
             rng: jnp.ndarray | None = None) -> jnp.ndarray:
    """KV-cached autoregressive generation from a trained :class:`CausalLM`.

    ``prompt`` is (B, P) token ids; returns the (B, max_new_tokens)
    continuation.  Greedy at ``temperature == 0.0``, else samples from
    ``softmax(logits / temperature)``, optionally truncated to the top-k
    logits and/or the top-p (nucleus) mass — both filters compose, k
    first then p, as in the common HF semantics.  The whole loop is one
    ``lax.scan`` of 1-token cached decode steps (O(T) per token via the
    attention KV cache; positions follow the cache index) —
    jit-compatible, static shapes, TPU-friendly.

    The reference has no inference story at all (SURVEY.md: every run is
    train-then-test); this is part of the LM-family surface a complete
    framework owes its users.

    The prompt is prefilled in ONE multi-token cached call (the decode
    path's causal prefix mask keeps in-chunk attention causal), then each
    new token is a 1-token step.  Pad positions (id ``model.pad_id``)
    inside the prompt are masked out of attention via the cache's
    validity buffer (with ``pad_id=None`` — e.g. imported GPT-2 — every
    prompt position is attended and nothing is masked), but generation
    always proceeds from each row's FINAL position — prefer unpadded
    (or left-trimmed) prompts.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got "
                         f"{max_new_tokens}")
    validate_sampling(top_k, top_p)
    # hidden-state mode: project ONLY the final position through the
    # weight-tied head — prefill never materialises the (B, P, V) logits
    lm = make_decode_model(model)
    B, P = prompt.shape
    total = P + max_new_tokens
    if total > model.max_len:
        raise ValueError(f"prompt {P} + {max_new_tokens} new tokens "
                         f"exceeds max_len {model.max_len}")
    cache = init_cache(lm, B, total, prompt.dtype)
    key0 = rng if rng is not None else jax.random.key(0)

    def pick(hidden_last, key):
        return sample_tokens(model, params, hidden_last, key,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p)

    # prefill: the whole prompt in ONE multi-token cached call (the
    # decode-mode causal prefix mask keeps in-chunk attention causal)
    hidden, cache = cached_apply(lm, params, cache, prompt)
    first, key0 = pick(hidden[:, -1], key0)
    first = first.astype(prompt.dtype)

    def step(carry, _):
        cache, tok, key = carry
        hidden, cache = cached_apply(lm, params, cache, tok[:, None])
        nxt, key = pick(hidden[:, -1], key)
        return (cache, nxt.astype(tok.dtype), key), nxt

    (_, _, _), toks = jax.lax.scan(
        step, (cache, first, key0), None, length=max_new_tokens - 1)
    return jnp.concatenate(
        [first[:, None], jnp.swapaxes(toks, 0, 1).astype(prompt.dtype)],
        axis=1)
