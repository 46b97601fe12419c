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

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

AttentionFn = Callable[..., jnp.ndarray]
dense_init = nn.initializers.xavier_uniform()


def dot_product_attention(q, k, v, *, mask=None, key_valid=None,
                          causal=False, window=None, dtype=jnp.float32):
    """Plain softmax attention; q/k/v are (B, T, H, D).

    Masking follows the structured convention shared with the flash and
    ring implementations: ``key_valid`` is a (B, Tk) boolean padding mask,
    ``causal`` a flag, ``window`` an optional causal sliding-window size
    (each query sees its last ``window`` positions); a pre-built dense
    ``mask`` (broadcastable to (B, H, Tq, Tk)) is also accepted and
    combined.
    """
    depth = q.shape[-1]
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
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               base: float = 10000.0) -> jnp.ndarray:
    """Rotary position embedding on ``(B, T, H, D)`` (D even).

    Rotates feature pairs ``(x[..., :D/2], x[..., D/2:])`` by
    ``position · base^(-2i/D)`` — attention then depends on RELATIVE
    positions only.  Parameter-free, so tensor-parallel sharding rules
    and the weight-tied head are untouched; the KV-cache decode path
    passes ``positions = cache_index + arange(T)`` so cached keys carry
    their absolute rotation.
    """
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


class MultiHeadAttention(nn.Module):
    """Projections + pluggable attention; ``decode=True`` adds a KV cache.

    The cache is created at init time (full-length call shapes the
    ``cached_key``/``cached_value`` buffers); each subsequent 1-token call
    appends its K/V at ``cache_index`` and attends the single query
    against the filled prefix — autoregressive decode costs O(T) per
    token instead of O(T²) recompute.
    """

    num_heads: int
    dtype: jnp.dtype = jnp.float32
    attention_fn: Optional[AttentionFn] = None
    decode: bool = False
    rope: bool = False
    window: Optional[int] = None   # causal sliding-window size
    num_kv_heads: Optional[int] = None  # < num_heads = grouped-query attn

    @nn.compact
    def __call__(self, x_q, x_kv, key_valid=None, *, causal: bool = False,
                 mask=None):
        d_model = x_q.shape[-1]
        head_dim = d_model // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        if self.num_heads % kv_heads:
            raise ValueError(f"num_kv_heads {kv_heads} must divide "
                             f"num_heads {self.num_heads}")
        proj = lambda name, h: nn.DenseGeneral(  # noqa: E731
            (h, head_dim), dtype=self.dtype,
            kernel_init=dense_init, name=name)
        q = proj("q", self.num_heads)(x_q)
        k = proj("k", kv_heads)(x_kv)
        v = proj("v", kv_heads)(x_kv)
        if self.rope:
            start = jnp.zeros((), jnp.int32)
            if self.decode and self.has_variable("cache", "cache_index"):
                start = self.get_variable("cache", "cache_index")
            positions = start + jnp.arange(q.shape[1])
            q = apply_rope(q, positions)
            k = apply_rope(k, positions)  # cached K carry their rotation
        attn = self.attention_fn or dot_product_attention
        if self.decode:
            is_init = not self.has_variable("cache", "cached_key")
            ck = self.variable("cache", "cached_key", jnp.zeros, k.shape,
                               k.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros, v.shape,
                               v.dtype)
            # remember each cached position's padding validity too — the
            # full forward masks pad tokens, so decode must as well
            cvalid = self.variable(
                "cache", "cached_valid",
                lambda: jnp.zeros(k.shape[:2], jnp.bool_))
            idx = self.variable("cache", "cache_index",
                                lambda: jnp.zeros((), jnp.int32))
            if not is_init:
                T = q.shape[1]
                max_len = ck.value.shape[1]
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k, (0, idx.value, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v, (0, idx.value, 0, 0))
                step_valid = (key_valid if key_valid is not None
                              else jnp.ones(k.shape[:2], jnp.bool_))
                cvalid.value = jax.lax.dynamic_update_slice(
                    cvalid.value, step_valid, (0, idx.value))
                k, v = ck.value, cv.value
                key_valid = cvalid.value
                # causal prefix: query j (global position idx+j) sees key
                # positions <= idx+j — correct for 1-token steps AND
                # multi-token prefill chunks
                qpos = idx.value + jnp.arange(T)
                kpos = jnp.arange(max_len)[None, None, None, :]
                mask = kpos <= qpos[None, None, :, None]
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
        if kv_heads != self.num_heads and \
                not getattr(attn, "supports_gqa", False):
            # GQA: K/V carry kv_heads (and the KV cache stores only those
            # — the H/kv_heads memory win); expand to full heads for the
            # attention contraction (XLA fuses the broadcast).  A
            # GQA-native implementation (the flash kernel) takes the
            # unexpanded K/V and maps heads internally — group× less K/V
            # HBM traffic, which is the other half of the GQA win.
            group = self.num_heads // kv_heads
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        kw = {}
        if self.window is not None and mask is None:
            # structured convention: window rides alongside causal so the
            # flash kernel can bound its key loops instead of masking
            kw["window"] = self.window
        y = attn(q, k, v, mask=mask, key_valid=key_valid, causal=causal,
                 dtype=self.dtype, **kw)
        return nn.DenseGeneral(d_model, axis=(-2, -1), dtype=self.dtype,
                               kernel_init=dense_init, name="out")(y)


class TransformerLayer(nn.Module):
    """Pre-LN block: [self-attn] → [cross-attn]? → [MLP], residuals.

    ``self_valid``/``cross_valid`` are (B, T) boolean padding masks handed
    to the attention implementation in structured form (never as a dense
    (T×T) tensor) so fused kernels can apply them in-block.
    """

    num_heads: int = 8
    mlp_dim: int = 2048
    dropout_rate: float = 0.1
    causal: bool = False
    cross_attention: bool = False
    dtype: jnp.dtype = jnp.float32
    attention_fn: Optional[AttentionFn] = None
    decode: bool = False
    rope: bool = False
    window: Optional[int] = None
    num_kv_heads: Optional[int] = None
    ln_eps: float = 1e-6   # 1e-5 matches torch/HF LayerNorm (GPT-2 import)

    @nn.compact
    def __call__(self, x, encoded=None, *, self_valid=None, cross_valid=None,
                 train: bool = False):
        h = nn.LayerNorm(dtype=self.dtype, epsilon=self.ln_eps)(x)
        h = MultiHeadAttention(self.num_heads, self.dtype, self.attention_fn,
                               decode=self.decode, rope=self.rope,
                               window=self.window,
                               num_kv_heads=self.num_kv_heads,
                               name="self_attn")(h, h, self_valid,
                                                 causal=self.causal)
        h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        x = x + h
        if self.cross_attention:
            h = nn.LayerNorm(dtype=self.dtype, epsilon=self.ln_eps)(x)
            h = MultiHeadAttention(self.num_heads, self.dtype,
                                   self.attention_fn,
                                   name="cross_attn")(h, encoded, cross_valid)
            h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
            x = x + h
        h = nn.LayerNorm(dtype=self.dtype, epsilon=self.ln_eps)(x)
        h = nn.Dense(self.mlp_dim, dtype=self.dtype, kernel_init=dense_init)(h)
        h = nn.gelu(h)
        h = nn.Dense(x.shape[-1], dtype=self.dtype, kernel_init=dense_init)(h)
        h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        return x + h


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
            return emb(tokens), emb
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
        return x, emb

    @staticmethod
    def logits(x, emb):
        """Weight-tied output projection, accumulated in f32.

        Not ``emb.attend``: Flax's attend re-casts both operands to the
        module dtype, so under bf16 the vocab-wide matmul would accumulate
        in bf16 — here the cast to f32 happens *before* the contraction.
        """
        with jax.named_scope("head"):
            table = jnp.asarray(emb.embedding, jnp.float32)
            return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                              table)


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
        return Embed.logits(y, emb)


class CausalLM(nn.Module):
    """GPT-style decoder-only LM — the long-context flagship shape.

    ``__call__(tokens)`` returns the final hidden states ``(B, T, d)``;
    ``loss(params, hidden, targets)`` computes the weight-tied LM loss via
    :func:`..ops.fused_ce.fused_linear_cross_entropy` (never materialises
    the ``(B·T, V)`` logit matrix), and ``logits_from(params, hidden)``
    the explicit projection for eval/tests.  The reference has no autoregressive model
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
    with_logits: bool = False   # True: __call__ returns (B, T, V) logits
    decode: bool = False        # KV-cached autoregressive decode mode
    pos_embedding: str = "learned"   # learned | rope
    attention_window: Optional[int] = None  # causal sliding window
    num_kv_heads: Optional[int] = None      # grouped-query attention
    dtype: jnp.dtype = jnp.float32
    attention_fn: Optional[AttentionFn] = None
    ln_eps: float = 1e-6   # 1e-5 matches torch/HF LayerNorm (GPT-2 import)
    pad_id: Optional[int] = 0   # None: no padding id (GPT-2's id 0 is "!")

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        valid = tokens != self.pad_id if self.pad_id is not None else None
        rope = self.pos_embedding == "rope"
        x, emb = Embed(self.vocab_size, self.d_model, max_len=self.max_len,
                       dtype=self.dtype, decode=self.decode,
                       use_pos=not rope, name="embed")(tokens)
        for i in range(self.num_layers):
            x = TransformerLayer(self.num_heads, self.mlp_dim,
                                 self.dropout_rate, causal=True,
                                 dtype=self.dtype,
                                 attention_fn=self.attention_fn,
                                 decode=self.decode, rope=rope,
                                 window=self.attention_window,
                                 num_kv_heads=self.num_kv_heads,
                                 ln_eps=self.ln_eps,
                                 name=f"layer_{i}")(x, self_valid=valid,
                                                    train=train)
        x = nn.LayerNorm(dtype=self.dtype, epsilon=self.ln_eps,
                         name="final_norm")(x)
        # the CLI/workload convention wants logits (token_cross_entropy +
        # argmax metrics); the bench path keeps hidden states and the
        # fused head (loss()) so (B·T, V) never materialises
        return Embed.logits(x, emb) if self.with_logits else x

    def _table(self, params):
        return params["params"]["embed"]["tok"]["embedding"]

    def loss(self, params, hidden, targets):
        """Mean next-token cross-entropy via the fused head; positions
        whose target equals ``self.pad_id`` are excluded, and with
        ``pad_id=None`` every position counts (e.g. imported GPT-2, whose
        id 0 is a real token).  Pass ``tokens[:, :-1]`` hidden vs
        ``tokens[:, 1:]``."""
        from distributed_deep_learning_tpu.ops.fused_ce import (
            fused_linear_cross_entropy)

        # -1 can never equal a vocab id, so it disables the exclusion
        ignore_id = self.pad_id if self.pad_id is not None else -1
        return fused_linear_cross_entropy(
            hidden.astype(jnp.float32),
            jnp.asarray(self._table(params), jnp.float32), targets,
            ignore_id)

    def logits_from(self, params, hidden):
        with jax.named_scope("head"):
            table = jnp.asarray(self._table(params), jnp.float32)
            return jnp.einsum("...d,vd->...v", hidden.astype(jnp.float32),
                              table)


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
        return Embed.logits(h, emb)


def transformer_base(**kw) -> TransformerSeq2Seq:
    return TransformerSeq2Seq(**kw)


def bert_base(**kw) -> BertEncoder:
    return BertEncoder(**kw)


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
