"""Serving-path quantization: int8 weights and a quantized KV cache.

Decode is memory-bound: at generation time every token streams the whole
parameter set and the slot's entire KV history through the MXU for a few
FLOPs each, so HBM bytes — not compute — cap slots, context length and
prefix-cache size per chip.  This module cuts those bytes without
inventing new numerics: the symmetric int8 machinery is
:func:`..parallel.collectives.quantize` / ``dequantize`` — the same
common-scale wire format the ZeRO/FSDP comm layer ships — applied at two
granularities chosen for the serving data layout:

* **Weights** (:func:`quantize_weights`) — per-OUTPUT-CHANNEL scales
  (one ``collectives.quantize`` per last-axis column, vmapped): matmul
  kernels have output features on the last axis, so each channel gets
  its own amax and the dequant ``q * s`` broadcasts along exactly that
  axis.  Only ``ndim >= 2`` floating leaves quantize; biases and norm
  scales are O(d) bytes and precision-critical, so they stay put.
  Dequantization happens INSIDE the jitted decode program
  (:func:`dequantize_weights` at the top of each impl), so XLA fuses
  the ``int8 -> f32 * scale`` upcast into the matmul operand and no
  full-precision weight copy ever exists at rest.
* **KV cache** (:func:`quantize_kv`) — per-POSITION-per-HEAD scales
  (one ``collectives.quantize`` per ``(..., D)`` row): a decode tick
  writes ONE new position into a block that already holds committed
  positions, so any coarser grain (per-block scales) would need a
  read-modify-write rescale of frozen neighbours — breaking both the
  compile-once scatter and prefix-block immutability (a COW-shared
  block's bytes must never change under its chain hash).  Row scales
  make every position self-contained: blocks stay bit-frozen once
  committed, so :class:`.paged.BlockManager` reuse, copy-on-write and
  the supervisor's replay ledger operate on the quantized
  representation unchanged.

The quantized KV pool is a tree of :class:`QuantTensor` — a registered
pytree node holding the int8 payload ``q`` and its f32 scales ``s``
with IDENTICAL leading dims (in the model's layout ``s`` is
``q.shape[:-1] + (1,)``; at rest in a pool both have their trailing
dims merged like every pool leaf, ``(nb, bs, H*D)`` and ``(nb, bs,
H)``).  That shape choice is the whole trick: every pool op in
:mod:`.paged` (``gather_slot``'s ``leaf[table]``, ``scatter_span``'s
``.at[blocks, offsets]``, ``copy_block``'s block slice) indexes leading
axes only, so ``jax.tree.map`` descending into ``q`` and ``s`` applies
each op to both arrays correctly with ZERO changes to the op — and
``obs.memory.pytree_bytes`` counts payload + scales automatically.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from distributed_deep_learning_tpu.parallel.collectives import (dequantize,
                                                                quantize)
from distributed_deep_learning_tpu.serve.cache import KV_LEAVES, _leaf_name

#: reduced-precision storage formats the serving CLI accepts for
#: ``--kv-dtype`` / ``--weight-dtype`` (``None``/unset means full
#: precision — the engine default, which keeps every exact-parity
#: guarantee bit-identical)
SERVE_DTYPES = ("bf16", "int8")


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class QuantTensor:
    """int8 payload + f32 scales travelling as ONE pytree node.

    ``s`` has ``q``'s leading dims (``q.shape[:-1] + (1,)`` for KV rows,
    ``(C,)`` for weight channels), so tree-mapped indexing ops hit both
    arrays coherently.  A registered class — not a raw ``{"q","s"}``
    dict — because param trees contain modules literally named ``q``;
    ``isinstance`` (via :func:`is_quant`) is the only safe detector.
    """

    q: jax.Array
    s: jax.Array

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.GetAttrKey("q"), self.q),
                (jax.tree_util.GetAttrKey("s"), self.s)), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


def is_quant(x) -> bool:
    return isinstance(x, QuantTensor)


def check_dtype(name: str, value):
    """Validate a ``--kv-dtype`` / ``--weight-dtype`` value (``None``
    passes — full precision).  Shared by the CLI parsers and the engine
    constructors so both reject with the same message."""
    if value is not None and value not in SERVE_DTYPES:
        raise ValueError(f"unknown {name} {value!r}; "
                         f"choose from {SERVE_DTYPES} (or leave unset "
                         "for full precision)")
    return value


# --------------------------------------------------------------------------
# leaf-level quantizers (vmapped reuse of the collectives wire format)
# --------------------------------------------------------------------------


def quantize_channels(x) -> QuantTensor:
    """Per-last-axis-channel symmetric int8: one
    :func:`collectives.quantize` per output-feature column."""
    flat = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    q, s = jax.vmap(lambda col: quantize(col, "int8"),
                    in_axes=1, out_axes=(1, 0))(flat)
    return QuantTensor(q.reshape(x.shape), s)


def quantize_rows(x) -> QuantTensor:
    """Per-row symmetric int8 (every leading index gets its own scale
    over the last axis): one :func:`collectives.quantize` per
    position-per-head KV vector."""
    flat = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    q, s = jax.vmap(lambda row: quantize(row, "int8"))(flat)
    return QuantTensor(q.reshape(x.shape),
                       s.reshape(x.shape[:-1] + (1,)))


def dequant(qt: QuantTensor, dtype):
    """``q * s`` via :func:`collectives.dequantize` (f32 accumulate),
    cast to the engine's compute dtype."""
    return dequantize(qt.q, qt.s, "int8", jnp.float32).astype(dtype)


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------


def quantize_weights(params, weight_dtype: str):
    """Reduced-precision AT-REST form of a decode param tree.

    ``int8``: per-channel :class:`QuantTensor` for every ``ndim >= 2``
    floating leaf (matmul kernels + embed table); vectors (biases, norm
    scales) stay full precision.  ``bf16``: a plain cast — the cast IS
    the quantization, no scales needed.
    """
    check_dtype("weight_dtype", weight_dtype)

    def wq(leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        if weight_dtype == "bf16":
            return leaf.astype(jnp.bfloat16)
        return quantize_channels(leaf) if leaf.ndim >= 2 else leaf

    return jax.tree.map(wq, params)


def dequantize_weights(params, dtype):
    """Compute-dtype view of an at-rest param tree — called at the TOP
    of each jitted impl, so the upcast fuses into each consumer matmul
    and no full-precision copy survives between programs."""
    def wd(leaf):
        if is_quant(leaf):
            return dequant(leaf, dtype)
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(dtype)
        return leaf

    return jax.tree.map(wd, params, is_leaf=is_quant)


def weight_bytes(params) -> int:
    """At-rest bytes of a (possibly quantized) param tree — payload plus
    scales, same accounting as ``obs.memory.pytree_bytes``."""
    return int(sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(params)))


# --------------------------------------------------------------------------
# KV cache
# --------------------------------------------------------------------------


def quantize_kv(x, kv_dtype: str):
    """One KV leaf → its at-rest form (per-row int8 or a bf16 cast)."""
    if kv_dtype == "bf16":
        return x.astype(jnp.bfloat16)
    if kv_dtype == "int8":
        return quantize_rows(x)
    raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                     f"choose from {SERVE_DTYPES}")


def _is_kv(path, leaf) -> bool:
    if is_quant(leaf):
        return True
    return (_leaf_name(path) in KV_LEAVES
            and jnp.issubdtype(leaf.dtype, jnp.floating))


def quantize_cache_span(span, kv_dtype: str):
    """Freshly-computed (floating) KV positions → the pool's at-rest
    representation, ready for ``scatter_span``/``write_slot``.
    Counters and the bool validity mask pass through exact."""
    def f(path, leaf):
        return quantize_kv(leaf, kv_dtype) if _is_kv(path, leaf) else leaf

    return jax.tree_util.tree_map_with_path(f, span)


def dequant_cache(cache, dtype):
    """At-rest cache/pool tree → the model's floating layout at the
    engine's compute dtype (the model's ``dynamic_update_slice`` cache
    writes are dtype-strict, so gathered KV must match computed K/V)."""
    def f(path, leaf):
        if is_quant(leaf):
            return dequant(leaf, dtype)
        if _is_kv(path, leaf):
            return leaf.astype(dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(f, cache, is_leaf=is_quant)


def cast_kv(cache, dtype):
    """Cast the floating KV leaves of a NON-int8 cache tree (used by the
    v1 engine's bf16 path, where the cast is the whole transform)."""
    def f(path, leaf):
        if _leaf_name(path) in KV_LEAVES and \
                jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(f, cache)


# --------------------------------------------------------------------------
# calibration
# --------------------------------------------------------------------------


def calibrate_weight_drift(model, params, qparams, probe_tokens, *,
                           margin: float = 1.5, floor: float = 5e-3):
    """Measure what int8 weights do to the greedy path on a probe batch
    and DECLARE the per-token logprob-drift bound the parity gate will
    hold the engine to.

    Runs the full (non-decode) forward under the original and the
    dequantized params, compares ``log_softmax`` at each position's
    full-precision argmax token (the greedy trajectory — the quantity
    the drift-bounded parity tests measure), and returns
    ``max(margin * max_drift, floor)`` so the declared bound has real
    headroom over the measured worst case without being vacuous.
    """
    full = model.clone(decode=False, with_logits=True)
    toks = jnp.asarray(probe_tokens)
    if toks.ndim == 1:
        toks = toks[None]

    compute = jax.tree.leaves(params)[0].dtype
    ref = full.apply({"params": params}, toks)
    deq = full.apply({"params": dequantize_weights(qparams, compute)},
                     toks)
    ref_lp = jax.nn.log_softmax(ref.astype(jnp.float32), axis=-1)
    deq_lp = jax.nn.log_softmax(deq.astype(jnp.float32), axis=-1)
    pick = jnp.argmax(ref_lp, axis=-1)[..., None]
    drift = jnp.abs(jnp.take_along_axis(ref_lp, pick, axis=-1)
                    - jnp.take_along_axis(deq_lp, pick, axis=-1))
    measured = float(jnp.max(drift))
    agree = float(jnp.mean(jnp.argmax(deq_lp, axis=-1)
                           == pick[..., 0]))
    return {
        "measured_max_drift": measured,
        "declared_bound": max(margin * measured, floor),
        "probe_argmax_agreement": agree,
        "probe_tokens": int(toks.size),
    }
