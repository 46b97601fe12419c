"""Plain reference for the GPT-2 configurations: forward pass, loss,
gradients and AdamW in straightforward ``jax.numpy`` and float32.

No kernels, no cache, no batching tricks, nothing imported from the
package under test, and no weights, scales or tables taken from it: the
weights are the flat dict `benchmark/weights/gpt2.py` draws from the seed
(layer arrays stacked on a leading axis).  Call it under
``jax.default_matmul_precision("highest")`` (`highest()` below): on a TPU a
float32 matrix multiplication otherwise runs in bfloat16 passes.

Departures from the published GPT-2, all shared with the package's
``CausalLM`` so that like is compared with like: LayerNorm epsilon 1e-6,
q/k/v as three projections, the loss averaged over positions whose
target is not the pad id 0.

``quant`` is the control, not a feature: every matrix product first
rounds both operands to a precision step below bfloat16.  ``"fp8"`` is
e4m3 with one scale a tensor (the usual fp8 recipe: 3 mantissa bits), the
control the limits were first set against.  ``"int8"`` is symmetric int8
with a scale per row of the activations and per output channel of the
weights: the gentlest step down, read on the chip at the cells' own sizes
in PR 23's review round (3.4 x the sound runs' largest on the gradient
difference, 4-8 x on the mean served-token gap; PERF.md), and the limits
in ``benchmark/limits/`` now hold it off too.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
PAD_ID = 0


def highest():
    return jax.default_matmul_precision("highest")


def _fake_int8(x, axis):
    """Round to symmetric int8 along `axis` (straight-through gradient)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x):
    """Round to float8 e4m3 (1 + 3 mantissa bits, least normal 2**-6,
    largest 448) after scaling the tensor's largest magnitude to 448;
    arithmetic only, so it runs wherever float32 does.  Straight-through
    gradient."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax == 0, 1.0, 448.0 / amax)
    y = x * scale
    m, e = jnp.frexp(y)                       # y = m * 2**e, |m| in [.5, 1)
    q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    q = jnp.where(jnp.abs(y) < 2.0 ** -6, jnp.round(y * 512.0) / 512.0, q)
    return x + jax.lax.stop_gradient(q / scale - x)


def _mm(spec: str, x, w, quant, w_axes):
    """einsum(spec, x, w); under the control both operands are rounded
    (int8: x along its last axis, w along its contracted axes)."""
    if quant == "int8":
        x = _fake_int8(x, -1)
        w = _fake_int8(w, w_axes)
    elif quant == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.einsum(spec, x, w)


def layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, w, quant=None):
    """One pre-LN decoder block; `w` holds ONE layer's arrays."""
    T = x.shape[1]
    h = layer_norm(x, w["ln1_g"], w["ln1_b"])
    q = _mm("btd,dhk->bthk", h, w["wq"], quant, (0,)) + w["bq"]
    k = _mm("btd,dhk->bthk", h, w["wk"], quant, (0,)) + w["bk"]
    v = _mm("btd,dhk->bthk", h, w["wv"], quant, (0,)) + w["bv"]
    s = jnp.einsum("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqt,bthk->bqhk", p, v)
    x = x + _mm("bqhk,hkd->bqd", o, w["wo"], quant, (0, 1)) + w["bo"]
    h = layer_norm(x, w["ln2_g"], w["ln2_b"])
    h = gelu_new(_mm("btd,df->btf", h, w["w1"], quant, (0,)) + w["b1"])
    return x + _mm("btf,fd->btd", h, w["w2"], quant, (0,)) + w["b2"]


_LAYER_NAMES = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                "bo", "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")


def hidden(w: dict, tokens, quant=None):
    """Final-norm hidden states (B, T, d) for int tokens (B, T)."""
    w = {n: a.astype(jnp.float32) for n, a in w.items()}
    T = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:T][None]
    layers = {n: w[n] for n in _LAYER_NAMES}

    # one layer's activations live at a time: the backward pass
    # recomputes a block from its input (same arithmetic, less memory)
    @jax.checkpoint
    def body(x, layer):
        return block(x, layer, quant), None

    x, _ = jax.lax.scan(body, x, layers)
    return layer_norm(x, w["lnf_g"], w["lnf_b"])


def logits(w: dict, tokens, quant=None):
    """(B, T, V) float32 logits through the tied head."""
    h = hidden(w, tokens, quant)
    return _mm("btd,vd->btv", h, w["wte"].astype(jnp.float32), quant, (1,))


def loss_sum(w: dict, x, y, quant=None):
    """(sum of next-token cross-entropy over non-pad targets, count)."""
    lg = logits(w, x, quant)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    valid = (y != PAD_ID).astype(jnp.float32)
    return -jnp.sum(picked * valid), jnp.sum(valid)


@functools.partial(jax.jit, static_argnames=("quant",))
def _block_grad(w, x, y, quant=None):
    (s, n), g = jax.value_and_grad(loss_sum, has_aux=True)(w, x, y, quant)
    return s, n, g


def loss_and_grad(w: dict, x, y, rows: int, quant=None):
    """Mean loss and its gradient over the whole batch, accumulated over
    blocks of `rows` rows so that the activations fit."""
    total = count = 0.0
    grads = None
    for i in range(0, x.shape[0], rows):
        s, n, g = _block_grad(w, x[i:i + rows], y[i:i + rows], quant)
        total, count = total + s, count + n
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / count, jax.tree.map(lambda a: a / count, grads)


@functools.partial(jax.jit, donate_argnums=(1, 2, 3))
def adamw_step(w, m, v, g, t, lr, b1, b2, eps, wd, mask):
    """optax.adamw's arithmetic, written out: Adam's bias-corrected
    moments, decoupled decay on the masked arrays, then the step."""
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def new(w_, m_, v_, decayed):
        u = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
        return w_ - lr * (u + jnp.where(decayed, wd, 0.0) * w_)

    return jax.tree.map(new, w, m, v, mask), m, v


def train_steps(w0: dict, batches, opt: dict, mask: dict, rows: int,
                quant=None):
    """Follow `batches` [(x, y), ...] from `w0`: each step's loss, the
    first gradient (on the host) and the weights after the last step."""
    w = w0
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    mask = {n: jnp.asarray(b) for n, b in mask.items()}
    losses, first_grad = [], None
    for t, (x, y) in enumerate(batches, start=1):
        loss, g = loss_and_grad(w, x, y, rows, quant)
        if first_grad is None:      # to the host: g is donated below
            first_grad = jax.device_get(g)
        losses.append(loss)
        w, m, v = adamw_step(w, m, v, g, jnp.float32(t), opt["lr"],
                             opt["b1"], opt["b2"], opt["eps"],
                             opt["weight_decay"], mask)
    return losses, first_grad, w


@functools.partial(jax.jit, static_argnames=("quant",))
def token_gaps(w: dict, tokens, quant=None):
    """For tokens (B, T): at each position t, how far the logit of the
    token that FOLLOWS (tokens[:, t + 1]) lies below the best logit,
    shape (B, T - 1) — 0 where the follower is the reference's own
    greedy choice.  Also the reference's greedy token at each position."""
    lg = logits(w, tokens, quant)[:, :-1]
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return best - got, jnp.argmax(lg, axis=-1)


@jax.jit
def gaps_of(w: dict, tokens, chosen):
    """Float32 logit gap of `chosen` (B, T) tokens, position by position,
    in the context `tokens` (B, T): best logit minus chosen's logit."""
    lg = logits(w, tokens)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, chosen[..., None], axis=-1)[..., 0]
    return best - got
