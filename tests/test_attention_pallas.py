"""Pallas flash attention (interpret mode on CPU) vs dense attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_deep_learning_tpu.ops.attention_pallas import (
    flash_attention, make_attention_fn)
from distributed_deep_learning_tpu.parallel.ring_attention import (
    full_attention)


def _qkv(B=2, T=64, H=2, D=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (B, T, H, D)) for k in ks)


def test_matches_dense():
    q, k, v = _qkv()
    got = flash_attention(q, k, v, block_q=16, block_k=16)
    expected = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_matches_dense_causal():
    q, k, v = _qkv(seed=1)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    expected = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_single_block():
    q, k, v = _qkv(T=16, seed=2)
    got = flash_attention(q, k, v)  # blocks clamp to T
    expected = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_gradients_match_dense():
    q, k, v = _qkv(T=32, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=8, block_k=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-5)


def test_bf16_inputs():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(seed=4))
    got = flash_attention(q, k, v, block_q=16, block_k=16)
    assert got.dtype == jnp.bfloat16
    expected = full_attention(*(x.astype(jnp.float32) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(expected), rtol=5e-2, atol=5e-2)


def test_indivisible_block_snaps():
    """Requested blocks act as upper bounds: T=24 with block 16 snaps to a
    divisor (12) instead of failing — real token files pick T, not us."""
    q, k, v = _qkv(T=24)
    got = flash_attention(q, k, v, block_q=16, block_k=16)
    expected = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_padding_mask_matches_dense():
    """key_valid (B, Tk) padding masks apply in-kernel with the dense
    path's -1e9 semantics."""
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    q, k, v = _qkv(T=32, seed=6)
    valid = jnp.arange(32)[None, :] < jnp.array([[20], [32]])  # (2, 32)
    got = flash_attention(q, k, v, key_valid=valid, block_q=8, block_k=8)
    expected = dot_product_attention(q, k, v, key_valid=valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_padding_plus_causal_matches_dense():
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    q, k, v = _qkv(T=32, seed=7)
    valid = jnp.arange(32)[None, :] < jnp.array([[24], [16]])
    got = flash_attention(q, k, v, key_valid=valid, causal=True,
                          block_q=8, block_k=8)
    expected = dot_product_attention(q, k, v, key_valid=valid, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_padding_mask_gradients_match_dense():
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    q, k, v = _qkv(T=16, seed=8)
    valid = jnp.arange(16)[None, :] < jnp.array([[12], [16]])

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, key_valid=valid,
                                       block_q=8, block_k=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, key_valid=valid) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-5)


def test_cross_attention_lengths():
    """Tq != Tk (decoder cross-attention shape)."""
    B, H, D = 2, 2, 16
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (B, 8, H, D))
    k = jax.random.normal(ks[1], (B, 32, H, D))
    v = jax.random.normal(ks[2], (B, 32, H, D))
    got = flash_attention(q, k, v, block_q=8, block_k=8)
    expected = full_attention(jnp.pad(q, ((0, 0), (0, 24), (0, 0), (0, 0))),
                              k, v)[:, :8]
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_fully_padded_sequence_no_nan():
    q, k, v = _qkv(T=16, seed=10)
    valid = jnp.zeros((2, 16), bool)  # everything masked
    got = flash_attention(q, k, v, key_valid=valid, block_q=8, block_k=8)
    assert np.isfinite(np.asarray(got)).all()


def test_fully_padded_sequence_zero_gradients():
    """Backward regression: with every key masked, lse = m + log(l) must not
    let f32 absorb log(l) into NEG_INF (p would come back as 1 per key and
    inflate dk/dv by ~Tk).  Fully-padded rows contribute zero gradient."""
    q, k, v = _qkv(T=16, seed=13)
    valid = jnp.zeros((2, 16), bool)
    g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, key_valid=valid, block_q=8, block_k=8) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for arr in g:
        arr = np.asarray(arr)
        assert np.isfinite(arr).all()
        np.testing.assert_allclose(arr, np.zeros_like(arr), atol=1e-6)


def test_bert_encoder_flash_matches_dense():
    """Model-level parity: the same BERT weights under flash and dense
    attention on padded token batches."""
    from distributed_deep_learning_tpu.models.transformer import BertEncoder

    tokens = jax.random.randint(jax.random.key(11), (2, 32), 0, 64)
    tokens = tokens.at[0, 24:].set(0)  # padding tail
    dense = BertEncoder(vocab_size=64, num_layers=2, d_model=32, num_heads=2,
                        mlp_dim=64, dropout_rate=0.0)
    flash = BertEncoder(vocab_size=64, num_layers=2, d_model=32, num_heads=2,
                        mlp_dim=64, dropout_rate=0.0,
                        attention_fn=make_attention_fn(block_q=8, block_k=8))
    params = dense.init(jax.random.key(0), tokens)
    np.testing.assert_allclose(np.asarray(flash.apply(params, tokens)),
                               np.asarray(dense.apply(params, tokens)),
                               rtol=2e-4, atol=2e-4)


def test_adapter_dense_mask_falls_back_to_dense_path():
    """VERDICT r4 item 9: a pre-built dense mask routes the call to the
    dense path (with a one-time warning) instead of raising, so any
    MultiHeadAttention(mask=...) config trains under --attention auto."""
    import warnings

    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)
    from distributed_deep_learning_tpu.ops import attention_pallas

    q, k, v = _qkv(T=16, seed=41)
    mask = jax.random.bernoulli(jax.random.key(42), 0.7, (1, 1, 16, 16))
    mask = mask | jnp.eye(16, dtype=bool)[None, None]  # no all-masked rows
    fn = make_attention_fn(block_q=8, block_k=8)
    attention_pallas._warn_dense_mask_fallback.cache_clear()
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        got = fn(q, k, v, mask=mask)
        fn(q, k, v, mask=mask)  # second call: warning already issued
    assert len([w for w in seen if "dense" in str(w.message)]) == 1
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dot_product_attention(q, k, v, mask=mask)),
        rtol=1e-5, atol=1e-5)
    # and gradients flow through the fallback
    g = jax.grad(lambda q: jnp.sum(fn(q, k, v, mask=mask) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()
    # a maker-baked window survives the fallback (code-review finding)
    fn_w = make_attention_fn(block_q=8, block_k=8, window=5)
    got_w = fn_w(q, k, v, mask=mask, causal=True)
    expected_w = dot_product_attention(q, k, v, mask=mask, causal=True,
                                       window=5)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(expected_w),
                               rtol=1e-5, atol=1e-5)
    # window without causal is rejected on the fallback, kernel parity
    with pytest.raises(ValueError, match="causal"):
        fn_w(q, k, v, mask=mask)


def _gqa_qkv(B=2, T=32, H=8, Hkv=2, D=16, seed=60):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    return q, k, v


def _expand(x, group):
    return jnp.repeat(x, group, axis=2)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_native_matches_expanded_dense(causal):
    """GQA-native kernel (unexpanded Hkv-headed K/V, head mapping via
    block index maps) == dense attention over head-EXPANDED K/V."""
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    q, k, v = _gqa_qkv()
    group = q.shape[2] // k.shape[2]
    expected = dot_product_attention(q, _expand(k, group), _expand(v, group),
                                     causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_native_gradients_match_expanded(causal):
    """dq/dk/dv parity vs the expanded dense path — dk/dv come back in
    the Hkv shape (the group-sum over shared heads)."""
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    q, k, v = _gqa_qkv(T=16, seed=61)
    group = q.shape[2] // k.shape[2]

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=8,
                                       block_k=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(
            q, _expand(k, group), _expand(v, group), causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == k.shape and gf[2].shape == v.shape
    for a, b in zip(gf, gd):  # autodiff of jnp.repeat group-sums dk/dv
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_gqa_native_with_padding_and_window():
    """GQA composes with key_valid and the sliding window in-kernel."""
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    q, k, v = _gqa_qkv(T=32, seed=62)
    group = q.shape[2] // k.shape[2]
    valid = jnp.arange(32)[None, :] < jnp.array([[24], [32]])
    # window 12 keeps every query's (causal ∩ window ∩ valid) key set
    # non-empty — empty-set rows differ between kernel and dense by
    # documented convention (uniform-over-visited vs uniform-over-all)
    expected = dot_product_attention(q, _expand(k, group), _expand(v, group),
                                     causal=True, window=12, key_valid=valid)
    got = flash_attention(q, k, v, causal=True, window=12, key_valid=valid,
                          block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_gqa_layer_skips_expansion_under_flash():
    """MultiHeadAttention(num_kv_heads=2, flash adapter) matches the dense
    layer (which expands) — the GQA-native path end to end through the
    layer, no expanded K/V materialised."""
    from distributed_deep_learning_tpu.models.transformer import (
        MultiHeadAttention)

    x = jax.random.normal(jax.random.key(63), (2, 32, 64))
    dense = MultiHeadAttention(num_heads=8, num_kv_heads=2)
    flash = MultiHeadAttention(num_heads=8, num_kv_heads=2,
                               attention_fn=make_attention_fn(block_q=8,
                                                              block_k=8))
    params = dense.init(jax.random.key(0), x, x, causal=True)
    got = flash.apply(params, x, x, causal=True)
    expected = dense.apply(params, x, x, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=1e-5)


def test_gqa_indivisible_heads_rejected():
    q, k, v = _gqa_qkv(H=8, Hkv=3)
    with pytest.raises(ValueError, match="KV"):
        flash_attention(q, k, v, block_q=8, block_k=8)


@pytest.mark.parametrize("backend,blocks", [("tpu", (512, 512)),
                                            ("cpu", (128, 128))])
def test_flash_default_blocks_follow_the_backend(monkeypatch, backend,
                                                 blocks):
    """The default (block_q, block_k) is the module's own constant, chosen
    by the backend alone: read off the call the kernel entry receives."""
    from distributed_deep_learning_tpu.ops import attention_pallas as ap

    seen = []

    def spy(q, k, v, kvalid, sm_scale, causal, block_q, block_k, *layout):
        seen.append((block_q, block_k))
        return q

    monkeypatch.setattr(ap, "_flash_rows", spy)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q, k, v = _qkv(T=32, seed=50)
    flash_attention(q, k, v, causal=True, interpret=True)
    flash_attention(q, k, v, causal=True, interpret=True, block_k=64)
    assert seen == [blocks, (blocks[0], 64)]


def test_flash_oversized_default_blocks_clamp_to_the_sequence(monkeypatch):
    """_fit_block clamps the TPU default (512) to a 32-token sequence, so
    the call still works and matches 8 x 8 blocks."""
    q, k, v = _qkv(T=32, seed=50)
    expected = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_transformer_layer_with_flash_attention():
    from distributed_deep_learning_tpu.models.transformer import (
        TransformerLayer)

    x = jax.random.normal(jax.random.key(5), (2, 32, 64))
    dense_layer = TransformerLayer(num_heads=4, mlp_dim=128, causal=False)
    flash_layer = TransformerLayer(
        num_heads=4, mlp_dim=128,
        attention_fn=make_attention_fn(block_q=8, block_k=8))
    params = dense_layer.init(jax.random.key(0), x)
    np.testing.assert_allclose(
        np.asarray(flash_layer.apply(params, x)),
        np.asarray(dense_layer.apply(params, x)), rtol=1e-4, atol=1e-5)


def test_causal_cross_length_backward():
    """Backward with causal=True and Tq != Tk must use the rectangular
    absolute-position mask (review regression: tril was square)."""
    B, H, D = 2, 2, 16
    ks = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(ks[0], (B, 8, H, D))
    k = jax.random.normal(ks[1], (B, 32, H, D))
    v = jax.random.normal(ks[2], (B, 32, H, D))
    g = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, causal=True, block_q=8, block_k=8) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()
    # parity with the dense structured path
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    gd = jax.grad(lambda q: jnp.sum(
        dot_product_attention(q, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gd), rtol=1e-4,
                               atol=1e-5)


def _band_mask(T, window):
    q = np.arange(T)[:, None]
    k = np.arange(T)[None, :]
    return jnp.asarray((q >= k) & (q - k < window))[None, None]


def test_sliding_window_matches_dense_band():
    """window=W == dense attention under an explicit causal band mask."""
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    q, k, v = _qkv(T=32, seed=20)
    for W in (1, 5, 8, 32, 100):
        got = flash_attention(q, k, v, causal=True, window=W,
                              block_q=8, block_k=8)
        expected = dot_product_attention(q, k, v, mask=_band_mask(32, W))
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"window={W}")


def test_sliding_window_gradients_match_dense_band():
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    q, k, v = _qkv(T=24, seed=21)
    W = 7

    g_flash = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=W, block_q=8, block_k=8) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
        q, k, v, mask=_band_mask(24, W)) ** 2), argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-5)


def test_sliding_window_requires_causal():
    q, k, v = _qkv(T=16, seed=22)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=4)


def test_sliding_window_with_padding():
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    q, k, v = _qkv(T=16, seed=23)
    valid = jnp.arange(16)[None, :] < jnp.array([[12], [16]])
    got = flash_attention(q, k, v, causal=True, window=5, key_valid=valid,
                          block_q=8, block_k=8)
    expected = dot_product_attention(q, k, v, key_valid=valid,
                                     mask=_band_mask(16, 5))
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_adapter_forwards_window_through_layer():
    """ADVICE r3 high: MultiHeadAttention(window=W) hands window= to the
    adapter at call time; the flash adapter must accept and forward it to
    the kernel (previously a fixed signature -> TypeError at trace time on
    the default TPU pairing)."""
    from distributed_deep_learning_tpu.models.transformer import (
        MultiHeadAttention)

    x = jax.random.normal(jax.random.key(13), (2, 32, 64))
    dense = MultiHeadAttention(num_heads=4, window=4)
    flash = MultiHeadAttention(num_heads=4, window=4,
                               attention_fn=make_attention_fn(block_q=8,
                                                              block_k=8))
    params = dense.init(jax.random.key(0), x, x, causal=True)
    np.testing.assert_allclose(
        np.asarray(flash.apply(params, x, x, causal=True)),
        np.asarray(dense.apply(params, x, x, causal=True)),
        rtol=2e-4, atol=1e-5)


def test_adapter_call_time_window_wins_over_maker():
    """A call-time window must override one baked into make_attention_fn."""
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    ks = jax.random.split(jax.random.key(14), 3)
    q, k, v = (jax.random.normal(kk, (2, 32, 4, 16)) for kk in ks)
    fn = make_attention_fn(block_q=8, block_k=8, window=16)
    got = fn(q, k, v, causal=True, window=4)
    expected = dot_product_attention(q, k, v, causal=True, window=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("padded", [False, True], ids=["dense", "key_valid"])
def test_adapter_runs_kernel_per_shard_under_a_step_mesh(padded):
    """The chip's compiler refuses to partition a Mosaic kernel, so under a
    step traced with a mesh whose batch / head axes are split the adapter
    must hand the kernel one shard at a time (shard_map: batch over
    data x fsdp, heads over model) — same values and gradients as dense,
    GQA included.  Without an ambient mesh the call stays direct."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    mesh = build_mesh({"data": 2, "fsdp": 2, "model": 2})
    q, _, _ = _qkv(B=4, T=32, H=4, D=16, seed=50)
    _, k, v = _qkv(B=4, T=32, H=2, D=16, seed=51)       # 2 KV heads: GQA
    valid = jnp.arange(32)[None, :] < jnp.array([20, 32, 7, 32])[:, None] \
        if padded else None
    fn = make_attention_fn(block_q=16, block_k=16)

    def loss(attend, q, k, v):
        return jnp.sum(attend(q, k, v, causal=True, key_valid=valid) ** 2)

    def step(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.value_and_grad(lambda *a: loss(fn, *a),
                                      argnums=(0, 1, 2))(q, k, v)

    sh = NamedSharding(mesh, P(("data", "fsdp"), None, "model", None))
    got = jax.jit(step, in_shardings=(sh, sh, sh))(q, k, v)
    dense = lambda q, k, v, **kw: dot_product_attention(  # noqa: E731
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), **kw)
    want = jax.value_and_grad(lambda *a: loss(dense, *a),
                              argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)
    assert "shard_map" in str(jax.make_jaxpr(step)(q, k, v))
    assert "shard_map" not in str(jax.make_jaxpr(
        lambda *a: loss(fn, *a))(q, k, v))


# --- the lane-blocked layout: q, k, v read as the projections write them ---

#: (H, Hkv, D) -> what the call must say of itself: lanes a block, heads a
#: block, transposed.  One entry a shape class of `_tiling`.
LAYOUTS = {
    "two-heads-a-block": ((16, 16, 64), (128, 2, False)),
    "odd-heads-boundary-block": ((25, 25, 64), (128, 2, False)),
    "four-heads-a-block": ((4, 4, 32), (128, 4, False)),
    "under-one-block": ((2, 2, 32), (64, 2, False)),
    "head-a-block": ((8, 8, 128), (128, 1, False)),
    "head-a-block-gqa": ((8, 2, 128), (128, 1, False)),
    "wide-head": ((2, 2, 256), (256, 1, False)),
    "narrow-gqa-transposed": ((8, 2, 64), (64, 1, True)),
    "no-lane-multiple-transposed": ((2, 2, 96), (96, 1, True)),
}


def _layout_case(name, T=32, Tk=None, B=2, seed=70):
    (H, Hkv, D), said = LAYOUTS[name]
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, Tk or T, Hkv, D))
    v = jax.random.normal(ks[2], (B, Tk or T, Hkv, D))
    return q, k, v, H // Hkv, said


def _dense(q, k, v, group, **kw):
    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)

    return dot_product_attention(q, _expand(k, group), _expand(v, group),
                                 **kw)


def _flash_notes():
    from distributed_deep_learning_tpu import obs

    return [text for event, _, text in obs.compile_log.notes()
            if event == "flash_layout"]


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("name", LAYOUTS)
def test_layouts_match_dense_and_say_which_path_they_took(name, causal):
    """Outputs AND the gradients of q, k and v against the dense path, for
    every shape class the tiling rule knows; the call's ``flash_layout``
    note says which path it took (read once a class, on the causal case)."""
    from distributed_deep_learning_tpu import obs

    q, k, v, group, said = _layout_case(name)

    def loss(attend, q, k, v):
        out = attend(q, k, v)
        return jnp.sum(out ** 2), out

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=causal, block_q=16, block_k=8)
    dense = lambda q, k, v: _dense(q, k, v, group, causal=causal)  # noqa: E731
    obs.compile_log.mark("test")
    (_, got), g_flash = jax.value_and_grad(
        lambda *a: loss(flash, *a), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    if causal:
        lanes, heads, transposed = said
        assert _flash_notes() == [
            f"calls=1 lanes_a_block={lanes} heads_a_block={heads} "
            f"transposed={int(transposed)}"]
    (_, want), g_dense = jax.value_and_grad(
        lambda *a: loss(dense, *a), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for gf, gd in zip(g_flash, g_dense):
        assert gf.shape == gd.shape
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=2e-5)


#: what else a call may carry, each on the boundary-block shape (25 heads of
#: 64) and on a grouped head-a-block one
FEATURES = {
    "key_valid-padded-row": dict(valid=[20, 0], T=32),
    "key_valid-causal": dict(valid=[24, 16], T=32, causal=True),
    "window": dict(window=5, causal=True, T=32),
    "cross-lengths": dict(T=8, Tk=32),
    "cross-lengths-causal": dict(T=8, Tk=32, causal=True),
    "no-128-divisor": dict(T=24, causal=True, blocks=(16, 16)),
}


@pytest.mark.parametrize("feature", FEATURES)
@pytest.mark.parametrize("name", ["odd-heads-boundary-block",
                                  "head-a-block-gqa"])
def test_layouts_carry_masks_windows_and_lengths(name, feature):
    """``key_valid`` with a fully padded row (its outputs are the kernel's
    own convention, so only the other row is compared, and its gradients
    are zero), ``window``, ``Tq != Tk`` and a ``T`` with no 128-multiple
    divisor (``_fit_block``), outputs and gradients, lane-blocked."""
    cfg = FEATURES[feature]
    T = cfg["T"]
    q, k, v, group, _ = _layout_case(name, T=T, Tk=cfg.get("Tk"), seed=71)
    valid = None
    if "valid" in cfg:
        valid = jnp.arange(T)[None, :] < jnp.array(cfg["valid"])[:, None]
    live = np.array([n > 0 for n in cfg.get("valid", [1, 1])])
    bq, bk = cfg.get("blocks", (8, 8))
    kw = dict(causal=cfg.get("causal", False), window=cfg.get("window"),
              key_valid=valid)

    def loss(attend, q, k, v):
        out = attend(q, k, v)
        return jnp.sum(out[live] ** 2), out

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, block_q=bq, block_k=bk, **kw)
    dense = lambda q, k, v: _dense(q, k, v, group, **kw)  # noqa: E731
    (_, got), g_flash = jax.value_and_grad(
        lambda *a: loss(flash, *a), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), g_dense = jax.value_and_grad(
        lambda *a: loss(dense, *a), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)
    for gf, gd in zip(g_flash, g_dense):
        assert gf.shape == gd.shape
        np.testing.assert_allclose(np.asarray(gf)[live],
                                   np.asarray(gd)[live],
                                   rtol=1e-4, atol=2e-5)
        assert not np.asarray(gf)[~live].any()


def test_fully_padded_row_has_zero_gradients_lane_blocked():
    """The fully-masked-row clamp, two heads a block: with every key of a
    row masked its q, k and v take exactly zero gradient, no NaN."""
    q, k, v, _, _ = _layout_case("two-heads-a-block", T=16, seed=72)
    valid = jnp.zeros((2, 16), bool)
    g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, key_valid=valid, block_q=8, block_k=8) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for arr in g:
        np.testing.assert_allclose(np.asarray(arr), 0.0, atol=1e-6)


def test_merged_projections_are_the_layer_the_dense_path_computes():
    """Under the flash adapter the layer projects on the merged ``H·D``
    (``MergedHeadsDense``): the same parameter tree as ``nn.DenseGeneral``
    gives, drawn the same way from the same key, and the same values and
    gradients as the dense layer, bias included."""
    from distributed_deep_learning_tpu.models.transformer import (
        MultiHeadAttention)

    x = jax.random.normal(jax.random.key(73), (2, 32, 128))
    dense = MultiHeadAttention(num_heads=4)
    flash = MultiHeadAttention(
        num_heads=4, attention_fn=make_attention_fn(block_q=8, block_k=8))
    params = dense.init(jax.random.key(0), x, x, causal=True)
    drawn = flash.init(jax.random.key(0), x, x, causal=True)
    assert jax.tree.structure(params) == jax.tree.structure(drawn)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(drawn)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    params = jax.tree.map(            # biases that are not zero
        lambda p: p + 0.1 * jax.random.normal(jax.random.key(1), p.shape),
        params)

    def loss(layer, params):
        return jnp.sum(layer.apply(params, x, x, causal=True) ** 2)

    got, g_flash = jax.value_and_grad(lambda p: loss(flash, p))(params)
    want, g_dense = jax.value_and_grad(lambda p: loss(dense, p))(params)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    scale = max(jnp.abs(g).max() for g in jax.tree.leaves(g_dense))
    for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_dense)):
        # against the tree's largest gradient: k's bias moves no score's
        # softmax, so its own gradient is rounding noise around zero
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=1e-5 * float(scale))


def test_a_traced_step_notes_its_flash_calls_once():
    """Under the compile log's ``notes_for`` (what the step builders trace
    under) the program's calls are counted into ONE note, the transposed
    among them."""
    from distributed_deep_learning_tpu import obs

    q, k, v, _, _ = _layout_case("two-heads-a-block", T=16)
    qg, kg, vg, _, _ = _layout_case("narrow-gqa-transposed", T=16)
    obs.compile_log.mark("test")
    with obs.compile_log.notes_for("jit(step)"):
        jax.make_jaxpr(lambda: (flash_attention(q, k, v),
                                flash_attention(q, k, v, causal=True),
                                flash_attention(qg, kg, vg)))()
    with obs.compile_log.notes_for("jit(no_flash)"):
        pass
    assert [n for n in obs.compile_log.notes() if n[0] == "flash_layout"] \
        == [("flash_layout", "jit(step)",
             "calls=3 lanes_a_block=128 heads_a_block=2 transposed=1")]
