"""KV-cached decode: per-step cached logits match the full forward, and
generate() reproduces uncached greedy decoding exactly."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_deep_learning_tpu.models.transformer import (CausalLM,
                                                              generate)

MODEL = dict(vocab_size=61, num_layers=2, d_model=32, num_heads=4,
             mlp_dim=64, max_len=32)


def _model(**kw):
    return CausalLM(**{**MODEL, **kw})


def test_cached_decode_matches_full_forward():
    """Feeding tokens one at a time through the cache reproduces the
    full-sequence logits at every position."""
    model = _model(with_logits=True)
    toks = jax.random.randint(jax.random.key(0), (2, 10), 1, 61)
    params = model.init(jax.random.key(1), toks)["params"]
    full = model.apply({"params": params}, toks)          # (2, 10, V)

    lm = model.clone(decode=True)
    cache = lm.init(jax.random.key(0), toks)["cache"]
    for t in range(toks.shape[1]):
        step_logits, upd = lm.apply({"params": params, "cache": cache},
                                    toks[:, t:t + 1], mutable=["cache"])
        cache = upd["cache"]
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=2e-4, atol=2e-4)


def test_generate_matches_uncached_greedy():
    """generate() == the O(T^2) recompute loop, token for token."""
    model = _model(with_logits=True)
    prompt = jax.random.randint(jax.random.key(2), (2, 4), 1, 61)
    params = model.init(jax.random.key(3), prompt)["params"]

    got = generate(model, params, prompt, max_new_tokens=6)

    seq = prompt
    for _ in range(6):
        logits = model.apply({"params": params}, seq)
        # generate() never emits pad id 0 — mirror that in the reference
        nxt = jnp.argmax(logits[:, -1].at[:, 0].set(-jnp.inf),
                         axis=-1).astype(seq.dtype)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(seq[:, 4:]))


def test_generate_sampling_shape_and_range():
    model = _model(with_logits=True)
    prompt = jax.random.randint(jax.random.key(4), (3, 2), 1, 61)
    params = model.init(jax.random.key(5), prompt)["params"]
    out = generate(model, params, prompt, max_new_tokens=5,
                   temperature=1.0, rng=jax.random.key(6))
    assert out.shape == (3, 5)
    assert ((np.asarray(out) >= 0) & (np.asarray(out) < 61)).all()


def test_generate_respects_max_len():
    import pytest

    model = _model(with_logits=True)
    prompt = jnp.ones((1, 30), jnp.int32)
    params = model.init(jax.random.key(7), prompt)["params"]
    with pytest.raises(ValueError, match="max_len"):
        generate(model, params, prompt, max_new_tokens=10)


def test_cached_decode_with_padding_matches_full_forward():
    """Pad tokens (id 0) inside the sequence must be masked in cached
    decode exactly as the full forward masks them."""
    model = _model(with_logits=True)
    toks = jax.random.randint(jax.random.key(8), (2, 12), 1, 61)
    toks = toks.at[0, 5:8].set(0)  # interior padding on row 0
    params = model.init(jax.random.key(9), toks)["params"]
    full = model.apply({"params": params}, toks)

    lm = model.clone(decode=True)
    shapes = jax.eval_shape(lm.init, jax.random.key(0), toks)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["cache"])
    for t in range(toks.shape[1]):
        step_logits, upd = lm.apply({"params": params, "cache": cache},
                                    toks[:, t:t + 1], mutable=["cache"])
        cache = upd["cache"]
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=2e-4, atol=2e-4)


def test_multi_token_prefill_matches_full_forward():
    """A single multi-token cached call (prompt prefill) must produce the
    same logits as the full forward — the in-chunk causal prefix mask."""
    model = _model(with_logits=True)
    toks = jax.random.randint(jax.random.key(10), (2, 9), 1, 61)
    params = model.init(jax.random.key(11), toks)["params"]
    full = model.apply({"params": params}, toks)

    lm = model.clone(decode=True)
    shapes = jax.eval_shape(lm.init, jax.random.key(0), toks)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["cache"])
    pre, upd = lm.apply({"params": params, "cache": cache}, toks[:, :6],
                        mutable=["cache"])
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :6]),
                               rtol=2e-4, atol=2e-4)
    # continue token-by-token from the prefilled cache
    cache = upd["cache"]
    for t in range(6, 9):
        step_logits, upd = lm.apply({"params": params, "cache": cache},
                                    toks[:, t:t + 1], mutable=["cache"])
        cache = upd["cache"]
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=2e-4, atol=2e-4)


def test_rope_causal_lm_trains_and_is_causal():
    """pos_embedding='rope': no learned position table, causality holds."""
    model = _model(with_logits=True, pos_embedding="rope")
    toks = jax.random.randint(jax.random.key(12), (2, 16), 1, 61)
    params = model.init(jax.random.key(13), toks)["params"]
    assert "pos" not in params["embed"], "rope must not create a pos table"
    t2 = toks.at[:, 10:].set(1 + (toks[:, 10:] % 60))
    h1 = model.apply({"params": params}, toks)
    h2 = model.apply({"params": params}, t2)
    np.testing.assert_allclose(np.asarray(h1[:, :10]),
                               np.asarray(h2[:, :10]), rtol=2e-4, atol=2e-4)


def test_rope_cached_decode_matches_full_forward():
    """RoPE + KV cache: cached keys carry their absolute rotation, so
    per-step decode logits must equal the full forward."""
    model = _model(with_logits=True, pos_embedding="rope")
    toks = jax.random.randint(jax.random.key(14), (2, 10), 1, 61)
    params = model.init(jax.random.key(15), toks)["params"]
    full = model.apply({"params": params}, toks)

    lm = model.clone(decode=True)
    shapes = jax.eval_shape(lm.init, jax.random.key(0), toks)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["cache"])
    for t in range(toks.shape[1]):
        step_logits, upd = lm.apply({"params": params, "cache": cache},
                                    toks[:, t:t + 1], mutable=["cache"])
        cache = upd["cache"]
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=3e-4, atol=3e-4)


def test_rope_generate_runs():
    model = _model(with_logits=True, pos_embedding="rope")
    prompt = jax.random.randint(jax.random.key(16), (2, 4), 1, 61)
    params = model.init(jax.random.key(17), prompt)["params"]
    out = generate(model, params, prompt, max_new_tokens=5)
    assert out.shape == (2, 5)


def test_windowed_cached_decode_matches_full_forward():
    """Train/inference parity with --window: the KV-cache decode applies
    the same causal band as the full forward (review regression — decode
    previously attended the whole prefix)."""
    model = _model(with_logits=True, attention_window=4)
    toks = jax.random.randint(jax.random.key(18), (2, 12), 1, 61)
    params = model.init(jax.random.key(19), toks)["params"]
    full = model.apply({"params": params}, toks)

    lm = model.clone(decode=True)
    shapes = jax.eval_shape(lm.init, jax.random.key(0), toks)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["cache"])
    for t in range(toks.shape[1]):
        step_logits, upd = lm.apply({"params": params, "cache": cache},
                                    toks[:, t:t + 1], mutable=["cache"])
        cache = upd["cache"]
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=3e-4, atol=3e-4)


def test_gqa_matches_mha_when_equal_heads():
    """num_kv_heads == num_heads must be numerically identical to MHA
    (same parameter shapes, same math)."""
    m1 = _model(with_logits=True)
    m2 = _model(with_logits=True, num_kv_heads=4)  # == num_heads
    toks = jax.random.randint(jax.random.key(20), (2, 8), 1, 61)
    p1 = m1.init(jax.random.key(21), toks)["params"]
    np.testing.assert_allclose(
        np.asarray(m1.apply({"params": p1}, toks)),
        np.asarray(m2.apply({"params": p1}, toks)), rtol=1e-6)


def test_gqa_cache_is_small_and_decode_matches_full():
    """GQA: the KV cache stores num_kv_heads (the memory win), and cached
    decode still matches the full forward exactly."""
    model = _model(with_logits=True, num_kv_heads=2)  # 4 q heads, 2 kv
    toks = jax.random.randint(jax.random.key(22), (2, 10), 1, 61)
    params = model.init(jax.random.key(23), toks)["params"]
    assert params["layer_0"]["self_attn"]["k"]["kernel"].shape[-2] == 2
    full = model.apply({"params": params}, toks)

    lm = model.clone(decode=True)
    shapes = jax.eval_shape(lm.init, jax.random.key(0), toks)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["cache"])
    ck = cache["layer_0"]["self_attn"]["cached_key"]
    assert ck.shape[-2] == 2, f"cache stores kv heads, got {ck.shape}"
    for t in range(toks.shape[1]):
        step_logits, upd = lm.apply({"params": params, "cache": cache},
                                    toks[:, t:t + 1], mutable=["cache"])
        cache = upd["cache"]
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=3e-4, atol=3e-4)


def test_gqa_indivisible_heads_rejected():
    import pytest

    model = _model(with_logits=True, num_kv_heads=3)  # 4 % 3 != 0
    toks = jnp.ones((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="divide"):
        model.init(jax.random.key(0), toks)


def test_top_k_sampling():
    """top_k=1 with temperature reproduces greedy; top_k restricts the
    sampled support; top_k < 1 is rejected."""
    import pytest

    model = _model(with_logits=True)
    prompt = jax.random.randint(jax.random.key(24), (2, 4), 1, 61)
    params = model.init(jax.random.key(25), prompt)["params"]

    greedy = generate(model, params, prompt, max_new_tokens=5)
    k1 = generate(model, params, prompt, max_new_tokens=5,
                  temperature=1.0, top_k=1, rng=jax.random.key(26))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))

    out = generate(model, params, prompt, max_new_tokens=5,
                   temperature=2.0, top_k=5, rng=jax.random.key(27))
    assert out.shape == (2, 5)

    with pytest.raises(ValueError, match="top_k"):
        generate(model, params, prompt, max_new_tokens=2, top_k=0)


def test_top_p_sampling():
    """Tiny top_p reproduces greedy (only the max token survives the
    nucleus); top_p composes with temperature; bounds are validated."""
    import pytest

    model = _model(with_logits=True)
    prompt = jax.random.randint(jax.random.key(40), (2, 4), 1, 61)
    params = model.init(jax.random.key(41), prompt)["params"]

    greedy = generate(model, params, prompt, max_new_tokens=5)
    nucleus = generate(model, params, prompt, max_new_tokens=5,
                       temperature=1.0, top_p=1e-9,
                       rng=jax.random.key(42))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(nucleus))

    out = generate(model, params, prompt, max_new_tokens=5,
                   temperature=1.5, top_p=0.9, rng=jax.random.key(43))
    assert out.shape == (2, 5)
    # top_p=1.0 is a no-op relative to plain temperature sampling
    plain = generate(model, params, prompt, max_new_tokens=5,
                     temperature=1.5, rng=jax.random.key(43))
    full = generate(model, params, prompt, max_new_tokens=5,
                    temperature=1.5, top_p=1.0, rng=jax.random.key(43))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(full))

    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="top_p"):
            generate(model, params, prompt, max_new_tokens=2,
                     temperature=1.0, top_p=bad)


def test_generate_pad_free_model_can_emit_id_zero():
    """pad_id=None (imported GPT-2: id 0 is a real token) removes the
    never-emit-0 mask — id 0 must be sampleable again."""
    model = _model(with_logits=True).clone(pad_id=None)
    prompt = jax.random.randint(jax.random.key(50), (8, 4), 1, 61)
    params = model.init(jax.random.key(51), prompt)["params"]
    out = generate(model, params, prompt, max_new_tokens=24,
                   temperature=50.0, rng=jax.random.key(52))
    # near-uniform sampling over 61 ids x 192 draws: id 0 shows up
    assert (np.asarray(out) == 0).any()


def test_generate_never_emits_pad_id():
    """ADVICE r3: a generated 0 would be recorded invalid in the KV cache
    (valid = tokens != 0) and silently vanish from later attention — so
    id 0 is masked out of every pick, greedy and sampled."""
    model = _model(with_logits=True)
    prompt = jax.random.randint(jax.random.key(30), (4, 4), 1, 61)
    params = model.init(jax.random.key(31), prompt)["params"]
    for kw in ({}, {"temperature": 1.5, "rng": jax.random.key(32)},
               {"temperature": 1.0, "top_k": 3, "rng": jax.random.key(33)}):
        out = generate(model, params, prompt, max_new_tokens=8, **kw)
        assert (np.asarray(out) != 0).all(), f"emitted pad id under {kw}"


def test_gpt_generate_too_long_rejected_before_training():
    """ADVICE r3: --generate N beyond what max_len admits must fail at
    validation time, not after the expensive training run."""
    import pytest

    from distributed_deep_learning_tpu.workloads.northstar import (
        _gpt_pre_check)
    from distributed_deep_learning_tpu.utils.config import Mode

    class DS:
        features = np.zeros((4, 64), np.int32)

    class Cfg:
        generate_tokens = 56
        mode = Mode.DATA
        serve = False
    _gpt_pre_check(Cfg(), DS())  # 8 + 56 == 64: fits

    Cfg.generate_tokens = 57
    with pytest.raises(ValueError, match="--generate"):
        _gpt_pre_check(Cfg(), DS())
