"""The broken-path and control tests (`test_broken_path.py`,
`test_control.py`) for a tiny latent-attention configuration with
bias-corrected sigmoid routing (``tiny_glm/``: GLM-4.7-Flash's keys at a
width a test run can hold): a program that drops the correction bias from
the router's choice, or multiplies by weights of a lower precision than the
configuration states, comes out with ``correct`` false; the plain
reference one precision step down, put in the program's place, fails a
limit while the program holds them all; so does the reference with the
cached rows ALONE a step down, and a decode program whose latent attention
skips a block or slices its values a lane off comes out not correct: the
comparison sees the attention path, not the token's own MLP path alone."""

import os
import time

import pytest

TINY_GLM = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tiny_glm")
NAME = "tiny-glm-serve"


@pytest.fixture
def cell():
    from benchmark import harness

    bench = harness.load_json(TINY_GLM, "BENCHMARK.json")
    return harness.Cell(NAME, root=TINY_GLM, bench=bench)


def _run(cell, seed=5):
    from benchmark import cellrun

    return cellrun.run_cell(NAME, seed, 1.0, False, allow_cpu=True,
                            cell=cell)


def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"] and out["attempted"] > 0 and not out["failed"]


def test_choice_that_ignores_the_correction_bias(cell, monkeypatch):
    from distributed_deep_learning_tpu.models import moe

    real = moe.route_top_k

    def unbiased(logits, top_k, norm_topk=True, routed_scale=1.0,
                 score="softmax", bias=None):
        return real(logits, top_k, norm_topk, routed_scale, score, None)

    monkeypatch.setattr(moe, "route_top_k", unbiased)
    assert not _run(cell)["correct"]


def test_weights_in_a_lower_precision_than_stated(cell, monkeypatch):
    """Every matrix the serving programs multiply by, rounded to fp8 e4m3
    (one scale a tensor) inside the programs."""
    import jax
    import jax.numpy as jnp

    from distributed_deep_learning_tpu.serve.engine import PagedEngine

    def fp8(x):
        if x.ndim < 2:
            return x
        scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / 448.0
        return ((x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * scale).astype(x.dtype)

    monkeypatch.setattr(PagedEngine, "_wp",
                        lambda self, params: jax.tree.map(fp8, params))
    assert not _run(cell)["correct"]


def _newest_block_unseen(real, q, pool, tables, lens, new, **kw):
    """The work list one block short: a slot's newest cached block is not
    attended (nor is the token's own row put where it belongs)."""
    import jax.numpy as jnp

    return real(q, pool, tables, jnp.maximum(lens - pool.shape[1], 0), new,
                **kw)


def _values_a_lane_off(real, *args, **kw):
    """The values sliced one column off where the up-projection expects
    them (a wrong ``v_width`` / ``W_UV`` alignment)."""
    import jax.numpy as jnp

    return jnp.roll(real(*args, **kw), 1, axis=-1)


@pytest.mark.parametrize("fault", [_newest_block_unseen, _values_a_lane_off])
def test_a_fault_in_the_decode_programs_latent_attention(cell, monkeypatch,
                                                         fault):
    """Off a TPU the decode program's absorbed attention is
    `paged_latent_reference` (on one, the kernel that must reproduce it):
    the fault is planted there, so only tokens after the first of a
    request see it, and the chunk program's expanded path stays sound."""
    import functools

    from distributed_deep_learning_tpu.ops import paged_decode_pallas as pdp

    monkeypatch.setattr(pdp, "paged_latent_reference", functools.partial(
        fault, pdp.paged_latent_reference))
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("control", ["fp8", "fp8_cache"])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_control_fails_and_program_holds(cell, seed, control):
    """`fp8`: every weight product's operands a step down; `fp8_cache`:
    the cached rows alone."""
    from benchmark import harness

    devices = harness.claim_devices(cell.chips, allow_cpu=True)
    sound, control = harness.runner_for(cell).readings(
        cell, seed, 1.0, devices, harness.SetupClock(time.perf_counter()),
        control)
    assert all(c["value"] <= c["limit"] for c in sound), sound
    assert any(c["value"] > c["limit"] for c in control), control
