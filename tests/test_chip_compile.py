"""The main path's Pallas kernels, compiled for a TPU v5e that is described
and not attached (on-chip-measurement guide, section 2.3).

Interpret mode cannot see what the chip's compiler refuses — a block not
aligned to the tiling, more VMEM than a kernel may use — so each case here
AOT-compiles the real kernel (``interpret=False``) for the ``v5e:2x2``
topology at the head shapes ``chip_smoke.py`` runs (GPT-2 small: 12 heads of
64, T=1024, 8 slots of 64 blocks of 16) and asserts a ``tpu_custom_call``
came out.  Nothing runs, so this says nothing about values or time: those
are the smoke's job on the chip.  About 2 s a case; the file sorts before
the tier-1 timeout cut.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep compiler logs out of /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_deep_learning_tpu.ops.attention_pallas import flash_attention
from distributed_deep_learning_tpu.ops.paged_decode_pallas import (
    paged_flash_decode)

B, T, H, D = 8, 1024, 12, 64          # the smoke's train batch
SLOTS, BPS = 8, 64                    # the smoke's paged engine


@pytest.fixture(scope="module")
def chip():
    """One described v5e device, with the persistent compilation cache off
    around the module: a compile for a described chip is written to the
    cache but cannot be read back without one, and the next run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


FLASH_CASES = {
    # blocks: 128x128 is the kernel's own default, 512x512 what
    # bench_baseline.json records and a TPU run therefore resolves to
    "default-128": dict(blocks=(128, 128)),
    "recorded-512": dict(blocks=(512, 512)),
    "gqa": dict(blocks=(128, 128), kv_heads=4),
    "window": dict(blocks=(128, 128), window=256),
    "key_valid": dict(blocks=(128, 128), key_valid=True),
    # the CLI's synthetic gpt set (T=64, 2 heads of 32) and a length whose
    # best divisor is 96: key blocks narrower than a 128-lane tile, where
    # the padding mask was once sliced along lanes and refused by Mosaic
    "key_valid-t64": dict(blocks=(128, 128), key_valid=True,
                          shape=(8, 64, 2, 32)),
    "key_valid-t192": dict(blocks=(128, 128), key_valid=True,
                           shape=(2, 192, 2, 64)),
}


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_compiles_for_v5e(chip, case):
    """Forward and backward in one program: the forward kernel a plain call
    would run, then the dq and the dk/dv kernels."""
    cfg = FLASH_CASES[case]
    bq, bk = cfg["blocks"]
    b, t, h, d = cfg.get("shape", (B, T, H, D))
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, t, cfg.get("kv_heads", h), d),
                              jnp.bfloat16, sharding=chip)
    valid = jax.ShapeDtypeStruct((b, t), jnp.bool_, sharding=chip)

    def loss(q, k, v, valid):
        out = flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk,
            window=cfg.get("window"), interpret=False,
            key_valid=valid if cfg.get("key_valid") else None)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, valid)
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_flash_decode_compiles_for_v5e(chip, kv_dtype, block):
    n_blocks = 2 * SLOTS * BPS + 1
    q = jax.ShapeDtypeStruct((SLOTS, H, 1, D), jnp.bfloat16, sharding=chip)
    pool = jax.ShapeDtypeStruct((n_blocks, block, H, D), jnp.dtype(kv_dtype),
                                sharding=chip)
    scale = jax.ShapeDtypeStruct((n_blocks, block, H, 1), jnp.float32,
                                 sharding=chip)
    tables = jax.ShapeDtypeStruct((SLOTS, BPS), jnp.int32, sharding=chip)
    lens = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=chip)

    def decode(q, k, v, ks, vs, tables, lens):
        scales = dict(k_scale=ks, v_scale=vs) if kv_dtype == "int8" else {}
        return paged_flash_decode(q, k, v, tables, lens, interpret=False,
                                  **scales)

    text = _compiled_text(decode, q, pool, pool, scale, scale, tables, lens)
    assert "tpu_custom_call" in text
