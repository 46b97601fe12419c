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

The paged engine's two hot programs are held to one more thing the chip's
compiler decides: the KV pool rests in the layout the programs compute in,
so neither copies a whole pool leaf to write a few positions into it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep compiler logs out of /tmp

import re

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
def v5e():
    """The four described v5e devices, with the persistent compilation cache
    off around the module: a compile for a described chip is written to the
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
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(v5e):
    """One of them."""
    return SingleDeviceSharding(v5e[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


FLASH_CASES = {
    # blocks: the kernel's default off a TPU (128x128) and on one (512x512)
    "default-128": dict(blocks=(128, 128)),
    "tpu-default-512": dict(blocks=(512, 512)),
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
    """On pool leaves as they rest (``Hkv*D`` merged, int8 with a scale a
    position and head), the token's own row and the validity leaf beside
    them, at the default blocks a step."""
    from distributed_deep_learning_tpu.serve.quant import QuantTensor

    n_blocks = 2 * SLOTS * BPS + 1
    q = jax.ShapeDtypeStruct((SLOTS, H, D), jnp.bfloat16, sharding=chip)
    pool = jax.ShapeDtypeStruct((n_blocks, block, H * D),
                                jnp.dtype(kv_dtype), sharding=chip)
    scale = jax.ShapeDtypeStruct((n_blocks, block, H), jnp.float32,
                                 sharding=chip)
    valid = jax.ShapeDtypeStruct((n_blocks, block), jnp.bool_, sharding=chip)
    new = jax.ShapeDtypeStruct((SLOTS, H, D), jnp.bfloat16, sharding=chip)
    tables = jax.ShapeDtypeStruct((SLOTS, BPS), jnp.int32, sharding=chip)
    lens = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=chip)

    def decode(q, k, v, ks, vs, valid, k_new, v_new, tables, lens):
        if kv_dtype == "int8":
            k, v = QuantTensor(k, ks), QuantTensor(v, vs)
        return paged_flash_decode(q, k, v, tables, lens, k_new=k_new,
                                  v_new=v_new, valid_pool=valid,
                                  interpret=False)

    text = _compiled_text(decode, q, pool, pool, scale, scale, valid, new,
                          new, tables, lens)
    assert "tpu_custom_call" in text


# the serve cells' engine (benchmark/traffic/*-heavy.json) at gpt2-xl
# widths, one layer deep: the layout of a pool leaf does not depend on depth
XL = dict(vocab_size=50257, num_layers=1, d_model=1600, num_heads=25,
          mlp_dim=6400, max_len=1024, with_logits=True, dtype=jnp.bfloat16)
CELL = dict(max_slots=16, max_len=1024, kv_block_size=16, num_blocks=1280,
            prefill_chunk=128, kv_dtype="bf16", donate=True)


@pytest.fixture(scope="module")
def xl_engine(chip):
    from distributed_deep_learning_tpu.models.transformer import CausalLM
    from distributed_deep_learning_tpu.serve.engine import PagedEngine

    def on_chip(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    model = CausalLM(**XL)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.ones((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda s: on_chip(s, XL["dtype"]), params)
    engine = PagedEngine(model, params, **CELL)
    head = (params, jax.tree.map(on_chip, engine.pools))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    slots, chunk, bps = (engine.max_slots, engine.chunk,
                         engine.blocks_per_slot)
    return engine, {
        "paged_chunk": (engine._chunk_prog, head + (
            i32(chunk), i32(bps), i32(), i32(), i32(chunk), i32(chunk),
            key)),
        "paged_decode": (engine._decode, head + (
            i32(slots, bps), i32(slots), i32(slots), i32(slots),
            i32(slots), key)),
    }


def _decode_kernels(text: str) -> list:
    """The block-table attention kernel's calls in a compiled program."""
    return re.findall(r"%(paged_flash_decode[\w.]*) = [^\n]*"
                      r'custom_call_target="tpu_custom_call"', text)


@pytest.fixture
def on_tpu(monkeypatch):
    """What the program would find on the chip where it asks for the
    backend: the dispatcher of the paged decode kernel does, and a program
    lowered here for the described chip has to take the chip's branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("program", ["paged_chunk", "paged_decode"])
def test_paged_program_copies_no_whole_pool_leaf(xl_engine, on_tpu, program):
    """With the pools donated, the entry computation of the compiled
    program holds no ``copy`` of a whole K/V pool leaf: 1,281 blocks
    leading, bf16.  (A 4-D ``bf16[1281,16,25,64]`` leaf rests block-index
    minor on a TPU and cost two such copies a leaf and call.)"""
    engine, programs = xl_engine
    prog, args = programs[program]
    text = prog._jit.lower(*args).compile().as_text()
    entry = text[text.index("ENTRY"):]
    rows = engine.num_blocks + 1
    assert f"bf16[{rows},16,1600]" in entry      # the pools are in there
    copies = re.findall(rf"= (bf16\[{rows},[^ ]*) copy\(", entry)
    assert not copies, copies


def test_paged_decode_attends_the_pools_in_place(xl_engine, on_tpu):
    """The one-token decode program at gpt2-xl widths reads K and V where
    they rest: the block-table kernel is in it, nothing of the size of the
    gathered slots (16 x 1,024 positions of 25 x 64) is, its temporaries
    stay under 1 GiB (they were 4.58 GiB of gathered caches a layer deep
    program scaled to 48), and the pools still leave through the write
    they came in by."""
    engine, programs = xl_engine
    assert engine.decode_attn_paths == {"block_table": 1, "gather": 0, "latent": 0}
    prog, args = programs["paged_decode"]
    compiled = prog._jit.lower(*args).compile()
    text = compiled.as_text()
    assert len(_decode_kernels(text)) == 1
    assert not re.findall(r"bf16\[16,1024,(?:25,64|1600)\]", text)
    assert not re.findall(r"bf16\[16,64,16,1600\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    rows = engine.num_blocks + 1
    aliased = re.findall(r"input_output_alias=\{([^\n]*)\}, entry", text) \
        or re.findall(r"input_output_alias=\{([^\n]*)", text)
    assert aliased, "no input/output aliasing in the module header"
    entry = text[text.index("ENTRY"):]
    assert not re.findall(rf"= (bf16\[{rows},[^ ]*) copy\(", entry)


# the laguna cell's engine (benchmark/traffic/long-mixed.json) at the
# configuration's widths, its first two layers: a full layer with the dense
# MLP and a sliding layer with the 32 held experts, so both pool kinds and
# the grouped product are in the programs
LAGUNA_CELL = dict(max_slots=16, max_len=8192, kv_block_size=16,
                   num_blocks=640, prefill_chunk=512, donate=True)


@pytest.fixture(scope="module")
def laguna_engine(chip):
    from distributed_deep_learning_tpu.models import describe
    from distributed_deep_learning_tpu.serve.engine import PagedEngine

    def on_chip(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    desc = dict(describe.read("benchmark/configs/laguna-s-2.1-ep8.json"),
                num_hidden_layers=2)
    model = describe.causal_lm(desc, max_len=8192, with_logits=True,
                               dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.ones((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda s: on_chip(s, jnp.bfloat16), params)
    engine = PagedEngine(model, params, **LAGUNA_CELL)
    head = (params, jax.tree.map(on_chip, engine.pools))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    slots, chunk = engine.max_slots, engine.chunk
    bps, ring = engine.blocks_per_slot, engine.ring_blocks
    return engine, {
        "paged_chunk": (engine._chunk_prog, head + (
            i32(chunk), (i32(bps), i32(ring)), i32(), i32(),
            (i32(chunk), i32(chunk)), i32(chunk), key)),
        "paged_decode": (engine._decode, head + (
            (i32(slots, bps), i32(slots, ring)), i32(slots), i32(slots),
            (i32(slots), i32(slots)), i32(slots), key)),
    }


@pytest.mark.parametrize("program", ["paged_chunk", "paged_decode"])
def test_two_kind_paged_program_compiles_for_v5e(laguna_engine, on_tpu,
                                                 program):
    """Both pool kinds rest as they are computed in (``Hkv*D`` = 1,024
    minor: no whole-leaf copy of either), and each expert layer's three
    grouped products are the chip's own ragged-dot kernel, not a dense
    product over every expert.  The decode program attends the full
    layer's pool in place (the block-table kernel) and gathers the
    sliding layer's ring; the chunk program gathers both."""
    engine, programs = laguna_engine
    assert engine.ring_blocks == 65             # ceil((512 + 512) / 16) + 1
    assert engine.decode_attn_paths == {"block_table": 1, "gather": 1, "latent": 0}
    prog, args = programs[program]
    text = prog._jit.lower(*args).compile().as_text()
    entry = text[text.index("ENTRY"):]
    full, ring = engine.num_blocks + 1, 16 * 65 + 1
    assert f"bf16[{full},16,1024]" in entry and \
        f"bf16[{ring},16,1024]" in entry
    copies = re.findall(rf"= (bf16\[(?:{full}|{ring}),[^ ]*) copy\(", entry)
    assert not copies, copies
    kernels = re.findall(r"%(ragged-dot-none[\w.]*) = [^\n]*"
                         r'custom_call_target="tpu_custom_call"', text)
    assert len(kernels) == 3, kernels
    in_place = _decode_kernels(text)
    gathered = re.findall(r"bf16\[16,(?:8192|512,16),(?:8,128|1024)\]", text)
    if program == "paged_decode":
        assert in_place and not gathered, (in_place, gathered)
    else:
        assert not in_place


# the two train cells' steps (benchmark/configs/gpt2-{medium,xl}.json +
# traffic train-1024{,-fsdp}) at their real widths, batch and mesh, two
# layers deep: the blocks do not depend on depth.  argv, chips, heads
TRAIN_CELLS = {
    "gpt2m-train-1chip": (
        "-l 2 -s 1024 -b 16 --dtype bfloat16 -m sequential --lr 0.001 "
        "--schedule none", 1, 16),
    "gpt2xl-train-fsdp4": (
        "-l 2 -s 1600 -b 8 --dtype bfloat16 -m data --zero fsdp "
        "--mesh fsdp=4 --lr 0.001 --schedule none", 4, 25),
}


def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, out)
    return out


@pytest.fixture(scope="module", params=TRAIN_CELLS)
def train_flash_calls(v5e, request):
    """The flash kernel's calls in the CLI's ``gpt`` train step, traced once
    and lowered for the described chips under ``--attention auto`` with no
    ``block_q`` / ``block_k`` given: ``(rows a chip, heads, [(kernel name,
    grid, block shapes)])``.  The program asks for the backend twice on the way (the
    ``auto`` rule, the kernel's default blocks): it is told what it would
    find on the chip."""
    argv, chips, heads = TRAIN_CELLS[request.param]
    with pytest.MonkeyPatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        rows, traced = _trace_train_step(argv, v5e[:chips])
    assert traced.lower().as_text().count("tpu_custom_call") == 6
    calls = []
    for eqn in _pallas_calls(traced.jaxpr.jaxpr, []):
        assert eqn.params["interpret"] is False
        mapping = eqn.params["grid_mapping"]
        calls.append((
            eqn.params["jaxpr"].debug_info.func_name, tuple(mapping.grid),
            [tuple(getattr(d, "block_size", d) for d in m.block_shape)
             for m in mapping.block_mappings]))
    return rows // chips, heads, calls


def _trace_train_step(argv, devices):
    """``(rows, the CLI's train step traced for `devices`)``, state and
    batch as shapes with the shardings the CLI gives them."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from distributed_deep_learning_tpu.data.loader import BATCH_AXES
    from distributed_deep_learning_tpu.data.tokens import TokenArrayDataset
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.train.step import _state_sharding
    from distributed_deep_learning_tpu.utils.config import Mode, parse_args
    from distributed_deep_learning_tpu.workloads import base, get_spec

    config = parse_args(argv.split(), workload="gpt")
    assert config.attention == "auto"
    spec = get_spec("gpt")
    rows = config.batch_size
    tokens = np.zeros((rows, T + 1), np.int32)
    tokens[0, 0] = 50256
    ds = TokenArrayDataset(tokens[:, :-1], tokens[:, 1:], 50257)
    if config.mode is Mode.SEQUENTIAL:
        mesh = build_mesh({"data": 1}, devices)
    else:
        mesh = build_mesh(config.mesh_shape,
                          base.mesh_devices(config.mesh_shape, devices))
    model = spec.build_model(config, ds)
    state = jax.eval_shape(lambda: create_train_state(
        model, jax.random.key(0), spec.example_input(config, ds),
        base.build_optimizer(spec, config, 17)))
    sspec = base.derive_state_spec(spec, config, mesh, state)
    train_step, _ = base.make_train_eval_steps(
        config, mesh, spec.build_loss(config), sspec)
    sharding = _state_sharding(mesh, sspec)
    if isinstance(sharding, NamedSharding):
        sharding = jax.tree.map(lambda _: sharding, state)
    state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), state, sharding)
    x = jax.ShapeDtypeStruct((rows, T), jnp.int32, sharding=NamedSharding(
        mesh, PartitionSpec(BATCH_AXES)))
    return rows, train_step.trace(state, x, x)


@pytest.mark.parametrize("kernels", [("_fwd_kernel",),
                                     ("_dq_kernel", "_dkv_kernel")],
                         ids=["forward", "backward"])
def test_train_step_lowers_flash_at_512_blocks(train_flash_calls, kernels):
    """What cells 1 and 4 compile: heads of 64 at T = 1,024 walk two
    512-wide query (or key) blocks a head, on each chip's own rows (cell 4
    calls the kernel per shard); each kernel's q / k / v / dO operands come
    whole or 512 rows at a time, and the padding mask is blocked 512 keys
    wide.  A default that moved would show here, before any chip does."""
    rows, heads, calls = train_flash_calls
    calls = [c for c in calls if c[0] in kernels]
    assert sorted({c[0] for c in calls}) == sorted(kernels)
    assert len(calls) == 2 * len(kernels)         # one a layer
    for _, grid, blocks in calls:
        assert grid == (rows * heads, T // 512)
        rows_at_a_time = {b[1] for b in blocks if len(b) == 3}
        assert rows_at_a_time == {512, T}, blocks
        masks = [b for b in blocks if len(b) == 4]
        assert masks and all(b[-1] == 512 for b in masks), blocks


# --- cell 4's step under FSDP: the weights come to the rows ---------------

_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (?P<type>.*?) (?P<kind>all-gather|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\((?P<rest>.*)$",
    re.M)


@pytest.fixture(scope="module")
def fsdp_collectives(v5e):
    """The collectives of the two-layer ``gpt2xl-train-fsdp4`` step COMPILED
    for the four described chips, one entry a channel: ``(kind, dtype,
    dims, op_name)`` (about half a minute)."""
    argv, chips, _ = TRAIN_CELLS["gpt2xl-train-fsdp4"]
    with pytest.MonkeyPatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        _, traced = _trace_train_step(argv, v5e[:chips])
    text = traced.lower().compile().as_text()
    found = {}
    for m in _COLLECTIVE.finditer(text):
        channel = re.search(r"channel_id=(\d+)", m["rest"])
        op_name = re.search(r'op_name="([^"]*)"', m["rest"])
        dtype, dims = re.search(r"(\w+)\[([\d,]*)\]", m["type"]).groups()
        found.setdefault(
            (m["kind"], channel[1] if channel else m.start()),
            (m["kind"], dtype, tuple(int(d) for d in dims.split(",") if d),
             op_name[1] if op_name else ""))
    return list(found.values())


def _no_activation_is_resharded(request):
    """Between the embedding and the loss no ``all-to-all`` is left (the
    one pair that stays is the lookup's own: rows out of a table split on
    its features) and nothing that holds a sequence is permuted.  The
    permutes that stay are the gradients' reduce-scatter: XLA's windowed
    einsum passes a SHARD of a kernel's gradient round the ring inside
    the backward's weight-gradient product."""
    found = request.getfixturevalue("fsdp_collectives")
    stray = [c for c in found if c[0] == "all-to-all"
             and "/embed/" not in c[3]]
    assert not stray, stray
    permutes = [c for c in found if c[0] == "collective-permute"]
    grads = re.compile(r"transpose\(jvp\(CausalLM\)\)/layer_\d+/"
                       r"(self_attn/(q|k|v|out)|Dense_[01])/dot_general$")
    stray = [c for c in permutes if not grads.search(c[3]) or T in c[2]
             or c[2] not in {(400, 25, 64), (25, 64, 400), (1600, 1600)}]
    assert not stray, stray
    assert not [c for c in found if c[0] == "reduce-scatter"]


def _layer_kernels_are_gathered_in_bf16(request):
    """Each of a layer's six kernels is all-gathered whole for the forward
    product (and again for the backward, unless the scheduler still holds
    it), as the bf16 cast the model computes in; no f32 all-gather is left
    in the layer stack."""
    found = request.getfixturevalue("fsdp_collectives")
    gathers = [c for c in found if c[0] == "all-gather" and "/layer_" in c[3]]
    assert {c[1] for c in gathers} == {"bf16"}, gathers
    whole = {"q": (1600, 25, 64), "k": (1600, 25, 64), "v": (1600, 25, 64),
             "out": (25, 64, 1600), "Dense_0": (1600, 6400),
             "Dense_1": (6400, 1600)}
    for layer in range(2):
        for name, dims in whole.items():
            assert [c for c in gathers if c[2] == dims and re.search(
                rf"jvp\(CausalLM\)/layer_{layer}/(self_attn/)?{name}"
                r"/dot_general$", c[3])], (layer, name, gathers)
    assert len(gathers) > 12       # and most of them again for the backward


def _one_chip_step_is_the_same_program(request):
    """Where no batch axis is split the helper emits nothing: the
    ``gpt2m-train-1chip`` step lowers to the same text with it as with it
    stubbed to the identity."""
    from distributed_deep_learning_tpu.models import transformer

    v5e = request.getfixturevalue("v5e")
    argv, chips, _ = TRAIN_CELLS["gpt2m-train-1chip"]
    texts = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        for stubbed in (False, True):
            if stubbed:
                patch.setattr(transformer, "pin_batch", lambda x: x)
            _, traced = _trace_train_step(argv, v5e[:chips])
            texts.append(traced.lower().as_text())
    assert "sharding_constraint" not in texts[0]
    assert texts[0] == texts[1]


@pytest.mark.parametrize("held", [
    _no_activation_is_resharded, _layer_kernels_are_gathered_in_bf16,
    _one_chip_step_is_the_same_program], ids=lambda f: f.__name__.strip("_"))
def test_fsdp_step_brings_the_weights_to_the_rows(request, held):
    """What ``runtime.batch_pin`` buys cell 4, read off the program the
    chip's compiler makes of it, and what it must not cost cell 1."""
    held(request)


# --- the latent layout (GLM-4.7-Flash on the paged engine) -----------------

def _latent_kernels(text: str) -> list:
    return re.findall(r"%(paged_latent_decode[\w.]*) = [^\n]*"
                      r'custom_call_target="tpu_custom_call"', text)


@pytest.mark.parametrize("width", [640, 576])
def test_paged_latent_decode_compiles_for_v5e(chip, width):
    """The latent kernel at the published widths (20 heads, 512 + 64 values
    a row, padded to 640 lanes as the model rests it; and the bare 576, a
    leaf no wider than its row), 16 slots of 1,536 blocks of 16."""
    from distributed_deep_learning_tpu.ops.paged_decode_pallas import (
        paged_latent_decode)

    slots, bps, n_blocks = 16, 1536, 2049
    q = jax.ShapeDtypeStruct((slots, 20, width), jnp.bfloat16, sharding=chip)
    pool = jax.ShapeDtypeStruct((n_blocks, 16, width), jnp.bfloat16,
                                sharding=chip)
    valid = jax.ShapeDtypeStruct((n_blocks, 16), jnp.bool_, sharding=chip)
    new = jax.ShapeDtypeStruct((slots, width), jnp.bfloat16, sharding=chip)
    tables = jax.ShapeDtypeStruct((slots, bps), jnp.int32, sharding=chip)
    lens = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)

    def decode(q, pool, valid, new, tables, lens):
        return paged_latent_decode(q, pool, tables, lens, new, v_width=512,
                                   sm_scale=1 / 16, valid_pool=valid,
                                   interpret=False)

    text = _compiled_text(decode, q, pool, valid, new, tables, lens)
    assert len(_latent_kernels(text)) == 1


# the glm cell's engine (benchmark/traffic/long-context.json) at the
# configuration's widths, its first two layers: the dense layer and an
# expert layer, both with latent attention, on a pool a tenth as long
GLM_CELL = dict(max_slots=16, max_len=24576, kv_block_size=16,
                num_blocks=2560, prefill_chunk=1024, donate=True)


@pytest.fixture(scope="module")
def glm_engine(chip):
    from distributed_deep_learning_tpu.models import describe
    from distributed_deep_learning_tpu.serve.engine import PagedEngine

    def on_chip(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    desc = dict(describe.read("benchmark/configs/glm-4.7-flash-d7.json"),
                num_hidden_layers=2)
    model = describe.causal_lm(desc, max_len=24576, with_logits=True,
                               dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.ones((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda s: on_chip(s, jnp.bfloat16), params)
    engine = PagedEngine(model, params, **GLM_CELL)
    head = (params, jax.tree.map(on_chip, engine.pools))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    slots, chunk, bps = (engine.max_slots, engine.chunk,
                         engine.blocks_per_slot)
    return engine, {
        "paged_chunk": (engine._chunk_prog, head + (
            i32(chunk), i32(bps), i32(), i32(), i32(chunk), i32(chunk),
            key)),
        "paged_decode": (engine._decode, head + (
            i32(slots, bps), i32(slots), i32(slots), i32(slots),
            i32(slots), key)),
    }


@pytest.mark.parametrize("program", ["paged_chunk", "paged_decode"])
def test_latent_paged_program_compiles_for_v5e(glm_engine, on_tpu, program):
    """The latent pool leaf rests as it is computed in (640 lanes: no copy
    of a whole leaf; a 576-wide leaf rests block-index minor and costs two
    such copies a layer and program).  The decode program attends ABSORBED
    through the block table, one latent kernel a layer, and holds nothing
    of the size of the gathered slots (16 x 24,576 rows); the chunk program
    attends EXPANDED over one gathered slot with the keys walked in blocks:
    no array of scores wider than a block of 512 keys, temporaries under
    1 GiB (whole scores, 20 x 1,024 x 24,576 in float32, are 1.9 GiB)."""
    engine, programs = glm_engine
    assert engine.ring_blocks is None
    assert engine.decode_attn_paths == {"block_table": 0, "gather": 0,
                                        "latent": 2}
    assert engine.latent_row_bytes == 1280
    prog, args = programs[program]
    compiled = prog._jit.lower(*args).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    rows = engine.num_blocks + 1
    assert f"bf16[{rows},16,640]" in entry
    copies = re.findall(rf"= (bf16\[{rows},[^ ]*) copy\(", entry)
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    kernels = re.findall(r"%(ragged-dot-none[\w.]*) = [^\n]*"
                         r'custom_call_target="tpu_custom_call"', text)
    assert len(kernels) == 3, kernels
    gathered = re.findall(r"bf16\[16,(?:24576|1536,16),640\]", text)
    scores = {int(k) for k in re.findall(r"f32\[20,1024,(\d+)\]", text)}
    if program == "paged_decode":
        assert len(_latent_kernels(text)) == 2 and not gathered
    else:
        assert not _latent_kernels(text)
        assert "bf16[1,24576,640]" in text          # one slot, gathered
        assert scores and max(scores) <= 512, scores
