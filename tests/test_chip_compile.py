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

import math
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_deep_learning_tpu.ops.attention_pallas import (
    flash_attention, make_attention_fn)
from distributed_deep_learning_tpu.ops.grouped_matmul_pallas import (
    Visits, _tiling)
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
    # sequences past the train cells' 1,024: the backward holds q, o, dO
    # and dq a lane block whole, so past T = 2,048 it asks for more than
    # the 16 MiB of VMEM a kernel gets unasked (``attention_pallas._vmem``);
    # the head widths of the described models (laguna 128, GQA; glm 256)
    "t2048-d64": dict(blocks=(512, 512), shape=(1, 2048, 16, 64)),
    "t4096-d64": dict(blocks=(512, 512), shape=(1, 4096, 16, 64)),
    "t4096-odd-heads": dict(blocks=(512, 512), shape=(1, 4096, 25, 64)),
    "t4096-d128-gqa": dict(blocks=(512, 512), shape=(1, 4096, 8, 128),
                           kv_heads=2),
    "t4096-d256": dict(blocks=(512, 512), shape=(1, 4096, 4, 256)),
    "t16384-d64": dict(blocks=(512, 512), shape=(1, 16384, 2, 64)),
    "t16384-d128": dict(blocks=(512, 512), shape=(1, 16384, 2, 128)),
}


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_compiles_for_v5e(chip, case):
    """Forward and backward in one program: the forward kernel a plain call
    would run, then the backward kernel (dq, dk and dv together)."""
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
    kernels = re.findall(r"%[\w.]*(flash_fwd|flash_bwd)[\w.]* = [^\n]*"
                         r'custom_call_target="tpu_custom_call"', text)
    assert sorted(kernels) == ["flash_bwd", "flash_fwd"], kernels


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

    model = CausalLM(**XL, attention_fn=make_attention_fn())
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.ones((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda s: on_chip(s, XL["dtype"]), params)
    engine = PagedEngine(model, params, **CELL)
    head = (params, jax.tree.map(on_chip, engine.pools))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    return engine, {
        "paged_chunk": (engine._chunk_prog, head + (
            i32(engine._chunk_io[0].size), key)),
        "paged_decode": (engine._decode, head + (
            i32(engine._decode_io[0].size), key)),
        "paged_copy": (engine._copy, (head[1], i32(), i32())),
    }


#: the grouped products of ONE expert layer in a compiled serving program
#: of the two expert cells, by kernel: a chunk program's 4,096 / 5,120 rows
#: and a decode program's 64 / 160 go through the package's kernels (gate
#: and up in one), none through XLA's ragged-dot call, three a layer
#: before PR 36
GROUPED_KERNELS = {
    "paged_chunk": {"grouped_swiglu": 1, "grouped_product": 1},
    "paged_decode": {"grouped_swiglu": 1, "grouped_product": 1},
}


def _grouped_kernels(text: str) -> dict:
    """Grouped-product kernels in a compiled program, counted by name."""
    names = re.findall(r"%(ragged-dot-none|grouped_swiglu|grouped_product)"
                       r'[\w.]* = [^\n]*custom_call_target="tpu_custom_call"',
                       text)
    return {n: names.count(n) for n in set(names)}


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
                               dtype=jnp.bfloat16,
                               attention_fn=make_attention_fn())
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.ones((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda s: on_chip(s, jnp.bfloat16), params)
    engine = PagedEngine(model, params, **LAGUNA_CELL)
    head = (params, jax.tree.map(on_chip, engine.pools))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    return engine, {
        "paged_chunk": (engine._chunk_prog, head + (
            i32(engine._chunk_io[0].size), key)),
        "paged_decode": (engine._decode, head + (
            i32(engine._decode_io[0].size), key)),
        "paged_copy": (engine._copy, (head[1], i32(), i32())),
    }


@pytest.mark.parametrize("program", ["paged_chunk", "paged_decode"])
def test_two_kind_paged_program_compiles_for_v5e(laguna_engine, on_tpu,
                                                 program):
    """Both pool kinds rest as they are computed in (``Hkv*D`` = 1,024
    minor: no whole-leaf copy of either), and the expert layer's grouped
    products are grouped kernels, not a dense product over every expert:
    in the chunk program (5,120 sorted rows) and in the decode program
    (160) the package's own two, gate and up fused and down, and no
    ragged-dot call of XLA's.  The decode program attends the full
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
    assert _grouped_kernels(text) == GROUPED_KERNELS[program]
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


def _eqns(jaxpr, primitive, out):
    """Every equation of `primitive` in `jaxpr`, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            out.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _eqns(sub, primitive, out)
    return out


def _self_attn_moves(text: str, elements: int, fused: bool = True) -> list:
    """The ``copy`` / ``transpose`` instructions of a compiled program that
    lie inside ``self_attn`` and move an array of `elements` elements;
    with `fused` false only those that are instructions of their own, not
    a layout change inside a fusion."""
    import math

    found, computation = [], ""
    for line in text.splitlines():
        if line and not line[0].isspace():
            computation = line
        if not fused and "fused_computation" in computation:
            continue
        m = re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)
        if m and "self_attn" in line and math.prod(
                int(d) for d in m[1].split(",")) == elements:
            found.append(line.strip()[:160])
    return found


@pytest.fixture(scope="module", params=TRAIN_CELLS)
def train_flash_calls(v5e, request):
    """The flash kernel's calls in the CLI's ``gpt`` train step, traced once
    and lowered for the described chips under ``--attention auto`` with no
    ``block_q`` / ``block_k`` given: ``(rows a chip, heads, [(kernel name,
    grid, block shapes)], the step's flash_layout note, what moves an
    activation around the kernel)``.  The program asks for the backend twice
    on the way (the ``auto`` rule, the kernel's default blocks): it is told
    what it would find on the chip.  The moves: every ``transpose`` the
    traced step holds of an array the size of a chip's q, and for the
    one-chip cell the ``copy`` / ``transpose`` instructions of that size
    the chip's compiler leaves inside ``self_attn`` (under cell 4's mesh
    the compiler rests the products' activations sequence-minor and copies
    the kernel's operands whatever the layer does: counted by
    ``test_fsdp_step_brings_the_weights_to_the_rows``)."""
    from distributed_deep_learning_tpu import obs

    argv, chips, heads = TRAIN_CELLS[request.param]
    obs.compile_log.mark("test")
    with pytest.MonkeyPatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        rows, traced = _trace_train_step(argv, v5e[:chips])
    note = [text for event, fun, text in obs.compile_log.notes()
            if (event, fun) == ("flash_layout", "jit(train_step)")]
    lowered = traced.lower()
    # each flash kernel is lowered once and called a layer; in cell 1,
    # whose logits are taken a block at a time, the head's forward kernel
    # is asked for twice (the loss, the argmax count: the compiled step
    # holds it once, ``test_one_chip_step_holds_no_logits``)
    head = {"head_ce_fwd": 2, "head_ce_bwd": 1} if chips == 1 else {}
    assert _lowered_kernels(lowered.as_text()) == {
        "flash_fwd": 1, "flash_bwd": 1, **head}
    calls = []
    for eqn in _eqns(traced.jaxpr.jaxpr, "pallas_call", []):
        assert eqn.params["interpret"] is False
        mapping = eqn.params["grid_mapping"]
        calls.append((
            eqn.params["name"], tuple(mapping.grid),
            [tuple(getattr(d, "block_size", d) for d in m.block_shape)
             for m in mapping.block_mappings]))
    size = rows // chips * T * heads * D
    moves = [str(eqn) for eqn in _eqns(traced.jaxpr.jaxpr, "transpose", [])
             if eqn.invars[0].aval.size in (size, size * chips)]
    if chips == 1:
        moves += _self_attn_moves(lowered.compile().as_text(), size)
    return rows // chips, heads, calls, note, moves


def _lowered_kernels(text: str) -> dict:
    """Pallas kernels in a lowered module's text, by name: how many
    ``tpu_custom_call`` sites name each."""
    import collections

    return dict(collections.Counter(re.findall(
        r'tpu_custom_call.*?kernel_name = \\?"(\w+)', text)))


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


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd"],
                         ids=["forward", "backward"])
def test_train_step_lowers_flash_at_512_blocks(train_flash_calls, kernel):
    """What cells 1 and 4 compile: q, k, v and dO read as the projections
    write them, ``(rows, T, H·64)``, in blocks of 128 lanes: two heads a
    block, 8 lane blocks for gpt2-medium's 16 heads and 13 for gpt2-xl's 25
    (the last a boundary block of one head), on each chip's own rows (cell
    4 calls the kernel per shard); at T = 1,024 a program walks 512-row
    query (or key) blocks, its operands come whole or 512 rows at a time,
    the row statistics a column a head of the block, the padding mask
    blocked 512 keys wide, and no call transposes.  A default that moved
    would show here, before any chip does."""
    rows, heads, calls, note, _ = train_flash_calls
    assert note == ["calls=2 lanes_a_block=128 heads_a_block=2 transposed=0"]
    calls = [c for c in calls if c[0] == kernel]
    assert len(calls) == 2                        # one a layer
    for _, grid, blocks in calls:
        assert grid == (rows, -(-heads * D // 128), T // 512)
        operands = [b for b in blocks if len(b) == 3]
        assert {b[2] for b in operands} == {128}, blocks
        assert {b[1] for b in operands} == {512, T}, blocks
        stats = [b for b in blocks if len(b) == 4 and b[2] != 1]
        assert stats and all(b[1] == 1 and b[2] in (512, T) and b[3] == 2
                             for b in stats), blocks
        masks = [b for b in blocks if len(b) == 4 and b[2] == 1]
        assert masks and all(b[3] == 512 for b in masks), blocks


def test_train_step_moves_no_activation_around_the_kernel(train_flash_calls):
    """No ``transpose`` of an array the size of a chip's q (``rows x 1,024
    x H x 64``) is traced into the step, forward or backward, and in the
    one-chip cell the chip's compiler leaves no ``copy`` or ``transpose``
    of that size inside ``self_attn``: the views to ``(B, T, H·D)`` and
    back move nothing (the parent's step held seven a layer)."""
    *_, moves = train_flash_calls
    assert not moves, moves


SERVE_ENGINES = ["xl_engine", "laguna_engine", "glm_engine"]


@pytest.mark.parametrize("program", ["paged_chunk", "paged_decode",
                                     "paged_copy"])
@pytest.mark.parametrize("engine", SERVE_ENGINES)
def test_no_serving_program_holds_a_flash_call(request, on_tpu, engine,
                                               program):
    """The serve cells' models carry the flash adapter (``--attention
    auto`` on a TPU) and none of their programs calls it: a cached layer
    attends densely, through the block table or over the latent rows.  The
    lowered text of each program of each cell's engine holds no kernel of
    ``attention_pallas`` (``flash_fwd``, ``flash_bwd``): a change to the
    flash kernels cannot move a serve cell."""
    built, programs = request.getfixturevalue(engine)
    assert built.lm.attention_fn.reads_heads_merged      # the adapter is on
    prog, args = programs[program]
    text = prog._jit.lower(*args).as_text()
    kernels = set(re.findall(r'kernel_name = "([^"]*)"', text))
    assert not {k for k in kernels if k.startswith("flash_")}, kernels
    want = set()
    if program == "paged_decode":         # the names are in there
        want = {"paged_latent_decode" if engine == "glm_engine"
                else "paged_flash_decode"}
    if program != "paged_copy" and engine != "xl_engine":
        # since PR 36 an expert layer's grouped products
        want |= {"grouped_swiglu", "grouped_product"}
    assert kernels == want, kernels


# --- the grouped expert products (ops/grouped_matmul_pallas.py) -----------

# an expert layer's three products at the two expert cells' chunk shapes:
# sorted rows, d, f, experts held
GROUPED_CELLS = {
    "glm-serve-long-context": (4096, 2048, 1536, 64),
    "laguna-serve-long-mixed": (5120, 3072, 1024, 32),
}


@pytest.mark.parametrize("cell", GROUPED_CELLS)
def test_grouped_product_compiles_for_v5e(chip, cell):
    """Gate and up fused, then down, at the tiles the rule picks for the
    cell (the whole of N a tile: 12.6 MB of weight tiles in flight in the
    fused call at glm's widths, which asks for its VMEM), with the visit
    count a traced value in the grid."""
    M, d, f, E = GROUPED_CELLS[cell]
    assert _tiling(M, d, f, E, weights=2) == (128, f)
    assert _tiling(M, f, d, E) == (128, d)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def layer(rows, w_gate, w_up, w_down, load):
        products = Visits(load, M, interpret=False)
        return products.product(products.swiglu(rows, w_gate, w_up), w_down)

    text = _compiled_text(layer, shape(M, d), shape(E, d, f), shape(E, d, f),
                          shape(E, f, d), shape(E, dtype=jnp.int32))
    assert _grouped_kernels(text) == GROUPED_KERNELS["paged_chunk"]


@pytest.mark.parametrize("program", ["paged_chunk", "paged_decode",
                                     "paged_copy"])
def test_no_gpt2_serving_program_names_a_grouped_product(xl_engine, on_tpu,
                                                         program):
    """The GPT-2 serve cells' programs hold no expert layer: their lowered
    text names neither the package's grouped kernels nor XLA's ragged dot,
    so a change to either cannot move them."""
    engine, programs = xl_engine
    prog, args = programs[program]
    text = prog._jit.lower(*args).as_text()
    assert "grouped_" not in text and "ragged" not in text
    assert not [said for said in engine.program_notes.values() if said]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_no_gpt2_train_step_names_a_grouped_product(v5e, cell):
    argv, chips, _ = TRAIN_CELLS[cell]
    with pytest.MonkeyPatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        _, traced = _trace_train_step(argv, v5e[:chips])
    text = traced.lower().as_text()
    assert "grouped_" not in text and "ragged" not in text


# --- cell 4's step under FSDP: the weights come to the rows ---------------

_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (?P<type>.*?) (?P<kind>all-gather|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\((?P<rest>.*)$",
    re.M)


#: cell 1's rows a chip, data parallel over four: no cell of the benchmark,
#: the one shape at which the head's kernels ran per shard on the chip and
#: won (``PERF.md`` section 6, PR 45)
DATA4 = {"gpt2m-train-data4": (
    "-l 2 -s 1024 -b 64 --dtype bfloat16 -m data --mesh data=4 --lr 0.001 "
    "--schedule none", 4, 16)}


@pytest.fixture(scope="module")
def compiled_train_step(v5e):
    """``cell -> (compiled two-layer step, its notes in the compile log,
    rows a chip)``, each cell's step COMPILED once a module for the
    described chips (a quarter to half a minute each)."""
    from distributed_deep_learning_tpu import obs

    done = {}

    def compiled(cell: str):
        if cell not in done:
            argv, chips, _ = {**TRAIN_CELLS, **DATA4}[cell]
            obs.compile_log.mark("test")
            with pytest.MonkeyPatch.context() as on_tpu:
                on_tpu.setattr(jax, "default_backend", lambda: "tpu")
                rows, traced = _trace_train_step(argv, v5e[:chips])
            notes = {event: text for event, fun, text
                     in obs.compile_log.notes() if fun == "jit(train_step)"}
            done[cell] = traced.lower().compile(), notes, rows // chips
        return done[cell]
    return compiled


@pytest.fixture(scope="module")
def fsdp_step_text(compiled_train_step):
    """The two-layer ``gpt2xl-train-fsdp4`` step compiled for the four
    described chips, as text."""
    return compiled_train_step("gpt2xl-train-fsdp4")[0].as_text()


# --- the head: the step's logits never rest (ops/fused_ce.py) -------------

_PRODUCED = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = (?P<dtype>\w+)\[(?P<dims>[\d,]+)\]\S* "
    r"(?P<op>[\w-]+)\((?P<rest>.*)$", re.M)
VOCAB = 50257


def _logit_sized(text: str, positions: int) -> list:
    """``(dtype, op, name)`` of every instruction of a compiled program
    whose result is as large as a chip's logits, `positions` x 50,257
    (views, a fusion of nothing but a view, and a fusion's parameters
    apart)."""
    found = []
    for m in _PRODUCED.finditer(text):
        dims = [int(d) for d in m["dims"].split(",")]
        if VOCAB in dims and math.prod(dims) == positions * VOCAB \
                and m["op"] not in ("parameter", "bitcast",
                                    "get-tuple-element") \
                and "calls=%bitcast_fusion" not in m["rest"]:
            found.append((m["dtype"], m["op"], m["name"]))
    return found


def test_one_chip_step_holds_no_logits(compiled_train_step):
    """Cell 1 as compiled (16 rows a chip: 3.07 GiB of f32 logits, over
    ``ops.fused_ce.REST_BYTES``): no f32 (or integer, or predicate) value
    of ``rows x 1,024 x 50,257`` is a buffer of the step, and the ONE value
    of that size is the bf16 cotangent the backward kernel writes for the
    two products that follow; the head's forward kernel, asked for twice
    (the loss, the argmax count), is in the program once; the step's
    ``fused_head`` note says so."""
    compiled, notes, rows = compiled_train_step("gpt2m-train-1chip")
    text = compiled.as_text()
    assert _logit_sized(text, rows * T) == [
        ("bf16", "custom-call", mock.ANY)], _logit_sized(text, rows * T)
    assert _logit_sized(text, rows * T)[0][2].startswith("head_ce_bwd")
    for kernel in ("head_ce_fwd", "head_ce_bwd"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    assert notes["fused_head"] == (
        f"calls=1 rows={rows * T} vocab=50257 path=pallas tiles=512x2048 "
        "logits_at_rest=0")


def test_fsdp_step_takes_its_small_logits_whole(compiled_train_step):
    """Cell 4 as compiled (2 rows a chip: 0.38 GiB of f32 logits a shard,
    under ``REST_BYTES``).  ISSUE 45 asked that this step, too, hold no
    ``f32[..., 50257]`` buffer of ``rows x T`` rows: NOT MET, and left for
    the next issue.  What ships instead, because on the chip blocks lost
    2.5% here (``PERF.md`` section 6): the deferred head is multiplied
    out, as the parent's model did; no head kernel, the f32 logits a
    buffer, the parent's program (the same instructions by shape and
    opcode, 0.93 GiB of temporaries: my AOT compiles of both trees, PR
    45).  This test holds that state, not the issue's criterion."""
    compiled, notes, rows = compiled_train_step("gpt2xl-train-fsdp4")
    text = compiled.as_text()
    assert "head_ce" not in text
    assert ("f32", mock.ANY, mock.ANY) in _logit_sized(text, rows * T)
    assert notes["fused_head"] == (
        f"calls=1 rows={rows * T} vocab=50257 path=logits tiles=none "
        "logits_at_rest=1")
    assert compiled.memory_analysis().temp_size_in_bytes <= 995344896


def test_data_parallel_step_runs_a_shards_rows_on_the_shard(
        compiled_train_step):
    """GPT-2 medium under ``--mesh data=4`` at cell 1's 16 rows a chip
    (3.07 GiB of f32 logits a shard, over ``REST_BYTES``): each chip runs
    its own rows through the head's two kernels, once each, inside a
    per-shard region (XLA cannot partition a Mosaic kernel); the one
    logit-sized value a chip holds is the bf16 cotangent, and the table's
    gradient is summed with the others' in the step's all-reduces, no
    more of them than the logits' step has (2).  On the chip this step
    read 191,966 tokens/s where the parent's read 180,467 (``PERF.md``
    section 6, PR 45)."""
    compiled, notes, rows = compiled_train_step("gpt2m-train-data4")
    text = compiled.as_text()
    assert _logit_sized(text, rows * T) == [
        ("bf16", "custom-call", mock.ANY)], _logit_sized(text, rows * T)
    for kernel in ("head_ce_fwd", "head_ce_bwd"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    assert len(re.findall(r"= \S+ all-reduce\(", text)) == 2
    assert notes["fused_head"] == (
        f"calls=1 rows={rows * T} vocab=50257 path=pallas tiles=512x2048 "
        "logits_at_rest=0")


def test_one_chip_step_temporaries_fell(compiled_train_step):
    """ISSUE 45 asked for cell 1's step under 12 GiB by memory analysis
    and ``train_hbm_peak_gib`` 3 GiB lower: NOT MET (at 24 layers both the
    parent's step and this one sit at the compiler's ceiling, 14.74 GiB:
    the parent's by making the logits twice and 14 MLP products twice,
    this one by making 3; ``PERF.md`` section 6), and left for the next
    issue.  What this test holds is what did fall: at two layers, where
    the compiler rematerialises nothing, 2.52 GiB of temporaries where the
    parent's step held 4.05 (my AOT compiles, PR 45)."""
    compiled, _, _ = compiled_train_step("gpt2m-train-1chip")
    assert compiled.memory_analysis().temp_size_in_bytes < 2.75 * 2 ** 30


@pytest.mark.parametrize("workload,argv", [
    ("bert", "-l 1 -s 64 -b 8 -m data"), ("resnet", "-b 8 -m data")])
def test_a_step_on_arrays_names_no_head_kernel(workload, argv):
    """A workload whose model hands its loss arrays traces the parent's
    step: no kernel, no per-shard region, and the note says which."""
    from distributed_deep_learning_tpu import obs
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.utils.config import parse_args
    from distributed_deep_learning_tpu.workloads import base, get_spec

    config = parse_args(argv.split(), workload=workload)
    spec = get_spec(workload)
    ds = spec.build_dataset(config)
    mesh = build_mesh({"data": 1}, jax.devices()[:1])
    state = jax.eval_shape(lambda: create_train_state(
        spec.build_model(config, ds), jax.random.key(0),
        spec.example_input(config, ds),
        base.build_optimizer(spec, config, 17)))
    sspec = base.derive_state_spec(spec, config, mesh, state)
    obs.compile_log.mark("test")
    with pytest.MonkeyPatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        train_step, _ = base.make_train_eval_steps(
            config, mesh, spec.build_loss(config), sspec)
        x, y = (jax.ShapeDtypeStruct((8, *a.shape[1:]), a.dtype)
                for a in (ds.features, ds.targets))
        text = train_step.lower(state, x, y).as_text()
    assert "head_ce" not in text and "shard_map" not in text
    notes = {event: note for event, fun, note in obs.compile_log.notes()
             if fun == "jit(train_step)"}
    assert notes["fused_head"] == "calls=0 path=logits logits_at_rest=1"


@pytest.fixture(scope="module")
def fsdp_collectives(fsdp_step_text):
    """Its collectives, one entry a channel: ``(kind, dtype, dims,
    op_name)``."""
    found = {}
    for m in _COLLECTIVE.finditer(fsdp_step_text):
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
    permutes that stay pass a SHARD of a kernel round the ring inside a
    product (XLA's windowed einsum): the gradients' reduce-scatter in the
    backward's weight-gradient products and, since the attention layer
    projects on the merged ``H·D`` (PR 33), the q / k / v kernels' own
    gather inside the forward products and the backward's input-gradient
    products, where an ``all-gather`` ahead of each stood."""
    found = request.getfixturevalue("fsdp_collectives")
    stray = [c for c in found if c[0] == "all-to-all"
             and "/embed/" not in c[3]]
    assert not stray, stray
    permutes = [c for c in found if c[0] == "collective-permute"]
    backward = re.compile(r"transpose\(jvp\(CausalLM\)\)/layer_\d+/"
                          r"(self_attn/(q|k|v|out)|Dense_[01])/dot_general$")
    forward = re.compile(r"/jvp\(CausalLM\)/layer_\d+/"
                         r"self_attn/(q|k|v)/dot_general$")
    shards = {(400, 25, 64), (25, 64, 400), (1600, 1600), (1, 400, 25, 64)}
    stray = [c for c in permutes if T in c[2] or not (
        backward.search(c[3]) and c[2] in shards
        or forward.search(c[3]) and c[2] == (1, 400, 1600))]
    assert not stray, stray
    assert [c for c in permutes if forward.search(c[3])]
    assert not [c for c in found if c[0] == "reduce-scatter"]


def _layer_kernels_come_to_the_rows_in_bf16(request):
    """A layer's ``out`` and MLP kernels are all-gathered whole for the
    forward product (and again for the backward, unless the scheduler
    still holds them); its q, k and v kernels are never gathered whole:
    their shards go round the ring inside the products.  All as the bf16
    cast the model computes in: no f32 all-gather in the layer stack."""
    found = request.getfixturevalue("fsdp_collectives")
    gathers = [c for c in found if c[0] == "all-gather" and "/layer_" in c[3]]
    assert {c[1] for c in gathers} == {"bf16"}, gathers
    whole = {"out": (25, 64, 1600), "Dense_0": (1600, 6400),
             "Dense_1": (6400, 1600)}
    for layer in range(2):
        for name, dims in whole.items():
            assert [c for c in gathers if c[2] == dims and re.search(
                rf"jvp\(CausalLM\)/layer_{layer}/(self_attn/)?{name}"
                r"/dot_general$", c[3])], (layer, name, gathers)
        for name in "qkv":
            ring = [c for c in found if c[:3] == (
                "collective-permute", "bf16", (1, 400, 1600)) and c[3].endswith(
                f"/jvp(CausalLM)/layer_{layer}/self_attn/{name}/dot_general")]
            assert len(ring) == 3, (layer, name, ring)   # four chips
    assert not [c for c in gathers if c[2] == (1600, 25, 64)], gathers
    assert len(gathers) > 6        # and most of them again for the backward


def _the_kernel_s_operands_are_still_copied(request):
    """What the ``flash_layout`` note (``transposed=0``) does not say of
    this cell: under a mesh the chip's compiler rests the products'
    activations sequence-minor and copies the kernel's operands and
    results between the two layouts, as it copied the parent's (8 a layer
    there): 12 instructions of their own a layer, each the size of a
    chip's ``(2, 1,024, 1,600)``, the ring's own among them.  On the chip
    they take less time than the parent's 8 did (``PERF.md`` section 5); a
    change that adds to them shows here first."""
    text = request.getfixturevalue("fsdp_step_text")
    moves = _self_attn_moves(text, 2 * T * 25 * D, fused=False)
    assert 0 < len(moves) <= 2 * 12, moves


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
    _no_activation_is_resharded, _layer_kernels_come_to_the_rows_in_bf16,
    _the_kernel_s_operands_are_still_copied,
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
                               dtype=jnp.bfloat16,
                               attention_fn=make_attention_fn())
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.ones((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda s: on_chip(s, jnp.bfloat16), params)
    engine = PagedEngine(model, params, **GLM_CELL)
    head = (params, jax.tree.map(on_chip, engine.pools))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    return engine, {
        "paged_chunk": (engine._chunk_prog, head + (
            i32(engine._chunk_io[0].size), key)),
        "paged_decode": (engine._decode, head + (
            i32(engine._decode_io[0].size), key)),
        "paged_copy": (engine._copy, (head[1], i32(), i32())),
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
    assert _grouped_kernels(text) == GROUPED_KERNELS[program]
    gathered = re.findall(r"bf16\[16,(?:24576|1536,16),640\]", text)
    scores = {int(k) for k in re.findall(r"f32\[20,1024,(\d+)\]", text)}
    if program == "paged_decode":
        assert len(_latent_kernels(text)) == 2 and not gathered
    else:
        assert not _latent_kernels(text)
        assert "bf16[1,24576,640]" in text          # one slot, gathered
        assert scores and max(scores) <= 512, scores
