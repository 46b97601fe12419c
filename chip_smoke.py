"""Chip smoke: the gpt train -> serve path on a TPU, through the normal CLI.

    python chip_smoke.py             # one chip: train, serve, serve_trace, kernels
    python chip_smoke.py --chips 4   # one host of four: FSDP training vs one chip

The quickest proof that the system still starts on the chip, and that what
ran there was the chip.  With no arguments it trains a GPT-2-small
``CausalLM`` (12 layers, d_model 768, 12 heads of 64, MLP 3072, vocabulary
50,257, context 1,024, bf16) for two short epochs by calling the package's
own ``main()`` with the argv a user would type, serves a few requests from
those weights through the paged engine on the same command line, pushes a
longer trace through a ``PagedEngine`` at the same widths, and compares the
two Pallas kernels with their references, compiled.  Every phase checks
what came out, and that it came out of a TPU; the first check that fails
names its phase and the script exits 1.  ``--chips 4`` runs the multi-chip
path instead — the same model, seed and global batch under ``-m data --zero
fsdp --mesh data=2,fsdp=2`` against a one-chip run in the same process — and
nothing else.

Everything runs in this one process (a chip belongs to one process at a
time).  Times and rates printed above the last line are smoke readings, not
measurements.  The last line of stdout is the contract's:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

``--tiny`` shrinks every size so the whole walk can be rehearsed on the CPU;
the platform check still fails there, as it should.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the platform every array, engine and line of this run must name
PLATFORM = "tpu"

#: GPT-2 small as published: the widths are never cut, ``--tiny`` exists
#: only to rehearse the control flow off the chip
REAL = dict(layers=12, d_model=768, heads=12, mlp=3072, vocab=50257,
            context=1024, batch=8, rows=200, slots=8, block=16, chunk=128,
            trace_requests=8, trace_prompt_max=512, trace_new_max=64)
TINY = dict(layers=2, d_model=64, heads=2, mlp=256, vocab=257,
            context=64, batch=8, rows=80, slots=2, block=8, chunk=16,
            trace_requests=4, trace_prompt_max=24, trace_new_max=8)

#: engine vs generate(): greedy tokens counted up to each request's first
#: divergence.  Both run the same bf16 weights but at different shapes
#: (padded 128-token chunks over a 1,024-slot cache vs the exact prompt), so
#: a near-tie between two logits may fall either way; anything below this
#: share is a wrong cache, not a tie.
PARITY_BAR = 0.9

#: --chips 4: largest relative gap allowed between a step's loss on four
#: chips and on one.  bf16 activations with f32 accumulation and a different
#: reduction order under FSDP: the largest gap seen on a v5e host over 17
#: steps was 2.8e-5.
LOSS_RTOL = 1e-3


class SmokeFailure(Exception):
    pass


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(f"chip_smoke: {message}", flush=True)


class Tee(io.TextIOBase):
    """Echo what the package prints and keep it for the checks."""

    def __init__(self, stream):
        self.stream, self.lines = stream, []

    def write(self, text):
        self.stream.write(text)
        self.lines.append(text)
        return len(text)

    def flush(self):
        self.stream.flush()

    @property
    def text(self) -> str:
        return "".join(self.lines)


class CompileMeter:
    """Counts what JAX's compile path reports: requests that consulted the
    persistent cache, hits, seconds spent compiling and retrieving."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.hits = 0
        self.compile_s = self.retrieve_s = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, seconds, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds
        elif name == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieve_s += seconds

    def line(self) -> str:
        return (f"{self.requests} compile requests, {self.hits} persistent-"
                f"cache hits, {self.compile_s:.1f}s compiling, "
                f"{self.retrieve_s:.1f}s retrieving")


class Spies:
    """Record what the CLI built, without changing what it does: the
    jitted train step, every batch-mean loss, the first batch, and each
    paged engine with its trace and outcome."""

    def __init__(self):
        from distributed_deep_learning_tpu.serve.engine import PagedEngine
        from distributed_deep_learning_tpu.workloads import base

        self.train_step = self.batch = None
        self.losses, self.engines = [], []
        self._base, self._engine = base, PagedEngine
        self._make, self._run = base.make_train_eval_steps, PagedEngine.run
        spies = self

        def make(*args, **kw):
            train_step, eval_step = spies._make(*args, **kw)
            spies.train_step = train_step

            def recording(state, x, y):
                if spies.batch is None:
                    spies.batch = (x, y)
                state, metrics = train_step(state, x, y)
                spies.losses.append(metrics["loss"])
                return state, metrics

            return recording, eval_step

        def run(engine, requests, *args, **kw):
            requests = list(requests)
            out = spies._run(engine, requests, *args, **kw)
            spies.engines.append((engine, requests, out))
            return out

        base.make_train_eval_steps, PagedEngine.run = make, run

    def remove(self):
        self._base.make_train_eval_steps = self._make
        self._engine.run = self._run


# --------------------------------------------------------------- phases

def check_device(chips: int):
    import jax
    import jaxlib

    from distributed_deep_learning_tpu import native
    from distributed_deep_learning_tpu.obs.mfu import chip_peak_flops
    from distributed_deep_learning_tpu.runtime.bootstrap import (
        enable_compile_cache)

    devices = jax.devices()
    dev = devices[0]
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{_libtpu_version()}")
    say(f"devices: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)}")
    require(dev.platform == PLATFORM,
            f"platform is {dev.platform!r} ({dev.device_kind}), not "
            f"{PLATFORM!r}: JAX found no accelerator")
    require(len(devices) == chips,
            f"{len(devices)} {dev.platform} devices, this run needs "
            f"{chips} (--chips)")
    if PLATFORM == "tpu":
        peak = chip_peak_flops(dev.device_kind)
        require(peak is not None, f"obs/mfu.py has no peak for device kind "
                                  f"{dev.device_kind!r}")
        say(f"peak table: {dev.device_kind!r} -> {peak:.3g} bf16 FLOP/s")
    say(f"compile cache: {enable_compile_cache()}")
    status = native.status()
    say(f"native: {status}")
    require(status == "built", f"native host library took the {status} path")
    return devices


def _libtpu_version() -> str:
    try:
        from importlib.metadata import version

        return version("libtpu")
    except Exception:
        return "not installed"


def write_corpus(path: str, *, seed: int, rows: int, row_len: int,
                 vocab: int) -> None:
    """Rows of a seeded first-order Markov chain over 512 ids drawn from the
    whole vocabulary (id 0 is the package's pad id and is left out; the top
    id is forced in, because the workload sizes its vocabulary as max id +
    1).  Each state moves to one of four successors with odds 70/15/10/5,
    so there is structure to learn and a falling loss means something:
    uniform random tokens cannot be learned."""
    import numpy as np

    rng = np.random.default_rng(seed)
    active = rng.choice(np.arange(1, vocab - 1), size=min(512, vocab - 2),
                        replace=False)
    active[0] = vocab - 1
    successors = rng.integers(0, len(active), (len(active), 4))
    state = rng.integers(0, len(active), rows)
    state[0] = 0
    tokens = np.empty((rows, row_len), np.int32)
    for t in range(row_len):
        tokens[:, t] = active[state]
        state = successors[state, rng.choice(4, size=rows,
                                             p=[0.7, 0.15, 0.1, 0.05])]
    os.makedirs(os.path.dirname(path), exist_ok=True)
    np.save(path, tokens)


def on_platform(tree, what: str, committed: bool = True) -> None:
    """Every array of `tree` lives on devices of PLATFORM — and was put
    there on purpose (`committed`), not left wherever the default device
    happened to be.  The engines' pools are the exception: they start as
    plain zeros and stay uncommitted through jits whose other arguments
    are uncommitted too, so for them only the device is checked."""
    import jax

    leaves = [a for a in jax.tree.leaves(tree) if isinstance(a, jax.Array)]
    require(leaves, f"{what}: no arrays")
    for a in leaves:
        platforms = {d.platform for d in a.devices()}
        require(platforms == {PLATFORM},
                f"{what}: an array lives on {sorted(platforms)}")
        require(a.committed or not committed,
                f"{what}: an array is not committed to its device")


def run_cli(argv: list[str], spies: Spies):
    """The package's own entry point, in this process, output kept."""
    from distributed_deep_learning_tpu.__main__ import main

    say("$ python -m distributed_deep_learning_tpu " + " ".join(argv))
    tee = Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        state, history = main(argv)
    return state, history, tee.text


def train_argv(size: dict, data_dir: str, epochs: int, mode: list[str]
               ) -> list[str]:
    return ["gpt", "-l", str(size["layers"]), "-s", str(size["d_model"]),
            "-b", str(size["batch"]), "-e", str(epochs), "-d", PLATFORM,
            *mode, "--dtype", "bfloat16", "--data-dir", data_dir]


def check_model(state, size: dict) -> None:
    """The run trained the vocabulary and context the corpus defines — a
    missing tokens.npy makes the workload fall back, silently, to its
    1,024-word synthetic set."""
    import jax

    embed = state.params["embed"]
    vocab, d_model = embed["tok"]["embedding"].shape
    context = embed["pos"].shape[0]
    n_params = sum(a.size for a in jax.tree.leaves(state.params))
    say(f"model: vocab {vocab} context {context} d_model {d_model} layers "
        f"{size['layers']} parameters {n_params:,}")
    require((vocab, context, d_model) ==
            (size["vocab"], size["context"], size["d_model"]),
            f"trained vocab/context/width {(vocab, context, d_model)}, "
            f"expected {(size['vocab'], size['context'], size['d_model'])}")


def check_losses(history, spies: Spies, epochs: int) -> list[float]:
    import numpy as np

    require(all(np.isfinite(r.loss) for r in history),
            f"a logged loss is not finite: {[r.loss for r in history]}")
    train = [r for r in history if r.phase == "train"]
    require(len(train) == epochs, f"{len(train)} train epochs, not {epochs}")
    steps = np.asarray([float(l) for l in spies.losses])
    require(np.isfinite(steps).all(), "a train step's loss is not finite")
    per_epoch = len(steps) // epochs
    require(per_epoch >= 3, f"{per_epoch} train steps an epoch, need >= 3")
    say(f"train: {per_epoch} steps an epoch, loss step 1 {steps[0]:.4f} -> "
        f"step {len(steps)} {steps[-1]:.4f}; "
        + "; ".join(f"epoch {r.epoch} logged {r.loss:.3e} in "
                    f"{r.seconds:.1f}s ({r.examples / r.seconds:,.0f} "
                    f"tokens/s, smoke reading)" for r in train))
    if epochs > 1:
        require(train[1].loss < train[0].loss,
                f"epoch 2 train loss {train[1].loss:.6e} is not below "
                f"epoch 1's {train[0].loss:.6e}")
    return steps.tolist()


def compiled_step_text(spies: Spies, state) -> tuple[str, float]:
    """The train step the CLI ran, compiled again ahead of time from the
    shapes and shardings it ran with: its text, and the seconds this took
    (the program was compiled once already, so this is what a warm cache
    costs)."""
    import jax

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

    t0 = time.perf_counter()
    compiled = spies.train_step.lower(
        jax.tree.map(shape, state), *map(shape, spies.batch)).compile()
    return compiled.as_text(), time.perf_counter() - t0


def check_engine(spies: Spies, line_tag: str):
    """The one paged engine the phase ran: everything completed, nothing
    retraced, pools on the chip."""
    require(len(spies.engines) == 1,
            f"{len(spies.engines)} paged engine runs, expected 1")
    engine, requests, out = spies.engines.pop()
    stats = out["stats"]
    require(not out["errors"], f"{line_tag}: errors {out['errors']}")
    require(len(out["results"]) == len(requests),
            f"{line_tag}: {len(out['results'])} of {len(requests)} "
            "requests completed")
    for uid, tokens in out["results"].items():
        want = next(r.max_new_tokens for r in requests if r.uid == uid)
        require(len(tokens) == want, f"{line_tag}: request {uid} returned "
                                     f"{len(tokens)} of {want} tokens")
    require((stats["chunk_compiles"], stats["decode_compiles"]) == (1, 1),
            f"{line_tag}: compiles chunk={stats['chunk_compiles']} "
            f"decode={stats['decode_compiles']}, expected 1 and 1")
    on_platform(engine.pools, "KV pools", committed=False)
    return engine, requests, out


def check_parity(engine, requests, out) -> None:
    """Engine tokens against generate() on the same weights."""
    import jax
    import numpy as np

    from distributed_deep_learning_tpu.models.transformer import generate

    n_max = max(r.max_new_tokens for r in requests)
    by_len: dict[int, list] = {}
    for r in requests:
        by_len.setdefault(len(r.prompt), []).append(r)
    same = total = exact = 0
    for reqs in by_len.values():      # one compile per prompt length
        prompts = np.stack([r.prompt for r in reqs]).astype(np.int32)
        ref = np.asarray(jax.jit(
            lambda p, x: generate(engine.model, p, x, max_new_tokens=n_max)
        )(engine.params, prompts))
        for r, row in zip(reqs, ref):
            got = np.asarray(out["results"][r.uid])
            diverged = np.flatnonzero(got != row[:len(got)])
            same += diverged[0] if len(diverged) else len(got)
            total += len(got)
            exact += not len(diverged)
    share = same / total
    say(f"engine vs generate(): {same}/{total} greedy tokens agree before "
        f"the first divergence ({share:.3f}), {exact}/{len(requests)} "
        f"requests identical; bar {PARITY_BAR} (bf16 ties)")
    require(share >= PARITY_BAR,
            f"engine agrees with generate() on {share:.3f} of tokens, "
            f"below {PARITY_BAR}")


def phase_train_serve(size: dict, data_dir: str, spies: Spies) -> None:
    argv = train_argv(size, data_dir, 2, ["-m", "sequential"]) + [
        "--serve", "--paged", "--kv-block-size", str(size["block"]),
        "--prefill-chunk", str(size["chunk"]), "--max-slots",
        str(size["slots"])]
    state, history, output = run_cli(argv, spies)
    require(f"platform={PLATFORM} " in output,
            "the run's devices line does not name the platform")
    check_model(state, size)
    on_platform(state.params, "parameters")
    on_platform(state.opt_state, "optimizer state")
    check_losses(history, spies, 2)
    text, warm_s = compiled_step_text(spies, state)
    say(f"train step: {text.count('tpu_custom_call')} tpu_custom_call in "
        f"the compiled program; compiling it a second time took "
        f"{warm_s:.1f}s")
    if PLATFORM == "tpu":
        require("tpu_custom_call" in text,
                "the compiled train step has no tpu_custom_call: "
                "--attention auto resolved to dense")
    require('"serve(paged): ' in output, "no serve(paged) line was logged")
    for notice in ("skipped", "disabled"):
        require(notice not in output, f"the run printed a '{notice}' notice")
    check_parity(*check_engine(spies, "serve(paged)"))


def phase_serve_trace(size: dict, seed: int, spies: Spies) -> None:
    """A longer trace than the CLI's, prompts up to half the context and
    six in ten behind one shared system prompt, through a fresh paged
    engine on randomly initialised weights of the same widths."""
    from distributed_deep_learning_tpu.models.transformer import (
        random_causal_lm)
    from distributed_deep_learning_tpu.serve.engine import PagedEngine
    from distributed_deep_learning_tpu.serve.load import LoadSpec, make_load
    from distributed_deep_learning_tpu.serve.paged import paged_max_len

    model, params = random_causal_lm(
        seed, vocab_size=size["vocab"], num_layers=size["layers"],
        d_model=size["d_model"], num_heads=size["heads"],
        mlp_dim=size["mlp"], max_len=size["context"])
    on_platform(params, "serve_trace parameters", committed=False)
    cap = paged_max_len(model.max_len, size["block"], False, 0)
    mid = (4 + size["trace_prompt_max"]) // 2
    trace = make_load(LoadSpec(
        n_requests=size["trace_requests"], arrival="poisson", rate=2.0,
        prompt_short=(4, mid), prompt_long=(mid, size["trace_prompt_max"]),
        long_frac=0.3, shared_prefix_len=min(32, size["context"] // 8),
        shared_frac=0.6, new_tokens=(4, size["trace_new_max"])),
        vocab_size=size["vocab"], seed=seed)
    PagedEngine(model, params, max_slots=size["slots"], max_len=cap,
                kv_block_size=size["block"],
                prefill_chunk=min(size["chunk"], cap)).run(trace)
    _, requests, out = check_engine(spies, "serve_trace")
    longest = max(len(r.prompt) for r in requests)
    require(longest > size["trace_prompt_max"] // 2,
            f"longest prompt {longest}: the long half of the trace is "
            "missing")
    stats = out["stats"]
    say(f"serve_trace: {len(requests)} requests, longest prompt {longest} "
        f"tokens, {stats['prefill_chunks']} prefill chunks, "
        f"{stats['decode_ticks']} decode ticks, "
        f"{stats['tokens_per_sec']:.2f} tokens/s (smoke reading), "
        f"prefix hit {stats['paged']['prefix_hit_rate']:.3f}")


def phase_kernels(size: dict, seed: int) -> None:
    """Both Pallas kernels against their plain references, compiled for
    this device at the engine's and the train step's head shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_deep_learning_tpu.models.transformer import (
        dot_product_attention)
    from distributed_deep_learning_tpu.ops.attention_pallas import (
        flash_attention)
    from distributed_deep_learning_tpu.ops.paged_decode_pallas import (
        paged_decode_reference, paged_flash_decode)
    from distributed_deep_learning_tpu.serve import paged, quant

    B, T = size["batch"], size["context"]
    H, D = size["heads"], size["d_model"] // size["heads"]
    keys = jax.random.split(jax.random.key(seed), 8)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
               for kk in keys[:3])
    # ragged padding, as the models always pass one: row b keeps its first
    # T/2 + b*T/(2B) keys
    valid = jnp.arange(T)[None, :] < (
        T // 2 + jnp.arange(B) * (T // (2 * B)))[:, None]

    def loss(attend, q, k, v):
        out = attend(q, k, v, causal=True,
                     key_valid=valid).astype(jnp.float32)
        return jnp.sum(out ** 2), out

    def both(attend, *qkv):
        (_, out), grads = jax.jit(jax.value_and_grad(
            lambda *a: loss(attend, *a), argnums=(0, 1, 2),
            has_aux=True))(*qkv)
        return [np.asarray(a, np.float32) for a in (out, *grads)]

    def dense(dtype):
        return lambda q, k, v, **kw: dot_product_attention(
            q, k, v, dtype=dtype, **kw)

    # the yardstick is plain attention in true f32 on the same inputs (the
    # chip's default f32 matmul is bf16 passes); the bar for the kernel is
    # the plain bf16 path's own distance from it
    with jax.default_matmul_precision("highest"):
        exact = both(dense(jnp.float32), *(a.astype(jnp.float32)
                                           for a in (q, k, v)))
    plain = both(dense(jnp.bfloat16), q, k, v)
    flash = both(flash_attention, q, k, v)
    for name, got, ref, want in zip(("out", "dq", "dk", "dv"), flash, plain,
                                    exact):
        require(np.isfinite(got).all(), f"flash {name} is not finite")
        err, ref_err = (np.abs(a - want).max() / np.abs(want).max()
                        for a in (got, ref))
        say(f"flash_attention {name}: max error {err:.2e} of the f32 "
            f"reference's largest value (dense bf16: {ref_err:.2e})")
        require(err <= 2 * ref_err + 1e-3,
                f"flash {name} is {err:.2e} from f32 attention, more than "
                f"twice dense bf16's {ref_err:.2e}")

    # pool leaves as the paged engine rests them (trailing dims merged),
    # the token's own row beside them, as its decode program calls it
    slots, block = size["slots"], size["block"]
    per_slot = size["context"] // block
    n_blocks = 2 * slots * per_slot + 1
    pool_k, pool_v = (jax.random.normal(
        kk, (n_blocks, block, H, D), jnp.bfloat16) for kk in keys[3:5])
    dq, new_k, new_v = (jax.random.normal(kk, (slots, H, D), jnp.bfloat16)
                        for kk in jax.random.split(keys[5], 3))
    tables = jax.random.permutation(keys[6], n_blocks - 1)[
        :slots * per_slot].reshape(slots, per_slot).astype(jnp.int32)
    lens = jax.random.randint(keys[7], (slots,), 1, size["context"])

    def at_rest(pool, int8):
        return paged.merge_trailing(quant.quantize_rows(pool) if int8
                                    else pool)

    def attend(fn):
        return jax.jit(lambda q, k, v, kn, vn: fn(
            q, k, v, tables, lens, k_new=kn, v_new=vn))

    for name, int8 in (("bf16", False), ("int8", True)):
        args = (dq, at_rest(pool_k, int8), at_rest(pool_v, int8), new_k,
                new_v)
        got = np.asarray(attend(paged_flash_decode)(*args), np.float32)
        want = np.asarray(attend(paged_decode_reference)(*args), np.float32)
        require(np.isfinite(got).all(), f"paged decode {name} not finite")
        err = np.abs(got - want).max() / np.abs(want).max()
        say(f"paged_flash_decode {name}: max error {err:.2e} of the "
            f"reference's largest value")
        require(err < 2e-2, f"paged_flash_decode {name} differs from "
                            f"paged_decode_reference by {err:.2e}")


def phase_four_chips(size: dict, data_dir: str, devices) -> None:
    """FSDP over a 2x2 mesh against one chip: same model, seed, global
    batch and steps, both through the CLI, losses compared step by step."""
    import jax
    import numpy as np

    runs = {}
    for name, mode in (("one chip", ["-m", "sequential"]),
                       ("four chips", ["-m", "data", "--zero", "fsdp",
                                       "--mesh", "data=2,fsdp=2"])):
        spies = Spies()
        try:
            state, history, _ = run_cli(
                train_argv(size, data_dir, 1, mode), spies)
        finally:
            spies.remove()
        check_model(state, size)
        runs[name] = check_losses(history, spies, 1)
        if name == "one chip":
            del state
            continue
        for what, tree in (("parameters", state.params),
                           ("optimizer state", state.opt_state),
                           ("batch", spies.batch)):
            on_platform(tree, what)
            used = {s.device for a in jax.tree.leaves(tree)
                    if isinstance(a, jax.Array) and a.ndim
                    for s in a.addressable_shards}
            require(len(used) == 4, f"{what} shards live on {len(used)} "
                                    f"devices, not 4: {sorted(map(str, used))}")
        biggest = max(jax.tree.leaves(state.params), key=lambda a: a.size)
        require(biggest.addressable_shards[0].data.size < biggest.size,
                "the largest parameter is not sharded: every device holds "
                "all of it")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
        say(f"bytes in use per device: {in_use}")
        if PLATFORM == "tpu":
            require(all(in_use), f"a device holds nothing: {in_use}")
        text, _ = compiled_step_text(spies, state)
        counts = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                  for op in ("all-gather", "reduce-scatter", "all-reduce")}
        say(f"compiled step: {counts}, "
            f"{text.count('tpu_custom_call')} tpu_custom_call")
        require(counts["all-gather"] > 0, "no all-gather in the FSDP step")
        require(counts["reduce-scatter"] + counts["all-reduce"] > 0,
                "no gradient reduction in the FSDP step")
        if PLATFORM == "tpu":
            require("tpu_custom_call" in text, "no flash kernel in the step")
    one, four = (np.asarray(runs[k]) for k in ("one chip", "four chips"))
    require(len(one) == len(four), f"{len(one)} vs {len(four)} steps")
    gap = np.abs(one - four) / np.abs(one)
    for i, (a, b) in enumerate(zip(one, four), 1):
        say(f"step {i}: one chip {a:.5f}  four chips {b:.5f}")
    say(f"largest relative gap {gap.max():.2e}; tolerance {LOSS_RTOL}")
    require(gap.max() <= LOSS_RTOL,
            f"four-chip losses differ from one chip's by {gap.max():.2e}")


# ----------------------------------------------------------------- main

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the multi-chip training comparison")
    p.add_argument("--seed", type=int, default=0,
                   help="corpus, trace and kernel inputs")
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                 "chip_smoke"),
                   help="where the corpus is written")
    p.add_argument("--tiny", action="store_true",
                   help="toy sizes, to rehearse the walk on the CPU")
    args = p.parse_args(argv)
    size = TINY if args.tiny else REAL
    sys.path.insert(0, ROOT)

    phase = "device"
    t_start = time.perf_counter()
    try:
        import jax  # noqa: F401  (a bare directory fails here or below)
        import distributed_deep_learning_tpu  # noqa: F401

        devices = check_device(args.chips)
        meter = CompileMeter()

        phase = "corpus"
        write_corpus(os.path.join(args.out, "tokens.npy"), seed=args.seed,
                     rows=size["rows"], row_len=size["context"] + 1,
                     vocab=size["vocab"])
        if args.chips == 4:
            phase = "four chips"
            phase_four_chips(size, args.out, devices)
        else:
            spies = Spies()
            try:
                phase = "train+serve"
                phase_train_serve(size, args.out, spies)
                say(f"compile (train+serve): {meter.line()}")
                phase = "serve_trace"
                phase_serve_trace(size, args.seed, spies)
            finally:
                spies.remove()
            phase = "kernels"
            phase_kernels(size, args.seed)
        phase = "summary"
        say(f"compile (whole run): {meter.line()} — a first run on a "
            "machine is cold; run it again for the warm reading")
        for d in devices:
            stats = d.memory_stats() or {}
            say(f"{d}: peak HBM "
                f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
                f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
        say(f"all phases passed in {time.perf_counter() - t_start:.0f}s")
        dev = devices[0]
        print(json.dumps({"ok": True, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}}), flush=True)
        return 0
    except Exception as exc:
        traceback.print_exc()
        say(f"FAILED at phase '{phase}': {type(exc).__name__}: {exc}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
