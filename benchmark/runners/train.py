"""Runner for ``kind: train`` mixes: the CLI's own train step in a window.

The step, the state's sharding, the optimizer and the loader are built by
the package's functions from the argv a user would type (the
configuration file's ``cli`` plus the mix's); only the
weights are the benchmark's, made on the device from the seed, so that the
plain reference can start from the same ones without taking anything the
program made.  Set-up drives the one compiled step through its first
`check_steps` steps, keeps their batches, losses and the norms of the
parameters' change, and hands the same state and step to the window.
What is dear stays out of ``setup_s``: once the window has closed and its
state is freed, the same compiled step takes a fresh state from the same
seed through the first batch again, and the first gradient goes to the
host from there (`first_gradient`); then the plain reference runs.
"""

from __future__ import annotations

import functools
import gc
import statistics
import time

import numpy as np

from benchmark import harness, traffic_gen
from benchmark.harness import say


def _adam_mu(opt_state):
    """The first-moment tree inside an optax optimizer state."""
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu")) if hasattr(s, "mu")]
    if not found:
        raise harness.BenchFailure("the optimizer state holds no Adam "
                                   "moments; the first gradient cannot be "
                                   "worked out from it")
    return found[0].mu


def reference_sharding(cell, devices):
    """Where the reference keeps its flat arrays on `devices`: one chip
    holds all; several split each array on its first axis (past the layer
    axis) that divides evenly, so that float32 weights, gradient and both
    Adam moments of the largest configuration fit."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    weights = harness.weights_for(cell)
    mesh = Mesh(np.asarray(devices), ("r",))
    n = len(devices)
    out = {}
    for name, shape in weights.leaf_shapes(cell.config).items():
        first = 1 if weights.is_stacked(name) else 0
        axis = next((a for a in range(first, len(shape))
                     if n > 1 and shape[a] % n == 0 and shape[a] >= n),
                    None)
        spec = [None] * len(shape)
        if axis is not None:
            spec[axis] = "r"
        out[name] = NamedSharding(mesh, P(*spec))
    return out


def build(cell, seed: int, devices, clock):
    """(state, step, feed, aux): the compiled step with its state, as the
    CLI builds them, and the endless batch feed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    with clock.phase("import_program"):
        from distributed_deep_learning_tpu.data.loader import make_loaders
        from distributed_deep_learning_tpu.data.splits import (
            train_val_test_split)
        from distributed_deep_learning_tpu.data.tokens import lm_dataset
        from distributed_deep_learning_tpu.runtime.mesh import build_mesh
        from distributed_deep_learning_tpu.train.state import (
            TrainState, create_train_state)
        from distributed_deep_learning_tpu.utils.config import (Mode,
                                                                parse_args)
        from distributed_deep_learning_tpu.workloads import base as wb
        from distributed_deep_learning_tpu.workloads import get_spec

    cfg, mix = cell.config, cell.traffic
    rows = int(mix["rows_per_chip"]) * cell.chips
    argv = (cfg["cli"][1:] + ["-b", str(rows), "-e", "1", "--seed",
                              str(seed % (2 ** 31 - 1))]
            + mix["cli"])
    say("program argv: " + " ".join(cfg["cli"][:1] + argv))
    with clock.phase("build_step"):
        config = parse_args(argv, workload=cfg["cli"][0])
        spec = get_spec(cfg["cli"][0])
        tokens = traffic_gen.markov_corpus(
            seed, mix["corpus_rows"], mix["seq_len"] + 1, cfg["vocab_size"])
        dataset = lm_dataset(tokens)
        if config.mode is Mode.SEQUENTIAL:
            mesh = build_mesh({"data": 1}, devices[:1])
        else:
            mesh = build_mesh(config.mesh_shape,
                              wb.mesh_devices(config.mesh_shape, devices))
        splits = train_val_test_split(len(dataset), seed=config.seed)
        epoch_steps = max(1, len(splits.train) // rows)
        model = spec.build_model(config, dataset)
        tx = wb.build_optimizer(spec, config, epoch_steps)
        shapes = jax.eval_shape(lambda: create_train_state(
            model, jax.random.key(0), spec.example_input(config, dataset),
            tx))
        state_spec = wb.derive_state_spec(spec, config, mesh, shapes)
        sharding = (NamedSharding(mesh, state_spec)
                    if isinstance(state_spec, P) else
                    jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 state_spec))
        train_step, _ = wb.make_train_eval_steps(
            config, mesh, spec.build_loss(config), state_spec)
        loader = make_loaders(dataset, splits, rows, mesh,
                              seed=config.seed)[0]
    weights = harness.weights_for(cell)
    key = harness.seed_key(seed)
    with clock.phase("weights"):
        @functools.partial(jax.jit, out_shardings=sharding)
        def init(key):
            params = weights.to_program_tree(
                weights.make_weights(key, cfg, jnp.float32), cfg)
            return TrainState.create(apply_fn=shapes.apply_fn,
                                     params=params, tx=tx, model_state={})

        state = jax.block_until_ready(init(key))

    def feed():
        epoch = 0
        while True:
            loader.set_epoch(epoch)
            yield from loader
            epoch += 1

    # the mesh's own device order: one jit takes no two orders
    aux = {"rows": rows, "key": key, "devices": list(mesh.devices.flat),
           "tokens_per_step": rows * int(mix["seq_len"]), "init": init}
    return state, train_step, feed(), aux


def first_steps(cell, state, step, feed, aux, clock):
    """Drive the step through its first steps, through the window's own
    call and feed; keep the batches, each loss and the norms of the
    parameters' change (all cheap: the first gradient is taken after the
    window, by `first_gradient`)."""
    import jax
    import jax.numpy as jnp

    cfg, mix = cell.config, cell.traffic
    weights = harness.weights_for(cell)

    @jax.jit
    def change_norms(params, key):
        start = weights.to_program_tree(
            weights.make_weights(key, cfg, jnp.float32), cfg)
        return weights.program_leaf_norms(
            jax.tree.map(jnp.subtract, params, start), cfg)

    batches, losses, first = [], [], None
    with clock.phase("compile_and_first_steps"):
        for i in range(int(mix["check_steps"])):
            x, y = next(feed)
            if first is None:
                first = (x, y)      # as the loader placed it, for the replay
            batches.append((np.asarray(x), np.asarray(y)))
            state, metrics = step(state, x, y)
            losses.append(metrics["loss"])
        dnorm = change_norms(state.params, aux["key"])
        got = {"losses": [float(l) for l in losses], "first_batch": first,
               "change_norms": {k: np.asarray(v) for k, v in dnorm.items()}}
    return state, batches, got


def first_gradient(cell, step, aux, got) -> None:
    """The first gradient as the optimizer got it, worked out from Adam's
    first moment after one step.  Called once the window's state is freed
    (two states do not fit): a fresh state from the same seed goes through
    the SAME compiled step on the first batch as the loader placed it; its
    loss joins the losses compared.  One stacked leaf at a time, each split
    over the chips like the reference's arrays and taken to the host at
    once: beside the state and the step's reserved temporaries a whole
    second gradient does not fit (gpt2-xl on four chips ran out of memory
    on it)."""
    import jax

    cfg = cell.config
    weights = harness.weights_for(cell)
    b1 = float(cell.traffic["optimizer"]["b1"])
    shard = reference_sharding(cell, aux["devices"])
    t = time.perf_counter()
    live = sum(a.nbytes for a in jax.live_arrays())
    state = aux["init"](aux["key"])
    state, metrics = step(state, *got.pop("first_batch"))
    mu = _adam_mu(state.opt_state)
    grad = {}
    for name in shard:
        leaf = jax.jit(
            lambda m, name=name: weights.from_program_tree(
                m, cfg, only=name) / (1.0 - b1),
            out_shardings=shard[name])(mu)
        grad[name] = np.asarray(leaf)
        del leaf
    got["grad"], got["replay_loss"] = grad, float(metrics["loss"])
    say(f"first gradient: {live / 2 ** 30:.3f} GiB of arrays were live "
        f"before the fresh state; first step again (loss "
        f"{got['replay_loss']:.5f}, in set-up {got['losses'][0]:.5f}) and "
        f"{sum(g.nbytes for g in grad.values()) / 2 ** 30:.2f} GiB of "
        f"gradient to the host in {time.perf_counter() - t:.1f}s (outside "
        f"setup_s and the window)")


def window(state, step, feed, aux, seconds: float | None = None,
           steps: int | None = None, annotate=None, depth: int = 1):
    """Steps until `seconds` have passed (or `steps` were issued), up to
    `depth` steps queued behind the one that runs, as the CLI's epoch loop
    queues them (it waits for no step).  Issuing stops when the steps still
    queued will fill the time; the queue is drained and the time runs to
    the last step's end, so the rate is whole steps over all their time.
    A host that stops for less time than the queue holds costs the device
    nothing (a stop makes the time a step seems to take longer, so the
    window may then end early, never late); `dry` counts the dispatches
    that found the queue empty, each a stretch in which the device may
    have waited for the host."""
    import collections
    import contextlib

    note = annotate or (lambda name: contextlib.nullcontext())
    queue, done, n, dry = collections.deque(), [], 0, 0
    t0 = time.perf_counter()
    while True:
        x, y = next(feed)
        if queue and queue[-1].is_ready():
            dry += 1
        with note("bench:train_step"):
            state, metrics = step(state, x, y)
        n += 1
        queue.append(metrics["loss"])
        if len(queue) > depth:
            queue.popleft().block_until_ready()
            done.append(time.perf_counter())
        # stop issuing once the steps still queued will fill the time
        now = time.perf_counter() - t0
        ahead = len(queue) * (done[-1] - t0) / len(done) if done else 0.0
        if n == steps or (seconds is not None and now + ahead >= seconds):
            break
    while queue:
        last = queue.popleft()
        last.block_until_ready()
        done.append(time.perf_counter())
    gaps = [b - a for a, b in zip([t0] + done, done)]
    return state, {"steps": n, "elapsed_s": done[-1] - t0, "step_s": gaps,
                   "last_loss": float(last), "dry": dry}


def check(cell, batches, got, aux, devices, quant=None) -> list[dict]:
    """The plain reference follows the same first steps from the same
    seeded weights; every number compared comes back with its limit.
    `quant` also puts the lower-precision control in the program's place:
    then (the program's numbers, the control's numbers) come back."""
    import jax
    import jax.numpy as jnp

    cfg, mix = cell.config, cell.traffic
    ref, weights = harness.reference_for(cell), harness.weights_for(cell)
    limits = cell.limits
    sharding = reference_sharding(cell, aux["devices"])
    make = jax.jit(lambda k: weights.make_weights(k, cfg, jnp.float32),
                   out_shardings=sharding)
    opt = mix["optimizer"]

    def follow(q):
        w0 = make(aux["key"])
        losses, g1, w3 = ref.train_steps(
            w0, [(jnp.asarray(x), jnp.asarray(y)) for x, y in batches],
            opt, weights.decay_mask(w0), int(mix["reference_rows"]), q)
        change = jax.tree.map(jnp.subtract, w3, w0)
        return {"losses": [float(l) for l in losses],
                "grad": {k: np.asarray(v) for k, v in g1.items()},
                "change_norms": {k: np.asarray(v) for k, v in
                                 weights.flat_leaf_norms(change).items()}}

    t = time.perf_counter()
    with ref.highest():
        want = follow(None)
        control = follow(quant) if quant is not None else None
    say(f"reference: {len(batches)} steps of {aux['rows']} rows in "
        f"{time.perf_counter() - t:.1f}s (outside setup_s and the window)")

    def worst_leaf(a: dict, b: dict, skip=()):
        """Largest gap between the two sides' norms of one leaf, against
        the reference's norm of that leaf or of the median leaf,
        whichever is larger."""
        med = statistics.median(float(v) for k in b for v in b[k])
        worst, where = 0.0, ""
        for k in b:
            gap = np.abs(a[k] - b[k]) / np.maximum(b[k], med)
            for i in range(len(gap)):
                if (k, i) not in skip and float(gap[i]) >= worst:
                    worst, where = float(gap[i]), f"{k}[{i}]"
        return worst, where

    norms = weights.host_leaf_norms     # gradients are on the host

    def diff_norms(a, b):
        return norms({k: a[k] - b[k] for k in b})

    # a gradient that is zero in exact arithmetic (the key bias: softmax
    # ignores a shift of all scores) is rounding noise on both sides, and
    # Adam scales noise up to a full-size step: such a leaf's change says
    # nothing, so it is left out of the change comparison, by name
    want_norms = norms(want["grad"])
    g_med = statistics.median(float(v) for k in want_norms
                              for v in want_norms[k])
    noise = {(k, i) for k, v in want_norms.items()
             for i in range(len(v)) if float(v[i]) < 1e-3 * g_med}
    say(f"leaves whose reference gradient is under 1e-3 of the median "
        f"leaf's, left out of the change comparison: "
        f"{sorted({k for k, _ in noise})} ({len(noise)} arrays)")

    def compare(got):
        # the program's first step run again after the window counts too
        again = [got["replay_loss"]] if "replay_loss" in got else []
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(
            got["losses"] + again, want["losses"] + want["losses"][:1]))
        g_gap, g_where = worst_leaf(norms(got["grad"]), want_norms)
        diff = diff_norms(got["grad"], want["grad"])
        d_gap, d_where = worst_leaf(
            {k: want_norms[k] + diff[k] for k in diff}, want_norms)
        c_gap, c_where = worst_leaf(got["change_norms"],
                                    want["change_norms"], noise)
        say("losses " + " ".join(f"{l:.5f}" for l in got["losses"] + again)
            + " | reference " + " ".join(f"{l:.5f}"
                                         for l in want["losses"]))
        return [
            {"name": "loss_rel_gap", "value": loss_gap,
             "limit": limits["loss_rel_gap"]},
            {"name": "first_grad_norm_worst_leaf_gap", "value": g_gap,
             "limit": limits["first_grad_norm_worst_leaf_gap"],
             "note": g_where},
            {"name": "first_grad_diff_norm_worst_leaf", "value": d_gap,
             "limit": limits["first_grad_diff_norm_worst_leaf"],
             "note": d_where},
            {"name": "param_change_norm_worst_leaf_gap", "value": c_gap,
             "limit": limits["param_change_norm_worst_leaf_gap"],
             "note": c_where},
        ]

    if control is None:
        return compare(got)
    return compare(got), compare(control)


def readings(cell, seed: int, seconds: float, devices, clock,
             quant: str | None):
    """Set-up and the first steps only (training's numbers need no
    window), then the comparison, with the control beside it."""
    import jax

    state, step, feed, aux = build(cell, seed, devices, clock)
    state, batches, got = first_steps(cell, state, step, feed, aux, clock)
    feed.close()
    del state
    gc.collect()
    first_gradient(cell, step, aux, got)
    del step
    aux.pop("init")
    jax.clear_caches()
    gc.collect()
    return check(cell, batches, got, aux, devices, quant)


def run(cell, seed: int, seconds: float, trace: bool, clock, meter,
        devices, tracer) -> dict:
    import jax

    mix = cell.traffic
    depth = int(mix["steps_queued"])
    state, step, feed, aux = build(cell, seed, devices, clock)
    state, batches, got = first_steps(cell, state, step, feed, aux, clock)
    traced = None
    if trace:
        with clock.phase("trace_start"):
            tracer.start()
    setup_s = clock.close(meter)
    clock.report(meter)
    gc.collect()
    gc.freeze()             # hold the host still: no collection mid-window
    meter.mark()
    if trace:
        with tracer.window():
            state, short = window(state, step, feed, aux,
                                  steps=int(mix["trace_steps"]),
                                  annotate=tracer.annotate, depth=depth)
        traced = tracer.stop()
        say(f"traced {short['steps']} steps in {short['elapsed_s']:.2f}s")
        seconds = max(1.0, seconds - short["elapsed_s"])
    state, win = window(state, step, feed, aux, seconds, depth=depth)
    compiles = meter.since_mark()
    gc.unfreeze()
    peak = harness.memory_peak_bytes(devices)
    feed.close()
    tokens_per_s = win["steps"] * aux["tokens_per_step"] / win["elapsed_s"]
    say(f"window: {win['steps']} steps of {aux['tokens_per_step']} tokens "
        f"in {win['elapsed_s']:.3f}s, {compiles} compiles inside it, last "
        f"loss {win['last_loss']:.4f}, step median "
        f"{statistics.median(win['step_s'][1:] or win['step_s']) * 1e3:.2f}"
        f"ms, longest wait for a step {max(win['step_s']) * 1e3:.0f}ms; up "
        f"to {depth} steps queued, {win['dry']} dispatches found the queue "
        f"empty")
    del state
    gc.collect()
    first_gradient(cell, step, aux, got)
    del step
    aux.pop("init")
    jax.clear_caches()
    gc.collect()
    checks = check(cell, batches, got, aux, devices)
    return {
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "attempted": win["steps"],
        "failed": 0 if np.isfinite(win["last_loss"]) else win["steps"],
        "checks": checks,
        "compiles_in_window": compiles,
        "memory_peak_bytes": peak,
        "trace": traced,
        "samples": {"train_step_s": win["step_s"][1:] or win["step_s"]},
        "counters": {"tokens_per_step": aux["tokens_per_step"],
                     "rows": aux["rows"], "steps": win["steps"],
                     "train_tokens_per_s": tokens_per_s},
    }
