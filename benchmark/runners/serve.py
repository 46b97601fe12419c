"""Runner for ``kind: serve`` mixes: the paged engine under a closed loop.

The engine is built as the CLI's ``--serve --paged`` builds it (the
package's model builder and ``PagedEngine`` with the mix's settings); the
weights are the benchmark's, made on the device from the seed in the type
they are served in.  The whole trace is queued at tick 0, so a slot is
refilled the tick it frees: `callers` callers with no think time.  The
benchmark takes its own clock in ``on_tick`` and around the engine's two
program dispatches, counts tokens as they are processed (prompt tokens
when their chunk is issued, output tokens when emitted), and closes the
window by raising out of its hooks: at the first dispatch or tick report
past the deadline.

After the window the engine is freed and the plain reference is run once
over a seeded sample of the requests the window served tokens to, finished
or not (the reference is teacher-forced and needs no whole answer), the
one with the most positions among them, prompt and served tokens together:
the number compared is how far each served token's logit lies below the
reference's best.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmark import harness, traffic_gen
from benchmark.harness import say


class _WindowClosed(Exception):
    """Raised out of on_tick to end engine.run() when the time is up."""


class _Probe:
    """The benchmark's eyes on one engine: wraps the two program
    dispatches and the prefill planner's scatter targets (all reachable
    from outside), and is the ``on_tick`` hook."""

    def __init__(self, engine, engine_module, prompt_len: dict,
                 annotate):
        self.engine, self.module = engine, engine_module
        self.prompt_len, self.note = prompt_len, annotate
        self.deadline = None
        self.reset()
        self._chunk, self._decode = engine._chunk_prog, engine._decode
        self._targets = engine_module.write_targets
        engine._chunk_prog = self._spy(self._chunk, "chunk_dispatch",
                                       self._on_chunk)
        engine._decode = self._spy(self._decode, "decode_dispatch", None)
        engine_module.write_targets = self._write_targets

    def reset(self):
        self.prompt_tokens = self.output_tokens = 0
        self.chunks = self.decode_ticks = 0
        self.ticks_with_chunk = 0
        self.last_emit: dict = {}
        self.tokens: dict = {}
        self.gaps: list = []
        self.tick_s: list = []
        self.chunk_s: list = []
        self.slots_sum = self.live_sum = self.blocks_sum = 0
        self.bad = 0
        self._pending_live = 0
        self._chunk_seen = False
        self._t_chunk = None
        self._t_report = None
        self.longest = (0.0, 0)     # longest report-to-report gap, its tick

    def remove(self):
        self.engine._chunk_prog, self.engine._decode = (self._chunk,
                                                        self._decode)
        self.module.write_targets = self._targets

    def _spy(self, prog, name, after):
        probe = self

        class Spy:
            traces = property(lambda s: prog.traces)
            _jit = prog._jit

            def __call__(s, *args):
                probe._close_chunk()
                t = time.perf_counter()
                if probe.deadline is not None and t >= probe.deadline:
                    raise _WindowClosed     # before the work, not after
                with probe.note("bench:" + name):
                    out = prog(*args)
                if after is not None:
                    after(t)
                return out

        return Spy()

    def _write_targets(self, *args, **kw):
        out = self._targets(*args, **kw)
        self._pending_live = int(np.count_nonzero(out[2]))
        return out

    def _close_chunk(self):
        """A chunk's host time: its dispatch to the next dispatch or
        tick report (the engine waits for the chunk in between)."""
        if self._t_chunk is not None:
            self.chunk_s.append(time.perf_counter() - self._t_chunk)
            self._t_chunk = None

    def _on_chunk(self, t_dispatch):
        self._t_chunk = t_dispatch
        self.chunks += 1
        self.prompt_tokens += self._pending_live
        self._pending_live = 0
        self._chunk_seen = True

    def on_tick(self, report):
        now = time.perf_counter()
        self._close_chunk()
        if self._t_report is not None:
            self.longest = max(self.longest,
                               (now - self._t_report, report.tick))
        self._t_report = now
        with self.note("bench:on_tick"):
            for uid, tok in report.emitted:
                self.output_tokens += 1
                last = self.last_emit.get(uid)
                if last is not None:
                    self.gaps.append(now - last)
                self.last_emit[uid] = now
                self.tokens.setdefault(uid, []).append(int(tok))
            self.bad += sum(1 for ok in report.finite.values() if not ok)
            if report.kind == "decode":
                self.decode_ticks += 1
                self.ticks_with_chunk += self._chunk_seen
                self._chunk_seen = False
                self.tick_s.append(report.elapsed_s)
                self.slots_sum += len(report.slots)
                self.live_sum += sum(
                    self.prompt_len[uid] + len(self.tokens[uid])
                    for uid, _ in report.emitted)
                self.blocks_sum += self.engine.manager.in_use
        if self.deadline is not None and now >= self.deadline:
            raise _WindowClosed


def _serve(engine, probe, requests, seconds, Request):
    """One closed-loop window; returns its length in seconds."""
    reqs = [Request(uid=u, prompt=p, max_new_tokens=n)
            for u, p, n in requests]
    probe.reset()
    t0 = time.perf_counter()
    probe.deadline = None if seconds is None else t0 + seconds
    try:
        engine.run(reqs, on_tick=probe.on_tick)
        if seconds is not None:
            raise harness.BenchFailure(
                f"the queue of {len(reqs)} requests ran dry before the "
                f"window closed; raise queue_cycles in the traffic file")
    except _WindowClosed:
        pass
    return time.perf_counter() - t0


def _fresh(engine):
    """Fresh pools, block manager and prefix index, same compiled
    programs.  The old pools go first: two sets do not fit the chip."""
    engine.pools = None
    gc.collect()
    engine.reset()


def build(cell, seed: int, devices, clock):
    import jax
    import jax.numpy as jnp

    with clock.phase("import_program"):
        from distributed_deep_learning_tpu.data.tokens import lm_dataset
        from distributed_deep_learning_tpu.serve import engine as eng_mod
        from distributed_deep_learning_tpu.serve.scheduler import Request
        from distributed_deep_learning_tpu.utils.config import parse_args
        from distributed_deep_learning_tpu.workloads import get_spec

    cfg, mix = cell.config, cell.traffic
    argv = cfg["cli"][1:] + ["-m", "sequential", "--serve", "--paged"]
    say("program argv: " + " ".join(cfg["cli"][:1] + argv)
        + f"; engine {mix['engine']}")
    with clock.phase("build_engine"):
        config = parse_args(argv, workload=cfg["cli"][0])
        spec = get_spec(cfg["cli"][0])
        shape_only = lm_dataset(
            np.ones((1, cfg["n_positions"] + 1), np.int32),
            vocab_size=cfg["vocab_size"])
        model = spec.build_model(config, shape_only)
    weights = harness.weights_for(cell)
    key = harness.seed_key(seed)
    dtype = jnp.dtype(cfg["serve_param_dtype"])
    with clock.phase("weights"):
        params = jax.block_until_ready(jax.jit(
            lambda k: weights.to_program_tree(
                weights.make_weights(k, cfg, dtype), cfg))(key))
    with clock.phase("build_engine"):
        kw = {k: v for k, v in mix["engine"].items() if v is not None}
        engine = eng_mod.PagedEngine(model, params, **kw)
        jax.block_until_ready(engine.pools)
    return engine, eng_mod, Request, key


def run(cell, seed: int, seconds: float, trace: bool, clock, meter,
        devices, tracer, keep_sample: bool = False) -> dict:
    import jax

    cfg, mix = cell.config, cell.traffic
    engine, eng_mod, Request, key = build(cell, seed, devices, clock)
    requests = traffic_gen.serve_requests(mix, seed, cfg["vocab_size"],
                                          mix["engine"]["max_len"])
    prompt_len = {u: len(p) for u, p, _ in requests}
    probe = _Probe(engine, eng_mod, prompt_len, tracer.annotate)
    try:
        with clock.phase("compile_and_warm"):
            # the cell's two programs, through the window's own call: one
            # request of two chunks and two tokens compiles both
            chunk = mix["engine"]["prefill_chunk"]
            warm = np.random.default_rng(seed ^ 0x5EED).integers(
                1, cfg["vocab_size"], size=chunk + 1)
            prompt_len[-1] = len(warm)
            _serve(engine, probe, [(-1, warm, 2)], None, Request)
            engine._cow(0, 0)   # the block-copy program (trash onto itself)
            _fresh(engine)
        traced = None
        if trace:
            with clock.phase("trace_start"):
                tracer.start()
        setup_s = clock.close(meter)
        clock.report(meter)
        gc.collect()
        gc.freeze()         # hold the host still: no collection mid-window
        meter.mark()
        if trace:
            # a short traced window of the same traffic, then the timed one
            with tracer.window():
                short = _serve(engine, probe, requests,
                               float(mix["trace_seconds"]), Request)
            traced = tracer.stop()
            say(f"traced {probe.decode_ticks} ticks in {short:.2f}s")
            _fresh(engine)
            seconds = max(1.0, seconds - short)
        elapsed = _serve(engine, probe, requests, seconds, Request)
        compiles = meter.since_mark()
        gc.unfreeze()
        peak = harness.memory_peak_bytes(devices)
    finally:
        probe.remove()

    total = probe.prompt_tokens + probe.output_tokens
    budget = {u: n for u, _, n in requests}
    finished = {u: t for u, t in probe.tokens.items()
                if len(t) == budget[u]}
    gaps = sorted(probe.gaps)
    p95 = gaps[min(len(gaps) - 1, int(0.95 * len(gaps)))] if gaps else 0.0
    say(f"window: {elapsed:.3f}s, {probe.prompt_tokens} prompt + "
        f"{probe.output_tokens} output tokens, {len(finished)} requests "
        f"finished, {len(probe.tokens)} started, {compiles} compiles "
        f"inside it")
    say(f"ticks: {probe.decode_ticks} decode ticks, "
        f"{probe.ticks_with_chunk} carried a chunk "
        f"({100.0 * probe.ticks_with_chunk / max(probe.decode_ticks, 1):.1f}"
        f"%), {probe.chunks} chunks; ITL samples {len(gaps)}, median "
        f"{statistics.median(gaps) * 1e3 if gaps else 0:.2f}ms, p95 "
        f"{p95 * 1e3:.2f}ms, {sum(g > p95 for g in gaps)} beyond it; the "
        f"longest wait between two tick reports {probe.longest[0]:.3f}s, "
        f"before tick {probe.longest[1]} (a stall of the host or the "
        f"machine shows here)")
    say(f"engine: chunk traces {engine._chunk_prog.traces}, decode traces "
        f"{engine._decode.traces}, blocks {engine.num_blocks}, kv cache "
        f"{engine.kv_cache_bytes / 2 ** 30:.2f} GiB, manager "
        f"{engine.manager.stats()}")

    sample = _sample(probe.tokens, requests, seed,
                     int(mix["check_requests"]))
    say(f"sample for the reference: {len(sample)} of {len(probe.tokens)} "
        f"requests with served tokens, "
        f"{sum(len(t) == budget[u] for u, t in probe.tokens.items())} of "
        f"those finished")
    ticks = max(probe.decode_ticks, 1)
    counters = {
        "mean_decoding_slots_share":
            probe.slots_sum / ticks / engine.max_slots,
        "mean_blocks_in_use_share":
            probe.blocks_sum / ticks / engine.num_blocks,
        "mean_live_tokens": probe.live_sum / ticks,
        "chunk_tick_share": probe.ticks_with_chunk / ticks,
        "prompt_tokens": probe.prompt_tokens,
        "output_tokens": probe.output_tokens,
    }
    samples = {"decode_tick_s": probe.tick_s, "chunk_s": probe.chunk_s,
               "itl_s": probe.gaps}
    n_bad = probe.bad
    del engine, probe
    jax.clear_caches()
    gc.collect()
    checks = check(cell, sample, key, devices)
    extra = {"sample": sample, "key": key} if keep_sample else {}
    return {
        **extra,
        "end_to_end": {"serve_total_tokens_per_s": total / elapsed,
                       "serve_itl_p95_ms": p95 * 1e3, "setup_s": setup_s},
        "attempted": len(finished) + n_bad, "failed": n_bad,
        "checks": checks, "compiles_in_window": compiles,
        "memory_peak_bytes": peak, "trace": traced,
        "samples": samples, "counters": counters,
    }


def _sample(served: dict, requests, seed: int, n: int) -> list:
    """[(prompt, served tokens)]: of the requests the window served
    tokens to, the one with the most positions and a seeded draw of the
    others."""
    if not served:
        raise harness.BenchFailure("no token was served inside the window; "
                                   "nothing to compare with the reference")
    by_uid = {u: p for u, p, _ in requests}
    order = sorted(served, key=lambda u: (-(len(by_uid[u])
                                            + len(served[u])), u))
    rest = order[1:]
    np.random.default_rng(seed).shuffle(rest)
    picked = [order[0]] + rest[:n - 1]
    return [(by_uid[u], np.asarray(served[u])) for u in picked]


def served_gaps(cell, sample, key, devices, quant=None):
    """Per served token, how far its float32 reference logit lies below
    the reference's best, over the sample.  With `quant` the tokens
    judged are not the served ones but those the lower-precision
    reference puts first at each served position (the control)."""
    import jax
    import jax.numpy as jnp

    cfg = cell.config
    ref, weights = harness.reference_for(cell), harness.weights_for(cell)
    width = max(len(p) + len(t) for p, t in sample)
    width = -(-width // 128) * 128 if width > 128 else width
    toks = np.ones((len(sample), width), np.int32)
    for i, (p, t) in enumerate(sample):
        toks[i, :len(p)] = p
        toks[i, len(p):len(p) + len(t)] = t
    with ref.highest():
        w = jax.jit(lambda k: weights.make_weights(
            k, cfg, jnp.dtype(cfg["serve_param_dtype"])))(key)
        rows = []
        for i in range(len(sample)):        # a row at a time: it fits
            row = jnp.asarray(toks[i:i + 1])
            if quant is None:
                gaps, _ = ref.token_gaps(w, row)
                gaps = np.asarray(gaps)[0]
            else:
                _, first = ref.token_gaps(w, row, quant)
                chosen = jnp.concatenate(
                    [first, jnp.ones((1, 1), first.dtype)], axis=1)
                gaps = np.asarray(ref.gaps_of(w, row, chosen))[0]
            p, t = sample[i]
            rows.append(gaps[len(p) - 1:len(p) + len(t) - 1])
    return np.concatenate(rows)


def readings(cell, seed: int, seconds: float, devices, clock,
             quant: str | None):
    """A short window at the cell's own load (long enough to finish the
    mix's longest requests), then the comparison, with the control's
    beside it."""
    from benchmark.cellrun import Tracer, compile_meter

    keep = run(cell, seed, seconds, False, clock, compile_meter(), devices,
               Tracer(False), keep_sample=True)
    sound = keep["checks"]
    if quant is None:
        return sound
    return sound, check(cell, keep["sample"], keep["key"], devices, quant)


def check(cell, sample, key, devices, quant=None) -> list[dict]:
    t = time.perf_counter()
    gaps = served_gaps(cell, sample, key, devices, quant)
    say(f"reference: {len(sample)} requests, {len(gaps)} served tokens "
        f"(longest {max(len(p) + len(s) for p, s in sample)} positions) in "
        f"{time.perf_counter() - t:.1f}s (outside setup_s and the window); "
        f"{int(np.count_nonzero(gaps))} tokens are not the reference's "
        f"first choice")
    limits = cell.limits
    return [
        {"name": "served_gap_widest", "value": float(np.max(gaps)),
         "limit": limits["served_gap_widest"]},
        {"name": "served_gap_mean", "value": float(np.mean(gaps)),
         "limit": limits["served_gap_mean"]},
    ]
