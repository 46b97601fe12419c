"""Continuous-batching decode engine: two programs, compiled once.

vLLM-style continuous batching mapped onto XLA's fixed-shape world:

* **Decode** is ONE compiled program for the engine's lifetime — a
  1-token step over ALL slots (the model's own tested single-sequence
  cached decode, ``vmap``-ed over the slot axis of the static slot
  table) followed by the shared sampling head.  Requests of any prompt
  length, arriving at any time, never change its shapes.
* **Prefill** is one compiled program PER POWER-OF-TWO BUCKET (a handful
  for the engine's lifetime): the prompt is padded to the bucket, run as
  one multi-token cached call, its position counters pinned back to the
  true length (:func:`..serve.cache.fix_counters` — padding leaves no
  numerical trace), and the filled cache written into the designated
  slot.  Slot index and true length are traced scalars, so one program
  serves every slot and every length inside a bucket.

Both programs take the slot table as a DONATED argument on accelerator
backends: the tick does not copy the cache in HBM, it updates it in
place (donation is skipped on CPU, which does not implement it and
would warn every call).

Compilation counts are PROVEN, not assumed: each program runs through
:class:`CountingJit`, whose counter increments at trace time only —
``tests/test_serve.py`` asserts the decode count stays 1 across a trace
of mixed lengths and staggered arrivals.
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from distributed_deep_learning_tpu.models.transformer import (
    CausalLM, cached_apply, cached_apply_counting, make_decode_model,
    sample_tokens,
    validate_sampling)
from distributed_deep_learning_tpu.obs import memory as obs_memory
from distributed_deep_learning_tpu.obs import runlog
from distributed_deep_learning_tpu.obs import trace as obs_trace
from distributed_deep_learning_tpu.obs.metrics import MetricsRegistry
from distributed_deep_learning_tpu.obs.window import LiveSignals
from distributed_deep_learning_tpu.ops.paged_decode_pallas import (
    latent_bytes_a_row)
from distributed_deep_learning_tpu.serve import cache as slot_cache
from distributed_deep_learning_tpu.serve import paged
from distributed_deep_learning_tpu.serve import quant
from distributed_deep_learning_tpu.serve import spec as spec_mod
from distributed_deep_learning_tpu.serve.load import slo_report
from distributed_deep_learning_tpu.serve.prefill import (chunk_tokens,
                                                         plan_chunks,
                                                         write_targets)
from distributed_deep_learning_tpu.serve.scheduler import (PagedScheduler,
                                                           Request,
                                                           SlotScheduler)


class CountingJit:
    """``jax.jit`` wrapper that counts traces.

    jit retraces exactly when a call presents a new (shape, dtype,
    static-arg) signature — i.e. when it must compile — so the trace
    count IS the compile count the tests assert on.  (A cache-evicted
    retrace would also count: the counter is conservative, never
    flattering.)

    ``name`` is the program's: the compiled module is ``jit_<name>`` in a
    profiler trace and the compile log, so an engine's programs are told
    apart there.  ``span`` names the :func:`..obs.trace.span` every call
    runs under: opened HERE, not by the caller, so it nests inside
    whatever wraps the program object from outside (the benchmark's
    dispatch annotation), on the same clock.

    What code deep inside says of the program as it is traced
    (``obs.compile_log.gather``: the grouped expert products' path and
    tiles) becomes its notes in the compile log, and `said`, the owner's
    ``program name -> {event: text}`` of each program's newest trace (the
    owner's, because a caller may wrap the program object).  `calls` is the
    owner's too: every call, traced or not, adds one to its ``"programs"``.
    """

    def __init__(self, fn, name: Optional[str] = None,
                 span: Optional[str] = None, said: Optional[dict] = None,
                 calls: Optional[dict] = None, **jit_kwargs):
        self.traces = 0
        self.name = name or fn.__name__.strip("_<>")
        self._span = span
        self._calls = calls

        def counted(*args):
            self.traces += 1   # runs at trace time only
            with runlog.compile_log.notes_for(f"jit({self.name})") as notes:
                out = fn(*args)
            if said is not None:
                said[self.name] = notes
            return out

        counted.__name__ = counted.__qualname__ = self.name
        self._jit = jax.jit(counted, **jit_kwargs)

    def __call__(self, *args):
        if self._calls is not None:
            self._calls["programs"] += 1
        if self._span is None:
            return self._jit(*args)
        with obs_trace.span(self._span, program=self.name):
            return self._jit(*args)


@dataclasses.dataclass(frozen=True)
class TickReport:
    """What one engine tick produced, handed to ``run(on_tick=...)``
    BEFORE the tokens are recorded into the scheduler.

    This ordering is the crash-containment contract: a hook that raises
    (watchdog anomaly, injected fault) discards the tick's tokens, so a
    supervisor that replays from the committed streams regenerates them
    — greedy outputs stay bit-identical to a fault-free run.

    ``finite`` carries DEVICE-computed per-request flags (``isfinite``
    over the sampled hidden state): NaN/inf anywhere in a request's
    attention window poisons its flag, which is how KV corruption
    surfaces one tick after injection.  ``logprob`` is the chosen
    token's log-probability under the engine's own head — the drift
    signal canary comparison feeds on.
    """

    tick: int
    kind: str                      # "prefill" | "decode"
    elapsed_s: float
    emitted: list                  # [(uid, token), ...] in commit order
    finite: dict                   # uid -> bool
    logprob: dict                  # uid -> float
    slots: list                    # active slot indices this tick
    engine: object
    queue_depth: int = 0


@dataclasses.dataclass
class _CanaryState:
    """Live canary: candidate weights serving a slice of slots.

    The engine runs the SAME compiled decode program twice per tick —
    once with the stable params (canary slots' KV writes routed to
    trash), once with the candidate params (everyone else's writes
    trashed) — and merges tokens per slot.  Same shapes/dtypes both
    calls, so the trace count never moves.  Per canary slot per tick it
    feeds ``observe`` with the old-vs-new argmax agreement and chosen
    log-prob drift; the reload manager turns those into windowed
    signals and a promote/rollback verdict."""

    params: object
    slots: frozenset
    observe: Optional[Callable] = None
    compared: int = 0
    agreed: int = 0
    drift_sum: float = 0.0
    nonfinite: int = 0

    def note(self, agree: bool, drift: float, finite: bool,
             now: float) -> None:
        self.compared += 1
        self.agreed += int(agree)
        self.drift_sum += drift
        self.nonfinite += int(not finite)
        if self.observe is not None:
            self.observe(agree=agree, drift=drift, finite=finite, now=now)

    def summary(self) -> dict:
        return {
            "compared": self.compared,
            "agreed": self.agreed,
            "acceptance": (self.agreed / self.compared
                           if self.compared else None),
            "mean_abs_logprob_drift": (self.drift_sum / self.compared
                                       if self.compared else None),
            "nonfinite": self.nonfinite,
            "canary_slots": sorted(self.slots),
        }


def _check_swappable(old, new) -> None:
    """New params must be drop-in for the compiled programs: identical
    tree structure, per-leaf shape and dtype — anything else would
    retrace (or worse, silently reshape)."""
    old_l, old_t = jax.tree_util.tree_flatten(old)
    new_l, new_t = jax.tree_util.tree_flatten(new)
    if old_t != new_t:
        raise ValueError("swap_params: new params tree structure differs "
                         "from the engine's (cannot hot-swap)")
    for i, (a, b) in enumerate(zip(old_l, new_l)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(
                f"swap_params: leaf {i} mismatch — engine has "
                f"{a.shape}/{a.dtype}, new params have {b.shape}/"
                f"{b.dtype}; hot swap requires identical geometry")


def default_buckets(max_len: int, floor: int = 8) -> tuple[int, ...]:
    """Powers of two from ``floor`` up to (and always including)
    ``max_len`` — the prefill shape vocabulary."""
    out = []
    b = floor
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


class ServeEngine:
    """Continuous-batching server for a trained :class:`CausalLM`.

    ``run(requests)`` drives a whole trace; each tick advances every
    active slot by one token, retires rows on EOS or budget, and
    refills freed slots from the arrived queue — throughput tracks slot
    occupancy, not the slowest request.
    """

    def __init__(self, model: CausalLM, params, *, max_slots: int = 8,
                 max_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 eos_id: Optional[int] = None, temperature: float = 0.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 rng=None, donate: Optional[bool] = None,
                 kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None):
        validate_sampling(top_k, top_p)
        quant.check_dtype("kv_dtype", kv_dtype)
        quant.check_dtype("weight_dtype", weight_dtype)
        if kv_dtype == "int8":
            raise ValueError(
                "kv_dtype='int8' requires the paged engine (PagedEngine /"
                " --paged): int8 KV stores per-position scales alongside "
                "the block pools; the v1 slot table supports bf16 only")
        self.kv_dtype, self.weight_dtype = kv_dtype, weight_dtype
        # the model's working precision, captured BEFORE the params go
        # to their at-rest form: every compiled impl dequantizes back to
        # this dtype at its top (XLA fuses the upcast into the matmuls)
        self.compute_dtype = jax.tree.leaves(params)[0].dtype
        if weight_dtype is not None:
            params = quant.quantize_weights(params, weight_dtype)
        self.model, self.params = model, params
        self.lm = make_decode_model(model)
        self.max_slots = int(max_slots)
        self.max_len = int(max_len if max_len is not None else model.max_len)
        if self.max_len > model.max_len:
            raise ValueError(f"max_len {self.max_len} exceeds the model's "
                             f"max_len {model.max_len}")
        if prefill_buckets is None:
            self.buckets = default_buckets(self.max_len)
        else:
            self.buckets = tuple(sorted({int(b) for b in prefill_buckets}))
            if not self.buckets or self.buckets[0] < 1:
                raise ValueError(f"bad prefill buckets {prefill_buckets}")
            if self.buckets[-1] > self.max_len:
                raise ValueError(f"prefill bucket {self.buckets[-1]} "
                                 f"exceeds max_len {self.max_len}")
            if self.buckets[-1] < self.max_len:
                # top bucket: any admissible prompt must fit some bucket
                self.buckets += (self.max_len,)
        self.eos_id = eos_id
        self.temperature, self.top_k, self.top_p = temperature, top_k, top_p
        # bucket padding uses the pad id (recorded invalid in the cache);
        # pad-free models pad with id 0 — those positions are causally
        # unreachable after the counter fixup, so the id never matters
        self.pad_fill = model.pad_id if model.pad_id is not None else 0
        self._key = rng if rng is not None else jax.random.key(0)
        if donate is None:
            donate = jax.default_backend() != "cpu"
        dk = {"donate_argnums": (1,)} if donate else {}
        self.slots = self._alloc_slots()
        # exact KV footprint by construction: the allocated cache pytree's
        # own shapes (what the analytic layers x 2 x slots x len x kv-heads
        # x head-dim computation must reproduce bit-exactly)
        self.kv_cache_bytes = obs_memory.pytree_bytes(self.slots)
        self._prefill = CountingJit(self._prefill_impl, "serve_prefill",
                                    **dk)
        self._decode = CountingJit(self._decode_impl, "serve_decode", **dk)
        self.restarts = 0
        self.weight_swaps = 0

    # --- quantization shims (identity at full precision) ------------------
    def _alloc_slots(self):
        slots = slot_cache.allocate_slots(self.lm, self.max_slots,
                                          self.max_len)
        if self.kv_dtype == "bf16":
            slots = quant.cast_kv(slots, jnp.bfloat16)
        return slots

    def _wp(self, params):
        """At-rest params -> compute-dtype view (inside the jitted impl,
        so the upcast fuses into the consuming matmuls)."""
        if self.weight_dtype is None:
            return params
        return quant.dequantize_weights(params, self.compute_dtype)

    def _kv_in(self, cache):
        """Stored cache -> the model's working precision (the model's
        ``dynamic_update_slice`` writes are dtype-strict)."""
        if self.kv_dtype is None:
            return cache
        return quant.cast_kv(cache, self.compute_dtype)

    def _kv_out(self, cache):
        """Freshly-computed cache -> the slab's at-rest precision."""
        if self.kv_dtype is None:
            return cache
        return quant.cast_kv(cache, jnp.bfloat16)

    # --- the two compiled programs ---------------------------------------
    def _sample(self, params, hidden_last, key):
        """Sample tokens plus their log-probability and a finiteness
        flag per row.  ``params`` is an explicit TRACED argument — NOT a
        closure capture, which jit would bake into the compiled program
        as constants and hot weight swap would then silently miss."""
        toks, _ = sample_tokens(self.model, params, hidden_last, key,
                                temperature=self.temperature,
                                top_k=self.top_k, top_p=self.top_p)
        nl = self.model.logits_from({"params": params}, hidden_last)
        lp = jnp.take_along_axis(jax.nn.log_softmax(nl, axis=-1),
                                 toks[:, None], axis=-1)[:, 0]
        ok = jnp.isfinite(hidden_last).all(axis=-1)
        return toks, lp, ok

    def _prefill_impl(self, params, slots, tokens, slot, true_len, key):
        """(Pb,)-padded prompt -> slot ``slot`` filled, first token out."""
        params = self._wp(params)
        fresh = self._kv_in(slot_cache.fresh_slot(slots))
        hidden, new = cached_apply(self.lm, params, fresh, tokens[None])
        new = slot_cache.fix_counters(new, true_len)
        slots = slot_cache.write_slot(slots, self._kv_out(new), slot)
        # sample from the TRUE final position, not the padded tail
        h_last = jax.lax.dynamic_slice_in_dim(hidden[0], true_len - 1, 1)
        tok, lp, ok = self._sample(params, h_last, key)
        return slots, tok[0], lp[0], ok[0]

    def _decode_impl(self, params, slots, toks, key):
        """One token for every slot: the model's single-sequence cached
        decode vmapped over the slot axis, then one shared sampling."""
        params = self._wp(params)

        def one(per_slot, tok):
            c = self._kv_in(slot_cache.lift(per_slot))
            hidden, new = cached_apply(self.lm, params, c, tok[None, None])
            return slot_cache.unlift(self._kv_out(new)), hidden[0, 0]

        slots, h = jax.vmap(one)(slots, toks)     # h: (max_slots, d)
        toks, lp, ok = self._sample(params, h, key)
        return slots, toks, lp, ok

    # --- host side --------------------------------------------------------
    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if b >= prompt_len:
                return b
        raise ValueError(f"prompt length {prompt_len} exceeds the top "
                         f"prefill bucket {self.buckets[-1]}")

    def _validate(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens exceeds the slot "
                f"capacity max_len={self.max_len}")
        self.bucket_for(len(req.prompt))

    def _next_key(self):
        if self.temperature == 0.0:
            return self._key           # unused by greedy sampling
        self._key, sub = jax.random.split(self._key)
        return sub

    # --- resilience seams -------------------------------------------------
    def reset(self) -> None:
        """Warm restart after a contained fault: FRESH slot caches (any
        poisoned KV dies here), SAME compiled programs — the new cache
        pytree has identical shapes, so no program retraces and
        ``decode_compiles`` stays where it was."""
        self.slots = self._alloc_slots()
        self.restarts += 1

    def swap_params(self, new_params) -> None:
        """Hot weight swap between ticks: same tree/shapes/dtypes slide
        into the already-compiled programs (params are traced arguments,
        never baked constants), so no recompile happens.  Incoming
        weights are published full-precision; a quantized engine takes
        them to its at-rest form FIRST, so the geometry check compares
        like with like."""
        if self.weight_dtype is not None:
            new_params = quant.quantize_weights(new_params,
                                                self.weight_dtype)
        _check_swappable(self.params, new_params)
        self.params = new_params
        self.weight_swaps += 1

    def run(self, requests: Iterable[Request], telemetry=None,
            on_tick: Optional[Callable] = None, admission=None) -> dict:
        """Serve a whole trace; returns ``{"results", "errors", "stats"}``.

        ``results`` maps uid -> generated token array; ``stats`` carries
        the throughput/occupancy/compile accounting the CLI logs, plus a ``latency`` sub-dict (p50/p99 TTFT, inter-token,
        end-to-end seconds) from per-request histograms.  Latency anchors
        at the wall time a request's arrival tick is first REACHED — so
        TTFT includes queue wait under load, the user-visible number.

        ``telemetry`` (:class:`..obs.RunTelemetry`) routes the latency/
        queue instruments into the run-level registry and emits an
        ``obs_serve`` event; without it the engine keeps a private
        per-run registry (percentiles are reported either way).

        Validation is PER REQUEST at submit: an invalid request (oversize
        prompt, prompt + ``max_new_tokens`` beyond the slot capacity) is
        recorded under ``errors`` (uid -> message) and the rest of the
        batch completes — one bad request must not abort every other
        request already queued behind it.  (Malformed :class:`Request`
        construction still raises where the request is BUILT — that bug
        belongs to the caller, not the batch.)

        ``on_tick`` receives a :class:`TickReport` after every tick's
        compute but BEFORE its tokens are recorded — a raising hook
        discards the tick (the supervisor's containment seam).
        ``admission`` (:class:`..serve.admission.AdmissionController`)
        is consulted before each placement; shed requests land in
        ``errors`` with a ``"shed: ..."`` message.
        """
        sched = SlotScheduler(self.max_slots)
        n_req = 0
        errors: dict[int, str] = {}
        for req in requests:
            try:
                self._validate(req)
            except ValueError as e:
                errors[req.uid] = str(e)
                continue
            sched.submit(req)
            n_req += 1

        reg = telemetry.registry if telemetry is not None \
            else MetricsRegistry()
        h_ttft = reg.histogram("serve_ttft_seconds")
        h_itl = reg.histogram("serve_intertoken_seconds")
        h_e2e = reg.histogram("serve_e2e_seconds")
        h_tick = reg.histogram("serve_decode_tick_seconds")
        g_queue = reg.gauge("serve_queue_depth")
        g_occ = reg.gauge("serve_slot_occupancy")
        reg.gauge("serve_kv_cache_bytes").set(self.kv_cache_bytes)
        first_wall: dict[int, float] = {}  # uid -> first-token wall time

        tracer = getattr(telemetry, "tracer", None) \
            if telemetry is not None else None
        recorder = getattr(telemetry, "recorder", None) \
            if telemetry is not None else None
        live = LiveSignals()
        root_span: dict[int, int] = {}       # uid -> open request span
        last_tok_wall: dict[int, float] = {}  # uid -> last emit wall
        last_window_emit = -float("inf")

        def retire(req, now):
            """Observe a retired request's TTFT-anchored latencies."""
            arr = sched.arrival_wall.get(req.uid, now)
            h_e2e.observe(now - arr)
            n_tok = len(sched.finished[req.uid])
            fw = first_wall.pop(req.uid, None)
            if fw is not None and n_tok > 1:
                h_itl.observe((now - fw) / (n_tok - 1))
            last_tok_wall.pop(req.uid, None)
            if tracer is not None:
                rid = root_span.pop(req.uid, None)
                tracer.add("retire", now, now, req.trace_id, parent=rid,
                           track=f"req{req.uid}", tokens=n_tok)
                if rid is not None:
                    tracer.end(rid, t1=now)
            if recorder is not None:
                recorder.record("retire", uid=req.uid, tokens=n_tok)

        t_start = time.perf_counter()
        t_prefill = t_decode = 0.0
        tick = prefill_calls = decode_ticks = occupancy_sum = 0
        while sched.pending or sched.occupancy:
            sched.mark_arrivals(tick, time.perf_counter())
            g_queue.set(sched.queue_depth(tick))
            # admit every arrived request a free slot can take; a row
            # retired below frees its slot for the very next tick's admit
            while True:
                head = sched.peek(tick)
                if head is None:
                    break
                if admission is not None:
                    reason = admission.should_shed(
                        head, sched.queue_depth(tick))
                    if reason is not None:
                        shed_req = sched.drop_head(tick)
                        errors[shed_req.uid] = f"shed: {reason}"
                        if recorder is not None:
                            recorder.record("shed", uid=shed_req.uid,
                                            reason=reason)
                        continue
                placed = sched.place(tick)
                if placed is None:
                    break
                idx, req = placed
                if tracer is not None:
                    t_adm = time.perf_counter()
                    arr = sched.arrival_wall.get(req.uid, t_adm)
                    trk = f"req{req.uid}"
                    rid = tracer.begin("request", req.trace_id, track=trk,
                                       t0=arr, prompt_len=len(req.prompt),
                                       max_new_tokens=req.max_new_tokens)
                    root_span[req.uid] = rid
                    tracer.add("queued", arr, t_adm, req.trace_id,
                               parent=rid, track=trk)
                    tracer.add("admit", t_adm, t_adm, req.trace_id,
                               parent=rid, track=trk, slot=idx)
                if recorder is not None:
                    recorder.record("admit", uid=req.uid, slot=idx)
                pb = self.bucket_for(len(req.prompt))
                padded = np.full(pb, self.pad_fill, np.int32)
                padded[:len(req.prompt)] = req.prompt
                t0 = time.perf_counter()
                self.slots, tok, lp, okf = self._prefill(
                    self.params, self.slots, jnp.asarray(padded),
                    np.int32(idx), np.int32(len(req.prompt)),
                    self._next_key())
                first = int(tok)          # host fetch = device barrier
                now = time.perf_counter()
                t_prefill += now - t0
                prefill_calls += 1
                first_wall[req.uid] = now
                h_ttft.observe(now - sched.arrival_wall.get(req.uid, t0))
                live.observe_ttft(
                    now - sched.arrival_wall.get(req.uid, t0), now)
                last_tok_wall[req.uid] = now
                if tracer is not None:
                    tracer.add("prefill", t0, now, req.trace_id,
                               parent=root_span.get(req.uid),
                               track=f"req{req.uid}", bucket=pb,
                               prompt_len=len(req.prompt))
                if on_tick is not None:
                    on_tick(TickReport(
                        tick=tick, kind="prefill", elapsed_s=now - t0,
                        emitted=[(req.uid, first)],
                        finite={req.uid: bool(okf)},
                        logprob={req.uid: float(lp)},
                        slots=[idx], engine=self,
                        queue_depth=sched.queue_depth(tick)))
                done = sched.record(idx, first, self.eos_id)
                if done is not None:
                    retire(done, now)

            if not sched.occupancy:
                nxt = sched.next_arrival()
                if nxt is None:
                    break
                tick = max(tick, nxt)     # idle engine: jump to arrival
                continue

            occupancy_sum += sched.occupancy
            g_occ.set(sched.occupancy)
            t0 = time.perf_counter()
            self.slots, out, lp, okf = self._decode(
                self.params, self.slots,
                jnp.asarray(sched.last_tokens()), self._next_key())
            out = np.asarray(out)         # host fetch = device barrier
            lp, okf = np.asarray(lp), np.asarray(okf)
            now = time.perf_counter()
            t_decode += now - t0
            h_tick.observe(now - t0)
            decode_ticks += 1
            live.sample(sched.queue_depth(tick), sched.occupancy, now)
            if admission is not None:
                admission.observe(live, sched.queue_depth(tick), now)
                admission.apply(self)
            if tracer is not None:
                tracer.add("decode_tick", t0, now, "engine",
                           track="engine", slots=sched.occupancy)
            if on_tick is not None:
                act = sched.active_slots
                on_tick(TickReport(
                    tick=tick, kind="decode", elapsed_s=now - t0,
                    emitted=[(sched.slots[i].request.uid, int(out[i]))
                             for i in act],
                    finite={sched.slots[i].request.uid: bool(okf[i])
                            for i in act},
                    logprob={sched.slots[i].request.uid: float(lp[i])
                             for i in act},
                    slots=list(act), engine=self,
                    queue_depth=sched.queue_depth(tick)))
            for idx in sched.active_slots:
                r = sched.slots[idx].request
                lt = last_tok_wall.get(r.uid)
                if lt is not None:
                    live.observe_itl(now - lt, now)
                last_tok_wall[r.uid] = now
                if tracer is not None:
                    tracer.add("decode", t0, now, r.trace_id,
                               parent=root_span.get(r.uid),
                               track=f"req{r.uid}")
                done = sched.record(idx, int(out[idx]), self.eos_id)
                if done is not None:
                    retire(done, now)
            if telemetry is not None and now - last_window_emit >= 1.0:
                last_window_emit = now
                telemetry.writer.emit("obs_window", scope="serve",
                                      **live.signals(now))
            tick += 1

        total = time.perf_counter() - t_start
        tokens = int(sum(len(v) for v in sched.finished.values()))
        latency = {
            "ttft_p50_s": h_ttft.percentile(50),
            "ttft_p99_s": h_ttft.percentile(99),
            "ttft_mean_s": h_ttft.mean,
            "itl_p50_s": h_itl.percentile(50),
            "itl_p99_s": h_itl.percentile(99),
            "e2e_p50_s": h_e2e.percentile(50),
            "e2e_p99_s": h_e2e.percentile(99),
            "e2e_max_s": h_e2e.max if h_e2e.count else None,
            "measured_requests": h_e2e.count,
        }
        stats = {
            "requests": n_req,
            "rejected": len(errors),
            "generated_tokens": tokens,
            "tokens_per_sec": tokens / total if total else None,
            "total_seconds": total,
            "prefill_seconds": t_prefill,
            "decode_seconds": t_decode,
            "prefill_calls": prefill_calls,
            "decode_ticks": decode_ticks,
            "mean_slot_occupancy":
                occupancy_sum / decode_ticks if decode_ticks else 0.0,
            "max_slots": self.max_slots,
            "kv_cache_bytes": self.kv_cache_bytes,
            "kv_dtype": self.kv_dtype,
            "weight_dtype": self.weight_dtype,
            "prefill_compiles": self._prefill.traces,
            "decode_compiles": self._decode.traces,
            "restarts": self.restarts,
            "weight_swaps": self.weight_swaps,
            "buckets": list(self.buckets),
            "latency": latency,
            "window": live.signals(),
        }
        if telemetry is not None:
            telemetry.writer.emit("obs_serve", stats=stats)
        return {"results": sched.finished, "errors": errors, "stats": stats}


@dataclasses.dataclass
class _SpillRecord:
    """A preempted request parked on the host: its scheduler identity
    plus the slot image needed to resume bit-identically — the token
    stream, commit watermark, pending (emitted, unfed) token, and the
    whole-slot KV copy in the pools' at-rest representation.  Draft
    pools are deliberately NOT captured: a resumed request restarts
    speculation cold, which only costs acceptance (verification stays
    exact), never output tokens."""

    request: Request
    generated: list
    stream: list
    committed: int
    pendtok: int
    kv: object                       # parked pytree, pools' treedef
                                     # (host arrays, or device arrays on
                                     # the spill device under
                                     # migrate="device")
    seq: int                         # spill order, FIFO tiebreak
    digest: Optional[bytes] = None   # end-to-end integrity (device path)


#: The phases of one :meth:`PagedEngine.run` tick, as they run.  Each is
#: a ``ddl:<phase>`` span under the tick's ``ddl:tick`` while something
#: listens, and an always-on sum in the run's published record
#: (``obs.last_run("serve")``).  The two ``*_dispatch`` spans are opened by
#: the program object itself (:class:`CountingJit`); the engine only
#: clocks them.  ``chunk_commit`` runs between dispatch and wait (the host
#: books the chunk while the device computes it) and again after the hook.
#: A tick's record in the ring carries ``meta = (slots decoded, chunks run,
#: counters)``: ``counters["kv_blocks"]`` the blocks in use by layer kind
#: (:meth:`.paged.BlockManager.blocks_by_kind`) and, for a model with
#: expert layers, ``counters["experts"]`` (:func:`expert_counters`), counted
#: on the device and fetched with the tick's tokens.
#:
#: ``counters["programs"]``: one dict a program the tick dispatched through
#: the chunk or the decode program object, in dispatch order, appended once
#: the dispatch has RETURNED (a dispatch that raises, as the benchmark's
#: closing window does, leaves none; the tick's meta is set before the
#: first dispatch, so an aborted tick keeps those it ran):
#:
#: * ``program``: ``"paged_chunk"`` or ``"paged_decode"``, the program
#:   object's own name; its device module is ``jit_<program>``, so the k-th
#:   record of a name is the k-th module event of that name in a trace.
#: * ``at``: ``[t_dispatch, t_returned, t_ready]`` on the ring's clock
#:   (``time.perf_counter``): the ``*_dispatch`` phase entered and left,
#:   the ``*_wait`` phase left (the host holds the result; None until
#:   then).  They are the phase clock's own readings, not new ones.
#: * a chunk also: ``slot``, ``uid``, ``start`` (the position its first
#:   token feeds) and ``live`` (positions it wrote: neither committed
#:   before nor past the prompt).
#: * a model with expert layers also: ``experts``, :func:`expert_counters`
#:   of THIS program's load, counted on the device; a chunk's rows are all
#:   ``prefill_chunk`` of them, padding included.  A request's last chunk
#:   and a decode program hand it down with their tokens, in the one fetch
#:   that is their barrier (a decode program's is the same dict as the
#:   tick's ``counters["experts"]``).  Any other chunk's result is fetched
#:   where the host next waits for a LATER program, before that barrier
#:   (the tick's decode program, or the next chunk), so its ``experts``
#:   appears one program later and the chunk a raising dispatch cut off
#:   may lack it.
#: * ``io``: ``[puts, fetches]``, the host arrays put on the device for
#:   this program and the fetches made of its result, as the two helpers
#:   (:meth:`PagedEngine.put`, :meth:`PagedEngine.fetch`) counted them:
#:   ``[1, 1]``, and ``[1, 0]`` for a chunk nothing is read of (not a
#:   request's last, no expert layer) or not read yet.
#:
#: The draft, verify and canary dispatches (and a chunk's draft twin)
#: leave no record: a run that uses them cannot be joined with a trace by
#: order.  Each tick's START is ``PhaseClock.started``, beside the ring.
TICK_PHASES = ("admit", "chunk_prepare", "chunk_dispatch", "chunk_commit",
               "chunk_wait", "decode_prepare", "decode_dispatch",
               "decode_wait", "decode_commit", "hook", "tick_end")
DISPATCH_PHASES = ("chunk_dispatch", "decode_dispatch")


class Packed:
    """Several small values as ONE flat int32 array: how a serving
    program's host-made integers go up in one put, and how what the host
    reads of its results comes down in one fetch.

    ``like`` names each value with its ``jax.ShapeDtypeStruct``, a
    ``(full, ring)`` pair of them for a model with rings, or None for what
    a model does not have (no expert layer: no load); the offsets are
    fixed here, in the order of the names.  int32 travels as it is, a
    float32 as its bits (`split` views them back: the same bits), a flag
    as 0 / 1.  `pack` is numpy alone: a ``jnp`` operation on the host
    would run a device program between two serving programs.  `split`
    takes the array apart again by static slices, the host's array or a
    program's traced one alike, into the structure of ``like``; `join` is
    `pack` inside a program."""

    def __init__(self, **like):
        self.like, self.tree = jax.tree.flatten(like)
        self.ends = np.cumsum([math.prod(x.shape)
                               for x in self.like]).tolist()
        self.size = self.ends[-1]

    def pack(self, **values) -> np.ndarray:
        flat = np.empty(self.size, np.int32)
        lo = 0
        for x, hi in zip(jax.tree.leaves(values), self.ends, strict=True):
            flat[lo:hi] = np.ravel(x)
            lo = hi
        return flat

    def join(self, **values):
        parts = []
        for x, want in zip(jax.tree.leaves(values), self.like, strict=True):
            if x.shape != want.shape:
                raise ValueError(f"a packed value has the shape {x.shape}, "
                                 f"its layout says {want}")
            if want.dtype == jnp.float32:
                x = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                                 jnp.int32)
            parts.append(x.astype(jnp.int32).reshape(-1))
        return jnp.concatenate(parts)

    def split(self, flat) -> dict:
        parts, lo = [], 0
        for want, hi in zip(self.like, self.ends):
            x = flat[lo:hi].reshape(want.shape)
            if want.dtype == jnp.float32:
                x = x.view(np.float32)
            elif want.dtype == jnp.bool_:
                x = x != 0
            parts.append(x)
            lo = hi
        return self.tree.unflatten(parts)


def expert_counters(load: np.ndarray) -> dict:
    """What one program (a decode tick's or a chunk's) did to the experts
    held here, from its load matrix (a row an expert layer, a column a
    held expert): assignments in all, held experts touched and the largest
    expert's load over the mean, both as the mean over the layers (the
    skew over those that took any assignment; 0 where none did), and how
    many expert layers there are."""
    took = load.sum(axis=1)
    busy = took > 0
    skew = (load.max(axis=1)[busy] * load.shape[1] / took[busy]).mean() \
        if busy.any() else 0.0
    return {"assignments": int(took.sum()),
            "touched": float((load > 0).sum(axis=1).mean()),
            "held": int(load.shape[1]), "skew": float(skew),
            "layers": int(load.shape[0])}


class PagedEngine:
    """Paged continuous batching: prefix reuse, chunked prefill,
    speculative decoding — identical greedy outputs, fewer FLOPs.

    The three classic serving optimizations, mapped onto the same
    compile-once discipline as :class:`ServeEngine`:

    * **Paged KV with prefix reuse** (:mod:`.paged`) — cache leaves live
      in fixed-size block pools; each slot holds a block TABLE.  A
      rolling chain hash over token-prefix chunks indexes committed
      blocks, so a request whose prompt prefix was served before
      references those blocks instead of recomputing them (refcounted;
      copy-on-write the moment it diverges mid-block).  Tables and
      positions are device DATA, so program shapes never change.
    * **Chunked prefill** (:mod:`.prefill`) — prompts land in fixed-size
      chunks interleaved with decode ticks under a per-tick budget, so
      one long prompt stalls live streams by at most ~one chunk of
      compute instead of a whole prompt.
    * **Speculative decoding** (:mod:`.spec`) — a truncated-layer draft
      sharing the target's weights proposes ``spec_k`` tokens per round;
      the target scores all ``spec_k + 1`` positions in ONE batched
      cached forward and keeps the longest greedy-matching prefix.
      Greedy parity is exact (see :func:`.spec.greedy_accept`); only
      the forward count changes.

    Each device program (chunk prefill, decode, draft propose, verify,
    draft chunk, block copy) runs through :class:`CountingJit` and
    compiles exactly ONCE for the engine's lifetime — asserted by
    tests, not assumed.  The block pools, prefix index, and compiled
    programs persist across ``run()`` calls, so a later trace sharing
    prompts with an earlier one starts with a warm prefix cache.
    """

    def __init__(self, model: CausalLM, params, *, max_slots: int = 8,
                 max_len: Optional[int] = None, kv_block_size: int = 16,
                 num_blocks: Optional[int] = None, prefill_chunk: int = 32,
                 prefill_chunks_per_tick: int = 1,
                 draft_layers: Optional[int] = None, spec_k: int = 4,
                 eos_id: Optional[int] = None, temperature: float = 0.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 rng=None, donate: Optional[bool] = None,
                 kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 preempt: bool = False,
                 spill_dir: Optional[str] = None,
                 migrate: str = "host"):
        validate_sampling(top_k, top_p)
        quant.check_dtype("kv_dtype", kv_dtype)
        quant.check_dtype("weight_dtype", weight_dtype)
        self.kv_dtype, self.weight_dtype = kv_dtype, weight_dtype
        # working precision, captured before params go at-rest (the
        # compiled impls dequantize back to it at their top — see
        # ServeEngine; same contract here)
        self.compute_dtype = jax.tree.leaves(params)[0].dtype
        if weight_dtype is not None:
            params = quant.quantize_weights(params, weight_dtype)
        self.model, self.params = model, params
        self.lm = make_decode_model(model)
        self.max_slots = int(max_slots)
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.max_len = int(max_len if max_len is not None else model.max_len)
        self.eos_id = eos_id
        self.temperature, self.top_k, self.top_p = temperature, top_k, top_p
        self.pad_fill = model.pad_id if model.pad_id is not None else 0
        self._key = rng if rng is not None else jax.random.key(0)

        bs = int(kv_block_size)
        if bs < 1:
            raise ValueError(f"kv_block_size must be >= 1, got {bs}")
        self.block_size = bs
        self.chunk = int(prefill_chunk)
        if self.chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        self.chunks_per_tick = max(1, int(prefill_chunks_per_tick))

        self.spec_k = int(spec_k)
        self.draft_layers = draft_layers
        if draft_layers is not None:
            if temperature != 0.0:
                raise ValueError("speculative decoding is greedy-only "
                                 "(acceptance is exact-match against the "
                                 "target argmax); set temperature=0")
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        # speculation writes up to spec_k positions past the stream tip,
        # so the slot's logical buffer gets that much headroom on top of
        # the serving cap, rounded up to whole blocks
        headroom = (self.spec_k + 1) if draft_layers is not None else 0
        self.padded_len = -(-(self.max_len + headroom) // bs) * bs
        if self.padded_len > model.max_len:
            raise ValueError(
                f"slot buffer {self.padded_len} (max_len {self.max_len} + "
                f"speculative headroom {headroom}, in whole blocks) "
                f"exceeds the model's max_len {model.max_len}; lower "
                f"max_len or spec_k")
        if self.chunk > self.padded_len:
            raise ValueError(f"prefill_chunk {self.chunk} exceeds the "
                             f"slot buffer {self.padded_len}")
        self.blocks_per_slot = self.padded_len // bs
        # a model that mixes full and window layers: its window layers
        # cache a ring that covers the window and one chunk (the oldest
        # positions a chunk's first query needs outlive the chunk's own
        # writes), in whole blocks and one to spare for a chunk that
        # starts mid-block; paged.py's docstring has the layout
        windows = {sp.window for sp in model.layer_specs()}
        self.ring_blocks = None
        if len(windows) > 1 and None in windows:
            ring = -(-(max(w for w in windows if w) + self.chunk) // bs) + 1
            if ring < self.blocks_per_slot:
                self.ring_blocks = ring
                self.lm = self.lm.clone(cache_ring=ring * bs)
        if self.ring_blocks is not None:
            for what, on in (("speculative decoding (draft_layers)",
                              draft_layers is not None),
                             ("preemption (spill / resume)", preempt)):
                if on:
                    raise ValueError(self._two_kinds(what))
        if kv_dtype == "int8" and any(sp.latent
                                      for sp in model.layer_specs()):
            raise ValueError(
                "kv_dtype='int8' is not supported for a model with latent "
                "attention layers: a latent row [c | k_r] has no heads to "
                "scale by, and the decode program's latent kernel reads "
                "floating rows (use kv_dtype='bf16' or None)")
        if num_blocks is None:
            # 1x for the live slots + 1x retention headroom so the
            # prefix index can keep blocks alive after their request
            num_blocks = 2 * self.max_slots * self.blocks_per_slot
        self.num_blocks = int(num_blocks)
        self.manager = paged.BlockManager(num_blocks, bs, self.max_slots,
                                          self.blocks_per_slot,
                                          self.ring_blocks)
        if donate is None:
            donate = jax.default_backend() != "cpu"
        dk = {"donate_argnums": (1,)} if donate else {}
        ck = {"donate_argnums": (0,)} if donate else {}
        # one slot's at-rest cache in the model's layout: the pools are
        # folded from it and every gather is unfolded by it
        self._slot_like = paged.slot_template(self.lm, self.padded_len,
                                              kv_dtype=kv_dtype)
        self.pools = self._new_pools(self._slot_like)
        #: attention layers the decode program serves in place through the
        #: block table (per-head K and V / a latent row) and by gathering
        #: (rings), read off the slot template
        self.decode_attn_paths = paged.attention_paths(self._slot_like)
        #: bytes the decode program's attention kernel lands in VMEM a
        #: live position and latent layer, counted off the kernel's call as
        #: traced for this engine's shapes (0: no latent layer), for
        #: ``counters["latent"]``
        leaf = paged.latent_leaf(self.pools)
        self.latent_row_bytes = 0 if leaf is None else latent_bytes_a_row(
            leaf, self.max_slots, self.blocks_per_slot)
        #: what the chunk and decode programs said of themselves as they
        #: were traced, ``program -> {event: text}`` (``grouped_product``)
        self.program_notes: dict = {}
        #: what crossed between host and device around the chunk and the
        #: decode program over the newest run: calls of the two program
        #: objects, host arrays put (:meth:`put`) and fetches made
        #: (:meth:`fetch`); one put and at most one fetch a program
        self.host_io = {"programs": 0, "puts": 0, "fetches": 0}
        # the two programs' host interface, `(up, down)` each: the layout
        # of the one int32 array the host puts and of the one it fetches,
        # from the shapes the engine already knows
        S, C, bps, ring = (self.max_slots, self.chunk, self.blocks_per_slot,
                           self.ring_blocks)

        def like(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype)

        def i32(*shape):
            return like(jnp.int32, *shape)

        def kinds(full, rings):
            """A table or its write targets: `full` alone, or the pair
            the programs' bodies take for a model with rings."""
            return full if ring is None else (full, rings)

        held = [sp.experts.num_experts for sp in self.lm.layer_specs()
                if sp.experts]
        self._load_like = i32(len(held), held[0]) if held else None
        self._chunk_io = (
            Packed(toks=i32(C), table=kinds(i32(bps), i32(ring or 0)),
                   pos=i32(), logit_idx=i32(), wb=kinds(i32(C), i32(C)),
                   wo=i32(C)),
            Packed(tok=i32(), lp=like(jnp.float32), ok=like(jnp.bool_),
                   load=self._load_like))
        self._decode_io = (
            Packed(tables=kinds(i32(S, bps), i32(S, ring or 0)), pos=i32(S),
                   toks=i32(S), wb=kinds(i32(S), i32(S)), wo=i32(S)),
            Packed(toks=i32(S), lp=like(jnp.float32, S),
                   ok=like(jnp.bool_, S), load=self._load_like))
        self._chunk_prog = CountingJit(self._chunk_packed, "paged_chunk",
                                       "chunk_dispatch", self.program_notes,
                                       self.host_io, **dk)
        self._decode = CountingJit(self._decode_packed, "paged_decode",
                                   "decode_dispatch", self.program_notes,
                                   self.host_io, **dk)
        self._copy = CountingJit(self._copy_impl, "paged_copy", **ck)
        if spill_dir is not None and not preempt:
            raise ValueError("spill_dir requires preempt=True (it is the "
                             "preemption spill audit directory)")
        self._preempt = bool(preempt)
        self.spill_dir = spill_dir
        if spill_dir is not None:
            os.makedirs(spill_dir, exist_ok=True)
        # preemption spill transport: "host" round-trips the slot image
        # through host numpy (always available); "device" parks it on
        # another local device via the chunked migration schedule — no
        # host copy on the hot path, digest-audited end to end.  The
        # npz audit (spill_dir) is written either way.
        if migrate not in ("host", "device"):
            raise ValueError(f"migrate must be 'host' or 'device', got "
                             f"{migrate!r}")
        if migrate == "device" and len(jax.local_devices()) < 2:
            raise ValueError(
                "migrate='device' needs a second local device to park "
                "spilled KV on; only 1 is visible (use migrate='host', "
                "or run under a multi-device mesh)")
        self.migrate_kind = migrate
        self._home_device = jax.local_devices()[0]
        self._spill_device = (jax.local_devices()[-1]
                              if migrate == "device" else None)
        #: fault-injection seam: callable payload -> payload applied to
        #: the spilled KV before the device hop (the ``migrate_drop``
        #: chaos kind); the resume-side digest check turns any
        #: corruption into a MigrationError the supervisor replays.
        self._migrate_chaos = None
        self._spill_moves = 0
        self._spill_move_bytes = 0
        self._spill_move_seconds = 0.0
        # spill gathers a whole slot WITHOUT donating the pools (they
        # must survive the read); unspill donates them like every other
        # pool-updating program
        self._spill = CountingJit(self._spill_impl, "paged_spill")
        self._unspill = CountingJit(self._unspill_impl, "paged_unspill",
                                    **ck)
        if draft_layers is not None:
            self.draft_lm, self.draft_params = spec_mod.truncated_draft(
                self.lm, params, draft_layers)
            # the draft pool INHERITS kv_dtype: speculation gathers and
            # scatters through the same shims, so a mixed-precision pair
            # would silently double the draft's footprint
            self._draft_slot_like = paged.slot_template(
                self.draft_lm, self.padded_len, kv_dtype=kv_dtype)
            self.draft_pools = self._new_pools(self._draft_slot_like)
            self._draft = CountingJit(self._draft_impl, "paged_draft",
                                      "decode_dispatch", **dk)
            self._verify = CountingJit(self._verify_impl, "paged_verify",
                                       "decode_dispatch", **dk)
            self._draft_chunk = CountingJit(
                self._draft_chunk_impl, "paged_draft_chunk",
                "chunk_dispatch", **dk)
            self._draft_copy = CountingJit(self._draft_copy_impl,
                                           "paged_draft_copy", **ck)
        # exact KV footprint: every allocated pool pytree (draft included
        # when speculating) — the paged analogue of ServeEngine's slots
        self.kv_cache_bytes = obs_memory.pytree_bytes(self.pools)
        if draft_layers is not None:
            self.kv_cache_bytes += obs_memory.pytree_bytes(self.draft_pools)
        self.restarts = 0
        self.weight_swaps = 0
        self._spec_enabled = draft_layers is not None
        self._base_chunks_per_tick = self.chunks_per_tick
        self._canary: Optional[_CanaryState] = None

    @staticmethod
    def _two_kinds(what: str) -> str:
        return (f"{what} is not supported for a model that mixes full and "
                "window layers: its window layers cache per-slot rings "
                "(serve/paged.py) that this path neither moves nor shares")

    def _new_pools(self, like):
        """Zeroed block pools for slots shaped `like`, placed where the
        weights live.
        Weights that come out of a training run are committed to its mesh,
        and so is whatever a jit computes from them: pools left as plain
        arrays would change type on their first trip through a program
        and make it trace a second time."""
        pools = paged.build_pools(
            like, self.num_blocks + 1, self.block_size,
            None if self.ring_blocks is None
            else self.max_slots * self.ring_blocks + 1)
        sharding = getattr(jax.tree.leaves(self.params)[0], "sharding", None)
        if isinstance(sharding, NamedSharding):
            pools = jax.device_put(
                pools, NamedSharding(sharding.mesh, PartitionSpec()))
        return pools

    # --- quantization shims (identity at full precision) ------------------
    def _wp(self, params):
        """At-rest params -> compute-dtype view inside the jitted impl
        (the int8 upcast fuses into each consuming matmul; no full-
        precision weight copy exists between programs)."""
        if self.weight_dtype is None:
            return params
        return quant.dequantize_weights(params, self.compute_dtype)

    def _gather(self, pools, table, pos, like=None):
        """Gather one slot's logical cache (`like`: the draft's template
        for the draft's pools) and lift it to the model's working
        precision (int8 pools dequantize ``q * s`` in f32)."""
        return self._lift(paged.gather_slot(
            pools, table, pos, self._slot_like if like is None else like))

    def _lift(self, cache):
        """A gathered cache at the model's working precision."""
        if self.kv_dtype is None:
            return cache
        return quant.dequant_cache(cache, self.compute_dtype)

    def _qspan(self, span):
        """Freshly-computed floating KV span -> the pools' at-rest
        representation (per-position-per-head int8 scales travel with
        the payload as one :class:`..serve.quant.QuantTensor`)."""
        if self.kv_dtype is None:
            return span
        return quant.quantize_cache_span(span, self.kv_dtype)

    # --- compiled programs (each traces exactly once) ---------------------
    def _sample(self, params, hidden_last, key):
        """Sample plus chosen-token log-prob and per-row finiteness.
        ``params`` is a traced argument, never a closure capture — the
        same program therefore serves ANY weights of identical geometry
        (hot swap, canary) without retracing."""
        toks, _ = sample_tokens(self.model, params, hidden_last, key,
                                temperature=self.temperature,
                                top_k=self.top_k, top_p=self.top_p)
        nl = self.model.logits_from({"params": params}, hidden_last)
        lp = jnp.take_along_axis(jax.nn.log_softmax(nl, axis=-1),
                                 toks[:, None], axis=-1)[:, 0]
        ok = jnp.isfinite(hidden_last).all(axis=-1)
        return toks, lp, ok

    def _chunk_impl(self, params, pools, tokens, table, pos, logit_idx,
                    wb, wo, key):
        """One prefill chunk for one slot: gather its logical cache,
        run the chunk through the model's multi-token cached forward,
        scatter the fresh KV span to its blocks (already-committed /
        padding positions routed to trash), and sample at ``logit_idx``
        (meaningful on the final chunk only — the caller ignores it
        otherwise; the extra 1-row head projection is noise).  Last of
        the results, as the decode program's: this chunk's load of each
        held expert, a row an expert layer, padding rows counted (None for
        a model without expert layers, whose program is then what it
        was)."""
        params = self._wp(params)
        with jax.named_scope("kv_gather"):
            cache = self._gather(pools, table, pos)
        hidden, new, load = cached_apply_counting(self.lm, params, cache,
                                                  tokens[None])
        with jax.named_scope("kv_write"):
            span = paged.extract_span(new, pos, self.chunk)
            pools = paged.scatter_span(pools, self._qspan(span), wb, wo)
        with jax.named_scope("sample"):
            h_last = jax.lax.dynamic_slice_in_dim(hidden[0], logit_idx, 1)
            tok, lp, ok = self._sample(params, h_last, key)
        return pools, tok[0], lp[0], ok[0], load

    def _chunk_packed(self, params, pools, up, key):
        """`paged_chunk` as the host calls it: :meth:`_chunk_impl` of the
        values in the one array the host put (``_chunk_io``'s first
        layout), its token, logprob, flag and load as the one array the
        host fetches (the second)."""
        v = self._chunk_io[0].split(up)
        pools, tok, lp, ok, load = self._chunk_impl(
            params, pools, v["toks"], v["table"], v["pos"], v["logit_idx"],
            v["wb"], v["wo"], key)
        return pools, self._chunk_io[1].join(tok=tok, lp=lp, ok=ok,
                                             load=load)

    def _draft_chunk_impl(self, dparams, dpools, tokens, table, pos,
                          wb, wo):
        """The draft model's KV for the same chunk — speculation needs
        the draft's cache warm over the whole committed stream."""
        dparams = self._wp(dparams)
        cache = self._gather(dpools, table, pos, self._draft_slot_like)
        _, new = cached_apply(self.draft_lm, dparams, cache, tokens[None])
        span = paged.extract_span(new, pos, self.chunk)
        return paged.scatter_span(dpools, self._qspan(span), wb, wo)

    def _decode_impl(self, params, pools, tables, positions, toks,
                     wb, wo, key):
        """One token for every slot: run the model's single-sequence
        cached decode (vmapped) over each slot's view of the pools
        (:func:`.paged.decode_view`: a full-kind layer attends the pool
        leaves in place through the slot's block table, over its live
        blocks only; a ring is gathered), scatter each slot's new KV
        position back, one shared sampling.  Free/prefilling slots come
        with position 0, so they read nothing, run on garbage and write to
        trash; their sampled tokens are ignored by the host.  Last of the
        results: the tick's load of each held expert, a row an expert
        layer (None for a model without them)."""
        runlog.compile_log.note(    # first: before any inner trace event
            "attn_paths", "jit(paged_decode)",
            " ".join(f"{k}={n}" for k, n in self.decode_attn_paths.items()))
        params = self._wp(params)

        def one(table, pos, tok):
            with jax.named_scope("kv_gather"):      # rings only
                cache = paged.decode_view(pools, table, pos,
                                          self._slot_like, self._lift)
            hidden, new, load = cached_apply_counting(
                self.lm, params, cache, tok[None, None])
            with jax.named_scope("kv_write"):
                return hidden[0, 0], paged.view_span(new, pos), load

        h, spans, load = jax.vmap(one)(tables, positions, toks)
        if load is not None:
            load = load[0]      # unmapped: every row holds the tick's total
        with jax.named_scope("kv_write"):
            kv = jax.tree_util.tree_map_with_path(
                lambda p, x: x if paged.is_counter(p) else x[:, 0], spans)
            pools = paged.scatter_span(pools, self._qspan(kv), wb, wo)
        with jax.named_scope("sample"):
            toks, lp, ok = self._sample(params, h, key)
        return pools, toks, lp, ok, load

    def _decode_packed(self, params, pools, up, key):
        """`paged_decode` as the host calls it: :meth:`_decode_impl`
        between ``_decode_io``'s two layouts, as :meth:`_chunk_packed`."""
        v = self._decode_io[0].split(up)
        pools, toks, lp, ok, load = self._decode_impl(
            params, pools, v["tables"], v["pos"], v["toks"], v["wb"],
            v["wo"], key)
        return pools, self._decode_io[1].join(toks=toks, lp=lp, ok=ok,
                                              load=load)

    def _draft_impl(self, dparams, dpools, tables, positions, toks,
                    wb, wo):
        """Draft proposal round: ``spec_k + 1`` greedy cached steps per
        slot (scan), writing draft KV at positions ``c .. c+k``.  The
        extra step exists to WRITE position ``c+k`` (its proposal is
        discarded) so an all-accept round leaves no KV hole."""
        T = self.spec_k + 1
        dparams = self._wp(dparams)

        def one(table, pos, tok):
            cache = self._gather(dpools, table, pos, self._draft_slot_like)

            def step(carry, _):
                c, t = carry
                hidden, c = cached_apply(self.draft_lm, dparams, c,
                                         t[None, None])
                nxt, _ = sample_tokens(self.draft_lm, dparams,
                                       hidden[0, 0][None],
                                       jax.random.key(0), temperature=0.0)
                nt = nxt[0].astype(t.dtype)
                return (c, nt), nt

            (cache, _), outs = jax.lax.scan(step, (cache, tok), None,
                                            length=T)
            return outs, paged.extract_span(cache, pos, T)

        outs, spans = jax.vmap(one)(tables, positions, toks)
        dpools = paged.scatter_span(dpools, self._qspan(spans), wb, wo)
        return dpools, outs[:, :self.spec_k]

    def _verify_impl(self, params, pools, tables, positions, toks, wb, wo):
        """Target verification: ONE batched ``spec_k + 1``-token cached
        forward per slot scores the pending token plus every draft
        proposal; returns the target's greedy choice at each position.
        This is the whole speedup: ``a + 1`` tokens per target forward
        instead of 1."""
        T = self.spec_k + 1
        params = self._wp(params)

        def one(table, pos, tk):
            cache = self._gather(pools, table, pos)
            hidden, new = cached_apply(self.lm, params, cache, tk[None])
            return hidden[0], paged.extract_span(new, pos, T)

        h, spans = jax.vmap(one)(tables, positions, toks)
        pools = paged.scatter_span(pools, self._qspan(spans), wb, wo)
        g, lp, _ = self._sample(params, h.reshape(-1, h.shape[-1]),
                                jax.random.key(0))
        ok = jnp.isfinite(h).all(axis=(1, 2))
        return (pools, g.reshape(tables.shape[0], T),
                lp.reshape(tables.shape[0], T), ok)

    def _copy_impl(self, pools, src, dst):
        return paged.copy_block(pools, src, dst)

    def _draft_copy_impl(self, dpools, src, dst):
        return paged.copy_block(dpools, src, dst)

    def _spill_impl(self, pools, table):
        """One slot's whole logical cache in its AT-REST representation
        (no dequant — an int8 pool spills int8 + scales, so the round
        trip back through :meth:`_unspill_impl` is bit-exact by
        construction).  The preemption read path."""
        return paged.gather_slot(pools, table, 0, self._slot_like)

    def _unspill_impl(self, pools, kv, blocks, offsets):
        """Write a spilled slot image back: positions ``< committed``
        land in the resumed slot's fresh blocks, everything beyond is
        routed to trash by the host-built ``blocks`` vector."""
        kv = jax.tree_util.tree_map_with_path(
            lambda p, x: x if paged.is_counter(p) else x[0], kv)
        return paged.scatter_span(pools, kv, blocks, offsets)

    # --- host side --------------------------------------------------------
    def put(self, layout: Packed, io=None, **values):
        """A program's host-made values on the device: packed into one
        array by `layout` and put with ONE call.  Counted in ``host_io``
        and in `io`, the program's own ``[puts, fetches]``."""
        self.host_io["puts"] += 1
        if io is not None:
            io[0] += 1
        return jnp.asarray(layout.pack(**values))

    def fetch(self, layout: Packed, down, io=None) -> dict:
        """What the host reads of a program's result, `down`, taken apart
        by `layout`: ONE fetch, which is the barrier where the program
        still runs.  Counted as :meth:`put` counts."""
        self.host_io["fetches"] += 1
        if io is not None:
            io[1] += 1
        return layout.split(np.asarray(down))

    def _cow(self, src: int, dst: int) -> None:
        """Device half of copy-on-write: duplicate the physical block in
        the target pools (and the draft pools, whose tables are shared,
        when speculation is on)."""
        s, d = np.int32(src), np.int32(dst)
        self.pools = self._copy(self.pools, s, d)
        if self.draft_layers is not None:
            self.draft_pools = self._draft_copy(self.draft_pools, s, d)

    def _make_writable(self, idx: int, lo_pos: int, hi_pos: int,
                       whose: Optional[dict] = None) -> int:
        """Run the manager's COW check over every logical block touched
        by positions ``[lo_pos, hi_pos]`` BEFORE computing scatter
        targets (the check may swap table entries).  Each block actually
        copied (none on the common path) is a ``cow`` span; `whose`
        gives it the request's trace id, track and parent.  Returns the
        number of copies."""
        copies = 0
        for lg in range(lo_pos // self.block_size,
                        hi_pos // self.block_size + 1):
            pair = self.manager.writable(idx, lg)
            if pair is not None:
                with obs_trace.span("cow", slot=idx, **(whose or {})):
                    self._cow(*pair)
                copies += 1
        return copies

    def _validate(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens exceeds the serving "
                f"capacity max_len={self.max_len}")
        # worst-case block need (zero prefix sharing) must fit the pool
        # — checked at SUBMIT so one impossible request lands in
        # ``errors`` instead of raising BlockPoolExhausted mid-run and
        # taking the whole batch with it (the v1/paged error-contract
        # unification the supervisor relies on)
        worst = -(-self._capacity_len(req) // self.block_size)
        if worst > self.num_blocks:
            raise ValueError(
                f"request {req.uid}: needs up to {worst} KV blocks "
                f"({self._capacity_len(req)} positions at block size "
                f"{self.block_size}) but the pool holds only "
                f"{self.num_blocks}")

    def _capacity_len(self, req: Request) -> int:
        """Stream positions a request may ever write — its whole block
        budget, reserved at admission (which is why the pool cannot
        deadlock: an admitted request never waits for blocks)."""
        extra = (self.spec_k + 1) if self.draft_layers is not None else 0
        return min(len(req.prompt) + req.max_new_tokens + extra,
                   self.padded_len)

    def _next_key(self):
        if self.temperature == 0.0:
            return self._key           # unused by greedy sampling
        self._key, sub = jax.random.split(self._key)
        return sub

    # --- resilience seams -------------------------------------------------
    def reset(self) -> None:
        """Warm restart after a contained fault: fresh block pools and
        a fresh block manager (so poisoned KV AND the prefix index that
        could resurrect it both die), SAME compiled programs — the new
        pools have identical shapes, so nothing retraces and
        ``decode_compiles`` stays put."""
        self._canary = None
        self.manager = paged.BlockManager(self.num_blocks, self.block_size,
                                          self.max_slots,
                                          self.blocks_per_slot,
                                          self.ring_blocks)
        self.pools = self._new_pools(self._slot_like)
        if self.draft_layers is not None:
            self.draft_pools = self._new_pools(self._draft_slot_like)
        self.restarts += 1

    def swap_params(self, new_params) -> None:
        """Hot weight swap between ticks: geometry-checked params slide
        into the compiled programs (traced arguments, not baked
        constants) — no recompile.  The prefix index is flushed: its KV
        was computed under the old weights, and matching it under the
        new ones would mix generations.  Draft params re-derive from
        the new target (they share weights by construction).  A
        quantized engine takes the (full-precision) publish to its
        at-rest form first, so the geometry check compares like with
        like."""
        if self.weight_dtype is not None:
            new_params = quant.quantize_weights(new_params,
                                                self.weight_dtype)
        _check_swappable(self.params, new_params)
        self.params = new_params
        if self.draft_layers is not None:
            self.draft_lm, self.draft_params = spec_mod.truncated_draft(
                self.lm, new_params, self.draft_layers)
        self.manager.flush_index()
        self.weight_swaps += 1

    def set_spec_enabled(self, enabled: bool) -> bool:
        """Toggle speculative decoding at runtime (admission control's
        first degradation step).  Returns the effective state; always
        False when the engine has no draft.  Greedy OUTPUTS are
        unaffected either way — disabling only changes the forward
        count, and re-enabling after a gap merely costs acceptance
        (the draft's cache has holes; verification stays exact)."""
        if self.draft_layers is None:
            return False
        self._spec_enabled = bool(enabled)
        return self._spec_enabled

    def begin_canary(self, new_params, slots: Iterable[int],
                     observe: Optional[Callable] = None) -> None:
        """Route ``slots`` to candidate weights while everyone else
        stays on the stable ones — one extra call of the SAME compiled
        decode program per tick, old/new KV writes cross-routed to the
        trash block so neither generation's cache sees the other's."""
        if self._canary is not None:
            raise RuntimeError("a canary is already active")
        if self.draft_layers is not None:
            raise RuntimeError(
                "canary mode requires a non-speculative engine (the "
                "draft's shared cache cannot serve two weight sets)")
        if self.ring_blocks is not None:
            raise RuntimeError(self._two_kinds("canary mode"))
        if self.weight_dtype is not None:
            new_params = quant.quantize_weights(new_params,
                                                self.weight_dtype)
        _check_swappable(self.params, new_params)
        sl = frozenset(int(s) for s in slots)
        if not sl or not all(0 <= s < self.max_slots for s in sl):
            raise ValueError(f"canary slots {sorted(sl)} must be a "
                             f"non-empty subset of 0..{self.max_slots - 1}")
        if len(sl) >= self.max_slots:
            raise ValueError("canary cannot take every slot (no stable "
                             "traffic left to compare against)")
        self._canary = _CanaryState(params=new_params, slots=sl,
                                    observe=observe)

    def end_canary(self, promote: bool) -> dict:
        """Finish the canary: promote swaps the candidate in for ALL
        slots (prefix index flushed); rollback just drops it.  Either
        way returns the engine-side comparison summary."""
        if self._canary is None:
            raise RuntimeError("no canary is active")
        can, self._canary = self._canary, None
        if promote:
            self.swap_params(can.params)
        return can.summary()

    def _canary_decode(self, mgr, pos, toks, wb, wo, dec, pc):
        """One decode tick under an active canary: two calls of the one
        compiled program.  Call A (stable params) trashes canary slots'
        KV writes; call B (candidate params) trashes everyone else's —
        each weight set's cache stays self-consistent.  Tokens merge
        per slot; canary slots contribute agreement/drift samples.
        `pc` is the run's phase clock."""
        can = self._canary
        with pc.phase("decode_prepare"):
            wb_old, wb_new = wb.copy(), wb.copy()
            for i in range(self.max_slots):
                if i in can.slots:
                    wb_old[i] = paged.TRASH
                else:
                    wb_new[i] = paged.TRASH
            up, down = self._decode_io
            up_old, up_new = (
                self.put(up, tables=mgr.tables, pos=pos, toks=toks, wb=w,
                         wo=wo) for w in (wb_old, wb_new))
            key = self._next_key()
        with pc.phase("decode_dispatch"):
            self.pools, old = self._decode(self.params, self.pools, up_old,
                                           key)
            self.pools, new = self._decode(can.params, self.pools, up_new,
                                           key)
        with pc.phase("decode_wait"):
            old, new = self.fetch(down, old), self.fetch(down, new)
            out_o, lp_o, ok_o = old["toks"], old["lp"], old["ok"]
            out_n, lp_n, ok_n = new["toks"], new["lp"], new["ok"]
        now = time.perf_counter()
        out, lp, ok = out_o.copy(), lp_o.copy(), ok_o.copy()
        for i in can.slots:
            out[i], lp[i], ok[i] = out_n[i], lp_n[i], ok_n[i]
        for i in dec:
            if i in can.slots:
                drift = abs(float(lp_n[i]) - float(lp_o[i]))
                can.note(agree=int(out_o[i]) == int(out_n[i]),
                         drift=drift if np.isfinite(drift) else np.inf,
                         finite=bool(ok_n[i]), now=now)
        return out, lp, ok

    def run(self, requests: Iterable[Request], telemetry=None,
            keep_timeline: bool = False, on_tick: Optional[Callable] = None,
            admission=None) -> dict:
        """Serve a trace; returns ``{"results", "errors", "stats"}``
        (plus ``"timeline"`` when ``keep_timeline`` — one dict per tick
        with ``placed``/``chunks``/``decoded`` uid lists, the record the
        fairness and stall-bound tests assert on).

        ``stats`` carries the v1 throughput/latency accounting plus
        ``paged`` (block pool + prefix hit rate), ``spec`` (acceptance),
        ``slo`` (attainment from per-request SLOs) and ``phases`` (summed
        seconds and count of each of :data:`TICK_PHASES`) sub-records.

        The run says what it is doing as it goes: every tick is a
        ``tick`` span with its phases as children, through
        :func:`..obs.trace.span` (the profiler's trace while a session
        is open, ``telemetry.tracer`` or an installed one while there is
        one), and the phase sums, the per-tick ring and the registry are
        published as ``obs.last_run("serve")`` BEFORE the first tick, so
        they outlive a run that a hook ends by raising.  Each tick's
        record lists the programs it dispatched with their instants
        (:data:`TICK_PHASES`: ``counters["programs"]``), and
        ``obs.runs("serve")`` keeps the last few runs, those a profiler
        listened to marked by ``phases.listened``.
        """
        with obs_trace.use_tracer(getattr(telemetry, "tracer", None)):
            return self._run(requests, telemetry, keep_timeline, on_tick,
                             admission)

    def _run(self, requests, telemetry, keep_timeline, on_tick,
             admission) -> dict:
        sched = PagedScheduler(self.max_slots)
        mgr = self.manager
        bs = self.block_size
        n_req = 0
        errors: dict[int, str] = {}
        accepted: list[Request] = []
        with obs_trace.span("submit", trace_id="engine", track="engine"):
            for req in requests:
                try:
                    self._validate(req)
                except ValueError as e:
                    errors[req.uid] = str(e)
                    continue
                sched.submit(req)
                accepted.append(req)
                n_req += 1

        reg = telemetry.registry if telemetry is not None \
            else MetricsRegistry()
        h_ttft = reg.histogram("serve_ttft_seconds")
        h_itl = reg.histogram("serve_intertoken_seconds")
        h_e2e = reg.histogram("serve_e2e_seconds")
        g_queue = reg.gauge("serve_queue_depth")
        g_occ = reg.gauge("serve_slot_occupancy")
        g_blocks = reg.gauge("serve_kv_blocks_in_use")
        g_hit = reg.gauge("serve_prefix_hit_rate")
        reg.gauge("serve_kv_cache_bytes").set(self.kv_cache_bytes)
        pc = obs_trace.PhaseClock(TICK_PHASES, spanless=DISPATCH_PHASES)
        runlog.publish(runlog.RunRecord(
            "serve", pc, reg, engine="paged", max_slots=self.max_slots,
            prefill_chunk=self.chunk))
        (p_admit, p_chunk_prepare, p_chunk_dispatch, p_chunk_commit,
         p_chunk_wait, p_decode_prepare, p_decode_dispatch, p_decode_wait,
         p_decode_commit, p_hook, p_tick_end) = map(pc.phase, TICK_PHASES)

        # per-slot host state: the token stream (prompt + emitted), how
        # many positions hold committed KV, remaining chunk plans, and
        # the pending token (emitted, not yet fed)
        stream: dict[int, list] = {}
        committed: dict[int, int] = {}
        plans: dict[int, list] = {}
        pendtok: dict[int, int] = {}
        first_wall: dict[int, float] = {}
        ttft_s: dict[int, float] = {}
        e2e_s: dict[int, float] = {}
        timeline = [] if keep_timeline else None

        programs: list = []     # the tick's program records, see the loop
        unsettled: list = []    # (chunk record, its result still on the device)
        for k in self.host_io:
            self.host_io[k] = 0
        shared_tokens = prompt_tokens = 0
        chunk_calls = spec_rounds = proposed_total = accepted_total = 0
        decode_ticks = occupancy_sum = 0
        t_prefill = t_decode = 0.0

        # per-request spans (queued, admit, prefix_match, prefill_chunk,
        # decode, retire) are causal records, not regions of host code:
        # they go to the Tracer alone, from clock reads the tick takes
        # anyway; regions go through obs_trace.span / the phase clock
        tracer = obs_trace.installed_tracer()
        recorder = getattr(telemetry, "recorder", None) \
            if telemetry is not None else None
        live = LiveSignals()
        root_span: dict[int, int] = {}       # uid -> open request span
        last_tok_wall: dict[int, float] = {}  # uid -> last emit wall
        last_window_emit = -float("inf")
        slo_tripped = False
        if recorder is not None:
            # block-manager events (evictions, COW detaches) go straight
            # into the black box; cleared before run() returns because
            # the manager outlives the run
            mgr.on_event = (lambda kind, **f:
                            recorder.record("kv_" + kind, **f))

        def check_slo(req, now):
            """Compare measured latencies against the request's SLOs;
            breaches land in the flight recorder and the FIRST breach
            trips a dump (the black box for "why did we fall off SLO")."""
            nonlocal slo_tripped
            breaches = []
            t = ttft_s.get(req.uid)
            if req.slo_ttft_ms is not None and t is not None \
                    and t * 1e3 > req.slo_ttft_ms:
                breaches.append(("ttft", t * 1e3, req.slo_ttft_ms))
            e = e2e_s.get(req.uid)
            if req.slo_e2e_ms is not None and e is not None \
                    and e * 1e3 > req.slo_e2e_ms:
                breaches.append(("e2e", e * 1e3, req.slo_e2e_ms))
            for which, ms, slo in breaches:
                recorder.record("slo_breach", uid=req.uid, which=which,
                                measured_ms=ms, slo_ms=slo)
            if breaches and not slo_tripped:
                slo_tripped = True
                recorder.trip("slo_breach")

        def retire(req, idx, now):
            mgr.release(idx)
            for d in (stream, committed, plans, pendtok):
                d.pop(idx, None)
            arr = sched.arrival_wall.get(req.uid, now)
            e2e_s[req.uid] = now - arr
            h_e2e.observe(now - arr)
            n_tok = len(sched.finished[req.uid])
            fw = first_wall.pop(req.uid, None)
            if fw is not None and n_tok > 1:
                h_itl.observe((now - fw) / (n_tok - 1))
            last_tok_wall.pop(req.uid, None)
            if tracer is not None:
                rid = root_span.pop(req.uid, None)
                tracer.add("retire", now, now, req.trace_id, parent=rid,
                           track=f"req{req.uid}", tokens=n_tok, slot=idx)
                if rid is not None:
                    tracer.end(rid, t1=now)
            if recorder is not None:
                recorder.record("retire", uid=req.uid, slot=idx,
                                tokens=n_tok)
                check_slo(req, now)

        def emit(idx, token, now):
            """Record one generated token; True when the slot retired
            (EOS or budget — same truncation rules as v1/generate)."""
            uid = sched.slots[idx].request.uid
            lt = last_tok_wall.get(uid)
            if lt is not None:
                live.observe_itl(now - lt, now)
            last_tok_wall[uid] = now
            done = sched.record(idx, token, self.eos_id)
            if done is not None:
                retire(done, idx, now)
                return True
            return False

        def make_writable(idx, lo, hi):
            """COW check; with a Tracer a copy's ``cow`` span joins the
            request's own chain."""
            whose = None
            if tracer is not None:
                req = sched.slots[idx].request
                whose = {"trace_id": req.trace_id,
                         "track": f"req{req.uid}",
                         "parent": root_span.get(req.uid)}
            self._make_writable(idx, lo, hi, whose)

        def settle():
            """Fetch the expert loads of the chunks that have run and were
            not a request's last.  Called where the host is about to wait
            for a LATER program anyway, before that barrier: the device is
            busy, the loads are done, so the fetch is on nobody's critical
            path (fetched after the chunk's own barrier it cost the glm
            cell 1.9% of its tokens/s, my chip runs, PR 37)."""
            for rec, down in unsettled:
                rec["experts"] = expert_counters(
                    self.fetch(self._chunk_io[1], down, rec["io"])["load"])
            unsettled.clear()

        def run_chunk(idx, ev):
            nonlocal chunk_calls, t_prefill
            with p_chunk_prepare:
                req = sched.slots[idx].request
                plan = plans[idx].pop(0)
                L = len(req.prompt)
                toks = chunk_tokens(stream[idx], plan, self.chunk,
                                    self.pad_fill)
                make_writable(idx, committed[idx], plan.commit_to - 1)
                wb, wo, written = write_targets(plan.feed_start, self.chunk,
                                                committed[idx], L,
                                                mgr.tables[idx], bs)
                ring_wb = mgr.ring_targets(
                    idx, plan.feed_start + np.arange(self.chunk), written)
                io = [0, 0]
                up = self.put(
                    self._chunk_io[0], io, toks=toks,
                    table=mgr.device_tables(idx), pos=plan.feed_start,
                    logit_idx=max(plan.logit_index, 0),
                    wb=wb if ring_wb is None else (wb, ring_wb), wo=wo)
                if self.draft_layers is not None:
                    # the draft's twin keeps its separate arguments (and a
                    # model with rings has no draft)
                    draft_args = tuple(map(jnp.asarray, (
                        toks, mgr.tables[idx], np.int32(plan.feed_start),
                        wb, wo)))
                n_live = (min(plan.feed_start + self.chunk, L)
                          - max(plan.feed_start, committed[idx]))
            t0 = time.perf_counter()
            with p_chunk_dispatch:
                self.pools, down = self._chunk_prog(
                    self.params, self.pools, up, self._next_key())
                if self.draft_layers is not None:
                    self.draft_pools = self._draft_chunk(
                        self.draft_params, self.draft_pools, *draft_args)
            # the dispatch has returned: the program ran (or will), so it
            # gets its record; the instants are the phases' own readings
            rec = {"program": "paged_chunk",
                   "at": [p_chunk_dispatch.t0, p_chunk_dispatch.t1, None],
                   "slot": idx, "uid": req.uid, "start": plan.feed_start,
                   "live": n_live, "io": io}
            programs.append(rec)
            with p_chunk_commit:    # while the device runs the chunk
                committed[idx] = plan.commit_to
                mgr.register_committed(idx, stream[idx], committed[idx])
                chunk_calls += 1
                if ev is not None:
                    ev["chunks"].append(req.uid)
                sched.note_chunk(idx)
            with p_chunk_wait:
                settle()                # earlier chunks', behind this one
                if plan.is_last:
                    # the one fetch is the barrier: the first token, its
                    # logprob, its flag and the chunk's load together
                    got = self.fetch(self._chunk_io[1], down, io)
                    first = int(got["tok"])
                    if got["load"] is not None:
                        rec["experts"] = expert_counters(got["load"])
                else:
                    jax.block_until_ready(self.pools)
                    if self._load_like is not None:
                        unsettled.append((rec, down))
            rec["at"][2] = p_chunk_wait.t1
            now = time.perf_counter()
            t_prefill += now - t0
            if tracer is not None:
                tracer.add("prefill_chunk", t0, now, req.trace_id,
                           parent=root_span.get(req.uid),
                           track=f"req{req.uid}",
                           feed_start=plan.feed_start,
                           commit_to=plan.commit_to, is_last=plan.is_last)
            if not plan.is_last:
                return
            pendtok[idx] = first
            first_wall[req.uid] = now
            ttft_s[req.uid] = now - sched.arrival_wall.get(req.uid, now)
            h_ttft.observe(ttft_s[req.uid])
            live.observe_ttft(ttft_s[req.uid], now)
            if on_tick is not None:
                with p_hook:
                    on_tick(TickReport(
                        tick=tick, kind="prefill", elapsed_s=now - t0,
                        emitted=[(req.uid, first)],
                        finite={req.uid: bool(got["ok"])},
                        logprob={req.uid: float(got["lp"])},
                        slots=[idx], engine=self,
                        queue_depth=sched.queue_depth(tick)))
            with p_chunk_commit:
                stream[idx].append(first)
                emit(idx, first, now)

        # --- priority preemption (opt-in): spilled-slot parking lot ----
        spilled: list[_SpillRecord] = []
        preempt_count = resume_count = spill_seq = 0

        def preempt_one(head, ev):
            """Spill ONE victim slot to make room for ``head``.  The
            victim is the lowest-priority decoding slot strictly below
            ``head`` (priority 0 is structurally unpreemptable: nothing
            outranks it), most-progressed first so the evicted work is
            the cheapest to finish later.  Returns False when no
            eligible victim exists."""
            nonlocal preempt_count, spill_seq
            cands = [i for i in sched.decoding_slots()
                     if sched.slots[i].request.priority > head.priority]
            if not cands:
                return False
            victim = sorted(
                cands,
                key=lambda i: (-sched.slots[i].request.priority,
                               len(sched.slots[i].generated), i))[0]
            t0_sp = time.perf_counter()
            kv_dev = self._spill(self.pools,
                                 jnp.asarray(mgr.tables[victim]))
            if self.migrate_kind == "device":
                # device-to-device handoff: digest the at-rest image,
                # then park it on the spill device via the chunked
                # migration schedule — no host copy, no barrier beyond
                # the digest read (which doubles as the audit)
                from distributed_deep_learning_tpu.serve import \
                    migrate as migrate_mod
                digest = migrate_mod.tree_digest(kv_dev)
                payload = kv_dev
                if self._migrate_chaos is not None:
                    payload = self._migrate_chaos(payload)
                kv = migrate_mod.offload(payload, self._spill_device)
                self._spill_moves += 1
                self._spill_move_bytes += migrate_mod.tree_bytes(kv_dev)
                self._spill_move_seconds += time.perf_counter() - t0_sp
            else:
                kv = jax.tree.map(np.asarray, kv_dev)  # host copy=barrier
                digest = None
            req, gen = sched.preempt(victim)
            mgr.release(victim)
            rec = _SpillRecord(request=req, generated=gen,
                               stream=stream.pop(victim),
                               committed=committed.pop(victim),
                               pendtok=pendtok.pop(victim),
                               kv=kv, seq=spill_seq, digest=digest)
            plans.pop(victim, None)
            spill_seq += 1
            spilled.append(rec)
            preempt_count += 1
            if self.spill_dir is not None:
                np.savez(os.path.join(
                    self.spill_dir, f"spill-{req.uid}-{rec.seq}.npz"),
                    **{f"leaf_{i:05d}": leaf for i, leaf in
                       enumerate(jax.tree.leaves(kv))})
            if ev is not None:
                ev["preempted"].append(req.uid)
            if recorder is not None:
                recorder.record("preempt", uid=req.uid, slot=victim,
                                committed=rec.committed,
                                by_uid=head.uid)
            return True

        def resume_one(ev):
            """Un-park the best spilled request (highest priority, then
            FIFO) into a free slot: fresh block budget, scatter the
            committed KV image back, restore the host stream state.
            Bit-identity holds because every committed position returns
            in its at-rest representation and greedy decode is batch-
            invariant.  False when no slot/budget is available."""
            nonlocal resume_count
            if not spilled or sched.occupancy >= self.max_slots:
                return False
            rec = min(spilled, key=lambda r: (r.request.priority, r.seq))
            need = self._capacity_len(rec.request)
            sp0 = paged.SharedPrefix([], None, 0, b"")
            if not mgr.can_admit(sp0, need):
                return False
            idx = sched.restore(rec.request, rec.generated)
            if idx is None:
                return False
            mgr.admit(idx, sp0, need)
            pidx = np.arange(self.padded_len)
            blocks = np.where(pidx < rec.committed,
                              mgr.tables[idx][pidx // bs],
                              paged.TRASH).astype(np.int32)
            offsets = (pidx % bs).astype(np.int32)
            if self.migrate_kind == "device":
                # hop the parked image back, then verify the round trip
                # end to end: a transfer lost or corrupted in EITHER
                # direction surfaces here, before anything is scattered
                # into the live pools
                from distributed_deep_learning_tpu.serve import \
                    migrate as migrate_mod
                t0_rs = time.perf_counter()
                kv_in = migrate_mod.offload(rec.kv, self._home_device)
                if rec.digest is not None and \
                        migrate_mod.tree_digest(kv_in) != rec.digest:
                    raise migrate_mod.MigrationError(
                        f"device spill/resume of request "
                        f"{rec.request.uid} failed its digest check — "
                        f"KV transfer lost or corrupted; replay from "
                        f"the ledger")
                self._spill_moves += 1
                self._spill_move_bytes += migrate_mod.tree_bytes(kv_in)
                self._spill_move_seconds += time.perf_counter() - t0_rs
                # the hop commits kv_in to the home device, but pools
                # born under a training mesh can live replicated across
                # it — match their placement or the scatter jit rejects
                # the mixed commitment
                kv_in = jax.device_put(
                    kv_in,
                    jax.tree.map(lambda l: l.sharding, self.pools))
            else:
                kv_in = jax.tree.map(jnp.asarray, rec.kv)
            self.pools = self._unspill(
                self.pools, kv_in,
                jnp.asarray(blocks), jnp.asarray(offsets))
            stream[idx] = rec.stream
            committed[idx] = rec.committed
            pendtok[idx] = rec.pendtok
            plans.pop(idx, None)
            mgr.register_committed(idx, stream[idx], committed[idx])
            spilled.remove(rec)
            resume_count += 1
            if ev is not None:
                ev["resumed"].append(rec.request.uid)
            if recorder is not None:
                recorder.record("resume", uid=rec.request.uid, slot=idx,
                                committed=rec.committed)
            return True

        def admit_all(tick, ev):
            """One tick's admission: place, resume, shed and preempt until
            the head of the queue has to wait."""
            nonlocal shared_tokens, prompt_tokens
            # admission: FIFO while a slot AND its whole block budget
            # are available (no partial admission, no pool deadlock);
            # an AdmissionController may shed the head first — placed
            # slots are never touched, so shedding cannot starve them
            while True:
                can_place = sched.occupancy < self.max_slots
                if not can_place and not self._preempt:
                    break              # legacy: a full house just waits
                head = sched.peek(tick)
                # resume politeness: a parked request was admitted once
                # already — it outranks any queue head of equal or lower
                # priority for the next free slot
                if self._preempt and spilled and can_place:
                    best = min(spilled,
                               key=lambda r: (r.request.priority, r.seq))
                    if head is None or \
                            best.request.priority <= head.priority:
                        if resume_one(ev):
                            continue
                if head is None:
                    break
                if admission is not None:
                    reason = admission.should_shed(
                        head, sched.queue_depth(tick))
                    if reason is not None:
                        shed_req = sched.drop_head(tick)
                        errors[shed_req.uid] = f"shed: {reason}"
                        if ev is not None:
                            ev["shed"].append(shed_req.uid)
                        if recorder is not None:
                            recorder.record("shed", uid=shed_req.uid,
                                            reason=reason)
                        continue
                if not can_place:
                    # slot pressure: evict a strictly-lower-priority
                    # victim so the head can take its slot — or stop if
                    # nothing outranked sits in one
                    if not preempt_one(head, ev):
                        break
                    continue
                t_adm = time.perf_counter()
                sp = mgr.match_prefix(head.prompt)
                need_ok = mgr.can_admit(sp, self._capacity_len(head))
                while not need_ok and self._preempt:
                    # make room by spilling strictly-lower-priority
                    # slots; each preemption shrinks the victim set, so
                    # this terminates.  Re-match after every eviction —
                    # releasing a victim can change the shareable prefix
                    if not preempt_one(head, ev):
                        break
                    sp = mgr.match_prefix(head.prompt)
                    need_ok = mgr.can_admit(sp,
                                            self._capacity_len(head))
                if not need_ok:
                    break              # wait for retirements to free KV
                idx, req = sched.place(tick)
                shared = mgr.admit(idx, sp, self._capacity_len(req))
                L = len(req.prompt)
                stream[idx] = [int(t) for t in req.prompt]
                committed[idx] = shared
                plans[idx] = plan_chunks(shared, L, self.chunk)
                sched.begin_prefill(idx, len(plans[idx]))
                shared_tokens += shared
                prompt_tokens += L
                if ev is not None:
                    ev["placed"].append(req.uid)
                if tracer is not None:
                    noww = time.perf_counter()
                    arr = sched.arrival_wall.get(req.uid, noww)
                    trk = f"req{req.uid}"
                    rid = tracer.begin("request", req.trace_id,
                                       track=trk, t0=arr, prompt_len=L,
                                       max_new_tokens=req.max_new_tokens)
                    root_span[req.uid] = rid
                    tracer.add("queued", arr, t_adm, req.trace_id,
                               parent=rid, track=trk)
                    aid = tracer.add("admit", t_adm, noww, req.trace_id,
                                     parent=rid, track=trk, slot=idx,
                                     shared_len=shared)
                    tracer.add("prefix_match", t_adm, noww, req.trace_id,
                               parent=aid, track=trk, shared_len=shared,
                               hit=shared > 0)
                if recorder is not None:
                    recorder.record("admit", uid=req.uid, slot=idx,
                                    shared_len=shared)

        n_latent = self.decode_attn_paths["latent"]
        in_place = self.decode_attn_paths["block_table"] + n_latent
        table_blocks = in_place * self.max_slots * self.blocks_per_slot
        attn_read = attn_tables = 0
        t_start = time.perf_counter()
        tick = 0
        while sched.pending or sched.occupancy or spilled:
            depth = sched.queue_depth(tick)     # walks the queue: once
            with pc.tick(tick, trace_id="engine", track="engine",
                         tick=tick, decoding=sched.occupancy
                         - len(sched.prefilling),
                         prefilling=len(sched.prefilling),
                         queue=depth) as tk:
                ev = ({"tick": tick, "placed": [], "chunks": [],
                       "decoded": [], "shed": [], "preempted": [],
                       "resumed": []} if keep_timeline else None)
                with p_admit:
                    sched.mark_arrivals(tick, time.perf_counter())
                    g_queue.set(depth)
                    admit_all(tick, ev)
                    kv_blocks = mgr.blocks_by_kind()    # as the tick runs

                if not sched.occupancy:
                    nxt = sched.next_arrival()
                    if nxt is None:
                        if spilled:
                            continue   # parked work only: resume next pass
                        break
                    tick = max(tick, nxt)  # idle engine: jump to arrival
                    continue
                occupancy_sum += sched.occupancy
                g_occ.set(sched.occupancy)
                # the tick's counters from here on, so that a tick a
                # raising dispatch aborts keeps the programs it did run
                programs = []
                counters = {"kv_blocks": kv_blocks, "programs": programs}
                tk.meta = (0, 0, counters)

                # chunked prefill under the per-tick budget, round-robin
                budget = self.chunks_per_tick
                ran = 0
                while budget > 0 and sched.prefilling:
                    for idx in sched.chunk_order():
                        if budget == 0:
                            break
                        if idx not in sched.prefilling:
                            continue       # finished earlier this pass
                        run_chunk(idx, ev)
                        budget -= 1
                        ran += 1

                # decode every tick: live streams advance regardless of
                # how much prefill work is queued — the stall bound
                dec = sched.decoding_slots()
                tk.kind = "decode" if dec else "prefill"
                tk.meta = (len(dec), ran, counters)
                if dec and not (self.draft_layers is not None
                                and self._spec_enabled):
                    with p_decode_prepare:
                        # what the decode program reads of what the tables
                        # hold, in blocks over the block-table layers: a
                        # slot attends the blocks its committed positions
                        # fill
                        read = in_place * sum(-(-committed[i] // bs)
                                              for i in dec)
                        counters["attn_blocks"] = {"read": read,
                                                   "tables": table_blocks}
                        if n_latent:
                            # live rows the latent layers' attention reads
                            # this tick, and the bytes it reads of each
                            counters["latent"] = {
                                "rows": n_latent * sum(committed[i]
                                                       for i in dec),
                                "row_bytes": self.latent_row_bytes}
                        attn_read += read
                        attn_tables += table_blocks
                        toks = np.zeros(self.max_slots, np.int32)
                        pos = np.zeros(self.max_slots, np.int32)
                        wb = np.full(self.max_slots, paged.TRASH, np.int32)
                        wo = np.zeros(self.max_slots, np.int32)
                        ring_wb = (None if self.ring_blocks is None
                                   else wb.copy())
                        for i in dec:
                            c = committed[i]
                            make_writable(i, c, c)
                            toks[i] = pendtok[i]
                            pos[i] = c
                            wb[i] = mgr.tables[i, c // bs]
                            wo[i] = c % bs
                            if ring_wb is not None:
                                ring_wb[i] = mgr.ring_targets(i, c, True)
                        t0 = time.perf_counter()
                        if self._canary is None:
                            io = [0, 0]
                            up = self.put(
                                self._decode_io[0], io,
                                tables=mgr.device_tables(), pos=pos,
                                toks=toks, wo=wo,
                                wb=wb if ring_wb is None else (wb, ring_wb))
                            key = self._next_key()
                    if self._canary is not None:
                        out, lp_h, ok_h = self._canary_decode(
                            mgr, pos, toks, wb, wo, dec, pc)
                    else:
                        with p_decode_dispatch:
                            self.pools, down = self._decode(
                                self.params, self.pools, up, key)
                        rec = {"program": "paged_decode",
                               "at": [p_decode_dispatch.t0,
                                      p_decode_dispatch.t1, None],
                               "io": io}
                        programs.append(rec)
                        with p_decode_wait:
                            settle()    # the tick's chunks', behind this one
                            # the one fetch is the barrier
                            got = self.fetch(self._decode_io[1], down, io)
                            out, lp_h, ok_h = (got["toks"], got["lp"],
                                               got["ok"])
                            if got["load"] is not None:
                                rec["experts"] = counters["experts"] = \
                                    expert_counters(got["load"])
                        rec["at"][2] = p_decode_wait.t1
                    now = time.perf_counter()
                    t_decode += now - t0
                    decode_ticks += 1
                    if on_tick is not None:
                        with p_hook:
                            on_tick(TickReport(
                                tick=tick, kind="decode",
                                elapsed_s=now - t0,
                                emitted=[(sched.slots[i].request.uid,
                                          int(out[i])) for i in dec],
                                finite={sched.slots[i].request.uid:
                                        bool(ok_h[i]) for i in dec},
                                logprob={sched.slots[i].request.uid:
                                         float(lp_h[i]) for i in dec},
                                slots=list(dec), engine=self,
                                queue_depth=sched.queue_depth(tick)))
                    with p_decode_commit:
                        for i in dec:
                            tok = int(out[i])
                            committed[i] += 1
                            stream[i].append(tok)
                            mgr.register_committed(i, stream[i],
                                                   committed[i])
                            pendtok[i] = tok
                            r = sched.slots[i].request
                            if ev is not None:
                                ev["decoded"].append(r.uid)
                            if tracer is not None:
                                tracer.add("decode", t0, now, r.trace_id,
                                           parent=root_span.get(r.uid),
                                           track=f"req{r.uid}")
                            emit(i, tok, now)
                elif dec:
                    k = self.spec_k
                    T = k + 1
                    with p_decode_prepare:
                        toks = np.zeros(self.max_slots, np.int32)
                        pos = np.zeros(self.max_slots, np.int32)
                        wb = np.full((self.max_slots, T), paged.TRASH,
                                     np.int32)
                        wo = np.zeros((self.max_slots, T), np.int32)
                        for i in dec:
                            c = committed[i]
                            make_writable(i, c, c + k)
                            toks[i] = pendtok[i]
                            pos[i] = c
                            pp = np.arange(c, c + T)
                            wb[i] = mgr.tables[i][pp // bs]
                            wo[i] = pp % bs
                        tables_dev = jnp.asarray(mgr.tables)
                        pos_dev = jnp.asarray(pos)
                        wb_dev, wo_dev = jnp.asarray(wb), jnp.asarray(wo)
                    t0 = time.perf_counter()
                    with p_decode_dispatch:
                        self.draft_pools, props = self._draft(
                            self.draft_params, self.draft_pools,
                            tables_dev, pos_dev, jnp.asarray(toks),
                            wb_dev, wo_dev)
                    with p_decode_wait:
                        props = np.asarray(props)
                    verify_toks = np.concatenate(
                        [toks[:, None], props], axis=1).astype(np.int32)
                    with p_decode_dispatch:
                        self.pools, g, v_lp, v_ok = self._verify(
                            self.params, self.pools, tables_dev, pos_dev,
                            jnp.asarray(verify_toks), wb_dev, wo_dev)
                    with p_decode_wait:
                        g = np.asarray(g)   # host fetch = device barrier
                        v_lp, v_ok = np.asarray(v_lp), np.asarray(v_ok)
                    now = time.perf_counter()
                    t_decode += now - t0
                    decode_ticks += 1
                    spec_rounds += len(dec)
                    # acceptance decided BEFORE any state mutates, so
                    # the tick report (and a hook that rejects it) sees
                    # exactly what would be committed
                    acc = {i: spec_mod.greedy_accept(props[i], g[i])
                           for i in dec}
                    if on_tick is not None:
                        with p_hook:
                            on_tick(TickReport(
                                tick=tick, kind="decode",
                                elapsed_s=now - t0,
                                emitted=[(sched.slots[i].request.uid,
                                          int(t))
                                         for i in dec for t in acc[i][1]],
                                finite={sched.slots[i].request.uid:
                                        bool(v_ok[i]) for i in dec},
                                logprob={sched.slots[i].request.uid:
                                         float(v_lp[i, 0]) for i in dec},
                                slots=list(dec), engine=self,
                                queue_depth=sched.queue_depth(tick)))
                    with p_decode_commit:
                        for i in dec:
                            a, emitted = acc[i]
                            proposed_total += k
                            accepted_total += a
                            committed[i] += a + 1
                            r = sched.slots[i].request
                            if ev is not None:
                                ev["decoded"].append(r.uid)
                            if tracer is not None:
                                tracer.add("decode", t0, now, r.trace_id,
                                           parent=root_span.get(r.uid),
                                           track=f"req{r.uid}",
                                           accepted=a)
                            retired = False
                            for tok in emitted:
                                stream[i].append(tok)
                                if emit(i, tok, now):
                                    retired = True
                                    break
                            if not retired:
                                pendtok[i] = emitted[-1]
                                mgr.register_committed(i, stream[i],
                                                       committed[i])
                with p_tick_end:
                    noww = time.perf_counter()
                    live.sample(sched.queue_depth(tick), sched.occupancy,
                                noww)
                    if admission is not None:
                        admission.observe(live, sched.queue_depth(tick),
                                          noww)
                        admission.apply(self)
                    if telemetry is not None \
                            and noww - last_window_emit >= 1.0:
                        last_window_emit = noww
                        telemetry.writer.emit("obs_window", scope="serve",
                                              **live.signals(noww))
                    if ev is not None:
                        timeline.append(ev)
                tick += 1

        total = time.perf_counter() - t_start
        settle()    # the last chunks', where no program followed
        tokens = int(sum(len(v) for v in sched.finished.values()))
        hit = shared_tokens / prompt_tokens if prompt_tokens else 0.0
        g_blocks.set(mgr.in_use)
        g_hit.set(hit)
        latency = {
            "ttft_p50_s": h_ttft.percentile(50),
            "ttft_p99_s": h_ttft.percentile(99),
            "ttft_mean_s": h_ttft.mean,
            "itl_p50_s": h_itl.percentile(50),
            "itl_p99_s": h_itl.percentile(99),
            "e2e_p50_s": h_e2e.percentile(50),
            "e2e_p99_s": h_e2e.percentile(99),
            "e2e_max_s": h_e2e.max if h_e2e.count else None,
            "measured_requests": h_e2e.count,
        }
        spec_stats = {
            "enabled": self.draft_layers is not None,
            "active_at_end": self._spec_enabled,
            "k": self.spec_k if self.draft_layers is not None else 0,
            "draft_layers": self.draft_layers,
            "rounds": spec_rounds,
            "proposed": proposed_total,
            "accepted": accepted_total,
            "acceptance_rate": (accepted_total / proposed_total)
            if proposed_total else None,
        }
        grouped = {name: said["grouped_product"]
                   for name, said in self.program_notes.items()
                   if "grouped_product" in said}
        stats = {
            "engine": "paged",
            "requests": n_req,
            "rejected": len(errors),
            "generated_tokens": tokens,
            "tokens_per_sec": tokens / total if total else None,
            "total_seconds": total,
            "prefill_seconds": t_prefill,
            "decode_seconds": t_decode,
            "prefill_chunks": chunk_calls,
            "decode_ticks": decode_ticks,
            "mean_slot_occupancy":
                occupancy_sum / decode_ticks if decode_ticks else 0.0,
            "max_slots": self.max_slots,
            "kv_cache_bytes": self.kv_cache_bytes,
            "kv_dtype": self.kv_dtype,
            "weight_dtype": self.weight_dtype,
            "kv_block_size": bs,
            "prefill_chunk": self.chunk,
            "chunk_compiles": self._chunk_prog.traces,
            "decode_compiles": self._decode.traces,
            "copy_compiles": self._copy.traces,
            "restarts": self.restarts,
            "weight_swaps": self.weight_swaps,
            "verify_compiles": self._verify.traces
            if self.draft_layers is not None else 0,
            "draft_compiles": self._draft.traces
            if self.draft_layers is not None else 0,
            "paged": {
                **mgr.stats(),
                "prefix_hit_rate": hit,
                "shared_tokens": shared_tokens,
                "prompt_tokens": prompt_tokens,
                "prefill_tokens_computed": chunk_calls * self.chunk,
                "host_io": dict(self.host_io),
                "decode_attn": {
                    "paths": dict(self.decode_attn_paths),
                    "blocks_read": attn_read,
                    "blocks_in_tables": attn_tables,
                    **({"latent_row_bytes": self.latent_row_bytes}
                       if n_latent else {}),
                },
                # the grouped expert products of each program as traced:
                # calls, rows, path and tiles (no key: no expert layer)
                **({"grouped_product": grouped} if grouped else {}),
            },
            "spec": spec_stats,
            "preempt": {
                "enabled": self._preempt,
                "preemptions": preempt_count,
                "resumes": resume_count,
                "still_spilled": len(spilled),
                "spill_compiles": self._spill.traces,
                "unspill_compiles": self._unspill.traces,
                "spill_path": self.migrate_kind,
                # engine-lifetime device-hop accounting (0 under "host")
                "migration_moves": self._spill_moves,
                "migration_bytes": self._spill_move_bytes,
                "migration_seconds": round(self._spill_move_seconds, 6),
            },
            "slo": slo_report(accepted, ttft_s, e2e_s),
            "latency": latency,
            "window": live.signals(),
            "phases": pc.summary(),
        }
        if recorder is not None:
            mgr.on_event = None
        if telemetry is not None:
            telemetry.writer.emit("obs_serve", stats=stats)
        out = {"results": sched.finished, "errors": errors, "stats": stats}
        if keep_timeline:
            out["timeline"] = timeline
        return out
