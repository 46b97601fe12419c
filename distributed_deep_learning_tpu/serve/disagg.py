"""Disaggregated prefill/decode serving: split the replica, migrate KV.

A unified :class:`..serve.engine.PagedEngine` runs compute-bound
prefill and latency-bound decode on the SAME device: every prompt
chunk stalls the decode streams sharing its chips, and every decode
tick leaves prefill FLOPs idle.  Disaggregation (the DistServe /
Splitwise deployment shape) gives each phase its own device pool and
connects them with the KV-block migration primitive
(:mod:`..serve.migrate`):

* **Prefill workers** run chunked prefill over ``prefill_streams``
  prompts at once through ONE batched (vmapped) chunk program —
  compile-once per chunk width, rows the scheduler leaves empty are
  trash-routed exactly like pad positions.  Each worker owns a normal
  :class:`..serve.paged.BlockManager` with prefix reuse + COW, so
  shared system prompts are computed once per worker, not per request.
* **Decode workers** are slot-bound and run the unified engine's OWN
  compiled decode program (literally the same ``_decode_impl`` — which
  is how disagg keeps greedy outputs bit-identical to the unified
  engine, and ``decode_compiles == 1`` per worker).  Migrated blocks
  arrive with refcount 1 and are never prefix-indexed on the decode
  side, so decode never takes a copy-on-write fault.
* **Migration** hands a finished prompt's committed blocks to the
  least-loaded decode worker as one packed device-to-device transfer.
  The dispatch is async and the host loop does not block on it, so
  migration overlaps the next prefill chunk; block refcounts make the
  early release safe (pool arrays are immutable values — the gather
  captured them).

The orchestration is HOST logic in this class; every device program
belongs to a worker engine and compiles exactly once per worker.
``run()`` honours the engines' ``{"results", "errors", "stats"}``
contract, with ``stats["engine"] == "disagg"`` and a migration
sub-record.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_deep_learning_tpu.models.transformer import (CausalLM,
                                                              cached_apply)
from distributed_deep_learning_tpu.obs.metrics import MetricsRegistry
from distributed_deep_learning_tpu.obs.window import LiveSignals
from distributed_deep_learning_tpu.serve import migrate as migrate_mod
from distributed_deep_learning_tpu.serve import paged
from distributed_deep_learning_tpu.serve.engine import (CountingJit,
                                                        PagedEngine,
                                                        TickReport)
from distributed_deep_learning_tpu.serve.load import slo_report
from distributed_deep_learning_tpu.serve.prefill import (chunk_tokens,
                                                         plan_chunks,
                                                         write_targets)
from distributed_deep_learning_tpu.serve.scheduler import Request


@dataclasses.dataclass
class _Stream:
    """One in-flight prefill on a prefill worker."""

    req: Request
    plans: list
    stream: list          # prompt tokens (host ints)
    committed: int
    shared: int


@dataclasses.dataclass
class _Slot:
    """One decoding request on a decode worker."""

    req: Request
    stream: list          # prompt + generated
    committed: int
    pendtok: int
    generated: list


@dataclasses.dataclass
class _Ready:
    """A finished prefill awaiting migration to a decode worker."""

    worker: int
    si: int
    req: Request
    stream: list
    L: int
    pendtok: int


class _Worker:
    """A device-pinned :class:`PagedEngine` used for its pools,
    manager, and compiled programs — never for its ``run()`` loop."""

    def __init__(self, wid: int, eng: PagedEngine, device):
        self.wid = wid
        self.eng = eng
        self.device = device
        self.streams: dict[int, _Stream] = {}   # prefill role
        self.slots: dict[int, _Slot] = {}       # decode role
        eng.params = migrate_mod.offload(eng.params, device)
        eng.pools = migrate_mod.offload(eng.pools, device)


class DisaggEngine:
    """Prefill/decode-disaggregated serving over >= 2 local devices.

    ``prefill_workers`` + ``decode_workers`` devices are taken from
    ``devices`` (default ``jax.local_devices()``) in order: prefill
    pools first, then decode.  Every worker shares one model geometry
    and the same at-rest KV representation (``kv_dtype``), so
    migration round trips are bit-exact; greedy outputs are therefore
    bit-identical to a unified :class:`PagedEngine` serving the same
    trace.

    ``wire`` selects the migration wire format (``"at_rest"`` exact,
    ``"int8"`` re-quantized — see :mod:`..serve.migrate`).
    """

    def __init__(self, model: CausalLM, params, *,
                 prefill_workers: int = 1, decode_workers: int = 1,
                 prefill_streams: int = 4, max_slots: int = 8,
                 max_len: Optional[int] = None, kv_block_size: int = 16,
                 num_blocks: Optional[int] = None, prefill_chunk: int = 32,
                 eos_id: Optional[int] = None, temperature: float = 0.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 rng=None, kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None, wire: str = "at_rest",
                 decode_passes: int = 2, devices=None, telemetry=None):
        if prefill_workers < 1 or decode_workers < 1:
            raise ValueError(f"need >= 1 worker of each kind, got "
                             f"prefill={prefill_workers} "
                             f"decode={decode_workers}")
        if prefill_streams < 1:
            raise ValueError(f"prefill_streams must be >= 1, got "
                             f"{prefill_streams}")
        if decode_passes < 1:
            raise ValueError(f"decode_passes must be >= 1, got "
                             f"{decode_passes}")
        devices = list(devices if devices is not None
                       else jax.local_devices())
        need = prefill_workers + decode_workers
        if len(devices) < 2:
            raise ValueError(
                "disaggregated serving needs >= 2 local devices (one "
                "per pool); only 1 is visible — run under a "
                "multi-device mesh or use the unified PagedEngine")
        if need > len(devices):
            raise ValueError(
                f"{prefill_workers} prefill + {decode_workers} decode "
                f"workers need {need} devices; only {len(devices)} "
                f"visible")
        if wire == "int8" and kv_dtype == "int8":
            raise ValueError(
                "wire='int8' over int8+scales pools is a no-op with "
                "extra loss (the at-rest wire already moves int8); use "
                "wire='at_rest'")
        kw = dict(max_len=max_len, kv_block_size=kv_block_size,
                  prefill_chunk=prefill_chunk,
                  eos_id=eos_id, temperature=temperature, top_k=top_k,
                  top_p=top_p, kv_dtype=kv_dtype,
                  weight_dtype=weight_dtype, donate=False)
        # kept for role elasticity: reassign() rebuilds a worker engine
        # with the OTHER role's geometry on the same device
        self._engine_kw = dict(kw)
        self._params = params
        self._num_blocks = num_blocks
        # prefill pools keep the 2x default (or the caller's override)
        # so the prefix index can retain shared blocks across requests;
        # decode pools are EXACT-FIT — decode never prefix-matches, so
        # every extra block would just make each tick's functional pool
        # update (and each migration scatter) copy more bytes.  Per-role
        # pool sizing is the point of disaggregating.
        self.prefill = [
            _Worker(w, PagedEngine(model, params,
                                   max_slots=prefill_streams,
                                   num_blocks=num_blocks, **kw),
                    devices[w])
            for w in range(prefill_workers)]
        if self.prefill[0].eng.ring_blocks is not None:
            # the prefill -> decode hand-off copies a slot's blocks by its
            # one block table; a window layer's ring is not in it
            raise ValueError(PagedEngine._two_kinds(
                "disaggregated serving (--disagg)"))
        bs = int(kv_block_size)
        plen = self.prefill[0].eng.padded_len
        self.decode = [
            _Worker(w, PagedEngine(model, params, max_slots=max_slots,
                                   num_blocks=max_slots * (plen // bs),
                                   **kw), devices[prefill_workers + w])
            for w in range(decode_workers)]
        e0 = self.decode[0].eng
        self.model = model
        self.eos_id = eos_id
        self.temperature = temperature
        self.max_slots = int(max_slots)
        self.prefill_streams = int(prefill_streams)
        self.block_size = e0.block_size
        self.chunk = e0.chunk
        self.max_len = e0.max_len
        self.padded_len = e0.padded_len
        self.pad_fill = e0.pad_fill
        self.kv_dtype, self.weight_dtype = kv_dtype, weight_dtype
        self.wire = wire
        # a prefill call is prefill_streams prompts wide, so decode
        # would otherwise tick once per ~4 prompt-chunks of work and
        # inter-token gaps would stretch during mixed phases; letting
        # the decode pool tick decode_passes times per iteration keeps
        # its cadence near the unified engine's 1 chunk : 1 tick
        self.decode_passes = int(decode_passes)
        self._key = rng if rng is not None else jax.random.key(0)
        reg = telemetry.registry if telemetry is not None else None
        self.migrator = migrate_mod.BlockMigrator(
            e0.blocks_per_slot, wire=wire, registry=reg)
        # one batched chunk program per prefill worker (compile-once
        # per worker: its pools/params are device-committed, so the
        # trace binds to that worker's device)
        self._bchunk = [CountingJit(self._make_batch_chunk(w.eng),
                                    "disagg_batch_chunk")
                        for w in self.prefill]
        self.kv_cache_bytes = sum(w.eng.kv_cache_bytes
                                  for w in self.prefill + self.decode)
        self.restarts = 0
        self.pool_reassignments = 0

    # --- compiled program factory --------------------------------------
    def _make_batch_chunk(self, eng: PagedEngine):
        """The unified chunk program, vmapped over ``prefill_streams``
        rows: same gather/forward/extract/scatter math per row (greedy
        parity is row-stable under vmap), one dispatch for the whole
        worker.  Inactive rows run on garbage and write to trash."""
        chunk = eng.chunk

        def impl(params, pools, tokens, tables, pos, logit_idx, wb, wo,
                 key):
            p = eng._wp(params)

            def one(table, q, toks, li):
                cache = eng._gather(pools, table, q)
                hidden, new = cached_apply(eng.lm, p, cache, toks[None])
                span = paged.extract_span(new, q, chunk)
                h_last = jax.lax.dynamic_slice_in_dim(hidden[0], li, 1)[0]
                return span, h_last

            spans, h = jax.vmap(one)(tables, pos, tokens, logit_idx)
            pools = paged.scatter_span(pools, eng._qspan(spans), wb, wo)
            toks, lp, ok = eng._sample(p, h, key)
            return pools, toks, lp, ok

        return impl

    # --- host helpers ---------------------------------------------------
    def _next_key(self):
        if self.temperature == 0.0:
            return self._key
        self._key, sub = jax.random.split(self._key)
        return sub

    def _validate(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens exceeds the serving "
                f"capacity max_len={self.max_len}")
        worst = -(-min(len(req.prompt) + req.max_new_tokens,
                       self.padded_len) // self.block_size)
        cap = min(min(w.eng.num_blocks for w in self.prefill),
                  min(w.eng.num_blocks for w in self.decode))
        if worst > cap:
            raise ValueError(
                f"request {req.uid}: needs up to {worst} KV blocks but "
                f"the smallest worker pool holds only {cap}")

    def _admit_prefill(self, req: Request, shared_out: list) -> bool:
        """Place a request on the least-loaded prefill worker that can
        hold it, reusing that worker's prefix index."""
        L = len(req.prompt)
        for pw in sorted(self.prefill,
                         key=lambda w: (len(w.streams), w.wid)):
            if len(pw.streams) >= self.prefill_streams:
                continue
            mgr = pw.eng.manager
            sp = mgr.match_prefix(req.prompt)
            if not mgr.can_admit(sp, L):
                continue
            si = min(i for i in range(self.prefill_streams)
                     if i not in pw.streams)
            shared = mgr.admit(si, sp, L)
            pw.streams[si] = _Stream(
                req=req, plans=plan_chunks(shared, L, self.chunk),
                stream=[int(t) for t in req.prompt],
                committed=shared, shared=shared)
            shared_out.append(shared)
            return True
        return False

    def _admit_decode(self, item: _Ready) -> bool:
        """Migrate a finished prefill's committed blocks to the
        least-loaded decode worker; frees the prefill stream.  False
        when no decode worker has a slot + block budget (backpressure:
        the blocks stay parked on the prefill worker)."""
        bs = self.block_size
        sp0 = paged.SharedPrefix([], None, 0, b"")
        total = min(item.L + item.req.max_new_tokens, self.padded_len)
        pw = self.prefill[item.worker]
        for dw in sorted(self.decode,
                         key=lambda d: (len(d.slots), d.wid)):
            if len(dw.slots) >= dw.eng.max_slots:
                continue
            if not dw.eng.manager.can_admit(sp0, total):
                continue
            slot = min(i for i in range(dw.eng.max_slots)
                       if i not in dw.slots)
            dw.eng.manager.admit(slot, sp0, total)
            nb = -(-item.L // bs)
            src_ids = [int(b) for b in
                       pw.eng.manager.tables[item.si][:nb]]
            dst_ids = [int(b) for b in
                       dw.eng.manager.tables[slot][:nb]]
            dw.eng.pools = self.migrator.migrate(
                pw.eng.pools, dw.eng.pools, src_ids, dst_ids,
                device=dw.device, trace_id=item.req.trace_id)
            # the gather captured the (immutable) pool values, so the
            # stream's blocks can be released before the transfer
            # completes — the prefix index keeps the reusable ones
            pw.eng.manager.release(item.si)
            del pw.streams[item.si]
            dw.slots[slot] = _Slot(
                req=item.req, stream=list(item.stream),
                committed=item.L, pendtok=item.pendtok,
                generated=[item.pendtok])
            return True
        return False

    def reassign(self, direction: str) -> bool:
        """Move one IDLE worker's device between the prefill and decode
        pools — role elasticity on sustained ``prefill_util`` skew (the
        :class:`..serve.autoscaler.PoolRebalancer` decides, this
        actuates).  The worker's engine is rebuilt with the new role's
        geometry on the same device; its new programs compile on first
        use (compile-once per worker, like any fresh worker).

        ``"to_prefill"`` takes an idle decode worker (no live slots);
        ``"to_decode"`` takes the newest idle prefill worker (prefill
        worker ids index ``self.prefill`` and the batched-chunk program
        list, so only the tail is removable).  Keeps >= 1 worker per
        role and only moves between runs or while the worker is idle;
        returns False when no worker is eligible."""
        if direction not in ("to_prefill", "to_decode"):
            raise ValueError(f"direction must be 'to_prefill' or "
                             f"'to_decode', got {direction!r}")
        kw = self._engine_kw
        bs = self.block_size
        plen = self.padded_len
        if direction == "to_prefill":
            if len(self.decode) < 2:
                return False
            victim = next((d for d in reversed(self.decode)
                           if not d.slots), None)
            if victim is None:
                return False
            self.decode.remove(victim)
            eng = PagedEngine(self.model, self._params,
                              max_slots=self.prefill_streams,
                              num_blocks=self._num_blocks, **kw)
            w = _Worker(len(self.prefill), eng, victim.device)
            self.prefill.append(w)
            self._bchunk.append(CountingJit(self._make_batch_chunk(eng),
                                            "disagg_batch_chunk"))
        else:
            if len(self.prefill) < 2 or self.prefill[-1].streams:
                return False
            victim = self.prefill.pop()
            self._bchunk.pop()
            eng = PagedEngine(self.model, self._params,
                              max_slots=self.max_slots,
                              num_blocks=self.max_slots * (plen // bs),
                              **kw)
            w = _Worker(len(self.decode), eng, victim.device)
            self.decode.append(w)
        self.kv_cache_bytes = sum(x.eng.kv_cache_bytes
                                  for x in self.prefill + self.decode)
        self.pool_reassignments += 1
        return True

    def reset(self) -> None:
        """Warm restart: fresh pools/managers on every worker, same
        compiled programs (the supervisor contract)."""
        for w in self.prefill + self.decode:
            w.eng.reset()
            w.eng.pools = migrate_mod.offload(w.eng.pools, w.device)
            w.streams.clear()
            w.slots.clear()
        self.restarts += 1

    # --- main loop -------------------------------------------------------
    def run(self, requests: Iterable[Request], telemetry=None,
            on_tick=None) -> dict:
        reg = telemetry.registry if telemetry is not None \
            else MetricsRegistry()
        h_ttft = reg.histogram("serve_ttft_seconds")
        h_itl = reg.histogram("serve_intertoken_seconds")
        h_e2e = reg.histogram("serve_e2e_seconds")
        h_tick = reg.histogram("serve_decode_tick_seconds")
        g_queue = reg.gauge("serve_queue_depth")
        g_occ = reg.gauge("serve_slot_occupancy")
        live = LiveSignals()

        errors: dict = {}
        finished: dict = {}
        queue: list[Request] = []
        for r in sorted(requests, key=lambda r: (r.arrival_tick, r.uid)):
            try:
                self._validate(r)
                queue.append(r)
            except ValueError as exc:
                errors[r.uid] = str(exc)
        ready: list[_Ready] = []
        accepted: list[Request] = []
        arrival_wall: dict[int, float] = {}
        first_wall: dict[int, float] = {}
        last_wall: dict[int, float] = {}
        ttft_s: dict[int, float] = {}
        e2e_s: dict[int, float] = {}
        shared_counts: list[int] = []
        prompt_tokens = sum(len(r.prompt) for r in queue)
        chunk_calls = chunk_rows = decode_ticks = 0
        occupancy_sum = 0
        t_prefill = t_decode = 0.0
        rejected = len(errors)
        bs = self.block_size

        def retire(uid, req, gen, now):
            finished[uid] = np.asarray(gen, dtype=req.prompt.dtype)
            arr = arrival_wall.get(uid, now)
            e2e_s[uid] = now - arr
            h_e2e.observe(e2e_s[uid])
            fw = first_wall.get(uid)
            if fw is not None and len(gen) > 1:
                h_itl.observe((now - fw) / (len(gen) - 1))

        def emit(uid, now):
            lt = last_wall.get(uid)
            if lt is not None:
                live.observe_itl(now - lt, now)
            last_wall[uid] = now

        def finish_prefill(pw, si, st, tok, now):
            """First token sampled: emit it; retire single-token /
            instant-EOS requests on the spot, park the rest for
            migration."""
            uid = st.req.uid
            ttft_s[uid] = now - arrival_wall.get(uid, now)
            h_ttft.observe(ttft_s[uid])
            live.observe_ttft(ttft_s[uid], now)
            first_wall[uid] = now
            emit(uid, now)
            done = st.req.max_new_tokens <= 1 or \
                (self.eos_id is not None and tok == self.eos_id)
            if done:
                retire(uid, st.req, [tok], now)
                pw.eng.manager.release(si)
                del pw.streams[si]
            else:
                ready.append(_Ready(worker=pw.wid, si=si, req=st.req,
                                    stream=st.stream + [tok], L=len(
                                        st.req.prompt), pendtok=tok))

        t_start = time.perf_counter()
        tick = 0
        while queue or ready or any(w.streams for w in self.prefill) \
                or any(d.slots for d in self.decode):
            now = time.perf_counter()
            qd = 0
            for r in queue:
                if r.arrival_tick > tick:
                    break
                arrival_wall.setdefault(r.uid, now)
                qd += 1
            g_queue.set(qd)
            progressed = False

            # 1) migrate finished prefills (FIFO) while decode has room
            while ready and self._admit_decode(ready[0]):
                ready.pop(0)
                progressed = True

            # 2) admit arrivals into prefill streams — decode-aware:
            # a prefill only starts when the decode pool will have a
            # slot for its handoff, so queue wait is paid BEFORE the
            # first token (TTFT, like the unified engine) instead of
            # stretching the gap after it (ITL) in the ready queue
            cap = sum(d.eng.max_slots for d in self.decode)
            in_system = len(ready) \
                + sum(len(w.streams) for w in self.prefill) \
                + sum(len(d.slots) for d in self.decode)
            while queue and queue[0].arrival_tick <= tick \
                    and in_system < cap:
                if not self._admit_prefill(queue[0], shared_counts):
                    break
                accepted.append(queue.pop(0))
                in_system += 1
                progressed = True

            # 3) one batched chunk per prefill worker with work.  The
            # host only synchronizes on workers that completed a
            # prompt this tick (their first token is needed); all
            # other chunk dispatches — and every migration above —
            # stay in flight while decode runs.
            for pw in self.prefill:
                active = []
                P = self.prefill_streams
                toks = np.full((P, self.chunk), self.pad_fill, np.int64)
                pos = np.zeros(P, np.int32)
                li = np.zeros(P, np.int32)
                wb = np.full((P, self.chunk), paged.TRASH, np.int32)
                wo = np.zeros((P, self.chunk), np.int32)
                mgr = pw.eng.manager
                for si, st in sorted(pw.streams.items()):
                    if not st.plans:
                        continue            # parked, awaiting migration
                    plan = st.plans.pop(0)
                    L = len(st.req.prompt)
                    pw.eng._make_writable(si, st.committed,
                                          plan.commit_to - 1)
                    toks[si] = chunk_tokens(st.stream, plan, self.chunk,
                                            self.pad_fill)
                    b_r, o_r, _ = write_targets(
                        plan.feed_start, self.chunk, st.committed, L,
                        mgr.tables[si], bs)
                    wb[si], wo[si] = b_r, o_r
                    pos[si] = plan.feed_start
                    li[si] = max(plan.logit_index, 0)
                    active.append((si, st, plan))
                if not active:
                    continue
                t0 = time.perf_counter()
                pw.eng.pools, toks_out, _lp, _ok = self._bchunk[pw.wid](
                    pw.eng.params, pw.eng.pools, jnp.asarray(toks),
                    jnp.asarray(mgr.tables), jnp.asarray(pos),
                    jnp.asarray(li), jnp.asarray(wb), jnp.asarray(wo),
                    self._next_key())
                finals = [a for a in active if a[2].is_last]
                toks_np = np.asarray(toks_out) if finals else None
                now = time.perf_counter()
                t_prefill += now - t0
                chunk_calls += 1
                chunk_rows += len(active)
                progressed = True
                for si, st, plan in active:
                    st.committed = plan.commit_to
                    mgr.register_committed(si, st.stream, st.committed)
                if on_tick is not None:
                    on_tick(TickReport(
                        tick=tick, kind="prefill", elapsed_s=now - t0,
                        emitted=[(st.req.uid, int(toks_np[si]))
                                 for si, st, p in finals],
                        finite={st.req.uid: bool(_f)
                                for (si, st, p), _f in
                                zip(finals, np.asarray(_ok)[
                                    [si for si, _, _ in finals]]
                                    if finals else [])},
                        logprob={}, slots=[si for si, _, _ in active],
                        engine=self, queue_depth=qd))
                for si, st, plan in finals:
                    finish_prefill(pw, si, st, int(toks_np[si]), now)

            # 3b) hand fresh finishes to decode NOW — their migration
            # dispatch overlaps this iteration's decode ticks, and the
            # request's second token lands one tick sooner (ITL)
            while ready and self._admit_decode(ready[0]):
                ready.pop(0)
                progressed = True

            # 4) decode ticks — the unified engine's own compiled
            # program, so tokens are bit-identical to it.  Several
            # passes per iteration (``decode_passes``) keep the decode
            # cadence near the unified 1-chunk : 1-tick ratio even
            # though each prefill call above is prefill_streams prompts
            # wide; finished prefills drain into freed slots between
            # passes.
            for _pass in range(self.decode_passes):
                if _pass:
                    while ready and self._admit_decode(ready[0]):
                        ready.pop(0)
                if not any(d.slots for d in self.decode):
                    break
                for dw in self.decode:
                    if not dw.slots:
                        continue
                    B = dw.eng.max_slots
                    mgr = dw.eng.manager
                    toks = np.full(B, self.pad_fill, np.int32)
                    pos = np.zeros(B, np.int32)
                    wb = np.full(B, paged.TRASH, np.int32)
                    wo = np.zeros(B, np.int32)
                    dec = sorted(dw.slots)
                    for i in dec:
                        sl = dw.slots[i]
                        c = sl.committed
                        dw.eng._make_writable(i, c, c)
                        toks[i] = sl.pendtok
                        pos[i] = c
                        wb[i] = mgr.tables[i, c // bs]
                        wo[i] = c % bs
                    t0 = time.perf_counter()
                    up, down = dw.eng._decode_io
                    dw.eng.pools, got = dw.eng._decode(
                        dw.eng.params, dw.eng.pools,
                        dw.eng.put(up, tables=mgr.tables, pos=pos,
                                   toks=toks, wb=wb, wo=wo),
                        self._next_key())
                    got = dw.eng.fetch(down, got)   # the one fetch = barrier
                    out, lp_h, ok_h = got["toks"], got["lp"], got["ok"]
                    now = time.perf_counter()
                    t_decode += now - t0
                    h_tick.observe(now - t0)
                    decode_ticks += 1
                    occupancy_sum += len(dec)
                    progressed = True
                    if on_tick is not None:
                        on_tick(TickReport(
                            tick=tick, kind="decode", elapsed_s=now - t0,
                            emitted=[(dw.slots[i].req.uid, int(out[i]))
                                     for i in dec],
                            finite={dw.slots[i].req.uid: bool(ok_h[i])
                                    for i in dec},
                            logprob={dw.slots[i].req.uid: float(lp_h[i])
                                     for i in dec},
                            slots=dec, engine=self, queue_depth=qd))
                    for i in dec:
                        sl = dw.slots[i]
                        tok = int(out[i])
                        sl.committed += 1
                        sl.stream.append(tok)
                        sl.pendtok = tok
                        sl.generated.append(tok)
                        uid = sl.req.uid
                        emit(uid, now)
                        if len(sl.generated) >= sl.req.max_new_tokens or \
                                (self.eos_id is not None
                                 and tok == self.eos_id):
                            retire(uid, sl.req, sl.generated, now)
                            mgr.release(i)
                            del dw.slots[i]
            g_occ.set(sum(len(d.slots) for d in self.decode))
            live.sample(qd, sum(len(d.slots) for d in self.decode), now)

            in_flight = ready or any(w.streams for w in self.prefill) \
                or any(d.slots for d in self.decode)
            if not progressed and not in_flight:
                if queue and queue[0].arrival_tick > tick:
                    tick = queue[0].arrival_tick
                    continue
                if queue:       # arrived but unplaceable: fail loudly
                    r = queue.pop(0)
                    errors[r.uid] = ("disagg: admission stalled with "
                                     "idle workers (request larger "
                                     "than any worker pool?)")
                    rejected += 1
                    continue
            tick += 1

        total = time.perf_counter() - t_start
        generated = sum(len(v) for v in finished.values())
        mig = self.migrator.stats.as_dict()
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
            "engine": "disagg",
            "requests": len(finished) + len(errors),
            "rejected": rejected,
            "generated_tokens": generated,
            "tokens_per_sec": generated / total if total else 0.0,
            "total_seconds": total,
            "prefill_seconds": t_prefill,
            "decode_seconds": t_decode,
            "prefill_chunks": chunk_rows,
            "prefill_chunk_calls": chunk_calls,
            "decode_ticks": decode_ticks,
            "mean_slot_occupancy":
                occupancy_sum / decode_ticks if decode_ticks else 0.0,
            "prefill_workers": len(self.prefill),
            "decode_workers": len(self.decode),
            "prefill_streams": self.prefill_streams,
            "max_slots": self.max_slots,
            # batching efficiency of the vmapped chunk program: useful
            # rows per dispatched row-slot (the prefill-utilization
            # fraction the disagg split is supposed to raise)
            "prefill_util":
                chunk_rows / (chunk_calls * self.prefill_streams)
                if chunk_calls else 0.0,
            "kv_cache_bytes": self.kv_cache_bytes,
            "kv_dtype": self.kv_dtype,
            "weight_dtype": self.weight_dtype,
            "kv_block_size": bs,
            "prefill_chunk": self.chunk,
            "wire": self.wire,
            "chunk_compiles": sum(j.traces for j in self._bchunk),
            "decode_compiles": max(d.eng._decode.traces
                                   for d in self.decode),
            "decode_compiles_per_worker": [d.eng._decode.traces
                                           for d in self.decode],
            "copy_compiles": sum(w.eng._copy.traces
                                 for w in self.prefill + self.decode),
            "migrate_gather_compiles": self.migrator._gather.traces,
            "migrate_scatter_compiles": self.migrator._scatter.traces,
            "restarts": self.restarts,
            "pool_reassignments": self.pool_reassignments,
            "migration": mig,
            "paged": {
                "prefill_workers": [w.eng.manager.stats()
                                    for w in self.prefill],
                "prefix_hit_rate":
                    sum(shared_counts) / prompt_tokens
                    if prompt_tokens else 0.0,
                "shared_tokens": int(sum(shared_counts)),
                "prompt_tokens": int(prompt_tokens),
                "prefill_tokens_computed": chunk_rows * self.chunk,
            },
            "slo": slo_report(accepted, ttft_s, e2e_s),
            "latency": latency,
            "window": live.signals(),
        }
        if telemetry is not None:
            telemetry.writer.emit("obs_serve", stats=stats)
        return {"results": finished, "errors": errors, "stats": stats}
