"""The span front door (``obs.trace.span``), the always-on phase sums the
paged engine and the loader publish (``obs.last_run``), the compile log,
and the readers and the report that consume them — all on the CPU, where
JAX's profiler works too."""

import contextlib
import importlib
import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_deep_learning_tpu import obs
from distributed_deep_learning_tpu.obs import runlog, xplane
from distributed_deep_learning_tpu.obs import trace as obs_trace
from distributed_deep_learning_tpu.obs.trace import PhaseClock, Tracer, span
from distributed_deep_learning_tpu.models.transformer import random_causal_lm
from distributed_deep_learning_tpu.serve.engine import (DISPATCH_PHASES,
                                                        TICK_PHASES,
                                                        PagedEngine)
from distributed_deep_learning_tpu.serve.load import make_trace
from distributed_deep_learning_tpu.serve.prefill import plan_chunks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engine(**kw):
    model, params = random_causal_lm(seed=3, vocab_size=61, num_layers=2,
                                     d_model=32, num_heads=4, mlp_dim=64,
                                     max_len=96)
    kw = {"max_slots": 3, "max_len": 96, "kv_block_size": 8,
          "prefill_chunk": 8, **kw}
    return PagedEngine(model, params, **kw)


def _requests(seed=4, n=6):
    return list(make_trace(n, vocab_size=61, seed=seed, prompt_lens=(4, 20),
                           new_tokens=(3, 6)))


class _Closed(Exception):
    pass


# ------------------------------------------------------------ front door

def test_span_with_nothing_listening_is_the_shared_null_context():
    assert obs_trace.installed_tracer() is None
    a, b = span("x"), span("y", tick=3)
    assert a is b                       # one object, nothing allocated
    with a:
        pass
    pc = PhaseClock(("p", "q"))
    with pc.tick(0) as tk:
        with pc.phase("p"):
            pass
        tk.kind = "work"
    assert pc.phase("p")._span is None  # no span object was ever made
    assert pc.counts == [1, 0] and pc.n_ticks == 1


def test_span_records_into_the_installed_tracer_with_its_parent():
    tr = Tracer()
    with obs_trace.use_tracer(tr):
        assert obs_trace.installed_tracer() is tr
        with span("outer", trace_id="engine", track="engine", tick=7):
            with span("inner"):
                pass
            with span("cow", trace_id="req-5", track="req5", parent=None):
                pass
    assert obs_trace.installed_tracer() is None
    by = {s.name: s for s in tr.spans}
    assert by["inner"].parent_id == by["outer"].span_id
    assert (by["inner"].trace_id, by["inner"].track) == ("engine", "engine")
    assert by["outer"].attrs == {"tick": 7}
    assert by["cow"].trace_id == "req-5"
    assert by["outer"].t0 <= by["inner"].t0 <= by["inner"].t1 <= by["outer"].t1
    # use_tracer(None) leaves an outer tracer in place
    with obs_trace.use_tracer(tr), obs_trace.use_tracer(None):
        assert obs_trace.installed_tracer() is tr


def test_phase_clock_sums_ticks_and_aborted_ticks():
    now = [0.0]
    pc = PhaseClock(("a", "b"), spanless=("b",), ring=2,
                    clock=lambda: now[0])

    def spend(name, dt):
        with pc.phase(name):
            now[0] += dt

    for i in range(3):
        with pc.tick(i) as tk:
            spend("a", 1.0)
            spend("b", 0.5)
            spend("a", 0.25)
            tk.kind, tk.meta = "decode", (i, 1)
    with pytest.raises(_Closed):
        with pc.tick(3):
            spend("a", 2.0)
            raise _Closed
    assert pc.seconds == [5.75, 1.5] and pc.counts == [7, 3]
    assert pc.n_ticks == 4 and len(pc.ticks) == 2       # bounded ring
    assert pc.ticks[0] == (2, "decode", (2, 1), 1.75, (1.25, 0.5))
    assert pc.ticks[1][:2] == (3, "aborted") and pc.ticks[1][4] == (2.0, 0.0)
    assert pc.summary() == {"a": {"seconds": 5.75, "count": 7},
                            "b": {"seconds": 1.5, "count": 3}}


# ------------------------------------------- the tick tree in the xplane

def _as_the_probe_wraps(engine):
    """Wrap the decode program as benchmark/runners/serve.py::_Probe
    does: an object in the engine's attribute that annotates the call."""
    prog = engine._decode

    class Spy:
        traces = property(lambda s: prog.traces)
        _jit = prog._jit

        def __call__(s, *args):
            with jax.profiler.TraceAnnotation("bench:decode_dispatch"):
                return prog(*args)

    engine._decode = Spy()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One tiny engine: an untraced run, then the same requests under the
    profiler AND a Tracer, with the decode program wrapped as the
    benchmark's probe wraps it.  Forty-eight requests, some hundred ticks: what a
    run does ONCE before its first tick (registry, phase clock, publishing
    its record) belongs to no phase and is 3 ms alone, 6-17 ms where the
    thread loses a timeslice to the other test workers; in a window of
    twelve ticks that was 6% to 13% of the idle time, and the share held
    below is of the idle time the ticks' phases own."""
    eng = _engine()
    plain = eng.run(_requests(n=48))
    _as_the_probe_wraps(eng)
    eng.reset()
    d = str(tmp_path_factory.mktemp("xplane"))
    tr = Tracer()
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation("bench:window"), \
                obs_trace.use_tracer(tr):
            out = eng.run(_requests(n=48))
    finally:
        jax.profiler.stop_trace()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        trace = xplane.load(xplane.newest(d))
    return {"plain": plain, "out": out, "trace": trace, "tracer": tr,
            "dir": d}


def test_tracing_changes_neither_outputs_nor_compile_counts(traced):
    plain, out = traced["plain"], traced["out"]
    assert sorted(plain["results"]) == sorted(out["results"])
    for uid, toks in plain["results"].items():
        assert list(toks) == list(out["results"][uid])
    for stats in (plain["stats"], out["stats"]):
        assert stats["chunk_compiles"] == 1 and stats["decode_compiles"] == 1


def test_xplane_holds_the_tick_tree_on_one_clock(traced):
    (line, rows), = traced["trace"]["spans"].items()
    names = {n for _, _, n, _ in rows}
    assert {"ddl:" + p for p in TICK_PHASES} - {"ddl:hook"} <= names
    ticks = [(s, t) for s, t, n, _ in rows if n == "ddl:tick"]
    assert len(ticks) == traced["out"]["stats"]["phases"]["admit"]["count"]
    bench = [(s, t) for s, t, n, _ in rows if n == "bench:decode_dispatch"]
    mine = [(s, t) for s, t, n, _ in rows if n == "ddl:decode_dispatch"]
    assert mine and len(mine) == len(bench)
    for s, t in mine:
        assert any(a <= s and t <= b for a, b in ticks)
        assert any(a <= s and t <= b for a, b in bench)
    assert xplane.nesting(traced["trace"]) == [
        ["decode_dispatch", len(mine), len(mine)]]
    # every phase span lies inside a tick, and a tick's attrs arrive
    for s, t, n, stats in rows:
        if n.startswith("ddl:") and n not in ("ddl:tick", "ddl:submit"):
            assert any(a <= s and t <= b for a, b in ticks), n
        if n == "ddl:tick":
            assert {"tick", "decoding", "prefilling", "queue"} <= set(stats)
    progs = {p[0] for p in xplane.programs(traced["trace"])}
    assert {"jit_paged_chunk", "jit_paged_decode"} <= progs
    assert not any("counted" in p for p in progs)


def test_tracer_gets_the_same_tree_and_the_request_chains(traced):
    spans = list(traced["tracer"].spans)
    by_id = {s.span_id: s for s in spans}
    ticks = [s for s in spans if s.name == "tick"]
    assert ticks and all(s.track == "engine" for s in ticks)
    for s in spans:         # (a request's own ``admit`` is on its track)
        if s.name in TICK_PHASES and s.track == "engine":
            assert by_id[s.parent_id].name == "tick", s.name
    roots = {s.trace_id: s for s in spans if s.name == "request"}
    assert len(roots) == 48             # the fixture's requests
    for s in spans:
        if s.name in ("queued", "prefill_chunk", "decode", "retire"):
            assert s.parent_id == roots[s.trace_id].span_id


def test_idle_falls_under_named_phases_and_the_report_renders(traced):
    idle = xplane.idle_by_phase(traced["trace"])
    assert idle["idle_s"] > 0
    assert idle["named_s"] >= 0.9 * idle["idle_s"]
    assert abs(idle["busy_s"] + idle["idle_s"] - idle["window_s"]) < 1e-6
    assert abs(sum(r[1] + r[2] for r in idle["by_phase"])
               - idle["idle_s"]) < 1e-6
    assert abs(idle["between_s"] + idle["inside_s"] - idle["idle_s"]) < 1e-6
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         "--xplane", traced["dir"]], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    for needle in ("device idle by host phase", "decode_prepare",
                   "jit_paged_decode", "under a named phase",
                   "decode_dispatch", "kv_gather", "kv_write",
                   "CausalLM/layer_*/self_attn"):
        assert needle in proc.stdout, needle


def test_innermost_and_scope_of():
    rows = [(0, 100, "ddl:tick", {}), (10, 30, "ddl:admit", {}),
            (12, 20, "ddl:cow", {}), (40, 60, "ddl:hook", {}),
            (45, 50, "bench:on_tick", {}), (200, 210, "ddl:tick", {})]
    assert xplane.innermost(rows) == [
        (0, 10, "ddl:tick"), (10, 12, "ddl:admit"), (12, 20, "ddl:cow"),
        (20, 30, "ddl:admit"), (30, 40, "ddl:tick"), (40, 60, "ddl:hook"),
        (60, 100, "ddl:tick"), (200, 210, "ddl:tick")]
    f = xplane.scope_of
    assert f("jit(train_step)/transpose(jvp(CausalLM))/layer_7/self_attn/"
             "q/dot_general") == "CausalLM/layer_*/self_attn"
    assert f("jit(paged_decode)/vmap(kv_gather)/gather") == "kv_gather"
    assert f("jit(train_step)/optimizer/mul") == "optimizer"
    assert f("jit(train_step)/jvp(loss)/jit(_take)/gather", 1) == "loss"
    assert f("jit(x)/add") == "(top level) add" and f("") == "(no op_name)"
    assert f("args[1]['layer_0']['self_attn']['cached_key']") == \
        "(argument) args[1]"


# ------------------------------------- records that outlive a raising run

def test_a_run_ended_by_its_hook_leaves_its_record():
    eng = _engine()
    eng.run(_requests(seed=9, n=2))             # an earlier run's record
    before = obs.last_run("serve")
    seen = []

    def hook(report):
        seen.append(report.tick)
        if len(seen) == 7:
            raise _Closed

    with pytest.raises(_Closed):
        eng.run(_requests(), on_tick=hook)
    rec = obs.last_run("serve")
    assert rec is not before and rec.kind == "serve"
    assert rec.meta["max_slots"] == 3 and rec.registry is not None
    pc = rec.phases
    assert pc.names == TICK_PHASES
    assert pc.n_ticks == len(pc.ticks) and pc.ticks[-1][1] == "aborted"
    assert pc.ticks[-1][0] == seen[-1]          # that run's ticks
    done = [t for t in pc.ticks if t[1] != "aborted"]
    assert done and all(t[1] in ("decode", "prefill") for t in done)
    sums = dict(zip(pc.names, pc.seconds))
    assert sums["hook"] > 0 and pc.counts[pc.names.index("hook")] == 7
    for name in ("admit", "decode_dispatch", "decode_wait", "tick_end"):
        assert sums[name] > 0, name
    # a tick's phases add up to its wall time (the rest is loop glue)
    walls = sum(t[3] for t in pc.ticks)
    inside = sum(sum(t[4]) for t in pc.ticks)
    assert 0.8 * walls <= inside <= walls * (1 + 1e-9)
    for t in done:
        assert sum(t[4]) <= t[3] * (1 + 1e-9)
    # and run totals = the ring's rows (nothing fell off this short ring)
    for i, s in enumerate(pc.seconds):
        assert abs(s - sum(t[4][i] for t in pc.ticks)) < 1e-9


def test_stats_carry_the_phase_sums_when_run_returns():
    out = _engine().run(_requests())
    phases = out["stats"]["phases"]
    assert set(phases) <= set(TICK_PHASES) and "hook" not in phases
    assert phases["decode_dispatch"]["count"] == out["stats"]["decode_ticks"]
    assert phases["chunk_dispatch"]["count"] == \
        out["stats"]["prefill_chunks"]
    assert set(DISPATCH_PHASES) <= set(phases)


def test_loader_publishes_its_batches():
    from distributed_deep_learning_tpu.data.datasets import ArrayDataset
    from distributed_deep_learning_tpu.data.loader import DeviceLoader
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    ds = ArrayDataset(np.arange(40 * 3, dtype=np.float32).reshape(40, 3),
                      np.arange(40, dtype=np.int32))
    mesh = build_mesh({"data": 1}, jax.devices()[:1])
    loader = DeviceLoader(ds, np.arange(40), 8, mesh)
    tr = Tracer()
    with obs_trace.use_tracer(tr):
        got = [np.asarray(x) for x, _ in loader]
    assert len(got) == 5 and got[0].shape == (8, 3)
    rec = obs.last_run("loader")
    assert rec is loader.record and rec.meta["global_batch_size"] == 8
    batches = [t for t in rec.phases.ticks if t[1] == "batch"]
    assert len(batches) == 5 and rec.phases.counts == [6, 5]
    assert all(t[4][0] > 0 and t[4][1] > 0 for t in batches)
    list(loader)                                # a second epoch adds on
    assert rec.phases.counts == [12, 10]
    names = [s.name for s in tr.spans]
    assert names.count("batch") == 6 and names.count("h2d_enqueue") == 5
    by_id = {s.span_id: s for s in tr.spans}
    assert all(by_id[s.parent_id].name == "batch" for s in tr.spans
               if s.name in ("batch_form", "h2d_enqueue"))


# ------------------------------------------------------------ compile log

def test_compile_log_names_a_retraced_program():
    from distributed_deep_learning_tpu.runtime.bootstrap import (
        enable_compile_cache)

    enable_compile_cache()
    log = obs.compile_log
    assert log.since_mark() == []               # marked at every start

    def wobbly_step(x):
        return x * 2 + 1

    step = jax.jit(wobbly_step)
    step(jnp.ones(3))
    step(jnp.ones(3))                           # cached: nothing logged
    step(jnp.ones(4))                           # a new shape: a retrace
    mine = [e for e in log.since_mark() if e[1] in (
        "wobbly_step", "jit(wobbly_step)")]
    assert [e[0] for e in mine].count("trace") == 2
    assert [e[0] for e in mine].count("lower") == 2
    assert [e[0] for e in mine].count("compile") == 2
    assert all(e[3] >= 0 and e[2] > 1e9 for e in mine)
    got = log.seconds(["wobbly_step"], ("trace", "lower", "compile"))
    assert all(v > 0 for v in got.values())
    assert log.seconds(["never_ran"]) == {"trace": 0.0, "lower": 0.0}
    enable_compile_cache()
    assert log.since_mark() == []
    eng = _engine()
    eng.run(_requests(n=2))
    named = {e[1] for e in log.since_mark() if e[0] == "trace"}
    assert {"paged_chunk", "paged_decode"} <= named


def test_compile_log_keeps_a_note_made_anywhere_in_the_trace():
    """A program may note a fact about itself at the END of its trace (a
    count it only then knows): the note stays, before the program's own
    trace entry, and the inner programs' traces before it still go."""
    log = obs.compile_log
    log.mark("test")

    @jax.jit
    def inner_piece(x):
        return x + 1

    def noting_step(x):
        y = inner_piece(inner_piece(x) * 2)
        log.note("sites", "jit(noting_step)", "n=2")
        return y

    jax.jit(noting_step)(jnp.ones(3))
    # by name: a thread an earlier test left behind may trace its own
    mine = [e[:2] for e in log.since_mark()
            if e[0] in ("trace", "sites")
            and e[1] in ("inner_piece", "noting_step", "jit(noting_step)")]
    assert mine == [("sites", "jit(noting_step)"), ("trace", "noting_step")]


# ------------------------------------------------- scopes are metadata

def _decode_args(eng):
    return (eng.params, eng.pools,
            jnp.zeros(eng._decode_io[0].size, jnp.int32), eng._next_key())


def _tiny_train_step():
    from distributed_deep_learning_tpu.data.tokens import TokenArrayDataset
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh
    from distributed_deep_learning_tpu.train.state import create_train_state
    from distributed_deep_learning_tpu.utils.config import parse_args
    from distributed_deep_learning_tpu.workloads import base as wb
    from distributed_deep_learning_tpu.workloads import get_spec

    config = parse_args("-l 2 -s 32 -b 4 -m sequential".split(),
                        workload="gpt")
    spec = get_spec("gpt")
    tok = np.zeros((16, 33), np.int32)
    tok[0, 0] = 60
    ds = TokenArrayDataset(tok[:, :-1], tok[:, 1:], 61)
    mesh = build_mesh({"data": 1}, jax.devices()[:1])
    model = spec.build_model(config, ds)
    state = create_train_state(model, jax.random.key(0),
                               spec.example_input(config, ds),
                               wb.build_optimizer(spec, config, 17))
    sspec = wb.derive_state_spec(spec, config, mesh, state)
    step, _ = wb.make_train_eval_steps(config, mesh, spec.build_loss(config),
                                       sspec)
    x = jnp.zeros((4, 32), jnp.int32)
    return step.lower(state, x, x)


def _analysis(lowered):
    from distributed_deep_learning_tpu.utils.profiling import (
        normalize_cost_analysis, normalize_memory_analysis)

    c = lowered.compile()
    return (normalize_cost_analysis(c.cost_analysis()).get("flops"),
            normalize_memory_analysis(c.memory_analysis()), c.as_text())


@pytest.mark.parametrize("program", ["decode", "train_step"])
def test_named_scopes_change_no_cost_and_no_memory(program, monkeypatch):
    def lower():
        if program == "decode":
            eng = _engine()
            return eng._decode._jit.lower(*_decode_args(eng))
        return _tiny_train_step()

    flops, memory, text = _analysis(lower())
    # the decode program gathers rings only: a model of full layers has
    # `kv_paged_attn` (attention over the pools in place) where its
    # `kv_gather` was
    wanted = (("kv_paged_attn", "kv_write", "sample") if program == "decode"
              else ("loss", "optimizer", "head"))
    for scope in wanted:
        assert f"{scope}/" in text or f"({scope})" in text, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    flops0, memory0, text0 = _analysis(lower())
    assert "kv_paged_attn" not in text0 and "optimizer/" not in text0
    assert flops == flops0 and flops > 0
    assert memory == memory0 and memory


# ------------------------------------------------ the benchmark's readers

def _spec(metric):
    with open(os.path.join(REPO, "benchmark", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return importlib.import_module(
        "benchmark.readers." + spec["reader"]).read, spec["args"]


def _serve_record():
    pc = PhaseClock(TICK_PHASES)
    i = {n: k for k, n in enumerate(TICK_PHASES)}

    def row(**ms):
        r = [0.0] * len(TICK_PHASES)
        for k, v in ms.items():
            r[i[k]] = v / 1e3
        return tuple(r)

    # two decode ticks with a chunk, one without, one prefill-only tick,
    # one aborted: (index, kind, (decoding slots, chunks), wall, row)
    with_chunk = dict(admit=0.2, chunk_prepare=1.0, chunk_dispatch=2.0,
                      chunk_commit=0.3, chunk_wait=110.0, decode_prepare=1.5,
                      decode_dispatch=1.0, decode_wait=160.0,
                      decode_commit=0.6, hook=0.9, tick_end=0.1)
    pc.ticks.extend([
        (0, "prefill", (0, 1), 0.1135, row(admit=0.2, chunk_prepare=1.0,
                                           chunk_dispatch=2.0,
                                           chunk_wait=110.0)),
        (1, "decode", (4, 1), 0.2780, row(**with_chunk)),
        (2, "decode", (4, 1), 0.2800, row(**{**with_chunk,
                                             "chunk_wait": 112.0})),
        (3, "decode", (4, 0), 0.1650, row(admit=0.1, decode_prepare=1.5,
                                          decode_dispatch=1.0,
                                          decode_wait=160.0,
                                          decode_commit=0.6, hook=0.9,
                                          tick_end=0.1)),
        (4, "aborted", (), 0.5, row(admit=0.1)),
    ])
    return runlog.RunRecord("serve", pc)


def test_serve_readers_on_a_hand_made_record(monkeypatch):
    monkeypatch.setitem(runlog._LAST, "serve", _serve_record())
    read, args = _spec("serve_tick_host_ms")
    # wall less the four program phases, median over the 3 decode ticks:
    # 278.0 - 273.0, 280.0 - 275.0, 165.0 - 161.0
    assert read({}, **args) == pytest.approx(5.0)
    read, args = _spec("serve_sched_ms")
    # admit + chunk_commit + decode_commit + tick_end: 1.2, 1.2, 0.8
    assert read({}, **args) == pytest.approx(1.2)
    read, args = _spec("serve_chunk_program_ms")
    # dispatch + wait of the ticks that ran a chunk, prefill tick too
    assert read({}, **args) == pytest.approx(112.0)


def test_train_input_reader_on_a_hand_made_record(monkeypatch):
    pc = PhaseClock(("batch_form", "h2d_enqueue"))
    pc.ticks.extend([(0, "batch", (), 0.001, (0.0004, 0.0003)),
                     (1, "batch", (), 0.001, (0.0002, 0.0003)),
                     (2, "batch", (), 0.001, (0.0009, 0.0003)),
                     (3, "idle", (), 0.001, (0.0001, 0.0))])
    monkeypatch.setitem(runlog._LAST, "loader", runlog.RunRecord("loader", pc))
    read, args = _spec("train_input_ms")
    assert read({}, **args) == pytest.approx(0.7)


def test_setup_trace_lower_reader_on_a_hand_made_log(monkeypatch):
    log = runlog.CompileLog()
    log.entries.extend([
        ("trace", "train_step", 1.0, 9.0),      # before the mark: left out
        ("mark", "enable_compile_cache", 2.0, 0.0),
        ("trace", "paged_decode", 3.0, 4.0),
        ("trace", "layer_norm", 3.5, 0.5),      # nested, not a program
        ("lower", "jit(paged_decode)", 7.0, 1.5),
        ("compile", "jit(paged_decode)", 8.5, 30.0),
        ("retrieve", None, 9.0, 0.25),
        ("trace", "token_gaps", 40.0, 6.0),     # the benchmark's reference
        ("trace", "paged_chunk", 50.0, 2.0)])
    monkeypatch.setattr(obs, "compile_log", log)
    read, args = _spec("setup_trace_lower_s")
    assert read({}, **args) == pytest.approx(7.5)


@pytest.mark.parametrize("metric", [
    "serve_tick_host_ms", "serve_sched_ms", "serve_chunk_program_ms",
    "train_input_ms", "setup_trace_lower_s"])
def test_readers_give_none_where_there_is_nothing_to_read(metric,
                                                          monkeypatch):
    monkeypatch.setattr(runlog, "_LAST", {})
    monkeypatch.setattr(obs, "last_run", runlog._LAST.get)
    monkeypatch.setattr(obs, "compile_log", runlog.CompileLog())
    read, args = _spec(metric)
    assert read({}, **args) is None
    # a record with no tick of the kind read gives None too
    empty = PhaseClock(TICK_PHASES if metric.startswith("serve")
                       else ("batch_form", "h2d_enqueue"))
    kind = "serve" if metric.startswith("serve") else "loader"
    monkeypatch.setattr(obs, "last_run",
                        {kind: runlog.RunRecord(kind, empty)}.get)
    assert read({}, **args) is None
    # and so does a program that has no such record at all (the parent)
    monkeypatch.delattr(obs, "last_run")
    monkeypatch.delattr(obs, "compile_log")
    assert read({}, **args) is None


@pytest.mark.parametrize("metric", [
    "serve_tick_host_ms", "serve_sched_ms", "serve_chunk_program_ms",
    "train_input_ms", "setup_trace_lower_s"])
def test_readers_give_none_on_a_record_of_another_shape(metric,
                                                        monkeypatch, capsys):
    """A reader of the program's own records never ends the benchmark's
    run: a program whose record or log is not what this reader knows
    leaves the metric out, and says why."""
    monkeypatch.setattr(obs, "last_run", lambda kind: object())
    monkeypatch.setattr(obs, "compile_log", object())
    read, args = _spec(metric)
    assert read({}, **args) is None
    assert "nothing to read (AttributeError" in capsys.readouterr().out


# ------------------------------- PR 37: program records, starts, the run log

def _flat_programs(pc):
    return [dict(p, tick=k) for k, t in enumerate(pc.ticks) if len(t[2]) > 2
            for p in t[2][2]["programs"]]


def test_the_ring_still_unpacks_into_five_and_keeps_each_ticks_start():
    """A sixth field would silently drop every metric that reads the ring
    through `last_run_phases`; a tick's start lies BESIDE the ring."""
    out = _engine().run(_requests())
    pc = obs.last_run("serve").phases
    for t in pc.ticks:
        index, kind, meta, wall, row = t        # five, as the readers unpack
        assert len(row) == len(TICK_PHASES)
    assert len(pc.started) == len(pc.ticks) == pc.n_ticks
    starts, walls = list(pc.started), [t[3] for t in pc.ticks]
    between = [b - a - w for a, b, w in zip(starts, starts[1:], walls)]
    assert all(g >= 0 for g in between)         # time no tick owns
    assert pc.listened == 0                     # nothing listened
    read, args = _spec("serve_tick_host_ms")
    assert read({}, **args) > 0                 # a run of the new code reads
    read, args = _spec("serve_chunk_program_ms")
    assert read({}, **args) > 0
    assert out["stats"]["decode_ticks"] > 0


def test_a_ticks_programs_are_recorded_in_dispatch_order():
    eng = _engine()
    reqs = _requests()
    out = eng.run(reqs)
    pc = obs.last_run("serve").phases
    progs = _flat_programs(pc)
    names = [p["program"] for p in progs]
    assert names.count("paged_chunk") == out["stats"]["prefill_chunks"]
    assert names.count("paged_decode") == out["stats"]["decode_ticks"]
    # a tick runs its chunks, then its decode program
    for t in pc.ticks:
        if len(t[2]) > 2:
            mine = [p["program"] for p in t[2][2]["programs"]]
            assert mine == (["paged_chunk"] * t[2][1]
                            + ["paged_decode"] * (t[1] == "decode"))
    # dispatch entered <= returned <= ready, and so on across records
    instants = [x for p in progs for x in p["at"]]
    assert None not in instants and instants == sorted(instants)
    # the instants are the phase clock's own: a tick's records lie inside it
    for p in progs:
        t0 = pc.started[p["tick"]]
        assert t0 <= p["at"][0] and p["at"][2] <= t0 + pc.ticks[p["tick"]][3]
    # a chunk says whose it is, where it starts and what it wrote
    chunks = [p for p in progs if p["program"] == "paged_chunk"]
    by_uid = {}
    for p in chunks:
        assert set(p) == {"program", "at", "slot", "uid", "start", "live",
                          "io", "tick"}         # no expert layer: no experts
        assert 0 <= p["slot"] < 3
        by_uid.setdefault(p["uid"], []).append(p)
    for r in reqs:                              # nothing shared in this mix
        mine = by_uid[r.uid]
        # the last slice is shifted back to end at the prompt's end, and
        # writes only what the slices before it did not
        assert [p["start"] for p in mine] == [
            c.feed_start for c in plan_chunks(0, len(r.prompt), 8)]
        assert sum(p["live"] for p in mine) == len(r.prompt)
        assert all(0 < p["live"] <= 8 for p in mine)
    assert all(set(p) == {"program", "at", "io", "tick"} for p in progs
               if p["program"] == "paged_decode")


def test_a_program_that_never_ran_leaves_no_record():
    """The benchmark ends a window by raising out of the program object it
    wraps, before the work: the aborted tick keeps the programs it did run
    and nothing of the one that did not."""
    eng = _engine()
    prog, calls = eng._decode, []

    class Spy:
        traces = property(lambda s: prog.traces)
        _jit = prog._jit

        def __call__(s, *args):
            if len(calls) == 4:
                raise _Closed
            calls.append(1)
            return prog(*args)

    eng._decode = Spy()
    with pytest.raises(_Closed):
        eng.run(_requests())
    pc = obs.last_run("serve").phases
    progs = _flat_programs(pc)
    assert [p["program"] for p in progs].count("paged_decode") == 4
    last = pc.ticks[-1]
    assert last[1] == "aborted" and len(last[2]) > 2
    assert all(p["program"] == "paged_chunk" and p["at"][2] is not None
               for p in last[2][2]["programs"])


def test_the_run_log_keeps_a_listened_run_and_drops_the_fifth():
    kind = "test-kind"

    def record(listen):
        pc = PhaseClock(("a",))
        with (obs_trace.use_tracer(Tracer()) if listen
              else contextlib.nullcontext()):
            with pc.tick(0) as tk:
                tk.kind = "work"
        with pc.tick(1):
            pass
        return runlog.publish(runlog.RunRecord(kind, pc))

    try:
        traced, rest = record(True), record(False)
        assert (traced.phases.listened, rest.phases.listened) == (1, 0)
        assert obs.runs(kind) == [traced, rest]
        assert obs.last_run(kind) is rest
        newest = [r for r in reversed(obs.runs(kind))
                  if r.phases.listened > 0][0]
        assert newest is traced                 # found with no clock
        # published again (a loader's one record, every epoch): once, newest
        runlog.publish(traced)
        assert obs.runs(kind) == [rest, traced]
        assert obs.last_run(kind) is traced
        runlog.publish(traced)
        assert obs.runs(kind) == [rest, traced]
        more = [record(False) for _ in range(3)]
        assert obs.runs(kind) == [traced] + more and len(more) == 3
        assert runlog.KEPT == 4                 # the fifth dropped `rest`
        assert obs.runs("no-such-kind") == []
    finally:
        runlog._RUNS.pop(kind, None)
        runlog._LAST.pop(kind, None)


# --------------------------------- PR 37: the readers of the traced window

def _reader(name):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return importlib.import_module("benchmark.readers." + name)


MS = 1_000_000
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _module(name, start_ms, dur_ms):
    return [DEV, "XLA Modules", f"jit_{name}(7)", int(start_ms * MS),
            int(dur_ms * MS)]


def _traced_pair(n_ticks=4, cut=None, copy_between=False, lag_ms=0.5):
    """A hand-made traced window: `n_ticks` ticks of a chunk (20 ms on
    the device) then a decode program (5 ms), the host 1 ms between a
    result and the next dispatch, the device `lag_ms` behind the host on
    each side (launch 0.3, notice 0.2 of 0.5).  Returns (record, events).
    The host's clock starts at 1000 s, the device's at 0: no reader may
    subtract one from the other."""
    pc = PhaseClock(TICK_PHASES)
    pc.listened = n_ticks
    events = [[HOST, "t", "bench:window", 0, int(1000 * MS)]]
    launch, notice = 0.6 * lag_ms, 0.4 * lag_ms

    def dev_of(host_s):         # ms on the device's clock
        return (host_s - 1000.0) * 1e3 + 2.0

    host = 1000.0
    for k in range(n_ticks):
        t_tick = host
        progs = []
        for name, dur in (("paged_chunk", 20.0), ("paged_decode", 5.0)):
            host += 1e-3                        # the host's bookkeeping
            t_dispatch = host
            dev_start = dev_of(t_dispatch) + launch
            events.append(_module(name, dev_start, dur))
            for j in range(2 * 2):              # two expert layers
                events.append([DEV, "XLA Ops",
                               ("grouped_swiglu" if j % 2 == 0
                                else "grouped_product")
                               + f".{j} [tpu_custom_call]",
                               int((dev_start + 0.1 + j) * MS),
                               int(0.5 * MS)])
            host = t_dispatch + (launch + dur + notice) / 1e3
            rec = {"program": name,
                   "at": (t_dispatch, t_dispatch + 1e-4, host),
                   "experts": {"assignments": 48 if name == "paged_chunk"
                               else 6, "touched": 3.0, "held": 4,
                               "skew": 1.5, "layers": 2}}
            if name == "paged_chunk":
                rec.update(slot=0, uid=k, start=0, live=8)
                if copy_between and k == 1:
                    events.append(_module("paged_copy",
                                          dev_start + dur + 0.2, 0.1))
            progs.append(rec)
        pc.ticks.append((k, "decode", (1, 1, {"kv_blocks": {},
                                              "programs": progs}),
                         host - t_tick, (0.0,) * len(TICK_PHASES)))
        pc.started.append(t_tick)
        pc.n_ticks += 1
    if cut == "event":          # the window closed on the last program
        events = events[:-5]
    elif cut == "record":
        pc.ticks[-1][2][2]["programs"].pop()
    return runlog.RunRecord("serve", pc), events


@pytest.fixture
def traced_window(monkeypatch):
    def install(**kw):
        record, events = _traced_pair(**kw)
        monkeypatch.setitem(runlog._RUNS, "serve", [record])
        monkeypatch.setitem(runlog._LAST, "serve", record)
        cfg = {"hidden_size": 16, "moe_intermediate_size": 8}
        return record, {"trace": {"events": events}, "config": cfg,
                        "peaks": {"hbm_bytes_per_s": 1e9,
                                  "bf16_flops": 1e12}}
    return install


@pytest.mark.parametrize("cut", [None, "event", "record"])
def test_the_join_is_by_order_and_bears_a_cut_last_program(traced_window,
                                                           cut):
    tr = _reader("traced_run")
    record, ctx = traced_window(cut=cut)
    pairs = tr.joined(ctx)
    # 4 chunks; 4 decode programs, 3 where the last lacks its partner
    assert [r["program"] for r, _ in pairs].count("paged_chunk") == 4
    assert len(pairs) == (8 if cut is None else 7)
    # the k-th record of a name met the k-th event of that name, in order
    starts = [ev[0] for _, ev in pairs]
    assert starts == sorted(starts)
    for r, (s, e) in pairs:
        assert (e - s) == (20 if r["program"] == "paged_chunk" else 5) * MS
    assert tr.traced_record() is record and tr.timed_record() is record


def test_the_join_refuses_counts_that_differ_by_more_than_one(
        traced_window, capsys):
    tr = _reader("traced_run")
    record, ctx = traced_window()
    for t in list(record.phases.ticks)[-2:]:
        t[2][2]["programs"].pop()               # two decode records gone
    assert tr.joined(ctx) is None
    assert "2 recorded paged_decode against 4 jit_paged_decode" \
        in capsys.readouterr().out
    # an unlistened record is not the traced window's
    record.phases.listened = 0
    assert tr.traced_record() is None and tr.joined(ctx) is None


def test_the_grouped_cost_at_the_glm_chunk_shape():
    """4,096 rows, 2,048 x 1,536, all 64 experts touched: the fused call
    moves 805 MB of weights + 29 MB of rows, 1.02 ms at 819 GB/s against
    0.26 ms by operations, so the builders' 1.213 ms a call reads 84%."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    cost = importlib.import_module("benchmark.costs.grouped_product").cost
    with open(os.path.join(REPO, "benchmark", "configs",
                           "glm-4.7-flash-d7.json")) as f:
        cfg = json.load(f)
    need = cost(cfg, 64, 4096)
    fused, down = need["swiglu"], need["down"]
    assert fused["weight_bytes"] == 2 * 64 * 2048 * 1536 * 2 == 805_306_368
    assert fused["row_bytes"] == 4096 * (2048 + 1536) * 2 == 29_360_128
    assert fused["bytes"] / 819e9 == pytest.approx(1.019e-3, rel=1e-3)
    assert fused["flops"] / 197e12 == pytest.approx(0.2616e-3, rel=1e-3)
    assert fused["bytes"] / 819e9 / 1.213e-3 == pytest.approx(0.84, abs=5e-3)
    assert down["weight_bytes"] * 2 == fused["weight_bytes"]
    assert down["flops"] * 2 == fused["flops"]
    assert need["bytes"] == fused["bytes"] + down["bytes"]
    # linear in both: six layers' calls are six times one layer's
    assert cost(cfg, 6 * 64, 6 * 4096)["bytes"] == 6 * need["bytes"]


def test_the_grouped_roofline_reads_the_traced_window_alone(traced_window,
                                                            capsys):
    read, args = _spec("grouped_product_roofline")
    record, ctx = traced_window()
    # 8 programs x 4 kernel events of 0.5 ms = 16 ms; touched 8 x 3 x 2
    # experts x 3 matrices of 16 x 8 x 2 B, rows (4 x 48 + 4 x 6) x 2
    # directions... the cost function's sum, bytes-bound at 1 GB/s
    cost = importlib.import_module("benchmark.costs.grouped_product").cost
    need = cost(ctx["config"], 8 * 3.0 * 2, 4 * 48 + 4 * 6)
    assert need["bytes"] / 1e9 > need["flops"] / 1e12
    assert read(ctx, **args) == pytest.approx(
        100.0 * need["bytes"] / 1e9 / 0.016)
    said = capsys.readouterr().out
    assert "8 programs joined (4 chunk, 4 decode), 4 kernel events a " \
           "program" in said and "bound by bytes" in said
    # the timed run that followed holds other counters: they are not read
    other, _ = _traced_pair()
    other.phases.listened = 0
    for t in other.phases.ticks:
        for p in t[2][2]["programs"]:
            p["experts"] = dict(p["experts"], touched=1.0)
    runlog._RUNS["serve"].append(other)
    runlog._LAST["serve"] = other
    assert read(ctx, **args) == pytest.approx(
        100.0 * need["bytes"] / 1e9 / 0.016)
    # a program whose calls went another way: not two events a layer
    ctx["trace"]["events"] = [e for e in ctx["trace"]["events"]
                              if "grouped_product.3" not in e[2]]
    assert read(ctx, **args) is None
    assert "not two a layer" in capsys.readouterr().out
    assert read(dict(ctx, trace=None), **args) is None


def test_chunk_experts_turnaround_and_the_longest_tick(traced_window,
                                                      capsys):
    record, ctx = traced_window()
    read, args = _spec("serve_chunk_expert_touched_pct")
    assert read(ctx, **args) == pytest.approx(75.0)         # 3 of 4 held
    read, args = _spec("serve_turnaround_ms")
    assert read(ctx, **args) == pytest.approx(1.0)          # by construction
    said = capsys.readouterr().out
    assert "turnaround chunk->decode: 4 pairs, median 1.000ms" in said
    assert "turnaround decode->chunk: 3 pairs, median 1.000ms" in said
    # a ring that has lost its oldest ticks still has consecutive pairs,
    # but no order from the run's start for a join
    record.phases.n_ticks += 100
    assert read(ctx, **args) == pytest.approx(1.0)
    assert _reader("traced_run").joined(ctx) is None
    assert "the ring kept 4 of 104 ticks" in capsys.readouterr().out
    record.phases.n_ticks -= 100
    # a stall before tick 2 that no tick owns: 2 s between two ticks
    starts = list(record.phases.started)
    record.phases.started.clear()
    record.phases.started.extend(s + (2.0 if k >= 2 else 0.0)
                                 for k, s in enumerate(starts))
    for t in list(record.phases.ticks)[2:]:
        for p in t[2][2]["programs"]:
            p["at"] = tuple(x + 2.0 for x in p["at"])
    read, args = _spec("serve_tick_longest_ms")
    wall = record.phases.ticks[2][3] * 1e3
    assert read(ctx, **args) == pytest.approx(2000.0 + wall)
    said = capsys.readouterr().out
    assert "tick 2 (decode), 1 slots decoding, 1 chunks" in said
    assert "2000.000ms before it that no tick owns" in said
    assert "1 of 4 over ten times it" in said
    assert "paged_chunk 1.000/1.100/" in said


@pytest.mark.parametrize("copy_between", [False, True])
def test_launch_and_notice_is_the_gap_less_the_turnaround(traced_window,
                                                          copy_between,
                                                          capsys):
    read, args = _spec("serve_launch_notice_ms")
    record, ctx = traced_window(copy_between=copy_between)
    # every gap is 1 ms of host + 0.2 notice + 0.3 launch
    assert read(ctx, **args) == pytest.approx(0.5)
    said = capsys.readouterr().out
    # 7 consecutive pairs; a block copy ran inside one of them
    assert f"{6 if copy_between else 7} of 7 pairs with nothing between" \
        in said
    assert "mean gap 1.500ms = turnaround 1.000 + remainder 0.500" in said
    assert "0.00% of pairs read a negative remainder" in said


def test_launch_and_notice_refuses_a_join_at_fault(traced_window, capsys):
    read, args = _spec("serve_launch_notice_ms")
    record, ctx = traced_window(lag_ms=-0.5)    # the device AHEAD: a fault
    assert read(ctx, **args) is None
    assert "over 1% negative" in capsys.readouterr().out


NEW_METRICS = ["grouped_product_roofline", "serve_chunk_expert_touched_pct",
               "serve_turnaround_ms", "serve_launch_notice_ms",
               "serve_tick_longest_ms"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_give_none_on_the_parents_program(metric, monkeypatch,
                                                      traced_window):
    """The driver runs the new readers over the PARENT's program too: no
    `obs.runs`, no `started`, no program records; nothing raised."""
    read, args = _spec(metric)
    record, ctx = traced_window()
    pc = PhaseClock(TICK_PHASES)                # a PR-36 ring: no programs
    pc.ticks.append((0, "decode", (1, 1, {"kv_blocks": {}}), 0.01,
                     (0.0,) * len(TICK_PHASES)))
    pc.n_ticks = 1
    old = runlog.RunRecord("serve", pc)
    monkeypatch.setitem(runlog._RUNS, "serve", [old])
    monkeypatch.setitem(runlog._LAST, "serve", old)
    del pc.started                              # the parent keeps none
    pc.listened = 1
    assert read(ctx, **args) is None
    monkeypatch.delattr(obs, "runs")            # the parent's package
    assert read(ctx, **args) is None
    monkeypatch.delattr(obs, "last_run")
    assert read(ctx, **args) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_give_none_on_a_record_of_another_shape(metric,
                                                            monkeypatch,
                                                            traced_window,
                                                            capsys):
    read, args = _spec(metric)
    record, ctx = traced_window()
    monkeypatch.setattr(obs, "runs", lambda kind: [object()])
    monkeypatch.setattr(obs, "last_run", lambda kind: object())
    assert read(ctx, **args) is None
    assert "nothing to read (AttributeError" in capsys.readouterr().out


def test_the_report_prints_a_runs_program_records():
    """`scripts/obs_report.py`'s view of what the readers use."""
    if os.path.join(REPO, "scripts") not in sys.path:
        sys.path.insert(0, os.path.join(REPO, "scripts"))
    import obs_report

    _engine().run(_requests())
    text = obs_report.render_programs(obs.last_run("serve"))
    assert "turnaround" in text and "chunk->decode" in text
    assert "longest tick" in text and "no tick owns" in text
    assert obs_report.render_programs(None) == \
        "no serving run has published a record"


@pytest.mark.parametrize("metric", ["grouped_product_roofline",
                                    "serve_chunk_expert_touched_pct",
                                    "serve_launch_notice_ms"])
def test_a_recorded_traced_window_reads_what_the_chip_run_printed(
        metric, monkeypatch):
    """The laguna cell's traced window as recorded on a v5e
    (`benchmark/testdata/programs.recorded.json`; the benchmark's own
    tests, `benchmark/tests/test_traced_run.py`, hold more of it)."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    recorded_window = importlib.import_module(
        "benchmark.tests.test_traced_run")
    record, ctx = recorded_window.recorded()
    monkeypatch.setitem(runlog._RUNS, "serve", [record])
    monkeypatch.setitem(runlog._LAST, "serve", record)
    read, args = _spec(metric)
    assert read(ctx, **args) == pytest.approx(
        recorded_window.PRINTED[metric], rel=1e-12)
