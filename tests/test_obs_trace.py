"""Observability generation 2 (ISSUE 11): per-request causal tracing,
rolling-window live signals, the crash flight recorder and sidecar
rotation.

Clock-sensitive pieces (span causality, window expiry, recorder
determinism) run against INJECTED clocks so every assertion is exact —
wall-clock never decides a pass here.  The process-death paths
(atexit / SIGTERM dumps) run in subprocesses so the hooks fire for
real.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import textwrap

import pytest

from distributed_deep_learning_tpu.obs.recorder import FlightRecorder
from distributed_deep_learning_tpu.obs.trace import (Tracer,
                                                     read_chrome_trace,
                                                     request_trace_id,
                                                     write_chrome_trace)
from distributed_deep_learning_tpu.obs.window import (LiveSignals,
                                                      WindowedHistogram)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    """Deterministic injectable clock: reads return the set time."""

    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


# --- tracer causality ------------------------------------------------------

def test_tracer_causality_under_injected_clock():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tid = request_trace_id(7)
    root = tr.begin("request", tid, t0=0.5, track="req7")
    clk.t = 1.0
    adm = tr.add("admit", 0.9, 1.0, tid, parent=root, slot=2)
    pm = tr.add("prefix_match", 0.95, 0.98, tid, parent=adm,
                hit=True, shared_len=32)
    clk.t = 2.0
    ended = tr.end(root, tokens=5)
    assert ended is not None and ended.t0 == 0.5 and ended.t1 == 2.0
    assert ended.attrs == {"tokens": 5}

    by_id = {s.span_id: s for s in tr.spans}
    assert by_id[pm].parent_id == adm
    assert by_id[adm].parent_id == root
    assert by_id[root].parent_id is None
    assert all(s.trace_id == tid for s in tr.spans)
    # ids are unique and parent spans exist for every non-root link
    assert len(by_id) == len(tr.spans)
    for s in tr.spans:
        if s.parent_id is not None:
            assert s.parent_id in by_id


def test_tracer_ring_bound_and_dropped():
    tr = Tracer(clock=FakeClock(), capacity=4)
    for i in range(10):
        tr.add("e", float(i), float(i) + 0.5, "t")
    assert len(tr.spans) == 4
    assert tr.dropped == 6
    assert [s.t0 for s in tr.spans] == [6.0, 7.0, 8.0, 9.0]


def test_tracer_drain_open_marks_truncated():
    clk = FakeClock(1.0)
    tr = Tracer(clock=clk)
    sid = tr.begin("request", "req-0")
    clk.t = 3.0
    tr.drain_open()
    sp = next(s for s in tr.spans if s.span_id == sid)
    assert sp.t1 == 3.0 and sp.attrs["truncated"] is True
    assert tr.end(sid) is None  # already closed: no-op, no raise


def test_tracer_on_span_feeds_recorder():
    rec = FlightRecorder(clock=None)
    tr = Tracer(clock=FakeClock(), on_span=rec.note_span)
    tr.add("decode", 1.0, 1.25, "req-3", track="engine")
    ev = list(rec.events)[0]
    assert ev["kind"] == "span" and ev["name"] == "decode"
    assert ev["trace_id"] == "req-3" and ev["dur_s"] == 0.25


def test_chrome_export_roundtrip(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    root = tr.begin("request", "req-1", t0=0.001, track="req1")
    tr.add("decode", 0.002, 0.002, "req-1", parent=root, track="engine")
    clk.t = 0.004
    tr.end(root)
    path = str(tmp_path / "trace.json")
    assert tr.export(path) == 2

    with open(path) as f:
        doc = json.load(f)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert {"req1", "engine"} <= names

    evs = read_chrome_trace(path)
    assert all(e["ph"] == "X" for e in evs)
    dec = next(e for e in evs if e["name"] == "decode")
    req = next(e for e in evs if e["name"] == "request")
    assert dec["ts"] == pytest.approx(2000.0)   # seconds -> microseconds
    assert dec["dur"] == 1.0                    # zero-duration floor
    assert req["dur"] == pytest.approx(3000.0)
    assert dec["args"]["parent_id"] == req["args"]["span_id"]
    assert dec["cat"] == "req-1"


# --- rolling windows -------------------------------------------------------

def test_windowed_histogram_expires_old_slices():
    clk = FakeClock()
    h = WindowedHistogram(window_s=10.0, slices=10, clock=clk)
    h.observe(1.0)
    clk.t = 5.0
    h.observe(2.0)
    assert h.count() == 2
    clk.t = 10.5          # t=0 slice now outside the 10 s window
    assert h.count() == 1
    assert h.percentile(50) == pytest.approx(2.0, rel=0.15)
    clk.t = 16.0          # everything expired
    assert h.count() == 0
    assert h.percentile(50) == 0.0


def test_windowed_percentiles_deterministic():
    clk = FakeClock()
    h = WindowedHistogram(window_s=10.0, slices=10, clock=clk)
    for i in range(100):
        clk.t = i * 0.05  # all inside one window
        h.observe(0.001 * (i + 1))
    # log buckets (growth 1.25) guarantee <= ~12% relative error
    assert h.percentile(50) == pytest.approx(0.050, rel=0.15)
    assert h.percentile(99) == pytest.approx(0.100, rel=0.15)
    assert h.count() == 100


def test_live_signals_shape_and_rates():
    clk = FakeClock()
    ls = LiveSignals(window_s=10.0, clock=clk)
    ls.observe_ttft(0.02, now=0.1)
    for i in range(5):
        ls.observe_itl(0.004, now=0.2 + 0.004 * i)
    ls.sample(queue_depth=3, occupancy=6.0, now=0.5)
    sig = ls.signals()
    assert sig["ttft_count"] == 1 and sig["itl_count"] == 5
    assert sig["ttft_p50_s"] == pytest.approx(0.02, rel=0.15)
    assert sig["itl_p99_s"] == pytest.approx(0.004, rel=0.15)
    assert sig["queue_depth_last"] == 3.0
    assert sig["occupancy_last"] == 6.0
    assert sig["request_rate_per_s"] == pytest.approx(0.1)  # 1 / 10 s
    assert sig["token_rate_per_s"] == pytest.approx(0.5)


# --- flight recorder -------------------------------------------------------

def _drive(rec: FlightRecorder) -> None:
    rec.record("admit", uid=0, shared_len=32)
    rec.record("retire", uid=0, tokens=7)
    rec.trip("slo_breach")


def test_flight_recorder_dump_bit_identical(tmp_path):
    """clock=None dumps carry only logical seq numbers and serialize
    with sorted keys: identical event sequences => identical bytes."""
    paths = []
    for i in range(2):
        rec = FlightRecorder(clock=None)
        rec.arm(str(tmp_path / f"bb{i}.json"))
        _drive(rec)
        paths.append(rec.dump_path)
    a, b = (open(p, "rb").read() for p in paths)
    assert a == b and len(a) > 0


def test_flight_recorder_trip_dumps_and_ring_bounds(tmp_path):
    rec = FlightRecorder(capacity=3, clock=None)
    rec.arm(str(tmp_path / "bb.json"))
    for i in range(7):
        rec.record("tick", i=i)
    out = rec.trip("sentinel_anomaly")
    assert out == rec.dump_path
    doc = FlightRecorder.read(out)
    assert doc["format"] == 1
    assert doc["reason"] == "sentinel_anomaly"
    assert doc["trips"] == ["sentinel_anomaly"]
    assert doc["captured"] == 3               # ring kept only the tail
    assert doc["dropped"] == 5                # 8 recorded (7 + trip) - 3
    assert doc["events"][-1]["kind"] == "trip"


def test_flight_recorder_unarmed_trip_keeps_evidence():
    rec = FlightRecorder(clock=None)
    assert rec.trip("early") is None          # no path yet: no dump
    assert rec.trips == ["early"]             # ...but the record stands


_CHILD = textwrap.dedent("""\
    import os, sys, time
    sys.path.insert(0, {repo!r})
    from distributed_deep_learning_tpu.obs.recorder import FlightRecorder
    rec = FlightRecorder(clock=None)
    rec.install(path={path!r})
    rec.record("work", step=1)
    {die}
""")


def test_flight_recorder_sigterm_dump(tmp_path):
    path = str(tmp_path / "bb.json")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(
            repo=REPO, path=path,
            die="os.kill(os.getpid(), __import__('signal').SIGTERM)\n"
                "time.sleep(30)")],
        capture_output=True, timeout=60)
    assert proc.returncode != 0               # still died by the signal
    doc = FlightRecorder.read(path)
    assert doc["reason"] == f"signal:{int(signal.SIGTERM)}"
    assert doc["events"][0]["kind"] == "work"


def test_flight_recorder_atexit_dump(tmp_path):
    path = str(tmp_path / "bb.json")
    proc = subprocess.run(
        [sys.executable, "-c",
         _CHILD.format(repo=REPO, path=path, die="sys.exit(0)")],
        capture_output=True, timeout=60)
    assert proc.returncode == 0
    doc = FlightRecorder.read(path)
    assert doc["reason"] == "atexit"
    assert doc["events"][0] == {"seq": 0, "kind": "work", "step": 1}


def test_flight_recorder_uninstall_restores(tmp_path):
    prev = signal.getsignal(signal.SIGTERM)
    rec = FlightRecorder(clock=None)
    rec.install(path=str(tmp_path / "bb.json"))
    assert signal.getsignal(signal.SIGTERM) is not prev
    rec.uninstall()
    assert signal.getsignal(signal.SIGTERM) is prev


# --- hot-path guard (extends the gen-1 25 us bound to span emission) ------

def test_per_step_cost_with_tracer_bounded():
    import time

    from distributed_deep_learning_tpu.obs import RunTelemetry, Tracer

    t = RunTelemetry(path=None, tracer=Tracer())
    tl = t.timeline
    fn = object()
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        d0 = tl.clock()
        kind = t.dispatch_kind(fn)
        tl.add("data_wait", tl.clock() - d0)
        d1 = tl.clock()
        tl.add(kind, tl.clock() - d1)
        tl.step()
    per_step_us = (time.perf_counter() - t0) / n * 1e6
    # same bound as the untraced guard in test_obs.py: tracing must not
    # move span emission out of the append-only regime
    assert per_step_us < 25.0, per_step_us


# --- sidecar rotation ------------------------------------------------------

def test_event_writer_rotation_and_read_rotated(tmp_path):
    from distributed_deep_learning_tpu.obs.export import (EventWriter,
                                                          read_rotated)

    path = str(tmp_path / "ev.jsonl")
    w = EventWriter(path, clock=FakeClock(), max_bytes=400, keep=2,
                    fsync_on_rollover=True)
    for i in range(40):
        w.emit("tick", i=i, pad="x" * 40)
    w.close()
    assert w.rollovers > 0
    files = sorted(p.name for p in tmp_path.iterdir())
    assert len(files) <= 3                    # live + keep rotated
    got = [e["i"] for e in read_rotated(path, event="tick")]
    assert got == sorted(got)                 # oldest segment first
    assert got[-1] == 39                      # newest events never lost
    assert len(got) < 40                      # oldest fell off (capped)


# --- prometheus exposition pins -------------------------------------------

def test_prometheus_counter_type_and_native_histogram():
    from distributed_deep_learning_tpu.obs.export import prometheus_text
    from distributed_deep_learning_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("requests", engine="paged").inc(3)
    h = reg.histogram("ttft_seconds")
    for v in (0.01, 0.02, 0.5):
        h.observe(v)
    text = prometheus_text(reg.snapshot())
    # counters: the TYPE line must declare the suffixed sample family
    # (name_total) it exports, or strict parsers read it as untyped
    assert "# TYPE requests_total counter" in text
    assert 'requests_total{engine="paged"} 3' in text
    # histograms: native _bucket/_sum/_count with a +Inf bucket
    assert "# TYPE ttft_seconds histogram" in text
    assert 'ttft_seconds_bucket{le="+Inf"} 3' in text
    assert "ttft_seconds_count 3" in text
    sum_line = next(line for line in text.splitlines()
                    if line.startswith("ttft_seconds_sum"))
    assert float(sum_line.split()[-1]) == pytest.approx(0.53, rel=0.15)


def test_obs_gen2_cli_flags():
    from distributed_deep_learning_tpu.utils.config import parse_args

    cfg = parse_args(["--obs", "--obs-trace", "t.json",
                      "--obs-rotate-mb", "64",
                      "--obs-blackbox", "bb.json"], workload="mlp")
    assert cfg.obs_trace == "t.json"
    assert cfg.obs_rotate_mb == 64.0
    assert cfg.obs_blackbox == "bb.json"
    for argv in (["--obs-trace", "t.json"],
                 ["--obs-blackbox", "bb.json"],
                 ["--obs-rotate-mb", "64"],
                 ["--obs", "--obs-rotate-mb", "0"]):
        with pytest.raises(SystemExit):
            parse_args(argv, workload="mlp")


# --- engine integration: the causal chain out of a real run ---------------

def test_paged_engine_emits_causal_trace(tmp_path):
    from distributed_deep_learning_tpu.obs import RunTelemetry
    from distributed_deep_learning_tpu.models.transformer import (
        random_causal_lm)
    from distributed_deep_learning_tpu.serve.engine import PagedEngine
    from distributed_deep_learning_tpu.serve.load import (LoadSpec,
                                                          make_load)

    model, params = random_causal_lm(
        seed=3, vocab_size=61, num_layers=1, d_model=32, num_heads=4,
        mlp_dim=64, max_len=96)
    spec = LoadSpec(n_requests=6, arrival="front", prompt_short=(4, 8),
                    prompt_long=(10, 16), long_frac=0.3,
                    shared_prefix_len=8, shared_frac=0.8,
                    new_tokens=(3, 6))
    trace_path = str(tmp_path / "trace.json")
    t = RunTelemetry(path=str(tmp_path / "ev.jsonl"),
                     trace_path=trace_path)
    out = PagedEngine(model, params, max_slots=3, max_len=96,
                      kv_block_size=8, prefill_chunk=8).run(
        make_load(spec, vocab_size=61, seed=3), telemetry=t)
    summary = t.close()
    assert summary["trace"]["spans"] > 0
    assert out["stats"]["window"]["ttft_count"] >= 1

    evs = read_chrome_trace(trace_path)
    reqs = {e["cat"] for e in evs if e["name"] == "request"}
    assert len(reqs) == 6
    hit = False
    for rid in reqs:
        ss = [e for e in evs if e["cat"] == rid]
        by_id = {e["args"]["span_id"]: e for e in ss}
        root = next(e for e in ss if e["name"] == "request")
        pm = next(e for e in ss if e["name"] == "prefix_match")
        adm = by_id[pm["args"]["parent_id"]]
        assert adm["name"] == "admit"
        assert adm["args"]["parent_id"] == root["args"]["span_id"]
        for name in ("queued", "prefill_chunk", "decode", "retire"):
            for e in (x for x in ss if x["name"] == name):
                assert e["args"]["parent_id"] == root["args"]["span_id"]
        assert sum(e["name"] == "retire" for e in ss) == 1
        hit = hit or bool(pm["args"].get("hit"))
    assert hit  # the shared-prefix load must produce at least one hit


def test_blackbox_drill_dump_bit_identical(tmp_path):
    from distributed_deep_learning_tpu.utils.chaos import \
        run_blackbox_drill

    a = run_blackbox_drill(seed=0,
                           dump_path=str(tmp_path / "a.json"))
    b = run_blackbox_drill(seed=0,
                           dump_path=str(tmp_path / "b.json"))
    assert a["trips"] == ["sentinel_anomaly"]
    assert a["dump_sha256"] == b["dump_sha256"]
    assert open(a["dump_path"], "rb").read() == \
        open(b["dump_path"], "rb").read()
    doc = FlightRecorder.read(a["dump_path"])
    kinds = [e["kind"] for e in doc["events"]]
    assert "chaos_fired" in kinds and "sentinel_anomaly" in kinds


# --- obs_report: --trace / --window views ---------------------------------

def test_obs_report_trace_and_window_views(tmp_path):
    from distributed_deep_learning_tpu.obs.export import EventWriter

    clk = FakeClock()
    tr = Tracer(clock=clk)
    root = tr.begin("request", "req-0", t0=0.0, track="req0")
    adm = tr.add("admit", 0.01, 0.02, "req-0", parent=root)
    tr.add("prefix_match", 0.015, 0.018, "req-0", parent=adm,
           hit=True, shared_len=16)
    tr.add("prefill_chunk", 0.02, 0.05, "req-0", parent=root)
    tr.add("decode", 0.06, 0.07, "req-0", parent=root)
    clk.t = 0.08
    tr.end(root)
    trace_path = str(tmp_path / "trace.json")
    write_chrome_trace(trace_path, list(tr.spans))

    stream = str(tmp_path / "ev.jsonl")
    w = EventWriter(stream, clock=FakeClock(1.0))
    w.emit("obs_window", scope="serve", window_s=10.0,
           ttft_p50_s=0.02, ttft_p99_s=0.03, ttft_count=1,
           itl_p50_s=0.004, itl_p99_s=0.005, itl_count=4,
           queue_depth_p50=1, queue_depth_max=2, queue_depth_last=0.0,
           occupancy_mean=2.5, occupancy_last=3.0,
           request_rate_per_s=0.1, token_rate_per_s=0.5)
    w.emit("obs_trace", path=trace_path, spans=5, dropped=0)
    w.close()

    script = os.path.join(REPO, "scripts", "obs_report.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, script, stream, "--trace"],
        env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "req-0" in out.stdout
    assert "prefix-hit shared=16" in out.stdout
    assert "decode x1" in out.stdout

    out = subprocess.run(
        [sys.executable, script, stream, "--window"],
        env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "live windows" in out.stdout
    assert "20.0" in out.stdout               # ttft p50 in ms
