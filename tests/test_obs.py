"""Unified run telemetry (ISSUE 7): metrics primitives, goodput math,
event stream, MFU accounting, serve latency histograms, overhead guard.

The load-bearing claims:

* log-bucketed histogram percentiles land within the bucket-growth error
  bound of the exact sample quantiles, clamped to observed [min, max];
* snapshot/merge is lossless for counters and bucket-exact for
  histograms, and refuses to merge mismatched bounds;
* goodput fractions sum to <= 1.0 whatever the span bookkeeping did;
* a staggered-arrival serve trace yields per-request TTFT/e2e
  percentiles anchored at arrival (queue wait counts);
* the per-step telemetry sequence costs microseconds a step (what it
  costs a real train loop on the chip is ``PERF.md``'s to say).
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_deep_learning_tpu.obs import RunTelemetry
from distributed_deep_learning_tpu.obs.export import (EventWriter,
                                                      prometheus_text,
                                                      read_events)
from distributed_deep_learning_tpu.obs.metrics import (Histogram,
                                                       MetricsRegistry,
                                                       log_bounds,
                                                       merge_snapshots)
from distributed_deep_learning_tpu.obs.mfu import (chip_peak_flops,
                                                   mfu_record)
from distributed_deep_learning_tpu.obs.timeline import CATEGORIES, Timeline


# --- histograms -----------------------------------------------------------

def test_log_bounds_geometric_and_cover():
    b = log_bounds(1e-3, 10.0, 2.0)
    assert b[0] == 1e-3 and b[-1] >= 10.0
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(abs(r - 2.0) < 1e-9 for r in ratios)


@pytest.mark.parametrize("lo,hi,growth", [(0, 1, 2), (1, 1, 2), (1, 2, 1)])
def test_log_bounds_rejects_degenerate(lo, hi, growth):
    with pytest.raises(ValueError):
        log_bounds(lo, hi, growth)


def test_histogram_bucketing_edges():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0):       # v <= bounds[0] -> bucket 0
        h.observe(v)
    h.observe(1.5)             # (1, 2]  -> bucket 1
    h.observe(4.0)             # (2, 4]  -> bucket 2
    h.observe(100.0)           # overflow
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5 and h.min == 0.5 and h.max == 100.0


def test_histogram_percentiles_within_bucket_error():
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=-4.0, sigma=1.0, size=5000)
    h = Histogram()  # default growth 1.25 => <= ~12% relative error
    for v in samples:
        h.observe(v)
    for p in (50, 90, 99):
        exact = float(np.percentile(samples, p))
        est = h.percentile(p)
        assert abs(est - exact) / exact < 0.13, (p, est, exact)
    # tails clamp to the exact observed extremes
    assert h.percentile(0) == samples.min()
    assert h.percentile(100) == samples.max()


def test_histogram_percentile_monotone_and_empty():
    h = Histogram()
    assert h.percentile(50) == 0.0
    for v in (0.001, 0.01, 0.1, 1.0):
        h.observe(v)
    ps = [h.percentile(p) for p in (10, 50, 90, 99)]
    assert ps == sorted(ps)
    with pytest.raises(ValueError):
        h.percentile(101)


def test_histogram_roundtrip():
    h = Histogram(lo=1e-4, hi=10.0, growth=1.5)
    for v in (2e-4, 3e-2, 0.5, 20.0):
        h.observe(v)
    h2 = Histogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert h2.bounds == h.bounds and h2.counts == h.counts
    assert h2.percentile(50) == h.percentile(50)
    assert math.isclose(h2.mean, h.mean)


# --- registry + merge -----------------------------------------------------

def test_registry_get_or_create_and_labels():
    reg = MetricsRegistry()
    c1 = reg.counter("requests", route="prefill")
    c1.inc(3)
    assert reg.counter("requests", route="prefill") is c1
    assert reg.counter("requests", route="decode") is not c1
    reg.gauge("depth").set(7)
    reg.histogram("lat").observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["requests{route=prefill}"] == 3.0
    assert snap["gauges"]["depth"] == 7.0
    assert snap["histograms"]["lat"]["count"] == 1


def test_merge_snapshots_semantics():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n").inc(2)
    b.counter("n").inc(5)
    a.gauge("g").set(1)
    b.gauge("g").set(9)
    for v in (0.01, 0.02):
        a.histogram("h").observe(v)
    for v in (0.04, 0.08, 0.16):
        b.histogram("h").observe(v)
    m = merge_snapshots(a.snapshot(), b.snapshot())
    assert m["counters"]["n"] == 7.0
    assert m["gauges"]["g"] == 9.0          # latest wins
    hm = Histogram.from_dict(m["histograms"]["h"])
    assert hm.count == 5 and hm.min == 0.01 and hm.max == 0.16
    assert sum(hm.counts) == 5


def test_merge_rejects_mismatched_bounds():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("h", lo=1e-5).observe(0.1)
    b.histogram("h", lo=1e-3).observe(0.1)
    with pytest.raises(ValueError, match="bounds"):
        merge_snapshots(a.snapshot(), b.snapshot())


# --- timeline / goodput ---------------------------------------------------

def _fake_clock(start=100.0):
    state = {"t": start}

    def clock(advance=None):
        if advance is not None:
            state["t"] += advance
        return state["t"]

    return clock


def test_goodput_attribution_deterministic():
    clock = _fake_clock()
    tl = Timeline(clock=clock)
    tl.add("compile", 2.0)
    tl.add("dispatch", 1.0, n=4)
    tl.add("device_sync", 1.0)
    tl.add("data_wait", 0.5)
    tl.add("checkpoint", 0.5)
    tl.step(4)
    clock(advance=10.0)  # wall = 10s, attributed = 5s
    gp = tl.goodput()
    assert gp["steps"] == 4
    assert math.isclose(gp["wall_seconds"], 10.0)
    assert math.isclose(gp["fractions"]["productive"], 0.2)
    assert math.isclose(gp["fractions"]["compile"], 0.2)
    assert math.isclose(gp["fractions"]["input_stall"], 0.05)
    assert math.isclose(gp["fractions"]["checkpoint"], 0.05)
    assert math.isclose(gp["fractions"]["other"], 0.5)
    assert gp["goodput_fraction"] == gp["fractions"]["productive"]


def test_goodput_fractions_never_exceed_one():
    # spans over-covering wall (coarse clocks / overlapping attribution)
    clock = _fake_clock()
    tl = Timeline(clock=clock)
    tl.add("dispatch", 8.0)
    tl.add("data_wait", 5.0)
    clock(advance=10.0)  # wall 10 < attributed 13
    gp = tl.goodput()
    assert sum(gp["fractions"].values()) <= 1.0 + 1e-9
    assert all(0.0 <= gp["fractions"][c] <= 1.0 for c in CATEGORIES)


def test_goodput_since_delta():
    clock = _fake_clock()
    tl = Timeline(clock=clock)
    tl.add("dispatch", 1.0)
    tl.step()
    clock(advance=4.0)
    mark = tl.snapshot()
    tl.add("dispatch", 3.0)
    tl.step(2)
    clock(advance=4.0)
    gp = tl.goodput(since=mark)
    assert gp["steps"] == 2
    assert math.isclose(gp["wall_seconds"], 4.0)
    assert math.isclose(gp["seconds"]["productive"], 3.0)


def test_timeline_span_contextmanager():
    clock = _fake_clock()
    tl = Timeline(clock=clock)
    with tl.span("recovery"):
        clock(advance=2.5)
    assert math.isclose(tl.seconds["recovery"], 2.5)
    assert tl.counts["recovery"] == 1


# --- export ---------------------------------------------------------------

def test_event_writer_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    w = EventWriter(path)
    w.emit("obs_goodput", scope="run", steps=3)
    w.emit("obs_mfu", mfu=float("nan"))  # non-finite must not corrupt JSON
    w.close()
    with open(path, "a") as f:
        f.write('{"torn line')  # a crash mid-write must not kill readers
    evs = list(read_events(path))
    assert len(evs) == 2
    assert evs[0]["scope"] == "run" and evs[0]["steps"] == 3
    assert evs[1]["mfu"] is None
    assert [e["event"] for e in read_events(path, event="obs_mfu")] \
        == ["obs_mfu"]


def test_event_writer_none_path_is_noop():
    w = EventWriter(None)
    w.emit("anything", x=1)
    w.close()


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("steps", phase="train").inc(12)
    reg.gauge("queue_depth").set(3)
    h = reg.histogram("ttft", lo=0.01, hi=1.0, growth=2.0)
    for v in (0.02, 0.3, 5.0):
        h.observe(v)
    text = prometheus_text(reg.snapshot())
    assert 'steps_total{phase="train"} 12' in text
    assert "queue_depth 3" in text
    assert 'le="+Inf"} 3' in text
    assert "ttft_count 3" in text
    # cumulative bucket counts are monotone
    cum = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
           if line.startswith("ttft_bucket")]
    assert cum == sorted(cum) and cum[-1] == 3


# --- MFU ------------------------------------------------------------------

def test_chip_peak_table_and_override(monkeypatch):
    assert chip_peak_flops("TPU v4") == 275e12
    assert chip_peak_flops("TPU v4 lite") == 138e12
    assert chip_peak_flops("cpu") is None
    monkeypatch.setenv("DDL_OBS_PEAK_FLOPS", "2e12")
    assert chip_peak_flops("cpu") == 2e12


def test_mfu_record_math():
    rec = mfu_record(step_flops=1e12, steps=100, seconds=10.0,
                     n_devices=4, device_kind="TPU v4")
    assert math.isclose(rec["steps_per_sec"], 10.0)
    assert math.isclose(rec["achieved_flops_per_sec"], 1e13)
    # 1e13 achieved / (4 chips * 275e12 peak)
    assert math.isclose(rec["mfu"], 1e13 / (4 * 275e12))
    # degrades field-by-field, never raises
    rec = mfu_record(step_flops=None, steps=0, seconds=0.0,
                     n_devices=1, device_kind="cpu")
    assert rec["mfu"] is None and rec["steps_per_sec"] is None


# --- RunTelemetry ---------------------------------------------------------

def test_dispatch_kind_compile_once_per_fn():
    t = RunTelemetry()
    f, g = object(), object()
    assert t.dispatch_kind(f) == "compile"
    assert t.dispatch_kind(f) == "dispatch"
    assert t.dispatch_kind(g) == "compile"


def test_run_telemetry_close_emits_and_is_idempotent(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("DDL_OBS_PEAK_FLOPS", "1e12")
    path = str(tmp_path / "run.jsonl")
    t = RunTelemetry(path=path)
    t.registry.counter("sentinel_anomalies").inc()
    t.timeline.add("dispatch", 0.2)
    t.timeline.step(5)
    t.note_train(5, 0.2)
    summary = t.close()
    assert t.close() == {}  # idempotent
    assert summary["goodput"]["steps"] == 5
    events = {e["event"] for e in read_events(path)}
    assert {"obs_goodput", "obs_mfu", "obs_snapshot"} <= events
    snap = next(read_events(path, event="obs_snapshot"))["snapshot"]
    assert snap["counters"]["sentinel_anomalies"] == 1.0


# --- serve latency under staggered arrivals -------------------------------

def test_serve_latency_staggered_arrivals():
    from distributed_deep_learning_tpu.models.transformer import (
        random_causal_lm)
    from distributed_deep_learning_tpu.serve.engine import ServeEngine
    from distributed_deep_learning_tpu.serve.load import make_trace

    model, params = random_causal_lm(
        seed=3, vocab_size=61, num_layers=1, d_model=32, num_heads=4,
        mlp_dim=64, max_len=48)
    trace = make_trace(8, vocab_size=61, seed=3, prompt_lens=(4, 12),
                       new_tokens=(4, 8), stagger=2)
    assert any(r.arrival_tick > 0 for r in trace)  # genuinely staggered
    out = ServeEngine(model, params, max_slots=3).run(trace)
    lat = out["stats"]["latency"]
    assert lat["measured_requests"] == 8
    for k in ("ttft", "e2e"):
        assert 0.0 < lat[f"{k}_p50_s"] <= lat[f"{k}_p99_s"]
    # e2e covers TTFT plus decode, so its p99 can't be below TTFT's p50
    assert lat["e2e_p99_s"] >= lat["ttft_p50_s"]
    assert lat["e2e_max_s"] >= lat["e2e_p99_s"]


def test_serve_stream_records_obs_serve(tmp_path):
    from distributed_deep_learning_tpu.models.transformer import (
        random_causal_lm)
    from distributed_deep_learning_tpu.serve.engine import ServeEngine
    from distributed_deep_learning_tpu.serve.load import make_trace

    t = RunTelemetry(path=str(tmp_path / "serve.jsonl"))
    model, params = random_causal_lm(
        seed=3, vocab_size=61, num_layers=1, d_model=32, num_heads=4,
        mlp_dim=64, max_len=48)
    trace = make_trace(4, vocab_size=61, seed=4, prompt_lens=(4, 8),
                       new_tokens=(4, 6))
    ServeEngine(model, params, max_slots=2).run(trace, telemetry=t)
    t.close()
    ev = next(read_events(str(tmp_path / "serve.jsonl"),
                          event="obs_serve"))
    assert ev["stats"]["latency"]["measured_requests"] == 4
    # engine instruments landed in the run's shared registry
    assert any(k.startswith("serve_ttft_seconds")
               for k in t.registry.histograms)


# --- end-to-end: --obs run -> report -------------------------------------

def test_obs_cli_run_and_report(tmp_path):
    stream = tmp_path / "obs_events.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu", DDL_DATA_LIMIT="192",
               DDL_OBS_PEAK_FLOPS="1e12",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    run = subprocess.run(
        [sys.executable, "-m", "distributed_deep_learning_tpu", "mlp",
         "-e", "1", "-b", "32", "--obs", "--obs-file", str(stream)],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    assert run.returncode == 0, run.stderr[-2000:]
    events = list(read_events(str(stream)))
    gp = next(e for e in events if e.get("event") == "obs_goodput"
              and e.get("scope") == "run")
    assert gp["steps"] > 0
    assert sum(gp["fractions"].values()) <= 1.0 + 1e-9
    mfu = next(e for e in events if e.get("event") == "obs_mfu")
    assert mfu["step_flops"] and mfu["mfu"] is not None

    report = subprocess.run(
        [sys.executable,
         os.path.join(env["PYTHONPATH"], "scripts", "obs_report.py"),
         str(stream), "--phases"],
        env=env, capture_output=True, text=True, timeout=120)
    assert report.returncode == 0, report.stderr[-2000:]
    assert "goodput (run)" in report.stdout
    assert "model FLOP utilization" in report.stdout


# --- overhead guard -------------------------------------------------------

def test_per_step_instrumentation_cost_bounded():
    # The per-step telemetry sequence _run_phase executes — clock reads,
    # dispatch_kind, two Timeline.add calls, step() — measured raw.
    # ~1.4 us/step on the CI box; the bound leaves >10x headroom so the
    # test never flakes, yet catches a regression that puts formatting,
    # allocation, or I/O on the hot path.
    import time

    t = RunTelemetry()
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
    assert per_step_us < 25.0, per_step_us


# --- satellite regressions (utils/profiling, utils/logging) ---------------

def test_measure_async_overlap_forwards_kwargs():
    from distributed_deep_learning_tpu.utils.profiling import (
        measure_async_overlap)

    seen = []

    def fn(x, *, scale):
        seen.append(scale)
        return x * scale

    measure_async_overlap(fn, 2.0, scale=3.0)
    assert seen and all(s == 3.0 for s in seen)


def test_step_timer_summary_sync_after_reset():
    import jax.numpy as jnp

    from distributed_deep_learning_tpu.utils.profiling import StepTimer

    times = iter([0.0, 1.0, 2.0, 100.0, 101.0, 102.0])
    t = StepTimer(warmup=1, clock=lambda: next(times))
    t.tick()
    t.tick()
    t.reset()
    # after reset there is no open window: a sync'd summary must not
    # plant a _last that would precede the next window's _t0 (which
    # used to corrupt the next window's rates)
    s = t.summary(sync=jnp.zeros(()))
    assert s == {"steps_per_sec": 0.0, "examples_per_sec": 0.0,
                 "seconds": 0.0}
    assert t._last is None
    t.tick()           # warmup tick re-opens the window
    t.tick(examples=8)
    assert t.summary()["steps_per_sec"] > 0


def test_phase_logger_jsonl_decoupled_from_verbose(tmp_path):
    from distributed_deep_learning_tpu.utils.logging import PhaseLogger

    path = str(tmp_path / "phases.jsonl")
    lg = PhaseLogger(verbose=False, jsonl_path=path)
    lg.phase_begin("train", epoch=1)
    lg.metrics(examples_per_sec=42.0)
    lg.close()
    events = [json.loads(line)["event"] for line in open(path)]
    assert events == ["phase_begin", "metrics"]
