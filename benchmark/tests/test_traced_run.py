"""The readers of the traced window against a RECORDED one: the laguna
cell's whole 3 s traced window on a v5e (``testdata/programs.recorded.json``:
the first chip's module and grouped-kernel events, and the ring the program
kept of that very window), beside ``selfcheck.py``'s recorded trace.  The
numbers the run itself printed on the chip must come out again."""
import collections
import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRINTED = {"grouped_product_roofline": 85.40202348666193,
           "serve_chunk_expert_touched_pct": 99.95535714285714,
           "serve_launch_notice_ms": 2.137143999997328}


def recorded():
    """``(record, ctx)``: the recorded window as the program's published
    record and the context the harness hands a reader."""
    from distributed_deep_learning_tpu.obs import runlog
    from distributed_deep_learning_tpu.obs.trace import PhaseClock

    with open(os.path.join(HERE, "testdata", "programs.recorded.json")) as f:
        data = json.load(f)
    by_tick = collections.defaultdict(list)
    for p in data["programs"]:
        by_tick[p["tick"]].append({k: v for k, v in p.items()
                                   if k != "tick"})
    pc = PhaseClock(data["names"])
    for place, (index, kind, meta, wall, row) in enumerate(data["ticks"]):
        if meta:
            meta = (*meta, {"programs": by_tick[place]})
        pc.ticks.append((index, kind, tuple(meta), wall, tuple(row)))
    pc.started.extend(data["started"])
    pc.n_ticks, pc.listened = len(pc.ticks), data["listened"]
    ctx = {"trace": {"events": data["events"]}, "config": data["config"],
           "peaks": data["peaks"]}
    return runlog.RunRecord("serve", pc), ctx


def read(metric, ctx):
    with open(os.path.join(HERE, "metrics", metric + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(ctx, **spec["args"])


@pytest.fixture
def window(monkeypatch):
    from distributed_deep_learning_tpu.obs import runlog

    record, ctx = recorded()
    monkeypatch.setitem(runlog._RUNS, "serve", [record])
    monkeypatch.setitem(runlog._LAST, "serve", record)
    return record, ctx


def test_the_recorded_window_joins_by_order(window):
    from benchmark.readers import traced_run

    record, ctx = window
    pairs = traced_run.joined(ctx)
    names = collections.Counter(r["program"] for r, _ in pairs)
    assert names == {"paged_chunk": 35, "paged_decode": 16}
    # 35 chunk programs of ~25.3 ms, 16 decode programs of ~6 ms, in order
    for r, (s, e) in pairs:
        ms = (e - s) / 1e6
        assert (24.5 < ms < 27.0) if r["program"] == "paged_chunk" \
            else (5.0 < ms < 7.5), (r["program"], ms)
    starts = [ev[0] for _, ev in pairs]
    assert starts == sorted(starts)
    # a device gap is never shorter than the host's turnaround inside it
    for (a, ea), (b, eb) in zip(pairs, pairs[1:]):
        assert (eb[0] - ea[1]) / 1e9 > b["at"][0] - a["at"][2] > 0
    # a 1 us jit_convert_element_type runs ahead of every chunk program:
    # only the pairs that END in a decode program have nothing between
    others = collections.Counter(
        n for _, _, n in traced_run.module_events(ctx["trace"]["events"]))
    assert others["jit_convert_element_type"] == 36


@pytest.mark.parametrize("metric", sorted(PRINTED))
def test_the_numbers_the_chip_run_printed_come_out_again(window, metric):
    record, ctx = window
    assert read(metric, ctx) == pytest.approx(PRINTED[metric], rel=1e-12)


def test_the_grouped_kernels_share_is_under_its_roofline(window, capsys):
    record, ctx = window
    share = read("grouped_product_roofline", ctx)
    said = capsys.readouterr().out
    assert 50.0 < share < 100.0
    assert "51 programs joined (35 chunk, 16 decode), 16 kernel events a " \
           "program" in said and "bound by bytes" in said
    read("serve_launch_notice_ms", ctx)
    said = capsys.readouterr().out
    assert "16 of 50 pairs with nothing between" in said
    assert "0.00% of pairs read a negative remainder" in said
    # gap = turnaround + remainder over the same pairs, as printed
    gap, turn, rest = (float(said.split(key)[1].split()[0].rstrip("ms;"))
                       for key in ("mean gap ", "turnaround ",
                                   "remainder "))
    assert gap == pytest.approx(turn + rest, abs=2e-3)


def test_the_timed_runs_readers_on_the_recorded_ring(window, capsys):
    """(On the chip they read the window AFTER the traced one.)"""
    record, ctx = window
    assert 1.5 < read("serve_turnaround_ms", ctx) < 4.0
    assert "turnaround chunk->decode: 16 pairs" in capsys.readouterr().out
    longest = read("serve_tick_longest_ms", ctx)
    walls = [t[3] * 1e3 for t in record.phases.ticks]
    assert max(walls) <= longest < max(walls) + 5.0
    assert "before it that no tick owns" in capsys.readouterr().out
