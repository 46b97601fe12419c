"""Self-check of the yardstick, runnable by hand where there is no chip:

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py [--quick]

1. the trace reduction against the small recorded trace in ``testdata/``
   and a hand-made one;
2. the traffic generator's invariants (the same multiset of lengths for
   two seeds, every request inside the context, counts by chunk);
3. the cost functions against hand-worked numbers for GPT-2 medium;
4. every file ``BENCHMARK.json`` names is there, and a metric's ``moves``
   is reported by every cell that reports the metric;
5. the plain reference against the package's ``CausalLM`` at a tiny width,
   through the real runners (``--quick`` skips this and 6);
6. the cell command itself on the CPU fails, names the platform and
   prints no result line.
"""

from __future__ import annotations

import collections
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import harness, trace_reduce, traffic_gen  # noqa: E402

FAILED = []


def expect(ok: bool, what: str) -> None:
    print(("ok   " if ok else "FAIL ") + what, flush=True)
    if not ok:
        FAILED.append(what)


def near(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def check_trace_reduction() -> None:
    d, ops, mods, host = "/device:TPU:0", "XLA Ops", "XLA Modules", "/host:CPU"
    ev = [
        [host, "t", "bench:window", 0, 1000],
        [host, "t", "bench:chunk_dispatch", 90, 10],
        [host, "t", "bench:decode_dispatch", 400, 10],
        [host, "t", "bench:on_tick", 800, 50],
        [d, mods, "jit_counted(1)", 100, 200],
        [d, mods, "jit_counted(2)", 420, 330],
        [d, ops, "fusion.1", 100, 100], [d, ops, "fusion.2.remat", 150, 150],
        [d, ops, "all-gather.3", 420, 100], [d, ops, "copy.9", 470, 100],
        [d, ops, "self_attn.7 [tpu_custom_call]", 600, 150],
        [d, ops, "fusion.5", 990, 50],          # runs past the window
    ]
    inside = ev + [[host, "t", "bench:decode_dispatch", 740, 30]]
    expect(near(dict(trace_reduce.idle_gaps(inside))
                ["in:decode_dispatch"], 240e-9),
           "a gap that opens while a span is open is 'in:' that span")
    b = trace_reduce.busy(ev)
    # union: [100,300] [420,570] [600,750] [990,1000] = 200+150+150+10
    expect(near(b["busy_s"], 510e-9) and near(b["window_s"], 1000e-9),
           f"busy union 510 of 1000 ns (got {b['busy_s'] * 1e9:.0f} of "
           f"{b['window_s'] * 1e9:.0f})")
    gaps = dict(trace_reduce.idle_gaps(ev))
    # [0,100] outside (before any span; the chunk span begins at 90: the
    # gap opened at 0), [300,420] since chunk, [570,600] since decode,
    # [750,990] since decode (on_tick begins at 800, after the gap opened)
    expect(near(gaps["outside-any-span"], 100e-9)
           and near(gaps["since:chunk_dispatch"], 120e-9)
           and near(gaps["since:decode_dispatch"], 270e-9),
           f"idle gaps by the last host span begun: {gaps}")
    runs = trace_reduce.program_runs(ev)
    expect([r[2] for r in runs] == ["chunk_dispatch", "decode_dispatch"],
           f"program runs labelled by their dispatch: {runs}")
    between = trace_reduce.between_programs(ev)
    expect(len(between) == 1 and near(between[0], 120e-9),
           "one gap of 120 ns between the two programs")
    top = dict(trace_reduce.top_ops(ev))
    expect(near(top["fusion"], 260e-9)
           and near(top["self_attn [tpu_custom_call]"], 150e-9),
           f"ops summed by kind, clipped to the window: {top}")
    k = trace_reduce.op_seconds(ev, "tpu_custom_call")
    expect(k["count"] == 1 and near(k["seconds"], 150e-9),
           "kernel time by pattern")
    c = trace_reduce.collective_exposed(ev)
    # all-gather [420,520]; copy covers [470,570]: exposed [420,470]
    expect(near(c["collective_s"], 100e-9) and near(c["exposed_s"], 50e-9),
           f"collective time 100 ns, 50 exposed: {c}")
    expect(trace_reduce.short_name(
        '%self_attn.72 = (bf16[2]) custom-call(bf16[2] %b.1), '
        'custom_call_target="tpu_custom_call", x={}')
        == "self_attn.72 [tpu_custom_call]", "HLO text to a short name")

    rec = os.path.join(HERE, "testdata", "recorded.events.json")
    want = harness.load_json(HERE, "testdata", "recorded.expected.json")
    events = harness.load_json(rec)
    got = {"busy": trace_reduce.busy(events),
           "top_ops": trace_reduce.top_ops(events),
           "idle_gaps": trace_reduce.idle_gaps(events),
           "programs": collections.Counter(
               r[2] for r in trace_reduce.program_runs(events)),
           "between_programs": trace_reduce.between_programs(events)}
    expect(near(got["busy"]["busy_s"], want["busy_s"], 1e-12)
           and near(got["busy"]["window_s"], want["window_s"], 1e-12),
           f"recorded trace: busy {got['busy']['busy_s']:.6f}s of "
           f"{got['busy']['window_s']:.6f}s")
    expect(got["top_ops"][:3] == want["top_ops"][:3],
           f"recorded trace: heaviest ops {got['top_ops'][:3]}")
    expect(got["idle_gaps"] == want["idle_gaps"],
           f"recorded trace: idle gaps {got['idle_gaps'][:3]}")
    expect(dict(got["programs"]) == want["programs"],
           f"recorded trace: program runs {dict(got['programs'])}")


def check_traffic() -> None:
    for name in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        mix = harness.load_json(HERE, "traffic", name)
        if mix["kind"] != "serve":
            continue
        k, max_len = mix["table_len"], mix["engine"]["max_len"]
        a = traffic_gen.serve_requests(mix, 1, 50257, max_len, cycles=4)
        b = traffic_gen.serve_requests(mix, 2 ** 31 + 7, 50257, max_len,
                                       cycles=4)

        def pairs(reqs, lo, hi):
            return sorted((len(p), n) for _, p, n in reqs[lo:hi])

        same = all(pairs(a, i, i + k) == pairs(b, i, i + k)
                   == sorted(traffic_gen.serve_table(mix))
                   for i in range(0, 4 * k, k))
        expect(same, f"{name}: two seeds issue the same multiset of "
                     f"(prompt, output) lengths every {k} requests")
        expect(pairs(a, 0, 64) == pairs(b, 0, 64),
               f"{name}: and so every 64 requests")
        same_order = ([len(p) for _, p, _ in a]
                      == [len(p) for _, p, _ in b])
        expect(same_order
               and any((p != q).any() for (_, p, _), (_, q, _)
                       in zip(a[:k], b[:k])),
               f"{name}: the same order of lengths for two seeds, other "
               f"token ids")
        expect(all(len(p) + n <= max_len and p.min() >= 1
                   and p.max() < 50257 for _, p, n in a),
               f"{name}: every request fits {max_len}, no pad id")
        got = traffic_gen.chunk_counts(mix, mix["engine"]["prefill_chunk"])
        by_hand = sum(-(-len(p) // mix["engine"]["prefill_chunk"])
                      for _, p, _ in a[:k])
        expect(got["chunks"] == by_hand and got["requests"] == k,
               f"{name}: {got['chunks']} chunks, {got['prompt_tokens']} "
               f"prompt and {got['output_tokens']} output tokens a cycle")
    c1 = traffic_gen.markov_corpus(5, 8, 65, 257)
    c2 = traffic_gen.markov_corpus(5, 8, 65, 257)
    expect((c1 == c2).all() and c1.min() >= 1 and c1.max() == 256
           and len({tuple(r) for r in c1}) == 8,
           "corpus: the same seed gives the same rows, all different, no "
           "pad id, the top id present")


def check_costs() -> None:
    m = harness.load_json(HERE, "configs", "gpt2-medium.json")
    t = harness.cost_function("train_step")(m, 1024)
    # 6 x 354,823,168 = 2,128,939,008; 6 x 24 x 1024 x 1024 = 150,994,944
    expect(t["dense"] == 2128939008 and t["attention"] == 150994944
           and t["flops_per_token"] == 2279933952,
           f"train step: {t['flops_per_token']:,} operations a token")
    f = harness.cost_function("flash_attention")(m, 16, 1024)
    # a layer: 6 x 16 x 16 x 1024^2 x 64 = 103,079,215,104; x 24 layers
    expect(f["flops"] == 24 * 103079215104
           and f["bytes"] == 24 * 12 * 16 * 1024 * 1024 * 2,
           f"flash: {f['flops']:.4g} operations, {f['bytes']:.4g} bytes")
    x = harness.load_json(HERE, "configs", "gpt2-xl.json")
    d = harness.cost_function("decode_tick")(x, 16, 8192.0)
    # weights (1,557,611,200 - 1024 x 1600) x 2; KV 2 x 48 x 1600 x 8192 x 2
    expect(d["weight_bytes"] == 3111945600 and d["kv_bytes"] == 2516582400,
           f"decode tick: {d['bytes'] / 1e9:.3f} GB to read")
    for cfg in (m, x):
        per = (4 * cfg["n_embd"] ** 2 + 4 * cfg["n_embd"]
               + 2 * cfg["n_embd"] * cfg["n_inner"] + cfg["n_inner"]
               + cfg["n_embd"] + 4 * cfg["n_embd"])
        n = ((cfg["vocab_size"] + cfg["n_positions"]) * cfg["n_embd"]
             + cfg["n_layer"] * per + 2 * cfg["n_embd"])
        expect(n == cfg["parameters"],
               f"{cfg['name']}: {n:,} parameters from its sizes")
    try:
        harness.peaks_for("TPU v9 imaginary")
        expect(False, "an unknown device kind raises")
    except harness.BenchFailure:
        expect(True, "an unknown device kind raises")
    expect(harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12,
           "v5e peak 197 TFLOP/s")


def check_files() -> None:
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        expect("setup_s" in e2e and len(e2e) >= 2 and cell.per_layer != [],
               f"{w['name']}: reports setup_s, {sorted(e2e - {'setup_s'})} "
               f"and {len(cell.per_layer)} per-layer metrics")
        harness.runner_for(cell)
        harness.reference_for(cell)
        harness.weights_for(cell)
    names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        spec = harness.load_json(HERE, "metrics", m["name"] + ".json")
        ok = (m["moves"] in names and os.path.exists(os.path.join(
            HERE, "readers", spec["reader"] + ".py"))
            and all(spec[k] == m[k] for k in ("unit", "layer", "moves",
                                              "source", "better")))
        if "cost" in spec.get("args", {}):
            harness.cost_function(spec["args"]["cost"])
        expect(ok, f"metric {m['name']}: its file, reader and "
                   f"BENCHMARK.json agree")


def check_reference_against_program() -> None:
    from benchmark import cellrun

    root = os.path.join(HERE, "tests", "tiny")
    bench = harness.load_json(root, "BENCHMARK.json")
    for name in ("tiny-train", "tiny-serve"):
        cell = harness.Cell(name, root=root, bench=bench)
        out = cellrun.run_cell(name, 2 ** 31 + 11, 1.0, False,
                               allow_cpu=True, cell=cell)
        expect(out["correct"], f"{name}: the package against the plain "
                               f"reference at a tiny width")


def check_refuses_cpu() -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         harness.load_json(ROOT, "BENCHMARK.json")["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    last = run.stdout.strip().splitlines()[-1:] or [""]
    expect(run.returncode != 0 and "platform" in run.stderr
           and "cpu" in run.stderr and not last[0].startswith("{"),
           f"the cell command on the CPU exits {run.returncode}, names the "
           f"platform, prints no result line")


def main() -> int:
    quick = "--quick" in sys.argv
    check_trace_reduction()
    check_traffic()
    check_costs()
    check_files()
    if not quick:
        check_reference_against_program()
        check_refuses_cpu()
    print(f"{len(FAILED)} failed" if FAILED else "all ok")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
