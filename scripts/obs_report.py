"""Render a run's obs/ telemetry stream as a human-readable report.

Reads the JSONL event stream a ``--obs`` run writes (goodput breakdowns,
MFU record, metrics snapshot, serve stats) and prints the production
questions in plain text: what fraction of wall-clock was productive,
what stalled the run, what MFU the chips achieved, and what latency
users saw.

    python scripts/obs_report.py obs_events.jsonl
    python scripts/obs_report.py obs_events.jsonl --phases   # per-phase too
    python scripts/obs_report.py obs_events.jsonl --prom     # Prometheus text
    python scripts/obs_report.py obs_events.jsonl --trace    # span trace
    python scripts/obs_report.py obs_events.jsonl --window   # live windows
    python scripts/obs_report.py obs_events.jsonl --memory   # memory view
    python scripts/obs_report.py --xplane DIR                # profiler trace
    python scripts/obs_report.py --programs gpt ... --serve --paged

``--prom`` dumps the final metrics snapshot in Prometheus text
exposition format (for a textfile collector or diffing against a scrape
endpoint) instead of the report.

``--trace`` summarises the Chrome/Perfetto span trace a
``trace_path`` run exported (per-request causal chains: queued wait,
prefill chunks, decode count, prefix hits) — the trace file itself
loads in Perfetto / chrome://tracing for the zoomable view.  The path
is taken from the stream's ``obs_trace`` event; pass
``--trace PATH`` to point at a trace file directly.

``--window`` prints the rolling-window live signals (``obs_window``
events): windowed TTFT/ITL percentiles, queue depth, slot occupancy
and request/token rates over the run.

``--programs ARGV`` runs the workload CLI with ARGV in this process and
prints what each serving run it made recorded of its own programs
(``obs.runs("serve")``: the tick ring's ``counters["programs"]`` and each
tick's start): the host's turnaround between two programs by kind of
pair, and the longest tick with its phase row, its programs and the time
before it that no tick owns: where to look first on a run that stalled.

``--xplane DIR`` reads the profiler trace a ``--profile-dir DIR`` run
wrote (no event stream needed) and answers the two questions the device
trace alone cannot: device seconds by named scope of each program (the
``jax.named_scope`` / Flax module path of every op), and device idle
time by the host phase it fell in (the engine's ``ddl:`` tick tree:
``tick`` > ``admit``, ``chunk_prepare`` / ``chunk_dispatch`` /
``chunk_commit`` / ``chunk_wait``, ``decode_prepare`` / ``decode_dispatch``
/ ``decode_wait`` / ``decode_commit``, ``hook``, ``tick_end``; the loader's
``batch`` > ``batch_form``, ``h2d_enqueue``).  How to get the tick tree:

    python -m distributed_deep_learning_tpu gpt ... --serve --paged \
        --profile-dir DIR            # then: obs_report.py --xplane DIR
    python -m distributed_deep_learning_tpu gpt ... --serve --paged \
        --obs --obs-trace trace.json # the same spans as a Chrome file
"""

from __future__ import annotations

import argparse
import os
import sys


def _script_env() -> None:
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fmt_frac(f: float) -> str:
    return f"{100.0 * f:5.1f}%"


def _goodput_block(gp: dict, indent: str = "  ") -> list[str]:
    order = ("productive", "input_stall", "checkpoint", "recovery",
             "compile", "other")
    lines = [f"{indent}wall {gp['wall_seconds']:.2f}s, "
             f"{gp['steps']} steps"]
    for cat in order:
        frac = gp["fractions"].get(cat, 0.0)
        sec = gp["seconds"].get(cat, 0.0)
        bar = "#" * int(round(40 * frac))
        lines.append(f"{indent}{cat:<12}{_fmt_frac(frac)}  "
                     f"{sec:8.3f}s  {bar}")
    return lines


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return f"{n:.1f}{unit}"
        n /= 1024.0
    return f"{n:.1f}GiB"  # pragma: no cover


def _comm_block(snapshot: dict) -> list[str]:
    """Collective wire traffic: ``comm_bytes{method,op}`` counters from
    the explicit FSDP step (parallel/collectives.py) plus the measured
    ring-overlap fraction gauge when a comm bench ran."""
    counters = snapshot.get("counters", {})
    gauges = snapshot.get("gauges", {})
    lines = []
    for key in sorted(counters):
        if key.startswith("comm_bytes{"):
            labels = key[len("comm_bytes{"):-1]
            lines.append(f"  {labels:<38}{_fmt_bytes(counters[key]):>12}")
    frac = gauges.get("comm_overlap_fraction")
    if frac is not None:
        lines.append(f"  overlap fraction {_fmt_frac(frac)}")
    return lines


def _span_ms(spans: list[dict], name: str) -> tuple[int, float]:
    """(count, summed duration ms) of the named spans."""
    picked = [s for s in spans if s["name"] == name]
    return len(picked), sum(s.get("dur", 0) for s in picked) / 1e3


def render_trace(spans: list[dict], limit: int = 40) -> str:
    """Per-request causal-chain summary of a ``ph:"X"`` span list
    (:func:`obs.trace.read_chrome_trace`).  One line per request trace,
    ordered by root-span start; non-request tracks (train, engine)
    roll up as name -> count/total."""
    from collections import defaultdict

    by_trace: dict[str, list[dict]] = defaultdict(list)
    for s in spans:
        by_trace[s.get("cat", "?")].append(s)

    reqs, other = [], []
    for tid, ss in by_trace.items():
        root = next((s for s in ss if s["name"] == "request"), None)
        (reqs if root is not None else other).append((tid, ss, root))
    reqs.sort(key=lambda r: r[2]["ts"])

    out = [f"== span trace ({len(spans)} spans, "
           f"{len(reqs)} request traces) =="]
    for tid, ss, root in reqs[:limit]:
        _, q_ms = _span_ms(ss, "queued")
        n_chunk, pf_ms = _span_ms(ss, "prefill_chunk")
        if not n_chunk:                       # v1 engine: single prefill
            n_chunk, pf_ms = _span_ms(ss, "prefill")
        n_dec, _ = _span_ms(ss, "decode")
        pm = next((s for s in ss if s["name"] == "prefix_match"), None)
        hit = ""
        if pm is not None and pm["args"].get("hit"):
            hit = f"  prefix-hit shared={pm['args'].get('shared_len')}"
        cow_n, _ = _span_ms(ss, "cow")
        cow = f"  cow x{cow_n}" if cow_n else ""
        out.append(f"  {tid:<8} e2e {root.get('dur', 0) / 1e3:9.1f}ms  "
                   f"queued {q_ms:8.1f}ms  "
                   f"prefill x{n_chunk} {pf_ms:8.1f}ms  "
                   f"decode x{n_dec}{hit}{cow}")
    if len(reqs) > limit:
        out.append(f"  ... {len(reqs) - limit} more request traces")
    for tid, ss, _ in sorted(other):
        out.append(f"  [{tid}]")
        names = sorted({s["name"] for s in ss})
        for name in names:
            n, ms = _span_ms(ss, name)
            out.append(f"    {name:<16} x{n:<5} {ms:10.1f}ms")
    return "\n".join(out)


def render_memory(events: list[dict]) -> str:
    """The run's memory story: the ``obs_memory`` rollup (HBM watermark
    timeline, host RSS) plus the final snapshot's ``mem_*`` /
    ``serve_kv_cache_bytes`` gauges."""
    mems = [e for e in events if e.get("event") == "obs_memory"]
    snaps = [e for e in events if e.get("event") == "obs_snapshot"]
    out = []
    for mem in mems[-1:]:
        out.append("== memory (run) ==")
        reports = mem.get("device_reports_memory")
        out.append(f"  samples {mem.get('samples')} over "
                   f"{mem.get('steps')} steps  "
                   f"(device reports memory: {reports})")
        if mem.get("peak_bytes"):
            out.append(f"  HBM peak        {_fmt_bytes(mem['peak_bytes'])}")
        if mem.get("host_rss_bytes"):
            out.append(f"  host RSS        "
                       f"{_fmt_bytes(mem['host_rss_bytes'])}")
        tail = mem.get("timeline_tail") or []
        if tail:
            out.append("  step   in-use        peak          peak-delta")
            for s in tail:
                out.append(
                    f"  {s.get('step', 0):<6}"
                    f"{_fmt_bytes(s.get('bytes_in_use', 0)):>10}  "
                    f"{_fmt_bytes(s.get('peak_bytes', 0)):>10}  "
                    f"{_fmt_bytes(s.get('peak_delta', 0)):>10}")
    if snaps:
        gauges = snaps[-1].get("snapshot", {}).get("gauges", {})
        rows = [(k, v) for k, v in sorted(gauges.items())
                if k.startswith("mem_") or "kv_cache_bytes" in k]
        if rows:
            out.append("== memory gauges (final snapshot) ==")
            for k, v in rows:
                out.append(f"  {k:<28}{_fmt_bytes(v):>12}")
    if not out:
        out.append("no obs_memory events or mem_* gauges in the stream "
                   "(was the run started with --obs?)")
    return "\n".join(out)


def render_window(events: list[dict]) -> str:
    """The rolling-window live signals over the run, one line per
    ``obs_window`` emit (engines emit at most one per second)."""
    wins = [e for e in events if e.get("event") == "obs_window"]
    if not wins:
        return ("no obs_window events (windows are emitted by serve "
                "engine runs with --obs)")
    t0 = wins[0].get("t", 0.0)
    out = [f"== live windows ({wins[0].get('window_s')}s rolling, "
           f"{len(wins)} samples) ==",
           "  t+s     ttft p50/p99 ms     itl p50/p99 ms   "
           "qdepth p50/max  occ   req/s   tok/s"]
    for w in wins:
        def ms(key):
            v = w.get(key)
            return f"{1e3 * v:8.1f}" if v is not None else "     n/a"
        out.append(
            f"  {w.get('t', 0.0) - t0:6.1f}"
            f"{ms('ttft_p50_s')}/{ms('ttft_p99_s')}"
            f"{ms('itl_p50_s')}/{ms('itl_p99_s')}"
            f"   {w.get('queue_depth_p50', 0):5.0f}/"
            f"{w.get('queue_depth_max', 0):<4.0f}"
            f"{w.get('occupancy_last', 0.0):6.1f}"
            f"{w.get('request_rate_per_s', 0.0):8.2f}"
            f"{w.get('token_rate_per_s', 0.0):8.1f}")
    return "\n".join(out)


def render_xplane(trace: dict, depth: int = 3, top: int = 24,
                  ops: str | None = None) -> str:
    """The operator's reading of one profiler trace (see
    :mod:`distributed_deep_learning_tpu.obs.xplane`)."""
    from distributed_deep_learning_tpu.obs import xplane

    def share(part: float, whole: float) -> str:
        return _fmt_frac(part / max(whole, 1e-12))

    idle = xplane.idle_by_phase(trace)
    spans = xplane.span_counts(trace)
    out = [f"== profiler trace: window {idle['window_s']:.3f}s on "
           f"{idle['plane']}, busy {idle['busy_s']:.3f}s, idle "
           f"{idle['idle_s']:.3f}s "
           f"({share(idle['idle_s'], idle['window_s']).strip()}) ==",
           f"  file {trace['bytes'] / 2 ** 20:.1f} MiB, "
           f"{sum(n for n, _ in spans.values())} ddl: spans of "
           f"{len(spans)} kinds on {len(trace['spans'])} thread(s)"]
    progs = xplane.programs(trace)
    if progs:
        out.append("== programs (XLA Modules) ==")
        for mod, runs, secs in progs[:top]:
            out.append(f"  {mod:<40} x{runs:<6} {secs:10.4f}s")
    by = xplane.seconds_by_scope(trace, depth, ops)
    what = f" of ops matching {ops!r}" if ops else ""
    out.append(f"== device seconds by scope{what} "
               f"({by['op_s']:.3f}s in all) ==")
    for prog, scope, secs, n in by["rows"][:top]:
        out.append(f"  {prog:<22} {scope:<44} {secs:9.4f}s "
                   f"{share(secs, by['op_s'])}  x{n}")
    if len(by["rows"]) > top:
        rest = sum(r[2] for r in by["rows"][top:])
        out.append(f"  ... {len(by['rows']) - top} more rows, {rest:.4f}s")
    out.append(f"== device idle by host phase (thread {idle['thread']}): "
               f"{idle['between_s']:.4f}s between program runs, "
               f"{idle['inside_s']:.4f}s inside them ==")
    out.append(f"  {'phase':<22} {'between':>10} {'inside':>10}  of idle")
    for name, between, inside, n in idle["by_phase"]:
        out.append(f"  {name:<22} {between:9.4f}s {inside:9.4f}s "
                   f"{share(between + inside, idle['idle_s'])}"
                   f"  in {n} pieces")
    out.append(f"  under a named phase: "
               f"{share(idle['named_s'], idle['idle_s']).strip()} of idle "
               f"time")
    if spans:
        out.append("== ddl: spans (host seconds, all threads) ==")
        for name, (n, secs) in sorted(spans.items(),
                                      key=lambda kv: -kv[1][1]):
            out.append(f"  {name:<22} x{n:<6} {secs:10.4f}s")
    nest = xplane.nesting(trace)
    if nest:
        out.append("== one clock: ddl: spans inside the bench: annotation "
                   "of the same call ==")
        for kind, inside, total in nest:
            out.append(f"  {kind:<22} {inside}/{total}")
    return "\n".join(out)


def _host_io_line(programs: int, puts: int, fetches: int) -> str:
    """What crossed between host and device around the chunk and the
    decode program: one put and at most one fetch a program where each
    takes one packed array and hands one back."""
    def per(n: int) -> str:
        return f"{n / programs:.2f}" if programs else "n/a"

    return (f"host io: {programs} programs, {puts} puts ({per(puts)} a "
            f"program), {fetches} fetches ({per(fetches)} a program)")


def render_programs(record) -> str:
    """What a serving run recorded of its own programs
    (``obs.last_run("serve")`` / ``obs.runs("serve")``; the tick ring's
    ``counters["programs"]`` and ``PhaseClock.started``): the host's
    turnaround from holding one program's result to entering the next
    dispatch, by kind of pair, and the longest tick, counted from the end
    of the tick before it, with its phase row, its programs and the time
    before it that no tick owns."""
    import statistics

    if record is None:
        return "no serving run has published a record"
    pc = record.phases
    ticks, starts = list(pc.ticks), list(pc.started)
    progs = [p for t in ticks if len(t[2]) > 2
             for p in t[2][2].get("programs", ())]
    lines = [f"programs of the run: {len(progs)} recorded in "
             f"{len(ticks)} ticks of {pc.n_ticks} (listened to: "
             f"{pc.listened})"]
    by_kind: dict = {}
    for a, b in zip(progs, progs[1:]):
        if a["at"][2] is not None:
            by_kind.setdefault(
                (a["program"], b["program"]), []).append(
                    (b["at"][0] - a["at"][2]) * 1e3)
    for (ka, kb), ms in sorted(by_kind.items()):
        short = [k.replace("paged_", "") for k in (ka, kb)]
        lines.append(f"  turnaround {short[0]}->{short[1]}: {len(ms)} "
                     f"pairs, median {statistics.median(ms):.3f} ms, "
                     f"longest {max(ms):.3f}")
    ios = [p["io"] for p in progs if "io" in p]
    if ios:
        lines.append("  " + _host_io_line(len(ios), sum(i[0] for i in ios),
                                          sum(i[1] for i in ios))
                     + ", by the records")
    touched = [p["experts"]["touched"] / p["experts"]["held"]
               for p in progs if p["program"] == "paged_chunk"
               and p.get("experts", {}).get("held")]
    if touched:
        lines.append(f"  held experts a chunk touched, a layer: "
                     f"{100 * statistics.fmean(touched):.1f}% over "
                     f"{len(touched)} chunks")
    if ticks and len(starts) == len(ticks):
        ends = [s + t[3] for s, t in zip(starts, ticks)]
        spans = [ticks[0][3]] + [b - a for a, b in zip(ends, ends[1:])]
        k = max(range(len(spans)), key=spans.__getitem__)
        index, kind, meta, wall, row = ticks[k]
        median = statistics.median(spans)
        lines.append(
            f"  longest tick: {spans[k] * 1e3:.3f} ms (median "
            f"{median * 1e3:.3f}; {sum(s > 10 * median for s in spans)} "
            f"of {len(spans)} over ten times it), tick {index} ({kind}), "
            f"{(spans[k] - wall) * 1e3:.3f} ms before it that no tick owns")
        lines.append("    phases ms: " + ", ".join(
            f"{n} {v * 1e3:.3f}" for n, v in zip(pc.names, row) if v))
        for p in (meta[2].get("programs", ()) if len(meta) > 2 else ()):
            at = "/".join("-" if t is None else f"{(t - starts[k]) * 1e3:.3f}"
                          for t in p["at"])
            rest = {k_: v for k_, v in p.items()
                    if k_ not in ("program", "at", "experts")}
            lines.append(f"    {p['program']}: dispatch/returned/ready at "
                         f"{at} ms of the tick {rest or ''}".rstrip())
    return "\n".join(lines)


def render(events: list[dict], phases: bool = False) -> str:
    run_gp = None
    phase_gps = []
    mfu = None
    serve = []
    snapshot = None
    programs = []
    for ev in events:
        kind = ev.get("event")
        if kind == "obs_goodput":
            if ev.get("scope") == "run":
                run_gp = ev
            else:
                phase_gps.append(ev)
        elif kind == "obs_mfu":
            mfu = ev
        elif kind == "obs_serve":
            serve.append(ev.get("stats", {}))
        elif kind == "obs_snapshot":
            snapshot = ev.get("snapshot", {})
        elif kind == "obs_programs":
            programs += ev.get("notes", [])

    out = []
    if run_gp is not None:
        out.append("== goodput (run) ==")
        out += _goodput_block(run_gp)
    if phases and phase_gps:
        for gp in phase_gps:
            out.append(f"== goodput ({gp.get('scope')}) ==")
            out += _goodput_block(gp)
    if mfu is not None:
        out.append("== model FLOP utilization ==")
        sps = mfu.get("steps_per_sec")
        out.append(f"  steps/sec       "
                   f"{sps:.3f}" if sps else "  steps/sec       n/a")
        if mfu.get("step_flops"):
            out.append(f"  step FLOPs      {mfu['step_flops']:.3e} "
                       f"(x{mfu.get('n_devices')} "
                       f"{mfu.get('device_kind')})")
        if mfu.get("achieved_flops_per_sec"):
            out.append(f"  achieved FLOP/s {mfu['achieved_flops_per_sec']:.3e}")
        if mfu.get("mfu") is not None:
            src = mfu.get("peak_flops_source")
            src_note = f", peak source: {src}" if src else ""
            out.append(f"  MFU             {100.0 * mfu['mfu']:.2f}% "
                       f"(peak {mfu['peak_flops_per_chip']:.3e}/chip"
                       f"{src_note})")
        else:
            out.append("  MFU             n/a (no peak-FLOPs table entry "
                       "for this device; set DDL_OBS_PEAK_FLOPS)")
    if programs:
        # what each program said of itself as it was traced: the batch
        # axes a train step's activations were pinned to and at how many
        # sites (batch_pins), how its flash kernel calls tiled q, k and v
        # (flash_layout: lanes and heads a block, calls that transposed),
        # whether its token loss took the logits a block at a time
        # (fused_head: calls, a shard's rows, vocabulary, pallas or logits,
        # tiles, logits_at_rest),
        # the paths a decode program's attention layers took (attn_paths),
        # a serving program's grouped expert products (grouped_product:
        # calls, rows, experts, pallas or ragged_dot, tiles, fused calls)
        out.append("== programs, as traced ==")
        out += [f"  {n.get('program')}: {n.get('note')} {n.get('text')}"
                for n in programs]
    if snapshot is not None:
        comm = _comm_block(snapshot)
        if comm:
            out.append("== collective wire traffic ==")
            out += comm
    for st in serve:
        lat = st.get("latency") or {}
        out.append("== serving latency ==")
        out.append(f"  requests {st.get('requests')}  "
                   f"tokens/sec {st.get('tokens_per_sec'):.1f}  "
                   f"occupancy {st.get('mean_slot_occupancy'):.2f}"
                   f"/{st.get('max_slots')}")
        pg = st.get("paged") or {}
        attn = pg.get("decode_attn")
        if attn:
            # the decode program's attention: layers that read K/V in
            # place through the block table, and what they read of what
            # the tables hold (the share of capacity a tick still pays)
            read, held = attn["blocks_read"], attn["blocks_in_tables"]
            share = f"{100.0 * read / held:.1f}%" if held else "n/a"
            latent = attn["paths"].get("latent", 0)
            out.append(f"  decode attention: {attn['paths']['block_table']} "
                       f"layers through the block table, "
                       + (f"{latent} latent layers through it (a row read "
                          f"{attn.get('latent_row_bytes', 0)} bytes), "
                          if latent else "")
                       + f"{attn['paths']['gather']} gathered; blocks read "
                       f"{read} of {held} in the tables ({share})")
        if pg.get("indexed_total"):
            # the prefix index: blocks registered over the run and the
            # tokens read out of the slots' streams to hash them; 1.0 a
            # token where each block is read once, as it fills
            indexed = pg["indexed_total"] * st["kv_block_size"]
            out.append(f"  prefix index: {pg['indexed_total']} blocks "
                       f"registered, {pg['tokens_read']} tokens read to "
                       f"hash them ({pg['tokens_read'] / indexed:.2f} a "
                       f"token indexed)")
        if pg.get("host_io"):
            out.append("  " + _host_io_line(**pg["host_io"]))
        for prog, text in (pg.get("grouped_product") or {}).items():
            out.append(f"  grouped expert products, {prog}: {text}")
        if lat.get("measured_requests"):
            out.append(f"  ttft  p50 {1e3 * lat['ttft_p50_s']:8.2f}ms   "
                       f"p99 {1e3 * lat['ttft_p99_s']:8.2f}ms")
            out.append(f"  itl   p50 {1e3 * lat['itl_p50_s']:8.2f}ms   "
                       f"p99 {1e3 * lat['itl_p99_s']:8.2f}ms")
            out.append(f"  e2e   p50 {lat['e2e_p50_s']:8.3f}s    "
                       f"p99 {lat['e2e_p99_s']:8.3f}s")
    if not out:
        out.append("no obs events found (was the run started with --obs?)")
    return "\n".join(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="render an --obs telemetry stream as a goodput/MFU/"
                    "latency report",
        epilog="The paged engine's tick tree (tick > admit, chunk_*, "
               "decode_*, hook, tick_end): run the CLI with --profile-dir "
               "DIR, then `obs_report.py --xplane DIR` (device idle time "
               "by host phase, device seconds by named scope); or run it "
               "with --obs --obs-trace PATH for the same spans as a "
               "Chrome/Perfetto file.")
    p.add_argument("stream", nargs="?",
                   help="JSONL event file written by --obs")
    p.add_argument("--xplane", metavar="DIR",
                   help="read the profiler trace a --profile-dir DIR run "
                        "wrote (or one .xplane.pb): device seconds by "
                        "named scope, device idle time by the engine's "
                        "ddl: host phase; needs no event stream")
    p.add_argument("--depth", type=int, default=3,
                   help="--xplane: scope segments kept (default 3)")
    p.add_argument("--ops", metavar="REGEX",
                   help="--xplane: count only ops whose HLO name matches "
                        "(e.g. '^copy')")
    p.add_argument("--phases", action="store_true",
                   help="also print per-phase goodput breakdowns")
    p.add_argument("--prom", action="store_true",
                   help="dump the final metrics snapshot as Prometheus "
                        "text exposition instead of the report")
    p.add_argument("--trace", nargs="?", const="", default=None,
                   metavar="PATH",
                   help="summarise the exported span trace instead of "
                        "the report (path defaults to the stream's "
                        "obs_trace event)")
    p.add_argument("--window", action="store_true",
                   help="print the rolling-window live signals "
                        "(obs_window events) instead of the report")
    p.add_argument("--memory", action="store_true",
                   help="print the memory view (obs_memory rollup + "
                        "mem_*/kv-cache gauges) instead of the report")
    p.add_argument("--programs", nargs=argparse.REMAINDER, metavar="ARGV",
                   help="run the workload CLI with ARGV in THIS process "
                        "(e.g. --programs gpt -l 2 -s 64 -e 1 -b 8 -m "
                        "sequential --serve --paged), then print what each "
                        "serving run recorded of its own programs: "
                        "turnaround by kind of pair, the longest tick")
    args = p.parse_args(argv)
    if args.programs:
        from distributed_deep_learning_tpu import obs
        from distributed_deep_learning_tpu.__main__ import main as cli

        cli(args.programs)
        for record in obs.runs("serve") or [None]:
            print(render_programs(record))
        return 0
    if args.xplane:
        from distributed_deep_learning_tpu.obs import xplane

        print(render_xplane(xplane.load(xplane.newest(args.xplane)),
                            depth=args.depth, ops=args.ops))
        return 0
    if not args.stream:
        p.error("give the event stream of an --obs run, or --xplane DIR")

    from distributed_deep_learning_tpu.obs.export import (prometheus_text,
                                                          read_events)

    events = list(read_events(args.stream))
    if args.trace is not None:
        from distributed_deep_learning_tpu.obs.trace import \
            read_chrome_trace

        path = args.trace
        if not path:
            recs = [e for e in events if e.get("event") == "obs_trace"]
            if not recs:
                print("no obs_trace event in the stream (run with a "
                      "trace path, or pass --trace PATH)",
                      file=sys.stderr)
                return 1
            path = recs[-1]["path"]
            if not os.path.isabs(path):
                # The producer recorded the path relative to its own cwd;
                # resolve against the stream it sits next to.
                path = os.path.join(os.path.dirname(os.path.abspath(args.stream)), path)
        print(render_trace(read_chrome_trace(path)))
        return 0
    if args.window:
        print(render_window(events))
        return 0
    if args.memory:
        print(render_memory(events))
        return 0
    if args.prom:
        snaps = [e for e in events if e.get("event") == "obs_snapshot"]
        if not snaps:
            print("no obs_snapshot event in the stream", file=sys.stderr)
            return 1
        sys.stdout.write(prometheus_text(snaps[-1]["snapshot"]))
        return 0
    print(render(events, phases=args.phases))
    return 0


if __name__ == "__main__":
    _script_env()
    sys.exit(main())
