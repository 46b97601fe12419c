"""Serving bench CLI: continuous batching, paged KV, prefix reuse, SLOs.

Thin driver over ``serve/bench.py`` — ALL load shapes and harness logic
live there; this script only parses flags and prints ONE JSON line to
stdout (human-readable latency summary to stderr).

Two modes:

* default — the v1 A/B: a seeded mixed-length trace through the
  slot-based continuous-batching engine AND the batch-synchronous
  run-to-completion ``generate()`` baseline (``serving_bench``).
* ``--paged`` — the second-generation bench (``paged_serving_bench``):
  a trace-driven SLO load (Poisson/bursty arrivals, shared system
  prompts, per-request TTFT/e2e deadlines) through the paged engine
  (block KV cache + prefix reuse + chunked prefill, optionally
  ``--draft N`` speculative decoding), A/B'd against the v1 engine on
  the same trace.  The record carries ``prefix_hit_rate``,
  ``slo_attainment``, ``spec_acceptance`` and the prefill-FLOPs saving.

    python scripts/serve_bench.py                                # v1 A/B
    python scripts/serve_bench.py --paged                        # paged
    python scripts/serve_bench.py --paged --draft 1 --spec-k 4 \
        --kv-block-size 16 --prefill-chunk 32 --slo-ttft-ms 500  # full
    python scripts/serve_bench.py --paged \
        --kv-dtype int8 --weight-dtype int8            # quantized path

Runs on JAX's default backend (``JAX_PLATFORMS=cpu`` rehearses on the
host); the JSON line's ``device`` names the platform, device kind and
count the numbers came from.  Defaults are CPU-CI sized.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _script_env() -> None:
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _latency_line(tag: str, lat: dict) -> None:
    if not lat.get("measured_requests"):
        return
    print(f"{tag} latency over {lat['measured_requests']} requests: "
          f"ttft p50={lat['ttft_p50_s'] * 1e3:.1f}ms "
          f"p99={lat['ttft_p99_s'] * 1e3:.1f}ms | "
          f"itl p50={lat['itl_p50_s'] * 1e3:.2f}ms "
          f"p99={lat['itl_p99_s'] * 1e3:.2f}ms | "
          f"e2e p50={lat['e2e_p50_s']:.3f}s "
          f"p99={lat['e2e_p99_s']:.3f}s",
          file=sys.stderr)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="serving bench: continuous-batching / paged engine "
                    "vs baselines")
    p.add_argument("--requests", type=int, default=None,
                   help="trace size (default: 32 v1 / 24 paged)")
    p.add_argument("--max-slots", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    # --- trace shape (both modes; paged splits the prompt envelope
    #     into short/long halves around its midpoint) ---
    p.add_argument("--prompt-min", type=int, default=None,
                   help="prompt length lower bound (default 4)")
    p.add_argument("--prompt-max", type=int, default=None,
                   help="prompt length upper bound (default 48)")
    p.add_argument("--new-min", type=int, default=None,
                   help="decode length lower bound (default 4)")
    p.add_argument("--new-max", type=int, default=None,
                   help="decode length upper bound (default 64)")
    p.add_argument("--stagger", type=int, default=0,
                   help="v1 trace: mean inter-arrival gap in decode "
                        "ticks (0 = all requests queued up front)")
    p.add_argument("--buckets", type=str, default=None,
                   help="v1 engine: comma-separated prefill bucket "
                        "lengths (default: powers of two up to max-len)")
    p.add_argument("--skip-naive", action="store_true",
                   help="v1 mode: engine only (e.g. profiling)")
    # --- paged mode ---
    p.add_argument("--paged", action="store_true",
                   help="bench the paged engine under trace-driven "
                        "SLO load instead of the v1 A/B")
    p.add_argument("--kv-block-size", type=int, default=16)
    p.add_argument("--prefill-chunk", type=int, default=32)
    p.add_argument("--draft", type=int, default=0,
                   help="speculative decoding: draft layer count "
                        "(0 = off; draft shares the target's weights)")
    p.add_argument("--spec-k", type=int, default=4)
    p.add_argument("--arrival", choices=("front", "poisson", "bursty"),
                   default=None, help="paged trace arrival process")
    p.add_argument("--rate", type=float, default=None,
                   help="paged trace: mean arrivals per decode tick")
    p.add_argument("--shared-prefix-len", type=int, default=None,
                   help="paged trace: shared system-prompt length")
    p.add_argument("--shared-frac", type=float, default=None,
                   help="paged trace: fraction of requests opening "
                        "with the shared prefix")
    p.add_argument("--slo-ttft-ms", type=float, default=None)
    p.add_argument("--slo-e2e-ms", type=float, default=None)
    p.add_argument("--skip-v1", action="store_true",
                   help="paged mode: skip the v1-engine comparison leg")
    # --- serving quantization (both modes; int8 KV is paged-only) ---
    p.add_argument("--kv-dtype", default=None,
                   help="KV-cache storage dtype: bf16 or int8 (int8 "
                        "stores per-position scales in the block pools, "
                        "so it requires --paged; unset = full precision)")
    p.add_argument("--weight-dtype", default=None,
                   help="decode weight storage dtype: bf16 or int8 "
                        "(int8 = per-channel scales, dequantized inside "
                        "the compiled decode program; unset = full "
                        "precision)")
    # model geometry (default: CPU-CI-sized, serve/bench.py)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--d-model", type=int, default=None)
    p.add_argument("--heads", type=int, default=None)
    p.add_argument("--mlp-dim", type=int, default=None)
    p.add_argument("--vocab", type=int, default=None)
    p.add_argument("--max-len", type=int, default=None)
    p.add_argument("--out", default=None, help="also write the JSON here")
    # --- observability (ISSUE 11) ---
    p.add_argument("--obs", action="store_true",
                   help="attach run telemetry: JSONL event stream "
                        "(--obs-file) + per-request span trace exported "
                        "as Chrome/Perfetto JSON (--obs-trace)")
    p.add_argument("--obs-file", default="obs_events.jsonl",
                   help="telemetry event stream path (with --obs)")
    p.add_argument("--obs-trace", default=None, metavar="PATH",
                   help="span-trace output path (default "
                        "serve_trace.json with --obs; giving a path "
                        "implies --obs)")
    args = p.parse_args(argv)

    from distributed_deep_learning_tpu.runtime.bootstrap import (
        describe_devices, enable_compile_cache)
    # parse-time quantization legality: fail HERE with the flag name,
    # not minutes later inside an engine constructor
    from distributed_deep_learning_tpu.serve.quant import SERVE_DTYPES

    for flag, val in (("--kv-dtype", args.kv_dtype),
                      ("--weight-dtype", args.weight_dtype)):
        if val is not None and val not in SERVE_DTYPES:
            p.error(f"unknown {flag} {val!r}; choose from "
                    f"{'/'.join(SERVE_DTYPES)} (or leave unset for "
                    "full precision)")
    if args.kv_dtype == "int8" and not args.paged:
        p.error("--kv-dtype int8 requires --paged: int8 KV stores "
                "per-position scales alongside the block pools; the v1 "
                "slot table supports bf16 only (the spec-decode draft "
                "pool inherits --kv-dtype automatically)")

    enable_compile_cache()
    telemetry = None
    if args.obs or args.obs_trace:
        from distributed_deep_learning_tpu.obs import RunTelemetry

        telemetry = RunTelemetry(
            path=args.obs_file,
            trace_path=args.obs_trace or "serve_trace.json")

    model_kw = {k: v for k, v in (
        ("num_layers", args.layers), ("d_model", args.d_model),
        ("num_heads", args.heads), ("mlp_dim", args.mlp_dim),
        ("vocab_size", args.vocab), ("max_len", args.max_len),
    ) if v is not None}

    if args.paged:
        from distributed_deep_learning_tpu.serve.bench import \
            paged_serving_bench

        load_kw = {k: v for k, v in (
            ("n_requests", args.requests), ("arrival", args.arrival),
            ("rate", args.rate),
            ("shared_prefix_len", args.shared_prefix_len),
            ("shared_frac", args.shared_frac),
            ("slo_ttft_ms", args.slo_ttft_ms),
            ("slo_e2e_ms", args.slo_e2e_ms),
        ) if v is not None}
        if args.prompt_min is not None or args.prompt_max is not None:
            lo = 4 if args.prompt_min is None else args.prompt_min
            hi = 48 if args.prompt_max is None else args.prompt_max
            if hi <= lo:
                p.error(f"--prompt-max must exceed --prompt-min "
                        f"(got {lo}..{hi})")
            mid = max(lo + 1, (lo + hi) // 2)
            load_kw["prompt_short"] = (lo, mid)
            load_kw["prompt_long"] = (mid, hi)
        if args.new_min is not None or args.new_max is not None:
            lo = 4 if args.new_min is None else args.new_min
            hi = 64 if args.new_max is None else args.new_max
            if hi <= lo:
                p.error(f"--new-max must exceed --new-min "
                        f"(got {lo}..{hi})")
            load_kw["new_tokens"] = (lo, hi)
        try:
            record = paged_serving_bench(
                seed=args.seed, load_kw=load_kw, model_kw=model_kw,
                max_slots=args.max_slots,
                kv_block_size=args.kv_block_size,
                prefill_chunk=args.prefill_chunk,
                draft_layers=args.draft or None, spec_k=args.spec_k,
                compare_engine=not args.skip_v1,
                kv_dtype=args.kv_dtype, weight_dtype=args.weight_dtype,
                telemetry=telemetry)
        except ValueError as e:
            p.error(f"{e} — shrink the trace (--prompt-max / --new-max "
                    f"/ --shared-prefix-len) or raise --max-len")
        pe = record["paged_engine"]
        _latency_line("paged", pe.get("latency") or {})
        print(f"prefix_hit_rate={pe['prefix_hit_rate']:.3f} "
              f"slo_attainment={pe['slo_attainment']} "
              f"spec_acceptance={pe['spec_acceptance']}",
              file=sys.stderr)
    else:
        from distributed_deep_learning_tpu.serve.bench import serving_bench

        buckets = [int(b) for b in args.buckets.split(",")] \
            if args.buckets else None
        record = serving_bench(
            seed=args.seed, n_requests=args.requests or 32,
            model_kw=model_kw,
            prompt_lens=(4 if args.prompt_min is None else args.prompt_min,
                         48 if args.prompt_max is None else args.prompt_max),
            new_tokens=(4 if args.new_min is None else args.new_min,
                        64 if args.new_max is None else args.new_max),
            max_slots=args.max_slots, prefill_buckets=buckets,
            stagger=args.stagger, skip_naive=args.skip_naive,
            kv_dtype=args.kv_dtype, weight_dtype=args.weight_dtype,
            telemetry=telemetry)
        _latency_line("engine", record["engine"].get("latency") or {})

    if telemetry is not None:
        summary = telemetry.close()
        tr = summary.get("trace")
        if tr:
            print(f"obs: {tr['spans']} spans -> {tr['path']} "
                  f"(load in Perfetto / chrome://tracing); "
                  f"events -> {args.obs_file}", file=sys.stderr)

    record["device"] = describe_devices()
    out = json.dumps(record)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    return 0


if __name__ == "__main__":
    _script_env()
    sys.exit(main())
