"""Disaggregated-serving bench CLI: prefill/decode pools vs unified.

Thin driver over ``serve/bench.py``'s ``disagg_serving_bench`` — the
load shape (``DEFAULT_LOAD``) and the A/B harness live there; this
script parses flags, guarantees a multi-device host (disaggregation
needs one device per pool — on a single-device CPU box it forces the
emulated topology via ``XLA_FLAGS`` BEFORE jax imports) and prints ONE
JSON line to stdout.

    python scripts/disagg_bench.py                       # 1P + 1D
    python scripts/disagg_bench.py --prefill-workers 2 \
        --decode-workers 2 --devices 4                   # wider pools
    python scripts/disagg_bench.py --kv-dtype int8       # int8 pools

``bench.py`` shells out to this script for its ``serving_disagg``
section when the worker process only sees one device (the usual
CPU-fallback worker), the same way ``comm_bench.py`` backs the
``collectives`` section.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="disaggregated prefill/decode serving vs the "
                    "unified paged engine")
    p.add_argument("--requests", type=int, default=None,
                   help="trace size (default: DEFAULT_LOAD's 24)")
    p.add_argument("--prefill-workers", type=int, default=1)
    p.add_argument("--decode-workers", type=int, default=1)
    p.add_argument("--prefill-streams", type=int, default=4,
                   help="prompts batched per prefill-worker chunk call")
    p.add_argument("--max-slots", type=int, default=8,
                   help="decode slots per decode worker")
    p.add_argument("--decode-passes", type=int, default=2,
                   help="decode ticks per scheduler iteration")
    p.add_argument("--kv-block-size", type=int, default=16)
    p.add_argument("--prefill-chunk", type=int, default=32)
    p.add_argument("--kv-dtype", type=str, default=None,
                   help="block-pool dtype (bf16/int8; unset = fp32)")
    p.add_argument("--devices", type=int, default=None,
                   help="force this many emulated CPU devices (default: "
                        "just enough for the worker pools)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    need = args.devices or (args.prefill_workers + args.decode_workers)
    # only shapes the CPU backend, should that be the one JAX picks
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
            f"{max(need, 2)}").strip()
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    from distributed_deep_learning_tpu.runtime.bootstrap import (
        describe_devices, enable_compile_cache, require_devices)
    from distributed_deep_learning_tpu.serve.bench import (
        disagg_serving_bench)

    require_devices(max(need, 2))
    enable_compile_cache()
    rec = disagg_serving_bench(
        seed=args.seed,
        load_kw=(dict(n_requests=args.requests)
                 if args.requests is not None else None),
        prefill_workers=args.prefill_workers,
        decode_workers=args.decode_workers,
        prefill_streams=args.prefill_streams,
        max_slots=args.max_slots,
        kv_block_size=args.kv_block_size,
        prefill_chunk=args.prefill_chunk,
        kv_dtype=args.kv_dtype,
        decode_passes=args.decode_passes)
    rec["device"] = describe_devices()
    print(json.dumps(rec))
    u, d = rec["unified"], rec["disagg"]
    print(f"disagg {d['tokens_per_sec']:.0f} tok/s vs unified "
          f"{u['tokens_per_sec']:.0f} tok/s = {rec['speedup']}x | "
          f"itl p99 {d['itl_p99_s'] * 1e3:.2f}ms vs "
          f"{u['itl_p99_s'] * 1e3:.2f}ms | migration "
          f"{rec['migration_gbps']} GB/s | agreement "
          f"{rec['token_agreement']}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
