"""Chaos drill: rehearse the detect→contain→recover chain, print one JSON
line.

Four scenarios, selected with ``--scenario``:

* ``resilience`` (default) runs
  :func:`distributed_deep_learning_tpu.utils.chaos.run_resilience_drill`
  — NaN'd batch contained by the anomaly sentinel (bit-identical
  params), truncated latest checkpoint quarantined with fallback to the
  verified save, injected worker failure recovered by elastic restart —
  and reports detection latency, recovery wall time, restarts used and
  the sentinel's step-time overhead.
* ``shrink`` runs
  :func:`distributed_deep_learning_tpu.reshard.drill.run_shrink_drill`
  — seed-kill 2 of the 8 emulated workers, re-plan for the 6 survivors
  via ``tune/``, reshard-restore the epoch checkpoint onto the new mesh
  and continue, gating on allclose params/optimizer state and an
  epoch-2 loss matching the uninterrupted topology's.
* ``serve`` runs
  :func:`distributed_deep_learning_tpu.utils.chaos.run_serve_resilience_drill`
  — engine crash / NaN logits / corrupted KV block / stalled tick
  injected mid-decode under the engine supervisor (every request
  completes bit-identically, zero lost), slow-tick SLO load under
  admission control, and the hot weight-swap gauntlet (canary promote,
  canary rollback with replay, bit-flipped publication rejected by the
  integrity manifest) — all on ONE engine whose ``decode_compiles``
  stays 1 throughout.
* ``fleet`` runs
  :func:`distributed_deep_learning_tpu.utils.chaos.run_fleet_resilience_drill`
  — three router-fronted paged replicas under a shared-prefix Poisson
  trace with priority classes: a replica killed mid-decode is
  quarantined and its in-flight requests replayed bit-identically onto
  the survivors (zero lost), a straggling replica is health-degraded,
  a flaky router loses its placement signal without losing
  correctness, priority preemption spills low-priority KV and resumes
  it bit-identically (priority 0 never preempted), and a
  ``migrate_drop`` — a device-to-device KV transfer corrupted in
  flight — trips the migration payload's end-to-end digest
  (``MigrationError``) and is recovered bit-identically by the
  supervisor's ledger replay, zero requests lost.

* ``rebalance`` runs
  :func:`distributed_deep_learning_tpu.utils.chaos.run_rebalance_drill`
  — live fleet rebalancing: a degraded/hot replica's open slots are
  evacuated MID-REQUEST to healthy peers (digest-verified committed-KV
  migration, bit-identical resume, fp32 and int8 pools), a corrupted
  evacuation payload (``evac_drop``) trips the digest and rolls the
  destination back with zero loss, a target crash mid-evacuation
  aborts and replays from the ledger, the elastic autoscaler grows a
  prefix-warmed replica and shrinks it back through the drain
  protocol, an oscillating ``scale_thrash`` load is damped by the
  patience/cool hysteresis, and (given >= 3 devices) a disaggregated
  engine reassigns a worker between the prefill and decode pools.

All are CPU-runnable (the chains are host+XLA logic, not
accelerator-specific).

Usage::

    python scripts/chaos_drill.py [--seed N]
        [--scenario resilience|shrink|serve|fleet|rebalance]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="chaos plan seed (same seed = same faults, "
                        "bit-identical poison masks / kill sets)")
    p.add_argument("--scenario", choices=("resilience", "shrink", "serve",
                                          "fleet", "rebalance"),
                   default="resilience",
                   help="resilience: sentinel/corruption/restart chain; "
                        "shrink: kill workers, re-plan, reshard, continue; "
                        "serve: engine supervisor replay + hot weight "
                        "swap + SLO admission under injected serve faults; "
                        "fleet: multi-replica failover, straggler "
                        "degradation, router flake, priority preemption "
                        "with KV spill/resume; rebalance: mid-request "
                        "slot evacuation, elastic autoscaling with drain "
                        "protocol, rebalance fault gauntlet")
    args = p.parse_args()

    # shrink kills 2 of 8 workers; fleet's migrate_drop scenario parks
    # spilled KV on a second local device; rebalance's pool-elasticity
    # scenario needs a reassignable third disagg worker.  The forced
    # count only shapes the CPU backend (it must land before jax
    # imports); on any other default backend require_devices says what
    # is missing.
    need = {"shrink": 8, "fleet": 2, "rebalance": 4}.get(args.scenario, 1)
    if need > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={need}"
            ).strip()
    from distributed_deep_learning_tpu.runtime.bootstrap import (
        describe_devices, require_devices)

    require_devices(need)

    if args.scenario == "shrink":
        from distributed_deep_learning_tpu.reshard.drill import \
            run_shrink_drill as drill
    elif args.scenario == "fleet":
        from distributed_deep_learning_tpu.utils.chaos import \
            run_fleet_resilience_drill as drill
    elif args.scenario == "rebalance":
        from distributed_deep_learning_tpu.utils.chaos import \
            run_rebalance_drill as drill
    elif args.scenario == "serve":
        from distributed_deep_learning_tpu.utils.chaos import \
            run_serve_resilience_drill as drill
    else:
        from distributed_deep_learning_tpu.utils.chaos import \
            run_resilience_drill as drill

    record = drill(seed=args.seed)
    if args.scenario == "resilience":
        record = {"metric": "resilience drill", **record,
                  "drill_passed": bool(
                      record["containment_bit_identical"] and
                      record["corrupt_restore_fell_back"] and
                      record["recovered_bit_identical"])}
    record["device"] = describe_devices()
    print(json.dumps(record))
    return 0 if record["drill_passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
