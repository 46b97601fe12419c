"""Continuous-batching inference for :class:`..models.transformer.CausalLM`.

The serving analogue of the train stack's compile-once discipline
(PAPERS.md "Scalable Training of Language Models using JAX pjit and
TPUv4"): a slot-based static KV cache (:mod:`.cache`), a host-side slot
scheduler (:mod:`.scheduler`), and an engine (:mod:`.engine`) whose
decode hot path is ONE compiled XLA program for its whole lifetime —
requests of any length enter and leave slots without changing a shape.
Batch-synchronous :func:`..models.transformer.generate` is the parity
reference.

Second generation, same discipline, planet-scale tricks:
:class:`.engine.PagedEngine` serves from block pools (:mod:`.paged` —
refcounted paged KV with rolling-hash prefix reuse and copy-on-write),
prefills in fixed chunks interleaved with decode (:mod:`.prefill`),
optionally speculates with a truncated-layer draft verified in one
batched forward (:mod:`.spec`), and is driven by replayable traces with
per-request SLOs (:mod:`.load`).
"""

from distributed_deep_learning_tpu.serve.autoscaler import (FleetAutoscaler,
                                                            PoolRebalancer)
from distributed_deep_learning_tpu.serve.engine import (PagedEngine,
                                                        ServeEngine)
from distributed_deep_learning_tpu.serve.fleet import (RETIRED, FleetRouter,
                                                       ReplicaCrash)
from distributed_deep_learning_tpu.serve.load import (LoadSpec, make_load,
                                                      merge_slo_reports,
                                                      slo_report)
from distributed_deep_learning_tpu.serve.rebalance import (EvacuationSignal,
                                                           HotspotDetector,
                                                           evacuate_slot)
from distributed_deep_learning_tpu.serve.scheduler import (PagedScheduler,
                                                           Request,
                                                           SlotScheduler)

__all__ = ["ServeEngine", "PagedEngine", "Request", "SlotScheduler",
           "PagedScheduler", "LoadSpec", "make_load", "slo_report",
           "merge_slo_reports", "FleetRouter", "ReplicaCrash", "RETIRED",
           "FleetAutoscaler", "PoolRebalancer", "EvacuationSignal",
           "HotspotDetector", "evacuate_slot"]
