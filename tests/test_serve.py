"""Continuous-batching engine: compile-once proof, generate() parity,
and scheduler semantics.

The two load-bearing guarantees (ISSUE 2 acceptance):

* the decode step compiles EXACTLY ONCE across a trace of requests with
  varying prompt lengths and staggered arrivals (``CountingJit`` counts
  traces — jit retraces exactly when it must compile).  The greedy
  engine here is module-shared, so the counter additionally proves one
  compilation across EVERY greedy trace in this file, whatever subset
  or order pytest runs;
* engine greedy tokens match batch-synchronous ``generate()`` token for
  token on the same prompts (slot decode is the model's own cached
  decode vmapped over slots, bucket padding leaves no numerical trace).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_deep_learning_tpu.models.transformer import (CausalLM,
                                                              generate)
from distributed_deep_learning_tpu.serve.engine import (ServeEngine,
                                                        default_buckets)
from distributed_deep_learning_tpu.serve.scheduler import (Request,
                                                           SlotScheduler)

MODEL = dict(vocab_size=61, num_layers=2, d_model=32, num_heads=4,
             mlp_dim=64, max_len=48)


def _model(**kw):
    return CausalLM(**{**MODEL, **kw})


@functools.lru_cache(maxsize=None)
def _shared(**kw):
    model = _model(**kw)
    toks = jnp.ones((1, 4), jnp.int32)
    return model, model.init(jax.random.key(1), toks)["params"]


@functools.lru_cache(maxsize=None)
def _greedy_engine():
    """ONE greedy engine reused across tests — exactly how a server
    lives across traffic, and the strongest form of the compile-once
    claim (the trace counter spans every test that uses it)."""
    model, params = _shared()
    return ServeEngine(model, params, max_slots=3)


def _trace(seed=0, n=7, max_new=(1, 12), plens=(3, 20), stagger=3):
    """Mixed lengths AND staggered arrivals — spans several buckets."""
    rng = np.random.default_rng(seed)
    reqs, tick = [], 0
    for uid in range(n):
        p = int(rng.integers(*plens))
        reqs.append(Request(uid, rng.integers(1, 61, p).astype(np.int32),
                            int(rng.integers(*max_new)),
                            arrival_tick=tick))
        tick += int(rng.integers(0, stagger + 1))
    return reqs


def _check_parity(model, params, out, reqs, label=""):
    for r in reqs:
        ref = generate(model, params, jnp.asarray(r.prompt)[None],
                       max_new_tokens=r.max_new_tokens)
        np.testing.assert_array_equal(out["results"][r.uid],
                                      np.asarray(ref)[0],
                                      err_msg=f"{label} request {r.uid}")


# --- the tentpole guarantees -------------------------------------------


def test_decode_compiles_once_across_mixed_trace():
    """THE compile-count guard: varying prompt lengths, staggered
    arrivals, slot churn — one decode compilation, total."""
    eng = _greedy_engine()
    out = eng.run(_trace(n=8))
    s = out["stats"]
    assert s["decode_compiles"] == 1, s
    # prefill compiles once per DISTINCT bucket ever used, never per
    # request (= per trace only when the engine is fresh)
    assert s["prefill_compiles"] <= len(eng.buckets), s
    assert s["prefill_calls"] == 8
    assert len(out["results"]) == 8
    # a second trace through the SAME engine: zero new compilations
    out2 = eng.run(_trace(seed=11, n=4))
    assert out2["stats"]["decode_compiles"] == 1
    assert out2["stats"]["prefill_compiles"] <= len(eng.buckets)


def test_engine_matches_generate_greedy():
    """Engine greedy tokens == generate() token for token, per request
    (bucket padding + counter fixup leave no numerical trace)."""
    model, params = _shared()
    reqs = _trace(n=4, max_new=(1, 10))
    out = _greedy_engine().run(reqs)
    _check_parity(model, params, out, reqs)


def test_engine_matches_generate_rope_and_gqa():
    """The parity contract holds for rotary positions and grouped-query
    caches too (both change the cache layout the slot table re-hosts)."""
    for kw in ({"pos_embedding": "rope"}, {"num_kv_heads": 2}):
        model, params = _shared(**kw)
        reqs = _trace(n=3, seed=3, max_new=(1, 8))
        out = ServeEngine(model, params, max_slots=2).run(reqs)
        _check_parity(model, params, out, reqs, label=str(kw))


def test_eos_retires_early_and_slot_is_reused():
    """EOS terminates a row before its budget and the freed slot serves
    the queue; every request still finishes."""
    eng = _greedy_engine()
    reqs = _trace(n=6, max_new=(6, 10))
    # pick the eos id the first request actually emits so at least one
    # row genuinely retires on EOS (greedy decode is deterministic)
    ref = eng.run(reqs)
    eos = int(ref["results"][0][2])
    first = int(np.where(ref["results"][0] == eos)[0][0])
    eng.eos_id = eos
    try:
        out = eng.run(reqs)
    finally:
        eng.eos_id = None
    assert len(out["results"]) == len(reqs)
    # row 0 stops AT its first eos emission, before the budget
    assert len(out["results"][0]) == first + 1 < len(ref["results"][0])
    assert out["results"][0][-1] == eos
    for r in reqs:                               # never over budget
        assert len(out["results"][r.uid]) <= r.max_new_tokens


def test_sampled_serving_shape_and_range():
    model, params = _shared()
    eng = ServeEngine(model, params, max_slots=2, temperature=1.0,
                      top_k=7, rng=jax.random.key(9))
    out = eng.run(_trace(n=3, seed=5, max_new=(1, 8)))
    assert out["stats"]["decode_compiles"] == 1
    for toks in out["results"].values():
        assert ((toks > 0) & (toks < 61)).all()   # pad id 0 never emitted


def test_request_validation():
    model, params = _shared()
    eng = _greedy_engine()
    # an invalid request is recorded, not raised: the submit-time check
    # isolates it so the rest of the batch still serves (ISSUE 3)
    out = eng.run([Request(0, np.arange(1, 47, dtype=np.int32), 5)])
    assert "max_len" in out["errors"][0] and not out["results"]
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(1, np.ones(3, np.int32), 0)
    with pytest.raises(ValueError, match="prompt"):
        Request(2, np.ones((2, 3), np.int32), 4)
    with pytest.raises(ValueError, match="max_len"):
        ServeEngine(model, params, max_len=4096)
    with pytest.raises(ValueError, match="bucket"):
        ServeEngine(model, params, prefill_buckets=(8, 4096))


def test_invalid_request_does_not_abort_batch():
    """One oversize request + three valid ones: the valid requests
    complete with full budgets, the bad one gets a per-uid error."""
    eng = _greedy_engine()
    reqs = [Request(0, np.arange(1, 5, dtype=np.int32), 4),
            Request(1, np.arange(1, 47, dtype=np.int32), 5),   # oversize
            Request(2, np.arange(1, 9, dtype=np.int32), 3),
            Request(3, np.arange(1, 3, dtype=np.int32), 2)]
    out = eng.run(reqs)
    assert set(out["results"]) == {0, 2, 3}
    assert set(out["errors"]) == {1}
    assert "max_len" in out["errors"][1]
    assert out["stats"]["requests"] == 3
    assert out["stats"]["rejected"] == 1
    for r in (reqs[0], reqs[2], reqs[3]):
        assert len(out["results"][r.uid]) == r.max_new_tokens


def test_default_buckets():
    assert default_buckets(160) == (8, 16, 32, 64, 128, 160)
    assert default_buckets(8) == (8,)
    # explicit buckets always gain the max_len top bucket
    model, params = _shared()
    eng = ServeEngine(model, params, prefill_buckets=(8,))
    assert eng.buckets == (8, 48)


# --- scheduler (pure host-side) ----------------------------------------


def test_scheduler_fifo_admission_and_retirement():
    s = SlotScheduler(2)
    for uid, tick in ((0, 0), (1, 0), (2, 1)):
        s.submit(Request(uid, np.ones(3, np.int32), 2, arrival_tick=tick))
    assert s.place(0)[0] == 0 and s.place(0)[0] == 1
    assert s.place(0) is None                  # uid 2: full AND not arrived
    assert s.occupancy == 2
    s.record(0, 7, None)
    assert s.record(0, 8, None).uid == 0       # budget 2 -> retired
    assert s.occupancy == 1
    idx, req = s.place(1)
    assert (idx, req.uid) == (0, 2)            # freed slot, next arrival
    np.testing.assert_array_equal(s.finished[0], [7, 8])


def test_scheduler_arrival_order_beats_submission_order():
    s = SlotScheduler(1)
    s.submit(Request(0, np.ones(2, np.int32), 1, arrival_tick=5))
    s.submit(Request(1, np.ones(2, np.int32), 1, arrival_tick=2))
    assert s.next_arrival() == 2
    assert s.place(2)[1].uid == 1


def test_scheduler_last_tokens_tracks_slots():
    s = SlotScheduler(3)
    s.submit(Request(0, np.ones(2, np.int32), 4))
    s.place(0)
    s.record(0, 17, None)
    np.testing.assert_array_equal(s.last_tokens(), [17, 0, 0])


# --- CLI / script surface ----------------------------------------------


def test_config_serve_flags():
    from distributed_deep_learning_tpu.utils.config import parse_args

    cfg = parse_args(["--serve", "--max-slots", "4",
                      "--prefill-buckets", "8,32"], workload="gpt")
    assert cfg.serve and cfg.max_slots == 4
    assert cfg.prefill_buckets == (8, 32)
    assert parse_args([], workload="gpt").serve is False
    with pytest.raises(SystemExit, match="prefill-buckets"):
        parse_args(["--prefill-buckets", "8,x"], workload="gpt")

