"""One array up and one array down a serving program (PR 41).

The paged engine's chunk and decode programs take what the host makes for
a tick as ONE int32 array and hand back what the host reads as ONE int32
array (``serve.engine.Packed``, ``PagedEngine.put`` / ``fetch``).  Held
here, on the CPU, for a GPT-2-shaped, a laguna-shaped (rings and experts)
and a glm-shaped (latent rows and experts) engine: the layout's round
trip; every program of a real run against its body called on the separate
values, bit for bit; the counters that say one put and at most one fetch a
program, against the calls the engine's module really made; and the canary
path, which goes through the same two helpers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_glm import build as glm_build
from test_glm import tiny as glm_tiny
from test_laguna import build as laguna_build
from test_laguna import tiny as laguna_tiny

from distributed_deep_learning_tpu import obs
from distributed_deep_learning_tpu.models.transformer import random_causal_lm
from distributed_deep_learning_tpu.serve import engine as engine_module
from distributed_deep_learning_tpu.serve.engine import Packed, PagedEngine
from distributed_deep_learning_tpu.serve.scheduler import Request

SHAPES = ("gpt2", "laguna", "glm")


def _engine(shape, **kw):
    if shape == "gpt2":
        model, params = random_causal_lm(seed=3, vocab_size=97, num_layers=2,
                                         d_model=32, num_heads=4, mlp_dim=64,
                                         max_len=64)
    else:
        model, params, _ = (laguna_build(laguna_tiny(), max_len=64)
                            if shape == "laguna"
                            else glm_build(glm_tiny(), max_len=64))
    return PagedEngine(model, params, **{
        "max_slots": 3, "max_len": 64, "kv_block_size": 4,
        "prefill_chunk": 8, **kw})


def _requests(seed=5):
    """Prompts of one to four chunks (past the laguna ring of 20
    positions) and a few new tokens each, more requests than slots."""
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(1, 97, size=n),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(30, 6), (7, 9), (23, 4), (12, 7),
                                        (17, 5)])]


def _serve(eng, requests=None):
    """Run the requests; (the run's record, {uid: [(token, logprob bits,
    finite)]} as the tick reports gave them)."""
    said = {}

    def on_tick(report):
        for uid, tok in report.emitted:
            said.setdefault(uid, []).append(
                (tok, np.float32(report.logprob[uid]).view(np.int32).item(),
                 report.finite[uid]))

    out = eng.run(requests or _requests(), on_tick=on_tick)
    assert not out["errors"]
    return out, said


# ------------------------------------------------------------ the layout

def test_a_layout_takes_apart_what_it_packed():
    """Pairs, scalars and a missing value; the host's pack against the
    program's join; a float's bits and a flag's truth across both."""
    def like(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    up = Packed(toks=like(jnp.int32, 5), pos=like(jnp.int32),
                table=(like(jnp.int32, 2, 3), like(jnp.int32, 4)), none=None)
    assert up.size == 5 + 1 + 6 + 4
    values = dict(toks=np.arange(5), pos=7,
                  table=(np.arange(6).reshape(2, 3) + 10, np.arange(4) + 20),
                  none=None)
    flat = up.pack(**values)
    assert flat.dtype == np.int32 and flat.shape == (16,)
    for got in (up.split(flat), jax.jit(up.split)(jnp.asarray(flat))):
        assert got["none"] is None and int(got["pos"]) == 7
        np.testing.assert_array_equal(got["toks"], values["toks"])
        np.testing.assert_array_equal(got["table"][0], values["table"][0])
        np.testing.assert_array_equal(got["table"][1], values["table"][1])
    with pytest.raises(ValueError):             # a value too few
        up.pack(toks=values["toks"], pos=7, table=values["table"][0])

    down = Packed(tok=like(jnp.int32), lp=like(jnp.float32, 3),
                  ok=like(jnp.bool_, 3), load=like(jnp.int32, 2, 2))
    lp = np.array([-0.1, -np.inf, np.nan], np.float32)
    ok = np.array([True, False, True])
    load = np.array([[1, 0], [2, 3]], np.int32)
    flat = np.asarray(jax.jit(down.join)(tok=jnp.int32(41), lp=lp, ok=ok,
                                          load=load))
    assert flat.dtype == np.int32 and flat.shape == (down.size,)
    got = down.split(flat)
    assert int(got["tok"]) == 41 and got["lp"].dtype == np.float32
    np.testing.assert_array_equal(got["lp"].view(np.int32),
                                  lp.view(np.int32))   # the same bits
    np.testing.assert_array_equal(got["ok"], ok)
    np.testing.assert_array_equal(got["load"], load)
    with pytest.raises(ValueError, match="shape"):
        down.join(tok=jnp.int32(1), lp=lp[:2], ok=ok, load=load)


@pytest.mark.parametrize("shape", SHAPES)
def test_the_layouts_are_the_engines_shapes(shape):
    """Fixed when the engine is built, from ``max_slots``,
    ``blocks_per_slot``, ``ring_blocks`` and ``prefill_chunk``; the ring
    and load sections are empty where the model has none."""
    eng = _engine(shape)
    S, C, bps = eng.max_slots, eng.chunk, eng.blocks_per_slot
    ring = eng.ring_blocks or 0
    assert (ring > 0) == (shape == "laguna")
    load = {"gpt2": 0, "laguna": 5 * 4, "glm": 2 * 8}[shape]
    (c_up, c_down), (d_up, d_down) = eng._chunk_io, eng._decode_io
    assert c_up.size == C + bps + ring + 2 + C * (2 if ring else 1) + C
    assert d_up.size == S * (bps + ring) + S * (5 if ring else 4)
    assert c_down.size == 3 + load and d_down.size == 3 * S + load


# ------------------------------------- a program is its body, bit for bit

def _same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        if x.dtype.kind == "f":     # bits, so that a nan equals itself
            x, y = (z.view(f"i{z.dtype.itemsize}") for z in (x, y))
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("program", ["paged_chunk", "paged_decode"])
@pytest.mark.parametrize("shape", SHAPES)
def test_every_program_of_a_run_is_its_body_on_the_separate_values(
        shape, program):
    """The program objects take ``(params, pools, one int32 array, key)``;
    their bodies stay functions of the separate values.  Every call of a
    real run is repeated on the body with the values the array held: the
    same pools, tokens, logprobs (as bits), flags and loads."""
    eng = _engine(shape)
    attr, body, io = {
        "paged_chunk": ("_chunk_prog", eng._chunk_impl, eng._chunk_io),
        "paged_decode": ("_decode", eng._decode_impl, eng._decode_io),
    }[program]
    prog, body, seen = getattr(eng, attr), jax.jit(body), []

    class Spy:
        traces = property(lambda s: prog.traces)
        _jit = prog._jit

        def __call__(s, params, pools, up, key):
            assert up.dtype == jnp.int32 and up.shape == (io[0].size,)
            v = io[0].split(np.asarray(up))
            if program == "paged_chunk":
                want = body(params, pools, v["toks"], v["table"], v["pos"],
                            v["logit_idx"], v["wb"], v["wo"], key)
            else:
                want = body(params, pools, v["tables"], v["pos"], v["toks"],
                            v["wb"], v["wo"], key)
            new_pools, down = prog(params, pools, up, key)
            assert down.dtype == jnp.int32 and down.shape == (io[1].size,)
            got = io[1].split(np.asarray(down))
            _same(new_pools, want[0])
            _same([got["tok" if program == "paged_chunk" else "toks"],
                   got["lp"], got["ok"], got["load"]], list(want[1:]))
            seen.append(got["load"])
            return new_pools, down

    setattr(eng, attr, Spy())
    out, _ = _serve(eng)
    stats = out["stats"]
    assert len(seen) == (stats["prefill_chunks"] if program == "paged_chunk"
                         else stats["decode_ticks"]) > 5
    assert (seen[0] is None) == (shape == "gpt2")
    assert stats["chunk_compiles"] == stats["decode_compiles"] == 1


# ------------------------------------------- the counters, and what they count

class _Counting:
    """A module whose `asarray` calls are counted: those of device arrays
    (fetches) apart from those of anything else (puts)."""

    def __init__(self, module):
        self._module, self.of_device, self.of_host = module, 0, 0

    def __getattr__(self, name):
        return getattr(self._module, name)

    def asarray(self, x, *args, **kw):
        if isinstance(x, jax.Array):
            self.of_device += 1
        else:
            self.of_host += 1
        return self._module.asarray(x, *args, **kw)


@pytest.mark.parametrize("shape", SHAPES)
def test_a_run_puts_once_and_fetches_at_most_once_a_program(shape,
                                                            monkeypatch):
    eng = _engine(shape)
    # other prompts first (nothing for the index to share), so that both
    # programs are traced and the run below makes no call but its ticks' own
    _serve(eng, _requests(seed=6))
    np_, jnp_ = _Counting(np), _Counting(jnp)
    monkeypatch.setattr(engine_module, "np", np_)
    monkeypatch.setattr(engine_module, "jnp", jnp_)
    out, _ = _serve(eng)
    monkeypatch.undo()
    io = out["stats"]["paged"]["host_io"]
    n = out["stats"]["prefill_chunks"] + out["stats"]["decode_ticks"]
    assert io["programs"] == io["puts"] == n
    # what the helpers counted is what the engine's module did: no other
    # array went up, nothing else was fetched
    assert (jnp_.of_host, jnp_.of_device) == (io["puts"], 0)
    assert (np_.of_device, np_.of_host) == (io["fetches"], 0)
    pc = obs.last_run("serve").phases
    progs = [p for t in pc.ticks for p in t[2][2]["programs"]]
    assert len(progs) == n and eng.host_io == io
    assert [sum(p["io"][k] for p in progs) for k in (0, 1)] == [
        io["puts"], io["fetches"]]
    for p in progs:
        if shape != "gpt2":         # every program's load is read, once
            assert p["io"] == [1, 1] and "experts" in p
        elif p["program"] == "paged_decode":
            assert p["io"] == [1, 1]
    if shape == "gpt2":             # only a request's last chunk is read
        chunks = [p["io"] for p in progs if p["program"] == "paged_chunk"]
        assert chunks.count([1, 1]) == len(_requests())
        assert chunks.count([1, 0]) == len(chunks) - len(_requests()) > 0
        assert io["fetches"] < io["programs"]
    else:
        assert io["fetches"] == io["programs"]


def test_the_report_prints_the_host_io_line():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(__file__), "..",
                                   "scripts", "obs_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    out, _ = _serve(_engine("gpt2"))
    io = out["stats"]["paged"]["host_io"]
    want = (f"host io: {io['programs']} programs, {io['puts']} puts (1.00 a "
            f"program), {io['fetches']} fetches "
            f"({io['fetches'] / io['programs']:.2f} a program)")
    assert want in report.render([{"event": "obs_serve",
                                   "stats": out["stats"]}])
    assert want in report.render_programs(obs.last_run("serve"))


# ----------------------------------------------------------- the canary

def test_a_canary_of_the_same_weights_serves_the_plain_tokens():
    """Two calls of the decode program a tick, each with its own packed
    array and its own fetch; the same weights on both sides serve what the
    plain path serves, logprob bits and flags too."""
    plain, said = _serve(_engine("gpt2"))
    eng = _engine("gpt2")
    eng.begin_canary(eng.params, [1])
    out, canary_said = _serve(eng)
    summary = eng.end_canary(promote=False)
    for uid, toks in plain["results"].items():
        np.testing.assert_array_equal(out["results"][uid], toks)
    assert canary_said == said
    assert summary["compared"] == summary["agreed"] > 0
    assert summary["mean_abs_logprob_drift"] == 0 == summary["nonfinite"]
    io, st = out["stats"]["paged"]["host_io"], out["stats"]
    assert io["programs"] == io["puts"] == (st["prefill_chunks"]
                                            + 2 * st["decode_ticks"])
    assert io["fetches"] == len(_requests()) + 2 * st["decode_ticks"]
    assert st["decode_compiles"] == 1


# ----------------------------------------------------------- disagg's decode

def test_disagg_decodes_through_the_same_two_helpers():
    """A decode worker's tick is one put, one call of the unified decode
    program and one fetch; the tokens are the unified engine's, and so are
    the logprobs and flags the tick reports carry."""
    from distributed_deep_learning_tpu.serve.disagg import DisaggEngine

    model, params = random_causal_lm(seed=3, vocab_size=97, num_layers=2,
                                     d_model=32, num_heads=4, mlp_dim=64,
                                     max_len=64)
    kw = dict(max_slots=3, max_len=64, kv_block_size=4, prefill_chunk=8)
    plain, said = _serve(PagedEngine(model, params, **kw))
    dis = DisaggEngine(model, params, prefill_streams=2, **kw)
    seen = {}

    def on_tick(report):
        if report.kind == "decode":
            for uid, tok in report.emitted:
                seen.setdefault(uid, []).append(
                    (tok, np.float32(report.logprob[uid]).view(
                        np.int32).item(), report.finite[uid]))

    out = dis.run(_requests(), on_tick=on_tick)
    assert not out["errors"]
    for uid, toks in plain["results"].items():
        np.testing.assert_array_equal(out["results"][uid], toks)
        assert seen[uid] == said[uid][1:]   # (the first is the prefill's)
    io = [w.eng.host_io for w in dis.decode]
    ticks = out["stats"]["decode_ticks"]
    assert sum(i["programs"] for i in io) == ticks > 0
    assert all(i["puts"] == i["fetches"] == i["programs"] for i in io)
