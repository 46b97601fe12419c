"""Driver-contract hooks: dryrun_multichip self-provisioning + bench ladder.

The driver calls ``dryrun_multichip(n)`` from an environment with one real
TPU chip; the hook must provision its own virtual n-device CPU platform
(round-1/2 failure mode: it ran on the ambient 1-device platform and died
in ``build_mesh``).  ``bench.py`` measures on the chip or not at all: with
no TPU, or when every attempt fails, it exits non-zero and prints no line.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

import __graft_entry__ as hooks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_with_device_count_appends():
    assert hooks._with_device_count("", 8) == \
        "--xla_force_host_platform_device_count=8"


def test_with_device_count_replaces_existing():
    out = hooks._with_device_count(
        "--foo --xla_force_host_platform_device_count=2 --bar", 8)
    assert "device_count=8" in out
    assert "device_count=2" not in out
    assert "--foo" in out and "--bar" in out


def test_ensure_virtual_devices_enough_already():
    # conftest forces 8 CPU devices; asking for <= 8 needs no re-exec
    assert hooks._ensure_virtual_devices(8) is True
    assert hooks._ensure_virtual_devices(4) is True


def test_ensure_virtual_devices_too_many_signals_subprocess():
    # jax is initialised with 8 devices here; 16 requires a re-exec
    assert hooks._ensure_virtual_devices(16) is False


def test_dryrun_multichip_subprocess_path(monkeypatch):
    # With jax bound to 8 devices, dryrun_multichip(16) must take the
    # subprocess branch with a forced-CPU 16-device environment.
    calls = {}

    def fake_run(cmd, env=None, **kw):
        calls["cmd"], calls["env"] = cmd, env

        class R:
            returncode = 0
        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    hooks.dryrun_multichip(16)
    assert calls["cmd"][1].endswith("__graft_entry__.py")
    assert calls["cmd"][2:] == ["--dryrun", "16"]
    assert calls["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=16" in \
        calls["env"]["XLA_FLAGS"]


def test_dryrun_multichip_subprocess_failure_raises(monkeypatch):
    def fake_run(cmd, env=None, **kw):
        class R:
            returncode = 3
        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    try:
        hooks.dryrun_multichip(16)
    except RuntimeError as exc:
        assert "rc=3" in str(exc)
    else:
        raise AssertionError("expected RuntimeError on child failure")


def _no_cpu_env(env) -> bool:
    """No child of the bench may be steered onto the CPU."""
    return env.get("JAX_PLATFORMS") != "cpu" and \
        "BENCH_CPU_FALLBACK" not in env


def _probe_aware(fn, probe_stdout="probe-ok tpu TPU v5 lite\n"):
    """Wrap a fake subprocess.run: answer the orchestrator's backend probe
    with ``probe_stdout``, delegate heavy attempts to ``fn``."""
    def run(cmd, env=None, timeout=None, **kw):
        assert _no_cpu_env(env)
        if env.get("BENCH_PROBE") == "1":
            class R:
                returncode = 0
                stdout = probe_stdout
            return R()
        return fn(cmd, env=env, timeout=timeout, **kw)
    return run


def _clean_bench_env(monkeypatch):
    for k in ("BENCH_BATCH", "BENCH_BATCH_PER_CHIP", "JAX_PLATFORMS"):
        monkeypatch.delenv(k, raising=False)


def test_bench_no_tpu_exits_nonzero_without_a_line(monkeypatch, capsys):
    """was test_bench_fallback_reexecs_on_cpu: a default backend that is
    alive but not a TPU ends the run at the probe — rc 1, no result line,
    no heavy attempt, no CPU re-exec."""
    sys.path.insert(0, REPO)
    import bench

    calls = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        calls.append(env)

    monkeypatch.setattr(subprocess, "run",
                        _probe_aware(fake_run, "probe-ok cpu cpu\n"))
    _clean_bench_env(monkeypatch)
    assert bench.orchestrate() == 1
    assert calls == []
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err and "cpu" in out.err


def test_bench_has_no_cpu_reexec_hook():
    """was test_bench_fallback_no_recursion: the in-process re-exec and
    its recursion guard are gone with the env switch that drove them."""
    import bench

    assert not hasattr(bench, "_devices_or_cpu_fallback")
    assert not hasattr(bench, "_enable_compile_cache")
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert "BENCH_CPU_FALLBACK" not in src
    assert "recorded_tpu" not in src


def test_bench_orchestrator_backoff(monkeypatch, capsys):
    """Two hung TPU attempts end the run: rc 1, nothing on stdout, and no
    CPU attempt; the s2d insurance attempt is skipped."""
    import bench

    calls = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        calls.append(env.get("BENCH_BATCH_PER_CHIP"))
        raise subprocess.TimeoutExpired(cmd, timeout)

    monkeypatch.setattr(subprocess, "run", _probe_aware(fake_run))
    _clean_bench_env(monkeypatch)
    assert bench.orchestrate() == 1
    assert calls == ["256", "128"]
    assert capsys.readouterr().out == ""


def test_bench_orchestrator_fast_errors_do_not_reach_cpu(monkeypatch, capsys):
    """Attempts that FAIL fast (rc != 0, e.g. a TPU erroring UNAVAILABLE)
    count like timeouts — two of any kind and the orchestrator gives up,
    rc 1, without a CPU line."""
    import bench

    calls = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        calls.append(env.get("BENCH_BATCH_PER_CHIP"))

        class R:
            returncode = 1
            stdout = ""
        return R()

    monkeypatch.setattr(subprocess, "run", _probe_aware(fake_run))
    _clean_bench_env(monkeypatch)
    assert bench.orchestrate() == 1
    assert calls == ["256", "128"]
    assert capsys.readouterr().out == ""


def test_bench_orchestrator_probe_failure_is_final(monkeypatch, capsys):
    """A dead/hung backend is detected by the cheap probe; no attempt of
    any kind is spawned after it and the run exits 1."""
    import bench

    calls = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        assert _no_cpu_env(env)
        if env.get("BENCH_PROBE") == "1":
            raise subprocess.TimeoutExpired(cmd, timeout)
        calls.append(env)

    monkeypatch.setattr(subprocess, "run", fake_run)
    _clean_bench_env(monkeypatch)
    assert bench.orchestrate() == 1
    assert calls == []
    assert capsys.readouterr().out == ""


def test_bench_orchestrator_global_deadline(monkeypatch):
    """Per-attempt timeouts are carved from the global budget: every
    spawned attempt must fit inside BENCH_TIMEOUT, and the worker gets a
    BENCH_DEADLINE to shed optional sections against."""
    import bench

    budgets = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        assert env.get("BENCH_DEADLINE") is not None
        budgets.append(timeout)

        class R:
            returncode = 1
            stdout = ""
        return R()

    monkeypatch.setattr(subprocess, "run", _probe_aware(fake_run))
    monkeypatch.setenv("BENCH_TIMEOUT", "600")
    _clean_bench_env(monkeypatch)
    assert bench.orchestrate() == 1
    assert len(budgets) == 2
    assert all(b <= 600 * 0.6 + 1 for b in budgets)


def test_bench_orchestrator_first_attempt_wins(monkeypatch, capsys):
    import bench

    calls = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        calls.append(env.get("BENCH_BATCH_PER_CHIP"))

        class R:
            returncode = 0
            stdout = '{"metric": "m", "value": 2}\n'
        return R()

    monkeypatch.setattr(subprocess, "run", _probe_aware(fake_run))
    _clean_bench_env(monkeypatch)
    assert bench.orchestrate() == 0
    assert calls == ["256"]
    assert json.loads(capsys.readouterr().out) == {"metric": "m", "value": 2}


def test_bench_orchestrator_respects_pinned_batch(monkeypatch):
    import bench

    calls = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        calls.append(env.get("BENCH_BATCH"))

        class R:
            returncode = 0
            stdout = '{"metric": "m", "value": 3}\n'
        return R()

    monkeypatch.setattr(subprocess, "run", _probe_aware(fake_run))
    _clean_bench_env(monkeypatch)
    monkeypatch.setenv("BENCH_BATCH", "32")
    assert bench.orchestrate() == 0
    assert calls == ["32"]


def test_bench_pinned_batch_failure_is_final(monkeypatch, capsys):
    """was test_bench_cpu_attempt_strips_batch_pins: a pinned batch gets
    its one attempt; when that fails there is no CPU attempt to strip the
    pin for — rc 1 and no line."""
    import bench

    calls = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        calls.append(env.get("BENCH_BATCH"))
        raise subprocess.TimeoutExpired(cmd, timeout)

    monkeypatch.setattr(subprocess, "run", _probe_aware(fake_run))
    _clean_bench_env(monkeypatch)
    monkeypatch.setenv("BENCH_BATCH", "2048")
    assert bench.orchestrate() == 1
    assert calls == ["2048"]
    assert capsys.readouterr().out == ""


def test_bench_retry_attempts_shed_optional_sections(monkeypatch):
    """After a first-attempt timeout only leftovers remain — retries must
    spend them on the headline, not on DenseNet/LM/input sections that
    cannot fit."""
    import bench

    calls = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        calls.append({k: env.get(k) for k in
                      ("BENCH_BATCH_PER_CHIP", "BENCH_SECONDARY",
                       "BENCH_LM", "BENCH_INPUT")})
        if env.get("BENCH_BATCH_PER_CHIP") == "256":
            raise subprocess.TimeoutExpired(cmd, timeout)

        class R:
            returncode = 0
            stdout = '{"metric": "m", "value": 3}\n'
        return R()

    monkeypatch.setattr(subprocess, "run", _probe_aware(fake_run))
    _clean_bench_env(monkeypatch)
    assert bench.orchestrate() == 0
    # the full-section first attempt timed out; the retry sheds extras
    assert calls[0]["BENCH_SECONDARY"] is None
    assert calls[1]["BENCH_BATCH_PER_CHIP"] == "128"
    assert calls[1]["BENCH_SECONDARY"] == "0"
    assert calls[1]["BENCH_LM"] == "0"
    assert calls[1]["BENCH_INPUT"] == "0"


@pytest.mark.parametrize("env_dir,backend", [
    (None, "tpu"), (None, "cpu"), ("/some/dir", "tpu"), ("/some/dir", "cpu")],
    ids=["env-unset", "env-unset-cpu", "env-set", "env-set-cpu"])
def test_bench_compile_cache_config(monkeypatch, env_dir, backend):
    """The one cache helper (runtime/bootstrap.enable_compile_cache, shared
    by run_workload, bench.py, scripts/* and chip_smoke.py): with
    JAX_COMPILATION_CACHE_DIR set it names no directory in code (JAX reads
    the variable itself); unset, it uses the fixed <checkout>/.jax_cache on
    an accelerator and nothing on the CPU backend."""
    from distributed_deep_learning_tpu.runtime import bootstrap

    seen = {}
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: seen.__setitem__(k, v))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    # hermetic: no .jax_cache dir creation in the source tree
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    if env_dir is not None:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert bootstrap.enable_compile_cache() == env_dir
        assert seen == {}
        return
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if backend == "cpu":
        assert bootstrap.enable_compile_cache() is None
        assert seen == {}
    else:
        assert bootstrap.enable_compile_cache() == \
            os.path.join(REPO, ".jax_cache")
        assert seen == {"jax_compilation_cache_dir":
                        os.path.join(REPO, ".jax_cache")}


def test_bench_worker_sheds_sections_past_deadline(monkeypatch):
    import time as _t

    import bench

    monkeypatch.setenv("BENCH_DEADLINE", repr(_t.time() + 30))
    assert bench._time_left() < 31
    monkeypatch.setenv("BENCH_DEADLINE", repr(_t.time() + 1000))
    assert 990 < bench._time_left() < 1001
    monkeypatch.delenv("BENCH_DEADLINE")
    assert bench._time_left() == float("inf")


def test_bench_worker_fails_fast_on_init_error(monkeypatch, capsys):
    """A worker whose backend fails to init raises — it spawns nothing
    and prints no line."""
    import bench

    monkeypatch.setattr(jax, "devices",
                        lambda *a: (_ for _ in ()).throw(RuntimeError("down")))
    called = {}
    monkeypatch.setattr(subprocess, "call",
                        lambda *a, **k: called.setdefault("spawned", True))
    monkeypatch.setattr(subprocess, "run",
                        lambda *a, **k: called.setdefault("spawned", True))
    with pytest.raises(RuntimeError, match="down"):
        bench.main()
    assert "spawned" not in called
    assert capsys.readouterr().out == ""


def _load_tpu_validation():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tpu_validation", os.path.join(REPO, "scripts",
                                       "tpu_validation.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_validation_sections_run_at_micro_shapes():
    """The watchdogged TPU validation sections execute end to end on CPU
    at micro shapes (round 5): the harness the next healthy hardware
    window depends on must not rot."""
    tv = _load_tpu_validation()
    r = tv.gqa_speedup(B=1, T=32, H=4, Hkv=2, D=16, steps=1)
    assert r["speedup"] > 0 and r["mha_ms"] > 0 and r["gqa_ms"] > 0
    r = tv.flash_vs_dense(B=1, T=32, H=2, D=16, steps=1)
    assert r["speedup"] > 0 and r["dense_ms"] > 0
    r = tv.flash_block_sweep(B=1, T=32, H=2, D=16, steps=1)
    assert r["best"] is not None and len(r["rows"]) >= 1
    assert all("ms" in row or "error" in row for row in r["rows"])


def test_lm_throughput_remat_micro():
    """The lm_sweep remat rows ride _lm_throughput(remat=True): the
    jax.checkpoint wrapping must compile and run (micro shape, CPU)."""
    import jax.numpy as jnp

    import bench
    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    mesh = build_mesh({"data": len(jax.devices())})
    tps, fps = bench._lm_throughput(batch=len(jax.devices()), seq_len=16,
                                    steps=1, mesh=mesh, dtype=jnp.float32,
                                    remat=True, vocab_size=128,
                                    num_layers=2, d_model=32, num_heads=2,
                                    mlp_dim=64)
    assert tps > 0
    assert fps is None or fps > 0


def test_lm_sweep_mfu_vs_hfu_bookkeeping(monkeypatch, capsys):
    """Remat rows must compute MFU from the non-remat model FLOPs/token
    (cost_analysis on a remat program counts the recompute — that's HFU),
    print one JSON line per completed row, and keep full exception text
    for failed configs."""
    import bench

    tv = _load_tpu_validation()

    ndev = len(jax.devices())

    def fake_lm(*, batch, seq_len, steps, mesh, dtype, remat=False, **kw):
        if batch >= 64 * ndev:
            raise RuntimeError("RESOURCE_EXHAUSTED: 17.2G of 16.0G hbm")
        # 100 FLOPs/token model cost; remat programs report 1.33x
        return 1000.0, batch * seq_len * (133.0 if remat else 100.0)

    monkeypatch.setattr(tv, "_lm_throughput", fake_lm, raising=False)
    # lm_sweep imports from bench inside the function body
    monkeypatch.setattr(bench, "_lm_throughput", fake_lm)
    monkeypatch.setattr(bench, "chip_peak_flops", lambda kind: 1e6)

    out = tv.lm_sweep(configs=((16, False), (32, True), (64, True)),
                      seq=128, steps=1)
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert out["rows_completed"] == 2
    rows = {(l["per_chip_batch"], l["remat"]): l for l in lines}
    # non-remat MFU from its own FLOPs; remat MFU from the non-remat
    # cost, with the inflated recompute count relegated to hfu
    assert rows[(16, False)]["mfu"] == pytest.approx(0.1)
    assert rows[(32, True)]["mfu"] == pytest.approx(0.1)
    assert rows[(32, True)]["hfu"] == pytest.approx(0.133)
    assert "RESOURCE_EXHAUSTED" in rows[(64, True)]["error"]


def test_validation_section_registry_resolves():
    """Every name in SECTIONS resolves to a callable (the parent spawns
    children by name via globals())."""
    tv = _load_tpu_validation()
    for name in tv.SECTIONS:
        assert callable(getattr(tv, name)), name
