"""Driver-contract hooks: dryrun_multichip self-provisioning + compile cache.

The driver calls ``dryrun_multichip(n)`` from an environment with one real
TPU chip; the hook must provision its own virtual n-device CPU platform
(round-1/2 failure mode: it ran on the ambient 1-device platform and died
in ``build_mesh``).
"""

import os
import subprocess

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


@pytest.mark.parametrize("env_dir,backend", [
    (None, "tpu"), (None, "cpu"), ("/some/dir", "tpu"), ("/some/dir", "cpu")],
    ids=["env-unset", "env-unset-cpu", "env-set", "env-set-cpu"])
def test_compile_cache_config(monkeypatch, env_dir, backend):
    """The one cache helper (runtime/bootstrap.enable_compile_cache, shared
    by run_workload, benchmark/, scripts/* and chip_smoke.py): with
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

