"""``chip_smoke.py`` rehearsed off the chip, at its ``--tiny`` size.

The smoke exists to fail when the chip is not what ran, so on this CPU it
must fail, at the platform check, without ever printing the contract's
line.  The second case steers that one check from here (the program has no
option for it) and lets every phase run to the end on a single CPU device:
wrong paths, arguments and control flow show up before any chip time is
spent.  What only a chip shows — compiled kernels, committed TPU arrays —
stays the smoke's own job there.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script: list[str], tmp_path, devices: int = 1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, *code_or_script, "--tiny", "--out", str(tmp_path)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


def test_chip_smoke_fails_on_cpu_and_names_the_platform(tmp_path):
    proc = _run(["chip_smoke.py"], tmp_path)
    assert proc.returncode != 0
    assert "FAILED at phase 'device'" in proc.stdout
    assert "platform is 'cpu'" in proc.stdout
    assert '"ok": true' not in proc.stdout + proc.stderr
    assert not os.path.exists(tmp_path / "tokens.npy")   # stopped at once


def test_chip_smoke_walks_every_phase_when_the_check_is_steered(tmp_path):
    steer = ("import sys, chip_smoke; chip_smoke.PLATFORM = 'cpu'; "
             "sys.exit(chip_smoke.main(sys.argv[1:]))")
    proc = _run(["-c", steer], tmp_path)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    # the CLI phase: two epochs, then the paged engine on those weights
    assert '"devices: platform=cpu ' in out
    assert '"train epoch 2 ends at ' in out
    assert '"serve(paged): 8 requests (8 completed, 0 errors)' in out
    assert "compiles chunk=1 decode=1" in out
    assert "chip_smoke: model: vocab 257 context 64" in out
    assert "chip_smoke: engine vs generate():" in out
    # the longer trace, then both kernels against their references
    assert "chip_smoke: serve_trace: 4 requests" in out
    assert "chip_smoke: flash_attention dv:" in out
    assert "chip_smoke: paged_flash_decode int8:" in out
    assert "chip_smoke: native: built" in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
