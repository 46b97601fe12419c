"""Shared example bootstrap: import FIRST, before anything touches jax.

Default: emulate an 8-device mesh on CPU so every example demonstrates
real sharding on any machine.  `--tpu` on the command line skips the
emulation and lets the mesh span the machine's accelerators.
"""

import os
import sys

USE_TPU = "--tpu" in sys.argv

if not USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
# runnable from a source checkout without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def train_phase_ends(metrics_path):
    """Parse the --metrics-file JSONL once and return the train-phase
    `phase_end` events in order (shared by the CLI examples' asserts)."""
    import json

    events = [json.loads(line) for line in open(metrics_path)]
    return [e for e in events
            if e["event"] == "phase_end" and e.get("phase") == "train"]
