"""Tests of the benchmark itself (not collected by the repo's tier-1 run,
which takes ``tests/`` only):  ``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/tests -q``.  They drive the real runners at a tiny width on the
CPU, skipping only the harness's look for a chip."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


@pytest.fixture
def tiny_cell():
    from benchmark import harness

    bench = harness.load_json(TINY, "BENCHMARK.json")
    return lambda name: harness.Cell(name, root=TINY, bench=bench)
