"""What the benchmark's commands do before the first heavy import."""

import os
import sys


def prepare(root: str) -> None:
    """Make the checkout importable, keep source locations out of the
    compile cache's key (the train step then hits from any checkout path:
    PERF.md, "Compile cache"), and keep Python's bytecode cache inside the
    checkout: the chip machines set PYTHONDONTWRITEBYTECODE and have no
    cache beside the installed packages, so every process compiled every
    module it imported (my chip runs, PR 23: importing jax 2.8 s without,
    1.3 s with; the package's serving imports 38 s without, 36 s with:
    those are slow for another reason, PERF.md)."""
    if root not in sys.path:
        sys.path.insert(0, root)
    os.environ.setdefault("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "0")
    sys.dont_write_bytecode = False
    sys.pycache_prefix = os.path.join(root, ".bench_pycache")
