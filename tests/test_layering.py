"""The package's layers, as the imports draw them (ROADMAP D14).

Pure AST, no import of the package: each subpackage's set of sibling
subpackages imported (function-level imports included) equals the row
written here.  The table is the tree as it stands, not as it should be: a
new edge fails until its row says why, and an edge that goes needs its row
shortened.  Edges that point UP (a lower layer reaching into a higher one)
carry the ROADMAP debt that removes them.
"""

import ast
import os

import pytest

PKG = "distributed_deep_learning_tpu"
ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), PKG)

IMPORTS = {
    "native": set(),
    "data": {"native", "obs"},
    "obs": {"utils"},
    "ops": {
        # no "utils": the flash kernel's block sizes are its own constants
        "models",    # UP: the dense fallback and the cache-leaf names (D14)
        "serve",     # UP: paged_decode_pallas reads serve.quant.is_quant (D14)
        "runtime",   # attention_pallas._per_shard asks runtime.batch_pin what the mesh splits
        "obs",       # attention_pallas leaves its flash_layout note in obs.runlog's compile log
    },
    "models": {
        "ops",
        "runtime",   # models.transformer pins activations: runtime.batch_pin
        "parallel",  # UP: models.pipelined_lm builds on parallel.spmd_pipeline (D8)
    },
    "parallel": {
        "data", "runtime",
        "models",    # UP, and a cycle with models -> parallel (D8)
        "train",     # UP: collectives / zero build TrainState steps (D14)
    },
    "runtime": {
        "obs", "utils",
        "data", "models", "train",   # UP: runtime.selftest trains an MLP (D14)
    },
    "train": {"data", "obs", "ops",    # the token loss on deferred logits
              "runtime", "utils"},
    "serve": {"models", "obs", "parallel", "reshard", "utils",
              "ops"},    # serve.engine asks the latent kernel what it reads a row
    "reshard": {"data", "models", "parallel", "runtime", "train", "tune",
                "utils"},
    "tune": {"data", "obs", "runtime", "train", "utils", "workloads"},
    "utils": {
        "reshard", "train",            # UP: utils.checkpoint (D14)
        "data", "models", "obs", "runtime",
        "serve",     # UP: utils.chaos's drills drive the engines (D6)
    },
    "workloads": {"data", "models", "obs", "ops", "parallel", "reshard",
                  "runtime", "serve", "train", "tune", "utils"},
}

#: modules that build a path above the package directory, and why each may:
#: nothing else in the package reads or writes around its checkout
ABOVE_PACKAGE = {
    "runtime/bootstrap.py",   # <checkout>/.jax_cache, the compile cache
    "models/describe.py",     # a --model-file the USER names, relative to
                              # the checkout when not to the working directory
}


def _modules(sub=None):
    top = os.path.join(ROOT, sub) if sub else ROOT
    for dirpath, _, names in os.walk(top):
        for name in sorted(names):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    yield os.path.relpath(path, ROOT), ast.parse(f.read())


def _imported(rel: str, tree) -> set:
    """Absolute dotted names a module imports, relative ones resolved."""
    parts = [PKG] + rel[:-3].split(os.sep)
    here = parts[:-1]                 # the containing package, both for a
    out = set()                       # module and for an __init__
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - (node.level - 1)] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            out.add(mod)
            out.update(f"{mod}.{a.name}" for a in node.names)
    return out


def test_the_table_names_every_subpackage():
    subs = {d for d in os.listdir(ROOT)
            if os.path.isfile(os.path.join(ROOT, d, "__init__.py"))}
    assert subs == set(IMPORTS)


@pytest.mark.parametrize("sub", sorted(IMPORTS))
def test_subpackage_imports(sub):
    found = {}
    for rel, tree in _modules(sub):
        for name in _imported(rel, tree):
            parts = name.split(".")
            if parts[0] == PKG and len(parts) > 1 and parts[1] in IMPORTS \
                    and parts[1] != sub:
                found.setdefault(parts[1], set()).add(rel)
    new = {k: sorted(v) for k, v in found.items() if k not in IMPORTS[sub]}
    gone = IMPORTS[sub] - set(found)
    assert not new and not gone, f"new edges {new}, edges gone {gone}"


def _dirname_depth(node) -> int:
    """How many ``os.path.dirname`` wrap ``__file__`` in this expression
    (through ``abspath`` / ``realpath``); -1 when it does not hold it."""
    if isinstance(node, ast.Name) and node.id == "__file__":
        return 0
    if isinstance(node, ast.Call) and node.args and \
            isinstance(node.func, ast.Attribute):
        inner = _dirname_depth(node.args[0])
        if inner >= 0:
            return inner + (node.func.attr == "dirname")
    return -1


def test_no_module_opens_a_path_above_the_package():
    above = set()
    for rel, tree in _modules():
        depth = rel.count(os.sep) + 1       # dirnames up to the package
        for node in ast.walk(tree):
            if _dirname_depth(node) > depth:
                above.add(rel)
            elif isinstance(node, ast.Attribute) and (
                    node.attr == "pardir"
                    or node.attr in ("parent", "parents") and any(
                        isinstance(n, ast.Name) and n.id == "__file__"
                        for n in ast.walk(node))):
                above.add(rel)
            elif isinstance(node, ast.Constant) and node.value == "..":
                above.add(rel)
    assert above == ABOVE_PACKAGE
