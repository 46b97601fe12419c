"""Packed sample cache: round-trip parity, resume determinism, error
paths, and the pack script's surface.

The contract under test (``data/packed.py``): packing a dataset and
reading it back through the mmap'd ``PackedDataset`` is invisible to
training — same batches, same order, same bits — while batch formation
drops the per-epoch decode entirely.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from distributed_deep_learning_tpu.data.datasets import synthetic_pcb
from distributed_deep_learning_tpu.data.loader import DeviceLoader
from distributed_deep_learning_tpu.data.packed import (PackedDataset,
                                                       PackedFormatError,
                                                       pack_dataset,
                                                       read_header)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def image_root(tmp_path_factory):
    from PIL import Image

    root = tmp_path_factory.mktemp("images")
    rng = np.random.default_rng(0)
    for cls, shade in (("cat", 60), ("dog", 180)):
        d = root / cls
        d.mkdir()
        for i in range(6):
            arr = np.full((20 + i, 24, 3), shade, np.uint8)
            arr += rng.integers(0, 20, arr.shape, dtype=np.uint8)
            Image.fromarray(arr).save(d / f"{i}.png")
    return str(root)


@pytest.fixture(scope="module")
def eager_ds(image_root):
    from distributed_deep_learning_tpu.data.imagefolder import (
        ImageFolderDataset)

    return ImageFolderDataset(image_root, image_size=8)


@pytest.fixture(scope="module")
def packed_path(eager_ds, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("cache") / "imgs.ddlpack")
    pack_dataset(eager_ds, path, chunk_size=5)  # chunk ∤ n: tail exercised
    return path


# --- round-trip parity ------------------------------------------------------

def test_imagefolder_roundtrip_bit_identical(eager_ds, packed_path):
    packed = PackedDataset(packed_path)
    assert len(packed) == len(eager_ds)
    assert packed.classes == eager_ds.classes
    idx = np.array([0, 11, 3, 7, 3])  # unordered + repeated
    xe, ye = eager_ds.batch(idx)
    xp, yp = packed.batch(idx)
    assert xp.dtype == xe.dtype
    np.testing.assert_array_equal(xp, xe)
    np.testing.assert_array_equal(yp, ye)


def test_array_dataset_roundtrip_bit_identical(tmp_path):
    ds = synthetic_pcb(n=40, seed=3)  # tabular/one-hot family
    path = str(tmp_path / "pcb.ddlpack")
    pack_dataset(ds, path)
    packed = PackedDataset(path)
    xe, ye = ds.batch(np.arange(40))
    xp, yp = packed.batch(np.arange(40))
    np.testing.assert_array_equal(xp, xe)
    np.testing.assert_array_equal(yp, ye)


def test_token_rows_keep_int_dtype(tmp_path):
    from distributed_deep_learning_tpu.data.datasets import ArrayDataset

    rng = np.random.default_rng(0)
    ds = ArrayDataset(rng.integers(0, 999, (30, 16)).astype(np.int32),
                      rng.integers(0, 999, (30, 16)).astype(np.int32))
    path = str(tmp_path / "tok.ddlpack")
    header = pack_dataset(ds, path)
    assert header["feature_dtype"] == "int32"  # ints never quantise to u8
    xp, yp = PackedDataset(path).batch(np.array([5, 2]))
    assert xp.dtype == np.int32 and yp.dtype == np.int32
    np.testing.assert_array_equal(xp, ds.features[[5, 2]])


def test_uint8_auto_storage_lossless(image_root, tmp_path):
    """Images decoded at native size are integral floats → stored uint8
    (4x smaller) yet read back bit-identical as float32."""
    from PIL import Image

    from distributed_deep_learning_tpu.data.imagefolder import (
        ImageFolderDataset)

    root = tmp_path / "native"
    for cls in ("a", "b"):
        (root / cls).mkdir(parents=True)
        rng = np.random.default_rng(7)
        for i in range(3):
            Image.fromarray(rng.integers(0, 255, (16, 16, 3),
                                         dtype=np.uint8)).save(
                root / cls / f"{i}.png")
    ds = ImageFolderDataset(str(root), image_size=16)  # identity resize
    path = str(tmp_path / "u8.ddlpack")
    header = pack_dataset(ds, path)
    assert header["feature_dtype"] == "uint8"
    assert header["feature_out_dtype"] == "float32"
    xe, _ = ds.batch(np.arange(6))
    xp, _ = PackedDataset(path).batch(np.arange(6))
    assert xp.dtype == np.float32
    np.testing.assert_array_equal(xp, xe)


def test_forced_uint8_rejects_lossy_samples(eager_ds, tmp_path):
    # 8px bilinear resize of 20-24px images produces fractional values
    with pytest.raises(ValueError, match="uint8-representable"):
        pack_dataset(eager_ds, str(tmp_path / "x.ddlpack"), dtype="uint8")


def test_pack_subset_indices(eager_ds, tmp_path):
    path = str(tmp_path / "sub.ddlpack")
    keep = np.array([2, 9, 4])
    pack_dataset(eager_ds, path, indices=keep)
    packed = PackedDataset(path)
    assert len(packed) == 3
    xe, _ = eager_ds.batch(keep)
    xp, _ = packed.batch(np.arange(3))
    np.testing.assert_array_equal(xp, xe)


# --- loader determinism / resume --------------------------------------------

def test_loader_batches_match_eager_path(eager_ds, packed_path, mesh8):
    """The full seeded DeviceLoader pipeline (epoch permutation + shard
    assembly + device_put) is bit-identical packed vs eager."""
    packed = PackedDataset(packed_path)
    n = (len(eager_ds) // 8) * 8
    le = DeviceLoader(eager_ds, np.arange(n), 8, mesh8, shuffle=True, seed=5)
    lp = DeviceLoader(packed, np.arange(n), 8, mesh8, shuffle=True, seed=5)
    le.set_epoch(2)
    lp.set_epoch(2)
    ae, ap = list(le), list(lp)
    assert len(ae) == len(ap) > 0
    for (xe, ye), (xp, yp) in zip(ae, ap):
        np.testing.assert_array_equal(np.asarray(xe), np.asarray(xp))
        np.testing.assert_array_equal(np.asarray(ye), np.asarray(yp))


def test_mid_epoch_skip_replays_exact_suffix(packed_path):
    """iter_batches(skip) — the loader-position-sidecar resume path — must
    replay the identical batch suffix on the packed loader."""
    import jax

    from distributed_deep_learning_tpu.runtime.mesh import build_mesh

    mesh2 = build_mesh({"data": 2}, jax.devices()[:2])
    packed = PackedDataset(packed_path)
    n = (len(packed) // 4) * 4
    loader = DeviceLoader(packed, np.arange(n), 4, mesh2, shuffle=True,
                          seed=11)
    loader.set_epoch(1)
    full = [(np.asarray(x), np.asarray(y)) for x, y in loader.iter_batches()]
    resumed = [(np.asarray(x), np.asarray(y))
               for x, y in loader.iter_batches(skip=1)]
    assert len(resumed) == len(full) - 1
    for (xf, yf), (xr, yr) in zip(full[1:], resumed):
        np.testing.assert_array_equal(xf, xr)
        np.testing.assert_array_equal(yf, yr)


def test_checkpoint_resume_through_packed_loader(tmp_path, monkeypatch):
    """Mid-epoch checkpoint resume (`--checkpoint-every` + the
    loader-position sidecar) stays deterministic with --packed-cache: the
    interrupted-and-resumed run's final params equal the uninterrupted
    run's, bit for bit.  (mlp keeps the e2e cheap; the loader mechanics
    are workload-independent.)"""
    import jax

    from distributed_deep_learning_tpu.data.datasets import synthetic_mqtt
    from distributed_deep_learning_tpu.utils.config import Config, Mode
    from distributed_deep_learning_tpu.workloads import get_spec, run_workload

    monkeypatch.delenv("DDL_INJECT_STEP_FAILURE", raising=False)
    cache = str(tmp_path / "mqtt.ddlpack")
    pack_dataset(synthetic_mqtt(n=64, seed=2), cache)

    def run(ckpt_dir=None, resume=False, every=0):
        config = Config(mode=Mode.SEQUENTIAL, packed_cache=cache,
                        batch_size=4, epochs=2, seed=9,
                        checkpoint_dir=ckpt_dir, resume=resume,
                        checkpoint_every=every)
        state, _ = run_workload(get_spec("mlp"), config)
        return state

    straight = run()
    ckpt = str(tmp_path / "ckpt")
    # save every step, then resume from a TRUNCATED copy of the directory
    run(ckpt_dir=ckpt, every=3)
    import glob

    steps = sorted(int(os.path.basename(p)) for p in glob.glob(
        os.path.join(ckpt, "[0-9]*")) if os.path.basename(p).isdigit())
    mid = [s for s in steps if s != max(steps)]
    assert mid, "need a mid-run checkpoint to resume from"
    cut = str(tmp_path / "cut")
    shutil.copytree(ckpt, cut)
    for s in steps:
        if s > mid[-1]:
            shutil.rmtree(os.path.join(cut, str(s)))
            extra = os.path.join(cut, f"extra-{s}.json")
            if os.path.exists(extra):
                os.remove(extra)
    resumed = run(ckpt_dir=cut, resume=True, every=3)
    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- error paths ------------------------------------------------------------

def test_bad_magic_rejected(tmp_path):
    path = tmp_path / "not.ddlpack"
    path.write_bytes(b"definitely not a packed cache, longer than header")
    with pytest.raises(PackedFormatError, match="magic"):
        PackedDataset(str(path))


def test_truncated_file_rejected(packed_path, tmp_path):
    cut = str(tmp_path / "trunc.ddlpack")
    shutil.copy(packed_path, cut)
    with open(cut, "r+b") as f:
        f.truncate(os.path.getsize(cut) - 64)
    with pytest.raises(PackedFormatError, match="truncated|bytes on disk"):
        PackedDataset(cut)


def test_version_mismatch_rejected(packed_path, tmp_path):
    fut = str(tmp_path / "v99.ddlpack")
    shutil.copy(packed_path, fut)
    with open(fut, "r+b") as f:
        f.seek(7)
        f.write(bytes([99]))
    with pytest.raises(PackedFormatError, match="version 99"):
        read_header(fut)


def test_empty_dataset_rejected(tmp_path):
    ds = synthetic_pcb(n=8)
    with pytest.raises(ValueError, match="empty"):
        pack_dataset(ds, str(tmp_path / "e.ddlpack"),
                     indices=np.array([], np.int64))


def test_missing_cache_flag_fails_loudly(tmp_path):
    from distributed_deep_learning_tpu.utils.config import Config
    from distributed_deep_learning_tpu.workloads import get_spec
    from distributed_deep_learning_tpu.workloads.base import _build_dataset

    config = Config(packed_cache=str(tmp_path / "missing.ddlpack"))
    with pytest.raises(FileNotFoundError):
        _build_dataset(get_spec("resnet"), config)


# --- config / workload wiring ----------------------------------------------

def test_cli_parses_packed_cache():
    from distributed_deep_learning_tpu.utils.config import parse_args

    c = parse_args(["--packed-cache", "/tmp/c.ddlpack"], workload="resnet")
    assert c.packed_cache == "/tmp/c.ddlpack"
    assert parse_args([], workload="resnet").packed_cache is None


def test_resnet_geometry_from_packed_cache(packed_path):
    """Head width and stem choice come from the cache's stored metadata,
    not from flags that described the original tree."""
    from distributed_deep_learning_tpu.utils.config import Config
    from distributed_deep_learning_tpu.workloads.northstar import (
        _resnet_model)

    packed = PackedDataset(packed_path)
    model = _resnet_model(Config(packed_cache=packed_path, size=18), packed)
    assert model.num_classes == 2
    assert model.small_inputs  # 8px samples → CIFAR stem


# --- script smokes (tier-1: the tools must not rot) -------------------------

def _run_script(name, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", name), *args],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)


def test_pack_dataset_script_smoke(image_root, tmp_path):
    out = str(tmp_path / "cli.ddlpack")
    proc = _run_script("pack_dataset.py", "--workload", "resnet",
                       "--data-dir", image_root, "--image-size", "8",
                       "--out", out, "--limit", "6")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["num_samples"] == 6
    assert os.path.getsize(out) == line["bytes"]
    assert len(PackedDataset(out)) == 6

