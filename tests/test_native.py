"""Native C++ data library vs its NumPy fallbacks (identical semantics)."""

import numpy as np
import pytest

from distributed_deep_learning_tpu import native


@pytest.fixture(scope="module")
def lib_available():
    if not native.available():
        pytest.skip("native library could not be built (no g++?)")
    return True


def test_build_succeeds(lib_available):
    assert native.get_lib() is not None


def test_gather_rows_matches_numpy(lib_available):
    rng = np.random.default_rng(0)
    data = rng.standard_normal((1000, 37), dtype=np.float32)
    idx = rng.integers(0, 1000, size=256)
    np.testing.assert_array_equal(native.gather_rows(data, idx), data[idx])


def test_take_nd(lib_available):
    rng = np.random.default_rng(1)
    imgs = rng.standard_normal((50, 8, 8, 3), dtype=np.float32)
    idx = rng.integers(0, 50, size=16)
    np.testing.assert_array_equal(native.take(imgs, idx), imgs[idx])


def test_window_gather_matches_numpy(lib_available):
    rng = np.random.default_rng(2)
    data = rng.standard_normal((500, 12), dtype=np.float32)
    pos = rng.integers(9, 500, size=64)
    got = native.window_gather(data, pos, history=10)
    offsets = np.arange(-9, 1)
    expected = data[pos[:, None] + offsets]
    np.testing.assert_array_equal(got, expected)
    assert got.shape == (64, 10, 12)


def test_csv_roundtrip(tmp_path, lib_available):
    rng = np.random.default_rng(3)
    data = rng.standard_normal((200, 7)).astype(np.float32)
    path = tmp_path / "t.csv"
    header = ",".join(f"c{i}" for i in range(7))
    np.savetxt(path, data, delimiter=",", header=header, comments="",
               fmt="%.9g")
    got = native.read_csv(str(path), skip_header=True)
    np.testing.assert_allclose(got, data, rtol=1e-6)


def test_csv_drop_first_col(tmp_path, lib_available):
    data = np.arange(12, dtype=np.float32).reshape(4, 3)
    path = tmp_path / "d.csv"
    np.savetxt(path, data, delimiter=",", header="a,b,c", comments="",
               fmt="%.9g")
    got = native.read_csv(str(path), skip_header=True, drop_first_col=True)
    np.testing.assert_allclose(got, data[:, 1:])


def test_csv_missing_file_raises(lib_available):
    with pytest.raises(FileNotFoundError):
        native.read_csv("/nonexistent/file.csv")


def test_crop_resize_matches_numpy_fallback(lib_available):
    rng = np.random.default_rng(4)
    img = rng.standard_normal((48, 40, 3)).astype(np.float32)
    got = native.crop_resize_bilinear(img, 4, 6, 32, 24, 16, 16)
    expected = native._crop_resize_numpy(img, 4, 6, 32, 24, 16, 16)
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)
    assert got.shape == (16, 16, 3)


def test_crop_resize_identity(lib_available):
    rng = np.random.default_rng(5)
    img = rng.standard_normal((16, 16, 3)).astype(np.float32)
    got = native.crop_resize_bilinear(img, 0, 0, 16, 16, 16, 16)
    np.testing.assert_allclose(got, img, rtol=1e-6, atol=1e-6)


def test_dataset_batch_uses_native(lib_available):
    from distributed_deep_learning_tpu.data.datasets import synthetic_mqtt

    ds = synthetic_mqtt(256)
    idx = np.arange(0, 64)
    x, y = ds.batch(idx)
    np.testing.assert_array_equal(x, ds.features[idx])
    np.testing.assert_array_equal(y, ds.targets[idx])


def test_pdm_windows_native_vs_fallback(monkeypatch):
    from distributed_deep_learning_tpu.data.datasets import synthetic_pdm

    ds = synthetic_pdm(512)
    idx = np.arange(0, 128, 3)
    x_native, y_native = ds.batch(idx)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)  # force fallback path
    x_np, y_np = ds.batch(idx)
    np.testing.assert_array_equal(x_native, x_np)
    np.testing.assert_array_equal(y_native, y_np)


def test_prefetch_loader_yields_same_batches(mesh8):
    from distributed_deep_learning_tpu.data.datasets import synthetic_mqtt
    from distributed_deep_learning_tpu.data.loader import (DeviceLoader,
                                                           PrefetchLoader)

    ds = synthetic_mqtt(512)
    base = DeviceLoader(ds, np.arange(256), 64, mesh8, shuffle=True, seed=3)
    direct = [(np.asarray(x), np.asarray(y)) for x, y in base]
    prefetched = [(np.asarray(x), np.asarray(y))
                  for x, y in PrefetchLoader(base)]
    assert len(direct) == len(prefetched) == 4
    for (x1, y1), (x2, y2) in zip(direct, prefetched):
        np.testing.assert_array_equal(x1, x2)
        np.testing.assert_array_equal(y1, y2)


def test_csv_blank_lines_do_not_shift_rows(tmp_path, lib_available):
    """Blank/whitespace lines are skipped (genfromtxt parity), not parsed
    as zero rows that shift everything after them."""
    path = tmp_path / "blank.csv"
    path.write_text("h1,h2\n1,2\n\n   \n3,4\n\n5,6\n")
    got = native.read_csv(str(path), skip_header=True)
    np.testing.assert_array_equal(got, [[1, 2], [3, 4], [5, 6]])


def test_csv_short_row_does_not_consume_next_row(tmp_path, lib_available):
    """A row with missing trailing fields parses to zeros for the missing
    columns; strtof must not skip the newline into the next row."""
    path = tmp_path / "short.csv"
    path.write_text("h1,h2,h3\n1,2,3\n4,\n7,8,9\n")
    got = native.read_csv(str(path), skip_header=True)
    np.testing.assert_array_equal(got, [[1, 2, 3], [4, 0, 0], [7, 8, 9]])


def test_csv_nan_parity_with_fallback(tmp_path, lib_available):
    """Literal nan fields become 0.0 on BOTH paths (the fallback applies
    np.nan_to_num; the native parser must match)."""
    path = tmp_path / "nan.csv"
    path.write_text("h1,h2\n1,nan\nNaN,4\n")
    got = native.read_csv(str(path), skip_header=True)
    np.testing.assert_array_equal(got, [[1, 0], [0, 4]])
    assert np.isfinite(got).all()


def test_csv_empty_mid_field(tmp_path, lib_available):
    path = tmp_path / "mid.csv"
    path.write_text("h1,h2,h3\n1,,3\n,5,\n")
    got = native.read_csv(str(path), skip_header=True)
    np.testing.assert_array_equal(got, [[1, 0, 3], [0, 5, 0]])


def test_csv_leading_blank_line_column_count(tmp_path, lib_available):
    """Columns derive from the first NON-blank data line (a leading blank
    would otherwise report cols=1 and mangle the file)."""
    path = tmp_path / "lead.csv"
    path.write_text("h1,h2\n\n1,2\n3,4\n")
    got = native.read_csv(str(path), skip_header=True)
    np.testing.assert_array_equal(got, [[1, 2], [3, 4]])


def test_library_is_named_by_its_source_and_a_stray_one_is_ignored(
        tmp_path, monkeypatch, lib_available):
    """The binary git does not track is loaded only if it was built from
    THIS source: its name carries the source's hash, so a stale library
    left in the tree (whatever its mtime) is never picked up, and an
    absent one is rebuilt."""
    import hashlib
    import shutil

    src = tmp_path / "ddl_native.cpp"
    shutil.copy(native._SRC, src)
    stray = tmp_path / "libddl_native.so"
    stray.write_bytes(b"not a library")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_status", "not-loaded")
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    assert native.status() == "built"
    assert (tmp_path / f"libddl_native_{digest}.so").exists()
    assert stray.read_bytes() == b"not a library"


def test_status_reports_the_fallback_and_why(monkeypatch):
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_status", "not-loaded")
    monkeypatch.setenv("DDL_DISABLE_NATIVE", "1")
    assert native.status() == "numpy-fallback (DDL_DISABLE_NATIVE=1)"
    assert not native.available()
