"""Native C++ host-data-path library: build, correctness vs numpy, and the
fetch_rows integration (native/gather.cpp via utils/native.py)."""

import numpy as np
import pytest

from neuroimagedisttraining_tpu.utils import native


@pytest.fixture(scope="module")
def lib():
    handle = native.load()
    if handle is None:
        pytest.skip("g++ unavailable: native library could not be built")
    return handle


def test_gather_rows_matches_numpy(lib):
    rng = np.random.default_rng(0)
    src = rng.integers(0, 256, size=(64, 7, 9), dtype=np.uint8)
    idx = rng.integers(0, 64, size=50)
    got = native.gather_rows(src, idx)
    np.testing.assert_array_equal(got, src[idx])


def test_gather_rows_into_preallocated(lib):
    rng = np.random.default_rng(1)
    src = rng.integers(0, 256, size=(32, 5), dtype=np.uint8)
    idx = np.asarray([3, 3, 0, 31])
    out = np.zeros((10, 5), np.uint8)
    res = native.gather_rows(src, idx, out=out)
    assert res is out
    np.testing.assert_array_equal(out[:4], src[idx])
    np.testing.assert_array_equal(out[4:], 0)


def test_numpy_fallback_for_non_u8():
    src = np.random.default_rng(3).normal(size=(8, 4)).astype(np.float32)
    idx = np.asarray([1, 5, 5])
    np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])


def test_fetch_rows_uses_native_path():
    from neuroimagedisttraining_tpu.data.hdf5 import fetch_rows

    rng = np.random.default_rng(4)
    src = rng.integers(0, 256, size=(40, 6, 6), dtype=np.uint8)
    idx = np.asarray([7, 2, 2, 39, 0])
    np.testing.assert_array_equal(fetch_rows(src, idx), src[idx])


def test_failed_build_logs_gpp_stderr(tmp_path, monkeypatch, caplog):
    """A compiler failure must not be silent: the g++ stderr is logged at
    warning level so the numpy-fallback slow path is diagnosable."""
    import logging

    bad_src = tmp_path / "broken.cpp"
    bad_src.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", str(bad_src))
    monkeypatch.setattr(native, "_SO", str(tmp_path / "broken.so"))
    with caplog.at_level(logging.WARNING,
                         logger="neuroimagedisttraining_tpu.native"):
        assert native._build() is False
    assert any("native gather build failed" in r.message
               for r in caplog.records)
    # the g++ diagnostic itself (or, without a toolchain, the OSError)
    # made it into the log record
    assert any("error" in r.message.lower() or "No such file" in r.message
               for r in caplog.records)


def test_stale_library_is_rebuilt_whatever_its_mtime(tmp_path, monkeypatch,
                                                     lib):
    """Staleness is the recorded source hash, never a file time: a
    library that is NEWER than gather.cpp but was built from other
    source (what a tree copy can fake) is rebuilt; one whose record
    matches is loaded untouched."""
    import os
    import shutil

    src = tmp_path / "gather.cpp"
    shutil.copy(native._SRC, src)
    so = tmp_path / "libnidt_gather.so"
    so.write_bytes(b"not a shared object")          # newer than src
    os.utime(src, (1, 1))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_SO", str(so))
    monkeypatch.setattr(native, "_SO_SRC_HASH", str(so) + ".sha256")
    monkeypatch.setattr(native, "_lib", None)
    assert native.load() is not None                # rebuilt, loadable
    assert (tmp_path / "libnidt_gather.so.sha256").read_text().strip() \
        == native._src_hash()
    built = so.read_bytes()
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build",
                        lambda: pytest.fail("rebuilt a fresh library"))
    assert native.load() is not None
    assert so.read_bytes() == built
