"""Native C++ data-pipeline tests: correctness vs numpy, determinism, epoch
reshuffling, prefetch ordering under many workers."""

import numpy as np
import pytest

from accelerate_tpu.native import (
    NativeDataLoader,
    TokenDataset,
    gather_rows,
    is_native_available,
    parallel_collate,
)


@pytest.mark.smoke
def test_native_builds():
    # the build toolchain exists in CI/dev images; if this fails the fallback
    # path still works but we want to know
    assert is_native_available()


def test_rebuild_is_keyed_on_source_content_not_mtimes(monkeypatch, tmp_path):
    """A copied or freshly checked-out tree has no mtimes worth trusting: the
    library is rebuilt when the sources' content (or the flags) changed, and
    only then."""
    import os

    from accelerate_tpu.native import build

    assert is_native_available()  # built, and stamped with the sources' digest
    digest = build._source_digest()
    assert not build._needs_build(digest)
    src = build._sources()[0]
    stat = os.stat(src)
    try:
        os.utime(src, (stat.st_atime, os.path.getmtime(build._LIB) + 3600))  # "newer"
        assert not build._needs_build(build._source_digest())
    finally:
        os.utime(src, (stat.st_atime, stat.st_mtime))
    edited = tmp_path / "src"
    edited.mkdir()
    for path in build._sources():
        (edited / os.path.basename(path)).write_bytes(open(path, "rb").read())
    (edited / os.path.basename(src)).write_bytes(open(src, "rb").read() + b"\n// edited\n")
    monkeypatch.setattr(build, "_SRC_DIR", str(edited))
    assert build._source_digest() != digest
    assert build._needs_build(build._source_digest())


def test_parallel_collate_matches_stack():
    rng = np.random.default_rng(0)
    samples = [rng.normal(size=(128, 64)).astype(np.float32) for _ in range(32)]
    out = parallel_collate(samples)
    np.testing.assert_array_equal(out, np.stack(samples))
    assert out.dtype == np.float32


def test_parallel_collate_large_uses_threads():
    samples = [np.full((512, 512), i, np.float32) for i in range(16)]  # 16 MB
    out = parallel_collate(samples, num_threads=4)
    np.testing.assert_array_equal(out, np.stack(samples))


def test_parallel_collate_ragged_falls_back():
    samples = [np.zeros((3,)), np.zeros((3,))]
    out = parallel_collate(samples)
    assert out.shape == (2, 3)


def test_gather_rows():
    src = np.arange(1000, dtype=np.int64).reshape(100, 10)
    idx = np.asarray([5, 1, 99, 0, 5])
    np.testing.assert_array_equal(gather_rows(src, idx), src[idx])


@pytest.fixture
def token_file(tmp_path):
    rng = np.random.default_rng(42)
    tokens = rng.integers(0, 50000, size=(257 * 128,), dtype=np.uint16)
    path = tmp_path / "shard.bin"
    tokens.tofile(path)
    return str(path), tokens.reshape(257, 128)  # 257 records of seq 128


def test_token_dataset(token_file):
    path, ref = token_file
    ds = TokenDataset(path, seq_len=128)
    assert len(ds) == 257
    np.testing.assert_array_equal(ds[0], ref[0])
    np.testing.assert_array_equal(ds[256], ref[256])
    ds.close()


def test_loader_sequential(token_file):
    path, ref = token_file
    ds = TokenDataset(path, seq_len=128)
    dl = NativeDataLoader(ds, batch_size=32, shuffle=False, drop_last=True,
                          num_workers=4)
    assert len(dl) == 8
    batches = list(dl)
    assert len(batches) == 8
    got = np.concatenate(batches)
    np.testing.assert_array_equal(got, ref[:256])
    dl.close()
    ds.close()


def test_loader_shuffle_is_permutation_and_deterministic(tmp_path):
    # 256 records exactly: drop_last drops nothing, so epochs are permutations
    # of each other (257 would drop a different record each epoch)
    rng = np.random.default_rng(42)
    tokens = rng.integers(0, 50000, size=(256 * 128,), dtype=np.uint16)
    path = str(tmp_path / "even.bin")
    tokens.tofile(path)
    ref = tokens.reshape(256, 128)
    ds = TokenDataset(path, seq_len=128)
    dl1 = NativeDataLoader(ds, batch_size=16, shuffle=True, seed=7, drop_last=True,
                           num_workers=4)
    ep1 = np.concatenate(list(dl1))
    # same seed → identical epoch-0 order
    dl2 = NativeDataLoader(ds, batch_size=16, shuffle=True, seed=7, drop_last=True,
                           num_workers=2)
    np.testing.assert_array_equal(ep1, np.concatenate(list(dl2)))
    # all rows come from the dataset, no duplicates within the epoch
    seen = {r.tobytes() for r in ep1}
    all_rows = {r.tobytes() for r in ref}
    assert seen <= all_rows
    assert len(seen) == ep1.shape[0]  # rows are unique with high probability
    # epoch 1 reshuffles
    ep1b = np.concatenate(list(dl1))
    assert not np.array_equal(ep1, ep1b)
    np.testing.assert_array_equal(np.sort(ep1.reshape(-1)), np.sort(ep1b.reshape(-1)))
    dl1.close()
    dl2.close()
    ds.close()


def test_loader_wraparound_no_drop_last(token_file):
    path, ref = token_file
    ds = TokenDataset(path, seq_len=128)
    dl = NativeDataLoader(ds, batch_size=100, shuffle=False, drop_last=False,
                          num_workers=3)
    batches = list(dl)
    assert len(batches) == 3
    assert all(b.shape == (100, 128) for b in batches)
    # final batch wraps to the start (even_batches semantics)
    np.testing.assert_array_equal(batches[2][57:], ref[: 100 - 57])
    dl.close()
    ds.close()


def test_loader_many_workers_small_window(token_file):
    """Reorder-window stress: more workers than prefetch depth must not deadlock."""
    path, ref = token_file
    ds = TokenDataset(path, seq_len=128)
    dl = NativeDataLoader(ds, batch_size=8, shuffle=False, drop_last=True,
                          num_workers=8, prefetch_depth=2)
    got = np.concatenate(list(dl))
    np.testing.assert_array_equal(got, ref[: got.shape[0]])
    dl.close()
    ds.close()


def test_default_collate_uses_native_path():
    from accelerate_tpu.data_loader import default_collate

    samples = [{"x": np.full((600, 600), i, np.float32)} for i in range(4)]  # >1MB
    out = default_collate(samples)
    np.testing.assert_array_equal(out["x"][2], samples[2]["x"])


def test_parallel_collate_mixed_dtypes_promotes():
    out = parallel_collate([np.zeros(4, np.int64), np.full(4, 2.9)])
    np.testing.assert_allclose(out[1], 2.9)  # np.stack promotion, no truncation
    out2 = parallel_collate([np.zeros(4, np.float32), np.zeros(4, np.float64)])
    assert out2.dtype == np.float64


def test_gather_rows_bounds_and_negatives():
    src = np.arange(20.0).reshape(4, 5)
    np.testing.assert_array_equal(gather_rows(src, np.asarray([-1])), src[[-1]])
    with pytest.raises(IndexError):
        gather_rows(src, np.asarray([4]))
    assert gather_rows(src, np.asarray([], dtype=np.int64)).shape == (0, 5)


def test_loader_partial_iteration_restarts_epoch(token_file):
    path, ref = token_file
    ds = TokenDataset(path, seq_len=128)
    dl = NativeDataLoader(ds, batch_size=32, shuffle=False, drop_last=True,
                          num_workers=4)
    first = next(iter(dl))  # peek and abandon mid-epoch
    np.testing.assert_array_equal(first, ref[:32])
    batches = list(dl)  # must be a FULL epoch, not the leftover 7 batches
    assert len(batches) == 8
    np.testing.assert_array_equal(np.concatenate(batches), ref[:256])
    dl.close()
    ds.close()


class TestChunkIO:
    """Native checkpoint IO engine (src/io.cc via native/io.py)."""

    def _arrays(self):
        rng = np.random.default_rng(7)
        return [
            rng.standard_normal((64, 33)).astype(np.float32),
            np.arange(17, dtype=np.int64),
            rng.integers(0, 255, (5, 5, 5), dtype=np.uint8),
        ]

    def test_roundtrip_and_alignment(self, tmp_path):
        from accelerate_tpu.native import io as nio

        arrays = self._arrays()
        p = str(tmp_path / "c.bin")
        offs, sizes, crcs = nio.write_chunks(p, arrays)
        assert all(o % nio.ALIGN == 0 for o in offs)
        bufs = nio.read_chunks(p, offs, sizes, crcs)
        for a, b in zip(arrays, bufs):
            np.testing.assert_array_equal(np.frombuffer(b, a.dtype).reshape(a.shape), a)

    def test_crc_detects_corruption(self, tmp_path):
        from accelerate_tpu.native import io as nio

        arrays = self._arrays()
        p = str(tmp_path / "c.bin")
        offs, sizes, crcs = nio.write_chunks(p, arrays)
        with open(p, "r+b") as f:
            f.seek(offs[1] + 3)
            f.write(b"\xab")
        with pytest.raises(ValueError, match="CRC mismatch"):
            nio.read_chunks(p, offs, sizes, crcs)
        # without crcs the (corrupt) read still succeeds — caller's choice
        nio.read_chunks(p, offs, sizes, None)

    def test_python_fallback_writes_identical_format(self, tmp_path, monkeypatch):
        from accelerate_tpu.native import io as nio

        arrays = self._arrays()
        p_native = str(tmp_path / "n.bin")
        res_native = nio.write_chunks(p_native, arrays)
        monkeypatch.setattr(nio, "_lib", lambda: None)
        p_py = str(tmp_path / "p.bin")
        res_py = nio.write_chunks(p_py, arrays)
        assert res_native == res_py
        with open(p_native, "rb") as a, open(p_py, "rb") as b:
            assert a.read() == b.read()
        # cross-read: python-written file through python reader with native crcs
        bufs = nio.read_chunks(p_py, *res_py)
        for a, b in zip(arrays, bufs):
            np.testing.assert_array_equal(np.frombuffer(b, a.dtype).reshape(a.shape), a)
