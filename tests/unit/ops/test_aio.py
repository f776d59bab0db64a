"""AIO handle + sweep tests — analog of reference ``tests/unit/ops/aio/``
and the ``csrc/aio/py_test`` validation suite."""

import os

import numpy as np
import pytest

from deepspeed_tpu.ops.aio import AioHandle, aio_available, aligned_array
from deepspeed_tpu.ops.aio.sweep import sweep, sync_baseline, validate

pytestmark = pytest.mark.skipif(not aio_available(),
                                reason="aio lib unavailable")


def test_roundtrip_basic(tmp_path):
    h = AioHandle(num_threads=2)
    data = np.random.default_rng(0).integers(0, 255, 1 << 20, dtype=np.uint8)
    path = str(tmp_path / "x.bin")
    h.async_pwrite(data, path)
    h.wait()
    out = np.empty_like(data)
    h.async_pread(out, path)
    h.wait()
    np.testing.assert_array_equal(out, data)
    h.close()


def test_offsets_and_partial_reads(tmp_path):
    h = AioHandle(num_threads=2, block_size=64 * 1024)
    data = np.arange(1 << 18, dtype=np.uint8)
    path = str(tmp_path / "x.bin")
    h.async_pwrite(data, path)
    h.wait()
    # read a window at a non-zero, non-aligned offset
    out = np.empty(1000, np.uint8)
    h.async_pread(out, path, offset=12345)
    h.wait()
    np.testing.assert_array_equal(out, data[12345:13345])
    # write a window back at an offset
    h.async_pwrite(np.full(1000, 7, np.uint8), path, offset=500)
    h.wait()
    full = np.fromfile(path, np.uint8)
    assert (full[500:1500] == 7).all()
    assert full[499] == data[499]
    h.close()


def test_block_splitting_many_chunks(tmp_path):
    # tiny block size → many chunks across threads; content must be exact
    h = AioHandle(num_threads=4, block_size=4096, queue_depth=8)
    data = np.random.default_rng(1).integers(0, 255, (1 << 20) + 777,
                                             dtype=np.uint8)
    path = str(tmp_path / "x.bin")
    h.async_pwrite(data, path)
    h.wait()
    out = np.empty_like(data)
    h.async_pread(out, path)
    h.wait()
    np.testing.assert_array_equal(out, data)
    h.close()


def test_o_direct_roundtrip(tmp_path):
    h = AioHandle(num_threads=2, block_size=64 * 1024, o_direct=True)
    data = aligned_array(1 << 20)
    data[:] = np.random.default_rng(2).integers(0, 255, data.size,
                                                dtype=np.uint8)
    path = str(tmp_path / "x.bin")
    h.async_pwrite(data, path)
    h.wait()
    out = aligned_array(data.size)
    h.async_pread(out, path)
    h.wait()
    np.testing.assert_array_equal(out, data)
    # unaligned tail falls back to the buffered fd — still exact
    odd = np.empty(4096 + 123, np.uint8)
    h.async_pread(odd, path, offset=1)
    h.wait()
    np.testing.assert_array_equal(odd, np.asarray(data)[1:1 + odd.size])
    h.close()


def test_wait_reports_failures(tmp_path):
    h = AioHandle(num_threads=1)
    out = np.empty(128, np.uint8)
    h.async_pread(out, str(tmp_path / "does_not_exist.bin"))
    with pytest.raises(IOError):
        h.wait()
    h.close()


def test_aligned_array_alignment():
    for n in (1, 100, 4096, 123457):
        a = aligned_array(n)
        assert a.ctypes.data % 4096 == 0
        assert a.nbytes == n


def test_validate_grid(tmp_path):
    assert validate(dir=str(tmp_path), nbytes=1 << 20)


def test_sweep_structure_and_sanity(tmp_path):
    """The sweep produces measured bandwidths per config. The async>sync
    claim itself comes from a full-size run of rounds 1-5 (buffered ~3x,
    O_DIRECT ~2x: a lead, PERF.md §8); a strict >1x assertion here would be a timing race
    on small files / loaded CI hosts, so only sanity is asserted."""
    out = sweep(file_mb=64, dir=str(tmp_path),
                block_sizes=(1 << 20, 8 << 20), threads=(2, 4))
    assert out["baseline_gbps"] > 0
    assert len(out["results"]) == 4
    assert out["best"]["read_gbps"] > 0
    assert out["results"] == sorted(out["results"],
                                    key=lambda r: -r["read_gbps"])
    # best multi-threaded chunked read should not be dramatically SLOWER
    # than sync (that would indicate a scheduling bug, not host noise)
    assert out["best"]["speedup_vs_sync"] > 0.5, out
