"""Sharded packing engine: bit-exact equivalence with the reference
single-process path, bounded-RSS streaming, and worker failure handling
(ops/packing.py)."""

import re

import numpy as np
import pytest

from oryx_tpu.ops import als as als_ops
from oryx_tpu.ops import packing


def _assert_identical(ref, got):
    assert len(ref) == len(got)
    for rb, gb in zip(ref, got):
        assert rb.chunk == gb.chunk
        assert rb.rows.dtype == gb.rows.dtype
        assert rb.idx.dtype == gb.idx.dtype
        assert rb.val.dtype == gb.val.dtype
        assert rb.deg.dtype == gb.deg.dtype
        np.testing.assert_array_equal(rb.rows, gb.rows)
        np.testing.assert_array_equal(rb.idx, gb.idx)
        np.testing.assert_array_equal(rb.val, gb.val)
        np.testing.assert_array_equal(rb.deg, gb.deg)


def _both_orientations(u, i, v, num_users, num_items, num_shards, options):
    """Pack X-solve (user rows) and Y-solve (item rows) orientations,
    exactly as train_als does, and check both against the reference."""
    for rows, cols, nr in ((u, i, num_users), (i, u, num_items)):
        ref = packing.build_neighbor_buckets_reference(
            rows, cols, v, nr, num_shards=num_shards
        )
        got = packing.pack_neighbor_buckets(
            rows, cols, v, nr, num_shards=num_shards, options=options
        )
        _assert_identical(ref, got)


@pytest.mark.parametrize("workers", [1, 2, 8])
@pytest.mark.parametrize("num_shards", [1, 4, 3])
def test_equivalence_power_law(workers, num_shards):
    gen = np.random.default_rng(42)
    num_users, num_items, nnz = 20_000, 900, 120_000
    w = (1.0 / (np.arange(num_users) + 5.0)) ** 0.9
    u = gen.choice(num_users, size=nnz, p=w / w.sum()).astype(np.int32)
    i = gen.integers(0, num_items, nnz).astype(np.int32)
    v = gen.random(nnz).astype(np.float32)
    opts = packing.PackingOptions(workers=workers, chunk_rows=10_000)
    _both_orientations(u, i, v, num_users, num_items, num_shards, opts)


@pytest.mark.parametrize("workers", [1, 2, 8])
def test_equivalence_adversarial_duplicates(workers):
    """Duplicate (row, col) pairs with distinct values, rows straddling
    radix-block boundaries, and interleaved arrival order: layout must
    keep the reference's arrival-order tie-breaks byte for byte."""
    gen = np.random.default_rng(7)
    num_users = 70_000  # > one 65536-row radix block
    hot = np.array([0, 1, 65535, 65536, 65537, 69_999], dtype=np.int32)
    u = np.concatenate([
        np.tile(hot, 4_000),                # interleaved duplicates
        gen.integers(0, num_users, 30_000, dtype=np.int32),
        np.repeat(hot, 100),                # runs of the same row
    ])
    nnz = len(u)
    i = np.tile(np.array([3, 3, 1, 0, 2], dtype=np.int32), nnz // 5 + 1)[:nnz]
    v = np.arange(nnz, dtype=np.float32)  # every value distinct -> order shows
    opts = packing.PackingOptions(workers=workers, chunk_rows=7_777)
    _both_orientations(u, i, v, num_users, 4, 2, opts)


@pytest.mark.parametrize("workers", [2, 8])
def test_equivalence_empty_shards(workers):
    """Entries only at the extremes of the row space: middle workers get
    ranges with zero entries and must contribute nothing."""
    gen = np.random.default_rng(11)
    num_users = 100_000
    lo = gen.integers(0, 50, 5_000, dtype=np.int32)
    hi = gen.integers(num_users - 50, num_users, 5_000, dtype=np.int32)
    u = np.concatenate([lo, hi])
    gen.shuffle(u)
    i = gen.integers(0, 300, len(u), dtype=np.int32)
    v = gen.random(len(u)).astype(np.float32)
    opts = packing.PackingOptions(workers=workers, chunk_rows=1_000)
    _both_orientations(u, i, v, num_users, 300, 4, opts)


def test_empty_inputs():
    opts = packing.PackingOptions(workers=4)
    assert packing.pack_neighbor_buckets(
        np.empty(0, np.int32), np.empty(0, np.int32), np.empty(0, np.float32),
        10, options=opts,
    ) == []
    assert packing.pack_neighbor_buckets(
        np.empty(0, np.int32), np.empty(0, np.int32), np.empty(0, np.float32),
        0, options=opts,
    ) == []


def test_build_neighbor_buckets_delegates_identically():
    """als.build_neighbor_buckets (no options) must match the reference:
    existing equivalence/zero-recompile tests key on this layout."""
    gen = np.random.default_rng(5)
    u = gen.integers(0, 5_000, 40_000, dtype=np.int32)
    i = gen.integers(0, 800, 40_000, dtype=np.int32)
    v = gen.random(40_000).astype(np.float32)
    ref = packing.build_neighbor_buckets_reference(u, i, v, 5_000, num_shards=4)
    got = als_ops.build_neighbor_buckets(u, i, v, 5_000, num_shards=4)
    _assert_identical(ref, got)


def test_shm_budget_falls_back_to_serial(caplog):
    gen = np.random.default_rng(9)
    u = gen.integers(0, 2_000, 30_000, dtype=np.int32)
    i = gen.integers(0, 500, 30_000, dtype=np.int32)
    v = gen.random(30_000).astype(np.float32)
    ref = packing.build_neighbor_buckets_reference(u, i, v, 2_000)
    with caplog.at_level("WARNING", logger="oryx_tpu.ops.packing"):
        got = packing.pack_neighbor_buckets(
            u, i, v, 2_000,
            options=packing.PackingOptions(workers=4, shm_budget_mb=0),
        )
    _assert_identical(ref, got)
    assert packing.last_pack_stats["workers"] == 1.0
    assert any("budget" in r.message for r in caplog.records)


def test_worker_crash_surfaces_clean_error(monkeypatch):
    """One worker dying must terminate the pool and raise a RuntimeError
    naming the shard — not hang the parent or return partial buckets."""
    real = packing._pack_range

    def bomb(row_idx, col_idx, values, lo, hi, *args, **kwargs):
        if lo > 0:
            raise RuntimeError("injected worker failure")
        return real(row_idx, col_idx, values, lo, hi, *args, **kwargs)

    monkeypatch.setattr(packing, "_pack_range", bomb)
    gen = np.random.default_rng(13)
    u = gen.integers(0, 10_000, 50_000, dtype=np.int32)
    i = gen.integers(0, 100, 50_000, dtype=np.int32)
    v = gen.random(50_000).astype(np.float32)
    with pytest.raises(RuntimeError, match=r"packing worker \d+ \(rows \["):
        packing.pack_neighbor_buckets(
            u, i, v, 10_000,
            options=packing.PackingOptions(workers=2, worker_timeout_sec=120.0),
        )


def test_bounded_rss_streaming_5m():
    """Streaming guard: packing 5M ratings with small chunks must not
    grow the process high-water mark by more than a small multiple of
    the working set (inputs 60 MB; bound covers outputs + bounded
    temporaries, and would fail if packing re-materialized several
    unchunked nnz-length int64 temporaries at once)."""

    def hwm_kb():
        with open("/proc/self/status") as f:
            return int(re.search(r"VmHWM:\s+(\d+) kB", f.read()).group(1))

    nnz, num_users = 5_000_000, 250_000
    gen = np.random.default_rng(21)
    w = (1.0 / (np.arange(num_users) + 10.0)) ** 0.8
    u = gen.choice(num_users, size=nnz, p=w / w.sum()).astype(np.int32)
    i = gen.integers(0, 50_000, nnz).astype(np.int32)
    v = gen.random(nnz).astype(np.float32)
    before = hwm_kb()
    buckets = packing.pack_neighbor_buckets(
        u, i, v, num_users,
        options=packing.PackingOptions(workers=1, chunk_rows=500_000),
    )
    grew_mb = (hwm_kb() - before) / 1024.0
    assert buckets, "expected non-empty buckets"
    padded = sum(b.num_slots * b.width for b in buckets)
    outputs_mb = padded * 8 / 1e6
    # inputs (60 MB) are excluded from the delta (allocated before the
    # baseline); allow outputs + ~36 bytes/entry of transient state
    assert grew_mb < outputs_mb + 36 * nnz / 1e6, (
        f"packing RSS grew {grew_mb:.0f} MB "
        f"(outputs {outputs_mb:.0f} MB) — streaming bound broken"
    )


def test_chunk_bounds_the_normal_equations_too():
    """At 250 features a narrow bucket's chunk used to be sized by its
    [chunk, 8, 250] gather workspace alone (65536 rows): the [chunk, 250,
    250] f32 systems it reduces to are then 16 GB, past one chip's HBM.
    Both workspaces stay under the budget; wide buckets are unchanged."""
    from oryx_tpu.ops.packing import bucket_geometry

    budget = 1 << 27
    for width in (8, 64, 256, 4096):
        n, chunk = bucket_geometry(1_000_000, width, 1, budget, 250, True)
        assert chunk * width * 250 <= budget
        assert chunk * 250 * 250 <= budget
        assert n % chunk == 0
    # width >= features: the gather workspace was, and is, the bound
    assert bucket_geometry(1_000_000, 4096, 1, budget, 250, True)[1] == 128
    assert bucket_geometry(1_000_000, 8, 1, budget, 250, True)[1] == 2048
