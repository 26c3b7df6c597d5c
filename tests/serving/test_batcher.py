"""Micro-batcher: concurrent scoring calls coalesce into batched device
submits without changing any per-request answer."""

import threading

import numpy as np
import pytest

from oryx_tpu.ops import topn as topn_ops
from oryx_tpu.serving import batcher as batcher_mod
from oryx_tpu.serving.batcher import TopNBatcher


def _make(n=500, kf=8, seed=0):
    gen = np.random.default_rng(seed)
    y = gen.standard_normal((n, kf), dtype=np.float32)
    return y, topn_ops.upload(y, streaming=False)


def test_single_request_matches_direct_path():
    y, up = _make()
    b = TopNBatcher()
    try:
        q = np.arange(8, dtype=np.float32)
        idx, vals = b.score(up, q, 5)
        ridx, rvals = topn_ops.top_k_scores(up, q, 5)
        np.testing.assert_array_equal(idx, ridx)
        np.testing.assert_allclose(vals, rvals, atol=1e-5)
    finally:
        b.close()


def test_concurrent_requests_batch_and_stay_correct():
    y, up = _make(n=800, kf=12, seed=2)
    gen = np.random.default_rng(3)
    queries = gen.standard_normal((64, 12), dtype=np.float32)
    b = TopNBatcher(max_batch=16)
    results: dict[int, tuple] = {}
    errors: list[BaseException] = []

    def worker(j):
        try:
            results[j] = b.score(up, queries[j], 7, cosine=(j % 2 == 0))
        except BaseException as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(j,)) for j in range(64)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        b.close()
    assert not errors
    assert len(results) == 64
    for j, (idx, vals) in results.items():
        ridx, rvals = topn_ops.top_k_scores(up, queries[j], 7, cosine=(j % 2 == 0))
        np.testing.assert_array_equal(idx, ridx)
        np.testing.assert_allclose(vals, rvals, atol=1e-4)


def test_mixed_k_and_snapshots_group_safely():
    _, up_a = _make(n=300, kf=8, seed=5)
    _, up_b = _make(n=200, kf=8, seed=6)
    queries = np.random.default_rng(7).standard_normal((20, 8)).astype(np.float32)
    b = TopNBatcher()
    results = {}

    def worker(j, up, k):
        results[(j, k)] = b.score(up, queries[j], k)

    threads = [
        threading.Thread(target=worker, args=(j, up_a if j % 2 else up_b, 3 + j % 5))
        for j in range(20)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        b.close()
    for (j, k), (idx, vals) in results.items():
        assert len(idx) == k and len(vals) == k


def test_closed_batcher_raises_and_default_revives():
    b = batcher_mod.get_default_batcher()
    b.close()
    with pytest.raises(RuntimeError):
        b.score(None, np.zeros(4, np.float32), 1)
    b2 = batcher_mod.get_default_batcher()
    assert b2 is not b and not b2._closed
    b2.close()


def test_large_group_routes_through_fused_multi(monkeypatch):
    """Coalesced groups past MULTI_THRESHOLD take the fused multi-scan
    dispatch; answers stay identical to the direct path."""
    y, up = _make(n=600, kf=10, seed=5)
    calls = {"multi": 0, "single": 0}
    real_multi = topn_ops.submit_top_k_multi
    real_single = topn_ops.submit_top_k
    monkeypatch.setattr(
        batcher_mod.topn_ops, "submit_top_k_multi",
        lambda *a, **k: calls.__setitem__("multi", calls["multi"] + 1) or real_multi(*a, **k),
    )
    monkeypatch.setattr(
        batcher_mod.topn_ops, "submit_top_k",
        lambda *a, **k: calls.__setitem__("single", calls["single"] + 1) or real_single(*a, **k),
    )
    b = TopNBatcher()
    b.MULTI_THRESHOLD = 8  # force the multi path with a small fleet
    gen = np.random.default_rng(6)
    queries = gen.standard_normal((40, 10)).astype(np.float32)
    results = [None] * len(queries)
    # hold the dispatcher back so all 40 requests coalesce into one batch
    gate = threading.Event()
    orig_take = b._take_batch

    def gated_take():
        gate.wait(5)
        return orig_take()

    b._take_batch = gated_take
    try:
        def run(j):
            results[j] = b.score(up, queries[j], 4)

        threads = [threading.Thread(target=run, args=(j,)) for j in range(len(queries))]
        for t in threads:
            t.start()
        import time

        time.sleep(0.3)  # let every request enqueue
        gate.set()
        for t in threads:
            t.join(timeout=30)
        for j in range(len(queries)):
            ridx, rvals = topn_ops.top_k_scores(up, queries[j], 4)
            np.testing.assert_array_equal(results[j][0], ridx)
            np.testing.assert_allclose(results[j][1], rvals, atol=1e-5)
        assert calls["multi"] >= 1
    finally:
        b.close()


def _pass_record() -> dict:
    """What the batcher's pass instruments hold now (they live in the
    process-global registry, so tests read deltas)."""
    snap = batcher_mod._metrics.snapshot()
    name = "serving.batcher."
    return {
        "passes": snap[name + "passes"]["value"],
        "rows": snap[name + "pass.rows"]["value"],
        "padded": snap[name + "pass.padded-rows"]["value"],
        "waits": snap[name + "queue-wait.seconds"].get("count", 0),
        "pass_seconds": snap[name + "pass.seconds"].get("count", 0),
        "deliveries": snap[name + "deliver.seconds"].get("count", 0),
        "depth_sum": snap[name + "pass.inflight-depth-sum"]["value"],
    }


@pytest.mark.parametrize("indexed", [False, True], ids=["vectors", "indexed"])
def test_every_pass_is_on_the_record_and_the_counts_agree(indexed):
    """N requests through a batcher: one queue-wait observation a request,
    one pass.seconds / deliver.seconds observation a pass, rows = N, and the device is never given fewer rows than asked."""
    y, up = _make(n=400, kf=8, seed=11)
    queries = np.random.default_rng(12).standard_normal((48, 8)).astype(np.float32)
    x_dev = topn_ops.upload_queries(queries)
    b = TopNBatcher(max_batch=16)
    before = _pass_record()
    results = {}

    def worker(j):
        if indexed:
            results[j] = b.score_indexed(up, x_dev, j, 5)
        else:
            results[j] = b.score(up, queries[j], 5)

    threads = [threading.Thread(target=worker, args=(j,)) for j in range(len(queries))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        b.close()  # joins the completer: every pass has been observed
    assert len(results) == len(queries)
    for j, (idx, _vals) in results.items():
        np.testing.assert_array_equal(idx, topn_ops.top_k_scores(up, queries[j], 5)[0])
    got = {k: v - before[k] for k, v in _pass_record().items()}
    n = len(queries)
    assert got["rows"] == n and got["waits"] == n
    assert 1 <= got["passes"] <= n
    assert got["pass_seconds"] == got["deliveries"] == got["passes"]
    assert got["padded"] >= got["rows"] and got["padded"] % 8 == 0
    # each pass took a slot, and the cap never passes 32: the mean depth at submit
    assert got["passes"] <= got["depth_sum"] <= 32 * got["passes"]


def test_fused_vector_path_counts_the_multiple_of_the_scan_batch():
    """Above MULTI_THRESHOLD the vector path pads to a multiple of the
    scan batch (ops.topn._group_pad), not to a power of two."""
    y, up = _make(n=300, kf=8, seed=13)
    b = TopNBatcher()
    b.MULTI_THRESHOLD = 8
    queries = np.random.default_rng(14).standard_normal((20, 8)).astype(np.float32)
    entries = [batcher_mod._Entry(up, q, 4, False) for q in queries]
    before = _pass_record()
    try:
        b._submit_group(entries, False)
        for e in entries:
            assert e.done.wait(30) and e.error is None
    finally:
        b.close()
    got = {k: v - before[k] for k, v in _pass_record().items()}
    assert (got["passes"], got["rows"], got["padded"]) == (1, 20, 24)


def test_a_dispatch_that_raises_releases_its_slot_and_counts_no_pass(monkeypatch):
    y, up = _make(n=100, kf=8, seed=15)

    def boom(*a, **k):
        raise RuntimeError("device refused the dispatch")

    monkeypatch.setattr(batcher_mod.topn_ops, "submit_top_k", boom)
    b = TopNBatcher()
    before = _pass_record()
    try:
        with pytest.raises(RuntimeError, match="device refused"):
            b.score(up, np.ones(8, np.float32), 3)
        assert b._inflight_count == 0
        monkeypatch.undo()
        # the slot came back: the next request is served
        idx, _ = b.score(up, np.ones(8, np.float32), 3)
        assert len(idx) == 3
    finally:
        b.close()
    got = {k: v - before[k] for k, v in _pass_record().items()}
    # the failed attempt waited in the queue like any other and was no pass
    assert got["waits"] == 2 and got["passes"] == 1 and got["rows"] == 1
    assert got["pass_seconds"] == 1


def test_inflight_cap_changes_are_counted_when_the_cap_moves():
    b = TopNBatcher()
    try:
        counter = b._m_cap_changes
        start = counter.value
        with b._flight_cv:
            b._observe_latency(10.0)  # 50 / 10 + 2 = 7: a step from the initial 4
            assert b._inflight_cap == 7 and counter.value == start + 1
            b._observe_latency(10.0)  # same cap: no step
            assert counter.value == start + 1
            for _ in range(40):
                b._observe_latency(100.0)  # EWMA -> 100 ms: 50 / 100 + 2 = 2
            assert b._inflight_cap == 2 and counter.value > start + 1
    finally:
        b.close()
    pinned = TopNBatcher(max_inflight=3)
    try:
        start = pinned._m_cap_changes.value
        with pinned._flight_cv:
            pinned._observe_latency(1.0)
        assert pinned._inflight_cap == 3 and pinned._m_cap_changes.value == start
    finally:
        pinned.close()
