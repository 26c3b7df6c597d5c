"""Micro-batcher: concurrent scoring calls coalesce into batched device
submits without changing any per-request answer."""

import contextlib
import threading
import time

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
    """A coalesced group past MULTI_THRESHOLD is still one dispatch,
    padded to whole scan groups; answers stay identical to the direct path."""
    y, up = _make(n=600, kf=10, seed=5)
    calls = []  # rows given to every submit
    real_submit = topn_ops.submit_top_k

    def counting(uploaded, queries, *a, **k):
        calls.append(len(queries))
        return real_submit(uploaded, queries, *a, **k)

    monkeypatch.setattr(batcher_mod.topn_ops, "submit_top_k", counting)
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
        # the take that was already waiting when the gate went in serves this
        # one; every take after it waits for the gate
        b.score(up, queries[0], 4)

        def run(j):
            results[j] = b.score(up, queries[j], 4)

        threads = [threading.Thread(target=run, args=(j,)) for j in range(len(queries))]
        for t in threads:
            t.start()
        time.sleep(0.3)  # let every request enqueue
        gate.set()
        for t in threads:
            t.join(timeout=30)
        for j in range(len(queries)):
            ridx, rvals = topn_ops.top_k_scores(up, queries[j], 4)
            np.testing.assert_array_equal(results[j][0], ridx)
            np.testing.assert_allclose(results[j][1], rvals, atol=1e-5)
        assert 40 in calls  # one dispatch of all 40 rows: whole groups of the forced 8
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
        # the close (a batch held open behind the pass ahead)
        "held": snap[name + "pass.held"]["value"],
        "hold_rows": snap[name + "hold.rows"]["value"],
        "hold_late": snap[name + "hold.late"]["value"],
        "holds": snap[name + "hold.seconds"].get("count", 0),
        "hold_s": snap[name + "hold.seconds"].get("sum", 0.0),
        "hold_errors": snap[name + "hold.error.seconds"].get("count", 0),
        "wait_s": snap[name + "queue-wait.seconds"].get("sum", 0.0),
        "pass_s": snap[name + "pass.seconds"].get("sum", 0.0),
    }


def _delta(before: dict) -> dict:
    got = {k: v - before[k] for k, v in _pass_record().items()}
    # what holds of every run: a held pass is a pass, a row that joined a hold is a row
    assert 0 <= got["hold_late"] <= got["held"] == got["holds"] <= got["passes"]
    assert 0 <= got["hold_rows"] <= got["rows"] and got["hold_errors"] <= got["held"]
    return got


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
    got = _delta(before)
    n = len(queries)
    assert got["rows"] == n and got["waits"] == n
    assert 1 <= got["passes"] <= n
    assert got["pass_seconds"] == got["deliveries"] == got["passes"]
    assert got["padded"] >= got["rows"] and got["padded"] % 8 == 0
    # each pass took a slot, and two are in flight at most: the mean depth at submit
    assert got["passes"] <= got["depth_sum"] <= 2 * got["passes"]


def test_an_ivf_handle_past_one_scan_group_is_given_its_rows_unpadded(monkeypatch):
    """An IVF index groups its queries itself: the exact layouts get whole
    scan groups (above), IVF the rows that asked."""
    from oryx_tpu.ops import ivf as ivf_ops

    gen = np.random.default_rng(8)
    index = ivf_ops.build_ivf(gen.standard_normal((400, 8)).astype(np.float32), n_cells=4, seed=1)
    given = []
    monkeypatch.setattr(
        batcher_mod.topn_ops, "submit_top_k",
        lambda uploaded, queries, *a, **k: given.append(len(queries)) or _StubHandle(
            0.0, None, np.zeros((len(queries), 4), np.int64), np.zeros((len(queries), 4), np.float32)
        ),
    )
    b = TopNBatcher()
    b.MULTI_THRESHOLD = 8
    try:
        entries = [
            batcher_mod._Entry(uploaded=index, query=np.zeros(8, np.float32), k=4, cosine=False)
            for _ in range(11)
        ]
        b._submit_vectors(entries, False, 4, None, padded=16)
        b._submit_vectors(entries[:5], False, 4, None, padded=8)
        assert given == [11, 8]  # past the group size as it came; inside it, its bucket
    finally:
        b.close()


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
    got = _delta(before)
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
    got = _delta(before)
    # the failed attempt waited in the queue like any other and was no pass
    assert got["waits"] == 2 and got["passes"] == 1 and got["rows"] == 1
    assert got["pass_seconds"] == 1


# -- submit kind and scoring on the record ------------------------------------------


def _scan_record() -> dict:
    """The counts by submit kind and by scoring, the bytes of query block
    handed to the device, and the submit histogram (deltas, as above)."""
    snap = batcher_mod._metrics.snapshot()
    submit = snap["serving.batcher.submit.seconds"]
    return {
        "cosine": snap["serving.scan.cosine.queries"]["value"],
        "vector": snap["serving.scan.vector.queries"]["value"],
        "indexed": snap["serving.scan.indexed.queries"]["value"],
        "upload": snap["serving.scan.vector.upload-bytes"]["value"],
        "submits": submit.get("count", 0),
        "submit_s": submit.get("sum", 0.0),
        "passes": snap["serving.batcher.passes"]["value"],
        "padded": snap["serving.batcher.pass.padded-rows"]["value"],
    }


def test_a_cosine_group_and_a_dot_group_in_one_queue_leave_as_two_passes():
    """Both slots are held until every request is in the dispatcher's one
    batch: it leaves as a cosine pass and a dot pass, each answer that of
    its own scoring, and the record moves by what was submitted."""
    y, up = _make(n=600, kf=8, seed=21)
    queries = np.random.default_rng(22).standard_normal((8, 8)).astype(np.float32)
    cosine = [True, False, True, True, False, True, False, True]  # 5 cosine, 3 dot
    b = TopNBatcher()
    b._acquire_slot(), b._acquire_slot()
    queued = threading.Semaphore(0)
    put = b._queue.put
    b._queue.put = lambda e: (put(e), queued.release())
    before, results = _scan_record(), {}

    def worker(j):
        results[j] = b.score(up, queries[j], 6, cosine=cosine[j])

    threads = [threading.Thread(target=worker, args=(j,)) for j in range(8)]
    try:
        for t in threads:
            t.start()
        for _ in threads:
            assert queued.acquire(timeout=30)
        time.sleep(0.05)  # the dispatcher's 1 ms waits take the last arrival in
        b._release_slot(), b._release_slot()
        for t in threads:
            t.join(timeout=30)
    finally:
        b.close()
    assert len(results) == 8
    for j, (idx, vals) in results.items():
        ridx, rvals = topn_ops.top_k_scores(up, queries[j], 6, cosine=cosine[j])
        np.testing.assert_array_equal(idx, ridx)
        np.testing.assert_allclose(vals, rvals, atol=1e-5)
        if cosine[j]:
            assert np.all(np.abs(vals) <= 1.0 + 1e-6)
    got = {k: v - before[k] for k, v in _scan_record().items()}
    assert got["passes"] == got["submits"] == 2 and got["submit_s"] > 0.0
    assert (got["vector"], got["cosine"], got["indexed"]) == (8, 5, 0)
    assert got["upload"] == 2 * 8 * 8 * 4  # two blocks of the 8-row bucket, 8 float32 a row


@pytest.mark.parametrize("indexed, cosine, moved", [
    (True, False, {"indexed": 3, "vector": 0, "cosine": 0, "upload": 0}),
    (True, True, {"indexed": 3, "vector": 0, "cosine": 3, "upload": 0}),
    (False, False, {"indexed": 0, "vector": 3, "cosine": 0, "upload": 8 * 8 * 4}),
    (False, True, {"indexed": 0, "vector": 3, "cosine": 3, "upload": 8 * 8 * 4}),
], ids=["indexed-dot", "indexed-cosine", "vector-dot", "vector-cosine"])
def test_one_pass_moves_the_record_by_its_kind_and_its_scoring(monkeypatch, indexed, cosine, moved):
    y, up = _make(n=300, kf=8, seed=23)
    queries = np.random.default_rng(24).standard_normal((3, 8)).astype(np.float32)
    x_dev = topn_ops.upload_queries(queries)
    marked = []
    monkeypatch.setattr(
        batcher_mod.profiling, "annotate",
        lambda name, **attrs: marked.append((name, attrs)) or contextlib.nullcontext(),
    )
    if indexed:
        entries = [batcher_mod._Entry(up, None, 4, cosine, x_dev=x_dev, row=j) for j in range(3)]
    else:
        entries = [batcher_mod._Entry(up, q, 4, cosine) for q in queries]
    b = TopNBatcher()
    before = _scan_record()
    try:
        b._submit_group(entries, cosine)
        for j, e in enumerate(entries):
            assert e.done.wait(30) and e.error is None
            np.testing.assert_array_equal(
                e.idx, topn_ops.top_k_scores(up, queries[j], 4, cosine=cosine)[0]
            )
    finally:
        b.close()
    got = {k: v - before[k] for k, v in _scan_record().items()}
    assert got["passes"] == got["submits"] == 1
    assert {k: got[k] for k in moved} == moved
    (attrs,) = [a for name, a in marked if name == "serving.pass.submit"]
    assert attrs["kind"] == ("indexed" if indexed else "vector") and attrs["cosine"] == int(cosine)
    assert (attrs["rows"], attrs["padded_rows"]) == (3, 8)


@pytest.mark.parametrize("n, padded", [(1, 8), (11, 16), (300, 512)],
                         ids=["one-row", "second-bucket", "two-scan-groups"])
def test_a_vector_pass_uploads_its_padded_rows_at_four_bytes_a_feature(n, padded):
    """What a reader may derive the uplink from: the rows after padding
    (a power-of-two bucket; whole scan groups past `MULTI_THRESHOLD`),
    times the features, times 4. The block is made once, and every row of
    it that is a request gets its own answer."""
    y, up = _make(n=300, kf=8, seed=25)
    queries = np.random.default_rng(26).standard_normal((n, 8)).astype(np.float32)
    entries = [batcher_mod._Entry(up, q, 4, False) for q in queries]
    b = TopNBatcher()
    before = _scan_record()
    try:
        b._submit_group(entries, False)
        assert all(e.done.wait(30) and e.error is None for e in entries)
    finally:
        b.close()
    for j in sorted({0, n // 2, n - 1}):
        np.testing.assert_array_equal(entries[j].idx, topn_ops.top_k_scores(up, queries[j], 4)[0])
    got = {k: v - before[k] for k, v in _scan_record().items()}
    assert (got["passes"], got["vector"], got["padded"]) == (1, n, padded)
    assert got["upload"] == padded * 8 * 4


def test_a_batcher_that_served_nothing_reads_zero_not_nothing():
    """Handles taken in __init__: a reader's delta on a cell that never
    feeds a counter is 0, where a missing counter would be nothing."""
    b = TopNBatcher()
    try:
        snap = batcher_mod._metrics.snapshot()
    finally:
        b.close()
    for name in ("serving.scan.cosine.queries", "serving.scan.vector.queries",
                 "serving.scan.indexed.queries", "serving.scan.vector.upload-bytes"):
        assert snap[name]["type"] == "counter" and snap[name]["value"] >= 0.0
    assert snap["serving.batcher.submit.seconds"]["type"] == "histogram"


# -- the in-flight depth ---------------------------------------------------------
#
# A stub device in place of `submit_top_k`: one pass at a time, `pass_s`
# each, its results on the host when it ends; a submit costs the host
# `submit_s`. No device, and every test sleeps about 100 ms in all at most.


class _StubDevice:
    def __init__(self, pass_s: float = 0.0, submit_s: float = 0.0) -> None:
        self.pass_s, self.submit_s = pass_s, submit_s
        self.gate: threading.Event | None = None  # set: results wait for it (a stalled completer)
        self.groups: list[np.ndarray] = []  # a pass's rows by the number they carry (padding: 0)
        self._free_at = 0.0
        self._lock = threading.Lock()

    def submit(self, uploaded, queries, kk, cosine=False, nprobe=None):
        if self.submit_s:
            time.sleep(self.submit_s)
        with self._lock:
            self.groups.append(queries[:, 0].copy())
            ready_at = self._free_at = max(time.perf_counter(), self._free_at) + self.pass_s
        # row r answers with the number its query carries, so that a
        # request handed another request's row would show
        idx = np.repeat(queries[:, :1].astype(np.int64), kk, axis=1)
        return _StubHandle(ready_at, self.gate, idx, idx.astype(np.float32))


class _StubHandle:
    packed = False  # a pair, as an IVF index hands back
    def __init__(self, ready_at, gate, idx, vals) -> None:
        self._ready_at, self._gate, self._out = ready_at, gate, (idx, vals)

    def result(self):
        if self._gate is not None:
            assert self._gate.wait(10)
        wait = self._ready_at - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        return self._out


def _ask(b, numbers, tenant=None, k=3, together=False, uploaded=None, parked=None) -> dict:
    """One thread a number: each asks the batcher with a query that
    carries its number. The threads and {number: served idx}, for `_join`.
    `together`: the threads are all started first and ask at once when
    this returns, so that starting them is in nobody's measured wait;
    `parked()` is then called once they all stand ready, before they ask.
    `uploaded`: the matrix handle they ask of (a new one a call if None)."""
    from oryx_tpu.tenancy.context import tenant_scope

    got: dict = {}
    uploaded = object() if uploaded is None else uploaded
    numbers = list(numbers)
    started = threading.Barrier(len(numbers) + 1) if together else None

    def one(n):
        if started is not None:
            started.wait(30)
        with tenant_scope(tenant(n) if tenant else None):
            got[n] = b.score(uploaded, np.full(4, n, np.float32), k)[0]

    threads = [threading.Thread(target=one, args=(n,)) for n in numbers]
    for t in threads:
        t.start()
    if started is not None:
        if parked is not None:
            _wait_until(lambda: started.n_waiting == len(numbers))
            parked()
        started.wait(30)
    return {"threads": threads, "got": got}


def _join(asked) -> None:
    for t in asked["threads"]:
        t.join(timeout=30)
        assert not t.is_alive()
    for n, idx in asked["got"].items():
        assert (idx == n).all()


def _wait_until(cond, seconds=10.0) -> None:
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, "the batcher never got there"
        time.sleep(0.0005)


@pytest.fixture
def stub(monkeypatch):
    device = _StubDevice()
    monkeypatch.setattr(batcher_mod.topn_ops, "submit_top_k", device.submit)
    return device


def _stall(b, stub) -> list:
    """Fill every slot with a pass whose results wait for `stub.gate`."""
    stub.gate = threading.Event()
    held = []
    for n in range(b._inflight_cap):
        held.append(_ask(b, [1000 + n]))
        _wait_until(lambda: b._inflight_count == n + 1)
    return held


@pytest.mark.parametrize(
    "pass_ms, submit_ms",
    [
        (3.0, 0.0),  # a cell: the host refills a slot well inside a pass
        (3.0, 1.5),  # the refill is half the pass
        (0.2, 2.0),  # a small catalog: the pass ten times shorter than the refill
        (0.0, 0.0),  # the CPU backend under tier-1: a dispatch that is done when it returns
    ],
    ids=["refill-inside-the-pass", "refill-half-the-pass", "pass-a-tenth-of-the-refill", "synchronous"],
)
def test_two_passes_are_in_flight_whatever_the_pass_and_the_refill_take(stub, pass_ms, submit_ms):
    """The depth rests at two:
    measured on the chip, a deeper pipeline buys nothing where the pass is
    shorter than the refill either (PERF.md, PR 28). And no close is held
    where the pass is shorter than the lead (all but the first stub),
    however many passes of one handle have been timed: such a replica
    runs the schedule it ran before there was a hold."""
    stub.pass_s, stub.submit_s = pass_ms / 1000.0, submit_ms / 1000.0
    b = TopNBatcher()
    before = _pass_record()
    handle = object()
    try:
        assert b._inflight_cap == batcher_mod.MIN_INFLIGHT == 2
        for first in (1, 13, 25):
            _join(_ask(b, range(first, first + 12), uploaded=handle))
        assert b._inflight_cap == 2
        assert len(b._flight) <= 2  # settled at every submit, whether or not a close was weighed
    finally:
        b.close()
    got = _delta(before)
    assert got["rows"] == 36 and got["passes"] <= got["depth_sum"] <= 2 * got["passes"]
    # the lead is the submit + the lag (the stub's: none) + the guard; a pass
    # about as long as that (the first two stubs) may be held for a moment
    if pass_ms < submit_ms + 1000.0 * batcher_mod.HOLD_GUARD_S - 0.5:
        assert got["held"] == got["hold_rows"] == 0


def test_the_third_group_waits_for_a_slot_and_takes_the_arrivals(stub):
    b = TopNBatcher()
    try:
        held = _stall(b, stub)
        assert b._inflight_count == 2 and len(stub.groups) == 2
        third = _ask(b, [3])
        _wait_until(lambda: b._queue.qsize() == 0)  # the dispatcher has it and waits for a slot
        late = _ask(b, [4, 5, 6])
        _wait_until(lambda: b._queue.qsize() == 0)  # ... and takes what arrives meanwhile
        assert b._inflight_count == 2 and len(stub.groups) == 2
        stub.gate.set()
        for asked in held + [third, late]:
            _join(asked)
        assert len(stub.groups) == 4  # 3 alone (its own matrix handle), then 4, 5 and 6 in one pass
    finally:
        b.close()


@pytest.mark.parametrize("depth", [1, 3, 5])
def test_an_explicit_max_inflight_pins_the_depth(stub, depth):
    b = TopNBatcher(max_inflight=depth)
    before = _pass_record()
    try:
        held = _stall(b, stub)
        assert len(held) == depth
        rest = _ask(b, [4, 5])
        _wait_until(lambda: b._queue.qsize() == 0)  # the dispatcher holds them, waiting for a slot
        assert b._inflight_count == depth and len(stub.groups) == depth
        stub.gate.set()
        for asked in held + [rest]:
            _join(asked)
        assert b._inflight_cap == depth
    finally:
        b.close()
    got = _delta(before)
    assert got["passes"] == depth + 1 and got["depth_sum"] <= depth * got["passes"]


def test_a_scheduler_configured_with_nothing_holds_depth_two_and_4096_rows():
    batcher_mod.configure_scheduler()
    try:
        b = batcher_mod.get_default_batcher()
        assert b._inflight_cap == 2 and b.max_batch == batcher_mod.DEFAULT_MAX_BATCH == 4096
    finally:
        batcher_mod.close_default_batcher()


@pytest.mark.parametrize("max_inflight", [None, 4], ids=["the-rule", "four-as-the-old-rule-held"])
def test_a_backlog_behind_a_stalled_completer_leaves_in_two_passes(stub, max_inflight):
    """128 entries arrive while every slot is taken and the completer is
    stalled (a pause of the process): they leave in at most two passes,
    and the wait the ladder is told of is the stall, as it was at the
    depth the old rule held in the cells."""
    def stalled_wait(depth) -> float:
        stub.groups.clear()
        b = TopNBatcher(max_inflight=depth)
        try:
            slots = b._inflight_cap
            first = _stall(b, stub)
            queued, put = [], b._queue.put
            b._queue.put = lambda e: (queued.append(e), put(e))[1]
            backlog = _ask(b, range(128), together=True)
            t0 = time.monotonic()
            # all 128 have asked and the dispatcher holds them, waiting for a slot
            _wait_until(lambda: len(queued) == 128 and b._queue.qsize() == 0)
            time.sleep(0.01 - min(0.01, time.monotonic() - t0))  # the stall: 10 ms
            stub.gate.set()
            _join(backlog)
            for asked in first:
                _join(asked)
            assert len(stub.groups) - slots <= 2
            return b.queue_wait_ewma_ms()
        finally:
            b.close()

    # a sample is the stall or, on a machine that runs five other test
    # files, the time 128 threads took to ask where that is longer: the
    # threads are started before the clock (`together`), and a depth is
    # read as the median of five samples
    waits = {depth: float(np.median([stalled_wait(depth) for _ in range(5)])) for depth in (max_inflight, 4)}
    assert waits[max_inflight] <= 1.25 * waits[4] + 2.0


def test_two_tenants_of_unequal_weight_get_their_shares_at_depth_two(stub):
    """The deficit round-robin feeds the same dispatcher: behind two
    stalled passes, tenant a (weight 3) and tenant b (weight 1) leave in
    passes of 16 rows that are three quarters a's."""
    b = TopNBatcher(max_batch=16, tenant_weights={"a": 3.0, "b": 1.0}, fair_quantum=4.0)
    try:
        first = _stall(b, stub)
        assert len(first) == 2
        held = _ask(b, [2000])  # the dispatcher takes it and waits for a slot with it
        _wait_until(lambda: b._queue.qsize() == 0)
        backlog = _ask(b, range(1, 129), tenant=lambda n: "a" if n <= 64 else "b")
        _wait_until(lambda: b._queue.qsize() >= 128 - 15)
        stub.gate.set()
        for asked in first + [held, backlog]:
            _join(asked)
    finally:
        b.close()
    served = np.concatenate(stub.groups)
    served = served[(served >= 1) & (served <= 128)]  # without the padding rows and the first three
    # the dispatcher took the first 15 arrivals as they came, beside the
    # entry it held; what queued up behind left in the round-robin's order
    assert len(served) == 128 and (served[15 : 15 + 64] <= 64).sum() == 48


# -- the close ---------------------------------------------------------------------
#
# A batch behind a pass that is far from its end stays open until that pass
# is due to leave the device within the lead. The stub's pass is 30 ms here
# (its results are on the host when it ends: no lag), so that a loaded
# machine's milliseconds do not decide a case; a case sleeps 0.2 s or so.

_PASS_S = 0.030


def _timed(b, stub, handle, behind=3, lag=True) -> None:
    """Let the batcher time the passes of `handle` (bucket of 8 rows, k
    bucket 16: the key of every pass below): one on the idle device and
    `behind` more, each submitted behind the one before it, which read the
    service time; then one more on the idle device, which reads the lag
    (the stub's results are on the host when its pass ends: about none)."""
    asked = []
    for n in range(behind + 1):
        seen = len(stub.groups)
        asked.append(_ask(b, [900 + n], uploaded=handle))
        _wait_until(lambda: len(stub.groups) == seen + 1)
    for a in asked:
        _join(a)
    if lag:
        _join(_ask(b, [999], uploaded=handle))


def _three_requests(b, stub, handle, before_second=None) -> tuple[dict, list[float]]:
    """Requests at 0, +2 and +10 ms of a 30 ms pass: 1 starts a pass on the
    idle device, 2 finds that pass ahead of it, 3 comes while 2's batch
    either is held open or already sits in the device's queue. What they
    were answered and when, from the first's asking."""
    done: dict[int, float] = {}
    t0 = time.perf_counter()
    n_groups = len(stub.groups)
    asked = [_ask(b, [1], uploaded=handle)]
    _wait_until(lambda: len(stub.groups) == n_groups + 1)
    if before_second is not None:
        before_second()
    for n, at in ((2, 0.002), (3, 0.010)):
        time.sleep(max(0.0, t0 + at - time.perf_counter()))
        asked.append(_ask(b, [n], uploaded=handle))
    for n, a in enumerate(asked, start=1):
        _join(a)
        done[n] = time.perf_counter() - t0
    return done, [g[g > 0].tolist() for g in stub.groups[n_groups:]]


@pytest.mark.parametrize(
    "timed, max_inflight, held, groups",
    [
        # an estimate of the pass ahead: the second batch stays open, and the third joins it
        ("this-handle", None, 1, [[1.0], [2.0, 3.0]]),
        # no estimate yet, and a rotation (the new handle has none): close at
        # once, as before there was a hold: the second is on the device's queue when the third comes
        ("nothing", None, 0, [[1.0], [2.0], [3.0]]),
        ("another-handle", None, 0, [[1.0], [2.0], [3.0]]),
        # the service time known and no pass yet that started on an idle device and said what the lag
        # is: the second is closed at once; the first then says it, and the third is held alone behind the second
        ("this-handle-no-lag", None, 1, [[1.0], [2.0], [3.0]]),
        # one slot: nothing is ever ahead of a free slot (the second waits for it and the third joins it there)
        ("this-handle", 1, 0, [[1.0], [2.0, 3.0]]),
        # three slots: the second fills the pipeline at once; the third finds both other slots' passes ahead and is held alone
        ("this-handle", 3, 1, [[1.0], [2.0], [3.0]]),
    ],
    ids=["held", "no-estimate-yet", "after-a-rotation", "no-lag-yet", "max-inflight-1", "max-inflight-3"],
)
def test_a_request_that_arrives_while_the_pass_ahead_has_far_to_go_joins_the_open_batch(
    stub, timed, max_inflight, held, groups
):
    stub.pass_s = _PASS_S
    b = TopNBatcher(max_inflight=max_inflight)
    handle = object()
    try:
        if timed != "nothing":
            _timed(b, stub, object() if timed == "another-handle" else handle, lag=timed != "this-handle-no-lag")
        before = _pass_record()
        done, served = _three_requests(b, stub, handle)
    finally:
        b.close()
    b._settle()  # its dispatcher has gone: the last pass ahead's error goes on the record
    got = _delta(before)
    assert got["rows"] == got["waits"] == 3 and served == groups
    # (`hold.late` is not held to 0: a loaded machine's timer oversleeps the guard now and then)
    assert (got["held"], got["hold_errors"]) == (held, held)
    assert got["hold_rows"] == (1 if len(groups) == 2 and held else 0)
    if len(groups) == 2 and held:
        assert done[3] < 2.6 * _PASS_S  # two passes, not three: the third's answer a pass sooner
        # the hold is queueing: in the queue wait, and in no pass's time in flight
        assert got["wait_s"] >= got["hold_s"] >= 0.5 * _PASS_S
        assert got["pass_s"] < 2.0 * _PASS_S + 0.5 * got["hold_s"]
    elif max_inflight is None:
        assert done[3] > 2.6 * _PASS_S


def test_a_submit_that_outlasts_the_lead_is_counted_late(stub):
    """The lead holds the dispatcher's own submit time as it was; a submit
    that takes 10 ms where the last took none ends after the pass ahead
    has left the device, which idled for it: `hold.late`."""
    stub.pass_s = _PASS_S
    b = TopNBatcher()
    handle = object()
    try:
        _timed(b, stub, handle)
        before = _pass_record()
        _three_requests(b, stub, handle, before_second=lambda: setattr(stub, "submit_s", 0.010))
    finally:
        b.close()
    got = _delta(before)
    assert (got["passes"], got["held"], got["hold_late"]) == (2, 1, 1)


def test_close_during_a_hold_submits_the_open_batch_and_joins(stub):
    stub.pass_s = _PASS_S
    b = TopNBatcher()
    handle = object()
    try:
        _timed(b, stub, handle)
        before = _pass_record()
        n_groups = len(stub.groups)
        first = _ask(b, [1], uploaded=handle)
        _wait_until(lambda: len(stub.groups) == n_groups + 1)
        second = _ask(b, [2], uploaded=handle)
        _wait_until(lambda: b._queue.qsize() == 0 and b._flight and b._flight[-1].due)  # held
        t0 = time.perf_counter()
        b.close()
        closed_in = time.perf_counter() - t0
        _join(first)
        _join(second)
    finally:
        b.close()
    assert not b._dispatcher.is_alive() and not b._completer.is_alive()
    got = _delta(before)
    assert (got["passes"], got["held"]) == (2, 1)
    # the hold ended with the close, far before its time: the second pass
    # was submitted behind the first and close() waited for both to drain
    assert got["hold_s"] < 0.5 * _PASS_S and closed_in < 2.5 * _PASS_S


def test_one_slow_pass_does_not_stretch_the_estimate_of_the_next():
    """Four passes of 20 ms behind one another and one whose results were
    stalled for 80 ms (a pause of the machine): the estimate stays the pass's.
    Decided on the stub device's own stamps, not on sleeps: `a_pass` says,
    by the stub's rule (one pass at a time, its results on the host when it
    ends), when a pass was submitted and when its results were there, and
    hands `_settle` the two as the dispatcher and the completer stamp them.
    The batcher's own dispatcher waits on its empty queue and touches none
    of this state."""
    pass_s, free_at = 0.020, 0.0
    key = ("a handle", False, False, None, 8, 16)
    b = TopNBatcher()

    def a_pass(submitted_at: float, stalled_s: float = 0.0) -> None:
        nonlocal free_at
        free_at = max(submitted_at, free_at) + pass_s
        b._flight.append(batcher_mod._PassTiming(key, submitted_at, t_ready=free_at + stalled_s))
        b._settle()

    try:
        a_pass(100.000)  # on the idle device (no estimate yet: it reads nothing)
        for n in range(1, 5):
            a_pass(100.000 + 0.001 * n)  # each behind the one before it: the service time
        assert b._service_estimate(key) == pytest.approx(pass_s) and not b._lag_s
        a_pass(100.200)  # on the idle device again: the lag (the stub's: none)
        a_pass(100.300)  # the pass ahead
        a_pass(100.301, stalled_s=0.080)  # behind it, its results 80 ms late
        samples = sorted(b._service_s[key])
        assert len(samples) == 5 and samples[-1] == pytest.approx(pass_s + 0.080)
        assert b._service_estimate(key) == pytest.approx(pass_s)
        assert len(b._lag_s) == 2 and b._lag() == pytest.approx(0.0, abs=1e-9)
        assert not b._flight
    finally:
        b.close()


def test_two_tenants_of_unequal_weight_keep_their_shares_through_a_held_batch(stub):
    """A batch held open takes what comes in the queue's own order, fills
    (`max_batch`) and is closed at once; behind it the deficit round-robin
    serves tenant a (weight 3) and tenant b (weight 1) three to one."""
    stub.pass_s = 4 * _PASS_S  # a hold long enough for 128 threads to ask inside it on a loaded machine
    b = TopNBatcher(max_batch=16, tenant_weights={"a": 3.0, "b": 1.0}, fair_quantum=4.0)
    handle = object()
    try:
        _timed(b, stub, handle)
        before = _pass_record()
        n_groups = len(stub.groups)
        asked = []

        def a_pass_ahead_and_a_batch_held_behind_it():
            asked.append(_ask(b, [2000], uploaded=handle))
            _wait_until(lambda: len(stub.groups) == n_groups + 1)
            asked.append(_ask(b, [2001], uploaded=handle))
            _wait_until(lambda: b._queue.qsize() == 0 and b._flight and b._flight[-1].due)

        asked.append(_ask(
            b, range(1, 129), tenant=lambda n: "a" if n <= 64 else "b", together=True, uploaded=handle,
            parked=a_pass_ahead_and_a_batch_held_behind_it,
        ))
        for a in asked:
            _join(a)
    finally:
        b.close()
    got = _delta(before)
    assert got["rows"] == 130 and got["held"] >= 1
    groups = stub.groups[n_groups + 1 :]
    assert len(groups[0]) == 16 and groups[0][0] == 2001  # the held batch: full, closed before its time
    assert got["hold_rows"] >= 15
    served = np.concatenate(groups)
    served = served[(served >= 1) & (served <= 128)]
    # the held batch and the one that waited for a slot behind it took the
    # arrivals as they came; from then on all are queued and any 32 served
    # in a row are two turns of the round-robin: 24 of a, 8 of b
    assert len(served) == 128 and (served[31:63] <= 64).sum() == 24
