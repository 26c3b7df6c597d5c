"""Native C++ feature store: parity with the Python FeatureVectors and
concurrency behavior (reference FeatureVectorsTest semantics)."""

import os
import threading

import numpy as np
import pytest

from oryx_tpu.app.als.common import FeatureVectors
from oryx_tpu.native import get_library
from oryx_tpu.native.store import NativeFeatureVectors, make_feature_vectors

needs_native = pytest.mark.skipif(
    get_library() is None, reason="native library unavailable"
)


@pytest.fixture(params=["python", "native"])
def store(request):
    if request.param == "python":
        return FeatureVectors()
    if get_library() is None:
        pytest.skip("native library unavailable")
    return NativeFeatureVectors()


def test_set_get_remove_size(store):
    assert store.size() == 0
    assert store.get_vector("a") is None
    store.set_vector("a", np.array([1.0, 0.5, -2.0], np.float32))
    store.set_vector("b", np.array([0.0, 1.0, 3.0], np.float32))
    assert store.size() == 2
    np.testing.assert_array_equal(store.get_vector("a"), [1.0, 0.5, -2.0])
    store.set_vector("a", np.array([9.0, 9.0, 9.0], np.float32))  # overwrite
    assert store.size() == 2
    np.testing.assert_array_equal(store.get_vector("a"), [9.0, 9.0, 9.0])
    store.remove_vector("a")
    assert store.size() == 1
    assert store.get_vector("a") is None
    store.remove_vector("never-there")  # no-op
    assert store.size() == 1


def test_to_matrix_and_ids_consistent(store):
    vecs = {f"id{i}": np.arange(4, dtype=np.float32) + i for i in range(37)}
    for k, v in vecs.items():
        store.set_vector(k, v)
    ids, mat = store.to_matrix()
    assert sorted(ids) == sorted(vecs)
    assert mat.shape == (37, 4)
    for row, id_ in enumerate(ids):
        np.testing.assert_array_equal(mat[row], vecs[id_])
    assert sorted(store.ids()) == sorted(vecs)
    got = dict(store.items())
    assert set(got) == set(vecs)
    np.testing.assert_array_equal(got["id3"], vecs["id3"])


def test_vtv(store):
    gen = np.random.default_rng(5)
    mats = gen.standard_normal((50, 6)).astype(np.float32)
    for i, v in enumerate(mats):
        store.set_vector(f"v{i}", v)
    vtv = store.get_vtv()
    expect = mats.astype(np.float64).T @ mats.astype(np.float64)
    np.testing.assert_allclose(vtv, expect, rtol=1e-5)


def test_vtv_empty(store):
    assert store.get_vtv() is None


def test_retain_recent_and_ids(store):
    """Rotation semantics (FeatureVectors.retainRecentAndIDs:131-136):
    survivors = new-model ids + written-since-last-rotation, recency resets."""
    store.set_vector("old1", np.ones(2, np.float32))
    store.set_vector("old2", np.ones(2, np.float32))
    store.retain_recent_and_ids({"old1", "old2"})  # resets recency
    store.set_vector("fresh", np.ones(2, np.float32))
    recent: set = set()
    store.add_all_recent_to(recent)
    assert recent == {"fresh"}
    store.retain_recent_and_ids({"old1"})
    assert sorted(store.ids()) == ["fresh", "old1"]
    # recency has reset again: nothing recent survives an immediate rotation
    store.retain_recent_and_ids(set())
    assert store.ids() == []


def test_add_all_ids_to(store):
    store.set_vector("x", np.zeros(3, np.float32))
    store.set_vector("y", np.zeros(3, np.float32))
    out: set = set()
    store.add_all_ids_to(out)
    assert out == {"x", "y"}


@needs_native
def test_native_dim_mismatch_raises():
    fv = NativeFeatureVectors()
    fv.set_vector("a", np.zeros(3, np.float32))
    with pytest.raises(ValueError):
        fv.set_vector("b", np.zeros(4, np.float32))


@needs_native
def test_native_unicode_ids():
    fv = NativeFeatureVectors()
    fv.set_vector("ключ-λ", np.array([1.0, 2.0], np.float32))
    np.testing.assert_array_equal(fv.get_vector("ключ-λ"), [1.0, 2.0])
    assert fv.ids() == ["ключ-λ"]


@needs_native
def test_native_hostile_ids():
    """IDs are arbitrary wire strings: newlines, NULs, and long ids must
    round-trip through pack/ids/retain without corrupting the mapping."""
    fv = NativeFeatureVectors()
    hostile = ["a\nb", "c\x00d", "plain", "x" * 500, ""]
    for i, id_ in enumerate(hostile):
        fv.set_vector(id_, np.full(3, float(i), np.float32))
    assert sorted(fv.ids()) == sorted(hostile)
    ids, mat = fv.to_matrix()
    assert len(ids) == mat.shape[0] == len(hostile)
    for row, id_ in enumerate(ids):
        assert mat[row][0] == float(hostile.index(id_))
    fv.retain_recent_and_ids(set())  # everything recent -> all survive
    fv.retain_recent_and_ids({"a\nb", "c\x00d"})
    assert sorted(fv.ids()) == ["a\nb", "c\x00d"]


@needs_native
def test_native_concurrent_read_write():
    """Hammer the store from writer + reader + packer threads; every read
    must return either None or a complete, self-consistent vector."""
    fv = NativeFeatureVectors(num_shards=8)
    dim = 8
    stop = threading.Event()
    errors: list[str] = []

    def writer(tid: int):
        gen = np.random.default_rng(tid)
        i = 0
        while not stop.is_set():
            key = f"k{tid}-{i % 200}"
            val = np.full(dim, float(i), np.float32)
            fv.set_vector(key, val)
            i += 1

    def reader():
        while not stop.is_set():
            v = fv.get_vector("k0-7")
            if v is not None and len(set(v.tolist())) != 1:
                errors.append(f"torn read: {v}")

    def packer():
        while not stop.is_set():
            ids, mat = fv.to_matrix()
            if len(ids) != mat.shape[0]:
                errors.append(f"inconsistent pack: {len(ids)} vs {mat.shape}")
            fv.get_vtv()

    threads = (
        [threading.Thread(target=writer, args=(t,)) for t in range(2)]
        + [threading.Thread(target=reader) for _ in range(2)]
        + [threading.Thread(target=packer)]
    )
    for t in threads:
        t.start()
    import time

    time.sleep(1.5)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not errors, errors[:3]
    assert fv.size() <= 400


def test_make_feature_vectors_fallback(monkeypatch):
    monkeypatch.setenv("ORYX_NATIVE", "0")
    assert isinstance(make_feature_vectors(), FeatureVectors)


# ---------------------------------------------------------------------------
# batched get + native JSON formatting
# ---------------------------------------------------------------------------


def test_get_batch_hits_and_misses():
    fv = make_feature_vectors()
    fv.set_vector("a", np.asarray([1.0, 2.0], np.float32))
    fv.set_vector("b", np.asarray([3.0, 4.0], np.float32))
    mat, valid = fv.get_batch(["a", "missing", "b", "a"])
    assert valid.tolist() == [True, False, True, True]
    np.testing.assert_array_equal(mat[0], [1.0, 2.0])
    np.testing.assert_array_equal(mat[2], [3.0, 4.0])
    np.testing.assert_array_equal(mat[3], [1.0, 2.0])
    np.testing.assert_array_equal(mat[1], [0.0, 0.0])


def test_get_batch_python_fallback_matches():
    from oryx_tpu.app.als.common import FeatureVectors

    fv = FeatureVectors()
    fv.set_vector("a", np.asarray([1.0, 2.0], np.float32))
    mat, valid = fv.get_batch(["a", "zz"])
    assert valid.tolist() == [True, False]
    np.testing.assert_array_equal(mat[0], [1.0, 2.0])


def test_format_vectors_json_round_trips_float32():
    import json

    from oryx_tpu.native.store import format_vectors_json

    gen = np.random.default_rng(3)
    mat = np.concatenate(
        [
            gen.standard_normal((50, 7)).astype(np.float32),
            (gen.standard_normal((50, 7)) * 1e6).astype(np.float32),
            (gen.standard_normal((50, 7)) * 1e-6).astype(np.float32),
            np.asarray([[0.0, -0.0, 1.0, -1.0, 0.1, 1e-38, 3.1e38]], np.float32),
        ]
    )
    out = format_vectors_json(mat)
    assert len(out) == mat.shape[0]
    for row, s in zip(mat, out):
        back = np.asarray(json.loads(s), dtype=np.float32)
        np.testing.assert_array_equal(back, row)  # exact float32 round-trip


def test_format_update_messages_wire_format():
    import json

    from oryx_tpu.native.store import format_update_messages

    mat = np.asarray([[0.5, -2.0], [1.0, 3.25]], np.float32)
    msgs = format_update_messages(mat, ["U1", 'we"ird\\id'], ["I1", "I2"], "X", True)
    if msgs is None:  # native lib unavailable: nothing to check
        return
    assert json.loads(msgs[0]) == ["X", "U1", [0.5, -2.0], ["I1"]]
    assert json.loads(msgs[1]) == ["X", 'we"ird\\id', [1.0, 3.25], ["I2"]]
    no_known = format_update_messages(mat, ["U1", "U2"], [], "Y", False)
    assert json.loads(no_known[0]) == ["Y", "U1", [0.5, -2.0]]


def test_format_update_messages_unicode_ids():
    import json

    from oryx_tpu.native.store import format_update_messages

    mat = np.asarray([[1.5]], np.float32)
    msgs = format_update_messages(mat, ["usér-Ω"], ["ítem"], "X", True)
    if msgs is None:
        return
    assert json.loads(msgs[0]) == ["X", "usér-Ω", [1.5], ["ítem"]]


def test_format_update_messages_many_threads_compaction():
    import json

    from oryx_tpu.native.store import format_update_messages

    gen = np.random.default_rng(9)
    n, k = 1000, 5
    mat = gen.standard_normal((n, k)).astype(np.float32)
    ids = [f"U{j}" for j in range(n)]
    others = [f"I{j}" for j in range(n)]
    msgs = format_update_messages(mat, ids, others, "X", True, num_threads=7)
    if msgs is None:
        return
    assert len(msgs) == n
    for j in (0, 142, 143, 999):  # across thread-chunk boundaries
        parsed = json.loads(msgs[j])
        assert parsed[0] == "X" and parsed[1] == f"U{j}" and parsed[3] == [f"I{j}"]
        np.testing.assert_array_equal(np.asarray(parsed[2], np.float32), mat[j])


def test_format_update_messages_multi_known_lists():
    import json

    from oryx_tpu.native.store import format_update_messages_multi

    mat = np.asarray([[0.5, -2.0], [1.0, 3.25], [7.0, 8.0]], np.float32)
    msgs = format_update_messages_multi(
        mat,
        ["U1", 'we"ird\\id', "usér-Ω"],
        [["I1", "I2", "I3"], [], ['ít"em']],
        "X",
    )
    if msgs is None:  # native lib unavailable: nothing to check
        return
    assert json.loads(msgs[0]) == ["X", "U1", [0.5, -2.0], ["I1", "I2", "I3"]]
    assert json.loads(msgs[1]) == ["X", 'we"ird\\id', [1.0, 3.25], []]
    assert json.loads(msgs[2]) == ["X", "usér-Ω", [7.0, 8.0], ['ít"em']]


def test_format_update_messages_multi_threads_compaction():
    import json

    from oryx_tpu.native.store import format_update_messages_multi

    gen = np.random.default_rng(11)
    n, k = 1000, 4
    mat = gen.standard_normal((n, k)).astype(np.float32)
    ids = [f"U{j}" for j in range(n)]
    knowns = [[f"I{j}-{m}" for m in range(j % 4)] for j in range(n)]
    msgs = format_update_messages_multi(mat, ids, knowns, "X", num_threads=7)
    if msgs is None:
        return
    assert len(msgs) == n
    for j in (0, 1, 142, 143, 501, 999):
        parsed = json.loads(msgs[j])
        assert parsed[0] == "X" and parsed[1] == f"U{j}" and parsed[3] == knowns[j]
        np.testing.assert_array_equal(np.asarray(parsed[2], np.float32), mat[j])


def test_format_update_messages_multi_sliced_buffer():
    """A huge known union on one row must not inflate the output buffer
    for every row: past the buffer budget the formatter slices rows into
    bounded calls (identical output)."""
    import json

    from oryx_tpu.native import store

    gen = np.random.default_rng(3)
    n, k = 200, 4
    mat = gen.standard_normal((n, k)).astype(np.float32)
    ids = [f"U{j}" for j in range(n)]
    knowns = [[f"I{j}-{m}" for m in range(j % 30)] for j in range(n)]
    whole = store.format_update_messages_multi(mat, ids, knowns, "X")
    if whole is None:  # native lib unavailable
        return
    prev = store._MULTI_BUFFER_BUDGET
    store._MULTI_BUFFER_BUDGET = 4096  # force slicing
    try:
        sliced = store.format_update_messages_multi(mat, ids, knowns, "X")
    finally:
        store._MULTI_BUFFER_BUDGET = prev
    assert sliced == whole
    p = json.loads(sliced[199])
    assert p[1] == "U199" and p[3] == knowns[199]


def test_library_is_keyed_by_host_cpu_as_well_as_sources(monkeypatch):
    """-march=native code from another machine must not be loaded: the
    artefact name changes with the CPU model/flags, so a _build/ directory
    that travelled with the tree is rebuilt, not reused."""
    from oryx_tpu import native

    here = native._library_target()
    assert here == native._library_target()  # stable on one host
    assert b"flags" in native._host_cpu()
    monkeypatch.setattr(native, "_host_cpu", lambda: b"model name: other\nflags: sse2")
    elsewhere = native._library_target()
    assert elsewhere != here
    assert os.path.dirname(elsewhere) == os.path.dirname(here)


# -- bulk paths (PR 26): a model's rows arrive in batches past the store's
# bulk threshold (32,768 rows), are filled shard by shard on the host's
# threads, and are packed the same way -------------------------------------

BULK = 40_000


def _bulk(seed: int, n: int = BULK, dim: int = 6, dups: int = 2_000):
    gen = np.random.default_rng(seed)
    ids = [f"i{j}" for j in range(n)]
    # duplicates late in the batch: the later row must win, as in sequence
    ids += [f"i{j}" for j in gen.integers(0, n, size=dups)]
    return ids, gen.standard_normal((len(ids), dim)).astype(np.float32)


@needs_native
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bulk_set_batch_equals_the_python_store(seed):
    ids, mat = _bulk(seed)
    native, plain = NativeFeatureVectors(), FeatureVectors()
    native.set_batch(ids, mat)
    plain.set_batch(ids, mat)
    assert native.size() == plain.size() == BULK
    probe = ids[-50:] + ids[:50]
    got, valid = native.get_batch(probe)
    want, _ = plain.get_batch(probe)
    assert valid.all()
    np.testing.assert_array_equal(got, want)
    # recency marked for every row of the batch
    native.retain_recent_and_ids([])
    assert native.size() == BULK


@needs_native
@pytest.mark.parametrize("shards", [1, 3, 16])
def test_bulk_pack_rows_follow_their_ids(shards):
    ids, mat = _bulk(7)
    store = NativeFeatureVectors(num_shards=shards)
    for lo in range(0, len(ids), 1 << 14):  # below the threshold: one thread
        store.set_batch(ids[lo : lo + (1 << 14)], mat[lo : lo + (1 << 14)])
    last = {id_: row for row, id_ in enumerate(ids)}
    got_ids, got = store.to_matrix()
    assert len(got_ids) == len(set(got_ids)) == BULK
    np.testing.assert_array_equal(got, mat[[last[i] for i in got_ids]])
    assert store.ids() == got_ids  # the same snapshot order with and without rows


@needs_native
def test_bulk_pack_skips_freed_slots_and_reuses_them():
    ids, mat = _bulk(9, dups=0)
    store = NativeFeatureVectors()
    store.set_batch(ids, mat)
    store.retain_recent_and_ids([])  # all recent: all stay; recency reset
    keep = set(ids[::3])
    store.retain_recent_and_ids(keep)  # two thirds of the slots are freed
    got_ids, got = store.to_matrix()
    assert set(got_ids) == keep
    np.testing.assert_array_equal(got, mat[[int(i[1:]) for i in got_ids]])
    new_ids = [f"n{j}" for j in range(BULK)]
    store.set_batch(new_ids, mat + 1.0)  # fills the freed slots, then grows
    got_ids, got = store.to_matrix()
    assert set(got_ids) == keep | set(new_ids)
    want = {**{i: mat[int(i[1:])] for i in keep}, **{f"n{j}": mat[j] + 1.0 for j in range(BULK)}}
    np.testing.assert_array_equal(got, np.stack([want[i] for i in got_ids]))
    recent: set[str] = set()
    store.add_all_recent_to(recent)
    assert recent == set(new_ids)


@needs_native
@pytest.mark.parametrize("odd", ["", "a\0b", "\0", "é\0ü", "日本"])
def test_pack_keeps_ids_no_delimiter_is_safe_for(odd):
    store = NativeFeatureVectors()
    ids = ["plain", odd, "last"]
    mat = np.arange(9, dtype=np.float32).reshape(3, 3)
    store.set_batch(ids, mat)
    got_ids, got = store.to_matrix()
    assert sorted(got_ids) == sorted(ids)
    np.testing.assert_array_equal(got, mat[[ids.index(i) for i in got_ids]])
    assert sorted(store.ids()) == sorted(ids)
    store.remove_vector(odd)
    assert sorted(store.ids()) == ["last", "plain"]


@needs_native
def test_bulk_writers_and_packers_side_by_side():
    """Bulk fills from two threads while a third packs: every snapshot is
    whole (each id once, its row one of the values written for it)."""
    ids, mat = _bulk(11, dups=0)
    store = NativeFeatureVectors()
    store.set_batch(ids, mat)
    errors: list[str] = []

    def write(shift):
        for _ in range(3):
            store.set_batch(ids, mat + shift)

    def pack():
        for _ in range(4):
            got_ids, got = store.to_matrix()
            base = mat[[int(i[1:]) for i in got_ids]]
            delta = got - base
            if len(set(got_ids)) != BULK or not np.isin(np.round(delta), (0.0, 1.0, 2.0)).all():
                errors.append("torn snapshot")

    threads = [threading.Thread(target=write, args=(s,)) for s in (1.0, 2.0)]
    threads.append(threading.Thread(target=pack))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
