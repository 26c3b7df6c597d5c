"""Shard-items serving (`oryx.als.serving.shard-items`): the item matrix
row-sharded over a mesh, the user matrix staged on every device, each pass
through the real TopNBatcher, the single-device scan on every shard and a
cross-device merge. On the CPU's 8 virtual devices (tests/conftest.py),
meshes of 4 and of 3, item counts that do and do not divide; the answers
are held to the benchmark's plain reference (NumPy, knows nothing of
shards) and to the single-device model, id for id."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from benchmark.reference import als_topn
from oryx_tpu.app.als.serving_model import ALSServingModel
from oryx_tpu.common import metrics
from oryx_tpu.ops import topn as topn_ops
from oryx_tpu.parallel.mesh import get_mesh

FEATURES, USERS, KNOWN, HOW_MANY = 16, 60, 4, 10
MESHES = [(4, 1200), (4, 1201), (3, 1000), (3, 1001)]


def _mesh(devices: int):
    import jax

    return get_mesh(devices=jax.devices()[:devices])


def _factors(items: int, seed: int = 0):
    rng = np.random.default_rng([seed, items])
    x = rng.standard_normal((USERS, FEATURES)).astype(np.float32)
    y = rng.standard_normal((items, FEATURES)).astype(np.float32)
    known = rng.integers(0, items, size=(USERS, KNOWN))
    return x, y, known


def _fill(model: ALSServingModel, x, y, known) -> ALSServingModel:
    model.set_item_vectors([f"i{j}" for j in range(len(y))], y)
    model.set_user_vectors([f"u{j}" for j in range(len(x))], x)
    model.add_known_items_many(
        (f"u{u}", [f"i{j}" for j in row]) for u, row in enumerate(known.tolist())
    )
    return model


def _wait_staged(model: ALSServingModel) -> None:
    deadline = time.monotonic() + 60
    while True:
        model.top_n_for_user("u0", 1)
        staged = model._x_matrix is not None and not model._x_building and not model._x_dirty
        if staged:
            return
        assert time.monotonic() < deadline, "the user matrix was never staged"
        time.sleep(0.02)


@pytest.fixture(params=MESHES, ids=lambda p: f"{p[0]}dev-{p[1]}items")
def served(request, monkeypatch):
    devices, items = request.param
    mesh = _mesh(devices)
    x, y, known = _factors(items)
    sharded = ALSServingModel(FEATURES, implicit=True, refresh_sec=0.0, shard_items=True)
    monkeypatch.setattr(sharded, "_shard_mesh", lambda: mesh)
    _fill(sharded, x, y, known)
    single = _fill(ALSServingModel(FEATURES, implicit=True, refresh_sec=0.0), x, y, known)
    _wait_staged(sharded)
    _wait_staged(single)
    return sharded, single, mesh, (x, y, known)


def _count(name: str) -> float:
    return (metrics.registry.snapshot().get(name) or {}).get("value", 0.0)


def _rows(answer) -> list[int]:
    return [int(item[1:]) for item, _ in answer]


def test_served_path_equals_the_reference_and_the_single_device_model(served):
    """(1) and (5): concurrent known-user (indexed submit) and vector
    requests through the default batcher; every answer is the plain
    reference's id for id, its scores within 1e-6 of the score scale, and
    the single-device model's id for id; one `serving.scan.sharded.queries`
    a query, and fewer passes than queries."""
    sharded, single, _mesh_, (x, y, known) = served
    handle = sharded._ensure_y_matrix()[2]
    assert isinstance(handle, topn_ops.ShardedItemMatrix)

    def exclude(u):
        return {f"i{j}" for j in known[u]}

    go = threading.Barrier(16)

    def ask(u):
        if u < 16:
            go.wait(timeout=30)  # the first wave arrives together
        by_row = sharded.top_n_for_user(f"u{u}", HOW_MANY, exclude=exclude(u))
        by_vec = sharded.top_n(x[u], HOW_MANY, exclude=exclude(u))
        return by_row, by_vec

    before = {n: _count(n) for n in (
        "serving.scan.sharded.queries", "serving.scan.indexed.queries",
        "serving.scan.vector.queries", "serving.batcher.passes")}
    with ThreadPoolExecutor(max_workers=16) as pool:
        answers = list(pool.map(ask, range(USERS)))
    moved = {n: _count(n) - v for n, v in before.items()}

    for u, (by_row, by_vec) in enumerate(answers):
        want_rows, want_scores = als_topn.top_n(x[u], y, known[u], HOW_MANY)
        scale = float(np.max(np.abs(want_scores)))
        for answer in (by_row, by_vec):
            assert _rows(answer) == want_rows.tolist()
            got = np.asarray([s for _, s in answer])
            assert np.max(np.abs(got - want_scores)) <= 1e-6 * scale
        alone = single.top_n_for_user(f"u{u}", HOW_MANY, exclude=exclude(u))
        assert [i for i, _ in by_row] == [i for i, _ in alone]

    queries = 2 * USERS
    assert moved["serving.scan.sharded.queries"] == queries
    assert moved["serving.scan.indexed.queries"] == USERS  # by submit kind, beside it
    assert moved["serving.scan.vector.queries"] == USERS
    assert 0 < moved["serving.batcher.passes"] < queries  # passes held several rows


def test_every_device_holds_a_slice_and_none_the_whole_matrix(served):
    """(4): d non-empty slices on d devices, each [features, cols]; the
    staged users on every device of the same mesh."""
    sharded, _single, mesh, (_x, y, _known) = served
    handle = sharded._ensure_y_matrix()[2]
    d = mesh.devices.size
    shards = handle.mat_t.addressable_shards
    assert len({s.device for s in shards}) == len(shards) == d
    assert all(s.data.shape == (FEATURES, handle.cols) for s in shards)
    assert handle.mat_t.shape == (FEATURES, d * handle.cols)
    assert sum(handle.counts) == len(y) and min(handle.counts) > 0
    assert max(handle.counts) - min(handle.counts) <= 1
    assert handle.starts == tuple(int(v) for v in np.cumsum((0,) + handle.counts[:-1]))
    snap = metrics.registry.snapshot()
    assert snap["serving.scan.shards"]["value"] == d
    assert snap["serving.scan.shard.rows-max"]["value"] == max(handle.counts)
    assert snap["serving.scan.shard.rows-min"]["value"] == min(handle.counts)
    layout = topn_ops.sharded_layout(handle)
    assert layout.count("dev") == d and f"({max(handle.counts)}, {FEATURES})" in layout
    x_dev = sharded._x_matrix
    assert x_dev.sharding.is_fully_replicated and x_dev.sharding.device_set == set(mesh.devices.flat)


def test_a_row_update_reaches_its_shard_and_is_served(served, monkeypatch):
    """(3): a dirty id is scattered into the shard that holds it (no
    second upload, the other shards' data untouched) and served; a new id
    appends into the last shard's padding."""
    sharded, _single, _mesh_, (x, y, _known) = served
    uploads = []
    sound = topn_ops.upload_sharded
    monkeypatch.setattr(
        topn_ops, "upload_sharded", lambda *a, **k: uploads.append(1) or sound(*a, **k)
    )
    ids, _index, before, _h, _p = sharded._ensure_y_matrix()
    ids = list(ids)  # device row -> id: the store's order, not the ids' numbers
    old = [np.asarray(s.data) for s in before.mat_t.addressable_shards]
    q = x[7]
    for shard, start in enumerate(before.starts):
        item = ids[start + before.counts[shard] - 1]  # on the shard's last row
        sharded.set_item_vector(item, (q * (10.0 + shard)).astype(np.float32))
        assert sharded.top_n(q, 1)[0][0] == item
        after = sharded._ensure_y_matrix()[2]
        new = [np.asarray(s.data) for s in after.mat_t.addressable_shards]
        changed = [s for s in range(len(old)) if not np.array_equal(old[s], new[s])]
        assert changed == [shard]
        old = new
    sharded.set_item_vector("brand-new", (q * 99.0).astype(np.float32))
    assert sharded.top_n(q, 1)[0][0] == "brand-new"
    grown = sharded._ensure_y_matrix()[2]
    assert grown.n_items == len(y) + 1 and grown.counts[-1] == before.counts[-1] + 1
    assert uploads == []


@pytest.mark.parametrize("devices, items", [(4, 10), (4, 4099), (3, 5), (3, 20000)])
def test_the_shards_own_lists_merged_are_the_uncut_matrixs(devices, items):
    """(2): each shard's own top-k (NumPy on its rows), merged by score
    then row, is the whole matrix's top-k and is what one sharded pass
    returns; padding columns (zero vectors, score 0) never appear although
    every real score is negative; k may exceed a shard's rows."""
    rng = np.random.default_rng([devices, items])
    y = np.abs(rng.standard_normal((items, FEATURES))).astype(np.float32) + 0.1
    q = -np.abs(rng.standard_normal((5, FEATURES))).astype(np.float32)  # all scores < 0
    up = topn_ops.upload_sharded(y, _mesh(devices))
    k = min(8, items)
    assert k > min(up.counts) or items > 100
    idx, vals = topn_ops.top_k_scores_batch(up, q, k)
    scores = q.astype(np.float64) @ y.astype(np.float64).T
    for b in range(len(q)):
        parts = []
        for start, count in zip(up.starts, up.counts):
            local = np.argsort(-scores[b, start : start + count], kind="stable")[:k] + start
            parts.extend(local.tolist())
        merged = sorted(parts, key=lambda r: (-scores[b, r], r))[:k]
        whole = np.argsort(-scores[b], kind="stable")[:k].tolist()
        assert merged == whole == idx[b].tolist()
        assert np.all(idx[b] < items) and np.all(vals[b] < 0)
        np.testing.assert_allclose(vals[b], scores[b, idx[b]], rtol=1e-5)


def test_equal_scores_on_two_shards_resolve_to_the_lower_row():
    """The cross-chip merge breaks ties as one chip does: lower row first."""
    y = np.zeros((40, FEATURES), np.float32)
    y[[3, 17, 29, 38], 0] = 2.0  # one on each of four shards, equal scores
    y[[5, 25], 0] = 1.0
    q = np.zeros((1, FEATURES), np.float32)
    q[0, 0] = 1.0
    sharded, _ = topn_ops.top_k_scores_batch(topn_ops.upload_sharded(y, _mesh(4)), q, 6)
    alone, _ = topn_ops.top_k_scores_batch(topn_ops.upload(y), q, 6)
    assert sharded[0].tolist() == alone[0].tolist() == [3, 17, 29, 38, 5, 25]
