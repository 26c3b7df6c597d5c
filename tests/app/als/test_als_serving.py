"""ALS serving model + endpoint tests over real HTTP
(reference: the 34 per-endpoint tests under app/oryx-app-serving/src/test/
.../als/ and TestALSModelFactory)."""

import json
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from oryx_tpu import bus
from oryx_tpu.app import pmml as app_pmml
from oryx_tpu.app.als.serving_model import ALSServingModel, ALSServingModelManager
from oryx_tpu.bus.core import KeyMessage
from oryx_tpu.common import config as C, pmml as pmml_io
from oryx_tpu.common.text import join_json
from oryx_tpu.serving.layer import ServingLayer

# hand-built model: users/items on clean axes
USER_VECS = {"U0": [1.0, 0.0], "U1": [0.0, 1.0], "U2": [0.7, 0.7]}
ITEM_VECS = {"I0": [1.0, 0.0], "I1": [0.0, 1.0], "I2": [0.9, 0.1], "I3": [0.5, 0.5]}
KNOWN = {"U0": ["I0"], "U1": ["I1", "I3"]}


def build_model(refresh_sec=0.0) -> ALSServingModel:
    m = ALSServingModel(2, implicit=True, refresh_sec=refresh_sec)
    for u, v in USER_VECS.items():
        m.set_user_vector(u, np.asarray(v, dtype=np.float32))
    for i, v in ITEM_VECS.items():
        m.set_item_vector(i, np.asarray(v, dtype=np.float32))
    for u, items in KNOWN.items():
        m.add_known_items(u, items)
    return m


# ---------------------------------------------------------------------------
# model unit tests
# ---------------------------------------------------------------------------


def test_top_n_excludes_and_orders():
    m = build_model()
    res = m.top_n(np.asarray([1.0, 0.0], dtype=np.float32), 2)
    assert [r[0] for r in res] == ["I0", "I2"]
    res2 = m.top_n(np.asarray([1.0, 0.0], dtype=np.float32), 2, exclude={"I0"})
    assert [r[0] for r in res2] == ["I2", "I3"]


def test_top_n_reflects_updates_after_refresh():
    m = build_model()
    m.top_n(np.asarray([1.0, 0.0], dtype=np.float32), 1)
    m.set_item_vector("I9", np.asarray([5.0, 0.0], dtype=np.float32))
    res = m.top_n(np.asarray([1.0, 0.0], dtype=np.float32), 1)
    assert res[0][0] == "I9"


def test_fraction_loaded_against_expected():
    m = ALSServingModel(2, True)
    m.set_expected({"U0", "U1"}, {"I0", "I1"})
    assert m.get_fraction_loaded() == 0.0
    m.set_user_vector("U0", np.zeros(2, dtype=np.float32))
    m.set_item_vector("I0", np.zeros(2, dtype=np.float32))
    assert m.get_fraction_loaded() == pytest.approx(0.5)


def test_yty_solver_invalidated_on_write():
    m = build_model()
    s1 = m.get_yty_solver()
    assert m.get_yty_solver() is s1  # cached
    m.set_item_vector("I5", np.asarray([0.3, 0.3], dtype=np.float32))
    assert m.get_yty_solver() is not s1


def _counts():
    from oryx_tpu.common import metrics

    snap = metrics.registry.snapshot()
    return {
        "device": snap["serving.yty.builds.device"]["value"],
        "host": snap["serving.yty.builds.host"]["value"],
        "seconds": snap["serving.yty.build.seconds"].get("count", 0),
        "foldin": snap["serving.foldin.requests"]["value"],
        "items": snap["serving.foldin.items"]["value"],
        "foldin_seconds": snap["serving.foldin.seconds"].get("count", 0),
    }


def test_yty_comes_from_the_device_matrix_and_is_rebuilt_from_the_refreshed_one(monkeypatch):
    """`YtY` is the Gram matrix of the device copy (not of the store), and
    a write to `Y` rebuilds it from that copy REFRESHED with the write,
    whatever the refresh interval says: one build, one count, each time."""
    m = build_model(refresh_sec=3600.0)  # the copy would otherwise wait an hour
    monkeypatch.setattr(
        m.y, "get_vtv", lambda: pytest.fail("the host store's loop ran beside a device copy")
    )
    before = _counts()
    s1 = m.get_yty_solver()
    y = np.asarray(list(ITEM_VECS.values()), dtype=np.float64)
    np.testing.assert_allclose(s1.matrix, y.T @ y, atol=1e-6)
    assert m.get_yty_solver() is s1
    now = _counts()
    assert now["device"] - before["device"] == 1 and now["host"] == before["host"]
    assert now["seconds"] - before["seconds"] == 1
    # a write: a new item and a rewritten one
    m.set_item_vectors(["I9", "I0"], np.asarray([[0.3, -2.0], [4.0, 0.0]], dtype=np.float32))
    s2 = m.get_yty_solver()
    assert s2 is not s1
    y2 = np.asarray([[4.0, 0.0], [0.0, 1.0], [0.9, 0.1], [0.5, 0.5], [0.3, -2.0]])
    np.testing.assert_allclose(s2.matrix, y2.T @ y2, atol=1e-5)
    assert _counts()["device"] - before["device"] == 2
    # and the scan reads the same refreshed copy
    assert m.top_n(np.asarray([0.0, -1.0], dtype=np.float32), 1)[0][0] == "I9"
    # a rotation drops it too
    m.retain_recent_and_item_ids({"I0", "I1", "I2", "I3", "I9"})
    assert m.get_yty_solver() is not s2


def test_a_model_with_no_usable_device_copy_answers_by_the_host_store():
    """An int8 item matrix holds codes, not the rows: `YtY` stays the host
    store's double-precision loop, counted as such; and a model with no
    items has no solver."""
    assert ALSServingModel(2, implicit=True).get_yty_solver() is None
    m = ALSServingModel(2, implicit=True, refresh_sec=0.0, score_dtype="int8")
    for i, v in ITEM_VECS.items():
        m.set_item_vector(i, np.asarray(v, dtype=np.float32))
    before = _counts()
    solver = m.get_yty_solver()
    y = np.asarray(list(ITEM_VECS.values()), dtype=np.float64)
    np.testing.assert_allclose(solver.matrix, y.T @ y, atol=1e-12)
    now = _counts()
    assert now["host"] - before["host"] == 1 and now["device"] == before["device"]
    assert now["seconds"] - before["seconds"] == 1


# ---------------------------------------------------------------------------
# manager consume protocol
# ---------------------------------------------------------------------------


def model_message(x_ids, y_ids, features=2):
    root = pmml_io.build_skeleton_pmml()
    app_pmml.add_extension(root, "features", features)
    app_pmml.add_extension(root, "implicit", "true")
    app_pmml.add_extension_content(root, "XIDs", list(x_ids))
    app_pmml.add_extension_content(root, "YIDs", list(y_ids))
    return pmml_io.to_string(root)


def serving_config(broker_loc):
    return C.get_default().with_overlay(
        f"""
        oryx {{
          input-topic.broker = "{broker_loc}"
          update-topic.broker = "{broker_loc}"
          serving {{
            api.port = 0
            model-manager-class = "oryx_tpu.app.als.serving_model:ALSServingModelManager"
            application-resources = "oryx_tpu.app.als.endpoints"
          }}
        }}
        """
    )


def test_manager_consume_and_known_items():
    mgr = ALSServingModelManager(serving_config("inproc://unused1"))
    mgr.consume(iter([
        KeyMessage("MODEL", model_message(["U0"], ["I0"])),
        KeyMessage("UP", join_json(["Y", "I0", [1.0, 0.0]])),
        KeyMessage("UP", join_json(["X", "U0", [1.0, 0.0], ["I0"]])),
    ]))
    model = mgr.get_model()
    assert model.get_fraction_loaded() == 1.0
    assert model.get_known_items("U0") == {"I0"}


# ---------------------------------------------------------------------------
# HTTP endpoint tests
# ---------------------------------------------------------------------------


def http(method, url, body=None, headers=None):
    req = urllib.request.Request(url, data=body, method=method, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=5) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


@pytest.fixture(scope="module")
def server():
    broker_loc = "inproc://als-serve"
    broker = bus.get_broker(broker_loc)
    layer = ServingLayer(serving_config(broker_loc))
    layer.start()
    with broker.producer("OryxUpdate") as p:
        p.send("MODEL", model_message(list(USER_VECS), list(ITEM_VECS)))
        for i, v in ITEM_VECS.items():
            p.send("UP", join_json(["Y", i, v]))
        for u, v in USER_VECS.items():
            p.send("UP", join_json(["X", u, v, KNOWN.get(u, [])]))
    base = f"http://127.0.0.1:{layer.port}"
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if http("GET", f"{base}/ready")[0] == 200:
            break
        time.sleep(0.05)
    # let the serving model's refresh window elapse so Y matrix is current
    time.sleep(0.3)
    yield base, broker
    layer.close()


def get_json(base, path):
    status, body, _ = http("GET", base + path)
    return status, (json.loads(body) if body and status == 200 else body)


def test_recommend(server):
    base, _ = server
    status, recs = get_json(base, "/recommend/U0")
    assert status == 200
    ids = [r["id"] for r in recs]
    assert "I0" not in ids  # known item excluded
    assert ids[0] == "I2"  # closest to [1,0] after I0
    # considerKnownItems brings I0 back on top
    _, recs2 = get_json(base, "/recommend/U0?considerKnownItems=true&howMany=2")
    assert [r["id"] for r in recs2][0] == "I0"
    # unknown user
    assert get_json(base, "/recommend/NOPE")[0] == 404
    # paging
    _, recs3 = get_json(base, "/recommend/U0?howMany=1&offset=1")
    assert [r["id"] for r in recs3] == [ids[1]]


def test_recommend_csv(server):
    base, _ = server
    status, body, headers = http("GET", f"{base}/recommend/U0", headers={"Accept": "text/csv"})
    assert status == 200
    assert headers["Content-Type"] == "text/csv"
    first = body.decode().splitlines()[0].split(",")
    assert first[0] == "I2" and float(first[1]) > 0


def test_recommend_to_many_and_anonymous(server):
    base, _ = server
    status, recs = get_json(base, "/recommendToMany/U0/U1")
    assert status == 200
    ids = [r["id"] for r in recs]
    assert "I0" not in ids and "I1" not in ids and "I3" not in ids  # union of known
    status, recs = get_json(base, "/recommendToAnonymous/I0=2.0/I2")
    assert status == 200
    assert all(r["id"] not in ("I0", "I2") for r in recs)
    assert get_json(base, "/recommendToAnonymous/NOPE")[0] == 400


def test_the_fold_in_s_instruments_are_fed_once_a_request_and_once_a_build(server):
    """Every endpoint that folds items into a user vector observes once a
    request: its seconds, itself, and the items its URL named (an unknown
    one included); the solver is built once for all of them."""
    base, _ = server
    get_json(base, "/recommendToAnonymous/I0")  # whoever is first builds the solver
    before = _counts()
    for path, items in [
        ("/recommendToAnonymous/I0=2.0/I2", 2),
        ("/recommendToAnonymous/I1/NOPE/I3", 3),
        ("/estimateForAnonymous/I2/I0=1.0", 1),
        ("/recommendWithContext/U0/I1/I3", 2),
    ]:
        assert get_json(base, path)[0] == 200, path
        now = _counts()
        assert now["foldin"] - before["foldin"] == 1, path
        assert now["foldin_seconds"] - before["foldin_seconds"] == 1, path
        assert now["items"] - before["items"] == items, path
        assert (now["device"], now["host"], now["seconds"]) == (
            before["device"], before["host"], before["seconds"]), path
        before = now
    # a request that does not fold in feeds none of them
    assert get_json(base, "/recommend/U0")[0] == 200
    assert _counts() == before


def test_similarity_family(server):
    base, _ = server
    status, sims = get_json(base, "/similarity/I0/I1")
    assert status == 200
    assert all(s["id"] not in ("I0", "I1") for s in sims)
    # I3 = [.5,.5] equidistant: avg cosine to I0,I1 higher than I2's
    assert sims[0]["id"] == "I3"
    status, vals = get_json(base, "/similarityToItem/I0/I2/I1")
    assert status == 200
    assert vals[0] > 0.9 and vals[1] == pytest.approx(0.0, abs=1e-6)


def test_estimates(server):
    base, _ = server
    status, vals = get_json(base, "/estimate/U0/I0/I1/I2")
    assert status == 200
    assert vals[0] == pytest.approx(1.0, abs=1e-5)
    assert vals[1] == pytest.approx(0.0, abs=1e-5)
    status, val = get_json(base, "/estimateForAnonymous/I2/I0=1.0")
    assert status == 200
    assert isinstance(val, float)


def test_because_known_surprising(server):
    base, _ = server
    status, why = get_json(base, "/because/U1/I3")
    assert status == 200
    assert why[0]["id"] in ("I1", "I3")
    status, known = get_json(base, "/knownItems/U1")
    assert known == ["I1", "I3"]
    status, sur = get_json(base, "/mostSurprising/U1")
    assert status == 200
    # I1 fits U1 perfectly so the surprising one is I3
    assert sur[0]["id"] == "I3"


def test_popularity(server):
    base, _ = server
    status, users = get_json(base, "/mostActiveUsers")
    assert [u["id"] for u in users][0] == "U1"  # 2 known items
    status, items = get_json(base, "/mostPopularItems")
    assert {i["id"] for i in items} == {"I0", "I1", "I3"}
    status, rep = get_json(base, "/popularRepresentativeItems")
    assert status == 200 and rep


def test_all_ids(server):
    base, _ = server
    assert get_json(base, "/item/allIDs")[1] == sorted(ITEM_VECS)
    assert get_json(base, "/user/allIDs")[1] == sorted(USER_VECS)


def test_pref_and_ingest_write_input(server):
    base, broker = server
    tail = broker.consumer("OryxInput", from_beginning=True)
    status, _, _ = http("POST", f"{base}/pref/U0/I1", body=b"2.5")
    assert status == 204
    status, _, _ = http("DELETE", f"{base}/pref/U0/I0")
    assert status == 204
    status, _, _ = http("POST", f"{base}/ingest", body=b"U9,I9,1.0\nU8,I8,2.0\n")
    assert status == 204
    msgs = tail.poll(max_records=10, timeout=2.0)
    assert sorted(m.message for m in msgs) == [
        "U0,I0,", "U0,I1,2.5", "U8,I8,2.0", "U9,I9,1.0",
    ]
    # bad pref value
    assert http("POST", f"{base}/pref/U0/I1", body=b"abc")[0] == 400


def test_ingest_gzip(server):
    import gzip as gz

    base, broker = server
    tail = broker.consumer("OryxInput")
    body = gz.compress(b"UG,IG,1.0\n")
    status, _, _ = http(
        "POST", f"{base}/ingest", body=body, headers={"Content-Encoding": "gzip"}
    )
    assert status == 204
    msgs = tail.poll(timeout=2.0)
    assert [m.message for m in msgs] == ["UG,IG,1.0"]


def test_console_served_at_root(server):
    base, _ = server
    status, body, headers = http("GET", f"{base}/")
    assert status == 200
    assert headers["Content-Type"] == "text/html"
    assert headers["X-Frame-Options"] == "SAMEORIGIN"
    assert b"ALS serving console" in body
    status2, body2, _ = http("GET", f"{base}/index.html")
    assert status2 == 200 and body2 == body


def test_score_dtype_config_reaches_model():
    """oryx.als.serving.score-dtype plumbs from config into the model's
    device upload choice (bfloat16 halves serving HBM traffic)."""
    from oryx_tpu.app.als.serving_model import ALSServingModelManager
    from oryx_tpu.common import config as C

    cfg = C.get_default().with_overlay(
        'oryx.als.serving.score-dtype = "bfloat16"\noryx.als.implicit = true'
    )
    mgr = ALSServingModelManager(cfg)
    assert mgr.score_dtype == "bfloat16"
    model = ALSServingModel(4, True, score_dtype="bfloat16")
    model.set_item_vector("i1", np.array([1, 0, 0, 0], np.float32))
    model.set_user_vector("u1", np.array([1, 0, 0, 0], np.float32))
    out = model.top_n(np.array([1, 0, 0, 0], np.float32), 1)
    assert out and out[0][0] == "i1"


def test_incremental_refresh_avoids_full_reupload(monkeypatch):
    """A small dirty set scatter-updates the device-resident Y instead of
    re-uploading the whole matrix (VERDICT r3 #7); rotation forces a
    genuine rebuild."""
    from oryx_tpu.app.als import serving_model as sm_mod
    from oryx_tpu.ops import topn as topn_ops

    # the padded streaming layout is the TPU serving path; force it here
    # (interpreter on CPU) so append-into-padding is exercised everywhere
    monkeypatch.setattr(topn_ops, "_default_streaming", lambda: True)
    m = ALSServingModel(2, implicit=True, refresh_sec=0.0)
    for j in range(200):
        m.set_item_vector(f"i{j}", np.asarray([1.0, float(j % 7)], np.float32))
    m.top_n(np.asarray([1.0, 0.0], np.float32), 1)  # first (full) build

    uploads = []
    real_upload = topn_ops.upload
    monkeypatch.setattr(
        sm_mod.topn_ops, "upload", lambda *a, **k: uploads.append(1) or real_upload(*a, **k)
    )

    # update one existing vector: no upload, new value visible
    m.set_item_vector("i5", np.asarray([50.0, 0.0], np.float32))
    res = m.top_n(np.asarray([1.0, 0.0], np.float32), 1)
    assert res[0][0] == "i5" and uploads == []

    # brand-new item appends into the padded region: still no upload
    m.set_item_vector("brand-new", np.asarray([99.0, 0.0], np.float32))
    res = m.top_n(np.asarray([1.0, 0.0], np.float32), 1)
    assert res[0][0] == "brand-new" and uploads == []

    # rotation forces a full rebuild. Writes since the last rotation are
    # retained by design (retainRecentAndIds), so rotate twice with no
    # writes in between: the second pass keeps exactly `keep`.
    keep = {f"i{j}" for j in range(100)}
    m.retain_recent_and_item_ids(keep)
    assert uploads == []  # rebuild is lazy until the next scoring call
    m.retain_recent_and_item_ids(keep)
    res = m.top_n(np.asarray([1.0, 0.0], np.float32), 3)
    assert uploads == [1]
    assert all(r[0] in keep for r in res)
    assert sorted(m.all_item_ids()) == sorted(keep)


def test_shard_items_serving_scan_over_mesh():
    """shard-items=true: the Y cache row-shards over all local devices
    and top_n answers match the single-device model exactly."""
    single = build_model()
    sharded = ALSServingModel(2, implicit=True, refresh_sec=0.0, shard_items=True)
    for u, v in USER_VECS.items():
        sharded.set_user_vector(u, np.asarray(v, dtype=np.float32))
    for i, v in ITEM_VECS.items():
        sharded.set_item_vector(i, np.asarray(v, dtype=np.float32))
    q = np.asarray([1.0, 0.0], dtype=np.float32)
    assert sharded.top_n(q, 2) == single.top_n(q, 2)
    assert sharded.top_n(q, 2, exclude={"I0"}) == single.top_n(q, 2, exclude={"I0"})
    from oryx_tpu.ops.topn import ShardedItemMatrix

    assert isinstance(sharded._ensure_y_matrix()[2], ShardedItemMatrix)
    # streaming UP updates still land (full rebuild per refresh)
    sharded.set_item_vector("I9", np.asarray([7.0, 0.0], np.float32))
    assert sharded.top_n(q, 1)[0][0] == "I9"


def test_serving_consume_blocks_matches_per_record():
    """Serving columnar consume lands identical state to per-record —
    including known-item lists, empty lists, escaped ids, and a MODEL
    rotation mid-stream."""
    from oryx_tpu.common.records import RecordBlock

    msgs = [
        KeyMessage("MODEL", model_message(["U0", 'u"q'], ["I0", "I1"])),
        KeyMessage("UP", '["Y","I0",[1.0,0.5]]'),
        KeyMessage("UP", '["Y","I1",[0.5,1.0],["whoever"]]'),  # Y extras ignored
        KeyMessage("UP", '["X","U0",[1.0,0.0],["I0","I1"]]'),
        KeyMessage("UP", '["X","u\\"q",[0.25,0.25],["I0"]]'),  # escaped id: slow
        KeyMessage("UP", '["X","U2",[0.0,1.0],[]]'),  # empty known list
        KeyMessage("MODEL", model_message(["U0"], ["I0"])),
        KeyMessage("UP", '["Y","I0",[9.0,9.0]]'),
    ]
    per = ALSServingModelManager(serving_config("inproc://unused-a"))
    per.consume(iter(msgs))
    blk = ALSServingModelManager(serving_config("inproc://unused-b"))
    blk.consume_blocks(iter([RecordBlock.from_key_messages(msgs)]))
    for mgr in (per, blk):
        m = mgr.get_model()
        np.testing.assert_array_equal(m.get_item_vector("I0"), [9.0, 9.0])
        np.testing.assert_array_equal(m.get_user_vector("U0"), [1.0, 0.0])
        np.testing.assert_array_equal(m.get_user_vector('u"q'), [0.25, 0.25])
        assert m.get_known_items("U0") == {"I0", "I1"}
        assert m.get_known_items('u"q') == {"I0"}
        assert m.get_known_items("U2") == set()
    assert per.get_model().y.size() == blk.get_model().y.size()
    assert per.get_model().x.size() == blk.get_model().x.size()


def test_consume_blocks_slow_fast_ordering_same_id():
    """A slow-path record for an id followed by a fast-path record for the
    same id in one block must end with the NEWER vector (the slow record
    flushes in stream position, not after the batch)."""
    from oryx_tpu.common.records import RecordBlock

    msgs = [
        KeyMessage("MODEL", model_message(["U7"], ["I0"])),
        # older record for U7 takes the slow path (escaped known item)
        KeyMessage("UP", '["X","U7",[1.0,2.0],["a\\"b"]]'),
        # newer record for U7 takes the fast path
        KeyMessage("UP", '["X","U7",[3.0,4.0],[]]'),
    ]
    blk = ALSServingModelManager(serving_config("inproc://unused-ord"))
    blk.consume_blocks(iter([RecordBlock.from_key_messages(msgs)]))
    np.testing.assert_array_equal(blk.get_model().get_user_vector("U7"), [3.0, 4.0])
    assert blk.get_model().get_known_items("U7") == {'a"b'}


def test_top_n_for_user_index_submit_and_freshness(monkeypatch):
    """Device-staged users serve /recommend via index submit with results
    identical to the vector path; a user updated since the last X refresh
    (or unknown) falls back so answers are never staler than the vector
    path's."""
    import types

    import numpy as np

    import oryx_tpu.app.als.serving_model as sm
    from oryx_tpu.app.als.serving_model import ALSServingModel

    calls = {"indexed": 0, "vector": 0}
    orig_i, orig_v = sm.score_indexed_default, sm.score_default
    sm.score_indexed_default = lambda *a, **k: (
        calls.__setitem__("indexed", calls["indexed"] + 1),
        orig_i(*a, **k),
    )[1]
    sm.score_default = lambda *a, **k: (
        calls.__setitem__("vector", calls["vector"] + 1),
        orig_v(*a, **k),
    )[1]
    try:
        gen = np.random.default_rng(2)
        m = ALSServingModel(4, True, refresh_sec=0.0)
        m.set_user_vectors(
            [f"u{i}" for i in range(20)], gen.standard_normal((20, 4)).astype(np.float32)
        )
        m.set_item_vectors(
            [f"i{i}" for i in range(50)], gen.standard_normal((50, 4)).astype(np.float32)
        )
        # the first request triggers the background restage and serves
        # via the vector path; once staged, requests go indexed
        m.top_n_for_user("u3", 5)
        assert calls == {"indexed": 0, "vector": 1}
        m._x_restage_thread.join(30)
        assert m._x_matrix is not None and not m._x_building and not m._x_dirty
        r_idx = m.top_n_for_user("u3", 5)
        assert calls == {"indexed": 1, "vector": 1}
        r_vec = m.top_n(m.get_user_vector("u3"), 5)
        assert [i for i, _ in r_idx] == [i for i, _ in r_vec]
        np.testing.assert_allclose(
            [v for _, v in r_idx], [v for _, v in r_vec], rtol=1e-5
        )
        assert m.top_n_for_user("nobody", 3) is None  # unknown -> 404 upstream

        # staleness: long refresh interval, then update a staged user —
        # the stale device row must NOT serve the request
        m2 = ALSServingModel(4, True, refresh_sec=999.0)
        m2.set_user_vectors(
            [f"u{i}" for i in range(5)], gen.standard_normal((5, 4)).astype(np.float32)
        )
        m2.set_item_vectors(
            [f"i{i}" for i in range(9)], gen.standard_normal((9, 4)).astype(np.float32)
        )
        # triggers the background X restage, however young the machine's
        # monotonic clock is beside refresh_sec (a machine up for 5 s)
        monkeypatch.setattr(sm, "time", types.SimpleNamespace(monotonic=lambda: 5.0))
        assert len(m2.top_n_for_user("u1", 3)) == 3
        m2._x_restage_thread.join(30)
        assert m2._x_matrix is not None and not m2._x_building and not m2._x_dirty
        base = dict(calls)
        fresh_vec = gen.standard_normal(4).astype(np.float32)
        m2.set_user_vector("u1", fresh_vec)  # dirty; refresh not due
        r_after = m2.top_n_for_user("u1", 3)
        assert calls["vector"] == base["vector"] + 1  # fell back
        r_direct = m2.top_n(fresh_vec, 3)
        assert [i for i, _ in r_after] == [i for i, _ in r_direct]
        # an untouched user still rides the staged matrix
        m2.top_n_for_user("u2", 3)
        assert calls["indexed"] == base["indexed"] + 1
    finally:
        sm.score_indexed_default = orig_i
        sm.score_default = orig_v


def test_device_x_append_rotation_and_disabled_tracking():
    """Device-X lifecycle: new users append into padded capacity (no full
    re-upload per trickle), rotation disables index submit until the
    rebuild lands (removed users 404 like the vector path), and disabled
    staging never accumulates dirty-id state."""
    import numpy as np

    from oryx_tpu.app.als.serving_model import ALSServingModel

    gen = np.random.default_rng(7)
    m = ALSServingModel(4, True, refresh_sec=0.0)
    m.set_user_vectors(
        [f"u{i}" for i in range(8)], gen.standard_normal((8, 4)).astype(np.float32)
    )
    m.set_item_vectors(
        [f"i{i}" for i in range(9)], gen.standard_normal((9, 4)).astype(np.float32)
    )
    assert m.top_n_for_user("u1", 3)
    m._x_restage_thread.join(30)
    assert m.top_n_for_user("u1", 3)  # staged now: rides the device matrix
    cap = m._x_capacity
    assert cap >= 8
    m.set_user_vector("uNEW", gen.standard_normal(4).astype(np.float32))
    assert m.top_n_for_user("uNEW", 3)
    assert m._x_capacity == cap  # appended via scatter, not rebuilt
    assert m._x_index["uNEW"] == 8
    # rotation drains the store (two rounds: first keeps recent writes)
    m.retain_recent_and_user_ids(set())
    m.retain_recent_and_user_ids(set())
    assert m.get_user_vector("u1") is None
    assert m.top_n_for_user("u1", 3) is None  # stale staged row must not serve
    # staging disabled: no dirty-id accumulation
    m2 = ALSServingModel(4, True, device_user_matrix=False)
    m2.set_user_vectors(
        [f"u{i}" for i in range(5)], gen.standard_normal((5, 4)).astype(np.float32)
    )
    assert not m2._x_dirty_ids


def test_rotation_during_x_restage_discards_stale_snapshot():
    """A MODEL rotation landing while the out-of-lock X restage is
    uploading must invalidate that build: the pre-rotation snapshot is
    discarded at swap time (epoch check) and removed users keep 404ing
    exactly like the vector path."""
    import threading
    import time as _time

    import numpy as np

    from oryx_tpu.app.als.serving_model import ALSServingModel

    gen = np.random.default_rng(1)
    m = ALSServingModel(4, True, refresh_sec=0.0)
    m.set_user_vectors(
        [f"u{i}" for i in range(10)], gen.standard_normal((10, 4)).astype(np.float32)
    )
    m.set_item_vectors(
        [f"i{i}" for i in range(8)], gen.standard_normal((8, 4)).astype(np.float32)
    )
    orig_to_matrix = m.x.to_matrix

    def slow_to_matrix():
        out = orig_to_matrix()
        _time.sleep(0.5)  # rotation lands while "uploading"
        return out

    m.x.to_matrix = slow_to_matrix
    t = threading.Thread(target=lambda: m.top_n_for_user("u1", 3))
    t.start()
    _time.sleep(0.15)
    m.retain_recent_and_user_ids(set())  # first keeps recent writes
    m.retain_recent_and_user_ids(set())  # second drains the store
    t.join()
    restage = m._x_restage_thread
    if restage is not None:
        restage.join(30)  # the build itself now runs on a daemon thread
    # whichever way the interleaving lands (swap discarded by the epoch
    # check, or the build won the race and rotation invalidated after),
    # the rebuild must be pending and the removed user must 404 (None) —
    # never served off a stale staged row
    assert m._x_full_rebuild
    assert m.get_user_vector("u1") is None
    assert m.top_n_for_user("u1", 3) is None
