"""The similar-items cell `als250-similarity-open` (configuration
`als-250f-5m-f32-sim`: `/similarity/i<id>` by the vector submit and the
scan's cosine variant):

- the command end to end on the CPU at a tiny size, the configuration
  ADDED to a temporary copy of the benchmark: every window answer by an
  uploaded query vector, scored by cosine, `correct: true`; the two
  controls (the program's bfloat16 item matrix; a path that serves the
  queried item itself) print `correct: false`;
- the plain reference (benchmark/reference/als_similarity.py) against the
  endpoint over HTTP (one item, three items, `offset`, an unknown item),
  and against the judged form: the builder's unit rows through
  `als_topn.judge`;
- the two new layer-metric files on a recorded snapshot.

A CPU run's numbers are read for their shape only."""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark import testing
from benchmark.builders import loadtest_als, loadtest_als_similarity
from benchmark.drivers import httpclient as hc
from benchmark.reductions import counter_ratio
from benchmark.reference import als_similarity, als_topn
from benchmark.spec import ROOT, Spec

CELL = "als250-similarity-open"
CONFIG = "als-250f-5m-f32-sim"
TINY, TINY_CONFIG, TINY_MIX = "tiny-similarity-open", "tiny-als-16f-sim", "tiny-sim-open"
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
SIM_METRICS = [m["name"] for m in DOC["per_layer"] if CELL in m["workloads"]]
# what only a device trace with a named kernel in it can give
DEVICE_TRACE = {"scan_roofline.open", "scan_kernel_ms_per_pass.open", "scan_rows_per_pass.open"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """benchmark/testing.py's copy, plus a tiny similar-items configuration,
    its mix and its cell, added as files and entries."""
    root = testing.make_copy(tmp_path_factory.mktemp("bench_sim"))
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / f"{CONFIG}.json").read_text())
    cfg.update(name=TINY_CONFIG, features=16, items=3000, users=400, source="test",
               reduced=["items", "users"])
    (bench / "configs" / f"{TINY_CONFIG}.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "similarity-open.json").read_text())
    mix.update(name=TINY_MIX, warm_seconds=1, workers=16, warm_batch_buckets=[8],
               check_users=16, check_sample_every=10, trace_seconds=1)
    (bench / "traffic" / f"{TINY_MIX}.json").write_text(json.dumps(mix))
    (bench / "cells" / f"{TINY}.json").write_text(json.dumps({"rate_per_s": 60}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": TINY_CONFIG, "source": "test", "reduced": ["items", "users"],
                           "file": f"benchmark/configs/{TINY_CONFIG}.json", "why": "tier-1"})
    doc["workloads"].append({"name": TINY, "config": TINY_CONFIG, "traffic": TINY_MIX,
                             "chips": 1, "why": "tier-1"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return root


def _run(root, seed, trace=False, **kw):
    return bench_run.run_cell(Spec(root), TINY, seed, 2.0, trace, require_chip=False, **kw)


# -- the command, end to end ---------------------------------------------------------------


def test_every_answer_goes_by_an_uploaded_vector_and_the_cosine_variant(copy, monkeypatch):
    monkeypatch.setattr(Spec, "peaks", lambda self, kind: PEAKS)
    out, lines = _run(copy, 2**31 + 33, trace=True)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 120
    got = out["metrics"]
    assert got["indexed_submit_pct.open"]["value"] == 0.0
    assert got["cosine_submit_pct.sim"]["value"] == 100.0
    assert got["compiles_in_window.open"]["value"] == 0.0  # the warmed programs are the served ones
    assert got["submit_mean_ms.open"]["value"] > 0.0
    # every metric of the cell a run without a TPU plane can read is on the line ...
    assert set(SIM_METRICS) - DEVICE_TRACE <= set(got)
    # ... and a device-trace reader that finds no named kernel returns nothing
    assert not DEVICE_TRACE & set(got)
    text = "\n".join(lines)
    assert "check: known_items_served = 0 " in text  # the queried item, never served
    assert "setup: factors" in text and "unit_rows" in text


def test_the_bfloat16_item_matrix_fails_the_check(copy):
    """The control: the program's own lower-precision matrix, same factors."""
    out, lines = _run(copy, 34, score_dtype="bfloat16")
    assert out["correct"] is False and out["failed"] == 0
    line = next(x for x in lines if "score_err_of_scale" in x)
    assert "FAIL" in line and float(line.split("=")[1].split()[0]) > 1e-4


def test_a_path_that_serves_the_queried_item_itself_fails_the_check(copy, monkeypatch):
    """The control of the exclusion: the item asked about is its own
    nearest neighbour (cosine 1), and a handler that forgets to leave it
    out serves it first, well-formed and at full quality."""
    from oryx_tpu.app.als.serving_model import ALSServingModel

    sound = ALSServingModel.top_n

    def forgetful(self, query, how_many, exclude=None, **kw):
        return sound(self, query, how_many, exclude=set(), **kw)

    monkeypatch.setattr(ALSServingModel, "top_n", forgetful)
    out, lines = _run(copy, 35)
    assert out["correct"] is False and out["failed"] == 0
    line = next(x for x in lines if "known_items_served" in x)
    assert "FAIL" in line and float(line.split("=")[1].split()[0]) >= 1


# -- the reference against the endpoint ----------------------------------------------------


@pytest.fixture(scope="module")
def served(copy):
    """A Session up on the tiny cell, and the factors as they were drawn
    (the builder hands the check unit rows; the reference starts from the
    raw ones, as the program does)."""
    seed = 36
    session = bench_run.Session(Spec(copy), TINY, seed, require_chip=False)
    _x, y, _known = loadtest_als.make_arrays(session.cell.config, seed)
    conn = hc.Connection("127.0.0.1", session.layer.port, 60.0)
    yield conn, y
    conn.close()
    session.close()


@pytest.mark.parametrize("items, how_many, offset", [
    ((7,), 10, 0),
    ((0, 1234, 2999), 10, 0),
    ((5, 6), 4, 3),
], ids=["one-item", "three-items", "offset"])
def test_the_endpoint_agrees_with_the_plain_reference(served, items, how_many, offset):
    conn, y = served
    path = "/similarity/" + "/".join(f"i{i}" for i in items)
    status, shed, body = conn.get(f"{path}?howMany={how_many}&offset={offset}")
    assert status == 200 and shed is None
    pairs = hc.parse_answer(body)
    rows, scores = als_similarity.most_similar(y, items, how_many + offset)
    assert [int(i[1:]) for i, _ in pairs] == rows[offset:].tolist()
    assert not set(items) & {int(i[1:]) for i, _ in pairs}
    np.testing.assert_allclose([s for _, s in pairs], scores[offset:], rtol=0, atol=2e-6)


def test_an_unknown_item_is_a_404(served):
    conn, _ = served
    status, _shed, _body = conn.get("/similarity/i999999?howMany=10")
    assert status == 404
    # an unknown item beside a known one is passed over, not an error
    status, _shed, body = conn.get("/similarity/i999999/i7?howMany=10")
    assert status == 200 and len(hc.parse_answer(body)) == 10


# -- the reference against the judged form -------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_builders_unit_rows_judged_by_als_topn_are_the_reference(seed):
    """What benchmark/check.py compares (`x[u] . y` over the builder's unit
    rows, `known[u] = [u]`) is `most_similar` for one queried item: the
    same ten items, the same scores, nothing left out, nothing known."""
    config = {"users": 300, "items": 2500, "features": 16, "known_items_per_user": 3}
    _x, y, _known = loadtest_als.make_arrays(config, seed)
    unit = als_similarity.unit_rows(y, block=512)
    np.testing.assert_allclose(np.linalg.norm(unit.astype(np.float64), axis=1), 1.0, atol=1e-6)
    queried = [0, 17, 299]
    want = [als_similarity.most_similar(y, [q], 10) for q in queried]
    for q, (rows, scores) in zip(queried, want):
        got_rows, got_scores = als_topn.top_n(unit[q], unit, [q], 10)
        assert got_rows.tolist() == rows.tolist()
        np.testing.assert_allclose(got_scores, scores, rtol=0, atol=1e-6)
    judged = als_topn.judge(
        unit[queried], unit, np.asarray(queried)[:, None],
        [rows for rows, _ in want], [scores for _, scores in want], block=1024,
    )
    assert max(judged["score_err"]) < 1e-6 and sum(judged["known"]) == 0
    assert max(judged["left_out"]) == 0.0 and max(judged["order"]) == 0.0
    # and the queried item itself, served, is what `known` catches
    judged = als_topn.judge(
        unit[queried[:1]], unit, np.asarray(queried[:1])[:, None],
        [np.concatenate([[queried[0]], want[0][0][:9]])],
        [np.concatenate([[1.0], want[0][1][:9]])], block=1024,
    )
    assert judged["known"] == [1]


def test_the_builder_hands_the_check_unit_rows_and_the_item_itself():
    config = {"users": 40, "items": 200, "features": 8, "known_items_per_user": 2,
              "implicit": True, "dtype": "float32"}
    built = loadtest_als_similarity.build(config, 9)
    _x, y, _known = loadtest_als.make_arrays(config, 9)
    assert built.y.shape == (200, 8) and built.y.dtype == np.float32
    assert built.x.shape == (40, 8) and np.shares_memory(built.x, built.y)
    np.testing.assert_allclose(built.y, y / np.linalg.norm(y, axis=1, keepdims=True), atol=1e-6)
    assert built.known.tolist() == [[u] for u in range(40)]
    assert "unit_rows_s" in built.timings and built.item_row("i17") == 17
    # the program was given the factors as drawn, not the unit rows
    np.testing.assert_array_equal(built.model.get_item_vector("i3"), y[3])
    assert not loadtest_als_similarity.staged(built.model)
    with pytest.raises(ValueError, match="ids are drawn below"):
        loadtest_als_similarity.build({**config, "users": 201}, 9)


# -- the new layer-metric files on a recorded snapshot -------------------------------------

BEFORE = {"serving.scan.vector.queries": {"type": "counter", "value": 10.0},
          "serving.scan.indexed.queries": {"type": "counter", "value": 4.0},
          "serving.scan.cosine.queries": {"type": "counter", "value": 10.0},
          "serving.batcher.passes": {"type": "counter", "value": 7.0},
          "serving.batcher.submit.seconds": {"type": "histogram", "count": 7, "sum": 0.007}}
AFTER = {"serving.scan.vector.queries": {"type": "counter", "value": 110.0},
         "serving.scan.indexed.queries": {"type": "counter", "value": 104.0},
         "serving.scan.cosine.queries": {"type": "counter", "value": 60.0},
         "serving.batcher.passes": {"type": "counter", "value": 107.0},
         "serving.batcher.submit.seconds": {"type": "histogram", "count": 107, "sum": 0.057}}
# a program from before the counters (the parent): the submit kinds and the passes only
OLD = {k: v for k, v in AFTER.items() if k in ("serving.scan.vector.queries",
                                               "serving.scan.indexed.queries",
                                               "serving.batcher.passes")}


@pytest.mark.parametrize("metric, reads, on_the_parent", [
    ("cosine_submit_pct.sim", 25.0, 0.0),
    ("submit_mean_ms.open", 0.5, None),
    ("submit_mean_ms.sat", 0.5, None),
])
def test_the_new_layer_metric_files_on_a_recorded_snapshot(metric, reads, on_the_parent):
    file = Spec().layer_metric(metric)
    assert file["name"] == metric.rsplit(".", 1)[0] and file["reduction"] == "counter_ratio"
    ctx = SimpleNamespace(counters={"window": (BEFORE, AFTER), "trace": None})
    assert counter_ratio.read(ctx, file["args"]) == pytest.approx(reads)
    # where the program has no such counter the reader returns 0 or nothing; it never raises
    old = SimpleNamespace(counters={"window": ({k: {**v, "value": 0.0} for k, v in OLD.items()},
                                               OLD)})
    assert counter_ratio.read(old, file["args"]) == on_the_parent
    assert counter_ratio.read(SimpleNamespace(counters={"window": None}), file["args"]) is None


def test_the_cell_asks_one_endpoint_of_a_cosine_deployment_on_one_chip():
    sim = Spec().cell(CELL)
    assert sim.traffic["endpoints"] == [{"path": "/similarity/i%d?howMany=10", "weight": 1.0}]
    assert sim.config["metric"] == "cosine" and sim.config["architecture"] is None
    assert sim.config["reduced"] == [] and sim.chips == 1
