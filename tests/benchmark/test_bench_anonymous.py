"""The anonymous-visitor cell `als250-anonymous-open` (configuration
`als-250f-5m-f32-anon`: `/recommendToAnonymous/i<h>/i<o_1>/...` folded in
through `(YtY)^-1`, by the vector submit and the scan's DOT variant):

- the command end to end on the CPU at a tiny size, the configuration
  ADDED to a temporary copy of the benchmark: every window answer by an
  uploaded query vector scored by dot product, baskets of mixed sizes,
  no program compiled in the window (both k buckets warmed), `correct:
  true`; the two controls (the program's bfloat16 item matrix; a fold-in
  that drops the basket's last item) print `correct: false`;
- the plain reference (benchmark/reference/als_foldin.py) against the
  endpoint over HTTP for every basket size 1..8, judged by
  `als_topn.judge` within `check.LIMITS`, and against the program's own
  recurrence;
- index <-> basket <-> URL between the driver and the builder, the basket
  law's mean, the schedule's determinism by seed;
- the four new layer-metric files on a recorded snapshot, and on a
  program without the instruments.

A CPU run's numbers are read for their shape only."""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import check
from benchmark import run as bench_run
from benchmark import testing
from benchmark.builders import loadtest_als, loadtest_als_anonymous
from benchmark.drivers import httpclient as hc
from benchmark.drivers import open_http, open_http_baskets
from benchmark.reductions import counter_ratio, counter_value
from benchmark.reference import als_foldin, als_topn
from benchmark.spec import ROOT, Spec

CELL = "als250-anonymous-open"
CONFIG = "als-250f-5m-f32-anon"
TINY, TINY_CONFIG, TINY_MIX = "tiny-anonymous-open", "tiny-als-16f-anon", "tiny-anon-open"
ITEMS, HEADS, FEATURES = 3000, 400, 16
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
ANON_METRICS = [m["name"] for m in DOC["per_layer"] if CELL in m["workloads"]]
# what only a device trace with a named kernel in it can give
DEVICE_TRACE = {"scan_roofline.open", "scan_kernel_ms_per_pass.open", "scan_rows_per_pass.open"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """benchmark/testing.py's copy, plus a tiny anonymous-visitor
    configuration, its mix and its cell, added as files and entries."""
    root = testing.make_copy(tmp_path_factory.mktemp("bench_anon"))
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / f"{CONFIG}.json").read_text())
    cfg.update(name=TINY_CONFIG, features=FEATURES, items=ITEMS, users=HEADS, source="test",
               reduced=["items", "users"])
    (bench / "configs" / f"{TINY_CONFIG}.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "anonymous-open.json").read_text())
    mix.update(name=TINY_MIX, warm_seconds=1, workers=16, warm_batch_buckets=[8, 16],
               check_users=16, check_sample_every=4, trace_seconds=1)
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


def _number(lines, name):
    line = next(x for x in lines if name in x)
    return line, float(line.split("=")[1].split()[0])


# -- the command, end to end ---------------------------------------------------------------


def test_every_answer_folds_in_and_goes_by_an_uploaded_vector_and_the_dot_variant(
    copy, monkeypatch, capsys
):
    monkeypatch.setattr(Spec, "peaks", lambda self, kind: PEAKS)
    out, lines = _run(copy, 2**31 + 40, trace=True)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 120
    got = out["metrics"]
    assert got["vector_submit_pct.anon"]["value"] == 100.0
    assert got["cosine_submit_pct.anon"]["value"] == 0.0
    assert got["indexed_submit_pct.open"]["value"] == 0.0
    # both k buckets were warmed: a window of mixed basket sizes compiles nothing
    assert got["compiles_in_window.open"]["value"] == 0.0
    assert got["foldin_mean_ms.anon"]["value"] > 0.0
    assert 2.0 < got["foldin_items_per_request.anon"]["value"] < 4.0  # 120 draws of a mean 2.95
    assert got["yty_build_s.anon"]["value"] > 0.0
    # every metric of the cell a run without a TPU plane can read is on the line (the
    # batcher sets its lag gauge once a pass key has a service estimate: with two k
    # buckets a window of two seconds may end before either has) ...
    assert set(ANON_METRICS) - DEVICE_TRACE - {"result_lag_ms.open"} <= set(got)
    # ... and a device-trace reader that finds no named kernel returns nothing
    assert not DEVICE_TRACE & set(got)
    text = "\n".join(lines)
    assert "check: known_items_served = 0 " in text  # no basket item, ever
    assert "setup: factors" in text and "reference_yty" in text
    line = next(x for x in lines if x.startswith("open_http_baskets: "))
    sizes = json.loads(line.split("1..8 ")[1].split(";")[0])
    assert sum(sizes) == 120 and sum(sizes[1:]) > 60 and sizes[6] + sizes[7] > 0  # k bucket 32 too
    # the sampled answers carry their basket's index, most of them of two items or more
    assert out["compared"]["window_answers_compared_min"]["value"] >= 20
    assert float(line.rsplit("(", 1)[1].split()[0]) >= 50.0
    # the builder's own line: the program's YtY against the float64 reference's
    printed = capsys.readouterr().out
    yty = next(x for x in printed.splitlines() if x.startswith("yty: "))
    assert float(yty.split("= ")[1].split()[0]) < 1e-6 and "built on the device" in yty


def test_the_bfloat16_item_matrix_fails_the_check(copy):
    """The control: the program's own lower-precision matrix, same factors."""
    out, lines = _run(copy, 41, score_dtype="bfloat16")
    assert out["correct"] is False and out["failed"] == 0
    line, value = _number(lines, "score_err_of_scale")
    assert "FAIL" in line and value > 1e-4


def test_a_fold_in_that_drops_the_last_basket_item_fails_the_check(copy, monkeypatch):
    """The control of the fold-in: the basket is still left out of the
    answer, every score is a sound dot product, and the vector is the one
    of a basket one item shorter."""
    from oryx_tpu.app.als import endpoints

    sound = endpoints._fold_in

    def forgetful(model, xu, pairs):
        return sound(model, xu, pairs[:-1] if len(pairs) > 1 else pairs)

    monkeypatch.setattr(endpoints, "_fold_in", forgetful)
    out, lines = _run(copy, 42)
    assert out["correct"] is False and out["failed"] == 0
    line, value = _number(lines, "left_out_gap_of_scale")
    assert "FAIL" in line and value > 1e-3
    assert "known_items_served = 0 " in "\n".join(lines)


# -- the reference against the endpoint ----------------------------------------------------


@pytest.fixture(scope="module")
def served(copy):
    """A Session up on the tiny cell (its builder's judged view with it),
    and a connection to it."""
    session = bench_run.Session(Spec(copy), TINY, 43, require_chip=False)
    conn = hc.Connection("127.0.0.1", session.layer.port, 60.0)
    yield conn, session
    conn.close()
    session.close()


@pytest.mark.parametrize("k", range(1, 9))
def test_the_endpoint_agrees_with_the_plain_reference_for_every_basket_size(served, k):
    """The real ServingLayer over HTTP against `als_foldin` + `als_topn`,
    by the check's own numbers and limits, and item for item."""
    conn, session = served
    built, path = session.built, session.path
    heads = [3, 77, 399]
    answers = []
    for head in heads:
        index = (k - 1) * HEADS + head
        rows = built.known.rows(index)
        assert len(rows) == k and rows[0] == head
        status, shed, body = conn.get(als_foldin.url(path, rows))
        assert status == 200 and shed is None
        answers.append({"user": index, "body": body.decode()})
        # item for item against the straight top-N of the reference's vector
        want_rows, want_scores = als_topn.top_n(built.x[[index]][0], built.y, rows, 10)
        pairs = hc.parse_answer(body)
        assert [built.item_row(i) for i, _ in pairs] == want_rows.tolist()
        scale = np.abs(want_scores).max()
        np.testing.assert_allclose([s for _, s in pairs], want_scores, rtol=0, atol=1e-5 * scale)
    numbers = check.judge_answers(built, answers, 10)
    ok, lines = check.verdict(numbers)
    assert ok, lines
    assert numbers["answers_compared_min"] == len(heads)


def test_the_reference_is_the_programs_recurrence_in_float64(served):
    """`als_foldin.fold_in` against `compute_updated_xu` over the program's
    solver, item by item, with strengths other than 1 and a negative one;
    and an explicit model, where the target is the value itself."""
    from oryx_tpu.app.als.common import compute_updated_xu
    from oryx_tpu.common.vectormath import Solver

    _conn, session = served
    y = session.built.y
    yty = als_foldin.yty(y, block=512)
    solver = Solver(yty)
    rows, values = [5, 1200, 17, 2999], [1.0, 2.5, -0.5, 1.0]
    for implicit in (True, False):
        xu = None
        for row, value in zip(rows, values):
            updated = compute_updated_xu(solver, value, xu, y[row], implicit)
            xu = xu if updated is None else updated
        want = als_foldin.fold_in(y, yty, [rows], [values], implicit)[0]
        np.testing.assert_allclose(xu, want, rtol=0, atol=2e-6 * np.abs(want).max())
    # a basket that asks for no change (an implicit strength of 0) keeps a zero vector
    assert not als_foldin.fold_in(y, yty, [[5]], [[0.0]], True).any()


def test_an_unknown_item_is_passed_over_and_none_known_is_a_400(served):
    conn, session = served
    status, _shed, body = conn.get("/recommendToAnonymous/i999999/i7?howMany=10")
    assert status == 200
    alone = hc.parse_answer(conn.get("/recommendToAnonymous/i7?howMany=10")[2])
    assert hc.parse_answer(body) == alone
    assert conn.get("/recommendToAnonymous/i999999?howMany=10")[0] == 400


def test_the_sweep_tool_prints_the_fold_in_and_the_k_buckets_of_a_step(served):
    """`tools/sweep_passes.py`, the tool that sites the cell's rate: each
    step's row says what the fold-in cost, how many items a request named
    and at which k bucket the passes ran."""
    from tools import sweep_passes

    _conn, session = served
    row = sweep_passes.window_row(session, "60", 2**31 + 47, 2.0, False)
    assert row["failed"] == 0 and row["attempted"] == 120 and row["compiles"] == 0
    assert row["foldin_mean_ms"] > 0.0 and 2.0 < row["foldin_items_per_request"] < 4.0
    assert 16.0 <= row["k_bucket_mean"] <= 32.0
    assert row["k32_pass_pct"] == pytest.approx(100.0 * (row["k_bucket_mean"] - 16.0) / 16.0)
    assert row["indexed_pct"] == 0.0 and row["cosine_pct"] == 0.0


def test_the_reference_yty_is_the_float64_gram_matrix():
    y = np.random.default_rng(5).standard_normal((1000, 12)).astype(np.float32)
    want = y.astype(np.float64).T @ y.astype(np.float64)
    for block in (64, 333, 4096):
        np.testing.assert_allclose(als_foldin.yty(y, block=block), want, rtol=0, atol=1e-12 * want.max())


# -- one integer names a basket: driver and builder ----------------------------------------


def _params(seed, rate=300.0, seconds=4.0):
    cell = Spec().cell(CELL)
    p = open_http_baskets.plan(cell, seed, seconds, "127.0.0.1", 1, 0.0, 1.0)
    return {**p, "rate_per_s": rate}, cell


def test_index_basket_and_url_round_trip_between_driver_and_builder():
    p, cell = _params(2**31 + 44)
    assert p["path"] == "/recommendToAnonymous/i%d?howMany=10"
    _due, heads, _n_warm, _n_win, _sampled = open_http.schedule(p)
    indices, urls = open_http_baskets.requests(p, heads)
    baskets = loadtest_als_anonymous._Baskets(cell.config)
    users = int(cell.config["users"])
    assert len(urls) == len(heads) == 300 * (5 + 4 + 1)
    for index, head, url in list(zip(indices, heads.tolist(), urls))[:400]:
        k = index // users + 1
        assert index % users == head and 1 <= k <= 8
        rows = baskets.rows(index)  # the builder's reading of the same integer
        assert rows[0] == head and len(rows) == k == len(set(rows))
        assert all(0 <= r < int(cell.config["items"]) for r in rows)
        assert url == "/recommendToAnonymous/" + "/".join(f"i{r}" for r in rows) + "?howMany=10"
        padded = baskets[[index]][0]
        assert padded.tolist() == rows + [head] * (8 - k) and padded.dtype == np.int32
    # index `head` IS `path % head`, what run.py asks by itself
    assert als_foldin.url(p["path"], baskets.rows(17)) == p["path"] % 17
    # a session is fixed by (basket_seed, head) alone, and its prefixes are its baskets
    whole = baskets.rows(7 * users + 17)
    assert [baskets.rows(j * users + 17) for j in range(8)] == [whole[: j + 1] for j in range(8)]
    with pytest.raises(ValueError, match="more than 8"):
        baskets.rows(8 * users)


def test_the_basket_law_s_mean_and_the_schedule_s_determinism_by_seed():
    law = als_foldin.basket_size_law(0.72)
    np.testing.assert_allclose(
        law, [0.302, 0.217, 0.157, 0.113, 0.081, 0.058, 0.042, 0.030], atol=6e-4
    )
    assert float(law @ np.arange(1, 9)) == pytest.approx(2.95, abs=0.005)
    p, _cell = _params(45, rate=320.0, seconds=50.0)
    ks = open_http_baskets.basket_sizes(p, 16000)
    assert ks.min() == 1 and ks.max() == 8 and ks.mean() == pytest.approx(2.95, rel=0.02)
    assert (ks >= 2).mean() > 0.6
    _due, heads, *_ = open_http.schedule(p)
    again = open_http_baskets.requests(p, heads)
    assert open_http_baskets.requests(p, heads) == again  # the same seed, the same requests
    other = open_http_baskets.requests({**p, "seed": 46}, open_http.schedule({**p, "seed": 46})[1])
    assert other != again
    # the k's have a stream of their own: the heads and due times are open_http's, untouched
    np.testing.assert_array_equal(heads, open_http.schedule(p)[1])


def test_the_builder_hands_the_check_folded_vectors_and_baskets():
    config = {"users": 40, "items": 200, "features": 8, "known_items_per_user": 2,
              "implicit": True, "dtype": "float32",
              "sessions": {"exponent": 1.1, "basket_seed": 40, "largest": 8}}
    built = loadtest_als_anonymous.build(config, 9)
    _x, y, _known = loadtest_als.make_arrays(config, 9)
    np.testing.assert_array_equal(built.y, y)  # as drawn: the dot product needs no other view
    assert "reference_yty_s" in built.timings and built.item_row("i17") == 17
    index = 2 * 40 + 5
    rows = built.known.rows(index)
    assert built.known[np.asarray([index, 5])].shape == (2, 8)
    x = built.x[np.asarray([index, 5])]
    assert x.shape == (2, 8) and x.dtype == np.float64
    want = als_foldin.fold_in(y, als_foldin.yty(y), [rows, [5]], [[1.0] * 3, [1.0]], True)
    np.testing.assert_array_equal(x, want)
    # staged() is false until the program holds its solver
    model = built.model
    assert not loadtest_als_anonymous.staged(model)
    model._ensure_y_matrix()
    assert model._y_matrix is not None and not loadtest_als_anonymous.staged(model)
    assert model.get_yty_solver() is not None and loadtest_als_anonymous.staged(model)
    with pytest.raises(ValueError, match="heads are drawn below"):
        loadtest_als_anonymous.build({**config, "users": 201}, 9)


# -- the new layer-metric files on a recorded snapshot -------------------------------------

BEFORE = {"serving.scan.vector.queries": {"type": "counter", "value": 10.0},
          "serving.scan.indexed.queries": {"type": "counter", "value": 4.0},
          "serving.scan.cosine.queries": {"type": "counter", "value": 0.0},
          "serving.foldin.requests": {"type": "counter", "value": 10.0},
          "serving.foldin.items": {"type": "counter", "value": 20.0},
          "serving.foldin.seconds": {"type": "histogram", "count": 10, "sum": 0.002},
          "serving.yty.build.seconds": {"type": "histogram", "count": 1, "sum": 0.25}}
AFTER = {"serving.scan.vector.queries": {"type": "counter", "value": 310.0},
         "serving.scan.indexed.queries": {"type": "counter", "value": 104.0},
         "serving.scan.cosine.queries": {"type": "counter", "value": 0.0},
         "serving.foldin.requests": {"type": "counter", "value": 110.0},
         "serving.foldin.items": {"type": "counter", "value": 315.0},
         "serving.foldin.seconds": {"type": "histogram", "count": 110, "sum": 0.022},
         "serving.yty.build.seconds": {"type": "histogram", "count": 1, "sum": 0.25}}
# a program from before the instruments (the parent): the submit kinds only
OLD = {k: v for k, v in AFTER.items() if k.startswith("serving.scan.")}


@pytest.mark.parametrize("metric, reduction, reads, on_the_parent", [
    ("foldin_mean_ms.anon", counter_ratio, 0.2, None),
    ("foldin_items_per_request.anon", counter_ratio, 2.95, None),
    ("vector_submit_pct.anon", counter_ratio, 75.0, 75.0),
    ("cosine_submit_pct.anon", counter_ratio, 0.0, 0.0),
    ("yty_build_s.anon", counter_value, 0.25, None),
])
def test_the_new_layer_metric_files_on_a_recorded_snapshot(metric, reduction, reads, on_the_parent):
    file = Spec().layer_metric(metric)
    assert file["name"] == metric.rsplit(".", 1)[0]
    assert file["reduction"] == reduction.__name__.rsplit(".", 1)[1]
    ctx = SimpleNamespace(counters={"window": (BEFORE, AFTER), "trace": None})
    assert reduction.read(ctx, file["args"]) == pytest.approx(reads)
    # where the program has no such instrument the reader returns nothing
    # (or what the counters it does have say); it never raises
    old = SimpleNamespace(counters={"window": ({k: BEFORE[k] for k in OLD}, OLD)})
    assert reduction.read(old, file["args"]) == on_the_parent
    assert reduction.read(SimpleNamespace(counters={"window": None}), file["args"]) is None


def test_the_cell_asks_one_endpoint_of_a_dot_deployment_on_one_chip():
    anon = Spec().cell(CELL)
    assert anon.traffic["endpoints"] == [
        {"path": "/recommendToAnonymous/i%d?howMany=10", "weight": 1.0}
    ]
    assert anon.traffic["driver"] == "open_http_baskets" and anon.traffic["how_many"] == 10
    assert anon.traffic["basket_size"]["ratio"] == 0.72 and anon.traffic["basket_size"]["largest"] == 8
    assert anon.config["metric"] == "dot" and anon.config["architecture"] is None
    assert anon.config["reduced"] == [] and anon.chips == 1
    assert (anon.config["features"], anon.config["items"]) == (250, 5_000_000)
    assert anon.cell["rate_per_s"] > 0
    own = {m["name"] for m in anon.per_layer if m["name"].endswith(".anon")}
    assert own == {"foldin_mean_ms.anon", "foldin_items_per_request.anon", "vector_submit_pct.anon",
                   "cosine_submit_pct.anon", "yty_build_s.anon"}
    assert len(anon.per_layer) == 36 and len(DOC["per_layer"]) == 67
