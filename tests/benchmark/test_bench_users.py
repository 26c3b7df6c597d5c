"""The users-heavy cell `als250u5m-recommend-open` (configuration
`als-250f-1m-5mu-f32`: five users an item, the user matrix staged under a
budget read from the device):

- the command end to end on the CPU at a tiny size, the configuration
  ADDED to a temporary copy of the benchmark: staged under the real
  budget (every window answer by row index), refused under a budget
  patched small (every answer by the vector path, still correct), and the
  control, a staged matrix whose rows are shifted by one;
- the builder failing at once on a program without the staging rule, and
  at once on a refusal;
- the `counter_value` reader on a recorded snapshot.

A CPU run's numbers are read for their shape only."""

import importlib
import json
import sys
from types import SimpleNamespace

import pytest

from benchmark import run as bench_run
from benchmark import testing
from benchmark.reductions import counter_delta, counter_value
from benchmark.spec import ROOT, Spec

CELL = "als250u5m-recommend-open"
CONFIG = "als-250f-1m-5mu-f32"
BUILDER = "benchmark.builders.loadtest_als_users"
TINY, TINY_CONFIG = "tiny-users-recommend-open", "tiny-als-16f-users"
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """benchmark/testing.py's copy, plus a tiny users-heavy configuration
    (five users an item) and its cell, added as files and entries."""
    root = testing.make_copy(tmp_path_factory.mktemp("bench_users"))
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / f"{CONFIG}.json").read_text())
    cfg.update(name=TINY_CONFIG, features=16, items=600, users=3000, source="test",
               reduced=["items", "users"])
    (bench / "configs" / f"{TINY_CONFIG}.json").write_text(json.dumps(cfg))
    (bench / "cells" / f"{TINY}.json").write_text(json.dumps({"rate_per_s": 60}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": TINY_CONFIG, "source": "test", "reduced": ["items", "users"],
                           "file": f"benchmark/configs/{TINY_CONFIG}.json", "why": "tier-1"})
    doc["workloads"].append({"name": TINY, "config": TINY_CONFIG, "traffic": "tiny-open",
                             "chips": 1, "why": "tier-1"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return root


def _run(root, seed, trace=True, **kw):
    return bench_run.run_cell(Spec(root), TINY, seed, 2.0, trace, require_chip=False, **kw)


def _gauge(name):
    from oryx_tpu.common import metrics

    return metrics.registry.gauge(name).value


def test_under_the_real_budget_every_user_is_staged_and_every_answer_goes_by_row(
    copy, monkeypatch, capsys
):
    monkeypatch.setattr(Spec, "peaks", lambda self, kind: PEAKS)
    out, lines = _run(copy, 2**31 + 31)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 120
    got = out["metrics"]
    assert got["indexed_submit_pct.open"]["value"] == 100.0
    assert got["unstaged_requests.users"]["value"] == 0.0
    assert got["compiles_in_window.open"]["value"] == 0.0
    assert got["stage_users_s.users"]["value"] > 0.0
    assert {"handler_mean_ms.open", "queue_wait_mean_ms.open", "pass_inflight_mean_ms.open",
            "deliver_mean_ms.open", "window_rows_per_pass.open", "useful_rows_pct.open",
            "inflight_depth_mean.open", "recommend_p50_ms.open", "recommend_p99_ms.open",
            "window_failed_pct.open", "generator_late_p99_ms.open",
            "generator_pause_max_ms.open", "server_pause_max_ms.open"} <= set(got)
    # a CPU trace holds no named kernel: the device-trace readers return nothing
    assert not {"scan_roofline.open", "scan_kernel_ms_per_pass.open",
                "scan_rows_per_pass.open"} & set(got)
    assert _gauge("serving.users.stage.refused") == 0
    assert _gauge("serving.users.staged-rows") == 3000
    assert _gauge("serving.users.staged-bytes") == 3750 * 16 * 4  # 25 % headroom
    assert _gauge("serving.users.stage-budget-bytes") >= 3750 * 16 * 4
    said = capsys.readouterr().out
    assert "user staging: 3000 rows of 3000 users staged" in said
    assert "host resident when the builder returned" in said and "host resident now" in said


def test_under_a_budget_too_small_nothing_is_staged_and_the_vector_path_is_still_correct(
    copy, monkeypatch, caplog
):
    """The program's side of a refusal, through the real Session: the
    builder of the cell would end the run at once (next test), so its two
    hooks are replaced by ones that wait for the refusal itself."""
    from oryx_tpu.app.als import serving_model as sm

    builder = importlib.import_module(BUILDER)
    monkeypatch.setattr(Spec, "peaks", lambda self, kind: PEAKS)
    monkeypatch.setattr(sm, "user_stage_budget", lambda devices: 100_000)
    monkeypatch.setattr(builder, "staged", lambda model: model._x_stage_refused)
    monkeypatch.setattr(builder, "warm_scan_programs", lambda *a: 0)
    with caplog.at_level("WARNING", logger=sm.__name__):
        out, lines = _run(copy, 32)
    assert out["correct"] is True and out["failed"] == 0
    got = out["metrics"]
    assert got["indexed_submit_pct.open"]["value"] == 0.0
    assert got["unstaged_requests.users"]["value"] == 120.0  # every request of the window
    assert _gauge("serving.users.stage.refused") == 1
    assert _gauge("serving.users.staged-rows") == 0
    assert _gauge("serving.users.stage-budget-bytes") == 100_000
    refusal = next(r.getMessage() for r in caplog.records if "not staged" in r.getMessage())
    assert "3000 users x 16 features ask 240000 bytes" in refusal and "100000" in refusal


def test_the_cells_builder_ends_a_refused_run_at_once(copy, monkeypatch):
    from oryx_tpu.app.als import serving_model as sm

    monkeypatch.setattr(sm, "user_stage_budget", lambda devices: 100_000)
    with pytest.raises(RuntimeError, match="refused to stage the user matrix"):
        bench_run.Session(Spec(copy), TINY, 33, require_chip=False)


def test_a_staged_matrix_shifted_by_one_row_fails_the_check(copy, monkeypatch):
    """The control: a wrong gather. Every user is served the row of the
    user before it, well-formed and at full quality."""
    import numpy as np

    from oryx_tpu.ops import topn as topn_ops

    sound = topn_ops.stage_queries

    def shifted(chunks, capacity, features, mesh=None):
        rows = np.concatenate(list(chunks))
        return sound([np.roll(rows, 1, axis=0)], capacity, features, mesh=mesh)

    monkeypatch.setattr(topn_ops, "stage_queries", shifted)
    out, lines = _run(copy, 34, trace=False)
    assert out["correct"] is False and out["failed"] == 0
    line = next(x for x in lines if "score_err_of_scale" in x)
    assert "FAIL" in line and float(line.split("=")[1].split()[0]) > 1e-2


def test_on_a_program_without_the_staging_rule_the_cell_fails_at_once(copy, monkeypatch):
    """The parent of the PR that brought the cell: the builder's import
    resolves the program's rule first and raises before any factor is
    made (a parent that ran on would wait out the harness's 600 s)."""
    from oryx_tpu.app.als import serving_model as sm

    monkeypatch.delattr(sm, "user_stage_budget")
    monkeypatch.delitem(sys.modules, BUILDER, raising=False)
    made = []
    from benchmark.builders import loadtest_als

    monkeypatch.setattr(loadtest_als, "make_arrays", lambda *a: made.append(1))
    try:
        with pytest.raises(ImportError, match="only under a 2 GiB constant"):
            bench_run.Session(Spec(copy), TINY, 1, require_chip=False)
    finally:
        monkeypatch.undo()
        sys.modules.pop(BUILDER, None)
        importlib.import_module(BUILDER)
    assert made == []


# -- the reader of a value as it stands -----------------------------------------------------

BEFORE = {"serving.users.stage.seconds": {"type": "histogram", "count": 1, "sum": 7.25},
          "serving.users.unstaged-requests": {"type": "counter", "value": 3.0}}
AFTER = {"serving.users.stage.seconds": {"type": "histogram", "count": 1, "sum": 7.25},
         "serving.users.unstaged-requests": {"type": "counter", "value": 3.0},
         "serving.request.seconds": {"type": "histogram", "count": 90, "sum": 0.5}}


def test_counter_value_reads_where_a_metric_stands_at_the_windows_end():
    ctx = SimpleNamespace(counters={"window": (BEFORE, AFTER), "trace": None})
    args = Spec().layer_metric("stage_users_s.users")["args"]
    assert counter_value.read(ctx, args) == 7.25  # a delta would read 0
    assert counter_value.read(ctx, {**args, "scale": 1000.0}) == 7250.0
    assert counter_value.read(ctx, {**args, "span": "trace"}) is None  # no traced slice
    # a program without the metric (the parent) reads nothing, not 0
    assert counter_value.read(ctx, {"metric": ["no.such.metric", "sum"]}) is None
    assert counter_value.read(ctx, {"metric": ["serving.request.seconds", "max"]}) is None
    bare = SimpleNamespace(counters={"window": ({}, {})})
    assert counter_value.read(bare, args) is None
    unstaged = Spec().layer_metric("unstaged_requests.users")
    assert unstaged["reduction"] == "counter_delta"
    assert counter_delta.read(ctx, unstaged["args"]) == 0.0
    assert counter_delta.read(bare, unstaged["args"]) is None
