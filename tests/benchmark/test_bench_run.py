"""The command end to end on the CPU at a tiny size: the harness's look
for a chip is skipped (`require_chip=False`), the rest of a run is the
real one: the program's ServingLayer in this process, the load generator
as a child, the plain reference, the result line. The tiny cells are
ADDED to a temporary copy of the benchmark (benchmark/testing.py), which
shows that a configuration, a traffic mix, a cell and a per-layer metric
come as files plus entries, with no edit to a file that is there.

A CPU run's numbers are read here for their shape only; none is a device
number."""

import gc
import json
import os
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark import testing
from benchmark.spec import ROOT, Spec

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = testing.make_copy(tmp_path_factory.mktemp("bench"))
    peaks = json.loads((root / "benchmark" / "peaks.json").read_text())
    return root, peaks


def _run(root, workload, seed, trace=False, **kw):
    out, lines = bench_run.run_cell(
        Spec(root), workload, seed, 2.0, trace, require_chip=False, **kw
    )
    # the last line of a run is this object and parses back to itself
    assert json.loads(json.dumps(out)) == out
    return out, lines


def test_open_cell_end_to_end_prints_the_contracts_line(copy):
    root, _ = copy
    frozen = gc.get_freeze_count()
    out, lines = _run(root, testing.TINY_OPEN, 2**31 + 7)
    assert set(out) == RESULT_KEYS and set(out["device"]) == DEVICE_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 120  # rate x seconds, whatever the seed
    assert set(out["metrics"]) == {"recommend_p95_ms", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert out["device"]["platform"] == "cpu"  # named, never passed off as a chip
    text = "\n".join(lines)
    # every number compared is printed beside its limit, and the set-up is split
    for name in ("score_err_of_scale", "left_out_gap_of_scale", "order_gap_of_scale",
                 "known_items_served"):
        assert f"check: {name} = " in text and "limit" in text
    # ... and once more on the line itself, under the key that comes last
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == set(bench_run.check.LIMITS) | {
        "answers_lost", "window_answers_compared_min"}
    for name, c in out["compared"].items():
        assert set(c) == {"value", "limit"}, name
        assert (c["value"] >= c["limit"]) if name.endswith("_min") else (c["value"] <= c["limit"])
    assert "window_failed_share = 0 (0 of 120;" in text
    assert "whole-window p50/p95/p99" in text and "setup: factors" in text
    assert "store_fill" in text and "collect " in text
    assert "longest pause in the window" in text and "generator process" in text
    # the window ran under the interpreter's default collector: nothing frozen
    assert "collector (interpreter's default, nothing frozen)" in text
    assert gc.isenabled() and gc.get_freeze_count() == frozen


def test_closed_cell_end_to_end_reports_a_rate(copy):
    root, _ = copy
    out, lines = _run(root, testing.TINY_SAT, 5)
    assert set(out) == RESULT_KEYS and out["correct"] is True
    assert set(out["metrics"]) == {"recommend_qps", "setup_s"}
    assert out["metrics"]["recommend_qps"]["unit"] == "answers/s"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert any(line.startswith("closed_http: 4 clients") for line in lines)


def test_traced_run_reports_per_layer_metrics_and_the_added_one(copy, monkeypatch):
    root, peaks = copy
    # an unknown device is an error; the test names the CPU's "peaks" in its
    # own copy so that the traced path can be walked here
    monkeypatch.setattr(Spec, "peaks", lambda self, kind: peaks["TPU v5 lite"])
    out, _ = _run(root, testing.TINY_OPEN, 11, trace=True)
    assert set(out) == RESULT_KEYS | {"breakdown"}
    assert set(out["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    got = set(out["metrics"])
    # counters and the generator's clock read on any backend ...
    assert {"handler_mean_ms.open", "indexed_submit_pct.open", "compiles_in_window.open",
            "generator_late_p99_ms.open", "recommend_p50_ms.open", "recommend_p99_ms.open",
            "server_pause_max_ms.open", "generator_pause_max_ms.open", "window_failed_pct.open",
            "scan_queries.tiny"} <= got
    assert out["metrics"]["window_failed_pct.open"]["value"] == 0.0
    assert out["metrics"]["scan_queries.tiny"]["value"] >= 100  # ~rate x seconds
    assert out["metrics"]["indexed_submit_pct.open"]["value"] == 100.0
    assert out["metrics"]["compiles_in_window.open"]["value"] == 0.0
    # ... a device-trace reader that finds nothing to read (no TPU plane in
    # a CPU trace) returns nothing, and the line leaves the metric out
    assert not {"scan_kernel_ms_per_pass.open", "scan_roofline.open", "scan_rows_per_pass.open"} & got
    assert "recommend_p95_ms" not in got and "setup_s" not in got


def test_a_bfloat16_item_matrix_fails_the_check_at_the_stated_tolerance(copy):
    """The control: the program's own lower-precision path, same factors."""
    root, _ = copy
    out, lines = _run(root, testing.TINY_OPEN, 3, score_dtype="bfloat16")
    assert out["correct"] is False
    line = next(x for x in lines if "score_err_of_scale" in x)
    assert "FAIL" in line
    assert float(line.split("=")[1].split()[0]) > 1e-4  # limit 1e-5, float32 reads ~2e-7
    err = out["compared"]["score_err_of_scale"]
    assert err["value"] > 1e-4 and err["limit"] == 1e-5


def test_the_command_prints_the_result_last_on_stdout_and_the_numbers_compared_last_on_stderr(
    monkeypatch, capsys
):
    """The contract's two records of a failed run: the end of the result
    line and the end of standard error both hold every number compared
    beside its limit."""
    result = {"correct": False, "attempted": 3, "failed": 0, "metrics": {}, "device": {},
              "compared": {"score_err_of_scale": {"value": 4.2e-3, "limit": 1e-5},
                           "answers_lost": {"value": 0, "limit": 0}}}
    checks = ["check: score_err_of_scale = 0.0042 (limit <= 1e-05) FAIL",
              "check: 65 answers asked before the window (0 lost), 3 sampled from the window"]
    monkeypatch.setattr(bench_run, "run_cell", lambda *a, **kw: (result, ["a line", *checks]))
    assert bench_run.main(["--workload", "w", "--seed", "2147483650", "--seconds", "1"]) == 0
    said = capsys.readouterr()
    assert json.loads(said.out.splitlines()[-1]) == result
    assert said.err.splitlines() == checks and said.out.splitlines()[:3] == ["a line", *checks]


def test_a_broken_timed_path_comes_out_not_correct(copy, monkeypatch):
    """An answer altered where it is produced: the best item of every
    answer is dropped inside the program's selection loop."""
    from oryx_tpu.app.als.serving_model import ALSServingModel

    sound = ALSServingModel._select_loop

    def broken(ids, num_candidates, score_fn, how_many, exclude, rescorer):
        return sound(ids, num_candidates, score_fn, how_many + 1, exclude, rescorer)[1:]

    monkeypatch.setattr(ALSServingModel, "_select_loop", staticmethod(broken))
    root, _ = copy
    out, lines = _run(root, testing.TINY_OPEN, 4)
    assert out["correct"] is False and out["failed"] == 0  # well-formed, and wrong
    assert "FAIL" in next(x for x in lines if "left_out_gap_of_scale" in x)


def test_a_window_that_sheds_pays_in_the_tail_and_in_failed_not_in_correct(copy, monkeypatch):
    """A stall of the machine makes the program's ladder shed the burst
    behind it: those answers are counted in `failed` and each is charged
    the client's timeout in the judged tail, but what was served is not
    wrong, so `correct` stays with the comparison (PERF.md section 2)."""
    from benchmark.drivers import open_http

    sound = open_http.reduce

    def twelve_shed(result, traffic):
        for i in range(40, 52):
            result["ok"][i] = False
        return sound(result, traffic)

    monkeypatch.setattr(open_http, "reduce", twelve_shed)
    root, _ = copy
    out, lines = _run(root, testing.TINY_OPEN, 6)
    assert out["correct"] is True and out["failed"] == 12 and out["attempted"] == 120
    # 10 % of the window failed: the p95 of ALL requests is the timeout
    assert out["metrics"]["recommend_p95_ms"]["value"] >= 10_000.0
    assert "window_failed_share = 0.1 (12 of 120;" in "\n".join(lines)


@pytest.mark.parametrize("refusals, correct", [(1, True), (99, False)])
def test_a_check_request_that_was_shed_is_asked_again(copy, monkeypatch, refusals, correct):
    """The answers asked before the window are compared, not timed: one
    that comes back shed is asked again; one that never comes is lost,
    and a run that lost one is not correct."""
    sound = bench_run.hc.judged_get
    seen: dict[str, int] = {}
    victim = []

    def shed_at_first(conn, path, how_many):
        if not victim and not path.startswith("/recommend/u0?"):
            victim.append(path)  # the first user asked after the staging polls of u0
        if victim and path == victim[0]:
            seen[path] = seen.get(path, 0) + 1
            if seen[path] <= refusals:
                return False, "shed-reduced-probe", b""
        return sound(conn, path, how_many)

    monkeypatch.setattr(bench_run.hc, "judged_get", shed_at_first)
    monkeypatch.setattr(bench_run, "CHECK_RETRY_S", 0.01)
    root, _ = copy
    out, lines = _run(root, testing.TINY_OPEN, 8)
    assert out["correct"] is correct and out["failed"] == 0
    assert ("(0 lost)" in "\n".join(lines)) is correct


def test_without_a_chip_the_command_fails_and_prints_no_result(copy):
    root, _ = copy
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", testing.TINY_OPEN,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "never falls back to the CPU" in proc.stderr


def test_nothing_that_was_there_is_edited_by_adding_a_cell(copy):
    root, _ = copy
    for path in (ROOT / "benchmark").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            assert (root / "benchmark" / path.relative_to(ROOT / "benchmark")).read_bytes() == path.read_bytes()
    old = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = json.loads((root / "BENCHMARK.json").read_text())
    assert new["workloads"][: len(old["workloads"])] == old["workloads"]
    assert new["configs"][: len(old["configs"])] == old["configs"]
