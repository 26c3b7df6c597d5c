"""The pass on the record, as the benchmark reads it (PR 24): the window's
counter metrics through a tiny CPU cell, and the pairing of the program's
profiler annotations with the device's scan ops: on a hand-made trace whose
answers can be worked out on paper, and on a small recording from the chip
(benchmark/testdata/trace_pass_v5e.json.gz). Numbers asserted on the
recording are what it holds; they are not measurements of this tree."""

import json
from types import SimpleNamespace

import pytest

from benchmark import run as bench_run
from benchmark import testing, trace
from benchmark.reductions import counter_delta, trace_pass
from benchmark.spec import ROOT, Spec

RECORDED = ROOT / "benchmark" / "testdata" / "trace_pass_v5e.json.gz"
WINDOW_QUANTITIES = [
    "queue_wait_mean_ms", "pass_inflight_mean_ms", "window_rows_per_pass", "useful_rows_pct",
    "inflight_depth_mean", "deliver_mean_ms",
]

MS = 1_000_000  # ns


def _scan(start_ms, dur_ms, rows=8, kernel="oryx_topn_scan.2"):
    name = (f"%{kernel} = (f32[{rows},32]{{1,0:T(8,128)}}, s32[{rows},32]{{1,0:T(8,128)}}) "
            "custom-call(f32[8,50]{1,0} %copy), custom_call_target=\"tpu_custom_call\"")
    return [name, int(start_ms * MS), int(dur_ms * MS)]


def _mark(name, number, start_ms, dur_ms, **stats):
    return [name, int(start_ms * MS), int(dur_ms * MS), {"pass": number, **stats}]


def _planes(ops, host):
    return [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                            {"name": "XLA Modules", "events": [["m", 0, 10**9]]}]},
        {"name": "/host:CPU", "lines": [{"name": "dispatcher", "events": host}]},
    ]


def _steady(passes=6, depth=3, first=100):
    """Back-to-back 10 ms scans; pass p is submitted (0.2 ms) `depth - 1`
    scans before its own starts, and its wait ends 0.3 ms after its scan.
    The trace starts in the middle: the first two scans' submits and the
    last two submits' scans fall outside it."""
    ops, host = [], []
    for j in range(passes):
        start = 10.0 * j
        ops.append(["%fusion = f32[8,50] fusion(...)", int((start - 0.004) * MS), 4000])
        ops.append(_scan(start, 9.99))
        number = first + j
        if j >= depth - 1:
            submitted = 10.0 * (j - (depth - 1)) + 0.5
            host.append(_mark(trace_pass.SUBMIT, number, submitted, 0.2, rows=3, padded_rows=8))
        host.append(_mark(trace_pass.WAIT, number, start - 5.0, 5.0 + 9.99 + 0.3))
    for extra in range(depth - 1):  # submitted inside the trace, scanned after it
        host.append(_mark(trace_pass.SUBMIT, first + passes + extra,
                          10.0 * (passes - (depth - 1) + extra) + 0.5, 0.2, rows=3, padded_rows=8))
    return ops, host


def test_passes_pair_by_order_and_the_means_are_the_ones_worked_out_on_paper():
    r = trace_pass.reduce(_planes(*_steady()))
    assert r["offset"] == 100 and r["scans"] == 6
    # scans 100 and 101 were submitted before the trace began: edges, not failures
    assert (r["paired"], r["unpaired"], r["edges"], r["early"]) == (4, [], 2, 0)
    assert r["kernel_ms"] == pytest.approx(9.99)
    # submitted at 10 (j - 2) + 0.5, over at + 0.7; its scan starts at 10 j
    assert r["device_queue_ms"] == pytest.approx(20.0 - 0.7)
    assert r["result_lag_ms"] == pytest.approx(0.3)
    # the only idle stretches are the 6 us before each fusion; the completer is
    # always waiting, the dispatcher is inside none of them
    assert len(r["gaps_us"]) == 5
    assert all(where == "serving.pass.wait only" for _us, where in r["gaps_us"])
    assert r["gaps_us"][0][0] == pytest.approx(6.0, abs=0.01)


def test_a_pass_that_does_not_fit_its_annotations_is_counted_not_paired():
    ops, host = _steady()
    ops[2 * 3 + 1] = _scan(30.0, 9.99, rows=16)  # pass 103 ran 16 rows, its submit says 8
    host = [ev for ev in host if not (ev[0] == trace_pass.SUBMIT and ev[3]["pass"] == 104)]
    for ev in host:  # pass 105's submit began after its scan had started
        if ev[0] == trace_pass.SUBMIT and ev[3]["pass"] == 105:
            ev[1] = int(50.5 * MS)
    r = trace_pass.reduce(_planes(ops, host))
    assert r["paired"] == 1 and r["edges"] == 2 and r["early"] == 1
    assert dict(r["unpaired"]) == {
        103: "rows 16, submit padded 8", 104: "no submit",
        105: "scan started 0.500 ms before its submit began",
    }


def test_the_recording_from_the_chip_gives_the_queue_and_lag_worked_out_by_hand():
    """40 scan ops of als250-recommend-open on a TPU v5 lite; the first is
    pass 3395. By hand, from the raw events (ns):
      pass 3395: submit 58060817 + 7654590 -> over at 65715407; scan 62763311
        + 10235035 -> over at 72998346; wait 68844407 + 6466719 -> over at
        75311126. Queue 62763311 - 65715407 = -2952096 (the device was free
        and took the scan while the host was still inside its submit); lag
        75311126 - 72998346 = 2312780.
      pass 3396: submit over at 68744216, scan 73000830 .. 83846158, wait over
        at 85886515: queue 4256614, lag 2040357.
      pass 3397 (22 rows, bucket 32): submit over at 77922186, scan 83849461
        .. 109884858, wait over at 111951434: queue 5927275, lag 2066576.
    The 40th scan (pass 3434) ends after the cut, so its wait is not in the
    recording: an edge. Over the 39 others the sums are 614452061 ns of
    queue and 83304699 ns of lag."""
    planes = trace.load_planes(str(RECORDED))
    r = trace_pass.reduce(planes)
    assert (r["scans"], r["paired"], r["unpaired"], r["edges"], r["early"]) == (40, 39, [], 1, 0)
    assert r["offset"] == 3395
    assert r["device_queue_ms"] == pytest.approx(614452061 / 39 / 1e6)  # 15.755 ms
    assert r["result_lag_ms"] == pytest.approx(83304699 / 39 / 1e6)  # 2.136 ms
    assert r["kernel_ms"] == pytest.approx(10.606536375)
    # the first three passes alone: the figures worked out above
    t_cut = 111951434 + 1
    first = [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [ev for ev in ln["events"] if ev[1] + ev[2] <= t_cut]}
            for ln in p["lines"]]}
        for p in planes
    ]
    r3 = trace_pass.reduce(first)
    assert (r3["scans"], r3["paired"], r3["edges"]) == (3, 3, 0)
    assert r3["device_queue_ms"] == pytest.approx((-2952096 + 4256614 + 5927275) / 3 / 1e6)
    assert r3["result_lag_ms"] == pytest.approx((2312780 + 2040357 + 2066576) / 3 / 1e6)
    # the device rests about 2 us between a scan and the next program's gather,
    # always with the completer waiting and the dispatcher outside its submit
    assert r["gaps_us"][0][0] == pytest.approx(2.077, abs=1e-3)
    assert {where for _us, where in r["gaps_us"]} == {"serving.pass.wait only"}
    # what benchmark/trace.py makes of the same device plane agrees on the kernel
    device = [p for p in planes if p["name"].startswith("/device:")]
    reduced = trace.reduce_planes(device, window_s=0.45)
    passes, seconds = trace.matching(reduced, "custom-call")
    assert passes == 40 and 1000 * seconds / passes == pytest.approx(r["kernel_ms"])
    assert reduced["breakdown"]["device_ops"][0][0].startswith("oryx_topn_scan")


def test_the_candidates_kernel_reads_its_rows_from_the_second_dimension():
    name = "%oryx_topn_candidates.2 = (f32[1224,512,32]{2,1,0}, s32[1224,512,32]{2,1,0}) custom-call("
    assert trace_pass._batch_rows(name) == 512
    assert trace_pass._batch_rows(_scan(0, 1, rows=64)[0]) == 64
    assert trace_pass._batch_rows("%copy = copy(") is None


def test_a_trace_without_names_or_annotations_gives_nothing():
    """PR 23's recording: a device plane whose scan is `closed_call.7`, no
    host plane. That is also what a run on the parent program records."""
    old = trace.load_planes(str(ROOT / "benchmark" / "testdata" / "trace_als50_v5e.json.gz"))
    assert trace_pass.reduce(old) is None
    ops, host = _steady()
    assert trace_pass.reduce(_planes(ops, [])) is None  # named kernels, no annotation
    unnamed = [[ev[0].replace("oryx_topn_scan", "closed_call"), ev[1], ev[2]] for ev in ops]
    assert trace_pass.reduce(_planes(unnamed, host)) is None
    # the reader: no trace at all, and a cell that has no file on disk
    cell = SimpleNamespace(name="no-such-cell-was-traced")
    ctx = SimpleNamespace(cell=cell, trace=None, lines=[])
    assert trace_pass.read(ctx, {"stat": "kernel_ms"}) is None
    ctx = SimpleNamespace(cell=cell, trace={"busy_s": 1.0}, lines=[])
    assert trace_pass.read(ctx, {"stat": "kernel_ms"}) is None and ctx.lines == []


def _run_of(planes) -> dict:
    """What benchmark/run.py hands the readers as `ctx.trace` after it
    recorded and reduced these planes."""
    device = [p for p in planes if p["name"].startswith("/device:")]
    return trace.reduce_planes(device, window_s=0.1)


def test_the_reader_parses_once_and_prints_its_lines_once(monkeypatch):
    calls = []
    planes = _planes(*_steady())
    monkeypatch.setattr(trace_pass, "_candidates", lambda cell: ["x.xplane.pb"])
    monkeypatch.setattr(trace_pass, "extract", lambda path: calls.append(path) or planes)
    ctx = SimpleNamespace(cell=SimpleNamespace(name="c"), trace=_run_of(planes), lines=[])
    assert trace_pass.read(ctx, {"stat": "kernel_ms"}) == pytest.approx(9.99)
    assert trace_pass.read(ctx, {"stat": "kernel_ms"}) == pytest.approx(9.99)
    assert calls == ["x.xplane.pb"] and len(ctx.lines) == 3
    assert "4 paired with their submit and wait, 0 not (100.0 % of 4 paired), 2 at the trace's edges" in ctx.lines[0]
    assert "longest device idle gaps" in ctx.lines[1]
    # the two halves the profiler's clock alignment blurs are printed, not metrics
    assert "device queue 19.300, kernel 9.990, result lag 0.300" in ctx.lines[2]
    for stat in ("device_queue_ms", "result_lag_ms", "no_such_stat"):
        with pytest.raises(ValueError, match="unknown stat"):
            trace_pass.read(ctx, {"stat": stat})


def test_a_recording_that_is_not_this_runs_is_not_read(monkeypatch, tmp_path):
    """An older trace of a cell of the same name (another run left it in
    the checkout) holds other scans than the run reduced: nothing is read
    from it, and the run's own recording is found behind it."""
    ours, stale = _planes(*_steady(passes=6)), _planes(*_steady(passes=7))
    on_disk = {"stale.xplane.pb": stale, "ours.xplane.pb": ours}
    monkeypatch.setattr(trace_pass, "extract", lambda path: on_disk[path])
    cell = SimpleNamespace(name="c")
    monkeypatch.setattr(trace_pass, "_candidates", lambda name: ["stale.xplane.pb"])
    ctx = SimpleNamespace(cell=cell, trace=_run_of(ours), lines=[])
    assert trace_pass.read(ctx, {"stat": "kernel_ms"}) is None and ctx.lines == []
    monkeypatch.setattr(trace_pass, "_candidates", lambda name: list(on_disk))
    ctx = SimpleNamespace(cell=cell, trace=_run_of(ours), lines=[])
    assert trace_pass.read(ctx, {"stat": "kernel_ms"}) == pytest.approx(9.99)
    # same count, another length: still not the run's
    longer = [[ev[0], ev[1], ev[2] + 1] for ev in ours[0]["lines"][0]["events"]]
    assert not trace_pass._same_recording(_planes(longer, []), _run_of(ours))
    assert not trace_pass._same_recording(ours, {"busy_s": 1.0})
    monkeypatch.undo()
    # on disk: the benchmark's own checkout and the working directory, newest first
    monkeypatch.chdir(tmp_path)
    folder = tmp_path / ".bench_trace" / "c" / "plugins" / "profile" / "2026_09_28"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(b"")
    assert trace_pass._candidates("c") == [str(folder / "host.xplane.pb")]
    assert trace_pass._candidates("no-such-cell-was-traced") == []


def test_a_counter_the_program_lacks_reads_nothing_not_zero():
    """`unstaged_requests` (the one `counter_delta` quantity since PR 39
    took out `inflight_cap_changes`, which read 0 in every line since PR
    28) on a program from before PR 31: its registry holds no such
    counter, so the result line leaves the metric out."""
    args = {"metric": ["serving.users.unstaged-requests", "value"], "scale": 1.0, "span": "window"}
    name = args["metric"][0]
    has = ({name: {"type": "counter", "value": 3}}, {name: {"type": "counter", "value": 11}})
    steady = ({name: {"type": "counter", "value": 3}}, {name: {"type": "counter", "value": 3}})
    lacks = ({"serving.requests": {"type": "counter", "value": 1}},) * 2
    read = lambda span: counter_delta.read(SimpleNamespace(counters={"window": span}), args)
    assert read(has) == 8.0 and read(steady) == 0.0 and read(lacks) is None
    assert read(None) is None  # no window was taken
    file = Spec().layer_metric("unstaged_requests.users")
    assert file["reduction"] == "counter_delta" and file["args"] == args


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = testing.make_copy(tmp_path_factory.mktemp("bench_pass"))
    peaks = json.loads((root / "benchmark" / "peaks.json").read_text())
    return root, peaks


@pytest.mark.parametrize("workload, suffix", [(testing.TINY_OPEN, ".open"), (testing.TINY_SAT, ".sat")])
def test_a_tiny_cpu_cell_prints_every_window_metric_of_the_pass(copy, monkeypatch, workload, suffix):
    """The real batcher under the real front at a tiny size: every counter
    metric of the pass reads a number, and the numbers fit each other.
    None is a device number."""
    from oryx_tpu.serving import batcher

    root, peaks = copy
    monkeypatch.setattr(Spec, "peaks", lambda self, kind: peaks["TPU v5 lite"])
    # a batcher takes its registry handles when it is made: one that an earlier
    # test of this process left alive may predate a cleared registry
    batcher.close_default_batcher()
    out, lines = bench_run.run_cell(Spec(root), workload, 2**31 + 24, 2.0, True, require_chip=False)
    assert out["correct"] is True
    got = {k[: -len(suffix)]: v["value"] for k, v in out["metrics"].items() if k.endswith(suffix)}
    assert set(WINDOW_QUANTITIES) <= set(got)
    assert got["queue_wait_mean_ms"] > 0 and got["pass_inflight_mean_ms"] > 0
    assert got["deliver_mean_ms"] > 0
    assert got["window_rows_per_pass"] >= 1.0
    # every tiny pass fits batch bucket 8: useful share = rows a pass / 8
    assert got["useful_rows_pct"] == pytest.approx(100.0 * got["window_rows_per_pass"] / 8.0)
    assert 1.0 <= got["inflight_depth_mean"] <= 32.0
    # a request is in the handler at least as long as it queued and scanned
    assert got["handler_mean_ms"] >= got["queue_wait_mean_ms"]
    # a quantity goes to the cells its entry lists and to no other
    if suffix == ".open":
        assert got["compiles_in_window"] == 0.0
    else:
        assert "compiles_in_window.sat" not in out["metrics"]
    # a CPU trace has no device plane: the readers of the profiler's timeline find nothing
    assert "scan_kernel_ms_per_pass" not in got
    assert not any("trace_pass" in line for line in lines)
