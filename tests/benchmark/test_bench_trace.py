"""The reduction from a device trace to busy time, idle gaps, scan passes
and kernel time, on a small trace recorded on the chip (benchmark/testdata:
80 scan passes of cell als50-recommend-open on a TPU v5 lite, PR 23). The
numbers asserted are what that recording holds; they check the arithmetic,
they are not measurements of this tree."""

from types import SimpleNamespace

import pytest

from benchmark import trace
from benchmark.reductions import counter_ratio, trace_device, trace_scan
from benchmark.spec import ROOT, Spec

RECORDED = ROOT / "benchmark" / "testdata" / "trace_als50_v5e.json.gz"


@pytest.fixture(scope="module")
def planes():
    return trace.load_planes(str(RECORDED))


def test_only_single_operations_count_as_busy(planes):
    reduced = trace.reduce_planes(planes, window_s=1.26)
    # 400 operations on the "XLA Ops" line; the 79 "XLA Modules" events
    # cover the same time at a coarser grain and are not added on top
    assert sum(reduced["op_count"].values()) == 400
    assert reduced["planes"] == 1
    assert reduced["busy_s"] == pytest.approx(1.247663, abs=1e-5)
    assert reduced["busy_s"] <= reduced["window_s"] == 1.26


def test_the_window_is_never_shorter_than_what_the_device_events_cover(planes):
    reduced = trace.reduce_planes(planes, window_s=0.5)
    assert reduced["window_s"] == pytest.approx(1.24777, abs=1e-4)
    assert reduced["busy_s"] <= reduced["window_s"]


def test_scan_passes_and_kernel_time_by_op_name(planes):
    reduced = trace.reduce_planes(planes, window_s=1.26)
    passes, seconds = trace.matching(reduced, "custom-call")
    assert passes == 80  # 77 at batch bucket 8 and 3 at bucket 16
    assert 1000 * seconds / passes == pytest.approx(15.594, abs=1e-2)
    assert trace.matching(reduced, "no-such-kernel") == (0, 0.0)


def test_breakdown_names_are_short_and_gaps_are_named_by_what_ran_before(planes):
    b = trace.reduce_planes(planes, window_s=1.26)["breakdown"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "closed_call.7_custom-call_f32_8_32_s32_8_32"
    assert b["device_ops"][0][1] == pytest.approx(1.17064, abs=1e-4)
    assert all(name.startswith("after_") and gap > 0 for name, gap in b["idle_gaps"])
    assert b["idle_gaps"] == sorted(b["idle_gaps"], key=lambda g: -g[1])
    assert trace._short("%copy = f32[8,50]{1,0:T(8,128)S(1)} copy(f32[8,50]{0,1} %fusion)") == (
        "copy_copy_f32_8_50"
    )


def _ctx(planes, window_s=1.26, rows=320):
    before = {"serving.scan.indexed.queries": {"type": "counter", "value": 1000.0}}
    after = {"serving.scan.indexed.queries": {"type": "counter", "value": 1000.0 + rows}}
    cell = Spec().cell("als50-recommend-open")
    return SimpleNamespace(
        cell=cell, loadgen={}, lines=[], peaks=Spec().peaks("TPU v5 lite"),
        counters={"window": (before, after), "trace": (before, after)},
        trace=trace.reduce_planes(planes, window_s),
    )


def test_readers_give_idle_share_rows_per_pass_and_a_roofline_under_100(planes):
    ctx = _ctx(planes)
    assert trace_device.read(ctx, {"stat": "idle_pct"}) == pytest.approx(
        100 * (1 - 1.247663 / 1.26), abs=1e-3
    )
    assert trace_scan.read(ctx, {"match": "custom-call", "stat": "rows_per_pass"}) == 4.0
    assert trace_scan.read(ctx, {"match": "custom-call", "stat": "ms_per_pass"}) == pytest.approx(
        15.594, abs=1e-2
    )
    share = trace_scan.read(ctx, {"match": "custom-call", "stat": "roofline_pct", "k_bucket": 32})
    # 20M x 50 float32 + norms over 819 GB/s = 4.98 ms, of a 15.59 ms pass
    assert share == pytest.approx(100 * 4.9817 / 15.594, abs=0.1) and share < 100
    assert any("bytes-bound" in line for line in ctx.lines)


def test_a_reader_that_finds_nothing_returns_nothing(planes):
    ctx = _ctx(planes)
    assert trace_scan.read(ctx, {"match": "no-such-kernel", "stat": "ms_per_pass"}) is None
    ctx.trace = None
    assert trace_device.read(ctx, {"stat": "idle_pct"}) is None
    assert trace_scan.read(ctx, {"match": "custom-call", "stat": "ms_per_pass"}) is None
    ctx.counters["trace"] = None
    assert counter_ratio.read(ctx, {"num": [["x", "value"]], "span": "trace"}) is None
    # a ratio whose denominator did not move has nothing to read either
    assert counter_ratio.read(
        ctx, {"num": [["serving.scan.indexed.queries", "value"]],
              "den": [["serving.scan.vector.queries", "value"]], "span": "window"}
    ) is None


def test_counter_ratio_reads_deltas_not_totals(planes):
    ctx = _ctx(planes, rows=250)
    args = {"num": [["serving.scan.indexed.queries", "value"]], "span": "window"}
    assert counter_ratio.read(ctx, args) == 250.0
    pct = dict(args, den=[["serving.scan.indexed.queries", "value"],
                          ["serving.scan.vector.queries", "value"]], scale=100.0)
    assert counter_ratio.read(ctx, pct) == 100.0


def test_record_reads_its_counters_while_the_profiler_records(tmp_path, monkeypatch):
    """Queries are set against the passes of the trace, so both cover the
    same stretch: the snapshots fall between the profiler's start and its
    stop, not around the start, the stop and the parse (which read rows a
    pass a fifth too high in PR 23's first traced runs)."""
    import jax

    order = []

    def start(trace_dir, **_kw):
        order.append("start")
        out = tmp_path / "t" / "plugins" / "profile" / "run"
        out.mkdir(parents=True)
        (out / "host.xplane.pb").write_bytes(b"")

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: order.append("stop"))
    monkeypatch.setattr(trace, "planes_from_xplane", lambda path: order.append("parse") or [])

    def snapshot():
        order.append("snapshot")
        return {"n": order.count("snapshot")}

    planes, window_s, span = trace.record(str(tmp_path / "t"), 0.05, snapshot)
    assert order == ["start", "snapshot", "snapshot", "stop", "parse"]
    assert span == ({"n": 1}, {"n": 2}) and planes == [] and 0.05 <= window_s < 1.0
    assert trace.record(str(tmp_path / "t"), 0.01)[2] is None  # no reader, no span
