"""The four-chip cell `als250x20m-recommend-open` (configuration
`als-250f-20m-f32-x4`: the item matrix row-sharded over a host's chips):

- the command end to end on the CPU's virtual devices at a tiny size, the
  sharded builder ADDED to a temporary copy of the benchmark (as
  benchmark/testing.py adds its tiny cells), float32 and the bf16 control;
- the builder failing at once on a program without the sharded path;
- the new per-layer readers on a synthetic four-plane trace;
- a compile rehearsal of the sharded program for a v5e 2x2 host at the
  cell's own shapes (no chip needed; says nothing about time).

A CPU run's numbers are read for their shape only."""

import importlib
import json
import sys
from types import SimpleNamespace

import pytest

from benchmark import run as bench_run
from benchmark import testing, trace
from benchmark.reductions import trace_scan, trace_shard
from benchmark.spec import ROOT, Spec

CELL = "als250x20m-recommend-open"
CONFIG = "als-250f-20m-f32-x4"
TINY_X4, TINY_X4_CONFIG = "tiny-x4-recommend-open", "tiny-als-16f-x4"
PEAKS = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """benchmark/testing.py's copy, plus a tiny sharded configuration and
    its cell, added as files and entries."""
    import jax

    root = testing.make_copy(tmp_path_factory.mktemp("bench_x4"))
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / f"{CONFIG}.json").read_text())
    cfg.update(name=TINY_X4_CONFIG, features=16, items=3001, users=400,
               shards=jax.device_count(), source="test", reduced=["items", "users"])
    (bench / "configs" / f"{TINY_X4_CONFIG}.json").write_text(json.dumps(cfg))
    (bench / "cells" / f"{TINY_X4}.json").write_text(json.dumps({"rate_per_s": 60}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": TINY_X4_CONFIG, "source": "test", "reduced": ["items", "users"],
                           "file": f"benchmark/configs/{TINY_X4_CONFIG}.json", "why": "tier-1"})
    doc["workloads"].append({"name": TINY_X4, "config": TINY_X4_CONFIG, "traffic": "tiny-open",
                             "chips": 4, "why": "tier-1"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY_X4)
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return root


def _run(root, seed, trace=False, **kw):
    return bench_run.run_cell(Spec(root), TINY_X4, seed, 2.0, trace, require_chip=False, **kw)


def test_sharded_cell_end_to_end_is_correct_and_batched(copy, monkeypatch, capsys):
    monkeypatch.setattr(Spec, "peaks", lambda self, kind: PEAKS)
    out, lines = _run(copy, 2**31 + 26, trace=True)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 120
    got = out["metrics"]
    assert got["sharded_submit_pct.x4"]["value"] == 100.0
    assert got["window_rows_per_pass.open"]["value"] >= 1.0
    assert got["compiles_in_window.open"]["value"] == 0.0
    assert {"handler_mean_ms.open", "queue_wait_mean_ms.open", "pass_inflight_mean_ms.open",
            "deliver_mean_ms.open", "useful_rows_pct.open", "inflight_depth_mean.open",
            "recommend_p50_ms.open", "recommend_p99_ms.open", "window_failed_pct.open",
            "generator_late_p99_ms.open", "generator_pause_max_ms.open",
            "server_pause_max_ms.open", "held_pass_pct.open"} <= set(got)
    # since PR 39 the cell is on the lists of the host-path stages and of the submit
    assert got["submit_mean_ms.open"]["value"] > 0.0
    assert {"front_ingress_mean_ms.open", "front_respond_mean_ms.open", "handler_pre_mean_ms.open",
            "handler_post_mean_ms.open", "batcher_entry_mean_ms.open", "waiter_wake_mean_ms.open",
            "handler_cpu_ms_per_request.open", "server_cpu_ms_per_request.open",
            "pass_cpu_ms_per_pass.open"} <= set(got)
    # the one-chip readers are not this cell's (a sharded row would be counted twice)
    assert not {"scan_roofline.open", "scan_rows_per_pass.open", "indexed_submit_pct.open"} & set(got)
    # a CPU trace holds no named kernel: the device-trace readers return
    # nothing and the line leaves their metrics out
    assert not {"shard_scan_roofline.x4", "shard_merge_ms_per_pass.x4", "shard_skew_pct.x4",
                "scan_kernel_ms_per_pass.open"} & set(got)
    # the builder says where the data is: every device a slice, users on each
    import jax

    layout = next(x for x in capsys.readouterr().out.splitlines() if x.startswith("shard layout:"))
    assert layout.count("dev") == jax.device_count() and "host peak resident" in layout
    assert ":(0," not in layout


def test_bfloat16_through_the_sharded_path_fails_the_check(copy):
    out, lines = _run(copy, 5, score_dtype="bfloat16")
    assert out["correct"] is False
    assert "FAIL" in next(x for x in lines if "score_err_of_scale" in x)


def test_on_a_program_without_the_sharded_path_the_cell_fails_at_once(copy, monkeypatch):
    """The parent of the PR that brought the cell: the builder's import
    resolves the program's entry point first and raises before any factor
    is made (a parent that ran on would exhaust chip 0 minutes later)."""
    from oryx_tpu.ops import topn as topn_ops

    name = "benchmark.builders.loadtest_als_sharded"
    monkeypatch.delattr(topn_ops, "sharded_layout")
    monkeypatch.delitem(sys.modules, name, raising=False)
    made = []
    from benchmark.builders import loadtest_als

    monkeypatch.setattr(loadtest_als, "make_arrays", lambda *a: made.append(1))
    try:
        with pytest.raises(ImportError, match="no batched shard-items serving path"):
            bench_run.Session(Spec(copy), TINY_X4, 1, require_chip=False)
    finally:
        monkeypatch.undo()
        sys.modules.pop(name, None)
        importlib.import_module(name)
    assert made == []


# -- the readers on a four-plane trace ------------------------------------------------------

SCAN = "%oryx_topn_scan.2 = (f32[8,32]{1,0:T(8,128)}, s32[8,32]{1,0:T(8,128)}) custom-call(%fusion)"
MERGE = ["%all-gather.10 = f32[8,128]{0,1} all-gather(%copy.10)", "%top_k.12 = (f32[1,8,32]) sort(%copy.16)",
         "%fusion.3 = s32[1,8,32] fusion(%top_k.12)"]


def _four_planes(scan_us=(6900, 6950, 6800, 7000), passes=50):
    planes = []
    for chip, us in enumerate(scan_us):
        events, t = [], 1_000
        for _ in range(passes):
            events.append([SCAN, t, us * 1000])
            t += us * 1000
            for name in MERGE:
                events.append([name, t, 20_000])
                t += 20_000
            t += 2_000
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Ops", "events": events}, {"name": "Steps", "events": [["1", 0, t]]}]})
    return planes


def _ctx(planes, rows_per_pass=2.5, passes=50):
    cell = Spec().cell(CELL)
    reduced = trace.reduce_planes(planes, 0.4)
    counters = {"trace": ({"serving.scan.sharded.queries": {"value": 10.0},
                           "serving.scan.indexed.queries": {"value": 10.0}},
                          {"serving.scan.sharded.queries": {"value": 10.0 + rows_per_pass * passes},
                           "serving.scan.indexed.queries": {"value": 10.0 + rows_per_pass * passes}})}
    return SimpleNamespace(cell=cell, trace=reduced, counters=counters, peaks=PEAKS, lines=[])


def test_shard_readers_on_a_four_plane_trace():
    ctx = _ctx(_four_planes())
    assert ctx.trace["planes"] == 4
    mean_ms = (6.9 + 6.95 + 6.8 + 7.0) / 4
    # 5M x 250 x 4 B + norms + queries + candidates at 819 GB/s: 6.13 ms
    least, bound = trace_shard.shard_least_seconds(ctx.cell.config, 2.5, 32, PEAKS)
    assert bound == "bytes" and least == pytest.approx(5.02e9 / 819e9, rel=2e-3)
    share = trace_shard.read(ctx, {"stat": "roofline_pct", "k_bucket": 32})
    assert share == pytest.approx(100.0 * least / (mean_ms / 1e3), rel=1e-6) and 80 < share < 100
    assert "2.500 rows a pass, 5000000 items a shard" in ctx.lines[-1]
    assert trace_shard.read(ctx, {"stat": "merge_ms_per_pass"}) == pytest.approx(0.06)
    per_plane = trace_shard.kernel_seconds_by_plane(_four_planes())
    assert per_plane == pytest.approx([0.345, 0.3475, 0.34, 0.35])
    assert trace_shard.skew_pct(per_plane) == pytest.approx(100 * 0.01 / (sum(per_plane) / 4))
    # the existing readers on four planes: the mean chip's time, a chip's passes
    assert trace_scan.read(ctx, {"match": "custom-call", "stat": "ms_per_pass"}) == pytest.approx(mean_ms)
    # the device's idle share is averaged over the planes
    assert ctx.trace["busy_s"] == pytest.approx(50 * (mean_ms / 1e3 + 60e-6), rel=1e-6)
    assert ctx.trace["breakdown"]["device_ops"][0][0].startswith("oryx_topn_scan")


def test_the_shard_roofline_cannot_pass_100_where_the_one_chip_reader_would():
    """At the stream of one shard (5.12 GB as stored at 819 GB/s, 6.25 ms)
    the shard's share stays under 100; the one-chip `scan_roofline` reader
    takes the configuration's 20M items against one chip's peaks and would
    read about 360 %, which the driver refuses: the cell is on none of its
    lists."""
    planes = _four_planes(scan_us=(6250, 6250, 6250, 6250))
    ctx = _ctx(planes)
    assert 95 < trace_shard.read(ctx, {"stat": "roofline_pct"}) < 100
    ctx.counters["trace"][1]["serving.scan.sharded.queries"]["value"] = 10.0  # as trace_scan sums
    assert trace_scan.read(ctx, {"match": "custom-call", "stat": "roofline_pct"}) > 300
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in doc["per_layer"]:
        if m["name"].startswith("scan_roofline") or m["name"].startswith("scan_rows_per_pass"):
            assert CELL not in m["workloads"]


def test_readers_find_nothing_on_a_trace_without_the_kernel():
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [["%fusion = f32[8]", 0, 1000]]}]}]
    ctx = _ctx(planes)
    for stat in ("roofline_pct", "merge_ms_per_pass", "skew_pct"):
        assert trace_shard.read(ctx, {"stat": stat}) is None
    assert trace_shard.read(SimpleNamespace(trace=None), {"stat": "skew_pct"}) is None


# -- compile rehearsal: the sharded program for a v5e 2x2 host ------------------------------


@pytest.fixture(scope="module")
def host_mesh():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.asarray(topo.devices), ("data",))


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("batch", [8, 16, 32, 64, 128])
def test_sharded_scan_program_compiles_for_a_v5e_host(batch, host_mesh, no_persistent_cache):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from oryx_tpu.ops import pallas_topn, topn

    cfg = json.loads((ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    d = cfg["shards"]
    assert d == host_mesh.devices.size == cfg["chips"]
    per_shard = cfg["items"] // d
    cols = pallas_topn._ceil_to(per_shard, pallas_topn.BLOCK_N)
    f = cfg["features"]

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(host_mesh, spec))

    fn = topn._sharded_scan_fn(host_mesh, 32, False, False, True, None, False)
    lowered = fn.lower(
        shape((f, d * cols), jnp.float32, P(None, "data")),
        shape((1, d * cols), jnp.float32, P(None, "data")),
        (),
        shape((d,), jnp.int32, P("data")), shape((d,), jnp.int32, P("data")),
        shape((1, batch), jnp.int32, P()),
        shape((int(cfg["users"] * 1.25), f), jnp.float32, P()),
    )
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    text = compiled.as_text()
    assert "oryx_topn_scan" in text and "all-gather" in text
    assert f"f32[{f},{cols}]" in text  # the kernel is given one shard, not the catalog
    vals, idxs = lowered.out_info
    assert vals.shape == idxs.shape == (1, batch, 32)
    mem = compiled.memory_analysis()  # a device's own: its shard and the staged users
    assert per_shard * f * 4 <= mem.argument_size_in_bytes < 8 * 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
