"""The host path of a request and of a pass, stage by stage
(oryx_tpu/serving/stages.py, docs/observability.md "The host path"): the
real ServingLayer behind either front, the real batcher, and a stubbed
device call that hands back a real `TopNHandle`. Tier-1, CPU: every number
here is a count or a host time of a tiny run, none a device number."""

from __future__ import annotations

import http.client
import json
import time

import numpy as np
import pytest

from benchmark.spec import ROOT, Spec
from benchmark.stats import counter_delta as _counter_delta
from oryx_tpu import native
from oryx_tpu.common import config as C
from oryx_tpu.common import metrics
from oryx_tpu.ops import topn as topn_ops
from oryx_tpu.serving import batcher as batcher_mod
from oryx_tpu.serving import overload, stages
from oryx_tpu.serving.layer import ServingLayer
from oryx_tpu.serving.web import OryxServingException

# a request's stages, the front's first and last, then the pass's
REQUEST_STAGES = (
    "serving.handler.pre.seconds", "serving.batcher.entry.seconds",
    "serving.batcher.wake.seconds", "serving.handler.post.seconds",
)
FRONT_STAGES = ("serving.front.ingress.seconds", "serving.front.respond.seconds")
# inside `respond`, the native front's alone: its hf_respond call
RESPOND_CALL = "serving.front.respond.call.seconds"
PASS_STAGES = (
    "serving.batcher.submit.device-call.seconds", "serving.batcher.submit.seconds",
)
CPU_COUNTERS = (
    "serving.handler.cpu.seconds",
    "serving.batcher.dispatch.cpu.seconds", "serving.batcher.complete.cpu.seconds",
)
INSTRUMENTS = (
    *REQUEST_STAGES, *FRONT_STAGES, RESPOND_CALL, *PASS_STAGES, *CPU_COUNTERS,
    "serving.handler.rescans", "serving.handler.requests", "serving.process.cpu.seconds",
    "serving.front.native", "serving.batcher.hold.lag-ms",
    "serving.front.taken", "serving.front.workers",
)

# the quantities this PR's data files read, and the cells' entries on them
QUANTITIES = (
    "front_ingress_mean_ms", "front_respond_mean_ms", "handler_pre_mean_ms",
    "handler_post_mean_ms", "batcher_entry_mean_ms", "waiter_wake_mean_ms",
    "handler_cpu_ms_per_request", "server_cpu_ms_per_request", "pass_cpu_ms_per_pass",
    "result_lag_ms",
)
# the three that tile the handler's time: on every cell's line
TILES = ("handler_pre_mean_ms", "batcher_entry_mean_ms", "handler_post_mean_ms")
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _snap() -> dict:
    return metrics.registry.snapshot()


def _stub_device(monkeypatch) -> list:
    """`submit_top_k` and `submit_top_k_multi_indexed` answer row r with the
    number its query carries, through a real `TopNHandle` (whose two
    fetches the batcher times); the list says which kind each pass was."""
    import jax.numpy as jnp

    passes = []

    def handle(numbers, kk):
        idx = np.repeat(np.asarray(numbers, np.int32).reshape(-1, 1), kk, axis=1)
        return topn_ops.TopNHandle(jnp.asarray(idx, jnp.float32), jnp.asarray(idx), len(idx))

    def vectors(uploaded, queries, kk, cosine=False, nprobe=None):
        passes.append("vector")
        return handle(queries[:, 0], kk)

    def indexed(uploaded, x_dev, rows, kk, cosine=False, scan_batch=256, nprobe=None):
        passes.append("indexed")
        return handle(rows, kk)

    monkeypatch.setattr(batcher_mod.topn_ops, "submit_top_k", vectors)
    monkeypatch.setattr(batcher_mod.topn_ops, "submit_top_k_multi_indexed", indexed)
    return passes


def _scan(kind: str, n: int) -> int:
    if kind == "indexed":
        idx, _ = batcher_mod.score_indexed_default("a matrix", "staged users", n, 3)
    else:
        idx, _ = batcher_mod.score_default("a matrix", np.full(4, n, np.float32), 3)
    assert (idx == n).all()
    return n


def _routes(layer) -> None:
    """Endpoints of the test's own: what a request does between the front
    and the batcher is theirs to say, the stamps are the program's."""
    add = layer.router.add
    add("GET", "/scan/{kind}/{n}", lambda req: {"n": _scan(req.params["kind"], int(req.params["n"]))})
    add("GET", "/twice/{kind}/{n}", lambda req: {
        "n": _scan(req.params["kind"], int(req.params["n"])) + _scan(req.params["kind"], 7)
    })
    add("GET", "/answer", lambda req: {"n": 0})

    def boom(req):
        raise OryxServingException(503, "no model")

    add("GET", "/boom", boom)


@pytest.fixture(params=["python", "native"])
def served(request, monkeypatch):
    """A started layer behind the front the case names (`native-one-thread`:
    the native front with one dispatch thread, so that every request is
    that thread's), the stub device, and a fresh default batcher (one that
    predates a cleared registry holds stale handles). Every request is
    staged here, where a serving replica stages every eighth of a
    thread's."""
    front, _, one_thread = request.param.partition("-")
    if front == "native" and not hasattr(native.get_library(), "hf_create"):
        pytest.skip("the native library does not build here")
    batcher_mod.close_default_batcher()
    monkeypatch.setattr(stages, "CPU_EVERY_S", 0.0)  # every thread accounts its CPU at every turn
    monkeypatch.setattr(stages, "SAMPLE_EVERY", 1)
    passes = _stub_device(monkeypatch)
    enabled = "true" if front == "native" else "false"
    cfg = C.get_default().with_overlay(
        f"""
        oryx {{
          input-topic.broker = "inproc://host-stages-{request.param}"
          update-topic.broker = "inproc://host-stages-{request.param}"
          serving {{
            api.port = 0
            native.enabled = "{enabled}"
            {"native.dispatch-threads = 1" if one_thread else ""}
            model-manager-class = "oryx_tpu.example.serving:ExampleServingModelManager"
            application-resources = "oryx_tpu.example.serving"
          }}
        }}
        """
    )
    layer = ServingLayer(cfg)
    _routes(layer)
    layer.start()
    conn = http.client.HTTPConnection("127.0.0.1", layer.port, timeout=30)
    try:
        yield layer, conn, passes, front
    finally:
        conn.close()
        layer.close()
        batcher_mod.close_default_batcher()


def _get(conn, path: str) -> tuple[int, bytes]:
    conn.request("GET", path)
    resp = conn.getresponse()
    return resp.status, resp.read()


def _one_request(conn, path: str, status: int = 200, a_pass: bool = False) -> tuple[dict, dict]:
    """The registry before and after one request, once its `respond`
    observation (made after the bytes left) is in and, with `a_pass`, the
    completer's last word on the pass (its CPU, counted after the wake)."""
    before = {**_snap(), "_at": time.perf_counter()}
    assert _get(conn, path)[0] == status
    deadline = time.monotonic() + 10.0
    while True:
        after = {**_snap(), "_at": time.perf_counter()}
        if _counter_delta(before, after, "serving.front.respond.seconds", "count") >= 1 and (
            not a_pass or _counter_delta(before, after, "serving.batcher.complete.cpu.seconds", "value") > 0
        ):
            return before, after
        assert time.monotonic() < deadline, "the front never said it had responded"
        time.sleep(0.001)


@pytest.mark.parametrize("kind", ["vector", "indexed"])
def test_the_stages_tile_a_request_and_each_is_observed_once(served, kind):
    layer, conn, passes, front = served
    assert _snap()["serving.front.native"]["value"] == (1 if front == "native" else 0)
    assert (layer._native_front is not None) == (front == "native")
    _one_request(conn, f"/scan/{kind}/1", a_pass=True)  # the connection, the worker thread and the batcher's threads are up
    for n in range(2, 7):
        n_passes = len(passes)
        before, after = _one_request(conn, f"/scan/{kind}/{n}", a_pass=True)
        assert passes[n_passes:] == [kind]
        moved = lambda name, field: _counter_delta(before, after, name, field)
        for name in (*REQUEST_STAGES, *FRONT_STAGES, *PASS_STAGES, "serving.request.seconds"):
            assert moved(name, "count") == 1, name
        assert moved("serving.batcher.passes", "value") == 1
        assert moved("serving.handler.rescans", "value") == 0
        assert moved("serving.handler.requests", "value") == 1
        pre, entry, wake, post = (moved(name, "sum") for name in REQUEST_STAGES)
        # the same stamps end one stage and start the next
        assert pre + entry + post == pytest.approx(moved("serving.request.seconds", "sum"), abs=50e-6)
        assert 0.0 <= wake <= entry and pre > 0.0 and post > 0.0
        assert all(moved(name, "sum") >= 0.0 for name in (*FRONT_STAGES, *PASS_STAGES))
        assert moved("serving.batcher.submit.device-call.seconds", "sum") <= moved(
            "serving.batcher.submit.seconds", "sum"
        )
        assert moved(RESPOND_CALL, "count") == (1 if front == "native" else 0)
        assert 0.0 <= moved(RESPOND_CALL, "sum") <= moved("serving.front.respond.seconds", "sum")
        # CPU only rises, and a thread cannot burn more than the wall it had:
        # the serving thread's from one answer handed over to the next (with
        # the Python front the parse of the next request is in it)
        for name in (*CPU_COUNTERS, "serving.process.cpu.seconds"):
            assert moved(name, "value") >= 0.0, name
        assert 0.0 < moved("serving.handler.cpu.seconds", "value") <= after["_at"] - before["_at"]
        # no thread between the parser and the one that serves: the native
        # front's request was taken by its own thread, the take and the
        # decode are in that thread's CPU above
        assert moved("serving.front.taken", "value") == (1 if front == "native" else 0)
        assert moved("serving.batcher.dispatch.cpu.seconds", "value") > 0.0
        assert moved("serving.batcher.complete.cpu.seconds", "value") > 0.0


@pytest.mark.parametrize("served", ["python", "native", "native-one-thread"], indirect=True)
def test_ingress_runs_from_the_last_byte_parsed_to_the_serving_thread_s_begin(served):
    """`serving.front.ingress.seconds` of a request a serving thread took
    itself, with one thread and with the default 64 idle in their takes:
    once a request, not negative, and with `serving.request.seconds`,
    which starts where it ends, inside the wall the client saw (one
    machine, one monotonic clock); the front says how many threads stand."""
    layer, conn, _passes, front = served
    workers = _snap()["serving.front.workers"]["value"]
    if front == "native":
        one = layer.config.get_optional_int("oryx.serving.native.dispatch-threads")
        assert one in (None, 1) and workers == len(layer._native_front._workers) == (one or 64)
    else:
        assert workers == 0
    _one_request(conn, "/scan/vector/1")
    for n in range(2, 8):
        before, after = _one_request(conn, f"/scan/vector/{n}")
        moved = lambda name, field: _counter_delta(before, after, name, field)
        assert moved("serving.front.ingress.seconds", "count") == 1
        ingress = moved("serving.front.ingress.seconds", "sum")
        assert ingress >= 0.0
        assert ingress + moved("serving.request.seconds", "sum") <= after["_at"] - before["_at"]
        assert moved("serving.front.taken", "value") == (1 if front == "native" else 0)
        assert moved("serving.handler.requests", "value") == 1


def test_the_sweep_tool_reads_the_taken_share_100_on_the_native_front_and_0_on_the_python(served):
    """`tools/sweep_passes.py` over a window of six requests: the share
    their serving thread took itself, and its `stages:` line, which says
    it with the number of serving threads and the wall stages in order."""
    from tools import sweep_passes

    _layer, conn, _passes, front = served
    _one_request(conn, "/scan/vector/1")
    before = _snap()
    for n in range(2, 8):
        _before, after = _one_request(conn, f"/scan/vector/{n}")
    assert _counter_delta(before, after, "serving.handler.requests", "value") == 6
    share = sweep_passes.taken_pct(before, after)
    assert share == (100.0 if front == "native" else 0.0)
    row = dict.fromkeys(
        ("front_ingress_mean_ms", "handler_pre_mean_ms", "batcher_entry_mean_ms",
         "queue_wait_mean_ms", "pass_inflight_mean_ms", "waiter_wake_mean_ms",
         "handler_post_mean_ms", "front_respond_mean_ms"), 0.25)
    row.update(
        front_native=after["serving.front.native"]["value"], taken_pct=share,
        front_workers=after["serving.front.workers"]["value"], requests_per_s=6.0,
    )
    line = sweep_passes.stages_line(row)
    if front == "native":
        assert line.startswith("stages: front_native 1, taken 100.0 % of 6 requests/s by 64 serving threads; ")
    else:
        assert line.startswith("stages: front_native 0, taken 0.0 % of 6 requests/s by 0 serving threads; ")
    assert line.endswith("ingress 0.250 -> pre 0.250 -> entry 0.250 -> queue 0.250 -> in flight 0.250 "
                         "-> wake 0.250 -> post 0.250 -> respond 0.250 ms")


@pytest.mark.parametrize("what", ["cache-hit", "shed", "error", "unknown-path"])
def test_a_request_that_never_scans_feeds_the_front_s_stages_and_no_other(served, monkeypatch, what):
    """The ladder's decision is the Python side's in both fronts here
    (pinned on `decide`; the native front's own rungs never reach Python
    and feed nothing at all)."""
    layer, conn, passes, _front = served
    path, status = {"error": ("/boom", 503), "unknown-path": ("/nowhere", 404)}.get(what, ("/answer", 200))
    if what == "shed":
        status = 429
        monkeypatch.setattr(
            layer.admission, "decide", lambda *a, **k: overload.Decision(overload.STAGE_SHED)
        )
    elif what == "cache-hit":
        monkeypatch.setattr(
            layer.admission, "decide", lambda *a, **k: overload.Decision(overload.STAGE_STALE)
        )
        monkeypatch.setattr(
            layer.admission.cache, "get",
            lambda key, generation: overload.CachedAnswer("g", 200, {"n": "cached"}, None),
        )
    _one_request(conn, path, status)
    before, after = _one_request(conn, path, status)
    if what == "cache-hit":
        assert _counter_delta(before, after, "serving.overload.shed.stale", "value") == 1
    for name in REQUEST_STAGES + PASS_STAGES:
        assert _counter_delta(before, after, name, "count") == 0, name
    for name in (*FRONT_STAGES, "serving.request.seconds"):
        assert _counter_delta(before, after, name, "count") == 1, name
    assert _counter_delta(before, after, "serving.handler.cpu.seconds", "value") > 0.0
    assert not passes


def test_a_request_that_scans_twice_feeds_entry_and_wake_twice_and_counts_a_rescan(served):
    _layer, conn, passes, _front = served
    _one_request(conn, "/twice/vector/1")
    before, after = _one_request(conn, "/twice/vector/2")
    moved = lambda name, field="count": _counter_delta(before, after, name, field)
    assert moved("serving.batcher.entry.seconds") == moved("serving.batcher.wake.seconds") == 2
    assert moved("serving.handler.pre.seconds") == moved("serving.handler.post.seconds") == 1
    assert moved("serving.handler.rescans", "value") == 1 and len(passes) == 4
    # `pre` ends at the first scan and `post` starts after the last: what
    # lies between the two scans is in neither, so the tiles fall short
    tiles = sum(moved(name, "sum") for name in REQUEST_STAGES if "wake" not in name)
    assert tiles <= moved("serving.request.seconds", "sum") + 50e-6


def test_a_scan_outside_any_request_feeds_no_stage(monkeypatch):
    """A tool or a test that asks the batcher from a thread no front began
    a request on: nobody staged it, and `entry` and `wake` are a staged
    request's."""
    batcher_mod.close_default_batcher()
    _stub_device(monkeypatch)
    before = _snap()
    try:
        _scan("vector", 5)
    finally:
        batcher_mod.close_default_batcher()
    after = _snap()
    assert _counter_delta(before, after, "serving.batcher.passes", "value") == 1
    for name in REQUEST_STAGES:
        assert _counter_delta(before, after, name, "count") == 0, name


@pytest.mark.parametrize("served", ["python"], indirect=True)  # one connection, one thread
def test_a_thread_stages_its_first_request_and_every_eighth_after_it(served, monkeypatch):
    """As a replica runs: of a thread's 17 requests the 1st, 9th and 17th
    feed the stages, each tiling its own `serving.request.seconds`
    observation, the others feed none; the thread counts all 17 for the
    CPU counters' reading, at the instants it accounts its CPU."""
    _layer, conn, passes, _front = served
    monkeypatch.setattr(stages, "SAMPLE_EVERY", 8)
    start = _snap()
    for n in range(17):
        if n % 8 == 0:
            before, after = _one_request(conn, f"/scan/indexed/{n}")
            moved = lambda name, field="count": _counter_delta(before, after, name, field)
            assert all(moved(name) == 1 for name in REQUEST_STAGES + FRONT_STAGES)
            pre, entry, _wake, post = (moved(name, "sum") for name in REQUEST_STAGES)
            assert pre + entry + post == pytest.approx(moved("serving.request.seconds", "sum"), abs=50e-6)
            assert _counter_delta(start, after, "serving.handler.requests", "value") == n + 1
        else:
            before = _snap()
            assert _get(conn, f"/scan/indexed/{n}")[0] == 200
            after = _snap()
            assert _counter_delta(before, after, "serving.request.seconds", "count") == 1
            for name in REQUEST_STAGES + FRONT_STAGES:
                assert _counter_delta(before, after, name, "count") == 0, name
    assert len(passes) == 17


@pytest.mark.parametrize("served", ["python", "native-one-thread"], indirect=True)
def test_the_respond_call_is_a_staged_request_s_and_lies_inside_its_respond(served, monkeypatch):
    """`serving.front.respond.call.seconds`: once a staged request of the
    native front (the 1st, 9th and 17th of its one thread), inside that
    request's `serving.front.respond.seconds`; never for the seven between
    two of them, answers and errors alike, and never by the Python front,
    which makes no such call."""
    _layer, conn, _passes, front = served
    monkeypatch.setattr(stages, "SAMPLE_EVERY", 8)
    start = _snap()
    for n in range(17):
        path, status = ("/boom", 503) if n % 4 == 0 else (f"/scan/vector/{n}", 200)
        if n % 8:
            assert _get(conn, path)[0] == status
        else:
            before, after = _one_request(conn, path, status)
            moved = lambda name, field="count": _counter_delta(before, after, name, field)
            assert moved(RESPOND_CALL) == (1 if front == "native" else 0)
            assert 0.0 <= moved(RESPOND_CALL, "sum") <= moved("serving.front.respond.seconds", "sum")
            if front == "native":
                assert moved(RESPOND_CALL, "sum") > 0.0
    # the 17th answer's observations are the thread's last: the seven
    # before it fed nothing that a later snapshot could still find
    done = _snap()
    assert _counter_delta(start, done, "serving.front.respond.seconds", "count") == 3
    assert _counter_delta(start, done, RESPOND_CALL, "count") == (3 if front == "native" else 0)
    assert _counter_delta(start, done, "serving.request.seconds", "count") == 17


@pytest.mark.parametrize("name", INSTRUMENTS)
def test_a_process_that_served_nothing_reads_zero_not_nothing(name):
    """Every handle is taken at construction (`ServingLayer.__init__`,
    `TopNBatcher.__init__`): a reader's delta over a window that fed none
    is 0 where a missing instrument would be nothing."""
    cfg = C.get_default().with_overlay(
        """
        oryx.input-topic.broker = "inproc://host-stages-idle"
        oryx.update-topic.broker = null
        oryx.serving.api.port = 0
        """
    )
    ServingLayer(cfg)  # never started: no front, no request
    b = batcher_mod.TopNBatcher()
    b.close()
    entry = _snap()[name]
    assert entry["type"] in ("histogram", "counter", "gauge")
    if entry["type"] == "histogram":
        assert entry["count"] >= 0
    elif name not in ("serving.front.native", "serving.batcher.hold.lag-ms"):
        assert entry["value"] >= 0.0  # these two say something once a front starts / a lag is read


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_a_quantity_s_file_reads_instruments_the_program_has(quantity):
    file = Spec().layer_metric(quantity + ".open")
    assert file["name"] == quantity and file["unit"] == "ms" and file["better"] == "lower"
    assert file["source"] == "program_counter" and file["args"]["span"] == "window"
    assert file["layer"] in ("HTTP front and handlers", "batcher")
    if file["reduction"] == "counter_value":
        read = [file["args"]["metric"]]
    else:
        assert file["reduction"] == "counter_ratio" and file["args"]["scale"] == 1000.0
        read = file["args"]["num"] + file["args"]["den"]
    for metric, field in read:
        assert metric in INSTRUMENTS, metric  # none that the parent had: it reads nothing, not 0
        assert field in ("sum", "count", "value")
    # one file a quantity: every entry on it is the quantity and a suffix,
    # lists cells that exist, and moves what those cells report
    entries = [m for m in DOC["per_layer"] if m["name"].rsplit(".", 1)[0] == quantity]
    assert {m["name"] for m in entries} >= {quantity + ".open"} | ({quantity + ".sat"} if quantity in TILES else set())
    cells = {w["name"]: w for w in DOC["workloads"]}
    for m in entries:
        suffix = m["name"].rsplit(".", 1)[1]
        assert suffix in ("open", "sat") and set(m["workloads"]) <= set(cells)
        assert m["moves"] == ("recommend_qps" if suffix == "sat" else "recommend_p95_ms")
        assert all(w.endswith("-sat") == (suffix == "sat") for w in m["workloads"])


@pytest.mark.parametrize("suffix", ["open", "sat"])
def test_the_tiny_cpu_cell_prints_the_stages(tmp_path, monkeypatch, suffix):
    """The real front, handlers and batcher under the benchmark's own
    drivers: every entry of this PR's is on the result line with a number,
    and the three tiles sum to the handler's mean."""
    from benchmark import run as bench_run
    from benchmark import testing

    root = testing.make_copy(tmp_path)
    peaks = json.loads((root / "benchmark" / "peaks.json").read_text())
    monkeypatch.setattr(Spec, "peaks", lambda self, kind: peaks["TPU v5 lite"])
    monkeypatch.setattr(stages, "SAMPLE_EVERY", 1)  # 2 s of a tiny cell: the eighth is a handful
    batcher_mod.close_default_batcher()
    workload = testing.TINY_OPEN if suffix == "open" else testing.TINY_SAT
    out, _lines = bench_run.run_cell(Spec(root), workload, 2**31 + 35, 2.0, True, require_chip=False)
    assert out["correct"] is True
    got = out["metrics"]
    mine = [m["name"] for m in DOC["per_layer"]
            if m["name"].rsplit(".", 1)[0] in QUANTITIES + ("submit_mean_ms",)
            and m["name"].endswith("." + suffix)]
    assert len(mine) >= 5
    for name in mine:
        if not name.startswith("result_lag_ms"):  # a tiny CPU pass is never timed behind another
            assert got[name]["unit"] == "ms" and got[name]["value"] >= 0.0, name
    tiles = sum(got[f"{q}.{suffix}"]["value"] for q in TILES)
    assert tiles == pytest.approx(got[f"handler_mean_ms.{suffix}"]["value"], rel=0.01)
    assert got[f"waiter_wake_mean_ms.{suffix}"]["value"] <= got[f"batcher_entry_mean_ms.{suffix}"]["value"]
