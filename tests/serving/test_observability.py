"""Serving-side observability surfaces: GET /trace export and the
traceparent request join, batcher lifecycle spans, Prometheus content
negotiation on /metrics, POST /debug/profile, the `cli trace` command,
and the update-apply freshness/span instrumentation."""

import io
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from oryx_tpu import bus
from oryx_tpu.common import config as C
from oryx_tpu.common import metrics, tracing
from oryx_tpu.common.tracing import TraceContext
from oryx_tpu.serving.layer import ServingLayer


@pytest.fixture(autouse=True)
def _traced(monkeypatch):
    """Sample every root (the default 1% would make span assertions
    flaky) — via the env override so ServingLayer's configure_from picks
    it up too — and leave a clean tracer behind."""
    monkeypatch.setenv("ORYX_TRACING_SAMPLE_RATE", "1.0")
    tracing.reset()
    yield
    monkeypatch.delenv("ORYX_TRACING_SAMPLE_RATE", raising=False)
    tracing.reset()


def make_config(broker, **overrides):
    extra = "\n".join(f"{k} = {v}" for k, v in overrides.items())
    return C.get_default().with_overlay(
        f"""
        oryx {{
          input-topic.broker = "{broker}"
          update-topic.broker = "{broker}"
          serving {{
            api.port = 0
            model-manager-class = "oryx_tpu.example.serving:ExampleServingModelManager"
            application-resources = "oryx_tpu.example.serving"
            {extra}
          }}
        }}
        """
    )


def http(method, url, body=None, headers=None):
    req = urllib.request.Request(url, data=body, method=method, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=5) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def wait_for(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def _ready_layer(broker_loc, **overrides):
    broker = bus.get_broker(broker_loc)
    layer = ServingLayer(make_config(broker_loc, **overrides))
    layer.start()
    base = f"http://127.0.0.1:{layer.port}"
    with broker.producer("OryxUpdate") as p:
        p.send("MODEL", json.dumps({"a": 2, "b": 1}))
    assert wait_for(lambda: http("GET", f"{base}/ready")[0] == 200)
    return broker, layer, base


def test_request_span_joins_incoming_traceparent():
    broker, layer, base = _ready_layer("inproc://obs-join")
    try:
        ctx = tracing.sample_root()
        assert ctx is not None
        status, _, _ = http(
            "GET", f"{base}/distinct", headers={"traceparent": ctx.traceparent()}
        )
        assert status == 200
        # the server-side breakdown of that request is one GET away,
        # keyed by the trace id the client already holds
        status, body, _ = http(
            "GET", f"{base}/trace?format=spans&trace={ctx.trace_id}"
        )
        assert status == 200
        doc = json.loads(body)
        assert doc["enabled"] is True
        (req_span,) = [s for s in doc["spans"] if s["name"] == "serving.request"]
        assert req_span["trace"] == ctx.trace_id
        assert req_span["parent"] == ctx.span_id  # joined, not re-rooted
        assert req_span["attrs"]["path"] == "/distinct"
        assert req_span["attrs"]["status"] == 200
    finally:
        layer.close()


def test_trace_endpoint_chrome_export():
    broker, layer, base = _ready_layer("inproc://obs-chrome")
    try:
        ctx = tracing.sample_root()
        http("GET", f"{base}/distinct", headers={"traceparent": ctx.traceparent()})
        status, body, headers = http("GET", f"{base}/trace?trace={ctx.trace_id}")
        assert status == 200
        assert headers["Content-Type"].startswith("application/json")
        doc = json.loads(body)
        assert doc["displayTimeUnit"] == "ms"
        (ev,) = [
            e for e in doc["traceEvents"] if e["args"]["trace"] == ctx.trace_id
        ]
        assert ev["ph"] == "X" and ev["dur"] >= 0
        assert ev["name"] == "serving.request"
    finally:
        layer.close()


def test_metrics_prometheus_content_negotiation():
    broker, layer, base = _ready_layer("inproc://obs-prom")
    try:
        http("GET", f"{base}/distinct")
        # default: JSON
        status, body, headers = http("GET", f"{base}/metrics")
        assert status == 200 and headers["Content-Type"].startswith("application/json")
        assert "serving.request.seconds" in json.loads(body)
        # a standard scraper's Accept header gets text exposition 0.0.4
        for target in (
            (f"{base}/metrics", {"Accept": "text/plain;version=0.0.4"}),
            (f"{base}/metrics?format=prometheus", {}),
        ):
            status, body, headers = http("GET", target[0], headers=target[1])
            assert status == 200
            assert headers["Content-Type"] == metrics.PROMETHEUS_CONTENT_TYPE
            text = body.decode()
            assert "# TYPE serving_request_seconds histogram" in text
            assert 'serving_request_seconds_bucket{le="+Inf"}' in text
            assert "serving_request_seconds_count" in text
        # ?format=json wins over the Accept header
        status, body, headers = http(
            "GET", f"{base}/metrics?format=json", headers={"Accept": "text/plain"}
        )
        assert headers["Content-Type"].startswith("application/json")
        json.loads(body)
    finally:
        layer.close()


def test_debug_profile_requires_profile_dir(tmp_path, monkeypatch):
    from oryx_tpu.common import profiling

    broker, layer, base = _ready_layer("inproc://obs-prof")
    try:
        status, body, _ = http("POST", f"{base}/debug/profile")
        assert status == 503 and b"profile-dir" in body
    finally:
        layer.close()

    captured = {}

    def fake_capture(profile_dir, name, seconds):
        captured.update(dir=profile_dir, name=name, seconds=seconds)
        return f"{profile_dir}/{name}"

    monkeypatch.setattr(profiling, "capture", fake_capture)
    broker, layer, base = _ready_layer(
        "inproc://obs-prof2", **{"compute.profile-dir": f'"{tmp_path}"'}
    )
    try:
        before = metrics.registry.counter("serving.debug.profiles").value
        status, body, _ = http("POST", f"{base}/debug/profile?seconds=99")
        assert status == 200
        doc = json.loads(body)
        assert doc["seconds"] == 30.0  # capped
        assert captured["seconds"] == 30.0 and captured["dir"] == str(tmp_path)
        assert doc["path"].startswith(str(tmp_path))
        assert metrics.registry.counter("serving.debug.profiles").value == before + 1
    finally:
        layer.close()


def test_cli_trace_dumps_span_ring(tmp_path):
    from oryx_tpu import cli

    broker, layer, base = _ready_layer("inproc://obs-cli")
    try:
        ctx = tracing.sample_root()
        http("GET", f"{base}/distinct", headers={"traceparent": ctx.traceparent()})
        probe_cfg = make_config("inproc://obs-cli").with_overlay(
            f"oryx.serving.api.port = {layer.port}"
        )
        out = io.StringIO()
        assert cli.run_trace(probe_cfg, out=out) == 0
        doc = json.loads(out.getvalue())
        assert any(
            e["args"]["trace"] == ctx.trace_id for e in doc["traceEvents"]
        )
        # filtered by trace id
        out2 = io.StringIO()
        assert cli.run_trace(probe_cfg, ctx.trace_id, out=out2) == 0
        doc2 = json.loads(out2.getvalue())
        assert doc2["traceEvents"] and all(
            e["args"]["trace"] == ctx.trace_id for e in doc2["traceEvents"]
        )
    finally:
        layer.close()
    # layer gone: unreachable exits 1
    out3 = io.StringIO()
    assert cli.run_trace(probe_cfg, out=out3) == 1


def test_update_apply_spans_and_freshness():
    """The consumer side of the publish->apply pair: an UP block carrying
    a `@trc` header feeds serving.freshness.seconds (global + instance)
    and records a serving.apply span with the propagation skew; a MODEL
    block records serving.model.apply."""
    broker, layer, base = _ready_layer("inproc://obs-apply")
    try:
        fresh0 = metrics.registry.histogram("serving.freshness.seconds").count
        ctx = TraceContext("ab" * 16, "cd" * 8, True)
        origin_ms = int(time.time() * 1000) - 3000  # published 3s ago
        records, extra = tracing.with_header([("UP", "c,5")], ctx, origin_ms)
        assert extra == 1
        with broker.producer("OryxUpdate") as p:
            p.send_many(records)
        assert wait_for(
            lambda: json.loads(http("GET", f"{base}/distinct")[1]).get("c") == 5
        )
        assert wait_for(
            lambda: any(
                s["name"] == "serving.apply" for s in tracing.spans(ctx.trace_id)
            )
        )
        (apply_span,) = [
            s for s in tracing.spans(ctx.trace_id) if s["name"] == "serving.apply"
        ]
        assert apply_span["parent"] == ctx.span_id
        assert apply_span["attrs"]["records"] == 1
        assert apply_span["attrs"]["instance"] == layer.port
        assert 2000 <= apply_span["attrs"]["skew_ms"] <= 60_000
        # freshness observed on the global AND the per-instance registry
        assert metrics.registry.histogram("serving.freshness.seconds").count > fresh0
        inst = layer.instance_metrics.histogram("serving.freshness.seconds")
        assert inst.count >= 1 and inst.snapshot()["max"] >= 2.0

        # a traced MODEL delivery records the model-apply span
        ctx2 = TraceContext("ef" * 16, "ab" * 8, True)
        records2, _ = tracing.with_header(
            [("MODEL", json.dumps({"a": 9}))], ctx2, int(time.time() * 1000)
        )
        with broker.producer("OryxUpdate") as p:
            p.send_many(records2)
        assert wait_for(
            lambda: any(
                s["name"] == "serving.model.apply"
                for s in tracing.spans(ctx2.trace_id)
            )
        )
    finally:
        layer.close()


def test_batcher_records_request_lifecycle_spans():
    """queue-wait -> assemble -> scan, recorded by the completion thread
    with wall-clock stamps, all parented on the request's context."""
    from oryx_tpu.ops import topn as topn_ops
    from oryx_tpu.serving.batcher import TopNBatcher

    y = np.random.default_rng(0).standard_normal((200, 8), dtype=np.float32)
    up = topn_ops.upload(y, streaming=False)
    b = TopNBatcher()
    ctx = tracing.sample_root()
    assert ctx is not None
    try:
        with tracing.use(ctx):
            idx, vals = b.score(up, np.arange(8, dtype=np.float32), 5)
        assert len(idx) == 5
    finally:
        b.close()
    spans = {s["name"]: s for s in tracing.spans(ctx.trace_id)}
    assert {"serving.queue-wait", "serving.assemble", "serving.scan"} <= set(spans)
    for s in spans.values():
        assert s["parent"] == ctx.span_id
    # the three phases tile the request timeline in order
    assert (
        spans["serving.queue-wait"]["ts"]
        <= spans["serving.assemble"]["ts"]
        <= spans["serving.scan"]["ts"]
    )
    # untraced requests record nothing and still answer correctly
    before = len(tracing.spans())
    b2 = TopNBatcher()
    try:
        tracing.configure(sample_rate=0.0)
        idx2, _ = b2.score(up, np.arange(8, dtype=np.float32), 5)
        assert len(idx2) == 5
    finally:
        b2.close()
    assert len(tracing.spans()) == before


def test_a_sampled_request_finds_its_pass_by_attribute():
    """One `serving.pass` span a sampled pass, and the request's
    `serving.scan` span names the same pass number: a pass is shared by
    several traces, so the join is the attribute, not parentage."""
    import threading

    from oryx_tpu.ops import topn as topn_ops
    from oryx_tpu.serving.batcher import TopNBatcher

    y = np.random.default_rng(1).standard_normal((200, 8), dtype=np.float32)
    up = topn_ops.upload(y, streaming=False)
    b = TopNBatcher()
    roots = [tracing.sample_root() for _ in range(6)]
    gate = threading.Event()
    take = b._take_batch
    b._take_batch = lambda: gate.wait(5) and take()  # let all six coalesce

    def ask(ctx):
        with tracing.use(ctx):
            b.score(up, np.arange(8, dtype=np.float32), 5)

    threads = [threading.Thread(target=ask, args=(ctx,)) for ctx in roots]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)
        gate.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        b.close()
    passes = [s for s in tracing.spans() if s["name"] == "serving.pass"]
    scans = [s for s in tracing.spans() if s["name"] == "serving.scan"]
    assert len(scans) == 6 and 1 <= len(passes) <= 6
    by_number = {p["attrs"]["pass"]: p for p in passes}
    assert len(by_number) == len(passes)  # one span a pass, however many it served
    for s in scans:
        p = by_number[s["attrs"]["pass"]]
        assert p["attrs"]["rows"] <= p["attrs"]["padded_rows"]
        assert p["attrs"]["k_bucket"] == 16 and p["attrs"]["inflight"] >= 1
        # the pass's span covers the stretch the request's scan span covers
        assert p["ts"] <= s["ts"] + 1e-3 and p["ts"] + p["dur"] >= s["ts"] + s["dur"] - 1e-3
    assert sum(p["attrs"]["rows"] for p in passes) == 6
    # it lives in the trace of a request it served, as a sibling of its spans
    assert {p["trace"] for p in passes} <= {ctx.trace_id for ctx in roots}
