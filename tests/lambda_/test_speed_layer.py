"""Speed layer integration tests (reference: SpeedLayerIT, AbstractSpeedIT
pattern: seed update topic with a model, then input, assert UP deltas)."""

import json
import time

from oryx_tpu import bus
from oryx_tpu.common import config as C
from oryx_tpu.lambda_.speed import SpeedLayer


def make_config(broker):
    return C.get_default().with_overlay(
        f"""
        oryx {{
          id = "SpeedIT"
          input-topic.broker = "{broker}"
          update-topic.broker = "{broker}"
          speed {{
            streaming.generation-interval-sec = 1
            model-manager-class = "oryx_tpu.example.speed:ExampleSpeedModelManager"
          }}
        }}
        """
    )


def wait_until(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def test_speed_layer_consumes_model_and_emits_updates():
    broker_loc = "inproc://speed-it"
    broker = bus.get_broker(broker_loc)
    cfg = make_config(broker_loc)
    layer = SpeedLayer(cfg)
    layer.init_topics()
    # seed the update topic with a batch model BEFORE starting (replay-from-0)
    with broker.producer("OryxUpdate") as p:
        p.send("MODEL", json.dumps({"a": 1, "b": 1}))
    layer.start()
    # wait for the manager to absorb the model
    assert wait_until(lambda: layer.manager._counts.get("a") == 1)
    # new co-occurrence: "a c" adds 1 distinct-other to each of a and c
    with broker.producer("OryxInput") as p:
        p.send(None, "a c")
    tail = broker.consumer("OryxUpdate")  # latest: skip the seeded model
    sent = layer.run_one_batch()
    assert sent == 2
    # the batch rides with a `@trc` freshness/trace control record that
    # block consumers strip; a raw poll sees it and must skip it
    from oryx_tpu.common import tracing

    ups = [m for m in tail.poll(timeout=2.0) if m.key != tracing.TRACE_KEY]
    assert all(m.key == "UP" for m in ups)
    got = dict(u.message.split(",") for u in ups)
    assert got == {"a": "2", "c": "1"}
    layer.close()


def test_speed_layer_background_microbatches():
    broker_loc = "inproc://speed-it2"
    broker = bus.get_broker(broker_loc)
    layer = SpeedLayer(make_config(broker_loc))
    layer.start()
    with broker.producer("OryxInput") as p:
        p.send(None, "x y z")
    assert wait_until(lambda: layer.batch_count >= 1 and layer.manager._counts.get("x") == 2)
    layer.close()


def test_layer_ui_port_serves_metrics(tmp_path):
    """oryx.<layer>.ui.port exposes the metrics registry + layer status as
    JSON (reference parity: batch/speed ui.port carried the Spark UI)."""
    import json
    import urllib.request

    from oryx_tpu.common import config as C
    from oryx_tpu.lambda_.speed import SpeedLayer

    cfg = C.get_default().with_overlay(
        f"""
        oryx {{
          input-topic.broker = "inproc://ui-test"
          update-topic.broker = "inproc://ui-test"
          speed {{
            streaming.generation-interval-sec = 3600
            model-manager-class = "oryx_tpu.app.als.speed:ALSSpeedModelManager"
            ui.port = 0
          }}
        }}
        """
    )
    layer = SpeedLayer(cfg)
    layer.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{layer.ui_port}/metrics", timeout=5
        ) as r:
            body = json.loads(r.read())
        assert body["layer"]["name"] == "speed"
        assert body["layer"]["stopped"] is False
        # what the process runs on, and the readiness signals a feeder of
        # the input topic needs (a new consumer group starts at latest)
        import jax

        device = {
            "platform": "cpu",
            "device_kind": jax.devices()[0].device_kind,
            "n_devices": len(jax.devices()),
        }
        assert body["layer"]["device"] == device
        assert body["layer"]["input_attached"] is True
        assert body["layer"]["model_fraction_loaded"] == 0.0  # no MODEL yet
        assert body["layer"]["batches"] == 0
        with urllib.request.urlopen(
            f"http://127.0.0.1:{layer.ui_port}/healthz", timeout=5
        ) as r:
            assert json.loads(r.read())["device"] == device
    finally:
        layer.close()


def test_batch_layer_status_reports_device_and_input(tmp_path):
    import json
    import urllib.request

    from oryx_tpu.common import config as C
    from oryx_tpu.lambda_.batch import BatchLayer

    cfg = C.get_default().with_overlay(
        f"""
        oryx {{
          input-topic.broker = "inproc://batch-ui-test"
          update-topic.broker = "inproc://batch-ui-test"
          batch {{
            streaming.generation-interval-sec = 3600
            update-class = "oryx_tpu.example.batch:ExampleBatchLayerUpdate"
            storage.data-dir = "{tmp_path}/data"
            storage.model-dir = "{tmp_path}/model"
            ui.port = 0
          }}
        }}
        """
    )
    layer = BatchLayer(cfg)
    assert layer.status() == {"input_attached": False, "generations": 0}
    layer.prepare()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{layer.ui_port}/status", timeout=5
        ) as r:
            status = json.loads(r.read())["layer"]
        assert status["device"]["platform"] == "cpu"
        assert set(status["device"]) == {"platform", "device_kind", "n_devices"}
        assert status["input_attached"] is True and status["generations"] == 0
    finally:
        layer.close()


def test_speed_batch_continues_input_trace_and_feeds_freshness():
    """End-to-end speed-side tracing: an input batch published with a
    `@trc` header (trace + origin timestamp) yields parse/fold/publish
    spans in the SAME trace, the UP publish re-stamps the origin onto the
    update topic (so serving can close the freshness chain), and
    speed.freshness.seconds observes the event's true age."""
    from oryx_tpu.common import metrics, tracing
    from oryx_tpu.common.tracing import TraceContext

    broker_loc = "inproc://speed-trace"
    broker = bus.get_broker(broker_loc)
    layer = SpeedLayer(make_config(broker_loc))
    layer.init_topics()
    tracing.reset()
    tracing.configure(sample_rate=1.0)
    try:
        with broker.producer("OryxUpdate") as p:
            p.send("MODEL", json.dumps({"a": 1, "b": 1}))
        layer.start()
        assert wait_until(lambda: layer.manager._counts.get("a") == 1)

        ctx = TraceContext("ab" * 16, "cd" * 8, True)
        origin_ms = int(time.time() * 1000) - 3000  # ingested 3s ago
        records, extra = tracing.with_header([(None, "a c")], ctx, origin_ms)
        assert extra == 1
        with broker.producer("OryxInput") as p:
            p.send_many(records)
        tail = broker.consumer("OryxUpdate")  # latest: skip the seeded model
        fresh = metrics.registry.histogram("speed.freshness.seconds")
        fresh0 = fresh.count
        sent = layer.run_one_batch()
        assert sent == 2  # the header never counts toward caller-visible sends

        # the UP batch re-stamps trace + ORIGINAL origin onto the update topic
        block = tail.poll_block(max_records=10, timeout=2.0)
        assert len(block) == 2
        info = tracing.parse_header(block.trace)
        assert info is not None and info.ingest_ms == origin_ms
        assert info.ctx is not None and info.ctx.trace_id == ctx.trace_id

        names = {s["name"] for s in tracing.spans(ctx.trace_id)}
        assert {"speed.parse", "speed.fold", "speed.publish", "speed.batch"} <= names
        (batch_span,) = [
            s for s in tracing.spans(ctx.trace_id) if s["name"] == "speed.batch"
        ]
        assert batch_span["parent"] == ctx.span_id  # continued, not re-rooted
        assert batch_span["attrs"] == {"events": 1, "updates": 2}

        # freshness observed against the carried origin, not receipt time
        assert fresh.count > fresh0
        assert fresh.snapshot()["max"] >= 2.0
    finally:
        tracing.reset()
        layer.close()
