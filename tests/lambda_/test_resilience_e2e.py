"""Resilience end-to-end chaos suite (docs/resilience.md).

Deterministic, tier-1-safe fault injection over the real layers:

- a poison update message is quarantined to the dead-letter topic and the
  speed layer keeps consuming;
- a speed -> serving wordcount pipeline under a seeded 10% drop + 20ms
  delay converges to the same final model as the fault-free run, with no
  dead layer threads;
- the serving /readyz flips unhealthy -> healthy across an injected
  broker outage while /healthz stays green (degraded mode);
- a netbus client reconnects mid-stream across a bus-server restart,
  resuming its consumer positions without loss or duplication.
"""

import json
import time
import threading
import urllib.error
import urllib.request

import pytest

from oryx_tpu import bus
from oryx_tpu.bus import faultbus
from oryx_tpu.common import config as C
from oryx_tpu.common import metrics

pytestmark = pytest.mark.chaos


def wait_until(pred, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


def speed_config(broker_loc, extra=""):
    return C.get_default().with_overlay(
        f"""
        oryx {{
          id = "ResilienceIT"
          input-topic.broker = "{broker_loc}"
          update-topic.broker = "{broker_loc}"
          speed {{
            streaming.generation-interval-sec = 3600
            model-manager-class = "oryx_tpu.example.speed:ExampleSpeedModelManager"
            retry {{
              max-attempts = 50
              initial-backoff-ms = 5
              max-backoff-ms = 20
              jitter = 0
            }}
          }}
          {extra}
        }}
        """
    )


def serving_config(broker_loc):
    return C.get_default().with_overlay(
        f"""
        oryx {{
          input-topic.broker = "{broker_loc}"
          update-topic.broker = "{broker_loc}"
          serving {{
            model-manager-class = "oryx_tpu.example.serving:ExampleServingModelManager"
            application-resources = "oryx_tpu.example.serving"
            api.port = 0
            retry {{
              max-attempts = 1000
              initial-backoff-ms = 10
              max-backoff-ms = 50
              jitter = 0
            }}
          }}
        }}
        """
    )


# -- poison message -> dead-letter --------------------------------------------


def test_poison_update_lands_in_dead_letter_topic():
    from oryx_tpu.lambda_.speed import SpeedLayer

    broker_loc = "inproc://dlq-it"
    broker = bus.get_broker(broker_loc)
    layer = SpeedLayer(speed_config(broker_loc))
    layer.init_topics()
    # a key the example manager rejects with ValueError: poison
    with broker.producer("OryxUpdate") as p:
        p.send("POISON", "unparseable")
    layer.start()
    try:
        # after max-consume-failures (3) retries of the same block, the
        # block is published to "<update topic>.dead-letter"
        assert layer.dead_letter_topic == "OryxUpdate.dead-letter"
        assert wait_until(lambda: broker.topic_exists("OryxUpdate.dead-letter"))
        dl = broker.consumer("OryxUpdate.dead-letter", from_beginning=True)
        got = []
        assert wait_until(lambda: got.extend(dl.poll(timeout=0.05)) or got)
        assert (got[0].key, got[0].message) == ("POISON", "unparseable")
        dl.close()
        # the stream moved on: a good message after the poison is consumed
        with broker.producer("OryxUpdate") as p:
            p.send("MODEL", json.dumps({"a": 7}))
        assert wait_until(lambda: layer.manager._counts.get("a") == 7)
        assert layer.healthy()
    finally:
        layer.close()


# -- convergence under chaos --------------------------------------------------

# disjoint word sets per line: the final counts are batching-independent
# (each word co-occurs only within its own line), so fault-induced batch
# boundaries cannot change the converged model
LINES = [f"w{3 * i} w{3 * i + 1} w{3 * i + 2}" for i in range(40)]
EXPECTED = {f"w{j}": 2 for j in range(120)}


def _run_pipeline(locator, inner_locator):
    """Speed + serving over `locator`; inputs fed through the un-faulted
    inner locator. Returns the serving layer's converged model counts."""
    from oryx_tpu.lambda_.speed import SpeedLayer
    from oryx_tpu.serving.layer import ServingLayer

    speed = SpeedLayer(speed_config(locator))
    speed.init_topics()
    serving = ServingLayer(serving_config(locator))
    speed.start()
    serving.start()
    try:
        # feed input through the (possibly faulted) locator, one send per
        # line: each send is a fault roll, so injected produce failures
        # actually happen — retried like any resilient client would
        feeder = bus.get_broker(locator)
        with feeder.producer("OryxInput") as p:
            for line in LINES:
                deadline = time.monotonic() + 10
                while True:
                    try:
                        p.send(None, line)
                        break
                    except ConnectionError:
                        if time.monotonic() >= deadline:
                            raise

        # drive micro-batches until the whole input is folded in; injected
        # produce failures beyond the layer's own retry budget surface as
        # RetryError -> just drive another batch
        def all_folded():
            try:
                speed.run_one_batch()
            except Exception:
                pass
            return speed.manager._counts == EXPECTED

        assert wait_until(all_folded, timeout=30.0), speed.manager._counts

        def serving_converged():
            model = serving.model_manager.get_model()
            return model is not None and model.get_words() == EXPECTED

        assert wait_until(serving_converged, timeout=30.0)
        return serving.model_manager.get_model().get_words()
    finally:
        speed.close()
        serving.close()
        assert speed.healthy()
        assert not speed._consume_thread.is_alive()
        assert not speed._batch_thread.is_alive()
        assert not serving._runtimes[0].thread.is_alive()


def test_pipeline_converges_under_seeded_drop_and_delay():
    leaked_before = metrics.registry.counter("layer.threads.leaked").value
    clean = _run_pipeline("inproc://conv-clean", "inproc://conv-clean")
    faultbus.reset()
    chaos = _run_pipeline(
        "fault+inproc://conv-chaos?drop=0.1&delay_ms=20&seed=5",
        "inproc://conv-chaos",
    )
    assert clean == chaos == EXPECTED
    state = faultbus.get_state("fault+inproc://conv-chaos?drop=0.1&delay_ms=20&seed=5")
    assert state.rolls > 0  # the fault schedule was consulted: chaos ran
    assert metrics.registry.counter("layer.threads.leaked").value == leaked_before


# -- serving health across an injected outage ---------------------------------


def _http_status(port, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as r:
            return r.status, json.loads(r.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


def test_readyz_flips_across_injected_outage():
    from oryx_tpu.serving.layer import ServingLayer

    loc = "fault+inproc://ready-chaos?seed=0"
    inner = bus.get_broker("inproc://ready-chaos")
    inner.create_topic("OryxUpdate", 1)
    with inner.producer("OryxUpdate") as p:
        p.send("MODEL", json.dumps({"a": 1}))
    layer = ServingLayer(serving_config(loc))
    layer.start()
    try:
        port = layer.port
        assert wait_until(lambda: _http_status(port, "/readyz")[0] == 200)

        faultbus.set_outage(loc, True)
        assert wait_until(lambda: _http_status(port, "/readyz")[0] == 503)
        status, body = _http_status(port, "/readyz")
        assert body == {"model_ready": True, "stream_ok": False,
                        "draining": False}
        # degraded, not dead: liveness stays green, the last good model
        # still answers
        status, body = _http_status(port, "/healthz")
        assert status == 200 and body["degraded"] is True
        assert layer.model_manager.get_model().get_words() == {"a": 1}

        faultbus.set_outage(loc, False)
        assert wait_until(lambda: _http_status(port, "/readyz")[0] == 200)
        status, body = _http_status(port, "/healthz")
        assert status == 200 and body["degraded"] is False
    finally:
        layer.close()


# -- netbus reconnect mid-stream ----------------------------------------------


def test_netbus_client_reconnects_across_server_restart(tmp_path):
    from oryx_tpu.bus.netbus import BusServer

    data_dir = str(tmp_path / "busdata")

    def start_server(port=0):
        server = BusServer(("127.0.0.1", port), data_dir)
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        return server

    server = start_server()
    port = server.server_address[1]
    loc = (
        f"tcp://127.0.0.1:{port}?connect_timeout=5"
        "&retry_max_attempts=100&retry_initial_backoff_ms=20&retry_max_backoff_ms=50"
    )
    broker = bus.get_broker(loc)
    broker.create_topic("T", 1)
    producer = broker.producer("T")
    producer.send_many([(None, f"a{j}") for j in range(5)])
    consumer = broker.consumer("T", group="g", from_beginning=True)
    got = []
    assert wait_until(lambda: got.extend(consumer.poll(timeout=0.2)) or len(got) >= 5)

    reconnects_before = metrics.registry.counter("bus.net.reconnects").value
    # bounce the server: server-side consumer sessions die with it, the
    # topic log survives on disk
    server.shutdown()
    server.server_close()
    server = start_server(port)
    try:
        # the client reconnects, reopens its consumer session, and seeks it
        # back to the committed wire positions: the stream continues with
        # no loss and no replay of a0..a4
        producer.send_many([(None, f"b{j}") for j in range(5)])
        assert wait_until(
            lambda: got.extend(consumer.poll(timeout=0.2)) or len(got) >= 10, timeout=20.0
        )
        assert [km.message for km in got] == [f"a{j}" for j in range(5)] + [
            f"b{j}" for j in range(5)
        ]
        assert metrics.registry.counter("bus.net.reconnects").value > reconnects_before
        consumer.close()
        producer.close()
    finally:
        server.shutdown()
        server.server_close()
