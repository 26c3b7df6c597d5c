"""Serving layer tests: real HTTP against the embedded server
(reference: ServingLayerTest, ModelManagerListenerIT, ReadyTest,
ReadOnlyTest, CompressedResponseTest — SURVEY.md §4 ring 2)."""

import gzip
import json
import time
import urllib.error
import urllib.request

import pytest

from oryx_tpu import bus
from oryx_tpu.common import config as C
from oryx_tpu.serving.layer import ServingLayer


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


def test_serving_end_to_end():
    broker_loc = "inproc://serve-it"
    broker = bus.get_broker(broker_loc)
    layer = ServingLayer(make_config(broker_loc))
    layer.start()
    base = f"http://127.0.0.1:{layer.port}"
    try:
        # not ready before any model
        status, _, _ = http("GET", f"{base}/ready")
        assert status == 503
        status, body, _ = http("GET", f"{base}/distinct")
        assert status == 503
        # publish a model on the update topic
        with broker.producer("OryxUpdate") as p:
            p.send("MODEL", json.dumps({"a": 2, "b": 1}))
        assert wait_for(lambda: http("GET", f"{base}/ready")[0] == 200)
        status, body, headers = http("GET", f"{base}/distinct")
        assert status == 200
        assert json.loads(body) == {"a": 2, "b": 1}
        assert headers["Content-Type"] == "application/json"
        # POST /add writes to the input topic
        tail = broker.consumer("OryxInput", from_beginning=True)
        status, _, _ = http("POST", f"{base}/add", body=b"hello world\n")
        assert status == 204
        got = tail.poll(timeout=2.0)
        assert [m.message for m in got] == ["hello world"]
        # UP update applies incrementally
        with broker.producer("OryxUpdate") as p:
            p.send("UP", "c,5")
        assert wait_for(lambda: json.loads(http("GET", f"{base}/distinct")[1]).get("c") == 5)
        # 404 and 405
        assert http("GET", f"{base}/nope")[0] == 404
        assert http("DELETE", f"{base}/distinct")[0] == 405
    finally:
        layer.close()


def test_read_only_rejects_mutation():
    broker_loc = "inproc://serve-ro"
    layer = ServingLayer(make_config(broker_loc, **{"api.read-only": "true"}))
    layer.start()
    base = f"http://127.0.0.1:{layer.port}"
    try:
        status, body, _ = http("POST", f"{base}/add", body=b"x y\n")
        assert status == 403
    finally:
        layer.close()


def test_basic_auth():
    broker_loc = "inproc://serve-auth"
    layer = ServingLayer(
        make_config(
            broker_loc,
            **{
                "api.user-name": '"u"',
                "api.password": '"p"',
                "api.allow-insecure-auth": "true",
            },
        )
    )
    layer.start()
    base = f"http://127.0.0.1:{layer.port}"
    try:
        status, _, headers = http("GET", f"{base}/ready")
        assert status == 401
        assert "Basic" in headers.get("WWW-Authenticate", "")
        import base64

        tok = base64.b64encode(b"u:p").decode()
        status, _, _ = http("GET", f"{base}/ready", headers={"Authorization": f"Basic {tok}"})
        assert status in (200, 503)  # authorized; readiness depends on model
    finally:
        layer.close()


def test_gzip_and_csv_negotiation():
    broker_loc = "inproc://serve-gz"
    broker = bus.get_broker(broker_loc)
    layer = ServingLayer(make_config(broker_loc))
    layer.start()
    base = f"http://127.0.0.1:{layer.port}"
    try:
        big_model = {f"word{i}": i for i in range(500)}
        with broker.producer("OryxUpdate") as p:
            p.send("MODEL", json.dumps(big_model))
        assert wait_for(lambda: http("GET", f"{base}/ready")[0] == 200)
        status, body, headers = http(
            "GET", f"{base}/distinct", headers={"Accept-Encoding": "gzip"}
        )
        assert status == 200
        assert headers.get("Content-Encoding") == "gzip"
        assert json.loads(gzip.decompress(body)) == big_model
    finally:
        layer.close()


def test_context_path():
    broker_loc = "inproc://serve-ctx"
    layer = ServingLayer(make_config(broker_loc, **{"api.context-path": '"/oryx"'}))
    layer.start()
    base = f"http://127.0.0.1:{layer.port}"
    try:
        assert http("GET", f"{base}/oryx/ready")[0] in (200, 503)
        assert http("GET", f"{base}/ready")[0] == 404
    finally:
        layer.close()


def test_head_routes_like_get_with_empty_body():
    broker_loc = "inproc://serve-head"
    layer = ServingLayer(make_config(broker_loc))
    layer.start()
    base = f"http://127.0.0.1:{layer.port}"
    try:
        status, body, _ = http("HEAD", f"{base}/ready")
        assert status in (200, 503)
        assert body == b""
    finally:
        layer.close()


def test_username_without_password_refused():
    with pytest.raises(ValueError):
        ServingLayer(make_config("inproc://serve-badauth", **{"api.user-name": '"u"'}))


def test_healthz_reports_device_and_native_library():
    """/healthz says what the replica runs on (platform, device_kind,
    n_devices, as logged at start) and which native library it loaded;
    null there means the pure-Python twins are serving."""
    import jax

    from oryx_tpu import native

    layer = ServingLayer(make_config("inproc://serve-device"))
    layer.start()
    try:
        status, body, _ = http("GET", f"http://127.0.0.1:{layer.port}/healthz")
        health = json.loads(body)
        assert status == 200
        assert health["device"] == {
            "platform": "cpu",
            "device_kind": jax.devices()[0].device_kind,
            "n_devices": len(jax.devices()),
        }
        assert health["device"] == layer.device
        assert health["native_library"] == native.library_path()
        if native.native_enabled() and native.get_library() is not None:
            assert health["native_library"].endswith(".so")
    finally:
        layer.close()


def test_await_termination_blocks_until_close_with_either_front():
    """`python -m oryx_tpu serving` is start() + await_termination(): with
    the native front there is no HTTP server thread to join, and the
    process used to exit right after start."""
    import threading

    for native_enabled in ("false", "auto"):
        layer = ServingLayer(
            make_config(f"inproc://serve-await-{native_enabled}",
                        **{"native.enabled": native_enabled})
        )
        layer.start()
        try:
            t0 = time.monotonic()
            layer.await_termination(timeout=0.3)
            assert time.monotonic() - t0 >= 0.25, native_enabled
            threading.Timer(0.2, layer.close).start()
            layer.await_termination(timeout=10)
            assert time.monotonic() - t0 < 5
        finally:
            layer.close()
