"""One runtime an update stream (`tenancy/mux.py` `TenantRuntime`): a
tenant's stream is opened, fed and closed by the routines that serve a
replica without tenants, so what the block generator does for the one
it does for the other: the apply span, the push of the native front's
health snapshots behind a MODEL block, the join at close."""

from __future__ import annotations

import http.client
import sys
import threading
import time
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO_ROOT / "tools"))

from oryx_tpu import native
from oryx_tpu.common import metrics, tracing

from fleet import FleetHarness  # noqa: E402

pytestmark = pytest.mark.fleet

TENANTS = {
    "acme": {"weight": 2.0, "slo_p99_ms": 500.0},
    "bob": {"weight": 1.0, "slo_p99_ms": 500.0},
}


def wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def readyz(port) -> int:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
    try:
        conn.request("GET", "/readyz")
        resp = conn.getresponse()
        resp.read()
        return resp.status
    finally:
        conn.close()


@pytest.mark.skipif(
    native.get_library() is None or not hasattr(native.get_library(), "hf_create"),
    reason="native toolchain unavailable",
)
def test_a_tenant_s_model_block_reads_ready_at_once_and_its_apply_span_names_the_tenant(
    tmp_path, monkeypatch
):
    """A tenant replica behind the native front whose control thread ticks
    once in ten minutes: `/readyz` is the C++ front's snapshot, and what
    renders it anew behind a tenant's MODEL block is the block generator
    itself. The traced publishes come back as one apply span a tenant."""
    monkeypatch.setenv("ORYX_TRACING_SAMPLE_RATE", "1.0")
    tracing.reset()
    try:
        with FleetHarness(
            1,
            str(tmp_path),
            bus_name="runtimes-ready",
            overlay='oryx.serving.native { enabled = "true", control-interval-ms = 600000 }',
            tenants=TENANTS,
        ) as fleet:
            (layer,) = fleet.replicas
            front = layer._native_front
            assert front is not None
            answered = metrics.registry.counter("serving.http.native-answered.snapshot")
            assert readyz(layer.port) == 503  # no tenant has a model yet
            want = {tid: fleet.publish_tenant(tid, metric=0.9) for tid in TENANTS}
            assert fleet.wait_tenants_converged(want, timeout=20.0)
            assert wait_for(lambda: readyz(layer.port) == 200, timeout=5.0)
            before = answered.value
            front._drain_stats()
            assert answered.value - before >= 2  # the C++ front's own answers

            def applies():
                return [s for s in tracing.spans() if s["name"] == "serving.model.apply"]

            assert wait_for(lambda: len(applies()) == len(TENANTS))
            for span in applies():
                assert span["attrs"]["instance"] == layer.port
                assert span["attrs"]["generation"] == want[span["attrs"]["tenant"]]
                assert span["attrs"]["skew_ms"] >= 0
            assert {s["attrs"]["tenant"] for s in applies()} == set(TENANTS)
    finally:
        tracing.reset()


@pytest.mark.parametrize("tenants", [None, TENANTS], ids=["no-tenants", "two-tenants"])
def test_close_joins_every_update_consumer_thread(tmp_path, tenants):
    leaked = metrics.registry.counter("layer.threads.leaked")
    leaked_before = leaked.value
    others = set(threading.enumerate())
    fleet = FleetHarness(
        1, str(tmp_path), bus_name=f"runtimes-close-{len(tenants or ())}", tenants=tenants
    )
    fleet.start()
    try:
        consumers = [
            t for t in threading.enumerate()
            if t not in others and t.name.startswith("ServingUpdateConsumer")
        ]
        assert sorted(t.name for t in consumers) == (
            sorted(f"ServingUpdateConsumer-{tid}" for tid in tenants)
            if tenants
            else ["ServingUpdateConsumer"]
        )
        assert all(t.is_alive() for t in consumers)
    finally:
        fleet.stop()
    assert not any(t.is_alive() for t in consumers)
    assert leaked.value == leaked_before
