"""Test bootstrap: virtual 8-device CPU mesh + deterministic seeding.

Mirrors the reference's OryxTest base class, which seeds every RNG for
reproducibility (framework/oryx-common/src/test/.../OryxTest.java:37-56,
RandomManager.useTestSeed). JAX runs on CPU with 8 virtual devices so all
mesh/sharding tests exercise real multi-device code paths without TPUs.
"""

import os

# tests run on the CPU backend with 8 virtual devices; the variables must
# be in the environment before the first `import jax` reads them
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _deterministic_rng():
    from oryx_tpu.common import rng

    rng.use_test_seed()
    yield
    rng.clear_test_seed()


@pytest.fixture(autouse=True)
def _reset_inproc_brokers():
    yield
    from oryx_tpu.bus import faultbus
    from oryx_tpu.bus.inproc import InProcessBroker

    InProcessBroker.reset_all()
    faultbus.reset()


@pytest.fixture()
def tmp_bus(tmp_path):
    """A fresh file-backed bus locator."""
    return f"file:{tmp_path}/bus"


@pytest.fixture(autouse=True)
def _lock_watchdog(request):
    """TSan-lite for the concurrency-heavy suites: chaos/fleet/pipeline
    tests run with threading.Lock/RLock swapped for OrderedLock wrappers
    (oryx_tpu/common/locks.py). A lock-order cycle raises in the
    acquiring thread before it blocks, and an over-budget acquire raises
    instead of hanging CI — so a reintroduced AB/BA deadlock fails the
    test with a named lock pair. Disable with ORYX_LOCK_WATCHDOG=0."""
    wanted = {"chaos", "fleet", "pipeline"}
    if not (wanted & {m.name for m in request.node.iter_markers()}) or (
        os.environ.get("ORYX_LOCK_WATCHDOG", "1") == "0"
    ):
        yield
        return
    from oryx_tpu.common import locks

    locks.instrument(strict=True, acquire_timeout=120.0)
    try:
        yield
        found = locks.violations()
    finally:
        locks.deinstrument()
        locks.reset()
    assert not found, f"lock watchdog violations: {found}"


@pytest.fixture(autouse=True)
def _resource_ledger(request):
    """Dynamic leak oracle for the suites that create and destroy whole
    layers: chaos/fleet/pipeline tests must release every thread, bus
    consumer, shm ring, and fold-in session they acquire. The ledger
    (oryx_tpu/common/ledger.py) tracks acquisitions via weakrefs; this
    fixture snapshots the live counts before the test and asserts the
    population returned to the snapshot after teardown — the runtime
    validation of the static lifecycle pass (ORX501-ORX506). Disable
    with ORYX_RESOURCE_LEDGER=0."""
    wanted = {"chaos", "fleet", "pipeline"}
    if not (wanted & {m.name for m in request.node.iter_markers()}) or (
        os.environ.get("ORYX_RESOURCE_LEDGER", "1") == "0"
    ):
        yield
        return
    import gc

    from oryx_tpu.common.ledger import ledger

    gc.collect()
    before = ledger.counts()
    yield
    # GC-released kinds (fold-in sessions) need the collector to run;
    # thread probes need the OS thread to actually exit, so give joined
    # daemon threads a beat to leave is_alive()
    import time

    gc.collect()
    after = ledger.counts()
    deadline = time.monotonic() + 5.0
    while (
        any(after.get(k, 0) > before.get(k, 0) for k in after)
        and time.monotonic() < deadline
    ):
        time.sleep(0.05)
        gc.collect()
        after = ledger.counts()
    leaked = {
        k: after[k] - before.get(k, 0)
        for k in after
        if after[k] > before.get(k, 0)
    }
    assert not leaked, (
        f"resource ledger: leaked {leaked} (before={before}, after={after})"
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "kafka: integration tests needing a real Kafka broker "
        "(kafka-python + ORYX_KAFKA_BOOTSTRAP); deselect with -m 'not kafka'",
    )
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (fault+ bus locators, "
        "seeded); fast and tier-1-safe, select with -m chaos",
    )
    config.addinivalue_line(
        "markers",
        "registry: model-registry subsystem tests (manifests, gating, "
        "rollback, retention GC); fast and tier-1-safe, select with -m registry",
    )
    config.addinivalue_line(
        "markers",
        "scan: quantized serving-scan parity suite (int8 two-plane recall, "
        "requantize round-trips, sharded equivalence); fast and tier-1-safe, "
        "select with -m scan",
    )
    config.addinivalue_line(
        "markers",
        "fleet: multi-replica serving fleet under open-loop load (generation "
        "rotation, rollback, chaos windows, drain restarts; zero failed "
        "requests as the SLO assertion); tier-1-safe, select with -m fleet",
    )
    config.addinivalue_line(
        "markers",
        "trainers: batch-trainer equivalence suite (RDF histogram modes, "
        "k-means device init / mini-batch, ALS compiled-run cache + "
        "zero-recompile regression); fast and tier-1-safe, select with "
        "-m trainers",
    )
    config.addinivalue_line(
        "markers",
        "pipeline: pipelined speed-layer micro-batching tests (parse/fold/"
        "publish hand-off); runs under the OrderedLock watchdog, select "
        "with -m pipeline",
    )
    config.addinivalue_line(
        "markers",
        "experiments: online champion/challenger experiment tests (sticky "
        "arm routing, interleaved evaluation joins, evidence-gated "
        "promotion); fast and tier-1-safe, select with -m experiments",
    )
    config.addinivalue_line(
        "markers",
        "tenancy: multi-tenant lambda tests (tenant spec parsing, DRR "
        "fairness, three packaged apps sharing one fleet); tier-1-safe, "
        "select with -m tenancy",
    )
