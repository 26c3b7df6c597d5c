"""Profiler hook tests: trace directory creation + no-op path."""

import os

import numpy as np

from oryx_tpu.common import profiling


def test_maybe_trace_noop_without_dir():
    ran = False
    with profiling.maybe_trace(None, "x"):
        ran = True
    assert ran


def test_maybe_trace_writes_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    with profiling.maybe_trace(str(tmp_path), "gen"):
        jnp.sum(jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    subdirs = [d for d in os.listdir(tmp_path) if d.startswith("gen-")]
    assert subdirs, "trace directory not created"
    # xprof writes plugin files under <target>/plugins/profile/...
    found = []
    for root, _dirs, files in os.walk(tmp_path):
        found += files
    assert found, "no trace artifacts written"


def test_body_exception_propagates(tmp_path):
    try:
        with profiling.maybe_trace(str(tmp_path), "boom"):
            raise RuntimeError("body failure")
    except RuntimeError as e:
        assert "body failure" in str(e)
    else:
        raise AssertionError("exception swallowed")


def test_profile_dir_from_config():
    from oryx_tpu.common.config import Config, parse_hocon

    cfg = Config(parse_hocon('oryx.batch.compute.profile-dir = "/tmp/tr"'))
    assert profiling.profile_dir_from_config(cfg, "batch") == "/tmp/tr"
    assert profiling.profile_dir_from_config(cfg, "speed") is None


def _annotation_names(trace_dir) -> list[str]:
    import glob

    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert files, "the profiler wrote no trace"
    data = ProfileData.from_file(files[-1])
    return [
        (ev.name, dict(ev.stats))
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("test.annotate")
    ]


def test_annotate_is_a_context_manager_with_and_without_a_trace(tmp_path):
    """With no profiler session it keeps nothing and the body runs; while
    one records, the name and its attributes land on a host plane of the
    profiler's own file (the device trace's timeline)."""
    import jax

    ran = []
    with profiling.annotate("test.annotate.idle", n=1):
        ran.append("idle")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # the level the benchmark records at
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with profiling.annotate("test.annotate.recorded", **{"pass": 7, "rows": 3}):
            ran.append("recorded")
    finally:
        jax.profiler.stop_trace()
    assert ran == ["idle", "recorded"]
    assert _annotation_names(str(tmp_path)) == [
        ("test.annotate.recorded", {"pass": 7, "rows": 3})
    ]


def test_annotate_lets_the_bodys_exception_through():
    try:
        with profiling.annotate("test.annotate.boom"):
            raise RuntimeError("body failure")
    except RuntimeError as e:
        assert "body failure" in str(e)
    else:
        raise AssertionError("exception swallowed")


def test_annotate_without_jax_is_a_null_context(monkeypatch):
    import builtins

    real_import = builtins.__import__

    def no_jax(name, *a, **k):
        if name == "jax.profiler":
            raise ImportError("no jax here")
        return real_import(name, *a, **k)

    monkeypatch.setattr(profiling, "_trace_annotation", None)
    monkeypatch.setattr(builtins, "__import__", no_jax)
    with profiling.annotate("test.annotate.nojax", n=1) as got:
        assert got is None
    assert profiling._trace_annotation is profiling._null_annotation


def test_device_memory_peak_gauge_is_absent_where_the_backend_gives_none():
    import jax

    from oryx_tpu.common import metrics

    profiling.record_device_memory_peak()  # must not raise on any backend
    stats = jax.local_devices()[0].memory_stats()
    gauge = metrics.registry.snapshot().get("device.memory.peak-bytes")
    if stats and "peak_bytes_in_use" in stats:
        assert gauge["value"] == stats["peak_bytes_in_use"]
    else:
        assert gauge is None or gauge["value"] is None


def test_a_scrape_reads_the_peak_only_after_a_staging_site_has(monkeypatch):
    """`/metrics` calls with refresh=True: no staging site has set the
    gauge yet, so the backend is not touched; and a backend that raises
    fails neither a scrape nor a model load."""
    import jax

    touched = []

    class Device:
        def memory_stats(self):
            touched.append(1)
            return {"peak_bytes_in_use": 4096 * len(touched)}

    def read():
        return metrics.registry.snapshot()["device.memory.peak-bytes"]["value"]

    from oryx_tpu.common import metrics

    # a registry of this test's own: the made-up peaks stay out of the process's
    monkeypatch.setattr(metrics, "registry", metrics.MetricsRegistry())
    monkeypatch.setattr(profiling, "_memory_peak_set", False)
    monkeypatch.setattr(jax, "local_devices", lambda: [Device()])
    profiling.record_device_memory_peak(refresh=True)
    assert touched == []
    profiling.record_device_memory_peak()  # a staging site
    assert read() == 4096 and profiling._memory_peak_set
    profiling.record_device_memory_peak(refresh=True)  # now a scrape refreshes it
    assert read() == 8192

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "local_devices", broken)
    profiling.record_device_memory_peak(refresh=True)
    profiling.record_device_memory_peak()
    assert read() == 8192

