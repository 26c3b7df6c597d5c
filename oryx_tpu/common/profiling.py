"""Profiling hooks: JAX profiler traces on demand.

The reference delegates job observability to the Spark web UI
(src/site/markdown/docs/performance.md:36-41); SURVEY.md §5 asks the
rebuild to exceed that with real profiler integration. When a profile
directory is configured (``oryx.batch.compute.profile-dir`` /
``oryx.speed.compute.profile-dir``) each traced span produces an xprof
trace under ``<dir>/<name>-<timestamp>/`` viewable with TensorBoard's
profile plugin or xprof; without one the context manager is a no-op
(zero overhead on the hot path).

Step-time breakdowns are separate: layers wrap their phases in
``metrics.timed`` histograms, exported at /metrics.

``annotate`` puts a host span into whatever profiler trace is recording
(``capture`` below, or the benchmark's), on the same timeline as the
device's operations; ``record_device_memory_peak`` publishes the
allocator's high-water mark as a gauge.
"""

from __future__ import annotations

import contextlib
import logging
import time

log = logging.getLogger(__name__)

_trace_annotation = None  # jax.profiler.TraceAnnotation, bound on first use
_memory_peak_set = False  # a staging site has read a peak from this process's device


def annotate(name: str, **attrs):
    """Context manager marking ``name`` (with ``attrs`` as its stats) on
    the host plane of a recording profiler trace. With no trace recording
    it costs the construction of the object (about a microsecond) and
    nothing is kept; without JAX it is a null context."""
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation

            _trace_annotation = TraceAnnotation
        except ImportError:
            _trace_annotation = _null_annotation
    return _trace_annotation(name, **attrs)


def _null_annotation(name: str, **attrs):
    return contextlib.nullcontext()


def record_device_memory_peak(refresh: bool = False) -> None:
    """Set the gauge ``device.memory.peak-bytes`` from the local devices'
    allocators, the fullest of them (a sharded model's chips differ);
    left unset where the backend reports none (the CPU). The staging
    sites call it right after an upload, when the
    process owns its device. With ``refresh`` (the ``/metrics`` scrape) it
    reads only where a staging site has set the gauge before, so a scrape
    never initialises a backend. A gauge must not fail a model load or a
    scrape: a backend that raises is logged, nothing is set."""
    global _memory_peak_set
    if refresh and not _memory_peak_set:
        return
    try:
        import jax

        peaks = [
            (dev.memory_stats() or {}).get("peak_bytes_in_use") for dev in jax.local_devices()
        ]
    except Exception:
        log.warning("device memory stats unavailable", exc_info=True)
        return
    peak = max((p for p in peaks if p is not None), default=None)
    if peak is not None:
        from oryx_tpu.common import metrics

        metrics.registry.gauge("device.memory.peak-bytes").set(int(peak))
        _memory_peak_set = True


@contextlib.contextmanager
def maybe_trace(profile_dir: str | None, name: str):
    """jax.profiler trace of the enclosed block when profile_dir is set."""
    if not profile_dir:
        yield
        return
    import jax

    target = f"{profile_dir.rstrip('/')}/{name}-{int(time.time() * 1000)}"
    log.info("profiling %s -> %s", name, target)
    # tracing must never take down a layer: profiler start/stop failures
    # are logged and swallowed; the body's own exceptions propagate
    started = False
    try:
        jax.profiler.start_trace(target)
        started = True
    except Exception:
        log.exception("could not start profiler trace %s", target)
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                log.exception("could not stop profiler trace %s", target)


def profile_dir_from_config(config, layer: str) -> str | None:
    """Configured trace directory for a layer, or None (off)."""
    return config.get(f"oryx.{layer}.compute.profile-dir", None)


def capture(profile_dir: str, name: str, seconds: float) -> str:
    """On-demand wall-clock profiler capture (the serving layer's
    ``POST /debug/profile``): trace whatever the process's devices do for
    ``seconds``, write under ``profile_dir``, return the trace path.
    Raises RuntimeError when the profiler cannot start (caller maps it to
    an HTTP error)."""
    import jax

    target = f"{profile_dir.rstrip('/')}/{name}-{int(time.time() * 1000)}"
    try:
        jax.profiler.start_trace(target)
    except Exception as e:
        raise RuntimeError(f"could not start profiler trace: {e}") from e
    try:
        time.sleep(max(0.0, seconds))
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:
            log.exception("could not stop profiler trace %s", target)
    return target
