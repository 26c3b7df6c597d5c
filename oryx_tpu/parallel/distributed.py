"""Multi-host (multi-process) JAX initialization.

The reference scales out via YARN containers coordinated by Spark; the
TPU-native equivalent is JAX's multi-controller runtime: every host in a
pod slice runs the same layer process, calls
``jax.distributed.initialize``, and from then on ``jax.devices()`` spans
the whole slice — the trainers' ``shard_map``/``NamedSharding`` programs
then run collectives over ICI/DCN with no further coordination code.

Configuration (all optional — absent means single-process):

- ``oryx.batch.compute.distributed.coordinator-address`` — host:port of
  process 0; also honored from $ORYX_COORDINATOR.
- ``oryx.batch.compute.distributed.num-processes`` / $ORYX_NUM_PROCESSES
- ``oryx.batch.compute.distributed.process-id`` / $ORYX_PROCESS_ID

On TPU pods, all three can be omitted when the environment provides
them (jax.distributed.initialize() auto-detects on Cloud TPU); setting
just ``auto = true`` opts into that detection.
"""

from __future__ import annotations

import logging
import os

from oryx_tpu.common import metrics

log = logging.getLogger(__name__)

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_initialized = False
_cache_dir: str | None = None
_device_info: dict | None = None

# <checkout>/.jax_cache: fixed, because the path is part of the cache key
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def claim_devices() -> dict:
    """Initialise the JAX backend now and report what this process runs
    on: ``{"platform", "device_kind", "n_devices"}``, logged once here.
    Every layer calls this at start-up and serves it in its health JSON.

    An accelerator belongs to one process, so which process gets it is
    the launcher's decision, passed as ``$JAX_PLATFORMS`` (``tpu`` for the
    owner, ``cpu`` for a host-only layer): JAX then raises if that
    platform cannot be had. When the variable is unset JAX tries the
    accelerator and silently carries on with the CPU if that fails (no
    chip, or another process holds it); that case is raised here too, so
    no layer reaches a device by accident."""
    global _device_info
    if _device_info is not None:
        return _device_info
    import jax
    from jax._src import xla_bridge

    devices = jax.devices()
    platform = devices[0].platform
    failed = {
        name: err for name, err in xla_bridge._backend_errors.items() if name != "cpu"
    }
    if platform == "cpu" and not jax.config.jax_platforms and failed:
        raise RuntimeError(
            "JAX fell back to the CPU because an accelerator could not be "
            f"claimed ({failed}); a chip has one owner process. Set "
            "JAX_PLATFORMS=cpu to run this process host-only on purpose."
        )
    _device_info = {
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
    }
    log.info(
        "running on platform=%s device_kind=%s n_devices=%d",
        platform, devices[0].device_kind, len(devices),
    )
    # XLA compile time of this process, next to the device it was paid
    # on: count = programs, sum = seconds (a persistent-cache hit costs
    # its load time only)
    compile_seconds = metrics.registry.histogram("jax.compile.seconds")
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **_: (
            compile_seconds.observe(seconds) if event == _BACKEND_COMPILE_EVENT else None
        )
    )
    return _device_info


def compile_cache_dir(config=None) -> str | None:
    """Where the persistent compilation cache goes, by one rule for the
    layers, the tools and chip_smoke.py: ``$JAX_COMPILATION_CACHE_DIR`` if
    set (JAX reads it itself, so None = set nothing in code), else
    ``oryx.compute.compile-cache-dir`` if given, else the fixed
    ``<checkout>/.jax_cache``. Never a temporary or per-process name."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    d = config.get("oryx.compute.compile-cache-dir", None) if config is not None else None
    return str(d) if d else DEFAULT_COMPILE_CACHE_DIR


def enable_compile_cache(config=None) -> None:
    """Apply :func:`compile_cache_dir` before this process compiles
    anything, so a restarted process, the next generation, or the next
    phase of chip_smoke.py reloads programs instead of recompiling them.

    The default directory is used on the TPU only: XLA:CPU entries are
    AOT code for the build machine's CPU features, and the checkout
    (ignored files included) gets copied between machines, where loading
    them logs errors or dies on an illegal instruction (seen once, on a CPU host).
    A directory named by config or environment is honoured anywhere."""
    global _cache_dir
    d = compile_cache_dir(config)
    if d is None or d == _cache_dir:
        return
    if d == DEFAULT_COMPILE_CACHE_DIR and claim_devices()["platform"] != "tpu":
        return
    import jax

    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    # bucketed training shapes compile in ~1-40s each; cache all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    _cache_dir = d
    log.info("persistent XLA compilation cache at %s", d)


def maybe_initialize(config) -> bool:
    """Initialize jax.distributed when configured; returns True if this
    process is now (or already was) part of a multi-process runtime."""
    global _initialized
    if _initialized:
        return True
    coord = (
        config.get("oryx.batch.compute.distributed.coordinator-address", None)
        or os.environ.get("ORYX_COORDINATOR")
    )
    nproc = (
        config.get("oryx.batch.compute.distributed.num-processes", None)
        or os.environ.get("ORYX_NUM_PROCESSES")
    )
    pid = config.get("oryx.batch.compute.distributed.process-id", None)
    if pid is None:
        pid = os.environ.get("ORYX_PROCESS_ID")
    auto = bool(config.get("oryx.batch.compute.distributed.auto", False))
    if coord is None and not auto:
        return False

    if coord is not None:
        missing = [
            name
            for name, val in (
                ("num-processes ($ORYX_NUM_PROCESSES)", nproc),
                ("process-id ($ORYX_PROCESS_ID)", pid),
            )
            if val is None
        ]
        if missing:
            raise ValueError(
                "oryx.batch.compute.distributed.coordinator-address is set but "
                + " and ".join(missing)
                + " is missing; all three are required for explicit multi-process init"
            )

    import jax

    if coord is None:
        jax.distributed.initialize()  # Cloud TPU auto-detection
    else:
        jax.distributed.initialize(
            coordinator_address=str(coord),
            num_processes=int(nproc),
            process_id=int(pid),
        )
    _initialized = True
    log.info(
        "jax.distributed initialized: process %d/%d, %d global devices",
        jax.process_index(),
        jax.process_count(),
        len(jax.devices()),
    )
    return True
