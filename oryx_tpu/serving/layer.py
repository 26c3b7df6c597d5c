"""The serving layer process: HTTP server + model-manager lifecycle.

Rebuild of ServingLayer (framework/oryx-lambda-serving/.../ServingLayer
.java:55-339) and ModelManagerListener (.../ModelManagerListener.java:
62-238): on start, creates the input-topic producer (unless read-only),
loads the configured ServingModelManager, starts a daemon thread replaying
the update topic from the beginning into manager.consume, and serves the
registered resources over HTTP with optional Basic auth, gzip, a context
path, and /ready readiness gating (Ready.java:34-42).

Divergence from the reference, by design: Tomcat becomes a threaded
stdlib HTTP(S) server. TLS is native (ServingLayer.makeConnector:194-245
parity): configure `oryx.serving.api.keystore-file`/`key-file` (PEM) and
the server listens on `secure-port` over TLS >= 1.2. DIGEST becomes
Basic-over-TLS — Basic under TLS carries the same security as DIGEST's
challenge dance did in 2015, and credentials over plaintext are refused
at startup unless `allow-insecure-auth = true` (for deployments behind a
TLS terminator). Jersey package scanning becomes import of the modules
named in oryx.serving.application-resources.
"""

from __future__ import annotations

import base64
import gzip
import importlib
import logging
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, HTTPServer
from urllib.parse import parse_qs, urlsplit

from oryx_tpu import native
from oryx_tpu.bus.core import get_broker
from oryx_tpu.common import metrics, profiling, tracing
from oryx_tpu.common.config import Config
from oryx_tpu.common.lang import load_instance_of
from oryx_tpu.common.resilience import RetryPolicy, SupervisedThread
from oryx_tpu.experiments import routing as _exp_routing
from oryx_tpu.serving import overload as _overload
from oryx_tpu.serving import stages as _stages
from oryx_tpu.tenancy import context as _tenancy
from oryx_tpu.serving.web import (
    OryxServingException,
    Request,
    Response,
    Router,
    ServingContext,
    render,
    resource,
)

log = logging.getLogger(__name__)


class _PooledHTTPServer(HTTPServer):
    """HTTP server with a bounded worker pool — the Tomcat maxThreads
    analogue (ServingLayer.java:225-228 tunes 400 threads). A worker owns
    a connection for its keep-alive lifetime; beyond `threads` concurrent
    connections, accepts queue instead of spawning unbounded threads the
    way ThreadingHTTPServer does.

    TLS is wrapped per-connection on the pool worker, never on the
    listener: a client that connects and stalls mid-handshake costs one
    worker, not the accept loop (Tomcat's connector does the same).
    Accepted sockets get a read timeout so idle keep-alive connections
    cannot pin workers past shutdown, and live connections are tracked so
    server_close() can unblock every worker deterministically."""

    daemon_threads = True
    read_timeout = 30.0

    def __init__(self, addr, handler_cls, threads: int, tls_ctx=None) -> None:
        super().__init__(addr, handler_cls)
        self._tls_ctx = tls_ctx
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, threads), thread_name_prefix="ServingWorker"
        )

    def process_request(self, request, client_address):
        self._pool.submit(self._work, request, client_address)

    def _work(self, request, client_address):
        conn = request
        try:
            conn.settimeout(self.read_timeout)
            if self._tls_ctx is not None:
                try:
                    conn = self._tls_ctx.wrap_socket(conn, server_side=True)
                except Exception as e:
                    log.debug("TLS handshake failed from %s: %s", client_address, e)
                    return
            with self._conns_lock:
                self._conns.add(conn)
            try:
                self.finish_request(conn, client_address)
            except Exception:
                self.handle_error(conn, client_address)
            finally:
                with self._conns_lock:
                    self._conns.discard(conn)
        finally:
            self.shutdown_request(conn)

    def server_close(self):
        super().server_close()
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        # Sockets are closed, so workers unblock promptly; waiting here keeps
        # interpreter exit from hanging on the executor's atexit join.
        self._pool.shutdown(wait=True, cancel_futures=True)


def _import_recursively(module_name: str) -> None:
    """Import a module — and, for a package, every submodule under it — so
    @resource decorators register. The OryxApplication package-scan
    analogue (OryxApplication.java:62-86 scans packages with Reflections,
    so configs may name either a module or a whole package)."""
    mod = importlib.import_module(module_name)
    path = getattr(mod, "__path__", None)
    if path is not None:
        import pkgutil

        def _fail(name: str) -> None:
            # default onerror swallows subpackage ImportErrors, which would
            # leave resources silently unregistered — fail loudly instead
            raise ImportError(f"cannot import serving resource package {name}")

        for info in pkgutil.walk_packages(path, prefix=module_name + ".", onerror=_fail):
            importlib.import_module(info.name)


class ServingHealth:
    """Liveness/readiness state for the serving layer (docs/resilience.md).

    The update-stream consumer reports in: every successful poll marks the
    stream healthy, every poll error marks it down. When the stream is
    down the layer keeps answering from the last good model — *degraded*,
    not dead — and `staleness()` says how old that model's last delta is.
    `stream_healthy` is None until the first poll (or when no update topic
    is configured), which readiness treats as "not known to be down".
    """

    def __init__(self, clock=time.time) -> None:
        self._clock = clock
        # one lock over every flag: the update-consume thread writes the
        # stream marks and generation id, the shutdown path flips
        # draining, and HTTP handler threads read all of them from
        # /ready, /healthz and /readyz (manual lockset audit riding the
        # oryxlint PR — the pass can't see this class because its thread
        # entry lives in ServingLayer)
        self._mu = threading.Lock()
        self._stream_healthy: bool | None = None
        self._last_update_time: float | None = None
        self.consume_thread: SupervisedThread | None = None
        self._draining: bool = False
        self._live_generation: str | None = None
        self._challenger_generation: str | None = None

    @property
    def stream_healthy(self) -> bool | None:
        with self._mu:
            return self._stream_healthy

    @property
    def last_update_time(self) -> float | None:
        with self._mu:
            return self._last_update_time

    # drain-aware shutdown: once True, /ready and /readyz answer 503 so
    # load balancers stop routing here, while in-flight requests (and
    # any still arriving from stale routing tables) complete normally
    @property
    def draining(self) -> bool:
        with self._mu:
            return self._draining

    @draining.setter
    def draining(self, value: bool) -> None:
        with self._mu:
            self._draining = bool(value)

    # generation id of the live model (set by the GenerationTracker as
    # MODEL/MODEL-REF records flow past); None until one arrives or
    # when models carry no generation identity
    @property
    def live_generation(self) -> str | None:
        with self._mu:
            return self._live_generation

    @live_generation.setter
    def live_generation(self, value: str | None) -> None:
        with self._mu:
            self._live_generation = value

    # generation id of the challenger arm while an online experiment is
    # active (docs/experiments.md); None otherwise
    @property
    def challenger_generation(self) -> str | None:
        with self._mu:
            return self._challenger_generation

    @challenger_generation.setter
    def challenger_generation(self, value: str | None) -> None:
        with self._mu:
            self._challenger_generation = value

    def mark_stream_ok(self) -> None:
        with self._mu:
            self._stream_healthy = True
        metrics.registry.gauge("serving.update-stream.healthy").set(1)

    def mark_stream_down(self) -> None:
        with self._mu:
            self._stream_healthy = False
        metrics.registry.gauge("serving.update-stream.healthy").set(0)

    def mark_update(self) -> None:
        with self._mu:
            self._last_update_time = self._clock()

    def staleness(self) -> float | None:
        """Seconds since the last model update was applied, or None if no
        update has ever arrived. Also published as a gauge."""
        with self._mu:
            last = self._last_update_time
        if last is None:
            return None
        s = self._clock() - last
        metrics.registry.gauge("serving.model.staleness-seconds").set(s)
        return s

    @property
    def alive(self) -> bool:
        """False only once the supervised consume thread exhausted its
        restart policy — the layer can no longer recover by itself."""
        t = self.consume_thread
        return t is None or not t.gave_up

    @property
    def degraded(self) -> bool:
        return self.stream_healthy is False


@resource("GET", "/ready")
def _ready(ctx: ServingContext, req: Request) -> Response:
    """503 until the model is sufficiently loaded (Ready.java:34-42) — and
    again once the instance is draining for shutdown."""
    if ctx.health is not None and ctx.health.draining:
        return Response(503, None)
    if _model_ready(ctx):
        return Response(200, None)
    return Response(503, None)


@resource("GET", "/healthz")
def _healthz(ctx: ServingContext, req: Request) -> Response:
    """Liveness + degraded-mode report. 200 while the process can serve —
    including degraded (update stream down, answering from the last good
    model); 503 only when the update consumer has given up for good.

    The ``status`` field unifies the two degraded-mode notions (last-good
    -model serving per reference.conf's degraded contract, and the shed
    ladder's reduced-quality stages) into one operator-facing word:
    down > draining > degraded > ok; ``shed_stage`` names the ladder rung
    currently serving answers. ``cli health`` renders exactly this."""
    health = ctx.health
    if health is None:
        return Response(200, {"alive": True}, content_type="application/json")
    from oryx_tpu.parallel.distributed import claim_devices

    stage = ctx.admission.stage if ctx.admission is not None else _overload.STAGE_FULL
    if not health.alive:
        status = "down"
    elif health.draining:
        status = "draining"
    elif health.degraded or stage > _overload.STAGE_FULL:
        status = "degraded"
    else:
        status = "ok"
    body = {
        "alive": health.alive,
        "degraded": health.degraded or stage > _overload.STAGE_FULL,
        "status": status,
        "shed_stage": _overload.STAGE_NAMES[stage],
        "stream_healthy": health.stream_healthy,
        "staleness_seconds": health.staleness(),
        "live_generation": health.live_generation,
        "challenger_generation": health.challenger_generation,
        # what this replica runs on (cached at layer start), and the native
        # library it loaded — null means the pure-Python twins are serving
        "device": claim_devices(),
        "native_library": native.library_path(),
    }
    # multi-tenant serving: the model manager is a TenantServingMux and
    # each tenant has its own live generation (cli health renders the
    # per-tenant skew line from exactly this)
    live_generations = getattr(ctx.model_manager, "live_generations", None)
    if callable(live_generations):
        body["tenants"] = live_generations()
    return Response(200 if health.alive else 503, body, content_type="application/json")


@resource("GET", "/readyz")
def _readyz(ctx: ServingContext, req: Request) -> Response:
    """Strict readiness for load balancers: the model must be loaded AND
    the update stream must not be known-down AND the instance must not be
    draining. Degraded/draining instances keep /healthz green but drop
    out of /readyz rotation."""
    ready = _model_ready(ctx)
    stream_ok = ctx.health is None or ctx.health.stream_healthy is not False
    draining = ctx.health is not None and ctx.health.draining
    body = {"model_ready": ready, "stream_ok": stream_ok, "draining": draining}
    ok = ready and stream_ok and not draining
    return Response(200 if ok else 503, body, content_type="application/json")


@resource("GET", "/metrics")
def _metrics(ctx: ServingContext, req: Request) -> Response:
    """Request QPS/latency histograms and model state, as JSON — the
    observability the reference lacks (SURVEY.md §5). Request-path metrics
    come from this instance's own registry when one is attached, so N
    replicas in one process each report their *own* traffic (the fleet
    harness computes per-replica SLO burn rates from exactly this)."""
    from oryx_tpu.common import ledger

    if ledger.enabled():
        # resources.<kind>.live gauges: the leak alarm for week-long runs
        ledger.ledger.refresh()
    profiling.record_device_memory_peak(refresh=True)
    snap = metrics.registry.snapshot()
    if ctx.instance_metrics is not None:
        # instance-scoped values shadow the process-global ones: in a
        # multi-replica process the shared registry aggregates all
        # replicas, the instance registry is this replica alone
        snap.update(ctx.instance_metrics.snapshot())
    manager = ctx.model_manager
    model = manager.get_model() if manager is not None else None
    if model is not None:
        snap["serving.model.fraction_loaded"] = {
            "type": "gauge",
            "value": getattr(model, "get_fraction_loaded", lambda: 1.0)(),
        }
    if ctx.health is not None and ctx.health.live_generation is not None:
        snap["serving.model.live_generation"] = {
            "type": "gauge",
            "value": ctx.health.live_generation,
        }
    accept = next(
        (v for k, v in req.headers.items() if k.lower() == "accept"), ""
    )
    if (
        req.q1("format") == "prometheus"
        or "text/plain" in accept
        or "openmetrics" in accept
    ) and req.q1("format") != "json":
        # standard-scraper exposition (Prometheus sends
        # `Accept: text/plain;version=0.0.4`); live_generation may be a
        # non-numeric id, which the renderer would choke on — drop it
        # from the text form (scrapers read the per-generation request
        # counters instead)
        prom = {
            k: v
            for k, v in snap.items()
            if not (k == "serving.model.live_generation" and _non_numeric(v))
        }
        return Response(
            200,
            metrics.render_prometheus(prom),
            content_type=metrics.PROMETHEUS_CONTENT_TYPE,
        )
    return Response(200, snap, content_type="application/json")


def _non_numeric(entry) -> bool:
    try:
        float(entry.get("value"))
        return False
    except (TypeError, ValueError):
        return True


@resource("GET", "/trace")
def _trace(ctx: ServingContext, req: Request) -> Response:
    """This process's recorded spans: Chrome-trace/Perfetto JSON by
    default (load in chrome://tracing or ui.perfetto.dev), or the raw
    span list with parent links under ?format=spans. ?trace=<32hex>
    filters to one trace id — the loadgen client records the ids it
    sent, so a request's server-side breakdown is one GET away."""
    trace_id = req.q1("trace")
    if req.q1("format") == "spans":
        body = {"spans": tracing.spans(trace_id), **tracing.stats()}
    else:
        body = tracing.export_chrome(trace_id)
    return Response(200, body, content_type="application/json")


@resource("POST", "/debug/profile")
def _debug_profile(ctx: ServingContext, req: Request) -> Response:
    """On-demand JAX profiler capture: trace this process's devices for
    ?seconds=N (default 1, capped at 30), write the xprof trace under
    oryx.serving.compute.profile-dir, return the path. 503 when no
    profile dir is configured or the profiler cannot start."""
    profile_dir = profiling.profile_dir_from_config(ctx.config, "serving")
    if not profile_dir:
        raise OryxServingException(
            503, "oryx.serving.compute.profile-dir is not configured"
        )
    seconds = min(30.0, max(0.0, req.q_float("seconds", 1.0)))
    try:
        target = profiling.capture(profile_dir, "serving-ondemand", seconds)
    except RuntimeError as e:
        raise OryxServingException(503, str(e))
    metrics.registry.counter("serving.debug.profiles").inc()
    return Response(
        200, {"path": target, "seconds": seconds}, content_type="application/json"
    )


@resource("GET", "/model/generations")
def _model_generations(ctx: ServingContext, req: Request) -> Response:
    """The registry's view of the model dir plus what this instance is
    actually serving — the skew between the two is what the `health` CLI
    probe alerts on (docs/model-registry.md)."""
    registry = ctx.registry
    if registry is None:
        raise OryxServingException(404, "no model registry configured")
    generations = []
    for gen_id in registry.list_generations():
        manifest = registry.read_manifest(gen_id)
        entry = {"generation_id": gen_id}
        if manifest is not None:
            entry.update(
                status=manifest.status,
                parent_id=manifest.parent_id,
                eval_metric=manifest.eval_metric,
                created_at_ms=manifest.created_at_ms,
            )
        generations.append(entry)
    body = {
        "live_generation": ctx.health.live_generation if ctx.health else None,
        "champion": registry.champion_id(),
        "generations": generations,
    }
    return Response(200, body, content_type="application/json")


@resource("POST", "/model/rollback/{generationID}")
def _model_rollback(ctx: ServingContext, req: Request) -> Response:
    """Republish an archived generation onto the update topic so every
    consumer (this instance, other serving replicas, the speed layer)
    converges on it, and move the CHAMPION pointer so subsequent batch
    runs gate/warm-start against the rolled-back generation."""
    registry = ctx.registry
    if registry is None:
        raise OryxServingException(404, "no model registry configured")
    if ctx.config.get_bool("oryx.serving.api.read-only"):
        raise OryxServingException(403, "serving layer is read-only")
    if ctx.rollback_publisher is None:
        raise OryxServingException(503, "no update topic configured")
    generation_id = req.params["generationID"]
    if not registry.has_generation(generation_id):
        raise OryxServingException(404, f"no such generation {generation_id}")
    key = ctx.rollback_publisher(generation_id)
    registry.set_champion(generation_id)
    metrics.registry.counter("serving.model.rollbacks").inc()
    log.warning("rollback: republished generation %s as %s", generation_id, key)
    body = {"generation_id": generation_id, "published_as": key}
    return Response(200, body, content_type="application/json")


@resource("GET", "/experiments")
def _experiments_report(ctx: ServingContext, req: Request) -> Response:
    """Online-experiment report (docs/experiments.md): arm assignment
    config, champion/challenger generations, per-arm online metrics and
    the standing online-gate decision. Always answers — with experiments
    disabled the body just says so, which keeps `cli experiments` and
    fleet dashboards probe-safe."""
    if ctx.experiments is None:
        return Response(
            200,
            {"enabled": False, "active": False},
            content_type="application/json",
        )
    return Response(200, ctx.experiments.report(), content_type="application/json")


def _observe_request(
    method: str, status: int, t0: float, layer=None, tenant: str | None = None
) -> None:
    now = time.perf_counter()
    dt = now - t0
    metrics.registry.counter(f"serving.requests.{method}").inc()
    metrics.registry.counter(f"serving.responses.{status // 100}xx").inc()
    metrics.registry.histogram("serving.request.seconds").observe(dt)
    if layer is None:
        return
    layer.stages.observed(now)  # the same instant: the stages tile `dt`
    # instance-scoped mirrors (per-replica truth in a multi-replica
    # process) plus the per-generation counter that makes a rotation
    # observable: the live generation at response time is stamped on the
    # request, so a rotation shows up as traffic moving between
    # serving.requests.generation.<gen> counters, not as a gap
    im = layer.instance_metrics
    im.counter(f"serving.requests.{method}").inc()
    im.counter(f"serving.responses.{status // 100}xx").inc()
    im.histogram("serving.request.seconds").observe(dt)
    generation = layer.health.live_generation or "none"
    im.counter(f"serving.requests.generation.{generation}").inc()
    # generation-labeled latency: per-generation dashboards (and the
    # per-arm comparison while an experiment runs) need the latency
    # distribution split the same way the request counter is
    im.histogram(f"serving.request.seconds.generation.{generation}").observe(dt)
    if tenant is not None:
        # tenant-labeled twins: per-tenant SLO burn and rate are computed
        # from these on a shared multi-tenant fleet (docs/multi-tenancy.md)
        im.counter(f"serving.requests.tenant.{tenant}").inc()
        im.histogram(f"serving.request.seconds.tenant.{tenant}").observe(dt)


def observe_block_freshness(raw_trace, instance_metrics=None):
    """Parse an update block's transport-carried ``@trc`` header and feed
    the freshness histogram: seconds from the origin timestamp the
    publisher stamped (earliest event-ingest time for speed updates,
    publish time for model publishes) to visibility on this replica.
    Returns the parsed :class:`tracing.BlockTrace` (or None) so the
    caller can continue the publisher's trace."""
    info = tracing.parse_header(raw_trace)
    if info is None:
        return None
    if info.ingest_ms is not None:
        age_s = max(0.0, time.time() - info.ingest_ms / 1000.0)
        metrics.registry.histogram("serving.freshness.seconds").observe(age_s)
        if instance_metrics is not None:
            instance_metrics.histogram("serving.freshness.seconds").observe(
                age_s
            )
    return info


def _block_has_model(block) -> bool:
    keys = getattr(block, "keys", None)
    if keys is None:
        return False
    return bool((keys == b"MODEL").any() or (keys == b"MODEL-REF").any())


def _model_ready(ctx: ServingContext) -> bool:
    manager = ctx.model_manager
    if manager is None:
        return False
    min_fraction = ctx.config.get_float("oryx.serving.min-model-load-fraction")
    tenant_models = getattr(manager, "tenant_models", None)
    if tenant_models is not None:
        # multi-tenant mux: the replica is ready when EVERY tenant's
        # model is loaded past the threshold — readiness gates fleet
        # rotation, and rotating onto a replica missing one tenant's
        # model would 503 that tenant's traffic
        models = tenant_models()
        if not models:
            return False
        return all(
            m is not None
            and getattr(m, "get_fraction_loaded", lambda: 1.0)() >= min_fraction
            for m in models.values()
        )
    model = manager.get_model()
    if model is None:
        return False
    fraction = getattr(model, "get_fraction_loaded", lambda: 1.0)()
    return fraction >= min_fraction


class ServingLayer:
    def __init__(self, config: Config) -> None:
        self.config = config
        # take the device now: a replica that cannot get the platform its
        # launcher named fails here, not at its first request (imported
        # here: the package import pulls in jax)
        from oryx_tpu.parallel.distributed import claim_devices, enable_compile_cache

        self.device = claim_devices()
        enable_compile_cache(config)  # device scans cache like training
        tracing.configure_from(config)
        self.port = config.get_int("oryx.serving.api.port")
        self.context_path = config.get_string("oryx.serving.api.context-path").rstrip("/")
        self.read_only = config.get_bool("oryx.serving.api.read-only")
        self.user_name = config.get_optional_string("oryx.serving.api.user-name")
        self.password = config.get_optional_string("oryx.serving.api.password")
        if self.user_name and not self.password:
            # auth requires BOTH set (reference.conf contract); a missing
            # password must not silently degrade to a guessable credential
            raise ValueError("oryx.serving.api.user-name set without password")
        self.keystore_file = config.get_optional_string("oryx.serving.api.keystore-file")
        self.key_file = config.get_optional_string("oryx.serving.api.key-file")
        self.keystore_password = config.get_optional_string(
            "oryx.serving.api.keystore-password"
        )
        if bool(self.keystore_file) != bool(self.key_file):
            raise ValueError(
                "oryx.serving.api.keystore-file and key-file must be set together"
            )
        self.use_tls = bool(self.keystore_file)
        if self.use_tls:
            self.port = config.get_int("oryx.serving.api.secure-port")
        if self.user_name and not self.use_tls:
            # Basic credentials in cleartext are a downgrade the reference
            # never allows (its DIGEST realm runs under a TLS constraint,
            # ServingLayer.java:290-321); require explicit opt-in
            if not (config.get_optional_bool("oryx.serving.api.allow-insecure-auth") or False):
                raise ValueError(
                    "oryx.serving.api.user-name is set but TLS is not configured; "
                    "set keystore-file/key-file, or allow-insecure-auth = true "
                    "behind a TLS terminator"
                )
        self.no_init_topics = config.get_optional_bool("oryx.serving.no-init-topics") or False
        self.model_manager_class = config.get_optional_string("oryx.serving.model-manager-class")
        self.app_resources = config.get_optional_strings("oryx.serving.application-resources")

        # multi-tenant mode (docs/multi-tenancy.md): the oryx.tenancy
        # block declares N tenants this one replica serves — None keeps
        # the classic single-tenant wiring byte-for-byte
        from oryx_tpu.tenancy.spec import TenantRegistry

        self.tenants = TenantRegistry.from_config(config)
        self.tenant_mux = None
        if self.tenants is not None:
            # one router hosts every tenant's app endpoints
            merged = list(self.app_resources or [])
            for mod in self.tenants.resource_modules():
                if mod not in merged:
                    merged.append(mod)
            self.app_resources = merged

        # push oryx.serving.scan.* into the micro-batcher scheduler before
        # it spins up (the default batcher is created on first use)
        from oryx_tpu.serving.batcher import configure_fairness, configure_scheduler

        if self.tenants is not None and self.tenants.fair_share:
            # DRR fair scheduling in the adaptive batcher: each tenant's
            # entries drain from a private queue at its weighted share
            configure_fairness(self.tenants.weights(), self.tenants.quantum)
        configure_scheduler(
            max_batch=config.get_optional_int("oryx.serving.scan.max-batch"),
            max_inflight=config.get_optional_int("oryx.serving.scan.max-inflight"),
            # bounded queue: full queue => immediate shed decision instead
            # of an unbounded wait queued behind the pipeline
            max_queue=config.get_optional_int("oryx.serving.overload.max-queue"),
        )
        from oryx_tpu.ops.ivf import configure_ann

        configure_ann(
            enabled=config.get_optional_bool("oryx.serving.scan.ann.enabled"),
            cells=config.get_optional_int("oryx.serving.scan.ann.cells"),
            nprobe=config.get_optional_int("oryx.serving.scan.ann.nprobe"),
            probe_fraction=config.get_optional_float(
                "oryx.serving.scan.ann.probe-fraction"
            ),
            min_items=config.get_optional_int("oryx.serving.scan.ann.min-items"),
            overlay_capacity=config.get_optional_int(
                "oryx.serving.scan.ann.overlay-capacity"
            ),
            query_block=config.get_optional_int("oryx.serving.scan.ann.query-block"),
            tile_chunks=config.get_optional_int("oryx.serving.scan.ann.tile-chunks"),
            host_stage1={"true": True, "false": False}.get(
                str(
                    config.get_optional_string("oryx.serving.scan.ann.host-stage1")
                ).lower()
            ),
        )
        # background ANN maintenance loop (docs/serving-scan.md): the
        # incremental overlay->clustered compaction + index-generation
        # publication knobs ride the same ann config block
        from oryx_tpu.serving.maintain import configure_maintain

        configure_maintain(
            enabled=config.get_optional_bool("oryx.serving.scan.ann.maintain.enabled"),
            interval_sec=config.get_optional_float(
                "oryx.serving.scan.ann.maintain.interval-sec"
            ),
            watermark=config.get_optional_float(
                "oryx.serving.scan.ann.maintain.watermark"
            ),
            split_max_items=config.get_optional_int(
                "oryx.serving.scan.ann.maintain.split-max-items"
            ),
            merge_min_items=config.get_optional_int(
                "oryx.serving.scan.ann.maintain.merge-min-items"
            ),
            publish=config.get_optional_bool("oryx.serving.scan.ann.maintain.publish"),
        )
        # tiered HBM->RAM->disk item store (native/store.py): catalogs
        # bigger than RAM keep serving out of the cell store
        from oryx_tpu.native.store import configure_tier

        tier_ram_mb = config.get_optional_int("oryx.serving.store.tier.ram-mb")
        configure_tier(
            enabled=config.get_optional_bool("oryx.serving.store.tier.enabled"),
            hot_cells=config.get_optional_int("oryx.serving.store.tier.hot-cells"),
            ram_bytes=None if tier_ram_mb is None else int(tier_ram_mb) << 20,
            spill_dir=config.get_optional_string("oryx.serving.store.tier.spill-dir"),
        )

        self.model_manager = None
        self._index_maintainer = None
        self.input_producer = None
        self._update_consumer = None
        self._consume_thread: SupervisedThread | None = None
        self._server: HTTPServer | None = None
        self._server_thread: threading.Thread | None = None
        self._native_front = None  # serving/native_front.NativeFront | None
        self._stop_event = threading.Event()
        self.health = ServingHealth()
        self.retry_policy = RetryPolicy.from_config(config, "oryx.serving.retry")
        # instance-scoped metrics: in a multi-replica process (tools/fleet.py)
        # the module-global registry aggregates every replica; this registry
        # is this replica alone, and /metrics serves it shadowing the global
        self.instance_metrics = metrics.MetricsRegistry()
        # the host path's instruments (serving/stages.py): handles taken
        # here, fed by whichever front serves
        self.stages = _stages.HostStages()
        self._inflight = 0
        self._inflight_cond = threading.Condition()
        # close() can race between the fleet driver and atexit/signal
        # paths; the flag flip must be one atomic check-then-set
        self._close_lock = threading.Lock()
        self._close_done = False

        # model registry over the batch model dir: /model/generations +
        # rollback, and live-generation tracking with duplicate-MODEL
        # suppression on the update stream
        from oryx_tpu.registry.store import RegistryStore
        from oryx_tpu.registry.tracking import GenerationTracker

        model_dir = config.get_optional_string("oryx.batch.storage.model-dir")
        self.registry_store = RegistryStore(model_dir) if model_dir else None

        # MODEL-REF restage cache (docs/durability.md): referenced
        # generation dirs download locally through an atomic temp-dir +
        # rename, so a crash mid-download never leaves a half-staged
        # model. Registered process-wide; replicas sharing a process
        # (tools/fleet.py) share one staged copy per generation.
        self.model_stager = None
        restage_dir = config.get_optional_string("oryx.serving.restage-dir")
        if restage_dir:
            from oryx_tpu.serving import restage

            self.model_stager = restage.ModelStager(restage_dir)
            restage.set_active(self.model_stager)

        # online experiments (docs/experiments.md): arm router + online
        # evaluator + evidence-gated promotion loop. Built only when
        # oryx.serving.ab.fraction > 0 AND a registry is configured (the
        # CHAMPION pointer is what classifies challenger publishes), so
        # the request path pays nothing with experiments off.
        self.experiments = None
        if (
            self.registry_store is not None
            and config.get_float("oryx.serving.ab.fraction") > 0
        ):
            from oryx_tpu.experiments.coordinator import ExperimentCoordinator

            self.experiments = ExperimentCoordinator(
                config, self.registry_store, instance_metrics=self.instance_metrics
            )
        self.generation_tracker = GenerationTracker(
            self.health, experiments=self.experiments
        )
        if self.experiments is not None:
            self.experiments.attach_tracker(self.generation_tracker)
        self._rollback_producer = None
        self._rollback_lock = threading.Lock()

        # adaptive overload control: the admission controller watches the
        # batcher's queue-wait EWMA / queue depth / HTTP inflight against
        # the oryx.serving.overload.* budget and walks the shed ladder
        # (docs/overload.md); None when disabled, so the request fast path
        # pays nothing
        self.overload_config = _overload.OverloadConfig.from_config(config)
        self.admission = (
            _overload.AdmissionController(
                self.overload_config,
                signals=self._overload_signals,
                instance_metrics=self.instance_metrics,
                generation_fn=lambda: self.health.live_generation,
            )
            if self.overload_config.enabled
            else None
        )
        if self.admission is not None and self.tenants is not None:
            from oryx_tpu.serving.batcher import default_tenant_depths

            # per-tenant shed ladders: a noisy neighbor's own queue depth
            # (vs its weighted share) walks its private ladder while the
            # global one — every other tenant's floor — stays low
            self.admission.configure_tenants(
                self.tenants.weights(), default_tenant_depths
            )

        self.router = Router()
        if self.app_resources:
            for mod in self.app_resources:
                _import_recursively(mod)
        # framework resources (this module) + configured app resources only —
        # never whatever else happens to be imported in this interpreter
        self.router.add_from_registry([__name__] + list(self.app_resources or []))

    # -- lifecycle (ModelManagerListener.contextInitialized analogue) -------

    def start(self) -> None:
        from oryx_tpu.serving.batcher import retain_default_batcher

        if (
            self._server is not None
            or self._server_thread is not None
            or self._native_front is not None
            or self._update_consumer is not None
        ):
            raise RuntimeError(
                "ServingLayer.start() called twice (or retried after a "
                "partial start): the live HTTP server, update consumer, "
                "and consume thread would be overwritten and leak"
            )
        retain_default_batcher()
        self._batcher_retained = True
        cfg = self.config
        input_broker_loc = cfg.get_optional_string("oryx.input-topic.broker")
        input_topic = cfg.get_optional_string("oryx.input-topic.message.topic")
        update_broker_loc = cfg.get_optional_string("oryx.update-topic.broker")
        update_topic = cfg.get_optional_string("oryx.update-topic.message.topic")

        if self.tenants is None and input_broker_loc and input_topic and not self.read_only:
            broker = get_broker(input_broker_loc)
            if not self.no_init_topics:
                broker.create_topic(
                    input_topic, cfg.get_optional_int("oryx.input-topic.message.partitions") or 1
                )
            self.input_producer = broker.producer(input_topic)

        if self.experiments is not None and input_broker_loc and input_topic:
            # online evaluator: follow the input topic live (new events
            # only — historical interactions can't join future serves)
            broker = get_broker(input_broker_loc)
            if not self.no_init_topics:
                broker.create_topic(
                    input_topic, cfg.get_optional_int("oryx.input-topic.message.partitions") or 1
                )
            self.experiments.start(broker.consumer(input_topic))

        if self.tenants is not None:
            # multi-tenant wiring replaces the single manager/consumer
            # pair with one runtime per tenant behind the mux facades
            self._start_tenants(cfg, input_broker_loc, update_broker_loc)
        elif self.model_manager_class:
            self.model_manager = load_instance_of(self.model_manager_class, cfg)
            if update_broker_loc and update_topic:
                broker = get_broker(update_broker_loc)
                if not self.no_init_topics:
                    broker.create_topic(
                        update_topic,
                        cfg.get_optional_int("oryx.update-topic.message.partitions") or 1,
                    )
                # replay the update topic from offset 0 on every start
                # (ModelManagerListener.java:118-132). Supervised: a poll
                # failure marks the stream down (degraded mode — keep
                # serving the last good model) and the thread restarts
                # with backoff under oryx.serving.retry.*; only after
                # max-attempts consecutive failures does /healthz go red.
                self._update_consumer = broker.consumer(update_topic, from_beginning=True)
                self._consume_thread = SupervisedThread(
                    "ServingUpdateConsumer",
                    self._consume_updates,
                    self.retry_policy,
                    self._stop_event,
                    metrics_prefix="serving.consume",
                )
                self.health.consume_thread = self._consume_thread
                self._consume_thread.start()

        # background ANN index maintenance: compaction loop + (optional)
        # index-generation publication over the update topic. Duck-typed
        # on get_model so any manager whose models speak the maintenance
        # protocol (app/als) gets the loop; others are left alone.
        from oryx_tpu.serving import maintain as maintain_mod

        if (
            self.model_manager is not None
            and maintain_mod.maintain_enabled()
            and hasattr(self.model_manager, "get_model")
        ):
            publish_fn = None
            if (
                maintain_mod.MAINTAIN_PUBLISH
                and self.registry_store is not None
                and update_broker_loc
                and update_topic
            ):

                def publish_fn(index, stats):
                    ref = maintain_mod.write_index_generation(
                        self.registry_store.model_dir, index, stats=stats
                    )
                    # shares the rollback path's lazy update-topic producer
                    # (and its lock: publications serialize with rollbacks)
                    with self._rollback_lock:
                        if self._rollback_producer is None:
                            self._rollback_producer = get_broker(
                                update_broker_loc
                            ).producer(update_topic)
                        self._rollback_producer.send(maintain_mod.INDEX_REF_KEY, ref)
                    return ref

            self._index_maintainer = maintain_mod.IndexMaintainer(
                self.model_manager.get_model, publish_fn=publish_fn
            )
            self._index_maintainer.start()

        rollback_publisher = None
        if self.registry_store is not None and update_broker_loc and update_topic:
            max_size = cfg.get_int("oryx.update-topic.message.max-size")

            def rollback_publisher(generation_id: str) -> str:
                from oryx_tpu.registry.store import publish_generation

                # lazy producer: rollbacks are rare, no point holding an
                # update-topic producer open on every serving instance.
                # The lock covers the WHOLE publish, not just producer
                # creation: concurrent rollback requests serialize, so two
                # racing rollbacks can never interleave their MODEL bytes
                # on the topic — the last one to publish wins cleanly.
                with self._rollback_lock:
                    if self._rollback_producer is None:
                        self._rollback_producer = get_broker(update_broker_loc).producer(
                            update_topic
                        )
                    return publish_generation(
                        self.registry_store,
                        generation_id,
                        self._rollback_producer,
                        max_size,
                        retry_policy=self.retry_policy,
                    )

        ctx = ServingContext(
            self.model_manager,
            self.input_producer,
            self.config,
            self.health,
            registry=self.registry_store,
            rollback_publisher=rollback_publisher,
            instance_metrics=self.instance_metrics,
            admission=self.admission,
            experiments=self.experiments,
        )
        handler_cls = _make_handler(self, ctx)
        threads = self.config.get_optional_int("oryx.serving.api.threads") or 64
        tls_ctx = None
        if self.use_tls:
            # HTTPS connector analogue (ServingLayer.makeConnector:194-245).
            # The listener stays plaintext; each accepted socket is wrapped
            # on a pool worker so a stalled handshake can't starve accept().
            import ssl

            tls_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            tls_ctx.minimum_version = ssl.TLSVersion.TLSv1_2
            tls_ctx.load_cert_chain(
                certfile=self.keystore_file,
                keyfile=self.key_file,
                password=self.keystore_password,
            )
        # native data plane (docs/serving-native.md): when the toolchain
        # is present and oryx.serving.native.* allows it, the epoll C++
        # front replaces the pooled stdlib server; it answers the cheap
        # rungs in C++ and forwards everything else through the same
        # _dispatch_parsed core. maybe_start() returns None on any
        # decline (TLS, auth, disabled, no g++) and the stdlib server
        # below serves identically — the bit-compatible fallback.
        from oryx_tpu.serving import native_front as _native_mod

        self._native_front = _native_mod.maybe_start(self, ctx, threads)
        self.stages.native.set(1 if self._native_front is not None else 0)
        from oryx_tpu.common import ledger

        if self._native_front is not None:
            self.port = self._native_front.port
            for t in self._native_front.threads():
                ledger.register("thread", t, live=threading.Thread.is_alive)
        else:
            self._server = _PooledHTTPServer(
                ("0.0.0.0", self.port), handler_cls, threads, tls_ctx=tls_ctx
            )
            if self.port == 0:
                self.port = self._server.server_address[1]
            self._server_thread = threading.Thread(
                target=self._server.serve_forever, name="ServingHTTP", daemon=True
            )
            self._server_thread.start()
            ledger.register(
                "thread", self._server_thread, live=threading.Thread.is_alive
            )
        log.info(
            "ServingLayer listening on :%d%s%s",
            self.port,
            self.context_path or "/",
            " (native front)" if self._native_front is not None else "",
        )

    def _consume_updates(self) -> None:
        self.model_manager.consume_blocks(self._health_blocks())

    def _health_blocks(self):
        """blocking_block_iterator with a health reporter: every poll that
        returns marks the update stream healthy, a poll that raises marks
        it down (degraded mode) and propagates to the supervisor, and each
        applied block timestamps the staleness clock.

        Observability rides here too: a block carrying a ``@trc`` header
        feeds the freshness histogram (origin timestamp -> visible on
        this replica) and, when the publisher's trace was sampled, the
        apply is recorded as a span of that trace — the consumer side of
        the publish->apply propagation pair. A redelivered duplicate
        carries the same header, so it shows up as the same trace id with
        a fresh span id per delivery."""
        consumer = self._update_consumer
        while not self._stop_event.is_set() and not consumer.closed():
            try:
                block = consumer.poll_block(max_records=10_000, timeout=0.2)
            except Exception:
                self.health.mark_stream_down()
                raise
            self.health.mark_stream_ok()
            raw_trace = getattr(block, "trace", None)
            # track live generation + suppress duplicate deliveries of the
            # live generation's MODEL before the manager sees the block
            block = self.generation_tracker.filter_block(block)
            if block is not None and len(block) > 0:
                # generation-aware managers read this during consume to
                # load a challenger model without swapping it live
                challenger_ctx = _exp_routing.consume_challenger(
                    self.generation_tracker.challenger_generation
                )
                info = observe_block_freshness(
                    raw_trace, self.instance_metrics
                )
                apply_ctx = (
                    tracing.continue_from(info.ctx)
                    if info is not None and info.ctx is not None
                    else None
                )
                if apply_ctx is None:
                    with challenger_ctx:
                        yield block
                else:
                    name = (
                        "serving.model.apply"
                        if _block_has_model(block)
                        else "serving.apply"
                    )
                    # parent = the publisher's span (info.ctx); the span
                    # covers the manager's processing of the block (the
                    # time between yield and resume)
                    with tracing.use(info.ctx):
                        with tracing.span(
                            name,
                            attrs={
                                "instance": self.port,
                                "records": len(block),
                            },
                        ) as sp:
                            if info.ingest_ms is not None:
                                sp.set(
                                    "skew_ms",
                                    round(
                                        time.time() * 1000 - info.ingest_ms, 3
                                    ),
                                )
                            with challenger_ctx:
                                yield block
                            if self.health.live_generation is not None:
                                sp.set(
                                    "generation", self.health.live_generation
                                )
                self.health.mark_update()
                if self._native_front is not None and _block_has_model(block):
                    # a MODEL apply flips readiness / live_generation NOW;
                    # callers that watch convergence in-process (fleet
                    # wait_converged) probe /readyz immediately after, so
                    # the native snapshots cannot wait for the next
                    # control tick (push_snapshots is safe off the
                    # control thread — begin_drain relies on that too)
                    self._native_front.push_snapshots()

    # -- multi-tenant wiring (docs/multi-tenancy.md) ------------------------

    def _start_tenants(self, cfg, input_broker_loc, update_broker_loc) -> None:
        """One serving runtime per tenant — private model manager,
        health, generation tracker, registry store, and a namespaced
        update-topic consumer replaying from offset 0 — multiplexed
        behind the single ``ServingContext`` surface the resource
        handlers already use."""
        from functools import partial

        from oryx_tpu.registry.store import RegistryStore
        from oryx_tpu.registry.tracking import GenerationTracker
        from oryx_tpu.tenancy.mux import (
            TenantInputMux,
            TenantRuntime,
            TenantServingMux,
        )
        from oryx_tpu.tenancy.spec import tenant_config

        runtimes: dict[str, TenantRuntime] = {}
        producers: dict = {}
        for spec in self.tenants:
            tid = spec.tenant_id
            tcfg = tenant_config(cfg, spec)
            manager = load_instance_of(spec.wiring("serving-manager"), tcfg)
            health = ServingHealth()
            tracker = GenerationTracker(health)
            model_dir = tcfg.get_optional_string("oryx.batch.storage.model-dir")
            rt = TenantRuntime(
                spec,
                tcfg,
                manager,
                health,
                tracker,
                store=RegistryStore(model_dir) if model_dir else None,
            )
            tenant_input = tcfg.get_optional_string("oryx.input-topic.message.topic")
            if input_broker_loc and tenant_input and not self.read_only:
                broker = get_broker(input_broker_loc)
                if not self.no_init_topics:
                    broker.create_topic(
                        tenant_input,
                        tcfg.get_optional_int("oryx.input-topic.message.partitions")
                        or 1,
                    )
                rt.producer = broker.producer(tenant_input)
                producers[tid] = rt.producer
            tenant_update = tcfg.get_optional_string(
                "oryx.update-topic.message.topic"
            )
            if update_broker_loc and tenant_update:
                broker = get_broker(update_broker_loc)
                if not self.no_init_topics:
                    broker.create_topic(
                        tenant_update,
                        tcfg.get_optional_int("oryx.update-topic.message.partitions")
                        or 1,
                    )
                rt.consumer = broker.consumer(tenant_update, from_beginning=True)
                rt.thread = SupervisedThread(
                    f"ServingUpdateConsumer-{tid}",
                    partial(self._consume_tenant_updates, rt),
                    self.retry_policy,
                    self._stop_event,
                    metrics_prefix="serving.consume",
                )
                health.consume_thread = rt.thread
                rt.thread.start()
            runtimes[tid] = rt
        self.tenant_mux = TenantServingMux(runtimes, self.tenants.default_tenant)
        self.model_manager = self.tenant_mux
        if producers:
            self.input_producer = TenantInputMux(
                producers, self.tenants.default_tenant
            )

    def _consume_tenant_updates(self, rt) -> None:
        rt.manager.consume_blocks(self._tenant_blocks(rt))

    def _tenant_blocks(self, rt):
        """The per-tenant twin of :meth:`_health_blocks`: same stream
        health marks, duplicate-MODEL suppression, freshness accounting
        and publish->apply span propagation, against the tenant's own
        consumer/tracker/health — and every apply span carries the
        tenant id."""
        consumer = rt.consumer
        while not self._stop_event.is_set() and not consumer.closed():
            try:
                block = consumer.poll_block(max_records=10_000, timeout=0.2)
            except Exception:
                rt.health.mark_stream_down()
                raise
            rt.health.mark_stream_ok()
            raw_trace = getattr(block, "trace", None)
            block = rt.tracker.filter_block(block)
            if block is not None and len(block) > 0:
                info = observe_block_freshness(raw_trace, self.instance_metrics)
                if info is not None and info.ctx is not None:
                    name = (
                        "serving.model.apply"
                        if _block_has_model(block)
                        else "serving.apply"
                    )
                    with tracing.use(info.ctx):
                        with tracing.span(
                            name,
                            attrs={
                                "instance": self.port,
                                "records": len(block),
                                "tenant": rt.spec.tenant_id,
                            },
                        ) as sp:
                            yield block
                            if rt.health.live_generation is not None:
                                sp.set("generation", rt.health.live_generation)
                else:
                    yield block
                rt.health.mark_update()

    def await_termination(self, timeout: float | None = None) -> None:
        """Block until close(). (Not a join of the HTTP server thread: the
        native front has none, and `python -m oryx_tpu serving` returned
        from here, and exited, right after start.)"""
        self._stop_event.wait(timeout)

    # -- drain-aware shutdown -----------------------------------------------

    def _request_began(self) -> None:
        with self._inflight_cond:
            self._inflight += 1
            n = self._inflight
        self.instance_metrics.gauge("serving.requests.in-flight").set(n)

    def _request_ended(self) -> None:
        with self._inflight_cond:
            self._inflight -= 1
            n = self._inflight
            if n <= 0:
                self._inflight_cond.notify_all()
        self.instance_metrics.gauge("serving.requests.in-flight").set(n)

    @property
    def inflight_requests(self) -> int:
        with self._inflight_cond:
            return self._inflight

    def _overload_signals(self) -> tuple[float, int, int]:
        """(queue_wait_ewma_ms, queue_depth, http_inflight) for the
        admission controller — the batcher half reads the process-wide
        default batcher without ever creating one."""
        from oryx_tpu.serving.batcher import default_batcher_signals

        queue_wait_ms, depth = default_batcher_signals()
        return queue_wait_ms, depth, self.inflight_requests

    def begin_drain(self) -> None:
        """Start refusing NEW traffic at the readiness level: /ready and
        /readyz flip to 503 so load balancers (and the open-loop engine's
        readiness router) stop sending here, while requests already in
        flight — or still arriving from stale routing tables — complete
        normally. The first half of a zero-downtime rolling restart."""
        self.health.draining = True
        self.instance_metrics.gauge("serving.draining").set(1)
        if self._native_front is not None:
            # the native /readyz snapshot must flip to 503 NOW, not at
            # the next control tick — load balancers poll readiness to
            # decide where new traffic goes during a rolling restart
            self._native_front.push_snapshots()
        log.info("ServingLayer :%d draining (readiness now 503)", self.port)

    def drain(self, timeout: float = 10.0) -> bool:
        """Block until no requests are in flight (or timeout). Returns
        True when the instance is idle and safe to close."""
        deadline = time.monotonic() + timeout
        with self._inflight_cond:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._inflight_cond.wait(remaining)
        return True

    def close(self, drain_seconds: float = 0.0) -> None:
        with self._close_lock:
            if self._close_done:
                return
            self._close_done = True
        if drain_seconds > 0:
            self.begin_drain()
            if not self.drain(drain_seconds):
                log.warning(
                    "close: %d request(s) still in flight after %.1fs drain",
                    self.inflight_requests,
                    drain_seconds,
                )
        if self._native_front is not None:
            self._native_front.close()
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
        self._stop_event.set()
        if self._update_consumer is not None:
            self._update_consumer.close()
        if self._consume_thread is not None:
            self._consume_thread.join(timeout=5)
            if self._consume_thread.is_alive():
                log.warning(
                    "serving thread %r still alive after 5s join; leaking it",
                    self._consume_thread.name,
                )
                metrics.registry.counter("layer.threads.leaked").inc()
        if self.tenant_mux is not None:
            # close every tenant consumer first (unblocks the polls),
            # then join the consume threads
            runtimes = self.tenant_mux.runtimes()
            for rt in runtimes.values():
                if rt.consumer is not None:
                    rt.consumer.close()
            for rt in runtimes.values():
                if rt.thread is not None:
                    rt.thread.join(timeout=5)
                    if rt.thread.is_alive():
                        log.warning(
                            "serving thread %r still alive after 5s join; "
                            "leaking it",
                            rt.thread.name,
                        )
                        metrics.registry.counter("layer.threads.leaked").inc()
        if self._index_maintainer is not None:
            # before the manager: the loop snapshots through get_model
            self._index_maintainer.close()
        if self.model_manager is not None:
            self.model_manager.close()
        if self.experiments is not None:
            self.experiments.close()
        if self.input_producer is not None:
            self.input_producer.close()
        if self._rollback_producer is not None:
            self._rollback_producer.close()
        if getattr(self, "_batcher_retained", False):
            self._batcher_retained = False
            from oryx_tpu.serving.batcher import release_default_batcher

            release_default_batcher()
        if self.model_stager is not None:
            from oryx_tpu.serving import restage

            # only clear the process-wide hook if it is still ours — a
            # replica started after us may have re-registered it
            if restage.active() is self.model_stager:
                restage.set_active(None)

    def __enter__(self) -> "ServingLayer":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _shed_response(retry_after_s: int) -> Response:
    """Fast-429 for the top ladder rung: tiny JSON body, Retry-After so
    well-behaved clients back off instead of hammering the retry path."""
    return Response(
        429,
        {"error": "overloaded", "retry_after_s": retry_after_s},
        content_type="application/json",
        headers={"Retry-After": str(retry_after_s)},
    )


def _admit_and_route(layer: ServingLayer, ctx: ServingContext, req, cache_key, sp):
    """Route one request through the shed ladder (docs/overload.md).

    The admission decision picks the *intended* stage; this function
    reports the stage the request was *actually* served at — a stale-rung
    request that misses the answer cache falls through to a reduced-probe
    scan, and a full-quality request that finds the batcher queue full is
    shed at the door. The served stage is stamped on the response header,
    the request span, and the per-stage counters, so loadgen's achieved-
    quality accounting always reflects reality, not intent.

    While an online experiment is active (docs/experiments.md) the
    request is first assigned an arm: challenger-arm dispatch runs under
    a generation override so generation-aware managers serve the
    challenger model, the arm lands on the X-Oryx-Experiment-Arm header
    and the request span, and the serve is recorded with the evaluator
    for the interaction-event join."""
    from oryx_tpu.serving.batcher import BatcherOverloadedError

    t_arrive = time.perf_counter()
    experiments = layer.experiments
    assignment = (
        experiments.assign_request(req.path, req.headers)
        if experiments is not None
        else None
    )

    def _dispatch():
        if experiments is not None:
            # pin every request to the tracker's generation for its arm
            # (challenger for the challenger arm, live for everything
            # else). With a generation-aware manager this keeps the
            # champion default intact while a challenger is loaded, and
            # flips all traffic the moment a promotion swaps the tracker;
            # managers without per-generation retention ignore it.
            generation = (
                assignment[1]
                if assignment is not None
                else layer.health.live_generation
            )
            with _exp_routing.serve_generation(generation):
                return layer.router.dispatch(ctx, req)
        return layer.router.dispatch(ctx, req)

    tenant = _tenancy.current_tenant()
    admission = layer.admission
    decision = (
        admission.decide(req.method, req.path, tenant=tenant)
        if admission is not None
        else None
    )

    def _champion_generation():
        # the generation stale-cache entries are stamped with / validated
        # against: the tenant's own champion on a multi-tenant fleet
        # (each tenant has a private lineage), the tracker's otherwise
        if tenant is not None and layer.tenant_mux is not None:
            rt = layer.tenant_mux.runtime(tenant)
            return rt.health.live_generation if rt is not None else None
        return admission.generation() if admission is not None else None
    served = None  # stage name actually used; None = full quality
    response = None
    if decision is not None and decision.stage >= _overload.STAGE_SHED:
        served = "shed"
        response = _shed_response(decision.retry_after_s)
    elif (
        decision is not None
        and decision.stage >= _overload.STAGE_STALE
        and req.method == "GET"
    ):
        cached = admission.cache.get(cache_key, _champion_generation())
        if cached is not None:
            served = "stale"
            response = Response(cached.status, cached.payload, cached.content_type)
    if response is None:
        try:
            if decision is not None and decision.probe_fraction is not None:
                with _overload.probe_override(decision.probe_fraction):
                    response = _dispatch()
                if getattr(response, "status", 200) == 200:
                    served = "reduced-probe"
            else:
                response = _dispatch()
        except BatcherOverloadedError:
            # bounded-queue rejection (oryx.serving.overload.max-queue):
            # an immediate shed decision instead of unbounded queueing,
            # taken even when the admission controller is disabled
            served = "shed"
            retry_after = (
                layer.overload_config.retry_after_s
                if layer.overload_config is not None
                else 1
            )
            response = _shed_response(retry_after)
        else:
            if (
                decision is not None
                and decision.stage == _overload.STAGE_FULL
                and req.method == "GET"
                and getattr(response, "status", 200) == 200
                and _champion_generation() is not None
                # challenger answers must never enter the stale cache:
                # it is stamped with the champion generation
                and (assignment is None or assignment[0] != _exp_routing.ARM_CHALLENGER)
            ):
                # feed the stale-answer cache with full-quality answers
                # only, stamped with the champion generation
                admission.cache.put(
                    cache_key,
                    _overload.CachedAnswer(
                        _champion_generation(),
                        response.status,
                        response.body,
                        response.content_type,
                    ),
                )
    if served is not None:
        _overload.count_shed(
            served,
            layer.instance_metrics,
            generation=(
                assignment[1]
                if assignment is not None
                else (_champion_generation() or layer.health.live_generation)
            ),
            tenant=tenant,
        )
        headers = getattr(response, "headers", None)
        if headers is not None:
            headers[_overload.SHED_HEADER] = served
        if sp is not None:
            sp.set("shed_stage", served)
    if assignment is not None:
        arm, generation, user = assignment
        headers = getattr(response, "headers", None)
        if headers is not None:
            headers[_exp_routing.ARM_HEADER] = arm
        if sp is not None:
            sp.set("experiment_arm", arm)
            if generation is not None:
                sp.set("experiment_generation", generation)
        items = (
            _served_items(getattr(response, "body", None))
            if getattr(response, "status", 200) == 200
            else ()
        )
        experiments.observe_request(
            user,
            arm,
            generation,
            items,
            latency_s=time.perf_counter() - t_arrive,
            shed_stage=served,
        )
    return response


def _served_items(body):
    """Item ids in a recommendation response body, in rank order, for
    the online join. Understands the two shapes the app endpoints
    produce: a dict with an ``items`` list, and a ranked list of
    item / (item, score) entries."""
    if isinstance(body, dict):
        items = body.get("items")
        if isinstance(items, list):
            return [str(i) for i in items]
        return ()
    if isinstance(body, list):
        out = []
        for entry in body:
            if isinstance(entry, (list, tuple)) and entry:
                out.append(str(entry[0]))
            elif isinstance(entry, (str, int)):
                out.append(str(entry))
        return out
    return ()


def _check_auth(layer: ServingLayer, headers) -> None:
    """Basic-auth gate shared by both fronts; raises 401 on failure."""
    if not layer.user_name:
        return
    auth = headers.get("Authorization", "") or ""
    if not auth.startswith("Basic "):
        raise OryxServingException(401, "unauthorized")
    try:
        userpass = base64.b64decode(auth[6:]).decode("utf-8")
    except Exception:
        raise OryxServingException(401, "unauthorized")
    import hmac

    if not hmac.compare_digest(userpass, f"{layer.user_name}:{layer.password}"):
        raise OryxServingException(401, "unauthorized")


def gzip_compress(body: bytes) -> bytes:
    """Deterministic response gzip (mtime pinned): the same body always
    produces the same bytes, which is what lets the native/Python fronts
    hold their byte-parity contract across the gzip rung."""
    return gzip.compress(body, mtime=0)


def _dispatch_parsed(layer, ctx, method: str, raw_path: str, headers, body,
                     tenant_box):
    """The front-agnostic request core: everything between "a parsed
    request" and "a rendered (status, payload, content-type, extras)
    tuple". Both the Python handler and the native front's dispatch
    workers (serving/native_front.py) call this, so tenant resolution,
    admission, tracing, experiments, and rendering cannot drift between
    fronts. `headers` needs case-insensitive ``get`` plus ``items()``
    with original casing (email.Message and native_front._Headers both
    qualify); ``tenant_box[0]`` receives the resolved tenant even when
    dispatch later raises."""
    _check_auth(layer, headers)
    split = urlsplit(raw_path)
    path = split.path
    if layer.context_path:
        if not path.startswith(layer.context_path):
            raise OryxServingException(404, "outside context path")
        path = path[len(layer.context_path) :] or "/"
    # tenant resolution (docs/multi-tenancy.md): the /t/<tenant>/
    # prefix wins over the X-Oryx-Tenant header; untenanted
    # data-plane requests fall to the default tenant. Resolved
    # before routing so the stripped path matches the resources,
    # and scoped over the dispatch so the batcher / admission /
    # mux all see it.
    tenant = None
    if layer.tenants is not None:
        tenant, path = _tenancy.split_tenant_path(path)
        if tenant is None:
            tenant = headers.get(_tenancy.TENANT_HEADER)
        if tenant is None and not _overload.exempt(path):
            tenant = layer.tenants.default_tenant
        if tenant is not None and tenant not in layer.tenants:
            raise OryxServingException(404, f"unknown tenant {tenant!r}")
        tenant_box[0] = tenant
    if headers.get("Content-Encoding") == "gzip":
        body = gzip.decompress(body)
    req = Request(
        # HEAD routes like GET; the body is suppressed at send time
        method="GET" if method == "HEAD" else method,
        path=path,
        params={},
        query=parse_qs(split.query),
        headers={k: v for k, v in headers.items()},
        body=body,
    )
    # answer-cache key: path + raw query, i.e. the full request
    # identity for the GET data plane the stale rung serves — the
    # tenant rides in front so two tenants' answers for the same
    # path can never alias in the cache
    cache_key = path + ("?" + split.query if split.query else "")
    if tenant is not None:
        cache_key = f"/t/{tenant}{cache_key}"
    attrs = {"path": path, "method": req.method}
    if tenant is not None:
        attrs["tenant"] = tenant
    # request-lifecycle span: a sampled incoming traceparent is
    # honored (the loadgen client's span becomes this span's
    # parent, joined by trace id); header-less requests roll the
    # root sampling dice. Untraced requests skip all of it.
    incoming = tracing.parse_traceparent(headers.get("traceparent"))
    # the same interval on the profiler's timeline while a trace records
    with _tenancy.tenant_scope(tenant), profiling.annotate("serving.request", path=path):
        if incoming is not None and incoming.sampled:
            with tracing.use(incoming):
                with tracing.span("serving.request", attrs=attrs) as sp:
                    response = _admit_and_route(layer, ctx, req, cache_key, sp)
                    sp.set("status", getattr(response, "status", 200))
        else:
            with tracing.span("serving.request", attrs=attrs, root=True) as sp:
                response = _admit_and_route(layer, ctx, req, cache_key, sp)
                sp.set("status", getattr(response, "status", 200))
    return render(response, headers.get("Accept", "application/json"))


def _make_handler(layer: ServingLayer, ctx: ServingContext):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        server_version = "oryx_tpu"
        # keep-alive clients see Nagle + delayed-ACK stack into ~40 ms
        # per-request stalls without this; the native front (httpfront.cpp)
        # sets TCP_NODELAY on every accepted socket for the same reason
        disable_nagle_algorithm = True

        def log_message(self, fmt, *args):  # route to logging, not stderr
            log.debug("%s " + fmt, self.address_string(), *args)

        def parse_request(self) -> bool:
            ok = super().parse_request()
            # the last byte of the request line and headers is parsed: the
            # front's stamp
            self._t_parsed = time.perf_counter()
            return ok

        def _handle(self, method: str) -> None:
            t0 = layer.stages.begin(time.perf_counter() - self._t_parsed)
            layer._request_began()
            try:
                self._handle_counted(method, t0)
            finally:
                layer.stages.responded()
                layer._request_ended()

        def _handle_counted(self, method: str, t0: float) -> None:
            try:
                status, payload, ct, extra = self._dispatch(method)
            except OryxServingException as e:
                _observe_request(
                    method, e.status, t0, layer, getattr(self, "_tenant", None)
                )
                self._send_error(e.status, e.message)
                return
            except Exception:
                log.exception("internal error handling %s %s", method, self.path)
                _observe_request(
                    method, 500, t0, layer, getattr(self, "_tenant", None)
                )
                self._send_error(500, "internal error")
                return
            _observe_request(
                method, status, t0, layer, getattr(self, "_tenant", None)
            )
            body = payload
            headers = dict(extra)
            if len(body) > 1024 and "gzip" in self.headers.get("Accept-Encoding", ""):
                body = gzip_compress(body)
                headers["Content-Encoding"] = "gzip"
            self.send_response(status)
            self.send_header("Content-Type", ct)
            self.send_header("Content-Length", str(len(body)))
            for k, v in headers.items():
                self.send_header(k, v)
            self.end_headers()
            if method != "HEAD":
                self.wfile.write(body)

        def _dispatch(self, method: str):
            self._tenant = None
            length = int(self.headers.get("Content-Length") or 0)
            body = self.rfile.read(length) if length else b""
            tenant_box = [None]
            try:
                return _dispatch_parsed(
                    layer, ctx, method, self.path, self.headers, body, tenant_box
                )
            finally:
                self._tenant = tenant_box[0]

        def _send_error(self, status: int, message: str) -> None:
            # plain error body (ErrorResource.java renders status + message)
            body = f"{status} {message}\n".encode("utf-8")
            self.send_response(status)
            if status == 401:
                self.send_header("WWW-Authenticate", 'Basic realm="Oryx"')
            self.send_header("Content-Type", "text/plain")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            try:
                self.wfile.write(body)
            except BrokenPipeError:
                pass

        def do_GET(self):
            self._handle("GET")

        def do_POST(self):
            self._handle("POST")

        def do_DELETE(self):
            self._handle("DELETE")

        def do_HEAD(self):
            self._handle("HEAD")

    return Handler
