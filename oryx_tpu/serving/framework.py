"""The framework's own resources, which every serving replica answers
beside its application's: readiness and liveness (`/ready`, `/healthz`,
`/readyz`), observability (`/metrics`, `/trace`, `/debug/profile`), the
model registry (`/model/generations`, `/model/rollback/...`) and
`/experiments`. `ServingLayer` registers this module by name; a resource
knows the replica only through its `ServingContext`.
"""

from __future__ import annotations

import logging

from oryx_tpu import native
from oryx_tpu.common import metrics, profiling, tracing
from oryx_tpu.serving import overload as _overload
from oryx_tpu.serving.web import (
    OryxServingException,
    Request,
    Response,
    ServingContext,
    resource,
)

log = logging.getLogger(__name__)


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
            "value": _fraction_loaded(model),
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
            m is not None and _fraction_loaded(m) >= min_fraction
            for m in models.values()
        )
    model = manager.get_model()
    return model is not None and _fraction_loaded(model) >= min_fraction


def _fraction_loaded(model) -> float:
    return getattr(model, "get_fraction_loaded", lambda: 1.0)()
