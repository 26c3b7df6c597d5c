"""The request core that both fronts run (`answer`): from a parsed
request to what goes on the wire.

A front (serving/python_front.py, serving/native_front.py) parses a
request off its socket and writes an answer onto it. In between, once:
the Basic-auth gate, context path and tenant, the request span, the
admission ladder and the experiment arm (docs/overload.md,
docs/experiments.md), dispatch and rendering, an exception's status,
the request's one observation and the gzip rule.

The serving layer is the object this module is handed: it imports neither
front and not serving/layer.py (tests/serving/test_serving_imports.py).
"""

from __future__ import annotations

import base64
import gzip
import hmac
import logging
import time
from urllib.parse import parse_qs, urlsplit

from oryx_tpu.common import metrics, profiling, tracing
from oryx_tpu.experiments import routing as _exp_routing
from oryx_tpu.serving import overload as _overload
from oryx_tpu.serving.web import (
    OryxServingException,
    Request,
    Response,
    ServingContext,
    render,
)
from oryx_tpu.tenancy import context as _tenancy

log = logging.getLogger(__name__)


def _observe_request(
    method: str, status: int, t0: float, layer, tenant: str | None = None
) -> None:
    now = time.perf_counter()
    dt = now - t0
    metrics.registry.counter(f"serving.requests.{method}").inc()
    metrics.registry.counter(f"serving.responses.{status // 100}xx").inc()
    metrics.registry.histogram("serving.request.seconds").observe(dt)
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


def _shed_response(retry_after_s: int) -> Response:
    """Fast-429 for the top ladder rung: tiny JSON body, Retry-After so
    well-behaved clients back off instead of hammering the retry path."""
    return Response(
        429,
        {"error": "overloaded", "retry_after_s": retry_after_s},
        content_type="application/json",
        headers={"Retry-After": str(retry_after_s)},
    )


def _admit_and_route(layer, ctx: ServingContext, req, cache_key, sp):
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
    # imported here: the batcher's module pulls in jax, and importing the
    # serving package must not (a driver process stays off the chip)
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
            response = _shed_response(layer.overload_config.retry_after_s)
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


def _check_auth(layer, headers) -> None:
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
    if not hmac.compare_digest(userpass, f"{layer.user_name}:{layer.password}"):
        raise OryxServingException(401, "unauthorized")


def _dispatch_parsed(layer, ctx, method: str, raw_path: str, headers, body,
                     tenant_box):
    """From a parsed request to a rendered (status, payload,
    content-type, extras) tuple: tenant resolution, admission, tracing,
    experiments and rendering, for whichever front parsed it (`answer`
    is the one caller). `headers` needs case-insensitive ``get`` plus
    ``items()`` with original casing (email.Message and
    native_front._Headers both qualify); ``tenant_box[0]`` receives the
    resolved tenant even when dispatch later raises."""
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


def answer(layer, ctx, method: str, raw_path: str, headers, body, t0: float):
    """Serve one parsed request: dispatch it, map what it raises to a
    status, observe it once, apply the gzip rule.

    Returns ``(status, message, content type, header fields, body as it
    leaves)``: ``message`` is None for an answer, and for an error it is
    all there is beside the status (a front writes it as its plain-text
    error answer). ``t0`` is the front's `stages.begin` stamp, where
    `serving.request.seconds` starts. ``body`` is the request's bytes or a
    callable that reads them: the Python front's read off its socket fails
    into the same 500 as a handler."""
    tenant_box = [None]
    message = None
    try:
        if callable(body):
            body = body()
        status, payload, ct, fields = _dispatch_parsed(
            layer, ctx, method, raw_path, headers, body, tenant_box
        )
    except OryxServingException as e:
        status, message = e.status, e.message
    except Exception:
        log.exception("internal error handling %s %s", method, raw_path)
        status, message = 500, "internal error"
    _observe_request(method, status, t0, layer, tenant_box[0])
    # one flat tuple, and the handler's own header dict where the gzip rule
    # adds nothing to it: the first build's pair of tuples, dict copy and
    # list of header pairs a request brought the interpreter's full
    # collection forward into a saturated 50 s window (PERF.md 6, PR 48)
    if message is not None:
        return status, message, None, None, None
    if len(payload) > 1024 and "gzip" in headers.get("Accept-Encoding", ""):
        # mtime pinned: the same body gives the same bytes from either
        # front (tests/serving/test_native_front.py compares them)
        payload = gzip.compress(payload, mtime=0)
        fields = {**fields, "Content-Encoding": "gzip"}
    return status, None, ct, fields, payload
