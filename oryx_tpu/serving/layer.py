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

This module is the wiring alone: a request is served by serving/request.py
`answer` behind whichever front parsed it, the framework's own resources are
serving/framework.py, and imports point one way (tests/serving/test_serving_imports.py).
"""

from __future__ import annotations

import importlib
import logging
import threading
import time

from oryx_tpu.bus.core import get_broker
from oryx_tpu.common import metrics, tracing
from oryx_tpu.common.config import Config
from oryx_tpu.common.lang import load_instance_of
from oryx_tpu.common.resilience import RetryPolicy, SupervisedThread
from oryx_tpu.experiments import routing as _exp_routing
from oryx_tpu.registry.store import RegistryStore
from oryx_tpu.registry.tracking import GenerationTracker
from oryx_tpu.serving import framework as _framework
from oryx_tpu.serving import native_front as _native_front
from oryx_tpu.serving import overload as _overload
from oryx_tpu.serving import stages as _stages
from oryx_tpu.serving.python_front import _make_handler, _PooledHTTPServer
from oryx_tpu.serving.web import Router, ServingContext
from oryx_tpu.tenancy.mux import TenantInputMux, TenantRuntime, TenantServingMux
from oryx_tpu.tenancy.spec import TenantRegistry, tenant_config

log = logging.getLogger(__name__)


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
        # one runtime an update stream this replica follows: its own where
        # it serves no tenants, one a tenant where it does (tenancy/mux.py)
        self._runtimes: list[TenantRuntime] = []
        self._server: _PooledHTTPServer | None = None
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
        # framework resources (serving/framework.py) + configured app
        # resources only — never whatever else happens to be imported in
        # this interpreter
        self.router.add_from_registry(
            [_framework.__name__] + list(self.app_resources or [])
        )

    # -- lifecycle (ModelManagerListener.contextInitialized analogue) -------

    def start(self) -> None:
        from oryx_tpu.serving.batcher import retain_default_batcher

        if (
            self._server is not None
            or self._server_thread is not None
            or self._native_front is not None
            or self._runtimes
        ):
            raise RuntimeError(
                "ServingLayer.start() called twice (or retried after a "
                "partial start): the live HTTP server, update consumer, "
                "and consume thread would be overwritten and leak"
            )
        retain_default_batcher()
        self._batcher_retained = True
        cfg = self.config
        update_broker_loc = cfg.get_optional_string("oryx.update-topic.broker")
        update_topic = cfg.get_optional_string("oryx.update-topic.message.topic")

        if self.experiments is not None:
            # online evaluator: follow the input topic live (new events
            # only — historical interactions can't join future serves)
            broker, input_topic = self._topic("input-topic", cfg)
            if broker is not None:
                self.experiments.start(broker.consumer(input_topic))

        if self.tenants is not None:
            # one runtime per tenant — private model manager, health,
            # generation tracker, registry store and namespaced topics —
            # multiplexed behind the single ``ServingContext`` surface the
            # resource handlers already use (docs/multi-tenancy.md)
            for spec in self.tenants:
                tcfg = tenant_config(cfg, spec)
                health = ServingHealth()
                model_dir = tcfg.get_optional_string("oryx.batch.storage.model-dir")
                self._open_runtime(
                    TenantRuntime(
                        spec,
                        tcfg,
                        load_instance_of(spec.wiring("serving-manager"), tcfg),
                        health,
                        GenerationTracker(health),
                        store=RegistryStore(model_dir) if model_dir else None,
                    )
                )
            default = self.tenants.default_tenant
            self.tenant_mux = TenantServingMux(
                {rt.spec.tenant_id: rt for rt in self._runtimes}, default
            )
            self.model_manager = self.tenant_mux
            producers = {
                rt.spec.tenant_id: rt.producer
                for rt in self._runtimes
                if rt.producer is not None
            }
            if producers:
                self.input_producer = TenantInputMux(producers, default)
        else:
            # "no tenants" is one runtime over the layer's own config,
            # health, tracker and store; the request path still reaches
            # the manager itself, not a mux
            if self.model_manager_class:
                self.model_manager = load_instance_of(self.model_manager_class, cfg)
            rt = TenantRuntime(
                None,
                cfg,
                self.model_manager,
                self.health,
                self.generation_tracker,
                store=self.registry_store,
            )
            self._open_runtime(rt)
            self.input_producer = rt.producer

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
                        self._update_producer().send(maintain_mod.INDEX_REF_KEY, ref)
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

                # The lock covers the WHOLE publish, not just producer
                # creation: concurrent rollback requests serialize, so two
                # racing rollbacks can never interleave their MODEL bytes
                # on the topic — the last one to publish wins cleanly.
                with self._rollback_lock:
                    return publish_generation(
                        self.registry_store,
                        generation_id,
                        self._update_producer(),
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
        # rungs in C++ and hands everything else to the same request core
        # (serving/request.py). maybe_start() returns None on any
        # decline (TLS, auth, disabled, no g++) and the stdlib server
        # below serves identically — the bit-compatible fallback.
        self._native_front = _native_front.maybe_start(self, ctx, threads)
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

    def _update_producer(self):
        """The update-topic producer of rollbacks and index publications,
        opened at the first of them: both are rare, no point holding a
        producer open on every serving instance. Called under
        `_rollback_lock`."""
        if self._rollback_producer is None:
            self._rollback_producer = get_broker(
                self.config.get_string("oryx.update-topic.broker")
            ).producer(self.config.get_string("oryx.update-topic.message.topic"))
        return self._rollback_producer

    def _topic(self, which: str, topic_cfg: Config):
        """``(broker, topic name)`` of ``oryx.<which>``: the broker is the
        replica's, the topic's name and partitions are `topic_cfg`'s (a
        tenant's are namespaced), and the topic is created unless
        `no-init-topics`. ``(None, None)`` where either is not configured."""
        loc = self.config.get_optional_string(f"oryx.{which}.broker")
        topic = topic_cfg.get_optional_string(f"oryx.{which}.message.topic")
        if not (loc and topic):
            return None, None
        broker = get_broker(loc)
        if not self.no_init_topics:
            broker.create_topic(
                topic,
                topic_cfg.get_optional_int(f"oryx.{which}.message.partitions") or 1,
            )
        return broker, topic

    def _open_runtime(self, rt: TenantRuntime) -> None:
        """Open one runtime's topics: the input producer unless the replica
        is read-only, and the update consumer with the thread that feeds
        the runtime's manager from it."""
        self._runtimes.append(rt)
        if not self.read_only:
            broker, topic = self._topic("input-topic", rt.config)
            if broker is not None:
                rt.producer = broker.producer(topic)
        if rt.manager is None:
            return
        broker, topic = self._topic("update-topic", rt.config)
        if broker is None:
            return
        # replay the update topic from offset 0 on every start
        # (ModelManagerListener.java:118-132). Supervised: a poll
        # failure marks the stream down (degraded mode — keep
        # serving the last good model) and the thread restarts
        # with backoff under oryx.serving.retry.*; only after
        # max-attempts consecutive failures does /healthz go red.
        rt.consumer = broker.consumer(topic, from_beginning=True)
        name = "ServingUpdateConsumer"
        if rt.spec is not None:
            name += f"-{rt.spec.tenant_id}"
        rt.thread = SupervisedThread(
            name,
            lambda: rt.manager.consume_blocks(self._blocks(rt)),
            self.retry_policy,
            self._stop_event,
            metrics_prefix="serving.consume",
        )
        rt.health.consume_thread = rt.thread
        rt.thread.start()

    def _blocks(self, rt: TenantRuntime):
        """blocking_block_iterator with a health reporter: every poll that
        returns marks the runtime's update stream healthy, a poll that
        raises marks it down (degraded mode) and propagates to the
        supervisor, and each applied block timestamps the staleness clock.

        Observability rides here too: a block carrying a ``@trc`` header
        feeds the freshness histogram (origin timestamp -> visible on
        this replica) and, when the publisher's trace was sampled, the
        apply is recorded as a span of that trace — the consumer side of
        the publish->apply propagation pair, with the tenant id on it
        where the runtime is a tenant's. A redelivered duplicate
        carries the same header, so it shows up as the same trace id with
        a fresh span id per delivery."""
        consumer, health, tracker = rt.consumer, rt.health, rt.tracker
        while not self._stop_event.is_set() and not consumer.closed():
            try:
                block = consumer.poll_block(max_records=10_000, timeout=0.2)
            except Exception:
                health.mark_stream_down()
                raise
            health.mark_stream_ok()
            raw_trace = getattr(block, "trace", None)
            # track live generation + suppress duplicate deliveries of the
            # live generation's MODEL before the manager sees the block
            block = tracker.filter_block(block)
            if block is not None and len(block) > 0:
                # generation-aware managers read this during consume to
                # load a challenger model without swapping it live (a
                # tracker without experiments has no challenger: a no-op)
                challenger_ctx = _exp_routing.consume_challenger(
                    tracker.challenger_generation
                )
                info = observe_block_freshness(
                    raw_trace, self.instance_metrics
                )
                # the publisher's trace, where it was sampled (and tracing is on)
                if info is None or tracing.continue_from(info.ctx) is None:
                    with challenger_ctx:
                        yield block
                else:
                    name = (
                        "serving.model.apply"
                        if _block_has_model(block)
                        else "serving.apply"
                    )
                    attrs = {"instance": self.port, "records": len(block)}
                    if rt.spec is not None:
                        attrs["tenant"] = rt.spec.tenant_id
                    # parent = the publisher's span (info.ctx); the span
                    # covers the manager's processing of the block (the
                    # time between yield and resume)
                    with tracing.use(info.ctx):
                        with tracing.span(name, attrs=attrs) as sp:
                            if info.ingest_ms is not None:
                                sp.set(
                                    "skew_ms",
                                    round(
                                        time.time() * 1000 - info.ingest_ms, 3
                                    ),
                                )
                            with challenger_ctx:
                                yield block
                            if health.live_generation is not None:
                                sp.set("generation", health.live_generation)
                health.mark_update()
                if self._native_front is not None and _block_has_model(block):
                    # a MODEL apply flips readiness / live_generation NOW;
                    # callers that watch convergence in-process (fleet
                    # wait_converged) probe /readyz immediately after, so
                    # the native snapshots cannot wait for the next
                    # control tick (push_snapshots is safe off the
                    # control thread — begin_drain relies on that too)
                    self._native_front.push_snapshots()

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
        # close every consumer first (unblocks the polls), then join the
        # consume threads
        for rt in self._runtimes:
            if rt.consumer is not None:
                rt.consumer.close()
        for rt in self._runtimes:
            if rt.thread is not None:
                rt.thread.join(timeout=5)
                if rt.thread.is_alive():
                    log.warning(
                        "serving thread %r still alive after 5s join; leaking it",
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
