"""Request micro-batcher: coalesce concurrent top-N calls into batched
device submits.

The reference parallelizes a single request across a thread pool
(ALSServingModel.topN / ALSServingModel.java:289-335, one thread per LSH
partition). On TPU the economics invert: one device scan is fast but each
dispatch pays a fixed host→device→host cost, so the win comes from
batching *across* concurrent requests instead of splitting one request.

This batcher implements continuous batching, the standard accelerator
serving pattern:

- request threads enqueue (item-matrix handle, query, k, cosine) and
  block on an event;
- a dispatcher thread takes whatever is queued the moment it wakes —
  no artificial wait, so an idle server adds zero batching latency: a
  batch is kept open only while it could not run anyway (every slot
  taken, or the pass ahead still far from its end: "The close" below) —
  groups entries by (matrix snapshot, cosine) so a model rotation
  mid-flight can never mix row indices from different snapshots, pads
  both k and the coalesced batch's row count to power-of-two buckets
  (jitted programs specialize on shape — buckets keep the compiled-
  program count logarithmic), and submits it, in scan groups of at most
  ``MULTI_THRESHOLD`` rows that are all one device dispatch
  (``ops/topn.py``: ``submit_top_k`` for query vectors,
  ``submit_top_k_multi_indexed`` for rows of a staged query matrix);
- a completer thread resolves the async handles in submission order and
  wakes the request threads. While the device works on batch r+1, batch
  r's results stream back.

Under load the queue naturally fills while the device is busy, so batch
size adapts to concurrency automatically (1 request → batch of 1,
hundreds of concurrent requests → full batches).

TWO passes are in flight at most: one executing and one queued behind
it, the least at which the device does not wait for the host as long as
the host refills a freed slot in less than one pass. A request then waits
behind one pass, not behind a pipeline of them. Where the pass is shorter
than the refill (a sub-millisecond pass on a small catalog) the host is
what bounds the rate, and a deeper pipeline only cuts the same requests
into more, emptier passes: measured, two is no slower there either
(PERF.md section 6, PR 28). An explicit ``max_inflight`` pins another
depth, ``max_batch`` another ceiling than ``DEFAULT_MAX_BATCH`` rows.

The dispatcher also fixes bucket fragmentation under backpressure: when
every inflight slot is taken, draining the queue in eager dribbles would
dispatch many small power-of-two-padded groups (each mostly padding).
Instead the dispatcher keeps absorbing arrivals in 1 ms waits while it
is blocked anyway, so one full batch goes out where several fragments
would have — ``serving.batcher.coalesced`` counts the requests that
piggybacked this way.

THE CLOSE. With a slot free and the other slot's pass still in flight,
a batch submitted now would only sit in the device's queue behind that
pass, and nothing can join it there: a request that arrives meanwhile
waits for the batch after it, a whole pass more. So the dispatcher keeps
such a batch OPEN, absorbing arrivals as it does while every slot is
taken, until the pass ahead is due to leave the device within a lead,
and submits then. It predicts from its own stamps and nothing else: the
times the results of the last passes were on the host (``_PassTiming.t_ready``).
Two passes that ran back to back are one SERVICE TIME apart there (kept
per (handle, cosine, probes, padded rows, k bucket), a low quantile of
the last few); a pass submitted to an idle device reads that and the
RESULT LAG on top (launch, download, the completer's wake: a median of
the last few such passes, whatever their key: it is the host's); its own
submits it times itself (the longest of the last few). The lead is that
submit time + the lag + ``HOLD_GUARD_S``, the one constant. By what it
observes it falls back to the immediate close: nothing in flight, no
estimate yet for the pass ahead (a new handle or bucket) or for the lag
(no pass has started on an idle device since there were estimates: a
replica that is never idle runs the schedule it always ran), the pass
ahead due within the lead (every pass shorter than the refill: a small
catalog, the CPU backend, where a dispatch is done when it returns), a
full batch, shutdown. A hold needs a pass ahead that outlasts the lead,
so an idle server still adds nothing; it never outlasts the predicted
end of the pass ahead, and ends at once when that pass's results come
early. The hold is queueing: it lies inside ``queue-wait.seconds`` and
the wait EWMA, outside ``pass.seconds``. ``serving.batcher.pass.held``,
``hold.rows``, ``hold.seconds``, ``hold.late`` and
``hold.error.seconds`` say how often it engages and how well it
predicts (docs/observability.md).

The unit of work is the PASS: one device dispatch serving one coalesced
group. Every pass is on the record three ways at the same boundaries
(docs/observability.md): always-on counters and histograms
(``serving.batcher.passes``, ``pass.rows`` / ``pass.padded-rows``,
``pass.inflight-depth-sum``, ``queue-wait.seconds``, ``submit.seconds``,
``pass.seconds``, ``deliver.seconds``), a ``serving.pass`` span in the
tracer's ring when a request of the pass is sampled, and
``serving.pass.submit`` /
``serving.pass.wait`` annotations on the profiler's timeline while a
device trace records. None of it feeds a scheduling decision: the close
reads the dispatcher's own stamps, never the registry.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field

import numpy as np

from oryx_tpu.common import profiling, tracing
from oryx_tpu.common.metrics import registry as _metrics
from oryx_tpu.ops import topn as topn_ops
from oryx_tpu.serving import stages
from oryx_tpu.serving.overload import active_probe_fraction
from oryx_tpu.tenancy.context import current_tenant

log = logging.getLogger(__name__)

# Rows a pass may coalesce unless ``max_batch`` says otherwise: where the
# former adaptive ceiling sat in every measured cell (PERF.md, PR 28).
DEFAULT_MAX_BATCH = 4096
# Passes in flight unless ``max_inflight`` says otherwise: one executing,
# one queued behind it.
MIN_INFLIGHT = 2

# Queue-wait EWMA (the admission controller's pressure signal): smoothing
# factor per dispatch, plus an idle decay so the signal fades once the
# queue goes quiet — without it a burst's last reading would pin the shed
# ladder engaged long after the overload passed.
WAIT_EWMA_ALPHA = 0.3
WAIT_DECAY_GRACE_S = 0.25
WAIT_DECAY_HALF_LIFE_S = 0.5

# The close (module docstring): a batch behind a pass in flight is
# submitted a LEAD before that pass's results are due on the host: the
# dispatcher's own submit time + the result lag, both measured (below), +
# this guard, which is what the prediction's error, the timer's and a
# submit slower than the last few need on top. Found on the chip (PERF.md
# section 6, PR 32: at 0.5 ms a sixth of the held passes reached the device
# late, at 1.0 ms 4 %, at 1.5 ms 1 %, and the saturated cell is level at
# each).
HOLD_GUARD_S = 0.0015
# Measured, each from the last HOLD_HISTORY of its kind: a pass's service
# time per key, once HOLD_MIN_SAMPLES passes of the key were submitted
# behind another, as their lower quartile (a machine pause reads anything,
# and an estimate that is too short only closes sooner); the result lag as
# the median of what the passes that started on an idle device took beyond
# their service time; the submit as the longest.
HOLD_HISTORY = 8
HOLD_MIN_SAMPLES = 3
HOLD_KEYS = 64  # estimates kept, least recently fed first out


class BatcherClosedError(RuntimeError):
    """Raised by ``score`` when the batcher was closed before the entry
    could be enqueued; distinguishes the benign close race from device
    errors so ``score_default`` never retries a real failure."""


class BatcherOverloadedError(RuntimeError):
    """Raised by ``score`` when the bounded queue
    (``oryx.serving.overload.max-queue``) is full at enqueue: the caller
    gets an immediate shed decision instead of an unbounded wait queued
    behind the pipeline (8.9-18 s p99 before the bound, on a CPU host).
    Deliberately NOT retried by ``score_default`` — the serving layer maps
    it to a fast 429 with Retry-After."""


@dataclass
class _Entry:
    uploaded: object
    query: np.ndarray | None  # None for index-submitted entries
    k: int
    cosine: bool
    x_dev: object | None = None  # device-resident query matrix (index entries)
    row: int | None = None  # row into x_dev
    done: threading.Event = field(default_factory=threading.Event)
    idx: np.ndarray | None = None
    vals: np.ndarray | None = None
    error: BaseException | None = None
    # tracing: the request's sampled context captured at enqueue, plus the
    # wall-clock phase stamps the completer turns into queue-wait /
    # assemble / scan spans. None/0.0 (unsampled) costs nothing.
    trace_ctx: object | None = None
    t_enqueue: float = 0.0
    t_dispatch: float = 0.0
    t_submit: float = 0.0
    # overload control: the enqueue stamp (perf_counter) feeding the
    # queue-wait EWMA (always set, unlike the tracing stamps) and the
    # stages of the host path, which also read `t_ready`, the instant the
    # completer had the pass's results (0.0: the pass failed); plus the
    # per-request reduced-probe override snapshotted from the admission
    # contextvar on the request thread — it rides the entry across to the
    # dispatcher.
    t_q: float = 0.0
    t_ready: float = 0.0
    probe_fraction: float | None = None
    nprobe_applied: int | None = None
    # multi-tenancy: the tenant identity snapshotted from the request
    # thread's contextvar at enqueue — the DRR queue services per-tenant
    # sub-queues by fair-share weight (docs/multi-tenancy.md)
    tenant: str | None = None


def _k_bucket(k: int) -> int:
    return max(16, 1 << (int(k) - 1).bit_length())


def _b_bucket(n: int) -> int:
    """Batch-row bucket: jitted programs specialize on the batch shape, so
    pad coalesced batches to power-of-two row counts (zero queries) to keep
    the number of distinct compiled programs logarithmic in max_batch."""
    return max(8, 1 << (int(n) - 1).bit_length())


@dataclass
class _Pass:
    """One device dispatch on its way from the dispatcher to the completer."""

    handle: object
    entries: list[_Entry]
    t_submit: float  # perf_counter when the host had submitted it
    seq: int  # per-batcher number of the dispatch attempt
    padded_rows: int  # rows the device is given
    k_bucket: int
    inflight: int  # passes in flight once this one held its slot
    timing: _PassTiming  # what the dispatcher keeps of it


@dataclass
class _PassTiming:
    """What the close keeps of a pass, and all it keeps: the dispatcher's
    ``_flight`` holds these, never the ``_Pass`` itself, so the last
    reference to a pass's handle (two device arrays, whose release costs
    the thread that drops it about a millisecond on a busy TPU host) falls
    in the completer as it always did, not on the dispatcher's way to the
    next submit."""

    key: tuple  # what decides the service time (the close's estimate)
    t_submit: float
    # perf_counter when the results were on the host or the pass had failed,
    # stamped by the completer BEFORE it frees the slot; 0.0 = in flight
    t_ready: float = 0.0
    failed: bool = False
    due: float = 0.0  # when a held batch behind it predicted its results (0.0: none was)


def _record_pass_spans(p: _Pass, t_done: float) -> None:
    """The sampled requests of one pass, and the pass itself once: a
    ``serving.pass`` span (submit -> results back) beside the spans of the
    first sampled request. A pass serves requests of several traces, so
    the others find it by the ``pass`` attribute their ``serving.scan``
    span carries, not by parentage."""
    sampled = [e for e in p.entries if e.trace_ctx is not None]
    if not sampled:
        return
    for e in sampled:
        _record_entry_spans(e, t_done, p.seq)
    first = sampled[0]
    tracing.record_span(
        "serving.pass", first.trace_ctx.child(), first.trace_ctx.span_id,
        first.t_submit, t_done - first.t_submit,
        {
            "pass": p.seq, "rows": len(p.entries), "padded_rows": p.padded_rows,
            "k_bucket": p.k_bucket, "inflight": p.inflight,
        },
    )


def _record_entry_spans(e: _Entry, t_done: float, pass_seq: int) -> None:
    """One request's batching lifecycle as three sibling spans under the
    request span — explicit timestamps because the phases were measured by
    three different threads, none of which carries the ambient context:

        serving.queue-wait   enqueue -> dispatcher picks it up (incl. the
                             inflight-slot wait: backpressure is queueing)
        serving.assemble     grouping / padding / device submit
        serving.scan         device scan (submit -> results back); carries
                             its pass's number, and the IVF probe count
                             when the scanned matrix is an IVF index
    """
    ctx = e.trace_ctx
    attrs = {"pass": pass_seq}
    if e.nprobe_applied is not None:
        attrs.update(nprobe=e.nprobe_applied, probe_fraction=e.probe_fraction)
    else:
        resolve_nprobe = getattr(e.uploaded, "resolve_nprobe", None)
        if resolve_nprobe is not None:
            try:
                attrs["nprobe"] = int(resolve_nprobe())
            except Exception:
                pass
    tracing.record_span(
        "serving.queue-wait", ctx.child(), ctx.span_id,
        e.t_enqueue, e.t_dispatch - e.t_enqueue,
    )
    tracing.record_span(
        "serving.assemble", ctx.child(), ctx.span_id,
        e.t_dispatch, e.t_submit - e.t_dispatch,
    )
    tracing.record_span(
        "serving.scan", ctx.child(), ctx.span_id,
        e.t_submit, t_done - e.t_submit, attrs,
    )


class _FairQueue:
    """Deficit-round-robin queue over per-tenant sub-queues.

    Drop-in for the subset of :class:`queue.Queue` the batcher uses
    (``put`` / ``get`` / ``get_nowait`` / ``qsize``), plus per-tenant
    depth accounting for the admission controller. Entries without a
    tenant ride a default sub-queue at weight 1.0, so with tenancy off
    every entry lands there and service order is plain FIFO — the wired
    -but-single-tenant overhead bench measures exactly this path.

    Fairness semantics (docs/multi-tenancy.md): each tenant with queued
    entries holds a credit; the queue serves the head tenant while its
    credit lasts (one request costs 1), then rotates it to the tail with
    a fresh quantum of ``quantum * weight`` credits. A hot tenant's
    backlog therefore waits behind at most one quantum from each other
    active tenant per rotation, bounding victim queue-wait regardless of
    attacker depth.

    The close sentinel (``put(None)``) is a flag, not a queued item:
    ``get`` keeps draining real entries first and only yields ``None``
    once every sub-queue is empty — the drain-then-stop contract the
    dispatcher shutdown relies on.
    """

    _DEFAULT = ""  # sub-queue key for untenanted entries

    def __init__(
        self, weights: dict[str, float] | None = None, quantum: float = 8.0
    ) -> None:
        self._cv = threading.Condition()
        self._weights = dict(weights or {})
        self._quantum = max(1.0, float(quantum))
        self._queues: dict[str, "deque[_Entry]"] = {}
        self._rr: "deque[str]" = deque()  # tenants with queued entries
        self._credit: dict[str, float] = {}
        self._size = 0
        self._sentinel = False

    def _refill(self, key: str) -> float:
        return max(1.0, self._quantum * self._weights.get(key, 1.0))

    def put(self, e) -> None:
        with self._cv:
            if e is None:
                self._sentinel = True
                self._cv.notify_all()
                return
            key = e.tenant or self._DEFAULT
            q = self._queues.get(key)
            if q is None:
                q = self._queues[key] = deque()
            if not q:
                self._rr.append(key)
                self._credit[key] = self._refill(key)
            q.append(e)
            self._size += 1
            self._cv.notify()

    def _pop_locked(self):
        while True:
            key = self._rr[0]
            q = self._queues[key]
            if self._credit[key] >= 1.0:
                self._credit[key] -= 1.0
                e = q.popleft()
                self._size -= 1
                if not q:
                    self._rr.popleft()  # re-enters the rotation on next put
                return e
            # credit spent: rotate to the tail with a fresh quantum
            self._rr.rotate(-1)
            self._credit[key] = self._refill(key)

    def get(self, block: bool = True, timeout: float | None = None):
        with self._cv:
            if not block:
                if self._size:
                    return self._pop_locked()
                if self._sentinel:
                    return None
                raise queue.Empty
            deadline = None if timeout is None else time.monotonic() + timeout
            while not self._size and not self._sentinel:
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise queue.Empty
                self._cv.wait(remaining)
            if self._size:
                return self._pop_locked()
            return None  # sentinel, queues drained

    def get_nowait(self):
        return self.get(block=False)

    def qsize(self) -> int:
        with self._cv:
            return self._size

    def depth(self, tenant: str) -> int:
        with self._cv:
            q = self._queues.get(tenant)
            return len(q) if q else 0

    def tenant_depths(self) -> dict[str, int]:
        """Queued entries per tenant (default sub-queue excluded) — the
        admission controller's per-tenant pressure signal."""
        with self._cv:
            return {
                k: len(q) for k, q in self._queues.items() if k and len(q)
            }

    def share_limit(self, tenant: str, max_queue: int) -> int:
        """`tenant`'s slice of a bounded queue, by fair-share weight."""
        weights = self._weights
        total = sum(weights.values()) or 1.0
        share = weights.get(tenant, 1.0) / max(total, weights.get(tenant, 1.0))
        return max(1, int(max_queue * share))

    def over_share(self, tenant: str, max_queue: int) -> bool:
        """True when `tenant` has exhausted its weighted slice of the
        bounded queue WHILE other tenants are queueing too. A lone
        burster may use the whole queue — the per-tenant bound only
        bites under contention, which is exactly when isolation matters."""
        with self._cv:
            q = self._queues.get(tenant)
            if q is None or not q:
                return False
            contended = any(
                k != tenant and len(other) for k, other in self._queues.items()
            )
            if not contended:
                return False
        return len(q) >= self.share_limit(tenant, max_queue)


class TopNBatcher:
    """Coalesces concurrent ``score`` calls into batched ``submit_top_k``
    device calls. Thread-safe; one instance serves any number of models
    (entries carry their own uploaded-matrix handle)."""

    # rows of one scan group (256): a coalesced group past it is still
    # one device dispatch, running ceil(n/256) fused full-matrix scans, so
    # the per-dispatch cost is paid once instead of per 256-row scan
    MULTI_THRESHOLD = topn_ops.MAX_GROUP_ROWS

    def __init__(
        self,
        max_batch: int | None = None,
        max_inflight: int | None = None,
        max_queue: int | None = None,
        tenant_weights: dict[str, float] | None = None,
        fair_quantum: float = 8.0,
    ) -> None:
        self.max_batch = DEFAULT_MAX_BATCH if max_batch is None else int(max_batch)
        self._inflight_cap = MIN_INFLIGHT if max_inflight is None else int(max_inflight)
        # bounded queue (oryx.serving.overload.max-queue): None = unbounded
        self._max_queue = None if max_queue is None else int(max_queue)
        # queue-wait EWMA (the admission controller's primary pressure
        # signal); guarded by _flight_cv
        self._queue_wait_ewma_ms = 0.0
        self._last_wait_obs = time.monotonic()
        # DRR service across per-tenant sub-queues; FIFO-equivalent when
        # every entry is untenanted (docs/multi-tenancy.md)
        self._queue = _FairQueue(tenant_weights, fair_quantum)
        self._pending: queue.Queue = queue.Queue()
        # the pass on the record: handles taken once, observed per pass
        # (per request for the queue wait); none is read back here
        self._pass_seq = 0  # dispatcher thread only
        self._m_queue_wait = _metrics.histogram("serving.batcher.queue-wait.seconds")
        self._m_sharded_queries = _metrics.counter("serving.scan.sharded.queries")
        self._m_cosine_queries = _metrics.counter("serving.scan.cosine.queries")
        self._m_vector_queries = _metrics.counter("serving.scan.vector.queries")
        self._m_indexed_queries = _metrics.counter("serving.scan.indexed.queries")
        self._m_upload_bytes = _metrics.counter("serving.scan.vector.upload-bytes")
        self._m_passes = _metrics.counter("serving.batcher.passes")
        self._m_pass_rows = _metrics.counter("serving.batcher.pass.rows")
        self._m_pass_padded_rows = _metrics.counter("serving.batcher.pass.padded-rows")
        self._m_pass_depth_sum = _metrics.counter("serving.batcher.pass.inflight-depth-sum")
        self._m_pass_k_sum = _metrics.counter("serving.batcher.pass.k-bucket-sum")
        # passes whose handle held ONE result array (ops/topn.py `pack_hits`:
        # every float32 handle): over `passes`, the share that made one
        # download and one fetch
        self._m_pass_packed = _metrics.counter("serving.batcher.pass.packed")
        self._m_submit_seconds = _metrics.histogram("serving.batcher.submit.seconds")
        # the host path, stage by stage (serving/stages.py): a request's time
        # in here and the part of it after the results were on the host; the
        # part of a submit inside the device call; and what the interpreter
        # spends on the two threads
        self._m_entry_seconds = _metrics.histogram("serving.batcher.entry.seconds")
        self._m_wake_seconds = _metrics.histogram("serving.batcher.wake.seconds")
        self._m_device_call_seconds = _metrics.histogram(
            "serving.batcher.submit.device-call.seconds"
        )
        self._m_dispatch_cpu = _metrics.counter("serving.batcher.dispatch.cpu.seconds")
        self._m_complete_cpu = _metrics.counter("serving.batcher.complete.cpu.seconds")
        self._m_pass_seconds = _metrics.histogram("serving.batcher.pass.seconds")
        self._m_deliver_seconds = _metrics.histogram("serving.batcher.deliver.seconds")
        self._m_coalesced = _metrics.counter("serving.batcher.coalesced")
        self._m_held = _metrics.counter("serving.batcher.pass.held")
        self._m_hold_rows = _metrics.counter("serving.batcher.hold.rows")
        self._m_hold_late = _metrics.counter("serving.batcher.hold.late")
        self._m_hold_seconds = _metrics.histogram("serving.batcher.hold.seconds")
        self._m_hold_error = _metrics.histogram("serving.batcher.hold.error.seconds")
        self._m_hold_lag = _metrics.gauge("serving.batcher.hold.lag-ms")
        # the close's own state, all of it the dispatcher thread's: the
        # timings of the passes it submitted whose results it has not yet
        # seen on the host (the completer only stamps `t_ready` on them),
        # when the last results were, the service times by key, the result
        # lags, its own submit times, and the hold of the batch in hand
        self._flight: deque[_PassTiming] = deque()
        self._last_ready = 0.0
        self._service_s: OrderedDict[tuple, deque[float]] = OrderedDict()
        self._lag_s: deque[float] = deque(maxlen=HOLD_HISTORY)
        self._submit_s: deque[float] = deque(maxlen=HOLD_HISTORY)
        self._hold_s = 0.0  # how long the close of the batch in hand was held
        self._hold_due = 0.0  # when the results of the pass ahead of it were due
        self._flight_cv = threading.Condition()
        self._inflight_count = 0
        self._state_lock = threading.Lock()  # serializes score-enqueue vs close
        self._closed = False
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="TopNBatcherDispatch", daemon=True
        )
        self._completer = threading.Thread(
            target=self._complete_loop, name="TopNBatcherComplete", daemon=True
        )
        self._dispatcher.start()
        self._completer.start()

    # -- request side --------------------------------------------------------

    def score(
        self, uploaded, query: np.ndarray, k: int, cosine: bool = False
    ) -> tuple[np.ndarray, np.ndarray]:
        """(indices, scores) for one query — blocks until its batch lands.

        When ``k`` exceeds the uploaded matrix's item count the device call
        clamps it, so fewer than ``k`` rows come back — same contract as
        ``top_k_scores``. Raises ``RuntimeError`` if the batcher is closed
        (callers going through :func:`score_default` get a retry)."""
        e = _Entry(uploaded, np.asarray(query, dtype=np.float32), int(k), bool(cosine))
        return self._enqueue(e)

    def score_indexed(
        self, uploaded, x_dev, row: int, k: int, cosine: bool = False
    ) -> tuple[np.ndarray, np.ndarray]:
        """score() with the query vector already device-resident: the
        entry carries only an int32 row into ``x_dev``; coalesced groups
        dispatch via submit_top_k_multi_indexed (device-side gather, no
        vector upload)."""
        e = _Entry(uploaded, None, int(k), bool(cosine), x_dev=x_dev, row=int(row))
        return self._enqueue(e)

    def _enqueue(self, e: _Entry) -> tuple[np.ndarray, np.ndarray]:
        if tracing.enabled():
            ctx = tracing.current()
            if ctx is not None and ctx.sampled:
                e.trace_ctx = ctx
                e.t_enqueue = time.time()
        # snapshot the admission controller's reduced-probe override and
        # the tenant identity here, on the request thread that carries
        # both contextvars
        e.probe_fraction = active_probe_fraction()
        e.tenant = current_tenant()
        e.t_q = time.perf_counter()
        with self._state_lock:  # an entry can never land after the sentinel
            if self._closed:
                raise BatcherClosedError("batcher is closed")
            if self._max_queue is not None and self._queue.qsize() >= self._max_queue:
                # approximate bound (qsize races concurrent enqueues by a
                # few entries) — exactness doesn't matter, unboundedness does
                _metrics.counter("serving.batcher.queue.rejected").inc()
                raise BatcherOverloadedError(
                    f"batcher queue full ({self._max_queue} entries)"
                )
            if (
                e.tenant is not None
                and self._max_queue is not None
                and self._queue.over_share(e.tenant, self._max_queue)
            ):
                # noisy-neighbor bound: under contention a tenant only
                # gets its weighted slice of the bounded queue; alone it
                # may still fill the whole thing
                _metrics.counter("serving.batcher.queue.rejected").inc()
                _metrics.counter(
                    f"serving.batcher.queue.rejected.tenant.{e.tenant}"
                ).inc()
                raise BatcherOverloadedError(
                    f"tenant {e.tenant} over fair queue share"
                )
            self._queue.put(e)
            _metrics.gauge("serving.batcher.queue.depth").set(self._queue.qsize())
        e.done.wait()
        if e.error is not None:
            raise e.error
        if stages.staged():  # one request in eight of a front's thread
            t_woken = time.perf_counter()
            self._m_entry_seconds.observe(t_woken - e.t_q)
            self._m_wake_seconds.observe(t_woken - e.t_ready)
            stages.scanned(e.t_q, t_woken)
        return e.idx, e.vals

    # -- dispatcher ----------------------------------------------------------

    def _device_busy(self) -> bool:
        with self._flight_cv:
            return self._inflight_count >= self._inflight_cap

    def _settle(self) -> None:
        """Dispatcher only: take the passes whose results have reached the
        host off ``_flight`` and learn from each. One submitted before the
        results ahead of it were there ran behind that pass: ready less
        those results is its service time (and up to a lag more where the
        device had already ended that pass: the low quantile's to discard;
        the lag estimate is not used here, or an error in it would feed
        itself). One submitted after them found the device idle: ready
        less submit less the service time of its key, where there is one,
        is the result lag."""
        flight = self._flight
        while flight and flight[0].t_ready:
            p = flight.popleft()
            if not p.failed and p.t_submit < self._last_ready:
                samples = self._service_s.setdefault(p.key, deque(maxlen=HOLD_HISTORY))
                samples.append(p.t_ready - self._last_ready)
                self._service_s.move_to_end(p.key)
                if len(self._service_s) > HOLD_KEYS:
                    self._service_s.popitem(last=False)
            elif not p.failed:
                service_s = self._service_estimate(p.key)
                if service_s is not None:
                    self._lag_s.append(max(0.0, p.t_ready - p.t_submit - service_s))
                    self._m_hold_lag.set(1000.0 * self._lag())
            if p.due and not p.failed:
                self._m_hold_error.observe(p.t_ready - p.due)
            self._last_ready = p.t_ready

    def _service_estimate(self, key: tuple) -> float | None:
        """The lower quartile of the last passes of `key`, None before
        ``HOLD_MIN_SAMPLES`` of them: one slow pass does not stretch it."""
        samples = self._service_s.get(key)
        if samples is None or len(samples) < HOLD_MIN_SAMPLES:
            return None
        return sorted(samples)[(len(samples) - 1) // 4]

    def _lag(self) -> float:
        """The median result lag of the last passes that started on an
        idle device; 0.0 while there is none (and then no close is held)."""
        return sorted(self._lag_s)[len(self._lag_s) // 2] if self._lag_s else 0.0

    def _close_at(self) -> float | None:
        """When the batch in hand has to be closed for its submit to reach
        the device before the pass ahead leaves it, and None for now: no
        pass ahead (or fewer in flight than the other slots hold: the
        pipeline is filled first), no estimate for one of them or for the
        lag. ``_hold_due`` is left at the results' predicted time."""
        self._settle()
        flight = self._flight
        if not flight or len(flight) != self._inflight_cap - 1 or not self._lag_s:
            return None
        lag_s = self._lag()
        due = self._last_ready
        for p in flight:
            service_s = self._service_estimate(p.key)
            if service_s is None:
                return None
            due = max(due, p.t_submit + lag_s) + service_s
        self._hold_due = due
        return due - (max(self._submit_s) + lag_s + HOLD_GUARD_S)

    def _take_batch(self) -> list[_Entry] | None:
        first = self._queue.get()
        if first is None:
            return None
        batch = [first]
        coalesced = 0
        hold_from, hold_len = 0.0, 0  # since when the close is held, the batch's rows then
        while len(batch) < self.max_batch:
            try:
                e = self._queue.get_nowait()
            except queue.Empty:
                # bucket-fragmentation fix: with every inflight slot taken
                # this thread is about to block anyway, so absorb arrivals
                # in bounded waits instead of dispatching a dribble now
                # and more power-of-two-padded fragments right after it
                busy = self._device_busy()
                wait = 0.001
                if not busy:
                    # the close: behind a pass that is far from its end the
                    # batch would only queue on the device, so it stays open
                    close_at = self._close_at()
                    now = time.perf_counter()
                    if close_at is None or close_at <= now:
                        break
                    if not hold_from:
                        hold_from, hold_len = now, len(batch)
                    self._flight[-1].due = self._hold_due
                    wait = min(wait, close_at - now)  # the submit is not up to 1 ms late
                try:
                    e = self._queue.get(timeout=wait)
                except queue.Empty:
                    continue
                if busy:
                    coalesced += 1
            if e is None:
                self._queue.put(None)  # keep the shutdown signal visible
                break
            batch.append(e)
        self._hold_s = time.perf_counter() - hold_from if hold_from else 0.0
        if coalesced:
            self._m_coalesced.inc(coalesced)
        if hold_from and len(batch) > hold_len:
            self._m_hold_rows.inc(len(batch) - hold_len)
        _metrics.gauge("serving.batcher.queue.depth").set(self._queue.qsize())
        return batch

    def _dispatch_loop(self) -> None:
        cpu = stages.ThreadCpu(self._m_dispatch_cpu)
        while True:
            batch = self._take_batch()
            if batch is None:
                self._pending.put(None)
                return
            # group by (matrix snapshot, cosine, query-matrix snapshot,
            # probe override): indices are only meaningful against the
            # snapshots the caller captured, vector entries never mix with
            # index entries, and a reduced-probe request must not widen a
            # full-probe neighbour's scan (or vice versa)
            groups: dict[tuple, list[_Entry]] = {}
            for e in batch:
                xk = id(e.x_dev) if e.row is not None else None
                groups.setdefault(
                    (id(e.uploaded), e.cosine, xk, e.probe_fraction), []
                ).append(e)
            for (_, cosine, _xk, _pf), entries in groups.items():
                self._submit_group(entries, cosine)
            cpu.account(time.perf_counter())

    def _acquire_slot(self) -> int:
        """Block until an inflight slot is free, take it, and return the
        number of passes now in flight (this one included)."""
        with self._flight_cv:
            while self._inflight_count >= self._inflight_cap:
                self._flight_cv.wait(timeout=1.0)
            self._inflight_count += 1
            _metrics.gauge("serving.batcher.inflight").set(self._inflight_count)
            return self._inflight_count

    def _release_slot(self) -> None:
        with self._flight_cv:
            self._inflight_count -= 1
            _metrics.gauge("serving.batcher.inflight").set(self._inflight_count)
            self._flight_cv.notify()

    def _group_nprobe(self, entries: list[_Entry]) -> int | None:
        """Resolve a reduced-probe override into a concrete ``nprobe`` for
        one coalesced group (all entries share the same probe fraction by
        group key). None when the group runs at full quality or the handle
        is not an IVF index."""
        pf = entries[0].probe_fraction
        if pf is None:
            return None
        resolve = getattr(entries[0].uploaded, "resolve_nprobe", None)
        if resolve is None:
            return None
        try:
            nprobe = max(1, int(resolve() * pf))
        except Exception:
            return None
        for e in entries:
            e.nprobe_applied = nprobe
        return nprobe

    def _observe_queue_wait(self, entries: list[_Entry]) -> None:
        """EWMA the worst enqueue->dispatch wait of the group — the
        admission controller's primary pressure signal — and put every
        entry's own wait, between the same instants, into the histogram."""
        now = time.perf_counter()
        worst = 0.0
        for e in entries:
            wait = now - e.t_q
            self._m_queue_wait.observe(wait)
            worst = max(worst, wait)
        wait_ms = worst * 1000.0
        with self._flight_cv:
            self._queue_wait_ewma_ms = (
                WAIT_EWMA_ALPHA * wait_ms
                + (1.0 - WAIT_EWMA_ALPHA) * self._queue_wait_ewma_ms
            )
            self._last_wait_obs = time.monotonic()
            _metrics.gauge("serving.batcher.queue.wait-ewma-ms").set(
                self._queue_wait_ewma_ms
            )

    def queue_wait_ewma_ms(self) -> float:
        """Current queue-wait EWMA with idle decay: when no dispatches
        happen (queue went quiet) the signal halves every
        ``WAIT_DECAY_HALF_LIFE_S`` so the shed ladder can release even
        though nothing is flowing to refresh the EWMA."""
        now = time.monotonic()
        with self._flight_cv:
            idle = now - self._last_wait_obs
            ewma = self._queue_wait_ewma_ms
        if idle <= WAIT_DECAY_GRACE_S:
            return ewma
        return ewma * 0.5 ** ((idle - WAIT_DECAY_GRACE_S) / WAIT_DECAY_HALF_LIFE_S)

    def _submit_group(self, entries: list[_Entry], cosine: bool) -> None:
        inflight = self._acquire_slot()
        # queue-wait ends here: the entry has a dispatcher AND an inflight
        # slot (slot contention is backpressure, i.e. still queueing)
        self._observe_queue_wait(entries)
        for e in entries:
            if e.trace_ctx is not None:
                e.t_dispatch = time.time()
        n = len(entries)
        indexed = entries[0].row is not None
        if indexed or n <= self.MULTI_THRESHOLD:
            padded = _b_bucket(n)
        else:  # vectors past one scan group: whole groups
            padded = -(-n // self.MULTI_THRESHOLD) * self.MULTI_THRESHOLD
        self._pass_seq += 1  # numbers dispatch attempts: a failed one leaves a gap
        seq = self._pass_seq
        t_slot = time.perf_counter()
        try:
            with profiling.annotate(
                "serving.pass.submit",
                **{
                    "pass": seq, "rows": n, "padded_rows": padded,
                    "kind": "indexed" if indexed else "vector", "cosine": int(cosine),
                },
            ):
                kk = _k_bucket(max(e.k for e in entries))
                nprobe = self._group_nprobe(entries)
                if isinstance(entries[0].uploaded, topn_ops.ShardedItemMatrix):
                    # beside the count by submit kind, which the submit
                    # makes next (a reader's snapshot falls between the two
                    # once in a great while, not once in six passes): these
                    # rows are scanned on every shard and merged across chips
                    self._m_sharded_queries.inc(n)
                if cosine:  # beside the count by submit kind too
                    self._m_cosine_queries.inc(n)
                if indexed:
                    handle = self._submit_indexed(entries, cosine, kk, nprobe, padded)
                else:
                    handle = self._submit_vectors(entries, cosine, kk, nprobe, padded)
            for e in entries:
                if e.trace_ctx is not None:
                    e.t_submit = time.time()
            key = (id(entries[0].uploaded), indexed, cosine, nprobe, padded, kk)
            t_submit = time.perf_counter()
            timing = _PassTiming(key, t_submit)
            self._pending.put(_Pass(handle, entries, t_submit, seq, padded, kk, inflight, timing))
        except BaseException as exc:  # deliver the failure to the waiters
            self._release_slot()
            self._hold_s = 0.0
            for e in entries:
                e.error = exc
                e.done.set()
            return
        self._settle()  # here too: under a queue that is never empty nothing asks `_close_at`
        self._flight.append(timing)
        self._submit_s.append(t_submit - t_slot)
        self._m_submit_seconds.observe(t_submit - t_slot)
        self._m_passes.inc()
        self._m_pass_rows.inc(n)
        self._m_pass_padded_rows.inc(padded)
        self._m_pass_depth_sum.inc(inflight)
        self._m_pass_k_sum.inc(kk)
        if handle.packed:
            self._m_pass_packed.inc()
        if self._hold_s:  # the first pass of a batch whose close was held
            self._m_held.inc()
            self._m_hold_seconds.observe(self._hold_s)
            self._hold_s = 0.0
            if t_submit > self._hold_due - self._lag():
                self._m_hold_late.inc()  # the pass ahead had left the device: it idled

    def _submit_vectors(self, entries: list[_Entry], cosine: bool, kk: int, nprobe, padded: int):
        """Dispatch one coalesced group of uploaded query vectors (caller
        holds the inflight slot and delivers errors); returns the handle."""
        n = len(entries)
        uploaded = entries[0].uploaded
        self._m_vector_queries.inc(n)
        if n > self.MULTI_THRESHOLD and isinstance(uploaded, topn_ops.IVFIndex):
            padded = n  # an IVF index groups its queries itself: no whole scan groups
        # the block the device is given, made once: the bucket's rows past
        # the entries stay zero queries, whose results are discarded
        queries = np.zeros((padded, entries[0].query.shape[-1]), np.float32)
        np.stack([e.query for e in entries], out=queries[:n])
        self._m_upload_bytes.inc(queries.nbytes)
        # tiered item store: hint the cells this group will probe so
        # the store's disk->RAM promotions overlap the dispatch below
        # instead of stalling the stage-1 gather (advisory; no-op on
        # flat-plane indexes)
        prefetch = getattr(uploaded, "prefetch_for_queries", None)
        if prefetch is not None:
            try:
                prefetch(queries[:n], nprobe=nprobe, cosine=cosine)
            except Exception:  # never let a hint fail a dispatch
                pass
        return self._device_call(
            topn_ops.submit_top_k, uploaded, queries, kk, cosine=cosine, nprobe=nprobe
        )

    def _submit_indexed(self, entries: list[_Entry], cosine: bool, kk: int, nprobe, padded: int):
        """Dispatch one coalesced index-entry group (caller holds the
        inflight slot and delivers errors); returns the handle."""
        rows = np.asarray([e.row for e in entries], dtype=np.int32)
        self._m_indexed_queries.inc(len(entries))
        pad = padded - len(rows)
        if pad:  # bucketed shapes: row 0 repeats, results discarded
            rows = np.concatenate([rows, np.zeros(pad, np.int32)])
        return self._device_call(
            topn_ops.submit_top_k_multi_indexed,
            entries[0].uploaded,
            entries[0].x_dev,
            rows,
            kk,
            cosine=cosine,
            scan_batch=self.MULTI_THRESHOLD,
            nprobe=nprobe,
        )

    def _device_call(self, submit, *args, **kwargs):
        """The part of a submit inside `ops/topn.py` (row groups, the
        jitted call, which takes them as NumPy, and the one result copy;
        two where the scores travel as bfloat16 or an IVF index answers),
        timed and marked on the profiler's timeline; the rest of
        `serving.batcher.submit.seconds` is the batcher's own Python."""
        t0 = time.perf_counter()
        with profiling.annotate("serving.pass.submit.device-call"):
            handle = submit(*args, **kwargs)
        self._m_device_call_seconds.observe(time.perf_counter() - t0)
        return handle

    # -- completer -----------------------------------------------------------

    def _complete_loop(self) -> None:
        cpu = stages.ThreadCpu(self._m_complete_cpu)
        while True:
            item = self._pending.get()
            if item is None:
                return
            entries = item.entries
            latency = None
            t_ready = 0.0
            try:
                with profiling.annotate("serving.pass.wait", **{"pass": item.seq}):
                    idx, vals = item.handle.result()
                t_ready = time.perf_counter()
                latency = t_ready - item.t_submit
                for row, e in enumerate(entries):
                    e.idx = idx[row, : e.k]
                    e.vals = vals[row, : e.k]
                    e.t_ready = t_ready
            except BaseException as exc:
                item.timing.failed = True
                for e in entries:
                    e.error = exc
            finally:
                # before the slot is free: the dispatcher reads the stamp
                # once it is (the close's estimate, `_settle`)
                item.timing.t_ready = t_ready or time.perf_counter()
                self._release_slot()
                _record_pass_spans(item, time.time())
                for e in entries:
                    e.done.set()
                if latency is not None:
                    self._m_pass_seconds.observe(latency)
                    self._m_deliver_seconds.observe(time.perf_counter() - t_ready)
                    cpu.account(t_ready)

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        with self._state_lock:
            if self._closed:
                return
            self._closed = True
            self._queue.put(None)
        self._dispatcher.join(timeout=5)
        self._completer.join(timeout=5)


_default_lock = threading.Lock()
_default: TopNBatcher | None = None
_default_init: dict = {}


_atexit_registered = False


def configure_scheduler(
    max_batch: int | None = None,
    max_inflight: int | None = None,
    max_queue: int | None = None,
) -> None:
    """Pin the process-wide batcher's scheduler knobs (the serving layer
    maps ``oryx.serving.scan.max-batch`` / ``max-inflight`` and
    ``oryx.serving.overload.max-queue`` here at startup, before the
    default batcher spins up). ``None`` leaves a knob at its default (for
    ``max_queue``: unbounded)."""
    with _default_lock:
        _default_init["max_batch"] = max_batch
        _default_init["max_inflight"] = max_inflight
        _default_init["max_queue"] = max_queue


def configure_fairness(
    tenant_weights: dict[str, float] | None, quantum: float = 8.0
) -> None:
    """Pin the DRR fair-share weights for the process-wide batcher (the
    serving layer maps ``oryx.tenancy.tenants.<id>.weight`` and
    ``oryx.tenancy.fair-share.quantum`` here at startup). ``None``
    weights keep tenancy-agnostic FIFO behavior."""
    with _default_lock:
        _default_init["tenant_weights"] = tenant_weights
        _default_init["fair_quantum"] = quantum


def default_batcher_signals() -> tuple[float, int]:
    """(queue_wait_ewma_ms, queue_depth) of the live default batcher, or
    zeros when none is running — the admission controller polls this on
    its control interval, so the idle fast path must stay cheap and must
    never lazily create a batcher."""
    with _default_lock:
        b = _default
    if b is None or b._closed:
        return 0.0, 0
    return b.queue_wait_ewma_ms(), b._queue.qsize()


def default_tenant_depths() -> dict[str, int]:
    """Per-tenant queued-entry counts of the live default batcher ({} when
    none is running) — the per-tenant admission ladders poll this the same
    way the global ladder polls :func:`default_batcher_signals`."""
    with _default_lock:
        b = _default
    if b is None or b._closed:
        return {}
    return b._queue.tenant_depths()


def get_default_batcher() -> TopNBatcher:
    """Process-wide batcher shared by all serving models. Lazily created
    (and re-created after a close); an atexit hook closes whatever default
    is live at interpreter shutdown so late re-creations — e.g. a request
    draining after the last serving layer released the batcher — cannot
    leak threads past process teardown."""
    global _default, _atexit_registered
    with _default_lock:
        if _default is None or _default._closed:
            _default = TopNBatcher(**_default_init)
            if not _atexit_registered:
                import atexit

                atexit.register(close_default_batcher)
                _atexit_registered = True
        return _default


def score_indexed_default(
    uploaded, x_dev, row: int, k: int, cosine: bool = False
) -> tuple[np.ndarray, np.ndarray]:
    """``score_default`` for index-submitted entries (same close-race
    retry contract)."""
    for attempt in range(4):
        try:
            return get_default_batcher().score_indexed(
                uploaded, x_dev, row, k, cosine=cosine
            )
        except BatcherClosedError:
            if attempt == 3:
                raise
    raise AssertionError("unreachable")


def score_default(
    uploaded, query: np.ndarray, k: int, cosine: bool = False
) -> tuple[np.ndarray, np.ndarray]:
    """``get_default_batcher().score(...)`` retried across close races: a
    concurrent ``close`` can flip ``_closed`` between the lookup and the
    enqueue, in which case the lookup is repeated against the replacement
    batcher. Only :class:`BatcherClosedError` is retried — device errors
    propagate immediately."""
    for attempt in range(4):
        try:
            return get_default_batcher().score(uploaded, query, k, cosine=cosine)
        except BatcherClosedError:
            if attempt == 3:
                raise
    raise AssertionError("unreachable")


_default_refs = 0


def retain_default_batcher() -> None:
    """Register a user of the process-wide batcher (serving-layer start)."""
    global _default_refs
    with _default_lock:
        _default_refs += 1


def release_default_batcher() -> None:
    """Drop a reference; the batcher is closed when the last serving layer
    in the process releases it (so one layer's close cannot kill a batcher
    another live layer is using)."""
    global _default, _default_refs
    with _default_lock:
        _default_refs = max(0, _default_refs - 1)
        if _default_refs > 0:
            return
        batcher, _default = _default, None
    if batcher is not None:
        batcher.close()


def close_default_batcher() -> None:
    """Unconditionally shut down the process-wide batcher (tests,
    process teardown)."""
    global _default, _default_refs
    with _default_lock:
        batcher, _default = _default, None
        _default_refs = 0
    if batcher is not None:
        batcher.close()
