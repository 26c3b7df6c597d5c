"""The host path of a request, stage by stage, in wall time and in
thread-CPU time (docs/observability.md, "The host path").

A request crosses these boundaries on the thread that serves it, and the
differences tile its wall time exactly:

    last byte parsed -> _handle / _serve_one begins     front.ingress
    begins -> the entry's t_q in TopNBatcher._enqueue   handler.pre     |
    t_q -> the thread runs again after done.wait()      batcher.entry   | = serving.request.seconds
    woken -> _observe_request                           handler.post    |
    _observe_request -> response handed to the socket   front.respond

(``_handle``: serving/python_front.py; ``_serve_one``: serving/native_front.py;
``_observe_request``: serving/request.py, called once a request, from ``answer``.)

The batcher feeds ``entry`` (and ``wake``, the part of it after the
pass's results were on the host) from its own handles; everything else
is fed here, once the answer has left, from stamps kept in one object a
thread that the front sets and the batcher writes into (``scanned``), so
no endpoint carries a new argument. A request that never scans (a cache
hit, a shed, an error) feeds ``ingress`` and ``respond`` and none of the
others; one that scans twice feeds ``entry`` and ``wake`` a scan, ``pre``
up to its first and ``post`` from its last, and counts in
``serving.handler.rescans``.

Inside ``front.respond`` the native front stamps either side of its one
call into the C++ front (``respond_called``): ``serving.front.respond.call``
is that call and the interpreter's return from it, a staged request's only.

Only every ``SAMPLE_EVERY``-th request of a thread is staged: the others
pass three tests of one attribute and feed nothing, because a histogram's
observation is 2 us of interpreter on a chip's host and a saturated
replica loses several times the share of its interpreter that it is
charged (PERF.md section 6, PR 35). The tiles of a staged request still
sum to that request's own ``serving.request.seconds`` observation; a
window's means are those of its staged eighth.

Wall time under one interpreter lock is mostly waiting for the lock, so
every wall stage is read with its CPU beside it: what the interpreter
spent on a thread is that thread's ``time.thread_time()``, the wall less
that is what the thread waited. Each thread of the host path accounts its
own CPU to its role's counter (``ThreadCpu``: a request's own thread, which
on the native front also takes the request from the C++ front and decodes
it, the batcher's dispatcher and completer), and
only every ``CPU_EVERY_S``: on a chip's host one read of a CPU clock is a
system call of 6 us under the interpreter lock, 70 times a wall stamp's
cost. Handles are taken once, in ``ServingLayer.__init__``: nothing is
looked up by name on the request path, and a process that served nothing
reads 0, not nothing.
"""

from __future__ import annotations

import threading
import time

from oryx_tpu.common import metrics

_local = threading.local()

# A thread's CPU clock (and the process's, which sums every thread in the
# kernel) is read this often at most: each read is a system call under the
# interpreter lock, 6 us on a v5e host where a wall stamp is 0.09 us, and
# read three times a request and twice a frame and a pass they cost the
# saturated cell a tenth of its rate; 35 threads reading ten times a second
# still showed there, so it is twice a second (PERF.md section 6, PR 35)
CPU_EVERY_S = 0.5

# Of a thread's requests, the first and every eighth after it are staged:
# six observations are 12 us of interpreter on a chip's host, and staging
# every request read the saturated cell 4-15 % under its parent; an eighth
# of a 30 s window still gives each mean 750-4,000 requests
SAMPLE_EVERY = 8


class ThreadCpu:
    """The calling thread's CPU, accounted to ``counter`` whenever
    ``account`` finds the last reading ``CPU_EVERY_S`` old: made on the
    thread itself, which spends its CPU on one role of the host path and
    blocks in between, so the counter's delta over a window is what that
    role's threads burnt in it (to the last half second a thread)."""

    __slots__ = ("counter", "cpu", "at")

    def __init__(self, counter: metrics.Counter) -> None:
        self.counter = counter
        self.cpu = time.thread_time()
        self.at = time.perf_counter()

    def account(self, now: float) -> bool:
        if now - self.at < CPU_EVERY_S:
            return False
        cpu = time.thread_time()
        self.counter.inc(cpu - self.cpu)
        self.cpu, self.at = cpu, now
        return True


class _Thread:
    """One serving thread's stamps of the staged request it serves
    (``perf_counter``), its CPU account and its count of requests (all of
    them; ``counted`` of them are in ``serving.handler.requests`` and,
    where the thread ``takes`` its requests from the native front itself,
    in ``serving.front.taken``): made at the thread's first request and
    kept, so a request allocates nothing here."""

    __slots__ = (
        "cpu", "n", "counted", "takes", "ingress_s", "t_begin", "t_q", "t_woken", "scans",
        "t_observed", "call_s",
    )

    def __init__(self, counter: metrics.Counter, takes: bool = False) -> None:
        self.cpu = ThreadCpu(counter)
        self.n = self.counted = 0
        self.takes = takes
        self.scans = -1  # passes through the batcher so far; -1: no staged request is open


def staged() -> bool:
    """Whether the calling thread serves a staged request: the batcher
    asks before it stamps and observes anything of its own."""
    st = getattr(_local, "thread", None)
    return st is not None and st.scans >= 0


def scanned(t_q: float, t_woken: float) -> None:
    """Batcher, on a staged request's thread, once it runs again after a
    scan: the entry's ``t_q`` and the wake, both ``perf_counter``."""
    st = _local.thread
    if not st.scans:
        st.t_q = t_q
    st.scans += 1
    st.t_woken = t_woken


def respond_called(t_call: float, t_back: float) -> None:
    """Native front, on a staged request's thread: the stamps either side
    of its ``hf_respond`` call, both ``perf_counter``."""
    _local.thread.call_s = t_back - t_call


class HostStages:
    """The instruments of the front and the handlers, and the three calls
    a front makes a request: ``begin`` first, ``observed`` from
    ``_observe_request``, ``responded`` last. The first two only stamp;
    every observation is made in ``responded``, once the answer has left
    and no client waits for it."""

    def __init__(self) -> None:
        registry = metrics.registry
        self.ingress = registry.histogram("serving.front.ingress.seconds")
        self.pre = registry.histogram("serving.handler.pre.seconds")
        self.post = registry.histogram("serving.handler.post.seconds")
        self.respond = registry.histogram("serving.front.respond.seconds")
        # inside `respond`: the native front's hf_respond call and the
        # interpreter's return from it (operator histogram; the Python
        # front makes no such call and feeds nothing)
        self.respond_call = registry.histogram("serving.front.respond.call.seconds")
        self.rescans = registry.counter("serving.handler.rescans")
        # every request a Python thread began, staged or not, counted when
        # its thread accounts its CPU: what the CPU counters are read over
        self.requests = registry.counter("serving.handler.requests")
        self.handler_cpu = registry.counter("serving.handler.cpu.seconds")
        # requests a serving thread took from the native front itself (all
        # of them there, none on the Python front; counted with `requests`,
        # so their ratio is exact), and how many such threads stand:
        # serving.front.taken over serving.handler.requests is the share
        # that came in with no thread in between
        self.taken = registry.counter("serving.front.taken")
        self.workers = registry.gauge("serving.front.workers")
        self.workers.set(0)
        # every thread of the process, XLA's and the native front's included;
        # it only rises, and a reader takes the delta of its value
        self.process_cpu = registry.gauge("serving.process.cpu.seconds")
        self.process_cpu.set(time.process_time())
        self._process_cpu_at = time.perf_counter()
        self.native = registry.gauge("serving.front.native")

    def takes_requests(self) -> None:
        """Native front, on each of its serving threads before its first
        request: every request this thread begins it took from the C++
        front itself, and counts in ``serving.front.taken``."""
        _local.thread = _Thread(self.handler_cpu, takes=True)

    def begin(self, ingress_s: float) -> float:
        """The request's first stamp, which is also where
        ``serving.request.seconds`` starts: returned for it. ``ingress_s``
        is how long ago, on the front's own clock, the request's last byte
        was parsed."""
        st = getattr(_local, "thread", None)
        if st is None:  # this thread's first request
            st = _local.thread = _Thread(self.handler_cpu)
        n = st.n
        st.n = n + 1
        if n % SAMPLE_EVERY:
            return time.perf_counter()
        st.ingress_s = ingress_s
        st.scans = 0
        st.t_observed = 0.0  # `_observe_request` has not run yet
        st.call_s = 0.0  # nor has the native front made its hf_respond call
        st.t_begin = now = time.perf_counter()
        return now

    def observed(self, now: float) -> None:
        """From ``_observe_request``, with the ``perf_counter`` reading
        that ended ``serving.request.seconds``."""
        st = getattr(_local, "thread", None)
        if st is not None:
            st.t_observed = now

    def responded(self) -> None:
        """Once the response is handed to the socket (or the request has
        failed past answering): ends the request on this thread."""
        st = getattr(_local, "thread", None)
        if st is None or st.scans < 0:
            return
        now = time.perf_counter()
        self.ingress.observe(st.ingress_s)
        t_observed = st.t_observed
        if t_observed:
            scans = st.scans
            if scans:
                self.pre.observe(st.t_q - st.t_begin)
                self.post.observe(t_observed - st.t_woken)
                if scans > 1:
                    self.rescans.inc(scans - 1)
            self.respond.observe(now - t_observed)
            if st.call_s:
                self.respond_call.observe(st.call_s)
        st.scans = -1
        if st.cpu.account(now):
            self.requests.inc(st.n - st.counted)
            if st.takes:
                self.taken.inc(st.n - st.counted)
            st.counted = st.n
        if now - self._process_cpu_at >= CPU_EVERY_S:
            self._process_cpu_at = now
            self.process_cpu.set(time.process_time())
