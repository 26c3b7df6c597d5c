"""Native HTTP front: byte-parity against the Python data plane.

The native front (oryx_tpu/native/httpfront.cpp + serving/native_front.py)
is a *performance* feature with a *correctness* contract: a client must
not be able to tell which front served it. These tests enforce that
contract literally — same request bytes in, same response bytes out
(modulo the Date header) — across routes, methods, error codes, content
negotiation, the shed/stale overload rungs, tenants, and seeded fuzz
with mid-run connection drops. Hardening tests cover the attack surface
the Python front never had (slowloris, oversized frames, pipelining),
and the fleet acceptance test proves a rolling restart with the native
front enabled still loses zero requests.

Documented divergences (docs/serving-native.md) are exactly the wire
errors the Python front cannot express byte-identically: 400/413/431/
501/505 answered natively carry ``Server: oryx_tpu`` without the
Python version suffix. Everything that reaches dispatch is bit-equal.
"""

from __future__ import annotations

import ctypes
import http.client
import json
import re
import socket
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO_ROOT / "tools"))

from oryx_tpu import bus, native
from oryx_tpu.bus import blockcodec
from oryx_tpu.common import config as C
from oryx_tpu.common import metrics
from oryx_tpu.serving.layer import ServingLayer

_HAVE_NATIVE = native.get_library() is not None and hasattr(
    native.get_library(), "hf_create"
)

needs_native = pytest.mark.skipif(
    not _HAVE_NATIVE, reason="native toolchain unavailable"
)

_DATE_RE = re.compile(rb"^Date: [^\r\n]+\r$", re.M)
# /healthz reports wall-clock staleness; the two layers measure at
# slightly different instants (and the native snapshot is rendered on the
# control tick), so the float — and the Content-Length it perturbs — are
# the only legitimately time-varying bytes in any body
_STALENESS_RE = re.compile(rb'"staleness_seconds": [0-9.eE+-]+')
_CLEN_RE = re.compile(rb"^Content-Length: \d+\r$", re.M)


def make_config(broker, **overrides):
    extra = "\n".join(f"{k} = {v}" for k, v in overrides.items())
    return C.get_default().with_overlay(
        f"""
        oryx {{
          input-topic.broker = "{broker}"
          update-topic.broker = "{broker}"
          serving {{
            api.port = 0
            model-manager-class = "oryx_tpu.example.serving:ExampleServingModelManager"
            application-resources = "oryx_tpu.example.serving"
            {extra}
          }}
        }}
        """
    )


def raw(port, data: bytes, timeout=5.0) -> bytes:
    """One connection: send request bytes, read to EOF."""
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.sendall(data)
        chunks = []
        while True:
            try:
                b = s.recv(65536)
            except (TimeoutError, socket.timeout):
                break
            if not b:
                break
            chunks.append(b)
    return b"".join(chunks)


def request_bytes(method, path, headers=None, body=None) -> bytes:
    h = {"Host": "127.0.0.1", "Connection": "close"}
    if body is not None:
        h["Content-Length"] = str(len(body))
    if headers:
        h.update(headers)
    head = f"{method} {path} HTTP/1.1\r\n" + "".join(
        f"{k}: {v}\r\n" for k, v in h.items()
    )
    return head.encode("latin-1") + b"\r\n" + (body or b"")


def fetch(port, method="GET", path="/", headers=None, body=None) -> bytes:
    return raw(port, request_bytes(method, path, headers=headers, body=body))


def mask(resp: bytes) -> bytes:
    """Strip the legitimately nondeterministic bytes before comparing."""
    resp = _DATE_RE.sub(b"Date: <masked>\r", resp)
    if b'"staleness_seconds"' in resp:
        resp = _STALENESS_RE.sub(b'"staleness_seconds": 0', resp)
        resp = _CLEN_RE.sub(b"Content-Length: <masked>\r", resp)
    return resp


def wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def publish_model(broker, payload: dict) -> None:
    with broker.producer("OryxUpdate") as p:
        p.send("MODEL", json.dumps(payload))


def is_200(port, path="/ready") -> bool:
    return fetch(port, path=path).startswith(b"HTTP/1.1 200")


class Pair:
    """Two identically configured layers on one broker, one per front."""

    def __init__(self, broker_loc, **overrides):
        self.broker_loc = broker_loc
        self.broker = bus.get_broker(broker_loc)
        self.native = ServingLayer(
            make_config(broker_loc, **{"native.enabled": '"true"'}, **overrides)
        )
        self.python = ServingLayer(
            make_config(broker_loc, **{"native.enabled": '"false"'}, **overrides)
        )
        self.native.start()
        self.python.start()
        assert self.native._native_front is not None, "native front must start"
        assert self.python._native_front is None

    def close(self):
        self.native.close()
        self.python.close()

    def layers(self):
        return (self.native, self.python)

    def tick(self):
        """Force a native control tick so pushed state is current."""
        self.native._native_front.push_control()

    def assert_parity(self, method, path, headers=None, body=None, label=""):
        a = mask(fetch(self.native.port, method, path, headers, body))
        b = mask(fetch(self.python.port, method, path, headers, body))
        assert a == b, (
            f"byte divergence on {method} {path} {label}\n"
            f"native: {a!r}\npython: {b!r}"
        )
        return a


@pytest.fixture()
def pair(request):
    name = re.sub(r"[^a-z0-9]+", "-", request.node.name.lower())[:48]
    p = Pair(f"inproc://nf-{name}")
    try:
        yield p
    finally:
        p.close()


def _pin_stage(layer, stage: int) -> None:
    """Freeze the admission ladder at ``stage`` on one layer: the control
    law stops moving it (evaluate no-ops) and the stage is set directly,
    exactly like sustained pressure would."""
    adm = layer.admission
    assert adm is not None
    adm.evaluate = lambda *a, **k: adm._stage  # instance attr shadows method
    adm._stage = stage


# -- byte parity: routes, methods, errors ------------------------------------


@needs_native
def test_parity_basic_routes(pair):
    # before any model: snapshots say 503, dynamic routes too (each layer
    # has polled its update stream once: `stream_healthy` is null before)
    for layer in pair.layers():
        assert wait_for(lambda l=layer: l.health.stream_healthy is not None)
    pair.tick()
    for path in ("/ready", "/healthz", "/readyz", "/distinct"):
        pair.assert_parity("GET", path, label="(pre-model)")

    publish_model(pair.broker, {"a": 2, "b": 1})
    for layer in pair.layers():
        assert wait_for(lambda l=layer: is_200(l.port)), "model not applied"
    pair.tick()

    for path in ("/", "/ready", "/healthz", "/readyz", "/distinct"):
        pair.assert_parity("GET", path)
    # query strings survive the forward verbatim
    pair.assert_parity("GET", "/distinct?x=1&y=2")
    pair.assert_parity("GET", "/distinct?x=%20a&x=b")
    # error routes travel the same dispatch core
    pair.assert_parity("GET", "/nope")
    pair.assert_parity("DELETE", "/distinct")
    pair.assert_parity("GET", "/../etc/passwd")
    # mutations forward with bodies intact
    pair.assert_parity("POST", "/add", body=b"hello native\n")
    # HEAD mirrors GET headers, no body
    head = pair.assert_parity("HEAD", "/distinct")
    assert head.endswith(b"\r\n\r\n")
    # content negotiation happens in Python for both fronts
    pair.assert_parity("GET", "/distinct", headers={"Accept": "text/csv"})
    pair.assert_parity(
        "GET", "/distinct", headers={"Accept": "text/csv,application/json"}
    )


@needs_native
def test_parity_gzip_large_body(pair):
    # a model big enough that the rendered JSON crosses the 1 KiB gzip
    # threshold — compression must be byte-identical (mtime=0 both sides)
    publish_model(pair.broker, {f"key-{i:04d}": i for i in range(200)})
    for layer in pair.layers():
        assert wait_for(lambda l=layer: is_200(l.port))
    pair.tick()
    resp = pair.assert_parity(
        "GET", "/distinct", headers={"Accept-Encoding": "gzip"}
    )
    assert b"Content-Encoding: gzip" in resp
    # identity requests skip compression identically
    plain = pair.assert_parity("GET", "/distinct")
    assert b"Content-Encoding" not in plain


# -- byte parity: overload rungs ---------------------------------------------


@needs_native
def test_parity_shed_rung(pair):
    publish_model(pair.broker, {"a": 1})
    for layer in pair.layers():
        assert wait_for(lambda l=layer: is_200(l.port))
    for layer in pair.layers():
        _pin_stage(layer, 3)  # STAGE_SHED
    pair.tick()

    shed = pair.assert_parity("GET", "/distinct", label="(stage=shed)")
    assert shed.startswith(b"HTTP/1.1 429")
    assert b"Retry-After:" in shed
    assert b"X-Oryx-Shed-Stage: shed" in shed
    # mutations shed too
    post = pair.assert_parity("POST", "/add", body=b"x y\n", label="(shed)")
    assert post.startswith(b"HTTP/1.1 429")
    # exempt paths never shed — still answered at full quality
    ready = pair.assert_parity("GET", "/ready", label="(shed-exempt)")
    assert ready.startswith(b"HTTP/1.1 200")
    pair.assert_parity("GET", "/healthz", label="(shed-exempt)")

    # native answered the shed fast-path in C++, not via dispatch
    pair.tick()
    from oryx_tpu.common import metrics

    snap = metrics.registry.snapshot()
    assert snap.get("serving.http.native-answered.shed", {}).get("value", 0) > 0


@needs_native
def test_parity_stale_rung(pair):
    publish_model(pair.broker, {"a": 7, "b": 9})
    for layer in pair.layers():
        assert wait_for(lambda l=layer: is_200(l.port))
    # the example app's JSON models carry no generation id, so stamp one:
    # the champion tracker is what gates both caches (Python AnswerCache
    # lookups and the C++ mirror's generation tag)
    for layer in pair.layers():
        layer.health.live_generation = "gen-A"
    # prime: a full-quality 200 GET populates the answer cache on both
    primed = pair.assert_parity("GET", "/distinct", label="(prime)")
    assert primed.startswith(b"HTTP/1.1 200")
    pair.tick()  # mirrors the cache entry into C++

    for layer in pair.layers():
        _pin_stage(layer, 2)  # STAGE_STALE
    pair.tick()

    stale = pair.assert_parity("GET", "/distinct", label="(stage=stale)")
    assert stale.startswith(b"HTTP/1.1 200")
    assert b"X-Oryx-Shed-Stage: stale" in stale
    # HEAD of a cached answer strips the body identically
    pair.assert_parity("HEAD", "/distinct", label="(stale HEAD)")
    # a miss (different query) falls through to dispatch on both
    pair.assert_parity("GET", "/distinct?other=1", label="(stale miss)")

    # champion swap invalidates both caches — full dispatch again, parity
    for layer in pair.layers():
        layer.health.live_generation = "gen-B"
    pair.tick()
    swapped = pair.assert_parity("GET", "/distinct", label="(post-swap)")
    assert swapped.startswith(b"HTTP/1.1 200")


# -- seeded fuzz with chaos drops --------------------------------------------


@needs_native
def test_parity_fuzz_with_connection_drops(pair):
    import random

    publish_model(pair.broker, {"a": 2, "b": 1, "c": 3})
    for layer in pair.layers():
        assert wait_for(lambda l=layer: is_200(l.port))
    pair.tick()

    rng = random.Random(1234)
    paths = ["/", "/ready", "/distinct", "/nope", "/distinct?q=%d", "/add"]
    accepts = [None, "application/json", "text/csv", "*/*"]
    for i in range(40):
        path = rng.choice(paths)
        if "%d" in path:
            path = path % rng.randrange(100)
        method = "POST" if path == "/add" else rng.choice(["GET", "HEAD"])
        headers = {}
        a = rng.choice(accepts)
        if a:
            headers["Accept"] = a
        if rng.random() < 0.3:
            headers["X-Fuzz"] = f"v{i}"
        body = b"x %d\n" % i if method == "POST" else None
        pair.assert_parity(method, path, headers or None, body, label=f"#{i}")
        if rng.random() < 0.25:
            # chaos drop: half a request then a hard close, on both
            # fronts — the NEXT request must be unaffected
            frag = f"GET /distinct HTTP/1.1\r\nHost: x\r\nX-Part: {i}".encode()
            for layer in pair.layers():
                s = socket.create_connection(("127.0.0.1", layer.port), 5)
                s.sendall(frag)
                s.close()


# -- hardening: the native parser's own attack surface -----------------------


@needs_native
def test_native_rejects_oversized_header():
    p = Pair("inproc://nf-hard-hdr", **{"native.max-header-bytes": "512"})
    try:
        resp = raw(
            p.native.port,
            b"GET / HTTP/1.1\r\nHost: x\r\nX-Big: " + b"a" * 1024 + b"\r\n\r\n",
        )
        assert resp.startswith(b"HTTP/1.1 431"), resp[:64]
    finally:
        p.close()


@needs_native
def test_native_rejects_oversized_body():
    p = Pair("inproc://nf-hard-body", **{"native.max-body-bytes": "1024"})
    try:
        resp = fetch(p.native.port, "POST", "/add", body=b"z" * 4096)
        assert resp.startswith(b"HTTP/1.1 413"), resp[:64]
    finally:
        p.close()


@needs_native
def test_native_rejects_bad_wire(pair):
    port = pair.native.port
    assert raw(port, b"BREW / HTTP/1.1\r\nHost: x\r\n\r\n").startswith(
        b"HTTP/1.1 501"
    )
    assert raw(port, b"GET / HTTP/2.0\r\nHost: x\r\n\r\n").startswith(
        b"HTTP/1.1 505"
    )
    assert raw(port, b"complete garbage\r\n\r\n").startswith(b"HTTP/1.1 400")
    # native wire errors carry the native Server token (documented
    # divergence: these never reach Python, which isn't running the parse)
    resp = raw(port, b"nonsense\r\n\r\n")
    assert b"Server: oryx_tpu\r\n" in resp


@needs_native
def test_native_slowloris_reaped():
    p = Pair("inproc://nf-slowloris", **{"native.idle-timeout-s": "0.5"})
    try:
        s = socket.create_connection(("127.0.0.1", p.native.port), 5)
        s.sendall(b"GET /ready HTTP/1.1\r\nHost: x\r\nX-Slow")  # never finishes
        s.settimeout(5.0)
        t0 = time.monotonic()
        got = s.recv(4096)  # server must reap: EOF or a 408-style close
        elapsed = time.monotonic() - t0
        # either an error response then close, or a silent close — but
        # within bounded time, never a hang
        assert elapsed < 4.0
        if got:
            assert got.startswith(b"HTTP/1.1 408") or not got
        s.close()
        # and the listener still serves new connections afterwards
        assert fetch(p.native.port, path="/healthz").startswith(b"HTTP/1.1 ")
    finally:
        p.close()


@needs_native
def test_native_pipelined_burst_order(pair):
    publish_model(pair.broker, {"a": 1})
    assert wait_for(lambda: is_200(pair.native.port))
    pair.tick()
    reqs = b"".join(
        f"GET /distinct?i={i} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
        for i in range(5)
    ) + b"GET /ready HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n"
    resp = raw(pair.native.port, reqs)
    statuses = re.findall(rb"HTTP/1\.1 (\d{3})", resp)
    assert statuses == [b"200"] * 6, statuses
    # responses come back in request order: the echoed query index is
    # monotonically increasing in the body stream
    order = [int(m) for m in re.findall(rb"\?i=(\d)", reqs)]
    assert order == sorted(order)


@needs_native
def test_native_keepalive_concurrent(pair):
    publish_model(pair.broker, {"a": 1, "b": 2})
    assert wait_for(lambda: is_200(pair.native.port))
    errors = []

    def hammer(n):
        conn = http.client.HTTPConnection("127.0.0.1", pair.native.port, timeout=10)
        try:
            for i in range(20):
                conn.request("GET", "/distinct")
                r = conn.getresponse()
                body = r.read()
                if r.status != 200 or not body:
                    errors.append((n, i, r.status))
        except Exception as e:  # noqa: BLE001
            errors.append((n, "exc", repr(e)))
        finally:
            conn.close()

    threads = [threading.Thread(target=hammer, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors[:5]


@needs_native
def test_native_mid_request_disconnect_is_isolated(pair):
    publish_model(pair.broker, {"a": 1})
    assert wait_for(lambda: is_200(pair.native.port))
    # a client that sends a full request then vanishes before reading
    s = socket.create_connection(("127.0.0.1", pair.native.port), 5)
    s.sendall(b"GET /distinct HTTP/1.1\r\nHost: x\r\n\r\n")
    s.close()
    # the next, well-behaved client is unaffected
    for _ in range(3):
        assert fetch(pair.native.port, path="/distinct").startswith(
            b"HTTP/1.1 200"
        )


# -- the respond stage: no Python lock on the data path, and the close path ----


class _Recorded:
    """Stands in for a front's library: every call through it is noted by
    name, in order, and made on the real one; what each `hf_respond`
    returned is kept."""

    def __init__(self, lib, calls, returned):
        self._lib, self._calls, self._returned = lib, calls, returned

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def call(*args):
            self._calls.append(name)
            got = fn(*args)
            if name == "hf_respond":
                self._returned.append(got)
            return got

        return call


@pytest.fixture()
def recorded(request, monkeypatch):
    """A native layer whose front calls its library through `_Recorded`
    from before its threads start, with as many dispatch threads as the
    case asks for. Yields (layer, front, calls, returned)."""
    from oryx_tpu.serving import native_front as nf

    calls, returned = [], []
    start = nf.NativeFront.start

    def recording_start(self):
        self._lib = _Recorded(self._lib, calls, returned)
        start(self)

    monkeypatch.setattr(nf.NativeFront, "start", recording_start)
    name = re.sub(r"[^a-z0-9]+", "-", request.node.name.lower())[:48]
    layer = ServingLayer(make_config(
        f"inproc://nf-{name}",
        **{"native.enabled": '"true"',
           "native.dispatch-threads": getattr(request, "param", 4)},
    ))
    layer.start()
    try:
        yield layer, layer._native_front, calls, returned
    finally:
        layer.close()


@needs_native
@pytest.mark.parametrize("recorded", [2, 8, 32], indirect=True)
def test_responses_handed_over_together_each_reach_their_own_connection(recorded):
    """As many requests as the front has threads wait at one barrier in
    their handlers, so their `_respond` calls start together: each
    connection reads its own answer, whole (20 KB: more than one send)."""
    layer, front, calls, returned = recorded
    n = len(front._workers)
    barrier = threading.Barrier(n)

    def answer(req):
        barrier.wait(timeout=30)
        return {"tag": req.params["tag"], "fill": req.params["tag"] * 5000}

    layer.router.add("GET", "/together/{tag}", answer)
    conns = [http.client.HTTPConnection("127.0.0.1", layer.port, timeout=30) for _ in range(n)]
    try:
        for i, c in enumerate(conns):
            c.request("GET", f"/together/t{i:02d}x")
        for i, c in enumerate(conns):
            resp = c.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read()) == {"tag": f"t{i:02d}x", "fill": f"t{i:02d}x" * 5000}
    finally:
        for c in conns:
            c.close()
    # a client can hold its whole answer before the thread that sent it is
    # back from the call and has noted its return: give the last one a moment
    deadline = time.monotonic() + 10.0
    while len(returned) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    assert calls.count("hf_respond") == n and returned == [0] * n


@needs_native
def test_a_respond_blocked_inside_the_call_holds_no_other_respond_back(recorded):
    """No Python lock is held across `hf_respond`: while one thread's call
    stands still inside the library, another thread's `_respond` returns."""
    _layer, front, calls, _returned = recorded
    inside, release = threading.Event(), threading.Event()
    real = front._lib.hf_respond

    def hf_respond(handle, conn_id, *rest):
        if conn_id == 4_000_000_001:
            inside.set()
            assert release.wait(30)
        return real(handle, conn_id, *rest)

    front._lib.hf_respond = hf_respond  # an instance attribute: found before __getattr__
    # connections that do not exist: the C++ side drops what it is handed
    stuck = threading.Thread(
        target=front._respond, args=(SimpleNamespace(conn_id=4_000_000_001, req_id=1), b"x")
    )
    stuck.start()
    try:
        assert inside.wait(10)
        other = threading.Thread(
            target=front._respond, args=(SimpleNamespace(conn_id=4_000_000_002, req_id=1), b"y")
        )
        other.start()
        other.join(timeout=10)
        assert not other.is_alive(), "a second _respond waited for the first one's call"
        assert stuck.is_alive() and calls.count("hf_respond") == 1
    finally:
        release.set()
        stuck.join(timeout=10)
    assert not stuck.is_alive() and calls.count("hf_respond") == 2


@needs_native
@pytest.mark.parametrize("when", ["before", "during", "after"])
def test_close_orders_the_library_s_calls_around_in_flight_responds(recorded, when):
    """`close()` before / during / after a request's respond: hf_shutdown,
    then whatever responds were still in their handlers (the live handle
    answers -1 and they are dropped), then hf_close, after which no
    hf_respond is made; nothing raises; a second close is no close."""
    layer, front, calls, returned = recorded
    entered, release = threading.Event(), threading.Event()

    def slow(req):
        entered.set()
        assert release.wait(30)
        return {"n": 1}

    layer.router.add("GET", "/slow", slow)
    errors = []
    respond = front._respond

    def checked_respond(rec, data):
        try:
            respond(rec, data)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)
            raise

    front._respond = checked_respond
    conn = http.client.HTTPConnection("127.0.0.1", layer.port, timeout=30)
    conn.connect()
    closer = threading.Thread(target=front.close)
    try:
        if when != "before":
            conn.request("GET", "/slow")
            assert entered.wait(10)
        if when == "after":
            release.set()
            assert conn.getresponse().read() == b'{"n": 1}'
        closer.start()
        if when == "during":
            # close() stands in the workers' join until the handler returns
            assert wait_for(lambda: "hf_shutdown" in calls)
            closer.join(timeout=0.3)
            assert closer.is_alive() and "hf_close" not in calls
            release.set()
        closer.join(timeout=30)
        assert not closer.is_alive()
    finally:
        release.set()
        conn.close()
    # a respond that starts once the front is closed (no serving thread can)
    front._respond(SimpleNamespace(conn_id=1, req_id=1), b"late")
    front.close()
    layer.close()
    assert not errors
    order = [c for c in calls if c in ("hf_respond", "hf_shutdown", "hf_close")]
    responds = 0 if when == "before" else 1
    if when == "after":
        assert order == ["hf_respond", "hf_shutdown", "hf_close"] and returned == [0]
    else:
        assert order == ["hf_shutdown"] + ["hf_respond"] * responds + ["hf_close"]
        assert returned == [-1] * responds  # started after hf_shutdown: dropped by the live handle


# -- the pull path: serving threads take their requests themselves ------------


def _taken() -> float:
    return metrics.registry.snapshot()["serving.front.taken"]["value"]


@needs_native
@pytest.mark.parametrize("recorded", [1, 4, 16], indirect=True)
def test_requests_sent_together_are_each_served_once_by_a_thread_that_took_it(recorded, monkeypatch):
    """N requests on N connections at once (more than the front has
    threads, and fewer): every one is answered exactly once, by a
    `NativeServe` thread that took it from the C++ front itself, and
    `serving.front.taken` counts each (a thread brings it up to date with
    `serving.handler.requests` when it accounts its CPU after a staged
    request: here after every one)."""
    from oryx_tpu.serving import stages

    monkeypatch.setattr(stages, "CPU_EVERY_S", 0.0)
    monkeypatch.setattr(stages, "SAMPLE_EVERY", 1)
    layer, front, calls, _returned = recorded
    n = 12
    served = []

    def who(req):
        served.append((req.params["tag"], threading.current_thread()))
        return {"tag": req.params["tag"]}

    layer.router.add("GET", "/who/{tag}", who)
    assert [t.name for t in front._workers] == [f"NativeServe_{i}" for i in range(len(front._workers))]
    before = _taken()
    conns = [http.client.HTTPConnection("127.0.0.1", layer.port, timeout=30) for _ in range(n)]
    try:
        for c in conns:
            c.connect()
        for i, c in enumerate(conns):
            c.request("GET", f"/who/t{i:02d}")
        for i, c in enumerate(conns):
            resp = c.getresponse()
            assert resp.status == 200 and json.loads(resp.read()) == {"tag": f"t{i:02d}"}
    finally:
        for c in conns:
            c.close()
    assert sorted(tag for tag, _ in served) == [f"t{i:02d}" for i in range(n)]
    assert all(thread in front._workers for _, thread in served)
    assert wait_for(lambda: _taken() - before == n)  # counted once the answer has left
    # a take a request and one more a thread, which stands in it now
    assert wait_for(lambda: calls.count("hf_take") == n + len(front._workers))
    assert not any(t.name == "NativePoll" for t in threading.enumerate())


@needs_native
@pytest.mark.parametrize("recorded", [4, 16], indirect=True)
def test_small_requests_behind_a_large_one_are_each_served_once(recorded):
    """A body of 1 MB heads the queue while several threads wait, and
    twelve small requests follow it at once on connections of their own:
    whichever threads meet the large one come back with room, ONE of them
    serves it whole, and every small request is answered once, none lost
    behind it and none twice."""
    from oryx_tpu.serving import native_front as nf

    layer, front, calls, _returned = recorded
    served = []

    def echo(req):
        served.append(req.params["tag"])
        return {"tag": req.params["tag"], "bytes": len(req.body)}

    layer.router.add("POST", "/echo/{tag}", echo)
    assert wait_for(lambda: calls.count("hf_take") == len(front._workers))
    size, n = 1_000_000, 12
    assert size > nf._TAKE_BYTES
    conns = [http.client.HTTPConnection("127.0.0.1", layer.port, timeout=30) for _ in range(n + 1)]
    try:
        for c in conns:
            c.connect()
        conns[0].request("POST", "/echo/large", body=b"x" * size)
        for i, c in enumerate(conns[1:]):
            c.request("POST", f"/echo/s{i:02d}", body=b"small")
        resp = conns[0].getresponse()
        assert resp.status == 200 and json.loads(resp.read()) == {"tag": "large", "bytes": size}
        for i, c in enumerate(conns[1:]):
            resp = c.getresponse()
            assert resp.status == 200 and json.loads(resp.read()) == {"tag": f"s{i:02d}", "bytes": 5}
    finally:
        for c in conns:
            c.close()
    assert sorted(served) == ["large"] + [f"s{i:02d}" for i in range(n)]


@needs_native
@pytest.mark.parametrize("recorded", [3, 32], indirect=True)
def test_threads_racing_for_the_queue_answer_every_request_once(recorded):
    """More client threads than cores on keep-alive connections, fewer and
    more serving threads than clients, the interpreter switching every
    10 us: each of the 600 requests is answered once, with its own tag, by
    the one thread that took it (a lost or doubled take would show as a
    missing or a repeated tag, or as a take too many)."""
    layer, front, calls, _returned = recorded
    clients, each = 12, 50
    served, errors = [], []
    layer.router.add("GET", "/tag/{tag}", lambda req: served.append(req.params["tag"]) or {"tag": req.params["tag"]})

    def client(c):
        conn = http.client.HTTPConnection("127.0.0.1", layer.port, timeout=30)
        try:
            for i in range(each):
                conn.request("GET", f"/tag/c{c}r{i}")
                resp = conn.getresponse()
                if resp.status != 200 or json.loads(resp.read()) != {"tag": f"c{c}r{i}"}:
                    errors.append((c, i, resp.status))
        except Exception as e:  # noqa: BLE001
            errors.append((c, "exc", repr(e)))
        finally:
            conn.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(c,)) for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:5]
    assert sorted(served) == sorted(f"c{c}r{i}" for c in range(clients) for i in range(each))
    assert wait_for(lambda: calls.count("hf_take") == clients * each + len(front._workers))


@needs_native
@pytest.mark.parametrize("recorded", [1, 8], indirect=True)
def test_an_idle_front_s_serving_threads_make_no_call_and_run_no_python(recorded):
    """Every serving thread stands in ONE `hf_take` for as long as nothing
    is pending: none returns to the interpreter to tick (the poll it
    replaces came back four times a second), and a request after the
    second of silence is served by a thread that was blocked all along."""
    layer, front, calls, _returned = recorded
    assert metrics.registry.snapshot()["serving.front.workers"]["value"] == len(front._workers)
    assert wait_for(lambda: calls.count("hf_take") == len(front._workers))
    time.sleep(1.0)
    assert calls.count("hf_take") == len(front._workers)
    assert all(t.is_alive() for t in front._workers)
    assert fetch(layer.port, path="/nope").startswith(b"HTTP/1.1 404")
    assert wait_for(lambda: calls.count("hf_take") == len(front._workers) + 1)


@needs_native
@pytest.mark.parametrize("recorded", [2, 8], indirect=True)
def test_two_requests_on_one_connection_are_answered_in_the_order_they_were_sent(recorded):
    """Two requests pipelined on one keep-alive connection are taken by
    two threads; the first's handler returns only once the second's
    answer has been handed over: the connection reads the first's answer,
    then the second's, each whole."""
    layer, front, calls, _returned = recorded
    handed, second_out = [], threading.Event()
    respond = front._respond

    def noting_respond(rec, data):
        respond(rec, data)
        if rec.target.endswith("/second"):
            second_out.set()

    front._respond = noting_respond

    def answer(req):
        if req.params["tag"] == "first":
            assert second_out.wait(30)
        handed.append(req.params["tag"])
        return {"tag": req.params["tag"]}

    layer.router.add("GET", "/order/{tag}", answer)
    both = b"".join(
        f"GET /order/{tag} HTTP/1.1\r\nHost: x\r\n{extra}\r\n".encode()
        for tag, extra in (("first", ""), ("second", "Connection: close\r\n"))
    )
    resp = raw(layer.port, both)
    assert handed == ["second", "first"]  # two threads: the second did not wait for the first
    assert re.findall(rb'\{"tag": "(\w+)"\}', resp) == [b"first", b"second"]
    assert resp.count(b"HTTP/1.1 200") == 2 and calls.count("hf_respond") == 2


@needs_native
@pytest.mark.parametrize("recorded", [2, 64], indirect=True)
def test_requests_one_after_the_other_are_served_by_the_thread_that_served_the_last(recorded):
    """Forty requests, each sent once the one before is answered: the
    thread that answered is the last to have come back to its take, and
    the front wakes that one. However many threads stand, one or two
    carry a load of one in flight (four are allowed here: a loaded machine
    can hold a thread between its answer and its take; woken in turn, 40
    of 64 would serve), so each meets its every eighth request, the one
    `serving/stages.py` stages, soon."""
    layer, front, _calls, _returned = recorded
    served = []
    layer.router.add("GET", "/who", lambda req: served.append(threading.current_thread()) or {"n": 1})
    conn = http.client.HTTPConnection("127.0.0.1", layer.port, timeout=30)
    try:
        for _ in range(40):
            conn.request("GET", "/who")
            assert conn.getresponse().read() == b'{"n": 1}'
            # the answer can reach the client before its thread is back in its take
            time.sleep(0.005)
    finally:
        conn.close()
    assert len(served) == 40 and set(served) <= set(front._workers)
    assert len(set(served)) <= 4, sorted(t.name for t in set(served))


@needs_native
@pytest.mark.parametrize("recorded", [1, 64], indirect=True)
def test_close_with_every_thread_blocked_in_its_take_joins_them_all(recorded):
    """`hf_shutdown` answers every blocked `hf_take` with -1: `close()`
    returns well inside a join's patience, no thread of the front is left
    alive, the gauge says so, and the library is closed after the last."""
    _layer, front, calls, _returned = recorded
    threads, n = front.threads(), len(front._workers)
    assert len(threads) == n + 1 and all(t.is_alive() for t in threads)
    assert wait_for(lambda: calls.count("hf_take") == n)
    t0 = time.monotonic()
    front.close()
    assert time.monotonic() - t0 < 5.0
    assert not any(t.is_alive() for t in threads)
    assert metrics.registry.snapshot()["serving.front.workers"]["value"] == 0
    assert calls.index("hf_shutdown") < calls.index("hf_close") == len(calls) - 1
    assert calls.count("hf_take") == n  # none came back for more


@needs_native
@pytest.mark.parametrize("where", ["handler", "respond"])
@pytest.mark.parametrize("recorded", [1], indirect=True)
def test_a_request_that_raises_leaves_its_thread_serving(recorded, where):
    """The front's ONE serving thread meets a handler that raises (answered
    500 by `_serve_one`) or a failure past it (the respond itself: nothing
    can be answered, the exception is logged in the thread's loop): the
    same thread takes and answers the requests that follow."""
    layer, front, calls, _returned = recorded
    (worker,) = front._workers
    served = []

    def boom(req):
        served.append(threading.current_thread())
        raise RuntimeError("boom")

    layer.router.add("GET", "/boom", boom)
    layer.router.add("GET", "/fine", lambda req: served.append(threading.current_thread()) or {"n": 1})
    if where == "respond":
        respond = front._respond

        def failing_respond(rec, data):
            if rec.target == "/boom":
                raise OSError("no way out")
            respond(rec, data)

        front._respond = failing_respond
    for _ in range(3):
        if where == "handler":
            assert fetch(layer.port, path="/boom").startswith(b"HTTP/1.1 500")
        else:
            assert raw(layer.port, request_bytes("GET", "/boom"), timeout=0.5) == b""
        assert fetch(layer.port, path="/fine").endswith(b'{"n": 1}')
    assert served == [worker] * 6 and worker.is_alive()
    assert wait_for(lambda: calls.count("hf_take") == 7)


@needs_native
@pytest.mark.parametrize("size", [20_000, 300_000, 1_000_000])
def test_a_request_larger_than_a_thread_s_buffer_is_served_whole(pair, size):
    """A body of more than `_TAKE_BYTES` (up to the default
    max-body-bytes): the thread that meets it at the head of the queue
    comes back with room, both fronts answer the same bytes, and the
    thread serves small requests from its own buffer again."""
    from oryx_tpu.serving import native_front as nf

    assert size > nf._TAKE_BYTES
    publish_model(pair.broker, {"a": 1})
    for layer in pair.layers():
        assert wait_for(lambda l=layer: is_200(l.port))
    seen = []

    def echo(req):
        seen.append(req.body)
        return {"bytes": len(req.body), "tail": req.body[-8:].decode("latin-1")}

    for layer in pair.layers():
        layer.router.add("POST", "/echo", echo)
    body = (b"%07d " % size) * (size // 8)
    got = pair.assert_parity("POST", "/echo", body=body, label=f"({size} bytes)")
    assert got.startswith(b"HTTP/1.1 200") and seen == [body, body]
    pair.assert_parity("GET", "/distinct", label="(after the large one)")
    pair.assert_parity("POST", "/echo", body=b"small", label="(after the large one)")


# -- hf_take itself: the library's queue with no Python front on it -----------


class _Bare:
    """A front of the library alone (`hf_create`, no `NativeFront`): the
    test's own threads stand in `hf_take`, as serving threads do. `close`
    shuts the front down, joins every taker and only then frees it."""

    def __init__(self):
        self.lib = native.get_library()
        self.handle = self.lib.hf_create(0, 128, 0, 0, 0.0, 0)
        assert self.handle
        self.port = self.lib.hf_port(self.handle)
        self.threads: list[threading.Thread] = []
        self._forwarded = 0

    def take(self, cap=16384):
        """One `hf_take` with a buffer of `cap` bytes: what it returned
        and, where that is a frame, (seqno, the record)."""
        buf = (ctypes.c_uint8 * max(cap, 1))()
        n = self.lib.hf_take(self.handle, buf, cap)
        if n < 0:
            return n, None
        frame = blockcodec.decode_frame(ctypes.string_at(buf, n))  # checks the CRC
        assert frame.kind == blockcodec.KIND_HTTP and frame.count == 1
        assert n == blockcodec.HEADER_BYTES + frame.length
        (rec,) = blockcodec.decode_http_records(frame.payload, 1)
        return n, (frame.seqno, rec)

    def taker(self, fn):
        t = threading.Thread(target=fn, daemon=True)
        self.threads.append(t)
        t.start()
        return t

    def respond(self, rec, body: bytes) -> int:
        data = b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n\r\n%s" % (len(body), body)
        buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
        return self.lib.hf_respond(self.handle, rec.conn_id, rec.req_id, buf, len(data), 0)

    def forwarded(self) -> int:
        """Requests the parser has put in the queue so far (a read of
        `hf_stats` takes what it reports)."""
        from oryx_tpu.serving.native_front import _SCALARS

        out = (ctypes.c_uint64 * 64)()
        assert self.lib.hf_stats(self.handle, out, 64, 0) > 0
        self._forwarded += out[_SCALARS.index("forwarded")]
        return self._forwarded

    def close(self):
        self.lib.hf_shutdown(self.handle)
        for t in self.threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in self.threads)
        self.lib.hf_close(self.handle)


@pytest.fixture()
def bare():
    b = _Bare()
    try:
        yield b
    finally:
        b.close()


def fetch_nowait(port, path) -> bool:
    """Send one request and leave without reading (the front has parsed
    and queued it by the time the taker's frame is checked)."""
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
        time.sleep(0.05)
    return True


def _pipelined(n, path="/r") -> bytes:
    return b"".join(f"GET {path}{i} HTTP/1.1\r\nHost: x\r\n\r\n".encode() for i in range(n))


@needs_native
@pytest.mark.parametrize("takers", [1, 4])
def test_hf_take_hands_out_requests_oldest_first_across_takers(bare, takers):
    """Twelve requests parsed in a known order (one connection,
    pipelined), taken by one thread or by four racing for them: each is
    handed out once, a frame of ONE record, and the frames' sequence
    numbers, given under the queue's lock, follow the order of arrival."""
    n = 12
    got, lock = [], threading.Lock()

    def loop():
        while True:
            size, frame = bare.take()
            if size == -1:
                return
            with lock:
                got.append(frame)

    with socket.create_connection(("127.0.0.1", bare.port), timeout=5) as s:
        s.sendall(_pipelined(n))
        assert wait_for(lambda: bare.forwarded() == n)
        for _ in range(takers):
            bare.taker(loop)
        assert wait_for(lambda: len(got) == n)
        time.sleep(0.2)
        assert len(got) == n  # none handed out twice
    got.sort(key=lambda frame: frame[0])
    assert [seq for seq, _ in got] == list(range(n))
    assert [rec.target for _, rec in got] == [f"/r{i}" for i in range(n)]
    assert [rec.req_id for _, rec in got] == sorted(rec.req_id for _, rec in got)
    assert all(rec.method == "GET" and rec.t_parsed > 0.0 for _, rec in got)


@needs_native
@pytest.mark.parametrize("takers", [2, 8, 64])
def test_hf_take_wakes_one_blocked_taker_a_request_and_shutdown_wakes_the_rest(bare, takers):
    """N threads blocked in `hf_take` with no timeout, and ONE request:
    one of them returns with it and the others stay blocked (a second
    request wakes one more); `hf_shutdown` then releases every one that is
    left with -1, and a take after it returns -1 at once."""
    returned, lock = [], threading.Lock()

    def once():
        size, frame = bare.take()
        with lock:
            returned.append((size, frame))

    threads = [bare.taker(once) for _ in range(takers)]
    time.sleep(0.3)
    assert returned == [] and all(t.is_alive() for t in threads)
    for k in (1, 2):
        assert fetch_nowait(bare.port, f"/one{k}")
        assert wait_for(lambda: len(returned) == k)
        time.sleep(0.3)
        assert len(returned) == k and sum(t.is_alive() for t in threads) == takers - k
    assert [frame[1].target for _, frame in returned] == ["/one1", "/one2"]
    bare.lib.hf_shutdown(bare.handle)
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    assert [size for size, _ in returned[2:]] == [-1] * (takers - 2)
    assert bare.take() == (-1, None)


@needs_native
def test_hf_take_wakes_the_taker_that_came_to_wait_last(bare):
    """Takers come to wait one after the other, half a second apart; a
    request wakes the LAST of them, a taker that comes after that goes on
    top, and the first is woken only when no other waits: at a load one
    thread can carry, one thread carries it, however many stand."""
    returned, lock = [], threading.Lock()

    def once(name):
        size, frame = bare.take()
        with lock:
            returned.append((name, frame[1].target if frame else size))

    def park(name):
        bare.taker(lambda: once(name))
        time.sleep(0.5)  # it stands in its take before the next comes

    park("first")
    park("second")
    assert fetch_nowait(bare.port, "/a") and wait_for(lambda: len(returned) == 1)
    park("third")
    assert fetch_nowait(bare.port, "/b") and wait_for(lambda: len(returned) == 2)
    assert fetch_nowait(bare.port, "/c") and wait_for(lambda: len(returned) == 3)
    assert returned == [("second", "/a"), ("third", "/b"), ("first", "/c")]


@needs_native
@pytest.mark.parametrize("cap", [0, 31, 64, 4096])
def test_hf_take_leaves_a_record_larger_than_the_buffer_at_the_head(bare, cap):
    """A request of 5 KB heads the queue and a small one follows it. A
    take with less room than the large one's frame returns minus the bytes
    it needs, writes nothing, and hands out NEITHER (the small one does
    not overtake); the retry with that room gets the large one whole, and
    the small one comes after it."""
    big = b"b" * 5000
    with socket.create_connection(("127.0.0.1", bare.port), timeout=5) as s:
        s.sendall(b"POST /big HTTP/1.1\r\nHost: x\r\nContent-Length: 5000\r\n\r\n" + big
                  + b"GET /small HTTP/1.1\r\nHost: x\r\n\r\n")
        assert wait_for(lambda: bare.forwarded() == 2)
        for _ in range(2):  # the refusal repeats for as long as the room is short
            size, frame = bare.take(cap)
            assert frame is None and size < -5000
        need = -size
        assert bare.take(need - 1)[0] == -need
        size, (seq, rec) = bare.take(need)
        assert size == need and seq == 0
        assert (rec.method, rec.target, rec.body) == ("POST", "/big", big)
        size, (seq, rec) = bare.take()
        assert seq == 1 and (rec.method, rec.target, rec.body) == ("GET", "/small", b"")


@needs_native
def test_hf_take_hands_out_nothing_that_was_queued_at_shutdown(bare):
    """A request parsed and not yet taken when the front shuts down: its
    connection is closed with nothing written, and a take returns -1, not
    the request (its answer could reach no one)."""
    with socket.create_connection(("127.0.0.1", bare.port), timeout=5) as s:
        s.sendall(b"GET /late HTTP/1.1\r\nHost: x\r\n\r\n")
        assert wait_for(lambda: bare.forwarded() == 1)
        bare.lib.hf_shutdown(bare.handle)
        assert bare.take() == (-1, None)
        s.settimeout(5.0)
        assert s.recv(4096) == b""


@needs_native
@pytest.mark.parametrize("takers", [2, 16])
def test_hf_take_respond_and_shutdown_race(bare, takers):
    """Takers answering what they take while clients keep sending, the
    interpreter switching every 10 us, and `hf_shutdown` in the middle of
    it: every answer a client read whole is its own request's, every taker
    leaves with -1, a respond that lost the race returns -1 on the live
    handle, and the front is freed only after the last of them (the
    sanitizer's build runs this: tests/native/test_sanitizer.py)."""
    answered, late, errors = [], [], []

    def serve():
        while True:
            size, frame = bare.take()
            if size == -1:
                return
            rec = frame[1]
            (answered if bare.respond(rec, rec.target.encode()) == 0 else late).append(rec.target)

    def client(c):
        conn = http.client.HTTPConnection("127.0.0.1", bare.port, timeout=10)
        try:
            for i in range(10_000):
                conn.request("GET", f"/c{c}r{i}")
                body = conn.getresponse().read()
                if body != f"/c{c}r{i}".encode():
                    errors.append((c, i, body))
        except (OSError, http.client.HTTPException):
            pass  # the front went away under it: what the test is about
        finally:
            conn.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _ in range(takers):
            bare.taker(serve)
        clients = [threading.Thread(target=client, args=(c,)) for c in range(8)]
        for t in clients:
            t.start()
        assert wait_for(lambda: len(answered) >= 200)
        bare.lib.hf_shutdown(bare.handle)
        for t in clients + bare.threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in clients + bare.threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:5]
    assert len(set(answered)) == len(answered) and not set(answered) & set(late)
    assert bare.take() == (-1, None)


# -- fallback: bit-compatible when the native path is unavailable ------------


def test_fallback_enabled_false_serves_identically():
    broker_loc = "inproc://nf-fallback-off"
    broker = bus.get_broker(broker_loc)
    layer = ServingLayer(make_config(broker_loc, **{"native.enabled": '"false"'}))
    layer.start()
    try:
        assert layer._native_front is None
        publish_model(broker, {"a": 5})
        assert wait_for(lambda: is_200(layer.port))
        resp = fetch(layer.port, path="/distinct")
        assert resp.startswith(b"HTTP/1.1 200")
        assert json.loads(resp.split(b"\r\n\r\n", 1)[1]) == {"a": 5}
    finally:
        layer.close()


def test_fallback_auto_without_toolchain(monkeypatch):
    monkeypatch.setattr(native, "get_library", lambda *a, **k: None)
    broker_loc = "inproc://nf-fallback-auto"
    broker = bus.get_broker(broker_loc)
    layer = ServingLayer(make_config(broker_loc))  # enabled = "auto"
    layer.start()
    try:
        assert layer._native_front is None  # silent, bit-compatible fallback
        publish_model(broker, {"k": 1})
        assert wait_for(lambda: is_200(layer.port))
        assert fetch(layer.port, path="/distinct").startswith(b"HTTP/1.1 200")
    finally:
        layer.close()


def test_forced_true_without_toolchain_falls_back(monkeypatch, caplog):
    monkeypatch.setattr(native, "get_library", lambda *a, **k: None)
    layer = ServingLayer(
        make_config("inproc://nf-forced", **{"native.enabled": '"true"'})
    )
    with caplog.at_level("WARNING"):
        layer.start()
    try:
        assert layer._native_front is None
        assert any("falling back" in r.message for r in caplog.records)
    finally:
        layer.close()


@needs_native
def test_native_declines_with_auth():
    layer = ServingLayer(
        make_config(
            "inproc://nf-auth-decline",
            **{
                "native.enabled": '"true"',
                "api.user-name": '"u"',
                "api.password": '"p"',
                "api.allow-insecure-auth": "true",
            },
        )
    )
    layer.start()
    try:
        # auth would be bypassed by native snapshot answers — must decline
        assert layer._native_front is None
        resp = fetch(layer.port, path="/ready")
        assert resp.startswith(b"HTTP/1.1 401")
    finally:
        layer.close()


# -- tenants: parity through the multi-tenant mux ----------------------------


@needs_native
@pytest.mark.fleet
def test_parity_tenants(tmp_path):
    from fleet import FleetHarness

    tenants = {
        "acme": {"weight": 2.0, "slo_p99_ms": 500.0},
        "bob": {"weight": 1.0, "slo_p99_ms": 500.0},
    }
    fn = FleetHarness(
        1,
        str(tmp_path / "native"),
        bus_name="nf-ten-native",
        overlay='oryx.serving.native.enabled = "true"',
        tenants=tenants,
    )
    fp = FleetHarness(
        1,
        str(tmp_path / "python"),
        bus_name="nf-ten-python",
        overlay='oryx.serving.native.enabled = "false"',
        tenants=tenants,
    )
    with fn, fp:
        assert fn.replicas[0]._native_front is not None
        assert fp.replicas[0]._native_front is None
        for fleet in (fn, fp):
            want = {
                tid: fleet.publish_tenant(tid, metric=0.9) for tid in tenants
            }
            assert fleet.wait_tenants_converged(want, timeout=20.0)
        np_, pp = fn.replicas[0].port, fp.replicas[0].port
        fn.replicas[0]._native_front.push_control()

        def parity(method, path, headers=None):
            a = mask(fetch(np_, method, path, headers))
            b = mask(fetch(pp, method, path, headers))
            assert a == b, f"tenant divergence on {method} {path}\n{a!r}\n{b!r}"
            return a

        # path-scoped, header-scoped, and default-tenant forms
        r = parity("GET", "/t/acme/probe/recommend/u1")
        assert r.startswith(b"HTTP/1.1 200")
        parity("GET", "/probe/recommend/u1", {"X-Oryx-Tenant": "bob"})
        parity("GET", "/probe/recommend/u7")  # default tenant
        parity("GET", "/t/nope/probe/recommend/u1")  # unknown tenant
        parity("GET", "/t/acme/nope")
        # tenant-scoped health snapshot stays identical too
        parity("GET", "/t/acme/ready")


# -- fleet acceptance: native front under rolling restart --------------------


@needs_native
@pytest.mark.fleet
def test_native_fleet_rolling_restart_zero_downtime(tmp_path):
    from fleet import FleetHarness

    from oryx_tpu.loadgen import (
        Action,
        OpenLoopEngine,
        PoissonProcess,
        PowerLawUsers,
        ScenarioRunner,
    )

    with FleetHarness(
        2,
        str(tmp_path),
        bus_name="nf-fleet-restart",
        overlay='oryx.serving.native.enabled = "true"',
    ) as fleet:
        for replica in fleet.replicas:
            assert replica._native_front is not None
        gen = fleet.publish(metric=0.90)
        assert fleet.wait_converged(gen, timeout=15.0)

        engine = OpenLoopEngine(
            fleet.targets, template="/probe/recommend/u%d", readiness_poll_s=0.1
        )
        runner = ScenarioRunner(
            [
                Action(0.8, "restart", {"replica": 0, "drain_s": 5.0}),
                Action(2.4, "restart", {"replica": 1, "drain_s": 5.0}),
            ],
            fleet.handlers(),
        )
        runner.start()
        result = engine.run(
            PoissonProcess(rate=40.0, seed=5), PowerLawUsers(10_000, seed=5), 5.0
        )
        runner.join(timeout=15.0)

        assert not runner.errors, runner.errors
        assert result.failed == 0, dict(result.error_kinds)
        assert result.ok == result.offered > 0
        # the restarted replicas came back with native fronts too
        for replica in fleet.replicas:
            assert replica._native_front is not None
        assert fleet.wait_converged(gen, timeout=10.0)
