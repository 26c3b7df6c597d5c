"""The Python HTTP front: a pooled stdlib server (the reference's
Tomcat; serving/layer.py has the divergences) whose handler parses a
request off its socket, hands it to the request core
(serving/request.py `answer`) and writes what comes back. The only
front under TLS, under Basic auth and on a host without a C++ toolchain,
where `native_front.maybe_start` declines (docs/serving-native.md).
"""

from __future__ import annotations

import logging
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, HTTPServer

from oryx_tpu.serving.request import answer
from oryx_tpu.serving.web import ServingContext

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


def _make_handler(layer, ctx: ServingContext):
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
                status, message, ct, fields, body = answer(
                    layer, ctx, method, self.path, self.headers, self._read_body, t0
                )
                if message is not None:
                    self._send_error(status, message)
                    return
                self.send_response(status)
                self.send_header("Content-Type", ct)
                self.send_header("Content-Length", str(len(body)))
                for k, v in fields.items():
                    self.send_header(k, v)
                self.end_headers()
                if method != "HEAD":
                    self.wfile.write(body)
            finally:
                layer.stages.responded()
                layer._request_ended()

        def _read_body(self) -> bytes:
            length = int(self.headers.get("Content-Length") or 0)
            return self.rfile.read(length) if length else b""

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
