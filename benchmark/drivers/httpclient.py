"""What the HTTP drivers share: a keep-alive connection per worker thread,
one judged request, and the child-process protocol. Standard library and
NumPy only: the load generator is a process of its own that never imports
JAX or the program (copy of the sound parts of oryx_tpu/loadgen: latency
from the time a request was due, lateness of the generator known)."""

from __future__ import annotations

import http.client
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np

SHED_HEADER = "X-Oryx-Shed-Stage"  # oryx_tpu.serving.overload.SHED_HEADER


def power_law_users(rng: np.random.Generator, n_users: int, exponent: float, count: int):
    """`count` user rows in [0, n_users), density ~ (i+1)^-exponent by the
    inverse CDF of the continuous law (oryx_tpu/loadgen/skew.py)."""
    u = rng.random(count)
    if abs(exponent - 1.0) < 1e-9:
        x = np.power(float(n_users + 1), u)
    else:
        top = float(n_users + 1) ** (1.0 - exponent)
        x = np.power(1.0 + u * (top - 1.0), 1.0 / (1.0 - exponent))
    return np.minimum(x.astype(np.int64) - 1, n_users - 1)


class Connection:
    """One persistent connection; reconnects once if a kept-alive socket
    turns out to be dead, never after a timeout."""

    def __init__(self, host: str, port: int, timeout_s: float) -> None:
        self._args = (host, port, timeout_s)
        self._conn: http.client.HTTPConnection | None = None

    def connect(self) -> None:
        host, port, timeout_s = self._args
        self._conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
        self._conn.connect()

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def get(self, path: str):
        """(status, shed stage or None, body bytes); raises on transport
        failure."""
        for attempt in (0, 1):
            if self._conn is None:
                self.connect()
            try:
                self._conn.request("GET", path, headers={"Accept": "application/json"})
                resp = self._conn.getresponse()
                body = resp.read()
            except (http.client.HTTPException, OSError) as e:
                self.close()
                # a kept-alive socket the server has closed earns one new
                # connection; a timeout is latency and is never retried
                if attempt == 0 and not isinstance(e, TimeoutError):
                    continue
                raise
            if resp.will_close:
                self.close()
            return resp.status, resp.getheader(SHED_HEADER), body
        raise AssertionError("unreachable")


def judged_get(conn: Connection, path: str, how_many: int):
    """One request, judged as the window judges it: ok only if the answer
    is 200, carries no shed stage (full quality) and holds `how_many`
    items. Returns (ok, kind, body)."""
    try:
        status, shed, body = conn.get(path)
    except OSError as e:
        return False, type(e).__name__, b""
    except http.client.HTTPException as e:
        return False, type(e).__name__, b""
    if status != 200:
        return False, f"http-{status}", body
    if shed is not None:
        return False, f"shed-{shed}", body
    try:
        n = len(json.loads(body))
    except ValueError:
        return False, "bad-json", body
    if n != how_many:
        return False, f"items-{n}", body
    return True, "ok", body


def parse_answer(body: bytes | str):
    """[(item id, score)] of a JSON /recommend answer."""
    return [(d["id"], float(d["value"])) for d in json.loads(body)]


class Clock:
    """Seconds since the agreed start `t0_unix`, on the monotonic clock."""

    def __init__(self, t0_unix: float) -> None:
        self._pc0 = time.perf_counter() + (t0_unix - time.time())

    def now(self) -> float:
        return time.perf_counter() - self._pc0

    def sleep_until(self, t: float) -> None:
        d = t - self.now()
        if d > 0:
            time.sleep(d)


class PauseWatch:
    """A thread that sleeps a few milliseconds over and over between two
    times of a Clock and remembers by how much it overslept most: for that
    long its process, or the whole machine, did not run it. The harness
    keeps one beside the server and the load generator one of its own; a
    pause that both saw at the same time is the machine's, not the
    program's (PR 23: 3 of 9 runs in one call had such a stall, tenths of
    a second to 5 s, and none of 39 in the three calls before)."""

    def __init__(self, clock: Clock, start_s: float, end_s: float, period_s: float = 0.005):
        self._clock, self._span, self._period = clock, (start_s, end_s), period_s
        self.max_ms, self.at_s, self.over_20ms = 0.0, 0.0, 0
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        start_s, end_s = self._span
        self._clock.sleep_until(start_s)
        last = self._clock.now()
        while last < end_s:
            time.sleep(self._period)
            now = self._clock.now()
            over_ms = (now - last - self._period) * 1000.0
            if over_ms > self.max_ms:
                self.max_ms, self.at_s = over_ms, last - start_s
            self.over_20ms += over_ms > 20.0
            last = now

    def reading(self) -> dict:
        """Waits for the end of the span."""
        self._thread.join()
        return {"max_ms": self.max_ms, "at_s": self.at_s, "over_20ms": int(self.over_20ms)}


def start_child(driver: str, params: dict, cwd: Path) -> subprocess.Popen:
    """The load generator as a child process of its own (module
    `benchmark.drivers.<driver>`); parameters go in on stdin, the records
    come back on stdout as one JSON line."""
    child = subprocess.Popen(
        [sys.executable, "-m", f"benchmark.drivers.{driver}"],
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        cwd=str(cwd),
    )
    child.stdin.write(json.dumps(params).encode())
    child.stdin.close()
    child.stdin = None  # communicate() must not touch the closed pipe
    return child


def finish_child(child: subprocess.Popen, timeout_s: float) -> dict:
    try:
        out, _ = child.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        child.kill()
        child.communicate()
        raise RuntimeError("load generator did not finish in time") from None
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited with {child.returncode}")
    return json.loads(out.decode().strip().splitlines()[-1])


def child_main(run) -> None:
    params = json.loads(sys.stdin.read())
    warm = params["warm_seconds"]
    watch = PauseWatch(Clock(params["t0_unix"]), warm, warm + params["seconds"])
    result = run(params)
    result["pause"] = watch.reading()
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
