"""Driver `open_http`: open-loop HTTP load at a fixed rate.

Requests are sent when they are due, whether or not earlier ones have
come back, and a request's latency runs from the time it was DUE, so a
stall shows in the latencies of the requests behind it. The schedule is
drawn from the seed: rate x seconds arrivals at sorted uniform times (a
Poisson process conditioned on its count, so that every seed offers the
same amount of work), users by a power law."""

from __future__ import annotations

import math
import queue
import threading

import numpy as np

from benchmark import stats
from benchmark.drivers import httpclient as hc


def plan(cell, seed: int, seconds: float, host: str, port: int, t0_unix: float,
         tail_seconds: float = 0.0) -> dict:
    traffic, rate = cell.traffic, cell.cell.get("rate_per_s")
    if not rate:
        raise ValueError(
            f"open-loop cell {cell.name} needs benchmark/cells/{cell.name}.json with rate_per_s"
        )
    return {
        "host": host,
        "port": port,
        "t0_unix": t0_unix,
        "seed": int(seed),
        "seconds": float(seconds),
        "warm_seconds": float(traffic["warm_seconds"]),
        "tail_seconds": float(tail_seconds),
        "rate_per_s": float(rate),
        "workers": int(traffic["workers"]),
        "timeout_s": float(traffic["timeout_s"]),
        "path": traffic["endpoints"][0]["path"],
        "how_many": int(traffic["how_many"]),
        "n_users": int(cell.config["users"]),
        "exponent": float(traffic["users"]["exponent"]),
        "sample_every": int(traffic["check_sample_every"]),
        "sample_max": int(traffic["check_sample_max"]),
    }


def schedule(p: dict):
    """(due seconds since t0, user rows, index of the first window
    request, number of window requests, sampled request indices), all
    from the seed. The same traffic runs before the window (warm phase)
    and, in a traced run, after it (the tail the profiler records, so
    that the window itself is measured with the profiler off)."""
    rng = np.random.Generator(np.random.PCG64([p["seed"], 11]))
    warm, seconds, rate = p["warm_seconds"], p["seconds"], p["rate_per_s"]
    tail = p.get("tail_seconds", 0.0)
    n_warm, n_win, n_tail = (int(round(rate * x)) for x in (warm, seconds, tail))
    due = np.concatenate(
        [
            np.sort(rng.random(n_warm)) * warm,
            warm + np.sort(rng.random(n_win)) * seconds,
            warm + seconds + np.sort(rng.random(n_tail)) * tail,
        ]
    )
    users = hc.power_law_users(rng, p["n_users"], p["exponent"], n_warm + n_win + n_tail)
    n_sample = min(p["sample_max"], math.ceil(n_win / p["sample_every"]))
    sampled = n_warm + rng.choice(n_win, size=n_sample, replace=False)
    return due, users, n_warm, n_win, set(int(i) for i in sampled)


def run(p: dict) -> dict:
    due, users, n_warm, n_win, sampled = schedule(p)
    clock = hc.Clock(p["t0_unix"])
    n = len(due)
    sent = np.zeros(n)
    done = np.zeros(n)
    ok = np.zeros(n, dtype=bool)
    kinds: dict[str, int] = {}
    bodies: dict[int, str] = {}
    lock = threading.Lock()
    work: queue.SimpleQueue = queue.SimpleQueue()

    def worker() -> None:
        conn = hc.Connection(p["host"], p["port"], p["timeout_s"])
        try:
            conn.connect()
        except OSError:
            pass  # the first request will try again and be judged
        while True:
            i = work.get()
            if i is None:
                conn.close()
                return
            sent[i] = clock.now()
            good, kind, body = hc.judged_get(conn, p["path"] % users[i], p["how_many"])
            done[i] = clock.now()
            ok[i] = good
            if not good or i in sampled:
                with lock:
                    if not good:
                        kinds[kind] = kinds.get(kind, 0) + 1
                    elif i in sampled:
                        bodies[i] = body.decode()

    threads = [threading.Thread(target=worker, daemon=True) for _ in range(p["workers"])]
    for t in threads:
        t.start()
    for i in range(n):
        clock.sleep_until(due[i])
        work.put(i)
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join(timeout=p["timeout_s"] + 5.0)
    w = slice(n_warm, n_warm + n_win)
    return {
        "window": [p["warm_seconds"], p["warm_seconds"] + p["seconds"]],
        "due": due[w].tolist(),
        "sent": sent[w].tolist(),
        "done": done[w].tolist(),
        "ok": ok[w].tolist(),
        "warm_ok": int(ok[:n_warm].sum()),
        "warm_sent": int(n_warm),
        "kinds": kinds,
        "sampled": [
            {"user": int(users[i]), "body": bodies[i]} for i in sorted(bodies)
        ],
    }


def reduce(result: dict, traffic: dict) -> dict:
    """Readings of one window: `values` by name for the end-to-end and the
    load generator's per-layer metrics, `attempted` / `failed`, and lines
    to print before the result."""
    due = np.asarray(result["due"])
    done = np.asarray(result["done"])
    sent = np.asarray(result["sent"])
    ok = np.asarray(result["ok"], dtype=bool)
    start, end = result["window"]
    out = {"attempted": int(ok.size), "failed": int((~ok).sum()), "values": {}, "lines": []}
    if not ok.any():
        return out
    # the judged tail is the tail of ALL the window's requests: one that
    # failed (a shed answer comes back fast) is charged the client's
    # timeout, so that shedding can never shorten the tail
    lat_ms = (done - due) * 1000.0
    lat_ms = np.where(ok, lat_ms, np.maximum(lat_ms, float(traffic["timeout_s"]) * 1000.0))
    v = out["values"]
    v["recommend_p95_ms"] = stats.percentile(lat_ms, 0.95)
    v["recommend_p50_ms"] = stats.percentile(lat_ms, 0.50)
    v["recommend_p99_ms"] = stats.percentile(lat_ms, 0.99)
    v["generator_late_p99_ms"] = stats.percentile((sent - due) * 1000.0, 0.99)
    v["answers_per_s"] = float(ok.sum()) / (end - start)
    out["lines"].append(
        "open_http: %d sent, %d ok; whole-window p50/p95/p99 ms %.3f/%.3f/%.3f; "
        "generator late p99 %.3f ms; warm phase %d/%d ok"
        % (
            ok.size, int(ok.sum()), v["recommend_p50_ms"], v["recommend_p95_ms"],
            v["recommend_p99_ms"], v["generator_late_p99_ms"],
            result["warm_ok"], result["warm_sent"],
        )
    )
    return out


if __name__ == "__main__":
    hc.child_main(run)
