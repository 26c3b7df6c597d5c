"""Driver `closed_http`: a fixed number of clients, each of which sends
its next request when its answer arrives (a web tier with a connection
pool). The load adapts to the server, so the reading is a rate: answers
completed inside the window over its length."""

from __future__ import annotations

import threading

import numpy as np

from benchmark import stats
from benchmark.drivers import httpclient as hc


def plan(cell, seed: int, seconds: float, host: str, port: int, t0_unix: float,
         tail_seconds: float = 0.0) -> dict:
    traffic = cell.traffic
    return {
        "host": host,
        "port": port,
        "t0_unix": t0_unix,
        "seed": int(seed),
        "seconds": float(seconds),
        "warm_seconds": float(traffic["warm_seconds"]),
        "tail_seconds": float(tail_seconds),
        "clients": int(traffic["clients"]),
        "think_seconds": float(traffic.get("think_seconds", 0)),
        "timeout_s": float(traffic["timeout_s"]),
        "path": traffic["endpoints"][0]["path"],
        "how_many": int(traffic["how_many"]),
        "n_users": int(cell.config["users"]),
        "exponent": float(traffic["users"]["exponent"]),
        "sample_every": int(traffic["check_sample_every"]),
        "sample_max": int(traffic["check_sample_max"]),
    }


def run(p: dict) -> dict:
    clock = hc.Clock(p["t0_unix"])
    end = p["warm_seconds"] + p["seconds"]
    stop = end + p.get("tail_seconds", 0.0)  # a traced run keeps the load up for the profiler
    records: list[list] = [[] for _ in range(p["clients"])]
    sampled: list[list] = [[] for _ in range(p["clients"])]

    def client(c: int) -> None:
        rng = np.random.Generator(np.random.PCG64([p["seed"], 12, c]))
        conn = hc.Connection(p["host"], p["port"], p["timeout_s"])
        try:
            conn.connect()
        except OSError:
            pass
        clock.sleep_until(0.0)
        while True:
            t_send = clock.now()
            if t_send >= stop:
                break
            # users and the sample are drawn in blocks so that the draws
            # of one client do not depend on how fast the server answers
            block = hc.power_law_users(rng, p["n_users"], p["exponent"], 256)
            picks = rng.random(256) < 1.0 / p["sample_every"]
            for user, pick in zip(block, picks):
                t_send = clock.now()
                if t_send >= stop:
                    break
                good, kind, body = hc.judged_get(conn, p["path"] % user, p["how_many"])
                t_done = clock.now()
                records[c].append((t_send, t_done, good, kind))
                if good and pick and p["warm_seconds"] <= t_done < end:
                    sampled[c].append({"user": int(user), "body": body.decode()})
                if p["think_seconds"]:
                    clock.sleep_until(t_done + p["think_seconds"])
        conn.close()

    threads = [threading.Thread(target=client, args=(c,), daemon=True) for c in range(p["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=stop - clock.now() + p["timeout_s"] + 5.0)
    flat = [r for per in records for r in per]
    kinds: dict[str, int] = {}
    for _s, _d, good, kind in flat:
        if not good:
            kinds[kind] = kinds.get(kind, 0) + 1
    picked = [s for per in sampled for s in per]
    if len(picked) > p["sample_max"]:
        rng = np.random.Generator(np.random.PCG64([p["seed"], 13]))
        keep = sorted(rng.choice(len(picked), size=p["sample_max"], replace=False))
        picked = [picked[i] for i in keep]
    return {
        "window": [p["warm_seconds"], end],
        "sent": [r[0] for r in flat],
        "done": [r[1] for r in flat],
        "ok": [bool(r[2]) for r in flat],
        "kinds": kinds,
        "sampled": picked,
    }


def reduce(result: dict, traffic: dict) -> dict:
    sent = np.asarray(result["sent"])
    done = np.asarray(result["done"])
    ok = np.asarray(result["ok"], dtype=bool)
    start, end = result["window"]
    inside = (done >= start) & (done < end)
    good = inside & ok
    out = {
        "attempted": int(inside.sum()),
        "failed": int((inside & ~ok).sum()),
        "values": {},
        "lines": [],
    }
    if not good.any():
        return out
    lat_ms = (done - sent)[good] * 1000.0
    v = out["values"]
    v["recommend_qps"] = float(good.sum()) / (end - start)
    v["closed_p50_ms"] = stats.percentile(lat_ms, 0.50)
    v["closed_p95_ms"] = stats.percentile(lat_ms, 0.95)
    out["lines"].append(
        "closed_http: %d clients, %d answers in the window, %d ok: %.3f answers/s; "
        "latency p50/p95 ms %.3f/%.3f"
        % (
            int(traffic["clients"]), int(inside.sum()), int(good.sum()),
            v["recommend_qps"], v["closed_p50_ms"], v["closed_p95_ms"],
        )
    )
    return out


if __name__ == "__main__":
    hc.child_main(run)
