"""Driver `open_http_baskets`: `open_http`'s open loop (the same schedule
of due times and head ids from the seed, the same judged tail), each
request a BASKET of 1 to 8 items instead of one id:
`/recommendToAnonymous/i<h>/i<o_1>/.../i<o_k-1>?howMany=10`, the first k
items of head item h's fixed session (benchmark/reference/als_foldin.py
`basket`), k drawn a request from a stream of its own by the mix's law.
A request is named by one integer, `(k - 1) * heads + h`, which is what a
sampled answer carries as its `user`; index `h` is the one-item basket,
which is `path % h`, what the harness asks by itself."""

from __future__ import annotations

import queue
import threading

import numpy as np

from benchmark.drivers import httpclient as hc
from benchmark.drivers import open_http
from benchmark.reference import als_foldin


def plan(cell, seed: int, seconds: float, host: str, port: int, t0_unix: float,
         tail_seconds: float = 0.0) -> dict:
    params = open_http.plan(cell, seed, seconds, host, port, t0_unix, tail_seconds)
    sizes, sessions = cell.traffic["basket_size"], cell.config["sessions"]
    params.update(
        n_items=int(cell.config["items"]),
        session_exponent=float(sessions["exponent"]),
        basket_seed=int(sessions["basket_seed"]),
        basket_ratio=float(sizes["ratio"]),
        basket_largest=int(sizes["largest"]),
    )
    return params


def basket_sizes(p: dict, count: int) -> np.ndarray:
    """k of each of `count` requests, from a stream of the seed's own: the
    heads and due times are `open_http.schedule`'s, untouched by it."""
    rng = np.random.Generator(np.random.PCG64([p["seed"], 12]))
    law = als_foldin.basket_size_law(p["basket_ratio"], p["basket_largest"])
    return 1 + rng.choice(len(law), size=count, p=law)


def requests(p: dict, heads: np.ndarray):
    """(index, URL) of every request of the schedule."""
    ks = basket_sizes(p, len(heads))
    sessions: dict[int, list[int]] = {}
    indices, urls = [], []
    for head, k in zip(heads.tolist(), ks.tolist()):
        items = sessions.get(head)
        if items is None:
            items = sessions[head] = als_foldin.session(
                head, p["n_items"], p["session_exponent"], p["basket_seed"]
            )
        k = min(k, len(items))
        indices.append((k - 1) * p["n_users"] + head)
        urls.append(als_foldin.url(p["path"], items[:k]))
    return indices, urls


def run(p: dict) -> dict:
    due, heads, n_warm, n_win, sampled = open_http.schedule(p)
    indices, urls = requests(p, heads)
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
            good, kind, body = hc.judged_get(conn, urls[i], p["how_many"])
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
    sizes = 1 + np.asarray(indices[w]) // p["n_users"]
    return {
        "window": [p["warm_seconds"], p["warm_seconds"] + p["seconds"]],
        "due": due[w].tolist(),
        "sent": sent[w].tolist(),
        "done": done[w].tolist(),
        "ok": ok[w].tolist(),
        "warm_ok": int(ok[:n_warm].sum()),
        "warm_sent": int(n_warm),
        "kinds": kinds,
        "sampled": [{"user": int(indices[i]), "body": bodies[i]} for i in sorted(bodies)],
        "basket_items": int(sizes.sum()),
        "basket_sizes": np.bincount(sizes, minlength=p["basket_largest"] + 1)[1:].tolist(),
        "sampled_sizes": [1 + indices[i] // p["n_users"] for i in sorted(bodies)],
    }


def reduce(result: dict, traffic: dict) -> dict:
    """`open_http.reduce`, and a line that says what the baskets were."""
    out = open_http.reduce(result, traffic)
    sampled = result["sampled_sizes"]
    out["lines"].append(
        "open_http_baskets: %.4f items a request over the window, requests by basket size "
        "1..%d %s; of the %d sampled answers %d have 2 items or more (%.1f %%)"
        % (
            result["basket_items"] / max(len(result["ok"]), 1), len(result["basket_sizes"]),
            result["basket_sizes"], len(sampled), sum(k >= 2 for k in sampled),
            100.0 * sum(k >= 2 for k in sampled) / max(len(sampled), 1),
        )
    )
    return out


if __name__ == "__main__":
    hc.child_main(run)
