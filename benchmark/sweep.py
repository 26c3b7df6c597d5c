"""python3 -m benchmark.sweep --workload <cell> --seed <n> [--raw]
       --plan rate:100:1:10,rate:250:6:30,clients:32:3:30   (kind:value:repeat:seconds)

One set-up, many short windows: the sweep that finds an open-loop cell's
knee (the highest rate with no growing backlog and no shed answer), and
the client count a closed-loop cell can hold at full quality. A tool for
the builder; the driver's check never runs it. Each window's counters give
the batcher's view beside the generator's."""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from benchmark import run, stats, spec as spec_mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument(
        "--plan", required=True,
        help="kind:value:repeat:seconds,... with kind `rate` (open loop, requests/s) or "
        "`clients` (closed loop), e.g. rate:250:6:30,clients:32:3:30",
    )
    ap.add_argument("--raw", action="store_true", help="keep each window's due times and latencies")
    args = ap.parse_args(argv)
    spec = spec_mod.Spec()
    session = run.Session(spec, args.workload, args.seed)
    rows = []
    try:
        points = []
        for item in [x for x in args.plan.split(",") if x]:
            kind, value, repeat, seconds = item.split(":")
            points.append(
                ("rate_per_s", float(value), int(repeat), float(seconds))
                if kind == "rate"
                else ("clients", int(value), int(repeat), float(seconds))
            )
        for key, value, repeat, seconds in points:
            for rep in range(repeat):
                over = (
                    {"cell": {"rate_per_s": value}}
                    if key == "rate_per_s"
                    else {"traffic": {"driver": "closed_http", "clients": value}}
                )
                got, result, span, _r, _w = session.window(
                    args.seed + 1000 * rep + int(value), seconds, False, over
                )
                before, after = span["window"]
                d = lambda m, f: stats.counter_delta(before, after, m, f)  # noqa: E731
                reqs = d("serving.request.seconds", "count")
                row = {
                    key: value,
                    "rep": rep,
                    "seconds": seconds,
                    "attempted": got["attempted"],
                    "failed": got["failed"],
                    "kinds": result.get("kinds", {}),
                    "handler_mean_ms": 1000 * d("serving.request.seconds", "sum") / max(reqs, 1),
                    "scan_queries": d("serving.scan.indexed.queries", "value")
                    + d("serving.scan.vector.queries", "value"),
                    "coalesced": d("serving.batcher.coalesced", "value"),
                    "inflight_gauge": (after.get("serving.batcher.inflight") or {}).get("value"),
                    "ewma_ms_gauge": (after.get("serving.batcher.dispatch_ewma_ms") or {}).get("value"),
                    "overload_pressure": (after.get("serving.overload.pressure") or {}).get("value"),
                    "compiles": d("jax.compile.seconds", "count"),
                }
                row.update({k: v for k, v in got["values"].items()})
                if "due" in result and any(result["ok"]):
                    ok = np.asarray(result["ok"], dtype=bool)
                    done = np.asarray(result["done"])
                    due = np.asarray(result["due"])
                    lat = (done - due)[ok] * 1000
                    # backlog: does latency grow through the window?
                    half = len(lat) // 2
                    if half:
                        row["p50_first_half_ms"] = float(np.median(lat[:half]))
                        row["p50_second_half_ms"] = float(np.median(lat[half:]))
                if args.raw:
                    os.makedirs("chiprun_out", exist_ok=True)
                    start = result["window"][0]
                    np.savez_compressed(
                        f"chiprun_out/raw_{args.workload}_{key}{value:g}_{seconds:g}s_{rep}.npz",
                        t=np.asarray(result.get("due", result["sent"])) - start,
                        done=np.asarray(result["done"]) - start,
                        ok=np.asarray(result["ok"], dtype=bool),
                    )
                rows.append(row)
                print("sweep:", json.dumps(row), flush=True)
    finally:
        session.close()
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/sweep_{args.workload}_{args.seed}.json", "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
