"""python3 tools/sweep_passes.py --workload <cell> --seed <n> --rates 500,1000,600:30,c16:30,...
       [--seconds 10] [--trace 0|1] [--raw]

`benchmark/sweep.py` with the batcher's and the device's view beside the
generator's: one set-up, one window a rate (or, `c16`, a closed loop of 16
clients: answers/s and the clients' p95), and for each window p50 / p95, the shed share, how late the generator ran, the
ladder's pressure at the window's end, rows and passes
(`serving.batcher.pass.*`), queue wait and pass in flight, the later
close (share of passes held, share of those submitted late, mean hold,
rows that joined a hold, mean error of the prediction, the result lag it
reckons with at the window's end), the host path stage by stage in wall
and in thread-CPU time (`serving.front.*`, `serving.handler.*`,
`serving.batcher.entry` / `wake` / `submit.device-call`, the
dispatcher's and the completer's CPU a pass,
the process's CPU as cores busy: which stage grows towards the knee;
the wall stages once more as a `stages:` line, with the share of requests
their serving thread took from the C++ front itself, `taken_pct`: 100 on
the native front, 0 on the Python front),
for a cell that folds in (`/recommendToAnonymous`) the fold-in's mean,
its items a request and the mean k bucket of a pass (whose knee is it:
the host fold-in's, or the device's), the share of passes whose results
came back as one array (`serving.batcher.pass.packed` over `passes`), and, with
`--trace 1`, the device's idle share and the scan kernel's ms a pass from
a profiler recording of the 4 s after the window at the same load; with
`--raw`, every request's due time and latency of every window. A tool
for the builder who sites a cell's rate (knee = the highest rate with no
shed answer and the generator on time); the driver's check never runs it."""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run, stats  # noqa: E402
from benchmark import spec as spec_mod  # noqa: E402


def taken_pct(before: dict, after: dict) -> float:
    """The share of a window's requests that their serving thread took
    from the C++ front itself, with no thread in between: 100 on the
    native front, 0 on the Python front (and on a program from before)."""
    taken = stats.counter_delta(before, after, "serving.front.taken", "value")
    requests = stats.counter_delta(before, after, "serving.handler.requests", "value")
    return 100.0 * taken / max(requests, 1.0)


def window_row(session, load: str, seed: int, seconds: float, trace: bool,
               raw: dict | None = None) -> dict:
    """One window at ``load``: requests/s of an open loop, or `c<n>`, a
    closed loop of n clients."""
    closed = load.startswith("c")
    rate = float(load.lstrip("c"))
    if closed:
        over = {"traffic": {"driver": "closed_http", "clients": int(rate)}}
    else:
        over = {"cell": {"rate_per_s": rate}}
    got, result, span, reduced, _w = session.window(seed, seconds, trace, over)
    if raw is not None and "due" in result:
        # every request's due time and latency, for a builder who asks how
        # a window's tail is made (what a pause of the machine moves)
        i = len(raw) // 2
        due, done = np.asarray(result["due"]), np.asarray(result["done"])
        raw[f"due_{i}_{rate:g}"] = due - result["window"][0]
        raw[f"lat_ms_{i}_{rate:g}"] = 1000.0 * (done - due)
    before, after = span["window"]

    def d(metric, field="value"):
        return stats.counter_delta(before, after, metric, field)

    def mean_ms(hist):
        return 1000.0 * d(hist, "sum") / max(d(hist, "count"), 1.0)

    def per_request_ms(counter):
        return 1000.0 * d(counter) / max(d("serving.handler.requests"), 1.0)

    passes = max(d("serving.batcher.passes"), 1.0)
    queries = max(d("serving.scan.indexed.queries") + d("serving.scan.vector.queries"), 1.0)
    held = d("serving.batcher.pass.held")  # 0 on a program without the later close
    k_sum = d("serving.batcher.pass.k-bucket-sum")  # 0 on a program without the counter
    row = {
        "clients" if closed else "rate_per_s": rate,
        "seconds": seconds,
        "attempted": got["attempted"],
        "failed": got["failed"],
        "shed_pct": 100.0 * got["failed"] / max(got["attempted"], 1),
        "kinds": result.get("kinds", {}),
        **{k: got["values"][k] for k in (
            "recommend_p50_ms", "recommend_p95_ms", "recommend_p99_ms", "generator_late_p99_ms",
            "recommend_qps", "closed_p95_ms",
        ) if k in got["values"]},
        "overload_pressure": (after.get("serving.overload.pressure") or {}).get("value"),
        "handler_mean_ms": mean_ms("serving.request.seconds"),
        "queue_wait_mean_ms": mean_ms("serving.batcher.queue-wait.seconds"),
        "pass_inflight_mean_ms": mean_ms("serving.batcher.pass.seconds"),
        "passes_per_s": passes / seconds,
        "rows_per_pass": d("serving.batcher.pass.rows") / passes,
        "inflight_depth_mean": d("serving.batcher.pass.inflight-depth-sum") / passes,
        "held_pass_pct": 100.0 * held / passes,
        "hold_late_pct": 100.0 * d("serving.batcher.hold.late") / max(held, 1.0),
        "hold_mean_ms": mean_ms("serving.batcher.hold.seconds"),
        "hold_rows": d("serving.batcher.hold.rows"),
        "hold_error_mean_ms": mean_ms("serving.batcher.hold.error.seconds"),
        "hold_lag_ms": (after.get("serving.batcher.hold.lag-ms") or {}).get("value"),
        "indexed_pct": 100.0 * d("serving.scan.indexed.queries") / queries,
        "cosine_pct": 100.0 * d("serving.scan.cosine.queries") / queries,
        "submit_mean_ms": mean_ms("serving.batcher.submit.seconds"),
        # the fold-in of a request without a user row (endpoints._fold_in), and the k
        # bucket its baskets give a pass: a pass runs at its largest entry's bucket,
        # so with buckets 16 and 32 alone (mean - 16) / 16 is the share of passes at 32
        "foldin_mean_ms": mean_ms("serving.foldin.seconds"),
        "foldin_items_per_request": d("serving.foldin.items")
        / max(d("serving.foldin.requests"), 1.0),
        "k_bucket_mean": k_sum / passes if k_sum else None,
        "k32_pass_pct": 100.0 * (k_sum / passes - 16.0) / 16.0 if k_sum else None,
        # the share of passes that came back as ONE array (one copy, one fetch):
        # 100 on a float32 handle, 0 on a bf16 wire, an IVF index or a program from before
        "packed_pass_pct": 100.0 * d("serving.batcher.pass.packed") / passes,
        # the host path, stage by stage (serving/stages.py): a request's
        # wall time in order, then a pass's, then the CPU beside them
        "front_native": (after.get("serving.front.native") or {}).get("value"),
        "taken_pct": taken_pct(before, after),
        "front_workers": (after.get("serving.front.workers") or {}).get("value"),
        "front_ingress_mean_ms": mean_ms("serving.front.ingress.seconds"),
        "handler_pre_mean_ms": mean_ms("serving.handler.pre.seconds"),
        "batcher_entry_mean_ms": mean_ms("serving.batcher.entry.seconds"),
        "waiter_wake_mean_ms": mean_ms("serving.batcher.wake.seconds"),
        "handler_post_mean_ms": mean_ms("serving.handler.post.seconds"),
        "front_respond_mean_ms": mean_ms("serving.front.respond.seconds"),
        # inside it: the hf_respond call and the interpreter's return from it
        "front_respond_call_mean_ms": mean_ms("serving.front.respond.call.seconds"),
        "rescans": d("serving.handler.rescans"),
        "deliver_mean_ms": mean_ms("serving.batcher.deliver.seconds"),
        "submit_device_call_mean_ms": mean_ms("serving.batcher.submit.device-call.seconds"),
        "handler_cpu_ms_per_request": per_request_ms("serving.handler.cpu.seconds"),
        "server_cpu_ms_per_request": per_request_ms("serving.process.cpu.seconds"),
        "dispatch_cpu_ms_per_pass": 1000.0 * d("serving.batcher.dispatch.cpu.seconds") / passes,
        "complete_cpu_ms_per_pass": 1000.0 * d("serving.batcher.complete.cpu.seconds") / passes,
        "process_cores_busy": d("serving.process.cpu.seconds") / seconds,
        "requests_per_s": d("serving.handler.requests") / seconds,
        "vector_upload_kb_per_pass": d("serving.scan.vector.upload-bytes") / passes / 1024.0,
        "unstaged_requests": d("serving.users.unstaged-requests"),
        "compiles": d("jax.compile.seconds", "count"),
        # the longest stop of each process in the window and its second: one
        # that both met at the same second is the machine's
        "server_pause_max_ms": session.pause["max_ms"],
        "server_pause_at_s": session.pause["at_s"],
        "generator_pause_max_ms": result["pause"]["max_ms"],
        "generator_pause_at_s": result["pause"]["at_s"],
    }
    if reduced is not None:
        from benchmark import trace as trace_mod

        row["device_idle_pct"] = 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
        n, s = trace_mod.matching(reduced, "oryx_topn")
        if n:
            row["scan_kernel_ms_per_pass"] = 1000.0 * s / n
    return row


def stages_line(row: dict) -> str:
    """A window's host path for the eye, one line: who brought the
    requests to their serving threads, then a request's wall stages in
    order (means over the staged requests, ms)."""
    path = (
        ("ingress", "front_ingress_mean_ms"), ("pre", "handler_pre_mean_ms"),
        ("entry", "batcher_entry_mean_ms"), ("queue", "queue_wait_mean_ms"),
        ("in flight", "pass_inflight_mean_ms"), ("wake", "waiter_wake_mean_ms"),
        ("post", "handler_post_mean_ms"), ("respond", "front_respond_mean_ms"),
    )
    return (
        "stages: front_native %s, taken %.1f %% of %.0f requests/s by %s serving threads; "
        % (row["front_native"], row["taken_pct"], row["requests_per_s"], row["front_workers"])
        + " -> ".join(f"{name} {row[key]:.3f}" for name, key in path)
        + " ms"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="requests/s, comma-separated, in this order; `600:30` = a 30 s window; "
                    "`c16` = a closed loop of 16 clients")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--raw", action="store_true",
                    help="also save every request's due time and latency (an .npz beside the rows)")
    ap.add_argument("--allow-cpu", action="store_true", help="rehearsal: no device number")
    ap.add_argument("--root", default=spec_mod.ROOT,
                    help="rehearsal: a copy of the benchmark with tiny cells")
    args = ap.parse_args(argv)
    session = run.Session(
        spec_mod.Spec(args.root), args.workload, args.seed, require_chip=not args.allow_cpu
    )
    rows = []
    raw = {} if args.raw else None
    try:
        print("setup: " + ", ".join(f"{k[:-2]} {v:.2f} s" for k, v in session.timings.items()))
        for i, item in enumerate(r for r in args.rates.split(",") if r):
            load, _, seconds = item.partition(":")
            row = window_row(session, load, args.seed + 1000 * i + int(float(load.lstrip("c"))),
                             float(seconds or args.seconds), bool(args.trace), raw)
            rows.append(row)
            print("sweep_passes:", json.dumps(row))
            print(stages_line(row), flush=True)
    finally:
        session.close()
    out = os.path.join("chiprun_out", f"sweep_passes_{args.workload}_{args.seed}.json")
    os.makedirs("chiprun_out", exist_ok=True)
    with open(out, "w", encoding="utf-8") as f:
        json.dump(rows, f, indent=1)
    if raw:
        np.savez_compressed(out[:-5] + "_raw.npz", **raw)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
