#!/usr/bin/env python
"""python3 tools/played_stop.py --workload <open-loop cell> --seed <n>
       [--stops 150,400,800] [--repeat 3] [--every 20] [--windows 8] [--seconds 30]

The played stop: how a change to the batcher or to the admission ladder
behaves when the server process stops for a moment (a collection, a
stall of the machine) while the ladder is near its first rung. One
set-up of a benchmark cell; then, under the cell's own open-loop load,
the server process (this one, which holds the ServingLayer; not the load
generator's child) is sent SIGSTOP for each of `--stops` milliseconds,
`--repeat` times each, `--every` seconds apart, by a small child that
imports nothing of the program. Per stop it prints the requests that
came back without a full-quality answer (`X-Oryx-Shed-Stage`, or any
other failure), the ladder's transitions and the peak of its smoothed
pressure, the time from SIGCONT until the last such request was due and
until the median latency is back (within a quarter of what it was in the
5 s before the stop, in a 50 ms bin of requests that all came back whole).
Then `--windows` plain windows, each with the longest pause the server
saw, the transitions and the failed count.

Run it on the parent and on the change in one chip call and compare stop
by stop. It imports the benchmark and edits nothing in it; no cell runs
it. Lines go to stdout, everything to chiprun_out/played_stop_<label>.json.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

RECOVERY_BIN_S = 0.05  # latency is judged "back" over bins of requests this long
RECOVERY_FACTOR = 1.25  # ... once a bin's median is within this of the median before the stop

# The child that plays the stops: standard library only, never stopped
# itself, and it continues the server whatever happens to it on the way.
_STOPPER = r"""
import json, os, signal, sys, time
pid, plan = int(sys.argv[1]), json.loads(sys.argv[2])
played = []
try:
    for at_unix, ms in plan:
        time.sleep(max(0.0, at_unix - time.time()))
        t_stop = time.time()
        os.kill(pid, signal.SIGSTOP)
        try:
            time.sleep(ms / 1000.0)
        finally:
            os.kill(pid, signal.SIGCONT)
        played.append([t_stop, time.time(), ms])
finally:
    try:
        os.kill(pid, signal.SIGCONT)
    except ProcessLookupError:
        pass
    print(json.dumps(played), flush=True)
"""


class LadderWatch:
    """Samples the admission ladder's smoothed pressure and rung every few
    milliseconds (the ladder keeps its transitions itself)."""

    def __init__(self, admission, period_s: float = 0.005) -> None:
        self._admission, self._period = admission, period_s
        self.samples: list[tuple[float, float, int]] = []  # unix time, pressure, stage
        self._stop = threading.Event()
        self._thread: threading.Thread | None = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self._period):
            self.samples.append((time.time(), self._admission.pressure, self._admission.stage))

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


def batcher_state() -> dict:
    from oryx_tpu.serving import batcher as batcher_mod

    b = batcher_mod._default
    return {} if b is None else {"inflight_cap": b._inflight_cap, "max_batch": b.max_batch}


def window_counters(span, stats) -> dict:
    before, after = span["window"]

    def d(metric, fld):
        return stats.counter_delta(before, after, metric, fld)

    def mean_ms(metric):
        return 1000.0 * d(metric, "sum") / max(d(metric, "count"), 1)

    passes = max(d("serving.batcher.passes", "value"), 1)
    return {
        "handler_mean_ms": mean_ms("serving.request.seconds"),
        "queue_wait_mean_ms": mean_ms("serving.batcher.queue-wait.seconds"),
        "pass_inflight_mean_ms": mean_ms("serving.batcher.pass.seconds"),
        "rows_per_pass": d("serving.batcher.pass.rows", "value") / passes,
        "inflight_depth_mean": d("serving.batcher.pass.inflight-depth-sum", "value") / passes,
        "compiles": d("jax.compile.seconds", "count"),
    }


def judge_stop(t_stop: float, t_cont: float, until: float, due, done, ok, transitions, samples):
    """One played stop, all times in seconds since the window's t0:
    requests DUE from the stop until `until`."""
    lat_ms = (done - due) * 1000.0
    before = ok & (due >= t_stop - 5.0) & (due < t_stop)
    base_ms = float(np.median(lat_ms[before])) if before.any() else float("nan")
    span = (due >= t_stop) & (due < until)
    recovered_s = None
    t = t_cont
    while t + RECOVERY_BIN_S <= until:
        in_bin = (due >= t) & (due < t + RECOVERY_BIN_S)
        if in_bin.any() and ok[in_bin].all():
            if float(np.median(lat_ms[in_bin])) <= RECOVERY_FACTOR * base_ms:
                recovered_s = t - t_cont
                break
        t += RECOVERY_BIN_S
    failed_due = due[span & ~ok]
    # the course of it, for the report: requests due in each bin around
    # SIGCONT, how many of them failed, the median latency of the rest
    timeline = []
    for k in range(-10, 50):
        lo = t_cont + k * RECOVERY_BIN_S
        in_bin = (due >= lo) & (due < lo + RECOVERY_BIN_S)
        good = in_bin & ok
        timeline.append([
            round(k * RECOVERY_BIN_S, 3), int(in_bin.sum()), int((in_bin & ~ok).sum()),
            round(float(np.median(lat_ms[good])), 3) if good.any() else None,
        ])
    moves = [m for m in transitions if t_stop <= m[0] < until]
    pressures = [p for (ts, p, _stage) in samples if t_stop <= ts < until]
    return {
        "stopped_ms": 1000.0 * (t_cont - t_stop),
        "due_in_stop": int(((due >= t_stop) & (due < t_cont)).sum()),
        "failed": int(failed_due.size),
        "failed_span_s": float(failed_due.max() - failed_due.min()) if failed_due.size else 0.0,
        "failed_until_s": float(failed_due.max() - t_cont) if failed_due.size else None,
        "transitions": len(moves),
        "stages": [m[2] for m in moves],
        "pressure_peak": max(pressures) if pressures else None,
        "p50_before_ms": base_ms,
        "worst_latency_ms": float(lat_ms[span].max()) if span.any() else None,
        "recovered_after_s": recovered_s,
        "timeline": timeline,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True, help="an open-loop cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--stops", default="150,400,800", help="milliseconds, comma-separated")
    ap.add_argument("--repeat", type=int, default=3, help="times each stop is played")
    ap.add_argument("--every", type=float, default=20.0, help="seconds between stops")
    ap.add_argument("--windows", type=int, default=8, help="plain windows after the stops")
    ap.add_argument("--seconds", type=float, default=30.0, help="length of a plain window")
    ap.add_argument("--label", default="run", help="names the output file")
    ap.add_argument("--out-dir", default="chiprun_out", help="where the report goes")
    ap.add_argument("--root", default=None, help="checkout whose BENCHMARK.json names the cell")
    ap.add_argument("--allow-cpu", action="store_true", help="rehearsal only: no chip, no device number")
    args = ap.parse_args(argv)

    from benchmark import run, stats, spec as spec_mod

    spec = spec_mod.Spec(args.root) if args.root else spec_mod.Spec()
    session = run.Session(spec, args.workload, args.seed, require_chip=not args.allow_cpu)
    report: dict = {"workload": args.workload, "seed": args.seed, "device": session.device,
                    "label": args.label, "stops": [], "windows": []}
    try:
        admission = session.layer.admission
        if admission is None:
            raise SystemExit("the layer runs without an admission ladder: nothing to play")
        warm = float(session.traffic["warm_seconds"])
        mono_to_unix = time.time() - time.monotonic()
        stops_ms = [float(x) for x in args.stops.split(",") if x] * args.repeat
        stops_ms.sort()  # the short ones first: 150 x3, 400 x3, 800 x3

        if stops_ms:
            # one long window; the first stop after `every` seconds of it
            t0_guess = time.time() + 2.0
            plan = [
                [t0_guess + warm + args.every * (i + 1), ms] for i, ms in enumerate(stops_ms)
            ]
            seconds = args.every * (len(stops_ms) + 1)
            stopper = subprocess.Popen(
                [sys.executable, "-c", _STOPPER, str(os.getpid()), json.dumps(plan)],
                stdout=subprocess.PIPE,
            )
            watch = LadderWatch(admission)
            try:
                got, result, span, _r, win_start = session.window(args.seed, seconds, False)
            finally:
                watch.close()
                try:
                    out, _ = stopper.communicate(timeout=30.0)
                except subprocess.TimeoutExpired:
                    stopper.kill()
                    out, _ = stopper.communicate()
            played = json.loads(out.decode().strip().splitlines()[-1])
            t0_unix = win_start - warm
            due, done = np.asarray(result["due"]), np.asarray(result["done"])
            ok = np.asarray(result["ok"], dtype=bool)
            transitions = [
                (t + mono_to_unix - t0_unix, a, b, p) for (t, a, b, p) in admission.transitions
            ]
            samples = [(ts - t0_unix, p, s) for (ts, p, s) in watch.samples]
            for i, (t_stop, t_cont, ms) in enumerate(played):
                until = (played[i + 1][0] if i + 1 < len(played) else t_cont + args.every) - t0_unix
                row = {"asked_ms": ms}
                row.update(judge_stop(t_stop - t0_unix, t_cont - t0_unix, until,
                                      due, done, ok, transitions, samples))
                report["stops"].append(row)
                print("stop:", json.dumps({k: v for k, v in row.items() if k != "timeline"}), flush=True)
            summary = {
                "attempted": got["attempted"], "failed": got["failed"],
                "kinds": result.get("kinds", {}), "batcher": batcher_state(),
                **window_counters(span, stats),
            }
            report["stop_phase"] = summary
            print("stop phase:", json.dumps(summary), flush=True)

        for w in range(args.windows):
            moves_before = len(admission.transitions)
            got, result, span, _r, _ws = session.window(args.seed + 1 + w, args.seconds, False)
            row = {
                "window": w, "attempted": got["attempted"], "failed": got["failed"],
                "kinds": result.get("kinds", {}),
                "transitions": len(admission.transitions) - moves_before,
                "server_pause_max_ms": session.pause["max_ms"],
                "generator_pause_max_ms": result["pause"]["max_ms"],
                "batcher": batcher_state(),
                **{k: got["values"].get(k) for k in
                   ("recommend_p50_ms", "recommend_p95_ms", "recommend_p99_ms")},
                **window_counters(span, stats),
            }
            report["windows"].append(row)
            print("window:", json.dumps(row), flush=True)
    finally:
        session.close()
    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"played_stop_{args.label}.json", "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (batcher, fronts) must not hold the exit
    os._exit(code)
