"""python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell: claim the chip (no chip, no run), build the cell's
model from the seed, start the real ServingLayer in this process with the
program's defaults, warm the shapes the traffic meets, drive the window
from a child process that never imports JAX, compare answers with the
plain reference, and print the one result line."""

from __future__ import annotations

import time

_T0 = time.time()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from types import SimpleNamespace  # noqa: E402

from benchmark import check, stats, spec as spec_mod  # noqa: E402
from benchmark.drivers import httpclient as hc  # noqa: E402

# A check request is asked for its answer, not for its time: one that a
# stall of the machine gets shed is asked again, this often at most.
CHECK_TRIES = 4
CHECK_RETRY_S = 0.5  # wait before the second try; twice that before the third, ...


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def claim_device(chips: int, require_chip: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_chip and (platform == "cpu" or len(devices) < chips):
        raise NoChip(
            f"cell needs {chips} accelerator chip(s); JAX gives {len(devices)} x {platform}. "
            "The benchmark never falls back to the CPU."
        )
    return {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}


def start_layer(root):
    """The real serving layer through its config path: HTTP front,
    admission, batcher and scan at the program's defaults, nothing pinned;
    only the model manager is the benchmark's."""
    from oryx_tpu.common import config as C
    from oryx_tpu.serving.layer import ServingLayer

    cfg = C.get_default().with_overlay(
        """
        oryx {
          id = "Benchmark"
          input-topic.broker = "inproc://benchmark"
          update-topic.broker = "inproc://benchmark"
          serving {
            api.port = 0
            api.read-only = true
            model-manager-class = "benchmark.manager:BenchModelManager"
            application-resources = "oryx_tpu.app.als.endpoints"
          }
        }
        """
    )
    layer = ServingLayer(cfg)
    layer.start()
    return layer


def counters() -> dict:
    from oryx_tpu.common import metrics

    return metrics.registry.snapshot()


def collections() -> list[int]:
    """Collections the cyclic collector has run so far, by generation."""
    return [g["collections"] for g in gc.get_stats()]


def ask(port: int, path_template: str, users, how_many: int, threads: int = 8) -> list[dict]:
    """Ask for each user in `users` over HTTP (a few at a time); returns
    [{"user", "body"}] for the answers that came back well-formed and at
    full quality. An answer that did not is asked for again, CHECK_TRIES
    times in all: these requests are compared, not timed."""

    def one(chunk):
        conn = hc.Connection("127.0.0.1", port, 600.0)
        out = []
        for u in chunk:
            for attempt in range(CHECK_TRIES):
                good, _kind, body = hc.judged_get(conn, path_template % u, how_many)
                if good:
                    out.append({"user": int(u), "body": body.decode()})
                    break
                time.sleep(CHECK_RETRY_S * (attempt + 1))
        conn.close()
        return out

    chunks = [users[i::threads] for i in range(threads)]
    with ThreadPoolExecutor(max_workers=threads) as pool:
        return [a for part in pool.map(one, chunks) for a in part]


def sleep_until(t_unix: float) -> None:
    d = t_unix - time.time()
    if d > 0:
        time.sleep(d)


class Session:
    """One set-up: the chip claimed, the cell's model built from the seed,
    the serving layer up with the model staged on the device, and every
    scan program the traffic meets compiled. `window` then drives one
    measured window; `run_cell` is one set-up and one window, the sweep
    (benchmark/sweep.py) is one set-up and many."""

    def __init__(
        self,
        spec: spec_mod.Spec,
        workload: str,
        seed: int,
        require_chip: bool = True,
        score_dtype: str | None = None,
        need_peaks: bool = False,
    ) -> None:
        import numpy as np

        self.spec = spec
        self.cell = cell = spec.cell(workload)
        self.seed = int(seed)
        self.device = claim_device(cell.chips, require_chip)
        # an unknown device is an error before the set-up, not after it
        self.peaks = spec.peaks(self.device["kind"]) if need_peaks else None
        self.builder = builder = spec_mod.load_module("builders", cell.config["builder"])
        self.traffic = traffic = cell.traffic
        self.how_many = how_many = int(traffic["how_many"])
        self.path = path = traffic["endpoints"][0]["path"]
        self.lines: list[str] = []

        # the bulk build makes millions of lists and sets that all live to
        # the end: the cyclic collector would walk them again and again
        # (it tripled the known-items fill) and find nothing. From here on
        # the program runs under the interpreter's default collector, as a
        # deployment's replica does: nothing is frozen. One full collection
        # now puts the model's objects into the oldest generation, where a
        # replica that has been up for a minute has them, so that every
        # run starts its window from the same collector state.
        gc.disable()
        try:
            self.built = built = builder.build(cell.config, seed, score_dtype=score_dtype)
        finally:
            gc.enable()
        self.timings = timings = dict(built.timings)
        t0 = time.perf_counter()
        gc.collect()
        timings["collect_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.layer = layer = start_layer(spec.root)
        try:
            layer.model_manager.model = built.model
            timings["layer_start_s"] = time.perf_counter() - t0

            # first request: the program packs its store, uploads the item
            # matrix and starts staging the user matrix; poll until
            # /recommend goes by device row index
            t0 = time.perf_counter()
            first = ask(layer.port, path, [0], how_many, threads=1)
            timings["first_request_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            while not builder.staged(built.model):
                if time.perf_counter() - t0 > 600:
                    raise RuntimeError("the user matrix was not staged within 600 s")
                time.sleep(0.25)
                ask(layer.port, path, [0], how_many, threads=1)
            timings["stage_users_s"] = time.perf_counter() - t0

            t0 = time.perf_counter()
            c0 = counters()
            builder.warm_scan_programs(
                built.model, traffic["warm_batch_buckets"], how_many,
                int(cell.config["known_items_per_user"]),
            )
            self.compiled = stats.counter_delta(c0, counters(), "jax.compile.seconds", "count")
            timings["warm_programs_s"] = time.perf_counter() - t0

            # answers asked before the window, judged after it
            t0 = time.perf_counter()
            rng = np.random.Generator(np.random.PCG64([self.seed, 21]))
            check_users = hc.power_law_users(
                rng, int(cell.config["users"]), float(traffic["users"]["exponent"]),
                int(traffic["check_users"]),
            ).tolist()
            self.answers = first + ask(layer.port, path, check_users, how_many)
            self.asked = 1 + len(check_users)
            timings["check_requests_s"] = time.perf_counter() - t0
        except BaseException:
            layer.close()
            raise

    def close(self) -> None:
        self.layer.close()

    def window(self, seed: int, seconds: float, trace: bool, cell_overrides: dict | None = None):
        """Drive one window from a child process. Returns (driver's
        readings, raw child result, counter spans, reduced trace or None,
        unix time the window started)."""
        cell, traffic = self.cell, self.traffic
        if cell_overrides:
            from dataclasses import replace

            cell = replace(
                cell,
                cell={**cell.cell, **cell_overrides.get("cell", {})},
                traffic={**cell.traffic, **cell_overrides.get("traffic", {})},
            )
            traffic = cell.traffic
        driver = spec_mod.load_module("drivers", traffic["driver"])
        warm = float(traffic["warm_seconds"])
        # a traced run keeps the same load up after the window and records
        # that tail, so the window's own latencies are taken with the
        # profiler off (starting and stopping it stalls this process for
        # some tenths of a second: call 1 of PR 23 read p99 164 ms around it)
        t_len = min(float(traffic["trace_seconds"]), seconds / 2.0) if trace else 0.0
        tail = t_len + 2.0 if trace else 0.0
        t0_unix = time.time() + 2.0  # the child needs a moment to start and connect
        params = driver.plan(cell, seed, seconds, "127.0.0.1", self.layer.port, t0_unix, tail)
        child = hc.start_child(traffic["driver"], params, self.spec.root)
        try:
            win_start, win_end = t0_unix + warm, t0_unix + warm + seconds
            watch = hc.PauseWatch(hc.Clock(t0_unix), warm, warm + seconds)
            sleep_until(win_start)
            span = {"window": None, "trace": None}
            before, gc_before = counters(), collections()
            sleep_until(win_end)
            span["window"] = (before, counters())
            self.pause = watch.reading()
            self.lines.append(
                "programs compiled in the window: %.0f (every bucket the mix can reach is "
                "warmed in set-up; anything but 0 is a fault of the harness)"
                % stats.counter_delta(*span["window"], "jax.compile.seconds", "count")
            )
            self.lines.append(
                "collector (interpreter's default, nothing frozen): collections in the window "
                "by generation %s" % [b - a for a, b in zip(gc_before, collections())]
            )
            reduced = None
            if trace:
                from benchmark import trace as trace_mod

                sleep_until(win_end + 0.5)
                planes, window_s, span["trace"] = trace_mod.record(
                    str(self.spec.root / ".bench_trace" / cell.name), t_len, counters
                )
                reduced = trace_mod.reduce_planes(planes, window_s)
            result = hc.finish_child(child, tail + float(traffic["timeout_s"]) + 60.0)
        except BaseException:
            child.kill()
            child.wait()
            raise
        return driver.reduce(result, traffic), result, span, reduced, win_start

    def memory_peak_bytes(self) -> int:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def judge(self, got: dict, result: dict) -> tuple[bool, list[str], dict]:
        """`correct` for one window: the answers asked before it and the
        seeded sample of its own answers against the plain reference.
        Returns (correct, lines, every number compared beside its limit)."""
        t0 = time.perf_counter()
        sampled = result.get("sampled", [])
        numbers = check.judge_answers(self.built, self.answers + sampled, self.how_many)
        correct, lines = check.verdict(numbers)
        # requests that failed (not 200, not `howMany` items, or a shed
        # stage) are counted in `failed` and weigh on the judged metric (the
        # open-loop tail charges each the client's timeout, the closed-loop
        # rate counts full-quality answers only); they do not decide
        # `correct`: a stall of the machine makes the program's overload
        # ladder shed the burst behind it, and its answers are not wrong
        lines.append(
            "window_failed_share = %.6g (%d of %d; in `failed` and in the judged metric, "
            "not in `correct`)"
            % (got["failed"] / max(got["attempted"], 1), got["failed"], got["attempted"])
        )
        lost = self.asked - len(self.answers)
        lines.append(
            "check: %d answers asked before the window (%d lost), %d sampled from the "
            "window; the reference took %.2f s"
            % (self.asked, lost, len(sampled), time.perf_counter() - t0)
        )
        compared = {
            name: {"value": numbers[name], "limit": limit} for name, limit in check.LIMITS.items()
        }
        compared["answers_lost"] = {"value": lost, "limit": 0}
        compared["window_answers_compared_min"] = {"value": len(sampled), "limit": 1}
        # a run that compared nothing of the window's own answers is not correct
        return correct and lost == 0 and len(sampled) > 0, lines, compared


def run_cell(
    spec: spec_mod.Spec,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    require_chip: bool = True,
    score_dtype: str | None = None,
) -> tuple[dict, list[str]]:
    """One set-up and one window. Returns (result object, lines to print
    before it)."""
    session = Session(spec, workload, seed, require_chip, score_dtype, need_peaks=trace)
    cell, lines = session.cell, session.lines
    try:
        got, result, span, reduced, win_start = session.window(seed, seconds, trace)
        peak = session.memory_peak_bytes()
    finally:
        session.close()
    setup_s = win_start - _T0
    lines.extend(got["lines"])
    pauses = {"server": session.pause, "generator": result["pause"]}
    lines.append(
        "longest pause in the window (a 5 ms sleep overslept): "
        + "; ".join(
            "%s process %.1f ms at %.1f s, %d over 20 ms" % (who, p["max_ms"], p["at_s"], p["over_20ms"])
            for who, p in pauses.items()
        )
    )
    if result.get("kinds"):
        lines.append(f"window failures by kind: {result['kinds']}")
    lines.append(
        "setup: "
        + ", ".join(f"{k[:-2]} {v:.2f} s" for k, v in session.timings.items())
        + f"; programs compiled while warming: {session.compiled:.0f}; total {setup_s:.2f} s "
        "(process start to window start; the warm phase at the cell's own load is in it, "
        "the reference is not)"
    )
    correct, check_lines, compared = session.judge(got, result)
    lines.extend(check_lines)

    values = dict(got["values"])
    values["setup_s"] = setup_s
    values["window_failed_pct"] = 100.0 * got["failed"] / max(got["attempted"], 1)
    values.update({f"{who}_pause_max_ms": p["max_ms"] for who, p in pauses.items()})
    metrics_out = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] not in values:
                # no answer of the window was good: no result line, and
                # what is known goes to stderr for whoever reads the failure
                raise RuntimeError(
                    f"the run produced no {m['name']}:\n" + "\n".join(lines)
                )
            metrics_out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = SimpleNamespace(
            cell=cell, loadgen=values, counters=span, trace=reduced, peaks=session.peaks,
            lines=lines,
        )
        for m in cell.per_layer:
            file = cell.layer_metrics[m["name"]]
            reader = spec_mod.load_module("reductions", file["reduction"])
            value = reader.read(ctx, file.get("args", {}))
            if value is not None:
                metrics_out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(session.device)
    device["memory_peak_bytes"] = peak
    out = {
        "correct": bool(correct),
        "attempted": got["attempted"],
        "failed": got["failed"],
        "metrics": metrics_out,
        "device": device,
    }
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["compared"] = compared  # last on the line: what a record of a failed run keeps
    return out, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--score-dtype", default=None,
        help="control only: serve the same factors from the program's bfloat16 or int8 "
        "item matrix; such a run must print correct: false",
    )
    args = ap.parse_args(argv)
    try:
        spec = spec_mod.Spec()
        result, lines = run_cell(
            spec, args.workload, args.seed, args.seconds, bool(args.trace),
            score_dtype=args.score_dtype,
        )
    except (NoChip, spec_mod.SpecError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    for line in lines:
        print(line)
    print(json.dumps(result), flush=True)
    # the numbers compared, each beside its limit, once more as the last
    # lines of standard error
    for line in lines:
        if line.startswith("check: "):
            print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (batcher, fronts) must not hold the exit
    os._exit(code)
