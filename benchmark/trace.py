"""From a profiler trace to device numbers.

`record` runs the JAX profiler for a few seconds of the cell's load and returns
the device planes as plain lists; `reduce_planes` turns those into busy
seconds, idle gaps and per-operation time. The reduction works on the
plain form, so that a small recorded trace (benchmark/testdata/) checks
it without a chip."""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import time
from collections import defaultdict

# lines of a device plane that hold single operations; "Steps" and
# "XLA Modules" cover the same time at a coarser grain and would make
# every gap between operations of one program look busy
_OP_LINES = ("XLA Ops",)
_SKIP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
               "Framework Ops", "Source code")


def record(trace_dir: str, seconds: float, snapshot=None) -> tuple[list, float, tuple | None]:
    """Trace for `seconds`; returns (device planes in plain form, length
    of the traced window in seconds, what `snapshot()` gave right after
    the profiler had started and right before it was stopped, or None).
    Only the process that holds the chip can do this."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = None
    if hasattr(jax.profiler, "ProfileOptions"):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
    if options is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    else:
        jax.profiler.start_trace(trace_dir)
    # the window is the time the profiler was recording, not the half
    # second it takes to start and to stop (call 1 of PR 23: 4.66 s around
    # a 4 s trace read as 14 % idle where the device was 99.7 % busy), and
    # counters that are set against the trace's events are read over that
    # same stretch (read around this whole call they covered 4.8 s of load
    # against 4 s of passes: rows a pass came out a fifth too high)
    before = after = None
    t0 = time.perf_counter()
    try:
        if snapshot is not None:
            before = snapshot()
        time.sleep(seconds)
        if snapshot is not None:
            after = snapshot()
    finally:
        window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    span = (before, after) if after is not None else None
    return planes_from_xplane(files[-1]), window_s, span


def planes_from_xplane(path: str, device_only: bool = True) -> list:
    """[{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns]]}]}]"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if device_only and not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def save_planes(planes: list, path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(planes, f, separators=(",", ":"))


def load_planes(path: str) -> list:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def _op_events(plane: dict) -> list:
    named = [ln for ln in plane["lines"] if ln["name"] in _OP_LINES]
    lines = named or [ln for ln in plane["lines"] if ln["name"] not in _SKIP_LINES]
    return sorted((ev for ln in lines for ev in ln["events"] if ev[2] > 0), key=lambda e: e[1])


def reduce_planes(planes: list, window_s: float, top: int = 10) -> dict:
    """Busy seconds (union of the operations' intervals, averaged over the
    device planes that ran anything), per-operation seconds, and the
    longest idle gaps named by the operation that ran before them."""
    busy_per_plane = []
    op_seconds: dict[str, float] = defaultdict(float)
    op_count: dict[str, int] = defaultdict(int)
    gaps: list[tuple[str, float]] = []
    span_s = 0.0
    for plane in planes:
        events = _op_events(plane)
        if not events:
            continue
        span_s = max(span_s, (max(e[1] + e[2] for e in events) - events[0][1]) / 1e9)
        busy_ns = 0
        cur_start, cur_end = events[0][1], events[0][1] + events[0][2]
        last_name = "window_start"
        for name, start, dur in events:
            op_seconds[name] += dur / 1e9
            op_count[name] += 1
            if start > cur_end:
                busy_ns += cur_end - cur_start
                gaps.append((f"after_{last_name}", (start - cur_end) / 1e9))
                cur_start, cur_end = start, start + dur
            else:
                cur_end = max(cur_end, start + dur)
            last_name = name
        busy_ns += cur_end - cur_start
        busy_per_plane.append(busy_ns / 1e9)
    n = len(busy_per_plane)
    busy_s = sum(busy_per_plane) / n if n else 0.0
    # recording starts a little before start_trace returns: the window is
    # never shorter than the stretch the device events themselves cover
    window_s = max(window_s, span_s)
    device_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    gaps.sort(key=lambda kv: -kv[1])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "planes": n,
        "op_seconds": dict(op_seconds),
        "op_count": dict(op_count),
        "breakdown": {
            "device_ops": [[_short(k), v / max(n, 1)] for k, v in device_ops[:top]],
            "idle_gaps": [[_short(k), v] for k, v in gaps[:top]],
        },
    }


_HLO = re.compile(r"^%?(?P<name>[^ =]+) = (?P<shape>.*?) (?P<op>[a-z][a-z0-9\-]*)\(")


def _short(name: str) -> str:
    """An XLA op's HLO text as a short name: `closed_call.7 = (f32[8,32]{..},
    s32[8,32]{..}) custom-call(...)` -> closed_call.7_custom-call_f32_8_32_s32_8_32."""
    m = _HLO.match(name)
    if m:
        shape = re.sub(r"\{[^}]*\}", "", m.group("shape"))
        name = f"{m.group('name')}_{m.group('op')}_{shape}"
    name = re.sub(r"[^A-Za-z0-9.\-]+", "_", name).strip("_")
    return name[:64]


def matching(reduced: dict, pattern: str) -> tuple[int, float]:
    """(events, seconds) of the operations whose name holds `pattern`."""
    n = sum(c for k, c in reduced["op_count"].items() if pattern in k)
    s = sum(v for k, v in reduced["op_seconds"].items() if pattern in k)
    planes = max(reduced["planes"], 1)
    return n // planes, s / planes
