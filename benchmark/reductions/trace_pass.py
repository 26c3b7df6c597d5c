"""One pass (one device dispatch of the batcher) on the profiler's own
timeline: the program's host annotations beside the device's operations.

The program marks `serving.pass.submit` (dispatcher: slot held until the
host has submitted the pass; stats pass, rows, padded_rows) and
`serving.pass.wait` (completer: blocked on that pass's results; stat pass)
with `jax.profiler.TraceAnnotation`, and names its scan kernels
`oryx_topn_*`. Both land in the `.xplane.pb` that benchmark/trace.py
records: annotations on a host plane, kernels on the device plane's "XLA
Ops" line, one timeline. This reader opens that file itself (it is still on
disk when readers run): `.bench_trace/<cell>/` under the benchmark's own
checkout or the working directory, and only the recording whose named
kernels are the ones the run itself reduced (`ctx.trace`), by count and by
summed time, so an older trace of a same-named cell is never read for this
run. A harness run from a copy elsewhere (`Spec(root=...)`) finds nothing.

Pairing. The device runs passes in the order one dispatcher thread
submitted them, so the n-th scan op of the trace is pass number n + offset.
The offset comes from the waits: a wait ends when its pass's results are on
the host, so the last scan op that ended before a wait ended is that wait's
pass, and the offset is what most waits agree on. A pass is PAIRED when its
submit and its wait are both in the trace, the op's batch rows equal the
submit's `padded_rows`, the op did not start before its submit began and did
not end after its wait ended. Scan ops whose submit began before the trace
did, or whose wait ended after it, are the trace's EDGES and are counted
apart; every other scan op is paired or not.

Precision. The two planes are on one timeline, but the profiler aligns
the device's clock with the host's only to about a millisecond, anew in
every recording (PR 24, on a v5e: in one trace scans that found the device
idle started 0.5-1.0 ms BEFORE the host call that executed them, in the
next 0.3-0.7 ms after it). The error moves the device queue one way and the
result lag the other, so each is good to about +-1.5 ms and their sum with
the kernel time, host submit to results back, is exact. With the device
always busy no scan comes near its submit; after an idle stretch the skew
can make one look early, which is reported with the amount, not hidden.

One metric (args {"stat": "kernel_ms"}): the mean device time of the ops
named oryx_topn_*; None where the trace holds no named kernel or no
annotation (a program without them, a CPU trace). The two halves that the
skew above blurs are lines of the run's output, not metrics a PR is judged
on, until the clocks are calibrated:
  device queue  mean of (scan op start - end of its submit): time the pass
                sat behind the passes queued before it on the device
  result lag    mean of (end of its wait - scan op end): download of the
                results and the completer's wake-up
"""

from __future__ import annotations

import bisect
import glob
import math
import os
import re
from collections import Counter

from benchmark.spec import ROOT

KERNEL = "oryx_topn_"
SUBMIT, WAIT = "serving.pass.submit", "serving.pass.wait"
_OP_LINE = "XLA Ops"
# `%oryx_topn_scan.2 = (f32[8,32]{..}, ..` gives 8 batch rows; the
# candidates kernel's result is [grid, rows, k]
_SHAPE = re.compile(r"= \(?[a-z0-9]+\[([0-9,]+)\]")


def extract(path: str) -> list:
    """The planes of an .xplane.pb in benchmark/trace.py's plain form,
    cut to what this reader uses: the device planes' "XLA Ops" lines
    ([name, start_ns, dur_ns]) and, from the host planes, the two pass
    annotations ([name, start_ns, dur_ns, {stat: value}])."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device:
                if line.name != _OP_LINE:
                    continue
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)] for ev in line.events]
            else:
                events = [
                    [ev.name, int(ev.start_ns), int(ev.duration_ns),
                     {k: v for k, v in ev.stats if isinstance(v, (int, float, str))}]
                    for ev in line.events if ev.name in (SUBMIT, WAIT)
                ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def _batch_rows(op_name: str) -> int | None:
    m = _SHAPE.search(op_name)
    if not m:
        return None
    dims = [int(d) for d in m.group(1).split(",")]
    return dims[1] if "candidates" in op_name.split("=")[0] and len(dims) > 1 else dims[0]


def reduce(planes: list, top_gaps: int = 10) -> dict | None:
    """Pair the passes of one trace (see the module's docstring); None
    where there is nothing to pair."""
    ops: list = []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            ops = sorted(
                (ev for ln in plane["lines"] if ln["name"] == _OP_LINE for ev in ln["events"]
                 if ev[2] > 0),
                key=lambda e: e[1],
            )
            if any(KERNEL in ev[0] for ev in ops):
                break
    scans = [ev for ev in ops if KERNEL in ev[0]]
    host = [
        ev for plane in planes if plane["name"].startswith("/host:")
        for ln in plane["lines"] for ev in ln["events"] if len(ev) > 3 and "pass" in ev[3]
    ]
    submits = {int(ev[3]["pass"]): ev for ev in host if ev[0] == SUBMIT}
    waits = {int(ev[3]["pass"]): ev for ev in host if ev[0] == WAIT}
    if not scans or not submits or not waits:
        return None

    ends = [ev[1] + ev[2] for ev in scans]
    votes = Counter()
    for number, w in waits.items():
        j = bisect.bisect_right(ends, w[1] + w[2]) - 1
        if j >= 0:
            votes[number - j] += 1
    if not votes:
        return None
    offset = votes.most_common(1)[0][0]

    first_submit, last_wait = min(submits), max(waits)
    queue_ns, lag_ns, unpaired, edges, early = [], [], [], 0, 0
    for j, (name, start, dur) in enumerate(scans):
        number = j + offset
        if number < first_submit or number > last_wait:
            edges += 1
            continue
        s, w = submits.get(number), waits.get(number)
        if s is None or w is None:
            unpaired.append((number, "no submit" if s is None else "no wait"))
            continue
        if start < s[1]:
            early += 1
            unpaired.append(
                (number, "scan started %.3f ms before its submit began" % ((s[1] - start) / 1e6))
            )
        elif start + dur > w[1] + w[2]:
            unpaired.append((number, "scan ended after its wait"))
        elif _batch_rows(name) != int(s[3].get("padded_rows", -1)):
            unpaired.append((number, "rows %s, submit padded %s" % (_batch_rows(name),
                                                                    s[3].get("padded_rows"))))
        else:
            queue_ns.append(start - (s[1] + s[2]))
            lag_ns.append(w[1] + w[2] - (start + dur))
    paired = len(queue_ns)

    # the longest idle gaps of the device, named by what the dispatcher
    # and the completer were in while the device rested
    gaps = []
    cur_end = ops[0][1] + ops[0][2]
    for _name, start, dur in ops[1:]:
        if start > cur_end:
            gaps.append((start - cur_end, cur_end, start))
        cur_end = max(cur_end, start + dur)
    gaps.sort(reverse=True)

    def covers(events, lo, hi):
        return any(ev[1] < hi and ev[1] + ev[2] > lo for ev in events)

    named = []
    for length, lo, hi in gaps[:top_gaps]:
        if covers(submits.values(), lo, hi):
            where = SUBMIT
        elif covers(waits.values(), lo, hi):
            where = WAIT + " only"
        else:
            where = "neither"
        named.append((length / 1e3, where))
    return {
        "scans": len(scans),
        "paired": paired,
        "unpaired": unpaired,
        "edges": edges,
        "early": early,
        "offset": offset,
        "kernel_ms": sum(ev[2] for ev in scans) / len(scans) / 1e6,
        "device_queue_ms": sum(queue_ns) / paired / 1e6 if paired else None,
        "result_lag_ms": sum(lag_ns) / paired / 1e6 if paired else None,
        "gaps_us": named,
    }


def _candidates(cell_name: str) -> list[str]:
    """The recordings of a cell of this name, newest first."""
    files = {
        os.path.realpath(f)
        for root in (str(ROOT), os.getcwd())
        for f in glob.glob(
            os.path.join(root, ".bench_trace", cell_name, "plugins", "profile", "*", "*.xplane.pb")
        )
    }
    return sorted(files, key=os.path.getmtime, reverse=True)


def _same_recording(planes: list, run: dict) -> bool:
    """Are these the planes the run reduced? Its named kernels, by count
    and by summed device time, are what `benchmark/trace.py` put into
    `op_count` / `op_seconds` from the file it had just recorded."""
    events = [
        ev for plane in planes if plane["name"].startswith("/device:")
        for ln in plane["lines"] if ln["name"] == _OP_LINE
        for ev in ln["events"] if KERNEL in ev[0] and ev[2] > 0
    ]
    count = sum(c for name, c in run.get("op_count", {}).items() if KERNEL in name)
    seconds = sum(v for name, v in run.get("op_seconds", {}).items() if KERNEL in name)
    return bool(events) and count == len(events) and math.isclose(
        seconds, sum(ev[2] for ev in events) / 1e9, rel_tol=1e-9
    )


def _this_runs_planes(ctx) -> list | None:
    if ctx.trace is None:
        return None
    for path in _candidates(ctx.cell.name):
        planes = extract(path)
        if _same_recording(planes, ctx.trace):
            return planes
    return None


def _reduced(ctx) -> dict | None:
    """Parse and pair once a run, however many metrics read it; the lines
    go to the run's output the first time."""
    if hasattr(ctx, "trace_pass"):
        return ctx.trace_pass
    ctx.trace_pass = None
    planes = _this_runs_planes(ctx)
    if planes is None:
        return None
    r = ctx.trace_pass = reduce(planes)
    if r is None:
        return None
    judged = r["paired"] + len(r["unpaired"])
    ctx.lines.append(
        "trace_pass: %d scan ops named %s*, %d paired with their submit and wait, %d not "
        "(%.1f %% of %d paired), %d at the trace's edges, %d started before their submit; "
        "the first scan op is pass %d"
        % (r["scans"], KERNEL, r["paired"], len(r["unpaired"]),
           100.0 * r["paired"] / max(judged, 1), judged, r["edges"], r["early"], r["offset"])
    )
    for number, why in r["unpaired"][:10]:
        ctx.lines.append(f"trace_pass: pass {number} not paired: {why}")
    ctx.lines.append(
        "trace_pass: longest device idle gaps, us, and what the dispatcher / completer were "
        "in: " + "; ".join("%.1f %s" % g for g in r["gaps_us"])
    )
    if r["paired"]:
        ctx.lines.append(
            "trace_pass: of a paired pass's time from submitted to results on the host, mean ms: "
            "device queue %.3f, kernel %.3f, result lag %.3f (queue and lag each good to about "
            "+-1.5 ms, the profiler's alignment of the two clocks; their sum with the kernel is exact)"
            % (r["device_queue_ms"], r["kernel_ms"], r["result_lag_ms"])
        )
    return r


def read(ctx, args: dict):
    stat = args["stat"]
    if stat != "kernel_ms":
        raise ValueError(f"trace_pass: unknown stat {stat!r}")
    r = _reduced(ctx)
    return None if r is None else r[stat]
