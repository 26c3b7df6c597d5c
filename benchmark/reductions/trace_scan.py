"""The serving scan in the device trace: the events whose name holds
args["match"] (until the program names its kernels, the XLA op name of a
Pallas call). Stats: `ms_per_pass` (mean device time of one pass),
`rows_per_pass` (the program's scan-query counters over the traced slice,
over the passes counted in it), `roofline_pct` (the least time a pass
could take, benchmark/roofline.py, over the measured mean)."""

from benchmark import roofline, trace
from benchmark.stats import counter_delta

_QUERY_COUNTERS = (
    "serving.scan.indexed.queries",
    "serving.scan.vector.queries",
    "serving.scan.sharded.queries",
)


def _rows_per_pass(ctx, passes: int):
    span = ctx.counters.get("trace")
    if span is None or passes <= 0:
        return None
    before, after = span
    return sum(counter_delta(before, after, m) for m in _QUERY_COUNTERS) / passes


def read(ctx, args: dict):
    if ctx.trace is None:
        return None
    passes, seconds = trace.matching(ctx.trace, args["match"])
    if passes <= 0:
        return None
    stat = args["stat"]
    if stat == "ms_per_pass":
        return 1000.0 * seconds / passes
    rows = _rows_per_pass(ctx, passes)
    if stat == "rows_per_pass":
        return rows
    if stat == "roofline_pct":
        if rows is None:
            return None
        k = int(args.get("k_bucket", 32))
        least, bound = roofline.scan_least_seconds(ctx.cell.config, rows, k, ctx.peaks)
        ctx.lines.append(
            "scan roofline: %d passes, %.4f ms a pass, %.3f rows a pass; least %.4f ms "
            "(%s-bound, benchmark/roofline.py)" % (passes, 1000.0 * seconds / passes, rows,
                                                    1000.0 * least, bound)
        )
        return 100.0 * least / (seconds / passes)
    raise ValueError(f"trace_scan: unknown stat {stat!r}")
