"""The sharded scan in the device trace: a replica whose item matrix is
row-sharded over the host's chips runs one program a pass on every chip,
the scan kernel (ops named `oryx_topn_*`) over the chip's own rows and,
around it, the query gather, the all-gather of the candidates and the
merging top-k. The reduced trace sums an operation's time over the device
planes and counts the planes (benchmark/trace.py), so a time here is the
MEAN chip's; only `skew_pct` opens the recording itself for each plane's
own (as benchmark/reductions/trace_pass.py does, with its finder).

Stats (args {"stat": ...}):
  roofline_pct       least time of one chip's pass (`shard_least_seconds`)
                     over the mean chip's measured kernel time a pass; the
                     rows a pass are `serving.scan.sharded.queries` over
                     the traced slice / the passes counted in it (that
                     counter is fed beside the counters by submit kind:
                     summed with them, as trace_scan does, it would count
                     each row twice)
  merge_ms_per_pass  device time a pass of every operation that is not the
                     kernel, a chip: latency-bound, so milliseconds and no
                     share of a peak
  skew_pct           (slowest chip's kernel seconds - fastest's) / mean
"""

from benchmark import roofline, trace
from benchmark.reductions import trace_pass
from benchmark.stats import counter_delta

KERNEL = trace_pass.KERNEL
_SHARDED = "serving.scan.sharded.queries"


def shard_config(config: dict) -> dict:
    """The configuration one chip scans: its share of the item rows (the
    fullest shard's, rows split evenly) at the configuration's width and
    dtype. benchmark/roofline.py counts bytes and operations from it."""
    shards = int(config["shards"])
    return {**config, "items": -(-int(config["items"]) // shards)}


def shard_least_seconds(config: dict, rows: float, k: int, peaks: dict) -> tuple[float, str]:
    """(least seconds of one chip's pass, which bound applied): the
    shard's rows once at the logical width, their norms, the query rows
    and the [rows, k] candidates it hands to the merge, against ONE
    chip's peaks."""
    return roofline.scan_least_seconds(shard_config(config), rows, k, peaks)


def _kernel(ctx) -> tuple[int, float]:
    """(passes, mean chip's kernel seconds) of the traced slice."""
    if ctx.trace is None:
        return 0, 0.0
    return trace.matching(ctx.trace, KERNEL)


def _rows_per_pass(ctx, passes: int):
    span = ctx.counters.get("trace")
    if span is None or passes <= 0 or _SHARDED not in span[1]:
        return None
    return counter_delta(*span, _SHARDED) / passes


def kernel_seconds_by_plane(planes: list) -> list[float]:
    """Seconds of the named kernels on each device plane that ran any
    (planes in benchmark/trace.py's plain form)."""
    out = []
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        ns = sum(
            ev[2] for ln in plane["lines"] if ln["name"] == "XLA Ops"
            for ev in ln["events"] if KERNEL in ev[0]
        )
        if ns > 0:
            out.append(ns / 1e9)
    return out


def skew_pct(per_plane: list[float]):
    if len(per_plane) < 2:
        return None
    mean = sum(per_plane) / len(per_plane)
    return 100.0 * (max(per_plane) - min(per_plane)) / mean


def read(ctx, args: dict):
    stat = args["stat"]
    passes, seconds = _kernel(ctx)
    if passes <= 0:
        return None
    if stat == "roofline_pct":
        rows = _rows_per_pass(ctx, passes)
        if rows is None:
            return None
        k = int(args.get("k_bucket", 32))
        least, bound = shard_least_seconds(ctx.cell.config, rows, k, ctx.peaks)
        ctx.lines.append(
            "shard scan roofline: %d passes a chip on %d chips, %.4f ms a pass (mean chip), "
            "%.3f rows a pass, %d items a shard; least %.4f ms (%s-bound, one chip's peaks)"
            % (passes, ctx.trace["planes"], 1000.0 * seconds / passes, rows,
               shard_config(ctx.cell.config)["items"], 1000.0 * least, bound)
        )
        return 100.0 * least / (seconds / passes)
    if stat == "merge_ms_per_pass":
        planes = max(ctx.trace["planes"], 1)
        rest = sum(v for name, v in ctx.trace["op_seconds"].items() if KERNEL not in name)
        return 1000.0 * rest / planes / passes
    if stat == "skew_pct":
        planes = trace_pass._this_runs_planes(ctx)
        if planes is None:
            return None
        per_plane = kernel_seconds_by_plane(planes)
        ctx.lines.append(
            "shard scan kernel seconds by chip: " + ", ".join("%.4f" % s for s in per_plane)
        )
        return skew_pct(per_plane)
    raise ValueError(f"trace_shard: unknown stat {stat!r}")
