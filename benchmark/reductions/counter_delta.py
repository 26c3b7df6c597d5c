"""How far one counter of the program's metrics registry moved over the
window (or over the traced slice): args {"metric": [metric, field],
"scale": x, "span": "window"|"trace"}. Unlike `counter_ratio` with no
"den", a program whose registry holds no such metric reads nothing, not
0: a counter the program takes a handle to is in every snapshot from the
start, so absent means the program does not count this."""


from benchmark.stats import counter_delta


def read(ctx, args: dict):
    span = ctx.counters.get(args.get("span", "window"))
    if span is None:
        return None
    before, after = span
    metric, field = args["metric"]
    if metric not in after:
        return None
    return float(args.get("scale", 1.0)) * counter_delta(before, after, metric, field)
