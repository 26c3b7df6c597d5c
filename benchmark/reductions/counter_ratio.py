"""A ratio of counter deltas of the program's metrics registry over the
window (or over the traced slice): args {"num": [[metric, field]...],
"den": [[metric, field]...], "scale": x, "span": "window"|"trace"}.
`field` is `value` for a counter, `sum` or `count` for a histogram.
With no "den" the reading is the scaled sum of the deltas itself."""


from benchmark.stats import counter_delta


def read(ctx, args: dict):
    span = ctx.counters.get(args.get("span", "window"))
    if span is None:
        return None
    before, after = span
    num = sum(counter_delta(before, after, m, f) for m, f in args["num"])
    if "den" not in args:
        return num * float(args.get("scale", 1.0))
    den = sum(counter_delta(before, after, m, f) for m, f in args["den"])
    if den <= 0:
        return None
    return float(args.get("scale", 1.0)) * num / den
