"""Where one field of one metric of the program's registry stands at the
END of the window (or of the traced slice), not how far it moved: args
{"metric": [metric, field], "scale": x, "span": "window"|"trace"}. For
what happens once, before any span starts (the staging of the user
matrix in set-up), where a delta reads 0. As in `counter_delta`, a
program whose registry holds no such metric reads nothing, not 0."""


def read(ctx, args: dict):
    span = ctx.counters.get(args.get("span", "window"))
    if span is None:
        return None
    _before, after = span
    metric, field = args["metric"]
    value = (after.get(metric) or {}).get(field)
    if value is None:
        return None
    return float(args.get("scale", 1.0)) * float(value)
