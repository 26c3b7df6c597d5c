"""Device busy / idle share of the traced slice (args: {"stat": "idle_pct"})."""


def read(ctx, args: dict):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    if args["stat"] == "idle_pct":
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    raise ValueError(f"trace_device: unknown stat {args['stat']!r}")
