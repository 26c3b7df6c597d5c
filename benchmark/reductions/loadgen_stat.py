"""A statistic the load generator's own clock gives (args: {"stat": name})."""


def read(ctx, args: dict):
    return ctx.loadgen.get(args["stat"])
