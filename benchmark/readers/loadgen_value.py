"""A number from the load generator's own summary of the window."""


def read(ctx, params):
    v = ctx.loadgen.get(params["key"])
    return None if v is None else float(v)
