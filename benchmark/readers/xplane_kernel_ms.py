"""Mean device milliseconds of one execution of the XLA modules that
``module`` (a regular expression) finds: one execution is one batch."""

from benchmark import xplane


def read(ctx, params):
    if ctx.trace is None:
        return None
    runs = xplane.module_runs(ctx.trace, params["module"])
    if not runs:
        return None
    return sum(runs) / len(runs) / 1e6
