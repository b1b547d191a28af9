"""Share (%) of the traced window in which no operation ran on the device."""

from benchmark import xplane


def read(ctx, params):
    if ctx.trace is None:
        return None
    return xplane.idle_share(ctx.trace, ctx.trace_window_s)
