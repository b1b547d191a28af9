"""Change of one or more /metrics series over the window, over the change
of another, times ``scale``: a mean per request, per batch or per row."""

from benchmark import prom


def read(ctx, params):
    den = prom.delta(ctx.prom_before, ctx.prom_after, params["den"])
    if den <= 0:
        return None
    num = prom.delta(ctx.prom_before, ctx.prom_after, params["num"])
    return num / den * params.get("scale", 1.0)
