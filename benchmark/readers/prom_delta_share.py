"""Share (%) that some /metrics series' change over the window has of
others' change: rows that fell back of all rows, hits of lookups."""

from benchmark import prom


def read(ctx, params):
    total = prom.delta(ctx.prom_before, ctx.prom_after, params["total"])
    if total <= 0:
        return None
    return 100.0 * prom.delta(ctx.prom_before, ctx.prom_after, params["part"]) / total
