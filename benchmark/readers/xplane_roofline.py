"""The match kernel's share (%) of its roofline: the least time the chip
could take for a batch (benchmark/kernels.py, from L, R and the batch's
rows, against the peaks of the device the run reports) over the mean device
time of one execution of the module. Memory bounds it at served batch
sizes: the rule plane is read once per batch."""

from benchmark import kernels, prom, xplane


def read(ctx, params):
    if ctx.trace is None:
        return None
    runs = xplane.module_runs(ctx.trace, params["module"])
    eng = ctx.engine_after.get(params.get("engine", "authorization")) or {}
    L, R = eng.get("L"), eng.get("R")
    if not runs or not L or not R:
        return None
    batches = prom.delta(ctx.prom_before, ctx.prom_after, params["batches"])
    rows = prom.delta(ctx.prom_before, ctx.prom_after, params["rows"])
    mean_rows = rows / batches if batches > 0 else 1.0
    bucket = 1
    while bucket < mean_rows:
        bucket *= 2
    least = kernels.match_least_seconds(
        int(L), int(R), bucket, kernels.peaks(ctx.device["kind"]),
        weight_bytes=int(params.get("weight_bytes", 1)),
    )
    return 100.0 * least["seconds"] / (sum(runs) / len(runs) / 1e9)
