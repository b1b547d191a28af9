"""The three per-layer metrics that read cedar_batch_lingers_total
(benchmark/metrics/linger_share.{lone,admission,saturate}.json): each on an
exposition written by the program's own metric class against the share
worked out by hand, each reading nothing from a server that has neither
family (tests/benchmark_tests/recorded_metrics_{before,after}.txt), and each
held to the manifest's checks. They stand beside tests/test_claim_metrics.py
for its reasons: a PR that claims a gain edits no file of the benchmark's
own tests, and the admission path's metric is ``.admission``, not ``.admit``.

The reader is ``prom_delta_ratio`` over cedar_batch_claims_total, which the
program has had since PR 31: a server that claims batches and has no
lingers family (PR 34's) reads 0 here, not nothing — its claims are counted
and no linger is. On such a server every lone claim slept out the window;
the 0 says only that nothing counted it.
"""

import pytest
from test_claim_metrics import RECORDED, read

from benchmark import prom
from benchmark.manifest import Manifest, validate
from benchmark.run import Context
from cedar_tpu.server.metrics import Counter

# metric -> (path it reads, the end-to-end metric it moves, cells)
METRICS = {
    "linger_share.lone": (
        "authorization", "latency_p50_ms",
        ["selector-1k.sar-lone", "synth-10k.sar-lone"]),
    "linger_share.admission": (
        "admission", "latency_p50_ms", ["pss-admit.admit-lone"]),
    "linger_share.saturate": (
        "authorization", "decisions_per_s", ["synth-10k.sar-saturate"]),
}
# between the two scrapes, by path: (claims held, claims not held, lingers)
WINDOW = {"authorization": (150, 50, 9), "admission": (0, 40, 0)}


def exposition(scale, lingers=True):
    claims = Counter("cedar_batch_claims_total", "claims", ["path", "held"])
    lingered = Counter("cedar_batch_lingers_total", "lingers", ["path"])
    for path, (yes, no, slept) in WINDOW.items():
        # a server that had claimed, and lingered, before the window opened
        claims.inc(7 + scale * yes, path=path, held="yes")
        claims.inc(3 + scale * no, path=path, held="no")
        if slept:
            lingered.inc(2 + scale * slept, path=path)
    lines = claims.collect() + (lingered.collect() if lingers else [])
    return prom.parse("\n".join(lines))


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_linger_metric_reads_the_share_worked_out_by_hand(metric):
    ctx = Context()
    ctx.prom_before, ctx.prom_after = exposition(0), exposition(1)
    yes, no, slept = WINDOW[METRICS[metric][0]]
    # authorization: 9 of 200 claims, 4.5 %; admission: none of 40
    assert read(ctx, metric) == pytest.approx(100.0 * slept / (yes + no))


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_linger_metric_reads_nothing_from_a_server_without_the_counters(metric):
    ctx = Context()
    ctx.prom_before = prom.parse((RECORDED / "recorded_metrics_before.txt").read_text())
    ctx.prom_after = prom.parse((RECORDED / "recorded_metrics_after.txt").read_text())
    assert read(ctx, metric) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_linger_metric_reads_zero_where_claims_are_counted_and_lingers_are_not(metric):
    ctx = Context()
    ctx.prom_before = exposition(0, lingers=False)
    ctx.prom_after = exposition(1, lingers=False)
    assert read(ctx, metric) == 0.0


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_linger_metric_is_the_entry_the_issue_asked_for(metric):
    m = Manifest()
    assert validate(m) == []
    entry = next(x for x in m.doc["per_layer"] if x["name"] == metric)
    path, moves, cells = METRICS[metric]
    assert entry == {
        "name": metric, "unit": "%", "better": "lower",
        "source": "program_counter",
        "layer": "batch forming engine/batcher.py",
        "moves": moves, "workloads": cells,
    }
    spec = m.metric_file(metric)
    assert {k: spec[k] for k in entry} == entry
    assert spec["reader"] == "prom_delta_ratio"
    assert spec["params"] == {
        "num": {"name": "cedar_batch_lingers_total", "labels": {"path": path}},
        "den": {"name": "cedar_batch_claims_total", "labels": {"path": path}},
        "scale": 100,
    }
    for cell in cells:
        assert moves in {x["name"] for x in m.metrics_for(cell, "end_to_end")}
        assert metric in {x["name"] for x in m.metrics_for(cell, "per_layer")}
    # no `.reask` twin: a cache hit never reaches the batcher
    assert "linger_share.reask" not in {x["name"] for x in m.doc["per_layer"]}
    # the three stand together where PR 35 appended them, after everything
    # that was there then: later entries come after them
    names = [x["name"] for x in m.doc["per_layer"]]
    at = names.index("linger_share.lone")
    assert names[at:at + 3] == [
        "linger_share.lone", "linger_share.admission", "linger_share.saturate"]
    assert names[at - 1] == "scan_read_share.reask"


def test_the_program_counts_a_linger_under_the_name_the_metrics_read():
    from cedar_tpu.server.metrics import REGISTRY, record_batch_linger

    record_batch_linger("linger-metrics-test")
    record_batch_linger("linger-metrics-test")
    samples = prom.parse(REGISTRY.expose())
    assert prom.total(
        samples, "cedar_batch_lingers_total", {"path": "linger-metrics-test"}
    ) == 2.0
