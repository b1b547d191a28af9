"""The three per-layer metrics that read cedar_batch_claims_total
(benchmark/metrics/claim_held_share.{saturate,lone,admission}.json): each on an
exposition written by the program's own metric class against the share
worked out by hand, each reading nothing from the parent's exposition
(tests/benchmark_tests/recorded_metrics_{before,after}.txt, recorded before
the counter existed), and each held to the manifest's checks. The
benchmark's own test files are not edited by a PR that claims a gain, so
this file stands beside them — and the admission path's metric is named
``.admission``, not ``.admit``, because
tests/benchmark_tests/test_benchmark_admission.py holds the names ending in
``.admit`` that the admission cell lists to exactly PR 30's nineteen.
"""

import pathlib

import pytest

from benchmark import prom
from benchmark.manifest import Manifest, reader_module, validate
from benchmark.run import Context
from cedar_tpu.server.metrics import Counter

RECORDED = pathlib.Path(__file__).resolve().parent / "benchmark_tests"

# metric -> (path it reads, the end-to-end metric it moves, better, cells)
METRICS = {
    "claim_held_share.saturate": (
        "authorization", "decisions_per_s", "higher",
        ["synth-10k.sar-saturate"]),
    "claim_held_share.lone": (
        "authorization", "latency_p50_ms", "lower",
        ["selector-1k.sar-lone", "synth-10k.sar-lone"]),
    "claim_held_share.admission": (
        "admission", "latency_p50_ms", "lower", ["pss-admit.admit-lone"]),
}
# claims between the two scrapes, by path: (held yes, held no)
WINDOW = {"authorization": (190, 10), "admission": (0, 40)}


def exposition(scale):
    c = Counter("cedar_batch_claims_total", "claims", ["path", "held"])
    for path, (yes, no) in WINDOW.items():
        # a server that had claimed before the window opened
        c.inc(7 + scale * yes, path=path, held="yes")
        c.inc(3 + scale * no, path=path, held="no")
    return prom.parse("\n".join(c.collect()))


def read(ctx, metric):
    spec = Manifest().metric_file(metric)
    return reader_module(spec["reader"]).read(ctx, spec["params"])


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_claim_metric_reads_the_share_worked_out_by_hand(metric):
    ctx = Context()
    ctx.prom_before, ctx.prom_after = exposition(0), exposition(1)
    yes, no = WINDOW[METRICS[metric][0]]
    assert read(ctx, metric) == pytest.approx(100.0 * yes / (yes + no))


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_claim_metric_reads_nothing_from_a_server_without_the_counter(metric):
    ctx = Context()
    ctx.prom_before = prom.parse((RECORDED / "recorded_metrics_before.txt").read_text())
    ctx.prom_after = prom.parse((RECORDED / "recorded_metrics_after.txt").read_text())
    assert read(ctx, metric) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_claim_metric_is_the_entry_the_issue_asked_for(metric):
    m = Manifest()
    assert validate(m) == []
    entry = next(x for x in m.doc["per_layer"] if x["name"] == metric)
    path, moves, better, cells = METRICS[metric]
    assert entry == {
        "name": metric, "unit": "%", "better": better,
        "source": "program_counter",
        "layer": "batch forming engine/batcher.py",
        "moves": moves, "workloads": cells,
    }
    spec = m.metric_file(metric)
    assert {k: spec[k] for k in entry} == entry
    assert spec["reader"] == "prom_delta_share"
    assert spec["params"]["part"]["labels"] == {"path": path, "held": "yes"}
    assert spec["params"]["total"]["labels"] == {"path": path}
    for cell in cells:
        assert moves in {x["name"] for x in m.metrics_for(cell, "end_to_end")}
        assert metric in {x["name"] for x in m.metrics_for(cell, "per_layer")}
    # the three stand together where PR 31 appended them, after everything
    # that was there then: later entries come after them
    names = [x["name"] for x in m.doc["per_layer"]]
    at = names.index("claim_held_share.saturate")
    assert names[at:at + 3] == [
        "claim_held_share.saturate", "claim_held_share.lone",
        "claim_held_share.admission"]
    assert names[at - 1] == "flagged_row_share.admit"
