"""The twenty-four per-layer metrics that read the program's phase ledger,
sub-stage histograms and stall recorder, each on a recorded /metrics pair
(recorded_phase_metrics_{before,after}.txt, written from the program's own
metric classes) against the value worked out by hand.

Between the two scrapes the recorded server answered 200 requests on
connections that were already open (so 200 `between`), in 100 batches, its
watcher ticked 2,500 times over 51 s, and two stalls came. Per request, in
ms: between 62.5, read 0.5, pre 0.2, parse 2, queue 4, encode_wait 0.1,
encode 0.7, dispatch_wait 3, dispatch 5, device_wait 0.6, decode 0.4,
wake 5, respond 1, write 1.5; the handler's own timer ran 0.2 ms longer
than parse … respond (21.8 ms) each time. Per batch, in ms: dispatch.stage
0.2, .launch 4, .readback 0.6, decode.device_wait 0.15. The admission
path's rows (other numbers) are in both scrapes and must not be read.
"""

import pathlib

import pytest

from benchmark import prom
from benchmark.manifest import Manifest, reader_module
from benchmark.run import Context

HERE = pathlib.Path(__file__).resolve().parent

WORKED_OUT = {
    "between_ms": 62.5,
    "http_io_ms": 0.5 + 0.2 + 1.5,
    "handler_host_ms": 2.0 + 1.0,
    "wake_ms": 5.0,
    "pipeline_wait_ms": 0.1 + 3.0 + 0.6,
    "timer_accounted_share": 100.0 * 21.8 / 22.0,
    "dispatch_stage_ms": 0.2,
    "dispatch_launch_ms": 4.0,
    "dispatch_readback_ms": 0.6,
    "decode_device_wait_ms": 0.15,
    "interpreter_wait_ms": 1.0,                   # 2,500 ticks, 1 ms late each
    "stall_share": 100.0 * (0.3 + 0.21) / 51.0,   # a gc and a held interpreter
}
SOURCES = {name: "program_span" for name in WORKED_OUT}
SOURCES.update(interpreter_wait_ms="program_counter", stall_share="program_counter")
CELLS = {".saturate": ("synth-10k.sar-saturate", "decisions_per_s"),
         ".lone": ("selector-1k.sar-lone", "latency_p50_ms")}
NAMES = [base + suffix for base in WORKED_OUT for suffix in CELLS]


def context(stem: str) -> Context:
    c = Context()
    c.prom_before = prom.parse((HERE / f"{stem}_before.txt").read_text())
    c.prom_after = prom.parse((HERE / f"{stem}_after.txt").read_text())
    return c


def read(ctx, metric):
    spec = Manifest().metric_file(metric)
    return reader_module(spec["reader"]).read(ctx, spec["params"])


def check_phase_entries(m: Manifest) -> None:
    """Each of the twenty-four lists at least its first cell, and every
    cell it lists (a later PR appends its own) reports what it moves."""
    per_layer = {x["name"]: x for x in m.doc["per_layer"]}
    assert len(NAMES) == 24 and set(NAMES) <= set(per_layer)
    for name in NAMES:
        base, suffix = name.rsplit(".", 1)
        cell, moves = CELLS["." + suffix]
        entry = per_layer[name]
        assert entry["workloads"][0] == cell and entry["moves"] == moves
        for listed in entry["workloads"]:
            assert moves in {x["name"] for x in m.metrics_for(listed, "end_to_end")}, listed
        assert entry["source"] == SOURCES[base]
        assert entry["unit"] == ("%" if "share" in base else "ms")
        assert entry["better"] == ("higher" if base == "timer_accounted_share" else "lower")


def test_the_issue_s_twelve_names_in_both_forms_are_in_the_manifest():
    check_phase_entries(Manifest())


@pytest.mark.parametrize("metric", NAMES)
def test_a_phase_metric_reads_the_value_worked_out_by_hand(metric):
    want = WORKED_OUT[metric.rsplit(".", 1)[0]]
    assert read(context("recorded_phase_metrics"), metric) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("metric", NAMES)
def test_a_phase_metric_reads_nothing_from_a_server_without_the_family(metric):
    # the parent's exposition: recorded before this ledger existed
    got = read(context("recorded_metrics"), metric)
    if metric.startswith("timer_accounted_share"):
        # its denominator is the handler's timer, which that server has:
        # none of it is accounted for, and 0 % is what the reader says
        assert got == 0.0
    else:
        assert got is None
