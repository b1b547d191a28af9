"""The per-layer metrics that read a launch's counters and a flagged row's
bits, and the fold of the lone cells' twins into the `.lone` entries.

Each new entry is a data file over a reader that was there; here each is
held to its specification, read on an exposition written by the program's
own metric functions against the number worked out by hand, and read on the
exposition of a server from before the families
(``recorded_metrics_{before,after}.txt``)."""

import pathlib

import pytest

from benchmark import prom
from benchmark.manifest import Manifest, reader_module, validate
from benchmark.run import Context

HERE = pathlib.Path(__file__).resolve().parent

LONE = ["selector-1k.sar-lone", "synth-10k.sar-lone", "pss-admit.admit-lone",
        "rbac-tenants.sar-reask-lone", "rbac-oidc-groups.sar-groups-lone"]
SATURATE = ["synth-10k.sar-saturate"]
SAR_LONE = ["selector-1k.sar-lone", "synth-10k.sar-lone", "rbac-oidc-groups.sar-groups-lone"]
DISPATCH = "host dispatch engine/evaluator.py"
STALL = "server process cedar_tpu/obs/stall.py"
CODEC = "encode and decode native/encoder.cpp, engine/fastpath.py"
BATCHES = {"name": "cedar_batch_occupancy_count"}


def launch(name, unit, family, layer=DISPATCH, p95=False, scale=None):
    """A launch counter over the batches, with no path label: one entry for
    the five lone cells and a `.saturate` twin."""
    params = {"num": {"name": family}, "den": BATCHES}
    if scale:
        params["scale"] = scale
    lone = ("latency_p95_ms" if p95 else "latency_p50_ms", LONE)
    return {name: (unit, "lower", layer, *lone, "prom_delta_ratio", params),
            name + ".saturate": (unit, "lower", layer, "decisions_per_s", SATURATE,
                                 "prom_delta_ratio", params)}


def share(family, path, **part):
    return {"part": {"name": family, "labels": dict(part, path=path)},
            "total": {"name": family, "labels": {"path": path}}}


# name -> (unit, better, layer, moves, cells, reader, params)
NEW = {
    **launch("uploads_per_batch", "arrays", "cedar_launch_uploads_total"),
    **launch("upload_bytes_per_batch", "B", "cedar_launch_upload_bytes_total"),
    **launch("readback_bytes_per_batch", "B", "cedar_launch_readback_bytes_total"),
    **launch("long_device_waits_per_kbatch", "per_kbatch", "cedar_long_device_waits_total",
             layer=STALL, p95=True, scale=1000),
    "flagged_row_share.lone": (
        "%", "lower", CODEC, "latency_p50_ms", SAR_LONE, "prom_delta_share",
        share("cedar_authorizer_row_routing_total", "authorization", row_class="flagged")),
    "bits_readback_share.lone": (
        "%", "higher", CODEC, "latency_p50_ms", SAR_LONE, "prom_delta_share",
        share("cedar_flagged_bits_total", "authorization", by="readback")),
    "bits_readback_share.admit": (
        "%", "higher", CODEC, "latency_p50_ms", ["pss-admit.admit-lone"], "prom_delta_share",
        share("cedar_flagged_bits_total", "admission", by="readback")),
}

# a window of 50 authorization and 30 admission batches
WORKED_OUT = {
    "uploads_per_batch": (50 * 4 + 30 * 4) / 80,
    "upload_bytes_per_batch": (50 * 96 + 30 * 400) / 80,
    "readback_bytes_per_batch": (50 * 300 + 30 * 2000) / 80,
    "long_device_waits_per_kbatch": 1000 * 2 / 80,
    "flagged_row_share.lone": 100 * 20 / 80,
    "bits_readback_share.lone": 100 * 18 / 20,
    "bits_readback_share.admit": 100.0,
}

# the twins folded into their `.lone` entry, which lists their cell instead,
# by that cell and its suffix
FOLDED = {
    "rbac-oidc-groups.sar-groups-lone": (
        "groups", ["decode_us_per_row", "device_idle_share", "device_ms_per_batch",
                   "dispatch_launch_ms", "dispatch_ms_per_batch", "handler_host_ms",
                   "http_io_ms", "ingress_ms"]),
    "rbac-tenants.sar-reask-lone": (
        "reask", ["between_ms", "handler_host_ms", "http_io_ms", "ingress_ms",
                  "timer_accounted_share"]),
    "pss-admit.admit-lone": ("admit", ["device_idle_share", "device_ms_per_batch"]),
}
FOLDS = [(cell, suffix, base) for cell, (suffix, bases) in FOLDED.items() for base in bases]


def read(ctx, name):
    spec = Manifest().metric_file(name)
    return reader_module(spec["reader"]).read(ctx, spec["params"])


@pytest.fixture(scope="module")
def window():
    from cedar_tpu.server import metrics as pm

    def scrape():
        return prom.parse(pm.REGISTRY.expose())

    ctx = Context()
    ctx.prom_before = scrape()
    for path, batches, upload_bytes, readback_bytes in (
            ("authorization", 50, 96, 300), ("admission", 30, 400, 2000)):
        for _ in range(batches):
            pm.record_batch_occupancy(path, 1)
            pm.record_launch_io(path, 4, upload_bytes, readback_bytes)
    pm.record_long_device_wait("authorization", "off")
    pm.record_long_device_wait("admission", "on")
    pm.record_row_routing("authorization", "flagged", 20)
    pm.record_row_routing("authorization", "clean_native", 60)
    pm.record_row_routing("admission", "flagged", 10)
    pm.record_flagged_bits("authorization", "readback", 18)
    pm.record_flagged_bits("authorization", "second_call", 2)
    pm.record_flagged_bits("admission", "readback", 10)
    ctx.prom_after = scrape()
    return ctx


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_entry_is_the_one_specified(name):
    m = Manifest()
    assert validate(m) == []
    unit, better, layer, moves, cells, reader, params = NEW[name]
    entry = next(x for x in m.doc["per_layer"] if x["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better, "source": "program_counter",
                     "layer": layer, "moves": moves, "workloads": cells}
    spec = m.metric_file(name)
    assert (spec["reader"], spec["params"]) == (reader, params)
    for cell in cells:
        assert moves in {x["name"] for x in m.metrics_for(cell, "end_to_end")}
        assert name in {x["name"] for x in m.metrics_for(cell, "per_layer")}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_entry_reads_the_number_worked_out_by_hand(window, name):
    assert read(window, name) == pytest.approx(WORKED_OUT[name.replace(".saturate", "")])


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_entry_on_a_server_from_before_the_families(name):
    """A launch counter reads 0, not nothing, where the family is absent and
    the batches are counted (as ``linger_share.*`` do); a share of a family
    that is absent reads nothing; the row routing was always there."""
    ctx = Context()
    ctx.prom_before = prom.parse((HERE / "recorded_metrics_before.txt").read_text())
    ctx.prom_after = prom.parse((HERE / "recorded_metrics_after.txt").read_text())
    got = read(ctx, name)
    if name.startswith("bits_readback_share"):
        assert got is None
    elif name == "flagged_row_share.lone":
        # the recorded window: one flagged row of 1,196 (flagged 1 -> 2,
        # clean_native 1,060 -> 2,255)
        assert got == pytest.approx(100 * 1 / 1196)
    else:
        assert got == 0.0


@pytest.mark.parametrize("cell,suffix,base", FOLDS)
def test_a_folded_twin_is_gone_and_its_lone_entry_lists_the_cell(cell, suffix, base):
    m = Manifest()
    names = {x["name"] for x in m.doc["per_layer"]}
    assert f"{base}.{suffix}" not in names
    assert not (m.dir / "metrics" / f"{base}.{suffix}.json").exists()
    lone = m.metric_file(f"{base}.lone")
    assert cell in lone["workloads"]
    # the cells in the order BENCHMARK.json lists them
    order = [w["name"] for w in m.doc["workloads"]]
    assert lone["workloads"] == sorted(lone["workloads"], key=order.index)
    assert f"{base}.lone" in {x["name"] for x in m.metrics_for(cell, "per_layer")}


def test_the_manifest_and_the_doors_entries_fit_under_the_limit():
    m = Manifest()
    door = list((HERE / "door" / "benchmark" / "metrics").glob("*.json"))
    assert len(m.doc["per_layer"]) + len(door) <= 128
    # the one twin of the sixteen that stays: tests beside the program's
    # read its place in the list
    assert "scan_read_share.reask" in {x["name"] for x in m.doc["per_layer"]}
