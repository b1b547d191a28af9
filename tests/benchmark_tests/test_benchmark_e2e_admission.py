"""The cell pss-admit.admit-lone end to end on the CPU rehearsal: the
webhook's second endpoint through every phase of a run — corpus from the
seed, the server child, AdmissionReviews over HTTPS on /v1/admit, every
answer against the ``admission`` kind's reference — at a tenth of the
tenancy (``tenants`` 30) and with a pool of bodies wider than the mix's,
which only a copy of the data files can state."""

import json
import pathlib
import shutil

from test_benchmark_e2e_rehearsal import ROOT, run_cell

CELL = "pss-admit.admit-lone"


def small_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A copy of BENCHMARK.json and the data files with the configuration's
    tenancy cut to 30 and the pool widened (the code is the package's own:
    ``--root`` adds data). A CPU server over a tenth of the tenancy has no
    launch to pay and has answered some 330 requests a second, where the
    mix's pool is sized to the chip's server; 600 keeps it clear of that."""
    tmp_path.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(ROOT / "benchmark" / sub, tmp_path / "benchmark" / sub)
    cfg = tmp_path / "benchmark" / "configs" / "pss-admit.json"
    doc = json.loads(cfg.read_text())
    doc["corpus"]["params"]["tenants"] = 30
    cfg.write_text(json.dumps(doc))
    mix = tmp_path / "benchmark" / "traffic" / "admit-lone.json"
    doc = json.loads(mix.read_text())
    doc["pool_per_s"] = doc["precompute_per_s"] = 600
    mix.write_text(json.dumps(doc))
    return tmp_path


def test_the_admission_cell_runs_every_phase_and_every_answer_is_the_references(tmp_path):
    proc = run_cell(
        ["--workload", CELL, "--seed", "3000000031", "--seconds", "3", "--trace", "1",
         "--allow-cpu", "--server-arg=--max-batch", "--server-arg=8",
         "--root", str(small_root(tmp_path / "root")), "--out", str(tmp_path / "o")],
        timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20
    assert line["compared"]["compared"]["value"] >= line["attempted"]
    for name in ("mismatched", "mismatched_with_error", "unanswered", "dropped_connections"):
        assert line["compared"][name] == {"value": 0, "limit": 0}
    assert "of kind admission for /v1/admit," in proc.stderr
    assert line["device"]["platform"] == "cpu" and "breakdown" not in line
    # the cell's per-layer metrics, but the device's; none of another path's
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [CELL])
            and m["moves"] != "decisions_per_s"}
    traced = {m["name"] for m in manifest["per_layer"] if m["source"] == "device_trace"}
    assert set(line["metrics"]) == mine - traced
    assert not [n for n in line["metrics"] if n.endswith((".lone", ".saturate"))]
    value = {n: e["value"] for n, e in line["metrics"].items()}
    assert value["fallback_row_share.admit"] == 0 and value["window_compiles"] == 0
    assert value["batch_rows.admit"] == 1.0
    assert 99.0 <= value["timer_accounted_share.admit"] <= 101.0
    assert 4.0 <= value["body_kb_per_request.admit"] <= 41.0
    assert value["ingress_ms.admit"] > value["dispatch_ms_per_batch.admit"] > 0
    assert value["encode_us_per_kb.admit"] > 0 and value["extras_per_row.admit"] >= 1.0
    # half the reviews are aimed: denies that name several policies are among them
    assert 5.0 < value["flagged_row_share.admit"] < 60.0
    # a deny's bits ride the launch's one readback; the launch's own counters
    assert value["bits_readback_share.admit"] == 100.0
    assert value["uploads_per_batch"] >= 1.0 and value["readback_bytes_per_batch"] > 0
    assert value["long_device_waits_per_kbatch"] >= 0.0
    # the end-to-end metrics the cell reports, under the names that were there
    e2e = {m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"latency_p50_ms", "latency_p95_ms", "setup_s"}
