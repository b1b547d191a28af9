"""The cell rbac-oidc-groups.sar-groups-lone end to end on the CPU rehearsal:
users whose token carries an identity provider's groups through every phase
of a run — corpus from the seed, the server child, 1-9 kB
SubjectAccessReviews over HTTPS, every answer against the reference — at
twenty tenants (so that a person's known groups pass the eight ancestor
slots and the extras list's former cap of 32) and with a pool large enough
for a CPU server over so small a corpus, which only a copy of the data
files can state."""

import json
import pathlib
import shutil

from test_benchmark_e2e_rehearsal import ROOT, run_cell

CELL = "rbac-oidc-groups.sar-groups-lone"


def small_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A copy of BENCHMARK.json and the data files with the tenancy cut to
    twenty and the pool of bodies widened (the code is the package's own:
    ``--root`` adds data)."""
    tmp_path.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(ROOT / "benchmark" / sub, tmp_path / "benchmark" / sub)
    cfg = tmp_path / "benchmark" / "configs" / "rbac-oidc-groups.json"
    doc = json.loads(cfg.read_text())
    doc["corpus"]["params"]["tenants"] = 20
    cfg.write_text(json.dumps(doc))
    mix = tmp_path / "benchmark" / "traffic" / "sar-groups-lone.json"
    doc = json.loads(mix.read_text())
    doc["pool_per_s"] = doc["precompute_per_s"] = 1500
    mix.write_text(json.dumps(doc))
    return tmp_path


def test_the_groups_cell_runs_every_phase_and_no_row_leaves_the_native_path(tmp_path):
    proc = run_cell(
        ["--workload", CELL, "--seed", "3600000031", "--seconds", "3", "--allow-cpu",
         "--server-arg=--max-batch", "--server-arg=8", "--trace", "1",
         "--root", str(small_root(tmp_path / "root")), "--out", str(tmp_path / "o")],
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 100
    assert line["compared"]["compared"]["value"] >= line["attempted"]
    for name in ("mismatched", "mismatched_with_error", "unanswered", "dropped_connections"):
        assert line["compared"][name] == {"value": 0, "limit": 0}
    assert "of kind sar for /v1/authorize," in proc.stderr
    assert line["device"]["platform"] == "cpu" and "breakdown" not in line
    # the cell's per-layer metrics, but the device's: its own suffix's, and
    # the `.lone` entries that read the authorization path's series
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [CELL])
            and m["moves"] != "decisions_per_s"}
    traced = {m["name"] for m in manifest["per_layer"] if m["source"] == "device_trace"}
    assert set(line["metrics"]) == mine - traced
    assert not [n for n in line["metrics"] if n.endswith((".saturate", ".admit", ".reask"))]
    value = {n: e["value"] for n, e in line["metrics"].items()}
    # the mechanism does most of the work: most known memberships ride the
    # extras list, past eight a row, and nothing falls back or compiles
    assert value["ancestor_extras_share.groups"] > 50.0
    assert value["extras_per_row.groups"] > 8.0
    assert value["fallback_row_share.groups"] == 0 and value["window_compiles"] == 0
    assert 3.0 < value["body_kb_per_request.groups"] < 6.0
    assert value["encode_us_per_row.groups"] > 0 and value["dispatch_ms_per_batch.lone"] > 0
