"""The cell rbac-tenants.sar-reask-lone end to end on the CPU rehearsal: a
stream that repeats through every phase of a run — corpus from the seed, the
server child with its decision cache, SubjectAccessReviews over HTTPS, every
answer, cache-given or evaluated, against the reference — at six tenants
and with a pool large enough for a CPU server over so small a corpus, which
only a copy of the data files can state."""

import json
import pathlib
import shutil

from test_benchmark_e2e_rehearsal import ROOT, run_cell

CELL = "rbac-tenants.sar-reask-lone"


def small_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A copy of BENCHMARK.json and the data files with the tenancy cut to
    six and the pool of bodies widened (the code is the package's own:
    ``--root`` adds data)."""
    tmp_path.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(ROOT / "benchmark" / sub, tmp_path / "benchmark" / sub)
    cfg = tmp_path / "benchmark" / "configs" / "rbac-tenants.json"
    doc = json.loads(cfg.read_text())
    doc["corpus"]["params"]["tenants"] = 6
    cfg.write_text(json.dumps(doc))
    mix = tmp_path / "benchmark" / "traffic" / "sar-reask-lone.json"
    doc = json.loads(mix.read_text())
    doc["pool_per_s"] = 4000
    mix.write_text(json.dumps(doc))
    return tmp_path


def rehearse(tmp_path, *more):
    proc = run_cell(
        ["--workload", CELL, "--seed", "3400000031", "--seconds", "3", "--allow-cpu",
         "--server-arg=--max-batch", "--server-arg=8",
         "--root", str(small_root(tmp_path / "root")), "--out", str(tmp_path / "o"), *more],
        timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc, json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_reask_cell_runs_every_phase_and_a_hit_is_held_to_the_reference_like_a_miss(tmp_path):
    proc, line = rehearse(tmp_path, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 100
    assert line["compared"]["compared"]["value"] >= line["attempted"]
    for name in ("mismatched", "mismatched_with_error", "unanswered", "dropped_connections"):
        assert line["compared"][name] == {"value": 0, "limit": 0}
    assert "of kind sar_memo for /v1/authorize," in proc.stderr
    assert line["device"]["platform"] == "cpu" and "breakdown" not in line
    # the cell's per-layer metrics, but the device's: its own suffix's, and
    # the `.lone` entries that read the series a hit and a miss share
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [CELL])
            and m["moves"] != "decisions_per_s"}
    traced = {m["name"] for m in manifest["per_layer"] if m["source"] == "device_trace"}
    assert set(line["metrics"]) == mine - traced
    assert not [n for n in line["metrics"] if n.endswith((".saturate", ".admit", ".groups"))]
    value = {n: e["value"] for n, e in line["metrics"].items()}
    # repeats are answered by the cache, and a cache's answer is the cheap one
    assert 30.0 < value["cache_hit_share.reask"] < 95.0
    assert 0 < value["cache_answer_ms.reask"] < value["engine_answer_ms.reask"]
    assert value["cache_answer_ms.reask"] < value["ingress_ms.lone"] < value["engine_answer_ms.reask"]
    # the memo holds every body the cache holds: its hits are the repeats
    assert value["memo_hit_share.reask"] >= value["cache_hit_share.reask"] > 0
    assert 0 <= value["rule_answer_share.reask"] < 10.0
    assert value["fallback_row_share.reask"] == 0 and value["window_compiles"] == 0
    assert value["batch_rows.reask"] == 1.0 and value["scan_read_share.reask"] == 100.0
    assert 99.0 <= value["timer_accounted_share.lone"] <= 101.0
    assert value["dispatch_ms_per_batch.reask"] > 0
    # the window's requests hold many repeats: the same body, the same answer
    records = json.loads((tmp_path / "o" / "records.json").read_text())
    assert len(records) > line["attempted"]


def test_altered_answers_make_the_reask_run_incorrect_hits_among_them(tmp_path):
    _, line = rehearse(
        tmp_path, "--trace", "0", "--server-arg=--confirm-non-prod-inject-errors",
        "--server-arg=--artificial-deny-rate", "--server-arg=20")
    assert line["correct"] is False
    assert line["compared"]["mismatched"]["value"] > 0
    assert line["compared"]["unanswered"]["value"] == 0
    assert set(line["metrics"]) == {"latency_p50_ms", "latency_p95_ms", "setup_s"}
    # the median is still a hit's: the injector alters a finished answer
    assert line["metrics"]["latency_p50_ms"]["value"] < line["metrics"]["latency_p95_ms"]["value"]
