"""The CPU rehearsal: every phase of a run end to end at a tiny size — no
device metric, ``platform: cpu`` — and the two ways a run must refuse."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
REHEARSAL = ["--allow-cpu", "--policies", "200",
             "--server-arg=--max-batch", "--server-arg=8"]


def run_cell(args, cwd=ROOT, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(pathlib.Path(cwd) / "benchmark" / "run.py")] + args,
        cwd=str(cwd), env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_rehearsal_runs_every_phase_and_prints_no_device_metric(tmp_path):
    proc = run_cell(["--workload", "selector-1k.sar-lone", "--seed", "3000000019",
                     "--seconds", "3", "--trace", "1", "--out", str(tmp_path / "o")]
                    + REHEARSAL)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    # the contract's keys, and the comparison's numbers last
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    # one caller that waits: every request of the window answered, in order
    assert line["attempted"] > 30
    assert line["compared"]["compared"]["value"] >= line["attempted"]
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    traced = {m["name"] for m in manifest["per_layer"] if m["source"] == "device_trace"}
    assert not traced & set(line["metrics"])
    per_layer = {m["name"] for m in manifest["per_layer"]
                 if "selector-1k.sar-lone" in m.get("workloads", ["selector-1k.sar-lone"])}
    # every per-layer metric of the cell but the device's is in the line
    assert set(line["metrics"]) == per_layer - traced
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert line["metrics"]["fallback_row_share.lone"]["value"] == 0
    assert line["metrics"]["over_deadline_share"]["value"] == 0
    assert line["metrics"]["gc_pause_max_ms"]["value"] >= 0
    assert line["metrics"]["batch_rows.lone"]["value"] == 1.0
    for name, entry in line["metrics"].items():
        assert set(entry) == {"value", "unit"}, name
    for name in ("mismatched", "mismatched_with_error", "unanswered", "dropped_connections"):
        assert line["compared"][name] == {"value": 0, "limit": 0}
    # the same numbers close standard error
    tail = proc.stderr.strip().splitlines()[-6:]
    assert tail[-1] == "correct True"
    assert tail[0].startswith("compared mismatched {")
    # the trace covers the window's end, and the profiler's dump (seconds,
    # in the server's own process) comes after the close, not inside it
    assert proc.stderr.index("window closed") < proc.stderr.index("trace of")
    # the cores and their sets are named on an earlier line
    assert "cores:" in proc.stderr and "generator processes on" in proc.stderr


def test_without_an_accelerator_there_is_no_result():
    proc = run_cell(["--workload", "selector-1k.sar-lone", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_with_nothing_but_the_benchmarks_own_files_there_is_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell(["--workload", "selector-1k.sar-lone", "--seed", "1",
                     "--seconds", "1", "--trace", "0"] + REHEARSAL, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "server exited" in proc.stderr


def test_the_saturate_cell_ends_with_its_end_to_end_metrics(tmp_path):
    proc = run_cell(["--workload", "synth-10k.sar-saturate", "--seed", "2147483999",
                     "--seconds", "3", "--trace", "0", "--out", str(tmp_path / "o")]
                    + REHEARSAL)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"decisions_per_s", "setup_s"}
    assert line["metrics"]["decisions_per_s"]["unit"] == "1/s"
    # completions inside the window over its whole length; the 64 in flight
    # at the close are attempted and compared, not completed
    assert 0 < line["metrics"]["decisions_per_s"]["value"] * 3 <= line["attempted"]
    assert line["compared"]["compared"]["value"] >= line["attempted"]
    assert "breakdown" not in line


def test_the_bars_corpus_under_the_lone_mix_ends_with_the_latency_metrics(tmp_path):
    """synth-10k.sar-lone, the first cell that came in as files and entries
    only: the accepted configuration under the accepted lone mix."""
    proc = run_cell(["--workload", "synth-10k.sar-lone", "--seed", "2900000029",
                     "--seconds", "3", "--trace", "0", "--out", str(tmp_path / "o")]
                    + REHEARSAL)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert 0 < line["metrics"]["latency_p50_ms"]["value"] <= line["metrics"]["latency_p95_ms"]["value"]
    assert line["attempted"] > 30
    assert line["compared"]["compared"]["value"] >= line["attempted"]
    assert "1 generator processes" in proc.stderr and "of kind sar for /v1/authorize," in proc.stderr
