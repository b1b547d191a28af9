"""Driven by data (benchmark/README.md): a configuration, a traffic mix, a
cell and a per-layer metric, added as files and entries only, and the
harness finds them by name and runs them."""

import json

from test_benchmark_e2e_rehearsal import REHEARSAL, run_cell
from test_benchmark_manifest import extended_root


def test_the_harness_runs_a_cell_added_as_files_only(tmp_path):
    root = extended_root(tmp_path / "root")
    proc = run_cell(
        ["--workload", "selector-300.sar-trickle", "--seed", "3000000029",
         "--seconds", "3", "--trace", "1", "--root", str(root),
         "--out", str(tmp_path / "o")] + REHEARSAL
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["attempted"] == 40 * 3
    assert line["metrics"]["allow_ms.trickle"]["unit"] == "ms"
    assert line["metrics"]["allow_ms.trickle"]["value"] > 0
    assert "ingress_ms.lone" not in line["metrics"]      # lists other cells
    assert "ready_s" in line["metrics"]                  # lists none: every cell
