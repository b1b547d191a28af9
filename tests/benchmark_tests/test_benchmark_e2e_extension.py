"""Driven by data (benchmark/README.md): a configuration with a corpus
generator of its own, a traffic mix with a request kind of its own, a cell
and per-layer metrics, added as files and entries only (``door/``), and the
harness finds them by name and runs them."""

import json

from test_benchmark_e2e_rehearsal import REHEARSAL, run_cell
from test_benchmark_manifest import DOOR_CELL, extended_root


def test_the_harness_runs_a_cell_added_as_files_only(tmp_path):
    root = extended_root(tmp_path / "root")
    proc = run_cell(
        ["--workload", DOOR_CELL, "--seed", "3000000029",
         "--seconds", "3", "--trace", "1", "--root", str(root),
         "--out", str(tmp_path / "o")] + REHEARSAL
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["attempted"] == 40 * 3
    assert line["compared"]["compared"]["value"] >= 40 * 3
    # the kind that came in as a file set the request line, and its answers
    # are not all one: the corpus's permits and forbids were both met
    assert "of kind sar_url for /v1/authorize?timeout=30s" in proc.stderr
    assert line["metrics"]["allow_ms.url"]["unit"] == "ms"
    assert line["metrics"]["allow_ms.url"]["value"] > 0
    assert line["metrics"]["ingress_ms.url"]["value"] > 0
    assert line["metrics"]["fallback_row_share.url"]["value"] == 0
    assert "ingress_ms.lone" not in line["metrics"]      # lists other cells
    assert "ready_s" in line["metrics"]                  # lists none: every cell
