"""A run with the timed path broken underneath: an answer altered where it
is produced has to come out as not correct. The fault is the program's own
gameday injector (fake denies at a fixed rate), switched on through the
server child's arguments; everything else is a run as the harness makes it,
except for its look for a chip."""

import json

from test_benchmark_e2e_rehearsal import REHEARSAL, run_cell


def test_altered_answers_make_the_run_incorrect(tmp_path):
    proc = run_cell(
        ["--workload", "synth-10k.sar-saturate", "--seed", "3000000023",
         "--seconds", "3", "--trace", "0", "--out", str(tmp_path / "o"),
         "--server-arg=--confirm-non-prod-inject-errors",
         "--server-arg=--artificial-deny-rate", "--server-arg=20"] + REHEARSAL
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["mismatched"]["value"] > 0
    assert line["compared"]["mismatched"]["limit"] == 0
    assert line["compared"]["unanswered"]["value"] == 0
    assert "correct False" in proc.stderr
    assert "first disagreements" in proc.stderr
