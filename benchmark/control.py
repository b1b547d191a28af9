#!/usr/bin/env python3
"""The control the comparison has to fail.

The configurations state no numeric precision; they state a guarantee:
every answer is the Cedar decision with the full set of determining
policies. The control is the reference put in the program's place with one
guarantee broken, the step that would tempt a later change:

  first_reason_only   the reason names only the first determining policy
                      (a first-match kernel in place of the rule bitset)
  forbid_blind        forbid policies are not evaluated (one plane of two)

For each seed this builds the cell's corpus and the bodies of one window at
the cell's own size, answers them with the reference and with each control,
and counts the answers that differ: the number ``mismatched`` that
``run.py`` holds to 0. A control has failed when its count is above 0.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import traffic  # noqa: E402
from benchmark.manifest import Manifest, corpus_module  # noqa: E402
from benchmark.reference import CONTROLS  # noqa: E402
from benchmark.refpool import ReferencePool  # noqa: E402


def control_counts(manifest: Manifest, workload: str, seed: int) -> dict:
    w = manifest.workload(workload)
    cfg = manifest.config(w["config"])
    corpus = corpus_module(cfg["corpus"]["generator"], manifest.dir).build(
        cfg["corpus"]["params"], seed)
    plan = traffic.Plan(corpus, manifest.traffic(w["traffic"]), manifest.cell(w["name"]),
                        seed, float(manifest.doc["run_seconds"]), bench_dir=manifest.dir)
    indices = plan.precompute_indices()
    answers = {}
    for control in ("",) + CONTROLS:
        pool = ReferencePool(corpus.files, workers=len(os.sched_getaffinity(0)),
                             kind_ref=plan.kind_ref, control=control)
        try:
            pool.submit(plan.specs, indices)
            answers[control] = pool.collect()
        finally:
            pool.close()
    want = answers[""]
    return {
        "seed": seed,
        "bodies": len(indices),
        **{c: sum(1 for i in indices if answers[c][i] != want[i]) for c in CONTROLS},
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1,2,3")
    args = p.parse_args(argv)
    manifest = Manifest()
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        counts = control_counts(manifest, args.workload, seed)
        print(json.dumps(counts), flush=True)
        failed_all = failed_all and any(counts[c] > 0 for c in CONTROLS)
    print(json.dumps({"every_seed_failed_by_a_control": failed_all}))
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
