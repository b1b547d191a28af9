#!/usr/bin/env python3
"""A control for a corpus without a forbid: one ClusterRole's policies blind.

``benchmark/control.py``'s ``forbid_blind`` reads 0 on ``rbac-tenants``,
whose converted RBAC only permits. This control stands in for it there: the
reference with every policy that came from one ClusterRole (``view`` unless
told otherwise) made to apply to nothing — a plane that lost one role's
rule columns, or a shard of the store that did not load. The policies keep
their places, so every other policy keeps its id. It is told apart by the
provenance line the converter writes above each policy
(``@clusterRole("view")``, as an annotation or as a comment).

For each seed it builds the cell's corpus and the bodies of one window at
the cell's own size, answers them with the reference and with the control,
and counts the answers that differ: the number ``mismatched`` that
``run.py`` holds to 0.

    python3 benchmark/control_role_blind.py --workload <cell> --seeds 1,2,3
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
from benchmark.refpool import ReferencePool  # noqa: E402


def blind(files: dict, role: str) -> dict:
    """The store with ``unless { true }`` on every policy of ``role``."""
    mark = f'@clusterRole("{role}")'
    out = {}
    for name, text in files.items():
        blocks = text.split("\n\n")
        out[name] = "\n\n".join(
            block.rstrip()[:-1] + "\nunless { true };" + ("\n" if block.endswith("\n") else "")
            if mark in block and block.rstrip().endswith(";") else block
            for block in blocks)
    return out


def counts(manifest: Manifest, workload: str, seed: int, role: str) -> dict:
    w = manifest.workload(workload)
    cfg = manifest.config(w["config"])
    corpus = corpus_module(cfg["corpus"]["generator"], manifest.dir).build(
        cfg["corpus"]["params"], seed)
    plan = traffic.Plan(corpus, manifest.traffic(w["traffic"]), manifest.cell(w["name"]),
                        seed, float(manifest.doc["run_seconds"]), bench_dir=manifest.dir)
    indices = plan.precompute_indices()
    answers = []
    for files in (corpus.files, blind(corpus.files, role)):
        pool = ReferencePool(files, workers=min(4, len(os.sched_getaffinity(0))),
                             kind_ref=plan.kind_ref)
        try:
            pool.submit(plan.specs, indices)
            answers.append(pool.collect())
        finally:
            pool.close()
    want, got = answers
    return {"seed": seed, "bodies": len(indices),
            f"{role}_blind": sum(1 for i in indices if got[i] != want[i])}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1,2,3")
    p.add_argument("--role", default="view")
    args = p.parse_args(argv)
    manifest = Manifest()
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        c = counts(manifest, args.workload, seed, args.role)
        print(json.dumps(c), flush=True)
        failed_all = failed_all and c[f"{args.role}_blind"] > 0
    print(json.dumps({"every_seed_failed_by_the_control": failed_all}))
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
