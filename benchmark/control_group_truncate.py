#!/usr/bin/env python3
"""A control for the ancestor overflow: a principal's known groups past the
eighth dropped.

The program gives the first eight policy-known groups of a principal an
ancestor code slot each and carries every further one on the row's extras
list (``cedar_tpu/compiler/table.py`` ``ANCESTOR_SLOTS``,
``native/encoder.cpp`` ``push_ancestors``). This control is what an encoder
that lost that overflow would answer: the reference, asked the same
requests with every group some policy names, after the eighth in the
token's order, left out of ``spec.groups``. A group is known when a policy
of the store tests ``principal in k8s::Group::"<name>"``; the groups no
policy names stay, and change nothing.

For each seed it builds the cell's corpus and the bodies of one window at
the cell's own size, answers them as they are and truncated, and counts the
answers that differ: the number ``mismatched`` that ``run.py`` holds to 0.

    python3 benchmark/control_group_truncate.py --workload <cell> --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import traffic  # noqa: E402
from benchmark.manifest import Manifest, corpus_module  # noqa: E402
from benchmark.refpool import ReferencePool  # noqa: E402

SLOTS = 8
GROUP_TEST = re.compile(r'principal in k8s::Group::"((?:[^"\\]|\\.)*)"')


def known_groups(files: dict) -> set:
    """The groups some policy of the store names."""
    return {m.group(1) for text in files.values() for m in GROUP_TEST.finditer(text)}


def truncated(spec: dict, known: set, slots: int = SLOTS) -> dict:
    """``spec`` without its known groups past the first ``slots``."""
    kept, seen = [], 0
    for g in spec.get("groups") or ():
        if g in known:
            seen += 1
            if seen > slots:
                continue
        kept.append(g)
    return dict(spec, groups=kept)


def counts(manifest: Manifest, workload: str, seed: int) -> dict:
    w = manifest.workload(workload)
    cfg = manifest.config(w["config"])
    corpus = corpus_module(cfg["corpus"]["generator"], manifest.dir).build(
        cfg["corpus"]["params"], seed)
    plan = traffic.Plan(corpus, manifest.traffic(w["traffic"]), manifest.cell(w["name"]),
                        seed, float(manifest.doc["run_seconds"]), bench_dir=manifest.dir)
    indices = plan.precompute_indices()
    known = known_groups(corpus.files)
    cut = [truncated(spec, known) for spec in plan.specs]
    answers = []
    for specs in (plan.specs, cut):
        pool = ReferencePool(corpus.files, workers=len(os.sched_getaffinity(0)),
                             kind_ref=plan.kind_ref)
        try:
            pool.submit(specs, indices)
            answers.append(pool.collect(timeout=3600.0))
        finally:
            pool.close()
    want, got = answers
    return {"seed": seed, "bodies": len(indices),
            "past_the_slots": sum(1 for i in indices if cut[i]["groups"] != plan.specs[i]["groups"]),
            "group_truncate": sum(1 for i in indices if got[i] != want[i])}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1,2,3")
    args = p.parse_args(argv)
    manifest = Manifest()
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        c = counts(manifest, args.workload, seed)
        print(json.dumps(c), flush=True)
        failed_all = failed_all and c["group_truncate"] > 0
    print(json.dumps({"every_seed_failed_by_the_control": failed_all}))
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
