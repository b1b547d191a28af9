"""The closed-loop pools of ``admit-lone`` and ``sar-reask-lone``, raised to
1.5 times the requests a second the chip's server answers in their cells,
and what raising them may not change: for a seed, the bodies a window sends.
A closed loop sends its pool from the head (one connection: bodies 0, 1,
2, ...), so a larger pool only grows at its end, and every body the smaller
pool held is the same body, byte for byte, at the same place."""

import hashlib

import pytest

from benchmark import traffic
from benchmark.manifest import Manifest, corpus_module

SEED = 4100000099
# cell -> (mix, the pool before, the pool now, precompute now)
POOLS = {
    "pss-admit.admit-lone": ("admit-lone", 330, 500, 500),
    "rbac-tenants.sar-reask-lone": ("sar-reask-lone", 1080, 1500, 400),
}


def digests(m, cell, mix, pool_per_s, seconds):
    """The sha256 of every body of the plan a run of ``seconds`` makes."""
    cfg = m.config(m.workload(cell)["config"])
    corpus = corpus_module(cfg["corpus"]["generator"]).build(dict(cfg["corpus"]["params"]), SEED)
    plan = traffic.Plan(corpus, dict(mix, pool_per_s=pool_per_s), m.cell(cell), SEED, seconds,
                        bench_dir=m.dir)
    return [hashlib.sha256(b).digest() for b in plan.bodies]


@pytest.mark.parametrize("cell", sorted(POOLS))
def test_the_pool_is_what_the_chip_answers_and_half_again(cell):
    m = Manifest()
    name, _, now, precompute = POOLS[cell]
    mix = m.traffic(name)
    assert (mix["pool_per_s"], mix["precompute_per_s"]) == (now, precompute)


@pytest.mark.parametrize("cell", sorted(POOLS))
def test_a_larger_pool_sends_the_same_bodies_first(cell):
    m = Manifest()
    name, before, now, _ = POOLS[cell]
    mix = m.traffic(name)
    seconds = m.doc["run_seconds"]
    old = digests(m, cell, mix, before, seconds)
    new = digests(m, cell, mix, now, seconds)
    # 51 s and 3 s of warm-up: 17,820 and 58,320 bodies before
    assert len(old) == int(before * (seconds + mix["warmup_s"]))
    assert len(new) == int(now * (seconds + mix["warmup_s"]))
    assert new[:len(old)] == old
