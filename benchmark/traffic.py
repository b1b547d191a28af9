"""The one general traffic generator: a mix's data file -> bodies and dues.

A mix (``benchmark/traffic/<name>.json``) states the loop (``open`` with
Poisson arrivals at the cell's ``rate_per_s``, or ``closed`` with one
request in flight per connection), the number of connections and of the
generator processes that share them, the share of requests aimed at a real
policy, the warm-up, how large a pool of distinct bodies a closed loop
may draw, and the ``kind`` of request it sends (``benchmark/kinds/``;
``sar`` where it names none). The corpus supplies the request attributes;
the kind wraps them as the review object and says what makes one distinct;
this file serializes and schedules them. Everything follows from the seed.

Every seed gets the same multiset of inter-arrival gaps — the exponential
distribution's quantiles at the cell's rate — in another order, so that
two seeds differ in which request meets which burst and not in how bursty
the window is.
"""

from __future__ import annotations

import json
import math
import random

from benchmark.manifest import DEFAULT_KIND, kind_module


def schedule(rate_per_s: float, seconds: float, seed: int) -> list:
    """Due times in [0, seconds): ``round(rate * seconds)`` arrivals whose
    gaps are the exponential quantiles, shuffled by the seed."""
    n = int(round(rate_per_s * seconds))
    if n < 1:
        raise ValueError("a schedule needs at least one arrival")
    gaps = [-math.log(1.0 - (k + 0.5) / n) for k in range(n)]
    random.Random(f"{seed}:arrivals").shuffle(gaps)
    scale = seconds / sum(gaps)
    due, t = [], 0.0
    for g in gaps:
        due.append(t)
        t += g * scale
    return due


def make_bodies(corpus, mix: dict, seed: int, n: int, tag: str, kind) -> tuple:
    """``n`` distinct bodies (bytes) of the request ``kind`` and their specs.
    ``tag`` keeps the warm-up's bodies apart from the window's."""
    rng = random.Random(f"{seed}:bodies:{tag}")
    aimed = float(mix.get("aimed_share", 0.8))
    specs, bodies = [], []
    for i in range(n):
        spec = corpus.spec(rng, aimed)
        if mix.get("name_per_request", True):
            kind.distinct(spec, f"{tag}-{i}")
        specs.append(spec)
        bodies.append(json.dumps(kind.body(spec)).encode())
    return bodies, specs


class Plan:
    """What the generator processes send in one run. ``bench_dir`` is where
    a kind that this package does not have is looked for (``--root``)."""

    def __init__(self, corpus, mix: dict, cell: dict, seed: int, seconds: float,
                 tag: str = "w", bench_dir=None):
        # (name, directory): what a spawned process needs to import the kind
        self.kind_ref = (mix.get("kind", DEFAULT_KIND), str(bench_dir) if bench_dir else None)
        self.kind = kind_module(*self.kind_ref)
        self.loop = mix["loop"]
        self.connections = int(mix["connections"])
        self.processes = int(mix["processes"])
        if self.processes < 1 or self.connections % self.processes:
            raise ValueError(f"{self.connections} connections do not divide among "
                             f"{self.processes} generator processes")
        self.warmup_s = float(mix.get("warmup_s", 3.0))
        self.seconds = float(seconds)
        if self.loop == "open":
            rate = float(cell["rate_per_s"])
            # warm-up arrivals carry negative due times: sent, uncounted
            warm = [t - self.warmup_s for t in
                    schedule(rate, self.warmup_s, seed + 1)]
            self.due = warm + schedule(rate, self.seconds, seed)
            n_bodies = len(self.due)
            self.precompute = n_bodies
        elif self.loop == "closed":
            span = self.seconds + self.warmup_s
            n_bodies = int(mix["pool_per_s"] * span)
            n_bodies -= n_bodies % self.connections
            self.due = None
            self.precompute = min(n_bodies, int(mix["precompute_per_s"] * span))
        else:
            raise ValueError(f"unknown loop {self.loop!r}")
        self.bodies, self.specs = make_bodies(corpus, mix, seed, n_bodies, tag, self.kind)

    def precompute_indices(self) -> list:
        """The bodies whose reference answers are worked out before the
        window: all of an open loop's, and of a closed loop's pool the
        head of every connection's sequence (connection c sends bodies c,
        c + connections, ...)."""
        return list(range(self.precompute))
