"""Prometheus text exposition -> samples, and sums over them."""

from __future__ import annotations


def parse(text: str) -> list:
    """[(name, {label: value}, float)]."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        labels: dict = {}
        name = head
        if "{" in head:
            name, _, rest = head.partition("{")
            for part in rest.rstrip("}").split('",'):
                if "=" in part:
                    k, _, v = part.partition("=")
                    labels[k.strip()] = v.strip().strip('"')
        try:
            out.append((name, labels, float(val)))
        except ValueError:
            continue
    return out


def total(samples: list, name: str, labels: dict | None = None) -> float:
    """Sum of the samples of ``name`` whose labels include ``labels``; a
    label's wanted value may be a list of alternatives."""
    want = labels or {}

    def fits(have: dict) -> bool:
        for k, v in want.items():
            if isinstance(v, list):
                if have.get(k) not in v:
                    return False
            elif have.get(k) != v:
                return False
        return True

    return sum(v for n, have, v in samples if n == name and fits(have))


def delta(before: list, after: list, selectors) -> float:
    """after - before, summed over one selector ``{"name": ..., "labels":
    {...}}`` or a list of them."""
    if isinstance(selectors, dict):
        selectors = [selectors]
    return sum(
        total(after, s["name"], s.get("labels")) - total(before, s["name"], s.get("labels"))
        for s in selectors
    )
