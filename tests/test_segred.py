"""Segmented-reduction kernel plane tests (CEDAR_TPU_SEGRED=1).

pack() lays rules out group-contiguously, so the per-group first/last-
match can reduce over static column segments (ops/match.py
_first_match_seg) instead of n_groups masked passes. The plane is opt-in
until a benchmark cell shows a measured win on the chip; these tests
pin exact equality against the default scan plane either way.
"""

import random

import numpy as np
import pytest

from cedar_tpu.compiler.table import encode_request_codes
from cedar_tpu.engine.evaluator import TPUPolicyEngine, _segment_plan
from cedar_tpu.lang import PolicySet

from tests.test_wire import _random_set_and_items


def _load(monkeypatch, src, segred):
    monkeypatch.setenv("CEDAR_TPU_SEGRED", "1" if segred else "0")
    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(src, "t0")], warm="off")
    return engine


def test_segment_plan_covers_every_live_rule(monkeypatch):
    """The static segments partition exactly the live (non-padding)
    columns of every chunk, each run carrying one group."""
    src, _items = _random_set_and_items(seed=21)
    engine = _load(monkeypatch, src, True)
    cs = engine._compiled
    assert cs.segs is not None
    packed = cs.packed
    # reconstruct the chunked group layout the plan was built from
    from cedar_tpu.ops.match import chunk_rules

    _w, _t, group_c, _p = chunk_rules(
        packed.W, packed.thresh, packed.rule_group, packed.rule_policy
    )
    C, rc = group_c.shape
    assert len(cs.segs) == C
    covered = 0
    for ci, runs in enumerate(cs.segs):
        limit = min(rc, max(0, packed.n_rules - ci * rc))
        prev_end = 0
        for g, a, b in runs:
            assert a == prev_end and b <= limit
            assert (group_c[ci, a:b] == g).all()
            prev_end = b
            covered += b - a
        assert prev_end == limit
    assert covered == packed.n_rules
    # group-contiguity across the whole layout (pack's sort invariant)
    live = packed.rule_group[: packed.n_rules]
    assert (np.diff(live) >= 0).all(), "rules not sorted by group"


def test_segred_and_scan_planes_agree(monkeypatch):
    src, items = _random_set_and_items(seed=22)
    res_on = _load(monkeypatch, src, True).evaluate_batch(items)
    res_off = _load(monkeypatch, src, False).evaluate_batch(items)
    for (d1, g1), (d2, g2) in zip(res_on, res_off):
        assert d1 == d2
        assert {r.policy for r in g1.reasons} == {r.policy for r in g2.reasons}
        assert len(g1.errors) == len(g2.errors)


def test_segred_kernel_words_full_and_bits_match_scan(monkeypatch):
    """Kernel-level equality incl. want_full matrices and the want_bits
    diagnostics plane, over the exact same encoded rows."""
    src, items = _random_set_and_items(seed=23)
    eng_on = _load(monkeypatch, src, True)
    eng_off = _load(monkeypatch, src, False)
    cs_on, cs_off = eng_on._compiled, eng_off._compiled
    rows = [
        encode_request_codes(cs_on.packed.plan, cs_on.packed.table, em, rq)
        for em, rq in items
    ]
    S = cs_on.packed.table.n_slots
    codes = np.zeros((len(rows), S), dtype=np.int32)
    max_e = max((len(e) for _c, e in rows), default=0)
    extras = np.full((len(rows), max(max_e, 1)), cs_on.packed.L, np.int32)
    for i, (c, e) in enumerate(rows):
        codes[i] = c
        if e:
            extras[i, : len(e)] = e
    w_on, full_on, bm_on = eng_on.match_arrays(
        codes, extras, cs=cs_on, want_full=True, want_bits=True
    )
    w_off, full_off, bm_off = eng_off.match_arrays(
        codes, extras, cs=cs_off, want_full=True, want_bits=True
    )
    np.testing.assert_array_equal(np.asarray(w_on), np.asarray(w_off))
    np.testing.assert_array_equal(np.asarray(full_on[0]), np.asarray(full_off[0]))
    np.testing.assert_array_equal(np.asarray(full_on[1]), np.asarray(full_off[1]))
    assert set(bm_on) == set(bm_off)
    for k in bm_on:
        np.testing.assert_array_equal(bm_on[k], bm_off[k])


def test_segred_with_gate_plane(monkeypatch):
    """A fallback policy's gate rules ride group n_tiers*3 — the LAST
    segment after the sort; gated rows must still re-route identically."""
    src, items = _random_set_and_items(seed=24, n_policies=20)
    src += (
        '\npermit (principal, action == k8s::Action::"get",'
        " resource is k8s::Resource)"
        " unless { resource has name && ip(resource.name).isLoopback() };"
    )
    res_on = _load(monkeypatch, src, True).evaluate_batch(items)
    res_off = _load(monkeypatch, src, False).evaluate_batch(items)
    for (d1, g1), (d2, g2) in zip(res_on, res_off):
        assert d1 == d2
        assert {r.policy for r in g1.reasons} == {r.policy for r in g2.reasons}


def test_segment_plan_unit():
    group_c = np.array([[0, 0, 1, 1], [1, 2, 2, 0]], dtype=np.int32)
    # 6 live rules: chunk 1's trailing columns are padding
    segs = _segment_plan(group_c, 6)
    assert segs == (((0, 0, 2), (1, 2, 4)), ((1, 0, 1), (2, 1, 2)))
    # exactly full: padding-free plan covers everything
    segs = _segment_plan(group_c, 8)
    assert segs[1] == ((1, 0, 1), (2, 1, 3), (0, 3, 4))


def test_engine_segred_kwarg_overrides_env(monkeypatch):
    """The per-engine kwarg wins over CEDAR_TPU_SEGRED in both directions
    (the webhook CLI enables the plane per engine on the CPU backend —
    never by mutating process env)."""
    src, items = _random_set_and_items(n_policies=10, n_items=8, seed=31)
    monkeypatch.setenv("CEDAR_TPU_SEGRED", "0")
    eng = TPUPolicyEngine(segred=True)
    eng.load([PolicySet.from_source(src, "t0")], warm="off")
    assert eng._compiled.segs is not None
    monkeypatch.setenv("CEDAR_TPU_SEGRED", "1")
    eng2 = TPUPolicyEngine(segred=False)
    eng2.load([PolicySet.from_source(src, "t0")], warm="off")
    assert eng2._compiled.segs is None
    # and the two planes still agree end to end
    r1 = eng.evaluate_batch(items)
    r2 = eng2.evaluate_batch(items)
    for (d1, _), (d2, _) in zip(r1, r2):
        assert d1 == d2


def test_shape_gate_selects_plane(monkeypatch):
    """Batches above SERVING_CHUNK must ride the scan plane even with
    segments enabled (the ~1GB-intermediate blowup guard,
    docs/Limitations.md); serving-sized batches keep the segments."""
    import cedar_tpu.engine.evaluator as ev
    from cedar_tpu.engine.evaluator import SERVING_CHUNK

    src, _items = _random_set_and_items(n_policies=6, n_items=4, seed=33)
    eng = TPUPolicyEngine(segred=True)
    eng.load([PolicySet.from_source(src, "t0")], warm="off")
    cs = eng._compiled
    assert cs.segs is not None
    S = cs.packed.table.n_slots
    seen = []
    real_wire = ev.match_rules_codes_wire
    real_flat = ev.match_rules_codes

    def spy_wire(*a, **k):
        seen.append(a[-1] if not k else k.get("segs", a[-1]))
        return real_wire(*a, **k)

    def spy_flat(*a, **k):
        seen.append(a[-1] if not k else k.get("segs", a[-1]))
        return real_flat(*a, **k)

    monkeypatch.setattr(ev, "match_rules_codes_wire", spy_wire)
    monkeypatch.setattr(ev, "match_rules_codes", spy_flat)

    def run(n):
        codes = np.zeros((n, S), dtype=np.int32)
        extras = np.full((n, 1), cs.packed.L, dtype=np.int32)
        eng.match_arrays(codes, extras, cs=cs)

    run(64)  # serving-sized: segments used
    assert seen and seen[-1] is not None
    run(SERVING_CHUNK + 1)  # pads above the gate: scan plane
    assert seen[-1] is None
