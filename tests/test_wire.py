"""u8 wire-format tests: the split (codes8, codes_w) device layout
(engine._CompiledSet.wire, ops/match.py match_rules_codes_wire) must be
byte-exactly equivalent to the flat int16/int32 code layout.

The wire plane halves the per-request h2d payload, so it is ON by
default; these tests pin (a) the soundness of the per-slot row ranges
the re-basing relies on (compiler/table.py slot_row_ranges), (b) verdict +
diagnostics equality against the flat layout, and (c) the wide-slot
(span > 255) fallback.
"""

import random

import numpy as np
import pytest

from cedar_tpu.compiler.table import encode_request_codes
from cedar_tpu.engine.evaluator import TPUPolicyEngine
from cedar_tpu.entities.attributes import Attributes, UserInfo
from cedar_tpu.lang import PolicySet
from cedar_tpu.server.authorizer import record_to_cedar_resource


def _sar(user, verb, resource, groups=(), subresource=""):
    return record_to_cedar_resource(
        Attributes(
            user=UserInfo(name=user, uid="u", groups=tuple(groups)),
            verb=verb,
            resource=resource,
            subresource=subresource,
            api_version="v1",
            namespace="default",
            resource_request=True,
        )
    )


def _random_set_and_items(n_policies=40, n_items=120, seed=11):
    rng = random.Random(seed)
    names = ["alice", "bob", "carol", "dave"]
    resources = ["pods", "services", "secrets", "nodes"]
    verbs = ["get", "list", "create", "delete"]
    groups = ["g1", "g2", "g3"]
    policies = []
    for _ in range(n_policies):
        effect = rng.choice(["permit", "forbid"])
        scope_p = rng.choice(
            [
                "principal",
                'principal in k8s::Group::"%s"' % rng.choice(groups),
                "principal is k8s::User",
            ]
        )
        scope_a = rng.choice(
            [
                "action",
                'action == k8s::Action::"%s"' % rng.choice(verbs),
            ]
        )
        conds = []
        if rng.random() < 0.7:
            conds.append('principal.name == "%s"' % rng.choice(names))
        if rng.random() < 0.7:
            conds.append('resource.resource == "%s"' % rng.choice(resources))
        if rng.random() < 0.3:
            conds.append('resource.resource like "p*"')
        body = " && ".join(conds) if conds else "true"
        policies.append(
            f"{effect} ({scope_p}, {scope_a}, resource is k8s::Resource) "
            f"when {{ {body} }};"
        )
    src = "\n".join(policies)
    items = [
        _sar(
            user=rng.choice(names + ["eve"]),
            verb=rng.choice(verbs),
            resource=rng.choice(resources + ["jobs"]),
            groups=tuple(rng.sample(groups, rng.randint(0, 2))),
            subresource=rng.choice(["", "status"]),
        )
        for _ in range(n_items)
    ]
    return src, items


def _load(monkeypatch, src, wire_on):
    monkeypatch.setenv("CEDAR_TPU_WIRE_U8", "1" if wire_on else "0")
    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(src, "t0")], warm="off")
    return engine


def test_slot_row_ranges_cover_every_emitted_code(monkeypatch):
    """Soundness of the re-basing: every code the python encoder emits for
    a u8 slot must fall in that slot's declared (lo, hi) range (or be 0) —
    an out-of-range code would silently map to the wrong activation row."""
    src, items = _random_set_and_items(seed=12)
    engine = _load(monkeypatch, src, wire_on=True)
    cs = engine._compiled
    ranges = cs.packed.table.slot_row_ranges()
    for em, rq in items:
        codes, _extras = encode_request_codes(
            cs.packed.plan, cs.packed.table, em, rq
        )
        for s, code in enumerate(codes):
            if code == 0:
                continue
            lo, hi = ranges[s]
            assert lo <= code <= hi, (
                f"slot {s}: code {code} outside declared range ({lo}, {hi})"
            )


def test_wire_plan_shape(monkeypatch):
    """The plan partitions the slots; u8 slots' spans fit one byte."""
    src, _items = _random_set_and_items(seed=13)
    engine = _load(monkeypatch, src, wire_on=True)
    cs = engine._compiled
    assert cs.wire is not None
    idx8, idx16, lo8 = cs.wire
    table = cs.packed.table
    assert sorted([*idx8.tolist(), *idx16.tolist()]) == list(
        range(table.n_slots)
    )
    ranges = table.slot_row_ranges()
    for s, lo in zip(idx8.tolist(), lo8.tolist()):
        r_lo, r_hi = ranges[s]
        assert lo == max(r_lo, 1)
        assert r_hi - max(r_lo, 1) + 1 <= 255
    # the disabled plane really is disabled
    engine_off = _load(monkeypatch, src, wire_on=False)
    assert engine_off._compiled.wire is None


def test_wire_and_flat_planes_agree(monkeypatch):
    """Same items through wire-on and wire-off engines: identical
    decisions, reason sets, and error attributions (the int8/bf16
    plane-agreement pattern, test_differential.py)."""
    src, items = _random_set_and_items(seed=14)
    res_on = _load(monkeypatch, src, True).evaluate_batch(items)
    res_off = _load(monkeypatch, src, False).evaluate_batch(items)
    for (d1, g1), (d2, g2) in zip(res_on, res_off):
        assert d1 == d2
        assert {r.policy for r in g1.reasons} == {r.policy for r in g2.reasons}
        assert len(g1.errors) == len(g2.errors)


def test_wire_kernel_words_and_bits_match_flat(monkeypatch):
    """Kernel-level equality including the want_bits diagnostics plane:
    words, full matrices, and the flagged-row bitmap agree between the two
    layouts for the exact same encoded rows."""
    src, items = _random_set_and_items(seed=15)
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
    E = max(max_e, 1)
    extras = np.full((len(rows), E), cs_on.packed.L, dtype=np.int32)
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


def test_wide_vocab_slot_routes_to_wide_lane(monkeypatch):
    """A slot with > 255 distinct vocab rows (300 resource names) must ride
    the wide lane — and decisions must still match the flat layout."""
    rng = random.Random(16)
    policies = [
        f'permit (principal, action == k8s::Action::"get", '
        f"resource is k8s::Resource) "
        f'when {{ resource.resource == "res-{i}" }};'
        for i in range(300)
    ]
    src = "\n".join(policies)
    eng_on = _load(monkeypatch, src, True)
    cs = eng_on._compiled
    ranges = cs.packed.table.slot_row_ranges()
    wide = [s for s, (lo, hi) in enumerate(ranges) if hi - max(lo, 1) + 1 > 255]
    assert wide, "expected at least one wide slot from a 300-value vocab"
    if cs.wire is not None:
        idx16 = set(cs.wire[1].tolist())
        assert set(wide) <= idx16
    items = [
        _sar("alice", "get", f"res-{rng.randint(0, 320)}") for _ in range(64)
    ]
    res_on = eng_on.evaluate_batch(items)
    res_off = _load(monkeypatch, src, False).evaluate_batch(items)
    for (d1, g1), (d2, g2) in zip(res_on, res_off):
        assert d1 == d2
        assert {r.policy for r in g1.reasons} == {r.policy for r in g2.reasons}


def test_wire_through_fastpath_raw(monkeypatch):
    """End-to-end: raw SAR bodies through the native fast path with the
    wire plane on vs off produce identical (decision, reason) results."""
    import json

    from cedar_tpu.engine.fastpath import SARFastPath
    from cedar_tpu.native import native_available
    from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
    from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

    if not native_available():
        pytest.skip("native encoder unavailable")
    src, _items = _random_set_and_items(seed=17)
    rng = random.Random(18)
    bodies = []
    for _ in range(256):
        bodies.append(
            json.dumps(
                {
                    "apiVersion": "authorization.k8s.io/v1",
                    "kind": "SubjectAccessReview",
                    "spec": {
                        "user": rng.choice(["alice", "bob", "eve"]),
                        "uid": "u",
                        "groups": rng.sample(["g1", "g2", "g3"], rng.randint(0, 2)),
                        "resourceAttributes": {
                            "verb": rng.choice(["get", "list", "create"]),
                            "version": "v1",
                            "resource": rng.choice(["pods", "secrets", "jobs"]),
                            "namespace": "default",
                        },
                    },
                }
            ).encode()
        )

    def run(wire_on):
        engine = _load(monkeypatch, src, wire_on)
        ps = PolicySet.from_source(src, "t0")
        auth = CedarWebhookAuthorizer(
            TieredPolicyStores([MemoryStore("t0", ps)]),
            evaluate=engine.evaluate,
        )
        fast = SARFastPath(engine, auth)
        assert fast.available
        return fast.authorize_raw(bodies)

    assert run(True) == run(False)
