"""Differential tests: the TPU tensor evaluator must produce identical
decisions to the interpreter oracle on the same (policy set, request) pairs.

This is the conformance mechanism SURVEY.md §4 calls for: the interpreter is
the reference-semantics oracle; the compiled matmul path must agree decision-
for-decision, including tier descent, error semantics, and default deny.
"""

import random


import pytest

from cedar_tpu.engine.evaluator import TPUPolicyEngine
from cedar_tpu.entities.attributes import (
    Attributes,
    LabelSelectorRequirement,
    UserInfo,
)
from cedar_tpu.lang import PolicySet
from cedar_tpu.server.authorizer import record_to_cedar_resource
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores


def interp_decision(tier_sources, entities, request):
    stores = TieredPolicyStores(
        [MemoryStore.from_source(f"t{i}", s) for i, s in enumerate(tier_sources)]
    )
    return stores.is_authorized(entities, request)


def tpu_decision(tier_sources, entities, request):
    engine = TPUPolicyEngine()
    engine.load(
        [PolicySet.from_source(s, f"t{i}") for i, s in enumerate(tier_sources)]
    )
    return engine.evaluate(entities, request)


def _err_policies(errors):
    """Erroring policy ids from diagnostics messages (the message TEXT
    differs between paths — the device only knows 'evaluation error' — but
    the SET of erroring policies must be identical)."""
    import re

    return {m.group(1) for m in (re.search(r"`([^`]+)`", e) for e in errors) if m}


def check(tier_sources, attributes_list):
    """Assert interpreter and TPU paths agree for every request: decision,
    complete reason SET (every determining policy, like cedar-go's
    Diagnostic.Reasons at /root/reference internal/server/store/store.go:31),
    and erroring-policy set. Ordering is not a contract."""
    engine = TPUPolicyEngine()
    engine.load(
        [PolicySet.from_source(s, f"t{i}") for i, s in enumerate(tier_sources)]
    )
    stores = TieredPolicyStores(
        [MemoryStore.from_source(f"t{i}", s) for i, s in enumerate(tier_sources)]
    )
    items = [record_to_cedar_resource(a) for a in attributes_list]
    tpu_results = engine.evaluate_batch(items)
    for (em, req), (tpu_dec, tpu_diag), attrs in zip(
        items, tpu_results, attributes_list
    ):
        int_dec, int_diag = stores.is_authorized(em, req)
        assert tpu_dec == int_dec, (
            f"decision mismatch for {attrs}: tpu={tpu_dec} interp={int_dec}"
        )
        tpu_reasons = {r.policy for r in tpu_diag.reasons}
        int_reasons = {r.policy for r in int_diag.reasons}
        assert tpu_reasons == int_reasons, (
            f"reason-set mismatch for {attrs}: "
            f"tpu={sorted(tpu_reasons)} interp={sorted(int_reasons)}"
        )
        assert _err_policies(tpu_diag.errors) == _err_policies(int_diag.errors), (
            f"error-set mismatch for {attrs}: "
            f"tpu={tpu_diag.errors} interp={int_diag.errors}"
        )
    return engine


USER = UserInfo(name="test-user", uid="u1", groups=("viewers", "devs"))
SA = UserInfo(name="system:serviceaccount:default:default", uid="sa1",
              extra={"authentication.kubernetes.io/node-name": ("node-a",)})


def sar(user=USER, verb="get", resource="pods", name="", namespace="default",
        api_group="", subresource="", path="", resource_request=True,
        selector=None):
    a = Attributes(
        user=user, verb=verb, namespace=namespace, api_group=api_group,
        api_version="v1", resource=resource, subresource=subresource,
        name=name, resource_request=resource_request, path=path,
    )
    if selector:
        a.label_selector = selector
    return a


DEMO = """
permit (
    principal,
    action in [k8s::Action::"get", k8s::Action::"list", k8s::Action::"watch"],
    resource is k8s::Resource
) when { principal.name == "test-user" && resource.resource == "pods" };
forbid (
    principal,
    action in [k8s::Action::"get", k8s::Action::"list", k8s::Action::"watch"],
    resource is k8s::Resource
) when { principal.name == "test-user" && resource.resource == "nodes" };
permit (
    principal in k8s::Group::"viewers",
    action in [k8s::Action::"get", k8s::Action::"list", k8s::Action::"watch"],
    resource is k8s::Resource
) unless { resource.resource == "secrets" && resource.apiGroup == "" };
"""


def test_demo_policy_matrix():
    cases = [
        sar(verb="get", resource="pods"),
        sar(verb="list", resource="pods"),
        sar(verb="get", resource="nodes"),
        sar(verb="delete", resource="pods"),
        sar(verb="get", resource="secrets"),
        sar(verb="get", resource="deployments", api_group="apps"),
        sar(user=UserInfo(name="stranger", uid="s1"), verb="get", resource="pods"),
        sar(user=UserInfo(name="bob", uid="b1", groups=("viewers",)),
            verb="watch", resource="configmaps"),
    ]
    engine = check([DEMO], cases)
    # everything in the demo set should be lowerable — no fallback
    assert engine.stats["fallback_policies"] == 0


def test_multi_match_reason_sets():
    """Several policies matching the same request must ALL be reported —
    cedar-go returns every determining policy (store.go:31), and admission
    deny messages render the whole list (handler.go:157-164)."""
    src = """
permit (principal, action, resource) when { principal.name == "test-user" };
permit (principal, action, resource) when { resource.resource == "pods" };
permit (principal in k8s::Group::"viewers", action, resource);
forbid (principal, action, resource) when { resource.resource == "nodes" };
forbid (principal, action, resource)
    when { principal.name == "test-user" && resource.resource == "nodes" };
"""
    cases = [
        sar(),  # 3 permits match -> allow with 3 reasons
        sar(resource="nodes"),  # 2 forbids + permits -> deny with 2 reasons
        sar(user=UserInfo(name="x", uid="x"), resource="configmaps"),  # none
        sar(user=UserInfo(name="x", uid="x", groups=("viewers",))),  # 2 permits
    ]
    engine = check([src], cases)
    assert engine.stats["fallback_policies"] == 0
    # sanity: the multi-match rows really do produce >1 reason
    em, req = record_to_cedar_resource(cases[0])
    _, diag = engine.evaluate(em, req)
    assert len(diag.reasons) == 3


def test_multi_match_across_tiers():
    """Multi-match resolution respects tier boundaries: only the winning
    tier's matches are reported."""
    t0 = 'permit (principal, action, resource) when { resource.resource == "pods" };'
    t1 = """
permit (principal, action, resource);
forbid (principal, action, resource) when { resource.resource == "nodes" };
forbid (principal, action, resource) when { principal.name == "test-user" };
"""
    check([t0, t1], [sar(), sar(resource="nodes"), sar(resource="svc")])


def test_error_set_with_multiple_erroring_policies():
    """More than one policy erroring on the same request: the complete
    erroring-policy set must surface (multi bit on the error group)."""
    src = """
permit (principal, action, resource) when { resource.subresource == "a" };
permit (principal, action, resource) when { resource.subresource == "b" };
permit (principal, action, resource) when { principal.name == "test-user" &&
                                            resource.resource == "pods" };
"""
    # without a subresource both unguarded accesses error... unless the
    # compiler has-guards them; either way sets must agree with the oracle
    check([src], [sar(), sar(subresource="a"), sar(subresource="c")])


def test_match_bits_arrays_splits_large_batches(monkeypatch):
    """Batches beyond the fixed chunk size must split into multiple kernel
    calls whose concatenated rows match the single-chunk result."""
    import numpy as np

    from cedar_tpu.engine import evaluator as ev

    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(DEMO, "t0")])
    cs = engine._compiled
    items = [record_to_cedar_resource(sar()) for _ in range(5)]
    from cedar_tpu.compiler.table import encode_request_codes

    packed = cs.packed
    encoded = [
        encode_request_codes(packed.plan, packed.table, em, req)
        for em, req in items
    ]
    codes, extras = engine._encode_batch_arrays(cs, encoded, len(encoded))
    # replicate rows beyond a (shrunken) sub-batch size and compare with the
    # unsplit result row-by-row
    reps = 9
    big_c = np.repeat(codes, reps, axis=0)
    big_e = np.repeat(extras, reps, axis=0)
    small = engine.match_bits_arrays(codes, extras, cs=cs)
    monkeypatch.setattr(ev.TPUPolicyEngine, "_BITS_CHUNK", 8)
    big = engine.match_bits_arrays(big_c, big_e, cs=cs)
    assert big.shape[0] == len(items) * reps
    for i in range(len(items)):
        for r in range(reps):
            assert (big[i * reps + r] == small[i]).all()


def test_tier_stacks():
    allow = 'permit (principal, action, resource) when { resource.resource == "pods" };'
    deny = 'forbid (principal, action, resource) when { resource.resource == "pods" };'
    nothing = 'permit (principal, action, resource) when { resource.resource == "zzz" };'
    allow_all = "permit (principal, action, resource);"
    for tiers in (
        [allow, deny],
        [deny, allow],
        [nothing, allow_all],
        [nothing, nothing],
        [allow],
        [nothing, deny, allow_all],
    ):
        check(tiers, [sar(), sar(resource="svc")])


def test_like_patterns():
    src = """
permit (
    principal,
    action == k8s::Action::"get",
    resource is k8s::NonResourceURL
) when { resource.path like "/healthz/*" || resource.path == "/version" };
"""
    cases = [
        sar(resource_request=False, path="/healthz/live", resource=""),
        sar(resource_request=False, path="/healthz", resource=""),
        sar(resource_request=False, path="/version", resource=""),
        sar(resource_request=False, path="/metrics", resource=""),
    ]
    check([src], cases)


def test_impersonation():
    src = """
permit (
    principal,
    action == k8s::Action::"impersonate",
    resource is k8s::Node
) when { principal.name == "test-user" && resource.name == "node-1" };
permit (
    principal,
    action == k8s::Action::"impersonate",
    resource == k8s::PrincipalUID::"1234"
);
"""
    cases = [
        sar(verb="impersonate", resource="users", name="system:node:node-1"),
        sar(verb="impersonate", resource="users", name="system:node:node-2"),
        sar(verb="impersonate", resource="users", name="alice"),
        sar(verb="impersonate", resource="uids", name="1234"),
        sar(verb="impersonate", resource="uids", name="999"),
        sar(verb="impersonate", resource="groups", name="admins"),
    ]
    check([src], cases)


def test_extra_contains_hard_literal():
    src = """
permit (
    principal is k8s::ServiceAccount,
    action == k8s::Action::"get",
    resource is k8s::Resource
) when {
    principal.name == "default" &&
    resource.resource == "nodes" &&
    resource has name &&
    principal.extra.contains({
        "key": "authentication.kubernetes.io/node-name",
        "values": [resource.name]})
};
"""
    cases = [
        sar(user=SA, resource="nodes", name="node-a", namespace=""),
        sar(user=SA, resource="nodes", name="node-b", namespace=""),
        sar(user=SA, resource="pods", name="p", namespace=""),
        sar(resource="nodes", name="node-a", namespace=""),
    ]
    check([src], cases)


def test_label_selector_forbid_unless():
    src = """
forbid (
    principal is k8s::User in k8s::Group::"requires-labels",
    action in [k8s::Action::"list", k8s::Action::"watch"],
    resource is k8s::Resource
) unless {
    resource has labelSelector &&
    resource.labelSelector.containsAny([
        {"key": "owner", "operator": "=", "values": [principal.name]},
        {"key": "owner", "operator": "==", "values": [principal.name]},
        {"key": "owner", "operator": "in", "values": [principal.name]}])
};
permit (principal, action, resource);
"""
    u = UserInfo(name="dev1", uid="d1", groups=("requires-labels",))
    sel = (LabelSelectorRequirement(key="owner", operator="=", values=("dev1",)),)
    wrong = (LabelSelectorRequirement(key="owner", operator="=", values=("other",)),)
    cases = [
        sar(user=u, verb="list"),
        sar(user=u, verb="list", selector=sel),
        sar(user=u, verb="list", selector=wrong),
        sar(user=u, verb="get"),
        sar(verb="list"),
    ]
    engine = check([src], cases)
    # the negated containsAny must have been lowered, not fallen back
    assert engine.stats["fallback_policies"] == 0


def test_unguarded_negation_hardened_with_has_guard():
    # `resource.subresource != "status"` errors in Cedar when the attribute
    # is missing; the compiler inserts a HAS guard instead of falling back
    src = """
permit (principal, action, resource)
when { resource.subresource != "status" };
permit (principal, action, resource)
when { principal.name == "test-user" && resource.resource == "pods" };
"""
    cases = [
        sar(),  # no subresource -> first policy errors in Cedar
        sar(subresource="status"),
        sar(subresource="log"),
    ]
    engine = check([src], cases)
    assert engine.stats["fallback_policies"] == 0


def test_negated_arithmetic_lowers_via_host_guard():
    # negated arithmetic can overflow-error; the HARD_OK guard path
    # (compiler/dyn.host_guardable) now lowers it — host evaluation
    # classifies bool-vs-error per request, so the clause dies on exactly
    # the requests where Cedar skips the policy — instead of dragging the
    # whole policy to the interpreter
    src = """
permit (principal, action, resource)
unless { context has n && context.n + 1 == 2 };
permit (principal, action, resource)
when { principal.name == "test-user" && resource.resource == "pods" };
"""
    cases = [sar(), sar(resource="svc")]
    engine = check([src], cases)
    assert engine.stats["fallback_policies"] == 0


def test_unlowerable_alternation_blowup_goes_to_fallback():
    # an ordered-DNF expansion past the spillover ceiling
    # (SPILL_MAX_CLAUSES) is the construct that still falls back: 13^3
    # alternation product = 2197 raw clauses
    names = " || ".join(f'resource.name == "n{i}"' for i in range(13))
    nss = " || ".join(f'resource.namespace == "ns{i}"' for i in range(13))
    subs = " || ".join(f'resource.subresource == "s{i}"' for i in range(13))
    src = f"""
permit (principal, action, resource)
when {{ ({names}) && ({nss}) && ({subs}) }};
permit (principal, action, resource)
when {{ principal.name == "test-user" && resource.resource == "pods" }};
"""
    cases = [sar(), sar(resource="svc"), sar(name="n3", namespace="ns5",
                                             subresource="s7")]
    engine = check([src], cases)
    assert engine.stats["fallback_policies"] >= 1


def test_has_guard_lowered_not_fallback():
    src = """
permit (principal, action, resource)
when { resource has subresource && resource.subresource == "status" };
"""
    engine = check(
        [src], [sar(), sar(subresource="status"), sar(subresource="log")]
    )
    assert engine.stats["fallback_policies"] == 0


def test_unless_has_negation():
    src = """
permit (principal, action, resource)
when { principal.name == "test-user" }
unless { resource has subresource };
"""
    check([src], [sar(), sar(subresource="status")])


def test_or_chain_same_slot():
    src = """
permit (principal, action, resource)
when {
    resource.resource == "pods" ||
    resource.resource == "services" ||
    resource.resource == "endpoints" ||
    ["batch", "apps"].contains(resource.apiGroup)
};
"""
    cases = [
        sar(resource="pods"),
        sar(resource="services"),
        sar(resource="endpoints"),
        sar(resource="jobs", api_group="batch"),
        sar(resource="deployments", api_group="apps"),
        sar(resource="secrets"),
    ]
    engine = check([src], cases)
    assert engine.stats["fallback_policies"] == 0


def test_batch_mixed_requests():
    users = [
        USER,
        SA,
        UserInfo(name="bob", uid="b", groups=("viewers",)),
        UserInfo(name="eve", uid="e"),
    ]
    verbs = ["get", "list", "create", "delete", "impersonate"]
    resources = ["pods", "nodes", "secrets", "configmaps"]
    rng = random.Random(42)
    cases = []
    for _ in range(64):
        cases.append(
            sar(
                user=rng.choice(users),
                verb=rng.choice(verbs),
                resource=rng.choice(resources),
                name=rng.choice(["", "obj-1", "node-a"]),
                namespace=rng.choice(["", "default", "kube-system"]),
                api_group=rng.choice(["", "apps"]),
                subresource=rng.choice(["", "status"]),
            )
        )
    check([DEMO], cases)


def test_randomized_policies_differential():
    rng = random.Random(7)
    names = ["alice", "bob", "carol"]
    resources = ["pods", "services", "secrets"]
    verbs = ["get", "list", "create"]
    groups = ["g1", "g2"]
    policies = []
    for i in range(40):
        effect = rng.choice(["permit", "forbid"])
        scope_p = rng.choice(
            ["principal", 'principal in k8s::Group::"%s"' % rng.choice(groups),
             "principal is k8s::User"]
        )
        scope_a = rng.choice(
            ["action", 'action == k8s::Action::"%s"' % rng.choice(verbs),
             'action in [k8s::Action::"get", k8s::Action::"list"]']
        )
        conds = []
        if rng.random() < 0.8:
            conds.append(
                'principal.name == "%s"' % rng.choice(names)
            )
        if rng.random() < 0.8:
            conds.append('resource.resource == "%s"' % rng.choice(resources))
        if rng.random() < 0.3:
            conds.append('resource has subresource && resource.subresource == "status"')
        if rng.random() < 0.2:
            conds.append(
                '["%s", "%s"].contains(resource.resource)'
                % (rng.choice(resources), rng.choice(resources))
            )
        body = " && ".join(conds) if conds else "true"
        if rng.random() < 0.3 and conds:
            body = body.replace(" && ", " || ", 1)
        kind = rng.choice(["when", "unless"])
        policies.append(
            f"{effect} ({scope_p}, {scope_a}, resource is k8s::Resource) "
            f"{kind} {{ {body} }};"
        )
    src = "\n".join(policies)
    cases = []
    for _ in range(80):
        cases.append(
            sar(
                user=UserInfo(
                    name=rng.choice(names + ["dave"]),
                    uid="u",
                    groups=tuple(rng.sample(groups, rng.randint(0, 2))),
                ),
                verb=rng.choice(verbs + ["delete"]),
                resource=rng.choice(resources + ["nodes"]),
                subresource=rng.choice(["", "status", "log"]),
            )
        )
    check([src], cases)


def test_want_bits_bitmap_matches_bits_kernel():
    """The compacted in-call bits payload (match_arrays want_bits) must be
    row-identical to the standalone bitset kernel, cover exactly the
    flagged rows, and never report bucket-padding rows."""
    import numpy as np

    from cedar_tpu.compiler.table import encode_request_codes
    from cedar_tpu.ops.match import WORD_ERR, WORD_MULTI

    src = """
permit (principal, action, resource) when { principal.name == "test-user" };
permit (principal, action, resource) when { resource.resource == "pods" };
forbid (principal, action, resource) when { resource.resource == "nodes" };
"""
    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(src, "t0")], warm="off")
    cs = engine._compiled
    packed = cs.packed
    cases = [
        sar(),  # multi-allow (2 permits)
        sar(user=UserInfo(name="x", uid="x"), resource="configmaps"),  # none
        sar(resource="nodes"),  # single forbid
    ]
    encoded = [
        encode_request_codes(packed.plan, packed.table, *record_to_cedar_resource(a))
        for a in cases
    ]
    codes, extras = engine._encode_batch_arrays(cs, encoded, len(encoded))
    words, _, bitmap = engine.match_arrays(codes, extras, cs=cs, want_bits=True)
    flagged = set(
        np.nonzero((words.astype(np.uint32) & (WORD_ERR | WORD_MULTI)) != 0)[0].tolist()
    )
    assert set(bitmap) == flagged
    assert all(0 <= i < len(cases) for i in bitmap)  # no padding rows
    ref = engine.match_bits_arrays(codes, extras, cs=cs)
    for i, row in bitmap.items():
        assert (row == ref[i]).all()


def test_bits_compaction_overflow_falls_back():
    """More flagged rows than the device compaction carries (BITS_TOPK):
    the overflow rows must still render exact reason sets via the
    standalone bitset kernel. Driven through the want_bits surface
    directly — the in-call compaction now serves only the latency-regime
    fast-path batches, so evaluate_batch no longer reaches it."""
    import numpy as np

    from cedar_tpu.compiler.table import encode_request_codes
    from cedar_tpu.ops.match import BITS_TOPK, WORD_MULTI

    src = """
permit (principal, action, resource) when { resource.resource == "pods" };
permit (principal, action, resource) when { principal.name == "test-user" };
"""
    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(src, "t0")], warm="off")
    cs = engine._compiled
    packed = cs.packed
    n = BITS_TOPK + 88  # > K once the batch bucket exceeds BITS_TOPK
    items = [record_to_cedar_resource(sar()) for _ in range(n)]
    encoded = [
        encode_request_codes(packed.plan, packed.table, em, rq)
        for em, rq in items
    ]
    codes, extras = engine._encode_batch_arrays(cs, encoded, n)
    words, _, bitmap = engine.match_arrays(codes, extras, cs=cs, want_bits=True)
    w = words.astype(np.uint32)
    assert ((w & WORD_MULTI) != 0).sum() == n  # every row double-matches
    # the in-call payload covers at most BITS_TOPK rows; the rest MUST be
    # absent (resolve_flagged fetches them via the standalone kernel)
    assert 0 < len(bitmap) <= BITS_TOPK < n
    resolved = engine.resolve_flagged(words, codes, extras, cs=cs, bitmap=bitmap)
    assert set(resolved) == set(range(n))
    for decision, diag in resolved.values():
        assert decision == "allow"
        assert len(diag.reasons) == 2
    # end-to-end the python path renders the same sets
    results = engine.evaluate_batch(items)
    assert len(results) == n
    for decision, diag in results:
        assert decision == "allow"
        assert len(diag.reasons) == 2
