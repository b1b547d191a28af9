"""The plain reference: its parser, its mapping of a SubjectAccessReview,
its agreement with a second witness (the program's own interpreter, at a
size both can hold), and the controls that must not agree with it."""

import json
import random

import pytest

from benchmark import reference as ref
from benchmark.corpora import selector, synth

DEMO = ["demo-require-owner-label.cedar", "demo-combined-authz-admission.cedar"]


def spec(user="alice", groups=(), verb="get", group="", resource="pods", **ra):
    return {
        "user": user, "uid": "u", "groups": list(groups),
        "resourceAttributes": {"verb": verb, "group": group, "version": "v1",
                               "resource": resource, **ra},
    }


def decide(policy_text, s, control=""):
    return ref.Reference({"p.cedar": policy_text}, control=control).decide(s)


PERMIT_PODS = (
    'permit (principal, action == k8s::Action::"get", resource is k8s::Resource) '
    'when { resource.resource == "pods" };'
)
FORBID_SECRETS = (
    'forbid (principal, action in [k8s::Action::"get", k8s::Action::"list"], '
    'resource is k8s::Resource) when { resource.resource == "secrets" };'
)


@pytest.mark.parametrize(
    "policies,request_,want",
    [
        (PERMIT_PODS, spec(), (True, False, {"p.cedar.policy0"})),
        (PERMIT_PODS, spec(resource="nodes"), (False, False, set())),
        (PERMIT_PODS, spec(verb="list"), (False, False, set())),
        (PERMIT_PODS + FORBID_SECRETS, spec(resource="secrets"),
         (False, True, {"p.cedar.policy1"})),
        # forbid overrides permit, and the reason names the forbid alone
        ('permit (principal, action, resource);' + FORBID_SECRETS,
         spec(resource="secrets"), (False, True, {"p.cedar.policy1"})),
        # two permits both determine
        (PERMIT_PODS + 'permit (principal, action, resource is k8s::Resource);',
         spec(), (True, False, {"p.cedar.policy0", "p.cedar.policy1"})),
        # group membership through the principal's parents
        ('permit (principal in k8s::Group::"ops", action, resource);',
         spec(groups=["ops"]), (True, False, {"p.cedar.policy0"})),
        ('permit (principal in k8s::Group::"ops", action, resource);',
         spec(groups=["dev"]), (False, False, set())),
        # a missing attribute is an error: the policy does not apply
        ('permit (principal, action, resource) when { resource.namespace == "a" };',
         spec(), (False, False, set())),
        ('permit (principal, action, resource) when '
         '{ resource has namespace && resource.namespace == "a" };',
         spec(namespace="a"), (True, False, {"p.cedar.policy0"})),
        # unless
        ('forbid (principal, action, resource) unless { resource has namespace };',
         spec(), (False, True, {"p.cedar.policy0"})),
        ('forbid (principal, action, resource) unless { resource has namespace };',
         spec(namespace="x"), (False, False, set())),
        # system users are skipped before any policy
        ('permit (principal, action, resource);', spec(user="system:kube-scheduler"),
         (False, False, set())),
        # principal types
        ('permit (principal is k8s::ServiceAccount, action, resource);',
         spec(user="system:serviceaccount:ns:sa"), (True, False, {"p.cedar.policy0"})),
        ('permit (principal is k8s::User, action, resource);',
         spec(user="system:serviceaccount:ns:sa"), (False, False, set())),
        # || and ! and !=
        ('permit (principal, action, resource) when '
         '{ !(resource.resource == "pods") || principal.name != "alice" };',
         spec(), (False, False, set())),
    ],
)
def test_decisions(policies, request_, want):
    allowed, denied, ids = decide(policies, request_)
    assert (allowed, denied, set(ids)) == want


SELECTOR_POLICY = (
    'permit (principal, action == k8s::Action::"list", resource is k8s::Resource) when { '
    'resource has labelSelector && resource.labelSelector.contains('
    '{key: "owner", operator: "in", values: ["team-1"]}) };'
)


@pytest.mark.parametrize(
    "requirements,allowed",
    [
        ([{"key": "owner", "operator": "In", "values": ["team-1"]}], True),
        ([{"key": "owner", "operator": "In", "values": ["team-2"]}], False),
        ([{"key": "owner", "operator": "NotIn", "values": ["team-1"]}], False),
        ([{"key": "tier", "operator": "In", "values": ["web"]},
          {"key": "owner", "operator": "In", "values": ["team-1"]}], True),
        ([{"key": "owner", "operator": "Bogus", "values": ["team-1"]}], False),
        ([], False),
    ],
)
def test_label_selectors_map_to_sets_of_records(requirements, allowed):
    s = spec(verb="list", labelSelector={"requirements": requirements})
    assert decide(SELECTOR_POLICY, s)[0] is allowed


def test_policy_ids_count_within_each_file():
    r = ref.Reference({"b.cedar": PERMIT_PODS + PERMIT_PODS, "a.cedar": PERMIT_PODS,
                       "notes.txt": "not a policy"})
    assert r.decide(spec())[2] == {"a.cedar.policy0", "b.cedar.policy0", "b.cedar.policy1"}


@pytest.mark.parametrize(
    "text",
    [
        'permit (principal, action, resource) when { resource.size < 3 };',
        'permit (principal, action, resource) when { ip("1.2.3.4").isLoopback() };',
        'allow (principal, action, resource);',
        'permit (principal, action, resource) when { resource.name like "a*" };',
    ],
)
def test_what_the_reference_does_not_cover_is_an_error(text):
    with pytest.raises(ref.ReferenceError_):
        ref.Reference({"p.cedar": text})


def test_the_demo_admission_policies_parse_and_never_answer_a_sar():
    files = {name: (synth.DATA / name).read_text() for name in DEMO}
    r = ref.Reference(files)
    assert len(r.policies) == 3
    # the one authorization policy among them: ci-bot may create configmaps
    s = spec(user="ci-bot", verb="create", resource="configmaps")
    assert r.decide(s) == (True, False, {"demo-combined-authz-admission.cedar.policy0"})
    assert r.decide(spec(user="bob", groups=["tenants"], verb="create",
                         resource="configmaps"))[:2] == (False, False)


@pytest.mark.parametrize(
    "response,want",
    [
        ({"status": {"allowed": True, "denied": False, "reason": json.dumps(
            {"reasons": [{"policy": "a.policy0", "position": {}}]})}},
         (True, False, {"a.policy0"})),
        ({"status": {"allowed": False, "denied": False, "reason": ""}}, (False, False, set())),
        ({"status": {"allowed": False, "reason": "", "evaluationError": "boom"}},
         (False, False, {"evaluationError: boom"})),
        ({"status": {"allowed": True, "reason": "free text"}},
         (True, False, {"unreadable reason: free text"})),
        ({}, (False, False, set())),
    ],
)
def test_served_verdict(response, want):
    allowed, denied, ids = ref.served_verdict(response)
    assert (allowed, denied, set(ids)) == want


def _program_interpreter(files, tmp_path):
    """The second witness: the program's own interpreter over the same files."""
    import yaml

    from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
    from cedar_tpu.server.http import get_authorizer_attributes, sar_response
    from cedar_tpu.stores.config import load_config_stores

    pol = tmp_path / "policies"
    pol.mkdir()
    for name, text in files.items():
        (pol / name).write_text(text)
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(yaml.safe_dump({
        "apiVersion": "cedar.k8s.aws/v1alpha1", "kind": "StoreConfig",
        "spec": {"stores": [{"type": "directory", "directoryStore": {
            "path": str(pol), "refreshInterval": "1h"}}]},
    }))
    stores = load_config_stores(str(cfg), timeout_s=60.0)
    auth = CedarWebhookAuthorizer(stores)

    def answer(s):
        decision, reason = auth.authorize(get_authorizer_attributes({"spec": s}))
        return ref.served_verdict(sar_response(decision, reason))

    def close():
        for s in stores.stores:
            getattr(s, "close", lambda: None)()

    return answer, close


@pytest.mark.parametrize(
    "module,params",
    [
        (synth, {"policies": 600, "clusters": 10, "extra_files": DEMO}),
        (selector, {"policies": 300}),
    ],
    ids=["synth", "selector"],
)
@pytest.mark.parametrize("seed", [1, 2_900_000_017, 42])
def test_reference_agrees_with_the_programs_interpreter(module, params, seed, tmp_path):
    corpus = module.build(params, seed)
    answer, close = _program_interpreter(corpus.files, tmp_path)
    try:
        plain = ref.Reference(corpus.files)
        rng = random.Random(seed)
        decisions = set()
        for i in range(150):
            s = corpus.spec(rng, 0.8)
            s["resourceAttributes"]["name"] = f"o-{i}"
            mine = plain.decide(s)
            assert mine == answer(s), s
            decisions.add(mine[:2])
        assert len(decisions) >= 2  # the traffic is not all one answer
    finally:
        close()


@pytest.mark.parametrize("control", ref.CONTROLS)
@pytest.mark.parametrize(
    "module,params",
    [(synth, {"policies": 2000, "clusters": 10}), (selector, {"policies": 300})],
    ids=["synth", "selector"],
)
def test_a_control_disagrees_with_the_reference(module, params, control):
    corpus = module.build(params, 11)
    plain = ref.Reference(corpus.files)
    broken = ref.Reference(corpus.files, control=control)
    rng = random.Random(11)
    specs = [corpus.spec(rng, 0.8) for _ in range(600)]
    differ = sum(1 for s in specs if plain.decide(s) != broken.decide(s))
    assert differ > 0


def test_unknown_control_is_refused():
    with pytest.raises(ValueError):
        ref.Reference({}, control="rounding")


@pytest.mark.parametrize("module,params", [
    (synth, {"policies": 500, "clusters": 10, "extra_files": DEMO}),
    (selector, {"policies": 200}),
], ids=["synth", "selector"])
def test_corpus_is_a_function_of_the_seed(module, params):
    a, b, c = (module.build(params, s) for s in (5, 5, 6))
    assert a.files == b.files
    assert a.files != c.files
    ra, rb = random.Random(1), random.Random(1)
    assert [a.spec(ra, 0.8) for _ in range(20)] == [b.spec(rb, 0.8) for _ in range(20)]
