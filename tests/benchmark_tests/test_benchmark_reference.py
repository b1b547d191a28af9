"""The plain reference: its parser, the ``sar`` kind's mapping of a
SubjectAccessReview, their agreement with a second witness (the program's
own interpreter, at a size both can hold), the controls that must not agree
with it, and the digests that pin what the accepted cells send and what the
reference answers."""

import hashlib
import json
import random

import pytest

from benchmark import reference as ref
from benchmark import traffic
from benchmark.corpora import selector, synth
from benchmark.kinds import sar
from benchmark.manifest import Manifest, corpus_module

DEMO = ["demo-require-owner-label.cedar", "demo-combined-authz-admission.cedar"]


def spec(user="alice", groups=(), verb="get", group="", resource="pods", **ra):
    return {
        "user": user, "uid": "u", "groups": list(groups),
        "resourceAttributes": {"verb": verb, "group": group, "version": "v1",
                               "resource": resource, **ra},
    }


def decide(policy_text, s, control=""):
    return sar.expected(ref.Reference({"p.cedar": policy_text}, control=control), s)


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
    assert sar.expected(r, spec())[2] == {"a.cedar.policy0", "b.cedar.policy0", "b.cedar.policy1"}


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
    assert sar.expected(r, s) == (True, False, {"demo-combined-authz-admission.cedar.policy0"})
    assert sar.expected(r, spec(user="bob", groups=["tenants"], verb="create",
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
    allowed, denied, ids = sar.verdict(response)
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
        return sar.verdict(sar_response(decision, reason))

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
            mine = sar.expected(plain, s)
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
    differ = sum(1 for s in specs if sar.expected(plain, s) != sar.expected(broken, s))
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


def test_evaluate_answers_an_environment_whatever_kind_of_request_made_it():
    """The evaluation proper takes principal, action, resource, context and
    entities, and knows nothing of a review object: here they are made by
    hand."""
    user, ops = ref.Entity(("k8s::User", "u")), ref.Entity(("k8s::Group", "ops"))
    thing = ref.Entity(("k8s::Resource", "resource"))
    env = {
        "principal": user, "action": ref.Entity(("k8s::Action", "get")), "resource": thing,
        "context": ref.Record(),
        "entities": {user: (ref.record({"name": "alice"}), frozenset({ops})),
                     ops: (ref.record({"name": "ops"}), frozenset()),
                     thing: (ref.record({"resource": "pods"}), frozenset())},
    }
    permits = ('permit (principal in k8s::Group::"ops", action, resource);'
               'permit (principal, action, resource) when { principal.name == "alice" };')
    forbid = 'forbid (principal, action, resource) when { resource.resource == "pods" };'
    assert ref.Reference({"p.cedar": permits}).evaluate(env) == (
        "allow", ["p.cedar.policy0", "p.cedar.policy1"])
    assert ref.Reference({"p.cedar": permits + forbid}).evaluate(env) == (
        "deny", ["p.cedar.policy2"])
    assert ref.Reference({"p.cedar": forbid}).evaluate(dict(env, resource=user)) == (None, [])
    # the controls live in the evaluation, so every kind's control is the same step
    assert ref.Reference({"p.cedar": permits}, control="first_reason_only").evaluate(env) == (
        "allow", ["p.cedar.policy0"])
    assert ref.Reference({"p.cedar": permits + forbid}, control="forbid_blind").evaluate(env)[0] \
        == "allow"


def test_the_sar_kind_states_its_five_things():
    assert sar.PATH == "/v1/authorize"
    s = spec()
    assert sar.body(s) == {"apiVersion": "authorization.k8s.io/v1",
                           "kind": "SubjectAccessReview", "spec": s}
    sar.distinct(s, "w-7")
    assert s["resourceAttributes"]["name"] == "w-7"
    gone = sar.verdict({"status": {"allowed": False, "evaluationError": "deadline exceeded"}})
    assert sar.gave_up(gone) and not sar.gave_up(sar.verdict({"status": {"allowed": True}}))
    # a non-resource request maps onto a k8s::NonResourceURL named by its path
    policy = ('permit (principal, action == k8s::Action::"get", resource is k8s::NonResourceURL) '
              'when { resource.path == "/healthz" };')
    url = {"user": "alice", "nonResourceAttributes": {"path": "/healthz", "verb": "get"}}
    assert decide(policy, url) == (True, False, {"p.cedar.policy0"})
    assert decide(policy, spec()) == (False, False, frozenset())


# Recorded from the parent's tree (PR 28, commit e26dc97) before any code moved
# behind benchmark/kinds/sar.py: the SHA-256 over the concatenated bodies of
# traffic.Plan at one second of window, and over the reference's answers to the
# plan's first 200 specs, each as json.dumps([allowed, denied, sorted(ids)]).
# What the accepted cells send, and what they are compared with, has not moved.
RECORDED = [
    ("synth-10k.sar-saturate", 29, 16000,
     "7d48e65e5e3c7b89475731764ec0fa6202d184730456160d224564013b2d8963",
     "af1f7bb9f1f6cceb33c1920239939cb3cd6e6b70cda7e222fcbed3ffc9689e88"),
    ("synth-10k.sar-saturate", 2900000029, 16000,
     "9112603c3d795b3244f3f0440a86b3e60de321895a77764005e3fe23b2b4ee24",
     "b4b7cc4142fde62f2bf23ff038c352cf463f26da7ccfc96bd0c9c75a47fb26c4"),
    ("selector-1k.sar-lone", 29, 4000,
     "cdf11bfa4858a95bb2e81fb07168b1fceeef7490adf15fbd1a056a905ee2d706",
     "e6916f6b99cbbebaa0edecd957845746f6ec4b30e0f8c942f6c5794bd77f2d8a"),
    ("selector-1k.sar-lone", 2900000029, 4000,
     "5a7817cbed918d422b46983d28ca5da57a461f305f3b4197cfc7a5f6337e662c",
     "4db1fd3190a7ccea33e8edffe4db09a5e710f1fa81878d00b415f3f35a9fd339"),
]


@pytest.mark.parametrize("cell,seed,n_bodies,bodies,answers", RECORDED,
                         ids=[f"{r[0]}-{r[1]}" for r in RECORDED])
def test_the_accepted_cells_send_the_bytes_and_get_the_answers_recorded_before_the_move(
        cell, seed, n_bodies, bodies, answers):
    m = Manifest()
    w = m.workload(cell)
    cfg = m.config(w["config"])
    corpus = corpus_module(cfg["corpus"]["generator"]).build(cfg["corpus"]["params"], seed)
    plan = traffic.Plan(corpus, m.traffic(w["traffic"]), m.cell(cell), seed, 1.0)
    assert plan.kind is sar
    assert len(plan.bodies) == n_bodies
    assert hashlib.sha256(b"".join(plan.bodies)).hexdigest() == bodies
    plain = ref.Reference(corpus.files)
    h = hashlib.sha256()
    for s in plan.specs[:200]:
        a = sar.expected(plain, s)
        h.update(json.dumps([a[0], a[1], sorted(a[2])]).encode())
    assert h.hexdigest() == answers
