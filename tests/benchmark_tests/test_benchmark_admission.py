"""The request kind ``admission`` and the pss-admit corpus: the kind's
mapping of an AdmissionReview case by case, its agreement with two
witnesses of the program (the interpreter over the tiered stores, and the
native fast path over a CPU engine) on the generator's own reviews at a
small tenancy, the controls that must not agree, what the generator
promises of its policies and objects, and the cell's per-layer files
against the program's own metric families."""

import json
import random

import pytest

from benchmark import prom
from benchmark import reference as ref
from benchmark.corpora import pss
from benchmark.kinds import admission as adm
from benchmark.manifest import Manifest, reader_module
from benchmark.run import Context

CELL = "pss-admit.admit-lone"
SMALL = {"tenants": 30}
SEEDS = [1, 2_900_000_017, 42]


def reviews(corpus, seed, n=150, aimed=0.5):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        s = corpus.spec(rng, aimed)
        adm.distinct(s, f"o-{i}")
        out.append(s)
    return out


# ------------------------------------------------------ the mapping, by case

def request(op="CREATE", kind="Pod", group="", ns="team-a", obj=None, old=None,
            user="alice", groups=("devs",), uid="r-1", name="web", **more):
    resource = {"Pod": "pods", "Deployment": "deployments", "ConfigMap": "configmaps"}[kind]
    return {"uid": uid, "operation": op, "name": name, "namespace": ns,
            "kind": {"group": group, "version": "v1", "kind": kind},
            "resource": {"group": group, "version": "v1", "resource": resource},
            "userInfo": {"username": user, "groups": list(groups)},
            "object": obj, "oldObject": old, "dryRun": False, **more}


def pod(ns="team-a", labels=None, **spec):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "web", "namespace": ns, "labels": labels or {}},
            "spec": spec, "status": {}}


def decide(policies, req, control=""):
    return adm.expected(ref.Reference({"p.cedar": policies}, control=control), req)


HOST_NET = ('forbid (principal, action == k8s::admission::Action::"create", '
            "resource is core::v1::Pod) when { resource.spec has hostNetwork && "
            "resource.spec.hostNetwork == true };")
NO_DELETE = ('forbid (principal in k8s::Group::"devs", action == k8s::admission::Action::"delete", '
             'resource) when { resource.metadata.namespace == "team-a" };')
IMMUTABLE = ('forbid (principal, action == k8s::admission::Action::"update", resource is core::v1::Pod) '
             "when { context has oldObject && resource has oldObject && "
             "context.oldObject.spec.nodeName != resource.spec.nodeName };")
DENY_0 = (False, {"p.cedar.policy0"})
ALLOW = (True, set())


@pytest.mark.parametrize("policies,req,want", [
    (HOST_NET, request(obj=pod(hostNetwork=True)), DENY_0),
    (HOST_NET, request(obj=pod(hostNetwork=False)), ALLOW),
    (HOST_NET, request(obj=pod()), ALLOW),
    # an UPDATE is another action; "all" is every action's parent
    (HOST_NET, request(op="UPDATE", obj=pod(hostNetwork=True), old=pod()), ALLOW),
    ('forbid (principal, action in k8s::admission::Action::"all", resource) '
     "when { resource.spec has hostPID };", request(op="UPDATE", obj=pod(hostPID=True), old=pod()),
     DENY_0),
    # the two namespaces that are never evaluated
    (HOST_NET, request(ns="kube-system", obj=pod(ns="kube-system", hostNetwork=True)), ALLOW),
    (HOST_NET, request(ns="cedar-k8s-authz-system", obj=pod(hostNetwork=True)), ALLOW),
    # DELETE evaluates the old object, and has no object
    (NO_DELETE, request(op="DELETE", old=pod()), DENY_0),
    (NO_DELETE, request(op="DELETE", old=pod(ns="team-b")), ALLOW),
    (NO_DELETE, request(op="DELETE", old=pod(), groups=("ops",)), ALLOW),
    # UPDATE: the old object under context.oldObject and resource.oldObject
    (IMMUTABLE, request(op="UPDATE", obj=pod(nodeName="n2"), old=pod(nodeName="n1")), DENY_0),
    (IMMUTABLE, request(op="UPDATE", obj=pod(nodeName="n1"), old=pod(nodeName="n1")), ALLOW),
    ('forbid (principal, action, resource) when { resource has oldObject && '
     'resource.oldObject.metadata.name == "web" };',
     request(op="UPDATE", obj=pod(), old=pod()), DENY_0),
    # a permit of the stores ends the walk as the allow-all tier would: no reason
    ("permit (principal, action, resource);", request(obj=pod()), ALLOW),
    # both determining forbids are named
    (HOST_NET + HOST_NET.replace("hostNetwork", "hostPID"),
     request(obj=pod(hostNetwork=True, hostPID=True)), (False, {"p.cedar.policy0", "p.cedar.policy1"})),
    # labels are a set of {key, value}; the principal's name joins
    ('forbid (principal, action, resource) unless { resource.metadata.labels.contains('
     '{key: "owner", value: principal.name}) };', request(obj=pod(labels={"owner": "alice"})), ALLOW),
    ('forbid (principal, action, resource) unless { resource.metadata.labels.contains('
     '{key: "owner", value: principal.name}) };', request(obj=pod(labels={"owner": "bob"})), DENY_0),
    # empty records are skipped (status: {}), nulls too; an empty label map is an empty set
    ("forbid (principal, action, resource) when { resource has status };",
     request(obj=pod()), ALLOW),
    ("forbid (principal, action, resource) when { resource.metadata has creationTimestamp };",
     request(obj={"metadata": {"creationTimestamp": None, "name": "x"}}), ALLOW),
    ("forbid (principal, action, resource) when { resource.metadata has labels };",
     request(obj=pod()), DENY_0),
    # lists are sets, ints Longs
    ('forbid (principal, action, resource) when { resource.spec.args.contains("--debug") && '
     "resource.spec.priority == 0 };", request(obj=pod(args=["-v", "--debug"], priority=0)), DENY_0),
    # a Pod's nodeSelector is a known string map; a Deployment's template's is a Record
    ('forbid (principal, action, resource is core::v1::Pod) when '
     '{ resource.spec.nodeSelector.contains({key: "pool", value: "gpu"}) };',
     request(obj=pod(nodeSelector={"pool": "gpu"})), DENY_0),
    ('forbid (principal, action, resource is apps::v1::Deployment) when '
     '{ resource.spec.template.spec.nodeSelector.pool == "gpu" };',
     request(kind="Deployment", group="apps", obj={"metadata": {"name": "d"}, "spec": {
         "template": {"spec": {"nodeSelector": {"pool": "gpu"}}}}}), DENY_0),
    # principals: a service account, a node, the groups as parents
    ("forbid (principal is k8s::ServiceAccount, action, resource) when "
     '{ principal.namespace == "kube-system" && principal.name == "replicaset-controller" };',
     request(obj=pod(), user="system:serviceaccount:kube-system:replicaset-controller"), DENY_0),
    ("forbid (principal is k8s::Node, action, resource);",
     request(obj=pod(), user="system:node:n1"), DENY_0),
    ("forbid (principal is k8s::User, action, resource);",
     request(obj=pod(), user="system:node:n1"), ALLOW),
    # the resource's id is the request's URL path
    ('forbid (principal, action, resource == core::v1::Pod::"/api/v1/namespaces/team-a/pods/web");',
     request(obj=pod()), DENY_0),
    ('forbid (principal, action, resource == apps::v1::Deployment::'
     '"/apis/apps/v1/namespaces/team-a/deployments/web");',
     request(kind="Deployment", group="apps", obj={"metadata": {"name": "web"}}), DENY_0),
])
def test_decisions(policies, req, want):
    allowed, ids = decide(policies, req)
    assert (allowed, set(ids)) == want


def test_what_the_mapping_has_no_type_for_is_an_error_never_a_skip():
    running = pod()
    running["status"] = {"podIP": "10.0.0.7", "podIPs": [{"ip": "10.0.0.7"}], "phase": "Running"}
    # carried: a policy that does not touch it is answered
    assert decide(HOST_NET, request(obj=running)) == (True, frozenset())
    with pytest.raises(ref.ReferenceError_, match="ipaddr"):
        decide('forbid (principal, action, resource) when { resource.status.podIP == "10.0.0.7" };',
               request(obj=running))
    # a string under an ip key that is no address stays a string
    running["status"]["podIP"] = "pending"
    assert not decide('forbid (principal, action, resource) when '
                      '{ resource.status.podIP == "pending" };', request(obj=running))[0]
    with pytest.raises(ref.ReferenceError_, match="float"):
        decide(HOST_NET, request(obj=pod(weight=0.5)))
    with pytest.raises(ref.ReferenceError_, match="unsupported operation"):
        decide(HOST_NET, request(op="PATCH", obj=pod()))
    with pytest.raises(ref.ReferenceError_, match="no object"):
        decide(HOST_NET, request(op="CREATE", obj=None))


@pytest.mark.parametrize("response,want,gave_up", [
    ({"response": {"uid": "r", "allowed": True, "status": {"code": 200, "message": ""}}},
     (True, frozenset()), False),
    ({"response": {"uid": "r", "allowed": False, "status": {"code": 200, "message": json.dumps(
        [{"policy": "a.cedar.policy3", "position": {}}, {"policy": "b.cedar.policy0"}])}}},
     (False, frozenset({"a.cedar.policy3", "b.cedar.policy0"})), False),
    # an error-deny: an empty message, which no reference deny equals
    ({"response": {"uid": "r", "allowed": False, "status": {"code": 200, "message": ""}}},
     (False, frozenset()), False),
    # the fail-open posture, a refused tenant, an unreadable message: the program gave up
    ({"response": {"uid": "r", "allowed": True, "status": {
        "code": 500, "message": "evaluation error (allowed on error): deadline"}}}, None, True),
    ({"response": {"uid": "", "allowed": False, "status": {"code": 403, "message": "tenant"}}},
     None, True),
    ({"response": {"allowed": False, "status": {"code": 200, "message": "not json"}}}, None, True),
    ({}, (False, frozenset()), False),
])
def test_served_verdict(response, want, gave_up):
    got = adm.verdict(response)
    assert adm.gave_up(got) is gave_up
    if want is not None:
        assert got == want
    else:
        assert got[1] and got not in ((True, frozenset()), (False, frozenset()))


def test_the_admission_kind_states_its_five_things():
    assert adm.PATH == "/v1/admit"
    s = pss.build(SMALL, 3).spec(random.Random(3), 0.5)
    posted = adm.body(s)
    assert posted["apiVersion"] == "admission.k8s.io/v1" and posted["kind"] == "AdmissionReview"
    assert posted["request"] is s
    assert {"uid", "operation", "userInfo", "kind", "resource", "namespace", "name", "object",
            "oldObject", "dryRun", "options"} <= set(s)
    assert s["dryRun"] is False
    adm.distinct(s, "w-17")
    assert s["name"] == "w-17" and s["uid"] == "review-w-17"
    assert {s[k]["metadata"]["name"] for k in ("object", "oldObject") if s[k]} == {"w-17"}
    assert callable(adm.expected) and callable(adm.verdict) and callable(adm.gave_up)
    # part of the reference: nothing of the program
    import pathlib

    for module in (adm, pss):
        assert "cedar_tpu" not in pathlib.Path(module.__file__).read_text().replace(
            "nothing of the program", "")


# ------------------------------------------- the corpus against two witnesses

class Program:
    """The program over the corpus as the webhook loads it: a directory
    store, the allow-all admission tier after it; the interpreter over the
    stores, and the native fast path over a CPU engine."""

    def __init__(self, files, tmp_path):
        import yaml

        from cedar_tpu.engine.evaluator import TPUPolicyEngine
        from cedar_tpu.engine.fastpath import AdmissionFastPath
        from cedar_tpu.server.admission import (
            CedarAdmissionHandler,
            allow_all_admission_policy_store,
        )
        from cedar_tpu.stores.config import load_config_stores
        from cedar_tpu.stores.store import TieredPolicyStores

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
        self._loaded = load_config_stores(str(cfg), timeout_s=60.0)
        self.stores = TieredPolicyStores(
            list(self._loaded.stores) + [allow_all_admission_policy_store()])
        self.interpreter = CedarAdmissionHandler(self.stores)
        self.engine = TPUPolicyEngine()
        self.load_stats = self.engine.load([s.policy_set() for s in self.stores], warm="off")
        self.fast = AdmissionFastPath(self.engine, CedarAdmissionHandler(
            self.stores, evaluate=self.engine.evaluate,
            evaluate_batch=self.engine.evaluate_batch))

    def by_interpreter(self, s):
        from cedar_tpu.entities.admission import AdmissionRequest

        req = AdmissionRequest.from_admission_review(adm.body(s))
        return adm.verdict(self.interpreter.handle(req).to_admission_review())

    def by_fast_path(self, specs):
        bodies = [json.dumps(adm.body(s)).encode() for s in specs]
        return [adm.verdict(r.to_admission_review()) for r in self.fast.handle_raw(bodies)]

    def errors_of(self, s) -> list:
        """The evaluation errors of the interpreter's tier walk."""
        from cedar_tpu.entities.admission import AdmissionRequest

        entities, request_ = self.interpreter._build(
            AdmissionRequest.from_admission_review(adm.body(s)))
        return list(self.stores.is_authorized(entities, request_)[1].errors)

    def close(self):
        for s in self._loaded.stores:
            getattr(s, "close", lambda: None)()


def erring(plain: ref.Reference, s) -> list:
    """The policies of the reference that err on a review: evaluate() skips
    them in silence, so each condition is wrapped to tell."""
    erred = []

    def telling(pid, cond):
        def run(env):
            try:
                return cond(env)
            except ref.EvalError:
                erred.append(pid)
                raise
        return run

    kept = plain.policies
    plain.policies = [
        (pid, forbid, *scopes, [(want, telling(pid, cond)) for want, cond in conds])
        for pid, forbid, *scopes, conds in kept]
    try:
        plain.evaluate(adm.environment(s))
    finally:
        plain.policies = kept
    return erred


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_with_the_programs_interpreter_and_its_native_path(seed, tmp_path):
    from cedar_tpu.native import native_available

    corpus = pss.build(SMALL, seed)
    program = Program(corpus.files, tmp_path)
    try:
        plain = ref.Reference(corpus.files)
        specs = reviews(corpus, seed)
        mine = [adm.expected(plain, s) for s in specs]
        for s, want in zip(specs, mine):
            assert want == program.by_interpreter(s), s["uid"]
        if native_available():
            # the whole corpus lowers: no policy is left to the interpreter
            assert program.load_stats["fallback_policies"] == 0
            assert program.load_stats["native_opaque_policies"] == 0
            assert program.by_fast_path(specs) == mine
        # what the stream holds: both decisions, every operation, both kinds,
        # a review that is never evaluated, a deny that names several policies
        seen = {(s["operation"], s["kind"]["kind"], want[0]) for s, want in zip(specs, mine)}
        assert seen >= {(op, kind, allowed) for op in ("CREATE", "UPDATE", "DELETE")
                        for kind in ("Pod", "Deployment") for allowed in (True, False)} - {
                            ("DELETE", "Deployment", False), ("DELETE", "Pod", False)}
        assert any(op == "DELETE" and not allowed for op, _, allowed in seen)
        assert any(s["namespace"] == "kube-system" for s in specs)
        assert any(len(ids) >= 2 for _, ids in mine)
        assert 0.35 < sum(1 for allowed, _ in mine if not allowed) / len(mine) < 0.65
        # no policy errs on any review, in the reference and in the program alike
        for s in specs:
            if s["namespace"] != "kube-system":
                assert erring(plain, s) == [] and program.errors_of(s) == [], s["uid"]
    finally:
        program.close()


def test_an_unguarded_access_is_what_the_guard_is_for(tmp_path):
    """The error the corpus avoids, shown once: the reference skips the
    erring policy (allowed), the webhook's walk ends on it (denied, no
    reason) — so the kind's answer holds only where no policy errs."""
    unguarded = {"p.cedar": 'forbid (principal, action, resource) when '
                            '{ resource.spec.securityContext.runAsUser == 0 };'}
    s = request(obj=pod())
    plain = ref.Reference(unguarded)
    assert erring(plain, s) == ["p.cedar.policy0"]
    program = Program(unguarded, tmp_path)
    try:
        assert program.errors_of(s)
        assert adm.expected(plain, s) == (True, frozenset())
        assert program.by_interpreter(s) == (False, frozenset())
    finally:
        program.close()


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_control_disagrees_with_the_reference(control):
    corpus = pss.build(SMALL, 11)
    plain, broken = ref.Reference(corpus.files), ref.Reference(corpus.files, control=control)
    differ = sum(1 for s in reviews(corpus, 11, n=400)
                 if adm.expected(plain, s) != adm.expected(broken, s))
    assert differ > 0


# ------------------------------------------------ what the generator promises

def test_corpus_and_stream_are_functions_of_the_seed():
    a, b, c = (pss.build(SMALL, s) for s in (5, 5, 6))
    assert a.files == b.files and a.cycle == b.cycle
    assert a.files != c.files and a.cycle != c.cycle
    assert reviews(a, 1, n=40) == reviews(b, 1, n=40)
    assert reviews(pss.build(SMALL, 5), 1, n=40) != reviews(pss.build(SMALL, 5), 2, n=40)


def test_the_tenancy_splits_by_level_and_every_policy_is_read():
    params = Manifest().config("pss-admit")["corpus"]["params"]
    corpus = pss.build(params, 2_900_000_003)
    levels = [t["level"] for t in corpus.tenants]
    assert {lv: levels.count(lv) for lv in set(levels)} == {
        "privileged": 30, "baseline": 180, "restricted": 90}
    plain = ref.Reference(corpus.files)   # every policy parses, or this raises
    assert len(plain.policies) == 22 + 2 * 300 + 3
    assert sum(1 for p in plain.policies if p[1]) == 22 + 2 * 300 + 2   # all but one: forbids
    by_file = {name: text.count("forbid (") for name, text in corpus.files.items()}
    assert by_file["pss.cedar"] == 22 and by_file["tenants.cedar"] == 600
    # a control names the namespaces of its level, and no privileged one
    privileged = [t["namespace"] for t in corpus.tenants if t["level"] == "privileged"]
    assert not any(f'"{ns}"' in corpus.files["pss.cedar"] for ns in privileged)


def test_every_stretch_of_a_stream_holds_the_same_operations_and_sizes():
    corpus = pss.build(SMALL, 9)
    assert len(corpus.cycle) == 120
    specs = reviews(corpus, 9, n=360)

    def shape(s):
        obj = s["object"] or s["oldObject"]
        spec = obj["spec"]["template"]["spec"] if obj["kind"] == "Deployment" else obj["spec"]
        return s["operation"], (len(spec["containers"]), len(spec["containers"][0]["env"]))

    stretches = [sorted(shape(s) for s in specs[k:k + 120]) for k in (0, 120, 240, 57)]
    assert all(st == sorted(corpus.cycle) for st in stretches)
    ops = [s["operation"] for s in specs]
    assert (ops.count("CREATE"), ops.count("UPDATE"), ops.count("DELETE")) == (216, 108, 36)
    for s in specs:
        assert (s["object"] is None) == (s["operation"] == "DELETE")
        assert (s["oldObject"] is None) == (s["operation"] == "CREATE")


@pytest.mark.parametrize("seed", SEEDS)
def test_objects_are_2_to_20_kb_whole_and_without_a_float(seed):
    corpus = pss.build(SMALL, seed)
    sizes, bodies = [], []

    def no_float(node):
        if isinstance(node, dict):
            return all(no_float(v) for v in node.values())
        if isinstance(node, list):
            return all(no_float(v) for v in node)
        return not isinstance(node, float)

    for s in reviews(corpus, seed, n=600):
        assert no_float(s)
        sizes += [len(json.dumps(s[k])) for k in ("object", "oldObject") if s[k] is not None]
        bodies.append(len(json.dumps(adm.body(s))))
        user = s["userInfo"]
        if user["username"].startswith("system:serviceaccount:"):
            assert (s["operation"], s["kind"]["kind"]) == ("CREATE", "Pod")
        else:
            assert 3 <= len(user["groups"]) <= 5 and user["groups"][0].endswith(":developers")
    assert 2_000 <= min(sizes) and max(sizes) <= 20_480
    assert 4_000 <= min(bodies) and max(bodies) <= 41_500
    by_controller = sum(1 for s in reviews(corpus, seed, n=600)
                        if s["userInfo"]["username"] == pss.REPLICASET_CONTROLLER)
    assert 0.2 < by_controller / 600 < 0.4   # 0.7 of the Pod CREATEs: 0.7 x 0.6 x 0.7


# ------------------------- the cell's files against the program's families

def test_the_admit_metrics_read_the_programs_own_families():
    """Every prom-read ``.admit`` file against a /metrics pair written from
    the program's own metric classes with known numbers: 100 reviews in 100
    batches between the scrapes (60 allowed, 40 denied), 12,000 bytes and
    1.5 extras each, 25 rows flagged, an authorization request beside them
    that must not be read."""
    from cedar_tpu.server import metrics as pm

    def scrape():
        return prom.parse(pm.REGISTRY.expose())

    phases = ("between", "read", "pre", "parse", "queue", "encode_wait", "encode",
              "dispatch_wait", "dispatch", "device_wait", "decode", "wake", "respond", "write")
    ms = dict(zip(phases, (40, 0.3, 0.1, 0.2, 0.9, 0.1, 1.2, 0.1, 1.0, 0.2, 0.8, 0.1, 0.1, 0.4)))
    ctx = Context()
    ctx.prom_before = scrape()
    for i in range(100):
        pm.record_admission_latency("allowed" if i < 60 else "denied", 4.7e-3)
        pm.record_request_body_bytes("admission", 12_000)
        pm.record_batch_occupancy("admission", 1)
        stamps = [0.0]
        for p in phases:
            stamps.append(stamps[-1] + ms[p] * 1e-3)
        pm.record_request_phases("admission", phases, tuple(stamps))
        for stage, value in (("queue_wait", 0.9), ("encode", 1.2), ("dispatch", 1.0),
                             ("dispatch.launch", 0.8), ("decode", 0.8),
                             ("decode.device_wait", 0.3)):
            pm.record_pipeline_stage("admission", stage, value * 1e-3)
    pm.record_encode_extras("admission", 150, 100)
    pm.record_row_routing("admission", "clean_native", 70)
    pm.record_row_routing("admission", "flagged", 25)
    pm.record_row_routing("admission", "encoder_gate", 5)
    pm.record_request_latency("allow", 9.0)
    pm.record_request_body_bytes("authorization", 400)
    pm.record_row_routing("authorization", "encoder_fallback", 7)
    ctx.prom_after = scrape()
    timer = sum(ms[p] for p in phases[3:-1])
    worked_out = {
        "ingress_ms.admit": 4.7, "dispatch_ms_per_batch.admit": 1.0,
        "fallback_row_share.admit": 0.0, "batch_rows.admit": 1.0, "queue_wait_ms.admit": 0.9,
        "decode_us_per_row.admit": 800.0, "decode_device_wait_ms.admit": 0.3,
        "dispatch_launch_ms.admit": 0.8, "http_io_ms.admit": 0.3 + 0.1 + 0.4,
        "handler_host_ms.admit": 0.2 + 0.1, "pipeline_wait_ms.admit": 0.1 + 0.1 + 0.2,
        "timer_accounted_share.admit": 100.0 * timer / 4.7,
        "body_kb_per_request.admit": 12.0, "encode_us_per_kb.admit": 100.0,
        "extras_per_row.admit": 1.5, "flagged_row_share.admit": 25.0,
    }
    m = Manifest()
    listed = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    # the device's two read the same module of the trace in every lone cell:
    # the `.lone` entries, which list this cell beside the others
    traced = {"match_roofline.admit", "device_idle_share.lone", "device_ms_per_batch.lone"}
    assert set(worked_out) | traced <= listed
    assert {n for n in listed if n.endswith(".admit")} - set(worked_out) == {
        "match_roofline.admit", "bits_readback_share.admit"}
    for name, want in worked_out.items():
        spec = m.metric_file(name)
        assert spec["workloads"] == [CELL] and spec["moves"] == "latency_p50_ms"
        got = reader_module(spec["reader"]).read(ctx, spec["params"])
        assert got == pytest.approx(want, rel=1e-9, abs=1e-9), name
    for name in traced:   # no trace, nothing to read; and the engine is admission's
        spec = m.metric_file(name)
        assert reader_module(spec["reader"]).read(ctx, spec["params"]) is None
        assert spec["source"] == "device_trace"
    assert m.metric_file("match_roofline.admit")["params"]["engine"] == "admission"
    # on a server without the new families (the parent) the readers of them
    # find nothing and raise nothing
    old = Context()
    old.prom_before, old.prom_after = [], [
        s for s in ctx.prom_after if not s[0].startswith((
            "cedar_admission_request_duration", "cedar_request_body_bytes", "cedar_encode_extras"))]
    for name in ("ingress_ms.admit", "body_kb_per_request.admit", "encode_us_per_kb.admit",
                 "extras_per_row.admit", "timer_accounted_share.admit"):
        spec = m.metric_file(name)
        assert reader_module(spec["reader"]).read(old, spec["params"]) is None


def test_the_cell_is_the_configuration_under_the_mix_the_issue_gave():
    m = Manifest()
    w = m.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == ("pss-admit", "admit-lone", 1)
    mix = m.traffic("admit-lone")
    assert (mix["kind"], mix["loop"], mix["connections"], mix["processes"]) == (
        "admission", "closed", 1, 1)
    assert mix["name_per_request"] is True and mix["warmup_s"] == 3 and mix["aimed_share"] == 0.5
    assert mix["pool_per_s"] == mix["precompute_per_s"]
    cfg = m.config("pss-admit")
    assert next(c for c in m.doc["configs"] if c["name"] == "pss-admit")["reduced"] == []
    assert {"source", "deployment", "shapes", "guarantees", "assumed", "departures"} <= set(cfg)
    assert {"tenancy", "operation_and_principal_mix", "size_cycle", "request_timeout_ms",
            "no_policy_errs"} <= set(cfg["assumed"])
    assert "stays off" in cfg["guarantees"]["decision_cache"]
    assert "status.code 500" in cfg["guarantees"]["failures"]
    # every control that is left out has its reason
    assert len(cfg["departures"]) >= 6 and all(len(why) > 40 for why in cfg["departures"].values())
    for left_out in ("capabilities", "hostPath", "sysctls", "AppArmor"):
        assert any(left_out in k for k in cfg["departures"])
