"""Decision explainability plane (cedar_tpu/explain, docs/explainability.md).

The load-bearing pieces:

  * a ≥1.1k-body differential proving the NON-explain serving path is
    byte-identical between a server whose explain plane was exercised and
    one that never explained — and that explain requests never populate
    the decision cache;
  * lazy-compile pay-for-use: zero fresh kernel traces until the first
    ?explain=1 request (trace-counter-asserted), which then compiles
    exactly the explain shapes;
  * ?explain=1 over HTTP on BOTH /v1/authorize and /v1/admit returning
    determining policy id + clause + per-test attribute/operator/value
    with source spans;
  * host-computed explanations for breaker-open and engine-less
    (interpreter) deployments, and interpreter-fallback policies
    attributed with fallback=true + their unlowerable reason code;
  * the cedar-why CLI: fingerprint join, no-match exit code, unparseable
    counting, live-vs-candidate trees;
  * rollout diff exemplars carrying live and candidate determining-policy
    attribution.
"""

import io
import json
import urllib.request
from contextlib import redirect_stderr, redirect_stdout

import numpy as np
import pytest

from cedar_tpu.cache import DecisionCache
from cedar_tpu.engine.breaker import CircuitBreaker
from cedar_tpu.engine.evaluator import TPUPolicyEngine
from cedar_tpu.explain import Explainer
from cedar_tpu.lang import PolicySet
from cedar_tpu.ops.match import kernel_trace_count
from cedar_tpu.rollout import RolloutController
from cedar_tpu.server.admission import (
    CedarAdmissionHandler,
    allow_all_admission_policy_store,
)
from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
from cedar_tpu.server.http import WebhookServer
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

FILENAME = "explain-test"

POLICIES = """
permit (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "alice" && resource.resource == "pods" };
forbid (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "carol" && resource.resource == "secrets" };
permit (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { resource.resource == "pods" };
forbid (principal is k8s::User,
        action == k8s::admission::Action::"create",
        resource is core::v1::ConfigMap)
  when { resource.metadata has labels &&
         resource.metadata.labels.contains({key: "env", value: "prod"}) };
"""

# the overlapping pods permits make alice/pods a multi-reason row

# still unlowerable AFTER the burn-down (docs/lowering.md): an ordered-DNF
# alternation product past the spillover ceiling (2^12 > SPILL_MAX_CLAUSES).
# Every disjunction is true for the test SAR (resource == "pods"), so the
# interpreter fallback ALLOWS it.
UNLOWERABLE = (
    "permit (principal, action, resource) when { "
    + " && ".join(
        f'(resource.resource == "pods" || resource.name == "z{i}")'
        for i in range(12)
    )
    + " };"
)


def _tiers(src=POLICIES):
    return [PolicySet.from_source(src, FILENAME)]


def sar_body(
    user="alice", resource="pods", namespace="default", verb="get", name=""
):
    ra = {
        "verb": verb,
        "version": "v1",
        "resource": resource,
        "namespace": namespace,
    }
    if name:
        ra["name"] = name
    return json.dumps(
        {
            "apiVersion": "authorization.k8s.io/v1",
            "kind": "SubjectAccessReview",
            "spec": {
                "user": user,
                "uid": "u",
                "groups": [],
                "resourceAttributes": ra,
            },
        }
    ).encode()


def review_body(env=None, uid="r1", name="c"):
    obj = {
        "apiVersion": "v1",
        "kind": "ConfigMap",
        "metadata": {"name": name, "namespace": "default"},
    }
    if env is not None:
        obj["metadata"]["labels"] = {"env": env}
    return json.dumps(
        {
            "apiVersion": "admission.k8s.io/v1",
            "kind": "AdmissionReview",
            "request": {
                "uid": uid,
                "operation": "CREATE",
                "userInfo": {"username": "sam", "groups": []},
                "kind": {"group": "", "version": "v1", "kind": "ConfigMap"},
                "resource": {
                    "group": "",
                    "version": "v1",
                    "resource": "configmaps",
                },
                "namespace": "default",
                "name": name,
                "object": obj,
            },
        }
    ).encode()


def _engine_stack(src=POLICIES, cache=False):
    """(server, engine, adm_engine, cache) with TPU engines wired the way
    the webhook CLI wires them (no fast path: the explain engine discovery
    goes through the bound evaluate backend)."""
    engine = TPUPolicyEngine(name="authorization")
    engine.load(_tiers(src), warm="off")
    adm_engine = TPUPolicyEngine(name="admission")
    adm_engine.load(
        _tiers(src) + [allow_all_admission_policy_store().policy_set()],
        warm="off",
    )
    stores = TieredPolicyStores([MemoryStore(FILENAME, _tiers(src)[0])])
    dc = None
    if cache:
        dc = DecisionCache(
            generation_fn=lambda: (
                stores.cache_generation(),
                engine.load_generation,
            ),
            path="authorization",
        )
    authorizer = CedarWebhookAuthorizer(
        stores,
        evaluate=engine.evaluate,
        evaluate_batch=engine.evaluate_batch,
    )
    handler = CedarAdmissionHandler(
        TieredPolicyStores(
            list(stores.stores) + [allow_all_admission_policy_store()]
        ),
        evaluate=adm_engine.evaluate,
        evaluate_batch=adm_engine.evaluate_batch,
    )
    server = WebhookServer(
        authorizer, handler, decision_cache=dc,
        # ports of its own: the defaults are shared with every other test
        # file that starts a server, and the files run side by side
        port=0, metrics_port=0,
    )
    return server, engine, adm_engine, dc


def _traffic():
    """≥1.1k bodies: SARs over users x resources x namespaces plus
    admission reviews over 3 label states."""
    bodies = []
    users = ["alice", "bob", "carol", "dave"]
    resources = ["pods", "secrets", "services"]
    for i in range(800):
        bodies.append(
            (
                "authorize",
                sar_body(
                    user=users[i % 4],
                    resource=resources[(i // 4) % 3],
                    namespace=f"ns-{i % 7}",
                ),
            )
        )
    envs = ["prod", "heha", None]
    for i in range(300):
        bodies.append(
            ("admit", review_body(env=envs[i % 3], uid=f"r{i}", name=f"c{i}"))
        )
    return bodies


# ------------------------------------------------------------ explanations


class TestExplanationContent:
    def test_device_determining_policy_clause_and_spans(self):
        server, engine, _adm, _ = _engine_stack()
        resp = server.handle_authorize(
            sar_body("carol", "secrets"), explain=True
        )
        assert resp["status"]["denied"] is True
        e = resp["explanation"]
        assert e["source"] == "device"
        assert e["webhookDecision"] == "deny"
        assert e["fallback"] is False
        det = e["determining"]
        assert det["policyId"] == "policy1"
        assert det["effect"] == "forbid"
        assert det["tier"] == 0
        # source span: the policy's position in the source file
        assert det["span"]["file"] == FILENAME
        assert det["span"]["line"] >= 1 and det["span"]["column"] >= 1
        # per-test attribute/operator/value of the winning clause
        tests = det["clause"]["tests"]
        by_attr = {t["attribute"]: t for t in tests}
        assert by_attr["principal.name"]["operator"] == "=="
        assert by_attr["principal.name"]["value"] == "carol"
        assert by_attr["resource.resource"]["value"] == "secrets"
        assert all("source" in t for t in tests)

    def test_multi_reason_rows_list_every_policy(self):
        server, *_ = _engine_stack()
        resp = server.handle_authorize(sar_body("alice", "pods"), explain=True)
        e = resp["explanation"]
        ids = {d["policyId"] for d in e["reasons"]}
        # both overlapping permits (policy0 + policy2) matched
        assert ids == {"policy0", "policy2"}
        # the determining policy is the first (lowest-index) reason
        assert e["determining"]["policyId"] == "policy0"

    def test_no_match_explanation(self):
        server, *_ = _engine_stack()
        resp = server.handle_authorize(
            sar_body("mallory", "services"), explain=True
        )
        e = resp["explanation"]
        assert e["determining"] is None
        assert e["webhookDecision"] == "no_opinion"

    def test_admission_explain(self):
        server, *_ = _engine_stack()
        review = server.handle_admit(review_body(env="prod"), explain=True)
        assert review["response"]["allowed"] is False
        e = review["explanation"]
        det = e["determining"]
        assert det["effect"] == "forbid"
        assert det["policyId"] == "policy3"
        srcs = [t["source"] for t in det["clause"]["tests"]]
        assert any("labels" in s for s in srcs)
        # allow side: the final allow-all tier answers, with attribution
        review = server.handle_admit(review_body(env="dev"), explain=True)
        assert review["response"]["allowed"] is True
        det = review["explanation"]["determining"]
        assert det["effect"] == "permit"
        assert review["explanation"]["tier"] >= 1  # the allow-all tail tier

    def test_short_circuits_explained(self):
        server, *_ = _engine_stack()
        resp = server.handle_authorize(
            sar_body("system:kube-scheduler"), explain=True
        )
        assert resp["explanation"]["shortCircuit"] == "system-user-skip"
        # parse errors are explained, not crashed on
        resp = server.handle_authorize(b"not json {", explain=True)
        assert resp["explanation"]["shortCircuit"] == "decode-error"
        assert "evaluationError" in resp["status"]
        review = server.handle_admit(b"not json {", explain=True)
        assert review["explanation"]["shortCircuit"] == "decode-error"


class TestHostPlanes:
    def test_fleet_breaker_open_explains_host_side(self):
        """With a fleet wired, ?explain must gate on replica 0's breaker
        (the template engine IS that replica's engine): an OPEN breaker
        routes explain to the host plane with ZERO device launches —
        never a want_full/bits dispatch on the sick device."""
        from cedar_tpu.engine.fastpath import SARFastPath
        from cedar_tpu.fleet.fleet import EngineFleet
        from cedar_tpu.fleet.replica import EngineReplica

        stores = TieredPolicyStores([MemoryStore(FILENAME, _tiers()[0])])
        authorizer = CedarWebhookAuthorizer(stores)
        engine = TPUPolicyEngine(name="fleet-explain-r0")
        breaker = CircuitBreaker(name="fleet-explain-r0")
        fastpath = SARFastPath(engine, authorizer, breaker=breaker)
        replica = EngineReplica(
            0, engine, fastpath, breaker=breaker, max_batch=8,
            fleet_name="fleet-explain",
        )
        fleet = EngineFleet([replica], name="fleet-explain")
        fleet.load([s.policy_set() for s in stores], warm="off")
        handler = CedarAdmissionHandler(
            TieredPolicyStores(
                list(stores.stores) + [allow_all_admission_policy_store()]
            )
        )
        server = WebhookServer(authorizer, handler, fleet=fleet)
        try:
            breaker.force_open()
            tc0 = kernel_trace_count()
            resp = server.handle_authorize(
                sar_body("carol", "secrets"), explain=True
            )
            assert kernel_trace_count() == tc0
            e = resp["explanation"]
            assert e["source"] == "host"
            assert e["determining"]["policyId"] == "policy1"
            # closed breaker: the device plane serves explain again
            breaker.half_open_now()
            breaker.record_success(0.001)
            resp = server.handle_authorize(
                sar_body("carol", "secrets"), explain=True
            )
            assert resp["explanation"]["source"] == "device"
        finally:
            server.stop(drain_grace_s=0.1)

    def test_breaker_open_host_explanation(self):
        engine = TPUPolicyEngine(name="authorization")
        engine.load(_tiers(), warm="off")
        stores = TieredPolicyStores([MemoryStore(FILENAME, _tiers()[0])])
        authorizer = CedarWebhookAuthorizer(stores, evaluate=engine.evaluate)
        breaker = CircuitBreaker(name="authorization")
        breaker.force_open()
        exp = Explainer(
            authorizer=authorizer, authz_engine=engine, authz_breaker=breaker
        )
        tc0 = kernel_trace_count()
        decision, _r, err, e = exp.explain_authorize(
            sar_body("carol", "secrets")
        )
        assert err is None and decision == "deny"
        # no device work behind an open breaker: zero traces, host source
        assert kernel_trace_count() == tc0
        assert e["source"] == "host"
        assert e["determining"]["policyId"] == "policy1"
        assert e["determining"]["clause"]["tests"]

    def test_interpreter_only_explanation(self):
        stores = TieredPolicyStores([MemoryStore(FILENAME, _tiers()[0])])
        exp = Explainer(authorizer=CedarWebhookAuthorizer(stores))
        decision, _r, err, e = exp.explain_authorize(sar_body("alice", "pods"))
        assert err is None and decision == "allow"
        assert e["source"] == "interpreter"
        det = e["determining"]
        assert det["policyId"] == "policy0"
        assert det["effect"] == "permit"
        assert det["span"]["file"] == FILENAME
        assert det["clause"] is None  # no lowered IR without a pack

    def test_interpreter_fallback_policy_attributed(self):
        """A request decided by an UNLOWERABLE policy still explains: the
        interpreter fallback answered, and the explanation says so with
        the policy's unlowerable reason code."""
        server, engine, _adm, _ = _engine_stack(src=UNLOWERABLE)
        assert engine._compiled.packed.fallback  # precondition
        resp = server.handle_authorize(
            sar_body("anyone", "pods", name="mypod"), explain=True
        )
        assert resp["status"]["allowed"] is True
        e = resp["explanation"]
        assert e["fallback"] is True
        det = e["determining"]
        assert det["fallback"] is True
        assert det["clause"] is None
        assert det["unlowerable"]["code"] == "clause_limit"


# ----------------------------------------------------------- pay-for-use


# a DISTINCT slot layout from POLICIES (namespace + verb slots): the jit
# cache is process-global and keyed on array shapes, so the lazy-compile
# assertion needs shapes no earlier test (in this file or another) can
# have traced — a different slot count changes every kernel shape
LAZY_POLICIES = """
permit (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "alice" && resource.namespace == "default" };
forbid (principal is k8s::User, action == k8s::Action::"list",
        resource is k8s::Resource)
  when { resource.resource == "secrets" && principal.name like "ba*" };
"""


class TestLazyCompile:
    def test_zero_traces_until_first_explain_request(self):
        server, *_ = _engine_stack(src=LAZY_POLICIES)
        # warm every non-explain serving shape the loop below hits (prod
        # and heha reviews land on different extras-width buckets)
        server.handle_authorize(sar_body("alice", "pods"))
        server.handle_authorize(sar_body("carol", "secrets"))
        server.handle_admit(review_body(env="prod"))
        server.handle_admit(review_body(env="heha"))
        tc0 = kernel_trace_count()
        for _ in range(5):
            server.handle_authorize(sar_body("carol", "secrets"))
            server.handle_admit(review_body(env="heha"))
        assert kernel_trace_count() == tc0, (
            "explain wiring must add ZERO traces to the non-explain path"
        )
        resp = server.handle_authorize(sar_body("alice", "pods"), explain=True)
        assert resp["explanation"]["source"] == "device"
        assert kernel_trace_count() > tc0, (
            "the first explain request compiles the explain plane lazily"
        )
        tc1 = kernel_trace_count()
        server.handle_authorize(sar_body("carol", "secrets"), explain=True)
        assert kernel_trace_count() == tc1, "explain shapes compile once"


class TestDifferential:
    def test_1100_body_differential_and_cache_bypass(self):
        """Non-explain responses are byte-identical between a server whose
        explain plane was exercised and one that never explained; explain
        requests never read or populate the decision cache."""
        bodies = _traffic()
        assert len(bodies) >= 1100

        plain_srv, *_ = _engine_stack(cache=True)
        exp_srv, _e, _a, cache = _engine_stack(cache=True)

        # exercise the explain plane on the explain server BEFORE the
        # differential sweep (both endpoints, flagged + clean rows)
        for ep, body in bodies[:6] + bodies[800:803]:
            if ep == "authorize":
                exp_srv.handle_authorize(body, explain=True)
            else:
                exp_srv.handle_admit(body, explain=True)
        assert cache.size() == 0, "explain must never populate the cache"

        diffs = 0
        for ep, body in bodies:
            if ep == "authorize":
                a = plain_srv.handle_authorize(body)
                b = exp_srv.handle_authorize(body)
            else:
                a = plain_srv.handle_admit(body)
                b = exp_srv.handle_admit(body)
            if json.dumps(a, sort_keys=True) != json.dumps(b, sort_keys=True):
                diffs += 1
        assert diffs == 0
        # the sweep itself populated the cache (sanity: bypass above was
        # the explain path, not a dead cache)
        assert cache.size() > 0
        # and an explain request on a now-warm cache still bypasses it:
        # same body, stats' hits unchanged
        hits_before = cache.stats()["hits"]
        exp_srv.handle_authorize(bodies[0][1], explain=True)
        assert cache.stats()["hits"] == hits_before


# ------------------------------------------------------------------ HTTP


class TestHTTP:
    def test_explain_on_both_endpoints(self):
        server, *_ = _engine_stack()
        server.start()
        try:
            port = server.bound_port

            def post(path, body):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}{path}",
                    data=body,
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=10) as r:
                    return json.loads(r.read())

            bare = post("/v1/authorize", sar_body("carol", "secrets"))
            assert "explanation" not in bare
            doc = post("/v1/authorize?explain=1", sar_body("carol", "secrets"))
            assert doc["status"]["denied"] is True
            det = doc["explanation"]["determining"]
            assert det["policyId"] == "policy1"
            assert det["clause"]["tests"]
            assert det["span"]["file"] == FILENAME
            # explain=0 keeps the bare path
            doc = post("/v1/authorize?explain=0", sar_body("carol", "secrets"))
            assert "explanation" not in doc
            adm = post("/v1/admit?explain=1", review_body(env="prod"))
            assert adm["response"]["allowed"] is False
            assert (
                adm["explanation"]["determining"]["policyId"]
                == "policy3"
            )
        finally:
            server.stop()


# ------------------------------------------------------------- cedar-why


class TestCedarWhy:
    @pytest.fixture()
    def recorded(self, tmp_path):
        from cedar_tpu.server.recorder import RequestRecorder

        policies = tmp_path / "policies"
        policies.mkdir()
        (policies / "demo.cedar").write_text(POLICIES)
        cand = tmp_path / "candidate"
        cand.mkdir()
        (cand / "demo.cedar").write_text(
            POLICIES.replace('"carol"', '"alice"')
        )
        rec_dir = tmp_path / "rec"
        rec = RequestRecorder(str(rec_dir))
        rec.record("/v1/authorize", sar_body("carol", "secrets"))
        rec.record("/v1/admit", review_body(env="prod"))
        (rec_dir / "req-authorize-unkeyed-1.json").write_bytes(b"not json {")
        from cedar_tpu.cache.fingerprint import fingerprint_body

        fp = fingerprint_body("authorize", sar_body("carol", "secrets"))
        return rec_dir, policies, cand, fp

    def _run(self, argv):
        from cedar_tpu.cli import why

        out, err = io.StringIO(), io.StringIO()
        with redirect_stdout(out), redirect_stderr(err):
            rc = why.main(argv)
        return rc, out.getvalue(), err.getvalue()

    def test_fingerprint_join_and_tree(self, recorded):
        rec_dir, policies, _cand, fp = recorded
        rc, out, err = self._run(
            [str(rec_dir), "--fingerprint", fp[:12],
             "--policy-dir", str(policies)]
        )
        assert rc == 0
        assert "forbid" in out and "demo.cedar.policy1" in out
        assert 'principal.name == "carol"' in out
        assert "1 unparseable" in err

    def test_no_match_exits_nonzero_with_message(self, recorded):
        rec_dir, policies, _cand, _fp = recorded
        rc, _out, err = self._run(
            [str(rec_dir), "--fingerprint", "deadbeef",
             "--policy-dir", str(policies)]
        )
        assert rc == 2
        assert "no recording matches fingerprint" in err
        assert "1 unparseable" in err

    def test_candidate_side_and_json(self, recorded):
        rec_dir, policies, cand, fp = recorded
        rc, out, _err = self._run(
            [str(rec_dir), "--fingerprint", fp,
             "--policy-dir", str(policies),
             "--candidate-dir", str(cand), "--json"]
        )
        assert rc == 0
        doc = json.loads(out[out.index("{"):])
        assert doc["matched"] == 1 and doc["unparseable"] == 1
        res = doc["results"][0]
        # live denies carol/secrets; the candidate (carol->alice) does not
        assert res["live"]["decision"] == "deny"
        assert res["candidate"]["decision"] == "no_opinion"
        assert (
            res["live"]["explanation"]["determining"]["policyId"]
            == "demo.cedar.policy1"
        )

    def test_all_admission_recordings(self, recorded):
        rec_dir, policies, _cand, _fp = recorded
        rc, out, _err = self._run(
            [str(rec_dir), "--all", "--policy-dir", str(policies)]
        )
        assert rc == 0
        assert "/v1/admit" in out and "/v1/authorize" in out


# ------------------------------------------------- rollout attribution


class TestRolloutAttribution:
    def test_diff_exemplars_carry_live_and_candidate_attribution(self):
        engine = TPUPolicyEngine(name="authorization")
        engine.load(_tiers(), warm="off")
        adm_engine = TPUPolicyEngine(name="admission")
        adm_engine.load(
            _tiers() + [allow_all_admission_policy_store().policy_set()],
            warm="off",
        )
        stores = TieredPolicyStores([MemoryStore(FILENAME, _tiers()[0])])
        authorizer = CedarWebhookAuthorizer(
            stores,
            evaluate=engine.evaluate,
            evaluate_batch=engine.evaluate_batch,
        )
        handler = CedarAdmissionHandler(
            TieredPolicyStores(
                list(stores.stores) + [allow_all_admission_policy_store()]
            ),
            evaluate=adm_engine.evaluate,
            evaluate_batch=adm_engine.evaluate_batch,
        )
        rollout = RolloutController(
            authz_engine=engine, admission_engine=adm_engine, sample_rate=1.0
        )
        server = WebhookServer(authorizer, handler, rollout=rollout)
        # candidate inverts carol/secrets (forbid -> permit) and retargets
        # the admission forbid prod -> heha
        cand_src = POLICIES.replace(
            'forbid (principal is k8s::User, action == k8s::Action::"get"',
            'permit (principal is k8s::User, action == k8s::Action::"get"',
            1,
        ).replace('value: "prod"', 'value: "heha"')
        rollout.stage(tiers=_tiers(cand_src), warm="off")
        server.handle_authorize(sar_body("carol", "secrets"))
        server.handle_admit(review_body(env="prod"))
        assert rollout.drain(30)
        exemplars = rollout.report.exemplars()
        assert exemplars
        by_path = {e["path"]: e for e in exemplars}
        auth = by_path["authorization"]
        attr = auth["attribution"]
        assert attr["live"]["policyId"] == "policy1"
        assert attr["live"]["effect"] == "forbid"
        assert attr["candidate"]["effect"] == "permit"
        assert attr["live"]["decision"] == "deny"
        adm = by_path["admission"]
        assert adm["attribution"]["live"]["effect"] == "forbid"
        assert adm["attribution"]["candidate"]["effect"] == "permit"
        # the text rendering carries the why line
        assert "why: live=forbid" in rollout.report.render_text()
