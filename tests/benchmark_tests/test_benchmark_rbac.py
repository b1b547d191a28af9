"""The rbac-tenants configuration: the cluster's own RBAC, converted, and the
stream that re-asks (benchmark/corpora/rbac.py, benchmark/kinds/sar_memo.py).

Held here, at six tenants on the CPU: the generator is a function of the
seed; its plain converter writes what the upstream's golden pairs pin, byte
for byte, and what the program's own converter writes for the corpus's
bindings; a plain RBAC evaluator allows exactly what the reference over the
converted policies allows (and where the upstream's conversion is wider
than RBAC, which requests those are); the program's interpreter and its
native path over a CPU engine answer as the reference does; the stream
repeats as the configuration says.
"""

import collections
import json
import math
import pathlib
import random

import pytest
import yaml

from benchmark import manifest as mf
from benchmark import reference as ref
from benchmark.control_role_blind import blind
from benchmark.corpora import rbac
from benchmark.kinds import sar, sar_memo

ROOT = pathlib.Path(__file__).resolve().parents[2]
GOLDEN = ROOT / "tests" / "testdata" / "rbac"
CELL = "rbac-tenants.sar-reask-lone"
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "rbac-tenants.json").read_text())
PARAMS = CONFIG["corpus"]["params"]
SMALL = dict(PARAMS, tenants=6)
SEEDS = (1, 3_400_000_017, 42)


def stream(corpus, seed, n, aimed=0.9):
    rng = random.Random(f"{seed}:test")
    return [corpus.spec(rng, aimed) for _ in range(n)]


# ------------------------------------------------------------ the converter

def golden_documents(path):
    docs = [d for d in yaml.safe_load_all(path.read_text()) if d]
    roles = {(d["kind"], d["metadata"].get("namespace", ""), d["metadata"]["name"]): d
             for d in docs if d["kind"] in ("Role", "ClusterRole")}
    for kind in ("ClusterRoleBinding", "RoleBinding"):
        for d in docs:
            if d["kind"] != kind:
                continue
            r = d["roleRef"]
            ns = d["metadata"].get("namespace", "")
            role = roles[(r["kind"], ns if r["kind"] == "Role" else "", r["name"])]
            binding = {"kind": kind, "name": d["metadata"]["name"], "namespace": ns,
                       "role": r["name"], "subjects": d.get("subjects") or []}
            yield binding, "role" if r["kind"] == "Role" else "clusterRole", role.get("rules") or []


@pytest.mark.parametrize("fixture", sorted(GOLDEN.glob("*.yaml")), ids=lambda p: p.stem)
def test_the_plain_converter_writes_the_upstreams_golden_pairs_byte_for_byte(fixture):
    got = "\n".join(rbac.convert(b, kind, rules) for b, kind, rules in golden_documents(fixture))
    assert got == fixture.with_suffix(".cedar").read_text()


def by_the_programs_converter(binding, rules, dialect):
    """What cedar_tpu/rbac/convert.py writes for one of the corpus's
    bindings; in the ``reference`` dialect, what the dialect says of it:
    the trailing-* URLs left out of the rule, the annotations as comments."""
    from cedar_tpu.cli.converter import sorted_policies
    from cedar_tpu.lang.format import format_policy_set
    from cedar_tpu.rbac import convert as program

    if dialect == "reference":
        rules = [dict(r, nonResourceURLs=[u for u in r["nonResourceURLs"]
                                          if u == "*" or not u.endswith("*")])
                 if r.get("nonResourceURLs") else r for r in rules]
    role = program.Role(kind="ClusterRole", name=binding["role"],
                        rules=[program.PolicyRule.from_dict(r) for r in rules])
    b = program.Binding(
        kind=binding["kind"], name=binding["name"], namespace=binding.get("namespace", ""),
        subjects=[program.Subject.from_dict(s) for s in binding["subjects"]],
        role_ref=program.RoleRef(kind="ClusterRole", name=binding["role"]))
    to_cedar = (program.role_binding_to_cedar if binding["kind"] == "RoleBinding"
                else program.cluster_role_binding_to_cedar)
    text = format_policy_set(sorted_policies(to_cedar(b, role)))
    if dialect == "reference":
        text = "\n".join("// " + line if line.startswith("@") else line
                         for line in text.split("\n"))
    return text


@pytest.mark.parametrize("dialect", rbac.DIALECTS)
def test_the_plain_converter_agrees_with_the_programs_on_the_corpus_s_own_bindings(dialect):
    corpus = rbac.build(dict(SMALL, dialect=dialect), 5)
    bindings = [dict(b, kind="ClusterRoleBinding") for b in corpus.doc["clusterRoleBindings"]]
    bindings += [b for ns in corpus.namespaces for b in rbac.tenant_bindings(ns)]
    theirs = {"cluster.cedar": ""}
    for b in bindings:
        mine = rbac.convert(b, "clusterRole", corpus.roles[b["role"]], dialect)
        other = by_the_programs_converter(b, corpus.roles[b["role"]], dialect)
        assert mine == other, b["name"]
        name = f"{b['namespace']}.cedar" if b["kind"] == "RoleBinding" else "cluster.cedar"
        theirs[name] = theirs.get(name, "") + other + "\n"
    # the same number of policies, and the store is those texts and no other
    assert theirs == corpus.files
    assert corpus.policies == sum(rbac.policies_of(t) for t in theirs.values()) == 17 + 6 * 81
    if dialect == "reference":
        # 0 differing decisions by the plain reference over 2,000 requests
        a, b = ref.Reference(corpus.files), ref.Reference(theirs)
        assert all(sar.expected(a, s) == sar.expected(b, s) for s in stream(corpus, 5, 2000))
    else:
        # what the reference dialect departs in, and nothing else
        assert 'like "/api/*"' in corpus.files["cluster.cedar"]
        with pytest.raises(ref.ReferenceError_):
            ref.Reference(corpus.files)


def test_the_roles_are_the_published_ones_built_as_the_cluster_builds_them():
    corpus = rbac.build(SMALL, 1)
    roles = corpus.roles
    assert [len(roles[r]) for r in ("view", "edit", "admin")] == [10, 23, 25]
    # edit holds all of view, admin all of edit; nothing is listed twice
    assert all(r in roles["edit"] for r in roles["view"])
    assert all(r in roles["admin"] for r in roles["edit"])
    for name in ("view", "edit", "admin"):
        assert len({json.dumps(r, sort_keys=True) for r in roles[name]}) == len(roles[name])
    assert not any("secrets" in r["resources"] for r in roles["view"])
    assert any("secrets" in r["resources"] and "get" in r["verbs"] for r in roles["edit"])
    assert any("rolebindings" in r["resources"] for r in roles["admin"])
    assert roles["cluster-admin"][0] == {"apiGroups": ["*"], "resources": ["*"], "verbs": ["*"]}
    bound = {(b["name"], s["name"]) for b in corpus.doc["clusterRoleBindings"]
             for s in b["subjects"]}
    assert {("cluster-admin", "system:masters"), ("system:basic-user", "system:authenticated"),
            ("system:discovery", "system:authenticated"),
            ("system:public-info-viewer", "system:authenticated"),
            ("system:public-info-viewer", "system:unauthenticated")} <= bound
    # nobody the authorizer chain keeps off the webhook is bound
    for b in corpus.doc["clusterRoleBindings"]:
        for s in b["subjects"]:
            assert s.get("namespace") != "kube-system" and not s["name"].startswith("system:node")
    # four bindings a tenant, each to a user-facing ClusterRole
    assert [(b["subjects"][0]["kind"], b["subjects"][0]["name"], b["role"])
            for b in rbac.tenant_bindings("tenant-003")] == [
        ("Group", "tenant-003:owners", "admin"), ("Group", "tenant-003:developers", "edit"),
        ("Group", "tenant-003:viewers", "view"), ("ServiceAccount", "deployer", "edit")]


def test_the_configuration_states_the_sizes_the_generator_gives():
    corpus = rbac.build(PARAMS, 1)
    assert corpus.policies == 8117 and len(corpus.files) == 101
    assert "8,117 in 101 files" in CONFIG["shapes"]["policies"]
    assert 5.7e6 < sum(len(t) for t in corpus.files.values()) < 5.9e6
    assert not any("forbid" in t for t in corpus.files.values())
    # the rehearsal's switch cuts the tenants so that about N policies are left
    assert 400 <= rbac.build(dict(PARAMS, policies=500), 1).policies <= 600
    m = mf.Manifest()
    entry = next(c for c in m.doc["configs"] if c["name"] == "rbac-tenants")
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]
    assert len(entry["source"]) <= 200
    for word in ("Default roles", "cmd/converter", "authorization-config.yaml"):
        assert word in entry["source"]


def test_the_cache_s_settings_are_the_defaults_the_configuration_states():
    """No cache flag is passed (check_configuration holds server_args to the
    two flags every configuration passes), so the program's defaults serve:
    a change of one of them changes what this cell measures, and has to show
    here first."""
    from cedar_tpu.cli.webhook import make_parser

    args = make_parser().parse_args(["--backend", "interpreter"])
    stated = CONFIG["guarantees"]["decision_cache"]
    for flag, value in (("decision_cache_size", 65536),
                        ("decision_cache_allow_ttl_seconds", 300.0),
                        ("decision_cache_deny_ttl_seconds", 30.0),
                        ("decision_cache_no_opinion_ttl_seconds", 5.0)):
        assert getattr(args, flag) == value
        assert f"--{flag.replace('_', '-')} {int(value)}" in stated


# ---------------------------------------------------- a plain RBAC evaluator

def rbac_rule_allows(rule, verb, attrs, mixed_rule_quirk):
    """Kubernetes' RBAC rule match (pkg/apis/rbac/v1/evaluation_helpers.go).
    ``mixed_rule_quirk``: the upstream's conversion of a rule that lists both
    plain resources and resource/subresource entries puts no
    ``unless { resource has subresource }`` on it, so its plain entries
    match a request for ANY of their subresources."""
    if "*" not in rule["verbs"] and verb not in rule["verbs"]:
        return False
    if "nonResourceURLs" in rule:
        path = attrs.get("path")
        return path is not None and any(
            u == "*" or u == path or (u.endswith("*") and path.startswith(u[:-1]))
            for u in rule["nonResourceURLs"])
    if "path" in attrs:
        return False
    if "*" not in rule["apiGroups"] and attrs["group"] not in rule["apiGroups"]:
        return False
    resource, sub = attrs["resource"], attrs.get("subresource", "")
    combined = f"{resource}/{sub}" if sub else resource
    mixed = any("/" in r for r in rule["resources"])
    for entry in rule["resources"]:
        if entry == "*" and (not sub or mixed):
            break  # "*" is every resource; the conversion keeps subresources out
        if entry == combined or (sub and entry == f"*/{sub}"):
            break
        if sub and mixed_rule_quirk and mixed and entry == resource:
            break
    else:
        return False
    names = rule.get("resourceNames")
    return not names or attrs.get("name", "") in names


def rbac_allows(corpus, spec, mixed_rule_quirk):
    """Whether the corpus's bindings, read as RBAC, allow the request."""
    user, groups = spec["user"], set(spec["groups"])
    ra = spec.get("resourceAttributes")
    attrs = dict(ra) if ra else {"path": spec["nonResourceAttributes"]["path"]}
    verb = (ra or spec["nonResourceAttributes"])["verb"]

    def held_by(subject):
        if subject["kind"] == "Group":
            return subject["name"] in groups
        if subject["kind"] == "ServiceAccount":
            return user == f"system:serviceaccount:{subject['namespace']}:{subject['name']}"
        return user == subject["name"]

    bindings = [(dict(b, kind="ClusterRoleBinding"), None)
                for b in corpus.doc["clusterRoleBindings"]]
    bindings += [(b, ns) for ns in corpus.namespaces for b in rbac.tenant_bindings(ns)]
    for binding, namespace in bindings:
        if not any(held_by(s) for s in binding["subjects"]):
            continue
        if namespace is not None and (ra is None or ra.get("namespace") != namespace):
            continue  # a RoleBinding grants inside its namespace only
        rules = corpus.roles[binding["role"]]
        if CONFIG["corpus"]["params"]["dialect"] == "reference":
            rules = [dict(r, nonResourceURLs=[u for u in r["nonResourceURLs"]
                                              if u == "*" or not u.endswith("*")])
                     if "nonResourceURLs" in r else r for r in rules]
        if any(rbac_rule_allows(r, verb, attrs, mixed_rule_quirk) for r in rules):
            return True
    return False


@pytest.mark.parametrize("seed", SEEDS)
def test_a_plain_rbac_evaluator_allows_exactly_what_the_converted_policies_allow(seed):
    corpus = rbac.build(SMALL, seed)
    plain = ref.Reference(corpus.files)
    wider, asked = [], 0
    for s in stream(corpus, seed, 2000):
        if s["user"].startswith("system:") and not s["user"].startswith("system:serviceaccount:"):
            continue  # the webhook's own rule answers before any policy
        asked += 1
        allowed, denied, _ = sar.expected(plain, s)
        assert not denied  # converted RBAC only permits
        assert allowed == rbac_allows(corpus, s, mixed_rule_quirk=True), s
        if allowed != rbac_allows(corpus, s, mixed_rule_quirk=False):
            wider.append(s)
    assert asked > 1800
    # the finding (PERF.md, PR 34): where the upstream's conversion is wider
    # than RBAC. Each such request asks for a subresource that its role does
    # not list, of a resource that a rule lists beside other subresources
    for s in wider:
        assert s["resourceAttributes"].get("subresource"), s
        assert sar.expected(plain, s)[0] is True
    assert len(wider) < 0.03 * asked


# ------------------------------------------------ the program's two witnesses

@pytest.mark.parametrize("seed", SEEDS)
def test_the_programs_interpreter_and_native_path_answer_as_the_reference(seed, tmp_path):
    from cedar_tpu.engine.evaluator import TPUPolicyEngine
    from cedar_tpu.engine.fastpath import SARFastPath
    from cedar_tpu.native import native_available
    from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
    from cedar_tpu.server.http import get_authorizer_attributes, sar_response
    from cedar_tpu.stores.config import load_config_stores

    corpus = rbac.build(SMALL, seed)
    pol = tmp_path / "policies"
    pol.mkdir()
    for name, text in corpus.files.items():
        (pol / name).write_text(text)
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(yaml.safe_dump({
        "apiVersion": "cedar.k8s.aws/v1alpha1", "kind": "StoreConfig",
        "spec": {"stores": [{"type": "directory", "directoryStore": {
            "path": str(pol), "refreshInterval": "1h"}}]}}))
    stores = load_config_stores(str(cfg), timeout_s=60.0)
    try:
        plain = ref.Reference(corpus.files)
        specs = stream(corpus, seed, 300)
        mine = [sar_memo.expected(plain, s) for s in specs]
        interpreter = CedarWebhookAuthorizer(stores)
        for s, want in zip(specs, mine):
            decision, reason = interpreter.authorize(get_authorizer_attributes({"spec": s}))
            assert sar.verdict(sar_response(decision, reason)) == want, s
        if native_available():
            engine = TPUPolicyEngine()
            stats = engine.load([s.policy_set() for s in stores.stores], warm="off")
            # the whole corpus lowers: no policy is left to the interpreter
            assert stats["fallback_policies"] == 0 and stats["native_opaque_policies"] == 0
            assert stats["lowered_policies"] == corpus.policies
            fast = SARFastPath(engine, CedarWebhookAuthorizer(stores, evaluate=engine.evaluate))
            got = fast.authorize_raw([json.dumps(sar.body(s)).encode() for s in specs])
            assert [sar.verdict(sar_response(*r)) for r in got] == mine
            # the webhook's own rule answers the component users, and says so
            by_rule = [s["user"] for s, r in zip(specs, got)
                       if getattr(r, "answered_by", "") == "rule"]
            assert by_rule and set(by_rule) <= set(rbac.COMPONENT_USERS)
        # the stream is not all one answer, and names several policies at times
        assert {m[:2] for m in mine} == {(True, False), (False, False)}
        assert any(len(m[2]) >= 2 for m in mine)
    finally:
        for s in stores.stores:
            getattr(s, "close", lambda: None)()


# ------------------------------------------------------------------ controls

def test_the_controls_that_can_fail_here_do_and_forbid_blind_cannot():
    corpus = rbac.build(SMALL, 11)
    specs = stream(corpus, 11, 1500)
    plain = ref.Reference(corpus.files)
    want = [sar_memo.expected(plain, s) for s in specs]

    def differing(reference):
        return sum(1 for s, w in zip(specs, want) if sar_memo.expected(reference, s) != w)

    assert differing(ref.Reference(corpus.files, control="first_reason_only")) > 0
    # no forbid in converted RBAC: this control has nothing to be blind to
    assert differing(ref.Reference(corpus.files, control="forbid_blind")) == 0
    # its stand-in: the policies of one ClusterRole's bindings apply to nothing
    blinded = blind(corpus.files, "view")
    assert sum(t.count("unless { true }") for t in blinded.values()) == 10 * 6
    assert [len(ref.parse_policies(t)) for t in blinded.values()] == [
        len(ref.parse_policies(t)) for t in corpus.files.values()]
    assert differing(ref.Reference(blinded)) > 0


def test_the_memo_kind_is_the_sar_kind_and_keeps_answers_with_their_reference():
    corpus = rbac.build(SMALL, 2)
    plain = ref.Reference(corpus.files)
    broken = ref.Reference(corpus.files, control="first_reason_only")
    for stated in ("PATH", "body", "distinct", "verdict", "gave_up"):
        assert getattr(sar_memo, stated) is getattr(sar, stated)
    specs = stream(corpus, 2, 600)
    for s in specs:
        assert sar_memo.expected(plain, s) == sar.expected(plain, s)
        assert sar_memo.expected(broken, s) == sar.expected(broken, s)
    distinct = {json.dumps(s, sort_keys=True) for s in specs}
    assert len(plain._answers_by_spec) == len(distinct) < len(specs)
    assert mf.kind_module(mf.Manifest().kind_of("sar-reask-lone")) is sar_memo


# ----------------------------------------------------------------- the stream

def test_corpus_and_stream_are_functions_of_the_seed():
    a, b, c = (rbac.build(SMALL, s) for s in (5, 5, 6))
    assert a.files == b.files == c.files  # the RBAC objects are the cluster's
    assert a.users == b.users and a.users != c.users
    assert a.tenant_order == b.tenant_order
    sa, sb, sc = (stream(x, 9, 3000) for x in (a, b, c))
    assert sa == sb and sa != sc


def test_the_stream_repeats_as_the_configuration_says():
    corpus = rbac.build(PARAMS, 7)
    specs = stream(corpus, 7, 20200)
    bodies = [json.dumps(sar.body(s)) for s in specs]
    latest = collections.OrderedDict()  # distinct bodies, oldest first
    repeats = 0
    for i, body in enumerate(bodies):
        if body in latest:
            # byte-identical to one of the 5,000 latest distinct before it
            assert i >= 200
            repeats += 1
            continue
        latest[body] = None
        if len(latest) > 5000:
            latest.popitem(last=False)
    assert repeats == corpus.repeats
    assert abs(repeats / (len(bodies) - 200) - 0.85) < 0.02
    assert 217 <= min(map(len, bodies)) and max(map(len, bodies)) <= 500
    # a repeat reaches back over the whole window, not just the last few
    first_seen = {}
    gaps = []
    distinct_so_far = 0
    for i, body in enumerate(bodies):
        if body in first_seen:
            gaps.append(distinct_so_far - first_seen[body])
        else:
            first_seen[body] = distinct_so_far
            distinct_so_far += 1
    assert max(gaps) > 2000 and sorted(gaps)[len(gaps) // 2] > 500


def test_a_new_request_s_tenant_subject_and_verb_follow_the_stated_shares():
    corpus = rbac.build(dict(PARAMS, repeat_share=0.0), 3)
    specs = stream(corpus, 3, 20000)
    n = len(specs)

    def tenant_of(s):
        for field in (s["user"], *s["groups"]):
            for part in field.replace(":", " ").split():
                if part.startswith("tenant-"):
                    return part[:10]
        return None

    def kind(s):
        user = s["user"]
        if user.startswith("system:serviceaccount:"):
            return "tenant_service_account"
        if user.startswith("system:"):
            return "component_user"
        if "system:masters" in s["groups"]:
            return "cluster_admin"
        return "tenant_user" if user.startswith("tenant-") else "unbound_user"

    shares = collections.Counter(kind(s) for s in specs)
    for name, want in PARAMS["subject_mix"].items():
        assert abs(shares[name] / n - want) < 0.015, name
    sa = [s for s in specs if kind(s) == "tenant_service_account"]
    assert abs(sum(s["user"].endswith(":deployer") for s in sa) / len(sa) - 0.8) < 0.03
    people = [s for s in specs if kind(s) == "tenant_user"]
    assert all(s["groups"][-1] == "system:authenticated" and 2 <= len(s["groups"]) <= 4
               for s in people)
    # tenants by Zipf(1.1): the slope of log(count) over log(rank), top 30 ranks
    counts = sorted(collections.Counter(
        tenant_of(s) for s in specs if tenant_of(s)).values(), reverse=True)
    assert len(counts) == 100
    xs = [math.log(r + 1) for r in range(30)]
    ys = [math.log(c) for c in counts[:30]]
    mx, my = sum(xs) / 30, sum(ys) / 30
    slope = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / sum((x - mx) ** 2 for x in xs)
    assert -1.25 < slope < -0.95
    # non-resource 0.10; of the resource requests reads 0.7, a subresource 0.10
    non_resource = [s for s in specs if "nonResourceAttributes" in s]
    # (a draw that equals one of the window's is made again, which thins the
    # requests with few variants: 0.086 of new requests, not 0.10)
    assert abs(len(non_resource) / n - 0.10) < 0.02
    ras = [s["resourceAttributes"] for s in specs if "resourceAttributes" in s]
    assert 0.62 < sum(ra["verb"] in rbac.READS for ra in ras) / len(ras) < 0.78
    # the three named subresources in 0.10, and a rule that lists subresources
    # only (pods/eviction, serviceaccounts/token) gives one too
    assert 0.10 < sum("subresource" in ra for ra in ras) / len(ras) < 0.16
    named = [ra for ra in ras if (ra["resource"], ra.get("subresource")) in {
        ("pods", "log"), ("pods", "exec"), ("deployments", "scale")}]
    assert abs(len(named) / len(ras) - 0.10) < 0.03
    assert all(("name" in ra) == (ra["verb"] in rbac.NAMED_VERBS or "subresource" in ra)
               for ra in ras)
    # nothing the sar kind refuses: no impersonation, no field selector
    assert not any(ra["verb"] == "impersonate" or "fieldSelector" in ra for ra in ras)
    # aimed 0.9: most requests are allowed, some are not
    plain = ref.Reference(rbac.build(SMALL, 3).files)
    small = stream(rbac.build(SMALL, 3), 3, 1500)
    allowed = sum(sar_memo.expected(plain, s)[0] for s in small) / len(small)
    assert 0.70 < allowed < 0.92


# --------------------------------------------------- the cell and its metrics

def test_the_cell_is_the_configuration_under_the_mix_the_issue_gave():
    m = mf.Manifest()
    w = m.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == ("rbac-tenants", "sar-reask-lone", 1)
    assert w in m.doc["workloads"] and "rbac-tenants" in [c["name"] for c in m.doc["configs"]]
    mix = m.traffic("sar-reask-lone")
    assert (mix["loop"], mix["connections"], mix["processes"]) == ("closed", 1, 1)
    assert mix["name_per_request"] is False and mix["aimed_share"] == 0.9
    assert mix["warmup_s"] == 3.0 and mix["precompute_per_s"] == 400
    assert (PARAMS["repeat_share"], PARAMS["repeat_window"], PARAMS["zipf_s"],
            PARAMS["tenants"], PARAMS["first_new"]) == (0.85, 5000, 1.1, 100, 200)
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "latency_p50_ms", "latency_p95_ms", "setup_s"}
    for e2e in m.doc["end_to_end"]:
        if e2e["name"] in ("latency_p50_ms", "latency_p95_ms"):
            assert CELL in e2e["workloads"]
    listed = {x["name"]: x for x in m.doc["per_layer"] if CELL in x.get("workloads", ())}
    mine = {n: x for n, x in listed.items() if x["workloads"] == [CELL]}
    assert all(n.endswith(".reask") for n in mine)
    assert {n.rsplit(".", 1)[0] for n in mine} == {
        "dispatch_ms_per_batch", "fallback_row_share", "match_roofline",
        "device_idle_share", "cache_hit_share", "cache_answer_ms", "engine_answer_ms",
        "rule_answer_share", "memo_hit_share", "batch_rows", "queue_wait_ms",
        "decode_us_per_row", "dispatch_launch_ms", "device_ms_per_batch", "scan_read_share"}
    # what a hit and a miss share with every lone SAR cell, and moves the
    # median here as there, is the `.lone` entry, which lists the cell
    assert {n for n in listed if n.endswith(".lone")} >= {
        "ingress_ms.lone", "handler_host_ms.lone", "http_io_ms.lone", "between_ms.lone",
        "timer_accounted_share.lone"}
    # and the launch's counters, one entry for the five lone cells
    assert {n for n in listed if "." not in n} >= {
        "uploads_per_batch", "upload_bytes_per_batch", "readback_bytes_per_batch",
        "long_device_waits_per_kbatch"}
    # a hit's metrics move the median, a miss's the 95th percentile
    assert mine["cache_answer_ms.reask"]["moves"] == "latency_p50_ms"
    assert mine["engine_answer_ms.reask"]["moves"] == "latency_p95_ms"
    assert all(listed[n]["moves"] == "latency_p50_ms" for n in listed if n.endswith(".lone"))
    # the unlisted ones come with the cell, and nothing of another suffix
    names = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert names - set(listed) == {
        "client_latency_p99_ms", "client_latency_max_ms", "over_deadline_share",
        "gc_pause_max_ms", "ready_s", "ladder_s", "window_compiles"}


def test_the_reask_metrics_read_the_programs_label_and_counters():
    """On an exposition written from the program's own metric classes: the
    timer's mean by who answered, the cache's and the memo's hit shares; and
    nothing from a server without the label or the family (the parent)."""
    from benchmark import prom
    from benchmark.run import Context
    from cedar_tpu.server import metrics

    def exposition():
        return prom.parse(metrics.REGISTRY.expose())

    def read(ctx, name):
        spec = mf.Manifest().metric_file(name)
        return mf.reader_module(spec["reader"]).read(ctx, spec["params"])

    ctx = Context()
    ctx.prom_before = exposition()
    for _ in range(30):
        metrics.record_request_latency("Allow", 0.0008, by="cache")
        metrics.record_cache_hit("authorization")
    for _ in range(9):
        metrics.record_request_latency("Allow", 0.0040, by="engine")
    for _ in range(5):
        metrics.record_request_latency("NoOpinion", 0.0060, by="engine")
    metrics.record_request_latency("NoOpinion", 0.0030, by="rule")
    for _ in range(15):
        metrics.record_cache_miss("authorization")
    metrics.record_cache_hit("admission")  # another path's: not read
    hits, misses = 28, 17
    before = dict(((lbl["outcome"]), v) for n, lbl, v in ctx.prom_before
                  if n == "cedar_fingerprint_memo_total" and lbl["path"] == "authorization")
    metrics.set_fingerprint_memo("authorization", before.get("hit", 0) + hits,
                                 before.get("miss", 0) + misses)
    ctx.prom_after = exposition()
    assert read(ctx, "cache_answer_ms.reask") == pytest.approx(0.8)
    assert read(ctx, "engine_answer_ms.reask") == pytest.approx((9 * 4.0 + 5 * 6.0) / 14)
    assert read(ctx, "ingress_ms.lone") == pytest.approx(
        (30 * 0.8 + 9 * 4.0 + 5 * 6.0 + 3.0) / 45)
    assert read(ctx, "cache_hit_share.reask") == pytest.approx(100 * 30 / 45)
    assert read(ctx, "rule_answer_share.reask") == pytest.approx(100 / 45)
    assert read(ctx, "memo_hit_share.reask") == pytest.approx(100 * 28 / 45)
    # the parent's exposition, recorded before the label and the family
    here = pathlib.Path(__file__).resolve().parent
    old = Context()
    old.prom_before = prom.parse((here / "recorded_metrics_before.txt").read_text())
    old.prom_after = prom.parse((here / "recorded_metrics_after.txt").read_text())
    for name in ("cache_answer_ms.reask", "engine_answer_ms.reask",
                 "rule_answer_share.reask", "memo_hit_share.reask"):
        assert read(old, name) is None, name
    # the handler's timer is read by the entry every lone SAR cell shares
    assert read(old, "ingress_ms.lone") > 0
    assert CELL in mf.Manifest().metric_file("ingress_ms.lone")["workloads"]
