"""The rbac-oidc-groups configuration: rbac-tenants' policies asked by people
whose token carries an identity provider's groups
(benchmark/corpora/rbac_groups.py, benchmark/control_group_truncate.py).

Held here, at twenty tenants on the CPU (sixty tenant groups known): the
policies are rbac-tenants' own; k follows the configuration's three classes
by request; the program's interpreter and its native path over a CPU engine
answer as the reference does, with no row sent to the Python path whatever
k; a plain RBAC evaluator allows exactly what the reference allows for
many-group principals; the control that drops a principal's known groups
past the eighth fails.
"""

import collections
import json
import pathlib
import random

import pytest
import yaml

from benchmark import manifest as mf
from benchmark import reference as ref
from benchmark.control_group_truncate import SLOTS, known_groups, truncated
from benchmark.corpora import rbac, rbac_groups
from benchmark.kinds import sar
from test_benchmark_rbac import rbac_allows

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "rbac-oidc-groups.sar-groups-lone"
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "rbac-oidc-groups.json").read_text())
PARAMS = CONFIG["corpus"]["params"]
SMALL = dict(PARAMS, tenants=20)
SEEDS = (1, 3_600_000_017, 42)
# 300 more groups some policy names, for a resource nobody asks about: they
# determine no answer, and let a principal pass the limit that remains (the
# eight slots and the encoder's extras list) where sixty tenant groups cannot
FILLER = "".join(
    f'permit (\n  principal in k8s::Group::"filler-{i:03d}",\n  action == k8s::Action::"get",\n'
    '  resource is k8s::Resource\n)\nwhen { resource.resource == "fillers" };\n\n'
    for i in range(300))
CLASSES = {"home": 0, "past_the_slots": 1, "past_the_former_cap": 2}


def only(klass: int, **more) -> dict:
    """The small corpus with every request of one class of k."""
    return dict(SMALL, k_classes=[dict(PARAMS["k_classes"][klass], share=1.0)], **more)


def stream(corpus, seed, n, aimed=0.8):
    rng = random.Random(f"{seed}:test")
    specs = []
    for i in range(n):
        spec = corpus.spec(rng, aimed)
        sar.distinct(spec, f"t-{i}")
        specs.append(spec)
    return specs


def k_of(spec) -> int:
    return sum(1 for g in spec["groups"] if g.startswith("tenant-"))


# ------------------------------------------------------------- the generator

def test_the_policies_are_rbac_tenants_own():
    tenants = json.loads((ROOT / "benchmark" / "configs" / "rbac-tenants.json").read_text())
    for key in ("tenants", "dialect", "users_per_tenant", "zipf_s"):
        assert PARAMS[key] == tenants["corpus"]["params"][key], key
    assert rbac_groups.build(SMALL, 5).files == rbac.build(dict(SMALL), 9).files
    corpus = rbac_groups.build(SMALL, 5)
    assert len([g for g in known_groups(corpus.files) if g.startswith("tenant-")]) == 60
    assert "system:authenticated" in known_groups(corpus.files)


def test_corpus_and_stream_are_functions_of_the_seed():
    a = stream(rbac_groups.build(SMALL, 7), 7, 200)
    assert a == stream(rbac_groups.build(SMALL, 7), 7, 200)
    assert a != stream(rbac_groups.build(SMALL, 8), 7, 200)
    # a person is fixed for the run: one name, one token
    tokens = collections.defaultdict(set)
    for s in a:
        tokens[s["user"]].add(tuple(s["groups"]))
    assert all(len(t) == 1 for t in tokens.values())


def test_k_follows_the_stated_shares_and_the_token_never_passes_200():
    corpus = rbac_groups.build(PARAMS, 3_600_000_017)  # the cell's own size
    specs = stream(corpus, 3, 6000)
    ks = sorted(k_of(s) for s in specs)
    share = collections.Counter(
        next(i for i, c in enumerate(PARAMS["k_classes"]) if c["k"][0] <= k <= c["k"][1])
        for k in ks)  # a k outside every class would raise here
    for i, c in enumerate(PARAMS["k_classes"]):
        assert abs(share[i] / len(ks) - c["share"]) < 0.03, (i, share)
    # the median lies past the slots, inside the former cap; the 95th
    # percentile past it; nobody past 190
    assert 8 < ks[len(ks) // 2] <= 40 and 40 < ks[int(len(ks) * 0.95)] <= 190
    least, most = PARAMS["token_groups"]
    for s in specs:
        assert s["groups"][-1] == "system:authenticated"
        token = s["groups"][:-1]
        assert least <= len(token) <= most == 200 and len(set(token)) == len(token)
        for g in token:
            assert g.startswith("tenant-") or (g.startswith("idp:") and len(g) == 40), g
        ra = s["resourceAttributes"]  # every request names a resource
        assert ra["name"].startswith("t-") and "nonResourceAttributes" not in s
    # the home tenant's groups as rbac-tenants draws them; elsewhere nobody owns
    for s in specs[:500]:
        home = s["user"].rsplit("-user-", 1)[0]
        assert [g for g in s["groups"] if g.endswith(":owners")] in ([], [f"{home}:owners"])
    # an aimed request asks where the person has a group
    asked_at_home = sum(
        1 for s in specs if f"{s['resourceAttributes'].get('namespace')}:" in
        "".join(g + ":" for g in s["groups"]))
    assert asked_at_home > 0.7 * len(specs)
    bodies = [len(json.dumps(sar.body(s))) for s in specs]
    assert 4000 < sum(bodies) / len(bodies) < 6000 and max(bodies) < 10_000


def test_the_configuration_states_what_the_issue_gave():
    assert [(c["share"], c["k"]) for c in PARAMS["k_classes"]] == [
        (0.30, [1, 3]), (0.45, [9, 40]), (0.25, [41, 190])]
    assert PARAMS["token_groups"] == [20, 200] and PARAMS["repeat_share"] == 0.0
    assert PARAMS["non_resource_share"] == 0.0 and PARAMS["subject_mix"] == {"tenant_user": 1.0}
    assert CONFIG["chips"] == 1 and set(CONFIG["departures"]) == {
        "annotations as comments", "nonResourceURLs that end in *"}
    for key in ("tenancy", "k_classes", "unknown_groups", "subject_mix", "request_mix"):
        assert CONFIG["assumed"][key], key
    for word in ("OpenID Connect", "Entra", "200", "user.go"):
        assert word in CONFIG["source"], word
    entry = next(c for c in mf.Manifest().doc["configs"] if c["name"] == "rbac-oidc-groups")
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]
    with pytest.raises(ValueError):
        rbac_groups.build(dict(SMALL, repeat_share=0.5), 1)


# --------------------------------------------------- a plain RBAC evaluator

@pytest.mark.parametrize("klass", CLASSES.values(), ids=CLASSES.keys())
def test_a_plain_rbac_evaluator_allows_what_the_reference_allows_for_such_principals(klass):
    corpus = rbac_groups.build(only(klass), 21 + klass)
    plain = ref.Reference(corpus.files)
    specs = stream(corpus, 21, 700)
    lo, hi = PARAMS["k_classes"][klass]["k"]
    # what twenty tenants can give a person: its home tenant's one to three
    # groups and two of each other tenant
    elsewhere = 2 * 19
    assert all(min(lo, 1 + elsewhere) <= k_of(s) <= min(hi, len(rbac.TENANT_GROUPS) + elsewhere)
               for s in specs)
    allowed = 0
    for s in specs:
        got, denied, reasons = sar.expected(plain, s)
        assert not denied and got == rbac_allows(corpus, s, mixed_rule_quirk=True), s
        allowed += got
    assert 0.3 * len(specs) < allowed < len(specs)


# ------------------------------------------------ the program's two witnesses

def routing() -> dict:
    from cedar_tpu.server import metrics

    with metrics.row_routing_total._lock:
        return {c: metrics.row_routing_total._values.get(
            (("path", "authorization"), ("row_class", c)), 0.0)
            for c in ("clean_native", "flagged", "gated", "encoder_fallback", "encoder_gate")}


@pytest.fixture(scope="module")
def program(tmp_path_factory):
    """The program over the small corpus: its stores, its interpreter, and
    (with a toolchain) the native path over a CPU engine."""
    from cedar_tpu.engine.evaluator import TPUPolicyEngine
    from cedar_tpu.engine.fastpath import SARFastPath
    from cedar_tpu.native import native_available
    from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
    from cedar_tpu.stores.config import load_config_stores

    tmp = tmp_path_factory.mktemp("groups")
    corpus = rbac_groups.build(SMALL, 1)  # the policies are every seed's
    files = dict(corpus.files, **{"filler.cedar": FILLER})
    pol = tmp / "policies"
    pol.mkdir()
    for name, text in files.items():
        (pol / name).write_text(text)
    cfg = tmp / "cfg.yaml"
    cfg.write_text(yaml.safe_dump({
        "apiVersion": "cedar.k8s.aws/v1alpha1", "kind": "StoreConfig",
        "spec": {"stores": [{"type": "directory", "directoryStore": {
            "path": str(pol), "refreshInterval": "1h"}}]}}))
    stores = load_config_stores(str(cfg), timeout_s=60.0)
    fast = None
    if native_available():
        engine = TPUPolicyEngine()
        stats = engine.load([s.policy_set() for s in stores.stores], warm="off")
        # the whole corpus lowers: no policy is left to the interpreter
        assert stats["fallback_policies"] == 0 and stats["native_opaque_policies"] == 0
        assert stats["lowered_policies"] == corpus.policies + 300
        fast = SARFastPath(engine, CedarWebhookAuthorizer(stores, evaluate=engine.evaluate))
    yield files, CedarWebhookAuthorizer(stores), fast
    for s in stores.stores:
        getattr(s, "close", lambda: None)()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_programs_interpreter_and_native_path_answer_as_the_reference(seed, program):
    from cedar_tpu.server.http import get_authorizer_attributes, sar_response

    files, interpreter, fast = program
    corpus = rbac_groups.build(SMALL, seed)
    assert dict(corpus.files, **{"filler.cedar": FILLER}) == files
    plain = ref.Reference(files)
    specs = stream(corpus, seed, 300)  # k drawn as the cell draws it
    mine = [sar.expected(plain, s) for s in specs]
    for s, want in zip(specs, mine):
        decision, reason = interpreter.authorize(get_authorizer_attributes({"spec": s}))
        assert sar.verdict(sar_response(decision, reason)) == want, s
    if fast is not None:
        before = routing()
        got = fast.authorize_raw([json.dumps(sar.body(s)).encode() for s in specs])
        assert [sar.verdict(sar_response(*r)) for r in got] == mine
        after = routing()
        # no row left the native path, whatever its principal's groups
        assert after["encoder_fallback"] == before["encoder_fallback"]
        assert after["gated"] == before["gated"]
        assert not [r for r in got if getattr(r, "answered_by", "")]
    # not all one answer, and a membership in several groups names several policies
    assert {m[:2] for m in mine} == {(True, False), (False, False)}
    assert any(len(m[2]) >= 2 for m in mine)
    assert max(k_of(s) for s in specs) > 32 + SLOTS


@pytest.mark.parametrize("klass", CLASSES.values(), ids=CLASSES.keys())
def test_no_class_of_k_leaves_the_native_path_and_a_principal_past_the_limit_does(klass, program):
    from cedar_tpu.native import NativeEncoder
    from cedar_tpu.server.http import sar_response

    files, _, fast = program
    if fast is None:
        pytest.skip("no C++ toolchain for the native encoder")
    corpus = rbac_groups.build(only(klass), 33)
    plain = ref.Reference(files)
    specs = stream(corpus, 33 + klass, 120)
    before = routing()
    got = fast.authorize_raw([json.dumps(sar.body(s)).encode() for s in specs])
    assert [sar.verdict(sar_response(*r)) for r in got] == [sar.expected(plain, s) for s in specs]
    after = routing()
    assert after["encoder_fallback"] == before["encoder_fallback"]
    assert (after["clean_native"] + after["flagged"]
            - before["clean_native"] - before["flagged"]) == len(specs)
    # past what remains (the eight slots and an extras list of the
    # encoder's cap) a principal is a row of the Python path, and is still
    # answered exactly: a member of every group the store names
    everyone = sorted(known_groups(files))
    assert len(everyone) > SLOTS + NativeEncoder.DEFAULT_EXTRAS_CAP
    wide = dict(specs[klass], groups=everyone)
    [r] = fast.authorize_raw([json.dumps(sar.body(wide)).encode()])
    assert sar.verdict(sar_response(*r)) == sar.expected(plain, wide)
    assert routing()["encoder_fallback"] - before["encoder_fallback"] == 1
    assert r.answered_by == "interpreter"


# ------------------------------------------------------------------ controls

@pytest.mark.parametrize("klass", CLASSES.values(), ids=CLASSES.keys())
def test_the_truncating_control_fails_where_groups_pass_the_slots(klass):
    corpus = rbac_groups.build(only(klass), 11)
    specs = stream(corpus, 11, 400)
    known = known_groups(corpus.files)
    plain = ref.Reference(corpus.files)
    differing = sum(1 for s in specs
                    if sar.expected(plain, truncated(s, known)) != sar.expected(plain, s))
    cut = sum(1 for s in specs if truncated(s, known)["groups"] != s["groups"])
    if klass == 0:
        # at most three tenant groups and system:authenticated: nothing to drop
        assert cut == 0 and differing == 0
    else:
        assert cut == len(specs) and differing > 0
    # what it drops: known groups past the eighth, in the token's order
    s = specs[0]
    kept = truncated(s, known)["groups"]
    assert [g for g in kept if g not in known] == [g for g in s["groups"] if g not in known]
    assert [g for g in kept if g in known] == [g for g in s["groups"] if g in known][:SLOTS]
    assert differing <= cut


def test_first_reason_only_fails_here_too():
    corpus = rbac_groups.build(SMALL, 11)
    specs = stream(corpus, 11, 600)
    plain, first = ref.Reference(corpus.files), ref.Reference(
        corpus.files, control="first_reason_only")
    assert sum(1 for s in specs if sar.expected(first, s) != sar.expected(plain, s)) > 0


# --------------------------------------------------- the cell and its metrics

def test_the_cell_is_the_configuration_under_the_mix_the_issue_gave():
    m = mf.Manifest()
    w = m.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == ("rbac-oidc-groups", "sar-groups-lone", 1)
    # appended after the cells that were there (a later PR appends after it)
    cells = [x["name"] for x in m.doc["workloads"]]
    assert cells.index(CELL) == cells.index("rbac-tenants.sar-reask-lone") + 1
    configs = [x["name"] for x in m.doc["configs"]]
    assert configs.index("rbac-oidc-groups") == configs.index("rbac-tenants") + 1
    mix = m.traffic("sar-groups-lone")
    assert (mix["kind"], mix["loop"], mix["connections"], mix["processes"]) == (
        "sar", "closed", 1, 1)
    assert mix["name_per_request"] is True and mix["aimed_share"] == 0.8
    assert mix["warmup_s"] == 3.0 and mix["pool_per_s"] == mix["precompute_per_s"]
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "latency_p50_ms", "latency_p95_ms", "setup_s"}
    for e2e in m.doc["end_to_end"]:
        if e2e["name"] in ("latency_p50_ms", "latency_p95_ms"):
            assert CELL in e2e["workloads"]
    listed = {x["name"]: x for x in m.doc["per_layer"] if CELL in x.get("workloads", ())}
    mine = {n: x for n, x in listed.items() if x["workloads"] == [CELL]}
    # what reads this cell's own mechanism, or the tail, carries its suffix
    assert all(n.endswith(".groups") for n in mine)
    assert {n.rsplit(".", 1)[0] for n in mine} == {
        "fallback_row_share", "match_roofline",
        "ancestor_extras_share", "extras_per_row", "body_kb_per_request", "encode_us_per_row"}
    # what reads the series every lone SAR cell reads is the `.lone` entry,
    # which lists the cell beside the others
    assert {n for n in listed if n.endswith(".lone")} >= {
        "ingress_ms.lone", "dispatch_ms_per_batch.lone", "device_idle_share.lone",
        "decode_us_per_row.lone", "dispatch_launch_ms.lone", "device_ms_per_batch.lone",
        "handler_host_ms.lone", "http_io_ms.lone",
        "flagged_row_share.lone", "bits_readback_share.lone"}
    # and the launch's counters, one entry for the five lone cells
    assert {n for n in listed if "." not in n} >= {
        "uploads_per_batch", "upload_bytes_per_batch", "readback_bytes_per_batch",
        "long_device_waits_per_kbatch"}
    # the manifest holds 128 per-layer metrics at most, and the door test
    # lays six of its own over a copy
    assert len(m.doc["per_layer"]) + len(list(
        (ROOT / "tests/benchmark_tests/door/benchmark/metrics").glob("*.json"))) <= 128
    # a row that leaves the fast path is the tail's; the rest is every request's
    assert {n for n, x in mine.items() if x["moves"] == "latency_p95_ms"} == {
        "fallback_row_share.groups"}
    # the roofline reads the module every SAR cell reads, by the same function
    assert m.metric_file("match_roofline.groups")["params"]["module"] == m.metric_file(
        "match_roofline.reask")["params"]["module"]
    assert "18.9 MB" in m.metric_file("match_roofline.groups")["params"]["reckoning"]
    # the unlisted ones come with the cell, and nothing of another suffix
    names = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert names - set(listed) == {
        "client_latency_p99_ms", "client_latency_max_ms", "over_deadline_share",
        "gc_pause_max_ms", "ready_s", "ladder_s", "window_compiles"}


def test_the_groups_metrics_read_the_programs_counters():
    """On an exposition written from the program's own metric classes: the
    share of known memberships on the extras list, the extras a row, the
    body's size, the encode stage a row; and nothing for the ancestors'
    share from a server without the family (the parent)."""
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
    metrics.record_encode_ancestors("authorization", "slot", 80)
    metrics.record_encode_ancestors("authorization", "extras", 320)
    metrics.record_encode_ancestors("authorization", "unknown", 900)
    metrics.record_encode_ancestors("admission", "extras", 77)  # another path's: not read
    metrics.record_encode_extras("authorization", 320, 10)
    metrics.record_encode_extras("admission", 5, 5)
    for _ in range(10):
        metrics.record_request_body_bytes("authorization", 5000)
        metrics.record_row_routing("authorization", "clean_native", 1)
    metrics.record_row_routing("authorization", "encoder_fallback", 2)
    ctx.prom_after = exposition()
    assert read(ctx, "ancestor_extras_share.groups") == pytest.approx(80.0)
    assert read(ctx, "extras_per_row.groups") == pytest.approx(32.0)
    assert read(ctx, "body_kb_per_request.groups") == pytest.approx(5.0)
    assert read(ctx, "fallback_row_share.groups") == pytest.approx(100 * 2 / 12)
    # the parent's exposition, recorded before the family
    here = pathlib.Path(__file__).resolve().parent
    old = Context()
    old.prom_before = prom.parse((here / "recorded_metrics_before.txt").read_text())
    old.prom_after = prom.parse((here / "recorded_metrics_after.txt").read_text())
    assert read(old, "ancestor_extras_share.groups") is None
    assert read(old, "encode_us_per_row.groups") == read(old, "encode_us_per_row.saturate")
    # the handler's timer is read by the entry every lone SAR cell shares
    assert read(old, "ingress_ms.lone") > 0
    assert CELL in mf.Manifest().metric_file("ingress_ms.lone")["workloads"]
