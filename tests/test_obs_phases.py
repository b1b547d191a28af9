"""The per-request phase ledger (docs/observability.md "Request phases").

A CPU-served WebhookServer with the native fast paths behind the pipelined
batcher is driven over loopback HTTP on keep-alive connections. What is
checked is what the ledger promises: a request's phases are consecutive
and non-negative, on a connection they sum to the time from one reply
flushed to the next, the phases between the handler's timer's two ends
cover the timer, and /metrics, /debug/traces and the stage histograms say
the same thing because they are cut from the same stamps.
"""

import http.client
import io
import json
import logging
import threading
import time

import pytest

from cedar_tpu.cache import DecisionCache
from cedar_tpu.engine.evaluator import TPUPolicyEngine
from cedar_tpu.engine.fastpath import AdmissionFastPath, SARFastPath
from cedar_tpu.lang import PolicySet
from cedar_tpu.native import native_available
from cedar_tpu.obs import logsink
from cedar_tpu.obs.trace import Tracer, span_tree_coverage
from cedar_tpu.server import metrics
from cedar_tpu.server.admission import (
    ALLOW_ALL_ADMISSION_POLICY_SOURCE,
    CedarAdmissionHandler,
    allow_all_admission_policy_store,
)
from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
from cedar_tpu.server.http import WebhookServer
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

pytestmark = pytest.mark.skipif(
    not native_available(), reason="no C++ toolchain for the native encoder"
)

POLICIES = """
permit (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "sam" && resource.resource == "pods" };
forbid (principal is k8s::User,
        action == k8s::admission::Action::"create",
        resource is core::v1::ConfigMap)
  when { resource.metadata has labels &&
         resource.metadata.labels.contains({key: "env", value: "prod"}) };
"""

HTTP_PHASES = ("read", "pre", "parse", "respond", "write")
PIPELINE_PHASES = (
    "queue", "encode_wait", "encode", "dispatch_wait", "dispatch",
    "device_wait", "decode", "wake",
)
TIMER_PHASES = ("parse",) + PIPELINE_PHASES + ("respond",)
ENDPOINTS = {"authorization": "/v1/authorize", "admission": "/v1/admit"}


def sar(i):
    return {
        "apiVersion": "authorization.k8s.io/v1",
        "kind": "SubjectAccessReview",
        "spec": {"user": "sam", "uid": "u", "groups": [],
                 "resourceAttributes": {"verb": "get", "resource": "pods",
                                        "version": "v1", "name": f"p{i}"}},
    }


def review(i):
    obj = {"apiVersion": "v1", "kind": "ConfigMap",
           "metadata": {"name": f"c{i}", "namespace": "default"}}
    return {
        "apiVersion": "admission.k8s.io/v1",
        "kind": "AdmissionReview",
        "request": {
            "uid": f"r{i}", "operation": "CREATE",
            "userInfo": {"username": "sam", "groups": []},
            "kind": {"group": "", "version": "v1", "kind": "ConfigMap"},
            "resource": {"group": "", "version": "v1",
                         "resource": "configmaps"},
            "namespace": "default", "name": f"c{i}", "object": obj,
        },
    }


BODIES = {"authorization": sar, "admission": review}


class Served:
    """The server, and every request's phase record as the handler
    finished it (the hook wraps the one call the handler makes)."""

    def __init__(self, **kwargs):
        engine = TPUPolicyEngine()
        engine.load([PolicySet.from_source(POLICIES, "srv")], warm="off")
        stores = TieredPolicyStores([MemoryStore.from_source("srv", POLICIES)])
        authorizer = CedarWebhookAuthorizer(stores, evaluate=engine.evaluate)
        adm_engine = TPUPolicyEngine()
        adm_engine.load(
            [PolicySet.from_source(POLICIES, "srv"),
             PolicySet.from_source(ALLOW_ALL_ADMISSION_POLICY_SOURCE, "aa")],
            warm="off",
        )
        handler = CedarAdmissionHandler(
            TieredPolicyStores([MemoryStore.from_source("srv", POLICIES),
                                allow_all_admission_policy_store()]),
            evaluate=adm_engine.evaluate,
            evaluate_batch=adm_engine.evaluate_batch,
        )
        self.tracer = Tracer(sample_rate=1.0, ring_capacity=4096)
        self.server = WebhookServer(
            authorizer=authorizer, admission_handler=handler,
            address="127.0.0.1", port=0, metrics_port=0,
            fastpath=SARFastPath(engine, authorizer),
            admission_fastpath=AdmissionFastPath(adm_engine, handler),
            pipeline_depth=2, tracer=self.tracer, **kwargs,
        )
        self.records = []
        finish = self.server._finish_phases

        def keep(phases):
            finish(phases)
            self.records.append(phases)

        self.server._finish_phases = keep
        self.server.start()

    def connection(self):
        return http.client.HTTPConnection(
            "127.0.0.1", self.server.bound_port, timeout=30
        )

    def stop(self):
        self.server.stop()


def post(conn, path, doc):
    conn.request("POST", path, body=json.dumps(doc).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    return resp, body


@pytest.fixture()
def served():
    s = Served()
    yield s
    s.stop()


def phase_totals(path):
    return {phase: total for (p, phase), (total, _n)
            in metrics.request_phase_seconds.totals().items() if p == path}


@pytest.mark.parametrize("path", sorted(ENDPOINTS))
def test_phases_partition_a_connection_from_flush_to_flush(served, path):
    n = 12
    before = phase_totals(path)
    conn = served.connection()
    read_at = []
    for i in range(n):
        resp, _ = post(conn, ENDPOINTS[path], BODIES[path](i))
        assert resp.status == 200
        read_at.append(time.monotonic())
    conn.close()
    deadline = time.monotonic() + 5
    while len(served.records) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    records = served.records
    assert len(records) == n and {r.path for r in records} == {path}

    ledger = {}
    for k, rec in enumerate(records):
        windows = rec.windows()
        names = [w[0] for w in windows]
        # the first request of a connection has nothing before it
        assert names == (["between"] if k else []) + [
            "read", "pre", "parse", *PIPELINE_PHASES, "respond", "write"]
        # consecutive and non-negative
        for (_, _a0, a1), (_, b0, _b1) in zip(windows, windows[1:]):
            assert a1 == b0
        assert all(t1 >= t0 for _, t0, t1 in windows)
        # flushed to flushed, exactly: the stamps are the boundaries
        start = rec.t_prev if k else rec.t_line
        assert sum(t1 - t0 for _, t0, t1 in windows) == pytest.approx(
            rec.t_flush - start, abs=1e-9)
        if k:
            assert rec.t_prev == records[k - 1].t_flush
        # the handler's own timer is covered by parse … respond
        timed = sum(t1 - t0 for p, t0, t1 in windows if p in TIMER_PHASES)
        assert timed >= 0.95 * (rec.t_stop - rec.t_start)
        assert timed == pytest.approx(rec.t_stop - rec.t_start, abs=1e-9)
        for p, t0, t1 in windows:
            ledger[p] = ledger.get(p, 0.0) + (t1 - t0)

    # against the client's clock: the replies took what their phases say.
    # Counted from the third reply: a kernel that delays ACKs does not yet
    # do so on a connection's first exchanges, so the first replies reach
    # the client sooner after their flush than the later ones
    # (the exact statement is the one above; the client's stamps come
    # when the test's thread next runs, so this one has slack)
    client = read_at[-1] - read_at[2]
    served_s = records[-1].t_flush - records[2].t_flush
    assert served_s == pytest.approx(client, rel=0.05, abs=25e-3)

    # /metrics holds the same sums: one source
    after = phase_totals(path)
    for p, total in ledger.items():
        assert after[p] - before.get(p, 0.0) == pytest.approx(total, abs=1e-9)


def test_the_handlers_timer_metric_is_covered_by_the_phases(served):
    def timer_sum():
        with metrics.request_latency._lock:
            return sum(metrics.request_latency._sums.values())

    before_timer, before = timer_sum(), phase_totals("authorization")
    conn = served.connection()
    for i in range(20):
        post(conn, "/v1/authorize", sar(100 + i))
    conn.close()
    deadline = time.monotonic() + 5
    while len(served.records) < 20 and time.monotonic() < deadline:
        time.sleep(0.01)
    after = phase_totals("authorization")
    inside = sum(after[p] - before.get(p, 0.0) for p in TIMER_PHASES)
    timer = timer_sum() - before_timer
    # timer_accounted_share, as the benchmark reads it
    assert 0.95 * timer <= inside <= timer * (1 + 1e-6)


def test_a_cache_hit_has_no_pipeline_phases():
    s = Served(decision_cache=DecisionCache())
    try:
        conn = s.connection()
        for _ in range(3):
            _, body = post(conn, "/v1/authorize", sar(0))
            assert body["status"]["allowed"] is True
        conn.close()
        deadline = time.monotonic() + 5
        while len(s.records) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        miss, hit = s.records[0], s.records[2]
        assert [w[0] for w in miss.windows()][3:-2] == list(PIPELINE_PHASES)
        assert [w[0] for w in hit.windows()] == [
            "between", "read", "pre", "parse", "respond", "write"]
        doc = s.tracer.get(hit.trace.trace_id)
        assert not any(sp["name"].startswith("batch.") for sp in doc["spans"])
        assert span_tree_coverage(doc) >= 0.95
    finally:
        s.stop()


def timer_counts_by():
    """{by: observations} of the handler's timer, over every decision."""
    out = {}
    with metrics.request_latency._lock:
        for key, n in metrics.request_latency._totals.items():
            by = dict(key)["by"]
            out[by] = out.get(by, 0) + n
    return out


def system_sar(i):
    doc = sar(i)
    doc["spec"]["user"] = "system:kube-scheduler"
    return doc


def test_the_timer_says_who_answered_and_the_memo_counts():
    """One observation a request, labelled by who answered: a repeated body
    by the cache, a system:* user by the webhook's own rule, the rest by
    the engine; the root span says the same, the cache.fingerprint span
    says whether the memo held the body, and /metrics (the memo's two
    counters among it) still reads under the benchmark's parser."""
    import urllib.request

    from benchmark import prom

    s = Served(decision_cache=DecisionCache())
    try:
        before = timer_counts_by()
        sent = [sar(0), sar(0), sar(1), system_sar(2), sar(0), system_sar(3)]
        conn = s.connection()
        for doc in sent:
            resp, _ = post(conn, "/v1/authorize", doc)
            assert resp.status == 200
        conn.close()
        deadline = time.monotonic() + 5
        while len(s.records) < len(sent) and time.monotonic() < deadline:
            time.sleep(0.01)
        after = timer_counts_by()
        moved = {by: after[by] - before.get(by, 0) for by in after
                 if after[by] != before.get(by, 0)}
        assert moved == {"engine": 2, "cache": 2, "rule": 2}
        assert sum(moved.values()) == len(sent)

        docs = [s.tracer.get(r.trace.trace_id) for r in s.records]
        assert [d["spans"][0]["attrs"]["answered_by"] for d in docs] == [
            "engine", "cache", "engine", "rule", "cache", "rule"]
        memo_hits = []
        for d in docs:
            (fp,) = [sp for sp in d["spans"] if sp["name"] == "cache.fingerprint"]
            (look,) = [sp for sp in d["spans"] if sp["name"] == "cache.lookup"]
            assert fp["start_us"] <= look["start_us"]
            memo_hits.append(fp["attrs"]["memo_hit"])
        assert memo_hits == [False, True, False, False, True, False]
        assert s.server._sar_memo.counts() == (2, 4)

        with urllib.request.urlopen(
            f"http://127.0.0.1:{s.server.bound_metrics_port}/metrics", timeout=10
        ) as r:
            samples = prom.parse(r.read().decode())
        memo = {labels["outcome"]: v for name, labels, v in samples
                if name == "cedar_fingerprint_memo_total"
                and labels["path"] == "authorization"}
        assert memo == {"hit": 2.0, "miss": 4.0}
        timer = "cedar_authorizer_request_duration_seconds_count"
        for by, n in after.items():
            assert prom.total(samples, timer, {"by": by}) == n
        assert prom.total(samples, timer) == sum(after.values())
    finally:
        s.stop()


def authorize_from_threads(served, callers, per_caller, path="authorization"):
    """Distinct requests from ``callers`` keep-alive connections at once;
    the X-Cedar-Trace-Id of every reply, once every request's phases are
    in."""
    ids, errors = [], []

    def caller(k):
        try:
            conn = served.connection()
            for i in range(per_caller):
                resp, _ = post(conn, ENDPOINTS[path], BODIES[path](1000 * k + i))
                assert resp.status == 200
                ids.append(resp.headers["X-Cedar-Trace-Id"])
            conn.close()
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=caller, args=(k,)) for k in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert not errors
    deadline = time.monotonic() + 5
    want = per_caller * callers
    while len(served.records) < want and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(served.records) == want
    return ids


def test_kept_traces_cover_the_request_under_16_concurrent_callers(served):
    authorize_from_threads(served, callers=16, per_caller=8)
    for rec in served.records:
        doc = served.tracer.get(rec.trace.trace_id)
        assert span_tree_coverage(doc) >= 0.95
        spans = {sp["name"]: sp for sp in doc["spans"]}
        assert {"http.read", "http.pre", "http.parse", "batch.queue_wait",
                "batch.encode_wait", "batch.encode", "batch.dispatch_wait",
                "batch.dispatch", "batch.device_wait", "batch.decode",
                "batch.wake", "http.respond", "http.write"} <= set(spans)
        # the spans ARE the phases: same windows, to the rendering's 0.1 us
        for phase, t0, t1 in rec.windows():
            if phase == "between":
                assert doc["spans"][0]["attrs"]["between_us"] == pytest.approx(
                    (t1 - t0) * 1e6, abs=0.06)
                continue
            name = ("batch.queue_wait" if phase == "queue" else
                    f"http.{phase}" if phase in HTTP_PHASES else f"batch.{phase}")
            assert spans[name]["duration_us"] == pytest.approx(
                (t1 - t0) * 1e6, abs=0.11)
        assert doc["duration_us"] == pytest.approx(
            (rec.t_flush - rec.t_line) * 1e6, abs=0.11)


def test_every_served_request_leaves_its_log_line_through_the_sink(served):
    """The serving log's sink as main() installs it (the root logger's
    handler): N distinct SARs from 8 threads leave exactly N
    ``authorize requestId=`` lines, their ids the N X-Cedar-Trace-Ids the
    callers were handed, and each request still has its ``write`` phase."""
    want = 8 * 12
    stream = io.StringIO()
    sink = logsink.LogSink(stream).start()
    root = logging.getLogger()
    level = root.level
    root.addHandler(sink)
    root.setLevel(logging.INFO)
    writes_before = metrics.request_phase_seconds.totals().get(
        ("authorization", "write"), (0.0, 0))[1]
    try:
        ids = authorize_from_threads(served, callers=8, per_caller=12)
        sink.drain()
    finally:
        root.removeHandler(sink)
        sink.close()
        root.setLevel(level)
    logged = [line.split("requestId=")[1].split(" ")[0]
              for line in stream.getvalue().splitlines()
              if " cedar_tpu.server.http INFO authorize requestId=" in line]
    assert len(ids) == len(set(ids)) == want
    assert sorted(logged) == sorted(ids)
    assert metrics.request_phase_seconds.totals()[
        ("authorization", "write")][1] - writes_before == want


def claims_on_metrics(served, path):
    """cedar_batch_claims_total{path, held} as /metrics prints it:
    (held yes, held no)."""
    conn = http.client.HTTPConnection(
        "127.0.0.1", served.server.bound_metrics_port, timeout=30)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    found = {"yes": 0.0, "no": 0.0}
    for line in text.splitlines():
        if line.startswith("cedar_batch_claims_total{") and f'path="{path}"' in line:
            held = line.split('held="')[1].split('"')[0]
            found[held] = float(line.rsplit(" ", 1)[1])
    return found["yes"], found["no"]


@pytest.mark.parametrize("path", sorted(ENDPOINTS))
def test_a_lone_callers_claims_are_never_held_on_metrics(served, path):
    yes0, no0 = claims_on_metrics(served, path)
    conn = served.connection()
    for i in range(10):
        resp, _ = post(conn, ENDPOINTS[path], BODIES[path](7000 + i))
        assert resp.status == 200
    conn.close()
    yes1, no1 = claims_on_metrics(served, path)
    assert (yes1 - yes0, no1 - no0) == (0.0, 10.0)


@pytest.mark.parametrize("path", sorted(ENDPOINTS))
def test_claims_behind_a_slow_launch_are_held_on_metrics(served, path):
    """16 callers against a launch that takes 20 ms: the dispatch stage
    sets the pace, the backlog waits in the submit queue for the one
    standing place, and the counter says so for the path's own batcher."""
    batcher = (served.server._batcher if path == "authorization"
               else served.server._adm_raw_batcher)
    launch = batcher.stages.pipeline_dispatch

    def slow_launch(ctx):
        time.sleep(0.02)
        return launch(ctx)

    batcher.stages.pipeline_dispatch = slow_launch
    other = "admission" if path == "authorization" else "authorization"
    before, other_before = claims_on_metrics(served, path), claims_on_metrics(served, other)
    batches_before = batcher.debug_stats()["batches_total"]
    try:
        authorize_from_threads(served, callers=16, per_caller=6, path=path)
    finally:
        del batcher.stages.pipeline_dispatch
    after = claims_on_metrics(served, path)
    yes, no = after[0] - before[0], after[1] - before[1]
    batches = batcher.debug_stats()["batches_total"] - batches_before
    assert yes + no == batches < 16 * 6
    assert yes >= 0.25 * batches
    assert claims_on_metrics(served, other) == other_before


def test_stage_histograms_and_spans_share_the_sub_stage_stamps(served):
    def stage_sums():
        h = metrics.pipeline_stage_seconds
        with h._lock:
            return {dict(k)["stage"]: (h._sums[k], h._totals[k])
                    for k in h._sums if dict(k)["path"] == "authorization"}

    before = stage_sums()
    conn = served.connection()
    for i in range(6):
        post(conn, "/v1/authorize", sar(5000 + i))
    conn.close()
    deadline = time.monotonic() + 5
    while len(served.records) < 6 and time.monotonic() < deadline:
        time.sleep(0.01)
    after = stage_sums()
    delta = {s: (after[s][0] - before.get(s, (0.0, 0))[0],
                 after[s][1] - before.get(s, (0.0, 0))[1]) for s in after}
    for stage in ("dispatch.stage", "dispatch.launch", "dispatch.readback",
                  "decode.device_wait", "decode.host"):
        assert delta[stage][1] == delta["dispatch"][1] == 6, stage
    # one caller, one row a batch: each request's batch is its own
    subs = {"dispatch": 0.0, "decode": 0.0}
    for rec in served.records:
        for k, v in rec.times.sub.items():
            subs[k] = subs.get(k, 0.0) + v
        subs["dispatch"] += rec.times.dispatch1 - rec.times.dispatch0
        subs["decode"] += rec.times.decode1 - rec.times.decode0
    for stage in ("dispatch", "dispatch.stage", "dispatch.launch",
                  "dispatch.readback", "decode", "decode.device_wait",
                  "decode.host"):
        assert delta[stage][0] == pytest.approx(subs[stage], abs=1e-9)
    # the parts are stamped, not derived, and they partition their stage
    for whole, parts in (
        ("dispatch", ("dispatch.stage", "dispatch.launch", "dispatch.readback")),
        ("decode", ("decode.device_wait", "decode.host")),
    ):
        assert sum(delta[s][0] for s in parts) == pytest.approx(
            delta[whole][0], abs=1e-9)
    # and a kept trace carries them on its dispatch and decode spans
    doc = served.tracer.get(served.records[-1].trace.trace_id)
    spans = {sp["name"]: sp for sp in doc["spans"]}
    assert {"stage_us", "launch_us", "readback_us"} <= set(
        spans["batch.dispatch"]["attrs"])
    assert {"device_wait_us", "host_us"} <= set(spans["batch.decode"]["attrs"])


def test_no_tracer_no_ledger():
    """--no-trace is the floor: no phase record, no stall recorder."""
    s = Served()
    s.stop()
    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(POLICIES, "srv")], warm="off")
    stores = TieredPolicyStores([MemoryStore.from_source("srv", POLICIES)])
    authorizer = CedarWebhookAuthorizer(stores, evaluate=engine.evaluate)
    server = WebhookServer(
        authorizer=authorizer,
        admission_handler=CedarAdmissionHandler(
            TieredPolicyStores([allow_all_admission_policy_store()])),
        address="127.0.0.1", port=0, metrics_port=0,
        fastpath=SARFastPath(engine, authorizer), pipeline_depth=2,
    )
    server.start()
    try:
        before = phase_totals("authorization")
        conn = http.client.HTTPConnection("127.0.0.1", server.bound_port, timeout=30)
        _, body = post(conn, "/v1/authorize", sar(1))
        conn.close()
        assert body["status"]["allowed"] is True
        assert phase_totals("authorization") == before
        assert server.stalls is None
        mconn = http.client.HTTPConnection(
            "127.0.0.1", server.bound_metrics_port, timeout=30)
        mconn.request("GET", "/debug/stalls")
        assert mconn.getresponse().status == 404
        mconn.close()
    finally:
        server.stop()


def test_every_metric_family_is_named_in_a_document():
    import pathlib

    docs = "\n".join(
        p.read_text() for p in
        (pathlib.Path(__file__).resolve().parents[1] / "docs").glob("*.md"))
    families = [m.name for m in metrics.REGISTRY._metrics]
    assert "cedar_request_phase_seconds" in families
    assert not [n for n in families if "packed_decode" in n]
    assert [n for n in families if n not in docs] == []
