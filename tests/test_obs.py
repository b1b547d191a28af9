"""Observability plane (cedar_tpu/obs, docs/observability.md).

The load-bearing pieces:

  * a ≥1.1k-body differential proving the serving path is byte-identical
    with the tracing plane compiled in but unsampled (sample rate 0)
    versus a server with no tracer at all;
  * W3C traceparent ingestion/propagation over HTTP: the ingested trace
    id becomes the requestId, the X-Cedar-Trace-Id response header, and
    the /debug/traces key; responses carry a fresh traceparent;
  * a slow request's span tree accounting for ≥95% of its measured e2e
    latency across named stages (the acceptance bar);
  * tail-keep of a deadline-expired request at sample rate 0;
  * audit-log lines joining recorder files by canonical fingerprint, and
    size-based audit rotation;
  * SLO burn-rate math over the multi-window ring;
  * cedar-trace exit codes (0 found / 2 no match / 1 unreadable);
  * the bounded e2e filename label and the per-stage pipeline histograms.
"""

import io
import json
import os
import time
import urllib.request
from contextlib import redirect_stderr, redirect_stdout

import pytest

from cedar_tpu.lang import PolicySet
from cedar_tpu.obs.audit import AuditLog, audit_entry, determining_policies
from cedar_tpu.obs.slo import SLOTracker
from cedar_tpu.obs.trace import (
    Trace,
    Tracer,
    current_trace,
    format_traceparent,
    ingest_request_id,
    parse_traceparent,
    set_current,
    span_tree_coverage,
)
from cedar_tpu.server.admission import (
    CedarAdmissionHandler,
    allow_all_admission_policy_store,
)
from cedar_tpu.server.authorizer import (
    DECISION_ALLOW,
    CedarWebhookAuthorizer,
)
from cedar_tpu.server.http import WebhookServer
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

FILENAME = "obs-test"

POLICIES = """
permit (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "alice" && resource.resource == "pods" };
forbid (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "carol" && resource.resource == "secrets" };
"""


def sar_body(user="alice", resource="pods", namespace="default", verb="get"):
    return json.dumps(
        {
            "apiVersion": "authorization.k8s.io/v1",
            "kind": "SubjectAccessReview",
            "spec": {
                "user": user,
                "uid": "u",
                "groups": [],
                "resourceAttributes": {
                    "verb": verb,
                    "version": "v1",
                    "resource": resource,
                    "namespace": namespace,
                },
            },
        }
    ).encode()


def review_body(uid="r1", name="c"):
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
                "object": {
                    "apiVersion": "v1",
                    "kind": "ConfigMap",
                    "metadata": {"name": name, "namespace": "default"},
                },
            },
        }
    ).encode()


def _interpreter_server(**kwargs) -> WebhookServer:
    store = MemoryStore(FILENAME, PolicySet.from_source(POLICIES, FILENAME))
    stores = TieredPolicyStores([store])
    authorizer = CedarWebhookAuthorizer(stores)
    handler = CedarAdmissionHandler(
        TieredPolicyStores([store, allow_all_admission_policy_store()])
    )
    return WebhookServer(authorizer, handler, **kwargs)


class _SlowFastPath:
    """Minimal fastpath stand-in: one slow batched evaluate, so the span
    tree's queue-wait + evaluate windows must account for the latency."""

    available = True
    breaker = None

    def __init__(self, delay_s: float):
        self.delay_s = delay_s

    def authorize_raw(self, bodies):
        time.sleep(self.delay_s)
        return [(DECISION_ALLOW, "", None) for _ in bodies]


def _wait_kept(tracer, trace_id, timeout_s=5.0):
    """A served request's trace reaches the ring once its reply has been
    flushed (its tree runs to the flush): a client that asks in the same
    millisecond it got the reply can be a moment early."""
    deadline = time.monotonic() + timeout_s
    while tracer.get(trace_id) is None and time.monotonic() < deadline:
        time.sleep(0.002)


def _post(port, path, body, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=body,
        method="POST",
        headers=headers or {},
    )
    return urllib.request.urlopen(req, timeout=10)


def _get_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as resp:
        return json.loads(resp.read())


# -------------------------------------------------------------- traceparent


class TestTraceparent:
    def test_parse_roundtrip(self):
        tid, sid = "a" * 32, "b" * 16
        hdr = format_traceparent(tid, sid, True)
        assert hdr == f"00-{tid}-{sid}-01"
        assert parse_traceparent(hdr) == (tid, sid)

    @pytest.mark.parametrize(
        "bad",
        [
            None,
            "",
            "not-a-traceparent",
            "00-short-span-01",
            "00-" + "0" * 32 + "-" + "b" * 16 + "-01",  # all-zero trace id
            "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # all-zero span id
            "00-" + "g" * 32 + "-" + "b" * 16 + "-01",  # non-hex
        ],
    )
    def test_malformed_rejected(self, bad):
        assert parse_traceparent(bad) is None

    def test_ingest_request_id(self):
        tid, sid = "c" * 32, "d" * 16
        rid, parent = ingest_request_id(f"00-{tid}-{sid}-01")
        assert rid == tid and parent == sid
        rid, parent = ingest_request_id(None)
        assert len(rid) == 32 and parent is None
        int(rid, 16)  # hex


# ------------------------------------------------------------ tracer policy


class TestTracer:
    def test_head_sample_and_drop(self):
        tracer = Tracer(sample_rate=1.0, tail_latency_s=10.0)
        t = tracer.begin("authorization")
        assert tracer.finish(t, decision="Allow") == "sampled"
        tracer = Tracer(sample_rate=0.0, tail_latency_s=10.0)
        t = tracer.begin("authorization")
        assert tracer.finish(t, decision="Allow") is None
        assert tracer.list_traces() == []

    def test_tail_keep_slow_error_fallback(self):
        tracer = Tracer(sample_rate=0.0, tail_latency_s=0.5)
        slow = tracer.begin("authorization")
        slow.root.t0 -= 2.0  # a 2s request without sleeping 2s
        assert tracer.finish(slow, decision="Allow") == "slow"
        err = tracer.begin("authorization")
        assert tracer.finish(err, decision="<error>", error=True) == "error"
        fb = tracer.begin("authorization")
        fb.fallback = True
        assert tracer.finish(fb, decision="Allow") == "fallback"
        kept = {t["kept"] for t in tracer.list_traces()}
        assert kept == {"slow", "error", "fallback"}

    def test_ring_bound_and_prefix_get(self):
        tracer = Tracer(sample_rate=1.0, ring_capacity=4)
        ids = []
        for _ in range(10):
            t = tracer.begin("authorization")
            ids.append(t.trace_id)
            tracer.finish(t)
        assert len(tracer.list_traces()) == 4
        assert tracer.get(ids[0]) is None  # evicted
        assert tracer.get(ids[-1][:10])["traceId"] == ids[-1]

    def test_jsonl_export(self, tmp_path):
        log = tmp_path / "trace.jsonl"
        tracer = Tracer(sample_rate=1.0, log_file=str(log))
        for _ in range(3):
            tracer.finish(tracer.begin("authorization"), decision="Allow")
        tracer.close()
        lines = log.read_text().splitlines()
        assert len(lines) == 3
        assert all(json.loads(ln)["kept"] == "sampled" for ln in lines)

    def test_span_attrs_bounded(self):
        t = Trace("authorization")
        with t.span("s") as sp:
            for i in range(50):
                sp.set_attr(f"k{i}", "v" * 1000)
        assert len(sp.attrs) <= 16
        assert all(len(str(v)) <= 200 for v in sp.attrs.values())

    def test_coverage_merges_overlaps(self):
        doc = {
            "duration_us": 100.0,
            "spans": [
                {"spanId": "r", "name": "root", "start_us": 0, "duration_us": 100.0},
                {"spanId": "a", "name": "x", "start_us": 0, "duration_us": 60.0},
                {"spanId": "b", "name": "y", "start_us": 40.0, "duration_us": 58.0},
                {"spanId": "c", "name": "z", "start_us": 50.0, "duration_us": 10.0},
            ],
        }
        # union of [0,60] + [40,98] + [50,60] = [0,98] -> 98%
        assert span_tree_coverage(doc) == pytest.approx(0.98, abs=1e-6)


# --------------------------------------------------- disarmed differential


class TestDisarmedDifferential:
    def test_1100_body_byte_identical_unsampled(self):
        """Tracing compiled in but unsampled (rate 0, SLO + thread-local
        machinery active) answers byte-for-byte what a tracer-less server
        answers, on >=1.1k bodies across both endpoints."""
        bare = _interpreter_server()
        traced = _interpreter_server(
            tracer=Tracer(sample_rate=0.0, tail_latency_s=100.0),
            slo=SLOTracker(latency_budget_s=100.0),
        )
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
        for i in range(300):
            bodies.append(("admit", review_body(uid=f"r{i}", name=f"c{i}")))
        assert len(bodies) >= 1100
        for kind, body in bodies:
            if kind == "authorize":
                a = bare.handle_authorize(body)
                b = traced.handle_authorize(body)
            else:
                a = bare.handle_admit(body)
                b = traced.handle_admit(body)
            assert json.dumps(a, sort_keys=False) == json.dumps(
                b, sort_keys=False
            )
        # rate 0 + nothing slow/errored: the ring stayed empty
        assert traced.tracer.list_traces() == []
        # the thread-local never leaks out of a request
        assert current_trace() is None


# ------------------------------------------------------- HTTP ingest + e2e


class TestHTTPTracing:
    def test_traceparent_ingest_propagate_and_fetch(self):
        tracer = Tracer(sample_rate=1.0)
        server = _interpreter_server(tracer=tracer)
        server.start()
        try:
            tid, sid = "ab" * 16, "cd" * 8
            with _post(
                server.bound_port,
                "/v1/authorize",
                sar_body(),
                headers={"traceparent": f"00-{tid}-{sid}-01"},
            ) as resp:
                assert resp.headers["X-Cedar-Trace-Id"] == tid
                echoed = parse_traceparent(resp.headers["traceparent"])
                assert echoed is not None and echoed[0] == tid
                assert echoed[1] != sid  # OUR root span, not the parent's
                # rate 1.0: the recorded flag is honest
                assert resp.headers["traceparent"].endswith("-01")
                json.loads(resp.read())
            _wait_kept(tracer, tid)
            doc = _get_json(
                server.bound_metrics_port, f"/debug/traces/{tid}"
            )
            assert doc["traceId"] == tid
            assert doc["upstreamParent"] == sid
            assert doc["decision"] == "Allow"
            listing = _get_json(server.bound_metrics_port, "/debug/traces")
            assert any(t["traceId"] == tid for t in listing["traces"])

            # no traceparent -> fresh 32-hex id, still echoed
            with _post(
                server.bound_port, "/v1/authorize", sar_body()
            ) as resp:
                rid = resp.headers["X-Cedar-Trace-Id"]
                assert len(rid) == 32 and rid != tid
                int(rid, 16)
        finally:
            server.stop()

    def test_slow_request_tree_covers_95_percent_of_e2e(self):
        """Acceptance: a slow request's /debug/traces span tree accounts
        for >=95% of its measured e2e latency across named stages."""
        tracer = Tracer(sample_rate=1.0)
        server = _interpreter_server(
            tracer=tracer, fastpath=_SlowFastPath(0.08)
        )
        server.start()
        try:
            with _post(
                server.bound_port, "/v1/authorize", sar_body()
            ) as resp:
                tid = resp.headers["X-Cedar-Trace-Id"]
                json.loads(resp.read())
            _wait_kept(tracer, tid)
            doc = _get_json(
                server.bound_metrics_port, f"/debug/traces/{tid}"
            )
            names = {s["name"] for s in doc["spans"]}
            assert {"batch.queue_wait", "batch.evaluate"} <= names
            assert doc["duration_us"] >= 80e3
            assert span_tree_coverage(doc) >= 0.95
        finally:
            server.stop()

    def test_sampled_flag_honest_at_rate_zero(self):
        """The response traceparent must not claim 'recorded' when head
        sampling is off — callers honoring the W3C flag would otherwise
        record 100% of their own spans against dropped traces."""
        server = _interpreter_server(
            tracer=Tracer(sample_rate=0.0, tail_latency_s=100.0)
        )
        server.start()
        try:
            with _post(
                server.bound_port, "/v1/authorize", sar_body()
            ) as resp:
                assert resp.headers["traceparent"].endswith("-00")
        finally:
            server.stop()

    def test_tail_keep_of_deadline_expired_request(self):
        """Sample rate 0: only the tail-keep policy can keep anything —
        and a deadline-expired (error-answered) request IS kept."""
        tracer = Tracer(sample_rate=0.0, tail_latency_s=100.0)
        server = _interpreter_server(
            tracer=tracer,
            fastpath=_SlowFastPath(0.6),
            request_timeout_s=0.05,
        )
        try:
            body = sar_body()
            resp = server.handle_authorize(body)
            assert "evaluationError" in resp["status"]
            traces = tracer.list_traces()
            assert len(traces) == 1
            assert traces[0]["kept"] == "error"
            full = tracer.get(traces[0]["traceId"])
            assert any(
                s["name"] == "deadline_exceeded" for s in full["spans"]
            )
        finally:
            server.stop()


# ------------------------------------------------------------- audit plane


class TestAuditLog:
    def test_determining_policies_both_shapes(self):
        diag = json.dumps(
            {"reasons": [{"policy": "policy0"}, {"policy": "policy2"}]}
        )
        assert determining_policies(diag) == ["policy0", "policy2"]
        adm = json.dumps([{"policy": "p1", "position": {}}])
        assert determining_policies(adm) == ["p1"]
        assert determining_policies("") == []
        assert determining_policies("plain text reason") == []

    def test_size_based_rotation(self, tmp_path):
        path = tmp_path / "audit.jsonl"
        audit = AuditLog(str(path), max_bytes=4096, max_files=2)
        for i in range(200):
            audit.record(
                audit_entry(
                    "authorization", f"{i:032x}", "f" * 32, "Allow",
                    latency_s=0.001,
                )
            )
        audit.close()
        assert audit.rotations >= 1
        assert path.exists() and (tmp_path / "audit.jsonl.1").exists()
        # every line in every generation parses, newest file is bounded
        for p in (path, tmp_path / "audit.jsonl.1"):
            for line in p.read_text().splitlines():
                doc = json.loads(line)
                assert doc["decision"] == "Allow"
        assert path.stat().st_size <= 4096 + 200

    def test_audit_line_joins_recorder_file_by_fingerprint(self, tmp_path):
        """Acceptance: an audit-log line joins a recorder file by the
        shared canonical fingerprint."""
        from cedar_tpu.server.recorder import RequestRecorder

        rec_dir = tmp_path / "rec"
        audit_path = tmp_path / "audit.jsonl"
        server = _interpreter_server(
            recorder=RequestRecorder(str(rec_dir)),
            audit_log=AuditLog(str(audit_path)),
            tracer=Tracer(sample_rate=1.0),
        )
        server.start()
        try:
            with _post(
                server.bound_port, "/v1/authorize", sar_body("alice", "pods")
            ) as resp:
                tid = resp.headers["X-Cedar-Trace-Id"]
                doc = json.loads(resp.read())
                assert doc["status"]["allowed"] is True
        finally:
            server.stop()
        recorded = list(rec_dir.glob("req-authorize-*.json"))
        assert len(recorded) == 1
        rec_fp = recorded[0].name.split("-")[2]
        lines = [
            json.loads(ln)
            for ln in audit_path.read_text().splitlines()
        ]
        assert len(lines) == 1
        entry = lines[0]
        assert entry["fingerprint"] == rec_fp  # the join
        assert entry["traceId"] == tid  # joins /debug/traces too
        assert entry["decision"] == "Allow"
        assert entry["policies"]  # determining policy from the reason
        assert entry["latency_us"] > 0
        assert entry["fallback"] is False and entry["cached"] is False

    def test_admission_audited(self, tmp_path):
        audit_path = tmp_path / "audit.jsonl"
        server = _interpreter_server(audit_log=AuditLog(str(audit_path)))
        server.handle_admit(review_body())
        server.stop()
        entry = json.loads(audit_path.read_text().splitlines()[0])
        assert entry["path"] == "admission"
        assert entry["decision"] == "allowed"
        assert entry["fingerprint"] != "unkeyed"


# --------------------------------------------------------------- SLO plane


class TestSLO:
    def test_burn_rate_math_multi_window(self):
        now = [1_000_000.0]
        slo = SLOTracker(
            availability_target=0.999,
            latency_target=0.99,
            latency_budget_s=2.0,
            clock=lambda: now[0],
        )
        for _ in range(990):
            slo.record("authorization", 0.01, error=False)
        for _ in range(9):
            slo.record("authorization", 0.01, error=True)
        slo.record("authorization", 5.0, error=False)  # slow, not an error
        doc = slo.status()
        w5 = doc["paths"]["authorization"]["5m"]
        assert w5["requests"] == 1000
        assert w5["errors"] == 9 and w5["slow"] == 1
        # 9/1000 bad over a 0.001 budget = burn 9.0
        assert w5["availability_burn_rate"] == pytest.approx(9.0, rel=1e-3)
        # 1/1000 slow over a 0.01 budget = burn 0.1
        assert w5["latency_burn_rate"] == pytest.approx(0.1, rel=1e-3)

        # 10 minutes later the 5m window is clean, the 1h window remembers
        now[0] += 600
        doc = slo.status()
        w = doc["paths"]["authorization"]
        assert w["5m"]["requests"] == 0
        assert w["5m"]["availability_burn_rate"] == 0.0
        assert w["1h"]["requests"] == 1000
        assert w["1h"]["availability_burn_rate"] == pytest.approx(
            9.0, rel=1e-3
        )
        # 7 hours later even the 6h window has forgotten
        now[0] += 6.5 * 3600
        assert slo.status()["paths"]["authorization"]["6h"]["requests"] == 0

    def test_tracker_agrees_with_histogram_cross_check(self):
        """The tracker's slow fraction and a cumulative histogram's
        bucket-derived fraction of the same observations agree — the
        'computed from the existing histograms' invariant."""
        from cedar_tpu.obs.slo import slo_from_histogram
        from cedar_tpu.server.metrics import Histogram

        h = Histogram("obs_test_xcheck", "x", ["path"], [0.1, 0.5, 1.0, 2.0])
        slo = SLOTracker(latency_budget_s=0.5, clock=lambda: 1000.0)
        for v in (0.05, 0.2, 0.6, 1.5, 3.0):
            h.observe(v, path="authorization")
            slo.record("authorization", v, error=False)
        frac = slo_from_histogram(h, 0.5, path_label="authorization")
        ((_, f),) = frac.items()
        assert f == pytest.approx(3 / 5)
        w = slo.status()["paths"]["authorization"]["5m"]
        assert w["slow"] / w["requests"] == pytest.approx(f)

    def test_gauges_published_and_debug_endpoint(self):
        from cedar_tpu.server import metrics

        slo = SLOTracker(latency_budget_s=0.5)
        server = _interpreter_server(slo=slo)
        server.start()
        try:
            with _post(server.bound_port, "/v1/authorize", sar_body()):
                pass
            doc = _get_json(server.bound_metrics_port, "/debug/slo")
            assert (
                doc["paths"]["authorization"]["5m"]["requests"] >= 1
            )
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.bound_metrics_port}/metrics",
                timeout=5,
            ) as resp:
                text = resp.read().decode()
            assert 'cedar_slo_burn_rate{path="authorization"' in text
            assert 'cedar_slo_target{path="authorization"' in text
        finally:
            server.stop()
        assert metrics.slo_target is not None  # registered once, globally


# ------------------------------------------------------- satellite metrics


class TestSatelliteMetrics:
    def test_e2e_filename_label_bounded(self):
        from cedar_tpu.server import metrics

        before = metrics.e2e_label_overflow_total._values.get((), 0.0)
        for i in range(200):
            metrics.record_e2e_latency(f"bound-test-{i}.json", 0.01)
        with metrics.e2e_latency._lock:
            labels = {dict(k)["filename"] for k in metrics.e2e_latency._counts}
        assert len(labels) <= metrics._E2E_LABEL_CAP + 1
        assert "other" in labels
        after = metrics.e2e_label_overflow_total._values.get((), 0.0)
        assert after > before

    def test_pipeline_stage_histograms_from_batcher(self):
        from cedar_tpu.engine.batcher import MicroBatcher
        from cedar_tpu.server import metrics

        def fn(items):
            time.sleep(0.005)
            return [i * 2 for i in items]

        batcher = MicroBatcher(fn, metrics_path="authorization")
        try:
            assert batcher.submit(21) == 42
        finally:
            batcher.stop()
        with metrics.pipeline_stage_seconds._lock:
            stages = {
                dict(k)["stage"]
                for k in metrics.pipeline_stage_seconds._counts
                if dict(k)["path"] == "authorization"
            }
        assert {"queue_wait", "evaluate"} <= stages

    def test_batch_spans_annotate_active_trace(self):
        from cedar_tpu.engine.batcher import MicroBatcher

        batcher = MicroBatcher(lambda items: [i for i in items])
        trace = Trace("authorization")
        set_current(trace)
        try:
            batcher.submit(1)
        finally:
            set_current(None)
            batcher.stop()
        names = {s.name for s in trace.spans}
        assert {"batch.queue_wait", "batch.evaluate"} <= names


# ------------------------------------------------------------- cedar-trace


class TestCedarTraceCLI:
    @pytest.fixture()
    def trace_log(self, tmp_path):
        log = tmp_path / "traces.jsonl"
        tracer = Tracer(sample_rate=1.0, log_file=str(log))
        t1 = tracer.begin("authorization")
        with t1.span("interpreter"):
            time.sleep(0.002)
        tracer.finish(t1, decision="Allow")
        t2 = tracer.begin("admission")
        tracer.finish(t2, decision="allowed")
        tracer.close()
        log.write_text(log.read_text() + "not json\n")  # poison line
        return log, t1.trace_id

    def _run(self, argv):
        from cedar_tpu.cli.trace import main

        out, err = io.StringIO(), io.StringIO()
        with redirect_stdout(out), redirect_stderr(err):
            rc = main(argv)
        return rc, out.getvalue(), err.getvalue()

    def test_list_and_fetch(self, trace_log):
        log, tid = trace_log
        rc, out, err = self._run(["--log", str(log)])
        assert rc == 0
        assert tid in out
        assert "unparseable" in err  # the poison line is COUNTED
        rc, out, _ = self._run(["--log", str(log), tid[:12]])
        assert rc == 0
        assert "interpreter" in out
        assert "dominant stage" in out

    def test_no_match_exits_2(self, trace_log):
        log, _ = trace_log
        rc, _, err = self._run(["--log", str(log), "deadbeef"])
        assert rc == 2
        assert "no trace" in err

    def test_empty_source_exits_2(self, tmp_path):
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        rc, _, err = self._run(["--log", str(empty)])
        assert rc == 2

    def test_unreadable_exits_1(self, tmp_path):
        rc, _, err = self._run(["--log", str(tmp_path / "missing.jsonl")])
        assert rc == 1
        assert "error" in err

    def test_url_mode_against_live_ring(self):
        tracer = Tracer(sample_rate=1.0)
        server = _interpreter_server(tracer=tracer)
        server.start()
        try:
            with _post(
                server.bound_port, "/v1/authorize", sar_body()
            ) as resp:
                tid = resp.headers["X-Cedar-Trace-Id"]
            _wait_kept(tracer, tid)
            base = f"http://127.0.0.1:{server.bound_metrics_port}"
            rc, out, _ = self._run(["--url", base])
            assert rc == 0 and tid in out
            rc, out, _ = self._run(["--url", base, tid])
            assert rc == 0 and "e2e=" in out
            rc, _, _ = self._run(["--url", base, "f" * 32])
            assert rc == 2
        finally:
            server.stop()
