"""A batch's round trip on the profiler's clock (docs/observability.md,
"Batch stages on the profiler's clock"), and the launch counters.

The program's annotations run only while a ``jax.profiler`` session is
open, so no untraced run and no test without a session exercises them.
Here a CPU-served WebhookServer (the native SAR and admission fast paths
behind the pipelined batcher) answers each case of the served path inside
a real session — a lone clean row, a flagged row, a row answered at the
encoder's gate (no launch), a batch of two chunks — and is held to the
same answers as without one, to no compile after the warm-up, to a clean
stop after ``stop_trace``, and to a dump that ``benchmark/xplane.py`` and
``tools/xplane_stages.py launch`` both read: every ``cedar.dispatch.launch``
and ``cedar.decode.device_wait`` of a launched batch under the batch's
one ``seq``, each on its own thread.
"""

import json
import logging
import time

import jax
import numpy as np
import pytest

from benchmark import xplane
from cedar_tpu.engine import aot
from cedar_tpu.engine import batcher as batcher_mod
from cedar_tpu.engine.batcher import MicroBatcher, _StageTimes
from cedar_tpu.engine.evaluator import TPUPolicyEngine
from cedar_tpu.engine.fastpath import AdmissionFastPath, SARFastPath
from cedar_tpu.lang import PolicySet
from cedar_tpu.native import native_available
from cedar_tpu.obs import trace
from cedar_tpu.obs.stall import StallRecorder
from cedar_tpu.obs.trace import Tracer, batch_stage, profiler_scope, sub_stage
from cedar_tpu.server import metrics
from cedar_tpu.server.admission import (
    ALLOW_ALL_ADMISSION_POLICY_SOURCE,
    CedarAdmissionHandler,
    allow_all_admission_policy_store,
)
from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
from cedar_tpu.server.http import WebhookServer
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores
from test_obs_phases import post
from tools.xplane_stages import LAUNCH_TERMS, launch_anatomy

needs_native = pytest.mark.skipif(
    not native_available(), reason="no C++ toolchain for the native encoder"
)

POLICIES = """
permit (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "sam" && resource.resource == "pods" };
permit (principal in k8s::Group::"viewers", action == k8s::Action::"get",
        resource is k8s::Resource)
  when { resource.resource == "pods" };
forbid (principal, action == k8s::admission::Action::"create",
        resource is core::v1::ConfigMap)
  when { resource.metadata has labels &&
         resource.metadata.labels.contains({key: "env", value: "prod"}) };
forbid (principal is k8s::User, action == k8s::admission::Action::"create",
        resource is core::v1::ConfigMap)
  when { resource.metadata has labels &&
         resource.metadata.labels.contains({key: "tier", value: "gold"}) };
"""

# what the benchmark's harness counts as window_compiles (benchmark/serve.py)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILES = [0]


def _on_duration(event, duration, **kwargs):
    if event == COMPILE_EVENT:
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def sar(i, user="sam", groups=()):
    return {
        "apiVersion": "authorization.k8s.io/v1",
        "kind": "SubjectAccessReview",
        "spec": {"user": user, "uid": "u", "groups": list(groups),
                 "resourceAttributes": {"verb": "get", "resource": "pods",
                                        "version": "v1", "namespace": "default",
                                        "name": f"p{i}"}},
    }


def review(i, labels=None, namespace="default"):
    meta = {"name": f"c{i}", "namespace": namespace}
    if labels:
        meta["labels"] = labels
    return {
        "apiVersion": "admission.k8s.io/v1",
        "kind": "AdmissionReview",
        "request": {
            "uid": f"r{i}", "operation": "CREATE",
            "userInfo": {"username": "sam", "groups": []},
            "kind": {"group": "", "version": "v1", "kind": "ConfigMap"},
            "resource": {"group": "", "version": "v1", "resource": "configmaps"},
            "namespace": namespace, "name": f"c{i}",
            "object": {"apiVersion": "v1", "kind": "ConfigMap", "metadata": meta},
        },
    }


# (path, case) -> the bodies one case sends; each is answered alone
CASES = {
    ("authorization", "clean"): [sar(i) for i in range(3)],
    ("authorization", "flagged"): [sar(i, groups=["viewers"]) for i in range(3)],
    ("authorization", "gate"): [sar(i, user="system:kube-scheduler") for i in range(3)],
    ("admission", "clean"): [review(i) for i in range(3)],
    ("admission", "flagged"): [review(i, {"env": "prod", "tier": "gold"}) for i in range(3)],
    ("admission", "gate"): [review(i, namespace="kube-system") for i in range(3)],
}
ENDPOINT = {"authorization": "/v1/authorize", "admission": "/v1/admit"}


class Served:
    def __init__(self):
        sets = [PolicySet.from_source(POLICIES, "srv")]
        engine = TPUPolicyEngine()
        engine.load(sets, warm="off")
        authorizer = CedarWebhookAuthorizer(
            TieredPolicyStores([MemoryStore.from_source("srv", POLICIES)]),
            evaluate=engine.evaluate,
        )
        adm_engine = TPUPolicyEngine()
        adm_engine.load(
            sets + [PolicySet.from_source(ALLOW_ALL_ADMISSION_POLICY_SOURCE, "aa")],
            warm="off",
        )
        self.tracer = Tracer(sample_rate=1.0)
        handler = CedarAdmissionHandler(
            TieredPolicyStores([MemoryStore.from_source("srv", POLICIES),
                                allow_all_admission_policy_store()]),
            evaluate=adm_engine.evaluate, evaluate_batch=adm_engine.evaluate_batch,
        )
        self.server = WebhookServer(
            authorizer=authorizer, admission_handler=handler,
            address="127.0.0.1", port=0, metrics_port=0,
            fastpath=SARFastPath(engine, authorizer),
            admission_fastpath=AdmissionFastPath(adm_engine, handler),
            pipeline_depth=2, tracer=self.tracer,
        )
        self.server.start()
        self.batchers = [self.server._batcher, self.server._adm_raw_batcher]

    def ask(self, path, bodies) -> list:
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", self.server.bound_port, timeout=30)
        try:
            return [post(conn, ENDPOINT[path], b)[1] for b in bodies]
        finally:
            conn.close()

    def batch_of_two_chunks(self, path, bodies) -> list:
        """One claimed batch of len(bodies) rows, launched as two chunks:
        the standing place before the dispatch thread is taken while the
        entries are queued, so the collector claims them together."""
        batcher = self.batchers[path == "admission"]
        fast = batcher.stages
        fast._CHUNK, fast._TAIL_CHUNK = len(bodies) - 1, 1
        place = batcher._place
        place.take()
        try:
            entries = [batcher.enqueue(json.dumps(b).encode()) for b in bodies]
        finally:
            place.free()
        return [repr(batcher.wait_entry(e, timeout=30)) for e in entries]

    def stop(self):
        self.server.stop()
        return [t for b in self.batchers for t in b._threads if t.is_alive()]


def session(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _traced(tmp_path, ask):
    """``ask()`` once with no session (the warm-up: it compiles), then
    again inside one; the server stopped after ``stop_trace``."""
    served = Served()
    try:
        plain = ask(served)
        compiles = _COMPILES[0]
        session(tmp_path)
        try:
            traced = ask(served)
        finally:
            jax.profiler.stop_trace()
    finally:
        alive = served.stop()
    return plain, traced, _COMPILES[0] - compiles, alive


def _held_to_one_seq(doc, chunks: int) -> list:
    """The launched batches' seqs, each with its launches and waits on
    their own threads."""
    seqs = []
    for seq, rec in doc["by_seq"].items():
        launches = rec.get("cedar.dispatch.launch", [])
        if not launches:
            continue
        waits = rec.get("cedar.decode.device_wait", [])
        assert len(launches) == len(waits) == chunks, (seq, rec)
        launch_threads = {t for t, _ in launches}
        wait_threads = {t for t, _ in waits}
        assert len(launch_threads) == len(wait_threads) == 1
        assert launch_threads != wait_threads
        # and the batch's stages carry the same seq on their threads
        assert {t for t, _ in rec["cedar.batch.dispatch"]} == launch_threads
        assert {t for t, _ in rec["cedar.batch.decode"]} == wait_threads
        seqs.append(seq)
    return seqs


@needs_native
@pytest.mark.parametrize("path,case", sorted(CASES), ids=lambda v: v)
def test_a_traced_case_answers_as_untraced_and_its_round_trip_joins_by_seq(
        tmp_path, path, case):
    bodies = CASES[path, case]
    flagged = _counter(metrics.flagged_bits_total, path=path, by="readback")
    plain, traced, compiles, alive = _traced(
        tmp_path, lambda served: served.ask(path, bodies))
    assert traced == plain
    # a flagged row names several policies: its bits rode the readback,
    # once untraced and once traced
    assert _counter(metrics.flagged_bits_total, path=path, by="readback") - flagged == (
        2 * len(bodies) if case == "flagged" else 0)
    assert compiles == 0
    assert alive == []
    dumped = xplane.dump(tmp_path)
    launched = xplane.host_launch_intervals(dumped)
    doc = launch_anatomy(tmp_path)
    if case == "gate":
        # answered at the encoder's gate: a batch, a dispatch stage, no launch
        assert doc["batches"]["no_launch"] == len(bodies)
        assert doc["batches"]["launched"] == 0
        assert launched == []
        return
    assert launched
    assert doc["batches"] == {"launched": len(bodies), "no_launch": 0,
                              "chunked": 0, "cut_by_window": 0}
    assert doc["launch_and_wait_on_own_threads"] is True
    assert len(_held_to_one_seq(doc, chunks=1)) == len(bodies)
    # the call inside each launch: its host arguments, and the runtime's
    # execute inside it
    assert doc["uploads_per_launch"] >= 3 and doc["upload_bytes_per_launch"] > 0
    assert any("Execute" in name for name in doc["in_call_by_name"])
    terms = doc["medians_ms"]
    assert terms["execute"] > 0 and terms["uploads"] > 0
    # the launch's terms add up to the launch, batch by batch
    for row in doc["examples"]:
        assert sum(row[t] for t in LAUNCH_TERMS) == pytest.approx(row["launch"], abs=1e-6)
    # a CPU trace has no device plane: no device term, the launch covered
    assert doc["clock_offset_ms"] is None and terms["device_run"] is None
    assert doc["covered_share"] > 0


@needs_native
@pytest.mark.parametrize("path", ["authorization", "admission"])
def test_a_batch_of_two_chunks_carries_one_seq_and_a_chunk_a_launch(tmp_path, path):
    bodies = CASES[path, "clean"]
    plain, traced, compiles, alive = _traced(
        tmp_path, lambda served: served.batch_of_two_chunks(path, bodies))
    assert traced == plain
    assert compiles == 0
    assert alive == []
    assert xplane.host_launch_intervals(xplane.dump(tmp_path))
    doc = launch_anatomy(tmp_path)
    assert doc["batches"]["chunked"] == 1 and doc["batches"]["launched"] == 0
    (seq,) = _held_to_one_seq(doc, chunks=2)
    # the launches of one batch are told apart by their chunk
    events = [e for e in trace_events(tmp_path) if e[0] == "cedar.dispatch.launch"]
    assert sorted(stats["chunk"] for _, stats in events) == [0, 1]
    assert {stats["seq"] for _, stats in events} == {seq}


def trace_events(trace_dir) -> list:
    """[(name, stats)] of the cedar.* host events of a session's dump."""
    path = xplane.find_xplane(trace_dir)
    return [(e.name, dict(e.stats))
            for plane in jax.profiler.ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("cedar.")]


@needs_native
def test_with_no_session_nothing_is_built_and_with_one_the_call_is_annotated(
        monkeypatch, tmp_path):
    """No session: sub_stage and profiler_scope are the shared no-op and no
    TraceAnnotation is built, on the whole served path; a session builds
    the call's annotation with its uploads."""
    built = []
    real = trace._annotation_cls()

    class Spy(real):
        def __init__(self, name, **kwargs):
            built.append((name, kwargs))
            super().__init__(name, **kwargs)

    monkeypatch.setattr(trace, "_ANNOTATION", Spy)
    assert profiler_scope("cedar.dispatch.call", uploads=1) is trace._NULL_CTX
    assert sub_stage("dispatch.launch") is trace._NULL_CTX
    times = _StageTimes(time.monotonic(), False, 7)
    with batch_stage(times, "dispatch", 1):
        assert type(sub_stage("dispatch.launch")) is trace._SubStage
    served = Served()
    try:
        served.ask("authorization", CASES["authorization", "clean"])
        assert built == []
        session(tmp_path)
        try:
            served.ask("authorization", CASES["authorization", "clean"][:1])
        finally:
            jax.profiler.stop_trace()
    finally:
        served.stop()
    names = [n for n, _ in built]
    assert "cedar.dispatch.call" in names
    call = dict(built)["cedar.dispatch.call"]
    assert set(call) == {"uploads", "upload_bytes"}
    launch = dict(built)["cedar.dispatch.launch"]
    assert set(launch) == {"batch", "seq", "chunk"} and launch["chunk"] == 0


def test_a_launch_notes_its_host_arguments_on_the_bound_batch():
    """aot.dispatch counts what is not on the device: host arrays and
    numpy scalars, with their bytes; device arrays and statics are not."""
    codes = np.zeros((4, 3), np.uint8)
    extras = np.zeros((4, 8), np.int16)
    on_device = jax.numpy.zeros((16,), np.int32)
    args = (codes, extras, on_device, 2, False, np.int32(4), None)
    times = _StageTimes(time.monotonic(), False, 1)
    with batch_stage(times, "dispatch", 4):
        out = aot.dispatch("test", lambda *a: "ran", args, ())
        aot.dispatch("test", lambda *a: "ran", (codes,), ())
    assert out == "ran"
    assert (times.launches, times.uploads) == (2, 4)
    assert times.upload_bytes == 2 * codes.nbytes + extras.nbytes + 4
    # outside a batch stage nothing is bound and nothing is noted
    aot.dispatch("test", lambda *a: None, args, ())
    assert times.launches == 2


def _counter(c, **labels) -> float:
    key = tuple((k, labels.get(k, "")) for k in c.label_names)
    with c._lock:
        return c._values.get(key, 0.0)


@pytest.fixture()
def recorder():
    b = MicroBatcher(lambda items: items, metrics_path="authorization")
    yield b
    b.stop()


def test_the_launch_counters_add_a_batchs_tallies_once(recorder):
    families = (metrics.launch_uploads_total, metrics.launch_upload_bytes_total,
                metrics.launch_readback_bytes_total)
    before = [_counter(c, path="authorization") for c in families]
    times = _StageTimes(time.monotonic(), False, 3)
    times.launches, times.uploads, times.upload_bytes = 2, 7, 1234
    times.readback_bytes = 4096
    recorder._record_batch_stages(times)
    after = [_counter(c, path="authorization") for c in families]
    assert [a - b for a, b in zip(after, before)] == [7, 1234, 4096]
    # a batch that launched nothing (every row at the encoder's gate)
    recorder._record_batch_stages(_StageTimes(time.monotonic(), False, 4))
    assert [_counter(c, path="authorization") for c in families] == after


@needs_native
@pytest.mark.parametrize("path", ["authorization", "admission"])
def test_the_launch_counters_read_a_served_batchs_arguments(path):
    families = (metrics.launch_uploads_total, metrics.launch_upload_bytes_total,
                metrics.launch_readback_bytes_total)
    served = Served()
    try:
        served.ask(path, CASES[path, "clean"][:1])
        before = [_counter(c, path=path) for c in families]
        served.ask(path, CASES[path, "clean"])
        served.ask(path, CASES[path, "gate"])
        after = [_counter(c, path=path) for c in families]
    finally:
        served.stop()
    uploads, upload_bytes, readback = (a - b for a, b in zip(after, before))
    # three lone launches: the codes (one or two wire arrays), the extras
    # and the valid-row count go up; the gate's rows launch nothing
    assert uploads in (9, 12)
    assert upload_bytes > 3 * 4
    # one uint32 buffer home a launch: the words, then the compaction
    assert readback > 0 and readback % (3 * 4) == 0


@pytest.mark.parametrize("wait_s,counted", [(0.1, False), (0.25, True)])
def test_a_long_device_wait_is_counted_off_without_a_session(
        recorder, caplog, wait_s, counted):
    before = _counter(metrics.long_device_waits_total,
                      path="authorization", profiler="off")
    times = _StageTimes(time.monotonic(), False, 41)
    times.rows = 1
    times.sub["decode.device_wait"] = wait_s
    with caplog.at_level(logging.WARNING, logger=batcher_mod.log.name):
        recorder._record_batch_stages(times)
    after = _counter(metrics.long_device_waits_total,
                     path="authorization", profiler="off")
    assert after - before == (1 if counted else 0)
    lines = [r.getMessage() for r in caplog.records if "long device wait" in r.getMessage()]
    if counted:
        assert lines == ["long device wait: batch seq=41 (1 rows) waited 0.250 s "
                         "for its result; profiler off"]
    else:
        assert lines == []


def test_a_long_device_wait_in_a_session_is_counted_on(recorder, tmp_path):
    before = _counter(metrics.long_device_waits_total,
                      path="authorization", profiler="on")
    times = _StageTimes(time.monotonic(), False, 42)
    times.sub["decode.device_wait"] = 1.5
    session(tmp_path)
    try:
        assert trace.profiler_on()
        recorder._record_batch_stages(times)
    finally:
        jax.profiler.stop_trace()
    assert not trace.profiler_on()
    assert _counter(metrics.long_device_waits_total,
                    path="authorization", profiler="on") - before == 1


def test_a_stall_says_whether_a_profiler_session_was_open(tmp_path):
    rec = StallRecorder()
    counters = (0.0, 0.0, 0, 0)
    rec._record(0.0, 0.3, counters, (0.29, 0.0, 0, 0), [])
    session(tmp_path)
    try:
        rec._record(1.0, 1.2, counters, (0.0, 0.0, 0, 0), [])
    finally:
        jax.profiler.stop_trace()
    newest, oldest = rec.status()["stalls"]
    assert (oldest["cause"], oldest["profiler"]) == ("interpreter_held", "off")
    assert (newest["cause"], newest["profiler"]) == ("descheduled", "on")


@needs_native
def test_a_kept_trace_names_its_batch_by_the_profiles_seq():
    served = Served()
    try:
        served.ask("authorization", CASES["authorization", "clean"])
        tracer = served.tracer
        deadline = time.monotonic() + 5
        while len(tracer.list_traces()) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)  # a trace is kept once its reply is flushed
        docs = [tracer.get(t["traceId"]) for t in tracer.list_traces()]
    finally:
        served.stop()
    seqs = []
    for doc in docs:
        spans = {sp["name"]: sp for sp in doc["spans"]}
        attrs = spans["batch.dispatch"]["attrs"]
        assert {"launch_us", "seq"} <= set(attrs)
        seqs.append(attrs["seq"])
    # one lone caller: a batch a request, numbered as claimed
    assert len(set(seqs)) == len(seqs) == 3
    assert sorted(seqs) == list(range(min(seqs), min(seqs) + 3))


@pytest.mark.parametrize("shift", [0, 1, -1])
def test_the_device_clock_is_paired_in_launch_order_and_bounded(shift):
    """The k-th execute pairs with the k-th module run (or a few runs off,
    where the trace's edges cut one side): the offset is bounded by the
    device starting after the execute began and ending before the host
    learned that it did."""
    from tools.xplane_stages import _clock_offset

    delta = -1_400_000  # device time = host time + delta (ns)
    rng = np.random.default_rng(5)  # a lone caller's batches: 3-5 ms apart
    execs = np.cumsum(rng.integers(3_000_000, 5_000_000, 12)).tolist()
    runs = [("m", x + delta + 50_000, x + delta + 120_000) for x in execs]
    dones = [x + 300_000 for x in execs]
    modules = (runs[1:] if shift > 0 else
               [("m", -9_000_000, -8_900_000)] + runs if shift < 0 else runs)
    pairs, got, (lo, hi) = _clock_offset(execs, modules, dones)
    assert all(modules[m][1] - execs[k] == delta + 50_000 for k, m in pairs.items())
    assert lo <= delta <= hi and lo <= got <= hi
    assert (lo, hi) == (delta + 120_000 - 300_000, delta + 50_000)
