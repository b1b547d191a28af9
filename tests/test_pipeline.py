"""Pipelined-evaluation tests (ISSUE 4, docs/performance.md).

The PipelinedBatcher splits the serial batch loop into encode / dispatch /
decode stages running on separate threads. Everything riding on it is
pinned here:

  * differential identity — >= 1k mixed SAR + AdmissionReview bodies
    produce BYTE-identical responses through the pipelined batcher and the
    serial fast-path entry points, including across a decision-inverting
    policy reload;
  * warmup() — after TPUPolicyEngine.warmup, a request at ANY batch bucket
    triggers zero new jit traces (ops.match.kernel_trace_count);
  * resilience semantics survive the move to three stages: per-waiter
    deadline withdrawal, breaker trips degrading to interpreter-fallback
    RESULTS (never errors), and drain-on-stop leaving no slot unset;
  * /debug/engine + the occupancy/stall metrics.
"""

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from cedar_tpu.engine.batcher import (
    DeadlineExceeded,
    MicroBatcher,
    PipelinedBatcher,
)
from cedar_tpu.engine.evaluator import EXTRAS_WIDTHS, TPUPolicyEngine
from cedar_tpu.lang import PolicySet
from cedar_tpu.native import native_available
from cedar_tpu.ops.match import kernel_trace_count
from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
from cedar_tpu.server.http import sar_response
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

needs_native = pytest.mark.skipif(
    not native_available(), reason="no C++ toolchain for the native encoder"
)

SAR_POLICIES = """
permit (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "sam" && resource.resource == "pods" };
permit (principal in k8s::Group::"viewers", action == k8s::Action::"get",
        resource is k8s::Resource)
  when { resource.resource == "pods" };
forbid (principal, action, resource is k8s::Resource)
  when { resource.resource == "nodes" };
permit (principal, action in [k8s::Action::"list", k8s::Action::"watch"],
        resource is k8s::Resource)
  when { resource has labelSelector &&
         resource.labelSelector.contains({key: "owner", operator: "=",
                                          values: ["team-a"]}) };
"""

# a hard literal outside every native class: its scope packs as a gate
# rule, and matching rows re-route through the exact Python path — the
# differential must cover the gated lane too
GATED_POLICY = """
forbid (principal, action == k8s::Action::"deletecollection",
        resource is k8s::Resource)
  when { resource has name && ip(resource.name).isLoopback() };
"""

# the reload flips pods-get for sam from permit to forbid: a decision
# inversion the post-reload differential must observe on both paths
SAR_POLICIES_RELOADED = """
forbid (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "sam" && resource.resource == "pods" };
permit (principal, action, resource is k8s::Resource)
  when { resource.resource == "services" };
"""

ADM_POLICIES = """
forbid (principal is k8s::User,
        action == k8s::admission::Action::"create",
        resource is core::v1::ConfigMap)
  when { resource.metadata has labels &&
         resource.metadata.labels.contains({key: "env", value: "prod"}) };
"""


def _sar_body(i: int) -> bytes:
    """Mixed SAR stream: clean allow/deny/no-opinion rows, multi-match rows
    (sam in viewers getting pods), selector extras, encoder gates
    (system users), gated rows (loopback deletecollection), and parse
    errors."""
    k = i % 11
    if k == 9:
        return b'{"not json' + str(i).encode()
    user, groups = f"user-{i % 7}", []
    verb, resource, name = "get", "pods", ""
    sel = None
    if k == 0:
        user = "sam"
    elif k == 1:
        user, groups = "sam", ["viewers"]  # two permits match: multi row
    elif k == 2:
        groups = ["viewers"]
    elif k == 3:
        resource = "nodes"  # forbid
    elif k == 4:
        verb, resource = "list", "secrets"
        sel = {
            "requirements": [
                {"key": "owner", "operator": "In", "values": ["team-a"]}
            ]
        }
    elif k == 5:
        user = "system:kube-scheduler"  # encoder gate: system skip
    elif k == 6:
        verb, resource, name = "deletecollection", "pods", "127.0.0.1"  # gated
    elif k == 7:
        verb, resource, name = "deletecollection", "pods", "box-7"  # gate scope
    ra = {
        "verb": verb,
        "version": "v1",
        "resource": resource,
        "namespace": f"ns-{i % 5}",
    }
    if name:
        ra["name"] = name
    if sel:
        ra["labelSelector"] = sel
    return json.dumps(
        {
            "apiVersion": "authorization.k8s.io/v1",
            "kind": "SubjectAccessReview",
            "spec": {
                "user": user,
                "uid": "u",
                "groups": groups,
                "resourceAttributes": ra,
            },
        }
    ).encode()


def _adm_body(i: int) -> bytes:
    k = i % 7
    if k == 6:
        return b'{"broken' + str(i).encode()
    ns = "kube-system" if k == 5 else "default"  # ns-skip lane
    labels = {"env": "prod"} if k % 2 else {"env": "dev"}
    return json.dumps(
        {
            "request": {
                "uid": f"adm-{i}",
                "operation": "CREATE",
                "userInfo": {"username": "bob", "groups": ["tenants"]},
                "kind": {"group": "", "version": "v1", "kind": "ConfigMap"},
                "resource": {
                    "group": "",
                    "version": "v1",
                    "resource": "configmaps",
                },
                "namespace": ns,
                "object": {
                    "apiVersion": "v1",
                    "kind": "ConfigMap",
                    "metadata": {
                        "name": f"cm-{i}",
                        "namespace": ns,
                        "labels": labels,
                    },
                    "data": {"k": "v"},
                },
            }
        }
    ).encode()


def _sar_stack(src, breaker=None, evaluate_engine=True):
    from cedar_tpu.engine.fastpath import SARFastPath

    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(src, "pipe")], warm="off")
    stores = TieredPolicyStores([MemoryStore.from_source("pipe", src)])
    authorizer = CedarWebhookAuthorizer(
        stores, evaluate=engine.evaluate if evaluate_engine else None
    )
    fast = SARFastPath(engine, authorizer, breaker=breaker)
    return engine, stores, authorizer, fast


def _adm_stack(src):
    from cedar_tpu.engine.fastpath import AdmissionFastPath
    from cedar_tpu.server.admission import (
        ALLOW_ALL_ADMISSION_POLICY_SOURCE,
        CedarAdmissionHandler,
        allow_all_admission_policy_store,
    )

    engine = TPUPolicyEngine()
    engine.load(
        [
            PolicySet.from_source(src, "pipe"),
            PolicySet.from_source(ALLOW_ALL_ADMISSION_POLICY_SOURCE, "aa"),
        ],
        warm="off",
    )
    handler = CedarAdmissionHandler(
        TieredPolicyStores(
            [
                MemoryStore.from_source("pipe", src),
                allow_all_admission_policy_store(),
            ]
        ),
        evaluate=engine.evaluate,
        evaluate_batch=engine.evaluate_batch,
    )
    fast = AdmissionFastPath(engine, handler)
    return engine, handler, fast


def _submit_all(batcher, bodies, timeout=60.0, workers=32):
    with ThreadPoolExecutor(workers) as pool:
        return list(
            pool.map(lambda b: batcher.submit(b, timeout=timeout), bodies)
        )


def _sar_bytes(results):
    return [
        json.dumps(sar_response(*r), sort_keys=True).encode() for r in results
    ]


def _adm_bytes(results):
    return [
        json.dumps(r.to_admission_review(), sort_keys=True).encode()
        for r in results
    ]


@needs_native
class TestPipelinedDifferential:
    def test_sar_differential_1k_with_reload(self):
        """>= 1k mixed SAR bodies: pipelined == serial byte-for-byte, on
        the initial policy set AND after a decision-inverting reload."""
        engine, _stores, _auth, fast = _sar_stack(
            SAR_POLICIES + GATED_POLICY
        )
        bodies = [_sar_body(i) for i in range(700)]
        serial = _sar_bytes(fast.authorize_raw(bodies))
        # small max_batch forces many batches through the pipeline so the
        # differential crosses batch boundaries, not one giant batch
        batcher = PipelinedBatcher(
            fast, max_batch=128, window_s=0.0002, depth=2
        )
        try:
            piped = _sar_bytes(_submit_all(batcher, bodies))
            assert piped == serial
            # decision-inverting hot swap: both paths must flip together
            engine.load(
                [PolicySet.from_source(SAR_POLICIES_RELOADED, "pipe2")],
                warm="off",
            )
            serial2 = _sar_bytes(fast.authorize_raw(bodies))
            piped2 = _sar_bytes(_submit_all(batcher, bodies))
            assert piped2 == serial2
            assert serial2 != serial  # the reload really inverted decisions
        finally:
            batcher.stop()

    def test_admission_differential_with_pipeline(self):
        _engine, _handler, fast = _adm_stack(ADM_POLICIES)
        bodies = [_adm_body(i) for i in range(400)]
        serial = _adm_bytes(fast.handle_raw(bodies))
        batcher = PipelinedBatcher(
            fast, max_batch=64, window_s=0.0002, depth=2
        )
        try:
            piped = _adm_bytes(_submit_all(batcher, bodies))
            assert piped == serial
        finally:
            batcher.stop()


class TestWarmup:
    def test_warmup_compiles_every_bucket_plane(self):
        """After warmup(), a first request at ANY batch bucket (either
        common extras width) triggers zero new jit traces — the compile
        counter in ops/match.py is the proof, not wall-clock."""
        src = """
permit (principal, action == k8s::Action::"get", resource is k8s::Resource)
  when { resource.resource == "pods" };
"""
        engine = TPUPolicyEngine()
        engine.load([PolicySet.from_source(src, "warm")], warm="off")
        report = engine.warmup(max_batch=128)
        assert report["shapes"] > 0
        assert report["seconds"] >= 0
        cs = engine._compiled
        n_slots = cs.packed.table.n_slots
        L = cs.packed.L
        tc0 = kernel_trace_count()
        for b in (1, 3, 8, 17, 32, 100, 128):
            # every native-fastpath extras width: selector-heavy and
            # group-heavy traffic must be as trace-free as no-extras
            for E in EXTRAS_WIDTHS:
                codes = np.zeros((b, n_slots), dtype=cs.code_dtype)
                extras = np.full((b, E), L, dtype=cs.active_dtype)
                engine.match_arrays(codes, extras, cs=cs)
                engine.match_arrays(codes, extras, cs=cs, want_bits=True)
        assert kernel_trace_count() == tc0, (
            "a post-warmup request at a warmed bucket traced a new kernel"
        )
        # a second warmup finds everything compiled: zero fresh traces
        assert engine.warmup(max_batch=128)["traces"] == 0

    def test_warmup_requires_loaded_set(self):
        with pytest.raises(RuntimeError):
            TPUPolicyEngine().warmup()


# ---------------------------------------------------- one result buffer

FLAG_POLICIES = """
permit (principal, action, resource) when { principal.name == "sam" };
permit (principal, action, resource) when { resource.resource == "pods" };
forbid (principal, action, resource) when { resource.resource == "nodes" };
"""


def _flag_rows(engine, kinds):
    """Python-encoded feature rows: "flag" (sam gets pods: two permits
    decide, the word's multi bit), "clean" (one permit), "none"."""
    from cedar_tpu.compiler.table import encode_request_codes
    from cedar_tpu.entities.attributes import Attributes, UserInfo
    from cedar_tpu.server.authorizer import record_to_cedar_resource

    what = {"flag": ("sam", "pods"), "clean": ("bob", "pods"),
            "none": ("bob", "secrets")}
    cs = engine._compiled
    encoded = [
        encode_request_codes(
            cs.packed.plan, cs.packed.table,
            *record_to_cedar_resource(Attributes(
                user=UserInfo(name=what[k][0], uid="u"), verb="get",
                resource=what[k][1], api_version="v1", resource_request=True,
            )),
        )
        for k in kinds
    ]
    return engine._encode_batch_arrays(cs, encoded, len(encoded))


@pytest.fixture
def device_calls(monkeypatch):
    """Every transfer a device array starts and every kernel the engine
    dispatches, in order, as (what, phase): the test sets the phase."""
    import jax.numpy as jnp

    from cedar_tpu.engine import evaluator

    log = {"phase": "launch", "calls": []}
    array_type = type(jnp.zeros((1,)))
    start_copy = array_type.copy_to_host_async
    dispatch = evaluator.aot.dispatch

    def counted_copy(self):
        log["calls"].append(("copy", log["phase"]))
        return start_copy(self)

    def counted_dispatch(name, *args):
        log["calls"].append((name, log["phase"]))
        return dispatch(name, *args)

    monkeypatch.setattr(array_type, "copy_to_host_async", counted_copy)
    monkeypatch.setattr(evaluator.aot, "dispatch", counted_dispatch)
    return log


class TestOneResultBuffer:
    """A want_bits launch brings words and flagged rows' bitsets home in
    ONE buffer: one transfer, started at launch; finish() touches the
    device no more (ISSUE 37)."""

    @pytest.fixture(scope="class")
    def engine(self):
        engine = TPUPolicyEngine()
        engine.load([PolicySet.from_source(FLAG_POLICIES, "flag")], warm="off")
        return engine

    # (the rows, valid_rows): a flagged one-row batch; a mixed 32-row
    # batch; the same staged as the fast paths stage it, its last three
    # rows bucket padding that WOULD be flagged were they counted
    CASES = {
        "one_flagged_row": (["flag"], None),
        "mixed_32": ((["flag", "clean", "none", "clean"] * 8), None),
        "mixed_32_staged_29_valid": (
            (["flag", "clean", "none", "clean"] * 8)[:29] + ["flag"] * 3, 29
        ),
    }

    @pytest.mark.parametrize("case", CASES)
    def test_one_transfer_at_launch_and_none_in_finish(
        self, engine, device_calls, case
    ):
        from cedar_tpu.ops.match import WORD_MULTI

        kinds, valid = self.CASES[case]
        codes, extras = _flag_rows(engine, kinds)
        cs = engine._compiled
        fin = engine.match_arrays_launch(
            codes, extras, cs=cs, want_bits=True, valid_rows=valid
        )
        at_launch = list(device_calls["calls"])
        device_calls["phase"] = "finish"
        words, full, bitmap = fin()
        assert [c for c in at_launch if c[0] == "copy"] == [("copy", "launch")]
        assert len(at_launch) == 2  # the one kernel, the one transfer
        assert device_calls["calls"] == at_launch  # finish(): nothing
        assert full is None and words.shape == (len(kinds),)
        n = len(kinds) if valid is None else valid
        want = [i for i, k in enumerate(kinds[:n]) if k == "flag"]
        assert sorted(bitmap) == want  # every flagged row, no padding row
        multi = (words.astype(np.uint32) & WORD_MULTI) != 0
        assert np.nonzero(multi)[0].tolist() == [
            i for i, k in enumerate(kinds) if k == "flag"
        ]
        device_calls["phase"] = "reference"
        ref_words, _ = engine.match_arrays(codes, extras, cs=cs)
        assert (words == ref_words).all()
        ref_bits = engine.match_bits_arrays(codes, extras, cs=cs)
        for i, row in bitmap.items():
            assert row.dtype == np.uint32 and (row == ref_bits[i]).all()

    def test_want_full_keeps_its_outputs_and_its_gated_fetch(
        self, engine, device_calls
    ):
        """want_full + want_bits (no served path): words, the two full
        matrices, and the compaction fetched by finish() only where a
        group matched two policies."""
        cs = engine._compiled
        for kinds, fetched in ((["clean", "none"], 0), (["flag", "clean"], 3)):
            del device_calls["calls"][:]
            device_calls["phase"] = "launch"
            codes, extras = _flag_rows(engine, kinds)
            fin = engine.match_arrays_launch(
                codes, extras, cs=cs, want_full=True, want_bits=True
            )
            device_calls["phase"] = "finish"
            words, (first, last), bitmap = fin()
            calls = device_calls["calls"]
            assert calls.count(("copy", "launch")) == 3
            assert calls.count(("copy", "finish")) == fetched
            assert sorted(bitmap) == ([0] if fetched else [])
            assert first.shape == last.shape and first.shape[0] == len(kinds)


def _flagged_bits() -> dict:
    from cedar_tpu.server import metrics

    with metrics.flagged_bits_total._lock:
        return {
            by: metrics.flagged_bits_total._values.get(
                (("path", "authorization"), ("by", by)), 0.0
            )
            for by in ("readback", "word_cache", "second_call")
        }


def _multi_sar(i: int, ns: str) -> bytes:
    """sam, in viewers, gets pods: two permits of SAR_POLICIES decide."""
    return json.dumps({
        "apiVersion": "authorization.k8s.io/v1",
        "kind": "SubjectAccessReview",
        "spec": {
            "user": "sam", "uid": "u", "groups": ["viewers"],
            "resourceAttributes": {
                "verb": "get", "version": "v1", "resource": "pods",
                "namespace": ns, "name": f"pod-{i}",
            },
        },
    }).encode()


@needs_native
def test_the_counter_says_how_each_flagged_rows_bits_came_home():
    """cedar_flagged_bits_total{path,by}: `readback` for the rows the
    launch's buffer carried, `second_call` for the rows past the 128 it
    holds, `word_cache` for such a row whose feature bytes were resolved
    before; a batch with no flagged row counts nothing."""
    from cedar_tpu.ops.match import BITS_TOPK

    _engine, _stores, _auth, fast = _sar_stack(SAR_POLICIES)
    clean = [_sar_body(i * 11 + 3) for i in range(5)]  # forbids on nodes
    before = _flagged_bits()
    fast.authorize_raw(clean)
    assert _flagged_bits() == before

    def moved():
        now = _flagged_bits()
        return {by: int(now[by] - before[by]) for by in now}

    # a lone flagged request, then a mixed small batch: all readback
    (res,) = fast.authorize_raw([_multi_sar(0, "solo")])
    assert res[0] == "allow" and len(json.loads(res[1])["reasons"]) == 2
    assert moved() == {"readback": 1, "word_cache": 0, "second_call": 0}
    fast.authorize_raw([_multi_sar(i, "small") for i in range(3)] + clean)
    assert moved() == {"readback": 4, "word_cache": 0, "second_call": 0}

    # more flagged rows than a launch's buffer holds: the two past it
    # (one feature row, so one standalone fetch) are second_call rows
    over = [_multi_sar(0, "over")] * (BITS_TOPK + 2)
    first = fast.authorize_raw(over)
    assert moved() == {
        "readback": 4 + BITS_TOPK, "word_cache": 0, "second_call": 2
    }
    # the same batch again: the overflow rows' feature bytes are in the
    # word cache now
    again = fast.authorize_raw(over)
    assert moved() == {
        "readback": 4 + 2 * BITS_TOPK, "word_cache": 2, "second_call": 2
    }
    assert _sar_bytes(first) == _sar_bytes(again)
    assert len({b for b in _sar_bytes(first)}) == 1


class _StubStages:
    """Controllable stages for batcher-semantics tests: encode tags, the
    dispatch stage sleeps (simulating in-flight device work), decode
    doubles each item."""

    def __init__(self, dispatch_sleep_s=0.0, decode_sleep_s=0.0):
        self.dispatch_sleep_s = dispatch_sleep_s
        self.decode_sleep_s = decode_sleep_s
        self.encoded_batches = []

    def pipeline_encode(self, items):
        self.encoded_batches.append(list(items))
        return list(items)

    def pipeline_dispatch(self, ctx):
        if self.dispatch_sleep_s:
            time.sleep(self.dispatch_sleep_s)
        return ctx

    def pipeline_decode(self, ctx):
        if self.decode_sleep_s:
            time.sleep(self.decode_sleep_s)
        return [x * 2 for x in ctx]


class TestPipelinedBatcherSemantics:
    def test_results_roundtrip_and_debug_stats(self):
        stages = _StubStages()
        b = PipelinedBatcher(stages, max_batch=16, window_s=0.0002, depth=2)
        try:
            assert _submit_all(b, list(range(50)), workers=8) == [
                2 * i for i in range(50)
            ]
            stats = b.debug_stats()
            assert stats["mode"] == "pipelined"
            assert stats["depth"] == 2
            assert stats["batches_total"] >= 1
            assert set(stats["stall_seconds"]) == {
                "collect",
                "dispatch",
                "decode",
            }
        finally:
            b.stop()

    def test_deadline_withdrawal_under_pipelining(self):
        """A submitter's budget expiring while its batch is stuck behind
        slow device work raises DeadlineExceeded without wedging the
        pipeline; per-waiter coalesce accounting survives too — a
        timed-out follower never cancels the leader's shared slot."""
        stages = _StubStages(dispatch_sleep_s=0.25)
        b = PipelinedBatcher(stages, max_batch=8, window_s=0.0002, depth=1)
        try:
            with pytest.raises(DeadlineExceeded):
                b.submit("late", timeout=0.03)
            # the withdrawn-or-evaluated item must not corrupt later work
            assert b.submit("ok", timeout=5.0) == "okok"

            leader_out = {}

            def leader():
                leader_out["r"] = b.submit("co", timeout=5.0, coalesce_key="k")

            t = threading.Thread(target=leader)
            t.start()
            time.sleep(0.01)  # leader enqueued (or already claimed)
            try:
                # follower with an instantly-expiring budget: must raise,
                # must NOT withdraw the leader's slot
                b.submit("co", timeout=0.0, coalesce_key="k")
            except DeadlineExceeded:
                pass
            t.join(timeout=10)
            assert leader_out["r"] == "coco"
        finally:
            b.stop()

    def test_drain_no_slot_left_unset(self):
        """stop() mid-pipeline drains every accepted item through all
        three stages: no submitter hangs, every slot is set."""
        stages = _StubStages(dispatch_sleep_s=0.02)
        b = PipelinedBatcher(stages, max_batch=4, window_s=0.0002, depth=2)
        results = []
        errors = []

        def one(i):
            try:
                results.append((i, b.submit(i, timeout=30)))
            except Exception as e:  # noqa: BLE001 — recorded for the assert
                errors.append((i, e))

        threads = [threading.Thread(target=one, args=(i,)) for i in range(40)]
        for t in threads:
            t.start()
        time.sleep(0.03)  # several batches in flight, several queued
        b.stop(drain_timeout_s=30)
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), "a submitter hung"
        # every item either completed with the right answer or was
        # EXPLICITLY rejected at submit time (post-stop arrival) — no slot
        # silently dropped, and no ACCEPTED waiter may read the
        # collector's drain-time exit as a dead batcher while the decode
        # stage is still delivering (PipelinedBatcher._alive)
        assert not errors or all(
            isinstance(e, RuntimeError) for _, e in errors
        )
        assert not any(
            "without delivering" in str(e) for _, e in errors
        ), f"accepted waiter errored during drain: {errors}"
        assert all(r == 2 * i for i, r in results)
        assert len(results) + len(errors) == 40

    def test_drain_with_slow_decode_outlives_liveness_poll(self):
        """The collector exits at the drain sentinel while decode is still
        working; a waiter whose liveness poll (0.5s) fires in that window
        must keep waiting for its result, not raise 'batcher dead'."""
        stages = _StubStages(decode_sleep_s=0.7)
        b = PipelinedBatcher(stages, max_batch=2, window_s=0.0002, depth=2)
        results = {}

        def one(i):
            results[i] = b.submit(i, timeout=30)

        threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)  # batches claimed, decode sleeping
        b.stop(drain_timeout_s=30)
        for t in threads:
            t.join(timeout=30)
        assert results == {i: 2 * i for i in range(4)}

    def test_stage_exception_fails_batch_without_killing_workers(self):
        class Boom(_StubStages):
            def pipeline_dispatch(self, ctx):
                if "boom" in ctx:
                    raise ValueError("stage bug")
                return ctx

        stages = Boom()
        b = PipelinedBatcher(stages, max_batch=4, window_s=0.0002)
        try:
            with pytest.raises(RuntimeError, match="batch evaluation failed"):
                b.submit("boom", timeout=5.0)
            # the pipeline survives and keeps serving
            assert b.submit("fine", timeout=5.0) == "finefine"
        finally:
            b.stop()


class _WatchedBatcher(PipelinedBatcher):
    """Records every claim on the collector's thread: its items, its
    shared stage record, and how many claimed batches already stood
    before the dispatch thread at that moment. A test that clears
    ``may_claim`` keeps the collector from coming to the queue again once
    it has delivered what it is waiting for now."""

    def __init__(self, *args, **kwargs):
        self.claims = []
        self.may_claim = threading.Event()
        self.may_claim.set()
        super().__init__(*args, **kwargs)

    def _form_batch(self, epoch=None):
        self.may_claim.wait(timeout=30)
        batch = super()._form_batch(epoch)
        if batch:
            self.claims.append(
                (
                    [it for it, _ in batch],
                    batch[0][1].times,
                    self._dispatch_q.qsize(),
                )
            )
        return batch


class _GatedStages(_StubStages):
    """The launch of the batch that holds ``gated`` waits for ``gate`` (a
    launch that does not return until the test says so); ``raise_in``
    names the stage that raises for a batch holding "boom"."""

    def __init__(self, raise_in=None, gated=None, **kwargs):
        super().__init__(**kwargs)
        self.gate = threading.Event()
        self.gate.set()
        self.gated = gated
        self.raise_in = raise_in
        self.dispatched = []
        self.encode_threads = []

    def pipeline_encode(self, items):
        self.encode_threads.append(threading.current_thread().name)
        if self.raise_in == "encode" and "boom" in items:
            raise ValueError("encode bug")
        if self.gated == "encode":
            self.gate.wait(timeout=30)
        return super().pipeline_encode(items)

    def pipeline_dispatch(self, ctx):
        if self.raise_in == "dispatch" and "boom" in ctx:
            raise ValueError("dispatch bug")
        if self.gated in ctx:
            self.gate.wait(timeout=30)
        self.dispatched.append(list(ctx))
        return super().pipeline_dispatch(ctx)


def _claims(path):
    """cedar_batch_claims_total{path, held} as (held yes, held no)."""
    from cedar_tpu.server.metrics import batch_claims_total

    with batch_claims_total._lock:
        values = dict(batch_claims_total._values)
    return tuple(
        values.get((("path", path), ("held", held)), 0.0)
        for held in ("yes", "no")
    )


def _lingers(path):
    """cedar_batch_lingers_total{path}."""
    from cedar_tpu.server.metrics import batch_lingers_total

    with batch_lingers_total._lock:
        return dict(batch_lingers_total._values).get((("path", path),), 0.0)


def _wait_for(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.002)
    return cond()


class TestLateClaim:
    """The late claim (PipelinedBatcher's docstring): one claimed batch
    stands before the dispatch thread, the rest of the backlog stays in
    the submit queue. The stage double's dispatch sleeps or waits for a
    gate; everything is asserted on counts and order, not on wall time."""

    CALLERS = 32

    def test_one_standing_batch_and_fuller_batches_under_closed_loop(self):
        rounds = 30
        stages = _StubStages(dispatch_sleep_s=0.004)
        b = _WatchedBatcher(
            stages, max_batch=64, window_s=0.0002, depth=2,
            metrics_path="late-claim-closed-loop",
        )
        wrong = []

        def caller(k):
            for i in range(rounds):
                item = 1000 * k + i
                if b.submit(item, timeout=60) != 2 * item:
                    wrong.append(item)

        threads = [
            threading.Thread(target=caller, args=(k,))
            for k in range(self.CALLERS)
        ]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads) and not wrong
        finally:
            b.stop()
        rows = [len(items) for items, _times, _standing in b.claims]
        assert sum(rows) == self.CALLERS * rounds
        # at every claim, no other claimed batch stood before the launch
        assert max(standing for _i, _t, standing in b.claims) == 0
        # the callers share the batch being launched and the backlog in
        # the submit queue: 21–22 rows a batch against this double (the
        # rule this replaced: five groups — launching, two in the
        # dispatch queue, one in the collector's hands, one queued — 6.4
        # rows each)
        assert sum(rows) / len(rows) >= 12
        held, free = _claims("late-claim-closed-loop")
        assert held + free == len(rows)
        # how many claims are held depends on how fast this double's
        # callers come back (all 32 can ride one launch and find the place
        # free); that some are is all that holds on any machine
        assert held >= 1
        assert b.debug_stats()["stall_seconds"]["collect"] > 0

    @pytest.mark.parametrize(
        "leaves_by", ["encode_raises", "dispatch_raises", "revive", "stop"]
    )
    def test_the_place_is_released_on_every_path_a_batch_leaves_it(
        self, leaves_by
    ):
        stages = _GatedStages(
            raise_in=leaves_by.split("_")[0]
            if leaves_by.endswith("_raises") else None,
            gated=0,
        )
        b = _WatchedBatcher(stages, max_batch=4, window_s=0.0002, depth=2)
        try:
            if leaves_by.endswith("_raises"):
                with pytest.raises(RuntimeError, match="evaluation failed"):
                    b.submit("boom", timeout=5.0)
            else:
                # a launch that does not return: its batch holds the
                # place, and the backlog is held in the submit queue
                stages.gate.clear()
                entries = [b.enqueue(0)]
                assert _wait_for(lambda: len(b.claims) == 1)
                entries += [b.enqueue(i) for i in (1, 2, 3)]
                time.sleep(0.02)
                assert len(b.claims) == 1 and b.queue_fill() == 3
                if leaves_by == "revive":
                    assert b.revive(force=True)
                    # the backlog was never claimed by the old stages: the
                    # fresh ones, with a place of their own, answer it
                    # while the old launch is still wedged (its own batch
                    # fails or completes whenever that call returns)
                    assert [
                        b.wait_entry(e, timeout=5.0) for e in entries[1:]
                    ] == [2, 4, 6]
                    stages.gate.set()
                else:
                    stopper = threading.Thread(
                        target=b.stop, kwargs={"drain_timeout_s": 30}
                    )
                    stopper.start()
                    time.sleep(0.02)
                    stages.gate.set()
                    stopper.join(timeout=30)
                    assert not stopper.is_alive()
                    assert [
                        b.wait_entry(e, timeout=5.0) for e in entries
                    ] == [0, 2, 4, 6]
                    assert not any(t.is_alive() for t in b._threads)
                    return
            for again in ("fine", "still"):
                assert b.submit(again, timeout=5.0) == 2 * again
        finally:
            stages.gate.set()
            b.stop()

    def test_a_deadline_behind_a_held_claim_withdraws_that_request_alone(self):
        stages = _GatedStages(gated="a0")
        stages.gate.clear()
        b = _WatchedBatcher(stages, max_batch=8, window_s=0.0002, depth=2)
        try:
            ahead = [b.enqueue("a0")]
            assert _wait_for(lambda: len(b.claims) == 1)
            ahead.append(b.enqueue("a1"))
            time.sleep(0.02)  # a1 waits for the place a0's launch holds
            before = b.enqueue("n0")
            late = b.enqueue("late")
            after = b.enqueue("n1")
            with pytest.raises(DeadlineExceeded):
                b.wait_entry(late, timeout=0.05)
            assert b.queue_fill() >= 2  # its neighbours are still queued
            stages.gate.set()
            assert [b.wait_entry(e, timeout=5.0) for e in ahead] == [
                "a0a0", "a1a1"]
            assert b.wait_entry(before, timeout=5.0) == "n0n0"
            assert b.wait_entry(after, timeout=5.0) == "n1n1"
            launched = [x for batch in stages.dispatched for x in batch]
            assert "late" not in launched
            assert sorted(launched) == ["a0", "a1", "n0", "n1"]
        finally:
            stages.gate.set()
            b.stop()

    def test_a_lone_submitter_is_claimed_at_once_and_never_held(self):
        # a window a timed wait cannot be mistaken for: every claim below
        # would take 50 ms if the collector slept on it
        window_s = 0.05
        b = _WatchedBatcher(
            _StubStages(), max_batch=8, window_s=window_s, depth=2,
            metrics_path="late-claim-lone",
        )
        try:
            for i in range(20):
                assert b.submit(i, timeout=5.0) == 2 * i
        finally:
            b.stop()
        assert [items for items, _t, _s in b.claims] == [[i] for i in range(20)]
        for _items, times, standing in b.claims:
            assert standing == 0
            assert not times.lingered
            assert times.claimed - times.first_enq < 0.5 * window_s
        assert _claims("late-claim-lone") == (0.0, 20.0)
        assert _lingers("late-claim-lone") == 0.0
        assert b.debug_stats()["stall_seconds"]["collect"] == 0

    def test_a_burst_that_has_begun_lingers_and_rides_one_claim(self):
        """Two or more entries waiting at an idle pipeline keep the forming
        window: what arrives inside it rides the same claim."""
        window_s = 0.2
        b = _WatchedBatcher(
            _StubStages(), max_batch=8, window_s=window_s, depth=2,
            metrics_path="late-claim-burst",
        )
        try:
            # the collector is kept from the queue until both entries are
            # in it, and the pipeline is idle again by then
            b.may_claim.clear()
            assert b.submit("first", timeout=5.0) == "firstfirst"
            assert _wait_for(lambda: b.debug_stats()["inflight"] == 0)
            entries = [b.enqueue(i) for i in (0, 1)]
            b.may_claim.set()
            time.sleep(window_s / 4)
            entries.append(b.enqueue(2))  # inside the window
            assert [b.wait_entry(e, timeout=5.0) for e in entries] == [0, 2, 4]
        finally:
            b.may_claim.set()
            b.stop()
        assert [items for items, _t, _s in b.claims] == [["first"], [0, 1, 2]]
        assert not b.claims[0][1].lingered
        times = b.claims[1][1]
        assert times.lingered
        assert times.claimed - times.first_enq >= 0.9 * window_s
        assert _lingers("late-claim-burst") == 1.0
        assert _claims("late-claim-burst") == (0.0, 2.0)

    def test_a_batch_in_flight_means_no_window(self):
        """While a launch is out, the backlog behind it is claimed the
        moment the place is free, however many entries wait."""
        window_s = 0.5
        stages = _GatedStages(gated="a0")
        stages.gate.clear()
        b = _WatchedBatcher(
            stages, max_batch=8, window_s=window_s, depth=2,
            metrics_path="late-claim-inflight",
        )
        try:
            first = b.enqueue("a0")
            assert _wait_for(lambda: len(b.claims) == 1)
            behind = [b.enqueue(x) for x in ("b0", "b1", "b2")]
            # hold the decode of a0 so it is still in flight at the claim
            stages.decode_sleep_s = 0.05
            stages.gate.set()
            t0 = time.monotonic()
            assert [b.wait_entry(e, timeout=5.0) for e in behind] == [
                "b0b0", "b1b1", "b2b2"]
            assert time.monotonic() - t0 < window_s
            assert b.wait_entry(first, timeout=5.0) == "a0a0"
        finally:
            stages.gate.set()
            b.stop()
        assert [items for items, _t, _s in b.claims] == [
            ["a0"], ["b0", "b1", "b2"]]
        assert not any(times.lingered for _i, times, _s in b.claims)
        assert _lingers("late-claim-inflight") == 0.0
        assert _claims("late-claim-inflight") == (1.0, 1.0)

    def test_the_encode_runs_on_the_thread_that_claimed(self):
        stages = _GatedStages()
        b = _WatchedBatcher(stages, max_batch=4, window_s=0.0002, depth=2)
        try:
            assert _submit_all(b, list(range(24)), workers=6) == [
                2 * i for i in range(24)]
            stats = b.debug_stats()
        finally:
            b.stop()
        assert set(stages.encode_threads) == {"pipe-collect"}
        assert len(stages.encode_threads) == len(b.claims)
        assert "encode_workers" not in stats
        # the dispatch thread's wait for the standing batch's encode
        assert stats["stall_seconds"]["dispatch"] > 0

    def test_an_encode_that_raises_fails_its_batch_alone(self):
        stages = _GatedStages(raise_in="encode")
        b = _WatchedBatcher(stages, max_batch=4, window_s=0.0002, depth=2)
        try:
            collector = b._thread
            with pytest.raises(RuntimeError, match="evaluation failed") as e:
                b.submit("boom", timeout=5.0)
            assert isinstance(e.value.__cause__, ValueError)
            # nothing counts as in flight, and the thread that ran the
            # encode serves the next submit (so the place was freed)
            assert b.debug_stats()["inflight"] == 0
            assert b.backlog() == 0
            assert b.submit("fine", timeout=5.0) == "finefine"
            assert b._thread is collector and collector.is_alive()
        finally:
            b.stop()

    def test_a_revive_during_the_encode_fails_that_batch_fast(self):
        """A collector superseded while it encodes (the supervisor's forced
        revive of a wedged stage) must not put its batch on a hand-off
        queue that no thread reads any more."""
        stages = _GatedStages(gated="encode")
        stages.gate.clear()
        b = _WatchedBatcher(stages, max_batch=4, window_s=0.0002, depth=2)
        try:
            wedged = b.enqueue("w")
            assert _wait_for(lambda: stages.encode_threads)
            assert b.revive(force=True)
            stages.gated = None
            assert b.submit("fresh", timeout=5.0) == "freshfresh"
            stages.gate.set()
            with pytest.raises(RuntimeError, match="restarted; batch shed"):
                b.wait_entry(wedged, timeout=5.0)
        finally:
            stages.gate.set()
            b.stop()

    def test_rows_leave_in_the_order_they_came_across_held_claims(self):
        stages = _GatedStages(dispatch_sleep_s=0.002)
        b = _WatchedBatcher(
            stages, max_batch=16, window_s=0.0002, depth=2,
            metrics_path="late-claim-fifo",
        )
        try:
            entries = []
            for i in range(240):
                entries.append(b.enqueue(i))
                if i % 7 == 6:
                    time.sleep(0.0005)
            assert [b.wait_entry(e, timeout=30) for e in entries] == [
                2 * i for i in range(240)]
        finally:
            b.stop()
        claimed = [x for items, _t, _s in b.claims for x in items]
        assert claimed == list(range(240))
        assert [x for batch in stages.dispatched for x in batch] == claimed
        assert _claims("late-claim-fifo")[0] >= 3


@needs_native
class TestBreakerUnderPipelining:
    def test_device_failure_degrades_then_trips_breaker(self):
        """A raising device plane feeds the breaker from the pipelined
        stages and answers from the interpreter fallback (RESULTS, not
        errors); once tripped, the encode stage routes batches directly to
        the fallback without touching the device."""
        from cedar_tpu.engine.breaker import OPEN, CircuitBreaker

        breaker = CircuitBreaker(
            name="pipe-test", failure_threshold=2, recovery_s=60.0
        )
        # authorizer WITHOUT the engine evaluate hook: the interpreter
        # fallback must keep answering while the device plane is sick
        engine, _stores, _auth, fast = _sar_stack(
            SAR_POLICIES, breaker=breaker, evaluate_engine=False
        )
        calls = {"n": 0}

        def boom(*a, **k):
            calls["n"] += 1
            raise RuntimeError("device wedged")

        engine.match_arrays_launch = boom  # type: ignore[method-assign]
        b = PipelinedBatcher(fast, max_batch=8, window_s=0.0002)
        try:
            body = _sar_body(0)  # sam gets pods: interpreter says Allow
            expected = json.dumps(
                sar_response(*fast._python_fallback(body)), sort_keys=True
            )
            for _ in range(3):
                got = json.dumps(
                    sar_response(*b.submit(body, timeout=30)), sort_keys=True
                )
                assert got == expected
            assert breaker.state == OPEN
            launches_when_open = calls["n"]
            for _ in range(3):
                b.submit(body, timeout=30)
            # open breaker: encode stage short-circuits, no device launches
            assert calls["n"] == launches_when_open
        finally:
            b.stop()


@needs_native
class TestDebugEngineEndpoint:
    def test_debug_engine_reports_pipeline_and_queue_fill(self):
        import urllib.request

        from cedar_tpu.server.http import WebhookServer
        from cedar_tpu.server.metrics import REGISTRY

        engine, _stores, _auth, fast = _sar_stack(SAR_POLICIES)
        _adm_engine, handler, adm_fast = _adm_stack(ADM_POLICIES)
        server = WebhookServer(
            authorizer=_auth,
            admission_handler=handler,
            address="127.0.0.1",
            port=0,
            metrics_port=0,
            fastpath=fast,
            admission_fastpath=adm_fast,
            pipeline_depth=2,
        )
        server.start()
        try:
            port = server.bound_port
            mport = server.bound_metrics_port
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/authorize",
                data=_sar_body(0),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
            with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/debug/engine", timeout=30
            ) as resp:
                doc = json.loads(resp.read())
            for path in ("authorization", "admission"):
                pipe = doc[path]["pipeline"]
                assert pipe["mode"] == "pipelined"
                assert pipe["depth"] == 2
                assert "encode_workers" not in pipe
                assert "dispatch_queue" in pipe and "decode_queue" in pipe
                assert "stall_seconds" in pipe
                eng = doc[path]["engine"]
                assert "load_generation" in eng and "warm_ready" in eng
            # the batch drove the occupancy histogram + stall counters
            exposition = REGISTRY.expose()
            assert "cedar_batch_occupancy_bucket" in exposition
            assert "cedar_pipeline_stall_seconds_total" in exposition
        finally:
            server.stop()


def test_webhook_help_has_no_encode_workers_flag():
    """The encode pool went with its flag (the collector's thread encodes
    what it claimed): refused like any unknown flag, not deprecated."""
    from cedar_tpu.cli.webhook import make_parser

    text = make_parser().format_help()
    assert "--pipeline-depth" in text and "--batch-window-us" in text
    assert "encode-workers" not in text
    with pytest.raises(SystemExit):
        make_parser().parse_args(["--encode-workers", "2"])
    assert not hasattr(make_parser().parse_args([]), "encode_workers")
