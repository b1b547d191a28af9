"""Overload-control plane tests (cedar_tpu/load, docs/performance.md
"Serving under overload").

The load-bearing pieces:

  * priority classification (byte scan, no JSON parse) and the graduated
    load states: sheddable sheds at pressure, normal at overload, high
    only at saturation — with ``offered == admitted + shed`` exact;
  * per-client fair-share token buckets under pressure (bounded client
    map: an adversary minting principals folds into one overflow bucket);
  * the shed/coalesce regression: a SingleFlight follower coalesced
    behind a leader that admission control sheds receives the shed answer
    immediately (bounded error, breaker untouched), not after its full
    deadline;
  * queue-wait-aware breaker accounting: a DeadlineExceeded whose whole
    budget burned in the submit queue (``queued=True``) must NOT feed the
    device breaker — under overload the breaker stays closed while the
    shedder does its job;
  * seeded arrival-process determinism (Poisson / burst / flash crowd):
    identical schedules across runs via the PR 11 derived-stream pattern,
    so ``bench.py --storm`` gates replay bit-for-bit;
  * the SLO-adaptive batch tuner's control law (grow batch with headroom
    + demand, shrink linger the moment the latency objective burns, decay
    home after the storm) with every move clamped and logged;
  * HTTP integration: honest shed answers (SAR NoOpinion + Retry-After,
    admission per the fail-open/closed flag), graduated /readyz,
    /debug/load, and the shed-storm chaos scenario.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from contextlib import ExitStack

import pytest

from cedar_tpu.cache import DecisionCache
from cedar_tpu.chaos import builtin_scenario, default_registry
from cedar_tpu.engine.batcher import DeadlineExceeded, MicroBatcher
from cedar_tpu.engine.breaker import CLOSED, CircuitBreaker
from cedar_tpu.load import (
    PRIORITY_HIGH,
    PRIORITY_NORMAL,
    PRIORITY_SHEDDABLE,
    STATE_OK,
    STATE_OVERLOAD,
    STATE_PRESSURE,
    STATE_SATURATED,
    AdaptiveBatchTuner,
    AdmissionController,
    RequestShed,
    TuningBounds,
    burst_schedule,
    classify,
    flash_crowd_schedule,
    poisson_schedule,
)
from cedar_tpu.obs.slo import SLOTracker
from cedar_tpu.server import metrics
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

DEMO_POLICY = """
permit (
    principal,
    action in [k8s::Action::"get", k8s::Action::"list"],
    resource is k8s::Resource
) when { principal.name == "test-user" && resource.resource == "pods" };
"""


def sar_body(user="test-user", resource="pods", verb="get"):
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
                    "namespace": "default",
                },
            },
        }
    ).encode()


def review_body(uid="r1", username="sam"):
    return json.dumps(
        {
            "apiVersion": "admission.k8s.io/v1",
            "kind": "AdmissionReview",
            "request": {
                "uid": uid,
                "operation": "CREATE",
                "userInfo": {"username": username, "groups": []},
                "kind": {"group": "", "version": "v1", "kind": "ConfigMap"},
                "resource": {
                    "group": "",
                    "version": "v1",
                    "resource": "configmaps",
                },
                "namespace": "default",
                "name": "c",
                "object": {
                    "apiVersion": "v1",
                    "kind": "ConfigMap",
                    "metadata": {"name": "c", "namespace": "default"},
                },
            },
        }
    ).encode()


def make_server(start=False, **kw):
    stores = TieredPolicyStores(
        [MemoryStore.from_source("demo", DEMO_POLICY)]
    )
    admission_stores = TieredPolicyStores(
        [
            MemoryStore.from_source("demo", DEMO_POLICY),
            allow_all_admission_policy_store(),
        ]
    )
    kw.setdefault("authorizer", CedarWebhookAuthorizer(stores))
    kw.setdefault("admission_handler", CedarAdmissionHandler(admission_stores))
    srv = WebhookServer(address="127.0.0.1", port=0, metrics_port=0, **kw)
    if start:
        srv.start()
    return srv


def post_raw(port, path, body):
    """(parsed json, response headers)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body, method="POST"
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read()), dict(resp.headers)


def get_raw(port, path):
    """(status, body bytes, headers) — HTTPError folded into the tuple."""
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5
        ) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def saturate(ctrl, stack, path="authorization", priority="high", n=None):
    """Hold ``n`` (default max_inflight) tracked requests open via the
    ExitStack so the controller reads the wanted load."""
    for _ in range(ctrl.max_inflight if n is None else n):
        stack.enter_context(ctrl.track(path, priority))


# ------------------------------------------------------------ classification


class TestClassify:
    @pytest.mark.parametrize(
        "user",
        [
            "system:node:ip-10-0-0-1",
            "system:kube-scheduler",
            "system:kube-controller-manager",
            "system:apiserver",
        ],
    )
    def test_system_critical_sars_are_high(self, user):
        assert classify("authorization", sar_body(user=user)) == PRIORITY_HIGH

    def test_kubelet_group_marker_is_high(self):
        body = json.dumps(
            {"spec": {"user": "worker", "groups": ["system:nodes"]}}
        ).encode()
        assert classify("authorization", body) == PRIORITY_HIGH

    def test_ordinary_sar_is_normal(self):
        assert classify("authorization", sar_body()) == PRIORITY_NORMAL

    def test_admission_is_normal_even_for_node_user(self):
        # admission reviews are controller/apiserver write-path traffic;
        # the node markers only promote AUTHORIZATION checks
        body = review_body(username="system:node:ip-10-0-0-1")
        assert classify("admission", body) == PRIORITY_NORMAL

    def test_explain_is_sheddable_regardless_of_principal(self):
        body = sar_body(user="system:node:ip-10-0-0-1")
        assert (
            classify("authorization", body, explain=True)
            == PRIORITY_SHEDDABLE
        )


# ------------------------------------------------------- graduated load gate


class TestAdmissionController:
    def test_graduated_states(self):
        ctrl = AdmissionController(max_inflight=10)
        assert ctrl.load_state() == STATE_OK
        with ExitStack() as stack:
            saturate(ctrl, stack, n=5)
            assert ctrl.load_state() == STATE_PRESSURE
            saturate(ctrl, stack, n=3)
            assert ctrl.load_state() == STATE_OVERLOAD
            saturate(ctrl, stack, n=2)
            assert ctrl.load_state() == STATE_SATURATED
        assert ctrl.load_state() == STATE_OK

    def test_shed_order_sheddable_normal_high(self):
        ctrl = AdmissionController(max_inflight=10)
        high = sar_body(user="system:node:n1")
        with ExitStack() as stack:
            saturate(ctrl, stack, n=5)  # pressure
            _, shed = ctrl.admit("authorization", sar_body(), explain=True)
            assert shed is not None and shed.reason == "load_pressure"
            assert ctrl.admit("authorization", sar_body())[1] is None
            assert ctrl.admit("authorization", high)[1] is None

            saturate(ctrl, stack, n=3)  # overload
            _, shed = ctrl.admit("authorization", sar_body())
            assert shed is not None and shed.reason == "load_overload"
            _, shed = ctrl.admit("admission", review_body())
            assert shed is not None and shed.reason == "load_overload"
            assert ctrl.admit("authorization", high)[1] is None

            saturate(ctrl, stack, n=2)  # saturated: even high sheds
            priority, shed = ctrl.admit("authorization", high)
            assert priority == PRIORITY_HIGH
            assert shed is not None and shed.reason == "saturated"

    def test_accounting_exact_offered_admitted_shed(self):
        ctrl = AdmissionController(max_inflight=4)
        with ExitStack() as stack:
            saturate(ctrl, stack, n=2)  # pressure: explain sheds
            for i in range(40):
                ctrl.admit(
                    "authorization", sar_body(), explain=bool(i % 2)
                )
        st = ctrl.stats()
        assert st["offered"] == 40
        assert st["admitted"] + st["shed"] == st["offered"]
        assert st["shed"] == 20  # every explain request shed at pressure
        assert st["shed_by"]["sheddable/load_pressure"] == 20

    def test_check_eval_sheds_normal_only_at_saturation(self):
        ctrl = AdmissionController(max_inflight=2)
        ctrl.check_eval(PRIORITY_NORMAL)  # idle: passes
        with ExitStack() as stack:
            saturate(ctrl, stack)
            ctrl.check_eval(PRIORITY_HIGH)  # high always passes
            with pytest.raises(RequestShed) as ei:
                ctrl.check_eval(PRIORITY_NORMAL)
            assert ei.value.reason == "eval_saturated"
        assert ctrl.stats()["eval_shed"] == 1
        # eval sheds are post-admission: they are NOT part of the ingress
        # offered/admitted/shed identity, but they ARE in shed_by
        assert ctrl.stats()["shed_by"]["normal/eval_saturated"] == 1

    def test_shed_metrics_published(self):
        ctrl = AdmissionController(max_inflight=2)
        with ExitStack() as stack:
            saturate(ctrl, stack)
            ctrl.admit("authorization", sar_body())
        expo = metrics.REGISTRY.expose()
        assert "cedar_load_shed_total" in expo
        assert 'reason="saturated"' in expo
        assert "cedar_load_state" in expo

    def test_inflight_gauge_published(self):
        ctrl = AdmissionController(max_inflight=8)
        with ctrl.track("authorization", PRIORITY_HIGH):
            expo = metrics.REGISTRY.expose()
            assert (
                'cedar_inflight_requests{path="authorization",'
                'priority="high"} 1' in expo
            )


class TestFairShare:
    def _ctrl(self, **kw):
        kw.setdefault("max_inflight", 10)
        kw.setdefault("client_qps", 1.0)
        kw.setdefault("client_burst", 1.0)
        kw.setdefault("client_enforce_at", 0.0)  # always enforced
        kw.setdefault("clock", lambda: 1000.0)  # frozen: no refill
        return AdmissionController(**kw)

    def test_hot_client_throttled_others_pass(self):
        ctrl = self._ctrl()
        hot = sar_body(user="hot-controller")
        assert ctrl.admit("authorization", hot)[1] is None  # burst token
        _, shed = ctrl.admit("authorization", hot)
        assert shed is not None and shed.reason == "client_quota"
        assert shed.client == "hot-controller"
        # a different client still has its own bucket
        assert ctrl.admit("authorization", sar_body(user="calm"))[1] is None

    def test_high_priority_exempt_from_quota(self):
        ctrl = self._ctrl()
        kubelet = sar_body(user="system:node:n1")
        for _ in range(5):
            assert ctrl.admit("authorization", kubelet)[1] is None

    def test_quota_idle_below_enforce_threshold(self):
        ctrl = self._ctrl(client_enforce_at=0.5)
        hot = sar_body(user="hot-controller")
        for _ in range(5):  # load 0 < 0.5: the bucket is never consulted
            assert ctrl.admit("authorization", hot)[1] is None
        assert ctrl.stats()["clients_tracked"] == 0

    def test_admission_client_parsed_from_userinfo(self):
        ctrl = self._ctrl()
        body = review_body(username="ctrl-loop")
        assert ctrl.admit("admission", body)[1] is None
        _, shed = ctrl.admit("admission", body)
        assert shed is not None and shed.client == "ctrl-loop"

    def test_client_map_bounded_with_overflow_bucket(self):
        ctrl = self._ctrl()
        ctrl.CLIENT_CAP = 2
        for user in ("a", "b"):
            ctrl.admit("authorization", sar_body(user=user))
        # clients c and d arrive with the map full: they SHARE the one
        # overflow bucket (c takes its burst token, d is refused)
        assert ctrl.admit("authorization", sar_body(user="c"))[1] is None
        _, shed = ctrl.admit("authorization", sar_body(user="d"))
        assert shed is not None and shed.reason == "client_quota"
        assert ctrl.stats()["clients_tracked"] == 2

    def test_unparseable_body_exempt(self):
        ctrl = self._ctrl()
        for _ in range(3):
            assert ctrl.admit("authorization", b"{not json")[1] is None


# ------------------------------------------------- arrival-process generators


class TestArrivalDeterminism:
    def test_poisson_identical_across_runs(self):
        a = poisson_schedule(200.0, 5.0, seed=7)
        b = poisson_schedule(200.0, 5.0, seed=7)
        assert a == b
        assert a != poisson_schedule(200.0, 5.0, seed=8)

    def test_poisson_prefix_stable_under_duration(self):
        # the derived-stream pattern makes gap i a pure function of
        # (seed, i): a shorter run is a strict PREFIX of a longer one
        short = poisson_schedule(100.0, 2.0, seed=3)
        long = poisson_schedule(100.0, 8.0, seed=3)
        assert long[: len(short)] == short

    def test_poisson_shape(self):
        sched = poisson_schedule(100.0, 5.0, seed=1)
        assert sched == sorted(sched)
        assert all(0.0 <= t < 5.0 for t in sched)
        # lambda=500: +/- 5 sigma keeps this deterministic-safe anyway
        assert 380 <= len(sched) <= 620

    def test_burst_identical_and_denser_in_burst(self):
        kw = dict(
            base_hz=20.0, burst_hz=400.0, period_s=1.0, duty=0.3,
            duration_s=6.0, seed=11,
        )
        a = burst_schedule(**kw)
        assert a == burst_schedule(**kw)
        in_burst = sum(1 for t in a if (t % 1.0) < 0.3)
        out_burst = len(a) - in_burst
        # expected ~720 in-burst vs ~84 outside
        assert in_burst > 4 * out_burst

    def test_flash_crowd_identical_and_peaks(self):
        kw = dict(
            base_hz=20.0, peak_hz=600.0, at_s=2.0, ramp_s=1.0,
            duration_s=8.0, seed=5,
        )
        a = flash_crowd_schedule(**kw)
        assert a == flash_crowd_schedule(**kw)
        hold = sum(1 for t in a if 3.0 <= t < 4.0)  # the hold window
        calm = sum(1 for t in a if t < 1.0)
        assert hold > 5 * calm

    def test_empty_for_degenerate_inputs(self):
        assert poisson_schedule(0.0, 5.0) == []
        assert poisson_schedule(10.0, 0.0) == []
        assert burst_schedule(0.0, 0.0, 1.0, 0.5, 5.0) == []
        assert flash_crowd_schedule(0.0, 0.0, 1.0, 1.0, 5.0) == []


# ------------------------------------------------------- SLO burn-rate query


class TestSLOBurnQueries:
    def test_latency_and_availability_burn_over_window(self):
        now = [1000.0]
        slo = SLOTracker(
            availability_target=0.999,
            latency_target=0.99,
            latency_budget_s=0.1,
            clock=lambda: now[0],
        )
        for i in range(10):
            slo.record("authorization", 0.5 if i < 5 else 0.01, error=i == 0)
        # slow fraction 0.5 over a 0.01 budget -> burn 50; errors 0.1 over
        # a 0.001 budget -> burn 100
        assert slo.latency_burn("authorization", 60.0) == pytest.approx(50.0)
        assert slo.availability_burn("authorization", 60.0) == pytest.approx(
            100.0
        )

    def test_no_traffic_reads_zero(self):
        slo = SLOTracker()
        assert slo.latency_burn("authorization", 60.0) == 0.0
        assert slo.availability_burn("nope", 1.0) == 0.0

    def test_window_floors_to_one_bucket(self):
        now = [2000.0]
        slo = SLOTracker(latency_budget_s=0.1, clock=lambda: now[0])
        slo.record("authorization", 1.0, error=False)
        # a 1ms window still sees the current 10s bucket
        assert slo.latency_burn("authorization", 0.001) > 0.0


# -------------------------------------------------------- adaptive batching


class _FakeBatcher:
    def __init__(self, max_batch=256, window_s=0.0004):
        self.max_batch = max_batch
        self.window_s = window_s
        self.queue = 0

    def queue_fill(self):
        return self.queue


class _FakeSLO:
    def __init__(self):
        self.burn = 0.0

    def latency_burn(self, path, window_s):
        return self.burn


class TestAdaptiveBatchTuner:
    def _tuner(self, batcher=None, **kw):
        batcher = batcher or _FakeBatcher()
        slo = _FakeSLO()
        kw.setdefault(
            "bounds",
            TuningBounds(
                min_batch=64, max_batch=1024,
                min_window_s=0.00005, max_window_s=0.002,
            ),
        )
        return AdaptiveBatchTuner(batcher, slo, **kw), batcher, slo

    def test_burning_shrinks_linger_only(self):
        tuner, batcher, slo = self._tuner()
        slo.burn = 2.0
        batcher.queue = 10_000  # demand present, but latency burns: the
        # linger must shrink and the batch must NOT grow this tick
        d = tuner.tick()
        assert d is not None and d["param"] == "linger_us"
        assert batcher.window_s == pytest.approx(0.0002)
        assert batcher.max_batch == 256
        assert "shrink linger" in d["reason"]
        assert d["latency_burn"] == pytest.approx(2.0)

    def test_linger_clamped_at_min(self):
        tuner, batcher, slo = self._tuner()
        slo.burn = 5.0
        for _ in range(20):
            tuner.tick()
        assert batcher.window_s == pytest.approx(tuner.bounds.min_window_s)
        # at the clamp there is no further move to log
        assert tuner.tick() is None

    def test_headroom_and_demand_grow_batch(self):
        tuner, batcher, slo = self._tuner()
        slo.burn = 0.0
        batcher.queue = 10_000
        d = tuner.tick()
        assert d is not None and d["param"] == "max_batch"
        assert batcher.max_batch == 512
        for _ in range(10):
            tuner.tick()
        assert batcher.max_batch == tuner.bounds.max_batch  # clamped

    def test_no_move_when_healthy_and_at_home(self):
        tuner, batcher, slo = self._tuner()
        slo.burn = 0.1
        batcher.queue = 0
        assert tuner.tick() is None
        assert tuner.moves == 0

    def test_decay_back_to_home_after_storm(self):
        tuner, batcher, slo = self._tuner()
        slo.burn = 2.0
        tuner.tick()  # shrink linger
        slo.burn = 0.0
        batcher.queue = 2_000
        tuner.tick()  # grow batch
        batcher.queue = 0  # storm over
        for _ in range(30):
            tuner.tick()
        assert batcher.window_s == pytest.approx(tuner.home_window_s)
        assert batcher.max_batch == tuner.home_batch

    def test_mid_burn_holds_steady(self):
        # between burn_low and burn_high nothing moves: hysteresis, not
        # dither
        tuner, batcher, slo = self._tuner(burn_low=0.25, burn_high=1.0)
        slo.burn = 0.5
        batcher.queue = 10_000
        assert tuner.tick() is None

    def test_decision_log_bounded_and_status(self):
        tuner, batcher, slo = self._tuner()
        slo.burn = 2.0
        tuner.tick()
        st = tuner.status()
        assert st["moves"] == 1 and len(st["decisions"]) == 1
        assert st["home"]["max_batch"] == 256
        assert st["bounds"]["max_batch"] == 1024
        tuner.DECISION_LOG = 4
        slo.burn = 0.0
        for i in range(16):
            batcher.queue = 10_000 if i % 2 else 0
            slo.burn = 0.0 if i % 2 else 2.0
            tuner.tick()
        assert len(tuner.status()["decisions"]) <= 4

    def test_tuning_gauges_published(self):
        self._tuner(path="authorization")
        expo = metrics.REGISTRY.expose()
        assert (
            'cedar_batch_tuning{path="authorization",param="max_batch"}'
            in expo
        )
        assert 'param="linger_us"' in expo

    def test_real_slo_tracker_drives_a_move(self):
        # integration with the real SLO ring: slow requests -> burn > 1 ->
        # the tuner shrinks linger
        now = [5000.0]
        slo = SLOTracker(
            latency_target=0.99, latency_budget_s=0.05, clock=lambda: now[0]
        )
        for _ in range(20):
            slo.record("authorization", 0.2, error=False)
        batcher = _FakeBatcher()
        tuner = AdaptiveBatchTuner(batcher, slo, window_s=60.0)
        d = tuner.tick()
        assert d is not None and d["param"] == "linger_us"

    def test_start_stop_thread(self):
        tuner, _, slo = self._tuner(interval_s=0.01)
        slo.burn = 2.0
        tuner.start()
        deadline = time.monotonic() + 2.0
        while tuner.moves == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        tuner.stop()
        assert tuner.moves >= 1
        assert tuner._thread is not None and not tuner._thread.is_alive()

    def test_tuner_prefers_backlog_over_queue_fill(self):
        # a pipelined batcher's demand sits in its stage queues, not the
        # submit queue: the tuner must read backlog() when the batcher
        # provides it, or the grow path is blind exactly under load
        batcher = _FakeBatcher()
        batcher.queue = 0  # submit queue empty...
        batcher.backlog = lambda: 10_000  # ...demand inside the pipeline
        slo = _FakeSLO()
        tuner = AdaptiveBatchTuner(
            batcher, slo,
            bounds=TuningBounds(
                min_batch=64, max_batch=1024,
                min_window_s=0.00005, max_window_s=0.002,
            ),
        )
        d = tuner.tick()
        assert d is not None and d["param"] == "max_batch"
        assert batcher.max_batch == 512


class TestPipelinedBacklog:
    def test_backlog_counts_claimed_entries_and_drains_to_zero(self):
        """backlog() = queued + claimed-into-the-pipeline entries. With
        every stage gated, all submitted entries stay visible even after
        the collector claimed them off the submit queue (where
        queue_fill() stops seeing them); after the drain it reads 0."""
        from cedar_tpu.engine.batcher import PipelinedBatcher

        gate = threading.Event()

        class _Stages:
            def pipeline_encode(self, items):
                return list(items)

            def pipeline_dispatch(self, ctx):
                gate.wait(5.0)
                return ctx

            def pipeline_decode(self, ctx):
                return [(DECISION_ALLOW, "", None)] * len(ctx)

        b = PipelinedBatcher(
            _Stages(), max_batch=2, window_s=0.0, depth=1
        )
        results = []
        try:
            ts = [
                threading.Thread(
                    target=lambda i=i: results.append(
                        b.submit(f"r{i}", timeout=5.0)
                    ),
                    daemon=True,
                )
                for i in range(6)
            ]
            for t in ts:
                t.start()
            # the collector claims a batch only when the dispatch thread
            # can take it, so all six may be enqueued before the first
            # claim: wait for both conditions, not for the backlog alone
            deadline = time.monotonic() + 2.0
            while (
                not (b.backlog() == 6 and b.queue_fill() < 6)
                and time.monotonic() < deadline
            ):
                time.sleep(0.005)
            assert b.backlog() == 6
            # the collector has claimed at least one batch into the
            # gated stages — the submit queue alone undercounts
            assert b.queue_fill() < 6
            gate.set()
            for t in ts:
                t.join(3.0)
            assert len(results) == 6
            deadline = time.monotonic() + 2.0
            while b.backlog() and time.monotonic() < deadline:
                time.sleep(0.005)
            assert b.backlog() == 0
        finally:
            gate.set()
            b.stop()


# --------------------------------------------- shed/coalesce regression fix


class TestShedCoalesceInteraction:
    def test_follower_behind_shed_leader_answers_immediately(self):
        ctrl = AdmissionController(max_inflight=1)
        server = make_server(
            decision_cache=DecisionCache(),
            load=ctrl,
            request_timeout_s=5.0,
        )
        entered = threading.Event()
        gate = threading.Event()
        real = server._authorize_uncached

        def gated_uncached(body, request_id, coalesce_key=None, **kw):
            entered.set()
            gate.wait(5)
            return real(body, request_id, coalesce_key=coalesce_key, **kw)

        server._authorize_uncached = gated_uncached
        body = sar_body()
        results = {}

        def run(name):
            results[name] = (
                server.handle_authorize(body, priority=PRIORITY_NORMAL),
                time.monotonic(),
            )

        with ExitStack() as stack:
            saturate(ctrl, stack)  # load 1.0: check_eval sheds normal
            leader = threading.Thread(target=run, args=("leader",))
            leader.start()
            assert entered.wait(5)
            follower = threading.Thread(target=run, args=("follower",))
            follower.start()
            deadline = time.monotonic() + 5
            while (
                server._sar_flights.in_flight() == 0
                and time.monotonic() < deadline
            ):
                time.sleep(0.002)
            time.sleep(0.05)  # the follower attaches to the flight
            t_release = time.monotonic()
            gate.set()
            leader.join(5)
            follower.join(5)

        assert set(results) == {"leader", "follower"}
        for doc, _ in results.values():
            status = doc["status"]
            assert not status["allowed"] and not status["denied"]
            assert "shed" in status["evaluationError"]
        # the regression: the follower must NOT wait out its 5s budget —
        # the leader's shed fans out the moment it lands
        assert results["follower"][1] - t_release < 1.0
        # exactly ONE evaluation-stage shed: the leader's; the follower
        # reused it
        assert ctrl.stats()["eval_shed"] == 1
        server.stop(drain_grace_s=0)

    def test_leader_shed_never_cached(self):
        # after the storm passes, the same SAR must evaluate cleanly —
        # a shed result leaking into the decision cache would serve
        # NoOpinion to polite traffic
        ctrl = AdmissionController(max_inflight=1)
        server = make_server(
            decision_cache=DecisionCache(), load=ctrl, request_timeout_s=5.0
        )
        body = sar_body()
        with ExitStack() as stack:
            saturate(ctrl, stack)
            doc = server.handle_authorize(body, priority=PRIORITY_NORMAL)
            assert "shed" in doc["status"]["evaluationError"]
        doc = server.handle_authorize(body, priority=PRIORITY_NORMAL)
        assert doc["status"]["allowed"] is True
        server.stop(drain_grace_s=0)


# --------------------------------------- queue-wait-aware breaker accounting


class _FakeFastPath:
    def __init__(self, fn, breaker=None):
        self.available = True
        self.authorize_raw = fn
        self.breaker = breaker


class TestQueueWaitBreakerAccounting:
    def test_deadline_exceeded_queued_flag(self):
        """Expiries on a MOVING plane (a batch completed during the
        wait) are queue-burned — both shapes: claimed only after half
        the budget was gone, and still unclaimed at expiry."""
        seen = []
        gate = threading.Event()

        def fn(items):
            seen.append(list(items))
            if "a" in items:
                # slow but completing: the plane MOVES. Long enough that a
                # main thread held up for tens of ms on a loaded host still
                # enqueues "c" before this batch completes
                time.sleep(0.11)
            elif "b" in items:
                gate.wait(2.0)  # the batch behind it stalls
            return [(DECISION_ALLOW, "", None)] * len(items)

        b = MicroBatcher(fn, max_batch=1, window_s=0.0)
        claimed = threading.Thread(target=lambda: b.submit("a"), daemon=True)
        claimed.start()
        while not seen:
            time.sleep(0.001)
        # "b": claimed only once "a" completes (~110ms > half its 150ms
        # budget), then stalls — a claim that got the tail end of a
        # spent deadline on a moving plane: queued=True
        holder = {}

        def submit_b():
            try:
                b.submit("b", timeout=0.15)
            except DeadlineExceeded as e:
                holder["b"] = e

        tb = threading.Thread(target=submit_b, daemon=True)
        tb.start()
        time.sleep(0.01)  # "b" enqueues ahead of "c"
        # "c": expires UNCLAIMED behind the stall, with "a" having
        # completed during its wait: queued=True
        with pytest.raises(DeadlineExceeded) as ei:
            b.submit("c", timeout=0.15)
        assert ei.value.queued is True
        gate.set()
        tb.join(2.0)
        claimed.join(2.0)
        assert isinstance(holder.get("b"), DeadlineExceeded)
        assert holder["b"].queued is True
        b.stop()

    def test_deadline_exceeded_claimed_flag(self):
        release = threading.Event()

        def fn(items):
            release.wait(2.0)
            return [(DECISION_ALLOW, "", None)] * len(items)

        b = MicroBatcher(fn, max_batch=4, window_s=0.0)
        # the sole submitter's slot is CLAIMED by the batch thread before
        # its budget expires: queued=False (a device-plane signal)
        with pytest.raises(DeadlineExceeded) as ei:
            b.submit("a", timeout=0.05)
        assert ei.value.queued is False
        release.set()
        b.stop()

    def test_wedged_plane_expiries_still_signal(self):
        """The OTHER side of the coin (tests/test_resilience.py
        TestHungDevicePlane): when the plane completes NOTHING, an
        unclaimed expiry is the hung-device signal, not queue burn —
        sparing it would leave a wedged batcher serving deadline errors
        forever with the breaker closed."""
        seen = []
        gate = threading.Event()

        def fn(items):
            seen.append(list(items))
            gate.wait(5.0)  # wedged from the very first batch
            return [(DECISION_ALLOW, "", None)] * len(items)

        b = MicroBatcher(fn, max_batch=1, window_s=0.0)
        claimed = threading.Thread(target=lambda: b.submit("a"), daemon=True)
        claimed.start()
        while not seen:
            time.sleep(0.001)
        # "b" expires unclaimed, but NO batch has ever completed: this
        # expiry must keep feeding the breaker
        with pytest.raises(DeadlineExceeded) as ei:
            b.submit("b", timeout=0.05)
        assert ei.value.queued is False
        gate.set()
        claimed.join(2.0)
        b.stop()

    def test_queue_burned_expiries_spare_the_breaker(self):
        """The storm shape: the device plane is MOVING — batches keep
        completing, just slower than offered load — so a train of
        requests burns its budgets in the submit queue. None of those
        expiries may feed the breaker (failure_threshold 3): under
        overload the breaker stays CLOSED while the shedder does its
        job; tripping it would route everything to the slower
        interpreter and deepen the storm."""

        def slow(items):
            time.sleep(0.05)  # per-batch service floor: moving, but slow
            return [(DECISION_ALLOW, "", None)] * len(items)

        breaker = CircuitBreaker(
            name="storm-test", failure_threshold=3, recovery_s=30.0
        )
        server = make_server(
            fastpath=_FakeFastPath(slow, breaker=breaker),
            request_timeout_s=0.12,
            max_batch=1,
        )
        try:
            # saturate: 12 concurrent submitters against a 20/s plane
            # with 120ms budgets — the tail's budgets burn in the queue
            results = []
            lock = threading.Lock()

            def one():
                doc = server.handle_authorize(sar_body())
                with lock:
                    results.append(doc)

            ts = [threading.Thread(target=one) for _ in range(12)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(10.0)
            expiries = [
                d for d in results
                if "deadline" in (d["status"].get("evaluationError") or "")
            ]
            assert len(expiries) >= 5  # the storm actually happened
            assert breaker.state == CLOSED
        finally:
            server.stop(drain_grace_s=0)


# ------------------------------------------------------------ HTTP behavior


class TestHTTPIntegration:
    def test_shed_answers_and_graduated_readyz(self):
        ctrl = AdmissionController(max_inflight=4, retry_after_s=2.0)
        srv = make_server(
            start=True,
            load=ctrl,
            admission_fail_open=True,
        )
        try:
            port, mport = srv.bound_port, srv.bound_metrics_port
            # idle: requests serve normally, /readyz says ok
            doc, _ = post_raw(port, "/v1/authorize", sar_body())
            assert doc["status"]["allowed"] is True
            status, body, headers = get_raw(mport, "/readyz")
            assert status == 200 and body == b"ok"
            assert headers["X-Cedar-Load-State"] == "ok"

            with ExitStack() as stack:
                saturate(ctrl, stack, n=2)  # pressure
                doc, headers = post_raw(
                    port, "/v1/authorize?explain=1", sar_body()
                )
                st = doc["status"]
                assert not st["allowed"] and not st["denied"]
                assert "shed" in st["evaluationError"]
                assert headers["Retry-After"] == "2"
                status, body, _ = get_raw(mport, "/readyz")
                assert status == 200 and body == b"pressure"

                saturate(ctrl, stack, n=2)  # saturated
                status, body, headers = get_raw(mport, "/readyz")
                assert status == 503 and body == b"saturated"
                assert headers["X-Cedar-Load-State"] == "saturated"
                # admission sheds answer the configured fail-mode
                doc, headers = post_raw(port, "/v1/admit", review_body())
                assert doc["response"]["allowed"] is True
                assert "shed" in doc["response"]["status"]["message"]
                assert "Retry-After" in headers
            st = ctrl.stats()
            assert st["offered"] == st["admitted"] + st["shed"]
        finally:
            srv.stop()

    def test_admission_shed_fail_closed(self):
        ctrl = AdmissionController(max_inflight=2)
        srv = make_server(start=True, load=ctrl, admission_fail_open=False)
        try:
            with ExitStack() as stack:
                saturate(ctrl, stack)
                doc, _ = post_raw(
                    srv.bound_port, "/v1/admit", review_body(uid="u-9")
                )
                assert doc["response"]["allowed"] is False
                assert doc["response"]["uid"] == "u-9"
        finally:
            srv.stop()

    def test_debug_load_document(self):
        ctrl = AdmissionController(max_inflight=4)
        srv = make_server(start=True, load=ctrl)
        tuner = AdaptiveBatchTuner(_FakeBatcher(), _FakeSLO())
        srv.tuners.append(tuner)
        try:
            with ExitStack() as stack:
                saturate(ctrl, stack, n=2)
                post_raw(
                    srv.bound_port, "/v1/authorize?explain=1", sar_body()
                )  # one shed on the books
            status, body, _ = get_raw(srv.bound_metrics_port, "/debug/load")
            assert status == 200
            doc = json.loads(body)
            ac = doc["admission_control"]
            assert ac["max_inflight"] == 4
            assert ac["offered"] == ac["admitted"] + ac["shed"]
            assert ac["shed_by"]["sheddable/load_pressure"] == 1
            tuning = doc["tuning"]["authorization"]
            assert tuning["max_batch"] == 256
            assert "decisions" in tuning
        finally:
            srv.stop()

    def test_debug_load_404_without_plane(self):
        srv = make_server(start=True)
        try:
            status, _, _ = get_raw(srv.bound_metrics_port, "/debug/load")
            assert status == 404
        finally:
            srv.stop()

    def test_idle_gate_byte_identical_to_ungated(self):
        # the enabled-but-idle differential: an admission controller at
        # load ~0 must not change a single response byte
        gated = make_server(load=AdmissionController(max_inflight=1024))
        plain = make_server()
        try:
            for user in ("test-user", "alice", "system:node:n1"):
                for resource in ("pods", "secrets"):
                    body = sar_body(user=user, resource=resource)
                    a = json.dumps(gated.serve_authorize(body), sort_keys=True)
                    b = json.dumps(plain.serve_authorize(body), sort_keys=True)
                    assert a == b
            rb = review_body()
            a = json.dumps(gated.serve_admit(rb), sort_keys=True)
            b = json.dumps(plain.serve_admit(rb), sort_keys=True)
            assert a == b
        finally:
            gated.stop(drain_grace_s=0)
            plain.stop(drain_grace_s=0)

    def test_serve_wrappers_gate_like_do_post(self):
        ctrl = AdmissionController(max_inflight=2)
        srv = make_server(load=ctrl)
        try:
            with ExitStack() as stack:
                saturate(ctrl, stack)
                doc = srv.serve_authorize(sar_body())
                assert "shed" in doc["status"]["evaluationError"]
                doc = srv.serve_admit(review_body())
                assert "shed" in doc["response"]["status"]["message"]
        finally:
            srv.stop(drain_grace_s=0)


# --------------------------------------------------------- chaos: shed-storm


class TestShedStormChaos:
    def setup_method(self):
        default_registry().reset()

    def teardown_method(self):
        default_registry().reset()

    def test_scenario_registered(self):
        sc = builtin_scenario("shed-storm")
        assert sc["faults"][0]["seam"] == "load.shed"
        assert sc["faults"][0]["kind"] == "corrupt"

    def test_forced_sheds_answer_honestly_breaker_closed(self):
        registry = default_registry()
        registry.configure(
            {
                "seed": 23,
                "faults": [
                    {"seam": "load.shed", "kind": "corrupt", "count": 50}
                ],
            }
        )
        ctrl = AdmissionController(max_inflight=1024)
        breaker = CircuitBreaker(name="shed-storm-test", failure_threshold=3)

        def fast(items):
            return [(DECISION_ALLOW, "", None)] * len(items)

        server = make_server(
            load=ctrl,
            fastpath=_FakeFastPath(fast, breaker=breaker),
            request_timeout_s=2.0,
        )
        try:
            registry.arm()
            sheds = answers = 0
            for _ in range(80):
                doc = server.serve_authorize(sar_body())
                st = doc["status"]
                if "shed" in (st.get("evaluationError") or ""):
                    sheds += 1
                    assert not st["allowed"] and not st["denied"]
                else:
                    answers += 1
                    assert st["allowed"] is True
            registry.disarm()
            assert sheds == 50 and answers == 30
            # the breaker watched a healthy device through the whole storm
            assert breaker.state == CLOSED
            st = ctrl.stats()
            assert st["offered"] == 80
            assert st["admitted"] + st["shed"] == st["offered"]
            assert st["shed_by"]["normal/chaos"] == 50
            # disarmed again: traffic is clean
            doc = server.serve_authorize(sar_body())
            assert doc["status"]["allowed"] is True
        finally:
            registry.reset()
            server.stop(drain_grace_s=0)


# ------------------------------------------------------------------ CLI glue


class TestCLIWiring:
    def test_parser_defaults_keep_plane_off(self):
        from cedar_tpu.cli.webhook import make_parser

        args = make_parser().parse_args([])
        assert args.max_inflight == 0
        assert args.adaptive_batching is False
        assert args.client_qps == 0.0
        assert args.tuner_min_batch == 64

    def test_parser_overload_flags(self):
        from cedar_tpu.cli.webhook import make_parser

        args = make_parser().parse_args(
            [
                "--max-inflight", "512",
                "--shed-sheddable-at", "0.4",
                "--client-qps", "50",
                "--adaptive-batching",
                "--tuner-max-linger-us", "900",
            ]
        )
        assert args.max_inflight == 512
        assert args.shed_sheddable_at == 0.4
        assert args.client_qps == 50.0
        assert args.adaptive_batching is True
        assert args.tuner_max_linger_us == 900.0

    def test_client_enforce_at_derives_from_pressure_threshold(self):
        # the quota must act across the whole pressure band: a fixed
        # enforce-at above --shed-normal-at would be silently inert
        # (normal traffic sheds wholesale before enforcement starts)
        from cedar_tpu.cli.webhook import _client_enforce_at, make_parser

        args = make_parser().parse_args(
            [
                "--max-inflight", "100",
                "--shed-sheddable-at", "0.3",
                "--shed-normal-at", "0.4",
                "--client-qps", "10",
            ]
        )
        assert args.client_enforce_at == -1.0  # default: derive
        enforce = _client_enforce_at(args)
        assert enforce == args.shed_sheddable_at
        assert enforce < args.shed_normal_at  # the band is non-empty
        # an explicit value wins over the derivation
        args = make_parser().parse_args(
            ["--max-inflight", "100", "--client-enforce-at", "0.7"]
        )
        assert _client_enforce_at(args) == 0.7
