"""Engine-fleet tests (ISSUE 7, docs/fleet.md).

The fleet replicates the authorization engine behind a health-aware
router with hedged dispatch and fleet-atomic rollout. Everything riding
on it is pinned here:

  * routing — least-loaded among healthy, deterministic spillover around
    open-breaker/dead replicas, FleetUnavailable when nothing admits;
  * hedged dispatch — a slow lone request hedges onto a second replica,
    first answer wins, the loser is cancelled through waiter accounting;
  * single-replica parity — a fleet-of-1 server answers BYTE-identically
    to the classic single-engine server over >= 1.1k mixed bodies;
  * fleet-atomic promotion — a clean promote swaps every replica with
    ZERO fresh jit traces; a chaos-injected failure on one replica leaves
    EVERY replica on the prior set (no mixed-generation answers) and the
    lifecycle recoverable; rollback restores all replicas and refuses
    after a per-replica lineage divergence;
  * the decision cache's composite generation folds the fleet epoch;
  * replica lifecycle (drain → revive) + the {component, replica} death
    metric + /debug/fleet and the per-replica /debug/engine;
  * the replica-loss game day (chaos-marked): killing one replica
    mid-traffic holds availability >= 99.5% with zero decision flips and
    the supervisor revives it.
"""

import json
import threading
import time
import urllib.request

import pytest

from cedar_tpu.chaos.registry import default_registry
from cedar_tpu.engine.batcher import DeadlineExceeded, MicroBatcher
from cedar_tpu.engine.breaker import CircuitBreaker
from cedar_tpu.engine.evaluator import TPUPolicyEngine
from cedar_tpu.fleet import (
    EngineFleet,
    EngineReplica,
    FleetUnavailable,
)
from cedar_tpu.lang import PolicySet
from cedar_tpu.native import native_available
from cedar_tpu.ops.match import kernel_trace_count
from cedar_tpu.server import metrics
from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
from cedar_tpu.server.http import WebhookServer, sar_response
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
"""

# the candidate flips pods-get for sam from permit to forbid: promotion
# must flip EVERY replica's answers together
CANDIDATE_POLICIES = """
forbid (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "sam" && resource.resource == "pods" };
permit (principal, action, resource is k8s::Resource)
  when { resource.resource == "services" };
"""


def _sar_body(i: int) -> bytes:
    k = i % 9
    if k == 8:
        return b'{"not json' + str(i).encode()
    user, groups = f"user-{i % 7}", []
    resource = "pods"
    if k == 0:
        user = "sam"
    elif k == 1:
        groups = ["viewers"]
    elif k == 2:
        resource = "nodes"
    elif k == 3:
        user = "system:kube-scheduler"
    elif k == 4:
        resource = "services"
    return json.dumps(
        {
            "apiVersion": "authorization.k8s.io/v1",
            "kind": "SubjectAccessReview",
            "spec": {
                "user": user,
                "uid": "u",
                "groups": groups,
                "resourceAttributes": {
                    "verb": "get",
                    "version": "v1",
                    "resource": resource,
                    "namespace": f"ns-{i % 5}",
                },
            },
        }
    ).encode()


class _StubFastPath:
    available = True


def _stub_replica(index, fn, breaker=None, window_s=0.0001):
    """A replica over a controllable MicroBatcher (router unit tests)."""
    batcher = MicroBatcher(
        fn, max_batch=8, window_s=window_s, replica=f"r{index}",
        dispatch_seam="fleet.replica_dispatch",
    )
    return EngineReplica(
        index, engine=None, fastpath=_StubFastPath(), breaker=breaker,
        batcher=batcher,
    )


def _sar_stack(src=SAR_POLICIES, n_replicas=2, hedge_delay_s=0.0,
               breakers=False, recoveries=False):
    """(stores, authorizer, fleet) over real engines + native fast paths."""
    from cedar_tpu.engine.fastpath import SARFastPath

    stores = TieredPolicyStores([MemoryStore.from_source("fleet", src)])
    authorizer = CedarWebhookAuthorizer(stores)
    replicas = []
    for i in range(n_replicas):
        engine = TPUPolicyEngine(name=f"fleet-test-r{i}")
        breaker = (
            CircuitBreaker(
                name=f"fleet-test-r{i}", failure_threshold=3, recovery_s=0.5
            )
            if breakers
            else None
        )
        recovery = None
        if recoveries:
            from cedar_tpu.server.supervisor import DeviceRecovery

            recovery = DeviceRecovery(
                engine, breaker=breaker, name=f"fleet-test-r{i}", warm=False
            )
        fastpath = SARFastPath(engine, authorizer, breaker=breaker)
        if recovery is not None:
            fastpath.on_device_error = recovery.observe
        replicas.append(
            EngineReplica(
                i, engine, fastpath, breaker=breaker, recovery=recovery,
                max_batch=64, pipeline_depth=2,
                fleet_name="fleet-test",
            )
        )
    fleet = EngineFleet(replicas, hedge_delay_s=hedge_delay_s,
                        name="fleet-test")
    fleet.load([s.policy_set() for s in stores], warm="off")
    return stores, authorizer, fleet


# --------------------------------------------------------------------------
# router units (stub batchers, no engines)


class TestRouterSelection:
    def test_least_loaded_pick_with_deterministic_tiebreak(self):
        r0 = _stub_replica(0, lambda items: list(items))
        r1 = _stub_replica(1, lambda items: list(items))
        fleet = EngineFleet([r0, r1], name="unit")
        try:
            assert fleet.router.pick() is r0  # tie breaks on index
            r0.begin_request()
            assert fleet.router.pick() is r1  # least loaded wins
            r0.end_request()
        finally:
            fleet.stop()

    def test_open_breaker_excluded_then_unavailable(self):
        b0 = CircuitBreaker(name="unit-r0", recovery_s=3600.0)
        b1 = CircuitBreaker(name="unit-r1", recovery_s=3600.0)
        r0 = _stub_replica(0, lambda items: list(items), breaker=b0)
        r1 = _stub_replica(1, lambda items: list(items), breaker=b1)
        fleet = EngineFleet([r0, r1], name="unit")
        try:
            b0.force_open()
            assert fleet.router.pick() is r1  # deterministic spillover
            b1.force_open()
            with pytest.raises(FleetUnavailable):
                fleet.router.pick()
            # a breaker-open fleet still SERVES through the caller's
            # interpreter path — submit surfaces the same signal
            with pytest.raises(FleetUnavailable):
                fleet.submit(b"x", timeout=1.0)
        finally:
            fleet.stop()

    def test_midflight_failure_spills_over(self):
        def boom(items):
            raise RuntimeError("replica 0 wedged")

        r0 = _stub_replica(0, boom)
        r1 = _stub_replica(1, lambda items: [i * 2 for i in items])
        fleet = EngineFleet([r0, r1], name="unit")
        try:
            r1.begin_request()  # bias the first pick onto the sick r0
            try:
                assert fleet.submit(3, timeout=5.0) == 6
            finally:
                r1.end_request()
            assert fleet.router.spillovers == 1
            assert fleet.router.routed["r0"] == 1
            assert fleet.router.routed["r1"] == 1
        finally:
            fleet.stop()

    def test_coalesce_key_affinity_beats_least_loaded(self):
        """Identical concurrent requests sharing a coalesce key must land
        on the replica already holding the pending slot — least-loaded
        spreading would evaluate K times what one batcher dedups to
        one."""
        calls = {"r0": 0, "r1": 0}
        gate = threading.Event()

        def slow0(items):
            calls["r0"] += 1
            gate.wait(5.0)
            return [i * 2 for i in items]

        def fast1(items):
            calls["r1"] += 1
            return [i * 2 for i in items]

        # a long window keeps the leader's entry QUEUED (unclaimed) so
        # the follower's affinity check sees it pending
        r0 = _stub_replica(0, slow0, window_s=0.2)
        r1 = _stub_replica(1, fast1)
        fleet = EngineFleet([r0, r1], name="unit")
        try:
            results = []

            def one():
                results.append(fleet.submit(9, timeout=5.0,
                                            coalesce_key="k"))

            t1 = threading.Thread(target=one)
            t1.start()
            time.sleep(0.05)  # leader enqueued on r0, still in the window
            t2 = threading.Thread(target=one)
            t2.start()
            gate.set()
            t1.join(timeout=10)
            t2.join(timeout=10)
            assert results == [18, 18]
            # ONE evaluation on r0, none on r1: the follower attached to
            # the leader's slot instead of spreading to the idle replica
            assert calls == {"r0": 1, "r1": 0}
        finally:
            gate.set()
            fleet.stop()

    def test_promotion_barrier_gate_blocks_until_budget(self):
        """While the barrier gate is down, a budgeted request answers the
        bounded deadline error rather than dispatching into a half-swapped
        fleet; a re-opened gate releases waiters promptly."""
        r0 = _stub_replica(0, lambda items: list(items))
        fleet = EngineFleet([r0], name="unit")
        try:
            fleet._gate.clear()
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceeded, match="barrier"):
                fleet.submit(1, timeout=0.05)
            assert time.monotonic() - t0 < 3.0  # bounded, not wedged
            fleet._gate.set()
            assert fleet.submit(2, timeout=5.0) == 2
        finally:
            fleet._gate.set()
            fleet.stop()

    def test_deadline_feeds_replica_breaker_and_raises(self):
        b0 = CircuitBreaker(
            name="unit-dead", failure_threshold=1, recovery_s=3600.0
        )

        def slow(items):
            time.sleep(0.5)
            return list(items)

        r0 = _stub_replica(0, slow, breaker=b0)
        fleet = EngineFleet([r0], name="unit")
        try:
            with pytest.raises(DeadlineExceeded):
                fleet.submit(1, timeout=0.02)
            from cedar_tpu.engine.breaker import OPEN

            assert b0.state == OPEN
        finally:
            fleet.stop()


class TestHedgedDispatch:
    def test_hedge_fires_and_first_answer_wins(self):
        ev = threading.Event()

        def slow(items):
            ev.wait(2.0)  # the primary wedges until released
            return [("slow", i) for i in items]

        def fast(items):
            return [("fast", i) for i in items]

        r0 = _stub_replica(0, slow)
        r1 = _stub_replica(1, fast)
        fleet = EngineFleet([r0, r1], hedge_delay_s=0.02, name="unit")
        try:
            got = fleet.submit(7, timeout=5.0)
            assert got == ("fast", 7)
            assert fleet.router.hedges == 1
            assert fleet.router.hedge_wins["hedge"] == 1
            ev.set()
            # the loser's late result is discarded without corrupting the
            # primary's queue/waiter accounting: it keeps serving
            time.sleep(0.05)
            assert fleet.router.pick() in (r0, r1)
            got2 = fleet.submit(8, timeout=5.0)
            assert got2 in (("fast", 8), ("slow", 8))
        finally:
            ev.set()
            fleet.stop()

    def test_primary_win_cancels_hedge(self):
        calls = {"r1": 0}

        def fast(items):
            return [i + 1 for i in items]

        def count(items):
            calls["r1"] += 1
            return [i + 1 for i in items]

        r0 = _stub_replica(0, fast)
        r1 = _stub_replica(1, count, window_s=0.05)
        fleet = EngineFleet([r0, r1], hedge_delay_s=10.0, name="unit")
        try:
            # primary answers well inside the 10s hedge delay: no hedge
            assert fleet.submit(1, timeout=5.0) == 2
            assert fleet.router.hedges == 0
            assert calls["r1"] == 0
        finally:
            fleet.stop()

    def test_single_replica_never_hedges(self):
        r0 = _stub_replica(0, lambda items: list(items), window_s=0.01)
        fleet = EngineFleet([r0], hedge_delay_s=0.001, name="unit")
        try:
            assert fleet.submit(5, timeout=5.0) == 5
            assert fleet.router.hedges == 0
        finally:
            fleet.stop()


class TestLifecycle:
    def test_drain_excludes_then_revive_restores(self):
        r0 = _stub_replica(0, lambda items: list(items))
        r1 = _stub_replica(1, lambda items: list(items))
        fleet = EngineFleet([r0, r1], name="unit")
        try:
            assert fleet.drain_replica(0) is True
            assert fleet.router.pick() is r1
            assert r0.state_code() == 3  # draining
            assert fleet.revive_replica(0) is True
            assert fleet.router.pick() is r0
        finally:
            fleet.stop()

    def test_retired_replica_is_terminal(self):
        r0 = _stub_replica(0, lambda items: list(items))
        r1 = _stub_replica(1, lambda items: list(items))
        fleet = EngineFleet([r0, r1], name="unit")
        try:
            assert fleet.retire_replica(0) is True
            assert fleet.router.pick() is r1
            assert fleet.revive_replica(0) is False
            assert fleet.submit(2, timeout=5.0) == 2  # r1 serves on
        finally:
            fleet.stop()

    def test_replica_death_metric_carries_replica_label(self):
        r = default_registry()
        r0 = _stub_replica(0, lambda items: list(items))
        r1 = _stub_replica(1, lambda items: list(items))
        fleet = EngineFleet([r0, r1], name="unit")
        try:
            r.configure(
                {"faults": [{"seam": "fleet.replica_dispatch",
                             "kind": "kill", "count": 1}]}
            )
            r.arm()
            # the kill unwinds whichever replica claims the batch; the
            # router spills the request to the survivor
            assert fleet.submit(4, timeout=5.0) == 4
            r.disarm()
            deadline = time.monotonic() + 2.0
            while (
                r0.alive() and r1.alive() and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert not (r0.alive() and r1.alive()), "no replica died"
            exposition = metrics.REGISTRY.expose()
            assert (
                'cedar_worker_deaths_total{component="batcher.worker",'
                'replica="r0"}' in exposition
                or 'cedar_worker_deaths_total{component="batcher.worker",'
                'replica="r1"}' in exposition
            )
            # supervisor-style revive puts the dead member back
            dead = r0 if not r0.alive() else r1
            assert fleet.revive_replica(dead.index) is True
            assert dead.alive()
        finally:
            r.reset()
            fleet.stop()


# --------------------------------------------------------------------------
# real-engine fleet (native fast paths)


@needs_native
class TestSingleReplicaParity:
    def test_fleet_of_one_is_byte_identical_to_single_engine(self):
        """>= 1.1k mixed bodies (clean allows/denies/no-opinions, encoder
        gates, parse errors): a fleet-of-1 server must answer BYTE-
        identically to the classic single-engine server — the router adds
        routing, never semantics."""
        from cedar_tpu.engine.fastpath import SARFastPath
        from cedar_tpu.server.admission import (
            CedarAdmissionHandler,
            allow_all_admission_policy_store,
        )

        bodies = [_sar_body(i) for i in range(1100)]

        def handler_for(fleet):
            stores = TieredPolicyStores(
                [MemoryStore.from_source("fleet", SAR_POLICIES)]
            )
            authorizer = CedarWebhookAuthorizer(stores)
            adm = CedarAdmissionHandler(
                TieredPolicyStores([allow_all_admission_policy_store()])
            )
            if fleet:
                _stores, authorizer, fl = _sar_stack(n_replicas=1)
                return WebhookServer(
                    authorizer, adm, fleet=fl, request_timeout_s=5.0
                ), fl
            engine = TPUPolicyEngine(name="single")
            engine.load([s.policy_set() for s in stores], warm="off")
            fast = SARFastPath(engine, authorizer)
            return WebhookServer(
                authorizer,
                adm,
                fastpath=fast,
                pipeline_depth=2,
                request_timeout_s=5.0,
            ), None

        classic, _ = handler_for(False)
        fleeted, fl = handler_for(True)
        try:
            classic_out = [
                json.dumps(classic.handle_authorize(b), sort_keys=True)
                for b in bodies
            ]
            fleet_out = [
                json.dumps(fleeted.handle_authorize(b), sort_keys=True)
                for b in bodies
            ]
            assert fleet_out == classic_out
        finally:
            classic.stop()
            fleeted.stop()


@needs_native
class TestFleetPromotion:
    def _controller(self, fleet):
        from cedar_tpu.rollout import RolloutController

        return RolloutController(
            authz_fleet=fleet,
            sample_rate=0.0,  # no shadow traffic needed for the swap tests
        )

    def _answers(self, fleet, bodies):
        """Per-replica serial answers — proves what each replica SERVES,
        not just what the router happens to route."""
        return [
            [sar_response(*r) for r in rep.fastpath.authorize_raw(bodies)]
            for rep in fleet.replicas
        ]

    def test_clean_promote_swaps_all_replicas_trace_free(self):
        _stores, _auth, fleet = _sar_stack(n_replicas=2)
        try:
            bodies = [_sar_body(i) for i in range(60)]
            before = self._answers(fleet, bodies)
            ctl = self._controller(fleet)
            ctl.stage(
                tiers=[PolicySet.from_source(CANDIDATE_POLICIES, "cand")],
                warm="sync",
            )
            tc0 = kernel_trace_count()
            ctl.promote()
            assert kernel_trace_count() == tc0, (
                "fleet promotion traced a fresh kernel on some replica"
            )
            after = self._answers(fleet, bodies)
            assert after[0] == after[1], "replicas diverged after promote"
            assert after != before, "the candidate really flips decisions"
            # generation barrier bumped every replica + the fleet epoch
            assert all(g >= 2 for g in fleet.load_generation)
        finally:
            fleet.stop()

    def test_partial_failure_leaves_every_replica_on_prior_set(self):
        """A chaos-injected failure on the SECOND replica's swap must
        restore the first — zero mixed-generation answers — and leave the
        lifecycle recoverable (the candidate stays staged; a re-promote
        after disarm succeeds)."""
        from cedar_tpu.rollout import RolloutError

        _stores, _auth, fleet = _sar_stack(n_replicas=2)
        registry = default_registry()
        try:
            bodies = [_sar_body(i) for i in range(60)]
            before = self._answers(fleet, bodies)
            ctl = self._controller(fleet)
            ctl.stage(
                tiers=[PolicySet.from_source(CANDIDATE_POLICIES, "cand")],
                warm="sync",
            )
            registry.configure(
                {"faults": [{"seam": "fleet.promote", "kind": "error",
                             "after": 1, "count": 1}]}
            )
            registry.arm()
            with pytest.raises(RolloutError, match="restored"):
                ctl.promote()
            registry.disarm()
            # EVERY replica serves the prior set — byte-identical answers
            assert self._answers(fleet, bodies) == before
            assert ctl.status()["state"] == "staged"
            # the lifecycle recovers: a clean promote lands
            ctl.promote()
            after = self._answers(fleet, bodies)
            assert after[0] == after[1] and after != before
        finally:
            registry.reset()
            fleet.stop()

    def test_rollback_restores_every_replica(self):
        _stores, _auth, fleet = _sar_stack(n_replicas=2)
        try:
            bodies = [_sar_body(i) for i in range(40)]
            before = self._answers(fleet, bodies)
            ctl = self._controller(fleet)
            ctl.stage(
                tiers=[PolicySet.from_source(CANDIDATE_POLICIES, "cand")],
                warm="sync",
            )
            ctl.promote()
            assert self._answers(fleet, bodies) != before
            tc0 = kernel_trace_count()
            ctl.rollback()
            assert kernel_trace_count() == tc0  # compile-free restore
            assert self._answers(fleet, bodies) == before
        finally:
            fleet.stop()

    def test_rollback_refuses_after_replica_lineage_divergence(self):
        """A store-driven reload landing on ONE replica after promotion
        makes the saved prior stale for the whole fleet: the per-replica
        generation tuple catches it and rollback refuses."""
        from cedar_tpu.rollout import RolloutError

        _stores, _auth, fleet = _sar_stack(n_replicas=2)
        try:
            ctl = self._controller(fleet)
            ctl.stage(
                tiers=[PolicySet.from_source(CANDIDATE_POLICIES, "cand")],
                warm="sync",
            )
            ctl.promote()
            fleet.replicas[1].engine.load(
                [PolicySet.from_source(SAR_POLICIES, "reload")], warm="off"
            )
            with pytest.raises(RolloutError, match="reloaded"):
                ctl.rollback()
        finally:
            fleet.stop()

    def test_reload_adoption_failure_restores_whole_fleet(self):
        """The reloader path carries the same no-mixed-generation
        invariant as promotion: replica 0 compiles and swaps, and if a
        later replica's adoption fails, replica 0 (and any adopted
        members) are restored to the PRIOR set before the error
        propagates — the reloader's 'serving previous set' stays true for
        the whole fleet."""
        _stores, _auth, fleet = _sar_stack(n_replicas=2)
        try:
            bodies = [_sar_body(i) for i in range(40)]
            before = self._answers(fleet, bodies)

            def boom(compiled, donor=None):
                raise RuntimeError("placement failed on a sick device")

            fleet.replicas[1].engine.adopt_compiled = boom
            with pytest.raises(RuntimeError, match="placement failed"):
                fleet.load(
                    [PolicySet.from_source(CANDIDATE_POLICIES, "reload")],
                    warm="off",
                )
            # EVERY replica — including the one that compiled — serves
            # the prior set
            assert self._answers(fleet, bodies) == before
        finally:
            fleet.stop()

    def test_cache_epoch_invalidates_on_fleet_swap(self):
        """The decision cache's composite generation folds the fleet
        epoch: a fleet-wide swap kills every cached decision, so no
        replica can answer from a stale policy set."""
        from cedar_tpu.cache import DecisionCache

        stores, _auth, fleet = _sar_stack(n_replicas=2)
        try:
            cache = DecisionCache(
                max_entries=64,
                generation_fn=lambda: (
                    stores.cache_generation(),
                    fleet.cache_epoch(),
                ),
                path="authorization",
            )
            cache.put("k", ("allow", "r"), "allow")
            assert cache.get("k") == ("allow", "r")
            ctl = self._controller(fleet)
            ctl.stage(
                tiers=[PolicySet.from_source(CANDIDATE_POLICIES, "cand")],
                warm="sync",
            )
            ctl.promote()
            assert cache.get("k") is None, (
                "a pre-promotion cached decision survived the fleet swap"
            )
        finally:
            fleet.stop()


@needs_native
class TestDebugEndpoints:
    def test_debug_fleet_and_per_replica_engine(self):
        from cedar_tpu.server.admission import (
            CedarAdmissionHandler,
            allow_all_admission_policy_store,
        )

        _stores, authorizer, fleet = _sar_stack(n_replicas=2)
        adm = CedarAdmissionHandler(
            TieredPolicyStores([allow_all_admission_policy_store()])
        )
        server = WebhookServer(
            authorizer,
            adm,
            fleet=fleet,
            address="127.0.0.1",
            port=0,
            metrics_port=0,
        )
        server.start()
        try:
            port, mport = server.bound_port, server.bound_metrics_port
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/authorize",
                data=_sar_body(0),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
            with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/debug/fleet", timeout=30
            ) as resp:
                doc = json.loads(resp.read())
            assert doc["fleet"] == "fleet-test"
            assert [r["name"] for r in doc["replicas"]] == ["r0", "r1"]
            for r in doc["replicas"]:
                assert r["state"] == "active" and r["alive"] is True
                assert "breaker" in r or r["admits"] is True
            assert doc["router"]["routed"]  # the request above was routed
            with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/debug/engine", timeout=30
            ) as resp:
                eng = json.loads(resp.read())
            replicas = eng["authorization"]["replicas"]
            assert set(replicas) == {"r0", "r1"}
            for entry in replicas.values():
                assert entry["pipeline"]["mode"] == "pipelined"
                assert "warm_ready" in entry["engine"]
                assert "health" in entry
            # the fleet state gauge published per replica
            exposition = metrics.REGISTRY.expose()
            assert 'cedar_fleet_replica_state{fleet="fleet-test"' in (
                exposition
            )
        finally:
            server.stop()


# --------------------------------------------------------------------------
# replica-loss game day (chaos suite)


@needs_native
@pytest.mark.chaos
@pytest.mark.slow
class TestReplicaLossGameDay:
    def test_replica_kill_holds_availability_and_revives(self):
        """The acceptance game day, in-process: with 2 replicas serving a
        deterministic SAR stream, killing one replica's worker holds
        availability >= 99.5% with ZERO decision flips, the supervisor
        revives it, and the fleet serves on both replicas afterwards."""
        from cedar_tpu.cli.chaos import make_sar_stream
        from cedar_tpu.server.supervisor import HeartbeatGroup, Supervisor

        _stores, _auth, fleet = _sar_stack(n_replicas=2, breakers=True)
        registry = default_registry()
        supervisor = Supervisor(interval_s=0.05, wedge_budget_s=5.0)
        for rep in fleet.replicas:
            supervisor.register(
                "batcher.fleet-test",
                replica=rep.name,
                threads=lambda rr=rep: list(rr.batcher._threads),
                restart=lambda reason, i=rep.index: fleet.revive_replica(
                    i, force=reason.startswith("wedged")
                ),
                heartbeat=HeartbeatGroup(lambda rr=rep: rr.batcher.heartbeats),
            )
        supervisor.start()
        try:
            stream = make_sar_stream(300, seed=5)
            control = [fleet.submit(b, timeout=10.0) for b in stream]
            registry.configure(
                {
                    "faults": [
                        {"seam": "fleet.replica_dispatch", "kind": "kill",
                         "after": 10, "count": 1}
                    ]
                }
            )
            registry.arm()
            clean = 0
            flips = 0
            for body, expected in zip(stream, control):
                try:
                    got = fleet.submit(body, timeout=10.0)
                except Exception:  # noqa: BLE001 — counted as unavailability
                    continue
                if got[2] is None:
                    clean += 1
                    if (got[0], got[1]) != (expected[0], expected[1]):
                        flips += 1
            registry.disarm()
            availability = clean / len(stream)
            assert availability >= 0.995, f"availability {availability}"
            assert flips == 0, f"{flips} decision flips under replica loss"
            # the kill really fired and really killed a replica worker
            fired = sum(
                sum(r.get("fired", 0) for r in s["rules"])
                for s in registry.stats()["seams"].values()
            )
            assert fired == 1
            # supervisor revives the dead member
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if all(rep.alive() for rep in fleet.replicas):
                    break
                time.sleep(0.02)
            assert all(rep.alive() for rep in fleet.replicas), (
                "the supervisor never revived the killed replica"
            )
            restarts = sum(
                c["restarts"]
                for c in supervisor.status()["components"].values()
            )
            assert restarts >= 1
            # post-recovery: the stream answers identically again
            recovered = [fleet.submit(b, timeout=10.0) for b in stream]
            assert recovered == control
        finally:
            registry.reset()
            supervisor.stop()
            fleet.stop()
