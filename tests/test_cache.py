"""Decision cache & request-coalescing subsystem (cedar_tpu/cache).

Covers the canonical fingerprinter (shared by the cache, the recorder, and
the replay CLI), the sharded LRU+TTL cache with generation invalidation,
the singleflight coalescer, MicroBatcher waiter accounting under
coalescing, the webhook-server wiring (a hit must answer WITHOUT a
MicroBatcher.submit), the cached-vs-uncached differential (byte-identical
across 1k fuzzed SARs, including across a policy reload), and the
breaker-open + warm-cache chaos behavior.
"""

from __future__ import annotations

import json
import random
import threading
import time
import types

import pytest

from cedar_tpu.cache import (
    DecisionCache,
    FingerprintMemo,
    SingleFlight,
    fingerprint_admission_request,
    fingerprint_attributes,
    fingerprint_body,
)
from cedar_tpu.engine.batcher import DeadlineExceeded, MicroBatcher
from cedar_tpu.entities.admission import AdmissionRequest
from cedar_tpu.entities.attributes import (
    Attributes,
    LabelSelectorRequirement,
    UserInfo,
)
from cedar_tpu.lang import PolicySet
from cedar_tpu.server.admission import (
    CedarAdmissionHandler,
    allow_all_admission_policy_store,
    cacheable_admission_request,
)
from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
from cedar_tpu.server.http import WebhookServer, get_authorizer_attributes
from cedar_tpu.server.recorder import RequestRecorder
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

DEMO_POLICY = """
permit (
    principal,
    action in [k8s::Action::"get", k8s::Action::"list", k8s::Action::"watch"],
    resource is k8s::Resource
) when { principal.name == "test-user" && resource.resource == "pods" };
forbid (
    principal is k8s::User,
    action == k8s::Action::"get",
    resource is k8s::Resource
) when { principal.name == "test-user" && resource.resource == "nodes" };
"""


def make_sar(user="test-user", verb="get", resource="pods", **ra_extra):
    return {
        "apiVersion": "authorization.k8s.io/v1",
        "kind": "SubjectAccessReview",
        "spec": {
            "user": user,
            "uid": "u1",
            "groups": ["dev"],
            "resourceAttributes": {
                "verb": verb,
                "resource": resource,
                "version": "v1",
                **ra_extra,
            },
        },
    }


class MutableStore:
    """A reloadable policy store: swap() models a CRD watch update —
    content changes and the generation counter bumps."""

    def __init__(self, name, policy_set):
        self._name = name
        self._ps = policy_set
        self._gen = 1

    def policy_set(self):
        return self._ps

    def initial_policy_load_complete(self):
        return True

    def name(self):
        return self._name

    def content_generation(self):
        return self._gen

    def swap(self, policy_set):
        self._ps = policy_set
        self._gen += 1


def make_server(policy_src=DEMO_POLICY, cache=None, store=None):
    if store is None:
        store = MemoryStore.from_source("test", policy_src)
    stores = TieredPolicyStores([store])
    authorizer = CedarWebhookAuthorizer(stores)
    handler = CedarAdmissionHandler(
        TieredPolicyStores([store, allow_all_admission_policy_store()])
    )
    return (
        WebhookServer(authorizer, handler, decision_cache=cache),
        stores,
    )


# --------------------------------------------------------------- fingerprint


class TestFingerprint:
    def test_wire_variation_is_canonicalized(self):
        sar = make_sar()
        compact = json.dumps(sar, separators=(",", ":")).encode()
        pretty = json.dumps(sar, indent=4).encode()
        reordered = json.dumps(
            {k: sar[k] for k in reversed(list(sar))}
        ).encode()
        fps = {
            fingerprint_body("authorize", b)
            for b in (compact, pretty, reordered)
        }
        assert len(fps) == 1 and None not in fps

    def test_group_and_extra_order_insensitive(self):
        a = Attributes(
            user=UserInfo(
                name="u", groups=("b", "a"), extra={"k": ("2", "1")}
            ),
            verb="get",
            resource="pods",
            resource_request=True,
        )
        b = Attributes(
            user=UserInfo(
                name="u", groups=("a", "b"), extra={"k": ("1", "2")}
            ),
            verb="get",
            resource="pods",
            resource_request=True,
        )
        assert fingerprint_attributes(a) == fingerprint_attributes(b)

    def test_selector_order_insensitive(self):
        def attrs(reqs):
            return Attributes(
                user=UserInfo(name="u"),
                verb="list",
                resource="pods",
                resource_request=True,
                label_selector=reqs,
            )

        r1 = LabelSelectorRequirement("env", "in", ("prod",))
        r2 = LabelSelectorRequirement("tier", "exists", ())
        assert fingerprint_attributes(attrs((r1, r2))) == (
            fingerprint_attributes(attrs((r2, r1)))
        )

    def test_decision_relevant_fields_split_keys(self):
        base = fingerprint_body(
            "authorize", json.dumps(make_sar()).encode()
        )
        for variant in (
            make_sar(user="other"),
            make_sar(verb="delete"),
            make_sar(resource="secrets"),
            make_sar(namespace="web"),
            make_sar(subresource="status"),
            make_sar(name="x"),
        ):
            assert (
                fingerprint_body("authorize", json.dumps(variant).encode())
                != base
            )

    def test_non_resource_vs_resource_distinct(self):
        nr = {"spec": {"user": "u", "nonResourceAttributes": {
            "path": "/healthz", "verb": "get"}}}
        r = make_sar(user="u")
        assert fingerprint_body(
            "authorize", json.dumps(nr).encode()
        ) != fingerprint_body("authorize", json.dumps(r).encode())

    def test_unparseable_body_is_unkeyed(self):
        assert fingerprint_body("authorize", b"{not json") is None
        assert fingerprint_body("authorize", b"[1,2]") is None

    def test_admission_fp_excludes_uid_nonce(self):
        def review(uid):
            return {
                "request": {
                    "uid": uid,
                    "operation": "CONNECT",
                    "userInfo": {"username": "bob"},
                    "kind": {"group": "", "version": "v1", "kind": "Pod"},
                    "namespace": "default",
                    "name": "p",
                }
            }

        f1 = fingerprint_admission_request(
            AdmissionRequest.from_admission_review(review("aaa"))
        )
        f2 = fingerprint_admission_request(
            AdmissionRequest.from_admission_review(review("bbb"))
        )
        assert f1 == f2

    def test_admission_fp_tracks_object_content(self):
        def review(data):
            return AdmissionRequest.from_admission_review(
                {
                    "request": {
                        "uid": "u",
                        "operation": "CREATE",
                        "kind": {"group": "", "version": "v1",
                                 "kind": "ConfigMap"},
                        "object": {"metadata": {"name": "c"}, "data": data},
                    }
                }
            )

        assert fingerprint_admission_request(
            review({"a": "1"})
        ) != fingerprint_admission_request(review({"a": "2"}))

    def test_memo_parses_each_unique_body_once(self, monkeypatch):
        calls = {"n": 0}
        import cedar_tpu.cache.fingerprint as fp_mod

        real = fp_mod.fingerprint_body

        def counting(endpoint, body):
            calls["n"] += 1
            return real(endpoint, body)

        monkeypatch.setattr(fp_mod, "fingerprint_body", counting)
        memo = FingerprintMemo(capacity=8)
        body = json.dumps(make_sar()).encode()
        fps = [memo.fingerprint("authorize", body) for _ in range(5)]
        assert len(set(fps)) == 1 and calls["n"] == 1

    def test_memo_capacity_bounded(self):
        memo = FingerprintMemo(capacity=4)
        for i in range(16):
            memo.fingerprint(
                "authorize", json.dumps(make_sar(name=f"n{i}")).encode()
            )
        assert len(memo._memo) <= 4

    def test_memo_counts_a_hit_and_a_miss_for_what_they_were(self):
        memo = FingerprintMemo(capacity=2)
        bodies = [json.dumps(make_sar(name=f"n{i}")).encode() for i in range(3)]
        assert memo.counts() == (0, 0)
        fp, hit = memo.lookup("authorize", bodies[0])
        assert fp is not None and hit is False
        assert memo.lookup("authorize", bodies[0]) == (fp, True)
        assert memo.fingerprint("authorize", bodies[0]) == fp
        assert memo.counts() == (2, 1)
        # a body pushed out by the capacity is a miss again
        memo.lookup("authorize", bodies[1])
        memo.lookup("authorize", bodies[2])
        assert memo.lookup("authorize", bodies[0])[1] is False
        assert memo.counts() == (2, 4)
        # a body that does not parse is remembered as such, and counted
        assert memo.lookup("authorize", b"{") == (None, False)
        assert memo.lookup("authorize", b"{") == (None, True)
        assert memo.counts() == (3, 5)


# ------------------------------------------------------------ decision cache


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


class TestDecisionCache:
    def test_hit_miss_and_lru_bound(self):
        cache = DecisionCache(max_entries=8, shards=2)
        assert cache.get("k") is None
        cache.put("k", ("allow", ""), "allow")
        assert cache.get("k") == ("allow", "")
        for i in range(64):
            cache.put(f"k{i}", ("allow", ""), "allow")
        assert cache.size() <= 8

    def test_split_ttls_per_decision_class(self):
        clock = FakeClock()
        cache = DecisionCache(
            allow_ttl_s=300, deny_ttl_s=30, no_opinion_ttl_s=5, clock=clock
        )
        cache.put("a", ("allow", ""), "allow")
        cache.put("d", ("deny", "r"), "deny")
        cache.put("n", ("no_opinion", ""), "no_opinion")
        clock.now += 6
        assert cache.get("n") is None  # no-opinion TTL (5s) elapsed
        assert cache.get("d") == ("deny", "r")
        clock.now += 26
        assert cache.get("d") is None  # deny TTL (30s) elapsed
        assert cache.get("a") == ("allow", "")
        clock.now += 300
        assert cache.get("a") is None

    def test_zero_ttl_disables_class(self):
        cache = DecisionCache(no_opinion_ttl_s=0)
        assert not cache.put("n", ("no_opinion", ""), "no_opinion")
        assert cache.get("n") is None
        assert cache.put("a", ("allow", ""), "allow")

    def test_generation_invalidation_without_scan(self):
        gen = {"v": (1,)}
        cache = DecisionCache(generation_fn=lambda: gen["v"])
        cache.put("k", ("allow", ""), "allow")
        assert cache.get("k") == ("allow", "")
        gen["v"] = (2,)  # policy reload
        assert cache.get("k") is None
        cache.put("k", ("deny", ""), "deny")
        assert cache.get("k") == ("deny", "")

    def test_stats_and_invalidate_all(self):
        cache = DecisionCache()
        cache.put("k", ("allow", ""), "allow")
        cache.get("k")
        cache.get("missing")
        st = cache.stats()
        assert st["hits"] == 1 and st["misses"] == 1 and st["size"] == 1
        assert 0 < st["hit_ratio"] < 1
        assert cache.invalidate_all() == 1
        assert cache.size() == 0

    def test_tiered_stores_cache_generation_moves_on_swap(self):
        store = MutableStore("m", PolicySet.from_source(DEMO_POLICY, "m"))
        stores = TieredPolicyStores([store])
        g1 = stores.cache_generation()
        store.swap(PolicySet.from_source("permit (principal, action, resource);", "m"))
        assert stores.cache_generation() != g1

    def test_engine_load_generation_bumps_composite_generation(self):
        """On the compiled backend the cache generation folds in the
        engine's load counter (cli/webhook wiring), so entries computed
        from the OLD compiled set during the recompile window die when the
        engine actually swaps — not merely when store content changes."""
        from cedar_tpu.engine.evaluator import TPUPolicyEngine

        engine = TPUPolicyEngine()
        assert engine.load_generation == 0
        ps = PolicySet.from_source(DEMO_POLICY, "m")
        engine.load([ps], warm="off")
        assert engine.load_generation == 1
        stores = TieredPolicyStores([MemoryStore("m", ps)])
        gen_fn = lambda: (stores.cache_generation(), engine.load_generation)  # noqa: E731
        cache = DecisionCache(generation_fn=gen_fn)
        cache.put("k", ("allow", ""), "allow")
        assert cache.get("k") == ("allow", "")
        engine.load([ps], warm="off")  # recompile swap, content unchanged
        assert cache.get("k") is None  # entry died with the engine swap

    def test_cache_generation_proxy_for_counterless_store(self):
        class Foreign:
            def __init__(self):
                self._ps = PolicySet.from_source(DEMO_POLICY, "f")

            def policy_set(self):
                return self._ps

            def initial_policy_load_complete(self):
                return True

            def name(self):
                return "foreign"

        f = Foreign()
        stores = TieredPolicyStores([f])
        g1 = stores.cache_generation()
        assert stores.cache_generation() == g1  # stable while content is
        f._ps = PolicySet.from_source("permit (principal, action, resource);", "f")
        assert stores.cache_generation() != g1  # swap moves the proxy


# -------------------------------------------------------------- singleflight


class TestSingleFlight:
    def test_leader_passthrough(self):
        sf = SingleFlight()
        value, leader = sf.do("k", lambda: 42)
        assert (value, leader) == (42, True)
        assert sf.in_flight() == 0

    def test_concurrent_identical_requests_evaluate_once(self):
        sf = SingleFlight()
        release = threading.Event()
        calls = []
        results = []

        def fn():
            calls.append(1)
            release.wait(5)
            return "decision"

        def worker():
            results.append(sf.do("k", fn, timeout=5))

        threads = [threading.Thread(target=worker) for _ in range(8)]
        [t.start() for t in threads]
        deadline = time.monotonic() + 5
        while sf.in_flight() == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)  # let followers attach
        release.set()
        [t.join(5) for t in threads]
        assert len(calls) == 1
        assert len(results) == 8
        assert all(v == "decision" for v, _ in results)
        assert sum(1 for _, leader in results if leader) == 1

    def test_follower_timeout_detaches_without_cancelling_leader(self):
        sf = SingleFlight()
        release = threading.Event()
        leader_result = []

        def fn():
            release.wait(5)
            return "late"

        def leader():
            leader_result.append(sf.do("k", fn, timeout=None))

        t = threading.Thread(target=leader)
        t.start()
        deadline = time.monotonic() + 5
        while sf.in_flight() == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(DeadlineExceeded):
            sf.do("k", lambda: "never-called", timeout=0.05)
        release.set()
        t.join(5)
        assert leader_result == [("late", True)]

    def test_leader_error_fans_out_fresh_exceptions(self):
        sf = SingleFlight()
        release = threading.Event()
        errors = []

        def fn():
            release.wait(5)
            raise ValueError("boom")

        def leader():
            try:
                sf.do("k", fn)
            except ValueError as e:
                errors.append(e)

        def follower():
            try:
                sf.do("k", lambda: None, timeout=5)
            except RuntimeError as e:
                errors.append(e)

        tl = threading.Thread(target=leader)
        tl.start()
        deadline = time.monotonic() + 5
        while sf.in_flight() == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        tf = threading.Thread(target=follower)
        tf.start()
        time.sleep(0.05)
        release.set()
        tl.join(5)
        tf.join(5)
        assert len(errors) == 2
        # the leader re-raises the original; followers get a FRESH wrapper
        # chained to it (never the shared object)
        leader_err = next(e for e in errors if isinstance(e, ValueError))
        follower_err = next(e for e in errors if isinstance(e, RuntimeError))
        assert follower_err is not leader_err
        assert follower_err.__cause__ is leader_err


# ------------------------------------------- MicroBatcher waiter accounting


class TestMicroBatcherCoalescing:
    def test_coalesced_submits_share_one_queue_slot(self):
        seen_batches = []
        gate = threading.Event()

        def fn(items):
            if not gate.is_set():
                gate.wait(5)
            seen_batches.append(list(items))
            return [f"r:{it.decode()}" for it in items]

        # window long enough for both submitters to land in ONE batch
        b = MicroBatcher(fn, window_s=0.2)
        try:
            results = []

            def worker():
                results.append(
                    b.submit(b"x", timeout=5, coalesce_key="k")
                )

            threads = [threading.Thread(target=worker) for _ in range(4)]
            [t.start() for t in threads]
            time.sleep(0.05)  # all four attach within the forming window
            gate.set()
            [t.join(5) for t in threads]
            assert results == ["r:x"] * 4
            assert sum(len(batch) for batch in seen_batches) == 1
        finally:
            gate.set()
            b.stop()

    def test_follower_timeout_does_not_withdraw_leader_slot(self):
        release = threading.Event()

        def fn(items):
            release.wait(5)
            return [i.decode().upper() for i in items]

        b = MicroBatcher(fn, window_s=0.3)
        try:
            leader_out = []
            leader = threading.Thread(
                target=lambda: leader_out.append(
                    b.submit(b"x", timeout=5, coalesce_key="k")
                )
            )
            leader.start()
            time.sleep(0.02)
            # follower with a tiny budget: expires during the forming
            # window, while the shared entry is still queued
            with pytest.raises(DeadlineExceeded):
                b.submit(b"x", timeout=0.05, coalesce_key="k")
            # the leader's queue slot must have survived the withdrawal
            release.set()
            leader.join(5)
            assert leader_out == ["X"]
        finally:
            release.set()
            b.stop()

    def test_all_waiters_withdrawing_removes_entry_and_future(self):
        batches = []
        started = threading.Event()

        def fn(items):
            batches.append(list(items))
            return [i for i in items]

        b = MicroBatcher(fn, window_s=10.0)  # nothing fires inside the test
        try:
            started.set()
            withdrawers = []

            def worker():
                try:
                    b.submit(b"x", timeout=0.05, coalesce_key="k")
                except DeadlineExceeded:
                    withdrawers.append(1)

            threads = [threading.Thread(target=worker) for _ in range(3)]
            [t.start() for t in threads]
            [t.join(5) for t in threads]
            assert len(withdrawers) == 3
            with b._cv:
                assert not b._queue  # entry withdrawn by the last waiter
                assert not b._pending  # no leaked result future
        finally:
            b.stop(drain_timeout_s=0.5)

    def test_post_claim_submit_enqueues_fresh_work(self):
        batches = []

        def fn(items):
            batches.append(list(items))
            return [i for i in items]

        b = MicroBatcher(fn, window_s=0.0001)
        try:
            b.submit(b"x", timeout=5, coalesce_key="k")
            b.submit(b"x", timeout=5, coalesce_key="k")
            # both completed: the claim dropped the pending registration,
            # so the second submit evaluated fresh instead of reading a
            # stale shared slot
            assert sum(len(batch) for batch in batches) == 2
        finally:
            b.stop()

    def test_plain_submit_unaffected(self):
        b = MicroBatcher(lambda items: [i * 2 for i in items], window_s=0.0001)
        try:
            assert b.submit(21, timeout=5) == 42
        finally:
            b.stop()


# ------------------------------------------------------------ server wiring


class CountingBatcher:
    """A stand-in for the fastpath micro-batcher that counts submits."""

    def __init__(self, result=("allow", "", None)):
        self.calls = 0
        self.result = result

    def submit(self, body, timeout=None, coalesce_key=None):
        self.calls += 1
        return self.result

    def stop(self, drain_timeout_s: float = 2.0):
        pass


class TestServerCaching:
    def test_hit_returns_without_microbatcher_submit(self):
        cache = DecisionCache()
        server, _ = make_server(cache=cache)
        batcher = CountingBatcher()
        server._batcher = batcher
        server.fastpath = types.SimpleNamespace(available=True, breaker=None)
        body = json.dumps(make_sar()).encode()
        r1 = server.handle_authorize(body)
        assert batcher.calls == 1 and r1["status"]["allowed"]
        for _ in range(5):
            assert server.handle_authorize(body) == r1
        assert batcher.calls == 1  # every repeat answered from cache

    def test_decision_classes_cached_and_correct(self):
        cache = DecisionCache()
        server, _ = make_server(cache=cache)
        cases = {
            "pods": ("allowed", True),
            "nodes": ("denied", True),
            "secrets": ("allowed", False),  # no opinion
        }
        for resource, (field, value) in cases.items():
            body = json.dumps(make_sar(resource=resource)).encode()
            first = server.handle_authorize(body)
            assert first["status"].get(field, False) is value
            assert server.handle_authorize(body) == first
        assert cache.stats()["hits"] == len(cases)

    def test_short_circuits_still_cached_consistently(self):
        # system:* skip and the authorizer self-allow are deterministic on
        # attributes, so caching them is safe — verify round trips
        cache = DecisionCache()
        server, _ = make_server(cache=cache)
        body = json.dumps(make_sar(user="system:kube-scheduler")).encode()
        r1 = server.handle_authorize(body)
        assert not r1["status"]["allowed"] and not r1["status"]["denied"]
        assert server.handle_authorize(body) == r1

    def test_no_caching_until_stores_ready(self):
        cache = DecisionCache()
        store = MemoryStore.from_source(
            "late", DEMO_POLICY, load_complete=False
        )
        server, _ = make_server(cache=cache, store=store)
        body = json.dumps(make_sar()).encode()
        r = server.handle_authorize(body)
        assert not r["status"]["allowed"]  # NoOpinion while loading
        assert cache.size() == 0  # startup artifact not cached
        store._load_complete = True
        assert server.handle_authorize(body)["status"]["allowed"]
        assert cache.size() == 1

    def test_decode_errors_never_cached(self):
        cache = DecisionCache()
        server, _ = make_server(cache=cache)
        r = server.handle_authorize(b"{not json")
        assert r["status"]["reason"] == "Encountered decoding error"
        assert cache.size() == 0

    def test_debug_cache_endpoint(self):
        import urllib.request

        cache = DecisionCache()
        server, _ = make_server(cache=cache)
        server.certfile = server.keyfile = None
        server.port = 0
        server.metrics_port = 0
        server.start()
        try:
            server.handle_authorize(json.dumps(make_sar()).encode())
            server.handle_authorize(json.dumps(make_sar()).encode())
            port = server.bound_metrics_port
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/cache", timeout=5
            ) as resp:
                doc = json.loads(resp.read())
            assert doc["authorization"]["hits"] == 1
            assert doc["authorization"]["size"] == 1
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5
            ) as resp:
                text = resp.read().decode()
            assert 'cedar_decision_cache_hits_total{path="authorization"} 1' in text
            assert "cedar_decision_cache_hit_ratio" in text
        finally:
            server.stop()

    def test_concurrent_identical_misses_coalesce_to_one_evaluation(self):
        cache = DecisionCache()
        server, _ = make_server(cache=cache)
        release = threading.Event()
        calls = []

        real = server._authorize_uncached

        def slow_uncached(body, request_id, coalesce_key=None, **kw):
            calls.append(1)
            release.wait(5)
            return real(body, request_id, coalesce_key=coalesce_key, **kw)

        server._authorize_uncached = slow_uncached
        body = json.dumps(make_sar()).encode()
        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(server.handle_authorize(body))
            )
            for _ in range(6)
        ]
        [t.start() for t in threads]
        deadline = time.monotonic() + 5
        while not calls and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)  # followers attach to the in-flight leader
        release.set()
        [t.join(5) for t in threads]
        assert len(results) == 6
        assert all(r["status"]["allowed"] for r in results)
        assert len(calls) == 1  # one evaluation for six concurrent arrivals

    def test_follower_deadline_answers_no_opinion_leader_warms_cache(self):
        cache = DecisionCache()
        server, _ = make_server(cache=cache)
        server.request_timeout_s = 0.05
        release = threading.Event()
        entered = threading.Event()

        real = server._authorize_uncached

        def slow_uncached(body, request_id, coalesce_key=None, **kw):
            entered.set()
            release.wait(5)
            return real(body, request_id, coalesce_key=coalesce_key, **kw)

        server._authorize_uncached = slow_uncached
        body = json.dumps(make_sar()).encode()
        leader_out = []
        t = threading.Thread(
            target=lambda: leader_out.append(server.handle_authorize(body))
        )
        t.start()
        assert entered.wait(5)
        follower = server.handle_authorize(body)  # expires at 50ms
        assert follower["status"]["evaluationError"]
        assert not follower["status"]["allowed"]
        release.set()
        t.join(5)
        assert leader_out[0]["status"]["allowed"]  # leader unaffected
        assert cache.size() == 1  # and its result warmed the cache


# ------------------------------------------------------- differential + gen


def _fuzz_sar_bodies(n, seed=11):
    """n raw SAR bodies over a small vocabulary with heavy repetition and
    wire-format variation (indent/key-order), like real apiserver traffic."""
    rng = random.Random(seed)
    users = ["test-user", "alice", "bob", "system:serviceaccount:ns:sa"]
    verbs = ["get", "list", "watch", "delete", "create"]
    resources = ["pods", "nodes", "secrets", "configmaps", "deployments"]
    nss = ["", "default", "web", "kube-system"]
    bodies = []
    for _ in range(n):
        sar = make_sar(
            user=rng.choice(users),
            verb=rng.choice(verbs),
            resource=rng.choice(resources),
        )
        ns = rng.choice(nss)
        if ns:
            sar["spec"]["resourceAttributes"]["namespace"] = ns
        if rng.random() < 0.2:
            sar["spec"]["groups"] = rng.sample(
                ["dev", "ops", "viewers"], rng.randint(0, 3)
            )
        if rng.random() < 0.1:
            sar = {
                "spec": {
                    "user": rng.choice(users),
                    "nonResourceAttributes": {
                        "path": rng.choice(["/healthz", "/metrics"]),
                        "verb": "get",
                    },
                }
            }
        dump = (
            json.dumps(sar, indent=2)
            if rng.random() < 0.3
            else json.dumps(sar, sort_keys=rng.random() < 0.5)
        )
        bodies.append(dump.encode())
    return bodies


RELOADED_POLICY = """
permit (
    principal,
    action in [k8s::Action::"get", k8s::Action::"list", k8s::Action::"watch"],
    resource is k8s::Resource
) when { principal.name == "test-user" && resource.resource == "nodes" };
forbid (
    principal is k8s::User,
    action == k8s::Action::"get",
    resource is k8s::Resource
) when { principal.name == "test-user" && resource.resource == "pods" };
"""


class TestDifferential:
    def test_cached_and_uncached_byte_identical_across_reload(self):
        """Acceptance: the cache introduces ZERO decision changes vs the
        uncached engine across 1k fuzzed SARs, including across a policy
        reload; after the reload every request misses (generation bump)."""
        store_c = MutableStore("m", PolicySet.from_source(DEMO_POLICY, "m"))
        store_u = MutableStore("m", PolicySet.from_source(DEMO_POLICY, "m"))
        cache = DecisionCache(generation_fn=None)
        cached, stores_c = make_server(cache=cache, store=store_c)
        cache._generation_fn = stores_c.cache_generation
        uncached, _ = make_server(cache=None, store=store_u)

        bodies = _fuzz_sar_bodies(1000)
        half = len(bodies) // 2
        for body in bodies[:half]:
            a = json.dumps(cached.handle_authorize(body), sort_keys=True)
            b = json.dumps(uncached.handle_authorize(body), sort_keys=True)
            assert a == b
        assert cache.stats()["hits"] > 100  # the stream really repeats

        # CRD-watch-style reload that INVERTS pods/nodes decisions: any
        # stale entry served post-reload shows up as a differential break
        new_ps_c = PolicySet.from_source(RELOADED_POLICY, "m")
        new_ps_u = PolicySet.from_source(RELOADED_POLICY, "m")
        store_c.swap(new_ps_c)
        store_u.swap(new_ps_u)

        st = cache.stats()
        hits_before, misses_before = st["hits"], st["misses"]
        post_keys = set()
        for body in bodies[half:]:
            a = json.dumps(cached.handle_authorize(body), sort_keys=True)
            b = json.dumps(uncached.handle_authorize(body), sort_keys=True)
            assert a == b
            post_keys.add(fingerprint_body("authorize", body))
        st = cache.stats()
        # every post-reload FIRST encounter of a key must miss; repeats may
        # hit again (they are post-reload entries). So misses grew by at
        # least the unique key count of the post-reload stream.
        assert st["misses"] - misses_before >= len(post_keys)
        assert st["hits"] - hits_before <= (half - len(post_keys))

    def test_mid_evaluation_reload_does_not_pin_stale_entry(self):
        """A reload landing while the leader evaluates must not let the
        pre-reload decision survive under the post-reload generation: the
        entry is stamped with the generation snapshot taken BEFORE
        evaluation, so the first post-reload lookup kills it."""
        store = MutableStore("m", PolicySet.from_source(DEMO_POLICY, "m"))
        cache = DecisionCache()
        server, stores = make_server(cache=cache, store=store)
        cache._generation_fn = stores.cache_generation
        body = json.dumps(make_sar(resource="pods")).encode()

        real = server._authorize_uncached
        fired = []

        def reload_mid_eval(b, request_id, coalesce_key=None, **kw):
            res = real(b, request_id, coalesce_key=coalesce_key, **kw)
            if not fired:  # the reload lands AFTER evaluation, BEFORE put
                fired.append(1)
                store.swap(PolicySet.from_source(RELOADED_POLICY, "m"))
            return res

        server._authorize_uncached = reload_mid_eval
        r1 = server.handle_authorize(body)
        assert r1["status"]["allowed"]  # evaluated pre-reload: allow
        # the stale allow was stamped pre-reload, so it must NOT be served
        # now that the generation has moved
        r2 = server.handle_authorize(body)
        assert r2["status"]["denied"]

    def test_admission_error_verdicts_never_cached(self):
        """A raising store tier reads as Deny-with-errors; caching that
        deny would pin a transient failure for the deny TTL."""
        from cedar_tpu.lang.authorize import DENY, Diagnostics

        cache = DecisionCache(path="admission")
        calls = []

        def erroring_evaluate(entities, req):
            calls.append(1)
            return DENY, Diagnostics(errors=["store x: boom"])

        handler = CedarAdmissionHandler(
            TieredPolicyStores([allow_all_admission_policy_store()]),
            evaluate=erroring_evaluate,
            cache=cache,
        )
        for _ in range(3):
            r = handler.handle(
                AdmissionRequest.from_admission_review(connect_review())
            )
            assert not r.allowed
        assert len(calls) == 3  # re-evaluated every time
        assert cache.size() == 0  # the errored deny never entered the cache

    def test_reload_flips_served_decision(self):
        store = MutableStore("m", PolicySet.from_source(DEMO_POLICY, "m"))
        cache = DecisionCache()
        server, stores = make_server(cache=cache, store=store)
        cache._generation_fn = stores.cache_generation
        body = json.dumps(make_sar(resource="pods")).encode()
        assert server.handle_authorize(body)["status"]["allowed"]
        assert server.handle_authorize(body)["status"]["allowed"]  # hit
        store.swap(PolicySet.from_source(RELOADED_POLICY, "m"))
        r = server.handle_authorize(body)  # post-reload: MUST miss + deny
        assert r["status"]["denied"]


# ----------------------------------------------------------------- admission


def connect_review(uid="u1", name="pod-a", dry_run=False):
    req = {
        "uid": uid,
        "operation": "CONNECT",
        "userInfo": {"username": "bob", "groups": ["tenants"]},
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "resource": {"group": "", "version": "v1", "resource": "pods"},
        "namespace": "default",
        "name": name,
        "object": {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
        },
    }
    if dry_run:
        req["dryRun"] = True
    return {"request": req}


class TestAdmissionCaching:
    def make_handler(self, cache):
        stores = TieredPolicyStores(
            [
                MemoryStore.from_source(
                    "adm",
                    'forbid (principal, action == k8s::admission::Action::"connect", '
                    'resource) when { resource.metadata.name == "blocked" };',
                ),
                allow_all_admission_policy_store(),
            ]
        )
        calls = []
        real = stores.is_authorized

        def counting(entities, req):
            calls.append(1)
            return real(entities, req)

        return (
            CedarAdmissionHandler(stores, evaluate=counting, cache=cache),
            calls,
        )

    def test_cacheable_gate(self):
        assert cacheable_admission_request(
            AdmissionRequest.from_admission_review(connect_review())
        )
        assert cacheable_admission_request(
            AdmissionRequest.from_admission_review(
                {"request": {"uid": "u", "operation": "CREATE",
                             "dryRun": True}}
            )
        )
        assert not cacheable_admission_request(
            AdmissionRequest.from_admission_review(
                {"request": {"uid": "u", "operation": "CREATE"}}
            )
        )

    def test_connect_reviews_cached_with_per_request_uid(self):
        cache = DecisionCache(path="admission")
        handler, calls = self.make_handler(cache)
        r1 = handler.handle(
            AdmissionRequest.from_admission_review(connect_review(uid="a"))
        )
        r2 = handler.handle(
            AdmissionRequest.from_admission_review(connect_review(uid="b"))
        )
        assert len(calls) == 1  # second review answered from cache
        assert r1.allowed and r2.allowed
        assert (r1.uid, r2.uid) == ("a", "b")  # uid rebuilt per review

    def test_denied_connect_cached(self):
        cache = DecisionCache(path="admission")
        handler, calls = self.make_handler(cache)
        for uid in ("a", "b"):
            r = handler.handle(
                AdmissionRequest.from_admission_review(
                    connect_review(uid=uid, name="blocked")
                )
            )
            assert not r.allowed
        assert len(calls) == 1

    def test_mutating_reviews_never_cached(self):
        cache = DecisionCache(path="admission")
        handler, calls = self.make_handler(cache)
        review = connect_review()
        review["request"]["operation"] = "CREATE"
        for _ in range(3):
            handler.handle(AdmissionRequest.from_admission_review(review))
        assert len(calls) == 3 and cache.size() == 0

    def test_without_cache_every_review_evaluates(self):
        handler, calls = self.make_handler(cache=None)
        for _ in range(3):
            handler.handle(
                AdmissionRequest.from_admission_review(connect_review())
            )
        assert len(calls) == 3


# ------------------------------------------------------- recorder and replay


class TestRecorderReplayFingerprint:
    def test_recorded_filename_carries_cache_key(self, tmp_path):
        rec = RequestRecorder(str(tmp_path / "recs"))
        body = json.dumps(make_sar()).encode()
        rec.record("/v1/authorize", body)
        files = list((tmp_path / "recs").glob("req-*.json"))
        assert len(files) == 1
        fp = fingerprint_body("authorize", body)
        assert files[0].name.startswith(f"req-authorize-{fp}-")
        assert files[0].read_bytes() == body

    def test_unparseable_body_recorded_unkeyed(self, tmp_path):
        rec = RequestRecorder(str(tmp_path / "recs"))
        rec.record("/v1/authorize", b"{not json")
        files = list((tmp_path / "recs").glob("req-*.json"))
        assert files[0].name.startswith("req-authorize-unkeyed-")

    def test_replay_reports_same_fingerprints(self, tmp_path, capsys):
        from cedar_tpu.cli.replay import main as replay_main

        policies = tmp_path / "policies"
        policies.mkdir()
        (policies / "p.cedar").write_text(DEMO_POLICY)
        config = tmp_path / "config.yaml"
        config.write_text(
            "apiVersion: cedar.k8s.aws/v1alpha1\nkind: StoreConfig\nspec:\n"
            "  stores:\n"
            '    - type: "directory"\n'
            "      directoryStore:\n"
            f'        path: "{policies}"\n'
        )
        rec_dir = tmp_path / "rec"
        recorder = RequestRecorder(str(rec_dir))
        body = json.dumps(make_sar()).encode()
        # the same canonical request twice, in different wire formats
        recorder.record("/v1/authorize", body)
        recorder.record(
            "/v1/authorize", json.dumps(make_sar(), indent=2).encode()
        )
        rc = replay_main([str(rec_dir), "--config", str(config)])
        assert rc == 0
        captured = capsys.readouterr()
        fp = fingerprint_body("authorize", body)
        lines = captured.out.strip().splitlines()
        assert len(lines) == 2
        # per-line fingerprint column matches the recorded filename stamp
        assert all(line.split("\t")[4] == fp for line in lines)
        assert "1 unique fingerprints / 2 keyed" in captured.err


# ------------------------------------------------------------------- chaos


chaos = [pytest.mark.chaos, pytest.mark.slow]


class OpenBreaker:
    def allow(self):
        return False

    def record_failure(self):
        pass


@pytest.mark.chaos
@pytest.mark.slow
class TestBreakerCacheInteraction:
    def test_breaker_open_warm_cache_serves_hits_misses_fall_back(self):
        """Chaos: with the device-plane breaker OPEN and a warm cache,
        repeated SARs are served from cache (no batcher submit, no
        interpreter walk) and only genuinely new requests fall through to
        the interpreter path."""
        cache = DecisionCache()
        server, stores = make_server(cache=cache)
        batcher = CountingBatcher()
        server._batcher = batcher
        server.fastpath = types.SimpleNamespace(
            available=True, breaker=None
        )
        warm_body = json.dumps(make_sar()).encode()
        r_warm = server.handle_authorize(warm_body)  # warms via "device"
        assert batcher.calls == 1 and r_warm["status"]["allowed"]

        # trip the breaker: the batcher must not see another submit
        server.fastpath.breaker = OpenBreaker()
        interp_calls = []
        real_auth = server.authorizer.authorize

        def counting_auth(attributes, use_cache=True):
            interp_calls.append(1)
            return real_auth(attributes, use_cache=use_cache)

        server.authorizer.authorize = counting_auth

        for _ in range(5):
            assert server.handle_authorize(warm_body) == r_warm
        assert batcher.calls == 1  # cache hits: breaker never consulted
        assert interp_calls == []  # and no interpreter walk either

        cold_body = json.dumps(make_sar(resource="nodes")).encode()
        r_cold = server.handle_authorize(cold_body)
        assert r_cold["status"]["denied"]
        assert batcher.calls == 1  # breaker open: bypassed the batcher
        assert len(interp_calls) == 1  # miss fell through to interpreter
        # and the miss's result is now warm too
        assert server.handle_authorize(cold_body) == r_cold
        assert len(interp_calls) == 1
