"""cedar-webhook: the authorization + admission webhook server CLI.

Wiring parity with reference cmd/cedar-webhook/main.go:39-131: read the
store config file, build the tiered stores, construct the authorizer and the
admission handler (with the allow-all final tier and allow-on-error=true),
start the TLS webhook server (self-signed certs generated when absent) and
the plain health/metrics server.

TPU-native addition: ``--backend tpu`` routes authorization evaluation
through the compiled TPU engine (cedar_tpu.engine.TPUPolicyEngine) with a
background recompile loop that hot-swaps the device tensors when any store's
policies change; the interpreter remains the admission path and the
correctness fallback.
"""

from __future__ import annotations

import argparse
import hashlib
import logging
import os
import signal
import sys
import threading
from typing import List, Optional

from ..obs import logsink
from ..server.admission import (
    CedarAdmissionHandler,
    allow_all_admission_policy_store,
)
from ..server.authorizer import CedarWebhookAuthorizer
from ..server.certs import maybe_self_signed_certs
from ..server.error_injector import ErrorInjectionConfig, ErrorInjector
from ..server.http import (
    DEFAULT_ADDRESS,
    DEFAULT_PORT,
    METRICS_PORT,
    WebhookServer,
)
from ..server.recorder import RequestRecorder
from ..stores.config import cedar_config_stores, parse_config
from ..stores.store import TieredPolicyStores

log = logging.getLogger(__name__)


def _fingerprint(stores: TieredPolicyStores) -> str:
    """Cheap change detector: stores expose a content generation counter
    bumped only on real content change, so a steady-state tick costs a few
    method calls instead of re-formatting the whole policy corpus. Stores
    without the counter fall back to the content hash."""
    parts = []
    for store in stores:
        gen = getattr(store, "content_generation", None)
        if gen is not None:
            parts.append(f"{store.name()}@{gen()}")
            continue
        h = hashlib.sha256()
        from ..lang.format import format_policy

        for p in store.policy_set().policies():
            h.update(p.policy_id.encode())
            h.update(format_policy(p).encode())
        parts.append(h.hexdigest())
    return "|".join(parts)


class TPUReloader:
    """Recompiles TPU engines whenever store contents change (the tensorized
    successor of the reference's RWMutex policy reload).

    One reloader drives any number of (engine, tier stores) targets off a
    single fingerprint pass over the shared dynamic stores — the authz and
    admission tier stacks differ only by a compile-time-constant allow-all
    tail, so fingerprinting the corpus twice would be pure waste."""

    def __init__(
        self,
        stores: TieredPolicyStores,
        targets=None,
        interval_s: float = 5.0,
    ):
        self.stores = stores  # dynamic stores: fingerprint + readiness gate
        self.targets = list(targets or [])  # [(engine, tier_stores)]
        self.interval_s = interval_s
        # fingerprint each target last loaded successfully — tracked per
        # target so one target's persistent load failure doesn't force the
        # healthy engines to recompile every tick
        self._fps: dict = {}
        self._stop = threading.Event()

    @staticmethod
    def _tiers_for(tier_stores) -> list:
        """Tiers for engine compilation, through the load-time analysis
        gate when the tier stack carries a validation mode
        (TieredPolicyStores.analyzed_policy_sets): strict raises
        AnalysisRejected so the engine keeps its previous compiled set."""
        analyzed = getattr(tier_stores, "analyzed_policy_sets", None)
        if analyzed is not None:
            return analyzed()
        return [s.policy_set() for s in tier_stores]

    def reload_if_changed(self) -> bool:
        from ..analysis import AnalysisRejected

        if not all(s.initial_policy_load_complete() for s in self.stores):
            return False
        fp = _fingerprint(self.stores)
        changed = False
        for idx, (engine, tier_stores) in enumerate(self.targets):
            if self._fps.get(idx) == fp:
                continue
            try:
                stats = engine.load(self._tiers_for(tier_stores))
            except AnalysisRejected as e:
                # strict validation: the new corpus is rejected wholesale;
                # keep serving the previous compiled set AND remember the
                # fingerprint — re-analyzing an unchanged bad corpus every
                # tick would only repeat the log/metric spam
                log.error(
                    "TPU engine [%d] load rejected by policy analysis; "
                    "serving previous set: %s",
                    idx,
                    e,
                )
                self._fps[idx] = fp
                continue
            except Exception:
                log.exception(
                    "TPU engine [%d] reload failed; serving previous set", idx
                )
                continue
            self._fps[idx] = fp
            changed = True
            log.info("TPU engine [%d] reloaded: %s", idx, stats)
        return changed

    def run_forever(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.reload_if_changed()
            except Exception:
                log.exception("TPU reload failed; serving previous compiled set")

    def start(self) -> None:
        threading.Thread(
            target=self.run_forever, name="tpu-reloader", daemon=True
        ).start()

    def stop(self) -> None:
        self._stop.set()


def _client_enforce_at(args) -> float:
    """Load fraction where per-client quota enforcement starts. The
    derived default (--client-enforce-at < 0) is the pressure threshold:
    the quota's whole point is the band below shed_normal_at — above it
    the load gate sheds normal traffic wholesale anyway, so a fixed value
    past that line would be silently inert."""
    if args.client_enforce_at >= 0:
        return args.client_enforce_at
    return args.shed_sheddable_at


def build_server(args) -> WebhookServer:
    # process worker identity first: every metrics family, trace and
    # audit record from here on carries it (docs/fleet.md "Cross-host
    # topology"); empty = single-process, label omitted
    if getattr(args, "worker_id", ""):
        from ..server.metrics import set_worker_label

        set_worker_label(args.worker_id)
    if (
        getattr(args, "fanout_workers", 1) > 1
        and getattr(args, "fleet_replicas", 1) > 1
    ):
        raise ValueError(
            "--fanout-workers and --fleet-replicas are mutually exclusive: "
            "the fanout tier IS the scale-out layer (each worker may "
            "itself be meshed); pick one"
        )
    # serving-plane default: the segmented-reduction kernel wins at
    # serving-chunk batch sizes on the CPU BACKEND (builders' cpu probes),
    # where the matmul has no MXU and the scan plane's n_groups masked
    # passes dominate. TPU keeps the scan default until a benchmark on
    # the chip justifies a flip (docs/Limitations.md). Explicit
    # CEDAR_TPU_SEGRED always wins; the preference is passed to the
    # engines directly (never via os.environ — a global flip would leak
    # into unrelated engines in the same process).
    segred = None
    if (
        args.backend == "tpu"
        and not getattr(args, "mesh", "")  # the sharded pjit plane has no
        # per-group scan to replace — segs would be silently ignored there
        and "CEDAR_TPU_SEGRED" not in os.environ
    ):
        import jax

        if jax.default_backend() == "cpu":
            segred = True
            log.info(
                "cpu backend: segmented-reduction kernel plane enabled "
                "(CEDAR_TPU_SEGRED=0 restores the scan plane)"
            )

    # native encoder worker-pool width: --native-encode-threads overrides
    # CEDAR_NATIVE_THREADS through the module reset hook, so a flag always
    # wins over a previously-cached (possibly malformed) env resolution
    if getattr(args, "native_encode_threads", 0) > 0:
        from ..native import set_encode_threads

        set_encode_threads(args.native_encode_threads)
    try:
        from ..native import _default_encode_threads
        from ..server.metrics import set_native_encode_threads

        set_native_encode_threads(_default_encode_threads())
    except Exception:  # noqa: BLE001 — metrics must never block startup
        pass

    # serialized-executable cache (engine/aot.py, docs/Operations.md):
    # the flag wins over CEDAR_TPU_AOT_CACHE; either enables warm-from-disk
    # cold starts (zero fresh jit traces when the key matches)
    if getattr(args, "aot_cache_dir", ""):
        from ..engine import aot

        aot.set_cache_dir(args.aot_cache_dir)

    config = None
    if args.config:
        with open(args.config) as f:
            config = parse_config(f.read())
    if config is not None and getattr(args, "validation_mode", ""):
        # CLI flag overrides the config file's spec.validationMode
        config.validation_mode = args.validation_mode
    stores = cedar_config_stores(config, kubeconfig_path=args.kubeconfig or None)

    # multi-tenant shared plane (cedar_tpu/tenancy, docs/multitenancy.md):
    # --tenant NAME=POLICY_DIR (repeatable) fuses every tenant's directory
    # store into ONE engine + batcher + cache stack — the serving tiers
    # become the registry's fused (guard-wrapped, tenant-stamped) clones,
    # so EVERY layer below this point is wired exactly like a
    # single-tenant server and tenant isolation rides the policy plane
    # itself. The resolver stamps each request's tenant at the front door.
    tenancy_resolver = None
    tenant_registry = None
    if getattr(args, "tenant", None):
        from ..stores.directory import DirectoryPolicyStore
        from ..tenancy import TenantRegistry, TenantResolver, fused_tier_stores

        tenant_registry = TenantRegistry()
        # the analysis gate runs PER TENANT on the pre-fusion originals
        # (registry.fused_tiers consumes analyzed_policy_sets when the
        # store offers it) — the fused stack itself stays ungated because
        # the tenant guards' context access would distort the verdicts
        tenant_validation = (
            config.validation_mode
            if config is not None
            else getattr(args, "validation_mode", "") or None
        )
        for spec in args.tenant:
            name, sep, tdir = spec.partition("=")
            if not sep or not name or not tdir:
                raise ValueError(
                    f"--tenant wants NAME=POLICY_DIR, got {spec!r}"
                )
            tenant_registry.add_tenant(
                name,
                stores=TieredPolicyStores(
                    [
                        # refresh at the engine-reload cadence: a tenant's
                        # directory edit must reach the fused plane within
                        # one reloader tick, not the store default's 60s
                        DirectoryPolicyStore(
                            tdir,
                            refresh_interval_s=max(
                                1.0, float(args.tpu_reload_seconds)
                            ),
                        )
                    ],
                    validation_mode=tenant_validation,
                ),
            )
        hosts = {}
        for spec in getattr(args, "tenant_host", None) or []:
            host, sep, name = spec.partition("=")
            if not sep or not host or not name:
                raise ValueError(
                    f"--tenant-host wants HOST=TENANT, got {spec!r}"
                )
            hosts[host] = name
        if len(stores.stores):
            log.warning(
                "--tenant set: the config's policy stores are replaced "
                "by the fused tenant stack"
            )
        stores = fused_tier_stores(tenant_registry)
        sources = tuple(
            s.strip() for s in args.tenant_sources.split(",") if s.strip()
        )
        tenancy_resolver = TenantResolver(
            tenant_registry,
            header=args.tenant_header,
            hosts=hosts,
            default=args.tenant_default or None,
            sources=sources,
        )
        log.info(
            "multi-tenant plane: %d tenant(s) fused (%s)",
            len(tenant_registry),
            ", ".join(tenant_registry.tenants()),
        )
    if not len(stores.stores):
        log.warning("no policy stores configured; authorizer will no-opinion")

    mesh = None
    if getattr(args, "mesh", ""):
        # "--mesh DATAxPOLICY" (e.g. 1x8, 2x4) or a bare device count
        # (policy-only split): the explicit (data, policy) factorization of
        # the device mesh the engines evaluate over
        from ..parallel.mesh import make_mesh

        spec = args.mesh.lower()
        if "x" in spec:
            d, p = (int(x) for x in spec.split("x", 1))
            mesh = make_mesh(d * p, shape=(d, p))
        else:
            mesh = make_mesh(int(spec))
        log.info(
            "device mesh: data=%d policy=%d",
            mesh.shape["data"],
            mesh.shape["policy"],
        )

    def _make_breaker(name: str):
        """Circuit breaker per TPU engine (engine/breaker.py); None when
        disabled by --breaker-failure-threshold 0."""
        if args.breaker_failure_threshold <= 0:
            return None
        from ..engine.breaker import CircuitBreaker

        latency_ms = args.breaker_latency_threshold_ms
        if latency_ms <= 0:
            # default the breach threshold to the request budget: a device
            # that "succeeds" slower than any caller waits is breaching.
            # Without this a uniformly slow device never trips — each
            # deadline expiry's record_failure would be erased by the late
            # batch completing as an unqualified success.
            latency_ms = args.request_timeout_ms
        return CircuitBreaker(
            name=name,
            failure_threshold=args.breaker_failure_threshold,
            latency_threshold_s=latency_ms / 1e3 if latency_ms > 0 else None,
            recovery_s=args.breaker_recovery_seconds,
            half_open_probes=args.breaker_half_open_probes,
        )

    def _tpu_backend(
        tier_stores: TieredPolicyStores, breaker=None, name: str = "hybrid"
    ):
        """(engine, evaluate, evaluate_batch, recovery) for a tier stack:
        compiled eval with an interpreter guard until the first successful
        load, a circuit breaker that routes evaluation to the tiered
        interpreter stores while the device plane is sick, and — with
        supervision enabled — a DeviceRecovery observing the guard's
        exceptions so a fatal device loss trips the breaker and rebuilds
        the engine off the serving path (docs/resilience.md)."""
        from ..engine.breaker import guarded_call
        from ..engine.evaluator import TPUPolicyEngine

        # warm_max_batch = the server's micro-batch ceiling: the warm-up
        # ladder (and explicit warmup()) precompiles EVERY batch bucket a
        # production batch can land on, so no request ever pays a trace
        tier_engine = TPUPolicyEngine(
            mesh=mesh, segred=segred, name=name,
            warm_max_batch=args.max_batch,
            incremental=not args.no_incremental_compile,
            shard_buckets=args.shard_buckets,
            partition=partition_spec,
        )
        recovery = None
        if args.supervisor_interval_seconds > 0:
            from ..server.supervisor import DeviceRecovery

            recovery = DeviceRecovery(
                tier_engine, breaker=breaker, name=name,
                warm_max_batch=args.max_batch,
            )
        on_error = recovery.observe if recovery is not None else None

        def _guarded(device_call, fallback_call):
            """engine/breaker.py guarded_call plus the pre-load interpreter
            guard: unloaded engines answer from the tiered stores without
            touching the breaker or the fallback metric (startup is not a
            sick device plane)."""
            if not tier_engine.loaded:
                return fallback_call()
            return guarded_call(
                breaker, device_call, fallback_call, name, on_error=on_error
            )

        def evaluate(entities, request):
            return _guarded(
                lambda: tier_engine.evaluate(entities, request),
                lambda: tier_stores.is_authorized(entities, request),
            )

        def evaluate_batch(items):
            return _guarded(
                lambda: tier_engine.evaluate_batch(items),
                lambda: [tier_stores.is_authorized(em, r) for em, r in items],
            )

        return tier_engine, evaluate, evaluate_batch, recovery

    # serving-partition spec (analysis/partition.py): prunes provably
    # never-matching policies off the device plane — the 100k-rule
    # org-store posture (docs/performance.md "Giant policy sets")
    partition_spec = None
    if getattr(args, "partition_spec", ""):
        from ..analysis.partition import PartitionSpec

        partition_spec = PartitionSpec.from_file(args.partition_spec)
        log.info(
            "serving partition %r: %d constrained slot(s)",
            partition_spec.name,
            len(partition_spec.allowed),
        )

    evaluate = None
    evaluate_batch = None
    engine = None
    admission_engine = None
    reloader = None
    authz_breaker = None
    authz_recovery = None
    admission_recovery = None
    if args.backend == "tpu" and not len(stores.stores):
        log.warning("TPU backend requested but no stores configured; using interpreter")
    elif args.backend == "tpu":
        authz_breaker = _make_breaker("authorization")
        engine, evaluate, evaluate_batch, authz_recovery = _tpu_backend(
            stores, breaker=authz_breaker, name="authorization"
        )
        reloader = TPUReloader(
            stores,
            targets=[(engine, stores)],
            interval_s=args.tpu_reload_seconds,
        )

    authorizer = CedarWebhookAuthorizer(
        stores, evaluate=evaluate, evaluate_batch=evaluate_batch
    )

    fastpath = None
    if engine is not None and partition_spec is not None and not args.no_native:
        # the raw native path encodes straight from request bytes and
        # cannot run the partition conformance gate, so a pruned plane
        # must serve through the python encode path (which routes
        # non-conforming requests to the exact interpreter walk)
        log.info(
            "serving partition set: native SAR fast path disabled "
            "(python encode path runs the conformance gate)"
        )
    elif engine is not None and not args.no_native:
        from ..engine.fastpath import SARFastPath
        from ..native import native_available, native_error

        if native_available():
            # the fast path shares the engine's breaker: a tripped device
            # plane routes BOTH the native raw pipeline and the hybrid
            # evaluate path to the interpreter. It also shares the
            # device-loss recovery observer: a fatal XLA error in either
            # plane triggers the one rebuild.
            fastpath = SARFastPath(engine, authorizer, breaker=authz_breaker)
            if authz_recovery is not None:
                fastpath.on_device_error = authz_recovery.observe
            log.info("native SAR fast path enabled")
        else:
            log.warning(
                "native SAR fast path unavailable (%s); using python encode",
                native_error(),
            )

    # engine fleet (cedar_tpu/fleet, docs/fleet.md): --fleet-replicas N>=2
    # replicates the authorization engine into N replicas — independent
    # engines + breakers + device recoveries + batchers — behind a
    # health-aware router the server routes through between the decision
    # cache and the batchers. Replica 0 reuses the objects built above;
    # replicas 1..N-1 clone the settings. The store reloader compiles once
    # and adopts into every replica; promotion swaps all replicas under
    # the fleet's generation barrier. N=1 (default) keeps the single-engine
    # path byte-identical to previous releases.
    fleet = None
    fleet_recoveries = []
    if args.fleet_replicas > 1 and fastpath is not None:
        from ..engine.evaluator import TPUPolicyEngine
        from ..engine.fastpath import SARFastPath
        from ..fleet import EngineFleet, EngineReplica

        replicas = [
            EngineReplica(
                0,
                engine,
                fastpath,
                breaker=authz_breaker,
                recovery=authz_recovery,
                max_batch=args.max_batch,
                window_s=args.batch_window_us / 1e6,
                pipeline_depth=args.pipeline_depth,
            )
        ]
        for i in range(1, args.fleet_replicas):
            r_breaker = _make_breaker(f"authorization-r{i}")
            r_engine = TPUPolicyEngine(
                mesh=mesh, segred=segred, name=f"authorization-r{i}",
                warm_max_batch=args.max_batch,
                incremental=not args.no_incremental_compile,
                shard_buckets=args.shard_buckets,
                partition=partition_spec,
            )
            r_recovery = None
            if args.supervisor_interval_seconds > 0:
                from ..server.supervisor import DeviceRecovery

                r_recovery = DeviceRecovery(
                    r_engine, breaker=r_breaker,
                    name=f"authorization-r{i}",
                    warm_max_batch=args.max_batch,
                )
                fleet_recoveries.append(r_recovery)
            r_fastpath = SARFastPath(r_engine, authorizer, breaker=r_breaker)
            if r_recovery is not None:
                r_fastpath.on_device_error = r_recovery.observe
            replicas.append(
                EngineReplica(
                    i,
                    r_engine,
                    r_fastpath,
                    breaker=r_breaker,
                    recovery=r_recovery,
                    max_batch=args.max_batch,
                    window_s=args.batch_window_us / 1e6,
                    pipeline_depth=args.pipeline_depth,
                )
            )
        fleet = EngineFleet(
            replicas, hedge_delay_s=args.hedge_delay_ms / 1e3
        )
        # the reloader drives the whole fleet through one target: compile
        # on replica 0, adopt (compile-free) into the rest
        reloader.targets[0] = (fleet, stores)
        log.info(
            "engine fleet enabled: %d replicas, hedge delay %.1fms",
            args.fleet_replicas,
            args.hedge_delay_ms,
        )
    elif args.fleet_replicas > 1:
        if partition_spec is not None:
            log.warning(
                "--fleet-replicas is unavailable with --partition-spec "
                "(the fleet's raw fast path cannot run the partition "
                "conformance gate); serving single-engine"
            )
        else:
            log.warning(
                "--fleet-replicas requires --backend tpu with the native "
                "fast path; serving single-engine"
            )

    # cross-process worker tier (cedar_tpu/fanout, docs/fleet.md
    # "Cross-host topology"): --fanout-workers N>=2 builds N isolated
    # worker stacks — own engine, breaker, native fast path, batcher and
    # peer-shared decision cache each — behind a consistent-hash
    # front-end the server routes raw bodies through. The store reloader
    # drives the tier's generation barrier (every worker swaps or none);
    # worker caches replicate through the peer mesh with shard-scoped
    # stamps, so an incremental CRD edit kills exactly the dirty shard's
    # entries on every worker. In this process the workers are
    # thread-isolated stacks sharing nothing but the stores; a multi-host
    # tier runs one webhook process per worker (--worker-id) behind the
    # same protocol.
    fanout = None
    if args.fanout_workers > 1 and engine is not None:
        from ..engine.evaluator import TPUPolicyEngine  # noqa: F401 — workers
        from ..fanout import FanoutFrontend, InProcessWorker
        from ..fanout.peers import PeerBackedCache
        from ..cache.generation import plane_composite, plane_wire_state

        peer_fetch = args.fanout_peer_cache in ("both", "fetch")
        peer_gossip = args.fanout_peer_cache in ("both", "gossip")
        native_ok = False
        if not args.no_native and partition_spec is None:
            from ..native import native_available

            native_ok = native_available()
        workers = []
        for i in range(args.fanout_workers):
            w_breaker = _make_breaker(f"authorization-w{i}")
            w_engine, w_eval, w_eval_batch, w_rec = _tpu_backend(
                stores, breaker=w_breaker, name=f"authorization-w{i}"
            )
            if w_rec is not None:
                fleet_recoveries.append(w_rec)  # /debug/supervisor report
            w_auth = CedarWebhookAuthorizer(
                stores, evaluate=w_eval, evaluate_batch=w_eval_batch
            )
            w_fast = None
            if native_ok:
                from ..engine.fastpath import SARFastPath

                w_fast = SARFastPath(w_engine, w_auth, breaker=w_breaker)
                if w_rec is not None:
                    w_fast.on_device_error = w_rec.observe
            w_cache = None
            if args.decision_cache_size > 0:
                w_cache = PeerBackedCache(
                    max_entries=args.decision_cache_size,
                    allow_ttl_s=args.decision_cache_allow_ttl_seconds,
                    deny_ttl_s=args.decision_cache_deny_ttl_seconds,
                    no_opinion_ttl_s=(
                        args.decision_cache_no_opinion_ttl_seconds
                    ),
                    generation_fn=(
                        lambda e=w_engine: plane_composite(stores, e)
                    ),
                    wire_state_fn=lambda e=w_engine: plane_wire_state(e),
                    fetch_enabled=peer_fetch,
                    gossip_enabled=peer_gossip,
                    path="authorization",
                )
            w_server = WebhookServer(
                w_auth,
                None,
                fastpath=w_fast,
                decision_cache=w_cache,
                pipeline_depth=args.pipeline_depth,
                max_batch=args.max_batch,
                batch_window_s=args.batch_window_us / 1e6,
                request_timeout_s=(
                    args.request_timeout_ms / 1e3
                    if args.request_timeout_ms > 0
                    else None
                ),
            )
            workers.append(
                InProcessWorker(f"w{i}", w_server, w_engine, cache=w_cache)
            )
        fanout = FanoutFrontend(
            workers,
            name="authorization",
            peer_fetch=peer_fetch,
            peer_gossip=peer_gossip,
        )
        # the reloader drives the tier barrier instead of the (now
        # bystander) single engine: every worker compiles its own view of
        # the store content and the swap commits tier-wide or not at all
        reloader.targets[0] = (fanout, stores)
        # the outer authz fast path would gate readiness on an engine the
        # reloader no longer loads; the tier serves instead
        fastpath = None
        log.info(
            "fanout worker tier enabled: %d workers, peer cache %s",
            args.fanout_workers,
            args.fanout_peer_cache,
        )
    elif args.fanout_workers > 1:
        log.warning(
            "--fanout-workers requires --backend tpu; serving single-stack"
        )

    # admission gets the allow-all final tier (main.go:111-116); it shares
    # the authz stack's validation posture (the synthetic allow-all tail is
    # trivially lowerable, so the gate treats both stacks identically)
    admission_stores = TieredPolicyStores(
        list(stores.stores) + [allow_all_admission_policy_store()],
        validation_mode=stores.validation_mode,
    )
    admission_evaluate = None
    admission_evaluate_batch = None
    admission_breaker = None
    if engine is not None:
        # the admission tier stack (same stores + the constant allow-all
        # final tier) compiles into its own engine; unlowerable admission
        # predicates fall back per policy with exact verdict merging. Both
        # engines ride the one reloader's fingerprint pass.
        admission_breaker = _make_breaker("admission")
        (
            admission_engine,
            admission_evaluate,
            admission_evaluate_batch,
            admission_recovery,
        ) = _tpu_backend(
            admission_stores, breaker=admission_breaker, name="admission"
        )
        reloader.targets.append((admission_engine, admission_stores))

    if reloader is not None:
        reloader.reload_if_changed()
        reloader.start()

    # decision cache (cedar_tpu/cache, docs/caching.md): canonical-
    # fingerprint LRU+TTL cache ahead of both engines, invalidated by the
    # stores' composite content generation. Admission caching is opt-in and
    # gated to read-only idempotent reviews (CONNECT / dry-run).
    decision_cache = None
    admission_cache = None
    if fanout is not None and args.decision_cache_size > 0:
        # the worker stacks own the (peer-shared) authorization caches;
        # an outer cache would double-store every decision and hide the
        # tier's hash-affinity warmth
        log.info("fanout tier: authorization decision cache lives per worker")
    if args.decision_cache_size > 0:
        from ..cache import DecisionCache

        def _generation_fn(tier_stores, tier_engine, tier_fleet=None):
            """Composite cache generation. Interpreter-only tiers keep the
            store CONTENT generations (any reload kills everything, the
            pre-shard posture). Compiled backends use the serving plane's
            SHARD lineage (cache/generation.py plane_composite): entries
            stamp the determining policies' shard generations, so an
            incremental reload kills exactly the entries whose shard
            changed — shard-B-served entries stay warm across a shard-A
            CRD edit — while full compiles, promotions, rollbacks and
            device rebuilds change the structural plane id and kill all.
            With a fleet, the per-replica plane bases fold into one
            composite so a diverged replica still invalidates."""
            target = tier_fleet if tier_fleet is not None else tier_engine
            if target is None:
                return tier_stores.cache_generation
            from ..cache.generation import plane_composite

            return lambda: plane_composite(tier_stores, target)

        if fanout is None:
            decision_cache = DecisionCache(
                max_entries=args.decision_cache_size,
                allow_ttl_s=args.decision_cache_allow_ttl_seconds,
                deny_ttl_s=args.decision_cache_deny_ttl_seconds,
                no_opinion_ttl_s=args.decision_cache_no_opinion_ttl_seconds,
                generation_fn=_generation_fn(stores, engine, fleet),
                path="authorization",
            )
        if args.decision_cache_admission:
            admission_cache = DecisionCache(
                max_entries=args.decision_cache_size,
                allow_ttl_s=args.decision_cache_allow_ttl_seconds,
                deny_ttl_s=args.decision_cache_deny_ttl_seconds,
                no_opinion_ttl_s=args.decision_cache_no_opinion_ttl_seconds,
                generation_fn=_generation_fn(
                    admission_stores,
                    admission_engine if engine is not None else None,
                ),
                path="admission",
            )

    # shadow rollout (cedar_tpu/rollout, docs/rollout.md): staged candidate
    # policy sets shadow-evaluated against live traffic, with atomic
    # promote/rollback over the engines' compiled sets. Wired only with the
    # TPU backend — promotion swaps compiled sets, which the interpreter
    # path doesn't have.
    rollout = None
    rollout_control_enabled = True
    rollout_control_token = None
    if tenant_registry is not None and (
        args.rollout_candidate_dir
        or args.rollout_control_token_file
        or args.rollout_insecure_control
    ):
        # the candidate corpus and the shadow diff are single-tenant: a
        # candidate engine carries no tenant guards, so shadowing fused
        # traffic against it would answer every request NoOpinion and
        # report vacuous mass diffs. Per-tenant rollout on a fused plane
        # is the registry-driven lifecycle (docs/multitenancy.md), not
        # the candidate-dir one — refuse rather than mislead.
        raise ValueError(
            "--tenant cannot combine with shadow-rollout flags "
            "(--rollout-candidate-dir/--rollout-control-token-file/"
            "--rollout-insecure-control): the candidate corpus carries "
            "no tenant guards, so every shadow diff on a fused plane "
            "would be vacuous (docs/multitenancy.md)"
        )
    if args.rollout_control_token_file:
        with open(args.rollout_control_token_file) as f:
            rollout_control_token = f.read().strip()
        if not rollout_control_token:
            raise ValueError(
                "--rollout-control-token-file is empty: refusing to serve "
                "unauthenticated rollout control by accident"
            )
    elif not args.rollout_insecure_control:
        # secure default: without a token (or the explicit insecure
        # opt-in) the mutating lifecycle endpoints answer 403; startup
        # staging via --rollout-candidate-dir still works, and
        # /debug/rollout stays readable
        rollout_control_enabled = False
    if engine is not None and fanout is not None:
        if args.rollout_candidate_dir or rollout_control_enabled:
            log.warning(
                "shadow rollout is not yet wired through the fanout tier "
                "(the tier barrier covers store reloads; candidate "
                "promote/rollback across workers is future work) — "
                "rollout disabled"
            )
    elif engine is not None:
        from ..rollout import RolloutController

        def _crd_candidates():
            """Candidate-labeled Policy objects across every CRD-backed
            store tier (the stores withhold them from live serving);
            POST /rollout/stage {"crd": true} builds the candidate
            corpus from them."""
            out = []
            for s in stores.stores:
                candidates = getattr(s, "candidate_objects", None)
                if candidates is not None:
                    out.extend(candidates())
            return out

        rollout = RolloutController(
            authz_engine=engine,
            authz_fleet=fleet,
            admission_engine=admission_engine,
            sample_rate=args.shadow_sample_rate,
            queue_depth=args.shadow_queue_depth,
            duty_cycle=args.shadow_duty_cycle,
            crd_candidate_provider=_crd_candidates,
        )
        if args.rollout_candidate_dir:
            try:
                rollout.stage(directory=args.rollout_candidate_dir)
                log.info(
                    "staged rollout candidate from %s",
                    args.rollout_candidate_dir,
                )
            except Exception:  # noqa: BLE001 — a bad candidate must not
                # block serving; the operator re-stages via /rollout/stage
                log.exception(
                    "failed to stage rollout candidate from %s",
                    args.rollout_candidate_dir,
                )
    elif args.rollout_candidate_dir:
        log.warning(
            "--rollout-candidate-dir requires --backend tpu; ignoring"
        )

    admission_fail_open = args.admission_fail_mode == "open"
    admission_handler = CedarAdmissionHandler(
        admission_stores,
        allow_on_error=admission_fail_open,
        evaluate=admission_evaluate,
        evaluate_batch=admission_evaluate_batch,
        cache=admission_cache,
    )

    admission_fastpath = None
    if admission_evaluate is not None and not args.no_native:
        from ..engine.fastpath import AdmissionFastPath
        from ..native import native_available

        if native_available():
            admission_fastpath = AdmissionFastPath(
                admission_engine, admission_handler, breaker=admission_breaker
            )
            if admission_recovery is not None:
                admission_fastpath.on_device_error = admission_recovery.observe
            log.info("native admission fast path enabled")

    # observability plane (cedar_tpu/obs, docs/observability.md): tracing
    # is wired BY DEFAULT at sample rate 0 — the armed-but-unsampled path
    # is bench-gated to parity (make bench-trace), and tail-keep means
    # slow/error/fallback requests land in /debug/traces with zero
    # configuration exactly when an operator needs them. The request phase
    # ledger (cedar_request_phase_seconds) and the stall recorder
    # (obs/stall.py, /debug/stalls) are armed with it: WebhookServer keeps
    # a phase record per request and starts the recorder only when it is
    # handed a tracer.
    tracer = None
    if not args.no_trace:
        from ..obs import Tracer

        tail_ms = args.trace_tail_ms
        if tail_ms <= 0:
            # default the tail-keep threshold to the request budget: a
            # request that burned its deadline budget is by definition
            # the one worth keeping
            tail_ms = (
                args.request_timeout_ms
                if args.request_timeout_ms > 0
                else 1000.0
            )
        tracer = Tracer(
            sample_rate=args.trace_sample_rate,
            ring_capacity=args.trace_ring,
            tail_latency_s=tail_ms / 1e3,
            log_file=args.trace_log_file or None,
        )
    audit_log = None
    if args.audit_log_file:
        from ..obs import AuditLog

        audit_log = AuditLog(
            args.audit_log_file,
            max_bytes=args.audit_max_bytes,
            max_files=args.audit_max_files,
        )
    if rollout is not None and audit_log is not None:
        # rollout lifecycle actions (stage/promote/rollback and refusals,
        # with divergence detail) land in the same audit stream as
        # policy-admin actions; late-bound because the audit log is built
        # after the rollout controller
        rollout.set_audit_sink(audit_log.record)
    slo = None
    if args.slo_availability_target > 0:
        from ..obs import SLOTracker

        budget_ms = args.slo_latency_budget_ms
        if budget_ms <= 0:
            budget_ms = (
                args.request_timeout_ms
                if args.request_timeout_ms > 0
                else 2000.0
            )
        slo = SLOTracker(
            availability_target=args.slo_availability_target,
            latency_target=args.slo_latency_target,
            latency_budget_s=budget_ms / 1e3,
        )

    injector = ErrorInjector(
        ErrorInjectionConfig(
            enabled=(
                args.confirm_non_prod_inject_errors
                and (args.artificial_error_rate > 0 or args.artificial_deny_rate > 0)
            ),
            artificial_error_rate=args.artificial_error_rate,
            artificial_deny_rate=args.artificial_deny_rate,
        )
    )
    recorder = RequestRecorder(args.recording_dir) if args.enable_recording else None
    if recorder is not None and tenant_registry is not None:
        # a recorded body is the raw wire bytes — the tenant the front
        # end resolved rides the TenantBody wrapper and is LOST on disk,
        # so replaying fused-plane recordings (cedar-why, cli.replay,
        # shadow diffing) would evaluate without context.tenantId and
        # answer NoOpinion everywhere. Refuse rather than record traffic
        # that silently cannot replay (docs/multitenancy.md).
        raise ValueError(
            "--enable-recording cannot combine with --tenant: recorded "
            "bodies lose the resolved tenant and cannot replay against "
            "a fused plane (docs/multitenancy.md)"
        )

    certfile, keyfile = args.tls_cert_file, args.tls_private_key_file
    if not args.insecure and not (certfile and keyfile):
        certfile, keyfile = maybe_self_signed_certs(args.cert_dir)
    if args.insecure:
        certfile = keyfile = None

    def analysis_provider() -> dict:
        """The last load-time analysis report per tier stack, for the
        /debug/analysis endpoint; {} until the first analyzed load."""
        out = {}
        for name, ts in (
            ("authorization", stores),
            ("admission", admission_stores),
        ):
            rep = getattr(ts, "last_analysis", None)
            if rep is not None:
                out[name] = rep.to_dict()
        return out

    # self-healing supervision (server/supervisor.py, docs/resilience.md):
    # a watchdog over every long-lived worker thread — batcher stages,
    # the shadow worker, CRD watch, directory reload tickers — restarting
    # dead/wedged components with their queues drained-or-shed; 0 disables
    supervisor = None
    if args.supervisor_interval_seconds > 0:
        from ..server.supervisor import Supervisor

        supervisor = Supervisor(
            interval_s=args.supervisor_interval_seconds,
            wedge_budget_s=args.supervisor_wedge_seconds,
        )
        for rec in (authz_recovery, admission_recovery, *fleet_recoveries):
            if rec is not None:
                supervisor.register_recovery(rec)

    # startup chaos scenario (cedar_tpu/chaos): gated by the same non-prod
    # confirmation flag as the reference error injector — an armed
    # scenario exists to BREAK serving
    if args.chaos_scenario:
        if not args.confirm_non_prod_inject_errors:
            raise ValueError(
                "--chaos-scenario requires --confirm-non-prod-inject-errors "
                "(fault injection is never a production default)"
            )
        from ..chaos import (
            builtin_scenario,
            default_registry,
            load_scenario_file,
        )

        scenario = builtin_scenario(args.chaos_scenario)
        if scenario is None:
            scenario = load_scenario_file(args.chaos_scenario)
        default_registry().configure(scenario)
        default_registry().arm()
        log.warning(
            "chaos scenario %r ARMED at startup (non-prod gate confirmed)",
            scenario.get("name", args.chaos_scenario),
        )

    # overload-control plane (cedar_tpu/load, docs/performance.md
    # "Serving under overload"): priority-aware ingress admission control
    # sized by --max-inflight; 0 keeps the gate-free serving path
    load_ctrl = None
    if getattr(args, "max_inflight", 0) > 0:
        from ..load import AdmissionController

        load_ctrl = AdmissionController(
            max_inflight=args.max_inflight,
            shed_sheddable_at=args.shed_sheddable_at,
            shed_normal_at=args.shed_normal_at,
            client_qps=args.client_qps,
            client_burst=args.client_burst,
            client_enforce_at=_client_enforce_at(args),
            retry_after_s=args.shed_retry_after_seconds,
        )

    if getattr(args, "adaptive_batching", False) and slo is None:
        # refuse BEFORE the server exists: WebhookServer() starts batcher
        # (and fleet/fanout) worker threads that an error path here would
        # leak with no stop_batchers() caller
        raise ValueError(
            "--adaptive-batching requires the SLO tracker "
            "(--slo-availability-target > 0): the burn rate is the "
            "control signal (docs/performance.md)"
        )

    # declarative policy lifecycle (cedar_tpu/lifecycle, docs/rollout.md
    # "Declarative lifecycle"): PolicyRollout specs drive the rollout
    # controller through verify → shadow → (canary) → promote with
    # evidence gates, journaled for crash resume
    lifecycle = None
    if args.lifecycle_spec_dir:
        if rollout is None:
            raise ValueError(
                "--lifecycle-spec-dir requires the shadow-rollout plane "
                "(--backend tpu, no fanout): the lifecycle controller "
                "drives stage/promote/rollback on the rollout controller "
                "(docs/rollout.md)"
            )
        from ..lifecycle import (
            LifecycleController,
            LifecycleJournal,
            RolloutLifecycleDriver,
            load_specs_dir,
        )

        specs = load_specs_dir(args.lifecycle_spec_dir)

        def _lifecycle_driver(spec):
            # server deployments have no in-process canary router on the
            # live serving path (live_eval=None): specs should use an
            # empty canary_ladder and promote on verify+shadow evidence
            if spec.canary_ladder:
                log.warning(
                    "lifecycle spec %r has a canary ladder but the "
                    "webhook server has no embedded canary router; the "
                    "canary quorum will never fill and the stage "
                    "deadline will halt the rollout — use "
                    '"canaryLadder": [] in server deployments',
                    spec.tenant,
                )
            return RolloutLifecycleDriver(
                spec.tenant,
                rollout,
                slo=slo,
                warm="async",
                sample_rate=args.shadow_sample_rate,
                # the analyze gate diffs the candidate against what the
                # authz engine actually serves: the same analyzed tier
                # view the reloader compiles from
                live_tiers=lambda: TPUReloader._tiers_for(stores),
            )

        journal = LifecycleJournal(args.lifecycle_journal_file or None)
        lifecycle = LifecycleController(journal=journal, audit_log=audit_log)
        by_tenant = {s.tenant: s for s in specs}
        resumed = lifecycle.resume(
            {t: _lifecycle_driver(s) for t, s in by_tenant.items()},
            specs=by_tenant,
        )
        for spec in specs:
            if spec.tenant in resumed:
                continue
            lifecycle.apply(spec, _lifecycle_driver(spec))
        if len(specs) > 1:
            log.warning(
                "%d lifecycle specs share one rollout controller: the "
                "shadow plane holds ONE candidate at a time, so rollouts "
                "serialize (a second stage while one is in flight "
                "retries under its deadline)",
                len(specs),
            )
        lifecycle.start(args.lifecycle_interval_seconds)
    elif args.lifecycle_journal_file:
        log.warning(
            "--lifecycle-journal-file without --lifecycle-spec-dir is "
            "inert; ignoring"
        )

    pdp = None
    if getattr(args, "pdp_listen", ""):
        # second front end (cedar_tpu/pdp): built here, lifecycle owned by
        # the WebhookServer (start()/stop() bring it up and down with the
        # webhook listeners)
        from ..pdp import PdpConfig, PdpListener

        pdp_config = (
            PdpConfig.load(args.pdp_schema)
            if getattr(args, "pdp_schema", "")
            else PdpConfig()
        )
        listen = str(args.pdp_listen)
        if ":" in listen:
            host, _, p = listen.rpartition(":")
            pdp_addr, pdp_port = (host or args.bind_address), int(p)
        else:
            pdp_addr, pdp_port = args.bind_address, int(listen)
        pdp = PdpListener(config=pdp_config, address=pdp_addr, port=pdp_port)

    server = WebhookServer(
        authorizer=authorizer,
        admission_handler=admission_handler,
        error_injector=injector,
        recorder=recorder,
        enable_profiling=args.profiling,
        address=args.bind_address,
        port=args.secure_port,
        metrics_port=args.metrics_port,
        certfile=certfile,
        keyfile=keyfile,
        fastpath=fastpath,
        admission_fastpath=admission_fastpath,
        fleet=fleet,
        fanout=fanout,
        batch_window_s=args.batch_window_us / 1e6,
        max_batch=args.max_batch,
        pipeline_depth=args.pipeline_depth,
        request_timeout_s=(
            args.request_timeout_ms / 1e3 if args.request_timeout_ms > 0 else None
        ),
        admission_fail_open=admission_fail_open,
        drain_grace_s=args.shutdown_grace_seconds,
        analysis_provider=analysis_provider,
        decision_cache=decision_cache,
        rollout=rollout,
        rollout_control_enabled=rollout_control_enabled,
        rollout_control_token=rollout_control_token,
        supervisor=supervisor,
        chaos_control_enabled=args.confirm_non_prod_inject_errors,
        tracer=tracer,
        audit_log=audit_log,
        slo=slo,
        tenancy=tenancy_resolver,
        load=load_ctrl,
        lifecycle=lifecycle,
        pdp=pdp,
    )
    if getattr(args, "adaptive_batching", False):
        # SLO-adaptive batching: one tuner per wired batcher, sensing the
        # burn rates the serving path is already measuring (the no-SLO
        # case was refused above, before any worker thread existed)
        from ..load import AdaptiveBatchTuner, TuningBounds

        bounds = TuningBounds(
            min_batch=args.tuner_min_batch,
            max_batch=args.tuner_max_batch,
            min_window_s=args.tuner_min_linger_us / 1e6,
            max_window_s=args.tuner_max_linger_us / 1e6,
        )
        for path, batcher in (
            ("authorization", server._batcher),
            ("admission", server._adm_raw_batcher),
        ):
            if batcher is None:
                continue
            tuner = AdaptiveBatchTuner(
                batcher,
                slo,
                path=path,
                bounds=bounds,
                interval_s=args.tuner_interval_seconds,
                window_s=args.tuner_burn_window_seconds,
            )
            tuner.start()
            server.tuners.append(tuner)
    if supervisor is not None:
        _register_supervised(supervisor, server, rollout, stores)
        if fanout is not None:
            # workers restart under the same watchdog as batcher stages:
            # liveness = worker.alive(), restart = rehash-in cold
            fanout.register_with(supervisor)
    return server


def _register_supervised(supervisor, server, rollout, stores) -> None:
    """Put every long-lived worker under the watchdog. ``threads``
    providers re-read the live objects so post-revive generations stay
    covered; restarts force-abandon wedged (still-alive) workers only when
    the probe said wedged."""
    from ..server.supervisor import HeartbeatGroup

    def _force(reason: str) -> bool:
        return reason.startswith("wedged")

    for name, batcher in (
        ("batcher.authorization", server._batcher),
        ("batcher.admission", server._adm_raw_batcher),
        ("batcher.admission_python", server._admission_batcher),
    ):
        if batcher is None:
            continue
        supervisor.register(
            name,
            threads=lambda b=batcher: list(b._threads),
            restart=lambda reason, b=batcher: b.revive(force=_force(reason)),
            heartbeat=HeartbeatGroup(lambda b=batcher: b.heartbeats),
        )
    for tuner in getattr(server, "tuners", []):
        # the adaptive batch tuner is a long-lived control thread like any
        # batcher stage: a dead/wedged tuner must restart, not silently
        # stop tuning (start() is idempotent on a live thread)
        supervisor.register(
            f"tuner.{tuner.path}",
            threads=lambda t=tuner: (
                [t._thread] if t._thread is not None else []
            ),
            restart=lambda reason, t=tuner: (t.start(), True)[1],
            heartbeat=HeartbeatGroup(lambda t=tuner: {"tick": t.heartbeat}),
        )
    fleet = getattr(server, "fleet", None)
    if fleet is not None:
        # one supervised component per replica, keyed {component, replica}
        # so a fleet member's death/restart is attributable; revive goes
        # through the fleet (it also returns a drained replica to the
        # routing set)
        for r in fleet.replicas:
            supervisor.register(
                "batcher.authorization",
                replica=r.name,
                threads=lambda rr=r: list(rr.batcher._threads),
                restart=lambda reason, i=r.index, f=fleet: f.revive_replica(
                    i, force=_force(reason)
                ),
                heartbeat=HeartbeatGroup(lambda rr=r: rr.batcher.heartbeats),
            )
    if rollout is not None:
        supervisor.register(
            "shadow.worker",
            threads=rollout.shadow_worker_threads,
            restart=lambda reason: rollout.revive_shadow(force=_force(reason)),
            heartbeat=HeartbeatGroup(rollout.shadow_heartbeats),
            # shadow drains can legitimately sit in a candidate jit trace
            # for a while: give the wedge probe extra slack
            wedge_budget_s=max(60.0, 4 * supervisor.wedge_budget_s),
        )
    for store in getattr(stores, "stores", []):
        if hasattr(store, "watch_threads"):
            supervisor.register(
                f"store.crd.{store.name()}",
                threads=store.watch_threads,
                restart=lambda reason, s=store: s.revive(force=_force(reason)),
            )
        elif hasattr(store, "ticker_threads"):
            supervisor.register(
                f"store.directory.{store.name()}",
                threads=store.ticker_threads,
                restart=lambda reason, s=store: s.revive(force=_force(reason)),
            )


def make_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="cedar-webhook",
        description="Cedar authorization + admission webhook for Kubernetes",
    )
    cedar = parser.add_argument_group("cedar")
    cedar.add_argument(
        "--config", default="", help="Cedar store config file (YAML/JSON)"
    )
    cedar.add_argument(
        "--kubeconfig", default="", help="kubeconfig for the CRD policy store"
    )
    cedar.add_argument(
        "--mesh",
        default="",
        help="device mesh for the TPU backend as DATAxPOLICY (e.g. 2x4) or "
        "a device count for a policy-only split; empty = single device",
    )
    cedar.add_argument(
        "--backend",
        default="interpreter",
        choices=["interpreter", "tpu"],
        help="authorization evaluation backend",
    )
    cedar.add_argument(
        "--tpu-reload-seconds",
        type=float,
        default=5.0,
        help="poll interval for TPU policy recompilation",
    )
    cedar.add_argument(
        "--no-native",
        action="store_true",
        help="disable the C++ SAR fast path (python encode only)",
    )
    cedar.add_argument(
        "--validation-mode",
        default="",
        choices=["", "strict", "permissive", "partial"],
        help="load-time policy-analysis posture, overriding the config "
        "file's spec.validationMode: strict rejects loads with blocking "
        "findings, permissive annotates, partial drops only the offending "
        "policies (docs/analysis.md)",
    )
    cedar.add_argument(
        "--batch-window-us",
        type=float,
        default=200.0,
        help="micro-batch forming window for the TPU fast path: with "
        "--pipeline-depth > 0 slept only by a burst's first claim at an "
        "idle pipeline (a request that comes alone is claimed at once), "
        "with the serial loop by every claim (docs/performance.md)",
    )
    cedar.add_argument(
        "--max-batch",
        type=int,
        default=8192,
        help="micro-batch row ceiling; also bounds the engine warm-up "
        "ladder so every production batch bucket is precompiled at load "
        "time (docs/performance.md)",
    )
    cedar.add_argument(
        "--pipeline-depth",
        type=int,
        default=2,
        help="0 restores the serial batch loop; N > 0 runs the "
        "three-stage evaluation pipeline (encode / dispatch / decode, "
        "docs/performance.md) and bounds the batches launched and not "
        "yet decoded at N. It does not let N encoded batches queue "
        "before the launch: one claimed batch stands there, and the rest "
        "of a backlog waits in the submit queue (the late claim)",
    )
    cedar.add_argument(
        "--native-encode-threads",
        type=int,
        default=0,
        help="native (C++) encoder worker-pool width per batch, "
        "overriding CEDAR_NATIVE_THREADS; 0 = env var, else cpu count "
        "(capped at 16). The bench projects near-linear encode scaling "
        "to ~16 cores (docs/performance.md, Host-side budget)",
    )
    cedar.add_argument(
        "--aot-cache-dir",
        default="",
        help="serialized-executable cache directory (engine/aot.py): "
        "compiled serving executables are exported here keyed by plane "
        "shapes/dtypes + jax/jaxlib version + backend topology, and a "
        "restart with a matching key warms from disk with ZERO fresh jit "
        "traces; stale keys recompile loudly. Also CEDAR_TPU_AOT_CACHE; "
        "CEDAR_TPU_AOT=0 disables. The dir must be trusted — entries are "
        "pickled executables (docs/Operations.md)",
    )
    cedar.add_argument(
        "--shard-buckets",
        type=int,
        default=0,
        help="tier/bucket shards per tier for incremental compilation "
        "(compiler/shard.py): a CRD edit re-lowers only its own shard, "
        "so finer sharding = faster edits, coarser = fewer shards to "
        "hash. 0 defers to CEDAR_TPU_SHARD_BUCKETS (default 64) "
        "(docs/performance.md, Giant policy sets)",
    )
    cedar.add_argument(
        "--no-incremental-compile",
        action="store_true",
        help="disable shard-granular incremental compilation: every "
        "reload re-lowers the whole corpus (the pre-shard behavior; "
        "escape hatch, also CEDAR_TPU_INCREMENTAL=0)",
    )
    cedar.add_argument(
        "--partition-spec",
        default="",
        help="JSON serving-partition spec ({'name':..., 'slots': "
        "{'resource.apiGroup': [...]}}): policies provably never "
        "matching this universe are pruned off the device plane "
        "(paged host-side); requests outside the universe answer via "
        "the exact interpreter walk. Disables the native raw fast "
        "path (docs/performance.md, Giant policy sets)",
    )

    fleet = parser.add_argument_group("engine fleet")
    fleet.add_argument(
        "--fleet-replicas",
        type=int,
        default=1,
        help="replicate the authorization engine into N fleet members "
        "behind a health-aware router (least-loaded among healthy, "
        "deterministic spillover around open-breaker/dead/rebuilding "
        "replicas); 1 keeps the single-engine path (docs/fleet.md). "
        "Requires --backend tpu with the native fast path",
    )
    fleet.add_argument(
        "--hedge-delay-ms",
        type=float,
        default=0.0,
        help="tail-latency hedge for LONE requests: when the routed "
        "replica has not answered within this delay, dispatch a "
        "duplicate to the next-healthiest replica and take the first "
        "answer (the loser is cancelled); 0 disables hedging "
        "(docs/fleet.md)",
    )
    fleet.add_argument(
        "--fanout-workers",
        type=int,
        default=1,
        help="cross-process worker tier (cedar_tpu/fanout, docs/fleet.md "
        "\"Cross-host topology\"): consistent-hash canonical request "
        "fingerprints onto N isolated worker stacks (own engine + fast "
        "path + batcher + peer-shared decision cache) behind one "
        "front-end, with policy swaps barriered across the tier. In this "
        "process the workers are thread-isolated stacks; a multi-host "
        "tier runs one webhook process per worker with --worker-id set. "
        "1 keeps the classic path; mutually exclusive with "
        "--fleet-replicas > 1",
    )
    fleet.add_argument(
        "--fanout-peer-cache",
        choices=("both", "fetch", "gossip", "off"),
        default="both",
        help="peer-shared decision cache mode for the fanout tier: "
        "fetch = on-miss asks the key's ring-preferred holders, gossip "
        "= miss-fills replicate to peers (warm rehash on worker loss), "
        "both (default), off",
    )
    fleet.add_argument(
        "--worker-id",
        default=os.environ.get("CEDAR_WORKER_ID", ""),
        help="this process's stable worker identity in a multi-process "
        "tier (CEDAR_WORKER_ID): stamps every metrics family's `worker` "
        "label and every trace/audit record, so N workers' scrapes and "
        "logs join instead of colliding; empty (default) on "
        "single-process deployments",
    )

    pod = parser.add_argument_group("pod (multi-host one-engine tier)")
    pod.add_argument(
        "--pod-coordinator",
        default=os.environ.get("CEDAR_POD_COORDINATOR", ""),
        help="jax.distributed coordinator host:port shared by every host "
        "of the pod (CEDAR_POD_COORDINATOR). With --pod-num-processes "
        ">= 2 this process joins ONE logical engine spanning the slice "
        "(cedar_tpu/pod, docs/fleet.md \"One mesh, many hosts\") — "
        "mutually exclusive with --fleet-replicas/--fanout-workers",
    )
    pod.add_argument(
        "--pod-num-processes",
        type=int,
        default=int(os.environ.get("CEDAR_POD_NUM_PROCESSES", "0") or 0),
        help="total processes in the pod (CEDAR_POD_NUM_PROCESSES); "
        "< 2 disables pod mode",
    )
    pod.add_argument(
        "--pod-process-id",
        type=int,
        default=int(os.environ.get("CEDAR_POD_PROCESS_ID", "0") or 0),
        help="this host's rank in the pod (CEDAR_POD_PROCESS_ID); rank 0 "
        "leads: control server, barrier swaps, HTTP serving — other "
        "ranks serve the collective over the control channel",
    )
    pod.add_argument(
        "--pod-control",
        default=os.environ.get("CEDAR_POD_CONTROL", ""),
        help="leader's pod control channel host:port (CEDAR_POD_CONTROL); "
        "empty = 127.0.0.1 on the default port — set it to the leader's "
        "reachable address on real multi-host deployments",
    )
    pod.add_argument(
        "--pod-local-devices",
        type=int,
        default=int(os.environ.get("CEDAR_POD_LOCAL_DEVICES", "0") or 0),
        help="simulated local device count (CPU platform CI only: "
        "XLA_FLAGS host_platform_device_count must ALSO be set before "
        "jax imports); 0 = the platform's real device count",
    )
    pod.add_argument(
        "--pod-mesh-shape",
        default=os.environ.get("CEDAR_POD_MESH_SHAPE", ""),
        help="explicit DATAxPOLICY factorization of the pod's GLOBAL "
        "device set (e.g. 2x4); empty defaults to (devices per host, "
        "hosts) — policy axis spans the pod, partitions host-exclusive",
    )

    serving = parser.add_argument_group("secure serving")
    serving.add_argument("--bind-address", default=DEFAULT_ADDRESS)
    serving.add_argument("--secure-port", type=int, default=DEFAULT_PORT)
    serving.add_argument("--metrics-port", type=int, default=METRICS_PORT)
    serving.add_argument(
        "--cert-dir",
        default="/var/run/cedar-authorizer/certs",
        help="directory for (generated) serving certs",
    )
    serving.add_argument("--tls-cert-file", default="")
    serving.add_argument("--tls-private-key-file", default="")
    serving.add_argument(
        "--insecure",
        action="store_true",
        help="serve plain HTTP (testing only)",
    )

    resilience = parser.add_argument_group("resilience")
    resilience.add_argument(
        "--request-timeout-ms",
        type=float,
        default=2000.0,
        help="per-request deadline budget; on expiry /v1/authorize answers "
        "NoOpinion+evaluationError and /v1/admit answers the configured "
        "fail-mode (0 disables)",
    )
    resilience.add_argument(
        "--admission-fail-mode",
        default="open",
        choices=["open", "closed"],
        help="admission answer when evaluation crashes or exceeds its "
        "deadline: open allows (keeps the cluster write path alive), "
        "closed denies (nothing unevaluated is admitted)",
    )
    resilience.add_argument(
        "--breaker-failure-threshold",
        type=int,
        default=5,
        help="consecutive evaluator errors that trip the TPU circuit "
        "breaker to the interpreter fallback (0 disables the breaker)",
    )
    resilience.add_argument(
        "--breaker-latency-threshold-ms",
        type=float,
        default=0.0,
        help="device evaluation latency counted as a breach; consecutive "
        "breaches also trip the breaker (0 = default to "
        "--request-timeout-ms: slower than any caller waits is breaching)",
    )
    resilience.add_argument(
        "--breaker-recovery-seconds",
        type=float,
        default=10.0,
        help="how long a tripped breaker stays open before half-open "
        "recovery probes",
    )
    resilience.add_argument(
        "--breaker-half-open-probes",
        type=int,
        default=2,
        help="consecutive successful probes that close a half-open breaker",
    )
    resilience.add_argument(
        "--supervisor-interval-seconds",
        type=float,
        default=1.0,
        help="watchdog poll interval for the self-healing supervisor: "
        "dead or wedged worker threads (batcher stages, shadow worker, "
        "CRD watch, store tickers) are restarted with their queues "
        "drained-or-shed, and fatal device errors trigger an engine "
        "rebuild (0 disables supervision; docs/resilience.md)",
    )
    resilience.add_argument(
        "--supervisor-wedge-seconds",
        type=float,
        default=10.0,
        help="busy-heartbeat age after which a live worker thread counts "
        "as wedged and is force-restarted (idle workers never trip this)",
    )
    resilience.add_argument(
        "--shutdown-grace-seconds",
        type=float,
        default=5.0,
        help="drain window on SIGTERM: /readyz flips to 503, new requests "
        "are shed, in-flight requests get this long to finish",
    )

    overload = parser.add_argument_group("overload control")
    overload.add_argument(
        "--max-inflight",
        type=int,
        default=0,
        help="size of the overload-control plane (cedar_tpu/load): "
        "requests are classified at ingress (kubelet/system SARs high, "
        "controller/admission normal, explain sheddable) and shed by "
        "priority as inflight/max-inflight crosses the graduated load "
        "states; sheds answer honestly (NoOpinion + Retry-After / the "
        "admission fail-mode) and /readyz reports the state (0 disables "
        "admission control entirely; docs/performance.md)",
    )
    overload.add_argument(
        "--shed-sheddable-at",
        type=float,
        default=0.5,
        help="load fraction at which sheddable (explain/operator) traffic "
        "sheds — the `pressure` state",
    )
    overload.add_argument(
        "--shed-normal-at",
        type=float,
        default=0.8,
        help="load fraction at which normal (controller/admission) "
        "traffic sheds — the `overload` state; high-priority traffic "
        "sheds only at saturation (load >= 1.0)",
    )
    overload.add_argument(
        "--client-qps",
        type=float,
        default=0.0,
        help="per-client fair-share quota (tokens/second) enforced under "
        "pressure so one hot controller cannot starve the kubelets; keyed "
        "by the SAR/admission username, high priority exempt (0 disables)",
    )
    overload.add_argument(
        "--client-burst",
        type=float,
        default=0.0,
        help="per-client quota burst headroom (0 = qps/2, min 1)",
    )
    overload.add_argument(
        "--client-enforce-at",
        type=float,
        default=-1.0,
        help="load fraction at which the per-client quota starts being "
        "enforced; default (-1) derives it from --shed-sheddable-at so "
        "the quota acts across the whole pressure band — a fixed value "
        "above --shed-normal-at would never act (normal traffic sheds "
        "wholesale first)",
    )
    overload.add_argument(
        "--shed-retry-after-seconds",
        type=float,
        default=1.0,
        help="the Retry-After hint shed answers carry",
    )
    overload.add_argument(
        "--adaptive-batching",
        action="store_true",
        help="SLO-adaptive batch tuning (cedar_tpu/load/tuner.py): a "
        "control loop reads the SLO latency burn rate and retunes each "
        "wired batcher's max-batch/linger inside the bounds below — grow "
        "batches while p99 has headroom, shrink linger the moment the "
        "latency objective burns; decisions logged at /debug/load. "
        "Requires the SLO tracker (--slo-availability-target > 0)",
    )
    overload.add_argument(
        "--tuner-interval-seconds",
        type=float,
        default=1.0,
        help="adaptive-batching control cadence (one knob move per tick)",
    )
    overload.add_argument(
        "--tuner-burn-window-seconds",
        type=float,
        default=60.0,
        help="trailing window the tuner reads the latency burn rate over "
        "(floored to one 10s SLO ring bucket)",
    )
    overload.add_argument(
        "--tuner-min-batch", type=int, default=64,
        help="adaptive-batching lower clamp on max-batch",
    )
    overload.add_argument(
        "--tuner-max-batch", type=int, default=16384,
        help="adaptive-batching upper clamp on max-batch",
    )
    overload.add_argument(
        "--tuner-min-linger-us", type=float, default=50.0,
        help="adaptive-batching lower clamp on the batch linger window",
    )
    overload.add_argument(
        "--tuner-max-linger-us", type=float, default=2000.0,
        help="adaptive-batching upper clamp on the batch linger window",
    )

    cache = parser.add_argument_group("decision cache")
    cache.add_argument(
        "--decision-cache-size",
        type=int,
        default=65536,
        help="max cached decisions (sharded LRU; 0 disables the cache). "
        "Keys are canonical request fingerprints; entries die on policy "
        "reload (generation bump) or their decision-class TTL",
    )
    cache.add_argument(
        "--decision-cache-allow-ttl-seconds",
        type=float,
        default=300.0,
        help="TTL for cached Allow decisions (mirrors kube-apiserver's "
        "--authorization-webhook-cache-authorized-ttl posture; 0 disables "
        "caching allows)",
    )
    cache.add_argument(
        "--decision-cache-deny-ttl-seconds",
        type=float,
        default=30.0,
        help="TTL for cached Deny decisions (shorter than allows: a newly "
        "granted permission should take effect quickly; 0 disables)",
    )
    cache.add_argument(
        "--decision-cache-no-opinion-ttl-seconds",
        type=float,
        default=5.0,
        help="TTL for cached NoOpinion decisions (shortest: these usually "
        "fall through to RBAC and carry the least signal; 0 disables)",
    )
    cache.add_argument(
        "--decision-cache-admission",
        action="store_true",
        help="opt-in admission decision caching, gated to read-only "
        "idempotent reviews (CONNECT operations and dryRun requests); "
        "mutating reviews always evaluate",
    )

    rollout = parser.add_argument_group("shadow rollout")
    rollout.add_argument(
        "--rollout-candidate-dir",
        default="",
        help="stage a candidate policy set from this directory of *.cedar "
        "files at startup (shadow evaluation starts immediately; promotion "
        "stays manual via POST /rollout/promote on the metrics port). "
        "Requires --backend tpu (docs/rollout.md)",
    )
    rollout.add_argument(
        "--shadow-sample-rate",
        type=float,
        default=1.0,
        help="fraction of live traffic shadow-evaluated against the staged "
        "candidate (0.0-1.0); sampling happens before the queue, so lower "
        "rates also shrink shadow CPU cost proportionally",
    )
    rollout.add_argument(
        "--shadow-queue-depth",
        type=int,
        default=1024,
        help="bounded shadow-evaluation queue; full-queue offers are shed "
        "(cedar_shadow_shed_total) rather than ever delaying live answers",
    )
    rollout.add_argument(
        "--shadow-duty-cycle",
        type=float,
        default=0.1,
        help="max fraction of one core the shadow worker may consume; "
        "under pressure the queue backs up and sheds so live serving "
        "never loses cpu to shadow evaluation (docs/rollout.md)",
    )
    rollout.add_argument(
        "--rollout-control-token-file",
        default="",
        help="file holding a bearer token required by the mutating "
        "rollout endpoints (POST /rollout/stage|promote|rollback). With "
        "neither this nor --rollout-insecure-control, those endpoints "
        "answer 403 — a staged allow-all + promote is a cluster "
        "authorization takeover, and the metrics listener is plain HTTP",
    )
    rollout.add_argument(
        "--rollout-insecure-control",
        action="store_true",
        help="allow UNAUTHENTICATED rollout lifecycle POSTs on the "
        "metrics listener (trusted-loopback deployments only)",
    )
    rollout.add_argument(
        "--lifecycle-spec-dir",
        default="",
        help="directory of PolicyRollout manifests (*.json) driven by "
        "the declarative lifecycle controller: verify → shadow → promote "
        "with evidence gates, automatic halt + rollback on breach "
        '(docs/rollout.md "Declarative lifecycle"). Requires the '
        "shadow-rollout plane (--backend tpu, no fanout); server specs "
        'should set "canaryLadder": [] — the in-process canary router '
        "is the embedded/bench deployment shape",
    )
    rollout.add_argument(
        "--lifecycle-journal-file",
        default="",
        help="JSONL write-ahead journal for lifecycle transitions; on "
        "restart the controller replays it, unwinds anything in flight "
        "to the live-only plane, and restarts those rollouts from "
        "pending (crash resume with no mixed-generation window). "
        "Default: in-memory (no resume across restarts)",
    )
    rollout.add_argument(
        "--lifecycle-interval-seconds",
        type=float,
        default=1.0,
        help="reconcile-loop period of the lifecycle controller",
    )

    obs = parser.add_argument_group("observability")
    obs.add_argument(
        "--trace-sample-rate",
        type=float,
        default=0.0,
        help="head-sample fraction of requests fully traced into "
        "/debug/traces (0.0-1.0). Independent of the rate, slow "
        "(past --trace-tail-ms), errored, and fallback-served requests "
        "are TAIL-KEPT — the default 0.0 still captures exactly the "
        "requests worth looking at (docs/observability.md)",
    )
    obs.add_argument(
        "--trace-tail-ms",
        type=float,
        default=0.0,
        help="tail-keep latency threshold: finished traces slower than "
        "this are kept even when unsampled; 0 defaults to "
        "--request-timeout-ms (a request that burned its budget is the "
        "one worth keeping)",
    )
    obs.add_argument(
        "--trace-ring",
        type=int,
        default=256,
        help="bounded in-memory ring of kept traces behind /debug/traces",
    )
    obs.add_argument(
        "--trace-log-file",
        default="",
        help="append kept traces as JSONL for offline cedar-trace "
        "analysis (empty disables export; the ring still serves)",
    )
    obs.add_argument(
        "--no-trace",
        action="store_true",
        help="disable the tracing plane entirely (no ring, no "
        "/debug/traces, no per-request span bookkeeping, no request "
        "phase ledger, no stall recorder and /debug/stalls)",
    )
    obs.add_argument(
        "--audit-log-file",
        default="",
        help="decision audit log (JSONL): one line per answered "
        "decision carrying the end-to-end trace id and the canonical "
        "request fingerprint shared with the recorder and the decision "
        "cache — joinable against recordings and cedar-why "
        "(docs/observability.md; empty disables)",
    )
    obs.add_argument(
        "--audit-max-bytes",
        type=int,
        default=64 * 1024 * 1024,
        help="size-based audit rotation threshold per file",
    )
    obs.add_argument(
        "--audit-max-files",
        type=int,
        default=3,
        help="rotated audit generations kept beside the live file",
    )
    obs.add_argument(
        "--slo-availability-target",
        type=float,
        default=0.999,
        help="availability SLO target (non-error answer fraction) behind "
        "/debug/slo and the cedar_slo_* burn-rate gauges; 0 disables "
        "the SLO plane",
    )
    obs.add_argument(
        "--slo-latency-target",
        type=float,
        default=0.99,
        help="latency SLO target: the fraction of requests that must "
        "answer within the latency budget",
    )
    obs.add_argument(
        "--slo-latency-budget-ms",
        type=float,
        default=0.0,
        help="latency SLO budget per request; 0 defaults to "
        "--request-timeout-ms",
    )

    gameday = parser.add_argument_group("gameday")
    gameday.add_argument("--artificial-error-rate", type=float, default=0.0)
    gameday.add_argument("--artificial-deny-rate", type=float, default=0.0)
    gameday.add_argument(
        "--confirm-non-prod-inject-errors",
        action="store_true",
        help="required gate for error injection — the reference response "
        "injector, the /chaos/* control endpoints, and --chaos-scenario "
        "(never set in production)",
    )
    gameday.add_argument(
        "--chaos-scenario",
        default="",
        help="arm a chaos scenario at startup: a built-in name "
        "(kill-decode, device-loss, poison-crd, store-stall) or a "
        "scenario JSON file; requires --confirm-non-prod-inject-errors "
        "(docs/resilience.md, cedar-chaos)",
    )

    tenancy = parser.add_argument_group("multi-tenancy")
    tenancy.add_argument(
        "--tenant",
        action="append",
        default=[],
        metavar="NAME=POLICY_DIR",
        help="register a tenant served from the fused shared plane "
        "(repeatable): NAME becomes the tenant id (DNS-label-ish), "
        "POLICY_DIR its *.cedar policy directory. All tenants compile "
        "into ONE engine with per-rule tenant discriminators; requests "
        "route by /t/<name>/v1/... path, the tenant header, or a host "
        "map (docs/multitenancy.md)",
    )
    tenancy.add_argument(
        "--tenant-header",
        default="x-cedar-tenant",
        help="HTTP header carrying the tenant id (default %(default)s)",
    )
    tenancy.add_argument(
        "--tenant-host",
        action="append",
        default=[],
        metavar="HOST=TENANT",
        help="map a Host/SNI hostname to a tenant (repeatable) — the "
        "shape a TLS-terminating LB hands multi-SNI traffic over in",
    )
    tenancy.add_argument(
        "--tenant-default",
        default="",
        help="tenant to assume when no path/header/host resolves one "
        "(default: refuse such requests)",
    )
    tenancy.add_argument(
        "--tenant-sources",
        default="path,header,host",
        metavar="SRC[,SRC...]",
        help="which resolution sources to trust, comma-separated subset "
        "of path,header,host (default %(default)s). Path and header are "
        "CLIENT-supplied: restrict to 'host' when tenants are "
        "authenticated by per-tenant SNI/LB routes, or a tenant could "
        "name a neighbor and evaluate under its policy slice. Enabled "
        "sources that disagree on a request are rejected (conflict)",
    )
    pdp = parser.add_argument_group("pdp front end")
    pdp.add_argument(
        "--pdp-listen",
        default="",
        metavar="[ADDR:]PORT",
        help="start the general PDP front end (cedar_tpu/pdp, "
        "docs/pdp.md) on this address: Envoy ext_authz HTTP-service "
        "checks on every path plus AVP-style POST /v1/batch-authorize; "
        "both map into the same planes, batcher ticks, cache and "
        "admission gate the webhook serves from (ADDR defaults to "
        "--bind-address; empty disables)",
    )
    pdp.add_argument(
        "--pdp-schema",
        default="",
        metavar="FILE",
        help="JSON attribute-mapping/fail-posture config for the PDP "
        "front end (identity/context headers, "
        "extauthz_deny_on_unavailable, tenant stamp, batch tuple cap); "
        "omitted = defaults (see docs/pdp.md)",
    )
    debug = parser.add_argument_group("debug")
    debug.add_argument("--profiling", action="store_true")
    debug.add_argument("--enable-recording", action="store_true")
    debug.add_argument("--recording-dir", default="/tmp/cedar-recordings")
    debug.add_argument("-v", "--verbosity", type=int, default=0)
    return parser


def _require_device() -> None:
    """--backend tpu serves from a TPU or not at all: raise unless JAX's
    default device is one (jaxenv.require_tpu). ``JAX_PLATFORMS=cpu`` is
    the operator asking for the CPU plane by name (tests, laptops) and
    skips the check; the CPU plane is never reached by accident."""
    from ..jaxenv import cpu_requested, require_tpu

    if cpu_requested():
        log.info("JAX_PLATFORMS=cpu: serving --backend tpu from the CPU plane")
        return
    dev = require_tpu()
    log.info(
        "device: platform=%s kind=%s count=%d",
        dev["platform"], dev["kind"], dev["count"],
    )


def _run_pod_mode(args) -> int:
    """Multi-host pod serving (cedar_tpu/pod): every host of the slice
    runs THIS entry with the same --config and coordinator, its own
    --pod-process-id. One logical engine spans the global device set;
    rank 0 leads (control server, barrier swaps, HTTP) and the other
    ranks serve the collective over the control channel — no HTTP, no
    private engine state beyond their addressable plane shards. Policy
    content resolves from each host's OWN stores; the pod swap barrier's
    token verify is what proves they resolved identically (a stale CRD
    cache on one host restores the whole pod and surfaces here).

    Exit codes match pod/hostmain.py: 3 = distributed bring-up refused
    (bounded, loud — a mis-wired coordinator/count/id must never hang)."""
    from ..jaxenv import DistributedInitError
    from ..pod.bootstrap import bootstrap
    from ..pod.control import PodControlServer, follow
    from ..pod.tier import PodTier, follower_handler
    from ..pod.topology import PodConfig

    if args.fleet_replicas > 1 or args.fanout_workers > 1:
        raise ValueError(
            "pod mode is its own scale-out layer: --pod-* is mutually "
            "exclusive with --fleet-replicas/--fanout-workers"
        )
    shape = None
    if args.pod_mesh_shape:
        d, _, p = args.pod_mesh_shape.lower().partition("x")
        shape = (int(d), int(p))
    config = PodConfig(
        coordinator=args.pod_coordinator or "127.0.0.1:7476",
        num_processes=args.pod_num_processes,
        process_id=args.pod_process_id,
        control=args.pod_control,
        local_devices=args.pod_local_devices or None,
        mesh_shape=shape,
    )
    try:
        ctx = bootstrap(config)
    except DistributedInitError as e:
        log.error("pod bring-up refused: %s", e)
        return 3
    try:
        # after bootstrap: jax.devices() before jax.distributed.initialize
        # would bring the backend up single-process
        _require_device()
    except RuntimeError as e:
        log.error("pod bring-up refused: %s", e)
        return 3

    from ..server.metrics import (
        set_pod_hosts,
        set_pod_process,
        set_worker_label,
    )

    set_worker_label(args.worker_id or ctx.host_name())
    set_pod_process(ctx.process_id)
    set_pod_hosts(ctx.num_processes)

    cfg = None
    if args.config:
        with open(args.config) as f:
            cfg = parse_config(f.read())
    stores = cedar_config_stores(cfg, kubeconfig_path=args.kubeconfig or None)

    from ..engine.evaluator import TPUPolicyEngine
    from ..fanout.worker import InProcessWorker
    from ..server.authorizer import CedarWebhookAuthorizer

    def tiers_factory(spec=None):
        # swaps re-resolve from THIS host's stores (spec is the barrier's
        # sentinel); the analysis gate rides along when the store has it
        del spec
        analyzed = getattr(stores, "analyzed_policy_sets", None)
        if analyzed is not None:
            return analyzed()
        return [s.policy_set() for s in stores.stores]

    env_rules = os.environ.get("CEDAR_TPU_MESH_DEVICE_RULES", "")
    engine = TPUPolicyEngine(
        name=ctx.host_name(),
        mesh=ctx.mesh,
        mesh_device_rules=int(env_rules) if env_rules else None,
    )

    def _eval(entities, request):
        if not engine.loaded:
            return stores.is_authorized(entities, request)
        return engine.evaluate(entities, request)

    def _eval_batch(items):
        if not engine.loaded:
            return [stores.is_authorized(em, r) for em, r in items]
        return engine.evaluate_batch(items)

    authorizer = CedarWebhookAuthorizer(
        stores, evaluate=_eval, evaluate_batch=_eval_batch
    )
    worker = InProcessWorker(
        ctx.host_name(),
        None,
        engine,
        tiers_factory=tiers_factory,
        authorizer=authorizer,
    )

    if not ctx.is_leader:
        # connect first, THEN compile: the leader's health scan must see
        # this host alive while its plane builds
        def setup():
            engine.load(tiers_factory(), warm="off")
            return follower_handler(worker, engine)

        log.info("pod follower %d serving the control loop", ctx.process_id)
        follow(config.control_addr(), ctx.process_id, setup)
        return 0

    ctl = PodControlServer(config.control_addr())
    try:
        ctl.wait_joined(ctx.num_processes - 1)
        engine.load(tiers_factory(), warm="off")
        tier = PodTier(ctx, worker, ctl.handles)
        ctl.start_health()

        server = WebhookServer(
            authorizer,
            None,
            address=args.bind_address,
            port=args.secure_port,
            metrics_port=args.metrics_port,
            certfile=args.tls_cert_file or None,
            keyfile=args.tls_private_key_file or None,
            pod=tier,
            max_batch=args.max_batch,
            batch_window_s=args.batch_window_us / 1e6,
        )
        server.start()
        stop = threading.Event()

        def _signal(signum, frame):
            log.info("received signal %d, shutting down", signum)
            stop.set()

        signal.signal(signal.SIGTERM, _signal)
        signal.signal(signal.SIGINT, _signal)

        last = _fingerprint(stores)
        interval = max(1.0, float(args.tpu_reload_seconds))
        while not stop.wait(interval):
            cur = _fingerprint(stores)
            if cur == last:
                continue
            try:
                tier.load({"generation": cur})
                last = cur
                log.info("pod: barrier swap committed (%s)", cur)
            except Exception:  # noqa: BLE001 — keep serving the prior set
                log.exception("pod: barrier swap failed; serving previous")
        server.stop()
        tier.stop()
        return 0
    finally:
        ctl.close()


def main(argv: Optional[List[str]] = None) -> int:
    args = make_parser().parse_args(argv)
    # basicConfig's one handler, its write and its lock taken off the
    # calling thread (obs/logsink.py): every line kept, none written by a
    # thread that owes an answer
    sink = logsink.install(
        logging.DEBUG if args.verbosity >= 5 else logging.INFO
    )
    if args.backend == "tpu" or args.pod_num_processes >= 2:
        from ..jaxenv import configure_compile_cache

        log.info("jax compilation cache: %s", configure_compile_cache())
    if args.pod_num_processes >= 2:
        return _run_pod_mode(args)
    if args.backend == "tpu":
        try:
            _require_device()
        except RuntimeError as e:
            log.error("--backend tpu refused: %s", e)
            return 1
    server = build_server(args)
    server.start()

    stop = threading.Event()

    def _signal(signum, frame):
        log.info("received signal %d, shutting down", signum)
        stop.set()

    signal.signal(signal.SIGTERM, _signal)
    signal.signal(signal.SIGINT, _signal)
    while not stop.wait(1.0):
        pass
    server.stop()
    if sink is not None:
        sink.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
