"""Rollout lifecycle: stage → shadow → inspect → promote / rollback.

The controller owns at most one CANDIDATE at a time. Staging compiles the
candidate tiers into fresh TPU engines (cloned from the live engines'
settings so they share backend, device, mesh and kernel-plane choices),
warms every serving shape through the existing ``TPUPolicyEngine.warmup``
ladder, and starts shadow evaluation of live traffic (shadow.py). All of
that happens off the hot path: the live engines, batchers and caches are
untouched until promotion.

Promotion is an atomic per-engine swap: the candidate's pre-warmed
compiled set moves into the live engine via ``adopt_compiled`` — zero new
jit traces (the candidate's warmup populated the shared kernel cache for
exactly these tensors) — and the live engine's ``load_generation`` bump
rides the existing ``cache_generation()`` composite, so every
pre-promotion decision-cache entry dies at its next lookup. The prior
compiled set is retained device-resident; ``rollback`` hands it back
through the same primitive without recompiling anything.

Interaction with the store reloader (cli/webhook.py TPUReloader): the
reloader recompiles only when store CONTENT changes, so a promotion —
which changes no store — keeps serving the candidate indefinitely. The
runbook (docs/rollout.md) has the operator commit the promoted content to
the backing store promptly; until then, breaker-open interpreter
fallbacks and store-level reloads serve the PRE-promotion corpus. If a
store reload lands between promote and rollback, rollback refuses (the
saved compiled set is no longer the serving lineage) instead of silently
reviving stale policy.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import List, Optional

from .report import DiffReport
from .shadow import DEFAULT_DUTY_CYCLE, DEFAULT_QUEUE_DEPTH, ShadowEvaluator
from .source import (
    candidate_tiers_from_directory,
    candidate_tiers_from_source,
)

log = logging.getLogger(__name__)

STATE_IDLE = "idle"
STATE_STAGED = "staged"
STATE_PROMOTED = "promoted"


class RolloutError(RuntimeError):
    """A lifecycle operation could not be performed (bad state, rejected
    candidate, diverged lineage). ``detail``, when present, is a
    JSON-shaped dict the HTTP layer returns in the 409 body (e.g. the
    per-replica lineage-divergence breakdown on a refused rollback)."""

    def __init__(self, message: str, detail: Optional[dict] = None):
        super().__init__(message)
        self.detail = detail


def _record_fleet_rollback() -> None:
    try:
        from ..server.metrics import record_fleet_promotion

        record_fleet_promotion("rolled_back")
    except Exception:  # noqa: BLE001 — metrics never gate the restore
        pass


def _clone_engine(name: str, template):
    """A fresh TPUPolicyEngine with the template's backend settings — the
    candidate must compile against the same device/mesh/kernel planes as
    the live engine or promotion would swap in tensors the serving kernels
    were never warmed for."""
    from ..engine.evaluator import TPUPolicyEngine

    return TPUPolicyEngine(
        schema=template.schema,
        device=template.device,
        mesh=template.mesh,
        segred=template.segred,
        name=name,
        warm_max_batch=template.warm_max_batch,
        incremental=template.incremental,
        shard_buckets=template.shard_buckets,
        partition=template.partition,
    )


def candidate_stores(tiers):
    """(authz TieredPolicyStores, admission TieredPolicyStores) over
    candidate tiers — the ONE candidate stack-store assembly (MemoryStore
    per tier + the allow-all admission tail), shared by the live stage
    path (_build_stack) and the offline cedar-shadow CLI so the two can
    never assemble different stacks from the same tiers."""
    from ..server.admission import allow_all_admission_policy_store
    from ..stores.store import MemoryStore, TieredPolicyStores

    authz = TieredPolicyStores(
        [MemoryStore(f"candidate-tier{i}", ps) for i, ps in enumerate(tiers)]
    )
    admission = TieredPolicyStores(
        list(authz.stores) + [allow_all_admission_policy_store()]
    )
    return authz, admission


class _Candidate:
    """Everything staged for one candidate: tiers, engines, and the
    interpreter stacks the shadow evaluator answers from."""

    def __init__(self, tiers, description: str):
        self.tiers = tiers
        self.description = description
        self.staged_at = time.time()
        self.analysis = None  # AnalysisReport from the stage gate
        self.authz_engine = None
        self.admission_engine = None
        self.authorizer = None
        self.admission_handler = None
        self.warm_state = "unwarmed"  # unwarmed | warming | ready | failed
        self.warm_stats: dict = {}


class RolloutController:
    """Owns the staged candidate, the shadow evaluator, and the
    promote/rollback swap points for the live engines."""

    def __init__(
        self,
        authz_engine=None,
        admission_engine=None,
        sample_rate: float = 1.0,
        queue_depth: int = DEFAULT_QUEUE_DEPTH,
        exemplar_cap: int = 64,
        stage_validation_mode: str = "strict",
        engine_factory=None,
        duty_cycle: float = DEFAULT_DUTY_CYCLE,
        crd_candidate_provider=None,
        authz_fleet=None,
        audit_sink=None,
    ):
        # live engines (None on interpreter-only deployments — staging and
        # shadowing still work through the interpreter; promotion needs
        # the engines and refuses without them)
        #
        # authz_fleet: an EngineFleet (cedar_tpu/fleet) replaces the single
        # authorization engine at the SWAP points — it duck-types
        # adopt_compiled/load_generation, so promotion becomes
        # fleet-atomic (every replica swaps under the fleet's generation
        # barrier or none do) and the lineage checks become per-replica.
        # The candidate still compiles on ONE clone of the template
        # engine; adoption into every replica is compile-free.
        self.authz_fleet = authz_fleet
        if authz_fleet is not None and authz_engine is None:
            authz_engine = authz_fleet.template_engine
        self.authz_engine = authz_engine
        self.admission_engine = admission_engine
        self.sample_rate = sample_rate
        self.queue_depth = queue_depth
        self.exemplar_cap = exemplar_cap
        self.duty_cycle = duty_cycle
        # the analysis posture applied at STAGE time, independent of the
        # serving stack's validation mode: a candidate that cannot lower
        # (or carries permit/forbid conflicts) must be rejected before it
        # shadows anything, whatever the live gate tolerates
        self.stage_validation_mode = stage_validation_mode
        self._engine_factory = engine_factory or _clone_engine
        # () -> [PolicyObject]: the CRD stores' candidate-labeled objects
        # (stores withhold them from live serving); stage(crd=True) builds
        # the candidate corpus from them (cli/webhook.py wires this)
        self._crd_candidate_provider = crd_candidate_provider
        # entry-dict callable (AuditLog.record-compatible): every
        # stage/promote/rollback — including refusals — lands one record,
        # so the audit trail shows WHO changed what served, not just the
        # decisions that followed. Best-effort: a sick sink never gates a
        # lifecycle operation.
        self._audit_sink = audit_sink
        self._lock = threading.Lock()
        self._state = STATE_IDLE
        self._candidate: Optional[_Candidate] = None
        self._shadow: Optional[ShadowEvaluator] = None
        self._report: Optional[DiffReport] = None
        self._promoted: Optional[_Candidate] = None
        # role -> (live engine, prior compiled set, generation after swap)
        self._rollback_points: dict = {}
        # monotonic lifecycle counter (cedar_rollout_generation): bumps on
        # every stage/promote/rollback so dashboards can see transitions
        self.generation = 0

    # ------------------------------------------------------------ lifecycle

    def stage(
        self,
        tiers: Optional[List] = None,
        directory: Optional[str] = None,
        source: Optional[str] = None,
        crd: bool = False,
        description: str = "",
        warm: str = "async",
        sample_rate: Optional[float] = None,
    ) -> dict:
        """Stage a candidate policy set: resolve the tiers, run the static
        analysis gate, compile candidate engines off the hot path, start
        warming, and begin shadow evaluation. Replaces any previously
        staged candidate (its diff report is discarded). Raises
        RolloutError when the candidate fails to load or is rejected by
        analysis."""
        from ..chaos.registry import chaos_fire

        chaos_fire("rollout.stage")
        if tiers is None:
            if directory:
                tiers = candidate_tiers_from_directory(directory)
                description = description or f"directory:{directory}"
            elif source is not None:
                tiers = candidate_tiers_from_source(source)
                description = description or "inline-source"
            elif crd:
                if self._crd_candidate_provider is None:
                    raise RolloutError(
                        "no CRD candidate provider wired (the webhook CLI "
                        "wires one when a CRD store is configured)"
                    )
                from .source import candidate_tiers_from_objects

                tiers = candidate_tiers_from_objects(
                    self._crd_candidate_provider()
                )
                description = description or "crd-label"
            else:
                raise RolloutError(
                    "stage requires tiers, a directory, a source string, "
                    "or crd=True"
                )
        if not tiers:
            raise RolloutError("stage: candidate has no tiers")

        self._finalize_or_refuse_promotion()
        cand = _Candidate(tiers, description)
        gated_tiers = self._gate(cand, tiers)
        self._build_stack(cand, gated_tiers)
        with self._lock:
            if self._state == STATE_PROMOTED:
                # a concurrent promote() landed while this stage was
                # compiling outside the lock; installing now would strand
                # its rollback point under a STAGED state
                raise RolloutError(
                    "a promotion landed while the candidate was compiling: "
                    "rollback or commit it before staging"
                )
            old_shadow = self._detach_shadow_locked()
            self._candidate = cand
            self._report = DiffReport(exemplar_cap=self.exemplar_cap)
            self._shadow = ShadowEvaluator(
                cand,
                self._report,
                sample_rate=(
                    self.sample_rate if sample_rate is None else sample_rate
                ),
                queue_depth=self.queue_depth,
                duty_cycle=self.duty_cycle,
                attributor=self._build_attributor(cand),
            )
            self._state = STATE_STAGED
            self._bump_generation_locked()
        self._stop_shadow(old_shadow)
        self._start_warm(cand, warm)
        log.info(
            "staged candidate %r (%d tier(s), warm=%s)",
            cand.description,
            len(tiers),
            warm,
        )
        self._audit("staged", description=cand.description, tiers=len(tiers))
        return self.status()

    def set_audit_sink(self, sink) -> None:
        """Late-bind the audit sink (the CLI builds the AuditLog after
        the rollout controller)."""
        self._audit_sink = sink

    def _audit(self, event: str, **fields) -> None:
        sink = self._audit_sink
        if sink is None:
            return
        try:
            sink(
                {
                    "kind": "rollout",
                    "event": event,
                    "ts": time.time(),
                    "generation": self.generation,
                    **fields,
                }
            )
        except Exception:  # noqa: BLE001 — audit never gates the lifecycle
            log.exception("rollout audit record failed")

    def _finalize_or_refuse_promotion(self) -> None:
        """Staging over an ACTIVE promotion would strand its rollback
        point (a later rollback would discard the new candidate and leave
        the promoted set irrevocable through the API). Two cases:

          * a store reload landed on ANY swapped engine — the promotion is
            superseded (rollback already refuses on the same predicate, so
            keeping the point would wedge the lifecycle: no stage, no
            rollback); finalize it and let the stage proceed;
          * the promotion is still live — refuse with the recovery steps.
        """
        with self._lock:
            if self._state != STATE_PROMOTED:
                return
            superseded = any(
                live.load_generation != generation
                for live, _prior, generation in self._rollback_points.values()
            )
            if superseded and self._rollback_points:
                log.info(
                    "previous promotion superseded by store reloads; "
                    "finalizing it (rollback point discarded)"
                )
                self._rollback_points = {}
                self._promoted = None
                self._state = STATE_IDLE
                return
            raise RolloutError(
                "a promotion is still active: rollback first, or commit "
                "the promoted content to the policy store (the reload "
                "finalizes the promotion) before staging a new candidate"
            )

    def _gate(self, cand: _Candidate, tiers) -> list:
        """Static-analysis stage gate (analysis/loadgate.py): the
        candidate is analyzed as a whole tier stack; blocking findings
        (unlowerable constructs, permit/forbid conflicts) reject the stage
        under the default strict posture. publish=False keeps candidate
        findings out of the LIVE set's cedar_policy_* metrics."""
        from ..analysis.loadgate import AnalysisRejected, enforce

        try:
            gated, report = enforce(
                tiers, self.stage_validation_mode, publish=False
            )
        except AnalysisRejected as e:
            cand.analysis = e.report
            raise RolloutError(f"candidate rejected by analysis: {e}")
        cand.analysis = report
        return gated

    def _build_stack(self, cand: _Candidate, gated_tiers) -> None:
        """Compile candidate engines (when the live side has engines) and
        build the interpreter stacks the shadow evaluator answers from."""
        from ..server.admission import (
            CedarAdmissionHandler,
            allow_all_admission_policy_store,
        )
        from ..server.authorizer import CedarWebhookAuthorizer

        authz_stores, admission_stores = candidate_stores(cand.tiers)
        admission_tail = allow_all_admission_policy_store().policy_set()

        evaluate = evaluate_batch = None
        adm_evaluate = adm_evaluate_batch = None
        try:
            if self.authz_engine is not None:
                cand.authz_engine = self._engine_factory(
                    "candidate-authorization", self.authz_engine
                )
                cand.authz_engine.load(list(gated_tiers), warm="off")
                evaluate = cand.authz_engine.evaluate
                evaluate_batch = cand.authz_engine.evaluate_batch
            if self.admission_engine is not None:
                cand.admission_engine = self._engine_factory(
                    "candidate-admission", self.admission_engine
                )
                cand.admission_engine.load(
                    list(gated_tiers) + [admission_tail], warm="off"
                )
                adm_evaluate = cand.admission_engine.evaluate
                adm_evaluate_batch = cand.admission_engine.evaluate_batch
        except Exception as e:
            raise RolloutError(f"candidate failed to compile: {e}")

        cand.authorizer = CedarWebhookAuthorizer(
            authz_stores, evaluate=evaluate, evaluate_batch=evaluate_batch
        )
        cand.admission_handler = CedarAdmissionHandler(
            admission_stores,
            evaluate=adm_evaluate,
            evaluate_batch=adm_evaluate_batch,
        )

    def _build_attributor(self, cand: _Candidate):
        """The explain-plane DiffAttributor for this candidate: on a
        shadow diff the exemplar gains live-vs-candidate
        determining-policy attribution (docs/explainability.md). Built
        best-effort — an attributor failure must never gate staging."""
        try:
            from ..explain import DiffAttributor

            return DiffAttributor(
                live_authz_engine=self.authz_engine,
                live_admission_engine=self.admission_engine,
                candidate=cand,
            )
        except Exception:  # noqa: BLE001 — attribution is optional
            log.exception("diff attributor construction failed")
            return None

    def _start_warm(self, cand: _Candidate, warm: str) -> None:
        engines = [
            e
            for e in (cand.authz_engine, cand.admission_engine)
            if e is not None
        ]
        if warm == "off" or not engines:
            cand.warm_state = "ready"
            return

        from ..engine.evaluator import (
            untrack_warm_thread,
            warm_shutdown_set,
        )

        def _live():
            # polled per shape inside warmup() too: an orphaned ladder of
            # compiles for a superseded candidate steals live-request cpu
            return self._candidate is cand and not warm_shutdown_set()

        def _warm_all():
            try:
                for engine in engines:
                    if not _live():
                        return  # superseded mid-warm; the new stage owns it
                    cand.warm_stats[engine.name] = engine.warmup(
                        should_continue=_live
                    )
                if not _live():
                    return  # bailed mid-ladder: never claim readiness
                cand.warm_state = "ready"
            except Exception:  # noqa: BLE001 — an unwarmed candidate still shadows
                log.exception("candidate warm-up failed")
                cand.warm_state = "failed"
            finally:
                untrack_warm_thread(threading.current_thread())

        cand.warm_state = "warming"
        if warm == "sync":
            _warm_all()
        else:
            from ..engine.evaluator import track_warm_thread

            # registered with the engine module's atexit join: a daemon
            # thread killed inside an XLA call at interpreter teardown
            # aborts the whole process (see evaluator.py)
            t = threading.Thread(
                target=_warm_all, name="rollout-warm", daemon=True
            )
            track_warm_thread(t)
            t.start()

    def warm_ready(self) -> bool:
        cand = self._candidate
        return cand is not None and cand.warm_state == "ready"

    def promote(self, force: bool = False) -> dict:
        """Atomically swap the candidate's pre-warmed compiled sets into
        the live engines and end shadowing. Requires a staged candidate
        whose warm-up finished (``force=True`` overrides — the first
        post-promotion requests may then pay compiles). The previous
        compiled sets are retained for rollback()."""
        from ..chaos.registry import chaos_fire

        chaos_fire("rollout.promote")
        with self._lock:
            cand = self._candidate
            if self._state != STATE_STAGED or cand is None:
                raise RolloutError("promote: no staged candidate")
            if self.authz_engine is None:
                raise RolloutError(
                    "promote requires the TPU backend (no live engine to "
                    "swap); interpreter deployments change the store content "
                    "instead"
                )
            if cand.warm_state != "ready" and not force:
                raise RolloutError(
                    f"promote: candidate warm-up is {cand.warm_state} "
                    "(pass force=True to promote cold)"
                )
            swaps = []
            for role, live, staged in (
                (
                    "authorization",
                    self.authz_fleet or self.authz_engine,
                    cand.authz_engine,
                ),
                ("admission", self.admission_engine, cand.admission_engine),
            ):
                if live is None or staged is None:
                    continue
                if staged.compiled_set is None:
                    raise RolloutError(f"promote: candidate {role} engine empty")
                swaps.append((role, live, staged))
            rollback_points = {}
            done = []
            failed_role = None
            try:
                for role, live, staged in swaps:
                    failed_role = role
                    # donor transplant covers the mesh engines'
                    # per-instance pjit-step caches (see adopt_compiled);
                    # a fleet swaps every replica under its generation
                    # barrier here — or raises having restored them all
                    prior, generation = live.adopt_compiled(
                        staged.compiled_set, donor=staged
                    )
                    done.append((role, live, prior))
                    rollback_points[role] = (live, prior, generation)
            except Exception as e:
                # cross-ROLE atomicity: an admission swap failing after
                # the authorization swap landed must not leave the two
                # roles on different policy sets — restore compile-free
                # and refuse the promotion (the fleet's own barrier
                # already restored its replicas before raising)
                for _role, live, prior in reversed(done):
                    try:
                        live.adopt_compiled(prior)
                        if hasattr(live, "replicas"):
                            # a fleet that committed its barrier and was
                            # then undone by a LATER role's failure must
                            # audit as rolled back, or the promotions
                            # counter shows a commit that never served
                            _record_fleet_rollback()
                    except Exception:  # noqa: BLE001 — keep restoring
                        log.exception(
                            "promote: restore of %s after a failed swap "
                            "ALSO failed",
                            _role,
                        )
                raise RolloutError(
                    f"promote: {failed_role} swap failed; every engine "
                    f"restored to the prior set: {e}"
                )
            self._rollback_points = rollback_points
            self._promoted = cand
            self._candidate = None
            old_shadow = self._detach_shadow_locked()
            self._state = STATE_PROMOTED
            self._bump_generation_locked()
        self._stop_shadow(old_shadow)
        log.info(
            "promoted candidate %r into %d live engine(s)",
            cand.description,
            len(self._rollback_points),
        )
        self._audit(
            "promoted",
            description=cand.description,
            roles=sorted(self._rollback_points),
        )
        return self.status()

    def rollback(self) -> dict:
        """Staged: discard the candidate (nothing live changed).
        Promoted: restore the prior compiled sets through adopt_compiled —
        no recompilation — unless a store-driven reload landed on a live
        engine since promotion (the saved set is then stale and rollback
        refuses)."""
        old_shadow = None
        with self._lock:
            if self._state == STATE_STAGED:
                old_shadow = self._detach_shadow_locked()
                self._candidate = None
                # nothing left to inspect: keeping the discarded
                # candidate's diff report would read as diffs of a
                # current/next rollout on /debug/rollout
                self._report = None
                self._state = STATE_IDLE
                self._bump_generation_locked()
                log.info("discarded staged candidate")
                discarded = True
            else:
                discarded = False
        if discarded:
            self._stop_shadow(old_shadow)
            self._audit("rollback_discarded")
            # status() re-acquires the (non-reentrant) lock — outside only
            return self.status()
        with self._lock:
            if self._state != STATE_PROMOTED:
                raise RolloutError("rollback: nothing staged or promoted")
            diverged = [
                self._divergence_entry(role, live, generation)
                for role, (live, _prior, generation)
                in self._rollback_points.items()
                if live.load_generation != generation
            ]
            if diverged:
                detail = {
                    "diverged": diverged,
                    "classification": self._classify_divergence(diverged),
                }
                self._audit("rollback_refused", detail=detail)
                raise RolloutError(
                    "rollback: live engine(s) reloaded since promotion "
                    "(store content changed); the saved set is stale — "
                    "restore by reverting the store content ("
                    + ", ".join(e["role"] for e in diverged)
                    + " diverged)",
                    detail=detail,
                )
            for role, (live, prior, _generation) in self._rollback_points.items():
                if prior is None:
                    raise RolloutError(
                        f"rollback: no prior compiled set for {role}"
                    )
            for role, (live, prior, _generation) in self._rollback_points.items():
                live.adopt_compiled(prior)
            self._rollback_points = {}
            self._promoted = None
            self._state = STATE_IDLE
            self._bump_generation_locked()
        log.info("rolled back to the pre-promotion compiled sets")
        self._audit("rolled_back")
        return self.status()

    @staticmethod
    def _divergence_entry(role: str, live, generation) -> dict:
        """One role's lineage-divergence breakdown for the refusal body
        and audit record: expected (post-promotion) vs live generations,
        per replica when the live side is a fleet — so operators can
        tell a whole-plane store reload from a single wedged replica."""
        def _doc(g):
            return list(g) if isinstance(g, tuple) else g

        entry = {
            "role": role,
            "expected_generation": _doc(generation),
            "live_generation": _doc(live.load_generation),
        }
        replicas = getattr(live, "replicas", None)
        if replicas is not None and isinstance(generation, tuple):
            entry["replicas"] = [
                {
                    "replica": r.name,
                    "expected_generation": expected,
                    "live_generation": r.engine.load_generation,
                    "diverged": r.engine.load_generation != expected,
                }
                for r, expected in zip(replicas, generation)
            ]
        return entry

    def _classify_divergence(self, diverged) -> str:
        """``store_reload_superseded`` — every engine (and every fleet
        replica) moved on uniformly, the signature of a store-content
        reload; ``partial_promotion_wedge`` — only a subset diverged,
        which means the serving plane is split across lineages and needs
        operator attention beyond a store revert."""
        if len(diverged) < len(self._rollback_points):
            return "partial_promotion_wedge"
        for entry in diverged:
            reps = entry.get("replicas")
            if reps and not all(r["diverged"] for r in reps):
                return "partial_promotion_wedge"
        return "store_reload_superseded"

    def stop(self) -> None:
        self._stop_shadow(self._detach_shadow())

    def shadow_worker_threads(self) -> list:
        """The CURRENT shadow worker thread(s) — supervisor liveness probe
        (empty with nothing staged, so the probe reads healthy)."""
        shadow = self._shadow
        return shadow.worker_threads() if shadow is not None else []

    def revive_shadow(self, force: bool = False) -> bool:
        """Supervisor restart hook for the current shadow worker."""
        shadow = self._shadow
        return shadow.revive(force) if shadow is not None else False

    def shadow_heartbeats(self) -> dict:
        """The current shadow worker's heartbeat (supervisor wedge probe;
        re-read per check so re-staging swaps stay covered)."""
        shadow = self._shadow
        return {"shadow": shadow.heartbeat} if shadow is not None else {}

    def _detach_shadow(self):
        """Unhook the shadow evaluator under the lock and hand it back for
        the caller to stop OUTSIDE the lock: stop() joins the worker (up
        to 5s, longer wall if it sits in a candidate jit trace), and
        holding the controller lock across that join would block
        /debug/rollout and every lifecycle call for the duration."""
        with self._lock:
            return self._detach_shadow_locked()

    def _detach_shadow_locked(self):
        shadow, self._shadow = self._shadow, None
        return shadow

    @staticmethod
    def _stop_shadow(shadow) -> None:
        if shadow is not None:
            shadow.stop()

    def _bump_generation_locked(self) -> None:
        self.generation += 1
        try:
            from ..server import metrics

            metrics.set_rollout_generation(self.generation)
        except Exception:  # noqa: BLE001 — metrics never gate lifecycle
            pass

    # -------------------------------------------------------------- serving

    def offer(self, endpoint: str, body: bytes, live) -> bool:
        """Hand one live (body, answer) pair to the shadow evaluator.
        Called from the serving paths — must never raise or block."""
        shadow = self._shadow
        if shadow is None:
            return False
        try:
            return shadow.offer(endpoint, body, live)
        except Exception:  # noqa: BLE001 — shadow must never hurt serving
            log.exception("shadow offer failed")
            return False

    def drain(self, timeout_s: float = 10.0) -> bool:
        shadow = self._shadow
        return True if shadow is None else shadow.drain(timeout_s)

    def candidate_stack(self):
        """(authorizer, admission_handler) of the STAGED candidate, or
        None — the lifecycle canary router (cedar_tpu/lifecycle) answers
        its canary slice through these: the same cache-bypassing stacks
        the shadow evaluator evaluates against."""
        cand = self._candidate
        if cand is None:
            return None
        return cand.authorizer, cand.admission_handler

    @property
    def report(self) -> Optional[DiffReport]:
        return self._report

    def set_sample_rate(self, rate: float) -> None:
        self.sample_rate = max(0.0, min(1.0, float(rate)))
        shadow = self._shadow
        if shadow is not None:
            shadow.sample_rate = self.sample_rate

    def effective_sample_rate(self) -> float:
        """The rate actually in force: a per-stage override lives on the
        shadow evaluator, not on the controller default."""
        shadow = self._shadow
        return shadow.sample_rate if shadow is not None else self.sample_rate

    # -------------------------------------------------------------- status

    def status(self) -> dict:
        """The /debug/rollout document."""
        with self._lock:
            cand = self._candidate or self._promoted
            doc: dict = {
                "state": self._state,
                "generation": self.generation,
                "sample_rate": self.effective_sample_rate(),
            }
            if cand is not None:
                doc["candidate"] = {
                    "description": cand.description,
                    "staged_at": cand.staged_at,
                    "tiers": len(cand.tiers),
                    "policies": sum(
                        len(ps.policies()) for ps in cand.tiers
                    ),
                    "warm_state": cand.warm_state,
                    "warm_stats": cand.warm_stats,
                    "analysis_findings": (
                        cand.analysis.counts() if cand.analysis else {}
                    ),
                }
            engines = {}
            for role, live in (
                ("authorization", self.authz_fleet or self.authz_engine),
                ("admission", self.admission_engine),
            ):
                if live is not None:
                    engines[role] = {
                        "load_generation": live.load_generation,
                        **live.stats,
                    }
            if engines:
                doc["live_engines"] = engines
            if self._report is not None:
                doc["diff"] = self._report.to_dict()
            shadow = self._shadow
            if shadow is not None:
                doc["shadow_queue"] = shadow.queue_depth()
            return doc
