"""The webhook HTTP servers.

Behavior parity with reference internal/server/server.go + health.go:
  * TLS server (default 127.0.0.1:10288) serving ``/v1/authorize``
    (SubjectAccessReview → decision; decode errors yield NoOpinion with an
    evaluationError, :104-107) and ``/v1/admit`` (AdmissionReview)
  * per-request metrics: decision-labelled counter + latency histogram, with
    ``<error>`` as the decision label on errors (:78-91)
  * optional request recording middleware and debug endpoints behind the
    profiling flag (the Python analogue of net/http/pprof: live thread
    dumps and a timed cProfile capture)
  * plain-HTTP health/metrics server (default 127.0.0.1:10289) with
    always-200 /healthz + /readyz stubs and /metrics (health.go:14-36)
  * SubjectAccessReview → Attributes conversion incl. label/field selector
    requirement parsing (GetAuthorizerAttributes, :163-214; the selector
    conversion mirrors the upstream-k8s helpers copied at :221-309)
"""

from __future__ import annotations

import contextlib
import json
import logging
import re
import ssl
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from ..chaos.registry import chaos_fire
from ..engine.batcher import DeadlineExceeded
from ..fanout.frontend import FanoutUnavailable
from ..fleet.router import FleetUnavailable
from ..load.admission import STATE_SATURATED, RequestShed
from ..obs.trace import (
    RequestPhases,
    current_phases,
    current_trace,
    format_traceparent,
    ingest_request_id,
    new_span_id,
    new_trace_id,
    profiler_scope,
    set_current,
    set_phases,
)
from ..obs.trace import span as trace_span
from ..entities.admission import AdmissionRequest
from ..entities.attributes import (
    Attributes,
    FieldSelectorRequirement,
    LabelSelectorRequirement,
    UserInfo,
)
from ..tenancy.frontend import TenantBody
from . import metrics
from .admission import AdmissionResponse, CedarAdmissionHandler
from .authorizer import (
    DECISION_ALLOW,
    DECISION_DENY,
    DECISION_NO_OPINION,
    CedarWebhookAuthorizer,
)
from .error_injector import ErrorInjector
from .recorder import RequestRecorder

log = logging.getLogger(__name__)

DEFAULT_ADDRESS = "127.0.0.1"
DEFAULT_PORT = 10288
METRICS_PORT = 10289
# Accepted POST body cap; the apiserver caps its own request payloads at
# ~3MiB, so 8MiB leaves headroom while bounding hostile bodies (which could
# otherwise drive deep-nesting parse attacks or exhaust memory).
MAX_BODY_BYTES = 8 * 1024 * 1024

_DECISION_LABEL = {
    DECISION_ALLOW: "Allow",
    DECISION_DENY: "Deny",
    DECISION_NO_OPINION: "NoOpinion",
}

# metav1.LabelSelectorOperator -> k8s selection.Operator strings
# (reference server.go:221-226)
_LABEL_OPS = {"In": "in", "NotIn": "notin", "Exists": "exists", "DoesNotExist": "!"}

# A webhook POST's header block as the handler's one scan takes it
# (Handler._scan_request, docs/performance.md "One read, one write"):
# field lines of a printable name, a colon and printable ASCII, each ended
# by CRLF, the blank line last; 99 of them at most, because http.client
# reads 100 lines, the blank one among them. A folded line, a bare CR or
# LF, a byte the email parser would break a line at or a line with no name
# does not match, and the request goes to http.server's reader untouched.
_HEADER_BLOCK = re.compile(rb"(?:[!-9;-~]+:[\t -~]*\r\n){1,99}\r\n")


class _ScannedHeaders:
    """The header fields of a scanned request under lower-cased names.
    ``get`` is what the handler, the tenant front end and
    ingest_request_id ask of ``headers``; of a repeated name the first
    value stands, as ``email.message.Message.get`` has it."""

    __slots__ = ("_fields",)

    def __init__(self, fields: dict):
        self._fields = fields

    def get(self, name: str, default=None):
        return self._fields.get(name.lower(), default)


# per-request observation context (cedar_tpu/obs): the serving layers
# report cached/fallback facts UPWARD to the request handler's trace
# tail-keep + audit line without changing any layer's call contract — a
# thread-local, like the active trace, because a request owns its thread
# end to end (singleflight leaders run in the requesting thread)
_obs_local = threading.local()


def _admit_outcome(review) -> tuple:
    """(metric label, error-or-None) for a rendered AdmissionReview —
    the decision facts read back out of the response the caller is
    already returning, so this can never change an answer."""
    resp = (review or {}).get("response") or {}
    status = resp.get("status") or {}
    error = (
        None
        if review is not None and status.get("code") in (None, 200)
        else (status.get("message") or "no response")
    )
    label = (
        "<error>"
        if error
        else ("allowed" if resp.get("allowed") else "denied")
    )
    return label, error


# the envelope's own fields, which a kube-apiserver writes before the
# objects: the request's kind ({"group", "version", "kind"}) and operation
_ADMIT_KIND = re.compile(rb'"kind"\s*:\s*\{[^{}]*?"kind"\s*:\s*"([^"\\]*)"')
_ADMIT_OPERATION = re.compile(rb'"operation"\s*:\s*"([A-Z]+)"')
_ADMIT_HEAD_BYTES = 2048


def _set_admit_attrs(root, body: bytes) -> None:
    """``body_bytes``, ``operation`` and ``kind`` on an admission trace's
    root. The native path never parses the review in Python, so the two
    names are read from the envelope's head by pattern: absent where the
    head does not hold them, never a parse."""
    root.set_attr("body_bytes", len(body))
    head = body[:_ADMIT_HEAD_BYTES]
    for name, pattern in (("operation", _ADMIT_OPERATION), ("kind", _ADMIT_KIND)):
        found = pattern.search(head)
        if found is not None:
            root.set_attr(name, found.group(1).decode("utf-8", "replace"))


def _octx() -> Optional[dict]:
    return getattr(_obs_local, "ctx", None)


def _octx_set(ctx: Optional[dict]) -> None:
    _obs_local.ctx = ctx


def _octx_mark(key: str) -> None:
    ctx = _octx()
    if ctx is not None:
        ctx[key] = True


def _answered_by(octx: dict, answer) -> str:
    """Who answered an authorization request, for the ``by`` label of the
    handler's timer and the root span's ``answered_by``: the decision
    cache, the webhook's own rules before Cedar or the interpreter for
    one row (both marked on the result by engine/fastpath.py), the
    interpreter in the request thread, else the engine."""
    if "cached" in octx:
        return "cache"
    by = getattr(answer, "answered_by", None)
    if by is not None:
        return by
    if "interpreter" in octx:
        return "interpreter"
    return "engine"


def convert_extra(extra: Optional[dict]) -> dict:
    """Extra keys are lower-cased (reference convertExtraForAuthorizerAttributes,
    server.go:205-214)."""
    if not extra:
        return {}
    return {k.lower(): tuple(v) for k, v in extra.items()}


def label_selector_requirements(requirements: list) -> tuple:
    """metav1.LabelSelectorRequirement list → parsed requirements; invalid
    operators are dropped (ANDed semantics make that strictly broader,
    reference server.go:228-261)."""
    out = []
    for req in requirements or []:
        op = _LABEL_OPS.get(req.get("operator", ""))
        if op is None:
            log.error(
                "%r is not a valid label selector operator", req.get("operator")
            )
            continue
        out.append(
            LabelSelectorRequirement(
                key=req.get("key", ""),
                operator=op,
                values=tuple(req.get("values") or ()),
            )
        )
    return tuple(out)


def field_selector_requirements(requirements: list) -> tuple:
    """metav1.FieldSelectorRequirement list → parsed requirements; only
    single-valued In/NotIn convert (to =/!=), like the upstream helper
    (reference server.go:263-309)."""
    out = []
    for req in requirements or []:
        values = req.get("values") or []
        op = req.get("operator", "")
        if op == "In" and len(values) == 1:
            out.append(
                FieldSelectorRequirement(
                    field=req.get("key", ""), operator="=", value=values[0]
                )
            )
        elif op == "NotIn" and len(values) == 1:
            out.append(
                FieldSelectorRequirement(
                    field=req.get("key", ""), operator="!=", value=values[0]
                )
            )
        else:
            log.error("unsupported field selector requirement: %r", req)
    return tuple(out)


def get_authorizer_attributes(sar: dict) -> Attributes:
    """Decoded SubjectAccessReview → Attributes (reference
    GetAuthorizerAttributes, server.go:163-203)."""
    spec = sar.get("spec") or {}
    attributes = Attributes(
        user=UserInfo(
            name=spec.get("user", ""),
            uid=spec.get("uid", ""),
            groups=tuple(spec.get("groups") or ()),
            extra=convert_extra(spec.get("extra")),
        )
    )
    ra = spec.get("resourceAttributes")
    if ra:
        attributes.verb = ra.get("verb", "")
        attributes.namespace = ra.get("namespace", "")
        attributes.api_group = ra.get("group", "")
        attributes.api_version = ra.get("version", "")
        attributes.resource = ra.get("resource", "")
        attributes.subresource = ra.get("subresource", "")
        attributes.name = ra.get("name", "")
        attributes.resource_request = True
        fs = ra.get("fieldSelector") or {}
        if fs.get("requirements"):
            attributes.field_selector = field_selector_requirements(
                fs["requirements"]
            )
        ls = ra.get("labelSelector") or {}
        if ls.get("requirements"):
            attributes.label_selector = label_selector_requirements(
                ls["requirements"]
            )
    nra = spec.get("nonResourceAttributes")
    if nra:
        attributes.path = nra.get("path", "")
        attributes.resource_request = False
        attributes.verb = nra.get("verb", "")
    return attributes


def sar_response(
    decision: str, reason: str, error: Optional[str] = None
) -> dict:
    resp = {
        "apiVersion": "authorization.k8s.io/v1",
        "kind": "SubjectAccessReview",
        "status": {
            "allowed": decision == DECISION_ALLOW,
            "denied": decision == DECISION_DENY,
            "reason": reason,
        },
    }
    if error:
        resp["status"]["evaluationError"] = error
    return resp


def _engine_doc(engine) -> dict:
    """One engine's /debug/engine entry (shared by the single-engine and
    per-replica renderings)."""
    doc = {
        "name": engine.name,
        "warm_ready": engine.warm_ready(),
        "load_generation": engine.load_generation,
        **engine.stats,
    }
    # shard lineage of the serving plane (incremental compilation,
    # docs/performance.md "Giant policy sets"): per-shard content hashes,
    # last reload's scope + dirty set, partition residency
    shard_status = getattr(engine, "shard_status", None)
    if shard_status is not None:
        try:
            doc["shards"] = shard_status()
        except Exception:  # noqa: BLE001 — debug must not 500
            log.exception("shard status failed")
    # fallback burn-down (docs/analysis.md): which Unlowerable codes the
    # serving plane still carries, per-code policy counts, and the served
    # interpreter-merged decision tally
    # (cedar_fallback_decisions_total{code}) — the coverage drive's
    # operator surface
    try:
        cs = getattr(engine, "compiled_set", None)
        packed = getattr(cs, "packed", None) if cs is not None else None
        if packed is not None:
            by_code: dict = {}
            for fp in packed.fallback:
                code = getattr(fp, "code", "unlowerable") or "unlowerable"
                by_code[code] = by_code.get(code, 0) + 1
            doc["fallback"] = {
                "policies": len(packed.fallback),
                "codes": dict(sorted(by_code.items())),
                "served_decisions": metrics.fallback_decision_counts(
                    engine.name
                ),
            }
    except Exception:  # noqa: BLE001 — debug must not 500
        log.exception("fallback status failed")
    return doc


class WebhookServer:
    """Owns the TLS webhook server and the plain health/metrics server."""

    def __init__(
        self,
        authorizer: CedarWebhookAuthorizer,
        admission_handler: CedarAdmissionHandler,
        error_injector: Optional[ErrorInjector] = None,
        recorder: Optional[RequestRecorder] = None,
        enable_profiling: bool = False,
        address: str = DEFAULT_ADDRESS,
        port: int = DEFAULT_PORT,
        metrics_port: int = METRICS_PORT,
        certfile: Optional[str] = None,
        keyfile: Optional[str] = None,
        fastpath=None,
        admission_fastpath=None,
        fleet=None,
        fanout=None,
        pod=None,
        batch_window_s: float = 0.0002,
        max_batch: int = 8192,
        request_timeout_s: Optional[float] = None,
        admission_fail_open: Optional[bool] = None,
        drain_grace_s: float = 0.0,
        analysis_provider=None,
        decision_cache=None,
        pipeline_depth: int = 0,
        rollout=None,
        rollout_control_enabled: bool = True,
        rollout_control_token: Optional[str] = None,
        supervisor=None,
        chaos_control_enabled: bool = False,
        tracer=None,
        audit_log=None,
        slo=None,
        tenancy=None,
        load=None,
        lifecycle=None,
        pdp=None,
    ):
        self.authorizer = authorizer
        self.admission_handler = admission_handler
        # pipeline_depth > 0 runs each raw fast path through the
        # three-stage PipelinedBatcher (engine/batcher.py): host encode of
        # batch N+1 overlaps device execution of batch N, with
        # `pipeline_depth` batches in flight. 0 keeps the serial MicroBatcher (identical results —
        # tests/test_pipeline.py pins the differential; the CLI defaults
        # to depth 2, embedders opt in).
        self.pipeline_depth = max(0, int(pipeline_depth))

        def _eval_batcher(fastpath_obj, serial_fn, path):
            from ..engine.batcher import MicroBatcher, PipelinedBatcher

            if self.pipeline_depth > 0:
                return PipelinedBatcher(
                    fastpath_obj,
                    max_batch=max_batch,
                    window_s=batch_window_s,
                    depth=self.pipeline_depth,
                    metrics_path=path,
                )
            return MicroBatcher(
                serial_fn,
                max_batch=max_batch,
                window_s=batch_window_s,
                metrics_path=path,
            )

        # engine fleet (cedar_tpu/fleet, docs/fleet.md): when wired, the
        # authorization miss path routes through the fleet's health-aware
        # router between this layer and the replicas' batchers — the
        # single-engine batcher below is NOT built (each replica owns its
        # own). The fleet raising FleetUnavailable (no replica admits)
        # degrades to the interpreter path in the request thread, exactly
        # like the single-engine breaker-open bypass.
        self.fleet = fleet
        # cross-process worker tier (cedar_tpu/fanout, docs/fleet.md):
        # when wired, both serving paths consistent-hash the canonical
        # fingerprint to a worker — each worker owns a FULL stack
        # (engine + fast path + batcher + peer-shared decision cache), so
        # the outer server keeps only the HTTP/TLS/obs envelope and the
        # interpreter fallback for FanoutUnavailable. Mutually exclusive
        # with an outer fleet by construction (the CLI enforces it).
        self.fanout = fanout
        # multi-host pod tier (cedar_tpu/pod): the PodTier over this
        # host's engine when the process is a pod leader — serving still
        # flows through the ordinary engine paths (engine.pod routes
        # mesh launches through the collective); this reference only
        # feeds /debug/pod
        self.pod = pod
        # native SAR fast path (engine/fastpath.py): request threads funnel
        # raw bodies through a micro-batcher into the C++ encoder + device
        # matcher; unavailable configurations fall back per request
        self.fastpath = fastpath
        self._batcher = None
        if fastpath is not None and fleet is None:
            self._batcher = _eval_batcher(
                fastpath, fastpath.authorize_raw, "authorization"
            )
        # admission reviews micro-batch into one device call when the
        # handler has a batched evaluation backend
        self._admission_batcher = None
        if admission_handler is not None and admission_handler.supports_batch:
            from ..engine.batcher import MicroBatcher

            self._admission_batcher = MicroBatcher(
                admission_handler.handle_batch,
                max_batch=max_batch,
                window_s=batch_window_s,
            )
        # native admission fast path: raw AdmissionReview bodies through the
        # C++ object walk + device matcher (engine/fastpath.py
        # AdmissionFastPath); rows it can't prove fall back per request
        self.admission_fastpath = admission_fastpath
        self._adm_raw_batcher = None
        if admission_fastpath is not None:
            self._adm_raw_batcher = _eval_batcher(
                admission_fastpath, admission_fastpath.handle_raw, "admission"
            )
        self.error_injector = error_injector or ErrorInjector(None)
        self.recorder = recorder
        self.enable_profiling = enable_profiling
        self.address = address
        self.port = port
        self.metrics_port = metrics_port
        self.certfile = certfile
        self.keyfile = keyfile
        # per-request deadline budget (None disables): a hung evaluation
        # answers NoOpinion (/v1/authorize) or the admission fail-mode
        # within the budget instead of holding the apiserver's thread
        self.request_timeout_s = request_timeout_s
        # deadline/crash posture for /v1/admit; defaults to the handler's
        # allow_on_error (fail-open, the reference's posture)
        if admission_fail_open is None:
            admission_fail_open = bool(
                getattr(admission_handler, "allow_on_error", True)
            )
        self.admission_fail_open = admission_fail_open
        # () -> dict | None: the last policy-set analysis report
        # (cedar_tpu/analysis), served on the metrics server's
        # /debug/analysis endpoint for operators
        self.analysis_provider = analysis_provider
        # decision cache (cedar_tpu/cache DecisionCache) consulted at the
        # raw-body layer AHEAD of both engines: a hit answers without a
        # MicroBatcher.submit or an interpreter walk, and a miss coalesces
        # concurrent identical requests into ONE evaluation (singleflight).
        # Because the lookup precedes the breaker check, a tripped device
        # plane keeps serving fresh-enough cached decisions and only the
        # misses pay the interpreter-fallback path (docs/caching.md).
        self.decision_cache = decision_cache
        self._sar_memo = None
        self._sar_flights = None
        if decision_cache is not None:
            from ..cache import FingerprintMemo, SingleFlight

            # memo sized with the cache: a working set that fits the
            # decision cache must also fit the body→fingerprint memo, or
            # mid-tail hits repay the parse the memo exists to avoid
            self._sar_memo = FingerprintMemo(
                capacity=decision_cache.max_entries
            )
            self._sar_flights = SingleFlight("authorization")
        # shadow-rollout controller (cedar_tpu/rollout RolloutController):
        # the serving paths hand (body, live answer) pairs to offer() —
        # a sampling check + put_nowait, shed under pressure — and the
        # metrics server exposes /debug/rollout plus the
        # stage/promote/rollback lifecycle endpoints (docs/rollout.md)
        self.rollout = rollout
        # the lifecycle POSTs MUTATE live cluster authorization (a staged
        # allow-all + promote is a policy takeover), while the metrics
        # listener is plain HTTP: control is therefore gateable. Embedders
        # constructing the server directly default to enabled (they own
        # their listener exposure); the webhook CLI default-DISABLES
        # control unless the operator supplies a bearer token file or
        # explicitly opts into unauthenticated control (docs/rollout.md).
        # GET /debug/rollout stays open — it is read-only.
        self.rollout_control_enabled = rollout_control_enabled
        self.rollout_control_token = rollout_control_token
        # self-healing supervisor (server/supervisor.py): started/stopped
        # with the server when wired; /debug/supervisor serves its status
        # (plus the poison-object quarantine) either way
        self.supervisor = supervisor
        # chaos game-day control (cedar_tpu/chaos, docs/resilience.md):
        # POST /chaos/{configure,arm,disarm,reset} on the metrics listener.
        # Injection wrecks live answers BY DESIGN, so control is off
        # unless the operator started the webhook with the same
        # --confirm-non-prod-inject-errors gate the reference injector
        # uses; GET /debug/chaos stays readable.
        self.chaos_control_enabled = chaos_control_enabled
        # ?explain=1 support (cedar_tpu/explain, docs/explainability.md):
        # the Explainer is built LAZILY on the first explain request — the
        # package is never imported, and no explain kernel shape compiles,
        # until an operator actually asks (strict pay-for-use; the
        # non-explain serving path is untouched)
        self._explainer = None
        self._explainer_lock = threading.Lock()
        # observability plane (cedar_tpu/obs, docs/observability.md):
        # request tracing (head-sample + tail-keep span trees served at
        # /debug/traces), the JSONL decision audit log, and the SLO
        # burn-rate tracker behind /debug/slo + the cedar_slo_* gauges.
        # All three are strictly optional — None keeps the serving path
        # at one thread-local read per annotation site.
        self.tracer = tracer
        # the process's stall recorder (obs/stall.py), watching from
        # start() to stop() whenever a tracer is wired; /debug/stalls
        self.stalls = None
        self.audit_log = audit_log
        self.slo = slo
        # canonical-fingerprint memos for the audit log, joinable against
        # recorder filenames and cache keys; the authorization side reuses
        # the cache's memo when one exists (same bodies, same parses)
        self._audit_memo = None
        self._adm_audit_memo = None
        if audit_log is not None:
            from ..cache import FingerprintMemo

            self._audit_memo = self._sar_memo or FingerprintMemo(4096)
            self._adm_audit_memo = FingerprintMemo(4096)
        # multi-tenant front end (cedar_tpu/tenancy TenantResolver,
        # docs/multitenancy.md): when wired, every POST resolves a tenant
        # (path prefix / header / host map), the raw body is wrapped in a
        # TenantBody so the stamp rides the whole serving stack, and
        # unresolvable requests are refused BEFORE evaluation — a fused
        # plane must never answer traffic it cannot attribute to a
        # tenant. None keeps the single-tenant path byte-identical.
        self.tenancy = tenancy
        # overload-control plane (cedar_tpu/load, docs/performance.md
        # "Serving under overload"): when wired, every POST is classified
        # and gated at ingress BEFORE the recorder/trace/serving path —
        # sheds answer honestly (SAR NoOpinion + Retry-After, admission
        # per the fail-open/closed flag) and admitted requests run inside
        # load.track() so the inflight count IS the load signal. None
        # keeps the gate-free path byte-identical (bench.py --storm gates
        # the enabled-but-idle differential).
        self.load = load
        # optional second front end (cedar_tpu/pdp): an Envoy ext_authz +
        # batch-authorize listener that maps mesh traffic into this
        # server's serving stack (serve_authorize), so its lifecycle is
        # owned here — start()/stop() bring it up and down with the
        # webhook listeners
        self.pdp = pdp
        if pdp is not None:
            pdp.bind(self)
        # declarative lifecycle controller (cedar_tpu/lifecycle): the
        # server serves its /debug/lifecycle document and the
        # /lifecycle/approve control verb, and stops its reconcile loop
        # on shutdown; the CLI (--lifecycle-spec-dir) wires it
        self.lifecycle = lifecycle
        # SLO-adaptive batch tuners (cedar_tpu/load/tuner.py), appended by
        # the CLI (or embedders) after construction — the server owns
        # their lifecycle (stop()) and serves their decision logs on
        # /debug/load
        self.tuners: list = []
        self.drain_grace_s = drain_grace_s
        self._draining = False
        self._inflight = 0
        self._inflight_cv = threading.Condition()
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._metrics_httpd: Optional[ThreadingHTTPServer] = None

    # ------------------------------------------------------------- handlers

    def warm_ready(self) -> bool:
        """Readiness beyond store load: every wired engine's first serving
        shape must be compiled (TPUPolicyEngine.warm_ready) — every fleet
        replica's, when a fleet is wired (adopted sets latch instantly)."""
        if self.fleet is not None and not self.fleet.warm_ready():
            return False
        if self.fanout is not None and not self.fanout.warm_ready():
            return False
        for fp in (self.fastpath, self.admission_fastpath):
            engine = getattr(fp, "engine", None)
            if engine is not None and not engine.warm_ready():
                return False
        return True

    def ready(self) -> bool:
        """The /readyz verdict (no longer the reference's always-200 stub):
        not draining, every policy store's initial load complete, and every
        wired engine's first serving shape compiled."""
        if self._draining:
            return False
        try:
            if self.authorizer is not None and not self.authorizer.ready():
                return False
        except Exception:  # noqa: BLE001 — a raising store reads as unready
            log.exception("readiness check failed")
            return False
        return self.warm_ready()

    # ----------------------------------------------------- overload control

    def render_shed(self, path_label: str, body: bytes, shed) -> dict:
        """The honest answer for a request the overload gate refused
        WITHOUT evaluating: authorization abstains (NoOpinion + an
        evaluationError naming the shed and the retry hint — the apiserver
        falls through its authorizer chain), admission answers the
        configured fail-open/closed posture exactly like a deadline
        expiry would. ``shed`` is a Shed or RequestShed."""
        msg = (
            f"request shed under overload ({shed.reason}); "
            f"retry after {shed.retry_after_s:g}s"
        )
        if path_label != "admission":
            return sar_response(DECISION_NO_OPINION, "", msg)
        from ..entities.admission import review_request_uid

        uid = ""
        try:
            uid = review_request_uid(json.loads(body)) or ""
        except Exception:  # noqa: BLE001 — uid is best-effort on a shed
            pass
        allowed = self.admission_fail_open
        # error forces status.code 500 on the wire (to_admission_review)
        # — the shape the shadow worker's code!=200 filter and the storm
        # harness's availability check both key on
        return AdmissionResponse(
            uid=uid, allowed=allowed,
            error=f"{msg} ({'allowed' if allowed else 'denied'} on shed)",
        ).to_admission_review()

    def serve_authorize(self, body: bytes, explain: bool = False) -> dict:
        """Ingress-gated in-process serving entry — the exact gate +
        track + handle sequence do_POST runs, for embedders and the storm
        harness (bench.py --storm) that drive the server without HTTP.
        With no overload plane wired this IS handle_authorize."""
        if self.load is None:
            return self.handle_authorize(body, explain=explain)
        priority, shed = self.load.admit("authorization", body, explain)
        if shed is not None:
            return self.render_shed("authorization", body, shed)
        with self.load.track("authorization", priority):
            return self.handle_authorize(
                body, explain=explain, priority=priority
            )

    def serve_admit(self, body: bytes, explain: bool = False) -> dict:
        """The admission twin of serve_authorize."""
        if self.load is None:
            return self.handle_admit(body, explain=explain)
        priority, shed = self.load.admit("admission", body, explain)
        if shed is not None:
            return self.render_shed("admission", body, shed)
        with self.load.track("admission", priority):
            return self.handle_admit(body, explain=explain, priority=priority)

    def _get_explainer(self):
        """Build the Explainer on first use (lazy: no explain import or
        compile cost until the first ?explain=1 request). Engines are
        discovered from the wired fast paths (with their breakers, so an
        open breaker routes explain to the host plane), the fleet's
        template engine, or the authorizer/handler's bound evaluate
        backend on fastpath-less stacks."""
        exp = self._explainer
        if exp is not None:
            return exp
        with self._explainer_lock:
            if self._explainer is None:
                from ..explain import Explainer, engine_of

                authz_engine = authz_breaker = None
                if self.fleet is not None:
                    # the template engine IS replica 0's engine
                    # (fleet.py), so its breaker must gate explain too:
                    # an OPEN replica-0 breaker routes ?explain to the
                    # host plane instead of launching device work on the
                    # sick (possibly mid-rebuild) device
                    authz_engine = getattr(
                        self.fleet, "template_engine", None
                    )
                    replicas = getattr(self.fleet, "replicas", None)
                    if replicas:
                        authz_breaker = getattr(
                            replicas[0], "breaker", None
                        )
                elif self.fastpath is not None:
                    authz_engine = self.fastpath.engine
                    authz_breaker = self.fastpath.breaker
                elif self.authorizer is not None:
                    authz_engine = engine_of(self.authorizer._evaluate)
                adm_engine = adm_breaker = None
                if self.admission_fastpath is not None:
                    adm_engine = self.admission_fastpath.engine
                    adm_breaker = self.admission_fastpath.breaker
                elif self.admission_handler is not None:
                    adm_engine = engine_of(self.admission_handler._evaluate)
                self._explainer = Explainer(
                    authorizer=self.authorizer,
                    admission_handler=self.admission_handler,
                    authz_engine=authz_engine,
                    admission_engine=adm_engine,
                    authz_breaker=authz_breaker,
                    admission_breaker=adm_breaker,
                )
        return self._explainer

    def _handle_authorize_explain(
        self, body: bytes, request_id: Optional[str] = None
    ) -> dict:
        """?explain=1 on /v1/authorize: the decision plus the attribution
        payload, bypassing the decision cache (never read, never
        populated — cached entries carry no clause indices), the
        batchers, the rollout shadow offer, and the error injector
        (operator surface, not serving traffic)."""
        start = time.monotonic()
        if request_id is None:
            request_id = new_trace_id()
        decision, error = DECISION_NO_OPINION, None
        try:
            metrics.record_explain_request("authorization")
            decision, reason, error, explanation = (
                self._get_explainer().explain_authorize(body)
            )
            resp = sar_response(decision, reason, error)
            resp["explanation"] = explanation
            return resp
        except Exception as e:  # noqa: BLE001 — always answer the operator
            log.exception("explain authorize requestId=%s failed", request_id)
            error = f"evaluation error: {e}"
            return sar_response(DECISION_NO_OPINION, "", error)
        finally:
            # deliberately NOT recorded into the serving request
            # counter/histogram: a first explain request pays lazy kernel
            # compiles, and one multi-second sample under the serving
            # labels would spike the p99 an SLO alert watches —
            # cedar_explain_requests_total is the explain-traffic signal
            label = "<error>" if error else _DECISION_LABEL[decision]
            log.info(
                "authorize(explain) requestId=%s decision=%s latency=%.6fs",
                request_id,
                label,
                time.monotonic() - start,
            )

    def handle_authorize(
        self,
        body: bytes,
        explain: bool = False,
        request_id: Optional[str] = None,
        parent_span_id: Optional[str] = None,
        root_span_id: Optional[str] = None,
        sampled: Optional[bool] = None,
        priority: str = "",
    ) -> dict:
        """``request_id`` is the end-to-end trace id (the ingested W3C
        traceparent's trace id when the apiserver sent one — do_POST
        echoes it back as ``X-Cedar-Trace-Id``); direct embedder calls
        without one get a fresh id, exactly like before. ``sampled`` is a
        pre-drawn head-sampling decision (do_POST draws it so the response
        traceparent's recorded flag is honest); None draws here.
        ``priority`` is the ingress gate's classification (cedar_tpu/load)
        — non-empty only for requests admitted through serve_authorize/
        do_POST with an overload plane wired; it arms the evaluation-stage
        shed gate on the miss path."""
        if explain:
            return self._handle_authorize_explain(body, request_id)
        start = time.monotonic()
        # the HTTP handler's phase record for this request (None for
        # direct embedder calls): the timer's two ends are two of its
        # boundaries, and it finishes the trace once the reply is out
        phases = current_phases()
        if phases is not None:
            phases.path, phases.t_start = "authorization", start
        if request_id is None:
            request_id = new_trace_id()
        trace = None
        if self.tracer is not None:
            trace = self.tracer.begin(
                "authorization",
                trace_id=request_id,
                parent_span_id=parent_span_id,
                root_span_id=root_span_id,
                sampled=sampled,
            )
            set_current(trace)
        # per-request facts the layers below report upward for the audit
        # line and the trace tail-keep policy (cached answer? served by a
        # degraded/fallback path?) without changing their return contracts;
        # the timer's `by` label reads them too, so they are always kept
        octx: dict = {}
        _octx_set(octx)
        tenant = getattr(body, "tenant", "")
        if tenant and trace is not None:
            trace.root.set_attr("tenant", tenant)
        # wire protocol (cedar_tpu/pdp): non-empty only for PDP-mapped
        # bodies — joins the trace root span, the request metric families
        # (bounded label) and the audit line, so mesh traffic stays
        # distinguishable from control-plane SARs on every obs surface
        protocol = getattr(body, "protocol", "")
        if protocol and trace is not None:
            trace.root.set_attr("protocol", protocol)
        decision, reason, error = DECISION_NO_OPINION, "", None
        answer = None
        try:
            try:
                answer = self._authorize_cached(
                    body, request_id, priority=priority
                )
                decision, reason, error = answer
            except RequestShed as e:
                # the evaluation-stage gate refused an already-admitted
                # request (server saturated by the time its cache-missed
                # evaluation would submit): bounded honest answer, breaker
                # untouched — the shedder doing its job is not a sick
                # device (cedar_tpu/load/admission.py)
                decision, reason, error = (
                    DECISION_NO_OPINION, "", str(e),
                )
            if phases is not None:
                phases.t_eval = time.monotonic()
            if error is not None:
                return sar_response(decision, reason, error)
            if self.rollout is not None and self._cache_usable():
                # shadow the REAL decision (pre-injection): offer() is a
                # sampling check plus a non-blocking enqueue — the live
                # answer below is already computed and never waits on it.
                # Gated on store readiness (the same latched check the
                # cache uses): a pre-ready NoOpinion is a startup
                # artifact, and diffing it against the always-ready
                # candidate would pollute the report with
                # decision_changed noise that says nothing about the
                # policy delta
                self.rollout.offer("authorize", body, (decision, reason))
            decision, reason, error = self.error_injector.inject_if_enabled(
                decision, reason
            )
            # scenario-driven twin of the injector above: the shared
            # registry's `response` seam (cedar_tpu/chaos), a no-op
            # attribute read unless a game day armed it
            decision, reason, error = chaos_fire(
                "response", (decision, reason, error)
            )
            return sar_response(decision, reason, error)
        finally:
            _octx_set(None)
            label = "<error>" if error else _DECISION_LABEL[decision]
            latency = time.monotonic() - start
            if phases is not None:
                phases.t_stop = start + latency
            by = _answered_by(octx, answer)
            metrics.record_request_total(label, protocol=protocol)
            metrics.record_request_latency(
                label, latency, protocol=protocol, by=by
            )
            if tenant:
                metrics.record_tenant_request(
                    "authorization", tenant, label, latency
                )
            if self.slo is not None:
                # fed the SAME measured latency the histogram above just
                # observed — the burn rates and the dashboards can never
                # structurally disagree (docs/observability.md)
                try:
                    self.slo.record(
                        "authorization", latency, error is not None
                    )
                except Exception:  # noqa: BLE001 — never break serving
                    log.exception("slo record failed")
            if trace is not None:
                trace.root.set_attr("answered_by", by)
                self._finish_trace(
                    trace, phases, octx, label, error is not None
                )
            if self.audit_log is not None:
                self._audit(
                    "authorization", "authorize", body, request_id,
                    label, reason, error, latency, octx,
                )
            log.info(
                "authorize requestId=%s decision=%s latency=%.6fs",
                request_id,
                label,
                latency,
            )

    def _authorize_cached(
        self, body: bytes, request_id: str, priority: str = ""
    ):
        """(decision, reason, error) through the decision cache: hit →
        answered without touching any engine; miss → singleflight-coalesced
        evaluation whose clean result is inserted for the next arrival.
        Error results (decode failures, deadline expiries, evaluator
        crashes) are transient and never cached. One deadline budget for
        the whole request: the submits below spend the REMAINING budget
        (queue/cache/coalesce wait included), never a fresh one — the
        admission path's posture, and the basis for the breaker's
        queue-wait-aware expiry accounting."""
        deadline = (
            None
            if self.request_timeout_s is None
            else time.monotonic() + self.request_timeout_s
        )
        cache = self.decision_cache
        if cache is None or not self._cache_usable():
            return self._authorize_uncached(
                body, request_id, priority=priority, deadline=deadline
            )
        t_fp = time.monotonic()
        key, memo_hit = self._sar_memo.lookup("authorize", body)
        tr = current_trace()
        if tr is not None:
            # every request pays this one: built only for a kept trace
            tr.defer_span(
                "cache.fingerprint", t_fp, time.monotonic(), memo_hit=memo_hit
            )
        if key is None:
            # unparseable body: the uncached path produces the exact
            # decode-error answer (never cached — the fingerprint requires
            # a parse, so decode errors cannot collide onto a key)
            return self._authorize_uncached(
                body, request_id, priority=priority, deadline=deadline
            )
        # generation snapshot BEFORE evaluation: a reload landing while the
        # leader evaluates leaves the entry stamped pre-reload, so it dies
        # at its first post-reload lookup instead of surviving the reload.
        # A RAISING cache (chaos cache.get seam, or a real bug) degrades to
        # the uncached path: a sick cache may cost an evaluation, never an
        # answer.
        try:
            with trace_span("cache.lookup") as sp:
                gen = cache.current_generation()
                hit = cache.get(key)
                if sp is not None:
                    sp.set_attr("hit", hit is not None)
        except Exception:  # noqa: BLE001 — a sick cache is a miss
            log.exception("decision cache lookup failed; evaluating")
            return self._authorize_uncached(
                body, request_id, priority=priority, deadline=deadline
            )
        if hit is not None:
            _octx_mark("cached")
            return hit[0], hit[1], None

        def _leader():
            res = self._authorize_uncached(
                body, request_id, coalesce_key=key,
                priority=priority, deadline=deadline,
            )
            if res[2] is None:
                try:
                    # shard-scoped stamp when the reason names the
                    # determining policies (cache/generation.py): an
                    # incremental reload then kills exactly the entries
                    # whose shard changed instead of the whole cache
                    g = gen
                    scoped = getattr(gen, "scoped", None)
                    if scoped is not None:
                        # the request's resolved tenant qualifies the
                        # stamp lookup on fused planes — bare policy ids
                        # collide across tenants (cache/generation.py)
                        t = getattr(body, "tenant", "")
                        g = scoped(res[1], tenant=t) if t else scoped(res[1])
                    cache.put(key, (res[0], res[1]), res[0], generation=g)
                except Exception:  # noqa: BLE001 — the answer still serves
                    log.exception("decision cache insert failed")
            return res

        try:
            result, _ = self._sar_flights.do(
                key, _leader, timeout=self.request_timeout_s
            )
        except RequestShed:
            raise  # the leader was shed: handle_authorize renders it
        except DeadlineExceeded as e:
            # a FOLLOWER's budget expired waiting on the leader; the leader
            # keeps running and its result still warms the cache
            metrics.record_deadline_exceeded("authorization")
            return DECISION_NO_OPINION, "", f"evaluation error: {e}"
        except Exception as e:  # noqa: BLE001 — always answer the apiserver
            if isinstance(e.__cause__, RequestShed):
                # a follower coalesced behind a leader that admission
                # control shed: unwrap the singleflight wrapper so every
                # waiter receives the SAME honest shed answer immediately
                # (bounded error, breaker untouched) instead of an opaque
                # "coalesced evaluation failed" — tests/test_load.py pins
                # this regression
                raise e.__cause__
            log.exception(
                "coalesced authorize requestId=%s failed", request_id
            )
            return DECISION_NO_OPINION, "", f"evaluation error: {e}"
        return result

    def authorize_core(self, body: bytes, request_id: Optional[str] = None):
        """(decision, reason, error) through cache + engines WITHOUT the
        HTTP/observability envelope — the fanout worker's serving entry
        (cedar_tpu/fanout/worker.py): a worker answers through exactly
        the stack a standalone webhook would, while the front-end process
        keeps the envelope."""
        if request_id is None:
            request_id = new_trace_id()
        return self._authorize_cached(body, request_id)

    def admit_core(self, body: bytes) -> dict:
        """The admission twin of authorize_core: the rendered
        AdmissionReview dict through the engines, envelope-free."""
        return self._handle_admit(body)

    def _cache_usable(self) -> bool:
        """No caching until every store's initial load completes: pre-ready
        NoOpinions are a startup artifact, not a decision worth keeping
        (the ready() latch makes this a cheap check at steady state)."""
        try:
            return self.authorizer is None or self.authorizer.ready()
        except Exception:  # noqa: BLE001 — unready reads as uncacheable
            return False

    def _authorize_uncached(
        self,
        body: bytes,
        request_id: str,
        coalesce_key: Optional[str] = None,
        priority: str = "",
        deadline: Optional[float] = None,
    ):
        """(decision, reason, error) through the engines — the pre-cache
        serving path: the fanout tier or fleet router (when wired) or the
        native fast path behind the breaker, then the python interpreter
        path. ``deadline`` is the request's absolute budget deadline (set
        by _authorize_cached): submits spend what remains of it."""
        if self.load is not None and priority:
            # evaluation-stage gate: a request admitted at ingress can
            # find the server saturated by the time its cache-missed
            # evaluation submits — shed NOW (RequestShed, rendered by
            # handle_authorize and fanned to any coalesced followers)
            # instead of burning a batcher slot and the whole budget
            self.load.check_eval(priority)

        def _remaining() -> Optional[float]:
            if deadline is None:
                return self.request_timeout_s
            return deadline - time.monotonic()

        if self.fanout is not None:
            try:
                with trace_span("fanout.route"):
                    return self.fanout.authorize(body, request_id)
            except FanoutUnavailable:
                # no worker alive: the interpreter path below answers in
                # the request thread — the tier twin of FleetUnavailable
                _octx_mark("fallback")
            except Exception as e:  # noqa: BLE001 — always answer
                log.exception(
                    "fanout authorize requestId=%s failed", request_id
                )
                return DECISION_NO_OPINION, "", f"evaluation error: {e}"
        if self.fleet is not None:
            try:
                with trace_span("fleet.submit"):
                    return self.fleet.submit(
                        body,
                        timeout=_remaining(),
                        coalesce_key=coalesce_key,
                    )
            except DeadlineExceeded as e:
                # the router already fed the owning replica's breaker
                metrics.record_deadline_exceeded("authorization")
                tr = current_trace()
                if tr is not None:
                    tr.event("deadline_exceeded")
                return DECISION_NO_OPINION, "", f"evaluation error: {e}"
            except FleetUnavailable:
                # no replica admits (every breaker open / every worker
                # down): the interpreter path below answers in the request
                # thread — bounded degradation, the fleet twin of the
                # single-engine breaker-open bypass
                _octx_mark("fallback")
            except Exception as e:  # noqa: BLE001 — always answer
                log.exception(
                    "fleet authorize requestId=%s failed", request_id
                )
                return DECISION_NO_OPINION, "", f"evaluation error: {e}"
        # why the interpreter path answered (trace/audit attribution):
        # no_fastpath = engine-less deployment, the interpreter IS the
        # serving plane; everything else is a degradation and tail-keeps
        py_reason = "no_fastpath"
        try:
            use_fastpath = (
                self._batcher is not None and self.fastpath.available
            )
            if use_fastpath and not self._breaker_admits(self.fastpath):
                use_fastpath = False
                py_reason = "breaker_open"
            elif self._batcher is not None and not use_fastpath:
                py_reason = "fastpath_unavailable"
        except Exception:  # noqa: BLE001 — degrade to the python path
            log.exception("fastpath availability check failed")
            use_fastpath = False
            py_reason = "availability_check_failed"
        if use_fastpath:
            try:
                return self._batcher.submit(
                    body,
                    timeout=_remaining(),
                    coalesce_key=coalesce_key,
                )
            except DeadlineExceeded as e:
                metrics.record_deadline_exceeded("authorization")
                if not getattr(e, "queued", False):
                    # feed the breaker only when the device plane actually
                    # held the request: an expiry whose whole budget burned
                    # in the submit queue (e.queued — the dominant shape
                    # under open-loop overload) says the server is drowning
                    # in offered load, not that the accelerator is sick.
                    # The shedder handles the former; tripping the breaker
                    # would route EVERYTHING to the slower interpreter and
                    # deepen the storm (tests/test_load.py pins this).
                    self._record_breaker_timeout(self.fastpath)
                tr = current_trace()
                if tr is not None:
                    tr.event("deadline_exceeded")
                return DECISION_NO_OPINION, "", f"evaluation error: {e}"
            except Exception as e:  # noqa: BLE001 — always answer
                log.exception(
                    "fastpath authorize requestId=%s failed", request_id
                )
                return DECISION_NO_OPINION, "", f"evaluation error: {e}"
        if py_reason != "no_fastpath" or self.fleet is not None:
            # a wired device plane was bypassed: fallback-served, which
            # tail-keeps the trace and stamps the audit line
            _octx_mark("fallback")
        _octx_mark("interpreter")
        with trace_span("interpreter") as sp:
            if sp is not None:
                sp.set_attr("reason", py_reason)
            try:
                sar = json.loads(body)
            except (ValueError, TypeError, RecursionError) as e:
                return (
                    DECISION_NO_OPINION,
                    "Encountered decoding error",
                    f"failed parsing request body: {e}",
                )
            try:
                attributes = get_authorizer_attributes(sar)
                # tenant stamp (cedar_tpu/tenancy): the interpreter walk
                # over the fused stack relies on the guard conditions
                # reading context.tenantId
                attributes.tenant = getattr(body, "tenant", "")
                # protocol stamp (cedar_tpu/pdp): keeps any
                # authorizer-level cache key domain-separated exactly
                # like the server-level fingerprint
                attributes.protocol = getattr(body, "protocol", "")
                # bypass the authorizer-level cache ONLY when the
                # server-level cache is wired: it already missed on this
                # exact canonical key, and a second lookup would
                # double-count the miss. With no server cache, an
                # embedder-wired authorizer cache stays live.
                decision, reason = self.authorizer.authorize(
                    attributes, use_cache=self.decision_cache is None
                )
            except Exception as e:  # noqa: BLE001 — always answer
                log.exception("authorize requestId=%s failed", request_id)
                return DECISION_NO_OPINION, "", f"evaluation error: {e}"
            return decision, reason, None

    def _breaker_admits(self, fastpath) -> bool:
        """False when the fastpath's circuit breaker is open. Requests then
        skip the micro-batcher entirely — its worker thread may be wedged
        inside a hung device call, and queueing behind it would burn every
        request's deadline budget — and take the python interpreter path in
        the request thread instead. No fallback metric here: the python
        path's own guarded_call records breaker_open once per evaluation;
        recording at the bypass too would double-count every request."""
        breaker = getattr(fastpath, "breaker", None)
        return breaker is None or breaker.allow()

    @staticmethod
    def _record_breaker_timeout(fastpath) -> None:
        """A deadline expiry is a device-plane failure signal: a wedged
        evaluator never returns, so _guarded_process's post-call accounting
        can never feed the breaker. Consecutive expiries trip it here, which
        routes traffic off the stuck batcher (see _breaker_admits) until
        half-open probes find the device answering again."""
        breaker = getattr(fastpath, "breaker", None)
        if breaker is not None:
            breaker.record_failure()

    def _admission_fail_mode(self, review, e) -> dict:
        """The configured fail-open/fail-closed admission answer for a
        request whose evaluation crashed or ran out of deadline budget.
        Fail-open (the reference's allowOnError=true posture) keeps the
        cluster's write path alive; fail-closed trades availability for the
        guarantee that nothing unevaluated is admitted."""
        from ..entities.admission import review_request_uid

        uid = review_request_uid(review) if review is not None else ""
        allowed = self.admission_fail_open
        return AdmissionResponse(
            uid=uid, allowed=allowed, code=200,
            error="evaluation error "
            f"({'allowed' if allowed else 'denied'} on error): {e}",
        ).to_admission_review()

    def _admission_deadline(self, body: bytes, e) -> dict:
        metrics.record_deadline_exceeded("admission")
        try:
            review = json.loads(body)
        except Exception:  # noqa: BLE001 — uid is best-effort here
            review = None
        return self._admission_fail_mode(review, e)

    def _handle_admit_explain(
        self, body: bytes, request_id: Optional[str] = None
    ) -> dict:
        """?explain=1 on /v1/admit — the admission twin of
        _handle_authorize_explain (same bypasses, same lazy plane). The
        request id is logged so the echoed X-Cedar-Trace-Id joins the
        serving log here too."""
        if request_id is None:
            request_id = new_trace_id()
        try:
            metrics.record_explain_request("admission")
            response, explanation = self._get_explainer().explain_admit(body)
            review = response.to_admission_review()
            review["explanation"] = explanation
            log.info("admit(explain) requestId=%s answered", request_id)
            return review
        except Exception as e:  # noqa: BLE001 — always answer the operator
            log.exception("explain admit requestId=%s failed", request_id)
            try:
                review = json.loads(body)
            except Exception:  # noqa: BLE001 — uid is best-effort here
                review = None
            return self._admission_fail_mode(review, e)

    def handle_admit(
        self,
        body: bytes,
        explain: bool = False,
        request_id: Optional[str] = None,
        parent_span_id: Optional[str] = None,
        root_span_id: Optional[str] = None,
        sampled: Optional[bool] = None,
        priority: str = "",
    ) -> dict:
        if request_id is None:
            request_id = new_trace_id()
        if explain:
            return self._handle_admit_explain(body, request_id)
        start = time.monotonic()
        phases = current_phases()  # see handle_authorize
        if phases is not None:
            phases.path, phases.t_start = "admission", start
        trace = None
        if self.tracer is not None:
            trace = self.tracer.begin(
                "admission",
                trace_id=request_id,
                parent_span_id=parent_span_id,
                root_span_id=root_span_id,
                sampled=sampled,
            )
            set_current(trace)
        octx: dict = {}
        if trace is not None or self.audit_log is not None:
            _octx_set(octx)
        tenant = getattr(body, "tenant", "")
        if trace is not None:
            if tenant:
                trace.root.set_attr("tenant", tenant)
            _set_admit_attrs(trace.root, body)
        review = None
        try:
            review = self._handle_admit(body, priority=priority)
            if phases is not None:
                phases.t_eval = time.monotonic()
            if self.rollout is not None and self._admission_shadowable():
                # non-blocking shadow offer; error/fail-mode responses are
                # filtered by the shadow worker (code != 200), but the
                # pre-ready allow is a CLEAN 200 — it must be gated here or
                # startup traffic diffs against the always-ready candidate
                self.rollout.offer("admit", body, review)
            return review
        finally:
            _octx_set(None)
            latency = time.monotonic() - start
            if phases is not None:
                phases.t_stop = start + latency
            # unconditional, like the authorization path's finally — the
            # timer and the per-tenant series must not depend on obs
            # being wired
            label, error = _admit_outcome(review)
            metrics.record_admission_latency(
                "error" if error else label, latency
            )
            if tenant:
                metrics.record_tenant_request(
                    "admission", tenant, label, latency
                )
            if (
                trace is not None
                or self.slo is not None
                or self.audit_log is not None
            ):
                self._finish_admit_obs(
                    body, request_id, review, trace, octx, latency,
                    label, error, phases,
                )

    def _finish_trace(self, trace, phases, octx, label, errored) -> None:
        """Close a request's trace where the handler's timer stops — or,
        for a request the HTTP handler keeps a phase record for, leave it
        with the record: _finish_phases closes it once the reply is
        flushed, so its tree runs from the request line to the flush."""
        set_current(None)
        trace.fallback = trace.fallback or bool(octx.get("fallback"))
        if phases is not None:
            phases.trace, phases.decision, phases.error = (
                trace, label, errored,
            )
            return
        try:
            self.tracer.finish(trace, decision=label, error=errored)
        except Exception:  # noqa: BLE001 — never break serving
            log.exception("trace finish failed")

    def _finish_phases(self, phases) -> None:
        """The reply is flushed: line the request's stamps up as phases
        ONCE and hand the same tuple to the phase ledger
        (cedar_request_phase_seconds) and, as spans to be, to the
        request's trace, whose root now runs from the request line to the
        flush."""
        try:
            names, stamps = phases.stamps()
            metrics.record_request_phases(phases.path, names, stamps)
            trace = phases.trace
            if trace is None:
                return
            root = trace.root
            trace.started_unix -= root.t0 - phases.t_line
            root.t0, root.t1 = phases.t_line, phases.t_flush
            if phases.t_prev is not None:
                root.set_attr(
                    "between_us",
                    round((phases.t_line - phases.t_prev) * 1e6, 1),
                )
            trace.add_windows(names, stamps, phases.times)
            self.tracer.finish(
                trace, decision=phases.decision, error=phases.error
            )
        except Exception:  # noqa: BLE001 — never break serving
            log.exception("request phase record failed")

    def _finish_admit_obs(
        self, body, request_id, review, trace, octx, latency, label, error,
        phases=None,
    ) -> None:
        """Close out the admission request's observability surfaces
        (trace finish + tail-keep, SLO record, audit line) from the
        rendered review — the decision facts (``label``, ``error``:
        _admit_outcome) are read back out of the response the caller is
        already returning, so this can never change an answer."""
        resp = (review or {}).get("response") or {}
        status = resp.get("status") or {}
        if self.slo is not None:
            try:
                self.slo.record("admission", latency, error is not None)
            except Exception:  # noqa: BLE001 — never break serving
                log.exception("slo record failed")
        if trace is not None:
            self._finish_trace(trace, phases, octx, label, error is not None)
        if self.audit_log is not None:
            self._audit(
                "admission", "admit", body, request_id, label,
                status.get("message") or "", error, latency, octx,
            )

    def _audit(
        self, path, endpoint, body, request_id, label, reason, error,
        latency, octx,
    ) -> None:
        """Append one decision audit line (docs/observability.md): the
        end-to-end trace id, the canonical fingerprint shared with the
        recorder/cache (memoized — repeat traffic pays one digest), the
        decision with its determining policies read from the rendered
        reason, latency, and the fallback/breaker posture it was served
        under. Best-effort by contract: a failing audit plane logs and
        serves."""
        try:
            from ..obs.audit import audit_entry

            memo = (
                self._audit_memo
                if endpoint == "authorize"
                else self._adm_audit_memo
            )
            fp = memo.fingerprint(endpoint, body) if memo is not None else None
            self.audit_log.record(
                audit_entry(
                    path,
                    request_id,
                    fp,
                    label,
                    reason=reason,
                    error=error,
                    latency_s=latency,
                    breaker_state=self._breaker_state_label(path),
                    fallback=bool(octx.get("fallback")),
                    cached=bool(octx.get("cached")),
                    tenant=getattr(body, "tenant", ""),
                    protocol=getattr(body, "protocol", ""),
                )
            )
            metrics.record_audit_record(path)
        except Exception:  # noqa: BLE001 — audit must never break serving
            log.exception("audit append failed")

    def _breaker_state_label(self, path: str) -> str:
        """The serving breaker's state at answer time (audit context;
        empty when no breaker is wired). With a fleet, replica 0's
        breaker — the same one the explain plane gates on."""
        try:
            if path == "authorization":
                if self.fleet is not None:
                    replicas = getattr(self.fleet, "replicas", None)
                    breaker = replicas[0].breaker if replicas else None
                else:
                    breaker = getattr(self.fastpath, "breaker", None)
            else:
                breaker = getattr(self.admission_fastpath, "breaker", None)
            return breaker.state if breaker is not None else ""
        except Exception:  # noqa: BLE001 — audit context is best-effort
            return ""

    def _admission_shadowable(self) -> bool:
        """Stores ready for admission (latched, like _cache_usable): the
        unready-allow answer is a startup artifact, not a decision the
        candidate should be diffed against."""
        try:
            return (
                self.admission_handler is None
                or self.admission_handler._ready()
            )
        except Exception:  # noqa: BLE001 — unready reads as unshadowable
            return False

    def _handle_admit(self, body: bytes, priority: str = "") -> dict:
        if self.load is not None and priority:
            # evaluation-stage gate, the authorization path's twin: a
            # saturated server answers the configured fail-mode NOW
            # (docstring of AdmissionController.check_eval)
            try:
                self.load.check_eval(priority)
            except RequestShed as e:
                return self.render_shed("admission", body, e)
        # one deadline budget for the whole request: a fastpath failure that
        # falls through to the python path spends the REMAINING budget, not
        # a fresh one, so the apiserver never waits ~2x the configured limit
        deadline = (
            None
            if self.request_timeout_s is None
            else time.monotonic() + self.request_timeout_s
        )

        def remaining():
            # non-positive remainders make submit() expire immediately
            return None if deadline is None else deadline - time.monotonic()

        # admission routes through the tier ONLY when every worker can
        # evaluate it (frontend.supports_admit): the CLI's workers carry
        # the authorization stack, and an admission-less worker would
        # answer its fail-mode instead of evaluating — the local
        # admission stack below is the real evaluator then
        if self.fanout is not None and self.fanout.supports_admit():
            try:
                with trace_span("fanout.route"):
                    return self.fanout.admit(body)
            except FanoutUnavailable:
                _octx_mark("fallback")  # local path below answers
            except Exception:  # noqa: BLE001 — local path below answers
                log.exception("fanout admit failed; local path")
        py_reason = "no_fastpath"
        try:
            use_fast = (
                self._adm_raw_batcher is not None
                and self.admission_fastpath.available
            )
            if use_fast and not self._breaker_admits(self.admission_fastpath):
                use_fast = False
                py_reason = "breaker_open"
            elif self._adm_raw_batcher is not None and not use_fast:
                py_reason = "fastpath_unavailable"
        except Exception:  # noqa: BLE001 — degrade to the python path
            log.exception("admission fastpath availability check failed")
            use_fast = False
            py_reason = "availability_check_failed"
        if use_fast:
            try:
                return self._adm_raw_batcher.submit(
                    body, timeout=remaining()
                ).to_admission_review()
            except DeadlineExceeded as e:
                # the budget is spent: answer the fail-mode now instead of
                # burning more wall-clock on the python path. Queue-burned
                # expiries spare the breaker, exactly like the
                # authorization path above.
                if not getattr(e, "queued", False):
                    self._record_breaker_timeout(self.admission_fastpath)
                tr = current_trace()
                if tr is not None:
                    tr.event("deadline_exceeded")
                return self._admission_deadline(body, e)
            except Exception:  # noqa: BLE001 — python path below still answers
                log.exception("admission fastpath failed; python path")
                py_reason = "fastpath_error"
        if py_reason != "no_fastpath":
            _octx_mark("fallback")
        with trace_span("interpreter") as sp:
            if sp is not None:
                sp.set_attr("reason", py_reason)
            try:
                review = json.loads(body)
            except (ValueError, TypeError, RecursionError) as e:
                return AdmissionResponse(
                    uid="", allowed=False, code=400,
                    error=f"failed parsing body: {e}",
                ).to_admission_review()
            try:
                req = AdmissionRequest.from_admission_review(review)
                # tenant stamp (cedar_tpu/tenancy): the interpreter path's
                # context must carry the tenant the device plane masks by
                req.tenant = getattr(body, "tenant", "")
                if self._admission_batcher is not None:
                    return self._admission_batcher.submit(
                        req, timeout=remaining()
                    ).to_admission_review()
                return self.admission_handler.handle(req).to_admission_review()
            except DeadlineExceeded as e:
                metrics.record_deadline_exceeded("admission")
                tr = current_trace()
                if tr is not None:
                    tr.event("deadline_exceeded")
                return self._admission_fail_mode(review, e)
            except Exception as e:  # noqa: BLE001 — fail-open like the ref
                # allow-on-error posture (/root/reference
                # internal/server/admission/handler.go:90-104 with
                # allowOnError=true): a conversion/evaluation crash must
                # not block the cluster's write path
                log.exception("admit failed")
                return self._admission_fail_mode(review, e)

    # -------------------------------------------------------------- serving

    def _make_handler(server):  # noqa: N805 — bound as a class closure
        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                log.debug("%s %s", self.address_string(), fmt % args)

            # the Server and Date lines of a reply, worked out once a second
            _stamp = (0, "")

            def _write_json(
                self, doc: dict, code: int = 200, headers: dict = None
            ):
                """The whole reply as one bytes and one write: the status
                line and the header lines send_response would write, in
                its order, then ``headers``, then the body. A second write
                waits out the peer's delayed ACK where the kernel keeps
                Nagle's algorithm, and gives the interpreter up once
                more."""
                data = json.dumps(doc).encode()
                if log.isEnabledFor(logging.DEBUG):
                    self.log_request(code)
                now = int(time.time())
                second, stamp = Handler._stamp
                if second != now:
                    stamp = (
                        f"Server: {self.version_string()}\r\n"
                        f"Date: {self.date_time_string(now)}\r\n"
                    )
                    Handler._stamp = (now, stamp)
                extra = "".join(
                    f"{k}: {v}\r\n" for k, v in (headers or {}).items()
                )
                head = (
                    f"{self.protocol_version} {code} "
                    f"{self.responses.get(code, ('',))[0]}\r\n{stamp}"
                    "Content-Type: application/json\r\n"
                    f"Content-Length: {len(data)}\r\n{extra}\r\n"
                )
                self.wfile.write(head.encode("latin-1") + data)

            # Request phases (docs/observability.md): with a tracer wired,
            # every request on this connection gets a RequestPhases record
            # whose first stamp is the request line in hand and whose last
            # is the reply flushed — the next request's `between` starts
            # there. BaseHTTPRequestHandler calls parse_request right after
            # it has read the line, and flushes right after do_POST.
            _phases = None
            _t_flushed = None
            # how this request was read (cedar_http_reads_total): "scan" or
            # "full" once parse_request has run, and the path it was for
            _read_how = None
            _read_path = None

            def parse_request(self):
                if server.tracer is not None:
                    self._phases = RequestPhases(self._t_flushed)
                scanned = self._scan_request()
                self._read_how = "scan" if scanned else "full"
                return scanned or super().parse_request()

            def _scan_request(self) -> bool:
                """Read the request every apiserver sends — ``POST <path>
                HTTP/1.1`` and a plain header block that is whole in the
                reader's buffer — in one pass, and leave what
                http.server's parse_request leaves. False for any other
                request, with nothing taken from ``rfile``: the caller
                hands it to http.server, which answers it as it always
                has."""
                requestline = str(self.raw_requestline, "iso-8859-1")
                words = requestline.split()
                if (
                    len(words) != 3
                    or words[0] != "POST"
                    or words[2] != "HTTP/1.1"
                    or words[1].startswith("//")
                ):
                    return False
                block = _HEADER_BLOCK.match(self.rfile.peek())
                if block is None:
                    return False
                lines = str(block.group(), "iso-8859-1")[:-4].split("\r\n")
                fields = {}
                for line in reversed(lines):  # the first of a name stands
                    name, _, value = line.partition(":")
                    fields[name.lower()] = value.lstrip(" \t")
                if "expect" in fields or "transfer-encoding" in fields:
                    return False
                self.rfile.read(block.end())
                self.requestline = requestline.rstrip("\r\n")
                self.command, self.path, self.request_version = words
                self.close_connection = (
                    fields.get("connection", "").lower() == "close"
                )
                self.headers = _ScannedHeaders(fields)
                return True

            def handle_one_request(self):
                self._phases = self._read_how = self._read_path = None
                try:
                    super().handle_one_request()
                finally:
                    if self._read_how is not None:
                        metrics.record_http_read(
                            self._read_path or "other", self._read_how
                        )
                    phases = self._phases
                    if phases is not None:
                        phases.t_flush = self._t_flushed = time.monotonic()
                        if phases.t_stop is not None:
                            server._finish_phases(phases)

            def do_POST(self):
                with profiler_scope("cedar.http.request"):
                    self._do_post()

            def _do_post(self):
                # the drain check and the in-flight increment are one
                # atomic step: once stop() sets _draining and sees
                # _inflight == 0 under this lock, no request can slip past
                # the check and reach a batcher that stop() already joined
                #
                # ?explain=1 (docs/explainability.md) splits off the query
                # string here; the bare-path requests the apiserver sends
                # take exactly the code path they always did
                path, _, query = self.path.partition("?")
                explain = False
                if query:
                    from urllib.parse import parse_qs

                    vals = parse_qs(query).get("explain")
                    explain = bool(vals) and vals[-1] not in ("0", "false", "")
                with server._inflight_cv:
                    draining = server._draining
                    if not draining:
                        server._inflight += 1
                if draining:
                    # drain: /readyz already reads 503, so the apiserver is
                    # steering away; requests that still race in are shed
                    # fast rather than answered by a server mid-teardown
                    metrics.record_shed(
                        "admission" if path == "/v1/admit"
                        else "authorization"
                    )
                    self.send_error(503, "server is draining")
                    return
                try:
                    try:
                        length = int(self.headers.get("Content-Length") or 0)
                    except ValueError:
                        self.send_error(400, "bad Content-Length")
                        return
                    if length < 0 or length > MAX_BODY_BYTES:
                        # 413 rather than reading an unbounded body into
                        # memory; real SAR/AdmissionReview payloads are far
                        # below the cap (apiserver itself limits request
                        # sizes to ~3MB).
                        self.send_error(413, "request body too large")
                        return
                    body = self.rfile.read(length) if length else b""
                    phases = self._phases
                    if phases is not None:
                        phases.t_body = time.monotonic()
                    if server.tenancy is not None:
                        # tenant front end (docs/multitenancy.md): resolve
                        # path-prefix/header/host → tenant, re-dispatch on
                        # the stripped path, and wrap the body so every
                        # layer below (cache keys, recorder filenames,
                        # encoders, audit) sees the stamp. Unresolvable
                        # requests answer a clean refusal — never an
                        # evaluation against a plane with no tenant slice.
                        tenant, path, why = server.tenancy.resolve(
                            path,
                            self.headers,
                            host=self.headers.get("Host"),
                        )
                        if tenant is None:
                            metrics.record_tenant_rejected(why)
                            self._reject_tenant(path, body, why)
                            return
                        body = TenantBody(body, tenant)
                    path_label = (
                        "authorization" if path == "/v1/authorize"
                        else "admission" if path == "/v1/admit"
                        else None
                    )
                    self._read_path = path_label
                    if phases is not None and path_label is not None:
                        metrics.record_request_body_bytes(path_label, length)
                    priority = ""
                    if server.load is not None and path_label is not None:
                        # ingress overload gate (cedar_tpu/load,
                        # docs/performance.md "Serving under overload"):
                        # refused requests answer the honest shed BEFORE
                        # the recorder/trace/serving path — never served,
                        # so the serving histograms and SLO rings never
                        # see them; cedar_load_shed_total{priority,reason}
                        # is the signal, and Retry-After tells a
                        # well-behaved caller when to come back
                        priority, shed = server.load.admit(
                            path_label, body, explain=explain
                        )
                        if shed is not None:
                            self._write_json(
                                server.render_shed(path_label, body, shed),
                                headers={
                                    "Retry-After": str(
                                        max(1, round(shed.retry_after_s))
                                    )
                                },
                            )
                            return
                    if server.recorder is not None:
                        server.recorder.record(path, body)
                    # one request id end to end: the ingested W3C
                    # traceparent's trace id (or a fresh one) becomes the
                    # logged requestId, the trace id in /debug/traces and
                    # the audit log, and the X-Cedar-Trace-Id response
                    # header the caller can quote back to an operator
                    request_id, parent_span = ingest_request_id(
                        self.headers.get("traceparent")
                    )
                    headers = {"X-Cedar-Trace-Id": request_id}
                    root_span = sampled = None
                    if server.tracer is not None:
                        # propagate: our root span becomes the downstream
                        # parent, and the recorded flag carries the HEAD
                        # sampling decision (drawn here, honored by the
                        # handler's trace) — tail-keep recording is not
                        # knowable at response time, so the flag must not
                        # overclaim at the default rate 0
                        root_span = new_span_id()
                        sampled = server.tracer.head_sample()
                        headers["traceparent"] = format_traceparent(
                            request_id, root_span, sampled
                        )
                    # admitted requests run inside load.track(): the
                    # inflight count (queue wait + evaluation, end to
                    # end) IS the load signal the graduated states read
                    tracked = (
                        server.load.track(path_label, priority)
                        if server.load is not None and path_label is not None
                        else contextlib.nullcontext()
                    )
                    # the layers below stamp their boundaries into this
                    # thread's phase record (handle_authorize/handle_admit:
                    # the timer's ends; the batcher: the slot's stages)
                    set_phases(phases)
                    with tracked:
                        if path == "/v1/authorize":
                            self._write_json(
                                server.handle_authorize(
                                    body,
                                    explain=explain,
                                    request_id=request_id,
                                    parent_span_id=parent_span,
                                    root_span_id=root_span,
                                    sampled=sampled,
                                    priority=priority,
                                ),
                                headers=headers,
                            )
                        elif path == "/v1/admit":
                            self._write_json(
                                server.handle_admit(
                                    body,
                                    explain=explain,
                                    request_id=request_id,
                                    parent_span_id=parent_span,
                                    root_span_id=root_span,
                                    sampled=sampled,
                                    priority=priority,
                                ),
                                headers=headers,
                            )
                        else:
                            self.send_error(404)
                finally:
                    set_phases(None)
                    with server._inflight_cv:
                        server._inflight -= 1
                        server._inflight_cv.notify_all()

            def _reject_tenant(self, path: str, body: bytes, why: str):
                """A clean, well-formed refusal for a request the tenant
                front end could not attribute: authorization answers
                NoOpinion + evaluationError (the apiserver treats it as
                an abstain), admission answers a denied review (403
                status) — fail-closed, a write must not slip through a
                misrouted tenant."""
                msg = {
                    "unknown": "unknown tenant",
                    "conflict": "conflicting tenant sources",
                }.get(why, "no tenant resolved")
                if path == "/v1/admit":
                    uid = ""
                    try:
                        uid = (json.loads(body).get("request") or {}).get(
                            "uid", ""
                        )
                    except Exception:  # noqa: BLE001 — reject regardless
                        pass
                    self._write_json(
                        AdmissionResponse(
                            uid=uid,
                            allowed=False,
                            code=403,
                            message=f"tenant rejected: {msg}",
                        ).to_admission_review()
                    )
                else:
                    self._write_json(
                        sar_response(
                            DECISION_NO_OPINION,
                            "",
                            f"tenant rejected: {msg}",
                        )
                    )

            def do_GET(self):
                if server.enable_profiling and self.path.startswith(
                    "/debug/pprof"
                ):
                    self._debug(self.path)
                else:
                    self.send_error(404)

            def _debug(self, path: str):
                import io

                if path.startswith("/debug/pprof/profile"):
                    # statistical whole-process sampler (Go's pprof.Profile
                    # samples every thread; cProfile would only see this
                    # handler thread sleeping)
                    import collections
                    import sys
                    import traceback

                    me = threading.get_ident()
                    counts: collections.Counter = collections.Counter()
                    deadline = time.monotonic() + 1.0
                    samples = 0
                    while time.monotonic() < deadline:
                        for tid, frame in sys._current_frames().items():
                            if tid == me:
                                continue
                            stack = tuple(
                                f"{fr.filename.rsplit('/', 1)[-1]}:{fr.lineno} {fr.name}"
                                for fr, _ in traceback.walk_stack(frame)
                            )[::-1]
                            counts[stack] += 1
                        samples += 1
                        time.sleep(0.01)
                    buf = io.StringIO()
                    buf.write(f"# {samples} samples over 1s, 10ms interval\n")
                    for stack, n in counts.most_common(50):
                        buf.write(f"\n{n} samples:\n")
                        for line in stack:
                            buf.write(f"  {line}\n")
                    data = buf.getvalue().encode()
                else:
                    import traceback
                    import sys

                    buf = io.StringIO()
                    frames = sys._current_frames()
                    for tid, frame in frames.items():
                        buf.write(f"--- thread {tid}\n")
                        traceback.print_stack(frame, file=buf)
                    data = buf.getvalue().encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

        return Handler

    def _make_metrics_handler(server):  # noqa: N805
        class MetricsHandler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                log.debug("%s %s", self.address_string(), fmt % args)

            def _send_json(self, doc: dict, code: int = 200):
                data = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                if self.path == "/healthz":
                    # always-200 stub (reference health.go:22-26)
                    self.send_response(200)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                elif self.path == "/readyz":
                    # goes beyond the reference's always-200 stub: unready
                    # while draining for shutdown, until every store's
                    # initial policy load completes, and until the engines'
                    # first serving shape is compiled — so a fresh server's
                    # first live request never eats an XLA compile inside
                    # the apiserver's 3s webhook deadline.
                    #
                    # With an overload plane wired, readiness is GRADUATED
                    # (docs/performance.md "Serving under overload"): the
                    # body and X-Cedar-Load-State header carry the load
                    # state (ok / pressure / overload / saturated), and
                    # saturation reads 503 so an apiserver honoring
                    # readiness steers new traffic to a healthier member
                    # while the shedder protects this one
                    ready = server.ready()
                    body = b""
                    state = ""
                    if server.load is not None:
                        state = server.load.load_state()
                        body = state.encode()
                        if state == STATE_SATURATED:
                            ready = False
                    self.send_response(200 if ready else 503)
                    if state:
                        self.send_header("X-Cedar-Load-State", state)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    if body:
                        self.wfile.write(body)
                elif self.path == "/metrics":
                    if server.fleet is not None:
                        try:
                            # scrape-time refresh: the replica-state gauge
                            # must reflect a dead/open/rebuilding replica
                            # NOW, not its last lifecycle transition
                            server.fleet.publish_states()
                        except Exception:  # noqa: BLE001 — scrape must serve
                            log.exception("fleet state publish failed")
                    if server.slo is not None:
                        try:
                            # burn rates are window functions of time, not
                            # of events: refresh at scrape so a quiet
                            # window decays the gauges
                            server.slo.publish()
                        except Exception:  # noqa: BLE001 — scrape must serve
                            log.exception("slo publish failed")
                    if server._sar_memo is not None:
                        # the memo counts under its own lock; the scrape
                        # mirrors the two totals
                        metrics.set_fingerprint_memo(
                            "authorization", *server._sar_memo.counts()
                        )
                    data = metrics.REGISTRY.expose().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/plain; version=0.0.4"
                    )
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                elif self.path == "/debug/cache":
                    # decision-cache stats per path (size, hit ratio,
                    # evictions, TTLs, current generation); {} with the
                    # cache disabled
                    doc = {}
                    try:
                        if server.decision_cache is not None:
                            doc["authorization"] = (
                                server.decision_cache.stats()
                            )
                        adm_cache = getattr(
                            server.admission_handler, "cache", None
                        )
                        if adm_cache is not None:
                            doc["admission"] = adm_cache.stats()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("cache stats failed")
                        doc = {"error": "cache stats failed"}
                    self._send_json(doc)
                elif self.path == "/debug/engine":
                    # per-path engine + batcher pipeline snapshot: mode
                    # (serial/pipelined), pipeline depth, encode workers,
                    # live queue fills, per-stage stall totals, and the
                    # engine's warm/compile state (docs/performance.md).
                    # With a fleet wired, the authorization entry
                    # enumerates every replica (health + breaker + warm
                    # state + queue fills, docs/fleet.md); {} with no fast
                    # path wired
                    doc = {}
                    try:
                        if server.fleet is not None:
                            doc["authorization"] = {
                                "fleet": server.fleet.name,
                                "replicas": {
                                    r.name: {
                                        "pipeline": r.batcher.debug_stats(),
                                        "engine": _engine_doc(r.engine),
                                        "health": r.health(),
                                    }
                                    for r in server.fleet.replicas
                                },
                            }
                        for name, fp, batcher in (
                            (
                                "authorization",
                                server.fastpath,
                                server._batcher,
                            ),
                            (
                                "admission",
                                server.admission_fastpath,
                                server._adm_raw_batcher,
                            ),
                        ):
                            if batcher is None:
                                continue
                            entry = {"pipeline": batcher.debug_stats()}
                            engine = getattr(fp, "engine", None)
                            if engine is not None:
                                entry["engine"] = _engine_doc(engine)
                            doc[name] = entry
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("engine stats failed")
                        doc = {"error": "engine stats failed"}
                    self._send_json(doc)
                elif self.path == "/debug/tenancy":
                    # multi-tenant front end + registry snapshot
                    # (docs/multitenancy.md): registered tenants with
                    # per-tenant policy counts, resolver config, and the
                    # serving plane's per-tenant shard rollup (via
                    # /debug/engine's shards.tenants); 404 single-tenant
                    if server.tenancy is None:
                        self.send_error(404)
                        return
                    try:
                        doc = {"resolver": server.tenancy.describe()}
                        reg = getattr(server.tenancy, "registry", None)
                        if reg is not None:
                            doc["registry"] = reg.stats()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("tenancy status failed")
                        doc = {"error": "tenancy status failed"}
                    self._send_json(doc)
                elif self.path == "/debug/fleet":
                    # replicated-engine fleet snapshot (docs/fleet.md):
                    # per-replica health/lifecycle, the fleet epoch, and
                    # router counters (routed / spillovers / hedges);
                    # 404 without a fleet
                    if server.fleet is None:
                        self.send_error(404)
                        return
                    try:
                        doc = server.fleet.status()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("fleet status failed")
                        doc = {"error": "fleet status failed"}
                    self._send_json(doc)
                elif self.path == "/debug/fanout":
                    # cross-process worker tier (docs/fleet.md "Cross-host
                    # topology"): per-worker health + plane tokens, routing
                    # splits, rehash/restart counts, peer-cache stats, and
                    # the tier coherence verdict; 404 without a tier
                    if server.fanout is None:
                        self.send_error(404)
                        return
                    try:
                        doc = server.fanout.status()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("fanout status failed")
                        doc = {"error": "fanout status failed"}
                    self._send_json(doc)
                elif self.path == "/debug/pod":
                    # multi-host pod tier (cedar_tpu/pod, docs/fleet.md
                    # "One mesh, many hosts"): per-host health + plane
                    # tokens, policy-partition ownership, per-host swap
                    # re-upload counts, and the pod coherence verdict;
                    # 404 off-pod
                    if server.pod is None:
                        self.send_error(404)
                        return
                    try:
                        doc = server.pod.status()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("pod status failed")
                        doc = {"error": "pod status failed"}
                    self._send_json(doc)
                elif self.path == "/debug/rollout":
                    # shadow-rollout state + decision-diff report
                    # (docs/rollout.md): lifecycle state, candidate warm
                    # progress, per-kind diff counts, and the exemplar ring
                    if server.rollout is None:
                        self.send_error(404)
                        return
                    try:
                        doc = server.rollout.status()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("rollout status failed")
                        doc = {"error": "rollout status failed"}
                    self._send_json(doc)
                elif self.path == "/debug/supervisor":
                    # self-healing state (docs/resilience.md): per-component
                    # thread/heartbeat health + restart counts, device
                    # recovery status, and the quarantine summary
                    doc = {}
                    try:
                        if server.supervisor is not None:
                            doc = server.supervisor.status()
                        from ..stores.quarantine import quarantine_registry

                        doc["quarantine"] = quarantine_registry().snapshot()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("supervisor status failed")
                        doc = {"error": "supervisor status failed"}
                    self._send_json(doc)
                elif self.path == "/debug/quarantine":
                    # poison-object quarantine: WHICH objects are being
                    # served from last-known-good content, and why
                    try:
                        from ..stores.quarantine import quarantine_registry

                        doc = quarantine_registry().snapshot()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("quarantine snapshot failed")
                        doc = {"error": "quarantine snapshot failed"}
                    self._send_json(doc)
                elif self.path == "/debug/chaos":
                    # chaos-plane state: armed flag, scenario name, per-seam
                    # call/fire counts ({} armed=False when never configured)
                    try:
                        from ..chaos.registry import default_registry

                        doc = default_registry().stats()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("chaos stats failed")
                        doc = {"error": "chaos stats failed"}
                    self._send_json(doc)
                elif self.path == "/debug/load":
                    # overload-control plane (docs/performance.md "Serving
                    # under overload"): graduated load state, honest shed
                    # accounting (offered == admitted + shed), per-client
                    # quota posture, and each adaptive batch tuner's live
                    # knobs + decision log with the measurement that
                    # justified every move; 404 with no plane wired
                    if server.load is None and not server.tuners:
                        self.send_error(404)
                        return
                    doc = {}
                    try:
                        if server.load is not None:
                            doc["admission_control"] = server.load.stats()
                        if server.tuners:
                            doc["tuning"] = {
                                t.path: t.status() for t in server.tuners
                            }
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("load status failed")
                        doc = {"error": "load status failed"}
                    self._send_json(doc)
                elif self.path == "/debug/lifecycle":
                    # declarative lifecycle controller (docs/rollout.md
                    # "Declarative lifecycle"): per-tenant stage, rung,
                    # gate evidence, halt reason, and the journal path;
                    # 404 with no controller wired
                    if server.lifecycle is None:
                        self.send_error(404)
                        return
                    try:
                        doc = server.lifecycle.status()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("lifecycle status failed")
                        doc = {"error": "lifecycle status failed"}
                    self._send_json(doc)
                elif self.path == "/debug/slo":
                    # SLO plane (docs/observability.md): targets plus
                    # per-path, per-window request/error/slow counts and
                    # burn rates; 404 with no tracker wired
                    if server.slo is None:
                        self.send_error(404)
                        return
                    try:
                        doc = server.slo.status()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("slo status failed")
                        doc = {"error": "slo status failed"}
                    self._send_json(doc)
                elif self.path == "/debug/traces" or self.path.startswith(
                    "/debug/traces/"
                ):
                    # kept request traces (docs/observability.md): the
                    # bare path lists the ring newest-first; /<trace id>
                    # (prefix accepted) fetches one full span tree — the
                    # online half of cedar-trace. 404 with no tracer
                    if server.tracer is None:
                        self.send_error(404)
                        return
                    trace_id = self.path[len("/debug/traces/"):].strip("/")
                    try:
                        if trace_id:
                            doc = server.tracer.get(trace_id)
                            if doc is None:
                                self.send_error(404)
                                return
                        else:
                            doc = server.tracer.stats()
                            doc["traces"] = server.tracer.list_traces()
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("trace lookup failed")
                        doc = {"error": "trace lookup failed"}
                    self._send_json(doc)
                elif self.path == "/debug/stalls":
                    # the stall recorder (docs/observability.md "Process
                    # stalls"): the last 32 times this process's watcher
                    # ran 100 ms late or more, each with its cause, the
                    # deltas taken across it and every thread's stack as
                    # it ended; 404 under --no-trace
                    if server.stalls is None:
                        self.send_error(404)
                        return
                    self._send_json(server.stalls.status())
                elif self.path == "/debug/analysis":
                    # the last policy-set analysis report (load-time
                    # lowerability/shadowing/conflict findings + capacity);
                    # {} until the first analyzed load completes
                    if server.analysis_provider is None:
                        self.send_error(404)
                        return
                    try:
                        doc = server.analysis_provider() or {}
                        # join the served-traffic ranking onto the static
                        # coverage rollup: which Unlowerable codes carry
                        # real decisions (cedar_fallback_decisions_total)
                        # tells the operator the next burn-down target,
                        # not just which codes exist in the set. The
                        # provider doc is either one report or a dict of
                        # per-engine reports keyed by engine name
                        # ({"authorization": ...}) — nested reports join
                        # THEIR engine's slice of the counter, so one
                        # plane's served fallback traffic never reads as
                        # another's burn-down signal.
                        def _joined(rep, engine=None):
                            if not isinstance(rep, dict):
                                return rep
                            if isinstance(rep.get("coverage"), dict):
                                rep = dict(rep)
                                rep["coverage"] = dict(
                                    rep["coverage"],
                                    served_decisions=(
                                        metrics.fallback_decision_counts(
                                            engine
                                        )
                                    ),
                                )
                            return rep

                        doc = _joined(doc)
                        if isinstance(doc, dict):
                            doc = {
                                k: _joined(v, engine=k)
                                for k, v in doc.items()
                            }
                    except Exception:  # noqa: BLE001 — debug must not 500
                        log.exception("analysis provider failed")
                        doc = {"error": "analysis provider failed"}
                    self._send_json(doc)
                else:
                    self.send_error(404)

            def do_POST(self):
                """Rollout lifecycle control (docs/rollout.md): POST
                /rollout/stage with {"directory": ...} or {"source": ...}
                (+ optional "warm", "sampleRate"), /rollout/promote with
                optional {"force": true}, /rollout/rollback. Served on the
                plain metrics listener like the debug endpoints — operator
                plane, not the apiserver-facing TLS port."""
                if self.path.startswith("/chaos/"):
                    self._chaos_control()
                    return
                if server.rollout is None and server.lifecycle is None:
                    self.send_error(404)
                    return
                if not server.rollout_control_enabled:
                    self._send_json(
                        {
                            "error": "rollout control is disabled on this "
                            "listener; start the webhook with "
                            "--rollout-control-token-file (bearer auth) or "
                            "--rollout-insecure-control (docs/rollout.md)"
                        },
                        403,
                    )
                    return
                if server.rollout_control_token:
                    import hmac

                    auth = self.headers.get("Authorization") or ""
                    expected = f"Bearer {server.rollout_control_token}"
                    # bytes compare: compare_digest raises TypeError on
                    # non-ASCII str input, and header bytes arrive
                    # latin-1-decoded — a stray byte must answer 403, not
                    # abort the connection with a traceback
                    if not hmac.compare_digest(
                        auth.encode("utf-8", "surrogateescape"),
                        expected.encode("utf-8", "surrogateescape"),
                    ):
                        self._send_json(
                            {"error": "missing or invalid bearer token"},
                            403,
                        )
                        return
                try:
                    length = int(self.headers.get("Content-Length") or 0)
                except ValueError:
                    self.send_error(400, "bad Content-Length")
                    return
                if length < 0 or length > MAX_BODY_BYTES:
                    self.send_error(413, "request body too large")
                    return
                raw = self.rfile.read(length) if length else b""
                try:
                    doc = json.loads(raw) if raw else {}
                except (ValueError, TypeError) as e:
                    self._send_json({"error": f"bad JSON body: {e}"}, 400)
                    return
                from ..lifecycle import LifecycleError
                from ..rollout import RolloutError
                from ..rollout.source import CandidateSourceError

                try:
                    if self.path.startswith("/rollout/") and (
                        server.rollout is None
                    ):
                        self.send_error(404)
                        return
                    if self.path == "/rollout/stage":
                        out = server.rollout.stage(
                            directory=doc.get("directory"),
                            source=doc.get("source"),
                            crd=bool(doc.get("crd")),
                            description=doc.get("description", ""),
                            warm=doc.get("warm", "async"),
                            sample_rate=doc.get("sampleRate"),
                        )
                    elif self.path == "/rollout/promote":
                        out = server.rollout.promote(
                            force=bool(doc.get("force"))
                        )
                        server._prebuild_snapshots()
                    elif self.path == "/rollout/rollback":
                        out = server.rollout.rollback()
                        server._prebuild_snapshots()
                    elif self.path == "/lifecycle/approve":
                        # manual-promotion consent for a declarative
                        # rollout holding at its last canary rung
                        if server.lifecycle is None:
                            self.send_error(404)
                            return
                        out = server.lifecycle.approve(
                            doc.get("tenant") or ""
                        )
                    else:
                        self.send_error(404)
                        return
                except (
                    RolloutError, CandidateSourceError, LifecycleError
                ) as e:
                    # a structured refusal (e.g. the per-replica lineage
                    # divergence on a refused rollback) rides the body so
                    # callers can distinguish "store reload superseded"
                    # from "partial promotion wedge" without parsing prose
                    body = {"error": str(e)}
                    detail = getattr(e, "detail", None)
                    if detail:
                        body["detail"] = detail
                    self._send_json(body, 409)
                    return
                except Exception as e:  # noqa: BLE001 — report, never crash
                    log.exception("rollout control %s failed", self.path)
                    self._send_json({"error": str(e)}, 500)
                    return
                self._send_json(out)

            def _chaos_control(self):
                """Game-day control (docs/resilience.md): POST
                /chaos/configure with a scenario JSON body, then
                /chaos/arm; /chaos/disarm stops injection instantly;
                /chaos/reset also drops the scenario. Gated by the
                non-prod confirmation flag — injection exists to BREAK the
                serving path."""
                if not server.chaos_control_enabled:
                    self._send_json(
                        {
                            "error": "chaos control is disabled; start the "
                            "webhook with --confirm-non-prod-inject-errors "
                            "(docs/resilience.md)"
                        },
                        403,
                    )
                    return
                from ..chaos.registry import default_registry
                from ..chaos.scenario import ScenarioError, load_scenario

                registry = default_registry()
                try:
                    length = int(self.headers.get("Content-Length") or 0)
                except ValueError:
                    self.send_error(400, "bad Content-Length")
                    return
                if length < 0 or length > MAX_BODY_BYTES:
                    self.send_error(413, "request body too large")
                    return
                raw = self.rfile.read(length) if length else b""
                try:
                    if self.path == "/chaos/configure":
                        scenario = load_scenario(raw or b"{}")
                        registry.configure(scenario)
                    elif self.path == "/chaos/arm":
                        registry.arm()
                    elif self.path == "/chaos/disarm":
                        registry.disarm()
                    elif self.path == "/chaos/reset":
                        registry.reset()
                    else:
                        self.send_error(404)
                        return
                except (ScenarioError, ValueError) as e:
                    self._send_json({"error": str(e)}, 400)
                    return
                except Exception as e:  # noqa: BLE001 — report, never crash
                    log.exception("chaos control %s failed", self.path)
                    self._send_json({"error": str(e)}, 500)
                    return
                self._send_json(registry.stats())

        return MetricsHandler

    def _prebuild_snapshots(self) -> None:
        """Touch the fast paths after a promote/rollback swap so their
        native-encoder snapshots rebuild NOW (a host-side C++ table build)
        instead of on the first live request — every fleet replica's too."""
        paths = [self.fastpath, self.admission_fastpath]
        if self.fleet is not None:
            paths.extend(r.fastpath for r in self.fleet.replicas)
        for fp in paths:
            try:
                if fp is not None:
                    fp.available  # noqa: B018 — property triggers the rebuild
            except Exception:  # noqa: BLE001 — the lazy path still works
                log.exception("snapshot prebuild failed")

    def start(self) -> None:
        """Start both servers on background threads."""
        self._httpd = ThreadingHTTPServer(
            (self.address, self.port), self._make_handler()
        )
        self._httpd.daemon_threads = True
        if self.certfile and self.keyfile:
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(self.certfile, self.keyfile)
            self._httpd.socket = ctx.wrap_socket(
                self._httpd.socket, server_side=True
            )
        threading.Thread(
            target=self._httpd.serve_forever, name="webhook-server", daemon=True
        ).start()

        self._metrics_httpd = ThreadingHTTPServer(
            (self.address, self.metrics_port), self._make_metrics_handler()
        )
        self._metrics_httpd.daemon_threads = True
        threading.Thread(
            target=self._metrics_httpd.serve_forever,
            name="metrics-server",
            daemon=True,
        ).start()
        if self.pdp is not None:
            self.pdp.start()
        if self.supervisor is not None:
            self.supervisor.start()
        if self.tracer is not None and self.stalls is None:
            from ..obs import stall

            self.stalls = stall.acquire()
        scheme = "https" if self.certfile else "http"
        log.info(
            "serving on %s://%s:%d (metrics http://%s:%d)",
            scheme,
            self.address,
            self.port,
            self.address,
            self.metrics_port,
        )

    def begin_drain(self) -> None:
        """Flip into draining: /readyz answers 503 (the apiserver stops
        sending), new POSTs are shed with 503, in-flight requests finish.
        Set under the in-flight lock so the flag and the request count form
        one consistent picture for stop()'s drain wait."""
        with self._inflight_cv:
            self._draining = True

    def stop(self, drain_grace_s: Optional[float] = None) -> None:
        """Graceful shutdown: drain (readiness 503 + shed new requests),
        wait up to the grace period for in-flight requests, stop the
        listeners, then drain and join the micro-batchers."""
        grace = self.drain_grace_s if drain_grace_s is None else drain_grace_s
        if self.supervisor is not None:
            # stop supervision FIRST: reviving a stage mid-teardown would
            # race the batcher joins below
            try:
                self.supervisor.stop()
            except Exception:  # noqa: BLE001 — teardown must finish
                log.exception("supervisor stop failed")
        for tuner in self.tuners:
            # stop tuning FIRST: a control loop mutating batcher knobs
            # mid-drain would race the batcher joins below
            try:
                tuner.stop()
            except Exception:  # noqa: BLE001 — teardown must finish
                log.exception("batch tuner stop failed")
        self.begin_drain()
        deadline = time.monotonic() + grace
        with self._inflight_cv:
            while self._inflight:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    log.warning(
                        "drain grace elapsed with %d request(s) in flight",
                        self._inflight,
                    )
                    break
                self._inflight_cv.wait(timeout=remaining)
        for httpd in (self._httpd, self._metrics_httpd):
            if httpd is not None:
                httpd.shutdown()
                httpd.server_close()
        self._httpd = None
        self._metrics_httpd = None
        if self.pdp is not None:
            try:
                # after the webhook listeners (drain covered both fronts:
                # PDP requests route through serve_authorize and count in
                # the same in-flight picture), before the batchers so no
                # PDP submit races a joining worker
                self.pdp.stop()
            except Exception:  # noqa: BLE001 — teardown must finish
                log.exception("pdp listener stop failed")
        # batcher stop drains the queue: every already-accepted request
        # still gets its answer before the worker joins
        for batcher in (
            self._batcher, self._admission_batcher, self._adm_raw_batcher
        ):
            if batcher is not None:
                batcher.stop()
        if self.fleet is not None:
            try:
                self.fleet.stop()  # replica batchers drain like the above
            except Exception:  # noqa: BLE001 — teardown must finish
                log.exception("fleet stop failed")
        if self.fanout is not None:
            try:
                self.fanout.stop()  # worker stacks drain their batchers
            except Exception:  # noqa: BLE001 — teardown must finish
                log.exception("fanout stop failed")
        if self.lifecycle is not None:
            try:
                # reconcile loop BEFORE the rollout controller: a tick
                # landing mid-teardown would drive stage/promote against
                # a stack that is being dismantled
                self.lifecycle.stop()
            except Exception:  # noqa: BLE001 — teardown must finish
                log.exception("lifecycle stop failed")
        if self.rollout is not None:
            try:
                self.rollout.stop()  # shadow worker; best-effort by design
            except Exception:  # noqa: BLE001 — teardown must finish
                log.exception("rollout stop failed")
        if self.stalls is not None:
            from ..obs import stall

            self.stalls = None
            stall.release()
        for closer in (self.tracer, self.audit_log):
            if closer is not None:
                try:
                    closer.close()  # flush trace-log / audit file handles
                except Exception:  # noqa: BLE001 — teardown must finish
                    log.exception("observability close failed")

    def stop_batchers(self) -> None:
        """Drain + stop the batchers WITHOUT touching HTTP listeners —
        the teardown for embedded stacks that never started them (fanout
        workers, tests building WebhookServer as a serving core)."""
        for tuner in self.tuners:
            try:
                tuner.stop()
            except Exception:  # noqa: BLE001 — teardown must finish
                log.exception("batch tuner stop failed")
        for batcher in (
            self._batcher, self._admission_batcher, self._adm_raw_batcher
        ):
            if batcher is not None:
                batcher.stop()

    @property
    def bound_port(self) -> Optional[int]:
        return self._httpd.server_address[1] if self._httpd else None

    @property
    def bound_metrics_port(self) -> Optional[int]:
        return (
            self._metrics_httpd.server_address[1] if self._metrics_httpd else None
        )
