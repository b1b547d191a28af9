"""Prometheus metrics for the webhook, with text exposition.

Metric names/labels/buckets parity with reference
internal/server/metrics/metrics.go:
  * ``cedar_authorizer_request_total{decision}`` counter (:28-36)
  * ``cedar_authorizer_request_duration_seconds{decision,by}`` histogram,
    buckets 0.25/0.5/0.7/1/1.5/3/5/10 (:38-47)
  * ``cedar_authorizer_e2e_latency_seconds{filename}`` histogram,
    exponential buckets 2*2^i, 8 buckets (:49-58)

The registry renders the Prometheus text exposition format directly (the
reference leans on client_golang + component-base legacyregistry).
"""

from __future__ import annotations

import threading
from bisect import bisect_left
from typing import Dict, List, Optional, Sequence, Tuple

SUBSYSTEM = "cedar_authorizer"

# process-wide worker identity (cross-process fanout tier, docs/fleet.md):
# when set, EVERY family's samples carry a stable `worker` label at
# exposition time, so a Prometheus scraping N worker processes can join
# (rather than collide) their series. Empty on single-process deployments
# — the label is then omitted, which is the same series identity in the
# Prometheus data model (absent label == empty value), so single-process
# dashboards and the test suite's exact-line assertions are unchanged.
_worker_label = ""


def set_worker_label(worker_id: str) -> None:
    global _worker_label
    _worker_label = str(worker_id or "")


def worker_label() -> str:
    return _worker_label


def _fmt_label(labels: Tuple[Tuple[str, str], ...]) -> str:
    if _worker_label:
        labels = labels + (("worker", _worker_label),)
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in labels)
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    if v == int(v):
        return str(int(v))
    return repr(v)


class Counter:
    def __init__(self, name: str, help_text: str, label_names: Sequence[str]):
        self.name = name
        self.help = help_text
        self.label_names = tuple(label_names)
        self._values: Dict[Tuple[Tuple[str, str], ...], float] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, extra: Tuple = (), **labels) -> None:
        # ``extra`` appends OPTIONAL label pairs to the series key (e.g. the
        # bounded ``protocol`` label on the request families): absent label
        # == empty label to Prometheus, so callers that never pass it keep
        # their exposition byte-identical.
        key = tuple((k, labels.get(k, "")) for k in self.label_names) + tuple(extra)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def add(self, key: Tuple, amount: float) -> None:
        """``inc`` by a series key a caller built once (``(("path",
        p),)`` for one label), for a once-a-batch site that updates
        several families of the same labels."""
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def set_total(self, value: float, **labels) -> None:
        """Mirror a total that its owner counts under a lock of its own
        (refreshed at scrape time): never mixed with ``inc`` on a series."""
        key = tuple((k, labels.get(k, "")) for k in self.label_names)
        with self._lock:
            self._values[key] = float(value)

    def collect(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        with self._lock:
            for key in sorted(self._values):
                out.append(
                    f"{self.name}{_fmt_label(key)} {_fmt_value(self._values[key])}"
                )
        return out


class Gauge:
    def __init__(self, name: str, help_text: str, label_names: Sequence[str]):
        self.name = name
        self.help = help_text
        self.label_names = tuple(label_names)
        self._values: Dict[Tuple[Tuple[str, str], ...], float] = {}
        self._lock = threading.Lock()

    def set(self, value: float, **labels) -> None:
        key = tuple((k, labels.get(k, "")) for k in self.label_names)
        with self._lock:
            self._values[key] = value

    def remove(self, **labels) -> None:
        """Drop one labeled row from the exposition (e.g. an offboarded
        tenant's gauge — a frozen last value would keep reporting state
        that no longer exists)."""
        key = tuple((k, labels.get(k, "")) for k in self.label_names)
        with self._lock:
            self._values.pop(key, None)

    def collect(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        with self._lock:
            for key in sorted(self._values):
                out.append(
                    f"{self.name}{_fmt_label(key)} {_fmt_value(self._values[key])}"
                )
        return out


class Histogram:
    def __init__(
        self,
        name: str,
        help_text: str,
        label_names: Sequence[str],
        buckets: Sequence[float],
    ):
        self.name = name
        self.help = help_text
        self.label_names = tuple(label_names)
        self.buckets = tuple(sorted(buckets))
        self._counts: Dict[Tuple[Tuple[str, str], ...], List[int]] = {}
        self._sums: Dict[Tuple[Tuple[str, str], ...], float] = {}
        self._totals: Dict[Tuple[Tuple[str, str], ...], int] = {}
        self._lock = threading.Lock()

    def observe(self, value: float, extra: Tuple = (), **labels) -> None:
        # ``extra``: optional appended label pairs, as on Counter.inc
        key = tuple((k, labels.get(k, "")) for k in self.label_names) + tuple(extra)
        # ``_counts`` holds each bucket's OWN count (the first bucket whose
        # bound is >= value; none for a value past the last): one increment
        # here, the cumulative sums of the exposition at collect time
        i = bisect_left(self.buckets, value)
        with self._lock:
            counts = self._counts.get(key)
            if counts is None:
                counts = self._counts[key] = [0] * (len(self.buckets) + 1)
                self._sums[key] = 0.0
                self._totals[key] = 0
            counts[i] += 1
            self._sums[key] += value
            self._totals[key] += 1

    def fraction_over(self, bound: float) -> Dict[Tuple[Tuple[str, str], ...], float]:
        """Per-label-set fraction of observations strictly above the
        largest bucket <= ``bound`` — the public read the SLO plane's
        histogram cross-check uses (cedar_tpu/obs/slo.py), so nothing
        outside this class touches the cumulative-bucket representation."""
        out: Dict[Tuple[Tuple[str, str], ...], float] = {}
        with self._lock:
            for key, counts in self._counts.items():
                total = self._totals.get(key, 0)
                if not total:
                    continue
                under = sum(
                    c for b, c in zip(self.buckets, counts) if b <= bound
                )
                out[key] = 1.0 - under / total
        return out

    def collect(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        with self._lock:
            for key in sorted(self._counts):
                running = 0
                for i, b in enumerate(self.buckets):
                    running += self._counts[key][i]
                    labels = key + (("le", _fmt_value(b)),)
                    out.append(
                        f"{self.name}_bucket{_fmt_label(labels)} {running}"
                    )
                inf_labels = key + (("le", "+Inf"),)
                out.append(
                    f"{self.name}_bucket{_fmt_label(inf_labels)} "
                    f"{self._totals[key]}"
                )
                out.append(
                    f"{self.name}_sum{_fmt_label(key)} "
                    f"{_fmt_value(self._sums[key])}"
                )
                out.append(f"{self.name}_count{_fmt_label(key)} {self._totals[key]}")
        return out


class Summary:
    """``_sum`` and ``_count`` per label set, no quantiles and no buckets:
    the cheapest family that still gives a mean over a scrape interval.
    ``observe_steps`` takes every row of one event under ONE lock, as a
    tuple of label values and a tuple of boundary stamps — the request
    phase ledger records a dozen phases per request from the request
    thread, which has the interpreter to itself for about a millisecond a
    request at saturation; a Histogram.observe each would double what the
    ledger costs."""

    def __init__(self, name: str, help_text: str, label_names: Sequence[str]):
        self.name = name
        self.help = help_text
        self.label_names = tuple(label_names)
        # (first label value, second label values) -> [count, sum, sum, …]
        self._rows: Dict[Tuple, List[float]] = {}
        self._lock = threading.Lock()

    def observe_steps(self, first_label: str, seconds: Tuple, stamps: Tuple) -> None:
        """One observation of ``stamps[i + 1] - stamps[i]`` under
        ``(first_label, seconds[i])`` for each i (the family has two
        labels; ``stamps`` is one longer than ``seconds``)."""
        with self._lock:
            row = self._rows.get((first_label, seconds))
            if row is None:
                row = self._rows[(first_label, seconds)] = [0.0] * (len(seconds) + 1)
            row[0] += 1
            prev = stamps[0]
            for i in range(1, len(stamps)):
                cur = stamps[i]
                row[i] += cur - prev
                prev = cur

    def observe(self, label: str, total: float, count: int = 1) -> None:
        """``count`` observations that sum to ``total``, in a family of one
        label (bytes a request, extras over a batch's rows)."""
        with self._lock:
            row = self._rows.get((label, ()))
            if row is None:
                row = self._rows[(label, ())] = [0.0, 0.0]
            row[0] += count
            row[1] += total

    def totals(self) -> Dict[Tuple[str, ...], Tuple[float, int]]:
        """{label values: (sum, count)}."""
        out: Dict[Tuple[str, ...], List[float]] = {}
        with self._lock:
            for (first, seconds), row in self._rows.items():
                if not seconds:  # a family of one label: [count, sum]
                    cell = out.setdefault((first,), [0.0, 0])
                    cell[0] += row[1]
                    cell[1] += int(row[0])
                for i, second in enumerate(seconds):
                    cell = out.setdefault((first, second), [0.0, 0])
                    cell[0] += row[i + 1]
                    cell[1] += int(row[0])
        return {k: (v[0], v[1]) for k, v in out.items()}

    def collect(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} summary"]
        for key, (total, count) in sorted(self.totals().items()):
            labels = _fmt_label(tuple(zip(self.label_names, key)))
            out.append(f"{self.name}_sum{labels} {_fmt_value(total)}")
            out.append(f"{self.name}_count{labels} {count}")
        return out


class Registry:
    def __init__(self):
        self._metrics: List = []
        self._lock = threading.Lock()

    def register(self, metric):
        with self._lock:
            self._metrics.append(metric)
        return metric

    def expose(self) -> str:
        lines: List[str] = []
        with self._lock:
            metrics = list(self._metrics)
        for m in metrics:
            lines.extend(m.collect())
        return "\n".join(lines) + "\n"


REGISTRY = Registry()

request_total = REGISTRY.register(
    Counter(
        f"{SUBSYSTEM}_request_total",
        "Number of HTTP requests partitioned by authorization decision.",
        ["decision"],
    )
)

request_latency = REGISTRY.register(
    Histogram(
        f"{SUBSYSTEM}_request_duration_seconds",
        "Request latency in seconds partitioned by authorization decision "
        "and by who answered (by: cache = the decision cache; engine = the "
        "device plane; rule = the webhook's own rules before Cedar, "
        "self-allow and the system:* skip; interpreter = the interpreter, "
        "for a gated or fallen-back row, a bypassed plane or a deployment "
        "without one). The one observation a request makes on this family "
        "carries both labels; sum over `by` for the family as it was.",
        ["decision", "by"],
        [0.25, 0.5, 0.7, 1, 1.5, 3, 5, 10],
    )
)

e2e_latency = REGISTRY.register(
    Histogram(
        f"{SUBSYSTEM}_e2e_latency_seconds",
        "End to end latency in seconds partitioned by filename. The "
        "filename label is CAPPED: after the first 64 distinct "
        "filenames, further names fold into the `other` bucket "
        "(cedar_authorizer_e2e_label_overflow_total counts the folds) — "
        "replay directories are unbounded and an unbounded label set is "
        "a scrape-size leak.",
        ["filename"],
        [2.0 * (2.0**i) for i in range(8)],
    )
)

# cap for the e2e histogram's filename label set (replay stamps one label
# per recording file; a big recording directory must not explode the
# exposition)
_E2E_LABEL_CAP = 64
_e2e_labels: set = set()
_e2e_label_lock = threading.Lock()

e2e_label_overflow_total = REGISTRY.register(
    Counter(
        f"{SUBSYSTEM}_e2e_label_overflow_total",
        "e2e latency observations whose filename label was folded into "
        "`other` because the bounded label set was full. Nonzero just "
        "means a big replay; per-file latency for the folded names lives "
        "in the replay CLI's own output, not the scrape.",
        [],
    )
)


# ------------------------------------------------------------- tenancy
# Multi-tenant shared planes (cedar_tpu/tenancy, docs/multitenancy.md):
# per-tenant serving series under a BOUNDED tenant label (the e2e
# filename-cap pattern above) — tenant ids are operator-registered, but a
# misconfigured front end must not explode the exposition.
_TENANT_LABEL_CAP = 64
_tenant_labels: set = set()
_tenant_label_lock = threading.Lock()

tenant_requests_total = REGISTRY.register(
    Counter(
        "cedar_tenant_requests_total",
        "Requests served per tenant, path and decision on a fused "
        "multi-tenant plane. The tenant label is CAPPED at 64 distinct "
        "ids; later ids fold into `other` "
        "(cedar_tenant_label_overflow_total counts the folds).",
        ["tenant", "path", "decision"],
    )
)

tenant_request_latency = REGISTRY.register(
    Histogram(
        "cedar_tenant_request_duration_seconds",
        "Per-tenant request latency on a fused multi-tenant plane "
        "(bounded tenant label, see cedar_tenant_requests_total).",
        ["tenant", "path"],
        [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1, 5],
    )
)

tenant_label_overflow_total = REGISTRY.register(
    Counter(
        "cedar_tenant_label_overflow_total",
        "Tenant-labeled observations folded into `other` because the "
        "bounded tenant label set was full.",
        [],
    )
)

# PDP front end (cedar_tpu/pdp, docs/pdp.md): the wire protocol a request
# arrived on joins the request counter/latency families as an OPTIONAL
# appended label (Counter.inc extra=) — the native webhook passes no
# protocol, so single-protocol deployments' exposition stays byte-identical.
# Protocol names come from code ("extauthz"/"batch"), but the cap guards
# against a future front end stamping request-derived values.
_PROTOCOL_LABEL_CAP = 8
_protocol_labels: set = set()
_protocol_label_lock = threading.Lock()

protocol_label_overflow_total = REGISTRY.register(
    Counter(
        "cedar_protocol_label_overflow_total",
        "Protocol-labeled observations folded into `other` because the "
        "bounded protocol label set was full.",
        [],
    )
)

tenant_rejected_total = REGISTRY.register(
    Counter(
        "cedar_tenant_rejected_total",
        "Requests the tenant front end refused before evaluation, by "
        "reason: `unknown` = a tenant id resolved but is not registered, "
        "`missing` = no tenant id resolved and no default configured, "
        "`conflict` = enabled resolution sources named different tenants.",
        ["reason"],
    )
)

tenant_policies = REGISTRY.register(
    Gauge(
        "cedar_tenant_policies",
        "Policies contributed to the fused plane per tenant.",
        ["tenant"],
    )
)

fallback_decisions_total = REGISTRY.register(
    Counter(
        "cedar_fallback_decisions_total",
        "Decisions whose evaluation was interpreter-merged because the "
        "serving plane carries unlowerable policies, partitioned by "
        "Unlowerable reason code (one increment per decision per distinct "
        "code present) and serving engine (authorization/admission/"
        "replica — names come from code, never request data, so the "
        "label set is bounded). The burn-down signal for the "
        "lowerability coverage drive: lowering a construct family drops "
        "its code's rate to zero (docs/analysis.md; tallied on "
        "/debug/engine).",
        ["code", "engine"],
    )
)


def _tenant_label_for(tenant: str) -> str:
    with _tenant_label_lock:
        if tenant != "other" and tenant not in _tenant_labels:
            if len(_tenant_labels) >= _TENANT_LABEL_CAP:
                tenant_label_overflow_total.inc()
                return "other"
            _tenant_labels.add(tenant)
    return tenant


def record_tenant_request(
    path: str, tenant: str, decision: str, latency_s: float
) -> None:
    if not tenant:
        return
    t = _tenant_label_for(tenant)
    tenant_requests_total.inc(tenant=t, path=path, decision=decision)
    tenant_request_latency.observe(latency_s, tenant=t, path=path)


def record_tenant_rejected(reason: str) -> None:
    tenant_rejected_total.inc(reason=reason)


def set_tenant_policies(tenant: str, n: int) -> None:
    tenant_policies.set(n, tenant=_tenant_label_for(tenant))


def clear_tenant_policies(tenant: str) -> None:
    """Drop an offboarded tenant's policy-count gauge row AND free its
    slot in the bounded tenant label set — with tenant churn, departed
    ids must not consume the cap forever or every newly onboarded tenant
    folds into ``other`` while live tenancy is far below the limit.
    (The departed tenant's counter/histogram rows keep their last values
    — counters never un-count — but new observations for a re-onboarded
    id register afresh.) Tenants that were folded into ``other`` are
    left alone — that row aggregates several tenants."""
    with _tenant_label_lock:
        known = tenant in _tenant_labels
        _tenant_labels.discard(tenant)
    if known:
        tenant_policies.remove(tenant=tenant)


# ----------------------------------------------------------- lifecycle
# Declarative policy-lifecycle controller (cedar_tpu/lifecycle,
# docs/rollout.md "Declarative lifecycle"): per-tenant rollout stage and
# transition accounting under the same bounded tenant label as the
# tenancy families above — lifecycle specs are operator-authored, but a
# runaway spec directory must not explode the exposition either.

lifecycle_stage = REGISTRY.register(
    Gauge(
        "cedar_lifecycle_stage",
        "Current lifecycle stage per tenant rollout, as a code: 0=pending "
        "1=verifying 2=shadowing 3=canary 4=promoting 5=promoted "
        "6=halted 7=rolled_back 8=failed 9=analyzing (appended so "
        "dashboards keyed on 0-8 stay valid). Bounded tenant label (see "
        "cedar_tenant_requests_total); the row is removed when the "
        "tenant's rollout spec is deleted.",
        ["tenant"],
    )
)

lifecycle_transitions_total = REGISTRY.register(
    Counter(
        "cedar_lifecycle_transitions_total",
        "Lifecycle stage transitions per tenant rollout (bounded tenant "
        "label). `from`/`to` are stage names; alert on any transition "
        "into `halted`/`failed`.",
        ["tenant", "from", "to"],
    )
)

lifecycle_gate_breaches_total = REGISTRY.register(
    Counter(
        "cedar_lifecycle_gate_breaches_total",
        "Gate breaches that halted a tenant's rollout, by gate tier "
        "(`lowerability`, `analyze_oracle`, `semantic_diff`, "
        "`shadow_diff`, `slo_burn`, `deadline`). Each breach triggers "
        "automatic halt + rollback.",
        ["tenant", "gate"],
    )
)

lifecycle_retries_total = REGISTRY.register(
    Counter(
        "cedar_lifecycle_retries_total",
        "Transient stage-failure retries per tenant rollout and stage "
        "(decorrelated-jitter backoff under the per-stage deadline).",
        ["tenant", "stage"],
    )
)


def set_lifecycle_stage(tenant: str, code: int) -> None:
    lifecycle_stage.set(code, tenant=_tenant_label_for(tenant))


def record_lifecycle_transition(tenant: str, frm: str, to: str) -> None:
    # "from" is a keyword, so the label dict is spelled out
    lifecycle_transitions_total.inc(
        **{"tenant": _tenant_label_for(tenant), "from": frm, "to": to}
    )


def record_lifecycle_gate_breach(tenant: str, gate: str) -> None:
    lifecycle_gate_breaches_total.inc(
        tenant=_tenant_label_for(tenant), gate=gate
    )


def record_lifecycle_retry(tenant: str, stage: str) -> None:
    lifecycle_retries_total.inc(
        tenant=_tenant_label_for(tenant), stage=stage
    )


def clear_lifecycle_tenant(tenant: str) -> None:
    """Drop a deleted rollout spec's stage gauge row and free the
    tenant's slot in the bounded label set (the clear_tenant_policies
    contract: counters keep their last values, gauges must not keep
    reporting a rollout that no longer exists)."""
    with _tenant_label_lock:
        known = tenant in _tenant_labels
        _tenant_labels.discard(tenant)
    if known:
        lifecycle_stage.remove(tenant=tenant)


def record_fallback_decision(codes, engine: str = "") -> None:
    """One interpreter-merged decision under each distinct Unlowerable
    code it was served with (precomputed tuple, compiler/pack.py), on the
    named serving engine."""
    eng = engine or "unknown"
    for code in codes or ("unlowerable",):
        fallback_decisions_total.inc(code=code, engine=eng)


def fallback_decision_counts(engine=None) -> dict:
    """Per-code snapshot of cedar_fallback_decisions_total for
    /debug/engine and /debug/analysis: codes aggregated across all
    engines by default, or one serving PLANE's slice when ``engine`` is
    given — an authorization plane's served fallback traffic must never
    read as the admission plane's burn-down signal. A plane filter
    includes its fleet replicas (``<engine>-r<i>``, cli/webhook.py): the
    replicas serve the same policy plane, so their fallback decisions
    belong to its burn-down ranking."""
    with fallback_decisions_total._lock:
        out: dict = {}
        for key, v in fallback_decisions_total._values.items():
            kd = dict(key)
            if engine is not None:
                got = kd.get("engine", "")
                if got != engine and not got.startswith(f"{engine}-r"):
                    continue
            code = kd.get("code", "")
            out[code] = out.get(code, 0) + int(v)
        return out


# --------------------------------------------------------- overload control
# Priority-aware admission control + SLO-adaptive batching
# (cedar_tpu/load, docs/performance.md "Serving under overload"). The
# client label on the throttle counter is BOUNDED like the tenant/e2e
# label sets above: a reconnect storm minting principals must not explode
# the exposition.
_CLIENT_LABEL_CAP = 64
_client_labels: set = set()
_client_label_lock = threading.Lock()

load_shed_total = REGISTRY.register(
    Counter(
        "cedar_load_shed_total",
        "Requests refused by the overload-control plane, by priority and "
        "reason (load_pressure / load_overload / saturated / client_quota "
        "/ eval_saturated / chaos). Sheds answer honestly — SAR NoOpinion "
        "+ Retry-After, admission per the fail-open/closed flag — and "
        "offered == admitted + shed holds exactly at the ingress gate.",
        ["priority", "reason"],
    )
)

inflight_requests = REGISTRY.register(
    Gauge(
        "cedar_inflight_requests",
        "Admitted requests currently in flight (queue wait + evaluation), "
        "per path and priority — the load signal the admission "
        "controller's graduated states derive from.",
        ["path", "priority"],
    )
)

load_state_gauge = REGISTRY.register(
    Gauge(
        "cedar_load_state",
        "Graduated overload state: 0 ok, 1 pressure (sheddable traffic "
        "shedding), 2 overload (normal traffic shedding), 3 saturated "
        "(everything sheds; /readyz reads 503).",
        [],
    )
)

batch_tuning = REGISTRY.register(
    Gauge(
        "cedar_batch_tuning",
        "Live value of each adaptive-batching knob per serving path "
        "(param: max_batch, linger_us) — watch the SLO-adaptive "
        "controller move during a storm (decision log at /debug/load).",
        ["path", "param"],
    )
)

client_throttled_total = REGISTRY.register(
    Counter(
        "cedar_client_throttled_total",
        "Requests shed by a per-client fair-share quota, by client "
        "(the SAR/admission username; CAPPED at 64 distinct ids, later "
        "ids fold into `other` — cedar_client_label_overflow_total "
        "counts the folds).",
        ["client"],
    )
)

client_label_overflow_total = REGISTRY.register(
    Counter(
        "cedar_client_label_overflow_total",
        "Client-labeled throttle observations folded into `other` "
        "because the bounded client label set was full.",
        [],
    )
)


def record_load_shed(priority: str, reason: str) -> None:
    load_shed_total.inc(priority=priority, reason=reason)


def set_inflight(path: str, priority: str, n: int) -> None:
    inflight_requests.set(n, path=path, priority=priority)


def set_load_state(code: int) -> None:
    load_state_gauge.set(code)


def set_batch_tuning(path: str, param: str, value: float) -> None:
    batch_tuning.set(value, path=path, param=param)


def record_client_throttled(client: str) -> None:
    with _client_label_lock:
        if client != "other" and client not in _client_labels:
            if len(_client_labels) >= _CLIENT_LABEL_CAP:
                client_label_overflow_total.inc()
                client = "other"
            else:
                _client_labels.add(client)
    client_throttled_total.inc(client=client)


row_routing_total = REGISTRY.register(
    Counter(
        f"{SUBSYSTEM}_row_routing_total",
        "Fast-path rows partitioned by routing class: clean_native rows "
        "decode on device verdicts alone; gated rows matched the scope of a "
        "fallback/native-opaque policy and re-ran the exact Python path; "
        "flagged rows needed a rule-bitset fetch (multi-policy/error "
        "verdicts); encoder_fallback rows the C++ encoder could not prove "
        "equivalent (parse quirks, extras overflow, unsupported shapes); "
        "encoder_gate rows short-circuited in the encoder (self-allow, "
        "system/namespace skip). A growing gated share is the early signal "
        "of the gate-plane throughput cliff (docs/Operations.md).",
        ["path", "row_class"],
    )
)


breaker_state = REGISTRY.register(
    Gauge(
        f"{SUBSYSTEM}_breaker_state",
        "Circuit breaker state per evaluation engine: 0 closed (device "
        "plane healthy), 1 open (whole batches routed to the interpreter "
        "fallback), 2 half-open (probing recovery).",
        ["engine"],
    )
)

breaker_transitions_total = REGISTRY.register(
    Counter(
        f"{SUBSYSTEM}_breaker_transitions_total",
        "Circuit breaker state transitions partitioned by engine and "
        "destination state.",
        ["engine", "to"],
    )
)

deadline_exceeded_total = REGISTRY.register(
    Counter(
        f"{SUBSYSTEM}_deadline_exceeded_total",
        "Requests whose per-request deadline budget elapsed before a batch "
        "result arrived; authorization answers NoOpinion+evaluationError, "
        "admission answers the configured fail-mode.",
        ["path"],
    )
)

requests_shed_total = REGISTRY.register(
    Counter(
        f"{SUBSYSTEM}_requests_shed_total",
        "Requests refused with 503 because the server is draining for "
        "shutdown.",
        ["path"],
    )
)

fallback_batches_total = REGISTRY.register(
    Counter(
        f"{SUBSYSTEM}_fallback_batches_total",
        "Evaluation work served by the Python interpreter fallback instead "
        "of the device plane, partitioned by path and reason (breaker_open: "
        "the circuit breaker rejected the work; evaluator_error: the device "
        "evaluation raised and the work re-ran on the interpreter). Counted "
        "per batch on the batched fastpaths and per request when an open "
        "breaker bypasses the batcher or on the hybrid evaluate path, so "
        "absolute counts are not comparable across reasons during an "
        "outage — alert on nonzero rate, not magnitude.",
        ["path", "reason"],
    )
)


# Decision-cache metrics (cedar_tpu/cache): the hot path in front of the
# engines. Outside the cedar_authorizer_* subsystem — the cache serves both
# authorization and admission, partitioned by the `path` label.
decision_cache_hits_total = REGISTRY.register(
    Counter(
        "cedar_decision_cache_hits_total",
        "Decision cache lookups answered from cache, partitioned by path "
        "(authorization / admission). A hit returns without any engine or "
        "interpreter evaluation.",
        ["path"],
    )
)

decision_cache_misses_total = REGISTRY.register(
    Counter(
        "cedar_decision_cache_misses_total",
        "Decision cache lookups that fell through to evaluation, "
        "partitioned by path. Expired-TTL and stale-generation entries "
        "count as misses (and as evictions).",
        ["path"],
    )
)

decision_cache_evictions_total = REGISTRY.register(
    Counter(
        "cedar_decision_cache_evictions_total",
        "Decision cache entries dropped, partitioned by path and reason "
        "(lru: capacity pressure; ttl: decision-class TTL elapsed; "
        "generation: policy-set reload invalidated the entry; flush: "
        "operator/test invalidate_all). A persistent lru rate means the "
        "working set exceeds --decision-cache-size.",
        ["path", "reason"],
    )
)

decision_cache_coalesced_total = REGISTRY.register(
    Counter(
        "cedar_decision_cache_coalesced_total",
        "Requests that attached to an in-flight identical evaluation "
        "(singleflight followers), partitioned by path. These requests "
        "neither hit nor evaluated: they waited for a concurrent leader.",
        ["path"],
    )
)

decision_cache_size = REGISTRY.register(
    Gauge(
        "cedar_decision_cache_size",
        "Current decision cache entry count, partitioned by path.",
        ["path"],
    )
)

decision_cache_hit_ratio = REGISTRY.register(
    Gauge(
        "cedar_decision_cache_hit_ratio",
        "Lifetime hits / (hits + misses), partitioned by path. Alert on a "
        "sustained drop: repetitive apiserver traffic should hold a high "
        "ratio, and a collapse usually means TTLs are too short or policy "
        "reloads are churning generations.",
        ["path"],
    )
)

fingerprint_memo_total = REGISTRY.register(
    Counter(
        "cedar_fingerprint_memo_total",
        "Body-digest -> canonical-fingerprint memo lookups in front of the "
        "decision cache (cache/fingerprint.py FingerprintMemo), by path "
        "and outcome: a hit is one sha256 and a dict read, a miss pays "
        "the JSON parse and the canonical hash the memo exists to avoid. "
        "Counted under the memo's own lock and mirrored here at scrape "
        "time, so the request path pays no observation for it. A hit "
        "share well under the decision cache's means the working set of "
        "bodies outgrew the memo.",
        ["path", "outcome"],
    )
)


# Pipelined-evaluation metrics (engine/batcher.py PipelinedBatcher +
# TPUPolicyEngine.warmup, docs/performance.md). Outside the
# cedar_authorizer_* subsystem like the cache metrics: they describe the
# engine pipeline shared by both paths, partitioned by the `path` label.
batch_occupancy = REGISTRY.register(
    Histogram(
        "cedar_batch_occupancy",
        "Rows per formed micro-batch, partitioned by path. A distribution "
        "stuck at 1 under load means the batch window is too short (or "
        "traffic too serialized) to amortize device dispatch; a "
        "distribution pinned at max_batch with rising pipeline stalls "
        "means the device is the bottleneck.",
        ["path"],
        [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384],
    )
)

batch_claims_total = REGISTRY.register(
    Counter(
        "cedar_batch_claims_total",
        "Batches the pipelined batcher's collector claimed, partitioned by "
        "path and held: yes = work was in the submit queue and had to wait "
        "for the one standing place before the dispatch thread (the late "
        "claim engaged: the dispatch stage sets the pace); no = the place "
        "was free when work came (a lone caller, an idle server). The time "
        "so held is cedar_pipeline_stall_seconds_total{stage=\"collect\"}.",
        ["path", "held"],
    )
)

batch_lingers_total = REGISTRY.register(
    Counter(
        "cedar_batch_lingers_total",
        "Claims of the pipelined batcher's collector that slept out the "
        "forming window (--batch-window-us) first, by path: nothing was in "
        "flight and two or more requests already waited (a burst's first "
        "claim). A request that came alone to an idle pipeline, and any "
        "claim while a batch is in flight, is claimed at once and counts "
        "nothing here; over cedar_batch_claims_total it is the share of "
        "claims the window engaged for.",
        ["path"],
    )
)

pipeline_stall_seconds_total = REGISTRY.register(
    Counter(
        "cedar_pipeline_stall_seconds_total",
        "Seconds a pipeline stage spent stalled, partitioned by path and "
        "stage: collect = requests stood in the submit queue while the "
        "standing place before the dispatch thread was taken (the "
        "dispatch stage, the device or the decode behind it sets the "
        "pace); dispatch = the dispatch thread "
        "waited for the standing batch's encode, which the collector's "
        "thread runs (encode-bound); decode = the decode "
        "thread sat idle while batches were in flight (pipeline "
        "starvation). Rate > ~0.5 s/s on one stage names the bottleneck "
        "(docs/performance.md has the tuning table).",
        ["path", "stage"],
    )
)

pipeline_stage_seconds = REGISTRY.register(
    Histogram(
        "cedar_pipeline_stage_seconds",
        "Per-batch pipeline stage latency partitioned by path and stage "
        "(queue_wait: oldest submit -> batch claim; encode / dispatch / "
        "decode on the pipelined batchers; evaluate on the serial "
        "batcher). Recorded from the SAME monotonic timestamps the "
        "request traces use (docs/observability.md), so a dashboard and "
        "a /debug/traces span tree can never disagree about where a "
        "batch spent its time.",
        ["path", "stage"],
        [
            0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
            0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
        ],
    )
)

# Request phase ledger and stall recorder (cedar_tpu/obs, docs/
# observability.md "Request phases", "Process stalls"): where a served
# request's time went, socket to socket, and whether the process itself
# ran. Both are armed with the tracer and off under --no-trace.
request_phase_seconds = REGISTRY.register(
    Summary(
        "cedar_request_phase_seconds",
        "A served request's time by consecutive phase, one observation "
        "per request per phase (between, read, pre, parse, queue, "
        "encode_wait, encode, dispatch_wait, dispatch, device_wait, "
        "decode, wake, respond, write; a serial batcher reports "
        "evaluate_wait and evaluate). On a keep-alive connection a "
        "request's phases sum to the time from one reply flushed to the "
        "next. Cut from the SAME stamps as the request's /debug/traces "
        "spans and cedar_pipeline_stage_seconds.",
        ["path", "phase"],
    )
)

http_reads_total = REGISTRY.register(
    Counter(
        "cedar_http_reads_total",
        "Requests the webhook's handler read, partitioned by path "
        "(authorization / admission / other: a request answered before "
        "its endpoint was known) and how: scan = the handler's one pass "
        "over the buffered header block (a POST, HTTP/1.1, plain header "
        "lines); full = http.server's own reader (any other method or "
        "version, Expect, Transfer-Encoding, a folded or malformed line, "
        "a header block that came in pieces). A rising full share under "
        "apiserver traffic means something in front re-shapes requests "
        "(docs/performance.md \"One read, one write\").",
        ["path", "how"],
    )
)

# The admission path's request timer and the sizes of what a request
# carries (docs/observability.md "Request phases"): the phase ledger says
# where an admission request's time went; these say how long the handler
# held it, how large its body was and how many set-membership extras its
# row took to the device.
admission_request_latency = REGISTRY.register(
    Histogram(
        "cedar_admission_request_duration_seconds",
        "Admission request latency in seconds partitioned by decision "
        "(allowed / denied / error): the /v1/admit twin of "
        "cedar_authorizer_request_duration_seconds, between the same two "
        "stamps as the phases parse … respond of "
        "cedar_request_phase_seconds{path=\"admission\"}.",
        ["decision"],
        [
            0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
            0.5, 1, 2.5, 5, 10,
        ],
    )
)

request_body_bytes = REGISTRY.register(
    Summary(
        "cedar_request_body_bytes",
        "Bytes of a served request's body by path, observed once a "
        "request where the body is read: _sum over _count is the mean "
        "body. An AdmissionReview carries whole objects (2-40 KB where a "
        "SubjectAccessReview is a few hundred bytes), and the native walk "
        "and the JSON parse scale with it.",
        ["path"],
    )
)

encode_extras = REGISTRY.register(
    Summary(
        "cedar_encode_extras",
        "Set-membership extras the native encoder emitted, by path: _sum "
        "over the extras of every encoded row, _count the rows. A row "
        "past the encoder's cap (256) leaves the native path and is "
        "counted as encoder_fallback in "
        "cedar_authorizer_row_routing_total; this family says how near "
        "the rows come to it.",
        ["path"],
    )
)

encode_ancestors_total = REGISTRY.register(
    Counter(
        "cedar_encode_ancestors_total",
        "Groups of the principals of natively encoded rows, by path and "
        "by where the encoder put each: slot (a policy-known group in one "
        "of the eight ancestor code slots), extras (a policy-known group "
        "past the slots: its `principal in` literals ride the row's "
        "extras list), unknown (no policy names it: it activates "
        "nothing). extras over slot + extras is the share of known "
        "memberships that the extras plane carries.",
        ["path", "where"],
    )
)

flagged_bits_total = REGISTRY.register(
    Counter(
        "cedar_flagged_bits_total",
        "Flagged rows (row_class=\"flagged\" of "
        "cedar_authorizer_row_routing_total: an answer that names several "
        "policies, or an error beside a match) by how each row's rule "
        "bitset reached the host: readback (it rode the launch's one "
        "result buffer, behind the verdict words: no further device "
        "call), word_cache (a row with the same feature bytes was "
        "resolved before), second_call (the standalone bits kernel: "
        "more flagged rows than the compaction holds, or a batch past "
        "the in-call bits plane).",
        ["path", "by"],
    )
)

launch_uploads_total = REGISTRY.register(
    Counter(
        "cedar_launch_uploads_total",
        "Host arrays the device launches sent up, by path: each argument "
        "of the jitted call that is not already on the device (the wire "
        "codes, the extras, the valid-row count) goes up on its own. Over "
        "the batches it is the transfers a launch makes before it can "
        "execute.",
        ["path"],
    )
)

launch_upload_bytes_total = REGISTRY.register(
    Counter(
        "cedar_launch_upload_bytes_total",
        "Bytes of the host arrays the device launches sent up, by path "
        "(cedar_launch_uploads_total's arrays).",
        ["path"],
    )
)

launch_readback_bytes_total = REGISTRY.register(
    Counter(
        "cedar_launch_readback_bytes_total",
        "Bytes of the device results whose copy home the launches "
        "started, by path: one buffer a launch on the served path (the "
        "verdict words and the flagged rows' rule bits).",
        ["path"],
    )
)

long_device_waits_total = REGISTRY.register(
    Counter(
        "cedar_long_device_waits_total",
        "Batches whose decode waited over 100 ms for the device's result "
        "(the decode.device_wait stage), by path and by whether a "
        "profiler session was open as the wait was counted (profiler on "
        "or off; off where jax was never imported). Each is logged at "
        "WARNING with the batch's seq and seconds.",
        ["path", "profiler"],
    )
)

interpreter_wait_seconds = REGISTRY.register(
    Histogram(
        "cedar_interpreter_wait_seconds",
        "How late the stall recorder's thread ran after each 20 ms wait: "
        "what a thread that is due waits to get the interpreter back "
        "(plus the kernel's timer slack).",
        [],
        [
            0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
            0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
        ],
    )
)

process_watch_seconds_total = REGISTRY.register(
    Counter(
        "cedar_process_watch_seconds_total",
        "Seconds the stall recorder has watched this process.",
        [],
    )
)

process_stalls_total = REGISTRY.register(
    Counter(
        "cedar_process_stalls_total",
        "Times the stall recorder's thread ran 100 ms late or more, by "
        "cause: gc (a collection overlaps at least half), descheduled "
        "(the whole process used under 10% of the stall in CPU), "
        "interpreter_held (the process ran, this thread could not). "
        "/debug/stalls lists the last 32.",
        ["cause"],
    )
)

process_stall_seconds_total = REGISTRY.register(
    Counter(
        "cedar_process_stall_seconds_total",
        "Seconds of lateness in the stalls cedar_process_stalls_total "
        "counts, by cause.",
        ["cause"],
    )
)

log_records_total = REGISTRY.register(
    Counter(
        "cedar_log_records_total",
        "Log records the serving log's sink (obs/logsink.py) took, by "
        "outcome: written (reached the stream) or dropped (below WARNING "
        "and over the queue's cap of 65,536; WARNING and above never are).",
        ["outcome"],
    )
)

log_writes_total = REGISTRY.register(
    Counter(
        "cedar_log_writes_total",
        "Writes the sink's one writer handed the log stream; written "
        "records over writes is how many lines a write carries.",
        [],
    )
)

engine_warmup_seconds = REGISTRY.register(
    Gauge(
        "cedar_engine_warmup_seconds",
        "Seconds the last TPUPolicyEngine.warmup() spent precompiling the "
        "(batch-bucket x extras-bucket) kernel planes, partitioned by "
        "engine. Near-zero after a reload means the bucketed shapes "
        "reused the previous executables (the common hot-swap case).",
        ["engine"],
    )
)

compile_seconds = REGISTRY.register(
    Histogram(
        "cedar_compile_seconds",
        "Policy-set compilation latency partitioned by phase (hash = "
        "shard-plan fingerprinting, lower = per-shard lowering, pack = "
        "fused plane assembly, place = device placement, total) and scope "
        "(full = every shard recompiled, incremental = only dirty shards "
        "re-lowered, cached slices reused). A CRD edit on a sharded plane "
        "should show scope=incremental with lower+pack+place well under a "
        "second (docs/performance.md, Giant policy sets).",
        ["phase", "scope"],
        [0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120],
    )
)

policy_shards = REGISTRY.register(
    Gauge(
        "cedar_policy_shards",
        "Tier/bucket shards in the engine's current compiled plane, "
        "partitioned by engine.",
        ["engine"],
    )
)

dirty_shards = REGISTRY.register(
    Gauge(
        "cedar_dirty_shards",
        "Shards recompiled by the engine's LAST reload (0 after a no-op "
        "reload, 1 after a single-policy CRD edit, = cedar_policy_shards "
        "after a full compile), partitioned by engine.",
        ["engine"],
    )
)

pruned_policies = REGISTRY.register(
    Gauge(
        "cedar_pruned_policies",
        "Policies excluded from the device plane by the serving-partition "
        "never-match proof (analysis/partition.py), partitioned by engine. "
        "Pruned policies stay host-side in the shard cache and page back "
        "in when the partition spec changes.",
        ["engine"],
    )
)

# Host-side budget metrics (docs/performance.md "Host-side budget"): the
# encode-threads gauge surfaces the resolved native encoder pool size so
# a mis-set CEDAR_NATIVE_THREADS is visible without a shell on the host.
native_encode_threads = REGISTRY.register(
    Gauge(
        "cedar_native_encode_threads",
        "Resolved per-batch native encoder worker-pool width "
        "(CEDAR_NATIVE_THREADS / --native-encode-threads / cpu count).",
        [],
    )
)


# Shadow-rollout metrics (cedar_tpu/rollout, docs/rollout.md): shadow
# evaluation is best-effort work BEHIND the live paths, so its counters
# are outside the cedar_authorizer_* request subsystem.
shadow_evaluations_total = REGISTRY.register(
    Counter(
        "cedar_shadow_evaluations_total",
        "Live requests re-evaluated against the staged candidate policy "
        "set, partitioned by path (authorization / admission). Compare "
        "with cedar_authorizer_request_total to see effective shadow "
        "coverage after sampling and shedding.",
        ["path"],
    )
)

shadow_diffs_total = REGISTRY.register(
    Counter(
        "cedar_shadow_diffs_total",
        "Shadow evaluations whose candidate answer differed from the live "
        "answer, partitioned by kind (allow_to_deny / deny_to_allow / "
        "decision_changed / reason_changed). Any nonzero allow_to_deny "
        "rate means promotion would break currently-working callers "
        "(docs/rollout.md).",
        ["kind"],
    )
)

shadow_shed_total = REGISTRY.register(
    Counter(
        "cedar_shadow_shed_total",
        "Sampled requests dropped because the shadow queue was full, "
        "partitioned by path. Shadow work is shed first under pressure by "
        "design; a sustained rate only means the diff report covers a "
        "smaller sample, never that live traffic slowed.",
        ["path"],
    )
)

# Explainability plane (cedar_tpu/explain, docs/explainability.md):
# ?explain=1 requests and the lazy explain-plane compiles they trigger.
explain_requests_total = REGISTRY.register(
    Counter(
        "cedar_explain_requests_total",
        "?explain=1 requests answered, partitioned by path (authorization "
        "/ admission). Explain traffic bypasses the decision cache and "
        "the batchers by design — a sustained high rate is an operator "
        "debugging session, not serving load (docs/explainability.md).",
        ["path"],
    )
)

explain_compiles_total = REGISTRY.register(
    Counter(
        "cedar_explain_compiles_total",
        "Fresh kernel traces paid by the lazily-compiled explain plane "
        "(the standalone bits shape, on first ?explain use per compiled "
        "set). Zero until the first explain request per (engine, "
        "generation) — the pay-for-use contract; nonzero growth outside "
        "policy reloads means explain traffic is hitting cold sets.",
        [],
    )
)

rollout_generation = REGISTRY.register(
    Gauge(
        "cedar_rollout_generation",
        "Monotonic rollout lifecycle counter: bumps on every stage, "
        "promote, and rollback. Join against decision-latency dashboards "
        "to correlate policy rollouts with behavior changes.",
        [],
    )
)


# Static-analysis metrics (cedar_tpu/analysis): deliberately outside the
# cedar_authorizer_* request subsystem — they describe the POLICY SET, not
# request traffic, and are re-published at every policy load.
policy_fastpath_lowerable = REGISTRY.register(
    Gauge(
        "cedar_policy_fastpath_lowerable",
        "Policies per tier the compiler lowers to the TPU fast path; the "
        "remainder evaluate on the per-row Python interpreter fallback. A "
        "drop after a policy deploy is the early signal of a latency "
        "regression (docs/analysis.md).",
        ["tier"],
    )
)

policy_analysis_findings_total = REGISTRY.register(
    Counter(
        "cedar_policy_analysis_findings_total",
        "Static-analysis findings observed at policy load, partitioned by "
        "reason code (docs/analysis.md catalog). Counted per load pass: "
        "alert on new codes appearing, not on magnitude.",
        ["kind"],
    )
)

# Device-exact policy-space analysis (analysis/space.py + semdiff.py):
# the enumerated request universe pushed through the packed plane. Mode
# is `sweep` (dead/shadowing/overlap verdicts) or `semdiff` (live vs
# candidate decision diff).
analysis_sweep_seconds = REGISTRY.register(
    Gauge(
        "cedar_analysis_sweep_seconds",
        "Wall-clock seconds of the last device-exact policy-space pass, "
        "by mode (`sweep`/`semdiff`). Scales with universe budget x "
        "rule count; watch for growth as the policy set grows.",
        ["mode"],
    )
)

analysis_universe_requests = REGISTRY.register(
    Gauge(
        "cedar_analysis_universe_requests",
        "Typed request-universe size of the last device-exact pass, by "
        "mode (`sweep`/`semdiff`). `exhaustive` reports whether the "
        "universe covered every vocab equivalence class (1) or was "
        "stratified under the budget (0).",
        ["mode", "exhaustive"],
    )
)

analysis_oracle_disagreements_total = REGISTRY.register(
    Counter(
        "cedar_analysis_oracle_disagreements_total",
        "Device-exact sweep verdicts that disagreed with the interpreter "
        "oracle on the sampled cross-check slice. Any nonzero value is a "
        "compiler or encoder bug, not a policy problem — page on it.",
        [],
    )
)

analysis_semdiff_flips_total = REGISTRY.register(
    Counter(
        "cedar_analysis_semdiff_flips_total",
        "Decision flips found by the lifecycle analyze gate's semantic "
        "diff (live vs candidate), by flip kind (`allow_to_deny`/"
        "`deny_to_allow`) under the bounded tenant label. Flips outside "
        "the spec's allowed intents breach the gate before any live "
        "traffic sees the candidate.",
        ["tenant", "kind"],
    )
)


# Supervision / chaos metrics (server/supervisor.py, cedar_tpu/chaos,
# docs/resilience.md "Game days"): the self-healing plane. Outside the
# cedar_authorizer_* request subsystem — these describe worker threads and
# injected faults, not request traffic.
worker_deaths_total = REGISTRY.register(
    Counter(
        "cedar_worker_deaths_total",
        "Long-lived worker threads that exited on an uncaught exception, "
        "partitioned by component (batcher stages, shadow worker, CRD "
        "watch, store reload ticker) and replica (the fleet member the "
        "worker served; empty on the single-engine path). Any nonzero "
        "rate is a bug or an injected fault; without supervision a dead "
        "worker leaves its bounded queue filling forever, so alert on "
        "this even before the supervisor restarts it.",
        ["component", "replica"],
    )
)

supervisor_restarts_total = REGISTRY.register(
    Counter(
        "cedar_supervisor_restarts_total",
        "Component restarts performed by the supervisor watchdog, "
        "partitioned by component and replica (empty on the single-engine "
        "path). Dead threads and wedged (stale busy heartbeat) stages "
        "both count; queued work held by the restarted stage is shed "
        "with per-request error answers rather than stranded.",
        ["component", "replica"],
    )
)

device_rebuilds_total = REGISTRY.register(
    Counter(
        "cedar_device_rebuilds_total",
        "TPU engine rebuilds performed by the device-loss recovery: a "
        "fatal XLA/runtime error tripped the breaker, the compiled set "
        "was re-placed from the retained host-side pack, the warm ladder "
        "re-ran, and the breaker re-armed half-open.",
        [],
    )
)

quarantined_objects = REGISTRY.register(
    Gauge(
        "cedar_quarantined_objects",
        "Policy objects currently quarantined (parse or load-gate "
        "failures); serving continues on each object's last-known-good "
        "content. /debug/quarantine names them — a nonzero steady state "
        "means someone shipped a poison policy object.",
        [],
    )
)

# Engine-fleet metrics (cedar_tpu/fleet, docs/fleet.md): the replicated
# serving tier. Outside the cedar_authorizer_* request subsystem — these
# describe replica routing and fleet lifecycle, not individual requests.
fleet_replica_state = REGISTRY.register(
    Gauge(
        "cedar_fleet_replica_state",
        "Per-replica serving state: 0 active (in the routing set), "
        "1 degraded (breaker open or fastpath unavailable; routed around), "
        "2 rebuilding (device recovery re-placing the compiled set), "
        "3 draining (operator drain; no new work), 4 dead/retired "
        "(worker threads down pending supervisor revive, or retired).",
        ["fleet", "replica"],
    )
)

fleet_routed_total = REGISTRY.register(
    Counter(
        "cedar_fleet_routed_total",
        "Requests dispatched to each fleet replica by the health-aware "
        "router. A sustained skew under even load means the other "
        "replicas are being scored unhealthy (see "
        "cedar_fleet_replica_state).",
        ["fleet", "replica"],
    )
)

fleet_spillover_total = REGISTRY.register(
    Counter(
        "cedar_fleet_spillover_total",
        "Requests re-routed to another replica after their first replica "
        "failed mid-flight (dead worker, raising batcher). Deterministic "
        "spillover preserves availability; a nonzero rate names a sick "
        "replica, not lost requests.",
        ["fleet"],
    )
)

fleet_hedges_total = REGISTRY.register(
    Counter(
        "cedar_fleet_hedges_total",
        "Lone requests that fired a tail-latency hedge: the primary "
        "replica had not answered within the hedge delay, so a duplicate "
        "was dispatched to a second healthy replica (first answer wins, "
        "the loser is cancelled).",
        ["fleet"],
    )
)

fleet_hedge_wins_total = REGISTRY.register(
    Counter(
        "cedar_fleet_hedge_wins_total",
        "Hedged requests partitioned by which dispatch answered first "
        "(primary / hedge). A high hedge share means the hedge delay is "
        "below the primary's healthy tail — or a replica is quietly "
        "slow.",
        ["fleet", "winner"],
    )
)

fanout_worker_state = REGISTRY.register(
    Gauge(
        "cedar_fanout_worker_state",
        "Per-fanout-worker liveness as the front-end sees it: 1 alive "
        "(in the hash ring's serving set), 0 dead (keys rehashed to the "
        "next ring choice pending restart).",
        ["fanout", "worker"],
    )
)

fanout_routed_total = REGISTRY.register(
    Counter(
        "cedar_fanout_routed_total",
        "Requests the front-end handed to each fanout worker. Under "
        "consistent hashing the split tracks key ownership (~1/N each "
        "with default vnodes); a skew names a hot key range, not a "
        "router bug.",
        ["fanout", "worker"],
    )
)

fanout_reroutes_total = REGISTRY.register(
    Counter(
        "cedar_fanout_reroutes_total",
        "Requests served by a non-home worker because an earlier ring "
        "choice was dead or died mid-request — the rehash in action. "
        "Sustained nonzero rate means a worker is flapping.",
        ["fanout"],
    )
)

fanout_worker_restarts_total = REGISTRY.register(
    Counter(
        "cedar_fanout_worker_restarts_total",
        "Dead fanout workers put back in rotation (supervisor watchdog "
        "or inline self-heal). A restarted worker comes back with an "
        "EMPTY decision cache and re-warms from traffic + peers.",
        ["fanout"],
    )
)

pod_hosts = REGISTRY.register(
    Gauge(
        "cedar_pod_hosts",
        "Processes in this pod's one logical engine (jax.distributed "
        "world size). 0/absent on single-host deployments; a value "
        "below the deployed host count means part of the slice never "
        "joined.",
        [],
    )
)

pod_partition_reuploads_total = REGISTRY.register(
    Counter(
        "cedar_pod_partition_reuploads_total",
        "Dirty policy partitions re-uploaded per OWNING host by pod "
        "barrier swaps. Under the policy-exclusive arrangement a "
        "one-policy edit moves exactly one host's counter — several "
        "hosts moving on one edit means shard->partition locality "
        "regressed (docs/fleet.md).",
        ["host"],
    )
)

peer_cache_events_total = REGISTRY.register(
    Counter(
        "cedar_peer_cache_events_total",
        "Peer-shared decision cache traffic by event: fetches/fetch_hits "
        "(miss-path asks to ring-preferred holders), gossip_out/"
        "gossip_in (miss-fill replication), peer_served (local hits on "
        "peer-originated entries — the cross-worker warmth signal), "
        "stale_dropped (records refused because this worker's plane "
        "content disagreed — the coherence guard working).",
        ["path", "event"],
    )
)

fleet_promotions_total = REGISTRY.register(
    Counter(
        "cedar_fleet_promotions_total",
        "Fleet-atomic compiled-set swaps partitioned by result: "
        "committed (every replica adopted the candidate under the "
        "generation barrier) or rolled_back (a replica swap failed and "
        "every already-swapped replica was restored to the prior set — "
        "no mixed-generation serving).",
        ["result"],
    )
)


# Observability plane (cedar_tpu/obs, docs/observability.md): request
# tracing keep counts, decision audit log rotation, and the SLO burn-rate
# gauges refreshed at scrape time. Outside the cedar_authorizer_* request
# subsystem — these describe the observability surfaces, not decisions.
trace_kept_total = REGISTRY.register(
    Counter(
        "cedar_trace_kept_total",
        "Finished request traces kept into the /debug/traces ring, "
        "partitioned by path and keep reason (sampled: head sampling; "
        "slow: tail-keep past the tail latency budget; error: the "
        "request answered with an evaluation error; fallback: served by "
        "a degraded path). A rising error/fallback rate with sampled "
        "flat is the tracing plane catching exactly the requests head "
        "sampling would have missed.",
        ["path", "reason"],
    )
)

audit_records_total = REGISTRY.register(
    Counter(
        "cedar_audit_records_total",
        "Decision audit log lines appended, partitioned by path. "
        "Compare with cedar_authorizer_request_total: a persistent gap "
        "means audit appends are failing (the log disables itself on "
        "I/O errors rather than slowing serving).",
        ["path"],
    )
)

audit_rotations_total = REGISTRY.register(
    Counter(
        "cedar_audit_rotations_total",
        "Size-based audit log rotations (<path> -> <path>.1 shifts).",
        [],
    )
)

slo_burn_rate = REGISTRY.register(
    Gauge(
        "cedar_slo_burn_rate",
        "Error-budget burn rate per path, objective (availability / "
        "latency) and trailing window (5m / 1h / 6h): bad-request "
        "fraction over the window divided by the objective's error "
        "budget. 1.0 consumes the budget exactly at the sustain rate; "
        "the canonical fast-burn page is rate > 14.4 on the short "
        "window AND > 1 on the long one (docs/observability.md).",
        ["path", "slo", "window"],
    )
)

slo_target = REGISTRY.register(
    Gauge(
        "cedar_slo_target",
        "Configured SLO target per path and objective (availability: "
        "non-error answer fraction; latency: fraction answered within "
        "the latency budget).",
        ["path", "slo"],
    )
)


chaos_injections_total = REGISTRY.register(
    Counter(
        "cedar_chaos_injections_total",
        "Faults injected by the chaos plane, partitioned by seam and kind "
        "(error / latency / corrupt / kill / response_error / "
        "response_deny). Nonzero only while a game-day scenario is armed "
        "(or the reference-parity response injector is enabled); alert on "
        "this in production — it should never move outside game days.",
        ["seam", "kind"],
    )
)


def _protocol_label_for(protocol: str) -> str:
    with _protocol_label_lock:
        if protocol != "other" and protocol not in _protocol_labels:
            if len(_protocol_labels) >= _PROTOCOL_LABEL_CAP:
                protocol_label_overflow_total.inc()
                return "other"
            _protocol_labels.add(protocol)
    return protocol


def _protocol_extra(protocol: str) -> Tuple:
    """Appended label pairs for the request families: empty protocol (the
    native SAR/AdmissionReview webhook) appends NOTHING, keeping
    single-protocol expositions byte-identical; PDP protocols append a
    bounded ``protocol`` label."""
    if not protocol:
        return ()
    return (("protocol", _protocol_label_for(protocol)),)


def record_request_total(decision: str, protocol: str = "") -> None:
    request_total.inc(decision=decision, extra=_protocol_extra(protocol))


def record_row_routing(path: str, row_class: str, n: int) -> None:
    if n:
        row_routing_total.inc(n, path=path, row_class=row_class)


def record_request_latency(
    decision: str, latency_s: float, protocol: str = "", by: str = "engine"
) -> None:
    request_latency.observe(
        latency_s, decision=decision, by=by, extra=_protocol_extra(protocol)
    )


def record_e2e_latency(filename: str, latency_s: float) -> None:
    """Observe under a BOUNDED filename label set: the first
    _E2E_LABEL_CAP distinct names get their own series, everything after
    folds into `other` (and counts the overflow). `other` is always
    admitted so the fold can never itself overflow."""
    with _e2e_label_lock:
        if filename != "other" and filename not in _e2e_labels:
            if len(_e2e_labels) >= _E2E_LABEL_CAP:
                e2e_label_overflow_total.inc()
                filename = "other"
            else:
                _e2e_labels.add(filename)
    e2e_latency.observe(latency_s, filename=filename)


def set_breaker_state(engine: str, state_code: int) -> None:
    breaker_state.set(state_code, engine=engine)


def record_breaker_transition(engine: str, to_state: str) -> None:
    breaker_transitions_total.inc(engine=engine, to=to_state)


def record_deadline_exceeded(path: str) -> None:
    deadline_exceeded_total.inc(path=path)


def record_shed(path: str) -> None:
    requests_shed_total.inc(path=path)


def record_fallback_batch(path: str, reason: str) -> None:
    fallback_batches_total.inc(path=path, reason=reason)


def record_cache_hit(path: str) -> None:
    decision_cache_hits_total.inc(path=path)


def record_cache_miss(path: str) -> None:
    decision_cache_misses_total.inc(path=path)


def record_cache_evictions(path: str, reason: str, n: int = 1) -> None:
    if n:
        decision_cache_evictions_total.inc(n, path=path, reason=reason)


def record_cache_coalesced(path: str) -> None:
    decision_cache_coalesced_total.inc(path=path)


def set_cache_size(path: str, size: int) -> None:
    decision_cache_size.set(size, path=path)


def set_fingerprint_memo(path: str, hits: int, misses: int) -> None:
    fingerprint_memo_total.set_total(hits, path=path, outcome="hit")
    fingerprint_memo_total.set_total(misses, path=path, outcome="miss")


def set_cache_hit_ratio(path: str, ratio: float) -> None:
    decision_cache_hit_ratio.set(round(ratio, 6), path=path)


def record_batch_occupancy(path: str, n: int) -> None:
    batch_occupancy.observe(n, path=path)


def record_batch_claim(path: str, held: bool) -> None:
    batch_claims_total.inc(path=path, held="yes" if held else "no")


def record_batch_linger(path: str) -> None:
    batch_lingers_total.inc(path=path)


def record_pipeline_stall(path: str, stage: str, seconds: float) -> None:
    if seconds > 0:
        pipeline_stall_seconds_total.inc(seconds, path=path, stage=stage)


def record_pipeline_stage(path: str, stage: str, seconds: float) -> None:
    if seconds >= 0:
        pipeline_stage_seconds.observe(seconds, path=path, stage=stage)


def record_request_phases(path: str, names, stamps) -> None:
    """One request's consecutive phases (obs.trace RequestPhases.stamps:
    names, and one boundary stamp more) into the phase ledger, under one
    lock."""
    request_phase_seconds.observe_steps(path, names, stamps)


def record_http_read(path: str, how: str) -> None:
    http_reads_total.inc(path=path, how=how)


def record_admission_latency(decision: str, latency_s: float) -> None:
    admission_request_latency.observe(latency_s, decision=decision)


def record_request_body_bytes(path: str, n: int) -> None:
    request_body_bytes.observe(path, n)


def record_encode_extras(path: str, extras: int, rows: int) -> None:
    if rows:
        encode_extras.observe(path, extras, rows)


def record_encode_ancestors(path: str, where: str, n: int) -> None:
    if n:
        encode_ancestors_total.inc(n, path=path, where=where)


def record_flagged_bits(path: str, by: str, n: int) -> None:
    if n:
        flagged_bits_total.inc(n, path=path, by=by)


def record_launch_io(
    path: str, uploads: int, upload_bytes: int, readback_bytes: int
) -> None:
    """One batch's launches: host arrays sent up, their bytes, and the
    bytes started home."""
    key = (("path", path),)
    launch_uploads_total.add(key, uploads)
    launch_upload_bytes_total.add(key, upload_bytes)
    launch_readback_bytes_total.add(key, readback_bytes)


def record_long_device_wait(path: str, profiler: str) -> None:
    long_device_waits_total.inc(path=path, profiler=profiler)


def record_interpreter_wait(late_s: float, watched_s: float) -> None:
    interpreter_wait_seconds.observe(late_s)
    process_watch_seconds_total.inc(watched_s)


def record_process_stall(cause: str, seconds: float) -> None:
    process_stalls_total.inc(cause=cause)
    process_stall_seconds_total.inc(seconds, cause=cause)


def record_log_write(records: int) -> None:
    log_records_total.inc(records, outcome="written")
    log_writes_total.inc()


def record_log_dropped() -> None:
    log_records_total.inc(outcome="dropped")


def record_trace_kept(path: str, reason: str) -> None:
    trace_kept_total.inc(path=path, reason=reason)


def record_audit_record(path: str) -> None:
    audit_records_total.inc(path=path)


def record_audit_rotation() -> None:
    audit_rotations_total.inc()


def set_slo_burn_rate(path: str, slo: str, window: str, rate: float) -> None:
    slo_burn_rate.set(round(rate, 4), path=path, slo=slo, window=window)


def set_slo_target(path: str, slo: str, value: float) -> None:
    slo_target.set(value, path=path, slo=slo)


def set_engine_warmup_seconds(engine: str, seconds: float) -> None:
    engine_warmup_seconds.set(round(seconds, 6), engine=engine)


def observe_compile_seconds(phase: str, scope: str, seconds: float) -> None:
    compile_seconds.observe(seconds, phase=phase, scope=scope)


def set_shard_state(engine: str, shards: int, dirty: int, pruned: int) -> None:
    policy_shards.set(shards, engine=engine)
    dirty_shards.set(dirty, engine=engine)
    pruned_policies.set(pruned, engine=engine)


def set_native_encode_threads(n: int) -> None:
    native_encode_threads.set(n)


def record_shadow_evaluation(path: str) -> None:
    shadow_evaluations_total.inc(path=path)


def record_shadow_diff(kind: str) -> None:
    shadow_diffs_total.inc(kind=kind)


def record_shadow_shed(path: str) -> None:
    shadow_shed_total.inc(path=path)


def record_explain_request(path: str) -> None:
    explain_requests_total.inc(path=path)


def record_explain_compiles(n: int) -> None:
    if n:
        explain_compiles_total.inc(n)


def set_rollout_generation(generation: int) -> None:
    rollout_generation.set(generation)


def set_fastpath_lowerable(tier: int, count: int) -> None:
    policy_fastpath_lowerable.set(count, tier=str(tier))


def record_analysis_findings(kind: str, n: int) -> None:
    if n:
        policy_analysis_findings_total.inc(n, kind=kind)


def record_analysis_sweep(mode: str, requests: int, exhaustive: bool,
                          seconds: float) -> None:
    analysis_sweep_seconds.set(seconds, mode=mode)
    analysis_universe_requests.set(
        requests, mode=mode, exhaustive="1" if exhaustive else "0"
    )


def record_analysis_oracle_disagreements(n: int) -> None:
    if n:
        analysis_oracle_disagreements_total.inc(n)


def record_semdiff_flips(tenant: str, kind: str, n: int) -> None:
    if n:
        analysis_semdiff_flips_total.inc(
            n, tenant=_tenant_label_for(tenant), kind=kind
        )


def record_worker_death(component: str, replica: str = "") -> None:
    worker_deaths_total.inc(component=component, replica=replica)


def record_supervisor_restart(component: str, replica: str = "") -> None:
    supervisor_restarts_total.inc(component=component, replica=replica)


def set_fleet_replica_state(fleet: str, replica: str, code: int) -> None:
    fleet_replica_state.set(code, fleet=fleet, replica=replica)


def record_fleet_routed(fleet: str, replica: str) -> None:
    fleet_routed_total.inc(fleet=fleet, replica=replica)


def record_fleet_spillover(fleet: str) -> None:
    fleet_spillover_total.inc(fleet=fleet)


def record_fleet_hedge(fleet: str) -> None:
    fleet_hedges_total.inc(fleet=fleet)


def record_fleet_hedge_win(fleet: str, winner: str) -> None:
    fleet_hedge_wins_total.inc(fleet=fleet, winner=winner)


def set_fanout_worker_state(fanout: str, worker: str, alive: int) -> None:
    fanout_worker_state.set(alive, fanout=fanout, worker=worker)


def record_fanout_routed(fanout: str, worker: str) -> None:
    fanout_routed_total.inc(fanout=fanout, worker=worker)


def record_fanout_reroute(fanout: str) -> None:
    fanout_reroutes_total.inc(fanout=fanout)


def record_fanout_restart(fanout: str) -> None:
    fanout_worker_restarts_total.inc(fanout=fanout)


def record_peer_cache(path: str, event: str, n: int = 1) -> None:
    peer_cache_events_total.inc(n, path=path, event=event)


def record_fleet_promotion(result: str) -> None:
    fleet_promotions_total.inc(result=result)


def record_device_rebuild() -> None:
    device_rebuilds_total.inc()


def set_quarantined_objects(n: int) -> None:
    quarantined_objects.set(n)


def record_chaos_injection(seam: str, kind: str) -> None:
    chaos_injections_total.inc(seam=seam, kind=kind)


# pod identity (cedar_tpu/pod): which process of the multi-host engine
# this is. None outside a pod; obs/trace.py and obs/audit.py stamp it on
# root spans and audit lines next to the fanout `worker` label so one
# request is attributable to a host even after log aggregation.
_pod_process: Optional[int] = None


def set_pod_process(process_id: int) -> None:
    global _pod_process
    _pod_process = int(process_id)


def pod_process() -> Optional[int]:
    return _pod_process


def set_pod_hosts(n: int) -> None:
    pod_hosts.set(n)


def record_pod_reupload(host: str, n: int = 1) -> None:
    pod_partition_reuploads_total.inc(n, host=host)
