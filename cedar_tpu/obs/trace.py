"""Request tracing: monotonic-clock span trees over the serving pipeline.

The serving path spans cache → single-flight → fleet router → pipelined
batcher (encode/dispatch/decode) → breaker/interpreter fallback; aggregate
counters say *that* it was slow, never *where one request* spent its
budget. This module is the zero-dependency recorder behind that question
(docs/observability.md):

  * ``Span``/``Trace`` — monotonic-clock spans with a bounded attribute
    set, parented into one tree per request. The request thread builds the
    tree; batch-level stages (engine/batcher.py) contribute their windows
    retroactively from the timestamps they stamp per batch anyway, so the
    worker loops never run tracing code.
  * W3C ``traceparent`` ingestion: the apiserver's trace id (when present)
    becomes the request's trace id AND its logged ``requestId``, echoed in
    the ``X-Cedar-Trace-Id`` response header — one id joins the apiserver
    audit log, our serving log, the decision audit log, and /debug/traces.
  * ``Tracer`` — head-samples at a configurable rate and TAIL-KEEPS
    unsampled requests that turn out slow (> the tail latency budget),
    errored, or fallback-served, into a bounded in-memory ring served at
    ``/debug/traces`` and (optionally) appended as JSONL to a trace log
    that ``cedar-trace`` reads offline.

  * ``RequestPhases`` — one served request's boundary stamps from the
    request line to the flushed reply. ``stamps()`` lines them up as the
    consecutive phases of docs/observability.md ("Request phases") ONCE;
    the same tuple feeds ``cedar_request_phase_seconds`` and the trace's
    ``http.*`` / ``batch.*`` spans, so the two cannot disagree.
  * ``batch_stage`` / ``sub_stage`` / ``profiler_scope`` — the one helper
    the worker loops use per stage: it stamps ``time.monotonic()`` into
    the batch's stage record AND, while a ``jax.profiler`` session runs,
    wraps the stage in a ``TraceAnnotation`` so the program's stages land
    on the profiler's clock beside the runtime's own events.

Pay-for-use contract: with no tracer wired, the serving path's only cost
is a thread-local read per annotation site; with a tracer armed but the
request unsampled, the cost is the span bookkeeping (no device work — the
recorder never launches anything, differential- and bench-gated like the
chaos and explain planes).
"""

from __future__ import annotations

import json
import logging
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Optional, Tuple

log = logging.getLogger(__name__)


def _process_worker_id() -> str:
    """This process's fanout worker id (set via the CLI --worker-id /
    CEDAR_WORKER_ID, held by server.metrics as the one source of truth
    for the metrics `worker` label too). Empty on single-process
    deployments — records then stay byte-identical to pre-tier output."""
    try:
        from ..server.metrics import worker_label

        return worker_label()
    except Exception:  # noqa: BLE001 — identity is best-effort context
        return ""


def _process_pod_id():
    """This process's pod process index (cedar_tpu/pod; set by PodTier /
    the CLI --pod-process-id). None off-pod — the field is then omitted
    entirely, like the `worker` label."""
    try:
        from ..server.metrics import pod_process

        return pod_process()
    except Exception:  # noqa: BLE001 — identity is best-effort context
        return None

# bounded per-span attribute set: traces are a debugging surface, not a
# logging pipeline — unbounded attributes would turn the ring into one
MAX_SPAN_ATTRS = 16
MAX_ATTR_CHARS = 200


# ids come from a generator of this module's own, seeded once from the
# OS: uuid4() asks the OS for every id, a call that gives the interpreter
# up — two of them a request cost a saturated server 1.4 ms of every
# request's cycle (PERF.md §5, `pre`). W3C ids have to be random, not
# secret, and never all zero.
_ids = random.Random(os.urandom(32))
# a forked child must not repeat its parent's ids
os.register_at_fork(after_in_child=lambda: _ids.seed(os.urandom(32)))


def new_trace_id() -> str:
    """Fresh 32-hex-char W3C trace id."""
    return f"{_ids.getrandbits(128) or 1:032x}"


def new_span_id() -> str:
    """Fresh 16-hex-char W3C span id."""
    return f"{_ids.getrandbits(64) or 1:016x}"


def parse_traceparent(header: Optional[str]) -> Optional[Tuple[str, str]]:
    """W3C ``traceparent`` → ``(trace_id, parent_span_id)``; None when the
    header is absent or malformed (version-format check only — future
    versions with extra fields still yield their first four). All-zero
    trace/span ids are invalid per spec."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id = parts[0], parts[1], parts[2]
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(version, 16), int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str, sampled: bool) -> str:
    return f"00-{trace_id}-{span_id}-{'01' if sampled else '00'}"


def ingest_request_id(traceparent: Optional[str]) -> Tuple[str, Optional[str]]:
    """(request id, upstream parent span id) for one HTTP request: the
    ingested traceparent's trace id when present, a fresh trace id
    otherwise — the ONE id the serving log, response header, audit log,
    and trace ring all share (server/http.py)."""
    parsed = parse_traceparent(traceparent)
    if parsed is None:
        return new_trace_id(), None
    return parsed


class Span:
    __slots__ = ("name", "span_id", "parent_id", "t0", "t1", "attrs")

    def __init__(self, name: str, span_id: str, parent_id: Optional[str]):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = time.monotonic()
        self.t1: Optional[float] = None
        self.attrs: dict = {}

    def set_attr(self, key: str, value) -> None:
        if len(self.attrs) >= MAX_SPAN_ATTRS and key not in self.attrs:
            return
        if isinstance(value, str) and len(value) > MAX_ATTR_CHARS:
            value = value[:MAX_ATTR_CHARS]
        self.attrs[key] = value

    def end(self) -> None:
        if self.t1 is None:
            self.t1 = time.monotonic()


class _SpanCtx:
    """Context manager binding one span into the trace's open-span stack."""

    __slots__ = ("_trace", "span")

    def __init__(self, trace: "Trace", span: Span):
        self._trace = trace
        self.span = span

    def __enter__(self) -> Span:
        return self.span

    def __exit__(self, exc_type, exc, tb) -> None:
        self._trace.end_span(self.span)


class _NullCtx:
    """No-trace stand-in: span() sites cost one thread-local read plus
    this shared context manager when tracing is disarmed."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


_NULL_CTX = _NullCtx()


class Trace:
    """One request's span tree. Built by the request thread (plus
    retroactive batch-stage windows via ``add_span``); not a general
    concurrent structure — exactly the serving path's shape."""

    __slots__ = (
        "trace_id",
        "path",
        "root",
        "_spans",
        "sampled",
        "parent_span_id",
        "started_unix",
        "decision",
        "error",
        "fallback",
        "_stack",
        "_n",
        "_windows",
    )

    def __init__(
        self,
        path: str,
        trace_id: Optional[str] = None,
        parent_span_id: Optional[str] = None,
        root_span_id: Optional[str] = None,
        sampled: bool = False,
    ):
        self.trace_id = trace_id or new_trace_id()
        self.path = path
        self.parent_span_id = parent_span_id
        self.started_unix = time.time()
        self.sampled = sampled
        self.decision: Optional[str] = None
        self.error = False
        # fallback-served (breaker open / fleet unavailable / device
        # degradation): a tail-keep trigger independent of latency
        self.fallback = False
        self.root = Span(path, root_span_id or new_span_id(), parent_span_id)
        self._spans = [self.root]
        self._stack = [self.root]
        self._n = 0
        # (phase names, boundary stamps, batch stage record) added in bulk;
        # they become Span objects only when somebody reads .spans (a kept
        # trace)
        self._windows: list = []

    # ------------------------------------------------------------- recording

    def _next_id(self) -> str:
        self._n += 1
        return f"{self._n:x}"

    @property
    def spans(self) -> list:
        """Every span of the tree, the deferred windows materialized."""
        if self._windows:
            deferred, self._windows = self._windows, []
            for names, stamps, times in deferred:
                if isinstance(names, str):  # defer_span: name, stamps, attrs
                    rows = [(names, stamps[0], stamps[1], times)]
                else:
                    rows = span_windows(windows_of(names, stamps), times)
                for name, t0, t1, attrs in rows:
                    span = Span(name, self._next_id(), self.root.span_id)
                    span.t0, span.t1 = t0, t1
                    if attrs:
                        for k, v in attrs.items():
                            span.set_attr(k, v)
                    self._spans.append(span)
        return self._spans

    def add_windows(self, names, stamps, times=None) -> None:
        """Add consecutive completed phases (``pipeline_stamps``,
        ``RequestPhases.stamps``: names, and one boundary stamp more) as
        children of the root, under their span names; ``times`` is the
        batch's stage record, whose sub-stage seconds hang on the dispatch
        and decode spans. Nothing is built until somebody reads
        ``.spans``: an unsampled request that is not tail-kept never pays
        for its spans."""
        self._windows.append((names, stamps, times))

    def begin_span(self, name: str) -> Span:
        span = Span(name, self._next_id(), self._stack[-1].span_id)
        self._spans.append(span)
        self._stack.append(span)
        return span

    def end_span(self, span: Span) -> None:
        span.end()
        if self._stack and self._stack[-1] is span:
            self._stack.pop()

    def span(self, name: str) -> _SpanCtx:
        return _SpanCtx(self, self.begin_span(name))

    def defer_span(self, name: str, t0: float, t1: float, **attrs) -> None:
        """A completed child of the root from two stamps the caller took,
        built only if somebody reads ``.spans`` (a kept trace): what a
        span costs on the request path of every request is two clock
        reads and one list append."""
        self._windows.append((name, (t0, t1), attrs))

    def add_span(
        self, name: str, t0: float, t1: float, **attrs
    ) -> Optional[Span]:
        """Retroactively add a completed span from externally captured
        monotonic timestamps (the batcher's per-batch stage stamps). The
        span parents onto the innermost open span of the calling thread's
        tree — for the serving path that is the request's evaluation
        span."""
        if t0 is None or t1 is None:
            return None
        span = Span(name, self._next_id(), self._stack[-1].span_id)
        span.t0, span.t1 = t0, t1
        for k, v in attrs.items():
            span.set_attr(k, v)
        self._spans.append(span)
        return span

    def event(self, name: str, **attrs) -> None:
        """Zero-duration marker span (fleet spillover, hedge fire,
        deadline expiry)."""
        now = time.monotonic()
        self.add_span(name, now, now, **attrs)

    def finish(
        self,
        decision: Optional[str] = None,
        error: bool = False,
    ) -> float:
        """Close the root span; returns the trace's duration (seconds)."""
        self.decision = decision
        self.error = bool(error) or self.error
        while self._stack:
            self._stack.pop().end()
        return self.root.t1 - self.root.t0

    @property
    def duration_s(self) -> float:
        if self.root.t1 is None:
            return 0.0
        return self.root.t1 - self.root.t0

    # ------------------------------------------------------------- rendering

    def to_dict(self, kept: str = "") -> dict:
        t0 = self.root.t0
        spans = []
        for s in self.spans:
            end = s.t1 if s.t1 is not None else t0
            spans.append(
                {
                    "name": s.name,
                    "spanId": s.span_id,
                    "parent": s.parent_id,
                    "start_us": round((s.t0 - t0) * 1e6, 1),
                    "duration_us": round(max(0.0, end - s.t0) * 1e6, 1),
                    "attrs": s.attrs,
                }
            )
        doc = {
            "traceId": self.trace_id,
            "path": self.path,
            "start_unix": round(self.started_unix, 6),
            "duration_us": round(self.duration_s * 1e6, 1),
            "decision": self.decision,
            "error": self.error,
            "fallback": self.fallback,
            "sampled": self.sampled,
            "kept": kept,
            "upstreamParent": self.parent_span_id or "",
            "spans": spans,
        }
        w = _process_worker_id()
        if w:
            # multi-process fanout tier: the serving worker's id, so a
            # trace pulled from any worker's ring joins the tier-wide
            # metrics scrape and audit records instead of colliding
            doc["worker"] = w
        p = _process_pod_id()
        if p is not None:
            # pod tier: which host of the one logical engine served this
            # request (the collective ran everywhere; the REQUEST lived
            # here) — joins cedar_pod_partition_reuploads_total{host}
            doc["podProcess"] = p
        return doc


# ------------------------------------------------------- thread-local current

_current = threading.local()


def current_trace() -> Optional[Trace]:
    """The calling thread's active trace, or None — the ONE check every
    annotation site pays when tracing is disarmed."""
    return getattr(_current, "trace", None)


def set_current(trace: Optional[Trace]) -> None:
    _current.trace = trace


def span(name: str):
    """Context manager opening ``name`` on the calling thread's active
    trace; a shared no-op when there is none (disarmed cost: one
    thread-local read)."""
    tr = current_trace()
    if tr is None:
        return _NULL_CTX
    return tr.span(name)


def annotate(fn) -> None:
    """Run ``fn(trace)`` against the active trace, if any — for sites
    that want more than one span call without re-reading the local."""
    tr = current_trace()
    if tr is not None:
        fn(tr)


# ------------------------------------------------------------ request phases

# phase -> span name: the served request's tree says the same thing as the
# cedar_request_phase_seconds ledger, under the names traces already used
_SPAN_NAMES = {
    "read": "http.read",
    "pre": "http.pre",
    "parse": "http.parse",
    "respond": "http.respond",
    "write": "http.write",
    "queue": "batch.queue_wait",
    "encode_wait": "batch.encode_wait",
    "encode": "batch.encode",
    "dispatch_wait": "batch.dispatch_wait",
    "dispatch": "batch.dispatch",
    "device_wait": "batch.device_wait",
    "decode": "batch.decode",
    "evaluate_wait": "batch.evaluate_wait",
    "evaluate": "batch.evaluate",
    "wake": "batch.wake",
}

_PIPELINED = (
    "queue", "encode_wait", "encode", "dispatch_wait", "dispatch",
    "device_wait", "decode", "wake",
)
_SERIAL = ("queue", "evaluate_wait", "evaluate", "wake")
# a request's phase names by shape: (it is its connection's first, so has
# no `between`; the kind of batcher that answered it, if one did)
_HEAD = ("read", "pre", "parse")
_TAIL = ("respond", "write")
_PHASES = {
    (first, kind): (() if first else ("between",)) + _HEAD + mid + _TAIL
    for first in (True, False)
    for kind, mid in (("none", ()), ("pipelined", _PIPELINED), ("serial", _SERIAL))
}


def pipeline_stamps(t_enq: float, times, t_wake: float):
    """One slot's way through its batch as ``(phase names, boundary
    stamps)``, one stamp more than names: from its own enqueue to its
    waiter running again — the batch's shared stage stamps
    (engine/batcher.py ``_StageTimes``), the ones
    ``cedar_pipeline_stage_seconds`` observes. None while a stamp is
    missing (a batch that failed before its last stage)."""
    if times.eval0 is not None:
        names = _SERIAL
        stamps = (t_enq, times.claimed, times.eval0, times.eval1, t_wake)
    else:
        names = _PIPELINED
        stamps = (
            t_enq, times.claimed, times.encode0, times.encode1,
            times.dispatch0, times.dispatch1, times.decode0, times.decode1,
            t_wake,
        )
    if None in stamps:
        return None
    return names, stamps


def windows_of(names, stamps) -> list:
    """``[(phase, t0, t1)]`` from phase names and their boundary stamps."""
    return [(n, stamps[i], stamps[i + 1]) for i, n in enumerate(names)]


def span_windows(windows, times=None) -> list:
    """Phase windows -> ``(span name, t0, t1, attrs-or-None)`` rows.
    ``times`` (the batch's stage record) hangs the sub-stage seconds of
    dispatch and decode on their spans."""
    sub = getattr(times, "sub", None) or {}
    out = []
    for phase, t0, t1 in windows:
        name = _SPAN_NAMES.get(phase)
        if name is None:
            continue  # `between` precedes the root span: a root attribute
        attrs = None
        if sub and phase in ("dispatch", "decode"):
            attrs = {
                k.split(".", 1)[1] + "_us": round(v * 1e6, 1)
                for k, v in sub.items()
                if k.startswith(phase + ".")
            }
            if phase == "dispatch":
                # the batch's number on the profiler's clock: joins a kept
                # trace to the profile's cedar.* events of its batch
                attrs["seq"] = times.seq
        elif phase == "encode" and getattr(times, "extras_max", None) is not None:
            attrs = {
                "extras_max": times.extras_max,
                "groups": times.groups,
                "known_groups": times.known_groups,
            }
        out.append((name, t0, t1, attrs))
    return out


class RequestPhases:
    """Boundary stamps of one request on a served connection, request line
    to flushed reply, each taken once with ``time.monotonic()`` where the
    table in docs/observability.md says. The HTTP handler owns it; the
    layers below reach it through ``current_phases()``. Nothing is derived
    while the request is served: ``stamps()`` lines the boundaries up once
    the reply is out, and both the phase ledger and the trace's spans are
    cut from that one tuple."""

    __slots__ = (
        "path", "t_prev", "t_line", "t_body", "t_start", "t_eval",
        "slot", "t_wake", "t_stop", "t_flush",
        "trace", "decision", "error",
    )

    def __init__(self, t_prev: Optional[float]):
        self.path = ""
        self.t_prev = t_prev  # previous reply flushed on this connection
        self.t_line = time.monotonic()  # request line in hand
        self.t_body = None  # body read
        self.t_start = None  # the handler's request timer starts
        self.t_eval = None  # verdict in hand (requests with no batch slot)
        self.slot = None  # the batcher slot that answered …
        self.t_wake = None  # … and when its waiter (this thread) ran again
        self.t_stop = None  # the handler's request timer stops
        self.t_flush = None  # reply flushed
        # the request's trace and outcome, finished once the reply is out
        self.trace = None
        self.decision = None
        self.error = False

    @property
    def times(self):
        """The stage record of the batch that answered, or None."""
        return self.slot.times if self.slot is not None else None

    def stamps(self):
        """``(phase names, boundary stamps)``: consecutive phases, one
        stamp more than names; on a keep-alive connection they run from
        one reply flushed to the next."""
        first = self.t_prev is None
        head = (self.t_line, self.t_body, self.t_start)
        if not first:
            head = (self.t_prev,) + head
        pipe = None
        if self.slot is not None and self.slot.times is not None:
            pipe = pipeline_stamps(self.slot.t_enq, self.slot.times, self.t_wake)
        if pipe is None:
            t_run = self.t_eval if self.t_eval is not None else self.t_stop
            return _PHASES[first, "none"], head + (t_run, self.t_stop, self.t_flush)
        names, mid = pipe
        if mid[0] < self.t_start:
            # a coalesced follower shares a slot enqueued before its own
            # timer started: its queue wait starts with its timer
            mid = (self.t_start, max(mid[1], self.t_start)) + mid[2:]
        kind = "serial" if names is _SERIAL else "pipelined"
        return _PHASES[first, kind], head + mid + (self.t_stop, self.t_flush)

    def windows(self) -> list:
        """The request as consecutive ``(phase, t0, t1)`` windows."""
        return windows_of(*self.stamps())


def current_phases() -> Optional[RequestPhases]:
    """The calling thread's request phase record, or None (a request that
    did not come over the webhook's HTTP handler, or no tracer)."""
    return getattr(_current, "phases", None)


def set_phases(phases: Optional[RequestPhases]) -> None:
    _current.phases = phases


def note_batch_result(slot) -> None:
    """The request thread is running again with its slot's result: stamp
    the wake and leave the slot with whoever reads its batch's stamps —
    the request's phase record when the HTTP handler keeps one (it cuts
    the phases once the reply is out), else the active trace."""
    phases = current_phases()
    if phases is not None:
        phases.t_wake = time.monotonic()
        phases.slot = slot
        return
    trace = current_trace()
    if trace is not None and slot.times is not None:
        pipe = pipeline_stamps(slot.t_enq, slot.times, time.monotonic())
        if pipe is not None:
            trace.add_windows(pipe[0], pipe[1], slot.times)


# ---------------------------------------------- stages on the profiler's clock

_ANNOTATION = None  # jax.profiler.TraceAnnotation, False when it cannot load
_stage_local = threading.local()


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` once some other module has imported
    jax — this package never pulls jax into a process that serves from the
    interpreter alone."""
    global _ANNOTATION
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # noqa: BLE001 — a jax without a profiler
            log.exception("jax.profiler.TraceAnnotation unavailable")
            _ANNOTATION = False
        else:
            _ANNOTATION = TraceAnnotation
    return _ANNOTATION or None


def _annotation(name: str, times):
    """``TraceAnnotation(name, batch=rows, seq=n)`` while a profiler
    session runs, else None (one atomic load). ``seq`` is the batch's
    number (engine/batcher.py ``_StageTimes``): the stages of one batch
    on the collector's, the dispatch and the decode thread carry the same
    one, and a batch's several launches carry ``chunk`` besides — each
    annotation begins and ends on its own thread, and the round trip is
    joined offline by ``seq`` (tools/xplane_stages.py ``launch``). With
    no batch record bound (the warm ladder, bench.py, tests) ``batch`` is
    0 and there is no ``seq``."""
    cls = _ANNOTATION or _annotation_cls()
    if cls is not None and cls.is_enabled():
        if times is None:
            return cls(name, batch=0)
        if name == "cedar.dispatch.launch":
            return cls(name, batch=times.rows, seq=times.seq, chunk=times.launches)
        return cls(name, batch=times.rows, seq=times.seq)
    return None


def profiler_on() -> bool:
    """Whether a profiler session is open now; False where jax was never
    imported (the stall recorder's and the long-wait counter's
    ``profiler`` word)."""
    cls = _ANNOTATION or _annotation_cls()
    return cls is not None and cls.is_enabled()


def profiler_scope(name: str, **kwargs):
    """A context manager that puts ``name`` (and ``kwargs``, as the
    event's stats) on a running profiler's clock
    (``jax.profiler.TraceAnnotation``); the shared no-op with no session."""
    cls = _ANNOTATION or _annotation_cls()
    if cls is not None and cls.is_enabled():
        return cls(name, **kwargs)
    return _NULL_CTX


# what a stage's time counts as while no sub_stage is open inside it
_STAGE_REST = {"dispatch": "dispatch.stage", "decode": "decode.host"}


class batch_stage:
    """``with batch_stage(times, "dispatch", rows):`` — stamp the stage's
    window into the batch's stage record (``times.dispatch0`` / ``1``),
    bind the record to this worker thread so the ``sub_stage`` sites below
    it find it, and put the stage on the profiler's clock as
    ``cedar.batch.dispatch``. The one thing a worker loop does per stage.

    The stage's seconds are split, by stamps, among its sub-stages
    (``times.sub``): whatever runs outside every ``sub_stage`` counts as
    the stage's own rest — ``dispatch.stage`` (host staging and the other
    Python of a dispatch), ``decode.host`` — so the parts sum to the
    window."""

    __slots__ = ("_times", "_name", "_scope")

    def __init__(self, times, name: str, rows: int):
        self._times = times
        self._name = name
        times.rows = rows

    def __enter__(self):
        times = self._times
        _stage_local.times = times
        self._scope = _annotation("cedar.batch." + self._name, times)
        if self._scope is not None:
            self._scope.__enter__()
        times.part = _STAGE_REST.get(self._name)
        times.part_t0 = now = time.monotonic()
        setattr(times, self._name + "0", now)
        return times

    def __exit__(self, exc_type, exc, tb) -> None:
        times = self._times
        now = time.monotonic()
        setattr(times, self._name + "1", now)
        if times.part is not None:
            times.sub[times.part] = (
                times.sub.get(times.part, 0.0) + now - times.part_t0
            )
            times.part = None
        if self._scope is not None:
            self._scope.__exit__(exc_type, exc, tb)
        _stage_local.times = None


class _SubStage:
    __slots__ = ("_name", "_times", "_scope", "_outer")

    def __init__(self, name: str, times, scope):
        self._name = name
        self._times = times
        self._scope = scope  # a TraceAnnotation, or None with no session

    def __enter__(self):
        if self._scope is not None:
            self._scope.__enter__()
        times = self._times
        if times is not None:
            # close the enclosing part's running segment, open this one
            now = time.monotonic()
            outer = self._outer = times.part
            if outer is not None:
                times.sub[outer] = times.sub.get(outer, 0.0) + now - times.part_t0
            times.part, times.part_t0 = self._name, now

    def __exit__(self, exc_type, exc, tb) -> None:
        times = self._times
        if times is not None:
            now = time.monotonic()
            times.sub[self._name] = (
                times.sub.get(self._name, 0.0) + now - times.part_t0
            )
            times.part, times.part_t0 = self._outer, now
        if self._scope is not None:
            self._scope.__exit__(exc_type, exc, tb)


def note_encode_extras(extras_max: int, groups: int, known_groups: int) -> None:
    """The widest encoded row's extras, the most groups a row's principal
    carries and the most of them that some policy names, onto the batch
    record bound to this worker thread (a stage may encode several chunks:
    the largest of each stays); nothing outside a batcher's encode stage."""
    times = getattr(_stage_local, "times", None)
    if times is None:
        return
    if times.extras_max is None or extras_max > times.extras_max:
        times.extras_max = extras_max
    times.groups = max(times.groups, groups)
    times.known_groups = max(times.known_groups, known_groups)


def note_launch(uploads: int, upload_bytes: int) -> None:
    """One launch's host arguments — each goes up on its own — and their
    bytes (engine/aot.py ``dispatch``), onto the batch record bound to
    this worker thread; a batch of several chunks adds up. Nothing
    outside a batcher's dispatch stage."""
    times = getattr(_stage_local, "times", None)
    if times is None:
        return
    times.launches += 1
    times.uploads += uploads
    times.upload_bytes += upload_bytes


def note_readback(nbytes: int) -> None:
    """The bytes of a device result whose copy home a launch started
    (engine/evaluator.py: since PR 37 one buffer a launch), onto the
    bound batch record."""
    times = getattr(_stage_local, "times", None)
    if times is not None:
        times.readback_bytes += nbytes


def sub_stage(name: str):
    """``with sub_stage("dispatch.launch"):`` inside a batch stage — the
    enclosed seconds go to the bound batch record's ``sub[name]`` instead
    of the enclosing part's (a stage may run once per chunk: they add
    up), and ``cedar.<name>`` is annotated while a profiler session runs.
    Outside a batcher's worker threads (the warm ladder, bench.py, tests)
    nothing is bound and, with no session, the cost is a thread-local read
    and an atomic load."""
    times = getattr(_stage_local, "times", None)
    scope = _annotation("cedar." + name, times)
    if times is None and scope is None:
        return _NULL_CTX
    return _SubStage(name, times, scope)


class Tracer:
    """Head-sampling + tail-keep trace collector (module docstring).

    ``sample_rate`` ∈ [0, 1] head-samples; independent of that, finished
    traces that were slow (duration > ``tail_latency_s``), errored, or
    fallback-served are kept too — the requests an operator actually goes
    looking for are exactly the ones head sampling misses. Kept traces
    land in a bounded ring (``/debug/traces``) and, when ``log_file`` is
    set, append as one JSON line each (``cedar-trace --log``)."""

    def __init__(
        self,
        sample_rate: float = 0.0,
        ring_capacity: int = 256,
        tail_latency_s: Optional[float] = 1.0,
        log_file: Optional[str] = None,
        rng: Optional[random.Random] = None,
    ):
        self.sample_rate = min(1.0, max(0.0, float(sample_rate)))
        self.tail_latency_s = tail_latency_s
        self.log_file = log_file
        self._rng = rng or random.Random()
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(ring_capacity)))
        self._log_fh = None
        self._log_lock = threading.Lock()
        self.kept = 0
        self.finished = 0

    # -------------------------------------------------------------- lifecycle

    def head_sample(self) -> bool:
        """Draw one head-sampling decision. Exposed so the HTTP layer can
        draw it BEFORE the handler runs and put the honest recorded flag
        into the response ``traceparent`` (tail-keep recording is not
        knowable at response time — the flag reflects head sampling)."""
        return self.sample_rate >= 1.0 or (
            self.sample_rate > 0.0 and self._rng.random() < self.sample_rate
        )

    def begin(
        self,
        path: str,
        trace_id: Optional[str] = None,
        parent_span_id: Optional[str] = None,
        root_span_id: Optional[str] = None,
        sampled: Optional[bool] = None,
    ) -> Trace:
        if sampled is None:
            sampled = self.head_sample()
        return Trace(
            path,
            trace_id=trace_id,
            parent_span_id=parent_span_id,
            root_span_id=root_span_id,
            sampled=sampled,
        )

    def finish(
        self,
        trace: Trace,
        decision: Optional[str] = None,
        error: bool = False,
    ) -> Optional[str]:
        """Close the trace and apply the keep policy; returns the keep
        reason (``sampled`` / ``slow`` / ``error`` / ``fallback``) or None
        when the trace is dropped."""
        duration = trace.finish(decision=decision, error=error)
        with self._lock:
            self.finished += 1
        reason = None
        if trace.sampled:
            reason = "sampled"
        elif trace.error:
            reason = "error"
        elif trace.fallback:
            reason = "fallback"
        elif (
            self.tail_latency_s is not None
            and self.tail_latency_s > 0
            and duration > self.tail_latency_s
        ):
            reason = "slow"
        if reason is None:
            return None
        doc = trace.to_dict(kept=reason)
        with self._lock:
            self._ring.append(doc)
            self.kept += 1
        self._export(doc)
        try:
            from ..server.metrics import record_trace_kept

            record_trace_kept(trace.path, reason)
        except Exception:  # noqa: BLE001 — metrics must never break tracing
            pass
        return reason

    def _export(self, doc: dict) -> None:
        if self.log_file is None:
            return
        try:
            with self._log_lock:
                if self._log_fh is None:
                    self._log_fh = open(self.log_file, "a", buffering=1)
                self._log_fh.write(
                    json.dumps(doc, separators=(",", ":")) + "\n"
                )
        except OSError:
            log.exception("trace log append failed; disabling export")
            self.log_file = None

    def close(self) -> None:
        with self._log_lock:
            if self._log_fh is not None:
                try:
                    self._log_fh.close()
                finally:
                    self._log_fh = None

    # ---------------------------------------------------------------- lookup

    def list_traces(self, limit: int = 64) -> list:
        """Newest-first trace summaries for /debug/traces."""
        with self._lock:
            docs = list(self._ring)
        out = []
        for doc in reversed(docs[-limit:] if limit else docs):
            out.append(
                {
                    "traceId": doc["traceId"],
                    "path": doc["path"],
                    "decision": doc["decision"],
                    "duration_us": doc["duration_us"],
                    "kept": doc["kept"],
                    "error": doc["error"],
                    "fallback": doc["fallback"],
                    "start_unix": doc["start_unix"],
                    "spans": len(doc["spans"]),
                }
            )
        return out

    def get(self, trace_id: str) -> Optional[dict]:
        """Full span tree by trace id (unambiguous prefixes accepted),
        newest match first."""
        if not trace_id:
            return None
        with self._lock:
            docs = list(self._ring)
        for doc in reversed(docs):
            if doc["traceId"].startswith(trace_id):
                return doc
        return None

    def stats(self) -> dict:
        with self._lock:
            return {
                "sample_rate": self.sample_rate,
                "tail_latency_ms": (
                    round(self.tail_latency_s * 1e3, 3)
                    if self.tail_latency_s
                    else None
                ),
                "ring_capacity": self._ring.maxlen,
                "ring_size": len(self._ring),
                "finished": self.finished,
                "kept": self.kept,
                "log_file": self.log_file or "",
            }


def span_tree_coverage(doc: dict) -> float:
    """Fraction of a trace's e2e duration covered by the union of its
    named child spans (interval-merged, so nested/overlapping spans never
    double-count). The acceptance bar for the instrumentation: a slow
    request's tree must account for >= 95% of where the time went."""
    total = doc.get("duration_us", 0.0)
    if total <= 0:
        return 1.0
    root_id = doc["spans"][0]["spanId"] if doc.get("spans") else None
    intervals = sorted(
        (s["start_us"], s["start_us"] + s["duration_us"])
        for s in doc.get("spans", ())
        if s["spanId"] != root_id
    )
    covered = 0.0
    cur_lo = cur_hi = None
    for lo, hi in intervals:
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                covered += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        covered += cur_hi - cur_lo
    return min(1.0, covered / total)


__all__ = [
    "MAX_SPAN_ATTRS",
    "RequestPhases",
    "Span",
    "Trace",
    "Tracer",
    "annotate",
    "batch_stage",
    "current_phases",
    "current_trace",
    "format_traceparent",
    "ingest_request_id",
    "new_span_id",
    "new_trace_id",
    "note_batch_result",
    "note_encode_extras",
    "note_launch",
    "note_readback",
    "parse_traceparent",
    "pipeline_stamps",
    "profiler_on",
    "profiler_scope",
    "set_current",
    "set_phases",
    "span",
    "span_tree_coverage",
    "span_windows",
    "sub_stage",
    "windows_of",
]
