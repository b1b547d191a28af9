"""SAR + admission fast paths: raw request bytes -> decisions, native end
to end.

Fuses the C++ encoder (cedar_tpu/native) with the device matcher: the host
never materializes Python entity objects for well-formed requests. Per
request the host work is one C++ JSON parse + a handful of hash lookups;
the device work rides the batched matmul kernel; the readback is 4 bytes.

Semantics are identical to the exact Python paths
(CedarWebhookAuthorizer.authorize / CedarAdmissionHandler.handle over the
TPU engine; the authorizer gates run inside the C++ encoder in the same
order as /root/reference internal/server/authorizer/authorizer.go:38-66).
Rows the native path cannot prove equivalent re-run through the exact
Python path:

  * parse quirks / extras overflow / unsupported admission shapes — routed
    per row by the encoder's flag column;
  * rows whose verdict word carries WORD_GATE — the scope of a policy the
    native plane cannot evaluate matched (compiler.pack packs one gate
    rule per interpreter-fallback policy and per native-opaque policy —
    one whose hard literals only the Python encoder can host-evaluate),
    so the device verdict is not authoritative; gated rows re-run batched
    through the hybrid engine path.

Both fast paths share one chunked pipeline (_RawFastPath): chunk k+1's C++
encode overlaps chunk k's in-flight device work; clean rows decode via a
per-distinct-verdict-word cache; flagged (multi/err) rows defer to one
cross-chunk bits fetch with feature-row-keyed memoization; gated rows defer
to one batched Python re-run. The subclasses contribute only the
domain-specific pieces: encoding, flag routing, per-row fallbacks, and how
a decoded payload renders into a response.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..chaos.registry import chaos_fire
from ..obs.trace import note_encode_extras, sub_stage
from ..native import (
    ANC_WHERE,
    F_ADM_ERROR,
    F_ADM_NS_SKIP,
    F_EXTRAS_OVERFLOW,
    F_OK,
    F_PARSE_ERROR,
    F_SELF_ALLOW_POLICIES,
    F_SELF_ALLOW_RBAC,
    F_SYSTEM_SKIP,
    NativeEncoder,
)
from ..server.authorizer import (
    DECISION_ALLOW,
    DECISION_DENY,
    DECISION_NO_OPINION,
    CedarWebhookAuthorizer,
    _diagnostic_to_reason,
)
from ..lang.authorize import ALLOW, DENY
from ..ops.match import WORD_ERR, WORD_GATE, WORD_MULTI
from .evaluator import (
    _BATCH_BUCKETS,
    BITS_INCALL_MAX,
    EXTRAS_WIDTHS,
    SERVING_CHUNK,
    TPUPolicyEngine,
    _round_bucket,
    _WordPacker,
)

log = logging.getLogger(__name__)


class RuleResult(tuple):
    """A (decision, reason, error) that the webhook's own rules gave
    before Cedar was asked (self-allow, system:* skip). A tuple to every
    consumer; the request handler reads ``answered_by`` off it for the
    ``by`` label of its timer (server/http.py)."""

    __slots__ = ()
    answered_by = "rule"


class InterpreterResult(tuple):
    """A (decision, reason, error) that the interpreter gave for a row the
    device plane could not answer (a gated row, an encoder fallback, a
    degraded batch)."""

    __slots__ = ()
    answered_by = "interpreter"


# (decision, reason, error) results for gate flags (authorizer.go:38-57)
_GATE_RESULTS = {
    F_SELF_ALLOW_POLICIES: RuleResult((
        DECISION_ALLOW,
        "cedar authorizer is always allowed to access policies",
        None,
    )),
    F_SELF_ALLOW_RBAC: RuleResult((
        DECISION_ALLOW,
        "cedar authorizer is always allowed to read RBAC policies",
        None,
    )),
    F_SYSTEM_SKIP: RuleResult((DECISION_NO_OPINION, "", None)),
}

# (decision, reason, error): error non-None mirrors the webhook handler's
# decode-error / evaluation-error response shapes (server/http.py)
Result = Tuple[str, str, Optional[str]]


def _packed_decode_enabled() -> bool:
    """CEDAR_TPU_PACKED_DECODE=0 restores per-chunk word readbacks — the
    operator escape hatch for the batch-wide packed D2H transfer, and the
    bench's A/B lever (bench.py --encode). Read per batch: the env lookup
    is noise next to one chunk's encode."""
    import os

    return os.environ.get("CEDAR_TPU_PACKED_DECODE", "1") != "0"


class _Snapshot(NamedTuple):
    """Immutable (encoder, compiled set, caches) tuple.

    Request threads and the batcher thread both read it with one attribute
    load, so a policy hot swap can never pair the old encoder's codes with
    the new compiled set's activation tables, and cache entries can never
    leak across swaps (each snapshot owns its cache dicts)."""

    encoder: Optional[NativeEncoder]
    cs: object  # the _CompiledSet the encoder was built on
    reason_cache: dict  # policy index -> reason JSON (guarded by GIL appends)
    # verdict word -> shared decoded payload (and feature-row bytes ->
    # flagged-row payload); verdict diversity is tiny, so decode is one
    # dict hit per row
    word_cache: dict


def _chunk_sizes(n: int, chunk: int, tail: int) -> List[int]:
    """Pipeline chunk plan for an n-row batch: full `chunk`s, then the
    remainder — split into EQUAL halves when it exceeds `tail`, so the
    final device wait (which no later encode hides) is at most a
    half-chunk. Any remainder in (tail, chunk] halves into pieces in
    (tail/2, tail], which stay above _BITS_INCALL_MAX — always the cheap
    plain plane at the warmed tail-chunk batch bucket, never a small
    piece on the unwarmed in-call bits plane."""
    sizes = []
    rem = n
    while rem > chunk:
        sizes.append(chunk)
        rem -= chunk
    # split only when BOTH halves exceed tail // 2 (== _BITS_INCALL_MAX
    # for the serving constants): a half at exactly the threshold would
    # ride the 4x-cost in-call bits plane at an unwarmed batch bucket
    if rem > tail and rem - (rem + 1) // 2 > tail // 2:
        half = (rem + 1) // 2
        sizes.extend((half, rem - half))
    elif rem:
        sizes.append(rem)
    return sizes


class _RawFastPath:
    """The shared chunked raw-bytes pipeline (see module docstring).

    Subclasses implement `_encode`, `_route_flags`, `_fallback_row`,
    `_run_gated`, `_decode_word_payload`, `_decode_bits_payload`, and
    `_emit`; everything else — snapshot management, chunk overlap, clean
    decode, deferred gated/flagged resolution, memoization — lives here
    once."""

    # chunk size for the encode/device overlap pipeline: chunk k's device
    # work proceeds while the host encodes chunk k+1 (4+ chunks in flight
    # at NB=65536 hide the device round trip; bigger chunks expose more of
    # the tail bits fetch; not measured on the current code). The
    # warm-up ladder pre-compiles this shape (evaluator.SERVING_CHUNK) so
    # post-swap batch/replay traffic never eats the trace+compile.
    _CHUNK = SERVING_CHUNK
    # the LAST chunk's device work has no later encode to hide behind: its
    # h2d + compute is an exposed serial tail. Splitting the tail into
    # smaller pieces shortens that exposed wait at negligible dispatch cost.
    # Kept above _BITS_INCALL_MAX so tail pieces stay on the cheap plain
    # plane; the warm ladder compiles this shape too.
    _TAIL_CHUNK = SERVING_CHUNK // 2
    # above this row count, skip the in-call diagnostics bitset plane
    # (want_bits): computing + compacting [B, R/32] bitsets costs ~4x the
    # plain match at large B, while flagged rows are rare (<1%) — fetching
    # their bitsets in a second fixed-shape call (match_bits_arrays) is far
    # cheaper in the throughput regime. Small batches keep the in-call
    # payload: there a second device round trip costs more than the bits
    # plane. Aliased from the evaluator so the warm-up bucket plan and
    # this routing threshold can never drift apart.
    _BITS_INCALL_MAX = BITS_INCALL_MAX
    # True when _emit returns the payload unchanged (SAR): clean rows then
    # decode via a VECTORIZED per-distinct-word scatter (~8x the per-row
    # python loop at 65k rows) instead of a dict-hit per row
    _EMIT_IDENTITY = False
    # label for the cedar_authorizer_row_routing_total{path=...} counter
    _METRIC_PATH = "raw"

    def __init__(self, engine: TPUPolicyEngine, breaker=None):
        self.engine = engine
        # optional CircuitBreaker (engine/breaker.py): when open, whole
        # batches skip the device plane and run the per-row interpreter
        # fallback; device outcomes (errors + latency) feed it back
        self.breaker = breaker
        # optional (exc) -> bool observer for device-plane exceptions
        # (server/supervisor.py DeviceRecovery.observe): a fatal XLA/runtime
        # error triggers a breaker trip + engine rebuild off the serving
        # path; evaluation bugs are ignored by its classifier
        self.on_device_error = None
        self._snap: Optional[_Snapshot] = None
        self._build_lock = threading.Lock()
        # accumulated encode/device/decode seconds (reset per process_raw
        # call on the serial path; the pipelined stages accumulate into it
        # from their worker threads, so treat it as approximate there)
        self.last_stage_s: dict = {"encode": 0.0, "device": 0.0, "decode": 0.0}

    # ---------------------------------------------------------- availability

    def _current_snapshot(self) -> Optional[_Snapshot]:
        """Atomic snapshot for the engine's current compiled set, rebuilding
        the native encoder when the set changes (policy hot swap); None when
        the set or environment rules the fast path out.

        Interpreter-fallback policies do NOT disable the native plane:
        their scopes are packed as device gate rules (compiler.pack), and
        rows whose verdict word carries WORD_GATE re-run through the exact
        Python path — everything else stays native."""
        cs = self.engine._compiled
        if cs is None:
            return None
        snap = self._snap  # lock-free fast path: one atomic attribute read
        if snap is not None and snap.cs is cs:
            return snap if snap.encoder is not None else None
        with self._build_lock:
            # re-read under the lock: a hot swap may have landed (and another
            # thread may have built its snapshot) while we waited; building
            # for the stale cs would evict the fresh snapshot and thrash
            cs = self.engine._compiled
            if cs is None:
                return None
            snap = self._snap
            if snap is None or snap.cs is not cs:
                try:
                    encoder = NativeEncoder.create(cs.packed)
                except Exception:  # noqa: BLE001 — cache the failure, don't loop
                    log.exception("native encoder build failed; python path only")
                    encoder = None
                snap = _Snapshot(encoder, cs, {}, {})
                self._snap = snap
        return snap if snap.encoder is not None else None

    @property
    def available(self) -> bool:
        return self._current_snapshot() is not None

    # ----------------------------------------------------- subclass surface

    def _encode_into(
        self, snap: _Snapshot, bodies, codes, extras, counts, flags, anc
    ):
        """C++ encode of one chunk DIRECTLY into the caller's buffers
        (the engine's pooled staging; `anc` takes each row's group tallies,
        native.ANC_WHERE); returns the path's aux payload (None for SAR,
        uids for admission)."""
        raise NotImplementedError

    def _route_flags(self, flags, results, bodies, aux) -> np.ndarray:
        """Fill encoder-gate rows into `results`; return the row indices
        that need the per-row Python fallback."""
        raise NotImplementedError

    def _fallback_row(self, body: bytes):
        """Exact Python path for one raw body."""
        raise NotImplementedError

    def _run_gated(self, bodies: List[bytes]) -> list:
        """Exact Python path for gate-flagged rows, batched."""
        raise NotImplementedError

    def _decode_word_payload(self, snap: _Snapshot, word: int):
        """Decode + cache the shared payload for one clean verdict word."""
        raise NotImplementedError

    def _decode_bits_payload(self, snap: _Snapshot, row_bits):
        """Decode one rule-bitset row into the shared payload."""
        raise NotImplementedError

    def _emit(self, payload, i: int, aux):
        """Render a shared payload into the response value for row i."""
        raise NotImplementedError

    # ------------------------------------------------------------- pipeline

    def _guarded_process(
        self, bodies: Sequence[bytes], snap: _Snapshot, fallback_one
    ) -> list:
        """process_raw behind the circuit breaker (engine/breaker.py
        guarded_call): an open breaker routes the whole batch to the per-row
        interpreter fallback, a raising device plane feeds the breaker and
        re-runs the batch on the fallback, and success/latency outcomes
        drive breach accounting and recovery probes."""
        from .breaker import guarded_call

        return guarded_call(
            self.breaker,
            lambda: self.process_raw(bodies, snap),
            lambda: [fallback_one(b) for b in bodies],
            self._METRIC_PATH,
            on_error=self.on_device_error,
        )

    def process_raw(self, bodies: Sequence[bytes], snap: _Snapshot) -> list:
        """Evaluate a batch of raw JSON bodies through the native plane.

        Large batches run a two-phase pipeline: each chunk's C++ encode +
        async device launch (_prepare_chunk) happens while the previous
        chunk's device work is in flight; every chunk's verdict words pack
        into ONE batch-wide D2H transfer (_WordPacker, flushed once all
        chunks have launched); materialization + verdict decode
        (_finish_words) drains in order; gated and flagged rows across ALL
        chunks resolve in one deferred pass. `last_stage_s` records the
        per-call encode/device/decode split for the bench's stage budget."""
        self.last_stage_s = {"encode": 0.0, "device": 0.0, "decode": 0.0}
        pack = _WordPacker() if _packed_decode_enabled() else None
        pending = []
        lo = 0
        for size in _chunk_sizes(len(bodies), self._CHUNK, self._TAIL_CHUNK):
            chunk = bodies[lo : lo + size]
            lo += size
            pending.append(
                (chunk, self._prepare_chunk(snap, chunk, word_pack=pack))
            )
        if pack is not None:
            with sub_stage("dispatch.readback"):
                pack.flush()
        ctxs = [self._finish_words(snap, chunk, pre) for chunk, pre in pending]
        self._resolve_deferred(snap, ctxs)
        if len(ctxs) == 1:
            return ctxs[0]["results"].tolist()
        out: list = []
        for ctx in ctxs:
            out.extend(ctx["results"].tolist())
        return out

    # ------------------------------------------------- pipelined stage API
    #
    # The engine/batcher.py PipelinedBatcher drives these three entry
    # points from its worker threads so batch N+1's host encode overlaps
    # batch N's device execution, and batch N's host decode overlaps batch
    # N+2's encode. Semantics are IDENTICAL to the serial
    # authorize_raw/handle_raw path (tests/test_pipeline.py pins the
    # differential): the same snapshot/readiness gates run at encode time,
    # an open breaker (or any device-plane exception) degrades to the same
    # per-row interpreter-fallback RESULTS the serial guarded path
    # produces, and breaker success latency is measured over the
    # dispatch→decode window (the serial guard's window minus the encode
    # it no longer serializes).

    def _pipeline_ready(self) -> bool:
        """Path-specific readiness gate (store initial loads), mirroring
        the serial entry point's check."""
        raise NotImplementedError

    def pipeline_encode(self, bodies: Sequence[bytes]):
        """Stage 1 (encode worker pool): availability gates + host encode.
        Returns an opaque ctx for pipeline_dispatch; when the native plane
        is unavailable, unready, or breaker-rejected, the ctx already
        carries the final per-row fallback results and the later stages
        pass it through untouched."""
        from ..server.metrics import record_fallback_batch

        try:
            snap = self._current_snapshot()
            usable = snap is not None and self._pipeline_ready()
        except Exception:  # noqa: BLE001 — degrade to the python path
            log.exception("fastpath availability check failed")
            usable = False
        if usable and self.breaker is not None and not self.breaker.allow():
            record_fallback_batch(self._METRIC_PATH, "breaker_open")
            usable = False
        if not usable:
            return ("direct", [self._fallback_row(b) for b in bodies])
        try:
            encs = []
            lo = 0
            for size in _chunk_sizes(
                len(bodies), self._CHUNK, self._TAIL_CHUNK
            ):
                chunk = bodies[lo : lo + size]
                lo += size
                encs.append((chunk, self._encode_chunk(snap, chunk)))
        except Exception:  # noqa: BLE001 — encode failure degrades
            return ("direct", self._pipeline_degrade(bodies, "encode"))
        return ("enc", snap, bodies, encs)

    def pipeline_dispatch(self, ctx):
        """Stage 2 (dispatch thread): launch every chunk's device match
        asynchronously — the batch's verdict words registering with one
        _WordPacker, flushed into a single packed D2H transfer once the
        last chunk is away — and return immediately; the caller dispatches
        the NEXT batch while this one executes."""
        if ctx[0] == "direct":
            return ctx
        _, snap, bodies, encs = ctx
        t0 = time.monotonic()
        pack = _WordPacker() if _packed_decode_enabled() else None
        try:
            launched = [
                (chunk, self._launch_chunk(snap, enc, word_pack=pack))
                for chunk, enc in encs
            ]
            if pack is not None:
                with sub_stage("dispatch.readback"):
                    pack.flush()
        except Exception:  # noqa: BLE001 — device failure degrades
            return ("direct", self._pipeline_degrade(bodies, "dispatch"))
        return ("run", snap, bodies, launched, t0)

    def pipeline_decode(self, ctx) -> list:
        """Stage 3 (decode thread): materialize the device results (the
        only stage that blocks on the device), decode clean rows, resolve
        gated/flagged rows, and return the per-body results."""
        if ctx[0] == "direct":
            return ctx[1]
        _, snap, bodies, launched, t0 = ctx
        try:
            ctxs = [
                self._finish_words(snap, chunk, pre) for chunk, pre in launched
            ]
            self._resolve_deferred(snap, ctxs)
        except Exception:  # noqa: BLE001 — device failure degrades
            return self._pipeline_degrade(bodies, "decode")
        if self.breaker is not None:
            self.breaker.record_success(time.monotonic() - t0)
        if len(ctxs) == 1:
            return ctxs[0]["results"].tolist()
        out: list = []
        for c in ctxs:
            out.extend(c["results"].tolist())
        return out

    def _pipeline_degrade(self, bodies: Sequence[bytes], stage: str) -> list:
        """A pipelined stage raised: feed the breaker and answer the whole
        batch from the per-row interpreter fallback — the exact degradation
        guarded_call gives the serial path."""
        import sys

        from ..server.metrics import record_fallback_batch

        log.exception(
            "%s pipelined %s stage failed; interpreter fallback",
            self._METRIC_PATH,
            stage,
        )
        if self.breaker is not None:
            self.breaker.record_failure()
        exc = sys.exc_info()[1]
        if self.on_device_error is not None and exc is not None:
            try:
                self.on_device_error(exc)
            except Exception:  # noqa: BLE001 — recovery must not break serving
                log.exception("device-error observer failed")
        record_fallback_batch(self._METRIC_PATH, "evaluator_error")
        return [self._fallback_row(b) for b in bodies]

    def _record_routing(
        self, n: int, n_fallback: int, n_ok: int, n_gated: int, n_flagged: int
    ) -> None:
        """One chunk's row counts -> the routing-class Prometheus counter.
        The gated share is the operator's early warning for the gate-plane
        cliff: a hot fallback/opaque scope re-routes its matching rows
        through the ~3k/s Python path (docs/Operations.md)."""
        from ..server.metrics import record_row_routing

        p = self._METRIC_PATH
        record_row_routing(p, "clean_native", n_ok - n_gated - n_flagged)
        record_row_routing(p, "gated", n_gated)
        record_row_routing(p, "flagged", n_flagged)
        record_row_routing(p, "encoder_fallback", n_fallback)
        record_row_routing(p, "encoder_gate", n - n_fallback - n_ok)

    def _record_extras(self, ok_counts, max_e: int, ok_anc) -> None:
        """One chunk's set-membership extras (the natively encoded rows'
        counts) -> cedar_encode_extras and `extras_max` on the
        batch.encode span: how near the rows come to the encoder's cap. A
        row past it is an encoder_fallback row of _record_routing. And the
        same rows' principal groups by where the encoder put them
        (`ok_anc` [rows, 3], native.ANC_WHERE) ->
        cedar_encode_ancestors_total and `groups` / `known_groups` on the
        span: how much of the extras list is group membership."""
        from ..server.metrics import (
            record_encode_ancestors,
            record_encode_extras,
        )

        p = self._METRIC_PATH
        record_encode_extras(p, int(ok_counts.sum()), len(ok_counts))
        for where, n in zip(ANC_WHERE, ok_anc.sum(axis=0).tolist()):
            record_encode_ancestors(p, where, n)
        note_encode_extras(
            max_e,
            int(ok_anc.sum(axis=1).max(initial=0)),
            int(ok_anc[:, :2].sum(axis=1).max(initial=0)),
        )

    def _encode_chunk(self, snap: _Snapshot, bodies: Sequence[bytes]):
        """Host-only half of chunk preparation: C++ encode STRAIGHT INTO
        bucket-padded buffers acquired from the engine's staging pool —
        the zero-copy staging path. The encoder's worker pool shards the
        chunk across cores and each shard writes its rows into the pooled
        buffer in place, so the encoded codes reach the (donated) H2D
        transfer with no intermediate copy: the engine's _pad_to_bucket
        sees an exact-bucket array and passes it through untouched. The
        buffers ride the chunk ctx (`held`) and return to the pool only
        after the deferred resolve — the device (which may alias numpy
        inputs on CPU, and holds donated transfers in flight on TPU) is
        provably done with them there. Any exception on the way abandons
        the buffers to the GC instead of releasing them: a buffer that
        MIGHT still back an in-flight transfer must never re-enter the
        pool (tests/test_hostpath.py pins this).

        No device interaction — this is the piece the pipelined batcher
        runs on its encode worker pool."""
        chaos_fire("engine.encode")
        n = len(bodies)
        staging = self.engine._staging
        pad_L = snap.cs.packed.L
        B = _round_bucket(n, _BATCH_BUCKETS)
        cap = snap.encoder.DEFAULT_EXTRAS_CAP
        codes = staging.acquire((B, snap.encoder.n_slots), np.int32)
        extras = staging.acquire((B, cap), np.int32)
        held = [codes, extras]
        counts = np.empty((n,), np.int32)
        flags = np.empty((n,), np.uint8)
        anc = np.empty((n, len(ANC_WHERE)), np.int32)
        try:
            aux = self._encode_into(
                snap, bodies, codes, extras, counts, flags, anc
            )
            # fused multi-tenant plane (cedar_tpu/tenancy): the body bytes
            # carry no tenant — stamp each request's tenant feature code
            # into the reserved discriminator column the front end
            # resolved for it (TenantBody). Unknown/unstamped tenants
            # stay code 0, which activates NOTHING: such a request can
            # match no tenant's rules — fail-safe by construction.
            tcol = snap.cs.tenant_column
            if tcol is not None:
                col, vocab = tcol
                codes[:n, col] = [
                    vocab.get(("s", getattr(b, "tenant", "")), 0)
                    for b in bodies
                ]
        except Exception:
            # the encode never reached the device: the buffers are
            # provably idle, hand them straight back
            staging.release(*held)
            raise
        if B != n:
            # bucket-padding rows: all-zero codes activate nothing, >= L
            # extras match nothing — the exact padding _pad_to_bucket used
            codes[n:] = 0
            extras[n:] = pad_L
        # object ndarray, not a list: clean rows scatter in one vectorized
        # fancy-index assignment (_finish_words); per-row assignments
        # (fallback/gate/flag rows) work the same on either container
        results = np.empty(n, dtype=object)
        py_rows = self._route_flags(flags, results, bodies, aux)

        ok = flags == F_OK
        n_ok = int(ok.sum())
        idx = ok_codes = ok_extras = None
        if n_ok:
            all_ok = n_ok == n
            idx = np.arange(n) if all_ok else np.nonzero(ok)[0]
            # trim the extras buffer to the live width (bucketed to avoid
            # retraces): most requests carry zero extras, and every padded
            # column costs a [B, E, L] broadcast-compare on device
            ok_counts = counts if all_ok else counts[idx]
            max_e = int(ok_counts.max(initial=0))
            self._record_extras(
                ok_counts, max_e, anc if all_ok else anc[idx]
            )
            # the ladder's own widths (a row past the widest never got
            # here: the encoder's cap is that width), so every served
            # shape is one the warm ladder compiled
            E = _round_bucket(max_e, EXTRAS_WIDTHS)
            if all_ok:
                ok_codes = codes
                ok_extras = extras[:, :E]
            else:
                # compacting to the ok rows copies them out of the pooled
                # buffers (fancy indexing), so the staging arrays never
                # reach the device — release them now
                ok_codes = codes[idx]
                ok_extras = extras[idx, :E]
                staging.release(*held)
                held = []
        else:
            staging.release(*held)
            held = []
        return results, py_rows, idx, ok_codes, ok_extras, aux, held

    def _launch_chunk(self, snap: _Snapshot, enc, word_pack=None):
        """Device half of chunk preparation: launch the encoded rows' match
        asynchronously (dispatch only — the readback happens in
        _finish_words). `word_pack` routes this chunk's verdict words into
        the batch-wide packed D2H transfer (engine/_WordPacker)."""
        chaos_fire("engine.dispatch")
        results, py_rows, idx, ok_codes, ok_extras, aux, held = enc
        fin = None
        if idx is not None:
            # small batches: rule bitsets for multi/err rows arrive
            # compacted IN the same device call and the same readback as
            # the words (one buffer a launch, no second trip). Large
            # batches skip the bits plane; the deferred resolve fetches
            # the rare flagged rows' bitsets in a second fixed-shape call
            # instead — and their words ride the packed batch transfer.
            fin = self.engine.match_arrays_launch(
                ok_codes, ok_extras, cs=snap.cs,
                want_bits=len(idx) <= self._BITS_INCALL_MAX,
                valid_rows=len(idx),
                word_pack=word_pack,
            )
        return results, py_rows, idx, ok_codes, ok_extras, fin, aux, held

    def _prepare_chunk(
        self, snap: _Snapshot, bodies: Sequence[bytes], word_pack=None
    ):
        """Encode one chunk natively and LAUNCH its device match; the device
        work proceeds asynchronously while the caller prepares the next
        chunk."""
        t0 = time.monotonic()
        pre = self._launch_chunk(
            snap, self._encode_chunk(snap, bodies), word_pack=word_pack
        )
        self.last_stage_s["encode"] += time.monotonic() - t0
        return pre

    def _finish_words(self, snap: _Snapshot, bodies, pre) -> dict:
        """Materialize one chunk's verdict words and decode every CLEAN row
        (one shared payload per distinct word — the r03 per-row branch
        chain was the serving-path bottleneck at ~10us/row). Gate-flagged
        and multi/err rows are recorded for _resolve_deferred."""
        results, py_rows, idx, ok_codes, ok_extras, fin, aux, held = pre
        for i in py_rows:
            results[i] = self._fallback_row(bodies[i])
        ctx = {
            "results": results,
            "bodies": bodies,
            "idx": idx,
            "aux": aux,
            "ok_codes": ok_codes,
            "ok_extras": ok_extras,
            "held": held,
            "bitmap": None,
            "gate_rows": [],
            "flag_rows": [],
            "flag_keys": {},
            "flag_cached": {},
            "bits_rows": [],
            "bits_fin": None,
        }
        if fin is None:
            self._record_routing(len(bodies), len(py_rows), 0, 0, 0)
            return ctx
        chaos_fire("engine.decode")
        t0 = time.monotonic()
        out = fin()
        words, bitmap = out[0], (out[2] if len(out) == 3 else None)
        t1 = time.monotonic()
        self.last_stage_s["device"] += t1 - t0
        # staged (bucket-padded) launches return words for the padding
        # rows too: everything below is indexed against idx, so trim
        w = words[: len(idx)].astype(np.uint32)
        ctx["bitmap"] = bitmap
        handled = set()
        if snap.cs.packed.has_gate:
            ctx["gate_rows"] = np.nonzero((w & WORD_GATE) != 0)[0].tolist()
            handled.update(ctx["gate_rows"])
        flagged = np.nonzero((w & (WORD_ERR | WORD_MULTI)) != 0)[0].tolist()
        ctx["flag_rows"] = [k for k in flagged if k not in handled]
        handled.update(ctx["flag_rows"])
        self._record_routing(
            len(bodies), len(py_rows), len(idx),
            len(ctx["gate_rows"]), len(ctx["flag_rows"]),
        )
        # a flagged row's complete reason set is a pure function of its
        # feature row (codes + extras fully determine the rule bitset), so
        # rows whose feature bytes were resolved before skip the fetch —
        # in steady state repeating traffic pays no bits round trip at all.
        # Launch the fetch for the truly-new rows NOW: it rides the link
        # while this (and later) chunks decode, instead of paying a serial
        # round trip at resolve time.
        cache = snap.word_cache
        if len(cache) > 200_000:  # adversarial-traffic growth bound;
            cache.clear()  # evict BEFORE the membership checks below
        miss = []
        miss_keys = set()  # dedupe repeats WITHIN the chunk too
        fkeys = ctx["flag_keys"]
        fc = ctx["flag_cached"]
        for k in ctx["flag_rows"]:
            if bitmap and k in bitmap:
                continue
            key = ok_codes[k].tobytes() + ok_extras[k].tobytes()
            fkeys[k] = key
            cached = cache.get(key)
            if cached is not None:
                # snapshot the VALUE now: a concurrent eviction between
                # launch and resolve must not strand the row
                fc[k] = cached
            elif key not in miss_keys:
                miss.append(k)
                miss_keys.add(key)
        if miss:
            ctx["bits_rows"] = miss
            ctx["bits_fin"] = self.engine.match_bits_arrays_launch(
                ok_codes[miss], ok_extras[miss], cs=snap.cs
            )
        decode = self._decode_word_payload
        emit = self._emit
        if not handled:
            # vectorized clean decode: one payload per DISTINCT word
            # (verdict diversity is tiny), then one fancy-index scatter.
            # SAR rows (_EMIT_IDENTITY) share the payload objects outright —
            # no per-row python work at all; admission rows still construct
            # one response per row (each carries its own uid) but the
            # per-row word-cache hits and branch chains are gone.
            uniq, inv = np.unique(w, return_inverse=True)
            payloads = np.empty(len(uniq), dtype=object)
            for j, word in enumerate(uniq.tolist()):
                payload = cache.get(word)
                if payload is None:
                    payload = decode(snap, word)
                payloads[j] = payload
            if self._EMIT_IDENTITY:
                results[idx] = payloads[inv]
            else:
                row_pay = payloads[inv]
                out_arr = np.empty(len(idx), dtype=object)
                for k, i in enumerate(idx.tolist()):
                    out_arr[k] = emit(row_pay[k], i, aux)
                results[idx] = out_arr
        else:
            wl = w.tolist()
            for k, i in enumerate(idx.tolist()):
                if k in handled:
                    continue
                word = wl[k]
                payload = cache.get(word)
                if payload is None:
                    payload = decode(snap, word)
                results[i] = emit(payload, i, aux)
        self.last_stage_s["decode"] += time.monotonic() - t1
        return ctx

    def _resolve_deferred(self, snap: _Snapshot, ctxs: List[dict]) -> None:
        """Resolve every chunk's gate-flagged and multi/err rows in ONE
        pass: a single batched Python re-run for gated rows and a single
        cross-chunk bits gather for flagged rows, instead of per-chunk
        device round trips."""
        gated = [(ctx, k) for ctx in ctxs for k in ctx["gate_rows"]]
        if gated:
            g_res = self._run_gated(
                [ctx["bodies"][int(ctx["idx"][k])] for ctx, k in gated]
            )
            for (ctx, k), res in zip(gated, g_res):
                ctx["results"][int(ctx["idx"][k])] = res

        cache = snap.word_cache
        decode_bits = self._decode_bits_payload
        key_bits = _gather_flag_bits(self.engine, snap, ctxs)
        n_readback = n_cached = n_flagged = 0
        for ctx in ctxs:
            if not ctx["flag_rows"]:
                continue
            bm = ctx["bitmap"]
            fc = ctx["flag_cached"]
            fkeys = ctx["flag_keys"]
            aux = ctx["aux"]
            n_flagged += len(ctx["flag_rows"])
            for k in ctx["flag_rows"]:
                if bm and k in bm:
                    payload = decode_bits(snap, bm[k])
                    n_readback += 1
                elif k in fc:
                    payload = fc[k]
                    n_cached += 1
                else:
                    key = fkeys[k]
                    payload = cache.get(key)
                    if payload is None:
                        payload = cache[key] = decode_bits(snap, key_bits[key])
                i = int(ctx["idx"][k])
                ctx["results"][i] = self._emit(payload, i, aux)
        if n_flagged:
            # once a batch, and only a batch with flagged rows: how each
            # row's bitset reached the host (a second_call row's key was
            # in a standalone bits fetch, launched or rescued)
            from ..server.metrics import record_flagged_bits

            p = self._METRIC_PATH
            record_flagged_bits(p, "readback", n_readback)
            record_flagged_bits(p, "word_cache", n_cached)
            record_flagged_bits(
                p, "second_call", n_flagged - n_readback - n_cached
            )

        # every device readback for this batch has materialized and every
        # flagged row's feature bytes have been consumed: the pooled
        # staging buffers the chunks encoded into are idle — hand them
        # back. Exception paths anywhere above skip this on purpose: an
        # abandoned buffer is GC'd, a prematurely released one could be
        # handed to a later batch while a donated transfer still reads it.
        staging = self.engine._staging
        for ctx in ctxs:
            if ctx["held"]:
                staging.release(*ctx["held"])
                ctx["held"] = []


def _gather_flag_bits(engine, snap, ctxs) -> dict:
    """Materialize each chunk's async bits fetch and return {feature key:
    bitset row} for EVERY flagged row that is not covered by an in-call
    bitmap or a launch-time cache-value snapshot (ctx["flag_cached"]) —
    duplicate keys within/across chunks share one entry, and rows whose
    cache entry was evicted between launch and resolve are rescued with
    ONE extra batched fetch (never a serial per-row round trip)."""
    cache = snap.word_cache
    key_bits: dict = {}
    for ctx in ctxs:
        if ctx["bits_fin"] is not None:
            bits = ctx["bits_fin"]()  # launched back in _finish_words
            fkeys = ctx["flag_keys"]
            for j, k in enumerate(ctx["bits_rows"]):
                key_bits[fkeys[k]] = bits[j]
    sync_rows: list = []
    for ctx in ctxs:
        bm = ctx["bitmap"]
        fc = ctx["flag_cached"]
        for k in ctx["flag_rows"]:
            if (bm and k in bm) or k in fc:
                continue
            key = ctx["flag_keys"][k]
            if key in key_bits:
                continue
            # NOT skipped when the key is (currently) in the shared cache:
            # a concurrent caller's eviction could clear it between this
            # check and the resolve loop, stranding the row — claiming the
            # bits row here makes resolve self-sufficient, and the cost is
            # one redundant row in a fetch that's already batched
            key_bits[key] = None  # claimed; filled below
            sync_rows.append((ctx, k, key))
    if not sync_rows:
        return key_bits
    packed = snap.cs.packed
    E = max(ctx["ok_extras"].shape[1] for ctx, _k, _key in sync_rows)
    codes_rows = np.stack([ctx["ok_codes"][k] for ctx, k, _ in sync_rows])
    extras_rows = np.full(
        (len(sync_rows), E), packed.L,
        dtype=sync_rows[0][0]["ok_extras"].dtype,
    )
    for j, (ctx, k, _) in enumerate(sync_rows):
        row = ctx["ok_extras"][k]
        extras_rows[j, : row.shape[0]] = row
    bits = engine.match_bits_arrays(codes_rows, extras_rows, cs=snap.cs)
    for j, (_ctx, _k, key) in enumerate(sync_rows):
        key_bits[key] = bits[j]
    return key_bits


class SARFastPath(_RawFastPath):
    """Batch evaluator over raw SubjectAccessReview JSON bodies."""

    _EMIT_IDENTITY = True  # _emit returns the shared Result unchanged
    _METRIC_PATH = "authorization"

    def __init__(
        self,
        engine: TPUPolicyEngine,
        authorizer: CedarWebhookAuthorizer,
        fallback: Optional[Callable[[bytes], Result]] = None,
        breaker=None,
    ):
        super().__init__(engine, breaker=breaker)
        self.authorizer = authorizer
        self._fallback = fallback or self._python_fallback

    def authorize_raw(self, bodies: Sequence[bytes]) -> List[Result]:
        """Evaluate a batch of raw SAR JSON bodies -> (decision, reason)."""
        snap = self._current_snapshot()
        if snap is None:
            return [self._fallback_row(b) for b in bodies]
        if not self.authorizer.ready():
            # NoOpinion until every store's initial load completes
            # (authorizer.go:58-66); gates still apply, so run the exact path
            return [self._fallback_row(b) for b in bodies]
        return self._guarded_process(bodies, snap, self._fallback_row)

    def _pipeline_ready(self) -> bool:
        return self.authorizer.ready()

    # --------------------------------------------------------------- hooks

    def _encode_into(self, snap, bodies, codes, extras, counts, flags, anc):
        snap.encoder.encode_batch_into(
            bodies, codes, extras, counts, flags, anc=anc
        )
        return None

    def _route_flags(self, flags, results, bodies, aux):
        for flag, res in _GATE_RESULTS.items():
            for i in np.nonzero(flags == flag)[0]:
                results[i] = res
        return np.nonzero(
            (flags == F_PARSE_ERROR) | (flags == F_EXTRAS_OVERFLOW)
        )[0]

    def _fallback_row(self, body: bytes) -> Result:
        return InterpreterResult(self._fallback(body))

    def _run_gated(self, bodies: List[bytes]) -> List[Result]:
        if self._fallback == self._python_fallback:
            return [InterpreterResult(r) for r in self._gated_batch(bodies)]
        # honor an injected custom fallback per row
        return [self._fallback_row(b) for b in bodies]

    def _decode_word_payload(self, snap: _Snapshot, word: int) -> Result:
        """Decode + cache one clean verdict word (no multi/err/gate flags —
        those rows are handled upstream). The deny-on-error log fires once
        per distinct word per snapshot, not once per row."""
        code = (word >> 30) & 0x3
        pol = word & 0xFFFFFF
        if code == 1:
            r: Result = (DECISION_ALLOW, self._reason(snap, pol), None)
        elif code == 2:
            r = (DECISION_DENY, self._reason(snap, pol), None)
        else:
            if code == 3:
                meta = snap.cs.packed.policy_meta[pol]
                log.error(
                    "Authorize errors: while evaluating policy `%s`:"
                    " evaluation error",
                    meta.policy_id,
                )
            r = (DECISION_NO_OPINION, "", None)
        snap.word_cache[word] = r
        return r

    def _decode_bits_payload(self, snap: _Snapshot, row_bits) -> Result:
        packed = snap.cs.packed
        groups = self.engine._bits_groups(packed, row_bits, snap.cs.col_map)
        decision, diag = self.engine._finalize_sets(packed, groups, None, None)
        return self._map_decision(decision, diag)

    def _emit(self, payload: Result, i: int, aux) -> Result:
        return payload  # Result tuples are shared directly across rows

    # ---------------------------------------------------------- python path

    def _python_fallback(self, body: bytes) -> Result:
        import json

        from ..server.http import get_authorizer_attributes

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
            # tenant stamp (cedar_tpu/tenancy): the interpreter path's
            # request context must carry the same tenant id the device
            # plane discriminates on
            attributes.tenant = getattr(body, "tenant", "")
            decision, reason = self.authorizer.authorize(attributes)
        except Exception as e:  # noqa: BLE001 — always answer the apiserver
            log.exception("fastpath python fallback failed")
            return DECISION_NO_OPINION, "", f"evaluation error: {e}"
        return decision, reason, None

    def _gated_batch(self, bodies: Sequence[bytes]) -> List[Result]:
        """Exact Python path for gate-flagged rows, but with ONE batched
        device call instead of a per-row engine.evaluate dispatch. The rows
        already passed the native gates (self-allow / system-skip fire
        before encoding) and readiness was checked by the caller, so the
        remaining work is entity build + hybrid evaluation + mapping —
        semantics identical to authorizer.authorize per row."""
        import json

        from ..server.authorizer import record_to_cedar_resource
        from ..server.http import get_authorizer_attributes

        results: List[Optional[Result]] = [None] * len(bodies)
        items = []  # (row, entities, request)
        for i, body in enumerate(bodies):
            try:
                sar = json.loads(body)
            except (ValueError, TypeError, RecursionError) as e:
                results[i] = (
                    DECISION_NO_OPINION,
                    "Encountered decoding error",
                    f"failed parsing request body: {e}",
                )
                continue
            try:
                attributes = get_authorizer_attributes(sar)
                attributes.tenant = getattr(body, "tenant", "")
                entities, request = record_to_cedar_resource(attributes)
            except Exception as e:  # noqa: BLE001 — always answer
                log.exception("fastpath gated entity build failed")
                results[i] = (DECISION_NO_OPINION, "", f"evaluation error: {e}")
                continue
            items.append((i, entities, request))
        if items:
            try:
                verdicts = self.engine.evaluate_batch(
                    [(em, req) for _, em, req in items]
                )
            except Exception:  # noqa: BLE001 — re-run rows independently
                log.exception("gated batch evaluation failed; per-row path")
                for i, _, _ in items:
                    results[i] = self._fallback(bodies[i])
            else:
                for (i, _, _), (decision, diag) in zip(items, verdicts):
                    results[i] = self._map_decision(decision, diag)
        return results  # type: ignore[return-value]

    # -------------------------------------------------------------- helpers

    @staticmethod
    def _reason(snap: _Snapshot, pol: int) -> str:
        """Reason JSON for a single-policy match; cached on the snapshot — it
        depends only on the policy index within that compiled set."""
        r = snap.reason_cache.get(pol)
        if r is None:
            from ..lang.authorize import Diagnostics, Reason

            meta = snap.cs.packed.policy_meta[pol]
            r = _diagnostic_to_reason(
                Diagnostics(
                    reasons=[Reason(meta.policy_id, meta.filename, meta.position)]
                )
            )
            snap.reason_cache[pol] = r
        return r

    @staticmethod
    def _map_decision(decision: str, diag) -> Result:
        """Cedar decision -> webhook decision (authorizer.go:75-84)."""
        if decision == ALLOW:
            return DECISION_ALLOW, _diagnostic_to_reason(diag), None
        if decision == DENY and diag.reasons:
            return DECISION_DENY, _diagnostic_to_reason(diag), None
        if diag.errors:
            log.error("Authorize errors: %s", diag.errors)
        return DECISION_NO_OPINION, "", None


class AdmissionFastPath(_RawFastPath):
    """Batch evaluator over raw AdmissionReview JSON bodies — the admission
    twin of SARFastPath. The C++ encoder parses the review, walks the
    (old)object into feature codes (native/encoder.cpp build_adm, mirroring
    entities/admission.py and reference
    internal/server/entities/admission.go:160-369), and the batched device
    kernel produces the verdicts; deny messages carry the complete
    matched-policy list like the reference's handler
    (internal/server/admission/handler.go:157-164)."""

    _METRIC_PATH = "admission"

    def __init__(self, engine: TPUPolicyEngine, handler, breaker=None):
        super().__init__(engine, breaker=breaker)
        self.handler = handler  # CedarAdmissionHandler: fallback + readiness
        # bound once: _emit runs per row on the clean-decode hot loop
        from ..server.admission import AdmissionResponse

        self._response_cls = AdmissionResponse

    def handle_raw(self, bodies: Sequence[bytes]) -> list:
        """Evaluate a batch of raw AdmissionReview JSON bodies."""
        snap = self._current_snapshot()
        if snap is None or not self.handler._ready():
            # unready stores answer allow in handler.handle_batch; keep the
            # exact path for both cases
            return [self._py_one(b) for b in bodies]
        return self._guarded_process(bodies, snap, self._py_one)

    def _pipeline_ready(self) -> bool:
        return self.handler._ready()

    # --------------------------------------------------------------- hooks

    def _encode_into(self, snap, bodies, codes, extras, counts, flags, anc):
        return snap.encoder.encode_adm_batch_into(
            bodies, codes, extras, counts, flags, anc=anc
        )

    def _route_flags(self, flags, results, bodies, uids):
        from ..server.admission import AdmissionResponse

        for i in np.nonzero(flags == F_ADM_NS_SKIP)[0]:
            results[i] = AdmissionResponse(uid=uids[i], allowed=True)
        return np.nonzero(
            (flags == F_PARSE_ERROR)
            | (flags == F_ADM_ERROR)
            | (flags == F_EXTRAS_OVERFLOW)
        )[0]

    def _fallback_row(self, body: bytes):
        return self._py_one(body)

    def _run_gated(self, bodies: List[bytes]) -> list:
        return self._gated_batch(bodies)

    def _decode_word_payload(self, snap: _Snapshot, word: int):
        """(allowed, message) payload for one clean verdict word, cached per
        snapshot; error logs fire once per distinct word, not per row."""
        code = (word >> 30) & 0x3
        pol = word & 0xFFFFFF
        if code == 1:
            payload = (True, "")
        elif code == 2:
            payload = (False, self._deny_message(snap, (pol,)))
        elif code == 3:
            meta = snap.cs.packed.policy_meta[pol]
            log.error(
                "admission errors: while evaluating policy `%s`:"
                " evaluation error",
                meta.policy_id,
            )
            payload = (False, "")
        else:  # no signal: the allow-all final tier should preclude
            log.error(
                "request denied without reasons; the default permit "
                "policy was not evaluated"
            )
            payload = (False, "")
        snap.word_cache[word] = payload
        return payload

    def _decode_bits_payload(self, snap: _Snapshot, row_bits):
        import json as _json

        packed = snap.cs.packed
        groups = self.engine._bits_groups(packed, row_bits, snap.cs.col_map)
        decision, diag = self.engine._finalize_sets(packed, groups, None, None)
        if decision == DENY and diag.reasons:
            return (
                False,
                _json.dumps(
                    [r.to_dict() for r in diag.reasons],
                    separators=(",", ":"),
                ),
            )
        if decision == DENY:
            if diag.errors:
                log.error("admission errors: %s", diag.errors)
            return (False, "")
        return (True, "")

    def _emit(self, payload, i: int, uids):
        return self._response_cls(
            uid=uids[i], allowed=payload[0], message=payload[1]
        )

    # ---------------------------------------------------------- python path

    def _parse_one(self, body: bytes):
        """Parse one raw body into an AdmissionRequest. Returns
        (request, review, None) on success or (None, review, error
        response) with the exact error semantics of
        WebhookServer.handle_admit."""
        import json

        from ..entities.admission import AdmissionRequest
        from ..server.admission import AdmissionResponse

        review = None
        try:
            review = json.loads(body)
            req = AdmissionRequest.from_admission_review(review)
            # tenant stamp (cedar_tpu/tenancy): the Python admission path's
            # context must carry the tenant the device plane masks by
            req.tenant = getattr(body, "tenant", "")
            return req, review, None
        except (ValueError, TypeError, RecursionError) as e:
            if review is None:
                return None, None, AdmissionResponse(
                    uid="",
                    allowed=False,
                    code=400,
                    error=f"failed parsing body: {e}",
                )
            return None, review, self._allow_on_error(review, e)
        except Exception as e:  # noqa: BLE001 — fail-open like the reference
            log.exception("admission fastpath conversion failed")
            return None, review, self._allow_on_error(review, e)

    def _py_one(self, body: bytes):
        """Exact Python path for one raw body; response parity with
        WebhookServer.handle_admit."""
        req, review, err = self._parse_one(body)
        if err is not None:
            return err
        try:
            return self.handler.handle(req)
        except Exception as e:  # noqa: BLE001 — fail-open like the reference
            log.exception("admission fastpath fallback failed")
            return self._allow_on_error(review, e)

    def _gated_batch(self, bodies: Sequence[bytes]) -> list:
        """Exact Python path for gate-flagged rows with ONE batched
        handler.handle_batch call instead of per-row handle dispatches;
        per-row parse/conversion error semantics shared with _py_one
        (_parse_one)."""
        results: list = [None] * len(bodies)
        reqs = []  # (row, AdmissionRequest)
        for i, body in enumerate(bodies):
            req, _review, err = self._parse_one(body)
            if err is not None:
                results[i] = err
            else:
                reqs.append((i, req))
        if reqs:
            try:
                responses = self.handler.handle_batch([r for _, r in reqs])
            except Exception:  # noqa: BLE001 — re-run rows independently
                log.exception("gated admission batch failed; per-row path")
                for i, _ in reqs:
                    results[i] = self._py_one(bodies[i])
            else:
                for (i, _), resp in zip(reqs, responses):
                    results[i] = resp
        return results

    def _allow_on_error(self, review, e):
        from ..entities.admission import review_request_uid
        from ..server.admission import AdmissionResponse

        uid = review_request_uid(review)
        allowed = bool(getattr(self.handler, "allow_on_error", True))
        return AdmissionResponse(
            uid=uid,
            allowed=allowed,
            code=200,
            error=f"evaluation error ({'allowed' if allowed else 'denied'} on error): {e}",
        )

    def _deny_message(self, snap: _Snapshot, pols) -> str:
        """Compact JSON list of reason dicts — byte-identical to the
        handler's _decide rendering (Reason.to_dict per matched policy)."""
        import json

        from ..lang.authorize import Reason

        key = ("adm", tuple(pols))
        msg = snap.reason_cache.get(key)
        if msg is None:
            packed = snap.cs.packed
            msg = json.dumps(
                [
                    Reason(
                        packed.policy_meta[p].policy_id,
                        packed.policy_meta[p].filename,
                        packed.policy_meta[p].position,
                    ).to_dict()
                    for p in pols
                ],
                separators=(",", ":"),
            )
            snap.reason_cache[key] = msg
        return msg
