"""Device kernel: batched rule matching as an MXU matmul.

The policy set is a matrix W [L, R] over literals x rules (+1 required-true,
-1 required-false) with per-rule positive-literal counts `thresh`. A request
batch arrives as padded active-literal index lists [B, A]; the kernel:

  1. expands them into a {0,1} literal matrix lit [B, L] (int8) via a
     broadcast compare against an iota — a fused VPU op. (A scatter would
     serialize on TPU; the compare keeps everything vectorized.)
  2. computes scores = lit @ W with int32 accumulation — one MXU matmul
     that evaluates EVERY rule of EVERY request at once
  3. sat = scores >= thresh  (a rule is satisfied iff all its positive
     literals are active and none of its negated literals are)
  4. reduces rules into per-(tier, effect) first-match policy indices and
     walks the tiers ON DEVICE, emitting one packed uint32 verdict word per
     request — the host round trip is 4 bytes/decision, which is what makes
     the webhook's readback latency budget work.

There is ONE scoring plane: int8 inputs with int32 accumulation. Scores
are exact (lit entries are 0/1, W entries are +/-1), and on TPU the MXU
runs int8 contractions at 2x bf16 peak (v5e: ~394 TOPS int8 vs ~197
TFLOP/s bf16) — the matmul is the entire device cost of a decision. Every
match function takes an int8 W and int32 thresholds.

This replaces the reference's per-request tree-walking interpreter loop
(cedar-go PolicySet.IsAuthorized called at /root/reference
internal/server/store/store.go:31) with a single data-parallel contraction.

Packed verdict word layout (uint32):

    bits 30..31  code: 0 = no signal in any tier (caller's default applies)
                       1 = allow   (policy = first matching permit)
                       2 = deny    (policy = first matching forbid)
                       3 = deny-on-error (policy = first erroring policy;
                           no permit/forbid matched in the winning tier)
    bit  29      err:  the winning tier ALSO had an error-group match
                       (only meaningful for code 1/2; the erroring policy
                       index requires the rule bitset)
    bit  28      multi: MORE than one policy matched in the group that
                       produced the verdict (code 1/2: the reason group;
                       code 3: the error group). cedar-go reports every
                       determining policy in Diagnostic.Reasons
                       (/root/reference internal/server/store/store.go:31),
                       so a caller rendering diagnostics must fetch the
                       rule bitset (match_rules_codes_bits) for this row;
                       without the bit the single packed policy IS the
                       complete reason set.
    bits 0..23   policy index into PackedPolicySet.policy_meta
                 (POLICY_NONE = 0xFFFFFF when no policy applies)

The tier that produced the verdict is recovered host-side from
policy_meta[policy].tier, so it needs no bits here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INT32_MAX = 2**31 - 1

POLICY_NONE = 0xFFFFFF
CODE_NONE = 0
CODE_ALLOW = 1
CODE_DENY = 2
CODE_ERROR = 3
# verdict-word flag masks (see module docstring)
WORD_ERR = 1 << 29
WORD_MULTI = 1 << 28
# bit 27: at least one GATE rule matched (compiler.pack packs one scope-
# conjunction rule into group n_tiers * 3 per policy the NATIVE plane can't
# evaluate: interpreter-fallback policies AND native-opaque policies whose
# hard literals only the Python encoder can host-evaluate). A gated row may
# match/error on such a policy, so a NATIVELY-encoded word is not
# authoritative — the fast paths re-route it to the exact Python path.
# Python-encoded words stay authoritative for native-opaque policies (hard
# literals were filled at encode time); only fallback policies need the
# host-side tier walk there. Rows without the bit are fully decided by the
# word in every case.
WORD_GATE = 1 << 27

# group-per-tier layout (mirrors compiler.pack)
_PERMIT, _FORBID, _ERROR = 0, 1, 2
_GPT = 3

# Monotonic count of kernel TRACES (not executions): every jitted match
# function bumps it from inside its traced body, which Python runs exactly
# once per (shape, dtype, static-arg) cache miss. TPUPolicyEngine.warmup()
# and tests/test_pipeline.py read it to prove a claim no wall-clock
# measurement can: that a post-warmup request at any batch bucket triggers
# ZERO new compiles (a fresh trace inside a request deadline is the r02
# selector1k collapse).
_TRACE_COUNT = 0


def kernel_trace_count() -> int:
    """Total jitted-kernel traces since import (see _note_trace)."""
    return _TRACE_COUNT


def _note_trace() -> None:
    global _TRACE_COUNT
    _TRACE_COUNT += 1


@jax.named_scope("cedar.match.score")
def _scores(lit, Wc):
    """lit [B, L] int8 @ Wc [L, Rc] int8, accumulated in int32 (exact)."""
    return jnp.dot(lit, Wc, preferred_element_type=jnp.int32)


@jax.named_scope("cedar.match.activation")
def _lit_matrix(active, L: int):
    """active [B, A] int -> {0,1} int8 literal matrix [B, L]. Out-of-range
    ids (the pad value) simply never match the iota."""
    a32 = active.astype(jnp.int32)
    iota = jnp.arange(L, dtype=jnp.int32)
    return (a32[:, :, None] == iota[None, None, :]).any(axis=1).astype(jnp.int8)


@jax.named_scope("cedar.match.scan")
def _first_match(
    lit, W_chunks, thresh_c, group_c, policy_c, n_groups: int,
    want_bits: bool = False,
):
    """Scan rule chunks; running per-group (min, max) matched policy index —
    first [B, G] int32 (INT32_MAX = none), last [B, G] int32 (-1 = none).
    min != max detects multiple DISTINCT matched policies exactly: a single
    policy lowered to several DNF rules shares one policy index, so it never
    false-positives the multi flag.

    With want_bits the scan ALSO emits the packed per-rule satisfaction
    bitset [B, R // 32] uint32 (the diagnostics payload) from the same
    scores matmul — no second device pass."""
    B = lit.shape[0]

    def body(carry, xs):
        first_acc, last_acc = carry
        Wc, tc, gc, pc = xs
        scores = _scores(lit, Wc)  # [B, Rc]
        sat = scores >= tc[None, :]
        masked_min = jnp.where(sat, pc[None, :], INT32_MAX)  # [B, Rc]
        masked_max = jnp.where(sat, pc[None, :], -1)
        mins = [
            jnp.min(jnp.where((gc == g)[None, :], masked_min, INT32_MAX), axis=1)
            for g in range(n_groups)
        ]
        maxs = [
            jnp.max(jnp.where((gc == g)[None, :], masked_max, -1), axis=1)
            for g in range(n_groups)
        ]
        y = _pack_sat_bits(sat) if want_bits else None
        return (
            jnp.minimum(first_acc, jnp.stack(mins, axis=1)),
            jnp.maximum(last_acc, jnp.stack(maxs, axis=1)),
        ), y

    init = (
        jnp.full((B, n_groups), INT32_MAX, dtype=jnp.int32),
        jnp.full((B, n_groups), -1, dtype=jnp.int32),
    )
    (first, last), bits = jax.lax.scan(
        body, init, (W_chunks, thresh_c, group_c, policy_c)
    )
    if want_bits:
        # scan stacks per-chunk [B, Rc/32] -> [C, B, Rc/32]; rules are
        # chunked contiguously, so transpose + reshape restores rule order
        C, Bb, w = bits.shape
        bits = jnp.transpose(bits, (1, 0, 2)).reshape(Bb, C * w)
    return first, last, bits


@jax.named_scope("cedar.match.scan")
def _first_match_seg(
    lit, W_chunks, thresh_c, policy_c, segs, n_groups: int,
    want_bits: bool = False,
):
    """Segment variant of _first_match (CEDAR_TPU_SEGRED): rules are
    group-contiguous (compiler.pack sorts by (group, policy)), so each
    chunk reduces every group over ONE static column slice — 2 passes
    over the [B, Rc] masked matrices total instead of 2 * n_groups masked
    passes. `segs` is a static per-chunk tuple of (group, start, end)
    local column ranges (padding columns excluded; they are never
    satisfied anyway). Chunks unroll as a Python loop because the segment
    lists differ per chunk — C is small (R/4096)."""
    B = lit.shape[0]
    first = jnp.full((B, n_groups), INT32_MAX, dtype=jnp.int32)
    last = jnp.full((B, n_groups), -1, dtype=jnp.int32)
    bits_parts = []
    for ci in range(W_chunks.shape[0]):
        scores = _scores(lit, W_chunks[ci])
        sat = scores >= thresh_c[ci][None, :]
        masked_min = jnp.where(sat, policy_c[ci][None, :], INT32_MAX)
        masked_max = jnp.where(sat, policy_c[ci][None, :], -1)
        # assemble the chunk's per-group reductions as ONE stacked [B, G]
        # update (a chunk holds at most one contiguous run per group), not
        # a chain of .at[] scatters — dynamic-update-slice chains compile
        # poorly (the XLA CPU emitter pathologically so at the headline
        # shape; see docs/Limitations.md)
        gmin = {g: jnp.min(masked_min[:, a:b], axis=1) for g, a, b in segs[ci]}
        gmax = {g: jnp.max(masked_max[:, a:b], axis=1) for g, a, b in segs[ci]}
        none_min = jnp.full((B,), INT32_MAX, dtype=jnp.int32)
        none_max = jnp.full((B,), -1, dtype=jnp.int32)
        first = jnp.minimum(
            first,
            jnp.stack(
                [gmin.get(g, none_min) for g in range(n_groups)], axis=1
            ),
        )
        last = jnp.maximum(
            last,
            jnp.stack(
                [gmax.get(g, none_max) for g in range(n_groups)], axis=1
            ),
        )
        if want_bits:
            bits_parts.append(_pack_sat_bits(sat))
    bits = jnp.concatenate(bits_parts, axis=1) if want_bits else None
    return first, last, bits


@jax.named_scope("cedar.match.tier_walk")
def _tier_walk(first, last, n_tiers: int):
    """Walk tiers on device -> packed uint32 verdict word per request.
    Mirrors TieredPolicyStores semantics (/root/reference
    internal/server/store/store.go:25-42): first tier with any explicit
    signal (reason or error) wins. `last` may be None (first-match-only
    callers); then the multi bit is never set."""
    B = first.shape[0]
    code = jnp.zeros((B,), jnp.uint32)
    err = jnp.zeros((B,), jnp.uint32)
    multi = jnp.zeros((B,), jnp.uint32)
    pol = jnp.full((B,), POLICY_NONE, dtype=jnp.uint32)
    done = jnp.zeros((B,), jnp.bool_)
    for t in range(n_tiers):
        p_f = first[:, t * _GPT + _PERMIT]
        f_f = first[:, t * _GPT + _FORBID]
        e_f = first[:, t * _GPT + _ERROR]
        has_p, has_f, has_e = p_f != INT32_MAX, f_f != INT32_MAX, e_f != INT32_MAX
        c_t = jnp.where(
            has_f,
            CODE_DENY,
            jnp.where(has_p, CODE_ALLOW, jnp.where(has_e, CODE_ERROR, CODE_NONE)),
        ).astype(jnp.uint32)
        pol_t = jnp.where(has_f, f_f, jnp.where(has_p, p_f, e_f)).astype(jnp.uint32)
        sig = c_t != CODE_NONE
        new = (~done) & sig
        code = jnp.where(new, c_t, code)
        pol = jnp.where(new, pol_t, pol)
        err = jnp.where(new & has_e & (has_p | has_f), jnp.uint32(1), err)
        if last is not None:
            # distinct-policy multi-match in the group that decides this
            # row's verdict (min != max): the complete reason set needs the
            # rule bitset — flag the row
            l_p = last[:, t * _GPT + _PERMIT]
            l_f = last[:, t * _GPT + _FORBID]
            l_e = last[:, t * _GPT + _ERROR]
            win_first = jnp.where(has_f, f_f, jnp.where(has_p, p_f, e_f))
            win_last = jnp.where(has_f, l_f, jnp.where(has_p, l_p, l_e))
            multi = jnp.where(
                new & sig & (win_first != win_last), jnp.uint32(1), multi
            )
        done = done | sig
    with jax.named_scope("cedar.match.word_pack"):
        return (
            (code << 30)
            | (err << 29)
            | (multi << 28)
            | (pol & jnp.uint32(POLICY_NONE))
        )


@functools.partial(jax.jit, static_argnames=("n_tiers", "want_full"))
def match_rules_device(
    active, W_chunks, thresh_c, group_c, policy_c, n_tiers: int, want_full: bool
):
    """active: [B, A] int16/int32 literal ids (pad with >= L to drop).
    W_chunks: [C, L, Rc] int8; thresh_c/group_c/policy_c: [C, Rc].

    Returns (packed uint32 [B], (first, last) [B, G] int32 pair or None).
    The full matrices are only materialized to the host when the caller
    needs them (interpreter-fallback merge or error attribution)."""
    _note_trace()
    L = W_chunks.shape[1]
    lit = _lit_matrix(active, L)
    first, last, _ = _first_match(
        lit, W_chunks, thresh_c, group_c, policy_c, n_tiers * _GPT
    )
    packed = _tier_walk(first, last, n_tiers)
    return (packed, (first, last)) if want_full else (packed, None)


@jax.named_scope("cedar.match.activation")
def _lit_matrix_codes(codes, extras, act_rows):
    """codes [B, S] int (row indices into act_rows [V, L] uint8) + extras
    [B, E] int (raw literal ids, pad >= L) -> {0,1} int8 literal matrix
    [B, L]. The activation table turns each dictionary-coded request
    feature into its precomputed literal-activation row; rows are
    OR-combined (a literal activated by two features must count once, not
    twice)."""
    L = act_rows.shape[1]
    S = codes.shape[1]
    acc = jnp.take(act_rows, codes[:, 0].astype(jnp.int32), axis=0)  # [B, L]
    for s in range(1, S):
        acc = acc | jnp.take(act_rows, codes[:, s].astype(jnp.int32), axis=0)
    if extras is not None and extras.shape[1] > 0:
        e32 = extras.astype(jnp.int32)
        iota = jnp.arange(L, dtype=jnp.int32)
        lit_e = (e32[:, :, None] == iota[None, None, :]).any(axis=1)
        acc = acc | lit_e.astype(acc.dtype)
    return acc.astype(jnp.int8)


# flagged-row compaction width: the kernel returns rule bitsets for up to
# this many flagged rows per call, IN the one result buffer that carries
# the verdict words (_pack_out): one readback a launch, so an answer that
# names several policies costs no second trip to the device. Overflow rows
# (> K flagged) fall back to match_rules_codes_bits. The buffer is
# 4 * (B + K * (2 + R/32)) bytes: 1.3 kB at one row and 164 kB from bucket
# 128 up at R=10240, clean batch or not (the D2H reading by bucket is in
# PERF.md section 6, PR 37); the in-call plane only serves latency-regime
# batches <= 4096 rows, where >128 flagged rows is vanishingly rare.
BITS_TOPK = 128


@jax.named_scope("cedar.match.bits_compact")
def _compact_flagged_bits(bits, flagged, n_valid):
    """Gather the bitset rows of flagged requests into a fixed [K, R/32]
    buffer on device: top_k over a keep-key compacts the (dynamic) flagged
    set into a static shape XLA can emit in the same executable. Returns
    (vals [K] int32 — >0 means the slot is live, idx [K] int32 row indices,
    kbits [K, R/32] uint32). Rows at or beyond n_valid (bucket padding) are
    never selected."""
    B = bits.shape[0]
    K = min(B, BITS_TOPK)
    iota = jnp.arange(B, dtype=jnp.int32)
    if n_valid is not None:
        flagged = flagged & (iota < jnp.asarray(n_valid, jnp.int32))
    key = jnp.where(flagged, jnp.int32(B) - iota, jnp.int32(0))
    vals, idx = jax.lax.top_k(key, K)
    return vals, idx, jnp.take(bits, idx, axis=0)


@jax.named_scope("cedar.match.out_pack")
def _pack_out(packed, vals, idx, kbits):
    """The one result buffer of a want_bits launch, a uint32 vector: the B
    verdict words, then vals [K] and idx [K] (int32 bit patterns), then
    kbits [K, R/32] row by row. unpack_out is its host-side inverse."""
    meta = jax.lax.bitcast_convert_type(
        jnp.concatenate([vals, idx]), jnp.uint32
    )
    return jnp.concatenate([packed, meta, kbits.reshape(-1)])


def unpack_out(host, B: int):
    """Zero-copy views of a fetched _pack_out buffer for a launch of B
    (bucket-padded) rows: (words [B] uint32, vals [K] int32, idx [K]
    int32, kbits [K, R/32] uint32)."""
    K = min(B, BITS_TOPK)
    meta = host[B : B + 2 * K].view("int32")
    return host[:B], meta[:K], meta[K:], host[B + 2 * K :].reshape(K, -1)


def _match_rules_codes_py(
    codes,
    extras,
    act_rows,
    W_chunks,
    thresh_c,
    group_c,
    policy_c,
    n_tiers: int,
    want_full: bool,
    want_bits: bool = False,
    n_valid=None,
    has_gate: bool = False,
    segs=None,
):
    """Feature-code variant of match_rules_device: the literal expansion
    happens ON DEVICE from the activation table, so the host ships one
    int16 code per feature slot (+ a few extras) instead of every active
    literal id. See compiler/table.py.

    want_full returns (packed, (first [B, G], last [B, G])): the exact
    per-group min/max matched policy indices, letting the host render
    complete diagnostics without a bitset fetch for rows where every group
    matched at most one distinct policy (min == max).

    want_bits adds the (vals, idx, kbits) triple of _compact_flagged_bits:
    rule bitsets for the rows whose verdict cannot be rendered from the
    word/first matrices alone, computed in the SAME scan — the diagnostics
    contract of cedar-go (/root/reference internal/server/store/store.go:31)
    without a second device call. Alone (the served launch) it returns ONE
    uint32 vector, words and triple together (_pack_out / unpack_out), so
    a launch has one readback; with want_full it returns (packed, (first,
    last), triple). n_valid (dynamic scalar) masks bucket-padding rows out
    of the compaction.

    has_gate: the packed set carries fallback-scope gate rules in group
    n_tiers * 3; rows with a gate hit get WORD_GATE set in their word (and
    an extra trailing column in the want_full matrices)."""
    _note_trace()
    lit = _lit_matrix_codes(codes, extras, act_rows)
    return _match_from_lit(
        lit, W_chunks, thresh_c, group_c, policy_c, n_tiers,
        want_full, want_bits, n_valid, has_gate, segs,
    )


_CODES_STATICS = ("n_tiers", "want_full", "want_bits", "has_gate", "segs")

match_rules_codes = functools.partial(
    jax.jit, static_argnames=_CODES_STATICS
)(_match_rules_codes_py)

# donated twin: the per-batch codes/extras staging transfers are dead the
# moment the literal expansion reads them, so donating lets XLA reuse
# their device buffers. On the v5e it uses one of them: an extras buffer
# of width 1 (a batch with no extras, the common SAR) becomes the [B]
# word output in place, and the code buffers and wider extras are
# reported "not usable" (chip check, PERF.md section 6, PR 32). Selected
# by the engine on TPU-class backends only: the CPU runtime may alias a
# numpy input buffer, where donation would hand the caller's (pooled,
# reused) staging array to XLA as writable scratch.
match_rules_codes_donated = functools.partial(
    jax.jit, static_argnames=_CODES_STATICS, donate_argnums=(0, 1)
)(_match_rules_codes_py)


def _match_from_lit(
    lit, W_chunks, thresh_c, group_c, policy_c, n_tiers: int,
    want_full: bool, want_bits: bool, n_valid, has_gate: bool, segs=None,
):
    """Shared post-literal-expansion body of match_rules_codes and its wire
    variant: scores + first-match reduction (segmented when `segs` is
    given, masked scan otherwise) + tier walk + gate bit + (optional)
    flagged-row bits compaction, packed behind the words into the one
    result buffer where the caller wants bits and no full matrices."""
    n_groups = n_tiers * _GPT + (1 if has_gate else 0)
    if segs is not None:
        first, last, bits = _first_match_seg(
            lit, W_chunks, thresh_c, policy_c, segs, n_groups,
            want_bits=want_bits,
        )
    else:
        first, last, bits = _first_match(
            lit, W_chunks, thresh_c, group_c, policy_c, n_groups,
            want_bits=want_bits,
        )
    packed = _tier_walk(first, last, n_tiers)
    if has_gate:
        with jax.named_scope("cedar.match.word_pack"):
            gate = (first[:, n_tiers * _GPT] != INT32_MAX).astype(jnp.uint32)
            packed = packed | (gate << 27)
    if not want_bits:
        return (packed, (first, last)) if want_full else (packed, None)
    if want_full:
        # the host walks tiers itself (interpreter-fallback merge): ANY
        # group with >1 distinct matched policy may end up deciding, so
        # flag on the full min != max test, not the device walk's verdict
        flagged = ((first != last) & (first != INT32_MAX)).any(axis=1)
        pack = _compact_flagged_bits(bits, flagged, n_valid)
        return packed, (first, last), pack
    flagged = (packed & jnp.uint32(WORD_ERR | WORD_MULTI)) != 0
    return _pack_out(packed, *_compact_flagged_bits(bits, flagged, n_valid))


@jax.named_scope("cedar.match.activation")
def _lit_matrix_codes_wire(codes8, codes_w, lo8, extras, act_rows):
    """u8-wire variant of _lit_matrix_codes: codes8 [B, S8] uint8 carries
    re-based rows for the narrow slots (0 = missing; v>0 = global row
    v + lo8[s] - 1), codes_w [B, Sw] int16/int32 carries the wide slots'
    global rows unchanged. The re-basing is one fused add on device; the
    wire saves half the per-request code bytes over the host->device link
    (the usual bottleneck — see engine._CompiledSet.wire)."""
    L = act_rows.shape[1]
    acc = None
    if codes8.shape[1]:
        c8 = codes8.astype(jnp.int32)
        c8 = jnp.where(c8 == 0, 0, c8 + (lo8[None, :] - 1))
        for s in range(c8.shape[1]):
            row = jnp.take(act_rows, c8[:, s], axis=0)
            acc = row if acc is None else acc | row
    for s in range(codes_w.shape[1]):
        row = jnp.take(act_rows, codes_w[:, s].astype(jnp.int32), axis=0)
        acc = row if acc is None else acc | row
    if acc is None:  # degenerate: no slots at all (n_slots floor is 1)
        acc = jnp.zeros((extras.shape[0], L), jnp.uint8)
    if extras is not None and extras.shape[1] > 0:
        e32 = extras.astype(jnp.int32)
        iota = jnp.arange(L, dtype=jnp.int32)
        lit_e = (e32[:, :, None] == iota[None, None, :]).any(axis=1)
        acc = acc | lit_e.astype(acc.dtype)
    return acc.astype(jnp.int8)


def _match_rules_codes_wire_py(
    codes8,
    codes_w,
    lo8,
    extras,
    act_rows,
    W_chunks,
    thresh_c,
    group_c,
    policy_c,
    n_tiers: int,
    want_full: bool,
    want_bits: bool = False,
    n_valid=None,
    has_gate: bool = False,
    segs=None,
):
    """match_rules_codes over the split u8 wire layout (see
    _lit_matrix_codes_wire and engine._CompiledSet.wire): identical
    semantics and outputs, roughly half the h2d bytes per request."""
    _note_trace()
    lit = _lit_matrix_codes_wire(codes8, codes_w, lo8, extras, act_rows)
    return _match_from_lit(
        lit, W_chunks, thresh_c, group_c, policy_c, n_tiers,
        want_full, want_bits, n_valid, has_gate, segs,
    )


match_rules_codes_wire = functools.partial(
    jax.jit, static_argnames=_CODES_STATICS
)(_match_rules_codes_wire_py)

# donated twin (see match_rules_codes_donated): codes8/codes_w/extras are
# the per-batch staging inputs; lo8 is the compiled set's resident tensor
# and must NOT be donated
match_rules_codes_wire_donated = functools.partial(
    jax.jit, static_argnames=_CODES_STATICS, donate_argnums=(0, 1, 3)
)(_match_rules_codes_wire_py)


@functools.partial(jax.jit, static_argnames=("n_groups",))
def match_rules_compact(active, W_chunks, thresh_c, group_c, policy_c, n_groups: int):
    """Full per-(tier, effect) first-match matrix [B, G] int32; INT32_MAX
    means "no rule matched". Kept for callers that always need per-group
    attribution (tests, fallback-heavy sets)."""
    _note_trace()
    L = W_chunks.shape[1]
    lit = _lit_matrix(active, L)
    first, _, _ = _first_match(lit, W_chunks, thresh_c, group_c, policy_c, n_groups)
    return first


@jax.named_scope("cedar.match.bits_pack")
def _pack_sat_bits(sat):
    """sat [B, Rc] bool -> [B, Rc // 32] uint32, little-endian bit order
    (rule r lives in word r // 32, bit r % 32). Rc is always a multiple of
    128 (compiler.pack buckets R), so the reshape is exact."""
    B, Rc = sat.shape
    s = sat.reshape(B, Rc // 32, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, None, :]
    return jnp.sum(s * weights, axis=2, dtype=jnp.uint32)


@functools.partial(jax.jit)
def match_rules_codes_bits(
    codes, extras, act_rows, W_chunks, thresh_c, group_c, policy_c
):
    """Per-rule satisfaction bitset [B, R // 32] uint32 for diagnostic
    rendering: the host maps set bits through rule_policy / rule_group to
    recover the COMPLETE matched-policy set per (tier, effect) — every
    determining policy, like cedar-go's Diagnostic.Reasons (/root/reference
    internal/server/store/store.go:31). Runs only for rows whose verdict
    word carries the multi or err flag, so the [B, R/32] readback never
    rides the hot path."""
    _note_trace()
    lit = _lit_matrix_codes(codes, extras, act_rows)

    def body(_, xs):
        Wc, tc, _gc, _pc = xs
        scores = _scores(lit, Wc)
        sat = scores >= tc[None, :]
        return None, _pack_sat_bits(sat)

    with jax.named_scope("cedar.match.scan"):
        _, bits = jax.lax.scan(
            body, None, (W_chunks, thresh_c, group_c, policy_c)
        )
    # scan stacks per-chunk [B, Rc/32] -> [C, B, Rc/32]; rules are chunked
    # contiguously, so transpose + reshape restores rule order
    C, B, w = bits.shape
    return jnp.transpose(bits, (1, 0, 2)).reshape(B, C * w)


def chunk_rules(W, thresh, rule_group, rule_policy, chunk: int = 4096):
    """Host-side: reshape [L, R] rule tensors into scan chunks [C, L, Rc]."""
    import numpy as np

    L, R = W.shape
    rc = min(chunk, R)
    while R % rc:
        rc //= 2
    C = R // rc
    W3 = np.ascontiguousarray(
        W.reshape(L, C, rc).transpose(1, 0, 2)
    )  # [C, L, Rc]
    return (
        W3,
        thresh.reshape(C, rc),
        rule_group.reshape(C, rc),
        rule_policy.reshape(C, rc),
    )


@functools.partial(jax.jit, static_argnames=("n_groups",))
def match_rules(active, W, thresh, rule_group, rule_policy, n_groups: int):
    """Unchunked single-matmul variant (small sets / compile checks).
    Returns (hits [B, G] bool, first_policy [B, G] int32)."""
    _note_trace()
    L = W.shape[0]
    lit = _lit_matrix(active, L)

    scores = _scores(lit, W)  # [B, R]
    sat = scores >= thresh[None, :]

    group_onehot = jax.nn.one_hot(rule_group, n_groups, dtype=jnp.bfloat16)  # [R, G]
    hit_counts = jnp.dot(
        sat.astype(jnp.bfloat16), group_onehot, preferred_element_type=jnp.float32
    )
    hits = hit_counts > 0.0  # [B, G]

    firsts = []
    for g in range(n_groups):
        mask = (rule_group == g)[None, :] & sat
        firsts.append(
            jnp.min(jnp.where(mask, rule_policy[None, :], INT32_MAX), axis=1)
        )
    first_policy = jnp.stack(firsts, axis=1)  # [B, G]
    return hits, first_policy


def decode_packed(word: int):
    """Host-side decode of one packed verdict word -> (code, err, policy)."""
    return (word >> 30) & 0x3, (word >> 29) & 0x1, word & POLICY_NONE
