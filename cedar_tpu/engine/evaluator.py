"""TPU policy-evaluation engine: compile, hot-swap, batch-evaluate.

The engine owns the compiled tensor form of a tiered policy set and evaluates
micro-batches of requests on the device. It is a drop-in `evaluate` backend
for CedarWebhookAuthorizer (same (entities, request) -> (decision,
diagnostics) contract as TieredPolicyStores.is_authorized), with:

  * hybrid verdict merge: policies the compiler can't lower are evaluated by
    the interpreter per request, and the per-tier verdicts are OR-merged
    before the tier walk — semantics stay exact while lowering coverage grows
  * double-buffered hot swap: `load()` builds a fresh compiled set and swaps
    one reference; bucketed shapes mean a same-bucket reload reuses the
    compiled XLA executable (no retrace)
  * packed fast path: when no interpreter fallback is needed the tier walk
    runs ON DEVICE (ops/match.py `_tier_walk`) and the readback is one
    uint32 per request. The full per-(tier, effect) matrix is fetched only
    when a verdict word carries the err bit (a policy errored alongside a
    real match — rare) or fallback policies exist.
  * pipelined batching: large batches are split into sub-batches whose
    transfers/compute/readbacks overlap (`copy_to_host_async`), hiding the
    host<->device round-trip latency.
  * diagnostics: EXACT matched-policy sets, like cedar-go's
    Diagnostic.Reasons (/root/reference internal/server/store/store.go:31,
    rendered into admission deny messages at
    internal/server/admission/handler.go:157-164). The verdict word's multi
    bit flags rows where more than one policy matched the deciding group;
    only those rows (plus err-bit rows) pay a second device call for the
    per-rule bitset (ops/match.py match_rules_codes_bits), from which the
    host recovers every determining policy. Reason *ordering* is not a
    contract (cedar-go iterates a Go map); sets are exact.

Tier semantics mirror /root/reference internal/server/store/store.go:25-42:
first tier with any explicit signal (reasons or errors) wins; the last
tier's default applies.
"""

from __future__ import annotations

import atexit
import itertools
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..compiler.ir import CompiledPolicies
from ..compiler.lower import AUTHZ_SCHEMA_INFO, SchemaInfo, lower_tiers
from ..compiler.pack import (
    ERROR_IDX,
    FORBID_IDX,
    GROUPS_PER_TIER,
    PERMIT_IDX,
    PackedPolicySet,
    pack,
)
from ..lang.authorize import ALLOW, DENY, Diagnostics, PolicySet, Reason
from ..lang.entities import EntityMap
from ..lang.eval import Env, Request, policy_matches
from ..chaos.registry import chaos_fire
from ..lang.values import EvalError
from ..obs.trace import note_readback, sub_stage
from ..compiler.table import encode_request_codes
from ..ops.match import (
    CODE_DENY,
    CODE_ERROR,
    CODE_NONE,
    INT32_MAX,
    POLICY_NONE,
    WORD_ERR,
    WORD_GATE,
    WORD_MULTI,
    chunk_rules,
    match_rules_codes,
    match_rules_codes_bits,
    match_rules_codes_donated,
    match_rules_codes_wire,
    match_rules_codes_wire_donated,
    unpack_out,
)
from . import aot

log = logging.getLogger(__name__)

_BATCH_BUCKETS = (1, 8, 32, 128, 512, 1024, 2048, 4096, 8192, 16384, 32768)
# the extras widths a batch is padded to (the live width of its widest
# row, rounded up; fastpath._encode_chunk): every one is a shape of the
# warm ladder (_warm_shape_plan), and the widest is the native encoder's
# cap (native.DEFAULT_EXTRAS_CAP), so no natively encoded batch meets a
# shape the ladder did not compile — a wide row (selector- or group-heavy
# traffic) paying a first-hit trace is the same deadline blowout as a
# cold bucket. Width 1 carries the batch without extras, 8 and 32 the
# set-membership tests of selectors and admission objects, 256 a
# principal whose token carries an identity provider's groups (up to 200;
# the first eight policy-known ones take the ancestor slots, the rest
# ride here). Four widths and not six: each width is 13 shapes of the
# ladder an engine, and a padded column costs the device one [B, L]
# compare of a launch that reads the whole rule plane.
EXTRAS_WIDTHS = (1, 8, 32, 256)

# chunk size of the raw fast paths' encode/device overlap pipeline
# (engine/fastpath.py uses this as _RawFastPath._CHUNK); defined here so the
# warm-up ladder can pre-compile the chunk shape without an import cycle
SERVING_CHUNK = 16384
# sub-batch size for the pipelined path: large enough to amortize the
# per-call device round trip, small enough to keep several in flight
_PIPELINE_SB = 32768
_PIPELINE_MIN = 8192  # don't split batches smaller than this
# above this row count the fast paths skip the in-call diagnostics bitset
# plane (see engine/fastpath.py _BITS_INCALL_MAX, which aliases this);
# defined here so the warm-up plan knows which buckets need the want_bits
# variant without an import cycle
BITS_INCALL_MAX = 4096

# Daemon warm-up threads must not be inside an XLA call when the
# interpreter finalizes: pthread teardown mid-C++-exception aborts the
# whole process ("FATAL: exception not rethrown"). atexit runs before
# interpreter teardown, so flag shutdown and join the stragglers there.
_shutdown = threading.Event()
_live_warm_threads: set = set()


def _join_warm_threads_at_exit() -> None:
    _shutdown.set()
    for t in list(_live_warm_threads):
        t.join(timeout=120)


atexit.register(_join_warm_threads_at_exit)


def track_warm_thread(t: threading.Thread) -> None:
    """Register an external warm-up thread (e.g. the shadow rollout's
    candidate warmer) with the atexit join above: any daemon thread that
    may sit inside an XLA call at interpreter teardown aborts the whole
    process otherwise. The thread's target must poll warm_shutdown_set()
    (warmup() does) so the join cannot hang."""
    _live_warm_threads.add(t)


def untrack_warm_thread(t: threading.Thread) -> None:
    _live_warm_threads.discard(t)


def warm_shutdown_set() -> bool:
    return _shutdown.is_set()


class WireSpanError(ValueError):
    """A feature code fell outside its slot's u8 wire span (see
    _CompiledSet.pack_wire); the flat code layout must be used instead."""


# process-wide structural plane ids: every FULL compile (or topology /
# partition change, device rebuild, foreign candidate) gets a fresh id, so
# shard-scoped cache stamps can never match across structurally different
# planes even when shard generation numbers collide
_plane_structs = itertools.count(1)


@dataclass
class PlaneState:
    """Shard lineage of one compiled set — rides the _CompiledSet through
    adoptions (fleet propagation, rollout promote/rollback), so every
    engine serving the set exposes the same shard generations and a
    rollback restores exactly the generations its cache entries were
    stamped with.

    ``shard_gens`` bumps per dirty shard on an incremental reload;
    ``structural`` changes whenever the whole plane is new (full compile,
    tier-topology or partition change, device rebuild). The decision
    cache's composite generation (cedar_tpu/cache/generation.py) compares
    (structural, determining shards' gens) — an incremental adoption
    kills exactly the entries whose shard changed. The dicts are
    IMMUTABLE once published: an incremental load builds fresh copies, so
    a generation snapshot taken mid-reload stays internally consistent."""

    structural: int
    shard_gens: Dict[str, int] = field(default_factory=dict)
    shard_hashes: Dict[str, str] = field(default_factory=dict)
    policy_shard: Dict[str, str] = field(default_factory=dict)
    scope: str = "full"  # how this plane came to be serving
    dirty: Tuple[str, ...] = ()
    partition: Optional[str] = None
    pruned_policies: int = 0
    # mesh deployments: which device partition each (tier, bucket) shard's
    # rules were placed on (parallel/mesh.py PartitionedPlanes) — the map
    # an incremental reload uses to re-place ONLY the dirty shard's
    # partition, surfaced on /debug/engine
    shard_partition: Dict[str, int] = field(default_factory=dict)


def _new_warm_state(shapes: int, running: bool) -> dict:
    """A fresh warm-ladder record (TPUPolicyEngine._warm_state)."""
    return {
        "shapes": shapes,
        "compiled": 0,
        "failures": 0,
        "running": running,
        "seconds": 0.0,
        "last_error": "",
    }


def _round_bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class _StagingPool:
    """Reusable host staging buffers for bucket-padded (codes, extras)
    batches. The serial path allocated a fresh np.zeros per batch; with the
    pipelined batcher keeping `depth` batches in flight the allocator was
    both a per-batch cost and a fragmentation source, while the working set
    is a handful of (bucket, width) shapes that repeat forever. Buffers are
    handed back AFTER the batch's finish() materializes its outputs — the
    device has fully consumed the inputs by then, so reuse is safe even on
    backends that zero-copy numpy inputs (the CPU runtime may alias them;
    releasing at dispatch time would let a later batch overwrite rows an
    in-flight computation is still reading).

    A buffer whose release is skipped (an exception unwound past finish) is
    simply garbage-collected — the pool holds no record of outstanding
    buffers, so it can neither leak nor double-hand one out.

    Occupancy accounting: acquire/release maintain an outstanding-buffer
    count and its peak. A batch holds its staging buffers from encode
    until its finish() materializes, so ``peak_outstanding`` exceeding
    one batch's buffer count is direct evidence that a second batch's
    H2D staging overlapped the first batch's device evaluation — the
    double-buffering claim bench.py --steady gates on (stats())."""

    def __init__(self, max_per_key: int = 8):
        self._free: dict = {}  # (shape, dtype str) -> [ndarray]
        self._lock = threading.Lock()
        self._max_per_key = max_per_key
        self._outstanding = 0
        self._peak_outstanding = 0
        self._acquires = 0
        # acquires issued while other buffers were already out — steady
        # state under the pipelined batcher keeps this climbing; the
        # serial path (one batch at a time, released before the next
        # encode) still overlaps within a batch (codes + extras), so the
        # honest overlap signal is peak_outstanding, not this counter
        self._overlapped_acquires = 0

    def acquire(self, shape, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            self._acquires += 1
            if self._outstanding > 0:
                self._overlapped_acquires += 1
            self._outstanding += 1
            if self._outstanding > self._peak_outstanding:
                self._peak_outstanding = self._outstanding
            bufs = self._free.get(key)
            if bufs:
                return bufs.pop()
        # caller fills EVERY row (payload + pad): no zeroing here
        return np.empty(shape, dtype=dtype)

    def release(self, *arrays) -> None:
        with self._lock:
            self._outstanding = max(0, self._outstanding - len(arrays))
            for a in arrays:
                key = (a.shape, a.dtype.str)
                bufs = self._free.setdefault(key, [])
                if len(bufs) < self._max_per_key:
                    bufs.append(a)

    def stats(self) -> dict:
        with self._lock:
            return {
                "outstanding": self._outstanding,
                "peak_outstanding": self._peak_outstanding,
                "acquires": self._acquires,
                "overlapped_acquires": self._overlapped_acquires,
            }


class _WordPacker:
    """Batch-wide packed D2H transfer for verdict words.

    The raw fast paths launch a batch as several overlapped chunks, and
    each chunk's finish() used to materialize its own [B] uint32 word
    array — one device round trip per chunk, so a 65k-row batch paid 4-6
    serial readbacks on the high-RTT serving link. The packer instead
    collects every chunk's DEVICE word array; flush() concatenates them
    into one packed output buffer on device (a trivial [n] u32 copy
    kernel) and starts a single async D2H for the whole batch; view()
    hands each chunk its rows as a zero-copy numpy view of the one host
    buffer, which the decode stage (and _decode_word_payload's word-cache
    lookups) consume directly.

    Single-chunk batches skip the concat — flush() just starts the same
    async copy the unpacked path would have, so a lone request's p99 is
    byte-for-byte the old path. Not used for want_bits launches (each
    already brings everything home in its own one buffer), want_full
    launches (the [B, G] matrices dominate the transfer) or mesh engines
    (concatenating sharded outputs would force a reshard)."""

    def __init__(self):
        self._parts: list = []  # device word arrays, padded lengths
        self._offsets: list = []
        self._packed = None  # device array after flush
        self._host: Optional[np.ndarray] = None
        self._flushed = False

    def add(self, words_dev) -> int:
        """Register one chunk's device word array; returns its part id."""
        if self._flushed:
            raise RuntimeError("_WordPacker: add() after flush()")
        self._offsets.append(
            self._offsets[-1] + self._parts[-1].shape[0]
            if self._parts
            else 0
        )
        self._parts.append(words_dev)
        return len(self._parts) - 1

    def flush(self) -> None:
        """Pack every registered part into one device buffer and start
        the single async D2H copy. Idempotent."""
        if self._flushed:
            return
        self._flushed = True
        if not self._parts:
            return
        if len(self._parts) == 1:
            self._packed = self._parts[0]
        else:
            import jax.numpy as jnp

            self._packed = jnp.concatenate(self._parts)
        try:
            self._packed.copy_to_host_async()
        except AttributeError:  # non-jax array (tests)
            return
        note_readback(self._packed.size * self._packed.dtype.itemsize)

    def view(self, part: int, m: int) -> np.ndarray:
        """Rows [0, m) of `part` as a view of the packed host buffer
        (materialized once for the whole batch). Flushes defensively if
        the caller never did."""
        self.flush()
        if self._host is None:
            self._host = np.asarray(self._packed)
        lo = self._offsets[part]
        return self._host[lo : lo + m]


def _mesh_spans_processes(mesh) -> bool:
    """True for a pod mesh — devices owned by more than one jax process."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def _segment_plan(group_c: np.ndarray, n_rules: int):
    """Static per-chunk (group, start, end) column segments for the
    segmented-reduction kernel plane (ops/match.py _first_match_seg).
    group_c is the chunked [C, Rc] rule-group layout; rules are
    group-contiguous after pack's (group, policy) sort, so each chunk
    holds at most a handful of runs. Padding columns (>= n_rules, never
    satisfied) are excluded outright."""
    C, rc = group_c.shape
    segs = []
    for ci in range(C):
        limit = min(rc, max(0, n_rules - ci * rc))
        cols = group_c[ci]
        runs = []
        j = 0
        while j < limit:
            g = int(cols[j])
            k = j
            while k < limit and cols[k] == g:
                k += 1
            runs.append((g, j, k))
            j = k
        # the kernel's per-chunk {group: reduction} assembly keeps ONE
        # entry per group — valid only while pack's (group, policy) sort
        # yields one contiguous run per group per chunk. A layout change
        # that breaks that must fail the compile, not mis-reduce silently.
        if len({g for g, _a, _b in runs}) != len(runs):
            raise AssertionError(
                f"rule layout not group-contiguous in chunk {ci}: {runs}"
            )
        segs.append(tuple(runs))
    return tuple(segs)


class _CompiledSet:
    """Immutable device-resident compiled policy set (the swap unit)."""

    def __init__(
        self, packed: PackedPolicySet, device=None,
        mesh=None, segred: "Optional[bool]" = None, plane_info=None,
        prior: "Optional[_CompiledSet]" = None,
        max_rules_per_partition: Optional[int] = None,
    ):
        """plane_info/prior/max_rules_per_partition drive MESH placement:
        with shard lineage (plane_info["policy_shard"]) the rule columns
        lay out by compiler shard (parallel/mesh.py PartitionedPlanes)
        and `prior`'s per-device pieces are reused for every partition
        whose bytes are unchanged — an incremental reload re-uploads one
        partition. max_rules_per_partition is the per-device packed
        capacity budget (MeshCapacityError when exceeded)."""
        import os

        self.packed = packed
        self.mesh = mesh
        # shard lineage (PlaneState), attached by the engine load paths;
        # None for externally assembled sets (tests, legacy embedders)
        self.plane: Optional[PlaneState] = None
        # the PartitionSpec this set was PRUNED under (+ the unpruned tier
        # stack for non-conforming requests) — attached by load() so the
        # serving-path conformance gate always matches the plane it guards:
        # a spec installed or cleared mid-flight takes effect only when a
        # load() produces a plane compiled under it
        self.partition_spec = None
        self.retained_tiers: Optional[list] = None
        # literal/code ids fit int16 whenever the id space allows — halves
        # the per-request transfer
        self.active_dtype = np.int16 if packed.L < 32767 else np.int32
        self.code_dtype = packed.table.code_dtype
        # fused multi-tenant plane (cedar_tpu/tenancy): (slot column,
        # {value_key: feature row}) of the reserved tenant discriminator
        # slot, or None. The raw fast paths stamp each request's tenant
        # code into this column post-encode (the body itself carries no
        # tenant), which is ALL the device plane needs — the tenant
        # literal then masks foreign rules like any other EQ test.
        self.tenant_column = None
        table = packed.table
        if table is not None:
            from ..compiler.pack import TENANT_SLOT

            tcol = table.scalar_slot_of.get(TENANT_SLOT)
            if tcol is not None:
                self.tenant_column = (
                    tcol,
                    dict(table.scalar_vocab.get(TENANT_SLOT, {})),
                )
        # u8 wire plan (set below for the single-device plane): slots
        # whose nonzero row span fits 255 ship ONE byte per request, re-based
        # on device (ops/match.py match_rules_codes_wire): half the code
        # bytes per request over the host->device link.
        # CEDAR_TPU_WIRE_U8=0 restores the flat layout.
        self.wire = None
        self.lo8_dev = None
        self._wire_pad8 = 0
        self._wire_padw = 0
        self.segs = None  # segmented-reduction plan (set below; not mesh)
        # the one scoring plane: W ships as int8 (compiler.pack writes it
        # so) with int32 accumulation and int32 thresholds — exact
        # (ops/match.py module docstring)
        thresh_host = packed.thresh.astype(np.int32)
        # mesh deployments: global column → packed rule index map when the
        # rule axis is laid out by compiler shard (None otherwise); bits
        # decode translates through it (_bits_groups)
        self.col_map = None
        self._mesh_planes = None
        if mesh is not None:
            # multi-chip: tensors placed with the (data, policy)
            # shardings; the engine routes evaluation through the pjit
            # steps in parallel/mesh.py. No chunked plane — the policy
            # axis shards replace the scan chunking.
            policy_shard = (
                dict(plane_info.get("policy_shard", ()))
                if plane_info
                else {}
            )
            if not policy_shard and _mesh_spans_processes(mesh):
                raise RuntimeError(
                    "a multi-process (pod) mesh needs shard lineage for "
                    "host-aware placement: load with incremental "
                    "compilation on (CEDAR_TPU_INCREMENTAL=1) so the "
                    "plane carries policy_shard"
                )
            if policy_shard:
                # shard-partitioned placement: each (tier, bucket) shard
                # owns a stable device partition, so an incremental
                # reload re-places only the dirty shard's partition
                from ..parallel.mesh import PartitionedPlanes

                prior_planes = None
                if prior is not None and prior.mesh is mesh:
                    prior_planes = prior._mesh_planes
                planes = PartitionedPlanes.build(
                    mesh,
                    packed,
                    policy_shard,
                    prior=prior_planes,
                    max_rules_per_partition=max_rules_per_partition,
                )
                self._mesh_planes = planes
                self.act_rows_dev = planes.act_rows_dev
                self.W_dev = planes.W_dev
                self.thresh_dev = planes.thresh_dev
                self.rule_group_dev = planes.rule_group_dev
                self.rule_policy_dev = planes.rule_policy_dev
                self.col_map = planes.col_map
                return
            from ..parallel.mesh import shard_codes_tensors

            (
                self.act_rows_dev,
                self.W_dev,
                self.thresh_dev,
                self.rule_group_dev,
                self.rule_policy_dev,
            ) = shard_codes_tensors(
                mesh,
                packed.table.rows,
                jax.numpy.asarray(packed.W, jax.numpy.int8),
                thresh_host,
                packed.rule_group,
                packed.rule_policy,
            )
            return
        kwargs = {"device": device} if device is not None else {}
        W3, thresh_c, group_c, policy_c = chunk_rules(
            packed.W, thresh_host,
            packed.rule_group, packed.rule_policy,
        )
        # segmented-reduction plane (opt-in, CEDAR_TPU_SEGRED=1): rules
        # are group-contiguous (pack sorts by (group, policy)), so each
        # chunk's per-group first/last-match reduces over one static
        # column slice instead of n_groups masked passes — a candidate
        # 2-4x cut of the scan's non-matmul device cost, not measured on
        # the chip (a benchmark cell does that before any default flip).
        # COST: segs is a jit-static tuple derived from the rule layout,
        # so a hot swap to a differently-laid-out set recompiles the match
        # kernel (in the background warm ladder, like other shape changes)
        # and each distinct layout retains its executables in the jit
        # cache — acceptable for an experimental plane, documented in
        # docs/Limitations.md alongside the flip criteria
        self.segs = None
        use_segred = (
            segred
            if segred is not None
            else os.environ.get("CEDAR_TPU_SEGRED", "0") == "1"
        )
        if use_segred:
            self.segs = _segment_plan(group_c, packed.n_rules)
        self.W_dev = jax.device_put(W3, **kwargs)
        self.thresh_dev = jax.device_put(thresh_c, **kwargs)
        self.rule_group_dev = jax.device_put(group_c, **kwargs)
        self.rule_policy_dev = jax.device_put(policy_c, **kwargs)
        self.act_rows_dev = jax.device_put(packed.table.rows, **kwargs)
        if os.environ.get("CEDAR_TPU_WIRE_U8", "1") != "0":
            ranges = packed.table.slot_row_ranges()
            idx8 = [
                s
                for s, (lo, hi) in enumerate(ranges)
                if hi - max(lo, 1) + 1 <= 255
            ]
            if idx8:
                in8 = set(idx8)
                idx16 = [
                    s for s in range(packed.table.n_slots) if s not in in8
                ]
                lo8 = np.array(
                    [max(ranges[s][0], 1) for s in idx8], np.int32
                )
                # lane widths bucket to multiples of 2 (zero-padded
                # columns; code 0 gathers the all-zero row, so padding
                # activates nothing): a reload that nudges one slot's
                # span across 255 then usually keeps both jitted input
                # shapes — preserving the retrace-free hot-swap property
                # the table's own row bucketing exists for — and unrelated
                # same-sized sets share more of the jit cache. Bucket 2,
                # not 4: every pad column is a shipped byte (u8) or two
                # (wide), and the wide lane is typically 0-2 slots
                self._wire_pad8 = -len(idx8) % 2
                self._wire_padw = -len(idx16) % 2 if idx16 else 0
                self.wire = (
                    np.array(idx8, np.intp),
                    np.array(idx16, np.intp),
                    lo8,
                )
                self.lo8_dev = jax.device_put(
                    np.concatenate(
                        [lo8, np.ones(self._wire_pad8, np.int32)]
                    ),
                    **kwargs,
                )

    def pack_wire(self, codes):
        """Split + re-base a [B, n_slots] code array into the u8 wire
        layout (codes8 u8, codes_w code_dtype) exactly as the device
        kernel expects it — the ONE definition of the wire transform,
        shared by the serving path (match_arrays_launch) and the bench so
        the two can never drift.

        Raises WireSpanError when any code falls outside its slot's
        promised [lo8, lo8+254] span: the uint8 cast would silently wrap
        and gather a WRONG activation row on device. A span violation
        means the codes were produced against a different table than this
        set's wire plan (encoder/set mismatch) — the caller falls back to
        the flat layout, which carries full-width codes."""
        idx8, idx16, lo8 = self.wire
        B = codes.shape[0]
        c8 = codes[:, idx8]
        if not ((c8 == 0) | ((c8 >= lo8) & (c8 - lo8 + 1 <= 255))).all():
            bad = np.nonzero(~((c8 == 0) | ((c8 >= lo8) & (c8 - lo8 + 1 <= 255))))
            raise WireSpanError(
                f"u8 wire span violation at (row, slot) {tuple(zip(*[b[:4].tolist() for b in bad]))}: "
                "codes out of the slot's promised 255-row span"
            )
        c8 = np.where(c8 == 0, 0, c8 - lo8 + 1).astype(np.uint8)
        if self._wire_pad8:
            c8 = np.concatenate(
                [c8, np.zeros((B, self._wire_pad8), np.uint8)], axis=1
            )
        # normalize the wide lane to the set's code dtype no matter what
        # the caller handed in (the C++ encoder emits int32)
        cw = np.ascontiguousarray(codes[:, idx16]).astype(
            self.code_dtype, copy=False
        )
        if self._wire_padw:
            cw = np.concatenate(
                [cw, np.zeros((B, self._wire_padw), cw.dtype)], axis=1
            )
        return c8, cw


class TPUPolicyEngine:
    def __init__(
        self,
        schema: Optional[SchemaInfo] = None,
        device=None,
        mesh=None,
        segred: Optional[bool] = None,
        name: str = "engine",
        warm_max_batch: int = 512,
        incremental: Optional[bool] = None,
        shard_buckets: Optional[int] = None,
        partition=None,
        mesh_device_rules: Optional[int] = None,
        lower_opts=None,
    ):
        """mesh: an optional jax.sharding.Mesh with ("data", "policy") axes
        (parallel.mesh.make_mesh). When set, compiled sets are placed with
        the (data, policy) shardings and every device call routes through
        the pjit steps — batch rows shard over `data`, the rule matmul over
        `policy`, with XLA inserting the cross-shard min/max reductions.

        segred: force the segmented-reduction kernel plane on/off for this
        engine's compiled sets; None defers to CEDAR_TPU_SEGRED (default
        off). Passed per engine — never by mutating process env — so one
        serving process can mix planes (the webhook CLI enables it on the
        CPU backend, where it measures 2-6x at serving chunk sizes).

        name labels the engine's metrics (cedar_engine_warmup_seconds);
        warm_max_batch bounds the batch-bucket ladder warm-up compiles
        (load-time warm threads and warmup() without an explicit
        max_batch) — the webhook CLI sets it to the server's max_batch so
        no production bucket ever pays a first-request trace.

        incremental: shard-granular compilation (compiler/shard.py) —
        load() diffs per-shard content hashes and re-lowers only the
        dirty shards, reassembling the fused plane from cached slices.
        None defers to CEDAR_TPU_INCREMENTAL (default on).
        shard_buckets: buckets per tier (CEDAR_TPU_SHARD_BUCKETS, 64).
        partition: an analysis.partition.PartitionSpec naming this
        serving process's request universe — never-matching policies are
        pruned from the device plane (paged off), and non-conforming
        requests answer via an exact interpreter walk over the retained
        tier stack instead of the pruned plane.
        mesh_device_rules: per-device packed rule-column capacity for
        mesh deployments (CEDAR_TPU_MESH_DEVICE_RULES; None = unbounded).
        With shard-partitioned placement the rule set may exceed ONE
        device's budget as long as each partition fits — capacity scales
        with the policy-axis device count; a set that cannot fit raises
        MeshCapacityError at load."""
        import os

        self.schema = schema or AUTHZ_SCHEMA_INFO
        # lowering feature gates (compiler/lower.LowerOptions); None = the
        # full compiler. bench.py --coverage builds LEGACY_OPTS engines to
        # measure each newly-lowered family's fallback-vs-device ratio
        # with the same code on both sides.
        self.lower_opts = lower_opts
        self.device = device
        self.mesh = mesh
        self.name = name
        self.warm_max_batch = warm_max_batch
        self.segred = segred
        # bucket-padded staging buffers, reused across batches (returned
        # by each launch's finish()); shared by every caller of this engine
        self._staging = _StagingPool()
        # donate the per-batch codes/extras device buffers on the TPU
        # (ops/match.py *_donated): inputs are dead after the
        # literal expansion, and with pipeline-depth batches in flight they
        # are the footprint term that scales. Never on CPU — the runtime
        # may alias numpy inputs, and the staging pool reuses those arrays.
        backend = jax.default_backend()
        donate_env = os.environ.get("CEDAR_TPU_DONATE", "1") != "0"
        self._donate = backend == "tpu" and mesh is None and donate_env
        # mesh twin: the pjit steps take the same donation (their own jit,
        # so the flag threads through _mesh_step instead)
        self._mesh_donate = backend == "tpu" and mesh is not None and donate_env
        self._compiled: Optional[_CompiledSet] = None
        # monotonic count of successful load() swaps: decision-cache
        # generations fold this in so entries computed from an older
        # compiled set die when the engine actually starts serving the new
        # one (store content generations alone bump at CONTENT change,
        # which precedes the async recompile by up to a reloader tick)
        self.load_generation = 0
        # shard-granular incremental compilation (compiler/shard.py)
        if incremental is None:
            incremental = os.environ.get("CEDAR_TPU_INCREMENTAL", "1") != "0"
        self.incremental = bool(incremental)
        # 0/None both defer to the env default (the CLI passes 0 through)
        self.shard_buckets = int(
            shard_buckets
            or os.environ.get("CEDAR_TPU_SHARD_BUCKETS", "64")
        )
        if mesh_device_rules is None:
            env_cap = os.environ.get("CEDAR_TPU_MESH_DEVICE_RULES", "")
            mesh_device_rules = int(env_cap) if env_cap else None
        self.mesh_device_rules = mesh_device_rules
        self._shard_compiler = None
        # monotonically unique shard generation values (never reused, so a
        # removed-then-re-added shard can't collide with old cache stamps)
        self._shard_gen_seq = itertools.count(1)
        self._last_plane = None  # PlaneState of this engine's last load()
        # the spec the NEXT load prunes under; the serving gate reads the
        # spec attached to the compiled set itself (_CompiledSet
        # .partition_spec), so mid-flight changes can't desync the two
        self._partition = partition
        # how the serving plane last changed (load scope / adoption /
        # rebuild) — /debug/engine surfaces it per engine and per replica
        self.last_adoption_scope = "none"
        self._lock = threading.Lock()
        self._mesh_steps: dict = {}  # (n_tiers, has_gate) -> pjit step
        self._mesh_bits_step = None
        # pod regime (cedar_tpu/pod): the mesh spans multiple jax
        # processes, so step outputs replicate (each host must read the
        # full result) and every device launch routes through self.pod —
        # the runtime that broadcasts the batch so all hosts enter the
        # collective together. None outside a pod; set by PodTier (leader)
        # — followers execute broadcast launches via pod.runtime helpers
        # and never originate their own.
        self._mesh_multiproc = mesh is not None and _mesh_spans_processes(mesh)
        self.pod = None
        # set once the first serving shape (b=1) of the current/previous set
        # has compiled: readiness gates on it so the first live request
        # never eats an XLA compile (latches across hot swaps — same-bucket
        # reloads reuse executables, so readiness must not flap)
        self._warm_first = threading.Event()
        self._warm_live: Optional[threading.Thread] = None
        # the last background warm ladder, as observed state (stats /
        # /debug/engine): shapes planned and compiled, shapes that RAISED
        # (a Mosaic/XLA refusal the first live batch would hit too), and
        # whether the ladder is still running
        self._warm_state = _new_warm_state(0, running=False)

    # ------------------------------------------------------------ lifecycle

    def load(self, tiers: Sequence[PolicySet], warm: str = "default") -> dict:
        """Compile + pack a tiered policy set and atomically swap it in.
        Returns compile stats.

        warm: "async" (default) kicks kernel warm-up onto a background
        daemon thread so readiness is NOT delayed by XLA compiles (the
        reference populates stores asynchronously too, /root/reference
        internal/server/store/crd.go:207); "sync" runs warm-up inline
        before returning (tests); "off" skips it. Warm-up front-loads the
        serving shapes a fresh server sees first: the latency-regime match
        shapes (with their in-call diagnostics plane) AND the standalone
        bitset kernel the throughput paths fetch flagged rows through.

        The unspecified default resolves through CEDAR_TPU_WARM_DEFAULT
        (else "async") — the test suite sets it to "off" so dozens of
        incidental engine loads don't each spawn a ~20-compile background
        ladder; explicit warm= arguments are never overridden.

        With incremental compilation (the default), only the shards whose
        content hash changed re-lower (compiler/shard.py); when the fused
        plane's jitted shapes also match the prior set's, the background
        warm ladder is SKIPPED outright — every serving executable is
        already in the shape-keyed kernel cache, so the swap is
        compile-free end to end (the `bench.py --scale` trace-counter
        pin). Returns compile stats incl. ``compile_scope``
        (full/incremental), ``dirty_shards`` and per-phase seconds."""
        import os

        if warm == "default":
            warm = os.environ.get("CEDAR_TPU_WARM_DEFAULT", "async")
        if not tiers:
            raise ValueError("TPUPolicyEngine.load: at least one tier required")
        t_start = time.monotonic()
        if self.incremental:
            if self._shard_compiler is None:
                from ..compiler.shard import ShardCompiler

                self._shard_compiler = ShardCompiler(
                    self.schema, buckets=self.shard_buckets,
                    opts=self.lower_opts,
                )
                self._shard_compiler.set_partition(self._partition)
            compiled, info = self._shard_compiler.compile(list(tiers))
            hash_s = info["phase_seconds"]["hash"]
            lower_s = info["phase_seconds"]["lower"]
        else:
            t_lower = time.monotonic()
            compiled: CompiledPolicies = lower_tiers(
                list(tiers), self.schema, opts=self.lower_opts
            )
            hash_s = 0.0
            lower_s = time.monotonic() - t_lower
            info = {
                "compile_scope": "full",
                "shards": 0,
                "dirty_shards": 0,
                "pruned_policies": 0,
            }
        t_pack = time.monotonic()
        packed = pack(compiled)
        pack_s = time.monotonic() - t_pack
        t_place = time.monotonic()
        prior = self._compiled
        new = _CompiledSet(
            packed, self.device, mesh=self.mesh,
            segred=self.segred, plane_info=info, prior=prior,
            max_rules_per_partition=self.mesh_device_rules,
        )
        place_s = time.monotonic() - t_place
        new.plane = self._next_plane(prior, info)
        if new._mesh_planes is not None:
            new.plane.shard_partition = dict(
                new._mesh_planes.shard_partition_map
            )
        if self.incremental and self._partition is not None:
            # the spec this plane was PRUNED under + the unpruned tiers
            # ride the set: the conformance gate and the plane it guards
            # can never desync across swaps/adoptions
            new.partition_spec = self._partition
            new.retained_tiers = list(tiers)
        with self._lock:
            self._compiled = new
            self.load_generation += 1
        self._last_plane = new.plane
        self.last_adoption_scope = info["compile_scope"]
        # a same-shape swap needs NO warm-up: the bucketed executables are
        # keyed by shape in the process-wide jit cache, so every serving
        # plane of the prior set serves the new one untraced
        same_shapes = (
            prior is not None
            and self._warm_first.is_set()
            and self._same_plane_shapes(prior, new)
        )
        if warm == "sync":
            if self._warm_kernels(new):
                self._warm_first.set()
        elif warm != "off" and not same_shapes:
            t = threading.Thread(
                target=self._warm_thread_main, args=(new,), daemon=True
            )
            _live_warm_threads.add(t)
            self._warm_live = t
            t.start()
        else:
            self._warm_first.set()  # skipped: intentional, or shapes warm
        total_s = time.monotonic() - t_start
        scope = info["compile_scope"]
        try:
            from ..server.metrics import (
                observe_compile_seconds,
                set_shard_state,
            )

            observe_compile_seconds("hash", scope, hash_s)
            observe_compile_seconds("lower", scope, lower_s)
            observe_compile_seconds("pack", scope, pack_s)
            observe_compile_seconds("place", scope, place_s)
            observe_compile_seconds("total", scope, total_s)
            set_shard_state(
                self.name,
                info.get("shards", 0),
                info.get("dirty_shards", 0),
                info.get("pruned_policies", 0),
            )
        except Exception:  # noqa: BLE001 — metrics never break a reload
            pass
        return {
            **compiled.stats(),
            "L": packed.L,
            "R": packed.R,
            "native_opaque_policies": packed.native_opaque,
            "compile_scope": scope,
            "shards": info.get("shards", 0),
            "dirty_shards": info.get("dirty_shards", 0),
            "pruned_policies": info.get("pruned_policies", 0),
            "warm_skipped": bool(same_shapes and warm not in ("sync",)),
            "compile_seconds": {
                "hash": round(hash_s, 4),
                "lower": round(lower_s, 4),
                "pack": round(pack_s, 4),
                "place": round(place_s, 4),
                "total": round(total_s, 4),
            },
        }

    def _next_plane(self, prior: Optional[_CompiledSet], info: dict):
        """PlaneState for a freshly compiled set: continue the prior
        plane's lineage (same structural id, dirty shards' generations
        bumped) ONLY when the prior serving plane is the one this engine's
        own last load produced — an adoption in between (promotion,
        rollback, rebuild) broke the lineage, so a fresh structural id
        conservatively kills every scoped cache stamp."""
        scope = info.get("compile_scope")
        prev_plane = getattr(prior, "plane", None) if prior is not None else None
        continues = (
            scope == "incremental"
            and prev_plane is not None
            and prev_plane is getattr(self, "_last_plane", None)
        )
        hashes = dict(info.get("shard_hashes", ()))
        if continues:
            gens = dict(prev_plane.shard_gens)
            for sid in list(gens):
                if sid not in hashes:
                    del gens[sid]
            for sid in info.get("dirty", ()):
                if sid in hashes:
                    gens[sid] = next(self._shard_gen_seq)
            for sid in hashes:
                gens.setdefault(sid, next(self._shard_gen_seq))
            structural = prev_plane.structural
        else:
            structural = next(_plane_structs)
            gens = {sid: next(self._shard_gen_seq) for sid in hashes}
        return PlaneState(
            structural=structural,
            shard_gens=gens,
            shard_hashes=hashes,
            policy_shard=dict(info.get("policy_shard", ())),
            scope=scope or "full",
            dirty=tuple(info.get("dirty", ())),
            partition=info.get("partition"),
            pruned_policies=info.get("pruned_policies", 0),
        )

    def _same_plane_shapes(self, a: "_CompiledSet", b: "_CompiledSet") -> bool:
        """True when every jitted serving shape of ``a`` also serves
        ``b`` — the warm-ladder skip condition for an incremental swap.
        Conservative: any doubt returns False and the ladder runs."""
        pa, pb = a.packed, b.packed
        if (
            pa.L != pb.L
            or pa.R != pb.R
            or pa.n_tiers != pb.n_tiers
            or pa.has_gate != pb.has_gate
            or bool(pa.fallback) != bool(pb.fallback)
            or a.code_dtype != b.code_dtype
            or a.active_dtype != b.active_dtype
            or pa.table.rows.shape != pb.table.rows.shape
            or a.segs != b.segs  # jit-static: a layout change retraces
        ):
            return False
        if (a.wire is None) != (b.wire is None):
            return False
        if a.wire is not None:
            if len(a.wire[0]) + a._wire_pad8 != len(b.wire[0]) + b._wire_pad8:
                return False
            if len(a.wire[1]) + a._wire_padw != len(b.wire[1]) + b._wire_padw:
                return False
        # mesh: the pjit step's shapes follow the PARTITIONED width, not
        # packed.R — a layout change (grown partition, device-count change)
        # must re-run the ladder even when the packed shapes agree
        ma, mb = a._mesh_planes, b._mesh_planes
        if (ma is None) != (mb is None):
            return False
        if ma is not None and (
            ma.r_part != mb.r_part or ma.n_partitions != mb.n_partitions
        ):
            return False
        return True

    def set_partition(self, spec) -> None:
        """Install (or clear) the serving-partition spec; takes effect
        ATOMICALLY at the next load() — shards re-filter against the new
        universe (paging pruned policies on/off the device plane) and the
        conformance gate follows the new plane, never the old one (the
        spec rides the compiled set, see _CompiledSet.partition_spec)."""
        self._partition = spec
        if self._shard_compiler is not None:
            self._shard_compiler.set_partition(spec)

    @property
    def partition(self):
        return self._partition

    def plane_generation(self):
        """The decision cache's composite-generation unit for this engine
        (cedar_tpu/cache/generation.py): a PlaneGenerations over the
        serving plane's shard lineage when available, else a plain tuple
        that changes on every swap (the legacy any-reload-kills-all
        posture). Cheap: wraps references, copies nothing."""
        cs = self._compiled
        if cs is None:
            return ("unloaded", self.load_generation)
        pl = cs.plane
        if pl is None:
            return ("plane", self.load_generation)
        from ..cache.generation import PlaneGenerations

        return PlaneGenerations(
            ("plane", pl.structural), pl.shard_gens, pl.policy_shard
        )

    def shard_status(self) -> dict:
        """The /debug/engine shard document: shard count/hashes, last
        reload's scope + dirty set, partition residency."""
        cs = self._compiled
        pl = cs.plane if cs is not None else None
        if pl is None:
            return {"scope": self.last_adoption_scope, "shards": 0}
        hashes = dict(sorted(pl.shard_hashes.items())[:256])
        doc = {
            "scope": pl.scope,
            "last_adoption_scope": self.last_adoption_scope,
            "shards": len(pl.shard_hashes),
            "dirty": list(pl.dirty),
            "partition": pl.partition,
            "pruned_policies": pl.pruned_policies,
            "structural": pl.structural,
            "hashes": {sid: h[:12] for sid, h in hashes.items()},
            "hashes_truncated": len(pl.shard_hashes) > 256,
        }
        # fused multi-tenant plane: per-tenant shard/dirty rollup — the
        # operator-facing proof that one tenant's edit dirtied only its
        # own (tenant, tier, bucket) shards (docs/multitenancy.md)
        from ..compiler.shard import shard_tenant

        tenants: Dict[str, dict] = {}
        for sid in pl.shard_hashes:
            t = shard_tenant(sid)
            if t is not None:
                tenants.setdefault(t, {"shards": 0, "dirty": 0})
                tenants[t]["shards"] += 1
        if tenants:
            for sid in pl.dirty:
                t = shard_tenant(sid)
                if t in tenants:
                    tenants[t]["dirty"] += 1
            doc["tenants"] = dict(sorted(tenants.items()))
        if self._partition is not None and self._shard_compiler is not None:
            # paging residency report (analysis/partition.py): what the
            # serving partition kept on the device vs paged host-side
            from ..analysis.partition import partition_report

            doc["residency"] = partition_report(
                self._partition, self._shard_compiler.shard_map()
            )
        return doc

    def warm_ready(self) -> bool:
        """True once the first serving shape has compiled (or warm-up was
        skipped/superseded): the readiness gate for a fresh server. An
        engine that has never loaded is NOT ready — answering 200 before
        the initial store load would admit traffic that later pays the
        first compile mid-flight (and flap 200->503 when the load lands)."""
        return self._warm_first.is_set()

    def warm_wait(self, timeout: Optional[float] = None) -> bool:
        """Join the current warm-up thread (tests); True when idle."""
        t = self._warm_live
        if t is None or not t.is_alive():
            return True
        t.join(timeout)
        return not t.is_alive()

    def _warm_thread_main(self, cs: "_CompiledSet") -> None:
        t0 = time.monotonic()
        first_ok = False
        try:
            first_ok = self._warm_kernels(cs)
        finally:
            # a bail (superseding load, shutdown) still latches: the new
            # load owns warming from here, and readiness must not wedge on
            # a dead thread. A first shape that RAISED does not — the b=1
            # serving shape cannot run on this device, and reading warm
            # would admit traffic straight into the interpreter fallback.
            if first_ok:
                self._warm_first.set()
            _live_warm_threads.discard(threading.current_thread())
            try:
                from ..server.metrics import set_engine_warmup_seconds

                set_engine_warmup_seconds(
                    self.name, time.monotonic() - t0
                )
            except Exception:  # noqa: BLE001 — metrics never break warm-up
                pass

    def _warm_shape_plan(
        self,
        packed: PackedPolicySet,
        max_batch: Optional[int] = None,
        extras_widths: Optional[Sequence[int]] = None,
    ) -> list:
        """The ordered (kind, batch, extras) ladder of serving shapes to
        precompile, first-hit order: the b=1 shape first (readiness gates
        on it via _warm_first), then every batch bucket up to max_batch
        (default self.warm_max_batch) at each extras width — no-extras
        requests ride width 1, selector/set-heavy requests land on the
        8/32/256 buckets (EXTRAS_WIDTHS).
        Three planes per bucket: the latency-regime fast path (want_bits
        in-call, only at buckets <= BITS_INCALL_MAX where the fast paths
        request it), the throughput/python path (plain words), and — for
        fallback sets — the want_full variant their host tier walk uses;
        plus the fixed shape of the standalone bits kernel. The raw fast
        paths' batch/replay chunk shapes come LAST — they are the most
        expensive compiles and nothing gates on them, but without them the
        first large-batch call after every hot swap eats a trace+compile
        (VERDICT r4 #8). The half-chunk is the pipeline's tail-split piece
        (fastpath._TAIL_CHUNK).

        NOTE: kind tags, not bound-method identity — `fn is
        self.match_arrays` is always False (a bound method is a fresh
        object per attribute access), which silently warmed the wrong
        want_bits variant for two rounds."""
        if extras_widths is None:
            extras_widths = EXTRAS_WIDTHS
        cap = max_batch if max_batch is not None else self.warm_max_batch
        buckets = [b for b in _BATCH_BUCKETS if b <= max(cap, 1)]
        shapes: list = [("match", 1, 1)]
        for b in buckets:
            for E in extras_widths:
                if (b, E) != (1, 1) and b <= BITS_INCALL_MAX:
                    shapes.append(("match", b, E))
                shapes.append(("plain", b, E))
                if packed.fallback:
                    shapes.append(("full", b, E))
        for E in extras_widths:
            shapes.append(("bits", self._BITS_CHUNK, E))
        for E in extras_widths:
            shapes.append(("plain", SERVING_CHUNK // 2, E))
            shapes.append(("plain", SERVING_CHUNK, E))
        return shapes

    def _warm_one(self, cs: "_CompiledSet", kind: str, b: int, E: int) -> None:
        """Compile one ladder shape by running it on all-padding rows."""
        packed = cs.packed
        warm_c = np.zeros((b, packed.table.n_slots), dtype=cs.code_dtype)
        warm_e = np.full((b, E), packed.L, dtype=cs.active_dtype)
        if kind == "match":
            self.match_arrays(warm_c, warm_e, cs=cs, want_bits=True)
        elif kind == "plain":
            self.match_arrays(warm_c, warm_e, cs=cs)
        elif kind == "full":
            self.match_arrays(warm_c, warm_e, cs=cs, want_full=True)
        else:
            self.match_bits_arrays(warm_c, warm_e, cs=cs)

    def _warm_kernels(self, cs: "_CompiledSet") -> bool:
        """Run the warm-up ladder for `cs`, off the critical path. Larger
        buckets than warm_max_batch compile on first use; every compile
        here is one the first live requests would otherwise pay. Bails out
        as soon as a hot swap supersedes `cs` — on a 1-core serving host an
        orphan compile steals the request thread's CPU.

        A shape that raises is logged with its (kind, b, E), counted in
        ``_warm_state`` (stats / /debug/engine) and skipped — it never
        takes down a swap, and it is never silent: the same refusal would
        send that shape's first live batch to the interpreter fallback.
        Returns False only when the FIRST (b=1 readiness) shape raised."""
        plan = self._warm_shape_plan(cs.packed)
        state = self._warm_state = _new_warm_state(len(plan), running=True)
        t0 = time.monotonic()
        first_ok = True
        try:
            for i, (kind, b, E) in enumerate(plan):
                if self._compiled is not cs or _shutdown.is_set():
                    break
                try:
                    self._warm_one(cs, kind, b, E)
                except Exception as e:  # noqa: BLE001 — warm-up must never take down a swap
                    log.exception(
                        "engine %s: warm ladder shape (%s, b=%d, E=%d) "
                        "failed to compile or run",
                        self.name, kind, b, E,
                    )
                    state["failures"] += 1
                    state["last_error"] = (
                        f"({kind}, {b}, {E}): {type(e).__name__}: "
                        f"{str(e)[:300]}"
                    )
                    if i == 0:
                        first_ok = False
                    continue
                state["compiled"] += 1
                if i == 0:
                    self._warm_first.set()
        finally:
            state["seconds"] = round(time.monotonic() - t0, 3)
            state["running"] = False
        return first_ok

    def warmup(
        self,
        max_batch: Optional[int] = None,
        extras_widths: Optional[Sequence[int]] = None,
        should_continue=None,
    ) -> dict:
        """Synchronously precompile EVERY (batch-bucket x extras-bucket)
        kernel plane up to max_batch (default warm_max_batch) for the
        current compiled set, so no production request at any bucket size
        ever pays a jit trace. Unlike the background ladder this runs
        inline, never bails on a concurrent swap (the caller wants THIS
        set warm), and reports what it cost: {"shapes", "seconds",
        "traces"} — traces is the number of fresh kernel compiles
        (ops.match.kernel_trace_count delta; 0 means everything was
        already warm, e.g. a same-bucket hot swap). Publishes the elapsed
        time as cedar_engine_warmup_seconds{engine=self.name}.

        should_continue: optional () -> bool polled between shapes; False
        stops the ladder early. Callers warming a set that can be
        superseded mid-ladder (the shadow rollout's candidate warmer)
        pass their liveness check here — on a small host an orphaned
        ladder of compiles steals the cpu live requests need."""
        from ..ops.match import kernel_trace_count

        cs = self._compiled
        if cs is None:
            raise RuntimeError("TPUPolicyEngine.warmup: no policy set loaded")
        t0 = time.monotonic()
        tc0 = kernel_trace_count()
        aot0 = aot.stats()
        shapes = self._warm_shape_plan(cs.packed, max_batch, extras_widths)
        for kind, b, E in shapes:
            if _shutdown.is_set() or (
                should_continue is not None and not should_continue()
            ):
                break
            self._warm_one(cs, kind, b, E)
        self._warm_first.set()
        elapsed = time.monotonic() - t0
        try:
            from ..server.metrics import set_engine_warmup_seconds

            set_engine_warmup_seconds(self.name, elapsed)
        except Exception:  # noqa: BLE001 — metrics must never break warm-up
            pass
        aot1 = aot.stats()
        out = {
            "shapes": len(shapes),
            "seconds": round(elapsed, 3),
            "traces": kernel_trace_count() - tc0,
        }
        if aot1["enabled"] or aot0["hits"] != aot1["hits"]:
            # executable-cache contribution to THIS warm ladder: all-hits
            # with traces == 0 is the warm-from-disk cold start the AOT
            # path exists for (docs/Operations.md, tests/test_aot.py)
            out["aot"] = {
                k: aot1[k] - aot0[k]
                for k in ("hits", "misses", "stale", "errors", "exports")
            }
        return out

    @property
    def compiled_set(self):
        """The live _CompiledSet (None before the first load). Exposed for
        the shadow-rollout subsystem, which moves compiled sets between a
        candidate engine and the serving engine at promotion; treat the
        object as opaque and immutable."""
        return self._compiled

    def adopt_compiled(self, compiled, donor=None) -> tuple:
        """Atomically swap in an externally compiled set — the shadow
        rollout's promotion/rollback primitive (cedar_tpu/rollout). Unlike
        load() this performs NO compilation: the set was compiled (and its
        kernel planes warmed) by a candidate engine sharing this engine's
        backend/device settings, so the jitted executables are already in
        the shared kernel cache and the first post-swap request pays no
        trace. Bumps load_generation (decision-cache composite generations
        fold it in, so every pre-swap entry dies) and latches warm
        readiness. Returns (prior compiled set, new load_generation); the
        prior set stays device-resident, so handing it back to
        adopt_compiled later (rollback) is also compile-free.

        donor: the engine that compiled/warmed `compiled`. On MESH
        deployments the pjit evaluation steps are cached per engine
        instance keyed (n_tiers, has_gate); without transplanting the
        donor's entries, a candidate whose tier count differs from the
        live set's would miss this engine's cache and the first post-swap
        request would pay a fresh pjit trace — exactly the cold-swap cost
        adoption exists to avoid. Single-device engines share the
        module-level jit caches and need no transplant."""
        if compiled is None:
            raise ValueError("adopt_compiled: compiled set required")
        if (
            donor is not None
            and self.mesh is not None
            and donor.mesh is self.mesh
        ):
            self._mesh_steps.update(donor._mesh_steps)
            if self._mesh_bits_step is None:
                self._mesh_bits_step = donor._mesh_bits_step
        with self._lock:
            prior = self._compiled
            self._compiled = compiled
            self.load_generation += 1
            generation = self.load_generation
        # shard lineage rides the set (PlaneState): every engine serving
        # it exposes the same shard generations, and /debug surfaces how
        # the plane arrived here
        pl = getattr(compiled, "plane", None)
        self.last_adoption_scope = pl.scope if pl is not None else "adopted"
        self._warm_first.set()
        return prior, generation

    def clear_compiled(self, expected=None) -> bool:
        """Drop the compiled set — the fleet's partial-failure restore for
        a replica that had NO prior set before a barrier swap
        (cedar_tpu/fleet): there is nothing to adopt back, so the
        candidate must come OUT or the replica would serve
        mixed-generation answers against the restored fleet. ``expected``
        guards against racing swaps: the clear only happens while the
        engine still holds that exact set. Bumps load_generation so any
        cached decisions from the cleared set die."""
        with self._lock:
            if expected is not None and self._compiled is not expected:
                return False
            if self._compiled is None:
                return False
            self._compiled = None
            self.load_generation += 1
        self.last_adoption_scope = "cleared"
        return True

    def rebuild_compiled(self) -> bool:
        """Re-place the CURRENT compiled set on the backend from its
        retained host-side pack — the device-loss recovery primitive
        (server/supervisor.py DeviceRecovery). The PackedPolicySet is pure
        host memory and survives any device death, so this performs no
        policy recompilation: a fresh _CompiledSet re-uploads the packed
        tensors, and the jitted kernels come from the shape-keyed cache —
        compile-free when the runtime survived (chaos drills, same-process
        resets), a re-trace off the serving path when it did not. Bumps
        load_generation so cached decisions from the dead plane die.
        Returns False with nothing loaded."""
        with self._lock:
            cs = self._compiled
        if cs is None:
            return False
        new = _CompiledSet(
            cs.packed, self.device, mesh=self.mesh, segred=self.segred,
            # keep the shard-partitioned mesh layout (and its col_map)
            # across a device loss; prior=None — the dead device's
            # buffers are exactly what must NOT be reused
            plane_info=(
                {"policy_shard": cs.plane.policy_shard}
                if cs.plane is not None
                else None
            ),
            max_rules_per_partition=self.mesh_device_rules,
        )
        # the rebuilt set serves the same pack: the partition gate (and
        # its exact-answer tier stack) must survive the device loss too
        new.partition_spec = cs.partition_spec
        new.retained_tiers = cs.retained_tiers
        if cs.plane is not None:
            # fresh structural id: cached decisions from the dead plane
            # die (PR 6 posture), even though the pack is unchanged
            new.plane = PlaneState(
                structural=next(_plane_structs),
                shard_gens=dict(cs.plane.shard_gens),
                shard_hashes=dict(cs.plane.shard_hashes),
                policy_shard=cs.plane.policy_shard,
                scope="rebuild",
                dirty=(),
                partition=cs.plane.partition,
                pruned_policies=cs.plane.pruned_policies,
            )
        with self._lock:
            # a concurrent load()/adopt_compiled() swap wins: its set is
            # newer than the one we re-placed
            if self._compiled is not cs:
                return False
            self._compiled = new
            self.load_generation += 1
        self.last_adoption_scope = "rebuild"
        return True

    def _mesh_step(self, packed: PackedPolicySet, want_full: bool = True):
        """The cached pjit evaluation step for this mesh + set shape.
        want_full=False is the serving variant: only the packed verdict
        word leaves the device — one uint32 per request across however
        many chips the rule axis spans."""
        key = (packed.n_tiers, packed.has_gate, want_full)
        fn = self._mesh_steps.get(key)
        if fn is None:
            from ..parallel.mesh import sharded_codes_match_fn

            fn = self._mesh_steps[key] = sharded_codes_match_fn(
                self.mesh, packed.n_tiers, packed.has_gate,
                donate=self._mesh_donate, want_full=want_full,
                replicated_out=self._mesh_multiproc,
            )
        return fn

    @property
    def loaded(self) -> bool:
        return self._compiled is not None

    def staging_stats(self) -> dict:
        """Staging-pool occupancy counters (overlap evidence for
        bench.py --steady; see _StagingPool)."""
        return self._staging.stats()

    @property
    def stats(self) -> dict:
        c = self._compiled
        if c is None:
            return {}
        out = {
            "rules": c.packed.n_rules,
            "lits": c.packed.n_lits,
            "L": c.packed.L,
            "R": c.packed.R,
            "fallback_policies": len(c.packed.fallback),
            "native_opaque_policies": c.packed.native_opaque,
        }
        if c.plane is not None:
            out["shard_count"] = len(c.plane.shard_hashes)
            out["compile_scope"] = c.plane.scope
            if c.plane.partition:
                out["partition"] = c.plane.partition
                out["pruned_policies"] = c.plane.pruned_policies
        out["staging"] = self._staging.stats()
        if aot.enabled():
            out["aot"] = aot.stats()
        # where this set is actually served — observed, not configured:
        # the device JAX resolved
        devices = jax.devices()
        dev = self.device if self.device is not None else devices[0]
        out["platform"] = dev.platform
        out["device_kind"] = dev.device_kind
        out["n_devices"] = len(devices)
        out["warm"] = dict(self._warm_state)
        return out

    # ----------------------------------------------------------- evaluation

    def evaluate(
        self, entities: EntityMap, request: Request
    ) -> Tuple[str, Diagnostics]:
        return self.evaluate_batch([(entities, request)])[0]

    def evaluate_batch(
        self, items: Sequence[Tuple[EntityMap, Request]]
    ) -> List[Tuple[str, Diagnostics]]:
        # the gate reads the spec off the SERVING set, not the engine: a
        # spec installed/cleared via set_partition() guards only planes
        # actually compiled under it (the engine-level field feeds the
        # next load), so gate and plane can never desync
        cs = self._compiled
        spec = cs.partition_spec if cs is not None else None
        if spec is not None:
            # partition-pruned plane: requests OUTSIDE the declared
            # universe must not be answered from it — the pruned rules
            # could have matched them. They take the exact interpreter
            # walk over the retained (unpruned) tier stack instead;
            # conforming rows ride the device exactly as without a spec.
            tiers = cs.retained_tiers or []
            overrides = {
                i: self._interpret_tiers(tiers, em, req)
                for i, (em, req) in enumerate(items)
                if not spec.conforms(em, req)
            }
            if overrides:
                rest = [
                    it for i, it in enumerate(items) if i not in overrides
                ]
                inner = self._evaluate_batch_compiled(rest) if rest else []
                out: List[Tuple[str, Diagnostics]] = []
                k = 0
                for i in range(len(items)):
                    if i in overrides:
                        out.append(overrides[i])
                    else:
                        out.append(inner[k])
                        k += 1
                return out
        return self._evaluate_batch_compiled(items)

    def _interpret_tiers(
        self, tiers: list, entities: EntityMap, request: Request
    ) -> Tuple[str, Diagnostics]:
        """Exact tiered interpreter walk over the retained (unpruned)
        policy sets — mirrors TieredPolicyStores.is_authorized INCLUDING
        its per-tier exception containment: a raising tier reads as
        deny-with-error (an explicit signal) instead of unwinding into
        the caller, where guarded_call would misread it as a device
        failure and feed a healthy plane's breaker."""
        decision, diag = DENY, Diagnostics()
        for i, ps in enumerate(tiers):
            try:
                decision, diag = ps.is_authorized(entities, request)
            except Exception as e:  # noqa: BLE001 — one sick tier must not 500
                log.exception(
                    "partition fallback tier %d evaluation failed", i
                )
                decision, diag = DENY, Diagnostics(errors=[f"tier {i}: {e}"])
            if i == len(tiers) - 1:
                break
            if decision == DENY and not diag.reasons and not diag.errors:
                continue  # no explicit signal; fall through
            break
        return decision, diag

    def _evaluate_batch_compiled(
        self, items: Sequence[Tuple[EntityMap, Request]]
    ) -> List[Tuple[str, Diagnostics]]:
        # chaos seam (docs/resilience.md): the hybrid evaluate path's
        # device launch — an injected fatal error here exercises the same
        # breaker + device-recovery machinery a real lost backend would,
        # without needing the native fast path
        chaos_fire("engine.dispatch")
        cs = self._compiled
        if cs is None:
            raise RuntimeError("TPUPolicyEngine: no policy set loaded")
        packed = cs.packed

        encoded = [
            encode_request_codes(packed.plan, packed.table, em, req)
            for em, req in items
        ]
        codes_arr, extras_arr = self._encode_batch_arrays(
            cs, encoded, len(encoded)
        )

        if packed.fallback:
            # interpreter-fallback policies can flip earlier tiers, so the
            # device tier walk is not authoritative: walk tiers host-side.
            # The (first, last) matrices give exact per-group sets wherever
            # min == max (at most one distinct policy); genuinely multi rows
            # fetch their rule bitsets in one second fixed-shape call —
            # cheaper than shipping the in-call compaction payload on every
            # batch
            _, full = self.match_arrays(
                codes_arr, extras_arr, want_full=True, cs=cs
            )
            first, last = full
            multi = np.nonzero(
                ((first != last) & (first != INT32_MAX)).any(axis=1)
            )[0]
            bits_groups = {}
            missing = multi.tolist()
            if missing:
                bits = self.match_bits_arrays(
                    codes_arr[missing], extras_arr[missing], cs=cs
                )
                for k, i in enumerate(missing):
                    bits_groups[i] = self._bits_groups(
                        packed, bits[k], cs.col_map
                    )
            return [
                self._finalize_sets(
                    packed,
                    bits_groups.get(i) or self._first_groups(packed, first[i]),
                    em,
                    req,
                )
                for i, (em, req) in enumerate(items)
            ]

        words, _ = self.match_arrays(codes_arr, extras_arr, cs=cs)
        resolved = self.resolve_flagged(
            words, codes_arr, extras_arr, cs=cs, bitmap=None
        )

        results: List[Tuple[str, Diagnostics]] = []
        for i in range(len(items)):
            if i in resolved:
                results.append(resolved[i])
            else:
                results.append(self._finalize_packed(packed, int(words[i])))
        return results

    def resolve_flagged(
        self,
        words: np.ndarray,
        codes_arr: np.ndarray,
        extras_arr: np.ndarray,
        cs: Optional["_CompiledSet"] = None,
        bitmap: Optional[dict] = None,
    ) -> dict:
        """Resolve rows whose verdict word cannot carry complete
        diagnostics — multiple distinct policies matched the deciding group
        (multi bit) or a policy errored alongside a real match (err bit).
        `bitmap` ({row index: bitset row}) is the compacted payload a
        want_bits match call already fetched with the words; rows it covers
        cost nothing extra, rows it misses (compaction overflow, batches
        launched without want_bits) fetch their bitsets in one batched
        call. Returns {row index: (decision, Diagnostics)} with the full
        reason/error sets; rows not in the dict are exactly described by
        their 4-byte word."""
        cs = cs or self._compiled
        packed = cs.packed
        w = words.astype(np.uint32)
        # WORD_GATE is ignored here on purpose: this path runs on the
        # PYTHON-encoded side, where hard literals were host-evaluated, so
        # the words/bits are authoritative even for gate-flagged rows
        # (gates exist for the NATIVE encoder's benefit — its fast paths
        # re-route gated rows before ever calling this)
        need = np.nonzero((w & (WORD_ERR | WORD_MULTI)) != 0)[0]
        out: dict = {}
        if not need.size:
            return out
        bitmap = dict(bitmap) if bitmap else {}
        missing = [i for i in need.tolist() if i not in bitmap]
        if missing:
            bits = self.match_bits_arrays(
                codes_arr[missing], extras_arr[missing], cs=cs
            )
            for k, i in enumerate(missing):
                bitmap[i] = bits[k]
        for i in need.tolist():
            groups = self._bits_groups(packed, bitmap[i], cs.col_map)
            out[i] = self._finalize_sets(packed, groups, None, None)
        return out

    def _pad_to_bucket(
        self,
        chunk_c,
        chunk_e,
        pad_L: int,
        target: Optional[int] = None,
        data_mult: int = 1,
        held: Optional[list] = None,
    ):
        """Pad a (codes, extras) chunk up to the next batch bucket — or to
        an explicit `target` row count (the fixed-shape bits kernel).
        Bucketed shapes keep the jitted executables retrace-free. Extras
        pad with >= L so padding rows activate nothing. data_mult rounds
        the row count up to a multiple of the mesh's data axis so the
        batch shards evenly.

        With `held`, the padded buffers come from the engine's staging
        pool instead of fresh np allocations and are appended to the list;
        the caller hands them back (pool.release) once the batch's
        finish() has materialized — not before: the device may still be
        reading a zero-copied input until then."""
        m = chunk_c.shape[0]
        B = target if target is not None else _round_bucket(m, _BATCH_BUCKETS)
        if data_mult > 1:
            B = -(-B // data_mult) * data_mult
        if B == m:
            return chunk_c, chunk_e
        if held is not None:
            pc = self._staging.acquire((B, chunk_c.shape[1]), chunk_c.dtype)
            pe = self._staging.acquire((B, chunk_e.shape[1]), chunk_e.dtype)
            held.extend((pc, pe))
            pc[m:] = 0  # reused buffers: the pad region must be re-filled
        else:
            pc = np.zeros((B, chunk_c.shape[1]), dtype=chunk_c.dtype)
            pe = np.empty((B, chunk_e.shape[1]), dtype=chunk_e.dtype)
        pc[:m] = chunk_c
        pe[:m] = chunk_e
        pe[m:] = pad_L
        return pc, pe

    def match_arrays(
        self,
        codes_arr: np.ndarray,
        extras_arr: np.ndarray,
        want_full: bool = False,
        cs: Optional["_CompiledSet"] = None,
        want_bits: bool = False,
    ):
        """Launch + materialize in one call (see match_arrays_launch)."""
        return self.match_arrays_launch(
            codes_arr, extras_arr, want_full=want_full, cs=cs,
            want_bits=want_bits,
        )()

    def match_arrays_launch(
        self,
        codes_arr: np.ndarray,
        extras_arr: np.ndarray,
        want_full: bool = False,
        cs: Optional["_CompiledSet"] = None,
        want_bits: bool = False,
        word_pack: Optional["_WordPacker"] = None,
        valid_rows: Optional[int] = None,
    ):
        """Device-match pre-encoded feature codes (e.g. from the native
        encoder): codes [n, S], extras [n, E] (padded with >= L). Dispatches
        every sub-batch asynchronously and returns a ``finish()`` callable;
        finish materializes (packed verdict words [n] uint32, full) where
        full is None or, with want_full, an ([n, G] first-match, [n, G]
        last-match) int32 pair. Callers overlap host work (encoding the
        next chunk) between launch and finish.
        Handles batch bucketing, dtype narrowing, and sub-batch pipelining.

        With want_bits a third element is returned: {row index: [R/32]
        uint32 bitset} for every flagged row (multi/err verdicts, or any
        multi-distinct group under want_full), compacted on device. The
        served launch (want_bits alone, one device) gets words and bitsets
        back as ONE buffer (ops/match.py _pack_out) whose single readback
        starts here at launch: finish() waits for it, makes no device
        call and starts no transfer, clean batch or flagged. With
        want_full the compaction stays a separate payload, fetched by
        finish() only when a row is flagged; a mesh launch has none
        (resolve_flagged fetches its bitsets).

        `cs` pins the compiled set the codes were encoded against — callers
        that encoded against a snapshot MUST pass it, or a concurrent policy
        hot swap would gather the codes through the new set's tables.

        `word_pack` (a _WordPacker) opts this launch's verdict words into
        the batch-wide packed D2H transfer: the device arrays register
        with the packer instead of starting their own readback, the caller
        flushes once after EVERY chunk of the batch has launched, and
        finish() consumes its rows as views of the one packed host buffer.
        Ignored (normal per-launch readback) for want_full/want_bits
        launches and mesh engines.

        `valid_rows` marks trailing rows as caller-side bucket padding
        (the fast paths' staged buffers arrive pre-padded so no copy
        happens here): the want_bits compaction excludes them, exactly as
        it excludes this function's own padding. Verdict words are still
        returned for every row; callers slice."""
        cs = cs or self._compiled
        if cs is None:
            raise RuntimeError("TPUPolicyEngine: no policy set loaded")
        packed = cs.packed
        n = codes_arr.shape[0]
        args = (
            cs.act_rows_dev,
            cs.W_dev,
            cs.thresh_dev,
            cs.rule_group_dev,
            cs.rule_policy_dev,
        )
        codes_arr = codes_arr.astype(cs.code_dtype, copy=False)
        extras_arr = extras_arr.astype(cs.active_dtype, copy=False)

        held: list = []  # pooled staging buffers, released by finish()
        # words and flagged rows' bitsets come home as one buffer
        one_buffer = want_bits and not want_full and cs.mesh is None

        # Both launch functions return (words_dev, full_dev_or_None,
        # pack_dev_or_None) — or, for a one-buffer launch, (buffer_dev,
        # None, its bucket-padded row count); m is the VALID row count
        # (excludes caller-side staging padding), used only to mask the
        # want_bits compaction. Host staging (pad to the bucket, the u8
        # wire pack) and the launch (the jitted call and the H2D it
        # implies) are timed apart: obs.trace sub_stage `dispatch.stage` — which is
        # also what a dispatch's time counts as outside every sub-stage —
        # and `dispatch.launch`.

        def mesh_launch(chunk_c, chunk_e, m):
            # multi-chip: the pjit step (parallel/mesh.py) shards the
            # batch over `data` and the rule matmul over `policy`; the
            # diagnostics bitsets come from the sharded bits step via
            # resolve_flagged instead of an in-call payload. The
            # serving (non-full) variant outputs ONLY the packed
            # word: the per-shard partial verdicts all-reduce on
            # device and 4 bytes per request come home.
            with sub_stage("dispatch.stage"):
                chunk_c, chunk_e = self._pad_to_bucket(
                    chunk_c, chunk_e, packed.L,
                    data_mult=cs.mesh.shape["data"], held=held,
                )
            with sub_stage("dispatch.launch"):
                if self.pod is not None:
                    # pod regime: broadcast the padded batch so every
                    # host enters this collective, serialized under the
                    # pod lock so dispatch order matches fleet-wide
                    w, full = self.pod.run_match(
                        self, cs, chunk_c, chunk_e, want_full
                    )
                    return w, full, None
                step_args = (chunk_c, chunk_e, *args)
                if want_full:
                    w, f, last = self._mesh_step(packed, True)(*step_args)
                    return w, (f, last), None
                w = self._mesh_step(packed, False)(*step_args)
                return w, None, None

        def device_launch(chunk_c, chunk_e, m):
            """The one single-device launch: the u8 wire layout where the
            set has a wire plan and the codes fit it, the flat layout
            otherwise — the same kernel body behind both."""
            with sub_stage("dispatch.stage"):
                chunk_c, chunk_e = self._pad_to_bucket(
                    chunk_c, chunk_e, packed.L, held=held
                )
                layout, lead = "codes", (chunk_c, chunk_e)
                if cs.wire is not None:
                    try:
                        lead = (*cs.pack_wire(chunk_c), cs.lo8_dev, chunk_e)
                        layout = "wire"
                    except WireSpanError:
                        # a span violation means these codes don't fit the
                        # u8 plan (advisor r5): serve THIS set via the flat
                        # layout from here on instead of wrapping uint8
                        # into a wrong activation row. One log; the flat
                        # kernel is correct, just a fatter transfer.
                        log.exception(
                            "u8 wire span violation; disabling the wire "
                            "layout for this compiled set (flat codes from "
                            "now on)"
                        )
                        cs.wire = None
            with sub_stage("dispatch.launch"):
                # shape-aware reduction: the segmented kernel's win is
                # measured at serving-chunk batch sizes; at super-batch
                # scale the unrolled per-chunk score intermediates cost
                # more than the masked scan saves (docs/Limitations.md).
                # Large batches therefore keep the scan even when segs
                # are enabled.
                segs = cs.segs if chunk_c.shape[0] <= SERVING_CHUNK else None
                if layout == "wire":
                    fn = (
                        match_rules_codes_wire_donated
                        if self._donate
                        else match_rules_codes_wire
                    )
                else:
                    fn = (
                        match_rules_codes_donated
                        if self._donate
                        else match_rules_codes
                    )
                out = aot.dispatch(
                    layout + "_donated" if self._donate else layout,
                    fn,
                    (
                        *lead, *args, packed.n_tiers, want_full, want_bits,
                        np.int32(m) if want_bits else None, packed.has_gate,
                        segs,
                    ),
                    aot.STATICS[layout],
                )
            if one_buffer:
                return out, None, chunk_c.shape[0]
            return out if want_bits else (*out, None)

        launch = mesh_launch if cs.mesh is not None else device_launch

        def trim_full(f, m):
            return (np.asarray(f[0])[:m], np.asarray(f[1])[:m])

        def live_rows(vals, idx, kbits, lo, bitmap):
            """A compaction's live slots (vals > 0) into the bitmap, by
            the row each slot came from."""
            live = np.nonzero(vals > 0)[0]
            for j, r in zip(live.tolist(), idx[live].tolist()):
                bitmap[lo + r] = kbits[j]

        def any_flagged(full_h):
            """want_full launches only: the host-side gate before their
            separate [K, R/32] compaction payload is fetched at all —
            the full matrices are already here, and a batch with no
            multi-distinct group skips that transfer."""
            first, last = full_h
            return bool(((first != last) & (first != INT32_MAX)).any())

        def pack_rows(pack, lo, bitmap):
            for a in pack:  # one overlapped transfer, not 3 serial RTTs
                a.copy_to_host_async()
            live_rows(*(np.asarray(a) for a in pack), lo, bitmap)

        # ---- launch: dispatch every sub-batch asynchronously. The returned
        # finish() materializes — callers that interleave host work (e.g.
        # SARFastPath encoding the next chunk) overlap it with the device.
        use_pack = (
            word_pack is not None
            and not want_full
            and not want_bits
            and cs.mesh is None
        )
        outs = []
        for lo in range(0, n, _PIPELINE_SB):
            hi = min(lo + _PIPELINE_SB, n)
            v = hi - lo if valid_rows is None else max(0, min(hi, valid_rows) - lo)
            if lo == 0 and hi == n:
                w, f, p = launch(codes_arr, extras_arr, v)
            else:
                w, f, p = launch(codes_arr[lo:hi], extras_arr[lo:hi], v)
            part = None
            with sub_stage("dispatch.readback"):
                if use_pack:
                    part = word_pack.add(w)
                else:
                    w.copy_to_host_async()
                    # size x itemsize: a jax.Array's nbytes costs 1 us
                    note_readback(w.size * w.dtype.itemsize)
                if f is not None:
                    f[0].copy_to_host_async()
                    f[1].copy_to_host_async()
            outs.append((lo, hi - lo, w, f, p, part))

        def finish():
            bitmap: dict = {}
            # the first materialization blocks until the device is done:
            # what the decode thread waits for, apart from what it does
            with sub_stage("decode.device_wait"):
                host = [
                    (
                        lo,
                        m,
                        word_pack.view(part, m)
                        if part is not None
                        else np.asarray(w),
                        trim_full(f, m) if want_full else None,
                        p,
                    )
                    for lo, m, w, f, p, part in outs
                ]
            # outputs are materialized: the device has fully consumed the
            # staged inputs, so their buffers can serve the next batch
            if held:
                self._staging.release(*held)
                del held[:]
            words = []
            for lo, m, wh, fh, p in host:
                if one_buffer:
                    # the bitsets are on the host already, behind the
                    # words: views of the one buffer, no device call
                    wh, *compaction = unpack_out(wh, p)
                    live_rows(*compaction, lo, bitmap)
                elif want_bits and p is not None and any_flagged(fh):
                    pack_rows(p, lo, bitmap)
                words.append(wh[:m])
            if len(host) == 1:
                words, full = words[0], host[0][3]
            else:
                words = np.concatenate(words)
                full = None
                if want_full:
                    full = (
                        np.concatenate([fh[0] for *_, fh, _ in host]),
                        np.concatenate([fh[1] for *_, fh, _ in host]),
                    )
            return (words, full, bitmap) if want_bits else (words, full)

        return finish

    # fixed row count for the standalone bitset kernel: every call pads to
    # exactly this many rows, so the kernel has ONE batch shape per extras
    # width — a cold call can't hit a fresh trace+compile at an arbitrary
    # bucket inside a request deadline (the r02 selector1k collapse)
    _BITS_CHUNK = 128

    def match_bits_arrays(
        self,
        codes_arr: np.ndarray,
        extras_arr: np.ndarray,
        cs: Optional["_CompiledSet"] = None,
    ) -> np.ndarray:
        """Launch + materialize in one call (see match_bits_arrays_launch)."""
        return self.match_bits_arrays_launch(codes_arr, extras_arr, cs=cs)()

    def match_bits_arrays_launch(
        self,
        codes_arr: np.ndarray,
        extras_arr: np.ndarray,
        cs: Optional["_CompiledSet"] = None,
    ):
        """Per-rule satisfaction bitsets [n, R // 32] uint32 for the given
        pre-encoded rows, as a launch + ``finish()`` pair (callers overlap
        host/device work between the two). Diagnostics path only — small
        batches get their bitsets compacted into the main match call
        (match_arrays want_bits); this one runs for large-batch flagged
        rows and compaction overflow. Rows process in
        fixed _BITS_CHUNK-sized pieces, pipelined."""
        cs = cs or self._compiled
        if cs is None:
            raise RuntimeError("TPUPolicyEngine: no policy set loaded")
        packed = cs.packed
        n = codes_arr.shape[0]
        if n == 0:
            empty = np.zeros((0, packed.R // 32), dtype=np.uint32)
            return lambda: empty
        codes_arr = codes_arr.astype(cs.code_dtype, copy=False)
        extras_arr = extras_arr.astype(cs.active_dtype, copy=False)
        CH = self._BITS_CHUNK
        if cs.mesh is not None and self._mesh_bits_step is None:
            from ..parallel.mesh import sharded_codes_bits_fn

            self._mesh_bits_step = sharded_codes_bits_fn(
                self.mesh, replicated_out=self._mesh_multiproc
            )

        held: list = []  # pooled staging buffers, released by finish()

        def one(chunk_c, chunk_e):
            if cs.mesh is not None:
                chunk_c, chunk_e = self._pad_to_bucket(
                    chunk_c, chunk_e, packed.L, target=CH,
                    data_mult=cs.mesh.shape["data"], held=held,
                )
                if self.pod is not None:
                    return self.pod.run_bits(self, cs, chunk_c, chunk_e)
                return self._mesh_bits_step(
                    chunk_c,
                    chunk_e,
                    cs.act_rows_dev,
                    cs.W_dev,
                    cs.thresh_dev,
                )
            chunk_c, chunk_e = self._pad_to_bucket(
                chunk_c, chunk_e, packed.L, target=CH, held=held
            )
            return aot.dispatch(
                "bits",
                match_rules_codes_bits,
                (
                    chunk_c,
                    chunk_e,
                    cs.act_rows_dev,
                    cs.W_dev,
                    cs.thresh_dev,
                    cs.rule_group_dev,
                    cs.rule_policy_dev,
                ),
                aot.STATICS["bits"],
            )

        outs = []
        for lo in range(0, n, CH):
            hi = min(lo + CH, n)
            b = one(codes_arr[lo:hi], extras_arr[lo:hi])
            b.copy_to_host_async()
            outs.append((hi - lo, b))

        def finish():
            out = np.concatenate([np.asarray(b)[:m] for m, b in outs])
            if held:
                self._staging.release(*held)
                del held[:]
            return out

        return finish

    # ---------------------------------------------------------- device path

    def _encode_batch_arrays(
        self, cs: _CompiledSet, encoded, B: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Pad (codes, extras) pairs into [B, S] and [B, E] arrays."""
        packed = cs.packed
        S = packed.table.n_slots
        codes_arr = np.zeros((B, S), dtype=cs.code_dtype)
        max_e = max((len(e) for _, e in encoded), default=0)
        if max_e <= EXTRAS_WIDTHS[-1]:
            E = _round_bucket(max_e, EXTRAS_WIDTHS)  # the ladder's shapes
        else:  # never truncate: dropping an extra would drop an activation
            E = -(-max_e // 128) * 128
        extras_arr = np.full((B, E), packed.L, dtype=cs.active_dtype)
        for i, (c, e) in enumerate(encoded):
            codes_arr[i] = c
            if e:
                extras_arr[i, : len(e)] = e
        return codes_arr, extras_arr

    # ------------------------------------------------- fallback + tier walk

    def _finalize_packed(
        self, packed: PackedPolicySet, word: int
    ) -> Tuple[str, Diagnostics]:
        """Decode one device verdict word (no-fallback fast path)."""
        code = (word >> 30) & 0x3
        pol = word & POLICY_NONE
        if code == CODE_NONE:
            return DENY, Diagnostics()
        meta = packed.policy_meta[pol]
        if code == CODE_ERROR:
            return DENY, Diagnostics(
                reasons=[],
                errors=[
                    f"while evaluating policy `{meta.policy_id}`: evaluation error"
                ],
            )
        reason = Reason(meta.policy_id, meta.filename, meta.position)
        decision = DENY if code == CODE_DENY else ALLOW
        return decision, Diagnostics(reasons=[reason])

    @staticmethod
    def _first_groups(packed: PackedPolicySet, first_row: np.ndarray) -> dict:
        """{group id: [policy index]} from one first-match row — exact when
        every group matched at most one rule (the caller checks counts)."""
        return {
            g: [int(p)]
            for g, p in enumerate(first_row.tolist())
            if p != INT32_MAX
        }

    @staticmethod
    def _bits_groups(
        packed: PackedPolicySet,
        bits_row: np.ndarray,
        col_map: Optional[np.ndarray] = None,
    ) -> dict:
        """Decode one rule bitset row -> {group id: [policy indices,
        ascending]} with every matched policy (deduped across the several
        DNF rules one policy may lower to).

        ``col_map`` translates shard-partitioned mesh layouts: there a
        bit's position names a PARTITIONED column, not a packed rule
        index — parallel/mesh.py bits_rule_indices (the one decoder of
        that wire format) maps it back."""
        from ..parallel.mesh import bits_rule_indices

        idx = bits_rule_indices(bits_row, col_map, packed.R)
        pols = packed.rule_policy[idx]
        grps = packed.rule_group[idx]
        valid = pols != INT32_MAX  # padding rules can never match, belt+braces
        out: dict = {}
        for g, p in zip(grps[valid].tolist(), pols[valid].tolist()):
            out.setdefault(g, set()).add(p)
        return {g: sorted(s) for g, s in out.items()}

    def _finalize_sets(
        self,
        packed: PackedPolicySet,
        groups: dict,
        entities: Optional[EntityMap],
        request: Optional[Request],
    ) -> Tuple[str, Diagnostics]:
        """Host tier walk over COMPLETE per-group policy sets (from
        _bits_groups), merged with interpreter-fallback verdicts when
        entities/request are given. Mirrors PolicySet.is_authorized +
        TieredPolicyStores semantics with full reason lists.

        TWIN: cedar_tpu/explain/attribution.py build_explanation walks
        the same tiers (same ordering, same error-string format) to
        produce attributed explanations — a semantic change here must be
        mirrored there, or ?explain answers drift from served answers
        (tests/test_explain.py's differential pins the covered cases)."""
        T = packed.n_tiers
        fb_allow: List[List[Reason]] = [[] for _ in range(T)]
        fb_deny: List[List[Reason]] = [[] for _ in range(T)]
        fb_errors: List[List[str]] = [[] for _ in range(T)]
        if packed.fallback and entities is not None:
            # fallback burn-down (ROADMAP item 3): this decision is being
            # interpreter-merged BECAUSE unlowerable policies exist —
            # count it under each distinct Unlowerable reason code so the
            # coverage drive can rank offenders by SERVED traffic, not
            # just by policy count (cedar_fallback_decisions_total{code},
            # tallied on /debug/engine)
            try:
                from ..server.metrics import record_fallback_decision

                record_fallback_decision(packed.fallback_codes, self.name)
            except Exception:  # noqa: BLE001 — metrics never break serving
                pass
            env = Env(request, entities)
            for fp in packed.fallback:
                p = fp.policy
                try:
                    if not policy_matches(p, env):
                        continue
                except EvalError as e:
                    fb_errors[fp.tier].append(
                        f"while evaluating policy `{p.policy_id}`: {e}"
                    )
                    continue
                reason = Reason(p.policy_id, p.filename, p.position)
                (fb_deny if p.effect == "forbid" else fb_allow)[fp.tier].append(reason)

        for t in range(T):
            base = t * GROUPS_PER_TIER
            deny_reasons = [
                self._meta_reason(packed, i)
                for i in groups.get(base + FORBID_IDX, ())
            ] + fb_deny[t]
            allow_reasons = [
                self._meta_reason(packed, i)
                for i in groups.get(base + PERMIT_IDX, ())
            ] + fb_allow[t]
            errors = [
                f"while evaluating policy "
                f"`{packed.policy_meta[i].policy_id}`: evaluation error"
                for i in groups.get(base + ERROR_IDX, ())
            ] + fb_errors[t]
            if deny_reasons:
                return DENY, Diagnostics(reasons=deny_reasons, errors=errors)
            if allow_reasons:
                return ALLOW, Diagnostics(reasons=allow_reasons, errors=errors)
            if errors:
                # explicit signal: stops tier descent with a reasonless deny
                return DENY, Diagnostics(reasons=[], errors=errors)
        return DENY, Diagnostics()

    @staticmethod
    def _meta_reason(packed: PackedPolicySet, idx: int) -> Reason:
        meta = packed.policy_meta[int(idx)]
        return Reason(meta.policy_id, meta.filename, meta.position)
