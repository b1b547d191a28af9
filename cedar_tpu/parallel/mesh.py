"""Multi-chip sharding of the policy evaluator.

The evaluation step shards over a 2-D device mesh:

  * ``data`` axis — batch data parallelism over in-flight requests (the
    moral successor of the reference's goroutine-per-HTTP-request model,
    SURVEY.md §2.4)
  * ``policy`` axis — tensor parallelism over the rule dimension of the
    policy matrix W [L, R]: each device holds a rule shard, computes its
    shard's verdicts, and the tiny per-(tier, effect) group reductions
    all-reduce across the axis (an OR-reduction — associative, so
    shard-and-reduce is exact)

XLA inserts the collectives from sharding annotations; they ride ICI within
a slice and DCN across hosts. There is no NCCL/MPI analogue to port — the
reference has no distributed backend (SURVEY.md §2.4); this mesh IS the
distributed communication design.
"""

from __future__ import annotations

import functools
import hashlib
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.match import (
    INT32_MAX,
    _lit_matrix_codes,
    _scores,
    _tier_walk,
    match_rules,
)


def make_mesh(
    n_devices: Optional[int] = None,
    shape: Optional[Tuple[int, int]] = None,
) -> Mesh:
    """Build a (data, policy) mesh.

    ``shape`` is the EXPLICIT (data_parallel, policy_parallel)
    factorization — the deployment chooses it from its workload (wide
    batches want data shards; huge policy sets want rule shards). When
    omitted, every device goes to the policy axis: the rule dimension
    (R ~ policies x clauses) is the axis that outgrows one chip first,
    batch data parallelism is already amortized by micro-batching, and a
    policy-only split needs no cross-shard reduction of the request axis.
    """
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"mesh needs {n_devices} devices, have {len(devices)}"
            )
        devices = devices[:n_devices]
    n = len(devices)
    if shape is None:
        shape = (1, n)
    data_parallel, policy_parallel = shape
    if data_parallel * policy_parallel != n:
        raise ValueError(
            f"mesh shape {shape} needs {data_parallel * policy_parallel} "
            f"devices, have {n}"
        )
    arr = np.array(devices).reshape(data_parallel, policy_parallel)
    return Mesh(arr, ("data", "policy"))


def mesh_is_multiprocess(mesh: Mesh) -> bool:
    """True when the mesh spans devices of more than one jax process —
    the pod regime, where placement must restrict itself to addressable
    devices and step outputs must replicate so every host can read them."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def partition_hosts(mesh: Mesh) -> Dict[int, Tuple[int, ...]]:
    """Policy-partition → owning process indexes. The pod topology
    (cedar_tpu/pod/topology.py) arranges the device grid so each policy
    column lives on exactly ONE host; this map is how placement, the
    dirty-reupload pinning, and /debug/pod all agree on who that is."""
    devs = np.asarray(mesh.devices)
    return {
        p: tuple(sorted({d.process_index for d in devs[:, p].flat}))
        for p in range(devs.shape[1])
    }


def shard_policy_tensors(mesh: Mesh, W, thresh, rule_group, rule_policy):
    """Place the packed policy tensors with the rule axis sharded."""
    w_s = NamedSharding(mesh, P(None, "policy"))
    r_s = NamedSharding(mesh, P("policy"))
    return (
        jax.device_put(W, w_s),
        jax.device_put(thresh, r_s),
        jax.device_put(rule_group, r_s),
        jax.device_put(rule_policy, r_s),
    )


def sharded_match_fn(mesh: Mesh, n_groups: int):
    """A jitted evaluation step with explicit input/output shardings.

    Inputs: active [B, A] sharded over data; policy tensors sharded over the
    policy axis. Outputs replicated on policy (XLA inserts the all-reduce
    for the group-hit matmul and the cross-shard min for first-match)."""
    in_shardings = (
        NamedSharding(mesh, P("data", None)),  # active
        NamedSharding(mesh, P(None, "policy")),  # W
        NamedSharding(mesh, P("policy")),  # thresh
        NamedSharding(mesh, P("policy")),  # rule_group
        NamedSharding(mesh, P("policy")),  # rule_policy
    )
    out_shardings = (
        NamedSharding(mesh, P("data", None)),  # hits
        NamedSharding(mesh, P("data", None)),  # first_policy
    )

    @functools.partial(
        jax.jit,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
    )
    def step(active, W, thresh, rule_group, rule_policy):
        return match_rules(active, W, thresh, rule_group, rule_policy, n_groups)

    return step


# ------------------------------------------------ shard-partitioned planes

# H2D placement transfer counter: every single-device upload the
# partitioned placement performs (and every replicated re-place) bumps it,
# so a test can pin "a one-policy edit re-places exactly ONE partition"
# the same way trace counters pin compile-free swaps.
_placement_transfers = 0
_placement_lock = threading.Lock()


def placement_transfer_count() -> int:
    """Monotonic count of per-device H2D uploads performed by
    PartitionedPlanes (diff across an operation to measure it)."""
    with _placement_lock:
        return _placement_transfers


class MeshCapacityError(ValueError):
    """The rule set does not fit the per-device packed capacity: one
    partition's column count exceeds max_rules_per_partition. The fix is
    more devices on the policy axis (or a higher capacity budget) — the
    whole point of rule-axis sharding is that capacity scales with
    device count."""


def bits_rule_indices(
    bits_row: np.ndarray, col_map: Optional[np.ndarray], n_rules: int
) -> np.ndarray:
    """Set-bit positions of one device rule-bitset row as PACKED rule
    indices — the ONE decoder of the partitioned wire format, shared by
    the engine's diagnostics (_bits_groups) and the explain plane
    (sat_from_bits) so the two can never drift from the layout this
    module defines. ``col_map`` is the PartitionedPlanes global-column →
    packed-rule map (None = unpartitioned: bit position IS the rule
    index, bounded by ``n_rules``); partition padding (-1) never yields
    an index."""
    bits = np.unpackbits(
        np.ascontiguousarray(bits_row).view(np.uint8), bitorder="little"
    )
    if col_map is not None:
        mask = bits[: col_map.size].astype(bool)
        idx = col_map[np.nonzero(mask)[0]]
        return idx[(idx >= 0) & (idx < n_rules)]
    mask = bits[:n_rules].astype(bool)
    return np.nonzero(mask)[0]


def shard_partition(shard_id: str, n_partitions: int) -> int:
    """Stable (tier, bucket)-shard → mesh policy-partition assignment:
    identity-hashed like shard buckets themselves, so an edited shard
    stays on its owning device and dirties exactly one partition.
    blake2b for the same GF(2)-linearity reason as compiler/shard.py."""
    h = int.from_bytes(
        hashlib.blake2b(shard_id.encode(), digest_size=8).digest(), "big"
    )
    return h % max(1, n_partitions)


def _roundup(n: int, m: int) -> int:
    return -(-max(n, 1) // m) * m


class PartitionedPlanes:
    """Shard-aware placement of the packed policy tensors on a mesh.

    The legacy path (shard_codes_tensors) lets jax.device_put split the
    rule axis evenly — opaque slices, so ANY reload re-uploads every
    device's shard. This class instead lays the rule columns out BY
    compiler shard: each (tier, bucket) shard's rules land contiguously
    in the partition `shard_partition()` assigns, each partition pads to
    a common bucketed width, and the global arrays assemble from
    per-device pieces (jax.make_array_from_single_device_arrays). A
    reload reuses the prior placement's per-device buffers for every
    partition whose bytes are unchanged — an incremental one-shard edit
    re-uploads ONE partition's slice of W/thresh/group/policy and leaves
    every other device's HBM untouched (placement_transfer_count pins
    it).

    Column order is a permutation of the packed layout, which the
    first/last reductions never see (they reduce POLICY indices); the
    only rule-INDEX output is the diagnostics bitset, which decodes
    through ``col_map`` (global column → packed rule index, -1 padding).
    """

    def __init__(self, mesh: Mesh, n_partitions: int, r_part: int):
        self.mesh = mesh
        self.n_partitions = n_partitions
        self.r_part = r_part
        self.col_map: Optional[np.ndarray] = None
        self.shard_partition_map: Dict[str, int] = {}
        # (tensor name, partition) -> (digest, per-device single arrays)
        self._pieces: Dict[Tuple[str, int], Tuple[str, tuple]] = {}
        self.act_rows_dev = None
        self.W_dev = None
        self.thresh_dev = None
        self.rule_group_dev = None
        self.rule_policy_dev = None
        self.transfers_last_build = 0

    # ------------------------------------------------------------ building

    @staticmethod
    def plan(packed, policy_shard: Dict[str, str], n_partitions: int):
        """Per-partition packed-rule-index lists. Rules attribute through
        the pack's per-column back-map (rule_clause carries policy -1 for
        gate rules — those, and rules of unmapped policies, go to the
        residual partition 0)."""
        parts: List[List[int]] = [[] for _ in range(n_partitions)]
        sids: Dict[int, set] = {p: set() for p in range(n_partitions)}
        for r in range(packed.n_rules):
            rc = packed.rule_clause[r]
            sid = None
            if rc.pm_idx >= 0:
                sid = policy_shard.get(packed.policy_meta[rc.pm_idx].policy_id)
            p = shard_partition(sid, n_partitions) if sid is not None else 0
            parts[p].append(r)
            if sid is not None:
                sids[p].add(sid)
        return parts, sids

    @classmethod
    def build(
        cls,
        mesh: Mesh,
        packed,
        policy_shard: Dict[str, str],
        prior: "Optional[PartitionedPlanes]" = None,
        max_rules_per_partition: Optional[int] = None,
        width_align: int = 64,
    ) -> "PartitionedPlanes":
        n_parts = mesh.shape["policy"]
        parts, sids = cls.plan(packed, policy_shard, n_parts)
        widest = max(len(p) for p in parts)
        # bucketed width: small edits that grow a shard keep the layout
        # (and therefore every clean partition's bytes) stable
        r_part = _roundup(widest, width_align)
        if (
            max_rules_per_partition is not None
            and r_part > max_rules_per_partition
        ):
            raise MeshCapacityError(
                f"partitioned plane needs {r_part} rule columns per device "
                f"(widest partition {widest}), over the "
                f"{max_rules_per_partition}-column device budget with "
                f"{n_parts} device partition(s) — add devices to the "
                "policy axis"
            )
        self = cls(mesh, n_parts, r_part)
        for p, ss in sids.items():
            for sid in ss:
                self.shard_partition_map[sid] = p
        if prior is not None and (
            prior.n_partitions != n_parts or prior.r_part != r_part
        ):
            prior = None  # layout changed: nothing is reusable

        L = packed.W.shape[0]
        thresh_host = packed.thresh.astype(np.int32)
        col_map = np.full(n_parts * r_part, -1, dtype=np.int32)
        w_parts, t_parts, g_parts, p_parts = [], [], [], []
        for p, rows in enumerate(parts):
            k = len(rows)
            col_map[p * r_part : p * r_part + k] = rows
            W_p = np.zeros((L, r_part), dtype=np.int8)
            t_p = np.full((r_part,), 10**9, dtype=thresh_host.dtype)
            g_p = np.zeros((r_part,), dtype=packed.rule_group.dtype)
            pol_p = np.full(
                (r_part,), np.iinfo(np.int32).max, dtype=packed.rule_policy.dtype
            )
            if k:
                idx = np.asarray(rows, dtype=np.intp)
                W_p[:, :k] = np.asarray(packed.W, dtype=np.int8)[:, idx]
                t_p[:k] = thresh_host[idx]
                g_p[:k] = packed.rule_group[idx]
                pol_p[:k] = packed.rule_policy[idx]
            w_parts.append(W_p)
            t_parts.append(t_p)
            g_parts.append(g_p)
            p_parts.append(pol_p)
        self.col_map = col_map

        R_total = n_parts * r_part
        self.W_dev = self._assemble(
            "W", w_parts, (L, R_total), P(None, "policy"), prior
        )
        self.thresh_dev = self._assemble(
            "thresh", t_parts, (R_total,), P("policy"), prior
        )
        self.rule_group_dev = self._assemble(
            "group", g_parts, (R_total,), P("policy"), prior
        )
        self.rule_policy_dev = self._assemble(
            "policy", p_parts, (R_total,), P("policy"), prior
        )
        self.act_rows_dev = self._assemble_replicated(
            "act_rows", packed.table.rows, prior
        )
        return self

    @staticmethod
    def _digest(block: np.ndarray) -> str:
        h = hashlib.blake2b(digest_size=16)
        h.update(str(block.shape).encode())
        h.update(np.dtype(block.dtype).str.encode())
        h.update(np.ascontiguousarray(block).tobytes())
        return h.hexdigest()

    def _put(self, block: np.ndarray, device):
        global _placement_transfers
        with _placement_lock:
            _placement_transfers += 1
        self.transfers_last_build += 1
        return jax.device_put(block, device)

    def _assemble(self, name, blocks, global_shape, spec, prior):
        """One global array from per-partition host blocks, reusing the
        prior placement's per-device pieces wherever the bytes match.

        Multi-process meshes (the pod): each process uploads ONLY the
        partitions that live on its own addressable devices and hands
        jax.make_array_from_single_device_arrays its local pieces — the
        multihost global-array idiom, no collective involved. A partition
        owned elsewhere still gets its digest recorded (empty piece
        tuple) so reuse bookkeeping stays uniform, but costs this host
        zero transfers — which is exactly the per-host pinning the pod
        dirty-swap tests gate on."""
        sharding = NamedSharding(self.mesh, spec)
        devs = np.asarray(self.mesh.devices)  # [data, policy]
        proc = jax.process_index()
        pieces: List = []
        for p, block in enumerate(blocks):
            digest = self._digest(block)
            local = [d for d in devs[:, p].flat if d.process_index == proc]
            held = prior._pieces.get((name, p)) if prior is not None else None
            if held is not None and held[0] == digest:
                per_dev = held[1]
            else:
                per_dev = tuple(self._put(block, dev) for dev in local)
            self._pieces[(name, p)] = (digest, per_dev)
            pieces.extend(per_dev)
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, pieces
        )

    def _assemble_replicated(self, name, block, prior):
        digest = self._digest(block)
        proc = jax.process_index()
        held = prior._pieces.get((name, 0)) if prior is not None else None
        if held is not None and held[0] == digest:
            per_dev = held[1]
        else:
            per_dev = tuple(
                self._put(block, dev)
                for dev in np.asarray(self.mesh.devices).flat
                if dev.process_index == proc
            )
        self._pieces[(name, 0)] = (digest, per_dev)
        return jax.make_array_from_single_device_arrays(
            block.shape, NamedSharding(self.mesh, P(*([None] * block.ndim))),
            list(per_dev),
        )


# --------------------------------------------------- production codes path


def shard_codes_tensors(mesh: Mesh, act_rows, W, thresh, rule_group, rule_policy):
    """Place the feature-code evaluation tensors: activation table
    replicated (every shard expands the same request features), rule axis
    sharded."""
    rep = NamedSharding(mesh, P(None, None))
    w_s = NamedSharding(mesh, P(None, "policy"))
    r_s = NamedSharding(mesh, P("policy"))
    return (
        jax.device_put(act_rows, rep),
        jax.device_put(W, w_s),
        jax.device_put(thresh, r_s),
        jax.device_put(rule_group, r_s),
        jax.device_put(rule_policy, r_s),
    )


# pjit step factory invocations: a fresh factory call is a fresh jit (and
# a first-call trace), so tests pin "an incremental swap builds no new
# mesh step" exactly like kernel_trace_count pins the XLA planes
_step_builds = 0


def mesh_step_build_count() -> int:
    return _step_builds


def sharded_codes_match_fn(
    mesh: Mesh,
    n_tiers: int,
    has_gate: bool = False,
    donate: bool = False,
    want_full: bool = True,
    replicated_out: bool = False,
):
    """The production evaluation step, sharded: feature codes in, packed
    uint32 verdict words out. This is the step TPUPolicyEngine.match_arrays
    routes through when the engine owns a mesh.

    - codes/extras shard over ``data`` (batch parallelism);
    - W [L, R] + rule tensors shard over ``policy`` (rule parallelism);
    - each shard computes its local per-(tier, effect) first/last-match
      extrema; the cross-shard combine is a min/max all-reduce XLA inserts
      from the sharding annotations — first-match is a min-reduction, so
      shard-and-reduce is exact;
    - the tier walk runs on the replicated [B, G] extrema, and the readback
      is 4 bytes per request, sharded over data.

    Returns (packed words [B], (first [B, G], last [B, G])) — the same
    surface as ops.match.match_rules_codes(want_full=True); has_gate adds
    the fallback-scope gate column and the WORD_GATE bit exactly like the
    single-device kernel.

    donate hands the per-batch codes/extras shards back to XLA as scratch
    (ops/match.py match_rules_codes_donated has the rationale); the
    engine enables it on TPU-class backends only — the CPU runtime may
    alias numpy inputs, which the engine's staging pool reuses.

    want_full=False is the SERVING variant: the per-shard partial
    verdicts still reduce on device, but only the one packed uint32 word
    per request leaves the computation — the [B, G] first/last extrema
    never materialize as outputs, so the per-request device→host payload
    is exactly 4 bytes however many devices the rules span.

    replicated_out=True (the pod regime — mesh_is_multiprocess) gathers
    every output to all devices: on a multi-host mesh a data-sharded
    output is only partially addressable per host, so the serving host
    could not read the rows that landed on its peers. The extra
    all-gather moves 4 bytes per request for the serving word."""
    global _step_builds
    _step_builds += 1
    G = n_tiers * 3 + (1 if has_gate else 0)
    in_shardings = (
        NamedSharding(mesh, P("data", None)),  # codes [B, S]
        NamedSharding(mesh, P("data", None)),  # extras [B, E]
        NamedSharding(mesh, P(None, None)),  # act_rows [V, L]
        NamedSharding(mesh, P(None, "policy")),  # W [L, R]
        NamedSharding(mesh, P("policy")),  # thresh [R]
        NamedSharding(mesh, P("policy")),  # rule_group [R]
        NamedSharding(mesh, P("policy")),  # rule_policy [R]
    )
    out_b = P() if replicated_out else P("data")
    out_bg = P() if replicated_out else P("data", None)
    if want_full:
        out_shardings = (
            NamedSharding(mesh, out_b),  # packed words [B]
            NamedSharding(mesh, out_bg),  # first [B, G]
            NamedSharding(mesh, out_bg),  # last [B, G]
        )
    else:
        out_shardings = NamedSharding(mesh, out_b)  # packed words only

    @functools.partial(
        jax.jit,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        donate_argnums=(0, 1) if donate else (),
    )
    def step(codes, extras, act_rows, W, thresh, rule_group, rule_policy):
        lit = _lit_matrix_codes(codes, extras, act_rows)  # [B, L]
        scores = _scores(lit, W)  # [B, R] — R sharded
        sat = scores >= thresh[None, :]
        masked_min = jnp.where(sat, rule_policy[None, :], INT32_MAX)
        masked_max = jnp.where(sat, rule_policy[None, :], -1)
        firsts = []
        lasts = []
        for g in range(G):
            in_g = (rule_group == g)[None, :]
            firsts.append(
                jnp.min(
                    jnp.where(in_g, masked_min, INT32_MAX),
                    axis=1,  # cross-shard min all-reduce over the policy axis
                )
            )
            lasts.append(
                # cross-shard max all-reduce; min != max flags multi-match
                jnp.max(jnp.where(in_g, masked_max, -1), axis=1)
            )
        first = jnp.stack(firsts, axis=1)  # [B, G] replicated on policy
        last = jnp.stack(lasts, axis=1)
        packed = _tier_walk(first, last, n_tiers)
        if has_gate:
            gate = (first[:, n_tiers * 3] != INT32_MAX).astype(jnp.uint32)
            packed = packed | (gate << 27)
        if not want_full:
            return packed
        return packed, first, last

    return step


def sharded_codes_bits_fn(mesh: Mesh, replicated_out: bool = False):
    """Sharded twin of ops.match.match_rules_codes_bits: per-rule
    satisfaction bitsets [B, R // 32] for diagnostic rendering. Each shard
    packs its contiguous rule range; the output sharding along the rule-word
    axis makes the host concatenation implicit (replicated_out gathers it
    everywhere instead — the pod regime, same rationale as the match step)."""
    global _step_builds
    _step_builds += 1
    from ..ops.match import _pack_sat_bits

    in_shardings = (
        NamedSharding(mesh, P("data", None)),  # codes
        NamedSharding(mesh, P("data", None)),  # extras
        NamedSharding(mesh, P(None, None)),  # act_rows
        NamedSharding(mesh, P(None, "policy")),  # W
        NamedSharding(mesh, P("policy")),  # thresh
    )
    out_shardings = NamedSharding(
        mesh, P() if replicated_out else P("data", "policy")
    )

    @functools.partial(
        jax.jit, in_shardings=in_shardings, out_shardings=out_shardings
    )
    def step(codes, extras, act_rows, W, thresh):
        lit = _lit_matrix_codes(codes, extras, act_rows)
        scores = _scores(lit, W)
        sat = scores >= thresh[None, :]
        return _pack_sat_bits(sat)

    return step
