"""Packing: lowered rules -> device tensors + host encode plan.

The packed form is the TPU-native policy representation:

  * ``W``      [L, R] int8   — +1 literal required true, -1 required false
  * ``thresh`` [R] float32   — number of positive literals per rule; a rule is
                               satisfied iff lit-vector @ W[:, r] >= thresh[r]
  * ``rule_group``  [R] int16 — tier*3 + routing class (+ trailing gate
                               group); values stay tiny (≤ ~30 for any real
                               tier stack), so a narrow column halves its
                               per-dispatch device traffic vs int32
  * ``rule_policy`` [R] int32 — index into the policy metadata list
                               (reasons); INT32_MAX padding sentinel keeps
                               this one wide

Shapes are bucketed (L, R rounded up to power-of-two-ish buckets) so a policy
reload of similar size is a pure device-buffer swap with no XLA recompile —
the hot-swap analogue of the reference's RWMutex PolicySet update
(/root/reference internal/server/store/crd.go:45-118).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..lang.ast import Pattern, Policy
from .ir import (
    CMP,
    ClauseLit,
    CompiledPolicies,
    ENTITY_IN,
    ENTITY_IN_ANY,
    EQ,
    EQ_ENTITY,
    HARD,
    HARD_ERR,
    HARD_OK,
    HAS,
    IN_SET,
    IN_SLOT,
    IS,
    LIKE,
    Literal,
    LoweredPolicy,
    SET_HAS,
    Slot,
    TYPE_ERR,
)

PERMIT_IDX = 0
FORBID_IDX = 1
ERROR_IDX = 2
GROUPS_PER_TIER = 3
# Gate rules live in ONE extra group past the tier groups (index
# n_tiers * GROUPS_PER_TIER): a gate rule is the scope conjunction of a
# policy the NATIVE plane cannot evaluate —
#   (a) an interpreter-fallback policy (Unlowerable), or
#   (b) a lowered policy carrying a hard literal outside the native
#       dyn-contains class ("native-opaque": the Python encoder host-
#       evaluates the literal per request, the C++ encoder cannot).
# A request matching no gate rule provably matches (and errors on) no such
# policy — every clause and error clause embeds the policy's scope prefix
# (lower_policy), so the device verdict word is authoritative for it. The
# fast paths re-route only gate-flagged rows to the exact Python path (the
# hybrid successor of disabling the native plane whenever any such policy
# exists). The Python engine path fills hard literals at encode time, so
# for it only class (a) needs the host-side tier walk.
GATE_RULE_POLICY = 0  # rule_policy for gate rules: any value != INT32_MAX

# ---------------------------------------------------------------- tenancy
# The fused multi-tenant plane (cedar_tpu/tenancy) shares ONE packed rule
# space between many tenants' policy sets. Isolation rides a reserved
# context slot: every rule of tenant T gets a synthetic FIRST-conjunct EQ
# literal over ("context", ("tenantId",)) — the same mechanism the
# partition-spec corpora use for their cluster discriminators — so the
# slot-match kernel (scan and segred reductions alike) masks foreign
# tenants' rules with zero new kernel code: a request whose context
# carries tenant A's id satisfies no rule carrying tenant B's literal, INCLUDING B's error clauses (the discriminator precedes
# the error indicators, exactly like Cedar's && short-circuit kills a
# foreign policy's errors). The literal is total and access-free (the
# encoder reads a slot the front end stamps), so discrimination adds no
# error machinery of its own.
TENANT_CONTEXT_KEY = "tenantId"
TENANT_SLOT: Slot = ("context", (TENANT_CONTEXT_KEY,))

_tenant_literals: Dict[str, Literal] = {}


def tenant_literal(tenant: str) -> Literal:
    """The (memoized, per-process-singleton) tenant discriminator literal:
    one object per tenant id, so repacks re-intern the SAME literal and
    the reload-allocation counters stay honest."""
    lit = _tenant_literals.get(tenant)
    if lit is None:
        lit = _tenant_literals[tenant] = Literal(
            EQ,
            var="context",
            slot=TENANT_SLOT,
            data=("s", tenant),
            accesses=(),
            total=True,
        )
    return lit


def discriminate_lowered(lp: LoweredPolicy, tenant: str) -> LoweredPolicy:
    """A lowered policy with the tenant discriminator prepended to every
    clause AND error clause — the IR-level twin of prepending
    ``context.tenantId == "<tenant>" &&`` to the source condition, minus
    the error clauses a fallible context access would have added."""
    cl = ClauseLit(tenant_literal(tenant), False)
    return LoweredPolicy(
        policy=lp.policy,
        tier=lp.tier,
        effect=lp.effect,
        clauses=[(cl,) + tuple(c) for c in lp.clauses],
        error_clauses=[(cl,) + tuple(c) for c in lp.error_clauses],
    )


def policy_tenant(policy) -> Optional[str]:
    """The tenant a policy was fused under (cedar_tpu/tenancy stamps the
    registry's per-tenant clones), or None outside a fused plane."""
    return policy.__dict__.get("_cedar_tenant")


_tenant_guards: Dict[str, object] = {}


def tenant_guard_condition(tenant: str):
    """Memoized per-tenant AST guard ``when { context.tenantId == t }``.

    The tenant registry prepends it to every fused clone's conditions so
    the INTERPRETER paths — the tiered-store walk a breaker-open request
    takes, fallback ``policy_matches``, explain attribution — isolate
    tenants exactly like the packed discriminator does, with Cedar's own
    &&-first short-circuit killing foreign policies' condition errors.
    Per-process singleton: the shard compiler recognizes the guard BY
    IDENTITY (compiler/shard.py) and lowers the deguarded policy plus
    ``discriminate_lowered`` instead — the guard's context access would
    otherwise lower with the error machinery the synthetic total literal
    exists to avoid."""
    c = _tenant_guards.get(tenant)
    if c is None:
        from ..lang.ast import Binary, Condition, GetAttr, Lit, Var

        c = _tenant_guards[tenant] = Condition(
            "when",
            Binary(
                "==",
                GetAttr(Var("context"), TENANT_CONTEXT_KEY),
                Lit(tenant),
            ),
        )
    return c


def _bucket(n: int, minimum: int = 128) -> int:
    """Power-of-two buckets up to 2048, then multiples of 2048: coarse enough
    that same-size policy reloads reuse compiled executables, fine enough not
    to waste matmul columns on padding."""
    b = minimum
    while b < n and b < 2048:
        b *= 2
    if n <= b:
        return b
    return ((n + 2047) // 2048) * 2048


@dataclass
class PolicyMeta:
    policy_id: str
    filename: str
    position: Tuple[int, int, int]
    tier: int
    effect: str


@dataclass(frozen=True)
class RuleClause:
    """Back-map entry for ONE packed rule column: which policy's clause it
    lowered from — the explain plane's IR attribution record
    (cedar_tpu/explain). ``kind`` is "match" (a policy condition clause),
    "error" (an error-detection clause), or "gate" (a fallback/opaque
    scope gate rule — no owning clause). ``ordinal`` is the clause's index
    within the owning policy's clauses (or error_clauses) list, and
    ``clause`` the IR Clause itself (a tuple of ClauseLit), so the host
    can render the exact attribute tests a winning rule asserted without
    re-lowering anything."""

    pm_idx: int  # index into policy_meta; -1 for gate rules
    group: int
    kind: str  # "match" | "error" | "gate"
    ordinal: int
    clause: object  # ir.Clause, or None for gate rules


@dataclass
class EncodePlan:
    """Inverted indices the host encoder uses to map one request to its
    active literal ids in O(touched slots), independent of policy count."""

    n_lits: int = 0
    # scalar slots to extract (var, path) -> nothing; presence implied
    slots: List[Slot] = field(default_factory=list)
    eq_idx: Dict[Slot, Dict[object, List[int]]] = field(default_factory=dict)
    has_idx: Dict[Slot, List[int]] = field(default_factory=dict)
    like_idx: Dict[Slot, List[Tuple[int, Pattern]]] = field(default_factory=dict)
    cmp_idx: Dict[Slot, List[Tuple[int, str, int]]] = field(default_factory=dict)
    inset_idx: Dict[Slot, Dict[object, List[int]]] = field(default_factory=dict)
    set_has_idx: Dict[Slot, Dict[object, List[int]]] = field(default_factory=dict)
    eq_entity_idx: Dict[str, Dict[Tuple[str, str], List[int]]] = field(
        default_factory=dict
    )
    entity_in_idx: Dict[str, Dict[Tuple[str, str], List[int]]] = field(
        default_factory=dict
    )
    # slot-valued entity `in`: slot -> target (type, id) -> literal ids;
    # the encoder resolves the slot value and tests its ancestor-or-self
    # closure (EntityMap.closure_of) against the targets
    in_slot_idx: Dict[Slot, Dict[Tuple[str, str], List[int]]] = field(
        default_factory=dict
    )
    # type-error indicators: slot -> [(literal id, required value_key
    # tag)]; active when the slot is present with a differently-tagged
    # value (in-vocab values ride the activation table rows, out-of-vocab
    # values are host-tagged into extras)
    type_err_idx: Dict[Slot, List[Tuple[int, str]]] = field(
        default_factory=dict
    )
    is_idx: Dict[str, Dict[str, List[int]]] = field(default_factory=dict)
    # (lit id, ok lit id, expr, error lit id) — each id -1 when absent. The
    # encoder evaluates expr per request: a bool result activates ok (and
    # lit when True); an EvalError or non-bool result activates the error id
    hard_lits: List[Tuple[int, int, object, int]] = field(default_factory=list)
    # parallel to hard_lits: a compiler.dyn spec (DynContains /
    # DynContainsMulti / DynEq / DynCmp) when the native encoder can
    # evaluate the expr itself, else None (the owning policies become
    # native-opaque and gate to the Python path per row)
    dyn_specs: List[object] = field(default_factory=list)
    # a safe upper bound on simultaneously-active literals per request
    max_active: int = 0


@dataclass
class PackedPolicySet:
    """Device-ready tensors (as numpy; the engine moves them to device)."""

    W: np.ndarray  # [L, R] int8
    thresh: np.ndarray  # [R] float32
    rule_group: np.ndarray  # [R] int16 (group ids are tiny; see module doc)
    rule_policy: np.ndarray  # [R] int32 (INT32_MAX pad sentinel needs width)
    n_tiers: int
    n_rules: int
    n_lits: int
    L: int  # bucketed literal dim
    R: int  # bucketed rule dim
    plan: EncodePlan
    policy_meta: List[PolicyMeta]
    fallback: list  # List[FallbackPolicy]
    table: object = None  # compiler.table.FeatureTable
    # per-rule IR back-map (RuleClause, parallel to the first n_rules
    # columns): the explain plane maps a winning rule index back to its
    # policy, clause ordinal, and literal tests here. Pure host memory —
    # references into the already-retained lowered IR, so it costs a few
    # pointers per rule and survives device loss with the rest of the pack
    rule_clause: List["RuleClause"] = field(default_factory=list)
    # True when gate rules were packed (group n_tiers * 3)
    has_gate: bool = False
    # lowered policies whose hard literals the NATIVE encoder cannot
    # evaluate (outside the dyn class); they gate like fallback policies on
    # the native path but evaluate exactly on the Python path
    native_opaque: int = 0
    # distinct Unlowerable reason codes across the fallback policies —
    # precomputed so the serving path's fallback burn-down counter
    # (cedar_fallback_decisions_total{code}) costs a tuple walk per
    # interpreter-merged decision, never a per-request set build
    fallback_codes: Tuple[str, ...] = ()

    @property
    def n_groups(self) -> int:
        return self.n_tiers * GROUPS_PER_TIER + (1 if self.has_gate else 0)


# fresh Literal.key() builds performed by intern() — the reload-allocation
# counter the perf-hardening test pins: a repack of cached shard slices
# re-interns the SAME Literal objects, so a steady-state incremental
# reload must build ZERO fresh keys (every one is memoized on its object)
_lit_key_builds = 0


def lit_key_build_count() -> int:
    return _lit_key_builds


class _LitRegistry:
    def __init__(self):
        self.by_key: Dict[tuple, int] = {}
        self.lits: List[Literal] = []

    def intern(self, lit: Literal) -> int:
        # the key tuple is memoized on the Literal: with shard-granular
        # incremental compilation the SAME Literal objects re-intern on
        # every reload's repack (cached lowered slices), so key() was a
        # per-reload O(resident literals) tuple-build. Literal is a frozen
        # dataclass without slots — writing through __dict__ bypasses the
        # frozen guard without changing equality/hash semantics.
        d = lit.__dict__
        k = d.get("_cedar_lit_key")
        if k is None:
            global _lit_key_builds
            _lit_key_builds += 1
            k = d["_cedar_lit_key"] = lit.key()
        idx = self.by_key.get(k)
        if idx is None:
            idx = len(self.lits)
            self.by_key[k] = idx
            self.lits.append(lit)
        return idx


def pack(compiled: CompiledPolicies) -> PackedPolicySet:
    from .dyn import dyn_spec

    reg = _LitRegistry()
    # (lits, group, pmeta, RuleClause) — the trailing back-map entry rides
    # the rule through the (group, policy) sort so rule_clause[r] always
    # describes column r
    rules: List[Tuple[List[Tuple[int, bool]], int, int, RuleClause]] = []
    policy_meta: List[PolicyMeta] = []
    opaque: List[Policy] = []  # lowered policies the NATIVE encoder can't eval
    _dyn_ok: Dict[int, bool] = {}  # id(expr) -> expr is in the dyn class

    def _native_opaque(lp) -> bool:
        for clause in list(lp.clauses) + list(lp.error_clauses):
            for cl in clause:
                if cl.lit.kind in (HARD, HARD_OK, HARD_ERR):
                    e = cl.lit.expr
                    ok = _dyn_ok.get(id(e))
                    if ok is None:
                        ok = _dyn_ok[id(e)] = dyn_spec(e) is not None
                    if not ok:
                        return True
                elif cl.lit.kind == IN_SLOT:
                    # the C++ encoder has no entity graph to walk a
                    # closure over; IN_SLOT stays inactive in native
                    # encodes, so the owning policy must gate (scope rows
                    # re-run the exact Python path) — under-activation of
                    # a GATED policy's rules is the one sound direction
                    return True
        return False

    for lp in compiled.lowered:
        p: Policy = lp.policy
        pm_idx = len(policy_meta)
        policy_meta.append(
            PolicyMeta(p.policy_id, p.filename, p.position, lp.tier, lp.effect)
        )
        effect_idx = FORBID_IDX if lp.effect == "forbid" else PERMIT_IDX
        group = lp.tier * GROUPS_PER_TIER + effect_idx
        for ci, clause in enumerate(lp.clauses):
            lits = [(reg.intern(cl.lit), cl.negated) for cl in clause]
            rules.append(
                (lits, group, pm_idx,
                 RuleClause(pm_idx, group, "match", ci, clause))
            )
        err_group = lp.tier * GROUPS_PER_TIER + ERROR_IDX
        for ci, clause in enumerate(lp.error_clauses):
            lits = [(reg.intern(cl.lit), cl.negated) for cl in clause]
            rules.append(
                (lits, err_group, pm_idx,
                 RuleClause(pm_idx, err_group, "error", ci, clause))
            )
        if _native_opaque(lp):
            opaque.append(p)

    # Gate rules: one per interpreter-fallback policy AND one per
    # native-opaque lowered policy (see GATE_RULE_POLICY comment), testing
    # just the policy's scope (principal/action/resource heads — always
    # lowerable, total, error-free). Group = n_tiers * 3; a request with no
    # gate hit cannot match or error on any of these policies, so its
    # device verdict needs no interpreter merge on the native path.
    has_gate = False
    if compiled.fallback or opaque:
        from .lower import scope_literals

        gate_group = compiled.n_tiers * GROUPS_PER_TIER
        for gi, gp in enumerate(
            [fp.policy for fp in compiled.fallback] + opaque
        ):
            gate_lits, _ = scope_literals(gp)
            lits = [(reg.intern(cl.lit), cl.negated) for cl in gate_lits]
            # fused multi-tenant plane: a tenant policy's gate tests the
            # tenant discriminator too, so a foreign tenant's request
            # never gate-flags (and never pays the exact Python walk) for
            # a scope it can't match by construction
            ten = policy_tenant(gp)
            if ten is not None:
                lits.insert(0, (reg.intern(tenant_literal(ten)), False))
            rules.append(
                (lits, gate_group, GATE_RULE_POLICY,
                 RuleClause(-1, gate_group, "gate", gi, None))
            )
        has_gate = True

    # group-contiguous rule layout: sorting by (group, policy) lets the
    # segmented-reduction kernel plane (ops/match.py, CEDAR_TPU_SEGRED)
    # reduce each group over ONE contiguous column slice instead of
    # n_groups masked passes over the full [B, Rc] score matrix. The
    # first/last-match semantics are order-independent (min/max over
    # POLICY indices, not rule indices), so the default scan plane is
    # unaffected; stability keeps the layout deterministic.
    rules.sort(key=lambda t: (t[1], t[2]))

    n_lits = len(reg.lits)
    n_rules = len(rules)
    L = _bucket(max(n_lits, 1))
    R = _bucket(max(n_rules, 1))

    W = np.zeros((L, R), dtype=np.int8)
    thresh = np.full((R,), 1e9, dtype=np.float32)  # padding never satisfied
    # int16 group column: ids run 0 .. n_tiers*3 (gate group last) — far
    # under the dtype ceiling, and half the int32 plane's device traffic.
    # Padding columns ride group 0 with a never-satisfied thresh, exactly
    # as before. rule_policy keeps int32 for its INT32_MAX pad sentinel.
    rule_group = np.zeros((R,), dtype=np.int16)
    rule_policy = np.full((R,), np.iinfo(np.int32).max, dtype=np.int32)

    for r, (lits, group, pm_idx, _rc) in enumerate(rules):
        npos = 0
        seen_sign: dict = {}
        for lit_id, negated in lits:
            val = -1 if negated else 1
            prev = seen_sign.get(lit_id)
            if prev is not None:
                if prev != val:
                    # both signs of one literal in a single rule: the
                    # clause is unsatisfiable and must have been dropped
                    # by the lowerer (simplify after harden); a silent
                    # last-write-wins here turns "never fires" into a
                    # wrong match — fail the compile loudly instead
                    is_gate = group == compiled.n_tiers * GROUPS_PER_TIER
                    owner = (
                        "gate-rule"
                        if is_gate
                        else policy_meta[pm_idx].policy_id
                        if 0 <= pm_idx < len(policy_meta)
                        else f"pm_idx={pm_idx}"
                    )
                    raise ValueError(
                        f"rule {r} (policy {owner}): literal {lit_id} "
                        "appears with both signs (unsatisfiable clause "
                        "leaked past the lowerer)"
                    )
                continue  # duplicate same-sign literal: count once
            seen_sign[lit_id] = val
            W[lit_id, r] = val
            if not negated:
                npos += 1
        thresh[r] = float(npos)
        rule_group[r] = group
        rule_policy[r] = pm_idx

    plan = _build_plan(reg.lits)
    plan.n_lits = n_lits
    from .table import build_table

    table = build_table(plan, n_lits, L)

    return PackedPolicySet(
        table=table,
        W=W,
        thresh=thresh,
        rule_group=rule_group,
        rule_policy=rule_policy,
        n_tiers=compiled.n_tiers,
        n_rules=n_rules,
        n_lits=n_lits,
        L=L,
        R=R,
        plan=plan,
        policy_meta=policy_meta,
        fallback=list(compiled.fallback),
        rule_clause=[rc for _lits, _g, _pm, rc in rules],
        has_gate=has_gate,
        native_opaque=len(opaque),
        fallback_codes=tuple(
            sorted(
                {
                    getattr(fp, "code", "unlowerable") or "unlowerable"
                    for fp in compiled.fallback
                }
            )
        ),
    )


def _build_plan(lits: List[Literal]) -> EncodePlan:
    plan = EncodePlan()
    slots = set()
    max_active = 0
    scalar_slots = set()
    hard_ids: Dict[object, int] = {}
    hard_err_ids: Dict[object, int] = {}
    hard_ok_ids: Dict[object, int] = {}
    for i, lit in enumerate(lits):
        if lit.kind == EQ:
            plan.eq_idx.setdefault(lit.slot, {}).setdefault(lit.data, []).append(i)
            slots.add(lit.slot)
            scalar_slots.add(lit.slot)
        elif lit.kind == HAS:
            plan.has_idx.setdefault(lit.slot, []).append(i)
            slots.add(lit.slot)
            max_active += 1
        elif lit.kind == LIKE:
            plan.like_idx.setdefault(lit.slot, []).append((i, Pattern(lit.data)))
            slots.add(lit.slot)
            max_active += 1
        elif lit.kind == CMP:
            op, c = lit.data
            plan.cmp_idx.setdefault(lit.slot, []).append((i, op, c))
            slots.add(lit.slot)
            max_active += 1
        elif lit.kind == IN_SET:
            d = plan.inset_idx.setdefault(lit.slot, {})
            for vk in lit.data:
                d.setdefault(vk, []).append(i)
            slots.add(lit.slot)
            max_active += 1
        elif lit.kind == SET_HAS:
            plan.set_has_idx.setdefault(lit.slot, {}).setdefault(
                lit.data, []
            ).append(i)
            slots.add(lit.slot)
            max_active += 1
        elif lit.kind == EQ_ENTITY:
            plan.eq_entity_idx.setdefault(lit.var, {}).setdefault(
                lit.data, []
            ).append(i)
            max_active += 1
        elif lit.kind == ENTITY_IN:
            plan.entity_in_idx.setdefault(lit.var, {}).setdefault(
                lit.data, []
            ).append(i)
            max_active += 1
        elif lit.kind == ENTITY_IN_ANY:
            d = plan.entity_in_idx.setdefault(lit.var, {})
            for uid in lit.data:
                d.setdefault(uid, []).append(i)
            max_active += 1
        elif lit.kind == IN_SLOT:
            d = plan.in_slot_idx.setdefault(lit.slot, {})
            for uid in lit.data:
                d.setdefault(uid, []).append(i)
            slots.add(lit.slot)
            max_active += 1
        elif lit.kind == TYPE_ERR:
            plan.type_err_idx.setdefault(lit.slot, []).append((i, lit.data))
            slots.add(lit.slot)
            max_active += 1
        elif lit.kind == IS:
            plan.is_idx.setdefault(lit.var, {}).setdefault(lit.data, []).append(i)
            max_active += 1
        elif lit.kind == HARD:
            hard_ids[lit.expr] = i
            max_active += 1
        elif lit.kind == HARD_ERR:
            hard_err_ids[lit.expr] = i
            max_active += 1
        elif lit.kind == HARD_OK:
            hard_ok_ids[lit.expr] = i
            max_active += 1
    for expr, lid in hard_ids.items():
        plan.hard_lits.append(
            (lid, hard_ok_ids.pop(expr, -1), expr, hard_err_ids.pop(expr, -1))
        )
    for expr, elid in hard_err_ids.items():
        # HARD_ERR without a surviving HARD literal (e.g. the hard literal
        # only appears in error clauses): still evaluate for the error bit
        plan.hard_lits.append((-1, hard_ok_ids.pop(expr, -1), expr, elid))
    for expr, okid in hard_ok_ids.items():
        plan.hard_lits.append((-1, okid, expr, -1))
    from .dyn import dyn_spec

    for _lid, _okid, expr, _elid in plan.hard_lits:
        spec = dyn_spec(expr)
        plan.dyn_specs.append(spec)
        if spec is not None:
            # the probe slot must be extracted even when no other literal
            # references it (the native evaluator reads it per request)
            slots.add(spec.slot)
    plan.slots = sorted(slots)
    # every scalar slot contributes at most one EQ hit and one IN_SET path
    max_active += len(scalar_slots)
    plan.max_active = max(max_active, 1)
    return plan
