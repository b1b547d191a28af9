"""The one scoring plane, against a plain numpy reference.

A single-device engine has one scoring plane (int8 W, int32 accumulation)
behind two input layouts: the u8 wire (``match_rules_codes_wire``) and the
flat codes (``match_rules_codes``). Both are held here, on the same random
requests, to a first-match / tier-walk reference written in numpy below —
verdict words (code, policy, err / multi flags, gate bit), the want_full
first / last matrices and the in-call flagged-row bitsets, which a served
launch returns behind its words as one buffer — and the engine end to end
to the interpreter. The last tests pin what a deleted plane
would bring back: a switch nothing reads, a key nothing reports, a second
copy of W on the device.
"""

import pathlib
import random
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cedar_tpu.engine.evaluator import TPUPolicyEngine, _segment_plan
from cedar_tpu.lang import PolicySet
from cedar_tpu.ops.match import (
    BITS_TOPK,
    INT32_MAX,
    POLICY_NONE,
    WORD_ERR,
    WORD_MULTI,
    chunk_rules,
    match_rules_codes,
    match_rules_codes_wire,
    unpack_out,
)

REPO = pathlib.Path(__file__).resolve().parents[1]
S8, SW, E, V = 3, 1, 4, 64  # u8 slots, wide slots, extras width, table rows


def _random_problem(rng, B, L, R, n_groups):
    """Sparse rules (two positive literals, now and then a negation) and
    sparse requests (some ten active literals), so that a row matches a
    rule or two: every verdict code and both flags occur."""
    W = np.zeros((L, R), np.int8)
    for r in range(R):
        W[rng.choice(L, size=2, replace=False), r] = 1
        if rng.random() < 0.3:
            W[int(rng.integers(0, L)), r] = -1
    thresh = np.maximum((W > 0).sum(0), 1).astype(np.int32)
    group = rng.integers(0, n_groups, size=R).astype(np.int16)
    policy = rng.integers(0, 10000, size=R).astype(np.int32)
    # activation table: row 0 is the all-zero "missing" row
    act = np.zeros((V, L), np.uint8)
    for v in range(1, V):
        act[v, rng.choice(L, size=int(rng.integers(1, 3)), replace=False)] = 1
    # u8 lanes: slot s owns table rows [lo8[s], lo8[s] + 15]
    lo8 = np.array([1, 17, 33], np.int32)
    c8 = rng.integers(0, 17, size=(B, S8)).astype(np.uint8)  # 0 = missing
    cw = rng.integers(0, V, size=(B, SW)).astype(np.int16)
    extras = rng.integers(0, L + 8, size=(B, E)).astype(np.int16)  # >= L pads
    return W, thresh, group, policy, act, lo8, c8, cw, extras


def _reference(W, thresh, group, policy, act, codes, extras, n_tiers, gate):
    """Plain numpy: literal matrix, scores, per-group first / last matched
    policy, the tier walk and the packed sat bits."""
    L, R = W.shape
    B = codes.shape[0]
    n_groups = n_tiers * 3 + (1 if gate else 0)
    lit = np.zeros((B, L), bool)
    for s in range(codes.shape[1]):
        lit |= act[codes[:, s]].astype(bool)
    for e in range(extras.shape[1]):
        ok = extras[:, e] < L
        lit[np.nonzero(ok)[0], extras[ok, e]] = True
    sat = lit.astype(np.int32) @ W.astype(np.int32) >= thresh[None, :]
    first = np.full((B, n_groups), INT32_MAX, np.int64)
    last = np.full((B, n_groups), -1, np.int64)
    for g in range(n_groups):
        cols = np.nonzero(group == g)[0]
        hit = sat[:, cols]
        first[:, g] = np.where(hit, policy[cols][None, :], INT32_MAX).min(
            axis=1, initial=INT32_MAX
        )
        last[:, g] = np.where(hit, policy[cols][None, :], -1).max(
            axis=1, initial=-1
        )
    words = np.zeros(B, np.uint32)
    for b in range(B):
        code, err, multi, pol = 0, 0, 0, POLICY_NONE
        for t in range(n_tiers):
            (p_f, f_f, e_f), (p_l, f_l, e_l) = (
                m[b, t * 3 : t * 3 + 3] for m in (first, last)
            )
            has_p, has_f, has_e = (x != INT32_MAX for x in (p_f, f_f, e_f))
            if not (has_p or has_f or has_e):
                continue
            if has_f:
                code, lo, hi = 2, f_f, f_l
            elif has_p:
                code, lo, hi = 1, p_f, p_l
            else:
                code, lo, hi = 3, e_f, e_l
            pol = int(lo)
            err = int(has_e and (has_p or has_f))
            multi = int(lo != hi)
            break
        word = (code << 30) | (err << 29) | (multi << 28) | (pol & POLICY_NONE)
        if gate and first[b, n_tiers * 3] != INT32_MAX:
            word |= 1 << 27
        words[b] = word
    # rule r lives in word r // 32, bit r % 32
    bits = np.packbits(sat, axis=1, bitorder="little").view("<u4")
    return words, first, last, bits


# the shape grids of the deleted pallas parity tests (B, L, R, tiers, gate),
# each against both layouts, with and without the in-call bits payload
SHAPES = [
    (256, 128, 512, 1, False),
    (256, 256, 1024, 2, False),  # two tiers
    (256, 128, 512, 2, True),  # the gate group rides the word's bit 27
    (512, 128, 512, 3, False),  # three tiers
    (8, 256, 8192, 2, True),  # a served bucket over two scan chunks
]


@pytest.mark.parametrize("want_bits", [False, True], ids=["words", "bits"])
@pytest.mark.parametrize("layout", ["wire", "flat"])
@pytest.mark.parametrize("B,L,R,T,gate", SHAPES)
def test_kernel_matches_numpy_reference(B, L, R, T, gate, layout, want_bits):
    rng = np.random.default_rng(B + L + R + T)
    n_groups = T * 3 + (1 if gate else 0)
    W, thresh, group, policy, act, lo8, c8, cw, extras = _random_problem(
        rng, B, L, R, n_groups
    )
    # the same requests as global table rows, for the flat layout and the
    # reference: v > 0 in a u8 lane is row v + lo8 - 1
    g8 = np.where(c8 == 0, 0, c8.astype(np.int32) + lo8[None, :] - 1)
    codes = np.concatenate([g8, cw.astype(np.int32)], axis=1).astype(np.int16)
    ref_words, ref_first, ref_last, ref_bits = _reference(
        W, thresh, group, policy, act, codes, extras, T, gate
    )
    # the random sets must exercise the flag planes, or the equality
    # below proves less than it claims
    assert (ref_words & np.uint32(WORD_ERR | WORD_MULTI)).any()
    assert B < 256 or len(set((ref_words >> 30).tolist())) == 4

    plane = tuple(jnp.asarray(a) for a in (act, *chunk_rules(W, thresh, group, policy)))
    n_valid = B - 3  # trailing rows are bucket padding: never in the payload
    if layout == "wire":
        kernel, lead = match_rules_codes_wire, (c8, cw, jnp.asarray(lo8), extras)
    else:
        kernel, lead = match_rules_codes, (codes, extras)
    assert plane[1].dtype == jnp.int8
    if not want_bits:
        # the serving words, then want_full: the same words and the exact
        # first / last matrices
        words, full = kernel(*lead, *plane, T, False, False, None, gate)
        assert (np.asarray(words) == ref_words).all() and full is None
        words, (first, last) = kernel(*lead, *plane, T, True, False, None, gate)
        assert (np.asarray(words) == ref_words).all()
        assert (np.asarray(first) == ref_first).all()
        assert (np.asarray(last) == ref_last).all()
        return
    out = kernel(*lead, *plane, T, False, True, np.int32(n_valid), gate)
    _assert_one_buffer(out, B, n_valid, ref_words, ref_bits)


def _assert_one_buffer(out, B, n_valid, ref_words, ref_bits):
    """A want_bits launch's whole result is ONE uint32 vector, and it
    unpacks to exactly the reference: every word, then the compaction —
    the first K flagged valid rows in row order, each with its bitset,
    every slot past them dead."""
    K = min(B, BITS_TOPK)
    w32 = ref_bits.shape[1]
    assert isinstance(out, jax.Array) and out.dtype == jnp.uint32
    assert out.shape == (B + K * (2 + w32),)
    host = np.asarray(out)
    words, vals, idx, kbits = unpack_out(host, B)
    for view in (words, vals, idx, kbits):
        assert np.shares_memory(view, host)  # views, not copies
    assert vals.dtype == idx.dtype == np.int32 and kbits.shape == (K, w32)
    assert (words == ref_words).all()
    flagged = np.nonzero(
        ((ref_words & np.uint32(WORD_ERR | WORD_MULTI)) != 0)
        & (np.arange(B) < n_valid)
    )[0]
    live = vals > 0
    n_live = min(len(flagged), K)
    assert live.tolist() == [True] * n_live + [False] * (K - n_live)
    assert idx[live].tolist() == flagged[:K].tolist()
    assert (vals[live] == B - idx[live]).all()
    assert (kbits[live] == ref_bits[idx[live]]).all()
    return len(flagged)


@pytest.mark.parametrize("B", [1, 8, 32, 128, 512])
@pytest.mark.parametrize("segs", [False, True], ids=["scan", "segs"])
@pytest.mark.parametrize("gate", [False, True], ids=["nogate", "gate"])
@pytest.mark.parametrize("layout", ["wire", "codes"])
def test_one_result_buffer_unpacks_to_the_reference(layout, gate, segs, B):
    """The served launch's one buffer at the served buckets: K = B up to
    128 rows and K = 128 < B past it, with more flagged rows than K at
    512 (the overflow the host sends to the standalone bits kernel); a
    row past n_valid is never in the compaction."""
    L, R, T = 128, 512, 2
    rng = np.random.default_rng(1000 + B)
    n_groups = T * 3 + (1 if gate else 0)
    W, thresh, group, policy, act, lo8, c8, cw, extras = _random_problem(
        rng, max(B, 64), L, R, n_groups
    )
    # group-contiguous rules, as compiler.pack lays them out: what the
    # segmented reduction's static column runs need
    group = np.sort(group)
    g8 = np.where(c8 == 0, 0, c8.astype(np.int32) + lo8[None, :] - 1)
    codes = np.concatenate([g8, cw.astype(np.int32)], axis=1).astype(np.int16)
    ref_words, _, _, ref_bits = _reference(
        W, thresh, group, policy, act, codes, extras, T, gate
    )
    flags = (ref_words & np.uint32(WORD_ERR | WORD_MULTI)) != 0
    # the rows the launch gets: flagged rows first where there are few
    # rows (a one-row batch is a flagged row), so every case has some
    order = np.argsort(~flags, kind="stable")[:B] if B < 64 else np.arange(B)
    c8, cw, codes, extras = (a[order] for a in (c8, cw, codes, extras))
    ref_words, ref_bits = ref_words[order], ref_bits[order]
    chunks = chunk_rules(W, thresh, group, policy)
    plan = _segment_plan(chunks[2], R) if segs else None
    plane = tuple(jnp.asarray(a) for a in (act, *chunks))
    n_valid = B - 3 if B >= 8 else B
    if layout == "wire":
        kernel, lead = match_rules_codes_wire, (c8, cw, jnp.asarray(lo8), extras)
    else:
        kernel, lead = match_rules_codes, (codes, extras)
    out = kernel(*lead, *plane, T, False, True, np.int32(n_valid), gate, plan)
    n_flagged = _assert_one_buffer(out, B, n_valid, ref_words, ref_bits)
    assert n_flagged >= 1
    assert B < 512 or n_flagged > BITS_TOPK


def test_engine_matches_interpreter_on_the_random_corpus():
    """The corpus the deleted engine-level parity test ran (200 random
    permit / forbid policies, 64 requests), held to the interpreter."""
    from cedar_tpu.entities.attributes import Attributes, UserInfo
    from cedar_tpu.server.authorizer import record_to_cedar_resource
    from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

    rng = random.Random(3)
    src = "\n".join(
        f'{"permit" if rng.random() < 0.85 else "forbid"} (principal, '
        'action == k8s::Action::"get", resource is k8s::Resource) when {'
        f' principal.name == "user-{rng.randint(0, 20)}" &&'
        f' resource.resource == "r-{rng.randint(0, 10)}" }};'
        for _ in range(200)
    )
    items = [
        record_to_cedar_resource(
            Attributes(
                user=UserInfo(name=f"user-{rng.randint(0, 25)}", uid="u"),
                verb="get",
                resource=f"r-{rng.randint(0, 12)}",
                api_version="v1",
                resource_request=True,
            )
        )
        for _ in range(64)
    ]
    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(src, "corpus")], warm="off")
    stores = TieredPolicyStores([MemoryStore.from_source("corpus", src)])
    decisions = set()
    for (em, req), (dec, diag) in zip(items, engine.evaluate_batch(items)):
        ref_dec, ref_diag = stores.is_authorized(em, req)
        assert dec == ref_dec
        assert {r.policy for r in diag.reasons} == {
            r.policy for r in ref_diag.reasons
        }
        decisions.add(dec)
    assert len(decisions) >= 2  # allows and denies both occur


GONE_VARIABLES = ("CEDAR_TPU_PALLAS", "CEDAR_TPU_PALLAS_INT8", "CEDAR_TPU_INT8")


def test_no_source_reads_a_deleted_switch():
    """The pallas and bf16 planes went with their switches: no file of the
    program, nor the chip smoke, names one (deleted, not ignored)."""
    pattern = re.compile(r"\b(" + "|".join(GONE_VARIABLES) + r")\b")
    files = sorted((REPO / "cedar_tpu").rglob("*.py")) + [REPO / "chip_smoke.py"]
    assert len(files) > 100
    hits = [
        f"{f.relative_to(REPO)}:{n}"
        for f in files
        for n, line in enumerate(f.read_text().splitlines(), 1)
        if pattern.search(line)
    ]
    assert hits == []
    assert not (REPO / "cedar_tpu" / "ops" / "pallas_match.py").exists()


def test_webhook_help_has_no_pallas_flag():
    from cedar_tpu.cli.webhook import make_parser

    text = make_parser().format_help()
    assert "--backend" in text
    assert "pallas" not in text
    with pytest.raises(SystemExit):
        make_parser().parse_args(["--pallas", "off"])


@pytest.fixture(scope="module")
def loaded_engine():
    src = "\n".join(
        'permit (principal, action == k8s::Action::"get", resource is '
        f'k8s::Resource) when {{ principal.name == "u{i}" }};'
        for i in range(40)
    )
    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(src, "one-plane")], warm="off")
    return engine


def test_debug_engine_reports_no_plane_choice(loaded_engine):
    stats = loaded_engine.stats  # what /debug/engine serves
    assert {"platform", "device_kind", "n_devices", "warm"} <= set(stats)
    assert not [k for k in stats if "pallas" in k]
    assert not hasattr(loaded_engine, "use_pallas")


def test_compiled_set_holds_one_int8_copy_of_w(loaded_engine):
    """A loaded single-device set keeps W on the device once, as int8:
    the 63 MB plane of the 10k corpus has no second, wider twin."""
    cs = loaded_engine._compiled
    n_w = cs.packed.W.size

    def arrays(v):
        if isinstance(v, jax.Array):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from arrays(x)

    w_sized = [a for v in vars(cs).values() for a in arrays(v) if a.size == n_w]
    assert len(w_sized) == 1
    assert w_sized[0] is cs.W_dev
    assert cs.W_dev.dtype == jnp.int8
    assert cs.thresh_dev.dtype == jnp.int32
    assert (np.asarray(cs.W_dev).transpose(1, 0, 2).reshape(cs.packed.W.shape)
            == cs.packed.W).all()
