"""Pallas TPU kernel: fused rule-match + first-match reduction.

The XLA path (ops/match.py) computes scores = lit @ W, then derives
per-(tier, effect) first-match policy indices with G masked min-reductions —
each a separate pass over the [B, Rc] f32 score matrix, which XLA may
materialize to HBM between passes. This kernel fuses the matmul epilogue:
score tiles live only in VMEM/registers, the satisfaction compare and all G
group-min reductions happen right after the MXU contraction, and the only
HBM output is the tiny [B, G] first-match matrix.

Grid: (B tiles, R tiles, L tiles) with the L (contraction) dimension
innermost; a VMEM scratch accumulates partial scores across L tiles
(f32 for the bf16 plane, int32 for the int8 plane — both exact for
0/1 x +/-1 operands), and an int32 VMEM scratch carries the running
per-group minima across R tiles for each B tile. Rules are padded with
thresh=1e9 (never satisfied; exactly representable in both thresh
dtypes), so padding never contributes a match — same invariant as the
XLA path.

Layouts (host side, prepared once per compiled policy set); lit and W
must share a plane — bf16 with f32 thresh, or int8 with int32 thresh
(the default XLA plane's dtype, opt-in here via CEDAR_TPU_PALLAS_INT8):
  lit     [B, L]  bf16|int8  {0, 1} literal activation matrix
  W       [L, R]  bf16|int8  +1 required-true / -1 required-false
  thresh  [1, R]  f32|int32  positive-literal count (1e9 padding)
  group   [1, R]  int32      tier * 3 + effect group id
  policy  [1, R]  int32      policy metadata index (INT32_MAX padding)
Returns first [B, G] int32 (INT32_MAX = no match), identical to
ops.match._first_match.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT32_MAX = 2**31 - 1

# tile sizes: TB x TK lit tile (1MB bf16), TK x TR W tile (2MB bf16),
# TB x TR f32 score tile (512KB) -> comfortably inside ~16MB VMEM with
# double buffering
_TB = 256
_TR = 512
_TK = 2048


# B tiles are independent; the R and L axes carry the scratch accumulators
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
)


def _accum_blocks(
    lit_ref, w_ref, thresh_ref, group_ref, policy_ref,
    score_ref, acc_ref, last_ref, *, n_groups: int, g_pad: int
):
    """The shared contraction + group-reduction body of both kernels:
    accumulate this (B, R, L) tile's partial scores in VMEM and, on the
    last L tile, fold the satisfaction compare + per-group first/last
    min/max into acc_ref/last_ref. The caller adds its own final-step
    emit block."""
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    j = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        score_ref[:] = jnp.zeros_like(score_ref)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _():
        acc_ref[:] = jnp.full_like(acc_ref, INT32_MAX)
        last_ref[:] = jnp.full_like(last_ref, -1)

    # MXU contraction for this (B, R, L) tile; the accumulator scratch's
    # dtype decides the plane: f32 for bf16 inputs, int32 for int8 inputs
    # (v5e MXU runs int8 at 2x bf16 peak; both planes are exact here)
    score_ref[:] += jnp.dot(
        lit_ref[:], w_ref[:], preferred_element_type=score_ref.dtype
    )

    @pl.when(k == nk - 1)
    def _():
        # fused epilogue: satisfaction + per-group first/last-match
        # min/max, all in VMEM — the score matrix never reaches HBM.
        # All operands kept 2D (TPU vector layout).
        sat = score_ref[:] >= thresh_ref[0:1, :]  # [TB, TR]
        pol_b = jnp.broadcast_to(policy_ref[0:1, :], sat.shape)
        masked_min = jnp.where(sat, pol_b, INT32_MAX)
        masked_max = jnp.where(sat, pol_b, -1)
        grp = group_ref[0:1, :]  # [1, TR]
        tb = sat.shape[0]
        mins = []
        maxs = []
        for g in range(n_groups):  # static unroll; G = 3 * tiers, tiny
            in_g = grp == g
            mins.append(
                jnp.min(
                    jnp.where(in_g, masked_min, INT32_MAX),
                    axis=1,
                    keepdims=True,
                )
            )
            maxs.append(
                jnp.max(
                    jnp.where(in_g, masked_max, -1), axis=1, keepdims=True
                )
            )
        for g in range(n_groups, g_pad):
            mins.append(jnp.full((tb, 1), INT32_MAX, jnp.int32))
            maxs.append(jnp.full((tb, 1), -1, jnp.int32))
        tile_min = jnp.concatenate(mins, axis=1)  # [TB, g_pad]
        acc_ref[:] = jnp.minimum(acc_ref[:], tile_min)
        last_ref[:] = jnp.maximum(last_ref[:], jnp.concatenate(maxs, axis=1))


def _kernel(
    lit_ref, w_ref, thresh_ref, group_ref, policy_ref, out_ref, last_out_ref,
    score_ref, acc_ref, last_ref, *, n_groups: int, g_pad: int
):
    _accum_blocks(
        lit_ref, w_ref, thresh_ref, group_ref, policy_ref,
        score_ref, acc_ref, last_ref, n_groups=n_groups, g_pad=g_pad,
    )
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(jnp.logical_and(j == nj - 1, k == nk - 1))
    def _():
        out_ref[:] = acc_ref[:]
        last_out_ref[:] = last_ref[:]


# packed verdict-word constants, mirrored from ops/match.py (kept literal
# here so the kernel module has no import cycle with match.py)
_POLICY_NONE = 0xFFFFFF
_CODE_ALLOW, _CODE_DENY, _CODE_ERROR = 1, 2, 3
_GPT = 3
# lane width of the words output tile: int32-sublane-friendly like g_pad;
# the host consumes column 0
_WORD_LANES = 8


def _words_kernel(
    lit_ref, w_ref, thresh_ref, group_ref, policy_ref, word_out_ref,
    score_ref, acc_ref, last_ref,
    *, n_groups: int, g_pad: int, n_tiers: int, has_gate: bool
):
    """The fully fused serving kernel: slot-match (satisfaction compare),
    clause-reduce (per-group first/last match), AND the tier walk all run
    in VMEM — the only HBM output is one packed verdict word per request
    (int32 bit pattern of ops.match's uint32 word, bitcast by the
    wrapper). Mirrors ops.match._tier_walk exactly: first tier with any
    explicit signal wins, err/multi/gate bits as documented there."""
    _accum_blocks(
        lit_ref, w_ref, thresh_ref, group_ref, policy_ref,
        score_ref, acc_ref, last_ref, n_groups=n_groups, g_pad=g_pad,
    )
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(jnp.logical_and(j == nj - 1, k == nk - 1))
    def _():
        first = acc_ref[:]  # [TB, g_pad] int32
        last = last_ref[:]
        tb = first.shape[0]
        code = jnp.zeros((tb, 1), jnp.int32)
        err = jnp.zeros((tb, 1), jnp.int32)
        multi = jnp.zeros((tb, 1), jnp.int32)
        pol = jnp.full((tb, 1), _POLICY_NONE, jnp.int32)
        done = jnp.zeros((tb, 1), jnp.bool_)
        for t in range(n_tiers):  # static unroll, tiers are 1-3
            p_f = first[:, t * _GPT : t * _GPT + 1]
            f_f = first[:, t * _GPT + 1 : t * _GPT + 2]
            e_f = first[:, t * _GPT + 2 : t * _GPT + 3]
            has_p = p_f != INT32_MAX
            has_f = f_f != INT32_MAX
            has_e = e_f != INT32_MAX
            c_t = jnp.where(
                has_f,
                _CODE_DENY,
                jnp.where(
                    has_p,
                    _CODE_ALLOW,
                    jnp.where(has_e, _CODE_ERROR, 0),
                ),
            ).astype(jnp.int32)
            pol_t = jnp.where(has_f, f_f, jnp.where(has_p, p_f, e_f))
            sig = c_t != 0
            new = jnp.logical_and(jnp.logical_not(done), sig)
            code = jnp.where(new, c_t, code)
            pol = jnp.where(new, pol_t, pol)
            err = jnp.where(
                new & has_e & (has_p | has_f), jnp.int32(1), err
            )
            l_p = last[:, t * _GPT : t * _GPT + 1]
            l_f = last[:, t * _GPT + 1 : t * _GPT + 2]
            l_e = last[:, t * _GPT + 2 : t * _GPT + 3]
            win_first = jnp.where(has_f, f_f, jnp.where(has_p, p_f, e_f))
            win_last = jnp.where(has_f, l_f, jnp.where(has_p, l_p, l_e))
            multi = jnp.where(
                new & sig & (win_first != win_last), jnp.int32(1), multi
            )
            done = jnp.logical_or(done, sig)
        word = (
            jnp.left_shift(code, 30)
            | jnp.left_shift(err, 29)
            | jnp.left_shift(multi, 28)
            | (pol & jnp.int32(_POLICY_NONE))
        )
        if has_gate:
            gate = (
                first[:, n_tiers * _GPT : n_tiers * _GPT + 1] != INT32_MAX
            ).astype(jnp.int32)
            word = word | jnp.left_shift(gate, 27)
        word_out_ref[:] = jnp.broadcast_to(word, (tb, _WORD_LANES))


@functools.partial(
    jax.jit, static_argnames=("n_groups", "interpret")
)
def pallas_first_match(
    lit, W, thresh_r, group_r, policy_r, n_groups: int, interpret: bool = False
):
    """lit [B, L] + W [L, R] in matching dtypes (bf16 with f32 thresh, or
    int8 with int32 thresh — the int8 plane of ops/match.py);
    group_r/policy_r [1, R]. Returns (first [B, n_groups] int32, last
    [B, n_groups] int32) — the same (min, max) matched-policy contract as
    ops.match._first_match. Shapes must tile: B % TB == 0
    (or B <= TB), R % TR == 0, L % TK == 0 (or L <= TK)."""
    B, L = lit.shape
    R = W.shape[1]
    acc_dtype = jnp.int32 if W.dtype == jnp.int8 else jnp.float32
    in_bytes = 1 if W.dtype == jnp.int8 else 2
    tb = min(_TB, B)
    tk = min(_TK, L)
    tr = min(_TR, R)
    g_pad = -(-n_groups // 8) * 8  # int32 sublane-friendly output width

    grid = (B // tb, R // tr, L // tk)
    kernel = functools.partial(_kernel, n_groups=n_groups, g_pad=g_pad)

    out, last = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B, g_pad), jnp.int32),
            jax.ShapeDtypeStruct((B, g_pad), jnp.int32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (tb, tk), lambda i, j, k: (i, k), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (tk, tr), lambda i, j, k: (k, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, tr), lambda i, j, k: (0, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, tr), lambda i, j, k: (0, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, tr), lambda i, j, k: (0, j), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (tb, g_pad), lambda i, j, k: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (tb, g_pad), lambda i, j, k: (i, 0), memory_space=pltpu.VMEM
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((tb, tr), acc_dtype),
            pltpu.VMEM((tb, g_pad), jnp.int32),
            pltpu.VMEM((tb, g_pad), jnp.int32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * B * L * R,
            bytes_accessed=B * L * in_bytes + L * R * in_bytes
            + 2 * B * g_pad * 4,
            transcendentals=0,
        ),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(lit, W, thresh_r, group_r, policy_r)
    return out[:, :n_groups], last[:, :n_groups]


@functools.partial(
    jax.jit, static_argnames=("n_tiers", "has_gate", "interpret")
)
def pallas_match_words(
    lit, W, thresh_r, group_r, policy_r, n_tiers: int,
    has_gate: bool = False, interpret: bool = False,
):
    """Fused slot-match + clause-reduce + tier-walk: one pallas_call from
    literal matrix to packed uint32 verdict words [B] — the hot-path
    variant of pallas_first_match for callers that don't need the full
    (first, last) matrices. Same layouts as pallas_first_match; the word
    format (incl. the has_gate bit 27) is ops/match.py's packed word,
    byte-identical to the lax plane (differential-tested in
    tests/test_pallas_match.py)."""
    B, L = lit.shape
    R = W.shape[1]
    acc_dtype = jnp.int32 if W.dtype == jnp.int8 else jnp.float32
    in_bytes = 1 if W.dtype == jnp.int8 else 2
    n_groups = n_tiers * _GPT + (1 if has_gate else 0)
    tb = min(_TB, B)
    tk = min(_TK, L)
    tr = min(_TR, R)
    g_pad = -(-n_groups // 8) * 8

    grid = (B // tb, R // tr, L // tk)
    kernel = functools.partial(
        _words_kernel, n_groups=n_groups, g_pad=g_pad, n_tiers=n_tiers,
        has_gate=has_gate,
    )

    words = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, _WORD_LANES), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (tb, tk), lambda i, j, k: (i, k), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (tk, tr), lambda i, j, k: (k, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, tr), lambda i, j, k: (0, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, tr), lambda i, j, k: (0, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, tr), lambda i, j, k: (0, j), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (tb, _WORD_LANES), lambda i, j, k: (i, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((tb, tr), acc_dtype),
            pltpu.VMEM((tb, g_pad), jnp.int32),
            pltpu.VMEM((tb, g_pad), jnp.int32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * B * L * R,
            bytes_accessed=B * L * in_bytes + L * R * in_bytes
            + B * _WORD_LANES * 4,
            transcendentals=0,
        ),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(lit, W, thresh_r, group_r, policy_r)
    return jax.lax.bitcast_convert_type(words[:, 0], jnp.uint32)


def pallas_supported(B: int, L: int, R: int) -> bool:
    """Shapes the kernel tiles cleanly; callers fall back to XLA otherwise."""
    ok_b = B % _TB == 0 or B in (8, 16, 32, 64, 128)
    ok_l = L % _TK == 0 or (L <= _TK and L % 128 == 0)
    ok_r = R % _TR == 0 or (R <= _TR and R % 128 == 0)
    return ok_b and ok_l and ok_r
