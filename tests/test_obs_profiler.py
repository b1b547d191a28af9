"""The program's stages on the profiler's clock, and names on the device.

With a ``jax.profiler`` session running, every batch stage and sub-stage
of the served path is a ``cedar.*`` event on a host plane of the trace,
beside the runtime's own ``PjitFunction``; with none, the same path runs
and answers the same. The kernels' logical steps carry ``cedar.match.*``
scopes — metadata only: the lowered program without its locations does not
hold them, and the engine's answers over the repo's synthetic corpora are
those the parent commit gave (tests/testdata/obs/answers_parent_pr26.json,
written by running ``answers()`` below on that commit).
"""

import glob
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from cedar_tpu.native import native_available
from cedar_tpu.ops import match as M

from test_obs_phases import Served, post, sar

GOLDEN = pathlib.Path(__file__).parent / "testdata" / "obs" / "answers_parent_pr26.json"

BATCH_EVENTS = {
    "cedar.http.request", "cedar.batch.encode", "cedar.batch.dispatch",
    "cedar.dispatch.stage", "cedar.dispatch.launch",
    "cedar.dispatch.readback", "cedar.batch.decode",
    "cedar.decode.device_wait",
}


def host_events(trace_dir) -> list:
    """[(thread line, name, start ns, end ns, stats)] of the host planes."""
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                out.append((k, e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)))
    return out


@pytest.mark.skipif(not native_available(), reason="no C++ toolchain")
def test_a_profiler_session_holds_the_served_stages_and_none_changes_no_answer(tmp_path):
    served = Served()
    try:
        conn = served.connection()
        # the same bodies with no session (the first call also compiles)
        plain = [post(conn, "/v1/authorize", sar(i))[1] for i in range(4)]
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            traced = [post(conn, "/v1/authorize", sar(i))[1] for i in range(4)]
        finally:
            jax.profiler.stop_trace()
        conn.close()
    finally:
        served.stop()
    assert traced == plain
    events = host_events(tmp_path)
    names = {e[1] for e in events}
    assert BATCH_EVENTS <= names, BATCH_EVENTS - names
    launches = [e for e in events if e[1] == "cedar.dispatch.launch"]
    dispatches = [e for e in events if e[1] == "cedar.batch.dispatch"]
    jitted = [e for e in events if e[1].startswith("PjitFunction(")
              and "_match_rules_codes" in e[1]]
    assert len(launches) == len(dispatches) == 4 and jitted
    for line, _name, start, end, stats in launches:
        assert stats.get("batch") == 1
        # the launch is inside its batch's dispatch stage, on its thread
        assert any(l2 == line and s2 <= start and end <= e2
                   for l2, _n, s2, e2, _ in dispatches)
    # and the runtime's own event of the jitted call is inside a launch
    for line, _name, start, end, _ in jitted:
        assert any(l2 == line and s2 <= start and end <= e2
                   for l2, _n, s2, e2, _ in launches)
    # one batch's stages sit on three threads: encode, dispatch, decode
    assert len({e[0] for e in events if e[1] in (
        "cedar.batch.encode", "cedar.batch.dispatch", "cedar.batch.decode")}) == 3


def _kernel_args(want_bits: bool, wire: bool):
    rng = np.random.default_rng(0)
    B, S, E, V, L, C, Rc = 8, 4, 8, 32, 256, 2, 128
    codes = rng.integers(0, V, (B, S)).astype(np.int16)
    extras = np.full((B, E), L, np.int16)
    act = rng.integers(0, 2, (V, L)).astype(np.uint8)
    W = rng.integers(0, 2, (C, L, Rc)).astype(np.int8)
    th = rng.integers(1, 4, (C, Rc)).astype(np.int32)
    grp = np.sort(rng.integers(0, 7, (C * Rc,))).reshape(C, Rc).astype(np.int32)
    pol = (np.arange(C * Rc).reshape(C, Rc) // 3).astype(np.int32)
    kw = dict(n_tiers=2, want_full=False, want_bits=want_bits,
              n_valid=np.int32(B) if want_bits else None, has_gate=True, segs=None)
    if wire:
        c8 = codes[:, :2].astype(np.uint8)
        lo8 = np.ones((2,), np.int32)
        return (c8, codes[:, 2:], lo8, extras, act, W, th, grp, pol), kw
    return (codes, extras, act, W, th, grp, pol), kw


SERVING = ("activation", "score", "scan", "tier_walk", "word_pack")
KERNELS = [
    ("match_rules_codes", False, False, SERVING),
    ("match_rules_codes", True, False, SERVING + ("bits_pack", "bits_compact", "out_pack")),
    ("match_rules_codes_donated", False, False, SERVING),
    ("match_rules_codes_wire", False, True, SERVING),
    ("match_rules_codes_wire", True, True, SERVING + ("bits_pack", "bits_compact", "out_pack")),
    ("match_rules_codes_wire_donated", False, True, SERVING),
    ("match_rules_codes_bits", None, False, ("activation", "score", "scan", "bits_pack")),
]


@pytest.mark.parametrize("name,want_bits,wire,scopes", KERNELS)
def test_every_serving_kernel_carries_its_scopes_as_metadata_only(
        name, want_bits, wire, scopes):
    fn = getattr(M, name)
    if want_bits is None:
        args, kw = _kernel_args(False, False)[0], {}
    else:
        args, kw = _kernel_args(want_bits, wire)
    lowered = fn.lower(*args, **kw)
    with_locations = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert f"cedar.match.{scope}" in with_locations, scope
    # the program itself — what is compiled, and what JAX's persistent
    # cache keys on (locations stripped) — does not know the scopes
    assert "cedar.match" not in lowered.as_text()


def answers() -> dict:
    """Digest of the engine's answers over the repo's synthetic corpora:
    decision, determining policies, error count, request by request."""
    from cedar_tpu.corpus.synth import coverage_corpus, synth_corpus
    from cedar_tpu.engine.evaluator import TPUPolicyEngine

    def digest(tiers, items):
        engine = TPUPolicyEngine()
        engine.load(tiers, warm="off")
        h = hashlib.sha256()
        for dec, diag in engine.evaluate_batch(items):
            h.update(repr((dec, sorted(r.policy for r in diag.reasons),
                           len(diag.errors))).encode())
        return {"requests": len(items), "sha256": h.hexdigest()}

    corpus = synth_corpus(300, seed=7)
    items = [it for c in range(corpus.clusters)
             for it in corpus.sar_items(40, cluster=c, seed=11 + c)]
    cov = coverage_corpus(60, seed=3)
    return {"synth-300": digest(corpus.tiers(), items),
            "coverage-60": digest(cov.tiers(), cov.items(200, seed=5))}


def test_the_scoped_kernels_answer_as_the_parent_commit_did():
    assert answers() == json.loads(GOLDEN.read_text())
