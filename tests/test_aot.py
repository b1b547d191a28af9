"""AOT serialized-executable cache tests (ISSUE 19, engine/aot.py).

Pins the three contracts docs/Operations.md sells:

  * round-trip differential — an engine serving through the executable
    cache (export, then warm-from-disk) produces the same decisions AND
    reason sets as a fresh-compile engine with the cache disabled;
  * stale-key refusal — a disk entry whose meta header names a different
    jaxlib/topology is refused loudly (stale counter + recompile), never
    deserialized into a wrong executable;
  * zero-trace warm start — a process whose key matches serves from the
    deserialized executable without ever tracing the python kernel body
    (in-process via a trace-side-effect counter, and end-to-end via a
    fresh subprocess running the real warm ladder twice).
"""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cedar_tpu.engine import aot
from cedar_tpu.engine.evaluator import TPUPolicyEngine
from cedar_tpu.entities.attributes import Attributes, UserInfo
from cedar_tpu.lang import PolicySet
from cedar_tpu.server.authorizer import record_to_cedar_resource

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SRC = """
permit (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "sam" && resource.resource == "pods" };
permit (principal in k8s::Group::"viewers", action == k8s::Action::"list",
        resource is k8s::Resource)
  when { resource.resource == "pods" };
forbid (principal, action, resource is k8s::Resource)
  when { resource.resource == "nodes" };
"""


@pytest.fixture(autouse=True)
def _clean_aot(monkeypatch):
    """Every test starts from a disabled cache and zeroed counters, and
    leaves no global cache-dir behind for the rest of the suite."""
    monkeypatch.delenv("CEDAR_TPU_AOT", raising=False)
    monkeypatch.delenv("CEDAR_TPU_AOT_CACHE", raising=False)
    aot.set_cache_dir(None)
    aot.reset_counters()
    yield
    aot.set_cache_dir(None)
    aot.reset_counters()


def _attrs(i):
    names = ["sam", "alex", "root"]
    resources = ["pods", "nodes", "secrets"]
    verbs = ["get", "list", "delete"]
    return Attributes(
        user=UserInfo(
            name=names[i % 3],
            uid="u",
            groups=("viewers",) if i % 2 else (),
        ),
        verb=verbs[i % 3],
        namespace=f"ns-{i % 4}",
        api_version="v1",
        resource=resources[(i // 3) % 3],
        subresource="",
        resource_request=True,
    )


def _serve(engine, n=64):
    items = [record_to_cedar_resource(_attrs(i)) for i in range(n)]
    out = []
    for dec, diag in engine.evaluate_batch(items):
        out.append((dec, tuple(sorted(r.policy for r in diag.reasons))))
    return out


# --------------------------------------------------------------- unit level


def test_dispatch_passthrough_when_disabled():
    calls = []

    def fn(x, y):
        calls.append(1)
        return x + y

    assert not aot.enabled()
    assert aot.dispatch("unit", fn, (2, 3), ()) == 5
    assert calls == [1]
    s = aot.stats()
    assert s["hits"] == s["misses"] == s["exports"] == 0


def test_escape_hatch_env_disables(tmp_path, monkeypatch):
    aot.set_cache_dir(str(tmp_path))
    assert aot.enabled()
    monkeypatch.setenv("CEDAR_TPU_AOT", "0")
    assert not aot.enabled()


def test_key_separates_shapes_statics_and_names():
    a32 = np.zeros((4, 8), np.int16)
    a64 = np.zeros((8, 8), np.int16)
    base = aot._key(aot._key_meta("codes", (a32, 3, True), (1, 2)))
    assert base == aot._key(aot._key_meta("codes", (a32, 3, True), (1, 2)))
    # different batch shape, different static value, different entry name
    assert base != aot._key(aot._key_meta("codes", (a64, 3, True), (1, 2)))
    assert base != aot._key(aot._key_meta("codes", (a32, 4, True), (1, 2)))
    assert base != aot._key(aot._key_meta("wire", (a32, 3, True), (1, 2)))
    # a None dynamic slot is part of the signature
    assert aot._key(
        aot._key_meta("codes", (a32, None, 3), (2,))
    ) != aot._key(aot._key_meta("codes", (a32, a32, 3), (2,)))


# ------------------------------------------------- round-trip differential


def test_roundtrip_differential_vs_fresh_compile(tmp_path):
    ps = PolicySet.from_source(SRC, "t0")

    # reference: plain jit path, cache disabled
    ref_engine = TPUPolicyEngine()
    ref_engine.load([ps], warm="off")
    ref = _serve(ref_engine)

    # export pass: same policies through the executable cache
    aot.set_cache_dir(str(tmp_path))
    eng_a = TPUPolicyEngine()
    eng_a.load([ps], warm="off")
    assert _serve(eng_a) == ref
    s = aot.stats()
    assert s["exports"] >= 1 and s["stale"] == 0
    assert glob.glob(str(tmp_path / "*.jexp"))

    # warm-from-disk pass: clearing resolved state forces the disk read;
    # decisions and reason sets must survive the deserialize round trip
    aot.set_cache_dir(str(tmp_path))
    aot.reset_counters()
    eng_b = TPUPolicyEngine()
    eng_b.load([ps], warm="off")
    assert _serve(eng_b) == ref
    s = aot.stats()
    assert s["hits"] >= 1
    assert s["exports"] == 0  # nothing recompiled
    assert s["stale"] == 0 and s["errors"] == 0


# ------------------------------------------------------- stale-key refusal


def test_stale_entry_refused_and_recompiled(tmp_path):
    import jax

    aot.set_cache_dir(str(tmp_path))
    x = np.arange(8, dtype=np.float32)

    f1 = jax.jit(lambda v: v * 2)
    np.testing.assert_allclose(
        np.asarray(aot.dispatch("unit", f1, (x,), ())), x * 2
    )
    assert aot.stats()["exports"] == 1
    (path,) = glob.glob(str(tmp_path / "*.jexp"))

    # tamper: same filename, meta header claiming a foreign environment —
    # the hand-copied-cache-from-another-host case
    meta, blob = aot._read_entry(path)
    meta["jaxlib"] = "0.0.0-foreign"
    meta["device_kind"] = "TPU v9000"
    aot._write_entry(path, meta, blob)

    aot.set_cache_dir(str(tmp_path))  # clear resolved: consult disk again
    aot.reset_counters()
    f2 = jax.jit(lambda v: v * 2)
    out = aot.dispatch("unit", f2, (x,), ())
    np.testing.assert_allclose(np.asarray(out), x * 2)  # never wrong
    s = aot.stats()
    assert s["stale"] == 1  # refused loudly
    assert s["misses"] == 1 and s["exports"] == 1  # recompiled + re-exported
    assert s["hits"] == 0

    # the refreshed entry is healthy again
    aot.set_cache_dir(str(tmp_path))
    aot.reset_counters()
    f3 = jax.jit(lambda v: v * 2)
    np.testing.assert_allclose(
        np.asarray(aot.dispatch("unit", f3, (x,), ())), x * 2
    )
    assert aot.stats()["hits"] == 1 and aot.stats()["stale"] == 0


def test_format_1_entry_is_refused(tmp_path):
    """An entry written before a want_bits launch returned ONE buffer
    (format 1: words and the compaction as four arrays) is never served,
    by either road: its key is another (the name it was filed under is
    not looked up), and one copied onto the new name is refused by its
    meta header and recompiled."""
    import jax

    aot.set_cache_dir(str(tmp_path))
    x = np.arange(4, dtype=np.float32)
    meta = aot._key_meta("unit", (x,), ())
    assert meta["format"] == 2
    old_meta = dict(meta, format=1)
    assert aot._key(old_meta) != aot._key(meta)

    aot.dispatch("unit", jax.jit(lambda v: v - 1), (x,), ())
    (path,) = glob.glob(str(tmp_path / "*.jexp"))
    disk_meta, blob = aot._read_entry(path)
    assert disk_meta == meta
    # filed under its own (format 1) name beside the new one, and copied
    # over the new name
    aot._write_entry(aot._path("unit", aot._key(old_meta)), old_meta, blob)
    aot._write_entry(path, old_meta, blob)

    aot.set_cache_dir(str(tmp_path))
    aot.reset_counters()
    out = aot.dispatch("unit", jax.jit(lambda v: v - 1), (x,), ())
    np.testing.assert_allclose(np.asarray(out), x - 1)
    s = aot.stats()
    assert s["stale"] == 1 and s["hits"] == 0
    assert s["misses"] == 1 and s["exports"] == 1
    assert aot._read_entry(path)[0] == meta  # rewritten under format 2


def test_corrupt_entry_refused(tmp_path):
    import jax

    aot.set_cache_dir(str(tmp_path))
    x = np.ones((4,), np.float32)
    aot.dispatch("unit", jax.jit(lambda v: v + 1), (x,), ())
    (path,) = glob.glob(str(tmp_path / "*.jexp"))
    with open(path, "wb") as f:
        f.write(b"NOTMAGIC garbage")

    aot.set_cache_dir(str(tmp_path))
    aot.reset_counters()
    out = aot.dispatch("unit", jax.jit(lambda v: v + 1), (x,), ())
    np.testing.assert_allclose(np.asarray(out), x + 1)
    assert aot.stats()["stale"] == 1 and aot.stats()["misses"] == 1


# ------------------------------------------------------- zero-trace warm


def test_warm_from_disk_never_traces_inprocess(tmp_path):
    """The deserialized executable is called WITHOUT touching the jit
    function: a fresh jit wrapper's python body never runs (no trace) on
    the warm path."""
    import jax

    aot.set_cache_dir(str(tmp_path))
    x = np.arange(16, dtype=np.float32)

    traced_a = []

    def body_a(v):
        traced_a.append(1)
        return v * 3 + 1

    out1 = aot.dispatch("pin", jax.jit(body_a), (x,), ())
    np.testing.assert_allclose(np.asarray(out1), x * 3 + 1)
    assert len(traced_a) == 1  # the one AOT lower()+compile() trace

    aot.set_cache_dir(str(tmp_path))  # fresh resolution state
    aot.reset_counters()
    traced_b = []

    def body_b(v):
        traced_b.append(1)
        return v * 3 + 1

    out2 = aot.dispatch("pin", jax.jit(body_b), (x,), ())
    np.testing.assert_allclose(np.asarray(out2), x * 3 + 1)
    assert traced_b == []  # served from disk: the body NEVER ran
    assert aot.stats()["hits"] == 1 and aot.stats()["misses"] == 0


_CHILD = r"""
import json, sys
from cedar_tpu.engine.evaluator import TPUPolicyEngine
from cedar_tpu.lang import PolicySet

SRC = sys.stdin.read()
eng = TPUPolicyEngine()
eng.load([PolicySet.from_source(SRC, "t0")], warm="off")
w = eng.warmup(max_batch=8)
print(json.dumps({"traces": w["traces"], "aot": w.get("aot")}))
"""


def _run_child(cache_dir):
    env = dict(os.environ)
    env["CEDAR_TPU_AOT_CACHE"] = str(cache_dir)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("CEDAR_TPU_AOT", None)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD],
        input=SRC,
        cwd=REPO_ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_warm_ladder_zero_traces_fresh_process(tmp_path):
    """The ISSUE 19 cold-start pin, end to end: a FRESH process (fresh jit
    caches, fresh trace counter) warming the real engine ladder from a
    populated cache reports zero fresh kernel traces and all-hits. A
    subprocess, not an in-process reset — the parent's jit caches would
    make a zero-trace claim vacuous."""
    cold = _run_child(tmp_path)
    assert cold["traces"] > 0  # the export pass really compiled
    assert cold["aot"]["exports"] == cold["traces"]

    warm = _run_child(tmp_path)
    assert warm["traces"] == 0, warm
    assert warm["aot"]["hits"] > 0
    assert warm["aot"]["misses"] == 0 and warm["aot"]["stale"] == 0
