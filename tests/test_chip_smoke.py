"""chip_smoke.py on the CPU at toy size, and the two jaxenv functions it
leans on.

The smoke is the driver's proof that the webhook serves from the chip. Two
subprocess cases pin the property that matters: the script passes when the
device planes answered every row, and FAILS — through the same checks —
when a kernel is forced to raise and the breaker + interpreter answer
instead (correct answers, wrong place). Together they show the smoke
cannot pass on a path that fell back.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"

SMALL = [
    "--allow-cpu", "--policies", "300", "--max-batch", "8",
    "--sar", "160", "--admission", "64", "--serial", "8", "--clients", "64",
    "--ready-deadline-s", "240", "--deadline-s", "400",
]


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """One JAX compile cache for both smoke runs (the second ladder reads
    what the first wrote), placed from outside like the driver does."""
    return tmp_path_factory.mktemp("jax-cache")


def _run_smoke(tmp_path, compile_cache, *extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(compile_cache)
    # the suite's 8 virtual devices and warm-up-off default (conftest) are
    # for the in-process tests, not for a server under test
    env.pop("XLA_FLAGS", None)
    env.pop("CEDAR_TPU_WARM_DEFAULT", None)
    proc = subprocess.run(
        [sys.executable, str(SMOKE), "--out", str(tmp_path / "out"), *SMALL, *extra],
        env=env, capture_output=True, text=True, timeout=420,
    )
    lines = proc.stdout.strip().splitlines()
    if not lines:
        return proc, None
    # the last line is the verdict and holds exactly its two keys; the
    # line before it is the report the assertions below read
    verdict, tail = json.loads(lines[-1]), json.loads(lines[-2])
    assert set(verdict) == {"ok", "device"}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict == {"ok": tail["ok"], "device": tail["device"]}
    assert json.loads((tmp_path / "out" / "report.json").read_text()) == tail
    return proc, tail


def test_smoke_passes_when_the_device_planes_answer(tmp_path, compile_cache):
    proc, tail = _run_smoke(tmp_path, compile_cache)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert tail["ok"] is True
    assert tail["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    c = tail["counts"]
    assert c["compared"] == 160 + 64 and c["disagreements"] == 0
    assert c["fallback_policies"] == 0 and c["fallback_batches"] == 0
    rows = c["authorization_rows"]
    assert (
        rows["clean_native"] + rows["flagged"] + rows["encoder_gate"]
        == 160 - c["authorization_cache_hits"]
    )
    assert rows["gated"] == 0 and rows["encoder_fallback"] == 0
    for path in ("authorization", "admission"):
        ladder = tail["ladder"][path]
        assert ladder["failures"] == 0
        assert ladder["compiled"] == ladder["shapes"] > 0
        assert tail["sizes"][path]["rules"] >= 300
    assert tail["parent_imported_jax"] is False
    assert c["server_exit_code"] == 0
    assert any(compile_cache.iterdir()), "the cache was not written where placed"


def test_smoke_fails_when_a_forced_kernel_failure_falls_back(
    tmp_path, compile_cache
):
    """The device-loss game day makes engine dispatch raise: every answer
    is still correct (interpreter fallback), and the smoke must say no."""
    proc, tail = _run_smoke(
        tmp_path, compile_cache,
        "--webhook-arg=--chaos-scenario=device-loss",
        "--webhook-arg=--confirm-non-prod-inject-errors",
    )
    assert proc.returncode != 0
    assert tail["ok"] is False
    assert tail["counts"]["disagreements"] == 0  # correct, from elsewhere
    assert tail["counts"]["fallback_batches"] > 0
    assert "no fallback batch" in tail["failed_checks"]
    assert (
        "authorization: every cache miss is a device-decoded row"
        in tail["failed_checks"]
    )


def test_smoke_refuses_cpu_without_being_asked(tmp_path):
    """JAX_PLATFORMS=cpu alone is 'no accelerator': nonzero, empty stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(SMOKE), "--out", str(tmp_path / "out")],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    (tmp_path / "chip_smoke.py").write_bytes(SMOKE.read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# ---------------------------------------------------------------- jaxenv


def test_require_tpu_raises_on_the_cpu_backend():
    from cedar_tpu.jaxenv import require_tpu

    with pytest.raises(RuntimeError, match="no TPU"):
        require_tpu()


def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch, tmp_path):
    import jax

    from cedar_tpu.jaxenv import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path(tmp_path):
    """Unset, the cache resolves to <repo>/.jax_cache whatever the working
    directory (the path is part of the cache key: it must not move)."""
    code = (
        "from cedar_tpu.jaxenv import configure_compile_cache\n"
        "import jax\n"
        "print(configure_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO)
    seen = set()
    for cwd in (tmp_path, REPO / "tests"):
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env,
            capture_output=True, text=True, timeout=120, check=True,
        ).stdout.split()
        assert out[0] == out[1]
        seen.add(out[0])
    assert seen == {str(REPO / ".jax_cache")}
