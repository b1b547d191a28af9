"""cedar-chaos: scripted game-day runner against a live webhook.

Executes a chaos scenario (built-in name or JSON file, cedar_tpu/chaos)
against a running server's /chaos control surface and asserts the SLOs
that make the exercise a PASS instead of an anecdote:

  1. CONTROL run — scenario disarmed; drive a deterministic SAR stream,
     record every response body and latency.
  2. FAULT run — configure + arm the scenario; drive the SAME stream.
     Availability = fraction of requests answered cleanly (HTTP 200, no
     evaluationError). Correctness = every clean fault-run answer's
     decision matches the control run's for the same body — degraded
     answers are allowed to become NoOpinion+error, never to flip a
     decision.
  3. RECOVERY run — disarm; drive the stream again and require p99 back
     within ``recovery_p99_ratio`` of control (+ an absolute floor).

The target server must have been started with
``--confirm-non-prod-inject-errors`` (the /chaos endpoints answer 403
otherwise). ``--spawn`` brings up a throwaway local server with a small
policy corpus first — what ``make gameday`` runs. One JSON result line on
stdout; rc 0 iff every SLO held. docs/resilience.md "Game days" is the
runbook.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time
from typing import List, Optional

from ..chaos.scenario import (
    BUILTIN_SCENARIOS,
    ScenarioError,
    builtin_scenario,
    load_scenario_file,
)


def _http(method: str, url: str, body: Optional[bytes] = None, timeout=10.0):
    """(status, body bytes) for one request; connection errors raise."""
    import urllib.error
    import urllib.request

    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def make_sar_stream(n: int, seed: int = 42) -> List[bytes]:
    """Deterministic mixed SAR bodies: the same seed produces the same
    stream on every run, so control/fault/recovery runs (and reruns of a
    failing game day) compare identical traffic."""
    rng = random.Random(seed)
    users = [f"user-{i}" for i in range(16)] + ["test-user"]
    verbs = ["get", "list", "watch", "create", "delete"]
    resources = ["pods", "secrets", "configmaps", "services"]
    out = []
    for _ in range(n):
        sar = {
            "apiVersion": "authorization.k8s.io/v1",
            "kind": "SubjectAccessReview",
            "spec": {
                "user": rng.choice(users),
                "uid": "u",
                "groups": ["system:authenticated"],
                "resourceAttributes": {
                    "verb": rng.choice(verbs),
                    "version": "v1",
                    "resource": rng.choice(resources),
                    "namespace": f"ns-{rng.randint(0, 7)}",
                },
            },
        }
        out.append(json.dumps(sar).encode())
    return out


def _decision(resp_body: bytes):
    """(clean, decision) from one /v1/authorize response body: clean means
    a decision with no evaluationError; decision is the (allowed, denied)
    pair — the thing a fault must never flip."""
    try:
        doc = json.loads(resp_body)
        status = doc.get("status") or {}
    except Exception:  # noqa: BLE001 — an unparseable answer is unclean
        return False, None
    clean = not status.get("evaluationError")
    return clean, (bool(status.get("allowed")), bool(status.get("denied")))


def drive(server_url: str, stream: List[bytes], timeout_s: float = 10.0):
    """POST every body; returns (results, latencies): results[i] =
    (clean, decision) with decision None on transport errors."""
    results, latencies = [], []
    for body in stream:
        t0 = time.monotonic()
        try:
            status, resp = _http(
                "POST", f"{server_url}/v1/authorize", body, timeout=timeout_s
            )
        except Exception:  # noqa: BLE001 — transport failure = unavailable
            results.append((False, None))
            latencies.append(time.monotonic() - t0)
            continue
        latencies.append(time.monotonic() - t0)
        if status != 200:
            results.append((False, None))
            continue
        results.append(_decision(resp))
    return results, latencies


def _p99(latencies: List[float]) -> float:
    s = sorted(latencies)
    return s[min(len(s) - 1, int(len(s) * 0.99))] if s else 0.0


def run_gameday(
    scenario: dict,
    server_url: str,
    control_url: str,
    requests: int = 400,
    settle_s: float = 2.0,
) -> dict:
    """The three-phase protocol from the module docstring; returns the
    result record (rc decided by the caller from result["pass"])."""
    slo = scenario["slo"]
    stream = make_sar_stream(requests, seed=int(scenario.get("seed", 0)))

    # make sure nothing stale is armed, then control-run
    status, body = _http("POST", f"{control_url}/chaos/reset", b"")
    if status == 403:
        raise RuntimeError(
            "chaos control is disabled on the target server; start it with "
            "--confirm-non-prod-inject-errors"
        )
    control, control_lat = drive(server_url, stream)
    control_p99 = _p99(control_lat)

    status, body = _http(
        "POST",
        f"{control_url}/chaos/configure",
        json.dumps(scenario).encode(),
    )
    if status != 200:
        raise RuntimeError(f"chaos configure failed ({status}): {body!r}")
    _http("POST", f"{control_url}/chaos/arm", b"")
    fault, fault_lat = drive(server_url, stream)
    _http("POST", f"{control_url}/chaos/disarm", b"")

    # let the supervisor / breaker / recovery settle before measuring the
    # recovered latency profile
    time.sleep(settle_s)
    recovery, recovery_lat = drive(server_url, stream)
    recovery_p99 = _p99(recovery_lat)
    _, chaos_stats = _http("GET", f"{control_url}/debug/chaos")

    clean = sum(1 for ok, _ in fault if ok)
    availability = clean / max(1, len(fault))
    wrong = sum(
        1
        for (f_ok, f_dec), (c_ok, c_dec) in zip(fault, control)
        if f_ok and c_ok and f_dec != c_dec
    )
    rec_wrong = sum(
        1
        for (f_ok, f_dec), (c_ok, c_dec) in zip(recovery, control)
        if f_ok and c_ok and f_dec != c_dec
    )
    p99_budget = (
        control_p99 * float(slo["recovery_p99_ratio"])
        + float(slo["recovery_p99_floor_ms"]) / 1e3
    )
    availability_ok = availability >= float(slo["availability"])
    recovered_ok = recovery_p99 <= p99_budget
    recovered_avail = sum(1 for ok, _ in recovery if ok) / max(1, len(recovery))
    result = {
        "metric": "chaos_gameday",
        "scenario": scenario.get("name", ""),
        "requests": len(stream),
        "availability": round(availability, 4),
        "availability_slo": slo["availability"],
        "wrong_decisions": wrong,
        "recovery_wrong_decisions": rec_wrong,
        "recovered_availability": round(recovered_avail, 4),
        "control_p99_ms": round(control_p99 * 1e3, 2),
        "fault_p99_ms": round(_p99(fault_lat) * 1e3, 2),
        "recovered_p99_ms": round(recovery_p99 * 1e3, 2),
        "recovered_p99_budget_ms": round(p99_budget * 1e3, 2),
        "availability_ok": availability_ok,
        "zero_wrong_decisions": wrong == 0 and rec_wrong == 0,
        "recovered_p99_ok": recovered_ok,
        "injections": _injection_summary(chaos_stats),
    }
    result["pass"] = bool(
        availability_ok and result["zero_wrong_decisions"] and recovered_ok
    )
    return result


def _injection_summary(raw: bytes) -> dict:
    try:
        doc = json.loads(raw)
        return {
            seam: sum(r.get("fired", 0) for r in s.get("rules", []))
            for seam, s in (doc.get("seams") or {}).items()
        }
    except Exception:  # noqa: BLE001 — summary is best-effort
        return {}


# ------------------------------------------------------------------ spawn


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


SPAWN_POLICIES = """
permit (
    principal,
    action in [k8s::Action::"get", k8s::Action::"list"],
    resource is k8s::Resource
) when { principal.name == "test-user" && resource.resource == "pods" };
forbid (
    principal,
    action == k8s::Action::"delete",
    resource is k8s::Resource
) when { resource.resource == "secrets" };
"""


def spawn_server(tmpdir: str, extra_args=()):
    """Launch a throwaway local webhook (plain HTTP, TPU backend on
    whatever jax backend the env pins, chaos control enabled) and wait for
    readiness. ``extra_args`` appends CLI flags — scenarios that need a
    particular topology carry them as "spawn_args" (replica-loss spawns
    --fleet-replicas 2). Returns (process, server_url, control_url)."""
    import os
    import subprocess

    policy_dir = os.path.join(tmpdir, "policies")
    os.makedirs(policy_dir, exist_ok=True)
    with open(os.path.join(policy_dir, "gameday.cedar"), "w") as f:
        f.write(SPAWN_POLICIES)
    config_path = os.path.join(tmpdir, "config.yaml")
    with open(config_path, "w") as f:
        f.write(
            "apiVersion: cedar.k8s.aws/v1alpha1\n"
            "kind: StoreConfig\n"
            "spec:\n"
            "  stores:\n"
            '    - type: "directory"\n'
            "      directoryStore:\n"
            f'        path: "{policy_dir}"\n'
        )
    port, metrics_port = _free_port(), _free_port()
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "cedar_tpu.cli.webhook",
            "--config", config_path,
            "--backend", "tpu",
            "--insecure",
            "--secure-port", str(port),
            "--metrics-port", str(metrics_port),
            "--confirm-non-prod-inject-errors",
            "--request-timeout-ms", "1000",
            "--supervisor-interval-seconds", "0.2",
            "--breaker-recovery-seconds", "1.0",
            *[str(a) for a in extra_args],
        ],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    server_url = f"http://127.0.0.1:{port}"
    control_url = f"http://127.0.0.1:{metrics_port}"
    deadline = time.time() + 120
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"spawned webhook exited rc={proc.returncode} before ready "
                "(--backend tpu refuses to start without a TPU; set "
                "JAX_PLATFORMS=cpu to run the game day on the CPU plane)"
            )
        try:
            status, _ = _http("GET", f"{control_url}/readyz", timeout=2.0)
            if status == 200:
                if "--fleet-replicas" in extra_args:
                    # the scenario REQUIRES the replicated topology: a
                    # server that silently downgraded to single-engine
                    # (no native fast path) would run the game day with
                    # no replica to kill and report a vacuous pass
                    status, _ = _http(
                        "GET", f"{control_url}/debug/fleet", timeout=2.0
                    )
                    if status != 200:
                        proc.terminate()
                        raise RuntimeError(
                            "spawned webhook is not serving a fleet "
                            "(/debug/fleet answered "
                            f"{status}); the scenario needs "
                            "--fleet-replicas support (native fast "
                            "path required)"
                        )
                return proc, server_url, control_url
        except RuntimeError:
            raise
        except Exception:  # noqa: BLE001 — still starting
            pass
        time.sleep(0.5)
    proc.terminate()
    raise RuntimeError("spawned webhook never became ready")


# ------------------------------------------------------------------- main


def make_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="cedar-chaos",
        description="scripted game-day runner for the cedar webhook "
        "(docs/resilience.md)",
    )
    parser.add_argument(
        "--scenario",
        default="",
        help="built-in scenario name or a scenario JSON file "
        "(--list-scenarios shows the builtins)",
    )
    parser.add_argument(
        "--server",
        default="http://127.0.0.1:10288",
        help="serving base URL (plain HTTP or terminated TLS proxy)",
    )
    parser.add_argument(
        "--control",
        default="http://127.0.0.1:10289",
        help="metrics/control base URL (the /chaos endpoints)",
    )
    parser.add_argument(
        "--spawn",
        action="store_true",
        help="launch a throwaway local webhook first (make gameday)",
    )
    parser.add_argument(
        "--requests",
        type=int,
        default=400,
        help="requests per phase (control / fault / recovery)",
    )
    parser.add_argument(
        "--settle-seconds",
        type=float,
        default=2.0,
        help="wait between disarm and the recovery measurement",
    )
    parser.add_argument(
        "--list-seams", action="store_true", help="print the seam catalogue"
    )
    parser.add_argument(
        "--list-scenarios",
        action="store_true",
        help="print the built-in scenarios",
    )
    return parser


def main(argv: Optional[List[str]] = None) -> int:
    args = make_parser().parse_args(argv)
    if args.list_seams:
        from ..chaos.registry import SEAMS

        for name, where in sorted(SEAMS.items()):
            print(f"{name:24s} {where}")
        return 0
    if args.list_scenarios:
        for name, doc in BUILTIN_SCENARIOS.items():
            print(f"{name:16s} {doc['description']}")
        return 0
    if not args.scenario:
        print("--scenario is required (see --list-scenarios)", file=sys.stderr)
        return 2
    try:
        scenario = builtin_scenario(args.scenario)
        if scenario is None:
            scenario = load_scenario_file(args.scenario)
    except (OSError, ScenarioError) as e:
        print(f"bad scenario: {e}", file=sys.stderr)
        return 2

    proc = tmpdir = None
    server_url, control_url = args.server, args.control
    try:
        if args.spawn:
            import tempfile

            tmpdir = tempfile.mkdtemp(prefix="cedar-gameday-")
            proc, server_url, control_url = spawn_server(
                tmpdir, extra_args=scenario.get("spawn_args") or ()
            )
        result = run_gameday(
            scenario,
            server_url,
            control_url,
            requests=args.requests,
            settle_s=args.settle_seconds,
        )
    except Exception as e:  # noqa: BLE001 — one parseable error line
        print(json.dumps({"metric": "chaos_gameday", "error": str(e)}))
        return 1
    finally:
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — best-effort teardown
                proc.kill()
        if tmpdir is not None:
            import shutil

            shutil.rmtree(tmpdir, ignore_errors=True)
    print(json.dumps(result))
    return 0 if result["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
