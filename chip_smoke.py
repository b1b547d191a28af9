#!/usr/bin/env python3
"""The chip check: serve the 10k-policy webhook from one TPU chip.

Drives the system's main path once, through the entry point an operator
uses, at the size BASELINE.json names as the bar (10,000 authorization
policies), and fails unless the answers provably came from the device:

  1. corpus   from ``--seed``: a 10,000-policy directory store
              (cedar_tpu.corpus.synth) plus the demo admission policies
              (demo/admission-policy.yaml) and a StoreConfig, all written
              under ``--out``; nothing outside the checkout is read.
  2. server   ONE child, ``python -m cedar_tpu.cli.webhook --backend tpu``,
              at the default ``--max-batch 8192``. It is the only process
              that touches the chip: this parent never imports JAX (it
              asserts so before exiting). The child refuses to start when
              JAX finds no TPU, which is how this script fails on a host
              without one.
  3. oracle   while the child loads and compiles its warm ladder (~80
              shapes per engine), the parent answers every request with the
              interpreter (TieredPolicyStores.is_authorized over the same
              files).
  4. wait     for /readyz AND for both engines' warm ladders to finish,
              under ``--ready-deadline-s``. Load, ladder shapes and ladder
              seconds are reported as set-up time.
  5. traffic  over loopback HTTPS (the child's self-signed certificate is
              verified): >= 1,000 DISTINCT SubjectAccessReviews, so the
              decision cache cannot answer them — some one at a time, the
              rest in waves from ``--clients`` concurrent connections — and
              >= 64 AdmissionReviews.
  6. compare  every decision and reason set with the oracle's. Any
              disagreement fails the run.
  7. evidence /debug/engine and /metrics must show: platform tpu;
              fallback_policies == native_opaque_policies == 0; the row
              routing counters accounting for every SAR as a device-decoded
              row (clean_native + flagged + encoder_gate == sent - cache
              hits; gated == encoder_fallback == 0); no fallback batch;
              breakers closed; zero warm-ladder failures; the native
              encoder in use on both paths.
  8. exit     SIGTERM to the child, which must exit 0.

Stdout carries two JSON lines. The last is the verdict, with exactly these
keys: ``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count":
1}}``, the device as the child's JAX reports it. The line before it is the
report (also written to ``<out>/report.json``): the same two keys plus
``sizes`` (rules, L, R), ``ladder``, ``counts``, ``seconds`` and any
``failed_checks``. Exit code 0 only when every check held; nonzero on a
failed check, a phase that raised, or a deadline. With no accelerator
nothing is printed on stdout.

The child runs with ``--batch-window-us 100000``: with the 200 us default the
thread-per-connection Python ingress feeds the batcher a few rows per
window, so closed-loop clients never coalesce into the >= 128-row batches
this check wants on the device (ROADMAP S2 — an ingress property, not a
device one). Every other serving flag is the default.

CPU mode, for debugging this script before chip time is spent::

    JAX_PLATFORMS=cpu python chip_smoke.py --allow-cpu --policies 300 \\
        --max-batch 8 --out /tmp/smoke

runs the same phases and checks in well under a minute, with only the
platform check relaxed. Without ``--allow-cpu`` a CPU platform is a
failure, whatever the size.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import pathlib
import random
import signal
import socket
import ssl
import subprocess
import sys
import threading
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

POLICIES_PER_FILE = 1000
# see the module docstring: the one serving flag that is not the default
BATCH_WINDOW_US = 100_000
SAR_PATH = "/v1/authorize"
ADMIT_PATH = "/v1/admit"


class SmokeFailure(Exception):
    """A phase could not complete (server died, deadline, bad response)."""


_SERVERS: list = []  # every child started, for the hard-deadline watchdog


# ------------------------------------------------------------------ corpus


def write_corpus(out: pathlib.Path, n_policies: int, seed: int):
    """Write the policy directory + StoreConfig; returns (corpus, config
    path, file count)."""
    import yaml

    from cedar_tpu.corpus.synth import synth_corpus

    corpus = synth_corpus(n_policies, seed)
    pol_dir = out / "policies"
    pol_dir.mkdir(parents=True, exist_ok=True)
    for old in pol_dir.glob("*.cedar"):
        old.unlink()
    n_files = 0
    for lo in range(0, len(corpus.sources), POLICIES_PER_FILE):
        chunk = corpus.sources[lo : lo + POLICIES_PER_FILE]
        (pol_dir / f"synth-{lo // POLICIES_PER_FILE:03d}.cedar").write_text(
            "\n".join(chunk) + "\n"
        )
        n_files += 1
    demo = (HERE / "demo" / "admission-policy.yaml").read_text()
    for doc in yaml.safe_load_all(demo):
        if not doc:
            continue
        name = doc["metadata"]["name"]
        (pol_dir / f"{name}.cedar").write_text(doc["spec"]["content"])
        n_files += 1
    config = out / "store-config.yaml"
    config.write_text(
        yaml.safe_dump(
            {
                "apiVersion": "cedar.k8s.aws/v1alpha1",
                "kind": "StoreConfig",
                "spec": {
                    "stores": [
                        {
                            "type": "directory",
                            "directoryStore": {
                                "path": str(pol_dir),
                                # no reload inside the run
                                "refreshInterval": "1h",
                            },
                        }
                    ]
                },
            }
        )
    )
    return corpus, config, n_files


def sar_bodies(corpus, n: int, seed: int) -> list:
    """n DISTINCT SubjectAccessReview bodies, drawn across every cluster of
    the corpus (the generator aims ~80% at real policies)."""
    seen: set = set()
    out: list = []
    round_ = 0
    while len(out) < n:
        for cluster in range(corpus.clusters):
            per = max(16, (n - len(out)) // corpus.clusters + 1)
            for b in corpus.sar_bodies(
                per, cluster=cluster, seed=seed * 1000 + round_
            ):
                if b not in seen:
                    seen.add(b)
                    out.append(b)
        round_ += 1
        if round_ > 200:
            raise SmokeFailure(f"could not draw {n} distinct SAR bodies")
    return out[:n]


def admission_bodies(n: int, seed: int) -> list:
    """n distinct AdmissionReviews aimed at the demo admission policies:
    ConfigMap creates by tenants with/without the owner label, ci-bot in
    and out of kube-public, plus kinds only the allow-all tier answers."""
    rng = random.Random(f"{seed}:admission")
    users = ["alice", "bob", "carol", "ci-bot", "dave"]
    namespaces = ["default", "kube-public", "team-a", "team-b"]
    out = []
    for i in range(n):
        user = rng.choice(users)
        groups = ["tenants"] if rng.random() < 0.6 else ["system:authenticated"]
        ns = rng.choice(namespaces)
        kind = rng.choice(["ConfigMap", "ConfigMap", "ConfigMap", "Secret", "Pod"])
        op = "CREATE" if rng.random() < 0.8 else "UPDATE"
        meta = {"name": f"obj-{i}", "namespace": ns}
        label = rng.random()
        if label < 0.4:
            meta["labels"] = {"owner": user}
        elif label < 0.6:
            meta["labels"] = {"owner": rng.choice(users), "tier": "web"}
        elif label < 0.7:
            meta["labels"] = {"tier": "web"}
        obj = {"apiVersion": "v1", "kind": kind, "metadata": meta}
        if kind == "ConfigMap":
            obj["data"] = {"k": f"v{i}"}
        elif kind == "Pod":
            obj["spec"] = {
                "containers": [{"name": "c", "image": f"img:{i}"}]
            }
        req = {
            "uid": f"smoke-{seed}-{i}",
            "operation": op,
            "userInfo": {
                "username": user,
                "uid": "u-" + user,
                "groups": groups,
            },
            "kind": {"group": "", "version": "v1", "kind": kind},
            "resource": {
                "group": "",
                "version": "v1",
                "resource": kind.lower() + "s",
            },
            "namespace": ns,
            "name": meta["name"],
            "object": obj,
        }
        if op == "UPDATE":
            req["oldObject"] = obj
        out.append(
            json.dumps(
                {
                    "apiVersion": "admission.k8s.io/v1",
                    "kind": "AdmissionReview",
                    "request": req,
                }
            ).encode()
        )
    return out


# ------------------------------------------------------------------ oracle


class Oracle:
    """The interpreter's answers over the same files the server loads."""

    def __init__(self, config_path: pathlib.Path):
        from cedar_tpu.server.admission import (
            CedarAdmissionHandler,
            allow_all_admission_policy_store,
        )
        from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
        from cedar_tpu.stores.config import load_config_stores
        from cedar_tpu.stores.store import TieredPolicyStores

        self.stores = load_config_stores(str(config_path), timeout_s=120.0)
        self.authorizer = CedarWebhookAuthorizer(self.stores)
        self.admission = CedarAdmissionHandler(
            TieredPolicyStores(
                list(self.stores.stores) + [allow_all_admission_policy_store()]
            )
        )

    def close(self) -> None:
        for s in self.stores.stores:
            close = getattr(s, "close", None)
            if close is not None:
                close()

    def sar(self, body: bytes) -> dict:
        from cedar_tpu.server.http import get_authorizer_attributes, sar_response

        decision, reason = self.authorizer.authorize(
            get_authorizer_attributes(json.loads(body))
        )
        return sar_response(decision, reason)

    def admit(self, body: bytes) -> dict:
        from cedar_tpu.entities.admission import AdmissionRequest

        req = AdmissionRequest.from_admission_review(json.loads(body))
        return self.admission.handle(req).to_admission_review()


def _reason_set(text: str):
    """A reason / deny-message string as an order-free value: reason
    ORDER is not a contract (cedar-go iterates a map), the set is."""
    if not text:
        return ()
    try:
        doc = json.loads(text)
    except ValueError:
        return text
    if isinstance(doc, dict):
        return tuple(
            (k, tuple(sorted(json.dumps(x, sort_keys=True) for x in v)))
            for k, v in sorted(doc.items())
        )
    if isinstance(doc, list):
        return tuple(sorted(json.dumps(x, sort_keys=True) for x in doc))
    return text


def sar_verdict(resp: dict):
    st = resp.get("status") or {}
    return (
        bool(st.get("allowed")),
        bool(st.get("denied")),
        _reason_set(st.get("reason", "")),
        st.get("evaluationError", ""),
    )


def admit_verdict(resp: dict):
    r = resp.get("response") or {}
    st = r.get("status") or {}
    return (
        r.get("uid", ""),
        bool(r.get("allowed")),
        st.get("code"),
        _reason_set(st.get("message", "")),
    )


# ------------------------------------------------------------------ server


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """The one child process and its two loopback ports."""

    def __init__(self, args, config: pathlib.Path, out: pathlib.Path):
        self.port = free_port()
        self.metrics_port = free_port()
        self.cert_dir = out / "certs"
        self.log_path = out / "server.log"
        self.cmd = [
            sys.executable, "-m", "cedar_tpu.cli.webhook",
            "--backend", "tpu",
            "--config", str(config),
            "--bind-address", "127.0.0.1",
            "--secure-port", str(self.port),
            "--metrics-port", str(self.metrics_port),
            "--cert-dir", str(self.cert_dir),
            "--max-batch", str(args.max_batch),
            "--batch-window-us", str(BATCH_WINDOW_US),
        ] + list(args.webhook_arg)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(HERE)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
        )
        self._log = open(self.log_path, "wb")
        self.t_start = time.monotonic()
        self.proc = subprocess.Popen(
            self.cmd, cwd=str(HERE), env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        _SERVERS.append(self)
        self._ssl = None

    # -- liveness / plain-http debug surface

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(
                f"server exited rc={rc} before the run finished; log tail:\n"
                + self.log_tail()
            )

    def log_tail(self, n: int = 4000) -> str:
        try:
            data = self.log_path.read_bytes()
        except OSError:
            return ""
        return data[-n:].decode(errors="replace")

    def get(self, path: str, timeout: float = 10.0):
        """(status, body bytes) from the metrics/health port; (None, b"")
        while nothing listens yet."""
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.metrics_port, timeout=timeout
        )
        try:
            conn.request("GET", path)
            r = conn.getresponse()
            return r.status, r.read()
        except OSError:
            return None, b""
        finally:
            conn.close()

    def get_json(self, path: str) -> dict:
        status, body = self.get(path)
        if status != 200:
            raise SmokeFailure(f"GET {path} -> {status}")
        return json.loads(body)

    # -- https

    def https(self) -> http.client.HTTPSConnection:
        if self._ssl is None:
            from cedar_tpu.server.certs import PAIR_NAME

            self._ssl = ssl.create_default_context(
                cafile=str(self.cert_dir / f"{PAIR_NAME}.crt")
            )
        return http.client.HTTPSConnection(
            "127.0.0.1", self.port, timeout=60.0, context=self._ssl
        )

    # -- shutdown

    def terminate(self, grace_s: float = 60.0):
        """SIGTERM, wait; returns the exit code (None = had to be killed)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(grace_s)
            except subprocess.TimeoutExpired:
                pass
        rc = self.proc.poll()
        self.kill()
        return rc

    def kill(self) -> None:
        """Leave nothing behind: the child's whole process group."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(10)
        except subprocess.TimeoutExpired:
            pass
        self._log.close()


def engine_docs(server: Server) -> dict:
    """{path: engine doc} from /debug/engine ({} until both are wired)."""
    status, body = server.get("/debug/engine")
    if status != 200:
        return {}
    doc = json.loads(body)
    out = {}
    for path in ("authorization", "admission"):
        eng = (doc.get(path) or {}).get("engine")
        if eng:
            out[path] = eng
    return out


def ladders_done(docs: dict) -> bool:
    if set(docs) != {"authorization", "admission"}:
        return False
    for eng in docs.values():
        w = eng.get("warm") or {}
        if w.get("running") or not w.get("shapes"):
            return False
        if w.get("compiled", 0) + w.get("failures", 0) < w["shapes"]:
            return False
    return True


# ----------------------------------------------------------------- traffic


def post(conn, path: str, body: bytes) -> dict:
    conn.request(
        "POST", path, body=body, headers={"Content-Type": "application/json"}
    )
    r = conn.getresponse()
    data = r.read()
    if r.status != 200:
        raise SmokeFailure(f"POST {path} -> {r.status}: {data[:200]!r}")
    return json.loads(data)


def send_serial(server: Server, work: list, results: list) -> None:
    conn = server.https()
    try:
        for idx, path, body in work:
            results[idx] = post(conn, path, body)
    finally:
        conn.close()


def send_waves(server: Server, work: list, results: list, clients: int) -> int:
    """The concurrent phase: waves of one request per client, each wave
    released together on keep-alive connections. The pipelined batcher
    lingers its forming window only when nothing is in flight, so it is a
    wave's FIRST tick that coalesces a large batch; pausing between waves
    lets the pipeline drain and gives every wave that tick. Connections are
    opened one after another first: the stdlib listener's accept backlog is
    5, and a burst of handshakes would spend the run in SYN retries.
    Returns the number of waves."""
    clients = max(1, min(clients, len(work)))
    conns = []
    waves = 0

    def one(conn, item, barrier, errors):
        try:
            barrier.wait(timeout=60)
            idx, path, body = item
            results[idx] = post(conn, path, body)
        except Exception as e:  # noqa: BLE001 — reported by the wave loop
            errors.append(f"{type(e).__name__}: {e}")
            barrier.abort()

    try:
        for _ in range(clients):
            conn = server.https()
            conn.connect()
            conns.append(conn)
        for lo in range(0, len(work), clients):
            wave = work[lo : lo + clients]
            barrier = threading.Barrier(len(wave))
            errors: list = []
            threads = [
                threading.Thread(
                    target=one, args=(conn, item, barrier, errors), daemon=True
                )
                for conn, item in zip(conns, wave)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            if any(t.is_alive() for t in threads):
                raise SmokeFailure("a wave of clients did not finish within 120s")
            if errors:
                raise SmokeFailure(
                    f"{len(errors)} client(s) failed: {errors[0]}"
                )
            waves += 1
            time.sleep(0.3)
    finally:
        for conn in conns:
            conn.close()
    return waves


# ----------------------------------------------------------------- metrics


def parse_metrics(text: str) -> list:
    """Prometheus text -> [(name, {label: value}, float)]."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        labels: dict = {}
        name = head
        if "{" in head:
            name, _, rest = head.partition("{")
            for part in rest.rstrip("}").split('",'):
                if "=" in part:
                    k, _, v = part.partition("=")
                    labels[k.strip()] = v.strip().strip('"')
        try:
            out.append((name, labels, float(val)))
        except ValueError:
            continue
    return out


def metric_sum(samples: list, name: str, **match) -> float:
    return sum(
        v
        for n, labels, v in samples
        if n == name and all(labels.get(k) == want for k, want in match.items())
    )


# -------------------------------------------------------------------- main


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--policies", type=int, default=10_000)
    p.add_argument("--max-batch", type=int, default=8192)
    p.add_argument("--sar", type=int, default=1024, help="distinct SAR bodies")
    p.add_argument("--admission", type=int, default=64)
    p.add_argument(
        "--serial", type=int, default=96,
        help="SAR bodies sent one at a time before the concurrent phase",
    )
    p.add_argument("--clients", type=int, default=384)
    p.add_argument("--out", default=str(HERE / "chip_smoke_out"))
    p.add_argument(
        "--ready-deadline-s", type=float, default=900.0,
        help="budget for load + /readyz + both warm ladders",
    )
    p.add_argument(
        "--deadline-s", type=float, default=1150.0,
        help="hard budget for the whole run",
    )
    p.add_argument(
        "--allow-cpu", action="store_true",
        help="debug mode: accept a CPU platform",
    )
    p.add_argument(
        "--webhook-arg", action="append", default=[],
        help="extra argument for the server child (tests inject faults "
        "with it); repeatable",
    )
    return p.parse_args(argv)


def run(args, out: pathlib.Path, result: dict, checks: list) -> None:
    seconds = result["seconds"]
    counts = result["counts"]

    def check(name: str, ok: bool, detail="") -> None:
        checks.append({"check": name, "ok": bool(ok), "detail": detail})

    t0 = time.monotonic()
    corpus, config, n_files = write_corpus(out, args.policies, args.seed)
    sars = sar_bodies(corpus, args.sar, args.seed)
    adms = admission_bodies(args.admission, args.seed)
    seconds["corpus"] = round(time.monotonic() - t0, 2)
    counts["policy_files"] = n_files

    server = Server(args, config, out)
    result["server_cmd"] = " ".join(server.cmd[1:])
    try:
        # ---- oracle, while the child loads + compiles
        t1 = time.monotonic()
        oracle = Oracle(config)
        try:
            want_sar, want_adm = [], []
            last_poll = 0.0
            for kind, bodies, sink in (
                ("sar", sars, want_sar), ("admit", adms, want_adm)
            ):
                fn = oracle.sar if kind == "sar" else oracle.admit
                for b in bodies:
                    sink.append(fn(b))
                    if time.monotonic() - last_poll > 2.0:
                        server.check_alive()
                        last_poll = time.monotonic()
        finally:
            oracle.close()
        seconds["oracle"] = round(time.monotonic() - t1, 2)

        # ---- wait: metrics port (= both engines loaded), /readyz, ladders
        deadline = server.t_start + args.ready_deadline_s
        docs: dict = {}
        while True:
            server.check_alive()
            now = time.monotonic()
            status, _ = server.get("/readyz", timeout=5.0)
            if status is not None and "load" not in seconds:
                seconds["load"] = round(now - server.t_start, 2)
            if status == 200 and "ready" not in seconds:
                seconds["ready"] = round(now - server.t_start, 2)
            if status == 200:
                docs = engine_docs(server)
                skipped = [
                    k for k, v in docs.items()
                    if not (v.get("warm") or {}).get("shapes")
                    and not (v.get("warm") or {}).get("running")
                ]
                if skipped:
                    # ready with no ladder planned: warm-up was switched
                    # off (CEDAR_TPU_WARM_DEFAULT), so nothing here would
                    # prove the chip's compilers took the serving shapes
                    raise SmokeFailure(
                        f"engines {skipped} are ready but ran no warm "
                        "ladder; unset CEDAR_TPU_WARM_DEFAULT"
                    )
                if ladders_done(docs):
                    seconds["ladders_done"] = round(now - server.t_start, 2)
                    break
            if now > deadline:
                raise SmokeFailure(
                    f"not warm within {args.ready_deadline_s:.0f}s "
                    f"(readyz={status}, warm="
                    f"{ {k: v.get('warm') for k, v in docs.items()} })"
                )
            time.sleep(1.0)

        # ---- traffic
        t2 = time.monotonic()
        results: list = [None] * (len(sars) + len(adms))
        work = [(i, SAR_PATH, b) for i, b in enumerate(sars)] + [
            (len(sars) + i, ADMIT_PATH, b) for i, b in enumerate(adms)
        ]
        n_serial = min(args.serial, len(sars))
        adm_serial = len(adms) // 4
        serial = work[:n_serial] + work[len(sars) : len(sars) + adm_serial]
        taken = {w[0] for w in serial}
        concurrent = [w for w in work if w[0] not in taken]
        random.Random(args.seed).shuffle(concurrent)
        send_serial(server, serial, results)
        seconds["traffic_serial"] = round(time.monotonic() - t2, 2)
        t3 = time.monotonic()
        counts["waves"] = send_waves(server, concurrent, results, args.clients)
        seconds["traffic_concurrent"] = round(time.monotonic() - t3, 2)
        counts["sar_sent"] = len(sars)
        counts["admission_sent"] = len(adms)
        counts["serial_requests"] = len(serial)
        counts["concurrent_requests"] = len(concurrent)
        counts["clients"] = min(args.clients, len(concurrent))

        # ---- compare
        bad = []
        for i, (got, want) in enumerate(zip(results[: len(sars)], want_sar)):
            if got is None or sar_verdict(got) != sar_verdict(want):
                bad.append({"kind": "sar", "body": sars[i].decode(),
                            "got": got, "want": want})
        for i, (got, want) in enumerate(zip(results[len(sars) :], want_adm)):
            if got is None or admit_verdict(got) != admit_verdict(want):
                bad.append({"kind": "admit", "body": adms[i].decode(),
                            "got": got, "want": want})
        counts["compared"] = len(results)
        counts["disagreements"] = len(bad)
        decisions = {"allow": 0, "deny": 0, "no_opinion": 0}
        for w in want_sar:
            st = w["status"]
            decisions[
                "allow" if st["allowed"] else "deny" if st["denied"] else "no_opinion"
            ] += 1
        counts["sar_oracle_decisions"] = decisions
        counts["admission_oracle_denied"] = sum(
            1 for w in want_adm if not w["response"]["allowed"]
        )
        if bad:
            (out / "disagreements.json").write_text(json.dumps(bad[:50], indent=1))
        check("answers equal the interpreter oracle", not bad,
              f"{len(bad)} of {len(results)} differ")

        # ---- evidence that the device answered
        docs = engine_docs(server)
        metrics_text = server.get("/metrics")[1].decode()
        samples = parse_metrics(metrics_text)
        (out / "debug_engine.json").write_text(json.dumps(docs, indent=1))
        (out / "metrics.txt").write_text(metrics_text)
        check("native fast path wired on both paths (engine docs present)",
              set(docs) == {"authorization", "admission"}, sorted(docs))
        log_text = server.log_tail(1 << 20)
        check("native encoder loaded; python encode not in use",
              "using python encode" not in log_text
              and "native encoder build failed" not in log_text)
        for path, eng in sorted(docs.items()):
            warm = eng.get("warm") or {}
            result["sizes"][path] = {
                k: eng.get(k) for k in ("rules", "lits", "L", "R")
            }
            result["ladder"][path] = {
                k: warm.get(k) for k in ("shapes", "compiled", "failures", "seconds")
            }
            result["device"] = {
                "platform": eng.get("platform"),
                "kind": eng.get("device_kind"),
                "count": eng.get("n_devices"),
            }
            if not args.allow_cpu:
                check(f"{path}: platform is tpu", eng.get("platform") == "tpu",
                      eng.get("platform"))
            check(f"{path}: no fallback / native-opaque policies",
                  eng.get("fallback_policies") == 0
                  and eng.get("native_opaque_policies") == 0,
                  {k: eng.get(k) for k in
                   ("fallback_policies", "native_opaque_policies")})
            check(f"{path}: warm ladder complete, zero failures",
                  warm.get("failures") == 0
                  and warm.get("compiled") == warm.get("shapes"),
                  warm)
        counts["fallback_policies"] = sum(
            eng.get("fallback_policies") or 0 for eng in docs.values()
        )
        counts["policies"] = args.policies

        def routing(path, row_class):
            return int(metric_sum(
                samples, "cedar_authorizer_row_routing_total",
                path=path, row_class=row_class,
            ))

        for path, sent in (("authorization", len(sars)), ("admission", len(adms))):
            rows = {
                c: routing(path, c)
                for c in ("clean_native", "flagged", "encoder_gate",
                          "gated", "encoder_fallback")
            }
            counts[f"{path}_rows"] = rows
            hits = int(metric_sum(
                samples, "cedar_decision_cache_hits_total", path=path
            ))
            counts[f"{path}_cache_hits"] = hits
            device_rows = (
                rows["clean_native"] + rows["flagged"] + rows["encoder_gate"]
            )
            check(f"{path}: every cache miss is a device-decoded row",
                  device_rows == sent - hits
                  and rows["gated"] == 0 and rows["encoder_fallback"] == 0,
                  {"sent": sent, "cache_hits": hits, **rows})
        fb = int(metric_sum(samples, "cedar_authorizer_fallback_batches_total"))
        counts["fallback_batches"] = fb
        check("no fallback batch", fb == 0, fb)
        breakers = {
            labels.get("engine"): v
            for n, labels, v in samples
            if n == "cedar_authorizer_breaker_state"
        }
        counts["breaker_state"] = breakers
        check("breakers closed on both engines",
              all(v == 0 for v in breakers.values())
              and {"authorization", "admission"} <= set(breakers),
              breakers)
        # batch sizes the concurrent phase reached. The histogram's edges
        # are powers of two, so "more than 128 rows" is the nearest it can
        # state to the 128-or-more this check wants; a toy --max-batch
        # (CPU mode) is held to more than half of itself instead.
        floor = 128 if args.max_batch > 128 else args.max_batch // 2
        formed = {}
        for path in ("authorization", "admission"):
            total = metric_sum(samples, "cedar_batch_occupancy_count", path=path)
            small = metric_sum(
                samples, "cedar_batch_occupancy_bucket", path=path,
                le=str(floor),
            )
            formed[path] = {
                "batches": int(total), f"over_{floor}_rows": int(total - small)
            }
        counts["batches"] = formed
        check(f"the batcher formed authorization batches of more than {floor} rows",
              formed["authorization"][f"over_{floor}_rows"] > 0, formed)
    finally:
        t4 = time.monotonic()
        rc = server.terminate()
        seconds["shutdown"] = round(time.monotonic() - t4, 2)
        counts["server_exit_code"] = rc
        check("server exits 0 on SIGTERM", rc == 0, rc)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        from cedar_tpu.jaxenv import cpu_requested  # imports no JAX
    except ImportError as e:
        print(f"chip_smoke: the cedar_tpu package is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    if cpu_requested() and not args.allow_cpu:
        print(
            "chip_smoke: JAX_PLATFORMS=cpu — no accelerator will be used. "
            "This check needs one TPU chip; pass --allow-cpu (with a small "
            "--policies) only to debug the script itself.",
            file=sys.stderr,
        )
        return 2

    out = pathlib.Path(args.out).resolve()
    out.mkdir(parents=True, exist_ok=True)
    result: dict = {
        "ok": False, "device": None, "sizes": {}, "ladder": {},
        "counts": {}, "seconds": {}, "seed": args.seed,
        "max_batch": args.max_batch,
    }
    checks: list = []

    # the whole run under one hard budget: a hung phase must not outlive
    # it, and must not leave the child holding the chip
    def on_deadline():
        print(f"chip_smoke: hard deadline {args.deadline_s:.0f}s hit",
              file=sys.stderr, flush=True)
        for server in _SERVERS:
            server.kill()
        os._exit(3)

    watchdog = threading.Timer(args.deadline_s, on_deadline)
    watchdog.daemon = True
    watchdog.start()
    t_all = time.monotonic()
    error = ""
    try:
        run(args, out, result, checks)
    except SmokeFailure as e:
        error = str(e)
    except Exception as e:  # noqa: BLE001 — a phase that raised fails the run
        import traceback

        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"
    watchdog.cancel()
    result["seconds"]["total"] = round(time.monotonic() - t_all, 2)
    failed = [c for c in checks if not c["ok"]]
    assert "jax" not in sys.modules, "the smoke's parent must never import JAX"
    result["parent_imported_jax"] = False
    for c in checks:
        print(("ok   " if c["ok"] else "FAIL ") + c["check"]
              + ("" if c["ok"] else f"  <- {c['detail']}"), file=sys.stderr)
    if error:
        print(f"chip_smoke: {error}", file=sys.stderr)
    result["ok"] = not error and not failed and bool(checks)
    if error:
        result["error"] = error[:2000]
    if failed:
        result["failed_checks"] = [c["check"] for c in failed]
    result["claim"] = None
    (out / "report.json").write_text(json.dumps(result, indent=1) + "\n")
    device = result.get("device") or {}
    if not (
        isinstance(device.get("platform"), str)
        and isinstance(device.get("kind"), str)
        and type(device.get("count")) is int
    ):
        # never reached a device (no accelerator, or the server never came
        # up): a failure with nothing to report on stdout
        return 1
    print(json.dumps(result))
    # the contract line: exactly these keys, last on stdout
    print(json.dumps({"ok": result["ok"], "device": device}), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
