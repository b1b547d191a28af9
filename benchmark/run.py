#!/usr/bin/env python3
"""One run of one cell: load, warm, measure, compare, print one line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The processes (a chip belongs to one):

  this parent     never imports JAX. Writes the corpus from ``--seed``,
                  starts the server child, works out the reference's
                  answers in a pool of processes while the child loads,
                  starts the generator processes, reads ``/metrics`` and
                  ``/debug/engine`` at both ends of the window, compares
                  every answer of the window with the reference, prints.
  serve.py        the server child, the only process on the chip:
                  ``cedar_tpu.cli.webhook.main`` — the operator's entry
                  point — with the configuration's ``server_args``.
  loadgen.py      generator processes on cores of their own.

Set-up (``setup_s``) runs from this process's start to the start of the
window: corpus, server load, /readyz, both engines' warm ladders, the
connections' TLS handshakes, and a warm-up of the cell's own traffic.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: every number the comparison held,
beside its limit. The same numbers are the last lines on standard error.
With no TPU (and no ``--allow-cpu``) it exits non-zero and prints no line.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import pathlib
import random
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

T_PROCESS_START = time.monotonic()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import loadgen, prom, stats, traffic  # noqa: E402
from benchmark.manifest import Manifest, corpus_module, reader_module  # noqa: E402
from benchmark.refpool import ReferencePool  # noqa: E402

TRACE_SECONDS = 3.0
# the program's default --request-timeout-ms: the configurations serve with
# 30 s, and over_deadline_share says what the default would have cost
DEFAULT_DEADLINE_S = 2.0
READY_DEADLINE_S = 1000.0  # server start -> /readyz and both warm ladders
RUN_DEADLINE_S = 1150.0  # the whole run: a cold first run may take 1200 s
CERT_PAIR = "cedar-authorizer-server"


class RunFailure(Exception):
    """A phase could not complete: no result line is printed."""


def log(msg: str) -> None:
    print(f"[run {time.monotonic() - T_PROCESS_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------------ cores

def plan_cores(processes: int, cores, rehearsal: bool = False) -> dict:
    """Disjoint CPU sets from ``cores``: the server on the first, each
    generator process on one of its own, this parent on the last. A host
    too small for that cannot make the measurement; a rehearsal, whose
    numbers mean nothing, goes on there with nothing pinned."""
    cores = sorted(cores)
    if len(cores) < processes + 3:
        if rehearsal:
            return {"cores": len(cores), "server": None, "parent": None,
                    "generators": [None] * processes}
        raise RunFailure(
            f"{len(cores)} cores: too few to give {processes} generator processes, "
            "the parent and the server (two or more) cores of their own"
        )
    return {"cores": len(cores), "server": cores[:-(processes + 1)],
            "generators": cores[-(processes + 1):-1], "parent": [cores[-1]]}


def cpu_list(cpus) -> str:
    return ",".join(str(c) for c in cpus)


# ----------------------------------------------------------------- server

def free_port() -> int:
    """A loopback port below the kernel's ephemeral range. The child binds
    it only once it has loaded, many seconds from now; a port the kernel
    hands out to ``bind(0)`` could be taken by another process meanwhile."""
    for _ in range(200):
        port = random.SystemRandom().randrange(20000, 32000)
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
            return port
    raise RunFailure("no free loopback port between 20000 and 32000")


class Server:
    """The one child on the chip, its two loopback ports and its side
    channel (benchmark/serve.py)."""

    def __init__(self, out: pathlib.Path, config_path: pathlib.Path,
                 server_args: list, cpus):
        self.port = free_port()
        self.metrics_port = free_port()
        self.cert_dir = out / "certs"
        self.ctl_dir = out / "ctl"
        self.ctl_dir.mkdir(parents=True, exist_ok=True)
        self.log_path = out / "server.log"
        self.cmd = [sys.executable, str(HERE / "serve.py"),
                    "--ctl-dir", str(self.ctl_dir)]
        if cpus:
            self.cmd += ["--cpus", cpu_list(cpus)]
        self.cmd += [
            "--",
            "--backend", "tpu",
            "--config", str(config_path),
            "--bind-address", "127.0.0.1",
            "--secure-port", str(self.port),
            "--metrics-port", str(self.metrics_port),
            "--cert-dir", str(self.cert_dir),
        ] + list(server_args)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(ROOT)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
        )
        # the warm ladder is part of what a run measures; a test harness
        # that switches it off for its own engines must not reach the child
        env.pop("CEDAR_TPU_WARM_DEFAULT", None)
        self._log = open(self.log_path, "wb")
        self.t_start = time.monotonic()
        self.proc = subprocess.Popen(
            self.cmd, cwd=str(ROOT), env=env, stdin=subprocess.PIPE,
            stdout=self._log, stderr=subprocess.STDOUT, start_new_session=True,
        )
        self._next_id = 0

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise RunFailure(f"server exited rc={rc}; log tail:\n{self.log_tail()}")

    def log_tail(self, n: int = 3000) -> str:
        try:
            return self.log_path.read_bytes()[-n:].decode(errors="replace")
        except OSError:
            return ""

    def get(self, path: str, timeout: float = 10.0):
        """(status, body) from the metrics port; (None, b"") while nothing
        listens."""
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{self.metrics_port}{path}", timeout=timeout
            ) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()
        except OSError:
            return None, b""

    def engine_docs(self) -> dict:
        status, body = self.get("/debug/engine")
        if status != 200:
            return {}
        doc = json.loads(body)
        return {
            path: (doc.get(path) or {})["engine"]
            for path in ("authorization", "admission")
            if (doc.get(path) or {}).get("engine")
        }

    def metrics(self, keep: pathlib.Path | None = None) -> list:
        status, body = self.get("/metrics")
        if status != 200:
            raise RunFailure(f"GET /metrics -> {status}")
        if keep is not None:
            keep.write_bytes(body)
        return prom.parse(body.decode())

    def control(self, cmd: dict, timeout: float = 60.0) -> dict:
        """One command to the child's side channel, and its reply."""
        self._next_id += 1
        cmd = dict(cmd, id=self._next_id)
        reply = self.ctl_dir / f"reply-{self._next_id}.json"
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        self.proc.stdin.flush()
        deadline = time.monotonic() + timeout
        while not reply.exists():
            self.check_alive()
            if time.monotonic() > deadline:
                raise RunFailure(f"no reply to {cmd} within {timeout}s")
            time.sleep(0.01)
        doc = json.loads(reply.read_text())
        if "error" in doc:
            raise RunFailure(f"server side channel: {cmd['cmd']}: {doc['error']}")
        return doc

    def terminate(self, grace_s: float = 60.0):
        """SIGTERM, wait; the exit code, or None if it had to be killed."""
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
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(10)
        except subprocess.TimeoutExpired:
            pass
        if self.proc.stdin:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
        self._log.close()


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


def wait_warm(server: "Server", deadline_s: float) -> tuple:
    """Poll until /readyz answers 200 and both engines' warm ladders are
    complete; (engine docs, monotonic time of the first 200)."""
    deadline = server.t_start + deadline_s
    docs: dict = {}
    t_ready = None
    while True:
        server.check_alive()
        now = time.monotonic()
        status, _ = server.get("/readyz", timeout=5.0)
        if status == 200:
            if t_ready is None:
                t_ready = now
                log("/readyz 200")
            docs = server.engine_docs()
            if ladders_done(docs):
                return docs, t_ready
        if now > deadline:
            raise RunFailure(
                f"not warm within {deadline_s:.0f}s (readyz={status}, "
                f"warm={ {k: v.get('warm') for k, v in docs.items()} })"
            )
        time.sleep(0.5)


def write_store(out: pathlib.Path, corpus) -> pathlib.Path:
    """The corpus as a directory store and its StoreConfig (JSON is YAML)."""
    pol_dir = out / "policies"
    pol_dir.mkdir(parents=True, exist_ok=True)
    for name, text in corpus.files.items():
        (pol_dir / name).write_text(text)
    config = out / "store-config.json"
    config.write_text(json.dumps({
        "apiVersion": "cedar.k8s.aws/v1alpha1",
        "kind": "StoreConfig",
        "spec": {"stores": [{
            "type": "directory",
            # no reload inside a run
            "directoryStore": {"path": str(pol_dir), "refreshInterval": "1h"},
        }]},
    }))
    return config


# -------------------------------------------------------------- generators

class Generators:
    """The generator processes of one run."""

    def __init__(self, plan, server: Server, cores: dict):
        ctx = multiprocessing.get_context("spawn")
        n = plan.processes
        threads = plan.connections // n
        shares = loadgen.split(plan, n, threads)
        cafile = str(server.cert_dir / f"{CERT_PAIR}.crt")
        self.procs = []
        self._drained: set = set()
        for k in range(n):
            spec = {
                "host": "127.0.0.1", "port": server.port, "cafile": cafile,
                "threads": threads, "loop": plan.loop, "items": shares[k],
                "seconds": plan.seconds, "warmup_s": plan.warmup_s,
                "cpu": cores["generators"][k], "kind": plan.kind_ref,
            }
            mine, theirs = ctx.Pipe()
            p = ctx.Process(target=loadgen.process_main, args=(spec, theirs), daemon=True)
            p.start()
            theirs.close()
            # one process after another: the listener's accept backlog is
            # short, and a burst of handshakes would sit in SYN retries
            self._expect(mine, "ready", 120.0)
            self.procs.append((p, mine))

    @staticmethod
    def _expect(pipe, what: str, timeout: float):
        if not pipe.poll(timeout):
            raise RunFailure(f"a generator process did not report {what} in {timeout}s")
        kind, payload = pipe.recv()
        if kind != what:
            raise RunFailure(f"generator process: {kind}: {payload}")
        return payload

    def go(self, t0: float) -> None:
        for _, pipe in self.procs:
            pipe.send(t0)

    def drained(self, timeout: float) -> None:
        """Wait until every process has every answer it was still owed."""
        for k, (_, pipe) in enumerate(self.procs):
            if k not in self._drained:
                self._expect(pipe, "drained", timeout)
                self._drained.add(k)

    def results(self, timeout: float) -> list:
        self.drained(timeout)
        out = [self._expect(pipe, "done", timeout) for _, pipe in self.procs]
        for p, _ in self.procs:
            p.join(10)
        return out

    def kill(self) -> None:
        for p, _ in self.procs:
            if p.is_alive():
                p.kill()
            p.join(5)


# ------------------------------------------------------------------ a run

class Context:
    """What the per-layer metrics' readers read from."""

    def __init__(self):
        self.prom_before = self.prom_after = []
        self.engine_before = self.engine_after = {}
        self.loadgen: dict = {}
        self.harness: dict = {}
        self.trace = None
        self.trace_window_s = 0.0
        self.device: dict = {}


def answered(record, kind) -> bool:
    """A 200 whose body read as a verdict that, by the request's kind, does
    not say the program gave up."""
    verdict = record[5]
    return record[4] == 200 and verdict is not None and not kind.gave_up(verdict)


def window_numbers(plan, records: list, t0: float) -> dict:
    """The window's requests reduced to what users would see. ``records``
    are (index, due, sent, done, status, verdict, note) on the shared
    monotonic clock."""
    if plan.loop == "open":
        window = [r for r in records if r[1] >= t0 - 1e-9]
    else:
        window = [r for r in records if r[3] >= t0]
    ok = [r for r in window if answered(r, plan.kind)]
    latencies = [r[3] - r[1] for r in window]
    if len(ok) < len(window):
        # a failed request is in every tail at the window's longest: a
        # refusal that comes back at once is not a fast answer
        longest = max(latencies)
        latencies = [longest if not answered(r, plan.kind) else v
                     for r, v in zip(window, latencies)]
    late = [r[2] - r[1] for r in window]
    out = {
        "attempted": len(window),
        "failed": len(window) - len(ok),
        "window": window,
        # correct answers completed inside the window, over the window
        "decisions_per_s": stats.rate_per_s([r[3] for r in ok], t0, plan.seconds),
    }
    if window:
        out["latency_p50_ms"] = 1e3 * stats.percentile(latencies, 50)
        out["latency_p95_ms"] = 1e3 * stats.percentile(latencies, 95)
        out["client_latency_p99_ms"] = 1e3 * stats.percentile(latencies, 99)
        out["client_latency_max_ms"] = 1e3 * max(latencies)
        out["over_deadline_share"] = (
            100.0 * sum(1 for v in latencies if v > DEFAULT_DEADLINE_S) / len(latencies))
        out["client_late_p99_ms"] = 1e3 * stats.percentile(late, 99)
    return out


def _printable(verdict) -> list:
    return [sorted(v) if isinstance(v, (set, frozenset)) else v for v in verdict]


def compare(records: list, answers: dict, kind) -> dict:
    """Every answer received against the reference's, one by one: tuple
    equality, whatever the request's kind puts in the tuple."""
    mismatched, unanswered, with_error, examples = 0, 0, 0, []
    for idx, _due, sent, done, status, verdict, note in records:
        ms = round(1e3 * (done - sent), 1)
        if status != 200 or verdict is None:
            unanswered += 1
            if len(examples) < 5:
                examples.append({"index": idx, "ms": ms, "status": status, "note": note})
            continue
        want = answers[idx]
        if tuple(verdict) != tuple(want):
            mismatched += 1
            # told apart from an answer that is plainly another
            with_error += kind.gave_up(verdict)
            if len(examples) < 5:
                examples.append({
                    "index": idx, "ms": ms,
                    "got": _printable(verdict), "want": _printable(want),
                })
    return {"mismatched": mismatched, "unanswered": unanswered,
            "with_error": with_error, "compared": len(records), "examples": examples}


def run(args, manifest: Manifest, out: pathlib.Path, state: dict) -> dict:
    w = manifest.workload(args.workload)
    if w["chips"] != 1:
        raise RunFailure("this harness drives one chip")
    cfg = manifest.config(w["config"])
    mix = manifest.traffic(w["traffic"])
    cell = manifest.cell(w["name"])
    seconds = float(args.seconds)
    cores = plan_cores(int(mix["processes"]), os.sched_getaffinity(0), args.allow_cpu)
    log(f"cores: {cores['cores']} available; server on {cores['server']}, "
        f"{mix['processes']} generator processes on {cores['generators']}, "
        f"parent and reference pool on {cores['parent']} (+ the generators' "
        "cores until the window)")
    pool_cpus = (cores["parent"] + cores["generators"]) if cores["parent"] else None
    if pool_cpus:
        os.sched_setaffinity(0, pool_cpus)

    # ---- corpus, bodies, schedule: all from the seed
    corpus_params = dict(cfg["corpus"]["params"])
    if args.policies:
        corpus_params["policies"] = args.policies
    corpus = corpus_module(cfg["corpus"]["generator"], manifest.dir).build(
        corpus_params, args.seed)
    config_path = write_store(out, corpus)
    plan = traffic.Plan(corpus, mix, cell, args.seed, seconds, bench_dir=manifest.dir)
    log(f"corpus: {len(corpus.files)} files; plan: {plan.loop} loop, "
        f"{len(plan.bodies)} bodies of kind {plan.kind_ref[0]} for {plan.kind.PATH}, "
        f"{plan.connections} connections")

    # ---- the server child, and the reference's answers while it loads
    server = state["server"] = Server(
        out, config_path, list(cfg.get("server_args", [])) + list(args.server_arg),
        cores["server"],
    )
    ctx = Context()
    pool = state["pool"] = ReferencePool(
        corpus.files, workers=len(pool_cpus) if pool_cpus else 2,
        kind_ref=plan.kind_ref, cpus=pool_cpus,
    )
    pool.submit(plan.specs, plan.precompute_indices())
    docs, t_ready = wait_warm(server, READY_DEADLINE_S)
    ctx.harness["ready_s"] = t_ready - T_PROCESS_START
    ctx.harness["ladders_done_s"] = time.monotonic() - T_PROCESS_START
    log("both warm ladders complete")
    for path, eng in docs.items():
        warm = eng.get("warm") or {}
        if warm.get("failures"):
            raise RunFailure(f"{path}: {warm['failures']} warm-ladder shapes failed")
        if eng.get("platform") != "tpu" and not args.allow_cpu:
            raise RunFailure(f"{path} engine serves from {eng.get('platform')!r}, not a TPU")
    device = server.control({"cmd": "stats"})  # also starts the compile count
    if device["platform"] != "tpu" and not args.allow_cpu:
        raise RunFailure(f"the server's JAX reports platform {device['platform']!r}, not a TPU")
    if device["count"] < w["chips"]:
        raise RunFailure(f"the cell asks for {w['chips']} chips, JAX reports {device['count']}")
    # set-up never waits for the reference: what is unfinished stands
    # still while the generators need their cores
    pool.pause()

    # ---- connections (one after another), warm-up, window
    gens = state["gens"] = Generators(plan, server, cores)
    t0 = time.monotonic() + plan.warmup_s + 0.5
    gens.go(t0)
    state["setup_s"] = t0 - T_PROCESS_START
    time.sleep(max(0.0, t0 - 0.1 - time.monotonic()))
    before = server.control({"cmd": "stats"})
    ctx.prom_before = server.metrics(out / "metrics_before.txt")
    ctx.engine_before = server.engine_docs()
    log(f"window opens; setup_s {state['setup_s']:.2f}")
    trace_dir = out / "trace"
    if args.trace:
        # the window's last seconds: the profiler's dump keeps the server
        # busy for 5 to 14 s more, which has to fall after the close for
        # the counters' window to be of the untraced regime
        time.sleep(max(0.0, t0 + max(0.0, seconds - TRACE_SECONDS) - time.monotonic()))
        traced_from = server.control({"cmd": "trace_start", "dir": str(trace_dir)})["t"]
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    after = server.control({"cmd": "stats"})
    ctx.prom_after = server.metrics(out / "metrics_after.txt")
    ctx.engine_after = server.engine_docs()
    t_closed = time.monotonic()
    log("window closed")
    # the answers still out come in before anything disturbs the server: a
    # request that met the profiler's dump would wait on the harness
    gens.drained(timeout=loadgen.REQUEST_TIMEOUT_S + 30.0)
    if args.trace:
        t_stop = time.monotonic()
        ctx.trace_window_s = t_stop - traced_from
        server.control({"cmd": "trace_stop"}, timeout=120.0)
        log(f"trace of {ctx.trace_window_s:.2f}s written in {time.monotonic() - t_stop:.2f}s")
    results = gens.results(timeout=loadgen.REQUEST_TIMEOUT_S + 30.0)
    records = [r for res in results for r in res["records"]]
    dropped = sum(res["dropped"] for res in results)
    # index, due, sent, done, status: for slicing a window offline
    (out / "records.json").write_text(json.dumps([r[:5] for r in records]))
    if any(res["exhausted"] for res in results):
        raise RunFailure("a connection ran out of distinct bodies: the mix's pool_per_s is too small")
    rc = server.terminate()
    state["server"] = None
    if rc != 0:
        raise RunFailure(f"server exited rc={rc} on SIGTERM")

    # ---- the reference's answers: what the pool had not finished, and a
    # closed loop's bodies beyond the head of the pool
    pool.resume()
    answers = pool.collect()
    missing = [r[0] for r in records if r[0] not in answers]
    if missing:
        pool.submit(plan.specs, missing)
        answers = pool.collect()
    pool.close()
    ctx.harness["reference_after_window_s"] = time.monotonic() - t_closed
    log(f"reference: {len(answers)} answers")
    state["pool"] = None

    # ---- numbers
    win = window_numbers(plan, records, t0)
    if not win["attempted"]:
        raise RunFailure("no request fell inside the window")
    cmp_ = compare(records, answers, plan.kind)
    log("window: " + ", ".join(
        f"{k} {win[k]:.3f}" for k in ("latency_p50_ms", "latency_p95_ms",
                                      "client_latency_p99_ms", "client_latency_max_ms",
                                      "client_late_p99_ms", "over_deadline_share",
                                      "decisions_per_s")
    ) + f", dropped connections {dropped}")
    ctx.loadgen = {k: v for k, v in win.items() if isinstance(v, (int, float))}
    ctx.harness["window_compiles"] = after["compiles"] - before["compiles"]
    if ctx.harness["window_compiles"]:
        raise RunFailure(f"{ctx.harness['window_compiles']} executables were built or "
                         "loaded inside the window: a shape was not warm")
    # the longest collection between the two readings, which are the window's ends
    ctx.harness["gc_pause_max_ms"] = after["gc_pause_max_ms"]
    log(f"server's collector in the window: {after['gc_collections']} collections, "
        f"{after['gc_pause_sum_ms']:.1f} ms in all, longest {after['gc_pause_max_ms']:.2f} ms")
    ctx.device = device
    values = {
        "setup_s": state["setup_s"],
        "latency_p50_ms": win.get("latency_p50_ms"),
        "latency_p95_ms": win.get("latency_p95_ms"),
        "decisions_per_s": win["decisions_per_s"],
    }
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": after["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        from benchmark import xplane

        dumped = out / "trace.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = str(ROOT)
        subprocess.run(
            [sys.executable, str(HERE / "xplane.py"), str(trace_dir), str(dumped)],
            check=True, env=env, cwd=str(ROOT), timeout=200,
        )
        ctx.trace = json.loads(dumped.read_text())
        if device["platform"] == "tpu":
            busy_s = xplane.busy_seconds(ctx.trace)
            if not busy_s:
                raise RunFailure("the trace holds no device operation")
            dev["busy_s"] = busy_s
            dev["window_s"] = ctx.trace_window_s
            breakdown = {"device_ops": xplane.top_ops(ctx.trace),
                         "idle_gaps": xplane.idle_gaps(ctx.trace)}
        else:
            ctx.trace = None  # a CPU trace has no device plane to read
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in manifest.metrics_for(w["name"], group):
        if group == "end_to_end":
            value = values.get(m["name"])
        else:
            if device["platform"] != "tpu" and m["source"] == "device_trace":
                continue  # no device metric from a CPU run
            spec = manifest.metric_file(m["name"])
            value = reader_module(spec["reader"], manifest.dir).read(
                ctx, spec.get("params", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    compared = {
        "mismatched": {"value": cmp_["mismatched"], "limit": 0},
        # those of the mismatched in which the program says it gave up
        "mismatched_with_error": {"value": cmp_["with_error"], "limit": 0},
        "unanswered": {"value": cmp_["unanswered"], "limit": 0},
        "dropped_connections": {"value": dropped, "limit": 0},
        "compared": {"value": cmp_["compared"], "at_least": win["attempted"]},
    }
    correct = (cmp_["mismatched"] == 0 and cmp_["unanswered"] == 0 and dropped == 0
               and cmp_["compared"] >= win["attempted"])
    if cmp_["examples"]:
        (out / "disagreements.json").write_text(json.dumps(cmp_["examples"], indent=1))
        log("first disagreements: " + json.dumps(cmp_["examples"])[:1500])
    result = {
        "correct": correct, "attempted": win["attempted"], "failed": win["failed"],
        "metrics": metrics, "device": dev,
    }
    if breakdown:
        result["breakdown"] = breakdown
    result["workload"] = w["name"]
    result["seed"] = args.seed
    result["phases_s"] = {k: round(v, 3) for k, v in ctx.harness.items()
                          if k.endswith("_s")}
    if cmp_["examples"]:
        result["first_disagreement"] = json.dumps(cmp_["examples"][0])[:400]
    result["compared"] = compared
    return result


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--allow-cpu", action="store_true",
                   help="rehearsal: accept a CPU platform; prints no device metric")
    p.add_argument("--policies", type=int, default=0,
                   help="rehearsal: a smaller corpus than the configuration's")
    p.add_argument("--server-arg", action="append", default=[],
                   help="extra argument for the server child, after the "
                   "configuration's (rehearsals and fault tests); repeatable")
    p.add_argument("--out", default="")
    p.add_argument("--root", default="",
                   help="where BENCHMARK.json and benchmark/'s data files are "
                   "read from (default: this checkout)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu" and not args.allow_cpu:
        print("run: JAX_PLATFORMS=cpu — no accelerator will be used; a measured "
              "run needs the TPU (pass --allow-cpu only to rehearse)", file=sys.stderr)
        return 2
    manifest = Manifest(pathlib.Path(args.root).resolve()) if args.root else Manifest()
    if args.seconds is None:
        args.seconds = manifest.doc["run_seconds"]
    out = pathlib.Path(args.out) if args.out else ROOT / ".bench_run" / args.workload
    out = out.resolve()
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True, exist_ok=True)
    state: dict = {"server": None, "pool": None, "gens": None}

    def cleanup() -> None:
        if state.get("gens") is not None:
            state["gens"].kill()
        if state.get("pool") is not None:
            state["pool"].close()
        if state.get("server") is not None:
            state["server"].kill()

    def on_deadline() -> None:
        print(f"run: hard deadline {RUN_DEADLINE_S:.0f}s hit", file=sys.stderr, flush=True)
        cleanup()
        os._exit(3)

    watchdog = threading.Timer(RUN_DEADLINE_S, on_deadline)
    watchdog.daemon = True
    watchdog.start()
    try:
        result = run(args, manifest, out, state)
    except RunFailure as e:
        print(f"run: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        watchdog.cancel()
        cleanup()
    if "jax" in sys.modules:
        print("run: the parent imported JAX", file=sys.stderr)
        return 1
    for name, entry in result["compared"].items():
        print(f"compared {name} {json.dumps(entry)}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
