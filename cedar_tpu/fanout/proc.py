"""Process transport: fanout workers as real OS processes.

``bench.py --fanout`` (and a production single-host deployment) runs
each worker as a spawned process with its own interpreter, JAX runtime,
engine, and decision cache — the GIL-free scaling the in-process flavor
cannot give. The wire protocol is exactly worker.py's:

  * **Serving/control** rides ``channels`` duplex pipes per worker
    (multiprocessing.Pipe, spawn context — never fork: the parent holds
    a live XLA runtime). Each pipe is one in-flight request lane; the
    parent-side handle leases lanes, so per-worker concurrency =
    channels and the worker's own micro-batcher coalesces across lanes.
  * **Peer traffic** rides a localhost TCP mesh: each worker serves
    ``peer_get``/``gossip_in`` as JSON lines on its own port, workers
    get the full port map once the tier is up (``peer_config``), and
    the worker-side PeerNet endpoints are thin TCP clients. Peer records
    are already content-addressed wire dicts (peers.py), so JSON is the
    whole serialization story — nothing process-local crosses.

Worker stacks build from a picklable SPEC (policy source text + serving
knobs) via ``build_worker_stack`` — the same builder the in-process
tests use, so both transports serve byte-identical answers.

A killed process (``ProcWorkerHandle.kill()``, or a real crash) surfaces
as ``WorkerDied`` on every in-flight lane; ``revive()`` respawns the
process from the CURRENT spec — cold cache, same plane — and re-announces
the peer map, mirroring InProcessWorker.revive's cold-restart honesty.
"""

from __future__ import annotations

import json
import logging
import multiprocessing as mp
import os
import socket
import socketserver
import threading
from typing import Dict, List, Optional

from .worker import WorkerDied

log = logging.getLogger(__name__)

_DIED = "__died__"


# --------------------------------------------------------------- worker side


def build_worker_stack(
    spec: dict, worker_id: str, mesh=None, mesh_device_rules=None
):
    """Build one worker's full serving stack from a picklable spec:

      spec["source"]        Cedar policy source text (one tier), or
      spec["synth"]         {"n", "seed", "clusters", "edit_probe"} — a
                            deterministic corpus/synth.py corpus (every
                            worker process regenerates the identical
                            corpus, so the tier's shard hashes agree and
                            ``edit_probe`` is the one-policy CRD edit)
      spec["fastpath"]      wire the native SAR fast path + batcher (default
                            True; falls back when the toolchain is absent)
      spec["cache"]         decision-cache entries (0 disables; default 64k)
      spec["peer_fetch"] / spec["peer_gossip"]   peer-cache modes
      spec["timeout_s"]     per-request deadline budget

    Returns an InProcessWorker (the process wrapper drives it). The
    engine is the authorizer's evaluate backend, so swaps reach the
    served answers on every path — with or without the native fast
    path.

    ``mesh``/``mesh_device_rules`` thread a (data, policy) device mesh
    into the engine — the pod tier (cedar_tpu/pod) builds every host's
    stack through here with the ONE pod-wide mesh, so a "fanout worker"
    and a "pod host" are the same stack pointed at different device
    sets."""
    from ..engine.evaluator import TPUPolicyEngine
    from ..lang import PolicySet
    from ..server.authorizer import CedarWebhookAuthorizer
    from ..server.http import WebhookServer
    from ..stores.store import MemoryStore, TieredPolicyStores
    from .peers import PeerBackedCache
    from .worker import InProcessWorker

    corpus_cache: dict = {}

    def tiers_from(s: dict):
        synth = s.get("synth")
        if synth is not None:
            from ..corpus.synth import synth_corpus

            key = (
                int(synth["n"]),
                int(synth.get("seed", 0)),
                int(synth.get("clusters", 1)),
            )
            base = corpus_cache.get(key)
            if base is None:
                base = corpus_cache[key] = synth_corpus(*key)
            c = base.with_edit() if synth.get("edit_probe") else base
            return c.tiers()
        return [PolicySet.from_source(s["source"], s.get("name", "fanout"))]

    tiers = tiers_from(spec)
    stores = TieredPolicyStores([MemoryStore(f"fanout-{worker_id}", tiers[0])])
    engine = TPUPolicyEngine(
        name=f"fanout-{worker_id}",
        mesh=mesh,
        mesh_device_rules=mesh_device_rules,
    )

    def _eval(entities, request):
        # pre-load / post-clear guard (the CLI's _guarded twin): an
        # engine without a set answers from the tiered stores
        if not engine.loaded:
            return stores.is_authorized(entities, request)
        return engine.evaluate(entities, request)

    def _eval_batch(items):
        if not engine.loaded:
            return [stores.is_authorized(em, r) for em, r in items]
        return engine.evaluate_batch(items)

    authorizer = CedarWebhookAuthorizer(
        stores, evaluate=_eval, evaluate_batch=_eval_batch
    )
    engine.load(tiers, warm="off")

    fastpath = None
    batch_depth = 0
    if spec.get("fastpath", True):
        try:
            from ..engine.fastpath import SARFastPath

            fp = SARFastPath(engine, authorizer)
            if fp.available:
                fastpath = fp
                batch_depth = int(spec.get("pipeline_depth", 2))
        except Exception:  # noqa: BLE001 — no toolchain: interpreter+engine path
            log.exception("worker %s: native fast path unavailable", worker_id)

    cache = None
    cache_entries = int(spec.get("cache", 65536))
    if cache_entries > 0:
        ttls = spec.get("ttls") or {}
        cache = PeerBackedCache(
            max_entries=cache_entries,
            allow_ttl_s=float(ttls.get("allow", 300.0)),
            deny_ttl_s=float(ttls.get("deny", 30.0)),
            no_opinion_ttl_s=float(ttls.get("no_opinion", 5.0)),
            generation_fn=None,  # bound below — needs the engine composite
            fetch_enabled=bool(spec.get("peer_fetch", True)),
            gossip_enabled=bool(spec.get("peer_gossip", True)),
            gossip_async=bool(spec.get("gossip_async", False)),
        )
        from ..cache.generation import plane_composite, plane_wire_state

        cache._generation_fn = lambda: plane_composite(stores, engine)
        cache.wire_state_fn = lambda: plane_wire_state(engine)

    server = WebhookServer(
        authorizer,
        None,
        fastpath=fastpath,
        decision_cache=cache,
        pipeline_depth=batch_depth,
        request_timeout_s=spec.get("timeout_s"),
    )
    return InProcessWorker(
        worker_id,
        server,
        engine,
        cache=cache,
        tiers_factory=tiers_from,
        authorizer=authorizer,
    )


class _TcpPeer:
    """Worker-side PeerNet endpoint for one sibling: JSON-line calls
    over ONE persistent connection (lock-serialized; reconnect on any
    error). Peer traffic is miss-path-only, but a connect() per miss
    still puts ~ms of handshake on the serving thread — persistent
    beats per-call by an order of magnitude and a dead sibling just
    resets the socket."""

    def __init__(self, port: int):
        self.port = port
        self._lock = threading.Lock()
        self._file = None

    def _connect(self):
        s = socket.create_connection(("127.0.0.1", self.port), timeout=2.0)
        s.settimeout(2.0)
        self._file = s.makefile("rwb")

    def _call(self, payload: dict):
        with self._lock:
            try:
                if self._file is None:
                    self._connect()
                self._file.write(json.dumps(payload).encode() + b"\n")
                self._file.flush()
                line = self._file.readline()
            except (OSError, ValueError):
                self._file = None
                raise
            if not line:
                self._file = None
                raise ConnectionError("peer closed")
            return json.loads(line)

    def peer_get(self, key: str):
        return self._call({"op": "peer_get", "key": key}).get("record")

    def gossip_in(self, record: dict):
        return self._call({"op": "gossip", "record": record}).get("ok", False)


class _PeerServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


def _serve_peers(worker) -> "_PeerServer":
    """Start the worker's peer TCP server on an ephemeral port."""

    class Handler(socketserver.StreamRequestHandler):
        def handle(self):
            # persistent line protocol: one request per line until the
            # sibling hangs up (matches _TcpPeer's held connection)
            try:
                while True:
                    line = self.rfile.readline()
                    if not line:
                        return
                    req = json.loads(line)
                    if req.get("op") == "peer_get":
                        out = {"record": worker.peer_get(req["key"])}
                    elif req.get("op") == "gossip":
                        out = {"ok": bool(worker.gossip_in(req["record"]))}
                    else:
                        out = {"error": "unknown op"}
                    self.wfile.write(json.dumps(out).encode() + b"\n")
                    self.wfile.flush()
            except Exception:  # noqa: BLE001 — peer serving is best-effort
                log.debug("peer request failed", exc_info=True)

    srv = _PeerServer(("127.0.0.1", 0), Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True, name="peer-srv")
    t.start()
    return srv


def _worker_main(worker_id: str, spec: dict, conns, boot_conn) -> None:
    """Spawned-process entry: build the stack, announce the peer port,
    then serve one request lane per pipe until EOF."""
    # a chip belongs to ONE process, and the parent may hold it: a spawned
    # worker runs its planes on the CPU platform unless its environment
    # names another (docs/fleet.md "Cross-host topology")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("CEDAR_TPU_WARM_DEFAULT", "off")
    try:
        worker = build_worker_stack(spec, worker_id)
        import jax

        # WARNING so it shows without a logging config: where a worker's
        # planes run is the first thing to know about its numbers
        log.warning(
            "fanout worker %s (pid %d): planes on jax platform %r",
            worker_id, os.getpid(), jax.default_backend(),
        )
        peer_srv = _serve_peers(worker)
        boot_conn.send(("ready", peer_srv.server_address[1]))
    except Exception as e:  # noqa: BLE001 — the parent must see the failure
        try:
            boot_conn.send(("error", repr(e)))
        finally:
            return

    def control(op: str, payload):
        if op == "peer_config":
            # {sibling id: port} — build the worker-side TCP peer mesh.
            # The ring is rebuilt HERE from the same ids the front-end
            # hashes (ring.py is deterministic across processes), so the
            # home-miss short-circuit, the fetch-order preference, and
            # the gossip fan-out cap all apply inside worker processes
            # exactly as in-process — without them every miss/fill would
            # fan out O(tier) sockets.
            from .peers import PeerNet
            from .ring import HashRing

            net = PeerNet()
            for wid, port in payload.items():
                net.register(wid, _TcpPeer(port))
            if worker.cache is not None:
                ring = HashRing(list(payload) + [worker_id])
                worker.cache.bind(
                    net, worker_id, order_fn=ring.preference
                )
            return True
        if op == "swap":
            return worker.swap(payload)
        if op == "restore":
            return worker.restore()
        if op == "commit":
            worker.commit()
            return True
        if op == "plane_wire":
            return worker.plane_wire()
        if op == "stats":
            return worker.stats()
        if op == "warm_ready":
            return worker.warm_ready()
        raise ValueError(f"unknown control op {op!r}")

    def lane(conn):
        while True:
            try:
                op, payload = conn.recv()
            except (EOFError, OSError):
                return
            try:
                if op == "authorize":
                    out = ("ok", worker.authorize(payload))
                elif op == "admit":
                    out = ("ok", worker.admit(payload))
                elif op == "stop":
                    conn.send(("ok", True))
                    os._exit(0)
                else:
                    out = ("ok", control(op, payload))
            except WorkerDied as e:
                out = (_DIED, str(e))
            except Exception as e:  # noqa: BLE001 — the lane must answer
                out = ("err", repr(e))
            try:
                conn.send(out)
            except (OSError, BrokenPipeError):
                return

    threads = [
        threading.Thread(target=lane, args=(c,), daemon=True, name=f"lane{i}")
        for i, c in enumerate(conns)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


# --------------------------------------------------------------- parent side


class ProcWorkerHandle:
    """Parent-side worker handle speaking the worker protocol over the
    pipes — a drop-in for InProcessWorker in FanoutFrontend."""

    def __init__(self, worker_id: str, spec: dict, channels: int = 4):
        self.worker_id = worker_id
        self.spec = dict(spec)
        self.channels = max(1, int(channels))
        self.peer_port: Optional[int] = None
        self.cache = None  # parent side holds no cache; peers are TCP
        self._pending_spec: Optional[dict] = None
        self._dead = False
        self._lock = threading.Lock()
        self._spawn()

    def _spawn(self) -> None:
        ctx = mp.get_context("spawn")
        pairs = [ctx.Pipe(duplex=True) for _ in range(self.channels)]
        boot_parent, boot_child = ctx.Pipe(duplex=True)
        self._conns = [p for p, _c in pairs]
        self._free: List = list(self._conns)
        self._free_cv = threading.Condition()
        self._lanes_lost = 0
        self._proc = ctx.Process(
            target=_worker_main,
            args=(self.worker_id, self.spec, [c for _p, c in pairs], boot_child),
            daemon=True,
            name=f"fanout-{self.worker_id}",
        )
        self._proc.start()
        boot_child.close()
        for _p, c in pairs:
            c.close()
        if not boot_parent.poll(180):
            raise RuntimeError(f"worker {self.worker_id}: boot timeout")
        status, payload = boot_parent.recv()
        if status != "ready":
            raise RuntimeError(f"worker {self.worker_id}: boot failed: {payload}")
        self.peer_port = payload
        self._dead = False

    def _call(self, op: str, payload, timeout: float = 120.0):
        if self._dead:
            raise WorkerDied(self.worker_id, "not running")
        with self._free_cv:
            while not self._free:
                if not self._free_cv.wait(timeout):
                    raise TimeoutError(f"worker {self.worker_id}: no free lane")
            conn = self._free.pop()
        # a lane whose request TIMED OUT still has a reply in flight: it
        # must never return to the pool, or the next request on it would
        # read the PREVIOUS operation's answer (cross-request corruption).
        # Abandoning it sheds one lane of capacity; a worker that times
        # out every lane stops being callable and reads dead.
        poisoned = False
        try:
            conn.send((op, payload))
            if not conn.poll(timeout):
                poisoned = True
                raise WorkerDied(self.worker_id, f"{op} timeout")
            status, result = conn.recv()
        except (EOFError, OSError, BrokenPipeError) as e:
            self._dead = True
            poisoned = True
            raise WorkerDied(self.worker_id, f"pipe: {e}") from e
        finally:
            with self._free_cv:
                if not poisoned:
                    self._free.append(conn)
                    self._free_cv.notify()
                else:
                    self._lanes_lost += 1
                    if self._lanes_lost >= self.channels:
                        # every lane abandoned: the worker is effectively
                        # unreachable — read dead so the ring rehashes
                        self._dead = True
        if status == _DIED:
            self._dead = True
            raise WorkerDied(self.worker_id, result)
        if status == "err":
            raise RuntimeError(f"worker {self.worker_id}: {result}")
        return result

    # ------------------------------------------------------ worker protocol

    def authorize(self, body: bytes, request_id=None):
        res = self._call("authorize", body)
        return tuple(res)

    def admit(self, body: bytes, request_id=None) -> dict:
        return self._call("admit", body)

    def supports_admit(self) -> bool:
        # build_worker_stack carries no admission stack yet; the front
        # end must keep /v1/admit on the local evaluator (http.py)
        return False

    def swap(self, spec) -> dict:
        out = self._call("swap", spec)
        # remember the candidate only after the worker accepted it; a
        # respawn must come back on whatever the barrier COMMITS
        self._pending_spec = dict(spec)
        return out

    def restore(self) -> bool:
        self._pending_spec = None
        return bool(self._call("restore", None))

    def commit(self) -> None:
        pending = getattr(self, "_pending_spec", None)
        if pending is not None:
            self.spec = pending  # a respawn comes back on the committed set
            self._pending_spec = None
        self._call("commit", None)

    def plane_wire(self):
        return self._call("plane_wire", None)

    def peer_config(self, port_map: Dict[str, int]) -> None:
        self._call("peer_config", port_map)

    def peer_get(self, key: str):  # parent-side peers unused (TCP mesh)
        return None

    def gossip_in(self, record: dict) -> bool:
        return False

    def warm_ready(self) -> bool:
        try:
            return bool(self._call("warm_ready", None, timeout=30))
        except WorkerDied:
            return True  # dead workers don't gate readiness

    def stats(self) -> dict:
        try:
            return self._call("stats", None, timeout=30)
        except WorkerDied:
            return {"worker": self.worker_id, "alive": False}

    def alive(self) -> bool:
        return not self._dead and self._proc.is_alive()

    def kill(self) -> None:
        """Hard process kill (bench/game days): SIGKILL, no goodbye."""
        self._dead = True
        try:
            self._proc.kill()
            self._proc.join(10)
        except Exception:  # noqa: BLE001 — it is dead either way
            pass

    def revive(self) -> bool:
        if self.alive():
            return False
        try:
            self._proc.join(5)
        except Exception:  # noqa: BLE001
            pass
        self._spawn()
        return True

    def stop(self) -> None:
        if not self._dead and self._proc.is_alive():
            try:
                self._call("stop", None, timeout=10)
            except Exception:  # noqa: BLE001 — force below
                pass
        self._dead = True
        try:
            self._proc.join(5)
            if self._proc.is_alive():
                self._proc.kill()
                self._proc.join(5)
        except Exception:  # noqa: BLE001 — teardown must finish
            pass


def wire_peer_mesh(handles: List[ProcWorkerHandle]) -> None:
    """Announce the full {worker id: peer port} map to every worker —
    call once after all workers booted, and again after any revive."""
    ports = {h.worker_id: h.peer_port for h in handles if h.peer_port}
    for h in handles:
        if h.alive():
            try:
                h.peer_config({w: p for w, p in ports.items() if w != h.worker_id})
            except Exception:  # noqa: BLE001 — a dead worker re-meshes at revive
                log.exception("peer mesh config for %s failed", h.worker_id)


__all__ = ["ProcWorkerHandle", "build_worker_stack", "wire_peer_mesh"]
