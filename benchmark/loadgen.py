"""Generator processes: keep-alive HTTPS connections that are never
reopened, an open loop timed from when each request was due, or a closed
loop with one request in flight per connection.

Built against the three faults of the first attempt (ledger, PR 22):
processes with a few threads each, pinned to cores the server does not
use, so the generator is not starved; every connection opened and
TLS-handshaken one after another during set-up, kept alive and reused — a
connection that fails inside the window is a failed request and sends
nothing more (``dropped``: the run is then not correct); and nothing here
chooses a rate.

No JAX anywhere in this file: the chip belongs to the server child.
"""

from __future__ import annotations

import gc
import json
import os
import socket
import ssl
import threading
import time

from benchmark.manifest import kind_module

REQUEST_TIMEOUT_S = 60.0


class Connection:
    """One keep-alive HTTPS connection speaking just enough HTTP/1.1."""

    def __init__(self, host: str, port: int, cafile: str, path: str):
        self.host, self.port = host, port
        self.ctx = ssl.create_default_context(cafile=cafile)
        self.head = (
            f"POST {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Content-Type: application/json\r\nContent-Length: "
        ).encode()
        self.sock = None
        self.buf = b""

    def open(self) -> None:
        raw = socket.create_connection((self.host, self.port), timeout=REQUEST_TIMEOUT_S)
        raw.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock = self.ctx.wrap_socket(raw, server_hostname=self.host)
        self.buf = b""

    def close(self) -> None:
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass
            self.sock = None

    def post(self, body: bytes) -> tuple:
        """(status, response body). Raises OSError on a broken connection."""
        self.sock.sendall(self.head + str(len(body)).encode() + b"\r\n\r\n" + body)
        buf = self.buf
        while True:
            end = buf.find(b"\r\n\r\n")
            if end >= 0:
                break
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed the connection")
            buf += chunk
        header = buf[:end].decode("latin-1")
        status = int(header.split(" ", 2)[1])
        length = 0
        for line in header.split("\r\n")[1:]:
            k, _, v = line.partition(":")
            if k.strip().lower() == "content-length":
                length = int(v)
        need = end + 4 + length
        while len(buf) < need:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed the connection")
            buf += chunk
        self.buf = buf[need:]
        return status, buf[end + 4:need]


def _sleep_until(t: float) -> None:
    """Sleep to just before ``t``, then spin: a sleeping thread wakes some
    tenths of a millisecond late."""
    while True:
        dt = t - time.monotonic()
        if dt <= 0:
            return
        if dt > 0.0004:
            time.sleep(dt - 0.0003)


class Worker:
    """One generator process's share of a plan, run by its threads.
    ``spec["kind"]`` is the request kind's (name, directory): this process
    imports it itself, for its request line and to read the answers."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.kind = kind_module(*spec["kind"])
        self.conns = [
            Connection(spec["host"], spec["port"], spec["cafile"], self.kind.PATH)
            for _ in range(spec["threads"])
        ]
        self.records = []  # (index, due, sent, done, status, raw body | None)
        self.lock = threading.Lock()
        self.next = 0
        self.exhausted = False  # a closed loop ran out of distinct bodies
        self.dropped = 0  # connections lost; each stops sending

    def connect(self) -> None:
        for c in self.conns:
            c.open()

    def _send(self, conn: Connection, idx: int, due: float, body: bytes) -> bool:
        """One request and its record; False once the connection is lost."""
        sent = time.monotonic()
        try:
            status, raw = conn.post(body)
        except (OSError, ValueError, IndexError) as e:
            done = time.monotonic()
            conn.close()
            with self.lock:
                self.dropped += 1
            self.records.append((idx, due, sent, done, 0, repr(e).encode()))
            return False
        self.records.append((idx, due, sent, time.monotonic(), status, raw))
        return True

    def _open_loop(self, conn: Connection) -> None:
        items, t0 = self.spec["items"], self.spec["t0"]
        while True:
            with self.lock:
                i = self.next
                self.next += 1
            if i >= len(items):
                return
            idx, due, body = items[i]
            _sleep_until(t0 + due)
            if not self._send(conn, idx, t0 + due, body):
                return

    def _closed_loop(self, conn: Connection, items: list) -> None:
        t0, t_end = self.spec["t0"], self.spec["t0"] + self.spec["seconds"]
        _sleep_until(t0 - self.spec["warmup_s"])
        for idx, body in items:
            now = time.monotonic()
            if now >= t_end:
                return
            if not self._send(conn, idx, now, body):
                return
        self.exhausted = True

    def drive(self) -> None:
        """Send the share and wait for every answer that is still out."""
        threads = []
        for k, conn in enumerate(self.conns):
            if self.spec["loop"] == "open":
                target, args = self._open_loop, (conn,)
            else:
                target, args = self._closed_loop, (conn, self.spec["items"][k])
            threads.append(threading.Thread(target=target, args=args, daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for c in self.conns:
            c.close()

    def reduce(self) -> dict:
        """The records with each answer read as a verdict."""
        out = []
        for idx, due, sent, done, status, raw in self.records:
            verdict = None
            note = ""
            if status == 200:
                try:
                    verdict = self.kind.verdict(json.loads(raw))
                except ValueError:
                    note = "unreadable body"
            else:
                note = raw[:200].decode("latin-1")
            out.append((idx, due, sent, done, status, verdict, note))
        return {
            "records": out,
            "dropped": self.dropped,
            "exhausted": self.exhausted,
        }

    def run(self) -> dict:
        self.drive()
        return self.reduce()


def process_main(spec: dict, pipe) -> None:
    """Entry of one generator process (multiprocessing, spawn)."""
    try:
        if spec.get("cpu") is not None:
            os.sched_setaffinity(0, [spec["cpu"]])
        worker = Worker(spec)
        worker.connect()
        pipe.send(("ready", len(worker.conns)))
        spec["t0"] = pipe.recv()
        gc.collect()
        gc.freeze()
        gc.disable()
        worker.drive()
        # every answer is in: the parent may now disturb the server (the
        # profiler's dump) without a request of this run waiting on it
        pipe.send(("drained", time.monotonic()))
        pipe.send(("done", worker.reduce()))
    except BaseException as e:  # noqa: BLE001 — reported to the parent, then re-raised
        pipe.send(("error", repr(e)))
        raise
    finally:
        pipe.close()


def split(plan, n_procs: int, threads: int) -> list:
    """The plan's work divided among ``n_procs`` processes of ``threads``
    connections each: an open loop's arrivals round-robin in due order, a
    closed loop's connections each with their own sequence of bodies."""
    if plan.loop == "open":
        order = sorted(range(len(plan.due)), key=lambda i: plan.due[i])
        shares = [[] for _ in range(n_procs)]
        for k, i in enumerate(order):
            shares[k % n_procs].append((i, plan.due[i], plan.bodies[i]))
        return shares
    shares = []
    conn = 0
    for _ in range(n_procs):
        per_conn = []
        for _ in range(threads):
            per_conn.append([
                (i, plan.bodies[i])
                for i in range(conn, len(plan.bodies), plan.connections)
            ])
            conn += 1
        shares.append(per_conn)
    return shares
