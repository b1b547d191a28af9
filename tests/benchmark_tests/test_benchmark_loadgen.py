"""The generator's connections: kept alive and reused, never reopened (a
connection that drops is a failed request and sends nothing more), and what
a stalled server does to a closed and to an open loop."""

import datetime
import json
import socket
import ssl
import threading
import time

import pytest

from benchmark import loadgen, traffic
from benchmark.corpora import selector
from benchmark.kinds import sar

OK_BODY = json.dumps({"status": {"allowed": True, "denied": False, "reason": ""}}).encode()


@pytest.fixture(scope="module")
def tls(tmp_path_factory):
    """A self-signed pair for 127.0.0.1."""
    import ipaddress

    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.x509.oid import NameOID

    d = tmp_path_factory.mktemp("tls")
    key = ec.generate_private_key(ec.SECP256R1())
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "127.0.0.1")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (
        x509.CertificateBuilder().subject_name(name).issuer_name(name)
        .public_key(key.public_key()).serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(days=1))
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(x509.SubjectAlternativeName(
            [x509.IPAddress(ipaddress.ip_address("127.0.0.1"))]), critical=False)
        .add_extension(x509.BasicConstraints(ca=True, path_length=None), critical=True)
        .sign(key, hashes.SHA256())
    )
    (d / "s.crt").write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    (d / "s.key").write_bytes(key.private_bytes(
        serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption()))
    return d


class FlakyServer:
    """Answers every request with 200, closes a connection without an
    answer once it has served ``close_after`` requests (0 = never), and
    stands still for ``stall_s`` before its ``stall_at``-th answer."""

    def __init__(self, tls_dir, close_after=0, stall_at=0, stall_s=0.0):
        self.close_after = close_after
        self.stall_at, self.stall_s = stall_at, stall_s
        self.answers = 0
        self.lock = threading.Lock()
        self.accepted = 0
        self.request_lines = set()
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(tls_dir / "s.crt", tls_dir / "s.key")
        self.ctx = ctx
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16)
        self.port = self.sock.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                raw, _ = self.sock.accept()
            except OSError:
                return
            self.accepted += 1
            threading.Thread(target=self._serve, args=(raw,), daemon=True).start()

    def _serve(self, raw):
        try:
            conn = self.ctx.wrap_socket(raw, server_side=True)
            served, buf = 0, b""
            while True:
                while b"\r\n\r\n" not in buf:
                    chunk = conn.recv(65536)
                    if not chunk:
                        return
                    buf += chunk
                head, _, rest = buf.partition(b"\r\n\r\n")
                self.request_lines.add(head.split(b"\r\n")[0])
                n = int([ln.split(b":")[1] for ln in head.split(b"\r\n")
                         if ln.lower().startswith(b"content-length")][0])
                while len(rest) < n:
                    rest += conn.recv(65536)
                buf = rest[n:]
                if self.close_after and served >= self.close_after:
                    conn.close()
                    return
                with self.lock:  # a stall holds every connection's answer
                    self.answers += 1
                    if self.answers == self.stall_at:
                        time.sleep(self.stall_s)
                conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
                             b"Content-Length: %d\r\n\r\n%s" % (len(OK_BODY), OK_BODY))
                served += 1
        except OSError:
            pass

    def close(self):
        self.sock.close()


def run_worker(server, tls_dir, loop, items, threads, seconds=1.0, kind=("sar", None)):
    spec = {"host": "127.0.0.1", "port": server.port, "cafile": str(tls_dir / "s.crt"),
            "threads": threads, "loop": loop, "items": items, "seconds": seconds,
            "warmup_s": 0.0, "kind": kind}
    w = loadgen.Worker(spec)
    w.connect()
    spec["t0"] = time.monotonic() + 0.05
    return w.run()


def test_connections_are_opened_once_and_reused(tls):
    server = FlakyServer(tls)
    try:
        items = [(i, 0.01 * i, b'{"n": %d}' % i) for i in range(40)]
        res = run_worker(server, tls, "open", items, threads=4)
    finally:
        server.close()
    assert server.accepted == 4
    assert res["dropped"] == 0
    assert sorted(r[0] for r in res["records"]) == list(range(40))
    assert all(r[4] == 200 and r[5] == (True, False, frozenset()) for r in res["records"])
    # each request is timed from when it was due, and is not sent early
    assert all(r[2] >= r[1] and r[3] >= r[2] for r in res["records"])


def test_the_request_line_and_the_reading_of_an_answer_are_the_kinds(tls, tmp_path):
    """The generator knows no endpoint: the worker's spec names a kind, by
    name and by the directory it came in under, and the process imports it."""
    (tmp_path / "kinds").mkdir()
    (tmp_path / "kinds" / "stub_review.py").write_text(
        'PATH = "/v1/stub?timeout=30s"\n'
        'def verdict(response):\n'
        '    return ("stub", response["status"]["allowed"])\n')
    items = [(i, 0.01 * i, b"{}") for i in range(6)]
    lines, verdicts = {}, {}
    for kind in (("sar", None), ("stub_review", str(tmp_path))):
        server = FlakyServer(tls)
        try:
            res = run_worker(server, tls, "open", items, threads=2, kind=kind)
        finally:
            server.close()
        lines[kind[0]] = server.request_lines
        verdicts[kind[0]] = {r[5] for r in res["records"]}
    assert lines["sar"] == {b"POST " + sar.PATH.encode() + b" HTTP/1.1"}
    assert lines["stub_review"] == {b"POST /v1/stub?timeout=30s HTTP/1.1"}
    assert verdicts == {"sar": {(True, False, frozenset())}, "stub_review": {("stub", True)}}


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_a_dropped_connection_is_a_failed_request_and_is_never_reopened(tls, loop):
    server = FlakyServer(tls, close_after=3)
    try:
        if loop == "open":
            items = [(i, 0.02 * i, b"{}") for i in range(8)]
        else:
            items = [[(i, b"{}") for i in range(8)]]
        res = run_worker(server, tls, loop, items, threads=1)
    finally:
        server.close()
    by_index = {r[0]: r for r in res["records"]}
    assert sorted(by_index) == [0, 1, 2, 3]        # nothing is sent after the drop
    assert [i for i, r in by_index.items() if r[4] != 200] == [3]
    assert by_index[3][5] is None                  # no answer was made up
    assert res["dropped"] == 1 and not res["exhausted"]
    assert server.accepted == 1


def test_a_stall_shows_in_a_closed_loops_max_and_rate_not_in_its_median(tls, monkeypatch):
    """Callers that wait send nothing while the server stands still: the
    stop costs its length in rate and touches one request per connection."""
    from benchmark import run

    monkeypatch.setattr(run, "DEFAULT_DEADLINE_S", 0.2)
    server = FlakyServer(tls, stall_at=40, stall_s=0.3)
    try:
        items = [[(c * 1_000_000 + i, b"{}") for i in range(100000)] for c in range(4)]
        res = run_worker(server, tls, "closed", items, threads=4, seconds=1.5)
    finally:
        server.close()
    t0 = min(r[1] for r in res["records"])
    win = run.window_numbers(_Plan("closed", 1.5), res["records"], t0)
    assert win["failed"] == 0 and win["attempted"] > 100
    assert win["client_latency_max_ms"] >= 300.0
    assert win["latency_p50_ms"] < 100.0
    slow = sum(1 for r in res["records"] if r[3] - r[1] > 0.2)
    assert 1 <= slow <= 4                          # at most one per connection
    assert win["over_deadline_share"] == pytest.approx(100.0 * slow / win["attempted"])


def test_a_stall_reaches_every_request_that_was_due_in_an_open_loop(tls):
    from benchmark import run

    server = FlakyServer(tls, stall_at=5, stall_s=0.4)
    try:
        items = [(i, 0.01 * i, b"{}") for i in range(60)]
        res = run_worker(server, tls, "open", items, threads=4)
    finally:
        server.close()
    t0 = min(r[1] for r in res["records"])
    win = run.window_numbers(_Plan("open", 1.0), res["records"], t0)
    # 4 connections are stuck behind the stall; the arrivals due meanwhile
    # wait for them and are timed from when they were due
    assert win["client_latency_max_ms"] >= 400.0
    assert sum(1 for r in res["records"] if r[3] - r[1] > 0.1) >= 20
    assert win["client_late_p99_ms"] >= 100.0


class _Plan:
    kind = sar

    def __init__(self, loop, seconds):
        self.loop, self.seconds = loop, seconds


def test_closed_loop_sends_the_next_body_on_reply_until_the_window_closes(tls):
    server = FlakyServer(tls)
    try:
        items = [[(c * 1_000_000 + i, b"{}") for i in range(100000)] for c in range(2)]
        res = run_worker(server, tls, "closed", items, threads=2, seconds=0.5)
    finally:
        server.close()
    assert not res["exhausted"]
    assert len(res["records"]) > 10
    for c in range(2):
        mine = sorted(r[0] for r in res["records"] if r[0] // 1_000_000 == c)
        assert mine == list(range(c * 1_000_000, c * 1_000_000 + len(mine)))  # in order, no gaps


def test_a_closed_loop_that_runs_out_of_bodies_says_so(tls):
    server = FlakyServer(tls)
    try:
        res = run_worker(server, tls, "closed", [[(0, b"{}"), (1, b"{}")]], threads=1)
    finally:
        server.close()
    assert res["exhausted"]


def test_split_gives_every_arrival_to_one_process_in_due_order():
    corpus = selector.build({"policies": 50}, 1)
    plan = traffic.Plan(corpus, {"loop": "open", "connections": 8, "processes": 4, "warmup_s": 1.0},
                        {"rate_per_s": 100}, 1, 2.0)
    shares = loadgen.split(plan, 4, 2)
    assert sorted(i for s in shares for i, _, _ in s) == list(range(300))
    for s in shares:
        assert [d for _, d, _ in s] == sorted(d for _, d, _ in s)
    assert sum(1 for d in plan.due if d < 0) == 100   # the warm-up, uncounted
    assert len(set(plan.bodies)) == 300               # every body distinct


def test_split_gives_every_connection_its_own_bodies():
    corpus = selector.build({"policies": 50}, 1)
    mix = {"loop": "closed", "connections": 8, "processes": 2, "warmup_s": 0.0,
           "pool_per_s": 100, "precompute_per_s": 50}
    plan = traffic.Plan(corpus, mix, {}, 1, 2.0)
    shares = loadgen.split(plan, 2, 4)
    flat = [i for proc in shares for conn in proc for i, _ in conn]
    assert sorted(flat) == list(range(200))
    assert [i for i, _ in shares[0][1]][:3] == [1, 9, 17]
    assert plan.precompute_indices() == list(range(100))


def test_the_lone_mix_is_one_connection_of_one_process():
    from benchmark.manifest import Manifest

    corpus = selector.build({"policies": 50}, 1)
    plan = traffic.Plan(corpus, Manifest().traffic("sar-lone"), {}, 7, 2.0)
    assert (plan.loop, plan.connections, plan.processes) == ("closed", 1, 1)
    shares = loadgen.split(plan, plan.processes, 1)
    assert [i for i, _ in shares[0][0]] == list(range(len(plan.bodies)))
    assert len(set(plan.bodies)) == len(plan.bodies) == 5000
    assert plan.kind_ref == ("sar", None)             # the mix names no kind
    # every request of the selector mix that lists or watches carries a selector
    listing = [s for s in plan.specs if s["resourceAttributes"]["verb"] == "watch"]
    assert listing and all("labelSelector" in s["resourceAttributes"] for s in listing)
