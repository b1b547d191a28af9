"""One scan in, one write out (docs/performance.md "One read, one write").

The webhook's handler reads a plain POST itself and answers with one write;
everything else goes to http.server's reader. The witness throughout is the
handler as it was: the same class with the scan switched off and
``_write_json`` built from send_response / send_header / end_headers and a
second write. Both are served side by side and fed the same bytes: what
they leave in the handler and what they put on the wire has to be equal,
``Date``'s second and the request's random ids apart.
"""

import json
import pathlib
import re
import socket
import time
from http.server import BaseHTTPRequestHandler

import pytest

from benchmark import prom
from benchmark.manifest import Manifest, reader_module, validate
from benchmark.run import Context
from cedar_tpu.obs.trace import Tracer
from cedar_tpu.server import metrics
from cedar_tpu.server.admission import (
    CedarAdmissionHandler,
    allow_all_admission_policy_store,
)
from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
from cedar_tpu.server.http import MAX_BODY_BYTES, WebhookServer
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

POLICY = """
permit (principal is k8s::User, action == k8s::Action::"get",
        resource is k8s::Resource)
  when { principal.name == "sam" && resource.resource == "pods" };
"""

# every header name the server asks its request for (http.py _do_post,
# tenancy/frontend.py, http.server's own Connection and Expect), as the
# code spells them and as a client might
NAMES = (
    "Content-Length", "content-length", "Host", "traceparent", "Traceparent",
    "Connection", "Expect", "x-cedar-tenant", "X-Cedar-Tenant",
    "Transfer-Encoding", "User-Agent", "Absent",
)


def sar(user="sam"):
    return json.dumps({
        "apiVersion": "authorization.k8s.io/v1",
        "kind": "SubjectAccessReview",
        "spec": {"user": user, "uid": "u", "groups": [],
                 "resourceAttributes": {"verb": "get", "resource": "pods",
                                        "version": "v1", "name": "p"}},
    }).encode()


def review():
    return json.dumps({
        "apiVersion": "admission.k8s.io/v1",
        "kind": "AdmissionReview",
        "request": {
            "uid": "r1", "operation": "CREATE",
            "userInfo": {"username": "sam", "groups": []},
            "kind": {"group": "", "version": "v1", "kind": "ConfigMap"},
            "resource": {"group": "", "version": "v1", "resource": "configmaps"},
            "namespace": "default", "name": "c",
            "object": {"apiVersion": "v1", "kind": "ConfigMap",
                       "metadata": {"name": "c", "namespace": "default"}},
        },
    }).encode()


class CountingSocket:
    """A connection that counts the calls that put bytes on it."""

    def __init__(self, sock, sends):
        self._sock, self._sends = sock, sends

    def sendall(self, data, *args):
        self._sends.append(len(data))
        return self._sock.sendall(data, *args)

    def send(self, data, *args):
        self._sends.append(len(data))
        return self._sock.send(data, *args)

    def __getattr__(self, name):
        return getattr(self._sock, name)


class Served:
    """A webhook on loopback whose handler notes what parse_request left
    and every send on its connections; ``as_it_was`` serves the witness."""

    def __init__(self, as_it_was):
        stores = TieredPolicyStores([MemoryStore.from_source("srv", POLICY)])
        self.server = WebhookServer(
            authorizer=CedarWebhookAuthorizer(stores),
            admission_handler=CedarAdmissionHandler(TieredPolicyStores(
                [MemoryStore.from_source("srv", POLICY),
                 allow_all_admission_policy_store()])),
            address="127.0.0.1", port=0, metrics_port=0,
            tracer=Tracer(sample_rate=0.0, ring_capacity=16),
        )
        self.parsed, self.sends = [], []
        parsed, sends = self.parsed, self.sends
        handler = self.server._make_handler()

        class Noting(handler):
            def setup(self):
                self.request = CountingSocket(self.request, sends)
                super().setup()

            def parse_request(self):
                ok = super().parse_request()
                left = {"ok": ok, "how": self._read_how}
                for attr in ("command", "path", "request_version",
                             "requestline", "close_connection"):
                    left[attr] = getattr(self, attr, None)
                if ok:
                    left["headers"] = {n: self.headers.get(n) for n in NAMES}
                    left["default"] = self.headers.get("Absent", "dflt")
                parsed.append(left)
                return ok

        class AsItWas(Noting):
            def _scan_request(self):
                return False

            def _write_json(self, doc, code=200, headers=None):
                data = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(data)

        self.server._make_handler = lambda: AsItWas if as_it_was else Noting
        self.server.start()

    def exchange(self, *pieces):
        """Send the pieces on a new connection, half a second apart,
        half-close, and return every byte the server wrote before it
        closed."""
        del self.parsed[:], self.sends[:]
        sock = socket.create_connection(("127.0.0.1", self.server.bound_port), 10)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        got = b""
        try:
            for i, piece in enumerate(pieces):
                if i:
                    time.sleep(0.5)
                sock.sendall(piece)
            sock.shutdown(socket.SHUT_WR)
            while True:
                chunk = sock.recv(65536)
                if not chunk:
                    break
                got += chunk
        except ConnectionError:
            pass  # closed on unread input: what was written is what counts
        finally:
            sock.close()
        return got


@pytest.fixture(scope="module")
def pair():
    ours, witness = Served(False), Served(True)
    yield ours, witness
    ours.server.stop()
    witness.server.stop()


def request(line=b"POST /v1/authorize HTTP/1.1", headers=(), body=None,
            length=True):
    body = sar() if body is None else body
    lines = [line, *headers]
    if length:
        lines.append(b"Content-Length: %d" % len(body))
    return b"\r\n".join(lines) + b"\r\n\r\n" + body


LOADGEN = (b"Host: 127.0.0.1:10288", b"Content-Type: application/json")
APISERVER = (
    b"Host: cedar-webhook.kube-system.svc:10288",
    b"User-Agent: kube-apiserver/v1.31.0 (linux/amd64) kubernetes/9edcffc",
    b"Accept: application/json, */*",
    b"Content-Type: application/json",
    b"Accept-Encoding: gzip",
)
TRACEPARENT = b"traceparent: 00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"

# name -> (the raw request, how the handler reads it, the status it answers;
# negative where http.server has no version to answer in yet and writes the
# error's page with no head, as to an HTTP/0.9 client)
TABLE = {
    "loadgen": (request(headers=LOADGEN), "scan", 200),
    "loadgen_admit": (
        request(b"POST /v1/admit HTTP/1.1", LOADGEN, review()), "scan", 200),
    "apiserver": (
        request(b"POST /v1/authorize?timeout=30s HTTP/1.1", APISERVER),
        "scan", 200),
    "apiserver_traced": (
        request(b"POST /v1/authorize?timeout=30s HTTP/1.1",
                APISERVER + (TRACEPARENT,)), "scan", 200),
    "mixed_case_names": (
        request(headers=(b"hOsT: a.example", b"CONTENT-TYPE: application/json",
                         b"X-CEDAR-TENANT: blue", b"TracePARENT: junk",
                         b"content-LENGTH: %d" % len(sar())), length=False),
        "scan", 200),
    "duplicate_names": (
        request(headers=(b"Host: first", b"host: second",
                         b"x-cedar-tenant: one", b"X-Cedar-Tenant: two")),
        "scan", 200),
    "value_spacing": (
        request(headers=(b"Host:no-space", b"x-cedar-tenant: \t padded  ",
                         b"User-Agent:", b"Connection:  keep-alive")),
        "scan", 200),
    "connection_close": (
        request(headers=LOADGEN + (b"Connection: close",)), "scan", 200),
    "connection_close_cased": (
        request(headers=LOADGEN + (b"connection: CLOSE",)), "scan", 200),
    "connection_keep_alive": (
        request(headers=LOADGEN + (b"Connection: keep-alive",)), "scan", 200),
    "unknown_path": (
        request(b"POST /v1/nothing HTTP/1.1", LOADGEN), "scan", 404),
    "bad_content_length": (
        request(headers=LOADGEN + (b"Content-Length: ten",), length=False,
                body=b""), "scan", 400),
    "negative_content_length": (
        request(headers=LOADGEN + (b"Content-Length: -1",), length=False,
                body=b""), "scan", 413),
    "body_over_the_cap": (
        request(headers=LOADGEN + (
            b"Content-Length: %d" % (MAX_BODY_BYTES + 1),), length=False,
            body=b""), "scan", 413),
    "no_content_length": (
        request(headers=LOADGEN, length=False, body=b""), "scan", 200),
    "http_1_0": (
        request(b"POST /v1/authorize HTTP/1.0", LOADGEN), "full", 200),
    "http_1_0_keep_alive": (
        request(b"POST /v1/authorize HTTP/1.0",
                LOADGEN + (b"Connection: keep-alive",)), "full", 200),
    "get": (b"GET /v1/authorize HTTP/1.1\r\nHost: h\r\n\r\n", "full", 404),
    "expect_100_continue": (
        request(headers=LOADGEN + (b"Expect: 100-continue",)), "full", 100),
    "transfer_encoding": (
        request(headers=LOADGEN + (b"Transfer-Encoding: identity",)),
        "full", 200),
    "folded_line": (
        request(headers=(b"Host: h", b"X-Long: one", b"\ttwo",
                         b"Content-Type: application/json")), "full", 200),
    "bare_lf_lines": (
        request(headers=LOADGEN).replace(b"\r\n", b"\n", 3), "full", 200),
    "bare_cr_in_a_value": (
        request(headers=(b"Host: h", b"X-Odd: a\rHost: b")), "full", 200),
    "form_feed_in_a_value": (
        request(headers=(b"X-Odd: a\x0cx-cedar-tenant: b", b"Host: h")),
        "full", 200),
    "latin_1_value": (
        request(headers=(b"Host: h", b"X-Name: caf\xe9")), "full", 200),
    "line_without_a_name": (
        request(headers=(b"Host: h", b"no colon here", b"X-After: lost")),
        "full", 200),
    "colon_first": (
        request(headers=(b"Host: h", b": nameless", b"X-After: kept")),
        "full", 200),
    "no_header_lines": (b"POST /v1/authorize HTTP/1.1\r\n\r\n", "full", 200),
    "doubled_slash": (
        request(b"POST //v1/authorize HTTP/1.1", LOADGEN), "full", 200),
    "line_of_70_kb": (
        request(headers=LOADGEN + (b"X-Big: " + b"a" * 70_000,)), "full", 431),
    "headers_101": (
        request(headers=tuple(b"X-%d: v" % i for i in range(101))),
        "full", 431),
    "headers_99": (
        request(headers=tuple(b"X-%d: v" % i for i in range(98))),
        "scan", 200),
    "headers_100": (
        request(headers=tuple(b"X-%d: v" % i for i in range(99))),
        "full", 431),
    "bad_request_line": (b"POST /v1/authorize\r\nHost: h\r\n\r\n", "full", -400),
    "four_words": (
        request(b"POST /v1/authorize extra HTTP/1.1", LOADGEN), "full", 400),
    "bad_version": (
        request(b"POST /v1/authorize HTTP/one", LOADGEN), "full", -400),
    "http_2_0": (request(b"POST /v1/authorize HTTP/2.0", LOADGEN), "full", -505),
    "lower_case_method": (
        request(b"post /v1/authorize HTTP/1.1", LOADGEN), "full", 501),
}

_VOLATILE = re.compile(
    rb"(?m)^(Date: |X-Cedar-Trace-Id: |traceparent: 00-)[^\r\n]*")


def settled(reply):
    """A reply with the second it was written in and the request's random
    ids taken out."""
    return _VOLATILE.sub(rb"\1~", reply)


@pytest.mark.parametrize("case", sorted(TABLE))
def test_the_scan_leaves_what_http_servers_reader_leaves(pair, case):
    ours, witness = pair
    raw, how, status = TABLE[case]
    reply, theirs = ours.exchange(raw), witness.exchange(raw)
    if status > 0:
        assert reply.startswith(b"HTTP/1.1 %d " % status), reply[:80]
    else:
        assert reply.startswith(b"<!DOCTYPE"), reply[:80]
        assert b"Error code: %d" % -status in reply
    assert settled(reply) == settled(theirs)
    assert [p["how"] for p in ours.parsed] == [how] + ["full"] * (
        len(ours.parsed) - 1)
    assert {p["how"] for p in witness.parsed} == {"full"}
    for p in ours.parsed + witness.parsed:
        del p["how"]
    assert ours.parsed == witness.parsed


@pytest.mark.parametrize("cut, how", [
    # the line alone: the scan waits for the block as the reader would
    (len(b"POST /v1/authorize HTTP/1.1\r\n"), "scan"),
    # the block in two pieces: what is in the buffer is no whole block
    (len(b"POST /v1/authorize HTTP/1.1\r\nHost: 127."), "full"),
])
def test_a_request_that_comes_in_pieces_is_answered_all_the_same(pair, cut, how):
    ours, witness = pair
    raw = TABLE["loadgen"][0]
    reply = ours.exchange(raw[:cut], raw[cut:])
    assert [p["how"] for p in ours.parsed] == [how]
    assert reply.startswith(b"HTTP/1.1 200 OK\r\n")
    assert settled(reply) == settled(witness.exchange(raw[:cut], raw[cut:]))
    for p in ours.parsed + witness.parsed:
        del p["how"]
    assert ours.parsed == witness.parsed


def test_a_traced_request_is_answered_under_its_own_id(pair):
    ours, _ = pair
    reply = ours.exchange(TABLE["apiserver_traced"][0])
    assert b"X-Cedar-Trace-Id: 0af7651916cd43dd8448eb211c80319c\r\n" in reply
    assert b"traceparent: 00-0af7651916cd43dd8448eb211c80319c-" in reply


def test_two_requests_in_one_segment_are_answered_in_order(pair):
    ours, _ = pair
    ids = ("1" * 32, "2" * 32)
    raw = b"".join(
        request(b"POST /v1/authorize HTTP/1.1", LOADGEN + (
            b"traceparent: 00-%s-b7ad6b7169203331-01" % i.encode(),),
            sar(user))
        for i, user in zip(ids, ("sam", "nobody")))
    reply = ours.exchange(raw)
    assert [p["how"] for p in ours.parsed] == ["scan", "scan"]
    heads = re.findall(rb"X-Cedar-Trace-Id: ([0-9a-f]+)\r\n", reply)
    assert heads == [i.encode() for i in ids]
    first, second = reply.split(b"HTTP/1.1 200 OK\r\n")[1:]
    assert b'"allowed": true' in first and b'"allowed": true' not in second


def header_lines(reply):
    head, _, body = reply.partition(b"\r\n\r\n")
    status, *lines = head.split(b"\r\n")
    return status, [line.split(b": ", 1) for line in lines], body


@pytest.mark.parametrize("case", ["loadgen", "loadgen_admit"])
def test_a_reply_reaches_the_socket_in_one_send(pair, case):
    ours, witness = pair
    raw = TABLE[case][0]
    reply = ours.exchange(raw)
    assert ours.sends == [len(reply)]
    witness_reply = witness.exchange(raw)
    assert len(witness.sends) == 2  # what it was: the head, then the body
    status, lines, body = header_lines(reply)
    theirs = header_lines(witness_reply)
    assert status == theirs[0] == b"HTTP/1.1 200 OK"
    assert [n for n, _ in lines] == [n for n, _ in theirs[1]] == [
        b"Server", b"Date", b"Content-Type", b"Content-Length",
        b"X-Cedar-Trace-Id", b"traceparent"]
    values, their_values = dict(lines), dict(theirs[1])
    for name in (b"Server", b"Content-Type", b"Content-Length"):
        assert values[name] == their_values[name]
    assert re.fullmatch(
        rb"[A-Z][a-z]{2}, \d{2} [A-Z][a-z]{2} \d{4} \d{2}:\d{2}:\d{2} GMT",
        values[b"Date"])
    assert int(values[b"Content-Length"]) == len(body)
    assert json.loads(body) == json.loads(theirs[2])


def test_the_access_line_is_formatted_only_under_debug(
    pair, caplog, monkeypatch
):
    ours, _ = pair
    formatted = []
    log_request = BaseHTTPRequestHandler.log_request

    def noting(self, *args, **kwargs):
        formatted.append(args)
        return log_request(self, *args, **kwargs)

    monkeypatch.setattr(BaseHTTPRequestHandler, "log_request", noting)
    ours.exchange(TABLE["loadgen"][0])
    assert formatted == []
    with caplog.at_level("DEBUG", logger="cedar_tpu.server.http"):
        ours.exchange(TABLE["loadgen"][0])
    assert len(formatted) == 1
    assert any('"POST /v1/authorize HTTP/1.1" 200' in r.getMessage()
               for r in caplog.records)


# ------------------------------------------------- cedar_http_reads_total


def reads():
    return {
        (dict(k)["path"], dict(k)["how"]): v
        for k, v in metrics.http_reads_total._values.items()
    }


def delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


EXPECTED_PATH = {
    "loadgen_admit": "admission", "unknown_path": "other", "get": "other",
    "bad_content_length": "other", "negative_content_length": "other",
    "body_over_the_cap": "other", "line_of_70_kb": "other",
    "headers_101": "other", "headers_100": "other",
    "bad_request_line": "other", "four_words": "other",
    "bad_version": "other", "http_2_0": "other", "lower_case_method": "other",
}


@pytest.mark.parametrize("case", sorted(TABLE))
def test_the_counter_says_how_each_request_was_read(pair, case):
    ours, _ = pair
    raw, how, _ = TABLE[case]
    before = reads()
    ours.exchange(raw)
    # the handler counts once the reply is out: wait for its thread
    for _ in range(200):
        moved = delta(before, reads())
        if sum(moved.values()) >= len(ours.parsed) >= 1:
            break
        time.sleep(0.01)
    path = EXPECTED_PATH.get(case, "authorization")
    assert moved.pop((path, how)) == 1
    # what the cut-off tail of an unanswerable request was read as
    assert set(moved) <= {("other", "full")}


def test_the_family_is_on_metrics_with_both_labels(pair):
    ours, _ = pair
    ours.exchange(TABLE["loadgen"][0])
    ours.exchange(TABLE["http_1_0"][0])
    text = metrics.REGISTRY.expose()
    assert "# TYPE cedar_http_reads_total counter" in text
    assert 'cedar_http_reads_total{path="authorization",how="scan"}' in text
    assert 'cedar_http_reads_total{path="authorization",how="full"}' in text


# --------------------------- the three per-layer metrics that read the family

RECORDED = pathlib.Path(__file__).resolve().parent / "benchmark_tests"

# metric -> (path it reads, the end-to-end metric it moves, cells)
METRICS = {
    "scan_read_share.saturate": (
        "authorization", "decisions_per_s", ["synth-10k.sar-saturate"]),
    "scan_read_share.lone": (
        "authorization", "latency_p50_ms",
        ["selector-1k.sar-lone", "synth-10k.sar-lone"]),
    "scan_read_share.admission": (
        "admission", "latency_p50_ms", ["pss-admit.admit-lone"]),
}
# requests between the two scrapes, by path: (scan, full)
WINDOW = {"authorization": (995, 5), "admission": (40, 0), "other": (0, 9)}


def exposition(scale):
    c = metrics.Counter("cedar_http_reads_total", "reads", ["path", "how"])
    for path, (scan, full) in WINDOW.items():
        # a server that had answered before the window opened
        c.inc(11 + scale * scan, path=path, how="scan")
        c.inc(2 + scale * full, path=path, how="full")
    return prom.parse("\n".join(c.collect()))


def read(ctx, metric):
    spec = Manifest().metric_file(metric)
    return reader_module(spec["reader"]).read(ctx, spec["params"])


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_scan_metric_reads_the_share_worked_out_by_hand(metric):
    ctx = Context()
    ctx.prom_before, ctx.prom_after = exposition(0), exposition(1)
    scan, full = WINDOW[METRICS[metric][0]]
    assert read(ctx, metric) == pytest.approx(100.0 * scan / (scan + full))


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_scan_metric_reads_the_served_counter(pair, metric):
    """From a /metrics pair of the program itself: plain requests between
    the scrapes read 100 %, and one odd client among four reads 75 %."""
    ours, _ = pair
    path = METRICS[metric][0]
    line = (b"POST /v1/admit HTTP/1.%d" if path == "admission"
            else b"POST /v1/authorize HTTP/1.%d")
    body = review() if path == "admission" else sar()

    def scrape():
        return prom.parse(metrics.REGISTRY.expose())

    ctx = Context()
    ctx.prom_before = scrape()
    for _ in range(3):
        ours.exchange(request(line % 1, LOADGEN, body))
    ctx.prom_after = scrape()
    assert read(ctx, metric) == pytest.approx(100.0)
    ours.exchange(request(line % 0, LOADGEN, body))
    ctx.prom_after = scrape()
    assert read(ctx, metric) == pytest.approx(75.0)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_scan_metric_reads_nothing_from_a_server_without_the_counter(metric):
    ctx = Context()
    ctx.prom_before = prom.parse((RECORDED / "recorded_metrics_before.txt").read_text())
    ctx.prom_after = prom.parse((RECORDED / "recorded_metrics_after.txt").read_text())
    assert read(ctx, metric) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_scan_metric_is_the_entry_the_issue_asked_for(metric):
    m = Manifest()
    assert validate(m) == []
    entry = next(x for x in m.doc["per_layer"] if x["name"] == metric)
    path, moves, cells = METRICS[metric]
    assert entry == {
        "name": metric, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "ingress server/http.py",
        "moves": moves, "workloads": cells,
    }
    spec = m.metric_file(metric)
    assert {k: spec[k] for k in entry} == entry
    assert spec["reader"] == "prom_delta_share"
    assert spec["params"]["part"] == {
        "name": "cedar_http_reads_total",
        "labels": {"path": path, "how": "scan"}}
    assert spec["params"]["total"] == {
        "name": "cedar_http_reads_total", "labels": {"path": path}}
    for cell in cells:
        assert moves in {x["name"] for x in m.metrics_for(cell, "end_to_end")}
        assert metric in {x["name"] for x in m.metrics_for(cell, "per_layer")}
    # the three stand together, in their order, where they were appended
    # (later PRs append after them): nothing that was there moved
    names = [x["name"] for x in m.doc["per_layer"]]
    at = names.index("scan_read_share.saturate")
    assert at == 82 and names[at:at + 3] == [
        "scan_read_share.saturate", "scan_read_share.lone",
        "scan_read_share.admission"]
