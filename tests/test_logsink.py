"""The serving log's sink alone (cedar_tpu/obs/logsink.py,
docs/observability.md "Serving log"): no server, no device."""

import contextlib
import copy
import io
import logging
import sys
import threading
import time

import pytest

from cedar_tpu.obs import logsink
from cedar_tpu.server import metrics

AUTHORIZE = "authorize requestId=%s decision=%s latency=%.6fs"


class Keep(logging.Handler):
    """Copies of the live records, taken before the sink renders them."""

    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(copy.copy(record))


class SlowStream(io.StringIO):
    """A stream whose every write takes ``delay_s``, as a file descriptor
    that gives the interpreter up does."""

    def __init__(self, delay_s):
        super().__init__()
        self.delay_s = delay_s

    def write(self, text):
        time.sleep(self.delay_s)
        return super().write(text)


class GatedStream(io.StringIO):
    """A stream whose writes wait for ``gate``."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()
        self.entered = threading.Event()

    def write(self, text):
        self.entered.set()
        assert self.gate.wait(10.0)
        return super().write(text)


def counts():
    out = {"written": 0.0, "dropped": 0.0, "writes": 0.0}
    for line in metrics.REGISTRY.expose().splitlines():
        head, _, value = line.rpartition(" ")
        if head == "cedar_log_writes_total":
            out["writes"] = float(value)
        elif head.startswith("cedar_log_records_total{"):
            out[head.split('"')[1]] = float(value)
    return out


def since(before):
    return {k: v - before[k] for k, v in counts().items()}


@pytest.fixture
def wired(request):
    """(logger, sink, stream) with the sink as a private logger's handler;
    a started writer unless the test asks for ``started=False``."""
    made = []

    def make(stream=None, started=True):
        stream = io.StringIO() if stream is None else stream
        sink = logsink.LogSink(stream)
        if started:
            sink.start()
        log = logging.getLogger(f"test.logsink.{request.node.name}.{len(made)}")
        log.propagate = False
        log.setLevel(logging.DEBUG)
        log.addHandler(sink)
        made.append((log, sink, stream))
        return log, sink, stream

    yield make
    for log, sink, stream in made:
        if isinstance(stream, GatedStream):
            stream.gate.set()
        log.removeHandler(sink)
        sink.close()


def emit_from_threads(log, threads, each, before_join=None):
    """Seconds the slowest thread spent inside its ``each`` log calls."""
    spent = [0.0] * threads
    go = threading.Barrier(threads)

    def work(t):
        go.wait(10.0)
        t0 = time.perf_counter()
        for i in range(each):
            log.info(AUTHORIZE, f"{t}-{i}", "allow", 0.001)
        spent[t] = time.perf_counter() - t0

    workers = [threading.Thread(target=work, args=(t,)) for t in range(threads)]
    for w in workers:
        w.start()
    if before_join is not None:
        before_join()
    for w in workers:
        w.join(30.0)
        assert not w.is_alive()
    return max(spent)


def assert_every_line_once_in_each_threads_order(text, threads, each):
    ids = [line.split("requestId=")[1].split(" ")[0] for line in text.splitlines()]
    assert len(ids) == threads * each
    assert len(set(ids)) == threads * each
    for t in range(threads):
        mine = [int(i.split("-")[1]) for i in ids if i.startswith(f"{t}-")]
        assert mine == list(range(each))


def test_the_convoy_cannot_form(wired):
    """32 threads x 200 lines over a stream whose write takes 1 ms: a handler
    that locks and writes needs 6.4 s of calls; here the callers only queue."""
    log, sink, stream = wired(SlowStream(0.001))
    before = counts()
    spent = emit_from_threads(log, threads=32, each=200)
    assert spent < 1.5
    sink.drain()
    assert_every_line_once_in_each_threads_order(stream.getvalue(), 32, 200)
    moved = since(before)
    assert moved["written"] == 6400 and moved["dropped"] == 0
    # (f) records per write > 1 under a burst
    assert moved["writes"] < 6400 / 2


def test_no_line_is_lost_or_doubled_when_drain_races_the_callers(wired):
    """More threads than cores, the interpreter switching every 10 us, and
    drain() called while they log: lines written by the writer, by drain's
    sweep and by the callers themselves afterwards, each exactly once."""
    log, sink, stream = wired()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        emit_from_threads(log, threads=24, each=300, before_join=sink.drain)
    finally:
        sys.setswitchinterval(old)
    sink.drain()
    assert_every_line_once_in_each_threads_order(stream.getvalue(), 24, 300)


def _plain(log):
    log.info(AUTHORIZE, "0b5f-77", "allow", 0.004321)


def _no_arguments(log):
    log.warning("100% of nothing, braces {} and all")


def _mapping(log):
    log.info("engine %(name)s loaded %(n)d policies", {"name": "authz", "n": 10000})


def _exception(log):
    try:
        raise ValueError("bad policy")
    except ValueError:
        log.exception("reload failed for %s", "store-a")


def _stack(log):
    log.error("where was this", stack_info=True)


@pytest.mark.parametrize("say", [_plain, _no_arguments, _mapping, _exception, _stack])
def test_a_line_is_what_basicconfigs_format_gives_and_its_time_is_the_events(wired, say):
    stream = GatedStream()
    log, sink, _ = wired(stream)
    keep = Keep()
    log.handlers.insert(0, keep)
    try:
        log.info("first")           # the writer takes it and stands in write()
        assert stream.entered.wait(5.0)
        say(log)
        time.sleep(0.05)            # ... for 50 ms after the event
        stream.gate.set()
        sink.drain()
    finally:
        log.removeHandler(keep)
    reference = logging.Formatter("%(asctime)s %(name)s %(levelname)s %(message)s")
    expected = "".join(reference.format(r) + "\n" for r in keep.records)
    assert stream.getvalue() == expected
    event = keep.records[1]
    assert reference.formatTime(event) in stream.getvalue().splitlines()[1]
    assert time.time() - event.created >= 0.05


def test_a_warning_wakes_the_writer_and_info_waits_for_the_tick(wired, monkeypatch):
    monkeypatch.setattr(logsink, "TICK_S", 2.0)
    log, sink, stream = wired()
    log.info("waits for company")
    time.sleep(0.1)
    assert stream.getvalue() == ""
    best = None
    for attempt in range(3):        # 50 ms is met on a quiet core
        t0 = time.perf_counter()
        log.warning("stall %d", attempt)
        while f"stall {attempt}" not in stream.getvalue():
            assert time.perf_counter() - t0 < 1.0
            time.sleep(0.001)
        took = time.perf_counter() - t0
        best = took if best is None else min(best, took)
        if best < 0.05:
            break
    assert best < 0.05
    lines = stream.getvalue().splitlines()
    assert "waits for company" in lines[0] and "stall 0" in lines[1]


def test_past_the_cap_info_is_dropped_and_counted_and_warnings_are_kept(wired, monkeypatch):
    monkeypatch.setattr(logsink, "CAP", 100)
    log, sink, stream = wired(started=False)
    before = counts()
    for i in range(150):
        log.info("info %d", i)
    log.warning("kept over the cap")
    for i in range(10):
        log.debug("debug %d", i)
    sink.start()
    sink.drain()
    lines = stream.getvalue().splitlines()
    assert [l.split(" INFO ")[1] for l in lines[:100]] == [f"info {i}" for i in range(100)]
    assert lines[100].endswith("WARNING kept over the cap")
    assert lines[101].endswith("cedar_tpu.obs.logsink WARNING 60 log records dropped")
    assert len(lines) == 102
    assert since(before) == {"written": 102, "dropped": 60, "writes": 1}
    log.info("room again")
    assert stream.getvalue().splitlines()[-1].endswith("INFO room again")
    assert since(before)["dropped"] == 60


@pytest.mark.parametrize("how", ["drain", "close"])
def test_drain_is_idempotent_and_a_later_line_is_written_by_its_caller(wired, how):
    log, sink, stream = wired(SlowStream(0.0))
    log.info("queued")
    writer = sink._thread
    getattr(sink, how)()
    getattr(sink, how)()
    sink.drain()
    assert not writer.is_alive()
    assert stream.getvalue().count("\n") == 1
    before = counts()
    log.info("shutdown's last line")
    assert stream.getvalue().splitlines()[-1].endswith("INFO shutdown's last line")
    assert since(before) == {"written": 1, "dropped": 0, "writes": 1}


def test_a_record_that_cannot_be_rendered_is_reported_not_raised(wired, capsys):
    log, sink, stream = wired()
    log.info("two %s %s", "one")
    log.info("fine")
    sink.drain()
    assert stream.getvalue().splitlines()[-1].endswith("INFO fine")
    assert "--- Logging error ---" in capsys.readouterr().err


@contextlib.contextmanager
def bare_root():
    """The root logger as a fresh process has it (pytest hangs its capture
    handlers on it for each test's call)."""
    root = logging.getLogger()
    handlers, level = root.handlers[:], root.level
    root.handlers[:] = []
    try:
        yield root
    finally:
        for h in root.handlers[:]:
            root.removeHandler(h)
            h.close()
        root.handlers[:] = handlers
        root.setLevel(level)


@pytest.mark.parametrize("level", [logging.INFO, logging.DEBUG])
def test_install_is_basicconfig_with_the_write_moved(level):
    stream = io.StringIO()
    with bare_root() as root:
        sink = logsink.install(level, stream)
        assert root.handlers == [sink] and root.level == level
        assert sink.lock is None
        logging.getLogger("cedar_tpu.server.http").info(AUTHORIZE, "id-1", "deny", 0.25)
        logging.getLogger("cedar_tpu.server.http").debug("verbose")
        sink.drain()
        # like basicConfig, nothing on a root that has handlers
        assert logsink.install(level, stream) is None
        assert root.handlers == [sink]
    lines = stream.getvalue().splitlines()
    assert lines[0].endswith(
        "cedar_tpu.server.http INFO authorize requestId=id-1 decision=deny latency=0.250000s")
    assert len(lines) == (2 if level == logging.DEBUG else 1)
