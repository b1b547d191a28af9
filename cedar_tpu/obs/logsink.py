"""Serving log sink: the process's log lines, written by one thread.

``logging.basicConfig`` gives a process one ``StreamHandler``: every log
call takes the handler's lock and writes to the log's file descriptor while
holding it. The write gives the interpreter up, and the holder has to win
it back before it can release the lock — so under 64 concurrent callers the
per-request ``authorize requestId=…`` line held the lock about a
millisecond a request and every caller stood in line behind it (PERF.md §6,
PR 27 and PR 28). The line is an operator's join key to ``/debug/traces``
and the audit logs and stays, every one of it; what goes is the I/O and the
lock on the thread that owes the apiserver an answer
(docs/observability.md "Serving log"):

  * the caller's side (``LogSink.emit``, the root logger's only handler)
    renders the record's ``%``-arguments and any exception to text, so no
    live object crosses threads, and makes one ``SimpleQueue.put``. It has
    no formatter, no stream and no handler lock. ``record.created`` is the
    caller's stamp, so ``%(asctime)s`` stays the time of the event;
  * the writer's side, one daemon thread, owns the ``StreamHandler``. It
    blocks on the queue; after a first record below WARNING it waits one
    tick for company, drains what has arrived, and hands the stream ONE
    ``write`` of the joined lines and one ``flush``. A record at WARNING or
    above wakes it at once. Its counters (``cedar_log_records_total``,
    ``cedar_log_writes_total``) say how often it coalesces;
  * the queue is bounded: past ``CAP`` records below WARNING are dropped
    and counted, WARNING and above never are, and the writer says how many
    went in one line once it has caught up;
  * ``drain()`` (``main()`` after ``server.stop()``, and ``close()`` from
    ``logging.shutdown`` at exit) writes what is queued and joins the
    writer; from then on the handler writes on the calling thread, so
    shutdown's last lines still appear.

The standard library's ``QueueHandler`` / ``QueueListener`` pair moves the
write too, but its listener writes and flushes a line at a time under the
handler's lock (at a thousand lines a second it would need a hand-off of
the interpreter per line and fall behind), and it has no cap, no tick and
no count. The tick, the cap and the WARNING rule are constants: no flag
turns any of this off. What a SIGKILL or a crash can lose is one tick of lines below
WARNING.
"""

from __future__ import annotations

import logging
import queue
import threading
from typing import Optional

from ..server import metrics

FORMAT = "%(asctime)s %(name)s %(levelname)s %(message)s"
TICK_S = 0.010
CAP = 65536

_STOP = object()


class LogSink(logging.Handler):
    """Queueing handler and its writer thread. ``start()`` starts the
    writer; until then records wait in the queue."""

    def __init__(self, stream=None):
        super().__init__()
        self._out = logging.StreamHandler(stream)
        self._out.setFormatter(logging.Formatter(FORMAT))
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._urgent = threading.Event()
        self._dropped = 0
        self._dropped_lock = threading.Lock()
        # held across the stream's write, so only ever taken where this
        # sink writes on the calling thread: in drain() and after it
        self._sync_lock = threading.Lock()
        self._drained = False
        self._thread: Optional[threading.Thread] = None

    def createLock(self) -> None:
        # SimpleQueue.put needs none, and a caller must never wait on one
        self.lock = None

    # ------------------------------------------------------------ caller
    def emit(self, record: logging.LogRecord) -> None:
        try:
            urgent = record.levelno >= logging.WARNING
            if not urgent and self._queue.qsize() >= CAP:
                with self._dropped_lock:
                    self._dropped += 1
                metrics.record_log_dropped()
                return
            # rendered in place: this is the root's only handler, and the
            # rendered record formats to the same bytes as the live one
            record.msg = record.getMessage()
            record.args = None
            if record.exc_info:
                if not record.exc_text:
                    record.exc_text = self._out.formatter.formatException(
                        record.exc_info
                    )
                record.exc_info = None
            self._queue.put(record)
            if self._drained:
                with self._sync_lock:
                    self._write_queued()
            elif urgent:
                self._urgent.set()
        except Exception:  # noqa: BLE001 — a log call never raises
            self.handleError(record)

    # ------------------------------------------------------------ writer
    def start(self) -> "LogSink":
        self._thread = threading.Thread(
            target=self._run, name="cedar-logsink", daemon=True
        )
        self._thread.start()
        return self

    def _run(self) -> None:
        q = self._queue
        while True:
            first = q.get()
            if first is not _STOP and first.levelno < logging.WARNING:
                self._urgent.wait(TICK_S)
            self._urgent.clear()
            # this thread is the only consumer: what qsize() counted is there
            batch = [first] + [q.get_nowait() for _ in range(q.qsize())]
            self._write(batch)
            if _STOP in batch:
                return

    def _write(self, batch: list) -> None:
        """One write and one flush for the records of ``batch`` and, where
        records were dropped since the last write, the line that says how
        many."""
        records = [r for r in batch if r is not _STOP]
        with self._dropped_lock:
            dropped, self._dropped = self._dropped, 0
        if dropped:
            records.append(logging.LogRecord(
                __name__, logging.WARNING, __file__, 0,
                "%d log records dropped", (dropped,), None,
            ))
        if not records:
            return
        out = self._out
        lines = []
        for record in records:
            try:
                lines.append(out.format(record) + out.terminator)
            except Exception:  # noqa: BLE001 — one bad record, not the batch
                out.handleError(record)
        try:
            out.stream.write("".join(lines))
            out.flush()
        except Exception:  # noqa: BLE001 — a closed or full stream
            out.handleError(records[-1])
        metrics.record_log_write(len(lines))

    def _write_queued(self) -> None:
        """With ``_sync_lock`` held and no writer thread: write the queue."""
        batch = []
        try:
            while True:
                batch.append(self._queue.get_nowait())
        except queue.Empty:
            pass
        self._write(batch)

    # ---------------------------------------------------------- shutdown
    def drain(self) -> None:
        """Write everything queued, join the writer, and write on the
        calling thread from here on. Idempotent."""
        with self._sync_lock:
            thread, self._thread = self._thread, None
            if thread is not None:
                self._queue.put(_STOP)
                thread.join()
            # set before the sweep: an emit that put without seeing it put
            # before the sweep, one that saw it sweeps for itself
            self._drained = True
            self._write_queued()

    def close(self) -> None:
        # logging.shutdown(), which logging registers with atexit, closes
        # every handler: the drain at exit
        self.drain()
        super().close()


def install(level: int, stream=None) -> Optional[LogSink]:
    """What ``logging.basicConfig(level=level, format=FORMAT)`` did, with
    the write moved off the caller; like it, nothing on a root logger that
    already has handlers. Returns the sink; ``logging.shutdown`` drains it
    at exit."""
    root = logging.getLogger()
    if root.handlers:
        return None
    sink = LogSink(stream).start()
    root.addHandler(sink)
    root.setLevel(level)
    return sink
