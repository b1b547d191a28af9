"""Stall recorder: did this process run, and if not, why not.

A served request can lose seconds to something no request-level stamp can
name: the whole process got no CPU, a collection ran, or one thread sat in
a call that keeps the interpreter lock while every other thread waited.
Only the server's own process can tell these apart, so it carries a
watcher (docs/observability.md "Process stalls"):

  * a daemon thread waits ``tick_s`` (20 ms) at a time on a
    ``threading.Event`` and measures how LATE it ran. That lateness, every
    tick, is ``cedar_interpreter_wait_seconds`` — a direct reading of what
    a thread that is due waits to get the interpreter back — and the time
    watched is ``cedar_process_watch_seconds_total``;
  * a tick at least ``stall_s`` (100 ms) late is a STALL, classified from
    deltas taken across it: ``gc`` (collections, timed by this module's
    ``gc.callbacks`` hook, overlap at least half of it), ``descheduled``
    (the whole process used under 10 % of it in CPU), else
    ``interpreter_held`` (the process burnt CPU, this thread could not
    run). Counted in ``cedar_process_stalls_total{cause}`` and
    ``cedar_process_stall_seconds_total{cause}``, kept in a ring of 32
    behind ``/debug/stalls`` with ``profiler`` ``on`` or ``off`` (whether
    a profiler session was open), and logged at WARNING;
  * the moment the watcher gets the interpreter back it snapshots every
    thread's Python stack (``sys._current_frames()``, under the lock like
    any Python code). A thread that kept the lock inside one long call
    gives it up at its first bytecode after that call, still inside the
    frame that made it, and then has to queue for it again — so the
    snapshot shows the culprit where it was. The stacks of threads whose
    innermost frame is not in the standard library (parked threads wait
    in ``threading``, ``selectors``, ``socket``, ``queue``) come first.

Why not ``faulthandler.dump_traceback_later``, which can look *during* a
stall because its C watchdog thread needs no lock: it walks the other
threads' frames while they may be running, which is only safe in a
process that is truly stuck. A first version re-armed it five times a
second; on the chip the server died with SIGSEGV in 4 of 11 runs, each
time just after a 0.4–0.5 s collection had kept the watcher from
re-arming and the dump met threads that were moving again (PERF.md §6,
PR 27).

One recorder per process (``acquire`` / ``release`` count its users): the
``gc.callbacks`` hook is process-wide, and two watchers would only read
the same lateness twice.
"""

from __future__ import annotations

import gc
import json
import logging
import os
import resource
import sys
import threading
import time
from collections import deque
from typing import Optional

from .trace import profiler_on

log = logging.getLogger(__name__)

CAUSES = ("gc", "descheduled", "interpreter_held")
_STDLIB = os.path.dirname(threading.__file__)


class StallRecorder:
    def __init__(
        self,
        tick_s: float = 0.02,
        stall_s: float = 0.1,
        ring: int = 32,
    ):
        self.tick_s = tick_s
        self.stall_s = stall_s
        self._stalls: deque = deque(maxlen=ring)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._schedstat_fd: Optional[int] = None
        # collections as (start, end) on time.monotonic(), newest last;
        # written by whichever thread collects (under the interpreter
        # lock, one collection at a time), read by the watcher
        self._gc_runs: deque = deque(maxlen=64)
        self._gc_started: Optional[float] = None
        self.ticks = 0
        self.watched_s = 0.0
        self.counts = dict.fromkeys(CAUSES, 0)

    # -------------------------------------------------------------- lifecycle

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(
            target=self._run, name="stall-recorder", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        thread, self._thread = self._thread, None
        if thread is None:
            return
        self._stop.set()
        thread.join(timeout=2.0)
        try:
            gc.callbacks.remove(self._on_gc)
        except ValueError:
            pass
        if self._schedstat_fd is not None:
            os.close(self._schedstat_fd)
            self._schedstat_fd = None

    # ------------------------------------------------------------------ hooks

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            self._gc_started = now
        elif self._gc_started is not None:
            self._gc_runs.append((self._gc_started, now))
            self._gc_started = None

    def _gc_overlap(self, t0: float, t1: float) -> float:
        """Seconds of [t0, t1] during which a collection ran."""
        runs = list(self._gc_runs)
        started = self._gc_started
        if started is not None:
            # the collector is done but its "stop" callback has not run
            # yet: the interpreter came to the watcher, which had waited
            # longest, before the callback's first bytecode
            runs.append((started, t1))
        return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in runs)

    def _counters(self) -> tuple:
        """(process CPU seconds, this thread's run-queue delay in seconds,
        involuntary context switches, major faults) — cumulative."""
        ru = resource.getrusage(resource.RUSAGE_SELF)
        delay = 0.0
        if self._schedstat_fd is not None:
            try:
                # "<ns on cpu> <ns waiting on a run queue> <timeslices>"
                # of the thread that opened it: this watcher
                fields = os.pread(self._schedstat_fd, 96, 0).split()
                delay = int(fields[1]) / 1e9
            except (OSError, IndexError, ValueError):
                pass
        return time.process_time(), delay, ru.ru_nivcsw, ru.ru_majflt

    # frames kept per thread, threads kept per stall: a record stays a few KB
    STACK_FRAMES = 8
    STACK_THREADS = 96

    def _stacks(self) -> list:
        """Every other thread's Python stack, innermost frame first, as
        ``"<thread name>: file:line func < file:line func < …"`` lines;
        threads that are not parked in the standard library first."""
        names = {t.ident: t.name for t in threading.enumerate()}
        me = threading.get_ident()
        busy, parked = [], []
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            top_is_stdlib = frame.f_code.co_filename.startswith(_STDLIB)
            hops = []
            while frame is not None and len(hops) < self.STACK_FRAMES:
                code = frame.f_code
                # the file with its directory: "server/http.py:852 f"
                where = "/".join(code.co_filename.rsplit("/", 2)[-2:])
                hops.append(f"{where}:{frame.f_lineno} {code.co_name}")
                frame = frame.f_back
            line = f"{names.get(ident, ident)}: " + " < ".join(hops)
            (parked if top_is_stdlib else busy).append(line)
        return (busy + parked)[: self.STACK_THREADS]

    # ------------------------------------------------------------------- loop

    def _run(self) -> None:
        from ..server.metrics import record_interpreter_wait

        try:
            # /proc/thread-self resolves at open(): this thread's own file
            self._schedstat_fd = os.open(
                "/proc/thread-self/schedstat", os.O_RDONLY
            )
        except OSError:
            self._schedstat_fd = None  # not Linux: no run-queue delay
        prev = time.monotonic()
        before = self._counters()
        while not self._stop.wait(self.tick_s):
            now = time.monotonic()
            late = max(0.0, now - prev - self.tick_s)
            self.ticks += 1
            self.watched_s += now - prev
            try:
                # first of all, while whoever held the interpreter is
                # still where it was
                stacks = self._stacks() if late >= self.stall_s else None
                record_interpreter_wait(late, now - prev)
                after = self._counters()
                if stacks is not None:
                    self._record(prev + self.tick_s, now, before, after, stacks)
                before = after
            except Exception:  # noqa: BLE001 — the watcher must keep watching
                log.exception("stall recorder tick failed")
            prev = time.monotonic()

    def _record(self, due: float, ran: float, before, after, stacks) -> None:
        from ..server.metrics import record_process_stall

        length = ran - due
        cpu_s = after[0] - before[0]
        gc_s = self._gc_overlap(due, ran)
        if gc_s >= 0.5 * length:
            cause = "gc"
        elif cpu_s < 0.1 * length:
            cause = "descheduled"
        else:
            cause = "interpreter_held"
        entry = {
            "start_unix": round(time.time() - length, 3),
            "length_ms": round(length * 1e3, 1),
            "cause": cause,
            "gc_ms": round(gc_s * 1e3, 1),
            "process_cpu_ms": round(cpu_s * 1e3, 1),
            "run_queue_delay_ms": round((after[1] - before[1]) * 1e3, 1),
            "involuntary_switches": after[2] - before[2],
            "major_faults": after[3] - before[3],
            # whether a profiler session was open as it ended: the stops of
            # seconds seen so far all fell in traced windows (PERF.md §7)
            "profiler": "on" if profiler_on() else "off",
        }
        record_process_stall(cause, length)
        log.warning(
            "process stall: %s\nstacks as it ended:\n%s",
            json.dumps(entry), "\n".join(stacks),
        )
        entry["stacks"] = stacks
        with self._lock:
            self._stalls.append(entry)
            self.counts[cause] += 1

    # ----------------------------------------------------------------- lookup

    def status(self) -> dict:
        """The /debug/stalls document, newest stall first."""
        with self._lock:
            stalls = list(reversed(self._stalls))
            counts = dict(self.counts)
        return {
            "watching": self._thread is not None and self._thread.is_alive(),
            "tick_ms": self.tick_s * 1e3,
            "stall_ms": self.stall_s * 1e3,
            "ticks": self.ticks,
            "watched_s": round(self.watched_s, 3),
            "stalls_total": counts,
            "stalls": stalls,
        }


# ------------------------------------------------- the process's one recorder

_recorder: Optional[StallRecorder] = None
_users = 0
_users_lock = threading.Lock()


def acquire() -> StallRecorder:
    """The process's recorder, started for its first user."""
    global _recorder, _users
    with _users_lock:
        if _recorder is None:
            _recorder = StallRecorder()
            _recorder.start()
        _users += 1
        return _recorder


def release() -> None:
    """Stopped when its last user lets go."""
    global _recorder, _users
    with _users_lock:
        if _users == 0:
            return
        _users -= 1
        if _users == 0 and _recorder is not None:
            _recorder.stop()
            _recorder = None
