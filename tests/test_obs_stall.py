"""The stall recorder (cedar_tpu/obs/stall.py), one child process a case.

A stall is made on purpose in a child that runs the recorder — never in
the pytest worker, whose interpreter the other tests of the worker share —
and the child prints the recorder's /debug/stalls document when it is
over. The three causes: a thread that keeps the interpreter lock inside
one C call (``interpreter_held``), a collection over a large heap (``gc``),
the whole child stopped by a signal (``descheduled``) — and a thread that
keeps the lock while it sleeps, which the deltas read as ``descheduled``
and only the stacks tell apart.
"""

import json
import os
import pathlib
import signal
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

CHILD = r'''
import gc, json, sys, threading, time
sys.path.insert(0, sys.argv[1])
from cedar_tpu.obs.stall import StallRecorder
from cedar_tpu.server import metrics

cause, seconds = sys.argv[2], float(sys.argv[3])

def hold_the_lock(n):
    # one call into C that does not give the interpreter lock back; like
    # most code, the frame goes on for a moment after its long call
    total = sum(range(n))
    time.sleep(0.05)
    return total

def sleep_with_the_lock(seconds):
    # the same, for a time the clock fixes: PyDLL calls keep the lock
    import ctypes
    ctypes.PyDLL(None).usleep(int(seconds * 1e6))
    time.sleep(0.05)

heap = None
if cause == "gc":
    gc.disable()
    heap = [[i] for i in range(int(seconds * 10_000_000))]
if cause == "interpreter_held":
    t = time.monotonic(); sum(range(2_000_000))
    n = int(2_000_000 * seconds / (time.monotonic() - t))

rec = StallRecorder()
rec.start()
time.sleep(0.3)
print("ready", flush=True)
if cause == "interpreter_held":
    th = threading.Thread(target=hold_the_lock, args=(n,))
    th.start(); th.join()
elif cause == "held_asleep":
    th = threading.Thread(target=sleep_with_the_lock, args=(seconds,))
    th.start(); th.join()
elif cause == "gc":
    gc.collect()
elif cause == "descheduled":
    sys.stdin.readline()          # the parent stops and continues us
else:
    time.sleep(seconds)
time.sleep(0.3)
doc = rec.status()
rec.stop()
doc["interpreter_wait_count"] = sum(metrics.interpreter_wait_seconds._totals.values())
doc["stall_seconds"] = {dict(k)["cause"]: v for k, v in
                        metrics.process_stall_seconds_total._values.items()}
doc["watch_seconds"] = sum(metrics.process_watch_seconds_total._values.values())
print(json.dumps(doc), flush=True)
'''


def run_child(cause: str, seconds: float) -> dict:
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD, str(ROOT), cause, str(seconds)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        assert child.stdout.readline().strip() == "ready"
        if cause == "descheduled":
            os.kill(child.pid, signal.SIGSTOP)
            time.sleep(seconds)
            os.kill(child.pid, signal.SIGCONT)
            child.stdin.write("\n")
            child.stdin.flush()
        out, _ = child.communicate(timeout=120)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(10)
    assert child.returncode == 0
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("cause", ["interpreter_held", "gc", "descheduled"])
def test_a_stall_is_recorded_with_its_cause(cause):
    doc = run_child(cause, 0.4)
    assert doc["stalls"], doc
    longest = max(doc["stalls"], key=lambda s: s["length_ms"])
    assert longest["cause"] == cause, doc["stalls"]
    assert 100.0 <= longest["length_ms"] <= 3000.0
    assert doc["stalls_total"][cause] >= 1
    assert doc["stall_seconds"][cause] == pytest.approx(
        sum(s["length_ms"] for s in doc["stalls"] if s["cause"] == cause) / 1e3,
        abs=2e-3)
    # the deltas that were taken across it say why
    if cause == "gc":
        assert longest["gc_ms"] >= 0.5 * longest["length_ms"]
    elif cause == "descheduled":
        assert longest["process_cpu_ms"] < 0.1 * longest["length_ms"]
    else:
        assert longest["process_cpu_ms"] >= 0.5 * longest["length_ms"]
        assert longest["gc_ms"] < 0.5 * longest["length_ms"]
    # every tick was a reading of the wait for the interpreter
    assert doc["interpreter_wait_count"] == doc["ticks"] > 10
    assert doc["watch_seconds"] == pytest.approx(doc["watched_s"], abs=1e-3)


@pytest.mark.parametrize("cause,frame", [
    ("interpreter_held", "hold_the_lock"), ("held_asleep", "sleep_with_the_lock")])
def test_the_stacks_taken_as_a_hold_ends_name_the_holding_frame(cause, frame):
    doc = run_child(cause, 0.7)
    longest = max(doc["stalls"], key=lambda s: s["length_ms"])
    assert longest["length_ms"] >= 250.0
    # the holder gave the lock up at its first bytecode after the call,
    # still inside the frame that made it; threads not parked come first
    assert frame in longest["stacks"][0], longest["stacks"]
    assert any(line.startswith("MainThread: python3") and "threading.py" in line
               for line in longest["stacks"][1:])
    if cause == "held_asleep":
        # the holder slept, so by the rule nobody in the process ran: the
        # deltas cannot tell this from a stopped process, the stacks can
        assert longest["cause"] == "descheduled"
        assert longest["process_cpu_ms"] < 0.1 * longest["length_ms"]
    else:
        assert longest["cause"] == "interpreter_held"


def test_an_idle_process_records_no_stall():
    doc = run_child("idle", 1.0)
    assert doc["stalls"] == [] and not any(doc["stalls_total"].values())
    assert doc["ticks"] >= 30 and doc["watching"]
