"""The server child: the operator's entry point, in the one process that
owns the chip, with a side channel for what only that process can do.

``python benchmark/serve.py --ctl-dir DIR [--cpus 0-7] -- <webhook args>``
calls ``cedar_tpu.cli.webhook.main(<webhook args>)`` in the main thread. A
second thread reads one JSON command per line from standard input and
answers each in ``DIR/reply-<id>.json``:

  ``stats``        the devices as JAX reports them, the fullest chip's
                   ``peak_bytes_in_use``, how many executables JAX has
                   built or loaded since the listener was registered, and
                   the Python collector's pauses since the last ``stats``
  ``trace_start``  ``jax.profiler.start_trace(dir)`` — host runtime events
                   on, the Python tracer off (it slows the host it traces)
  ``trace_stop``   ``jax.profiler.stop_trace()``

The program has no profiler call of its own, and only the chip's owner can
trace it; that is why the trace is taken from here. A collection stops every
thread of the server (it holds the interpreter lock), so its length is read
here too, by a ``gc.callbacks`` hook: one hypothesis for the server's stops.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def parse_cpus(text: str) -> set:
    cpus = set()
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        cpus.update(range(int(lo), int(hi or lo) + 1))
    return cpus


class GcPauses:
    """Lengths of the collector's runs, by ``gc.callbacks``. The collector
    runs under the interpreter lock, one run at a time, in whichever thread
    tripped it; ``take`` may miss a run that ends while it reads."""

    def __init__(self):
        self.started = None
        self.count, self.sum_s, self.max_s = 0, 0.0, 0.0

    def __call__(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self.started = now
        elif self.started is not None:
            pause = now - self.started
            self.started = None
            self.count += 1
            self.sum_s += pause
            self.max_s = max(self.max_s, pause)

    def take(self) -> dict:
        """The pauses since the last call, and a fresh count."""
        out = {"gc_collections": self.count, "gc_pause_sum_ms": 1e3 * self.sum_s,
               "gc_pause_max_ms": 1e3 * self.max_s}
        self.count, self.sum_s, self.max_s = 0, 0.0, 0.0
        return out


class Control(threading.Thread):
    def __init__(self, ctl_dir: pathlib.Path):
        super().__init__(daemon=True, name="benchmark-control")
        self.dir = ctl_dir
        self.compiles = 0
        self.listening = False
        self.gc_pauses = GcPauses()
        gc.callbacks.append(self.gc_pauses)

    def _listen(self) -> None:
        """Count executables built or loaded, once JAX is imported."""
        if self.listening or "jax" not in sys.modules:
            return
        import jax.monitoring

        def on_duration(event, duration, **kwargs):
            if event == COMPILE_EVENT:
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        self.listening = True

    def _stats(self) -> dict:
        import jax

        devices = jax.devices()
        peak = 0
        for d in devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
            "compiles": self.compiles,
            "listening": self.listening,
            **self.gc_pauses.take(),
        }

    def handle(self, cmd: dict) -> dict:
        self._listen()
        name = cmd.get("cmd")
        if name == "stats":
            return self._stats()
        if name == "trace_start":
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(cmd["dir"], profiler_options=opts)
            return {"t": time.monotonic()}
        if name == "trace_stop":
            import jax

            jax.profiler.stop_trace()
            return {"t": time.monotonic()}
        return {"error": f"unknown command {name!r}"}

    def run(self) -> None:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            cmd: dict = {}
            try:
                cmd = json.loads(line)
                reply = self.handle(cmd)
            except Exception as e:  # noqa: BLE001 — the parent reads the failure
                reply = {"error": f"{type(e).__name__}: {e}"}
            path = self.dir / f"reply-{cmd.get('id', 'x')}.json"
            tmp = path.with_suffix(".tmp")
            tmp.write_text(json.dumps(reply))
            os.replace(tmp, path)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--ctl-dir", required=True)
    p.add_argument("--cpus", default="")
    p.add_argument("rest", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.cpus:
        os.sched_setaffinity(0, parse_cpus(args.cpus))
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    Control(pathlib.Path(args.ctl_dir)).start()
    from cedar_tpu.cli.webhook import main as webhook_main

    return webhook_main(rest)


if __name__ == "__main__":
    sys.exit(main())
