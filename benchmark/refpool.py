"""The reference's answers, worked out by processes of their own.

The reference walks every policy for every request in plain Python (about
10 ms a request at 10,000 policies), and a window holds ten to thirty
thousand requests, so several processes share the work while the server
child loads. Set-up never waits for them: where they have not finished
when the window is due, they are stopped (SIGSTOP) for the length of the
window and go on once it has closed, so that they take no core from the
generators and the reference never sits inside ``setup_s``.
"""

from __future__ import annotations

import multiprocessing
import os
import signal

from benchmark.manifest import kind_module
from benchmark.reference import Reference


def _work(files: dict, control: str, kind_ref: tuple, cpus, items: list, pipe) -> None:
    try:
        if cpus:
            os.sched_setaffinity(0, cpus)
        ref = Reference(files, control=control)
        kind = kind_module(*kind_ref)
        pipe.send(("done", [(i, kind.expected(ref, spec)) for i, spec in items]))
    except BaseException as e:  # noqa: BLE001 — reported to the parent, then re-raised
        pipe.send(("error", repr(e)))
        raise
    finally:
        pipe.close()


class ReferencePool:
    """``kind_ref`` is the request kind's (name, directory), as a plan
    carries it: each process imports the kind itself."""

    def __init__(self, files: dict, workers: int, kind_ref: tuple, cpus=None,
                 control: str = ""):
        self.files, self.workers, self.cpus, self.control = files, workers, cpus, control
        self.kind_ref = kind_ref
        self.answers: dict = {}
        self.running: list = []  # (process, pipe)

    def submit(self, specs: list, indices: list) -> None:
        """Start processes over the indices that have no answer yet."""
        todo = [(i, specs[i]) for i in indices if i not in self.answers]
        if not todo:
            return
        ctx = multiprocessing.get_context("spawn")
        n = min(self.workers, len(todo))
        for k in range(n):
            mine, theirs = ctx.Pipe(duplex=False)
            p = ctx.Process(
                target=_work, daemon=True,
                args=(self.files, self.control, self.kind_ref, self.cpus, todo[k::n], theirs),
            )
            p.start()
            theirs.close()
            self.running.append((p, mine))

    def _signal(self, sig) -> None:
        for p, _ in self.running:
            if p.is_alive():
                os.kill(p.pid, sig)

    def pause(self) -> None:
        self._signal(signal.SIGSTOP)

    def resume(self) -> None:
        self._signal(signal.SIGCONT)

    def collect(self, timeout: float = 600.0) -> dict:
        for p, pipe in self.running:
            if not pipe.poll(timeout):
                raise TimeoutError("a reference process gave no answers in time")
            kind, payload = pipe.recv()
            if kind != "done":
                raise RuntimeError(f"reference process: {payload}")
            self.answers.update(payload)
            p.join(10)
        self.running = []
        return self.answers

    def close(self) -> None:
        self._signal(signal.SIGCONT)
        for p, _ in self.running:
            if p.is_alive():
                p.kill()
            p.join(5)
        self.running = []
