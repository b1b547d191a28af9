"""Micro-batching bridge between request threads and the batch evaluator.

The webhook serves one HTTP request per thread (the moral equivalent of the
reference's goroutine-per-request, /root/reference internal/server/server.go),
but the TPU engine wants batches. The MicroBatcher collects items submitted
by concurrent request threads inside a short window and hands them to the
batch function in one call; each submitter blocks until its own result is
ready. This is the micro-batching gRPC-link design of SURVEY.md §5.8,
in-process.

Latency shape: under the serial batcher a lone request waits at most
``window_s`` (default 200µs) before the batch fires, while a saturated
server naturally forms large batches (up to ``max_batch``) and rides the
device's throughput curve. The pipelined batcher claims a request that
came alone to an idle pipeline at once, on no timer.

``PipelinedBatcher`` replaces the strictly serial worker loop with a
three-stage pipeline (docs/performance.md): the COLLECT thread claims a
batch and runs its host ENCODE itself while batch N's device work is in
flight, the DISPATCH thread launches each encoded batch asynchronously and
immediately moves to the next, and a DECODE thread materializes results
and completes each submitter's slot. At most ONE claimed batch stands
before the dispatch thread (the late claim, PipelinedBatcher): until that
place is free the collector leaves requests in the submit queue, where
they can still be withdrawn and join what arrives next; a bounded
depth-``depth`` queue before the decode stage provides the backpressure
behind the launch.
Submission semantics (deadline withdrawal, coalescing, drain-on-stop) are
IDENTICAL to the serial batcher: both share one queue/slot front end, and
the stages are required to produce the same results the serial batch fn
would.
"""

from __future__ import annotations

import logging
import os
import queue as _queue
import threading
import time
from typing import Callable, List, Optional, Sequence, TypeVar

from ..chaos.registry import chaos_fire
from ..obs.trace import batch_stage, note_batch_result, profiler_on
from ..server.supervisor import Heartbeat

log = logging.getLogger(__name__)

T = TypeVar("T")
R = TypeVar("R")


def _record_worker_death(component: str, replica: str = "") -> None:
    """A worker thread is unwinding on an uncaught exception: make the
    death VISIBLE (log + cedar_worker_deaths_total{component, replica}) at
    the point it happens — before supervision, a dead stage just left its
    bounded queue filling forever with nothing in any dashboard. The
    replica label names the fleet member the worker served (empty on the
    single-engine path), so a fleet member's death is attributable."""
    log.critical(
        "worker thread %s%s died on an uncaught exception",
        component,
        f" [{replica}]" if replica else "",
    )
    try:
        from ..server.metrics import record_worker_death

        record_worker_death(component, replica)
    except Exception:  # noqa: BLE001 — metrics must never mask the death
        pass

# end-of-stream marker flowing through the pipeline hand-off queues on
# drain: the collector sends it after its last batch, each stage forwards
# it after finishing all prior work, so every accepted item's slot is set
# before any worker thread exits
_SENTINEL = object()


def _record_stall(path: Optional[str], stage: str, seconds: float) -> None:
    if path is None or seconds <= 0:
        return
    try:
        from ..server.metrics import record_pipeline_stall

        record_pipeline_stall(path, stage, seconds)
    except Exception:  # noqa: BLE001 — metrics must never break serving
        pass


def _record_occupancy(path: Optional[str], n: int) -> None:
    if path is None:
        return
    try:
        from ..server.metrics import record_batch_occupancy

        record_batch_occupancy(path, n)
    except Exception:  # noqa: BLE001 — metrics must never break serving
        pass


def _record_claim(path: Optional[str], held: bool, lingered: bool) -> None:
    if path is None:
        return
    try:
        from ..server.metrics import record_batch_claim, record_batch_linger

        record_batch_claim(path, held)
        if lingered:
            record_batch_linger(path)
    except Exception:  # noqa: BLE001 — metrics must never break serving
        pass


class DeadlineExceeded(Exception):
    """A submitter's per-request budget elapsed before its batch result
    arrived. The request may still be evaluated by the batch thread; the
    caller has already answered (NoOpinion / configured admission
    fail-mode), so the late result is discarded.

    ``queued`` is True when the budget demonstrably burned in the submit
    queue of a MOVING plane: some batch finished after this slot
    enqueued (progress — an overloaded device keeps completing batches;
    a hung one completes nothing, and then the expiry is the breaker's
    only signal, so it must keep counting) AND the slot was either still
    unclaimed at expiry or claimed only after more than half the budget
    was already gone (the batch got the tail end of a spent deadline).
    Under open-loop overload these are the dominant expiry shapes, and
    they must not feed the device breaker's latency-breach accounting
    (server/http.py): the breaker watches the device plane, and a queue
    drowning in offered load is the admission controller's problem, not
    a sick accelerator's."""

    queued = False


# a decode's wait for the device's result past this is counted and logged
# (cedar_long_device_waits_total): a lone batch's is 0.4-0.5 ms on the chip
LONG_DEVICE_WAIT_S = 0.1


class _StageTimes:
    """Per-batch monotonic stage stamps, shared by every slot the batch
    claimed. ONE source of truth for both the request traces
    (cedar_tpu/obs) and the cedar_pipeline_stage_seconds histograms, so a
    span tree and a dashboard can never disagree about where a batch
    spent its time. The worker loops only stamp time.monotonic() (through
    obs.trace.batch_stage, which also puts the stage on a running
    profiler's clock) — all span construction happens later, in the
    request thread, and only for requests that carry an active trace.

    ``sub`` holds the seconds of the stages inside dispatch and decode
    (``dispatch.stage`` / ``.launch`` / ``.readback``,
    ``decode.device_wait`` / ``.host``), split by the obs.trace.sub_stage
    sites in engine/evaluator.py and engine/fastpath.py while this record
    is bound to the worker's thread (``part`` / ``part_t0``: the running
    segment); a stage's parts sum to its window. ``lingered``: the claim
    slept out a forming window first (_form_batch). ``seq``: the batch's
    number in its batcher, given at the claim — every ``cedar.*``
    annotation of the batch carries it, on whichever thread. ``launches``,
    ``uploads``, ``upload_bytes``, ``readback_bytes``: what the dispatch's
    launches sent up and started home (obs.trace.note_launch /
    note_readback), counted once the batch is done."""

    __slots__ = (
        "claimed", "first_enq", "lingered", "seq", "rows", "extras_max",
        "groups", "known_groups", "sub", "part", "part_t0",
        "launches", "uploads", "upload_bytes", "readback_bytes",
        "encode0", "encode1", "dispatch0", "dispatch1",
        "decode0", "decode1", "eval0", "eval1",
    )

    def __init__(self, claimed: float, lingered: bool, seq: int = 0):
        self.claimed = claimed
        self.first_enq: Optional[float] = None
        self.lingered = lingered
        self.seq = seq
        self.rows = 0
        # the widest row's set-membership extras, where a native encode ran
        # (obs.trace.note_encode_extras): `extras_max` on batch.encode,
        # beside the most groups a row's principal carries and the most of
        # them that some policy names (`groups`, `known_groups`)
        self.extras_max: Optional[int] = None
        self.groups = 0
        self.known_groups = 0
        self.sub: dict = {}
        self.part: Optional[str] = None
        self.part_t0 = 0.0
        self.launches = self.uploads = self.upload_bytes = 0
        self.readback_bytes = 0
        self.encode0 = self.encode1 = None
        self.dispatch0 = self.dispatch1 = None
        self.decode0 = self.decode1 = None
        self.eval0 = self.eval1 = None


class _Slot:
    __slots__ = ("event", "result", "error", "waiters", "key", "t_enq", "times")

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.error: BaseException | None = None
        # coalescing accounting: how many submitters share this slot, and
        # the coalesce key it is registered under while still queued
        self.waiters = 1
        self.key = None
        # queue-wait accounting: when this slot was enqueued, and the
        # claiming batch's shared stage-stamp record (None until claimed)
        self.t_enq = time.monotonic()
        self.times: Optional[_StageTimes] = None


class _Place:
    """The one standing place before the pipelined batcher's dispatch
    thread (PipelinedBatcher's docstring). The collector takes it before
    it claims a batch and waits for it on this object alone; the dispatch
    thread frees it, stamping when — whether a claim was HELD is decided
    from that stamp, not from when the collector's thread next ran.
    Freeing a free place changes nothing, so every path a batch can leave
    by may free it."""

    __slots__ = ("_free", "freed_at")

    def __init__(self):
        self._free = threading.Event()
        self._free.set()
        self.freed_at = time.monotonic()

    def free(self) -> None:
        if not self._free.is_set():
            self.freed_at = time.monotonic()
            self._free.set()

    def wait(self, timeout: float) -> bool:
        """True once free (at once where it already is)."""
        return self._free.wait(timeout)

    def take(self) -> None:
        self._free.clear()


class MicroBatcher:
    # how often a blocked submitter re-checks the worker thread's liveness:
    # if the worker dies without setting its slots (anything outside the
    # per-batch try/except — an interpreter teardown, a C-extension crash
    # that unwinds the thread), waiters must not hang forever
    LIVENESS_POLL_S = 0.5

    def __init__(
        self,
        fn: Optional[Callable[[Sequence[T]], List[R]]],
        max_batch: int = 8192,
        window_s: float = 0.0002,
        metrics_path: Optional[str] = None,
        replica: str = "",
        dispatch_seam: Optional[str] = None,
    ):
        self._fn = fn
        self.max_batch = max_batch
        self.window_s = window_s
        # label for cedar_batch_occupancy / cedar_pipeline_stall metrics;
        # None (embedders, tests) records nothing
        self.metrics_path = metrics_path
        # fleet-member identity for worker-death attribution
        # (cedar_worker_deaths_total{component, replica}); "" on the
        # single-engine path so existing label sets stay stable
        self.replica = replica
        # optional extra chaos seam fired by the batch-claiming worker loop
        # (after pipeline.collect, same containment: OUTSIDE the per-batch
        # try, so a kill rule unwinds the worker like a real crash). The
        # fleet wires "fleet.replica_dispatch" here so a game day can kill
        # exactly one replica's worker mid-traffic (docs/fleet.md).
        self._dispatch_seam = dispatch_seam
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: List[tuple] = []
        # coalesce_key -> queued entry, for submitters that opt into
        # sharing one queue slot per identical pending item; entries leave
        # this map when the worker claims them (or the last waiter
        # withdraws), so post-claim submitters enqueue fresh work
        self._pending: dict = {}
        self._stopped = False
        # the number the next claimed batch gets (_StageTimes.seq)
        self._seq = 0
        self._threads: List[threading.Thread] = []
        # worker generation: revive() bumps it, and every worker loop
        # checks its captured epoch so a superseded (dead-and-replaced, or
        # wedged-and-abandoned) generation can never race the fresh one
        # for queued work
        self._epoch = 0
        # when the last batch finished (monotonic; completion or failure
        # both count — either proves the plane is MOVING): the deadline
        # expiry accounting uses it to tell overload (batches completing,
        # this slot just never got its turn → spare the breaker) from a
        # wedge (nothing has finished since this slot enqueued → the
        # expiry is the only signal a hung device ever emits)
        self._last_batch_done = 0.0
        # per-stage liveness beacons for the supervisor's wedge detection
        # (server/supervisor.py): busy+stale = wedged, idle = healthy
        self.heartbeats: dict = {}
        # batches claimed per protocol-mix signature ("sar" for plain
        # bodies; PDP bodies carry .protocol): a multi-protocol signature
        # is the direct evidence that SAR + ext_authz + batch traffic
        # sharing a tick landed in ONE device dispatch (docs/pdp.md;
        # asserted by bench.py --mesh-traffic and /debug/engine)
        self._protocol_mix: dict = {}
        self._start_workers()

    def _start_workers(self) -> None:
        self.heartbeats.setdefault("worker", Heartbeat())
        self._thread = threading.Thread(
            target=self._run, name="micro-batcher", daemon=True
        )
        self._threads = [self._thread]
        self._thread.start()

    def revive(self, force: bool = False) -> bool:
        """Restart dead worker threads (supervisor hook). ``force`` also
        abandons live-but-wedged workers: the epoch bump makes any old
        generation exit at its next loop iteration, and fresh workers take
        over the submit queue. Queued items survive (the new workers
        evaluate them); work held INSIDE a wedged stage call completes
        whenever that call returns, or its waiters' deadlines free them.
        Returns False when nothing needed doing (or the batcher is
        stopped)."""
        with self._cv:
            if self._stopped:
                return False
            dead = [t for t in self._threads if not t.is_alive()]
            if not dead and not force:
                return False
            self._epoch += 1
            self._cv.notify_all()
            self._start_workers()
        log.warning(
            "micro-batcher revived (%d dead worker(s)%s)",
            len(dead),
            ", forced" if force else "",
        )
        return True

    def _alive(self) -> bool:
        """True while every worker thread is running: any dead stage means
        accepted items may never complete, so submitters must bail."""
        return all(t.is_alive() for t in self._threads)

    def debug_stats(self) -> dict:
        """Live queue/config snapshot for /debug/engine."""
        with self._cv:
            q = len(self._queue)
            mix = dict(self._protocol_mix)
        return {
            "mode": "serial",
            "queue": q,
            "max_batch": self.max_batch,
            "window_us": round(self.window_s * 1e6, 1),
            "protocol_mix": mix,
        }

    def queue_fill(self) -> int:
        """Queued (unclaimed) items — the fleet router's load signal."""
        with self._cv:
            return len(self._queue)

    def has_pending(self, coalesce_key) -> bool:
        """True while an entry for this coalesce key is still QUEUED here
        — the fleet router's coalescing-affinity signal: identical
        concurrent requests must land on the replica already holding the
        shared slot, or least-loaded spreading would evaluate K times
        what one batcher would have evaluated once."""
        if coalesce_key is None:
            return False
        with self._cv:
            return coalesce_key in self._pending

    def submit(
        self,
        item: T,
        timeout: Optional[float] = None,
        coalesce_key: Optional[str] = None,
    ) -> R:
        """Enqueue one item and block until its result is available.

        ``timeout`` bounds the wall-clock wait (queue slot + batch window +
        evaluation): on expiry the item is withdrawn from the queue when
        still pending and ``DeadlineExceeded`` is raised. With or without a
        timeout the wait is never unbounded — a dead worker thread raises
        ``RuntimeError`` instead of stranding the submitter forever.

        ``coalesce_key`` opts into request coalescing: while an entry for
        the same key is still QUEUED (not yet claimed by the worker), a new
        submit attaches to its slot as an extra waiter instead of enqueuing
        a duplicate — the batch evaluates the item once and fans the result
        out. Waiter accounting keeps per-waiter deadlines independent: a
        timed-out follower only detaches itself; the shared queue slot is
        withdrawn (and its pending registration dropped) only when the LAST
        waiter leaves, so a follower expiry can never cancel the leader or
        strand a result future nobody can reach."""
        return self.wait_entry(
            self.enqueue(item, coalesce_key=coalesce_key), timeout=timeout
        )

    def enqueue(self, item: T, coalesce_key: Optional[str] = None) -> tuple:
        """Enqueue one item WITHOUT waiting; returns an opaque entry for
        ``wait_entry``/``entry_done``/``take_result``/``cancel``. The split
        surface exists for the fleet router's hedged dispatch
        (cedar_tpu/fleet): a request thread can hold entries on two
        replicas' batchers and take whichever answers first. Semantics
        (coalescing, stopped/dead refusal) are exactly submit()'s front
        half."""
        with self._cv:
            if self._stopped:
                raise RuntimeError("MicroBatcher is stopped")
            if not self._alive():
                raise RuntimeError("batcher dead: worker thread has exited")
            entry = (
                self._pending.get(coalesce_key)
                if coalesce_key is not None
                else None
            )
            if entry is not None:
                slot = entry[1]
                slot.waiters += 1
            else:
                slot = _Slot()
                entry = (item, slot)
                if coalesce_key is not None:
                    slot.key = coalesce_key
                    self._pending[coalesce_key] = entry
                self._queue.append(entry)
                self._cv.notify()
        return entry

    @staticmethod
    def entry_done(entry: tuple) -> bool:
        """True once the entry's result (or error) landed."""
        return entry[1].event.is_set()

    @staticmethod
    def entry_error(entry: tuple) -> Optional[BaseException]:
        """The completed entry's error, if its batch failed (hedged
        waiters drop an errored side and keep waiting on the other)."""
        return entry[1].error

    @staticmethod
    def entry_wait(entry: tuple, timeout: Optional[float]) -> bool:
        """Block up to ``timeout`` for the entry's result; True when set.
        No liveness polling — hedged waiters interleave this with their own
        ``_alive`` checks (wait_entry is the full-service wait)."""
        return entry[1].event.wait(timeout)

    def cancel(self, entry: tuple) -> None:
        """Detach one waiter without waiting (the hedge loser's
        cancel-on-first-answer): the shared queue slot is withdrawn only
        when the LAST waiter leaves, exactly like a deadline expiry."""
        with self._cv:
            self._withdraw(entry)

    def wait_entry(self, entry: tuple, timeout: Optional[float] = None) -> R:
        """submit()'s back half: block until the entry's result is
        available (bounded by ``timeout`` and worker liveness)."""
        slot = entry[1]
        deadline = None if timeout is None else time.monotonic() + timeout
        while not slot.event.is_set():
            wait = self.LIVENESS_POLL_S
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    with self._cv:
                        self._withdraw(entry)
                    if slot.event.is_set():
                        break  # result landed while we were withdrawing
                    err = DeadlineExceeded(
                        f"deadline of {timeout:.3f}s exceeded waiting for "
                        "batch result"
                    )
                    # queue-burned (class docstring) iff (a) the plane is
                    # demonstrably MOVING — some batch finished after this
                    # slot enqueued; a wedged device finishes nothing, and
                    # then the expiry is the breaker's only signal — AND
                    # (b) the budget burned waiting for a turn: still
                    # unclaimed, or claimed only after more than half the
                    # budget was already gone (the batch got the tail end
                    # of a spent deadline)
                    err.queued = self._last_batch_done > slot.t_enq and (
                        slot.times is None
                        or (
                            timeout is not None
                            and slot.times.claimed - slot.t_enq
                            > 0.5 * timeout
                        )
                    )
                    raise err
                wait = min(wait, remaining)
            if slot.event.wait(wait):
                break
            if not self._alive():
                if slot.event.is_set():
                    break  # final result delivered as the worker exited
                raise RuntimeError(
                    "batcher dead: worker thread exited without "
                    "delivering results"
                )
        self.annotate_trace(entry)
        return self.take_result(entry)

    @staticmethod
    def annotate_trace(entry: tuple) -> None:
        """The request thread has its entry's result: stamp the wake and
        hand the slot's batch stamps (cedar_tpu/obs pipeline_stamps:
        queue wait from the slot's own enqueue stamp, then the claiming
        batch's stages and the waits between them — the exact timestamps
        cedar_pipeline_stage_seconds observed) to the request's phase
        record or active trace. With tracing disarmed the cost is two
        thread-local reads."""
        note_batch_result(entry[1])

    def _record_batch_stages(self, times: "_StageTimes") -> None:
        """Publish one claimed batch's stage windows to the
        cedar_pipeline_stage_seconds histograms — same stamps the traces
        consume — and what its launches sent up and started home to the
        cedar_launch_* counters; a decode that waited over
        LONG_DEVICE_WAIT_S for the device's result is counted by whether a
        profiler session is open, and logged with the batch's number.
        Advisory like every metrics hook here."""
        if self.metrics_path is None or times is None:
            return
        try:
            from ..server.metrics import record_launch_io, record_pipeline_stage

            p = self.metrics_path
            if times.first_enq is not None:
                record_pipeline_stage(
                    p, "queue_wait", times.claimed - times.first_enq
                )
            for stage, a, b in (
                ("encode", times.encode0, times.encode1),
                ("dispatch", times.dispatch0, times.dispatch1),
                ("decode", times.decode0, times.decode1),
                ("evaluate", times.eval0, times.eval1),
            ):
                if a is not None and b is not None:
                    record_pipeline_stage(p, stage, b - a)
            for stage, seconds in times.sub.items():
                record_pipeline_stage(p, stage, seconds)
            if times.launches:
                record_launch_io(
                    p, times.uploads, times.upload_bytes, times.readback_bytes
                )
            wait = times.sub.get("decode.device_wait", 0.0)
            if wait > LONG_DEVICE_WAIT_S:
                from ..server.metrics import record_long_device_wait

                profiler = "on" if profiler_on() else "off"
                record_long_device_wait(p, profiler)
                log.warning(
                    "long device wait: batch seq=%d (%d rows) waited %.3f s "
                    "for its result; profiler %s",
                    times.seq, times.rows, wait, profiler,
                )
        except Exception:  # noqa: BLE001 — metrics must never break serving
            pass

    @staticmethod
    def take_result(entry: tuple) -> R:
        """Result (or raise) for a COMPLETED entry (entry_done() is True)."""
        slot = entry[1]
        if slot.error is not None:
            if slot.key is not None:
                # coalesced slots can have MULTIPLE waiters reaching this
                # raise: re-raising the shared object from several request
                # threads mutates its __traceback__ concurrently — the
                # exact interleaving the worker's per-slot fan-out
                # prevents. Wrap a fresh object per waiter, chained to the
                # shared one so the original traceback stays reachable.
                err = RuntimeError(str(slot.error))
                err.__cause__ = slot.error
                raise err
            raise slot.error
        return slot.result

    def stop(self, drain_timeout_s: float = 2.0) -> None:
        """Stop accepting new work and drain: the worker(s) process every
        queued item (late submitters get their answers) before exiting."""
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        deadline = time.monotonic() + drain_timeout_s
        for t in self._threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.05))

    # ------------------------------------------------------------- internals

    def _withdraw(self, entry: tuple) -> None:
        """One waiter's deadline expired (caller holds the lock). Decrement
        the slot's waiter count; only the LAST departing waiter removes the
        still-queued entry — by IDENTITY, never by equality. An equality
        ``list.remove`` could withdraw a different submitter's
        equal-looking entry (identical request bodies are the norm under
        coalescing) and would crash outright on items like numpy arrays
        whose ``==`` is elementwise."""
        slot = entry[1]
        slot.waiters -= 1
        if slot.waiters > 0:
            return  # other waiters still want the result: slot stays queued
        for i, e in enumerate(self._queue):
            if e is entry:
                del self._queue[i]
                break
        if slot.key is not None and self._pending.get(slot.key) is entry:
            del self._pending[slot.key]

    def _form_batch(self, epoch: Optional[int] = None) -> Optional[list]:
        """Wait for work and claim one batch under the lock — the shared
        front end of the serial worker and the pipeline collector. Returns
        None when stopped with an empty queue (the worker should exit), or
        when ``epoch`` no longer matches (this worker generation was
        superseded by revive(); a fresh generation owns the queue), or a
        possibly-empty batch (empty: every queued item withdrew during
        the forming window — never call the batch fn with zero rows, a
        no-op "success" must not feed breaker recovery probes)."""
        with self._cv:
            while not self._queue and not self._stopped:
                if epoch is not None and self._epoch != epoch:
                    return None
                self._cv.wait()
            if epoch is not None and self._epoch != epoch:
                return None
            if self._stopped and not self._queue:
                return None
            # batch-forming window: let concurrent submitters pile in.
            # The window is a hook (_linger_window_s): the pipelined
            # batcher returns 0 while batches are already in flight —
            # its collector only gets here once the place before the
            # dispatch thread is free (the late claim), so whatever
            # arrived while the launch of batch N held that thread is
            # already in the queue and rides one claim with NO host
            # linger added to its latency — and 0 for a request that
            # came alone to an idle pipeline. The pacing clock on the
            # chip is that launch (5.68 of a dispatch's 6.11 ms at 32
            # callers, the device 0.130 of them: ledger, PR 30), not
            # the device (docs/performance.md).
            window = self._linger_window_s()
            if window > 0:
                deadline = time.monotonic() + window
                while len(self._queue) < self.max_batch:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cv.wait(timeout=remaining)
            batch = self._queue[: self.max_batch]
            del self._queue[: self.max_batch]
            # claimed entries leave the coalesce map: submitters
            # arriving after the claim must enqueue fresh work rather
            # than attach to a result computed against an older policy
            # snapshot. The same pass stamps the batch's shared stage
            # record (queue-wait measured from the OLDEST member — the
            # worst wait in the batch is what the claim latency cost).
            times = None
            if batch:
                self._seq += 1
                times = _StageTimes(time.monotonic(), window > 0, self._seq)
            for _, slot in batch:
                slot.times = times
                if times.first_enq is None or slot.t_enq < times.first_enq:
                    times.first_enq = slot.t_enq
                if (
                    slot.key is not None
                    and self._pending.get(slot.key) is not None
                    and self._pending[slot.key][1] is slot
                ):
                    del self._pending[slot.key]
            if batch:
                sig = ",".join(
                    sorted(
                        {
                            getattr(item, "protocol", "") or "sar"
                            for item, _ in batch
                        }
                    )
                )
                self._protocol_mix[sig] = self._protocol_mix.get(sig, 0) + 1
        if batch:
            _record_occupancy(self.metrics_path, len(batch))
        return batch

    def _linger_window_s(self) -> float:
        """The batch-forming linger for THIS claim (see _form_batch).
        The serial batcher always lingers window_s; the pipelined
        batcher overrides this with its in-flight-aware version."""
        return self.window_s

    def _complete_batch(self, batch: list, results: Sequence[R]) -> None:
        if len(results) != len(batch):
            raise RuntimeError(
                f"batch fn returned {len(results)} results for "
                f"{len(batch)} items"
            )
        self._last_batch_done = time.monotonic()
        for (_, slot), res in zip(batch, results):
            slot.result = res
            slot.event.set()

    def _fail_batch(self, batch: list, e: BaseException) -> None:
        # one fresh exception per slot: sharing a single exception
        # object (and its traceback) across request threads interleaves
        # tracebacks and leaks one request's error text into others
        self._last_batch_done = time.monotonic()
        for _, slot in batch:
            err = RuntimeError(f"batch evaluation failed: {e!r}")
            err.__cause__ = e  # keep the original traceback reachable
            slot.error = err
            slot.event.set()

    def _run(self) -> None:
        try:
            self._run_loop()
        except BaseException:  # noqa: BLE001 — visibility, then unwind
            _record_worker_death("batcher.worker", self.replica)
            raise

    def _run_loop(self) -> None:
        epoch = self._epoch
        hb = self.heartbeats["worker"]
        while True:
            hb.idle()
            batch = self._form_batch(epoch)
            if batch is None:
                return
            if not batch:
                continue
            # chaos seams OUTSIDE the per-batch containment below: a kill
            # rule unwinds this worker exactly like a C-extension crash
            chaos_fire("pipeline.collect")
            if self._dispatch_seam is not None:
                chaos_fire(self._dispatch_seam, self.replica)
            hb.busy()
            times = batch[0][1].times
            times.eval0 = time.monotonic()
            # the end stamp lands BEFORE _complete_batch sets any waiter's
            # event: a woken request thread annotates its trace from these
            # stamps immediately, and a missing eval1 would silently drop
            # the batch.evaluate span
            try:
                results = self._fn([it for it, _ in batch])
                times.eval1 = time.monotonic()
                self._record_batch_stages(times)
                self._complete_batch(batch, results)
            except BaseException as e:  # noqa: BLE001 — propagate per-item
                if times.eval1 is None:
                    times.eval1 = time.monotonic()
                    self._record_batch_stages(times)
                self._fail_batch(batch, e)


class PipelinedBatcher(MicroBatcher):
    """Three-stage pipelined variant of the MicroBatcher (module docstring).

    ``stages`` must provide the split evaluation surface the raw fast paths
    expose (engine/fastpath.py):

      * ``pipeline_encode(items) -> ctx`` — host-only parse/encode; runs on
        the collector's thread, which claimed the batch: one batch stands
        before the dispatch thread, so one encode runs at a time and the
        collector has nothing else it may do until that place is freed
      * ``pipeline_dispatch(ctx) -> ctx`` — launch the device work
        asynchronously (no blocking readback); runs on the dispatch thread,
        which immediately moves to the next encoded batch
      * ``pipeline_decode(ctx) -> results`` — materialize (the only stage
        that blocks on the device), decode, resolve deferred rows; runs on
        the decode thread, which completes each submitter's slot

    so host decode of batch N overlaps the encode, the launch and the
    device execution of batch N+1.

    The late claim: at most ONE claimed batch stands before the dispatch
    thread (being encoded, or encoded and waiting). The collector claims
    the next batch only once that standing place is free — the dispatch
    thread frees it once the standing batch's launch has returned and the
    batch is handed to the decode stage — and until then requests stay in
    the submit queue, where they cost nothing, can still be withdrawn at
    their deadline, and join whatever else arrives before the claim. On the chip the one dispatch thread is the slowest
    stage (162 batches/s x 6.11 ms = 0.99 s of dispatch a second at 32
    callers; ledger, PR 30): the rule this replaced let ``depth`` encoded
    batches queue before it plus one in the collector's hands, each a
    launch of its own that every row behind it waited out (14 ms of a
    33 ms cycle, 5.9 rows a launch). Freeing the place after the launch
    costs the dispatch thread one encode of idling a batch and leaves a
    request no launch to wait out but the one in progress when it
    arrives and its own: 15 rows a launch and +22 to +25 % decisions a
    second on that cell; freeing it earlier, as the dispatch thread
    takes the standing batch, kept the encode overlapped, gave 7 rows a
    launch and +2 % (my chip runs, PR 31; PERF.md section 6). The
    collector waits for the place on an object of its own (_Place),
    never on the submitters' condition and never holding it; the held
    time is published as
    cedar_pipeline_stall_seconds_total{stage="collect"} and how often it
    engages as cedar_batch_claims_total{path, held}.

    The forming window: a claim sleeps ``window_s`` only where nothing is
    in flight and two or more entries already wait (a burst that has
    begun, _linger_window_s). A request that came alone to an idle
    pipeline is claimed at once: in the lone cells not one claim in
    ~12,000 a run ever gained a second row from the window (ledger,
    PR 34: batch_rows 1.0), and the timed wait cost a lone request 0.8 ms
    on the chip's host for its 0.2 (queue phase 0.83-0.91 -> 0.05-0.07 ms:
    my chip runs, PR 35). How often the window engages is
    cedar_batch_lingers_total{path}.

    ``depth`` bounds the batches launched and not yet decoded (the queue
    before the decode stage): when the device or the decode falls behind,
    the dispatch thread blocks there with the place still taken, and the
    backlog stays in the submit queue instead of in encoded batches.

    Error/drain contracts match the serial batcher exactly: a stage
    exception fails that batch's slots with per-waiter wrapped errors (the
    stages themselves degrade to interpreter-fallback RESULTS on device
    errors, so slot errors only surface stage bugs); stop() drains the
    submit queue through all three stages before the workers exit, so no
    accepted item's slot is ever left unset."""

    def __init__(
        self,
        stages,
        max_batch: int = 8192,
        window_s: float = 0.0002,
        depth: int = 2,
        metrics_path: Optional[str] = None,
        replica: str = "",
        dispatch_seam: Optional[str] = None,
    ):
        self.stages = stages
        # CEDAR_TPU_INFLIGHT caps the in-flight batch depth from the
        # environment: "1" is the single-buffer escape hatch for the
        # double-buffering byte differential (bench.py --steady compares
        # responses with and without overlap), larger values widen the
        # staging window beyond the constructor's depth
        env_depth = os.environ.get("CEDAR_TPU_INFLIGHT", "")
        if env_depth:
            try:
                depth = int(env_depth)
            except ValueError:
                pass
        self.depth = max(1, int(depth))
        self._batches_total = 0
        # batches accepted into the pipeline but not yet decoded; lets the
        # decode stage distinguish starvation (work exists upstream, the
        # decoder is idle) from a genuinely idle server. Three threads
        # mutate it — always through _inflight_add (a bare += is
        # LOAD/ADD/STORE and loses updates under contention, which would
        # pin the decode-stall accounting on forever-idle servers)
        self._inflight = 0
        # the same, in ENTRIES (every batch's len added/removed at the
        # exact sites _inflight moves): backlog()'s in-pipeline half
        self._inflight_entries = 0
        # high-water mark of concurrent in-flight batches: > 1 is the
        # direct overlap evidence (batch N+1 staged/launched while batch
        # N was still in the pipeline) bench.py --steady gates on
        self._inflight_peak = 0
        self._inflight_lock = threading.Lock()
        self._stall_s = {"collect": 0.0, "dispatch": 0.0, "decode": 0.0}
        super().__init__(
            fn=None, max_batch=max_batch, window_s=window_s,
            metrics_path=metrics_path, replica=replica,
            dispatch_seam=dispatch_seam,
        )

    def _alive(self) -> bool:
        """During a drain the collector (and then the dispatcher) exit as
        soon as they forward the sentinel — their remaining work is already
        in the downstream queues — so a waiter's liveness poll must not
        read those exits as 'batcher dead' while the decoder is still
        delivering results. Before stop(), all three stages must live."""
        if self._stopped:
            return self._decoder.is_alive()
        return all(t.is_alive() for t in self._threads)

    def _start_workers(self) -> None:
        # fresh hand-off queues per worker generation: after a revive() a
        # superseded (possibly wedged) stage thread still holds references
        # to ITS generation's queues, so it can never consume — or block
        # on — the new stages' work. Stage threads receive their epoch,
        # queues, and downstream consumer as bound arguments for the same
        # reason.
        for stage in ("collect", "dispatch", "decode"):
            self.heartbeats.setdefault(stage, Heartbeat())
        # the standing place before the dispatch thread (class docstring):
        # one claimed batch, so the hand-off queue holds one. Per
        # generation like the queues — a superseded stage can neither
        # free nor hold the fresh generation's place.
        self._dispatch_q = _queue.Queue(maxsize=1)
        self._decode_q = _queue.Queue(maxsize=self.depth)
        self._place = _Place()
        epoch = self._epoch
        self._decoder = threading.Thread(
            target=self._run_decode, name="pipe-decode", daemon=True,
            args=(epoch, self._decode_q),
        )
        self._dispatcher = threading.Thread(
            target=self._run_dispatch, name="pipe-dispatch", daemon=True,
            args=(
                epoch, self._dispatch_q, self._decode_q, self._decoder,
                self._place,
            ),
        )
        self._thread = threading.Thread(
            target=self._run_collect, name="pipe-collect", daemon=True,
            args=(epoch, self._dispatch_q, self._dispatcher, self._place),
        )
        self._threads = [self._thread, self._dispatcher, self._decoder]
        for t in self._threads:
            t.start()

    def revive(self, force: bool = False) -> bool:
        """Restart the pipeline after a stage death (or, forced, a wedge):
        supersede the old worker generation, SHED every batch sitting in
        the old hand-off queues (their slots fail fast with a restart
        error — the callers' serving paths answer the bounded degraded
        response), and bring up fresh stages with fresh queues. Batches
        held inside a wedged stage call are not reachable; their waiters'
        deadlines bound the damage."""
        with self._cv:
            if self._stopped:
                return False
            dead = [t for t in self._threads if not t.is_alive()]
            if not dead and not force:
                return False
            self._epoch += 1
            old_threads = list(self._threads)
            old_qs = [self._dispatch_q, self._decode_q]
            self._cv.notify_all()
            # a superseded collector waiting for the place wakes and exits
            self._place.free()
        # wake + retire the surviving old stages: a sentinel unblocks a
        # blocked get, and the epoch check exits the loop
        shed = self._shed_queues(old_qs)
        for q in old_qs:
            try:
                q.put_nowait(_SENTINEL)
            except _queue.Full:
                pass
        for t in old_threads:
            if t.is_alive():
                t.join(timeout=0.5)
        # second pass: anything a still-live old stage pushed between the
        # first drain and its exit
        shed += self._shed_queues(old_qs)
        with self._inflight_lock:
            self._inflight = 0
            self._inflight_entries = 0
        with self._cv:
            if self._stopped:
                return False
            self._start_workers()
        log.warning(
            "pipeline revived: %d dead stage(s)%s, %d queued batch(es) shed",
            len(dead),
            ", forced" if force else "",
            shed,
        )
        return True

    def _shed_superseded(self, item) -> None:
        """A superseded stage pulled ``item`` off its old queue in the
        window between revive()'s drain passes: shed it like the drain
        would have."""
        if item is not None and item is not _SENTINEL:
            self._fail_batch(
                item[0],
                RuntimeError("pipeline stage restarted; batch shed"),
            )

    def _shed_queues(self, qs) -> int:
        """Fail every batch queued in ``qs`` (revive shed path)."""
        shed = 0
        for q in qs:
            while True:
                try:
                    item = q.get_nowait()
                except _queue.Empty:
                    break
                if item is _SENTINEL:
                    continue
                self._fail_batch(
                    item[0],
                    RuntimeError("pipeline stage restarted; batch shed"),
                )
                shed += 1
        return shed

    def debug_stats(self) -> dict:
        with self._cv:
            q = len(self._queue)
            mix = dict(self._protocol_mix)
        return {
            "mode": "pipelined",
            "protocol_mix": mix,
            "queue": q,
            "max_batch": self.max_batch,
            "window_us": round(self.window_s * 1e6, 1),
            "depth": self.depth,
            "dispatch_queue": self._dispatch_q.qsize(),
            "decode_queue": self._decode_q.qsize(),
            "batches_total": self._batches_total,
            "inflight": self._inflight,
            "inflight_peak": self._inflight_peak,
            "stall_seconds": {
                k: round(v, 6) for k, v in self._stall_s.items()
            },
        }

    # ------------------------------------------------------------- plumbing

    def _linger_window_s(self) -> float:
        """Device-side accumulation: while batches are already in flight
        the collector claims immediately — requests that arrived during
        the device's evaluation of batch N ARE the accumulated batch, so
        an extra host linger only adds latency without adding rows. So
        does a single entry at an idle pipeline: a request that came
        alone has nobody to wait for. Two or more entries waiting with
        nothing in flight are a burst that has begun, and its first
        claim keeps the forming window. Called under ``_cv``."""
        if self._inflight > 0 or len(self._queue) < 2:
            return 0.0
        return self.window_s

    def _inflight_add(self, n: int, entries: int = 0) -> None:
        with self._inflight_lock:
            self._inflight += n
            self._inflight_entries += entries
            if self._inflight > self._inflight_peak:
                self._inflight_peak = self._inflight

    def backlog(self) -> int:
        """Submitted-but-unanswered entries across the whole batcher:
        queued PLUS claimed into the pipeline stages. The adaptive batch
        tuner's demand signal (cedar_tpu/load/tuner.py). Since the late
        claim a backlog waits in the submit queue, so queue_fill() (the
        router's pre-claim load signal) sees most of it; the sum here is
        what it was."""
        with self._inflight_lock:
            entries = self._inflight_entries
        return self.queue_fill() + entries

    def _encode_timed(self, items, times: Optional[_StageTimes]):
        """pipeline_encode with the batch's encode window stamped — the
        stage traces and histograms read these (the encode itself is
        unchanged)."""
        if times is None:
            return self.stages.pipeline_encode(items)
        with batch_stage(times, "encode", len(items)):
            return self.stages.pipeline_encode(items)

    def _stall(self, stage: str, seconds: float) -> None:
        if seconds <= 0:
            return
        self._stall_s[stage] += seconds
        _record_stall(self.metrics_path, stage, seconds)

    def _put(self, q: _queue.Queue, item, consumer: threading.Thread) -> bool:
        """Bounded put that can never wedge on a dead consumer thread: a
        stage that crashed outside its per-batch try (should not happen,
        but a wedged pipeline strands every submitter) turns the put into
        a False return and the batch fails fast instead."""
        while True:
            try:
                q.put(item, timeout=0.5)
                return True
            except _queue.Full:
                if not consumer.is_alive():
                    return False

    # --------------------------------------------------------------- stages

    def _run_collect(self, epoch, dispatch_q, dispatcher, place) -> None:
        try:
            self._collect_loop(epoch, dispatch_q, dispatcher, place)
        except BaseException:  # noqa: BLE001 — visibility, then unwind
            _record_worker_death("pipeline.collect", self.replica)
            raise

    def _take_place(self, epoch, place, dispatcher) -> Optional[float]:
        """Wait until the standing place before the dispatch thread is
        free and take it. Returns when the wait began, or 0.0 where the
        place was free at once; None when this generation was superseded
        meanwhile. A dead dispatch thread never frees the place: the wait
        ends and the claimed batch fails fast in _put, as it did before
        the late claim."""
        since = 0.0
        if not place.wait(0):
            since = time.monotonic()
            while not place.wait(0.5):
                if self._epoch != epoch:
                    return None
                if not dispatcher.is_alive():
                    break
        place.take()
        return since

    def _collect_loop(self, epoch, dispatch_q, dispatcher, place) -> None:
        hb = self.heartbeats["collect"]
        while True:
            hb.idle()
            since = self._take_place(epoch, place, dispatcher)
            if since is None:
                break
            batch = self._form_batch(epoch)
            if batch is None:
                break
            if not batch:
                place.free()  # nothing claimed: nothing stands in it
                continue
            # held: the collector had to wait for the place and work was
            # in the queue before it came free (the batch's oldest member
            # waited for the place, not for the window); the time so held
            # is this stage's stall
            times = batch[0][1].times
            held = 0.0
            if since:
                held = place.freed_at - max(since, times.first_enq)
            _record_claim(self.metrics_path, held > 0, times.lingered)
            self._stall("collect", held)
            # chaos kill seams OUTSIDE the per-batch containment: unwind
            # this stage like a real crash would
            chaos_fire("pipeline.collect")
            if self._dispatch_seam is not None:
                chaos_fire(self._dispatch_seam, self.replica)
            # busy across the encode: a --max-batch batch encodes in well
            # under a second (tens of µs a row), the wedge budget is 10 s
            hb.busy()
            self._batches_total += 1
            self._inflight_add(1, len(batch))
            try:
                ctx = self._encode_timed([it for it, _ in batch], times)
            except BaseException as e:  # noqa: BLE001 — per-batch isolation
                place.free()
                self._inflight_add(-1, -len(batch))
                self._fail_batch(batch, e)
                continue
            if self._epoch != epoch:
                # superseded during the encode (a forced revive): the old
                # hand-off queue has no reader left, so fail fast here
                self._shed_superseded((batch, ctx))
                return
            if not self._put(dispatch_q, (batch, ctx), dispatcher):
                self._inflight_add(-1, -len(batch))
                self._fail_batch(
                    batch, RuntimeError("pipeline dispatch stage died")
                )
        if self._epoch == epoch:
            self._put(dispatch_q, _SENTINEL, dispatcher)

    def _run_dispatch(
        self, epoch, dispatch_q, decode_q, decoder, place
    ) -> None:
        try:
            self._dispatch_loop(epoch, dispatch_q, decode_q, decoder, place)
        except BaseException:  # noqa: BLE001 — visibility, then unwind
            _record_worker_death("pipeline.dispatch", self.replica)
            raise

    def _dispatch_loop(
        self, epoch, dispatch_q, decode_q, decoder, place
    ) -> None:
        hb = self.heartbeats["dispatch"]
        while True:
            hb.idle()
            t0 = time.monotonic()
            item = dispatch_q.get()
            if self._epoch != epoch:
                # superseded by revive(): a fresh stage owns the work — but
                # a real batch this get RACED away from revive's queue
                # drain must still fail fast, not strand its waiters until
                # their deadlines
                place.free()
                self._shed_superseded(item)
                return
            # chaos seam after the queue get, outside any per-batch try
            chaos_fire("pipeline.dispatch_q")
            hb.busy()
            if item is _SENTINEL:
                place.free()
                self._put(decode_q, _SENTINEL, decoder)
                return
            batch, ctx = item
            times = batch[0][1].times
            # time this thread waited for the standing batch's encode (from
            # its claim, or from this thread's coming back for work if that
            # was later): since the late claim one encode a batch by design
            self._stall("dispatch", time.monotonic() - max(t0, times.claimed))
            try:
                with batch_stage(times, "dispatch", len(batch)):
                    ctx = self.stages.pipeline_dispatch(ctx)
            except BaseException as e:  # noqa: BLE001 — per-batch isolation
                place.free()
                self._inflight_add(-1, -len(batch))
                self._fail_batch(batch, e)
                continue
            # launched: the decode stage is handed its batch first, then
            # the place is freed and the collector may claim the next one
            # (class docstring: why not before). A full decode queue keeps
            # the place taken: the backlog waits in the submit queue
            handed = self._put(decode_q, (batch, ctx), decoder)
            place.free()
            if not handed:
                self._inflight_add(-1, -len(batch))
                self._fail_batch(
                    batch, RuntimeError("pipeline decode stage died")
                )

    def _run_decode(self, epoch, decode_q) -> None:
        try:
            self._decode_loop(epoch, decode_q)
        except BaseException:  # noqa: BLE001 — visibility, then unwind
            _record_worker_death("pipeline.decode", self.replica)
            raise

    def _decode_loop(self, epoch, decode_q) -> None:
        hb = self.heartbeats["decode"]
        while True:
            busy = self._inflight > 0
            t0 = time.monotonic()
            hb.idle()
            item = decode_q.get()
            if self._epoch != epoch:
                self._shed_superseded(item)  # see _dispatch_loop
                return
            # chaos seam after the queue get, outside any per-batch try
            chaos_fire("pipeline.decode_q")
            hb.busy()
            if busy:
                # time waiting for launched work WHILE batches were in
                # flight = pipeline starvation (encode/dispatch cannot keep
                # the decoder busy); an idle server records nothing
                self._stall("decode", time.monotonic() - t0)
            if item is _SENTINEL:
                return
            batch, ctx = item
            times = batch[0][1].times
            # end stamp + histogram BEFORE completing any slot (see the
            # serial loop): a woken waiter reads these stamps immediately
            try:
                try:
                    with batch_stage(times, "decode", len(batch)):
                        results = self.stages.pipeline_decode(ctx)
                finally:
                    self._record_batch_stages(times)
                self._complete_batch(batch, results)
            except BaseException as e:  # noqa: BLE001 — per-batch isolation
                self._fail_batch(batch, e)
            finally:
                self._inflight_add(-1, -len(batch))

    def stop(self, drain_timeout_s: float = 5.0) -> None:
        """Drain the whole pipeline: the collector pushes every remaining
        queued item through encode/dispatch/decode (trailed by a sentinel
        each stage forwards), so every accepted submitter gets an answer
        before the workers exit."""
        super().stop(drain_timeout_s=drain_timeout_s)
