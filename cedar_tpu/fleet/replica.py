"""One member of the engine fleet: an engine + fast path + batcher +
breaker + device recovery, with the lifecycle/health surface the router
scores.

A replica is the unit of failure the fleet exists to survive: its batcher
worker threads can die (chaos ``fleet.replica_dispatch`` kill, a
C-extension crash), its device plane can wedge (per-replica breaker opens),
or its engine can need a rebuild (per-replica ``DeviceRecovery``). Any of
those takes the replica OUT of the routing set — capacity degrades, the
webhook surface does not — and the supervisor's revive (or the recovery's
rebuild) puts it back.

Lifecycle states:

  ``active``    in the routing set when healthy
  ``draining``  operator drain: no new routes; queued work still answers
  ``retired``   drained and stopped; a retired replica never serves again
                (build a fresh one instead — compiled sets adopt for free)
"""

from __future__ import annotations

import logging
import threading

from ..engine.batcher import MicroBatcher, PipelinedBatcher

log = logging.getLogger(__name__)

ACTIVE = "active"
DRAINING = "draining"
RETIRED = "retired"

# cedar_fleet_replica_state gauge encoding (server/metrics.py)
STATE_ACTIVE = 0
STATE_DEGRADED = 1
STATE_REBUILDING = 2
STATE_DRAINING = 3
STATE_DEAD = 4

# the chaos seam every replica batcher's worker loop fires after claiming
# a batch: a kill rule here unwinds exactly one replica's worker —
# replica loss, the game day this package exists for (docs/fleet.md)
REPLICA_DISPATCH_SEAM = "fleet.replica_dispatch"


class EngineReplica:
    """See module docstring. ``fastpath`` is the replica's own
    SARFastPath-like object (its ``available`` gate and breaker are THIS
    replica's health signals); ``batcher`` may be injected for tests,
    otherwise one is built over the fast path with the replica identity
    threaded through for death attribution and the chaos seam."""

    def __init__(
        self,
        index: int,
        engine,
        fastpath,
        breaker=None,
        recovery=None,
        max_batch: int = 8192,
        window_s: float = 0.0002,
        pipeline_depth: int = 2,
        fleet_name: str = "authorization",
        batcher=None,
    ):
        self.index = int(index)
        self.name = f"r{self.index}"
        self.engine = engine
        self.fastpath = fastpath
        self.breaker = breaker
        self.recovery = recovery
        self.fleet_name = fleet_name
        if batcher is None:
            if pipeline_depth > 0:
                batcher = PipelinedBatcher(
                    fastpath,
                    max_batch=max_batch,
                    window_s=window_s,
                    depth=pipeline_depth,
                    metrics_path=fleet_name,
                    replica=self.name,
                    dispatch_seam=REPLICA_DISPATCH_SEAM,
                )
            else:
                batcher = MicroBatcher(
                    fastpath.authorize_raw,
                    max_batch=max_batch,
                    window_s=window_s,
                    metrics_path=fleet_name,
                    replica=self.name,
                    dispatch_seam=REPLICA_DISPATCH_SEAM,
                )
        # faster dead-worker detection than the standalone default (0.5s):
        # a waiter stranded by a replica kill must notice and spill over
        # to a healthy replica well inside its deadline budget, or the
        # router's availability win turns into a timeout
        batcher.LIVENESS_POLL_S = 0.05
        self.batcher = batcher
        self.state = ACTIVE
        self._inflight = 0
        self._inflight_lock = threading.Lock()

    # ------------------------------------------------------------- routing

    @property
    def inflight(self) -> int:
        return self._inflight

    def begin_request(self) -> None:
        with self._inflight_lock:
            self._inflight += 1

    def end_request(self) -> None:
        with self._inflight_lock:
            self._inflight -= 1

    def lone(self) -> bool:
        """True when this request is alone on the replica (hedge
        eligibility): duplicated device work is free capacity, not stolen
        throughput."""
        return self._inflight <= 1 and self.batcher.queue_fill() == 0

    def alive(self) -> bool:
        try:
            return self.batcher._alive()
        except Exception:  # noqa: BLE001 — a sick probe reads dead
            return False

    def rebuilding(self) -> bool:
        rec = self.recovery
        return bool(rec is not None and rec.rebuilding)

    def admits(self) -> bool:
        """True when the router may hand this replica new work: serving
        state, live workers, no rebuild in flight, a usable fast path, and
        a breaker that admits. A breaker-OPEN replica is excluded rather
        than queued behind — its batcher worker may be wedged inside the
        sick device call, exactly the single-engine bypass rationale
        (server/http.py _breaker_admits)."""
        if self.state != ACTIVE:
            return False
        if not self.alive():
            return False
        if self.rebuilding():
            return False
        try:
            if not getattr(self.fastpath, "available", True):
                return False
        except Exception:  # noqa: BLE001 — degrade: route elsewhere
            return False
        breaker = self.breaker
        return breaker is None or breaker.allow()

    # -------------------------------------------------------------- status

    def state_code(self) -> int:
        """cedar_fleet_replica_state gauge encoding."""
        if self.state == RETIRED or not self.alive():
            return STATE_DEAD
        if self.state == DRAINING:
            return STATE_DRAINING
        if self.rebuilding():
            return STATE_REBUILDING
        if not self.admits():
            return STATE_DEGRADED
        return STATE_ACTIVE

    def health(self) -> dict:
        """The /debug/fleet per-replica document."""
        doc = {
            "name": self.name,
            "state": self.state,
            "alive": self.alive(),
            "admits": self.admits(),
            "rebuilding": self.rebuilding(),
            "inflight": self._inflight,
            "queue": self.batcher.queue_fill(),
            "state_code": self.state_code(),
        }
        if self.breaker is not None:
            doc["breaker"] = self.breaker.state
        engine = self.engine
        if engine is not None:
            doc["warm_ready"] = engine.warm_ready()
            doc["load_generation"] = engine.load_generation
        return doc

    def publish_state(self) -> None:
        try:
            from ..server.metrics import set_fleet_replica_state

            set_fleet_replica_state(
                self.fleet_name, self.name, self.state_code()
            )
        except Exception:  # noqa: BLE001 — metrics must never break routing
            pass

    # ----------------------------------------------------------- lifecycle

    def drain(self) -> bool:
        """Stop routing new work here; queued work still answers."""
        if self.state != ACTIVE:
            return False
        self.state = DRAINING
        self.publish_state()
        log.warning("fleet replica %s draining", self.name)
        return True

    def retire(self, drain_timeout_s: float = 5.0) -> bool:
        """Drain + stop the batcher. Terminal: revive() will not restart a
        retired replica (its batcher refuses work once stopped)."""
        if self.state == RETIRED:
            return False
        self.state = RETIRED
        self.publish_state()
        self.batcher.stop(drain_timeout_s=drain_timeout_s)
        log.warning("fleet replica %s retired", self.name)
        return True

    def revive(self, force: bool = False) -> bool:
        """Supervisor restart hook: restart dead (or, forced, wedged)
        batcher workers and return the replica to the routing set."""
        if self.state == RETIRED:
            return False
        revived = self.batcher.revive(force=force)
        undrained = self.state == DRAINING
        if undrained:
            self.state = ACTIVE
        self.publish_state()
        return revived or undrained

    def stop(self, drain_timeout_s: float = 5.0) -> None:
        self.batcher.stop(drain_timeout_s=drain_timeout_s)


__all__ = [
    "ACTIVE",
    "DRAINING",
    "RETIRED",
    "EngineReplica",
    "REPLICA_DISPATCH_SEAM",
]
