"""The lazily-compiled device explain plane.

One ``ExplainPlane`` wraps a ``TPUPolicyEngine`` and answers explain
requests with the standalone bits kernel (``match_bits_arrays``, fixed
``_BITS_CHUNK`` shape): its per-rule satisfaction bitset
is a superset of every other attribution payload — complete per-group
policy sets AND the winning rule — so one launch carries the whole
explanation. The ``want_full`` first/last plane (which serves
fallback-set evaluation) is deliberately NOT launched here: everything
it reports derives from the bitset, and a second dispatch would only
double the first-explain compile cost.

STRICTLY PAY-FOR-USE: nothing here compiles until the first explain
request per (engine, compiled set). The serving warm ladder pre-compiles
the bits shape for its own flagged-row fetches, so the first
``?explain=1`` pays at most one fresh trace — and the non-explain path
pays nothing, ever (trace-counter-asserted by tests/test_explain.py).
Fresh explain-plane traces are counted on
``cedar_explain_compiles_total``.
"""

from __future__ import annotations

import logging
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)


class ExplainPlane:
    """Per-engine explain dispatch with lazy compile accounting."""

    def __init__(self, engine):
        self.engine = engine

    def explain_row(
        self, codes_arr: np.ndarray, extras_arr: np.ndarray, cs=None
    ) -> np.ndarray:
        """Rule-satisfaction bitsets [n, R/32] uint32 for pre-encoded
        rows — one bits fetch through the engine's existing entry point
        (bucketed to the fixed bits-chunk shape, snapshot-pinned via
        ``cs``)."""
        from ..ops.match import kernel_trace_count

        engine = self.engine
        cs = cs or engine._compiled
        if cs is None:
            raise RuntimeError("ExplainPlane: no policy set loaded")
        tc0 = kernel_trace_count()
        bits = engine.match_bits_arrays(codes_arr, extras_arr, cs=cs)
        traces = kernel_trace_count() - tc0
        if traces:
            # first use per (engine, compiled set) is exactly when fresh
            # traces appear; a warm jit cache (same-bucket reload, or the
            # serving ladder's own bits warm-up) makes the "lazy compile"
            # genuinely free and counts nothing
            try:
                from ..server.metrics import record_explain_compiles

                record_explain_compiles(traces)
            except Exception:  # noqa: BLE001 — metrics never break explain
                pass
        return bits


def encode_single(engine, cs, entities, request) -> Optional[tuple]:
    """One request through the Python encoder into the engine's bucketed
    (codes [1, S], extras [1, E]) arrays — the explain plane's encode
    (exact semantics: hard literals host-evaluated, same activation
    table as the serving engine path)."""
    from ..compiler.table import encode_request_codes

    packed = cs.packed
    encoded = encode_request_codes(packed.plan, packed.table, entities, request)
    return engine._encode_batch_arrays(cs, [encoded], 1)
