"""What the admission path says about itself (docs/observability.md): the
handler's timer ``cedar_admission_request_duration_seconds``, the body sizes
``cedar_request_body_bytes``, the encoder's extras ``cedar_encode_extras``,
and the span attributes ``body_bytes`` / ``operation`` / ``kind`` (root) and
``extras_max`` (``batch.encode``). Served over loopback HTTP from a CPU
engine behind the native fast path, as tests/test_obs_phases.py serves.
"""

import json
import time

import numpy as np
import pytest

from cedar_tpu.engine.evaluator import TPUPolicyEngine
from cedar_tpu.engine.fastpath import AdmissionFastPath
from cedar_tpu.entities.admission import AdmissionRequest
from cedar_tpu.lang import PolicySet
from cedar_tpu.compiler.table import ANCESTOR_SLOTS
from cedar_tpu.native import F_EXTRAS_OVERFLOW, F_OK, NativeEncoder, native_available
from cedar_tpu.server import metrics
from cedar_tpu.server.admission import (
    ALLOW_ALL_ADMISSION_POLICY_SOURCE,
    CedarAdmissionHandler,
    allow_all_admission_policy_store,
)
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores
from test_obs_phases import TIMER_PHASES, Served, phase_totals, post, review, sar

pytestmark = pytest.mark.skipif(
    not native_available(), reason="no C++ toolchain for the native encoder"
)


@pytest.fixture()
def served():
    s = Served()
    yield s
    s.stop()


def histogram(h):
    """{label values: (sum, count)} of a Histogram."""
    with h._lock:
        return {tuple(v for _, v in key): (h._sums[key], h._totals[key])
                for key in h._totals}


def delta(after: dict, before: dict, key) -> tuple:
    a, b = after.get(key, (0.0, 0)), before.get(key, (0.0, 0))
    return a[0] - b[0], a[1] - b[1]


def wait_for(served, n):
    deadline = time.monotonic() + 5
    while len(served.records) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(served.records) == n


def prod(i):
    """A review the one admission policy of test_obs_phases denies."""
    doc = review(i)
    doc["request"]["object"]["metadata"]["labels"] = {"env": "prod"}
    return doc


def test_the_admission_timer_counts_every_review_between_the_phases_two_ends(served):
    before, phases_before = histogram(metrics.admission_request_latency), phase_totals("admission")
    conn = served.connection()
    n = 14
    for i in range(n):
        _, body = post(conn, "/v1/admit", prod(i) if i % 3 == 0 else review(i))
        assert body["response"]["allowed"] is (i % 3 != 0)
    conn.close()
    wait_for(served, n)
    after = histogram(metrics.admission_request_latency)
    denied, allowed = delta(after, before, ("denied",)), delta(after, before, ("allowed",))
    # its count is the reviews answered, by decision
    assert (denied[1], allowed[1]) == (5, 9)
    assert delta(after, before, ("error",)) == (0.0, 0)
    # its two ends are the stamps the phases parse … respond run between
    stamped = sum(r.t_stop - r.t_start for r in served.records)
    assert denied[0] + allowed[0] == pytest.approx(stamped, abs=1e-9)
    now = phase_totals("admission")
    inside = sum(now[p] - phases_before.get(p, 0.0) for p in TIMER_PHASES)
    assert inside == pytest.approx(stamped, abs=1e-9)


def test_a_direct_call_and_an_unparseable_body_are_timed_too(served):
    before = histogram(metrics.admission_request_latency)
    out = served.server.handle_admit(b"{not json")
    assert "failed parsing body" in out["response"]["status"]["message"]
    served.server.handle_admit(json.dumps(review(1)).encode())
    after = histogram(metrics.admission_request_latency)
    assert delta(after, before, ("error",))[1] == 1
    assert delta(after, before, ("allowed",))[1] == 1


def test_body_bytes_are_what_was_posted_on_each_path(served):
    before = metrics.request_body_bytes.totals()
    conn = served.connection()
    posted = {"admission": [], "authorization": []}
    for i in range(6):
        for path, url, doc in (("admission", "/v1/admit", prod(i)),
                               ("authorization", "/v1/authorize", sar(i))):
            posted[path].append(len(json.dumps(doc).encode()))
            post(conn, url, doc)
    conn.close()
    wait_for(served, 12)
    after = metrics.request_body_bytes.totals()
    for path, sizes in posted.items():
        assert delta(after, before, (path,)) == (sum(sizes), len(sizes))
    assert sum(posted["admission"]) > sum(posted["authorization"])
    text = metrics.REGISTRY.expose()
    assert "# TYPE cedar_request_body_bytes summary" in text
    assert 'cedar_request_body_bytes_sum{path="admission"}' in text
    assert 'cedar_request_body_bytes_count{path="authorization"}' in text


def test_the_root_span_names_the_review_and_encode_its_widest_row(served):
    conn = served.connection()
    doc = prod(7)
    resp, _ = post(conn, "/v1/admit", doc)
    conn.close()
    wait_for(served, 1)
    trace = served.tracer.get(resp.headers["X-Cedar-Trace-Id"])
    root = trace["spans"][0]
    assert root["attrs"]["body_bytes"] == len(json.dumps(doc).encode())
    assert root["attrs"]["operation"] == "CREATE"
    assert root["attrs"]["kind"] == "ConfigMap"
    encode = next(sp for sp in trace["spans"] if sp["name"] == "batch.encode")
    assert encode["attrs"]["extras_max"] >= 0


def test_a_head_without_the_envelopes_names_sets_no_name():
    from cedar_tpu.obs.trace import Trace
    from cedar_tpu.server.http import _set_admit_attrs

    root = Trace("admission").root
    _set_admit_attrs(root, b'{"request": {"object": {"kind": "Pod", "operation": 3}}}')
    assert root.attrs == {"body_bytes": 56}
    # an escaped key inside a string is not the envelope's key
    root = Trace("admission").root
    _set_admit_attrs(root, rb'{"request": {"name": "\"operation\":\"DELETE\""}}')
    assert set(root.attrs) == {"body_bytes"}


# ------------------------------------------------- the encoder's extras

# one group more than a native row can carry: the eight ancestor slots, then
# the extras list up to the encoder's cap
GROUPS = ANCESTOR_SLOTS["principal"] + NativeEncoder.DEFAULT_EXTRAS_CAP + 1
WIDE = "\n".join(
    f'forbid (principal in k8s::Group::"g{i}", action == k8s::admission::Action::"create", '
    'resource is core::v1::ConfigMap) when { resource.metadata has labels && '
    f'resource.metadata.labels.contains({{key: "tier", value: "t{i}"}}) }};'
    for i in range(GROUPS)
)


def wide_review(i, groups, tier="t0"):
    doc = review(i)
    doc["request"]["userInfo"]["groups"] = [f"g{k}" for k in range(groups)]
    doc["request"]["object"]["metadata"]["labels"] = {"tier": tier}
    return json.dumps(doc).encode()


@pytest.fixture(scope="module")
def wide():
    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(WIDE, "wide"),
                 PolicySet.from_source(ALLOW_ALL_ADMISSION_POLICY_SOURCE, "aa")], warm="off")
    stores = TieredPolicyStores([MemoryStore.from_source("wide", WIDE),
                                 allow_all_admission_policy_store()])
    handler = CedarAdmissionHandler(stores, evaluate=engine.evaluate,
                                    evaluate_batch=engine.evaluate_batch)
    return AdmissionFastPath(engine, handler), CedarAdmissionHandler(stores)


def routing(row_class):
    with metrics.row_routing_total._lock:
        return metrics.row_routing_total._values.get(
            (("path", "admission"), ("row_class", row_class)), 0.0)


def test_encode_extras_is_the_encoders_counts(wide):
    fast, _ = wide
    bodies = [wide_review(i, groups) for i, groups in enumerate((0, 1, 5, 12, 31))]
    enc = fast._current_snapshot().encoder
    _codes, _extras, counts, flags, _uids = enc.encode_adm_batch(bodies)
    assert (flags == F_OK).all() and counts.max() > counts.min()
    before = metrics.encode_extras.totals()
    fast.handle_raw(bodies)
    after = metrics.encode_extras.totals()
    assert delta(after, before, ("admission",)) == (int(counts.sum()), len(bodies))


def test_a_row_past_the_cap_reads_encoder_fallback_and_answers_as_the_python_path(wide):
    fast, interpreter = wide
    bodies = [wide_review(0, GROUPS), wide_review(1, GROUPS, tier="t39"), wide_review(2, 3)]
    enc = fast._current_snapshot().encoder
    counts, flags = enc.encode_adm_batch(bodies)[2:4]
    assert flags.tolist() == [F_EXTRAS_OVERFLOW, F_EXTRAS_OVERFLOW, F_OK]
    before, fell_back = metrics.encode_extras.totals(), routing("encoder_fallback")
    got = [r.to_admission_review() for r in fast.handle_raw(bodies)]
    want = [interpreter.handle(AdmissionRequest.from_admission_review(json.loads(b)))
            .to_admission_review() for b in bodies]
    assert got == want
    assert [g["response"]["allowed"] for g in got] == [False, False, False]
    assert routing("encoder_fallback") - fell_back == 2
    # the rows past the cap wrote no count: the family holds the encoded row
    after = metrics.encode_extras.totals()
    assert delta(after, before, ("admission",)) == (int(counts[2]), 1)
    assert int(np.count_nonzero(flags == F_OK)) == 1
