"""A principal's groups past the eight ancestor slots (docs/observability.md,
docs/Limitations.md): the native encoder against
``compiler/table.py encode_request_codes`` at 8, 9, 40, 41 and 200
policy-known groups (the same activations), the row limit that remains (the
slots and an extras list of ``NativeEncoder.DEFAULT_EXTRAS_CAP``), the
widths a batch is padded to, and what the encode stage says of it all:
``cedar_encode_ancestors_total{path,where}`` and the ``groups`` /
``known_groups`` attributes on ``batch.encode``.
"""

import json
import time

import numpy as np
import pytest

from cedar_tpu.compiler.table import ANCESTOR_SLOTS, encode_request_codes
from cedar_tpu.engine.evaluator import EXTRAS_WIDTHS, TPUPolicyEngine
from cedar_tpu.engine.fastpath import SARFastPath
from cedar_tpu.lang import PolicySet
from cedar_tpu.native import (
    ANC_WHERE,
    F_EXTRAS_OVERFLOW,
    F_OK,
    NativeEncoder,
    native_available,
)
from cedar_tpu.server import metrics
from cedar_tpu.server.authorizer import CedarWebhookAuthorizer, record_to_cedar_resource
from cedar_tpu.server.http import get_authorizer_attributes
from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

pytestmark = pytest.mark.skipif(
    not native_available(), reason="no C++ toolchain for the native encoder"
)

SLOTS = ANCESTOR_SLOTS["principal"]
CAP = NativeEncoder.DEFAULT_EXTRAS_CAP
KNOWN = SLOTS + CAP + 6  # more groups than a native row can carry
# one permit a group, each for a resource of its own: which groups a
# principal is in decides which policies determine the answer
POLICIES = "\n".join(
    f'permit (principal in k8s::Group::"team-{i}", action == k8s::Action::"get", '
    f'resource is k8s::Resource) when {{ resource.resource == "r{i % 7}" }};'
    for i in range(KNOWN)
)


def review(known: int, unknown: int = 0, resource: str = "r3", name: str = "x") -> dict:
    """A SubjectAccessReview whose user is in ``known`` groups the policies
    name and ``unknown`` they do not, interleaved."""
    groups = [f"team-{i}" for i in range(known)]
    for j in range(unknown):
        groups.insert((j * 3) % (len(groups) + 1), f"idp:{j:08x}")
    return {
        "apiVersion": "authorization.k8s.io/v1",
        "kind": "SubjectAccessReview",
        "spec": {"user": "pat", "uid": "pat", "groups": groups,
                 "resourceAttributes": {"verb": "get", "version": "v1", "group": "",
                                        "resource": resource, "namespace": "ns", "name": name}},
    }


@pytest.fixture(scope="module")
def served():
    engine = TPUPolicyEngine()
    engine.load([PolicySet.from_source(POLICIES, "teams")], warm="off")
    stores = TieredPolicyStores([MemoryStore.from_source("teams", POLICIES)])
    fast = SARFastPath(engine, CedarWebhookAuthorizer(stores, evaluate=engine.evaluate))
    return engine, fast, CedarWebhookAuthorizer(stores)


def activations(packed, codes, extras) -> set:
    rows = packed.table.rows
    lit = set(int(e) for e in extras)
    for c in codes:
        lit |= set(np.nonzero(rows[int(c)])[0].tolist())
    return lit


def test_the_cap_is_the_ladders_widest_width():
    assert EXTRAS_WIDTHS[-1] == CAP
    assert list(EXTRAS_WIDTHS) == sorted(EXTRAS_WIDTHS) and EXTRAS_WIDTHS[0] == 1


@pytest.mark.parametrize("known", [8, 9, 40, 41, 200])
def test_native_and_python_encoders_activate_the_same_literals(served, known):
    engine, _, _ = served
    packed = engine._compiled.packed
    enc = NativeEncoder.create(packed)
    sar = review(known, unknown=17)
    anc = np.empty((1, len(ANC_WHERE)), np.int32)
    codes = np.empty((1, enc.n_slots), np.int32)
    extras = np.empty((1, CAP), np.int32)
    counts, flags = np.empty((1,), np.int32), np.empty((1,), np.uint8)
    enc.encode_batch_into([json.dumps(sar).encode()], codes, extras, counts, flags, anc=anc)
    assert flags[0] == F_OK
    # one `principal in` literal a group: what is past the slots is extras
    assert counts[0] == max(0, known - SLOTS)
    assert dict(zip(ANC_WHERE, anc[0].tolist())) == {
        "slot": min(known, SLOTS), "extras": max(0, known - SLOTS), "unknown": 17}
    em, req = record_to_cedar_resource(get_authorizer_attributes(sar))
    py_codes, py_extras = encode_request_codes(packed.plan, packed.table, em, req)
    assert activations(packed, codes[0], extras[0, :counts[0]]) == activations(
        packed, py_codes, py_extras)
    # and each of the groups' literals is among them
    assert len(activations(packed, codes[0], extras[0, :counts[0]])) >= known


@pytest.mark.parametrize("known", [1, 8, 9, 40, 41, 200, SLOTS + CAP])
def test_a_principal_within_the_limit_is_a_clean_native_row_and_exact(served, known):
    _, fast, interpreter = served
    before = routing()
    bodies = [json.dumps(review(known, unknown=5, resource=f"r{r}")).encode() for r in range(7)]
    got = fast.authorize_raw(bodies)
    for body, (decision, reason, error) in zip(bodies, got):
        want = interpreter.authorize(get_authorizer_attributes(json.loads(body)))
        assert (decision, reason) == want and not error
        # every determining policy is named: each group whose policy is
        # for this resource
        r = json.loads(body)["spec"]["resourceAttributes"]["resource"]
        named = {x["policy"] for x in json.loads(reason)["reasons"]} if reason else set()
        assert len(named) == len([i for i in range(known) if f"r{i % 7}" == r])
    after = routing()
    assert after["encoder_fallback"] == before["encoder_fallback"]
    assert after["gated"] == before["gated"]
    assert (after["clean_native"] + after["flagged"]
            - before["clean_native"] - before["flagged"]) == len(bodies)


def test_a_principal_past_the_limit_falls_back_and_is_still_exact(served):
    engine, fast, interpreter = served
    enc = NativeEncoder.create(engine._compiled.packed)
    body = json.dumps(review(SLOTS + CAP + 1)).encode()
    assert enc.encode_batch([body])[3].tolist() == [F_EXTRAS_OVERFLOW]
    before = routing()
    [(decision, reason, error)] = fast.authorize_raw([body])
    assert routing()["encoder_fallback"] - before["encoder_fallback"] == 1
    assert (decision, reason) == interpreter.authorize(
        get_authorizer_attributes(json.loads(body)))
    assert not error and len(json.loads(reason)["reasons"]) > 30


@pytest.mark.parametrize("known,width", [(0, 1), (SLOTS, 1), (SLOTS + 1, 1), (SLOTS + 2, 8),
                                         (SLOTS + 8, 8), (SLOTS + 9, 32), (SLOTS + 32, 32),
                                         (SLOTS + 33, 256), (SLOTS + CAP, 256)])
def test_a_batch_is_padded_to_a_width_of_the_ladder(served, known, width):
    _, fast, _ = served
    snap = fast._current_snapshot()
    enc = fast._encode_chunk(snap, [json.dumps(review(known)).encode(),
                                    json.dumps(review(1)).encode()])
    ok_extras, held = enc[4], enc[6]
    assert ok_extras.shape[1] == width and width in EXTRAS_WIDTHS
    fast.engine._staging.release(*held)


def routing() -> dict:
    with metrics.row_routing_total._lock:
        return {c: metrics.row_routing_total._values.get(
            (("path", "authorization"), ("row_class", c)), 0.0)
            for c in ("clean_native", "flagged", "gated", "encoder_fallback", "encoder_gate")}


def ancestors() -> dict:
    with metrics.encode_ancestors_total._lock:
        return {w: metrics.encode_ancestors_total._values.get(
            (("path", "authorization"), ("where", w)), 0.0) for w in ANC_WHERE}


def test_the_counter_says_where_each_group_went(served):
    _, fast, _ = served
    before = ancestors()
    fast.authorize_raw([json.dumps(review(3, unknown=20)).encode(),
                        json.dumps(review(30, unknown=4)).encode(),
                        json.dumps(review(SLOTS + CAP + 1)).encode(),  # falls back: uncounted
                        b"{not json"])
    after = ancestors()
    assert {w: after[w] - before[w] for w in ANC_WHERE} == {
        "slot": 3 + SLOTS, "extras": 30 - SLOTS, "unknown": 24}


def test_the_encode_span_carries_the_widest_rows_groups(monkeypatch):
    import test_obs_phases as phases

    monkeypatch.setattr(phases, "POLICIES", phases.POLICIES + "\n" + POLICIES)
    s = phases.Served()
    try:
        conn = s.connection()
        resp, body = phases.post(conn, "/v1/authorize", review(41, unknown=9))
        conn.close()
        assert body["status"]["allowed"] is True
        deadline = time.monotonic() + 5
        while not s.records and time.monotonic() < deadline:
            time.sleep(0.01)
        trace = s.tracer.get(resp.headers["X-Cedar-Trace-Id"])
        encode = next(sp for sp in trace["spans"] if sp["name"] == "batch.encode")
        assert encode["attrs"] == {"extras_max": 41 - SLOTS, "groups": 50, "known_groups": 41}
    finally:
        s.stop()
