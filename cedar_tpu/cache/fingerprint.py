"""Canonical request fingerprints — the ONE key definition shared by the
decision cache (cache/decision_cache.py), the request recorder
(server/recorder.py), and the replay CLI (cli/replay.py).

Why canonical rather than raw-body hashing: the apiserver serializes SARs
stably in practice, but nothing guarantees it — field order, whitespace, and
redundant members are all wire-legal variation that must not split cache
entries or let a recorded request disagree with the key the live server
cached it under. The fingerprint therefore hashes a canonical JSON rendering
of the PARSED attributes (sorted keys, order-insensitive collections
sorted), not the bytes on the wire.

Determinism is what makes this safe: Cedar evaluation is total and
deterministic (arXiv:2403.04651 §3), so two requests with equal canonical
attributes are guaranteed the same decision against the same policy-set
generation. Anything that can influence a decision MUST be part of the
fingerprint; anything that cannot (the AdmissionReview ``uid`` nonce, JSON
formatting) must not be.

Versioned: ``FINGERPRINT_VERSION`` is folded into every hash so a future
canonicalization change invalidates old keys wholesale instead of silently
colliding with them.
"""

from __future__ import annotations

import hashlib
import json
import threading
from collections import OrderedDict
from typing import Optional, Tuple

FINGERPRINT_VERSION = "1"

# hex digest length kept at 32 chars (128 bits): collision-safe for any
# realistic corpus while halving per-entry key memory vs the full digest
_DIGEST_CHARS = 32


def _hash_canonical(doc: dict) -> str:
    payload = json.dumps(
        doc, sort_keys=True, separators=(",", ":"), ensure_ascii=True
    )
    return hashlib.sha256(
        (FINGERPRINT_VERSION + "\x00" + payload).encode()
    ).hexdigest()[:_DIGEST_CHARS]


def _canonical_user(user) -> dict:
    """UserInfo → canonical dict. Groups and extra values are SETS to the
    evaluator (entity parents / Set<String> attributes), so order is
    normalized away here."""
    return {
        "name": user.name,
        "uid": user.uid,
        "groups": sorted(user.groups),
        "extra": {k: sorted(v) for k, v in sorted((user.extra or {}).items())},
    }


def fingerprint_attributes(attributes) -> str:
    """Canonical fingerprint of an authorization request
    (entities.attributes.Attributes). Label/field selector requirements are
    order-insensitive (the evaluator exposes them as Cedar Sets)."""
    doc = {
        "kind": "sar",
        "user": _canonical_user(attributes.user),
        "verb": attributes.verb,
        "namespace": attributes.namespace,
        "apiGroup": attributes.api_group,
        "apiVersion": attributes.api_version,
        "resource": attributes.resource,
        "subresource": attributes.subresource,
        "name": attributes.name,
        "resourceRequest": attributes.resource_request,
        "path": attributes.path,
        "labelSelector": sorted(
            (r.key, r.operator, sorted(r.values))
            for r in attributes.label_selector
        ),
        "fieldSelector": sorted(
            (r.field, r.operator, r.value) for r in attributes.field_selector
        ),
    }
    if getattr(attributes, "tenant", ""):
        # multi-tenant serving (cedar_tpu/tenancy): two tenants'
        # byte-identical SARs evaluate against different policy slices, so
        # the tenant MUST split the key — cache entries, recordings and
        # audit lines become tenant-scoped. Folded only when present:
        # single-tenant fingerprints stay byte-identical to every
        # previously recorded key.
        doc["tenant"] = attributes.tenant
    if getattr(attributes, "protocol", ""):
        # PDP front end (cedar_tpu/pdp): an ext_authz check or batch tuple
        # is mapped into the SAR attribute shape, so without a protocol tag
        # a mapped request could collide with a genuine SAR's cache /
        # recorder / audit key. Folded only when present: native-webhook
        # fingerprints stay byte-identical (regression-pinned).
        doc["protocol"] = attributes.protocol
    return _hash_canonical(doc)


def fingerprint_admission_request(req) -> str:
    """Canonical fingerprint of an admission request
    (entities.admission.AdmissionRequest).

    The review ``uid`` is deliberately EXCLUDED: it is a per-review nonce
    (fresh on every retry of the same write), and the decision cannot depend
    on it — the only place it reaches evaluation is as the re-ID of the
    oldObject entity, whose attributes are fingerprinted by content below.
    Including it would make every entry single-use."""
    doc = {
        "kind": "admission",
        "operation": req.operation,
        "gvk": (req.kind.group, req.kind.version, req.kind.kind),
        "gvr": (req.resource.group, req.resource.version, req.resource.resource),
        "subResource": req.sub_resource,
        "name": req.name,
        "namespace": req.namespace,
        "user": _canonical_user(req.user_info),
        "dryRun": bool(getattr(req, "dry_run", False)),
        # objects canonicalize through the same sorted-keys dump as the
        # envelope; lists stay ordered (k8s list fields are positional)
        "object": req.object,
        "oldObject": req.old_object,
    }
    if getattr(req, "tenant", ""):
        # tenant-scoped, like fingerprint_attributes above
        doc["tenant"] = req.tenant
    return _hash_canonical(doc)


def fingerprint_body(endpoint: str, body: bytes) -> Optional[str]:
    """Fingerprint a raw webhook POST body. ``endpoint`` is ``authorize``
    or ``admit`` (the /v1/ path tail, also the recorder's filename tag).
    Returns None for bodies that do not parse — the serving paths produce
    their decode-error answer uncached."""
    # a TenantBody (cedar_tpu/tenancy) carries the tenant the front end
    # resolved — never part of the wire bytes — and the canonical
    # fingerprint must scope to it; a PdpBody (cedar_tpu/pdp) additionally
    # carries the wire protocol, which must domain-separate the key
    tenant = getattr(body, "tenant", "")
    protocol = getattr(body, "protocol", "")
    try:
        doc = json.loads(body)
        if not isinstance(doc, dict):
            return None
        if endpoint == "authorize":
            # lazy import: server.http wires the cache, so the cache layer
            # must not import it at module load
            from ..server.http import get_authorizer_attributes

            attrs = get_authorizer_attributes(doc)
            if tenant:
                attrs.tenant = tenant
            if protocol:
                attrs.protocol = protocol
            return fingerprint_attributes(attrs)
        if endpoint == "admit":
            from ..entities.admission import AdmissionRequest

            req = AdmissionRequest.from_admission_review(doc)
            if tenant:
                req.tenant = tenant
            return fingerprint_admission_request(req)
    except Exception:  # noqa: BLE001 — unkeyable bodies are served uncached
        return None
    return None


class FingerprintMemo:
    """Bounded raw-body-digest → canonical-fingerprint memo.

    The native SAR fast path ships raw bytes to the C++ encoder without a
    Python JSON parse; computing a canonical fingerprint needs that parse.
    Repetitive traffic (the premise of the cache) re-sends byte-identical
    bodies, so this memo makes the parse a once-per-unique-body cost: the
    hot path pays one sha256 over the body plus a dict hit.

    Two wire variants of the same canonical request simply occupy two memo
    rows that map to the SAME fingerprint — the decision cache still
    coalesces them."""

    def __init__(self, capacity: int = 4096):
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        self._memo: "OrderedDict[bytes, Optional[str]]" = OrderedDict()
        # counted under the lock the lookup takes anyway; /metrics reads
        # them at scrape time (cedar_fingerprint_memo_total)
        self._hits = 0
        self._misses = 0

    def counts(self) -> Tuple[int, int]:
        """(hits, misses) since start: a miss paid the JSON parse."""
        with self._lock:
            return self._hits, self._misses

    def fingerprint(self, endpoint: str, body: bytes) -> Optional[str]:
        return self.lookup(endpoint, body)[0]

    def lookup(self, endpoint: str, body: bytes) -> Tuple[Optional[str], bool]:
        """(fingerprint, whether the memo held it)."""
        # tenant-scoped memo rows: two tenants' byte-identical bodies map
        # to DIFFERENT canonical fingerprints, so the raw-digest key must
        # split on the tenant too or the second tenant would hit the
        # first's memo row. Protocol splits rows the same way (a PDP-mapped
        # body must never hit a SAR row); \x01 vs \x00 separators keep the
        # two prefixes unambiguous, and protocol-less tenant-less bodies
        # keep the bare-body key.
        tenant = getattr(body, "tenant", "")
        protocol = getattr(body, "protocol", "")
        raw = body if not tenant else tenant.encode() + b"\x00" + body
        if protocol:
            raw = protocol.encode() + b"\x01" + raw
        digest = hashlib.sha256(raw).digest()
        with self._lock:
            if digest in self._memo:
                self._memo.move_to_end(digest)
                self._hits += 1
                return self._memo[digest], True
            self._misses += 1
        fp = fingerprint_body(endpoint, body)
        with self._lock:
            self._memo[digest] = fp
            self._memo.move_to_end(digest)
            while len(self._memo) > self.capacity:
                self._memo.popitem(last=False)
        return fp, False


def recorded_name_parts(url_path: str, body: bytes) -> Tuple[str, str]:
    """(endpoint basename, fingerprint-or-'unkeyed') for a recorded request
    — the recorder's filename stamp, so a recording carries the exact cache
    key the live server used for it."""
    import os

    endpoint = os.path.basename(url_path) or "request"
    fp = fingerprint_body(endpoint, body)
    return endpoint, (fp if fp is not None else "unkeyed")
