"""The request kind ``sar``: a SubjectAccessReview on ``/v1/authorize``.

A kind states what one request is, and nothing else in the harness knows:
the request line (``PATH``), the review object a corpus spec is wrapped in
(``body``) and what makes one body distinct (``distinct``), this request's
answer by the plain reference (``expected``), and how a served response
reads in the same terms (``verdict``, ``gave_up``). The comparison is tuple
equality.

A kind is part of the reference: it holds the webhook's documented mapping
of the review onto Cedar entities and the webhook's own rules that answer
before Cedar is asked, written against cedar-access-control-for-k8s, and
imports nothing of the program.
"""

from __future__ import annotations

import json

from benchmark.reference import Entity, Record, ReferenceError_, record

PATH = "/v1/authorize"

USER = "k8s::User"
GROUP = "k8s::Group"
NODE = "k8s::Node"
SERVICE_ACCOUNT = "k8s::ServiceAccount"
ACTION = "k8s::Action"
RESOURCE = "k8s::Resource"
NON_RESOURCE = "k8s::NonResourceURL"
LABEL_OPS = {"In": "in", "NotIn": "notin", "Exists": "exists", "DoesNotExist": "!"}

NO_OPINION = (False, False, frozenset())


def body(spec: dict) -> dict:
    """A corpus spec wrapped as the review object that is posted."""
    return {
        "apiVersion": "authorization.k8s.io/v1",
        "kind": "SubjectAccessReview",
        "spec": spec,
    }


def distinct(spec: dict, name: str) -> None:
    """What makes every body distinct under ``name_per_request``, so that the
    decision cache cannot answer: a kube-apiserver's own cache absorbs
    repeats."""
    spec["resourceAttributes"]["name"] = name


def environment(spec: dict) -> dict:
    """The webhook's mapping of a SubjectAccessReview spec onto Cedar
    entities (cedar-access-control-for-k8s: users with their groups as
    parents, the verb as a k8s::Action, resourceAttributes as a
    k8s::Resource whose empty attributes are absent)."""
    name = spec.get("user", "")
    groups = frozenset(Entity((GROUP, g)) for g in spec.get("groups") or ())
    ptype, attrs = USER, {"name": name}
    if name.startswith("system:node:") and name.count(":") == 2:
        ptype, attrs = NODE, {"name": name.split(":")[2]}
    elif name.startswith("system:serviceaccount:") and name.count(":") == 3:
        parts = name.split(":")
        ptype, attrs = SERVICE_ACCOUNT, {"namespace": parts[2], "name": parts[3]}
    extra = spec.get("extra") or {}
    if extra:
        attrs["extra"] = frozenset(
            record({"key": k, "values": frozenset(v)}) for k, v in extra.items()
        )
    principal = Entity((ptype, spec.get("uid") or name))
    entities = {principal: (record(attrs), groups)}
    for g in groups:
        entities[g] = (record({"name": g[1]}), frozenset())
    ra = spec.get("resourceAttributes")
    if ra:
        verb = ra.get("verb", "")
        if verb == "impersonate":
            raise ReferenceError_("impersonation requests are not covered by this reference")
        rattrs = {"apiGroup": ra.get("group", ""), "resource": ra.get("resource", "")}
        for key in ("name", "subresource", "namespace"):
            if ra.get(key):
                rattrs[key] = ra[key]
        reqs = (ra.get("labelSelector") or {}).get("requirements") or ()
        selector = frozenset(
            record({"key": r.get("key", ""),
                    "operator": LABEL_OPS[r["operator"]],
                    "values": frozenset(r.get("values") or ())})
            for r in reqs if r.get("operator") in LABEL_OPS
        )
        if selector:
            rattrs["labelSelector"] = selector
        if (ra.get("fieldSelector") or {}).get("requirements"):
            raise ReferenceError_("field selectors are not covered by this reference")
        resource = Entity((RESOURCE, "resource"))
    else:
        nra = spec.get("nonResourceAttributes") or {}
        verb = nra.get("verb", "")
        rattrs = {"path": nra.get("path", "")}
        resource = Entity((NON_RESOURCE, rattrs["path"]))
    entities[resource] = (record(rattrs), frozenset())
    return {
        "principal": principal,
        "action": Entity((ACTION, verb)),
        "resource": resource,
        "context": Record(),
        "entities": entities,
    }


def expected(reference, spec: dict) -> tuple:
    """(allowed, denied, frozenset of determining policy ids): this
    request's answer by the plain reference."""
    user = spec.get("user", "")
    if (
        user.startswith("system:")
        and not user.startswith("system:serviceaccount:")
        and not user.startswith("system:node:")
    ):
        # the webhook's own rule: system users are skipped before any policy
        return NO_OPINION
    decision, reasons = reference.evaluate(environment(spec))
    if decision is None:
        return NO_OPINION
    return (decision == "allow", decision == "deny", frozenset(reasons))


def verdict(response: dict) -> tuple:
    """A served response in ``expected``'s terms: (allowed, denied,
    frozenset of policy ids); an evaluationError or an unreadable reason
    makes a verdict no reference answer equals."""
    st = response.get("status") or {}
    reason = st.get("reason", "")
    ids = frozenset()
    if reason:
        try:
            ids = frozenset(r["policy"] for r in json.loads(reason)["reasons"])
        except (ValueError, KeyError, TypeError):
            ids = frozenset({f"unreadable reason: {reason[:80]}"})
    if st.get("evaluationError"):
        ids = ids | {f"evaluationError: {st['evaluationError'][:80]}"}
    return (bool(st.get("allowed")), bool(st.get("denied")), ids)


def gave_up(verdict: tuple) -> bool:
    """The program's own word that it gave up: its deadline, a shed
    request, a crash (``verdict`` puts it among the reason's ids)."""
    return any(str(i).startswith("evaluationError") for i in verdict[2])
