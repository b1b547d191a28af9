"""The request kind ``admission``: an AdmissionReview on ``/v1/admit``.

The five things a kind states (``kinds/sar.py``, benchmark/README.md), for
the webhook's validating-admission endpoint. Like every kind it is part of
the reference: the mapping of a review onto Cedar entities is written
against cedar-access-control-for-k8s (``internal/server/entities/
admission.go``, ``internal/server/admission/handler.go``) and imports
nothing of the program.

The mapping, in ``environment``:

  principal   from ``userInfo``: ``k8s::User``, ``k8s::ServiceAccount``
              (``system:serviceaccount:<ns>:<name>``) or ``k8s::Node``
              (``system:node:<name>``), id the uid (the username without
              one), the groups as parents, ``extra`` as a Set of
              ``{key, values}``
  action      ``k8s::admission::Action::"<operation>"``, a child of ``"all"``
  resource    ``<group or core>::<version>::<Kind>`` with the request's URL
              path as id and the object walked into a Record: dict -> Record
              (an empty one is skipped, a null too), list -> Set, int ->
              Long, bool -> Boolean, string -> String; the string maps the
              webhook knows (``labels`` and ``annotations`` anywhere,
              ``nodeSelector`` of a Pod, ...) -> Set of ``{key, value}``
  DELETE      evaluates ``oldObject`` as the resource
  UPDATE      (any review with both objects) the old object is an entity of
              the same type whose id is the review's uid, linked from
              ``resource.oldObject`` and given as ``context.oldObject``

Before Cedar: a review in ``kube-system`` or ``cedar-k8s-authz-system`` is
allowed unevaluated. After it, the tier walk: the policy stores first, then
the allow-all admission policy; a tier with a determining policy ends the
walk, and the review is denied iff the walk ends in a deny — so ``allowed``
with no reason unless a ``forbid`` held, and then ``status.message`` names
every determining policy. The tuple that is compared: ``(allowed,
frozenset of policy ids)``.

Not modelled, and why it need not be: a policy that errs ends the walk too
(an unguarded access denies with an empty message), but ``reference.py``
skips an erring policy in silence. The corpus guards every access with
``has``, and a test holds, in the reference and in the program alike, that
no policy errs on any review the generator makes.
"""

from __future__ import annotations

import ipaddress
import json

from benchmark.reference import Entity, Record, ReferenceError_, record

PATH = "/v1/admit"

USER = "k8s::User"
GROUP = "k8s::Group"
NODE = "k8s::Node"
SERVICE_ACCOUNT = "k8s::ServiceAccount"
ACTION = "k8s::admission::Action"
OPERATIONS = {"CREATE": "create", "UPDATE": "update", "DELETE": "delete", "CONNECT": "connect"}
SKIPPED_NAMESPACES = ("kube-system", "cedar-k8s-authz-system")
MAX_DEPTH = 32

# (group, version, kind) of the reviewed object -> the attributes whose
# map[string]string becomes a Set of {key, value} (admission.go); ``labels``
# and ``annotations`` do so under every kind
STRING_MAPS = {
    ("core", "v1", "ConfigMap"): ("data", "binaryData"),
    ("core", "v1", "CSIPersistentVolumeSource"): ("volumeAttributes",),
    ("core", "v1", "CSIVolumeSource"): ("volumeAttributes",),
    ("core", "v1", "FlexPersistentVolumeSource"): ("options",),
    ("core", "v1", "FlexVolumeSource"): ("options",),
    ("core", "v1", "PersistentVolumeClaimStatus"): ("allocatedResourceStatuses",),
    ("core", "v1", "Pod"): ("nodeSelector",),
    ("core", "v1", "ReplicationController"): ("selector",),
    ("core", "v1", "Secret"): ("data", "stringData"),
    ("core", "v1", "Service"): ("selector",),
    ("discovery", "v1", "Endpoint"): ("deprecatedTopology",),
    ("node", "v1", "Scheduling"): ("nodeSelectors",),
    ("storage", "v1", "StorageClass"): ("parameters",),
    ("storage", "v1", "VolumeAttachmentStatus"): ("attachmentMetadata",),
    ("meta", "v1", "LabelSelector"): ("matchLabels",),
    ("meta", "v1", "ObjectMeta"): ("annotations", "labels"),
}
EVERYWHERE = ("labels", "annotations")
# string leaves under these names are Cedar ipaddr values where they parse
IP_KEYS = frozenset({"podIP", "clusterIP", "loadBalancerIP", "hostIP", "ip", "podIPs", "hostIPs"})

ALLOWED = (True, frozenset())


class Opaque:
    """A value this reference has no type for (an ``ipaddr``): carried, and
    an error if a policy touches it — never a silent skip."""

    __slots__ = ("what",)

    def __init__(self, what: str):
        self.what = what

    def __hash__(self) -> int:
        return id(self)

    def __eq__(self, other):
        raise ReferenceError_(f"a policy compared {self.what}, which this reference cannot")


def body(spec: dict) -> dict:
    """A corpus spec (the review's ``request``) in its v1 envelope."""
    return {"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview", "request": spec}


def distinct(spec: dict, name: str) -> None:
    """What makes every review distinct under ``name_per_request``: the
    object's name, in the request and in both objects, and the uid."""
    spec["name"] = name
    spec["uid"] = f"review-{name}"
    for key in ("object", "oldObject"):
        if spec.get(key) is not None:
            spec[key]["metadata"]["name"] = name


def _walk(value, key: str, gvk: tuple, depth: int):
    """One node of the object as a Cedar value; None for what is skipped."""
    if depth == 0:
        raise ReferenceError_("an object deeper than 32 levels")
    if value is None:
        return None
    if isinstance(value, dict):
        if key in STRING_MAPS.get(gvk, ()) or key in EVERYWHERE:
            return frozenset(record({"key": k, "value": v})
                             for k, v in value.items() if isinstance(v, str))
        fields = {}
        for k, v in value.items():
            got = _walk(v, k, gvk, depth - 1)
            if got is not None:
                fields[k] = got
        return record(fields) if fields else None
    if isinstance(value, list):
        items = (_walk(v, key, gvk, depth - 1) for v in value)
        return frozenset(v for v in items if v is not None)
    if isinstance(value, str):
        return Opaque(f"the ipaddr {key}") if key in IP_KEYS and _is_ip(value) else value
    if isinstance(value, (bool, int)):
        return value
    raise ReferenceError_(f"a {type(value).__name__} under {key}: no Cedar type for it")


def _is_ip(text: str) -> bool:
    try:
        ipaddress.ip_network(text, strict=False)
    except ValueError:
        return False
    return True


def _object_record(obj: dict, gvk: tuple) -> Record:
    if obj is None:
        raise ReferenceError_("the review carries no object to evaluate")
    fields = {}
    for k, v in obj.items():
        got = _walk(v, k, gvk, MAX_DEPTH)
        if got is not None:
            fields[k] = got
    return record(fields)


def _principal(user: dict) -> tuple:
    """(entity, {entity: (attributes, parents)}) from ``userInfo``."""
    name = user.get("username", "")
    groups = frozenset(Entity((GROUP, g)) for g in user.get("groups") or ())
    ptype, attrs = USER, {"name": name}
    if name.startswith("system:node:") and name.count(":") == 2:
        ptype, attrs = NODE, {"name": name.split(":")[2]}
    elif name.startswith("system:serviceaccount:") and name.count(":") == 3:
        parts = name.split(":")
        ptype, attrs = SERVICE_ACCOUNT, {"namespace": parts[2], "name": parts[3]}
    extra = user.get("extra") or {}
    if extra:
        attrs["extra"] = frozenset(
            record({"key": k, "values": frozenset(v)}) for k, v in extra.items())
    principal = Entity((ptype, user.get("uid") or name))
    entities = {principal: (record(attrs), groups)}
    for g in groups:
        entities[g] = (record({"name": g[1]}), frozenset())
    return principal, entities


def _url_path(req: dict) -> str:
    res = req.get("resource") or {}
    path = f"/apis/{res['group']}" if res.get("group") else "/api"
    path += f"/{res.get('version', '')}"
    if req.get("namespace"):
        path += f"/namespaces/{req['namespace']}"
    path += f"/{res.get('resource', '')}"
    for part in (req.get("name"), req.get("subResource")):
        if part:
            path += f"/{part}"
    return path


def environment(req: dict) -> dict:
    """The webhook's mapping of an AdmissionReview request onto Cedar
    principal, action, resource, context and entities."""
    operation = req.get("operation", "")
    if operation not in OPERATIONS:
        raise ReferenceError_(f"unsupported operation {operation!r}")
    principal, entities = _principal(req.get("userInfo") or {})
    every = Entity((ACTION, "all"))
    action = Entity((ACTION, OPERATIONS[operation]))
    entities[every] = (Record(), frozenset())
    entities[action] = (Record(), frozenset({every}))
    kind = req.get("kind") or {}
    group = (req.get("resource") or {}).get("group") or "core"
    gvk = (group, kind.get("version", ""), kind.get("kind", ""))
    rtype = "::".join(gvk)
    resource = Entity((rtype, _url_path(req)))
    context = {}
    old = req.get("oldObject")
    if operation == "DELETE":
        attrs = _object_record(old, gvk)
    else:
        attrs = _object_record(req.get("object"), gvk)
        if old is not None:
            # both share the path: the old one goes by the review's uid
            old_entity = Entity((rtype, req.get("uid", "")))
            old_attrs = _object_record(old, gvk)
            entities[old_entity] = (old_attrs, frozenset())
            attrs = Record(attrs | {("oldObject", old_entity)})
            context["oldObject"] = old_attrs
    entities[resource] = (attrs, frozenset())
    return {"principal": principal, "action": action, "resource": resource,
            "context": record(context), "entities": entities}


def expected(reference, spec: dict) -> tuple:
    """(allowed, frozenset of determining policy ids): this review's answer
    by the plain reference."""
    if spec.get("namespace") in SKIPPED_NAMESPACES:
        # the webhook's own rule: its own and the system's namespace are
        # never evaluated
        return ALLOWED
    # the tier walk: the stores, then the allow-all admission policy, which
    # holds for every review — only a forbid of the stores denies
    decision, reasons = reference.evaluate(environment(spec))
    if decision == "deny":
        return (False, frozenset(reasons))
    return ALLOWED


def verdict(response: dict) -> tuple:
    """A served AdmissionReview in ``expected``'s terms. An answer by the
    fail-open (or fail-closed) posture, an error or an unreadable message
    makes a verdict no reference answer equals."""
    resp = response.get("response") or {}
    status = resp.get("status") or {}
    message = status.get("message") or ""
    ids = frozenset()
    if status.get("code") not in (None, 200):
        ids = frozenset({f"error: status {status.get('code')}: {message[:80]}"})
    elif message:
        try:
            ids = frozenset(r["policy"] for r in json.loads(message))
        except (ValueError, KeyError, TypeError):
            ids = frozenset({f"error: unreadable message: {message[:80]}"})
    return (bool(resp.get("allowed")), ids)


def gave_up(verdict: tuple) -> bool:
    """The program's own word that it did not evaluate: its deadline, a
    shed review, a crash (``verdict`` puts it among the ids)."""
    return any(str(i).startswith("error:") for i in verdict[1])
