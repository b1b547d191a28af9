"""The rbac-tenants corpus: a cluster's own RBAC, converted to Cedar, and the
SubjectAccessReviews its apiserver re-asks.

Three parts, none of which imports anything of the program:

  RBAC objects   the stock ClusterRoles and ClusterRoleBindings that can
                 reach the webhook behind the upstream's authorizer chain
                 (``data/rbac-defaults.json``: Kubernetes' published default
                 roles, every rule list whole, with what a kind cluster adds),
                 and per tenant one namespace with four RoleBindings to the
                 user-facing ClusterRoles: group ``<ns>:owners`` -> ``admin``,
                 ``<ns>:developers`` -> ``edit``, ``<ns>:viewers`` -> ``view``,
                 ServiceAccount ``<ns>/deployer`` -> ``edit``.
  the converter  ``convert(binding, rules, ...)``: a plain RBAC -> Cedar
                 converter written against docs/ConvertRBAC.md and the
                 upstream converter's behaviour that ``tests/testdata/rbac``'s
                 golden pairs pin (one permit a subject and rule, policies in
                 the order of the upstream's policy ids, the provenance
                 annotations, ``unless { resource has subresource }`` where a
                 rule names no subresource, the mixed resource/subresource OR
                 chain, ``like`` for a trailing ``*``, the impersonation
                 policies). In the ``upstream`` dialect its text equals the
                 golden files byte for byte.
  the stream     ``Corpus.spec``: with probability ``repeat_share`` a request
                 re-sends, byte for byte, one of the latest ``repeat_window``
                 distinct requests of the stream, drawn uniformly; a new
                 request draws its tenant by Zipf, its subject, verb and
                 resource by the shares in ``configs/rbac-tenants.json``.

**The ``reference`` dialect** is what the configuration runs, because
``benchmark/reference.py`` (which this change may not edit) reads neither an
annotation nor ``like``: the provenance annotations are written as ``//``
comment lines above each policy, and a nonResourceURLs entry that ends in
``*`` other than ``*`` itself is left out of its rule (the configuration's
``departures`` lists them). Everything else is the converter's output as the
upstream writes it. ``{"dialect": "upstream"}`` gives that text whole.
"""

from __future__ import annotations

import json
import pathlib
import random

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEFAULTS = "rbac-defaults.json"
DIALECTS = ("reference", "upstream")

USER = "k8s::User"
GROUP = "k8s::Group"
SERVICE_ACCOUNT = "k8s::ServiceAccount"
RESOURCE = "k8s::Resource"
NON_RESOURCE = "k8s::NonResourceURL"
IMPERSONATION_TYPES = {"users": USER, "groups": GROUP, "uids": "k8s::PrincipalUID"}
EXTRA = "k8s::Extra"

# the tenant's four RoleBindings: (binding name, subject kind, subject's
# name after the namespace, the ClusterRole)
TENANT_BINDINGS = (
    ("owners", "Group", "owners", "admin"),
    ("developers", "Group", "developers", "edit"),
    ("viewers", "Group", "viewers", "view"),
    ("deployer", "ServiceAccount", "deployer", "edit"),
)
TENANT_GROUPS = ("owners", "developers", "viewers")
READS = ("get", "list", "watch")
NAMED_VERBS = ("get", "update", "patch", "delete")
SUBRESOURCES = (("", "pods", "log"), ("", "pods", "exec"), ("apps", "deployments", "scale"))
# what no user-facing role holds: the "resource its role lacks" of an
# unaimed request (secrets among them: view lacks it, edit does not)
LACKING = (
    ("", "nodes"), ("", "persistentvolumes"), ("", "secrets"),
    ("rbac.authorization.k8s.io", "clusterroles"),
    ("rbac.authorization.k8s.io", "clusterrolebindings"),
    ("storage.k8s.io", "storageclasses"),
    ("apiextensions.k8s.io", "customresourcedefinitions"),
    ("certificates.k8s.io", "certificatesigningrequests"),
)
NON_RESOURCE_PATHS = ("/healthz", "/version", "/api", "/apis/{group}", "/openapi/v2")
API_GROUPS = ("apps", "batch", "networking.k8s.io", "policy", "autoscaling")
COMPONENT_USERS = ("system:kube-controller-manager", "system:kube-scheduler")


# ---------------------------------------------------------------- the text

def quote(s: str) -> str:
    """A Cedar string literal."""
    out = ['"']
    for ch in s:
        out.append({'"': '\\"', "\\": "\\\\", "\n": "\\n", "\t": "\\t",
                    "\r": "\\r", "\0": "\\0"}.get(ch, ch))
    out.append('"')
    return "".join(out)


# An expression is ("and" | "or", left, right) or ("leaf", text): the one
# thing the text needs of a tree is where the parentheses go. `||` binds
# loosest, then `&&`, then every leaf (a comparison, has, like, is, in, a
# method call); the left operand of an operator takes its own level, the
# right one level more, as the upstream's formatter prints them.
_LEVEL = {"or": 1, "and": 2, "leaf": 3}


def leaf(text: str) -> tuple:
    return ("leaf", text)


def both(left, right):
    if left is None or right is None:
        return left if right is None else right
    return ("and", left, right)


def either(left, right):
    if left is None or right is None:
        return left if right is None else right
    return ("or", left, right)


def text_of(expr, level: int = 0) -> str:
    kind = expr[0]
    if kind == "leaf":
        return expr[1]
    mine = _LEVEL[kind]
    op = " && " if kind == "and" else " || "
    text = text_of(expr[1], mine) + op + text_of(expr[2], mine + 1)
    return f"({text})" if mine < level else text


def eq(attr: str, value: str):
    return leaf(f"{attr} == {quote(value)}")


def among(values: list, attr: str):
    return leaf("[" + ", ".join(quote(v) for v in values) + f"].contains({attr})")


def eq_or_among(values: list, attr: str):
    return eq(attr, values[0]) if len(values) == 1 else among(values, attr)


def has_and(attr: str, test):
    return both(leaf(f"resource has {attr}"), test)


def like(attr: str, glob: str):
    pattern = "*".join(quote(part)[1:-1].replace("*", "\\*") for part in glob.split("*"))
    return leaf(f'{attr} like "{pattern}"')


def unique(items: list) -> list:
    out = []
    for s in items:
        if s not in out:
            out.append(s)
    return out


def star_or(items: list) -> list:
    return ["*"] if "*" in items else items


# ------------------------------------------------------------ the converter

def urls_condition(urls: list):
    if len(urls) == 1:
        if urls[0] == "*":
            return None
        if urls[0].endswith("*"):
            return like("resource.path", urls[0])
        return eq("resource.path", urls[0])
    cond = None
    for u in urls:
        if u.endswith("*"):
            cond = either(cond, like("resource.path", u))
    plain = [u for u in urls if not u.endswith("*")]
    if plain:
        cond = either(cond, eq_or_among(plain, "resource.path"))
    return cond


def subresource_condition(entry: str):
    left, right = entry.split("/", 1)
    cond = None if left == "*" else eq("resource.resource", left)
    if right == "*":
        return both(cond, has_and("subresource", leaf('resource.subresource != ""')))
    return both(cond, has_and("subresource", eq("resource.subresource", right)))


def resources_condition(cond, resources: list):
    if len(resources) == 1:
        if resources[0] == "*":
            return cond
        if "/" not in resources[0]:
            return both(cond, eq("resource.resource", resources[0]))
        return both(cond, subresource_condition(resources[0]))
    subs = None
    for entry in resources:
        if "/" in entry:
            subs = either(subs, subresource_condition(entry))
    regular = [r for r in resources if "/" not in r]
    plain = eq_or_among(regular, "resource.resource") if regular else None
    return both(cond, either(plain, subs))


def names_condition(cond, names: list, attr: str = "name", guarded: bool = True):
    if not names:
        return cond
    test = eq_or_among(names, f"resource.{attr}")
    return both(cond, has_and(attr, test) if guarded else test)


def impersonation(rule: dict) -> tuple:
    """(resource scope, condition) of the impersonation policy of a rule, over
    the principal-typed resources; on the rule as written, not reduced."""
    resources = rule.get("resources") or []
    names = rule.get("resourceNames") or []
    first = resources[0] if resources else ""

    def extras(cond):
        keys = [r.split("/", 1)[1] for r in resources if "/" in r]
        if keys:
            cond = both(cond, eq_or_among(keys, "resource.key"))
        return names_condition(cond, names, "value")

    def uids(cond):
        if len(names) == 1:
            return cond
        ids = ", ".join(f"k8s::PrincipalUID::{quote(n)}" for n in names)
        return both(cond, leaf(f"resource in [{ids}]"))

    if first.startswith("userextras"):
        same = all(r.startswith("userextras") for r in resources)
    else:
        same = all(r == first for r in resources)
    if same:
        if first in ("users", "groups"):
            return (f"resource is {IMPERSONATION_TYPES[first]}",
                    names_condition(None, names, guarded=False))
        if first == "uids":
            if len(names) == 1:
                return f"resource == k8s::PrincipalUID::{quote(names[0])}", None
            return "resource is k8s::PrincipalUID", uids(None)
        if first.startswith("userextras"):
            return f"resource is {EXTRA}", extras(None)
        return "resource", None
    cond = None
    for r in resources:
        local = None
        if r in ("users", "groups"):
            local = names_condition(leaf(f"resource is {IMPERSONATION_TYPES[r]}"),
                                    names, guarded=False)
        elif r == "uids":
            if len(names) == 1:
                local = leaf(f"resource == k8s::PrincipalUID::{quote(names[0])}")
            local = uids(local or leaf("resource is k8s::PrincipalUID"))
        if r.startswith("userextras"):
            local = extras(leaf(f"resource is {EXTRA}"))
        cond = either(local, cond)
    return "resource", cond


def convert(binding: dict, role_kind: str, rules: list, dialect: str = "upstream") -> str:
    """The Cedar text of one (Cluster)RoleBinding over its role's rules.
    ``binding``: ``kind`` (``ClusterRoleBinding`` | ``RoleBinding``), ``name``,
    ``namespace`` (a RoleBinding's), ``role`` (the role's name), ``subjects``
    (``kind``, ``name``, a ServiceAccount's ``namespace``). ``role_kind``:
    ``clusterRole`` | ``role``."""
    if dialect not in DIALECTS:
        raise ValueError(f"unknown dialect {dialect!r}")
    binder = "roleBinding" if binding["kind"] == "RoleBinding" else "clusterRoleBinding"
    namespace = binding.get("namespace", "") if binder == "roleBinding" else ""
    subjects = [s for s in binding["subjects"]
                if s.get("kind") in ("Group", "User", "ServiceAccount")]
    made = []  # (policy id, text)
    for pi, subject in enumerate(subjects):
        if subject["kind"] == "Group":
            principal, who = f"principal in {GROUP}::{quote(subject['name'])}", None
        elif subject["kind"] == "User":
            principal, who = f"principal is {USER}", eq("principal.name", subject["name"])
        else:
            sa_ns, sa_name = subject.get("namespace", ""), subject["name"]
            if f"system:serviceaccount:{sa_ns}:{sa_name}".count(":") != 3:
                continue  # not a service account's name: the upstream skips it
            principal = f"principal is {SERVICE_ACCOUNT}"
            who = both(eq("principal.namespace", sa_ns), eq("principal.name", sa_name))
        for ri, rule in enumerate(rules):
            notes = [(binder, binding["name"]), (role_kind, binding["role"]),
                     ("policyRule", f"{ri:02d}")]
            if namespace:
                notes.append(("namespace", namespace))
            verbs = star_or(unique(rule.get("verbs") or []))
            if verbs == ["*"]:
                action = "action"
            elif len(verbs) == 1:
                action = f"action == k8s::Action::{quote(verbs[0])}"
            else:
                action = "action in [" + ", ".join(
                    f"k8s::Action::{quote(v)}" for v in verbs) + "]"

            def policy(pid, action, resource, when, unless=False):
                mark = "@" if dialect == "upstream" else "// @"
                lines = [f"{mark}{k}({quote(v)})" for k, v in notes]
                lines += ["permit (", f"  {principal},\n  {action},\n  {resource}", ")"]
                if when is not None:
                    lines.append(f"when {{ {text_of(when)} }}")
                if unless:
                    lines.append("unless { resource has subresource }")
                made.append((pid, "\n".join(lines) + ";"))

            urls = rule.get("nonResourceURLs") or []
            if urls:
                if dialect == "reference":
                    urls = [u for u in urls if u == "*" or not u.endswith("*")]
                    if not urls:
                        continue
                policy(f"{binding['name']}{pi}{ri}", action,
                       f"resource is {NON_RESOURCE}", both(who, urls_condition(urls)))
                continue
            resources = rule.get("resources") or []
            groups = rule.get("apiGroups") or []
            if not resources:
                continue
            wildcard = verbs[0] == "*" and resources[0] == "*" and groups[:1] == ["*"]
            if wildcard or ("impersonate" in verbs and "authentication.k8s.io" in groups):
                scope, cond = impersonation(rule)
                policy(f"{binding['name']}:{binder}/impersonate:{pi}{ri}",
                       'action == k8s::Action::"impersonate"', scope, both(who, cond))
                if verbs == ["impersonate"]:
                    continue
            if not groups:
                continue
            groups, resources = star_or(unique(groups)), star_or(unique(resources))
            cond = None if groups == ["*"] else eq_or_among(groups, "resource.apiGroup")
            cond = resources_condition(cond, resources)
            cond = names_condition(cond, unique(rule.get("resourceNames") or []))
            if namespace:
                cond = both(cond, has_and("namespace", eq("resource.namespace", namespace)))
            policy(f"{binding['name']}:{binder}:{pi}{ri}", action,
                   f"resource is {RESOURCE}", both(who, cond),
                   unless=not any("/" in r for r in resources))
    made.sort(key=lambda p: p[0])
    return "\n\n".join(text for _, text in made) + ("\n" if made else "")


# --------------------------------------------------------- the RBAC objects

def cluster_roles(doc: dict) -> dict:
    """Every ClusterRole of the defaults by name, the aggregated three built
    from their parts as the aggregation controller builds them."""
    roles = dict(doc["clusterRoles"])
    parts = dict(doc["aggregates"])
    for name in ("view", "edit", "admin"):  # each after what it takes from
        rules = []
        for source in doc["aggregated"][name]:
            for rule in parts.get(source) or roles[source]:
                if rule not in rules:
                    rules.append(rule)
        roles[name] = rules
    return roles


def tenant_bindings(namespace: str) -> list:
    out = []
    for name, kind, tail, role in TENANT_BINDINGS:
        if kind == "Group":
            subject = {"kind": "Group", "name": f"{namespace}:{tail}"}
        else:
            subject = {"kind": kind, "name": tail, "namespace": namespace}
        out.append({"kind": "RoleBinding", "name": f"{namespace}-{name}",
                    "namespace": namespace, "role": role, "subjects": [subject]})
    return out


def policies_of(text: str) -> int:
    return text.count("\npermit (") + text.startswith("permit (")


# ------------------------------------------------------------------ corpus

def canonical(spec: dict) -> str:
    return json.dumps(spec, sort_keys=True, separators=(",", ":"))


class Corpus:
    def __init__(self, params: dict, seed: int):
        self.doc = json.loads((DATA / params.get("defaults", DEFAULTS)).read_text())
        self.roles = cluster_roles(self.doc)
        dialect = params.get("dialect", "reference")
        stock = "".join(
            convert(dict(b, kind="ClusterRoleBinding"), "clusterRole",
                    self.roles[b["role"]], dialect) + "\n"
            for b in self.doc["clusterRoleBindings"])
        n = int(params["tenants"])
        if params.get("policies"):
            # the rehearsal's switch: as many tenants as leave about that many
            per_tenant = policies_of("".join(
                convert(b, "clusterRole", self.roles[b["role"]], dialect)
                for b in tenant_bindings("t")))
            n = max(2, min(n, round((int(params["policies"]) - policies_of(stock))
                                    / per_tenant)))
        self.namespaces = [f"tenant-{i:03d}" for i in range(n)]
        self.files = {"cluster.cedar": stock}
        for ns in self.namespaces:
            self.files[f"{ns}.cedar"] = "".join(
                convert(b, "clusterRole", self.roles[b["role"]], dialect) + "\n"
                for b in tenant_bindings(ns))
        self.policies = sum(policies_of(t) for t in self.files.values())

        rng = random.Random(f"{seed}:rbac")
        # which tenant is how busy: rank r of a seed-drawn order gets r^-s
        order = list(range(n))
        rng.shuffle(order)
        s = float(params.get("zipf_s", 1.1))
        self.tenant_order = order
        self.tenant_weights = [1.0 / (r + 1) ** s for r in range(n)]
        # a tenant's people: each with 1-3 of the tenant's three groups, fixed
        self.users = []
        for ns in self.namespaces:
            people = []
            for k in range(int(params.get("users_per_tenant", 12))):
                groups = rng.sample(TENANT_GROUPS, rng.choice((1, 1, 1, 2, 2, 3)))
                people.append((f"{ns}-user-{k:02d}",
                               [f"{ns}:{g}" for g in TENANT_GROUPS if g in groups]))
            self.users.append(people)
        self.repeat_share = float(params.get("repeat_share", 0.85))
        self.window = int(params.get("repeat_window", 5000))
        self.all_new = int(params.get("first_new", 200))
        self.subject_mix = params.get("subject_mix") or {
            "tenant_user": 0.60, "tenant_service_account": 0.25,
            "component_user": 0.05, "cluster_admin": 0.05, "unbound_user": 0.05}
        self.non_resource_share = float(params.get("non_resource_share", 0.10))
        self.read_share = float(params.get("read_share", 0.7))
        self.subresource_share = float(params.get("subresource_share", 0.10))
        self.deployer_share = float(params.get("deployer_share", 0.8))
        self.self_review_share = float(params.get("self_review_share", 0.3))
        # the stream's state: every distinct request so far in order, of
        # which the latest ``window`` (from ``oldest`` on) can be re-sent
        self.recent = {}  # canonical JSON -> spec, the window's
        self.keys = []
        self.oldest = 0
        self.sent = 0
        self.repeats = 0
        # the resource rules of each user-facing role, and of what every
        # authenticated subject holds
        self.rules = {role: [r for r in self.roles[role] if r.get("resources")]
                      for role in ("admin", "edit", "view", "system:basic-user")}

    # -- a new request
    def tenant(self, rng: random.Random) -> int:
        return self.tenant_order[
            rng.choices(range(len(self.tenant_order)), self.tenant_weights)[0]]

    def subject(self, rng: random.Random) -> tuple:
        """(user, groups, the roles whose rules it asks from in its tenant —
        those it is bound to there, but for the component users — tenant)."""
        t = self.tenant(rng)
        ns = self.namespaces[t]
        kind = rng.choices(list(self.subject_mix), list(self.subject_mix.values()))[0]
        if kind == "tenant_user":
            name, groups = rng.choice(self.users[t])
            roles = [{"owners": "admin", "developers": "edit", "viewers": "view"}[
                g.rsplit(":", 1)[1]] for g in groups]
            return name, groups + ["system:authenticated"], roles, t
        if kind == "tenant_service_account":
            bound = rng.random() < self.deployer_share
            sa = "deployer" if bound else rng.choice(("default", "builder", "metrics"))
            groups = ["system:serviceaccounts", f"system:serviceaccounts:{ns}",
                      "system:authenticated"]
            return f"system:serviceaccount:{ns}:{sa}", groups, ["edit"] if bound else [], t
        if kind == "component_user":
            # the controllers and the scheduler work on every tenant's objects
            return rng.choice(COMPONENT_USERS), ["system:authenticated"], ["admin"], t
        if kind == "cluster_admin":
            return (f"cluster-admin-{rng.randint(0, 4)}",
                    ["system:masters", "system:authenticated"], ["admin"], t)
        return f"visitor-{rng.randint(0, 49)}", ["system:authenticated"], [], t

    def fresh(self, rng: random.Random, aimed_share: float) -> dict:
        user, groups, roles, t = self.subject(rng)
        spec = {"user": user, "uid": user, "groups": groups}
        aimed = rng.random() < aimed_share
        if rng.random() < self.non_resource_share:
            path = rng.choice(NON_RESOURCE_PATHS).format(group=rng.choice(API_GROUPS))
            if not aimed:
                path = rng.choice(("/metrics", "/logs/", "/debug/pprof/profile"))
            spec["nonResourceAttributes"] = {"path": path, "verb": "get"}
            return spec
        ns = self.namespaces[t]
        read = rng.random() < self.read_share
        held = [r for role in roles for r in self.rules[role]]
        scoped = bool(held)
        if not held:
            # bound to nothing in any tenant: what every authenticated
            # subject may do, which is cluster-scoped, or (most of the time)
            # what a viewer could, which nobody granted
            scoped = rng.random() >= self.self_review_share
            held = self.rules["view" if scoped else "system:basic-user"]
        fits = [r for r in held if any((v in READS) == read for v in r["verbs"])]
        rule = rng.choice(fits or held)
        verbs = [v for v in rule["verbs"] if (v in READS) == read and v != "impersonate"]
        verb = rng.choice(verbs or [v for v in rule["verbs"] if v != "impersonate"]
                          or ["get"])
        group = rng.choice(rule["apiGroups"])
        plain = [r for r in rule["resources"] if "/" not in r]
        resource, sub = (rng.choice(plain), "") if plain else \
            rng.choice(rule["resources"]).split("/", 1)
        if rng.random() < self.subresource_share:
            group, resource, sub = rng.choice(SUBRESOURCES)
            verb = {"log": "get", "exec": rng.choice(("create", "get")),
                    "scale": rng.choice(("get", "update", "patch"))}[sub]
        if not aimed:
            if rng.random() < 0.5 and len(self.namespaces) > 1:
                # in another tenant's namespace
                ns = self.namespaces[(t + rng.randint(1, len(self.namespaces) - 1))
                                     % len(self.namespaces)]
            else:
                (group, resource), sub = rng.choice(LACKING), ""
        ra = {"group": group, "version": "v1", "resource": resource, "verb": verb}
        if scoped or not aimed:
            ra["namespace"] = ns
        if sub:
            ra["subresource"] = sub
        if verb in NAMED_VERBS or sub:
            ra["name"] = f"{resource}-{rng.randint(0, 39):02d}"
        spec["resourceAttributes"] = ra
        return spec

    # -- the stream
    def spec(self, rng: random.Random, aimed_share: float) -> dict:
        """The next request of the stream. A repeat is the same object as its
        original, so its body is the original's byte for byte."""
        self.sent += 1
        if self.sent > self.all_new and rng.random() < self.repeat_share:
            self.repeats += 1
            return self.recent[self.keys[rng.randrange(self.oldest, len(self.keys))]]
        for _ in range(16):
            spec = self.fresh(rng, aimed_share)
            key = canonical(spec)
            if key not in self.recent:
                break
        else:
            raise RuntimeError("the stream cannot find a request that is not "
                               f"among its latest {len(self.recent)}")
        self.recent[key] = spec
        self.keys.append(key)
        if len(self.recent) > self.window:
            del self.recent[self.keys[self.oldest]]
            self.oldest += 1
        return spec


def build(params: dict, seed: int) -> Corpus:
    return Corpus(params, seed)
