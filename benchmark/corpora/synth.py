"""The synth-10k corpus: an org-wide, multi-cluster Cedar policy store.

A copy of the text-generating half of ``cedar_tpu/corpus/synth.py`` (and of
``chip_smoke.py``'s directory layout), kept here so that a later change to
the program's generator cannot move the yardstick. It imports nothing of
the program: policies leave as Cedar source text, requests as plain
SubjectAccessReview ``spec`` dicts.

Per policy ``i`` the parameters come from ``Random(f"{seed}:{i}")``: 10
clusters, three cluster-local apiGroups each, 2% org-wide policies on the
core groups; 55% team / 20% user / 15% read / 10% forbid.
"""

from __future__ import annotations

import pathlib
import random

CORE_GROUPS = ("", "apps", "rbac.authorization.k8s.io")
RESOURCES = (
    "pods", "services", "secrets", "configmaps", "deployments",
    "jobs", "statefulsets", "daemonsets", "cronjobs", "endpoints",
)
VERBS = ("get", "list", "watch", "create", "update", "delete", "patch")
POLICIES_PER_FILE = 1000
DATA = pathlib.Path(__file__).resolve().parent / "data"


def cluster_groups(cluster: int) -> tuple:
    return (
        f"platform.c{cluster}.corp",
        f"data.c{cluster}.corp",
        f"ml.c{cluster}.corp",
    )


def policy_source(i: int, seed: int, clusters: int):
    """(Cedar text, parameters) of policy ``i``."""
    rng = random.Random(f"{seed}:{i}")
    cluster = i % clusters
    org_wide = rng.random() < 0.02
    if org_wide:
        group = rng.choice(CORE_GROUPS)
        cluster = -1
    else:
        group = rng.choice(cluster_groups(cluster))
    prefix = "org" if org_wide else f"c{cluster}"
    team = f"{prefix}-team-{rng.randint(0, 99)}"
    user = f"{prefix}-user-{rng.randint(0, 499)}"
    ns = f"{prefix}-ns-{rng.randint(0, 199)}"
    res = rng.choice(RESOURCES)
    verbs = tuple(rng.sample(VERBS, rng.randint(1, 3)))
    acts = ", ".join(f'k8s::Action::"{v}"' for v in verbs)
    kind = rng.random()
    if kind < 0.55:
        src = (
            f'permit (principal in k8s::Group::"{team}", action in [{acts}], '
            "resource is k8s::Resource) when { "
            f'resource.apiGroup == "{group}" && '
            f'resource.resource == "{res}" && '
            "resource has namespace && "
            f'resource.namespace == "{ns}" }};'
        )
        params = dict(kind="team", cluster=cluster, group=group, team=team,
                      user="", ns=ns, resource=res, verbs=verbs)
    elif kind < 0.75:
        src = (
            f"permit (principal is k8s::User, action in [{acts}], "
            "resource is k8s::Resource) when { "
            f'resource.apiGroup == "{group}" && '
            f'principal.name == "{user}" && '
            f'resource.resource == "{res}" }};'
        )
        params = dict(kind="user", cluster=cluster, group=group, team="",
                      user=user, ns="", resource=res, verbs=verbs)
    elif kind < 0.9:
        src = (
            "permit (principal, action in [k8s::Action::\"get\", "
            'k8s::Action::"list", k8s::Action::"watch"], '
            "resource is k8s::Resource) when { "
            f'resource.apiGroup == "{group}" && '
            f'resource.resource == "{res}" && '
            "resource has namespace && "
            f'resource.namespace == "{ns}" }};'
        )
        params = dict(kind="read", cluster=cluster, group=group, team="",
                      user="", ns=ns, resource=res,
                      verbs=("get", "list", "watch"))
    else:
        src = (
            f"forbid (principal, action in [{acts}], "
            "resource is k8s::Resource) when { "
            f'resource.apiGroup == "{group}" && '
            f'resource.resource == "secrets" && '
            "resource has namespace && "
            f'resource.namespace == "{ns}" }};'
        )
        params = dict(kind="forbid", cluster=cluster, group=group, team="",
                      user="", ns=ns, resource="secrets", verbs=verbs)
    return src, params


def probe_source() -> str:
    return (
        'permit (principal is k8s::User, action == k8s::Action::"get", '
        "resource is k8s::Resource) when { "
        f'resource.apiGroup == "{cluster_groups(0)[0]}" && '
        'principal.name == "probe-user" && '
        'resource.resource == "probes" };'
    )


class Corpus:
    """Policy files (name -> text) and the request generator aimed at them."""

    def __init__(self, params: dict, seed: int):
        self.n = int(params["policies"])
        self.clusters = int(params.get("clusters", 10))
        self.seed = seed
        sources = [probe_source()]
        self.params = [None]
        for i in range(1, self.n):
            src, p = policy_source(i, seed, self.clusters)
            sources.append(src)
            self.params.append(p)
        self.files = {}
        for lo in range(0, self.n, POLICIES_PER_FILE):
            name = f"synth-{lo // POLICIES_PER_FILE:03d}.cedar"
            self.files[name] = "\n".join(sources[lo:lo + POLICIES_PER_FILE]) + "\n"
        # the two demo admission policies ride in the same store, as an
        # operator's directory holds both kinds (chip_smoke.py does the same)
        for extra in params.get("extra_files", ()):
            self.files[extra] = (DATA / extra).read_text()
        self._by_cluster = {}

    def _cluster_params(self, cluster: int) -> list:
        got = self._by_cluster.get(cluster)
        if got is None:
            got = [p for p in self.params
                   if p is not None and p["cluster"] in (cluster, -1)]
            self._by_cluster[cluster] = got
        return got

    def spec(self, rng: random.Random, aimed_share: float) -> dict:
        """One SubjectAccessReview spec: ``aimed_share`` of them target a
        generated policy's (group, resource, namespace, verb), the rest
        draw in-universe misses. Clusters are drawn uniformly."""
        cluster = rng.randrange(self.clusters)
        candidates = self._cluster_params(cluster)
        if candidates and rng.random() < aimed_share:
            p = rng.choice(candidates)
            user = p["user"] or f"c{cluster}-user-{rng.randint(0, 499)}"
            groups = [p["team"]] if p["team"] else []
            verb = rng.choice(p["verbs"] or VERBS)
            ns = p["ns"] or f"c{cluster}-ns-{rng.randint(0, 199)}"
            group = p["group"]
            res = p["resource"] or rng.choice(RESOURCES)
        else:
            group = rng.choice(CORE_GROUPS + cluster_groups(cluster))
            user = f"c{cluster}-user-{rng.randint(0, 499)}"
            groups = [f"c{cluster}-team-{rng.randint(0, 99)}"]
            verb = rng.choice(VERBS)
            ns = f"c{cluster}-ns-{rng.randint(0, 199)}"
            res = rng.choice(RESOURCES)
        return {
            "user": user,
            "uid": "u",
            "groups": groups,
            "resourceAttributes": {
                "verb": verb, "group": group, "version": "v1",
                "resource": res, "namespace": ns,
            },
        }


def build(params: dict, seed: int) -> Corpus:
    return Corpus(params, seed)
