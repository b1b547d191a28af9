"""The selector-1k corpus: mixed authorization policies with when/unless
conditions and label-selector set-contains tests (BASELINE.json config 3).

A copy of ``bench.py build_selector_policy_set`` as source text, with two
stated differences (``assumed`` in the configuration's file): the stream of
random numbers starts from ``--seed`` and not from the constant 7, and the
selector literal's operator is ``"in"`` — what a metav1 ``In`` requirement
becomes on the served path — where bench.py writes ``"="``, which no HTTP
SubjectAccessReview can carry. It imports nothing of the program.

40% group list/watch with ``labelSelector.contains``, 30% forbid-list
unless in one namespace, 30% get by user.
"""

from __future__ import annotations

import random

RESOURCES = ("pods", "secrets", "configmaps", "deployments")
TEAMS = 41
NAMESPACES = 21
USERS = 101


def policy_source(rng: random.Random):
    team = f"team-{rng.randint(0, TEAMS - 1)}"
    res = rng.choice(RESOURCES)
    kind = rng.random()
    if kind < 0.4:
        src = (
            f'permit (principal in k8s::Group::"{team}", action in '
            '[k8s::Action::"list", k8s::Action::"watch"], '
            "resource is k8s::Resource) when { "
            f'resource.resource == "{res}" && '
            "resource has labelSelector && "
            'resource.labelSelector.contains({key: "owner", '
            f'operator: "in", values: ["{team}"]}}) }};'
        )
        return src, dict(kind="selector", team=team, resource=res)
    if kind < 0.7:
        ns = f"ns-{rng.randint(0, NAMESPACES - 1)}"
        src = (
            'forbid (principal, action == k8s::Action::"list", '
            "resource is k8s::Resource) when { "
            f'resource.resource == "{res}" }} unless {{ '
            "resource has namespace && "
            f'resource.namespace == "{ns}" }};'
        )
        return src, dict(kind="forbid", ns=ns, resource=res)
    user = f"user-{rng.randint(0, USERS - 1)}"
    src = (
        'permit (principal, action == k8s::Action::"get", '
        "resource is k8s::Resource) when { "
        f'principal.name == "{user}" && '
        f'resource.resource == "{res}" }};'
    )
    return src, dict(kind="user", user=user, resource=res)


class Corpus:
    def __init__(self, params: dict, seed: int):
        self.n = int(params["policies"])
        rng = random.Random(f"{seed}:selector")
        made = [policy_source(rng) for _ in range(self.n)]
        self.params = [p for _, p in made]
        self.files = {
            "selector.cedar": "\n".join(src for src, _ in made) + "\n"
        }

    def spec(self, rng: random.Random, aimed_share: float) -> dict:
        """One SubjectAccessReview spec mirroring the policy mix: list or
        watch with a label selector, list in a namespace, get by user;
        ``aimed_share`` of them take their attributes from a real policy."""
        aimed = rng.random() < aimed_share
        p = rng.choice(self.params)
        kind = p["kind"]
        user = f"user-{rng.randint(0, USERS - 1)}"
        team = f"team-{rng.randint(0, TEAMS - 1)}"
        ns = f"ns-{rng.randint(0, NAMESPACES - 1)}"
        res = p["resource"] if aimed else rng.choice(RESOURCES)
        ra = {"group": "", "version": "v1", "resource": res, "namespace": ns}
        if kind == "selector":
            if aimed:
                team = p["team"]
            owner = team if aimed else f"team-{rng.randint(0, TEAMS - 1)}"
            ra["verb"] = rng.choice(("list", "watch"))
            reqs = [{"key": "owner", "operator": "In", "values": [owner]}]
            # a second requirement on some requests: wider extras rows
            if rng.random() < 0.3:
                reqs.append({"key": "tier", "operator": "In",
                             "values": [rng.choice(("web", "db", "batch"))]})
            ra["labelSelector"] = {"requirements": reqs}
        elif kind == "forbid":
            ra["verb"] = "list"
            if aimed and rng.random() < 0.5:
                ra["namespace"] = p["ns"]
        else:
            ra["verb"] = "get"
            if aimed:
                user = p["user"]
        return {
            "user": user, "uid": "u", "groups": [team],
            "resourceAttributes": ra,
        }


def build(params: dict, seed: int) -> Corpus:
    return Corpus(params, seed)
