"""A corpus that comes in as a file: authorization policies over
non-resource URLs (``/healthz``, ``/apis/...``), which the two corpora of
the package never touch. It imports nothing of the program.

50% get of one path by group, 30% get of one path by user, 20% forbid of
a debug path to everyone.
"""

from __future__ import annotations

import random

TEAMS = 13
USERS = 31
PATHS = 17


def policy_source(rng: random.Random):
    path = f"/apis/group-{rng.randint(0, PATHS - 1)}"
    kind = rng.random()
    if kind < 0.5:
        team = f"team-{rng.randint(0, TEAMS - 1)}"
        src = (
            f'permit (principal in k8s::Group::"{team}", action == k8s::Action::"get", '
            f'resource is k8s::NonResourceURL) when {{ resource.path == "{path}" }};'
        )
        return src, dict(kind="team", team=team, path=path)
    if kind < 0.8:
        user = f"user-{rng.randint(0, USERS - 1)}"
        src = (
            'permit (principal, action == k8s::Action::"get", '
            "resource is k8s::NonResourceURL) when { "
            f'principal.name == "{user}" && resource.path == "{path}" }};'
        )
        return src, dict(kind="user", user=user, path=path)
    path = f"/debug/{rng.randint(0, PATHS - 1)}"
    src = (
        "forbid (principal, action, resource is k8s::NonResourceURL) "
        f'when {{ resource.path == "{path}" }};'
    )
    return src, dict(kind="forbid", path=path)


class Corpus:
    def __init__(self, params: dict, seed: int):
        rng = random.Random(f"{seed}:urls")
        made = [policy_source(rng) for _ in range(int(params["policies"]))]
        self.params = [p for _, p in made]
        self.files = {"urls.cedar": "\n".join(src for src, _ in made) + "\n"}

    def spec(self, rng: random.Random, aimed_share: float) -> dict:
        """One review spec over ``nonResourceAttributes``; ``aimed_share``
        of them take user, group and path from a real policy."""
        p = rng.choice(self.params)
        user = f"user-{rng.randint(0, USERS - 1)}"
        team = f"team-{rng.randint(0, TEAMS - 1)}"
        path = f"/apis/group-{rng.randint(0, PATHS - 1)}"
        if rng.random() < aimed_share:
            user, team, path = p.get("user", user), p.get("team", team), p["path"]
        return {
            "user": user, "uid": user, "groups": [team, "system:authenticated"],
            "nonResourceAttributes": {"path": path, "verb": "get"},
        }


def build(params: dict, seed: int) -> Corpus:
    return Corpus(params, seed)
