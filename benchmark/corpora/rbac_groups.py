"""The rbac-oidc-groups corpus: rbac-tenants' policies, asked by people whose
token carries an identity provider's groups.

The policies are ``benchmark/corpora/rbac.py``'s, by importing it: the same
RBAC objects, the same converter, the same files for the same ``tenants``.
What this module adds is the principal. A kube-apiserver that authenticates
by OpenID Connect puts every group of the ID token's groups claim into the
SubjectAccessReview's ``spec.groups``; Microsoft Entra ID emits up to 200
groups in a JWT before the overage claim replaces them. So a person here
carries

  k policy-known tenant groups   the home tenant's 1-3 as rbac-tenants
                                 draws them; past those, ``<ns>:viewers``
                                 or ``<ns>:developers`` of other tenants,
                                 each tenant drawn by the corpus's Zipf,
                                 without repeats
  groups no policy names         ``idp:`` and a GUID-shaped id, as many as
                                 bring the token's total to a number drawn
                                 from ``token_groups`` (20-200), never past
                                 its upper end
  system:authenticated           which the apiserver appends, last

in the token's order (shuffled once a person), and asks in the namespace of
one of the tenants it has a group in. People are fixed: a tenant's
``users_per_tenant`` people are split among the classes of ``k_classes`` in
proportion to their shares, each person's groups are drawn once from
``--seed`` and the person's name, and a request draws its class by the
shares and then a person of that class — so the shares hold by request.

Every request is a person's and names a resource (``subject_mix`` and
``non_resource_share`` are not this configuration's): service accounts and
the control plane's users do not log in through the identity provider, and
the mix gives every body a name of its own.
"""

from __future__ import annotations

import random

from benchmark.corpora import rbac

ROLE_OF = {"owners": "admin", "developers": "edit", "viewers": "view"}
ELSEWHERE = ("viewers", "developers")  # what a person is given in other tenants


def guid(rng: random.Random) -> str:
    h = f"{rng.getrandbits(128):032x}"
    return f"{h[:8]}-{h[8:12]}-{h[12:16]}-{h[16:20]}-{h[20:]}"


class Corpus(rbac.Corpus):
    def __init__(self, params: dict, seed: int):
        super().__init__(params, seed)
        if self.repeat_share or self.non_resource_share:
            raise ValueError("rbac_groups sends no repeat and no non-resource request")
        self.seed = seed
        self.classes = params["k_classes"]  # [{"share": .., "k": [lo, hi]}, ...]
        self.shares = [float(c["share"]) for c in self.classes]
        # a tenant's Zipf weight, by tenant
        self.weight = {u: self.tenant_weights[r] for r, u in enumerate(self.tenant_order)}
        self.token_groups = tuple(params["token_groups"])  # (least, most) in a token
        # which of a tenant's people are of which class: in proportion
        per = len(self.users[0])
        cuts, acc = [0], 0.0
        for share in self.shares:
            acc += share
            cuts.append(min(per, round(acc * per)))
        self.members = [range(lo, hi) for lo, hi in zip(cuts, cuts[1:])]
        if any(len(m) == 0 for m in self.members):
            raise ValueError(f"{per} people a tenant do not cover every class of k")
        self.people = {}  # (tenant, index) -> (name, groups in token order, {tenant: roles})

    def person(self, t: int, index: int, klass: int) -> tuple:
        """The person ``index`` of tenant ``t``: fixed for the run."""
        key = (t, index)
        if key not in self.people:
            name, home = self.users[t][index]
            rng = random.Random(f"{self.seed}:rbac-groups:{name}")
            lo, hi = self.classes[klass]["k"]
            known = list(home)
            roles = {t: [ROLE_OF[g.rsplit(":", 1)[1]] for g in home]}
            # a class within the home tenant's own three keeps what
            # rbac-tenants drew; the others take the rest of k from other
            # tenants: the busy ones first, as the corpus's Zipf weighs
            # them; a (tenant, group) once
            want = rng.randint(lo, hi) if hi > len(rbac.TENANT_GROUPS) else len(home)
            others = [(u, g) for u in range(len(self.namespaces)) if u != t
                      for g in ELSEWHERE]
            # weighted sampling without replacement (Efraimidis and
            # Spirakis' keys u ** (1 / w), largest first, taken as
            # -log(u) / w, smallest first, so that none underflows)
            others.sort(key=lambda ug: rng.expovariate(1.0) / self.weight[ug[0]])
            for u, g in others[:max(0, want - len(known))]:
                known.append(f"{self.namespaces[u]}:{g}")
                roles.setdefault(u, []).append(ROLE_OF[g])
            least, most = self.token_groups
            total = rng.randint(max(least, len(known)), most)
            groups = known + [f"idp:{guid(rng)}" for _ in range(total - len(known))]
            rng.shuffle(groups)
            self.people[key] = (name, groups + ["system:authenticated"], roles)
        return self.people[key]

    def subject(self, rng: random.Random) -> tuple:
        """(user, groups, the roles it holds in the tenant it asks about,
        that tenant): the home tenant by Zipf, the class of k by the
        shares, a person of that class, and one of the tenants the person
        has a group in."""
        home = self.tenant(rng)
        klass = rng.choices(range(len(self.classes)), self.shares)[0]
        name, groups, roles = self.person(home, rng.choice(self.members[klass]), klass)
        t = rng.choice(list(roles))
        return name, groups, roles[t], t


def build(params: dict, seed: int) -> Corpus:
    return Corpus(params, seed)
