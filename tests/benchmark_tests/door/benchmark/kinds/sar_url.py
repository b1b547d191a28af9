"""A request kind that comes in as a file: a SubjectAccessReview over
``nonResourceAttributes``, posted as a kube-apiserver posts it — with the
webhook's ``timeout`` in the query string.

The mapping onto Cedar entities, the rules that answer before Cedar, and
the reading of a response are the package's ``sar`` kind's, which covers
non-resource requests; what this kind states itself is the request line and
what makes one body distinct (a non-resource request has no name: the
caller's ``uid``, which is the principal's entity id and nothing a policy
of the corpus tests). A kind is part of the reference and imports nothing
of the program.
"""

from benchmark.kinds import sar

PATH = "/v1/authorize?timeout=30s"

body = sar.body
expected = sar.expected
verdict = sar.verdict
gave_up = sar.gave_up


def distinct(spec: dict, name: str) -> None:
    spec["uid"] = name
