"""The request kind ``sar_memo``: the ``sar`` kind for a stream that repeats.

Everything a SubjectAccessReview is — the request line, the review object,
the mapping onto Cedar entities, the webhook's own rules, the reading of a
response — is ``kinds/sar.py``'s, handed on unchanged. What this kind adds
is that a request's answer by the plain reference is worked out once a
spec: the reference's answer is a function of the policies and the spec
alone, a stream that re-asks sends the same spec many times, and walking
some 8,000 policies takes 9 ms a request. The answers are kept with the
``Reference`` they came from (a control is another ``Reference``), by the
spec's canonical JSON. A kind is part of the reference and imports nothing
of the program.
"""

import json

from benchmark.kinds import sar

PATH = sar.PATH

body = sar.body
distinct = sar.distinct
verdict = sar.verdict
gave_up = sar.gave_up


def expected(reference, spec: dict) -> tuple:
    answers = reference.__dict__.setdefault("_answers_by_spec", {})
    key = json.dumps(spec, sort_keys=True, separators=(",", ":"))
    if key not in answers:
        answers[key] = sar.expected(reference, spec)
    return answers[key]
