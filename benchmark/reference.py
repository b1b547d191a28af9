"""The plain reference: Cedar authorization of one request's environment.

A straightforward implementation of the semantics the configurations state
— every answer is the Cedar decision (forbid overrides permit, no matching
policy is no opinion) with the set of determining policies as its reason —
written against the Cedar language and importing nothing of the program.
It reads the same ``*.cedar`` files the server loads. What a request *is* —
the webhook's documented mapping of a review object onto principal, action,
resource, context and entities, the rules that answer before Cedar is asked,
and the tuple that is compared — belongs to the request's kind
(``benchmark/kinds/<kind>.py``), which is part of the reference too.

Covered: ``permit``/``forbid``, scopes (``is``, ``in``, ``==``, action
lists), ``when``/``unless``, ``&&`` ``||`` ``!`` ``==`` ``!=`` ``has``
``in``, attribute access, ``contains`` / ``containsAll`` / ``containsAny``,
string, integer and boolean literals, sets, records and entity literals.
Anything else in a policy that could apply to a request is an error, never
a silent skip.

``Reference(files, control=...)`` breaks one stated guarantee on purpose;
that is the control the comparison has to fail (``benchmark/control.py``).
"""

from __future__ import annotations

import json
import re

CONTROLS = ("first_reason_only", "forbid_blind")


class ReferenceError_(Exception):
    """A policy the reference cannot read."""


class EvalError(Exception):
    """A Cedar evaluation error: the policy does not apply."""


# ------------------------------------------------------------------ lexer

_TOKEN = re.compile(
    r"""\s+|//[^\n]*
    |(?P<str>"(?:[^"\\]|\\.)*")
    |(?P<int>\d+)
    |(?P<id>[A-Za-z_][A-Za-z0-9_]*(?:::[A-Za-z_][A-Za-z0-9_]*)*)
    |(?P<op>::|==|!=|<=|>=|&&|\|\||[(){}\[\],;.!<>:])
    """,
    re.X,
)


def tokenize(text: str) -> list:
    """[(kind, value, offset)] with kinds str, int, id, op."""
    out = []
    pos = 0
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if m is None:
            raise ReferenceError_(f"unreadable policy text at {pos}: {text[pos:pos + 30]!r}")
        kind = m.lastgroup
        if kind == "str":
            out.append(("str", json.loads(m.group()), m.start()))
        elif kind == "int":
            out.append(("int", int(m.group()), m.start()))
        elif kind is not None:
            out.append((kind, m.group(), m.start()))
        pos = m.end()
    return out


# ----------------------------------------------------------------- values

class Entity(tuple):
    """(type, id)."""


class Record(frozenset):
    """frozenset of (key, value): equality as Cedar's records."""

    def get_attr(self, key):
        for k, v in self:
            if k == key:
                return v
        raise EvalError(f"record has no attribute {key}")

    def has_attr(self, key) -> bool:
        return any(k == key for k, _ in self)


def record(d: dict) -> Record:
    return Record(d.items())


# ----------------------------------------------------------------- parser

class _Parser:
    def __init__(self, tokens: list):
        self.t = tokens
        self.i = 0

    def peek(self, k=0):
        j = self.i + k
        return self.t[j][:2] if j < len(self.t) else ("eof", None)

    def next(self):
        tok = self.peek()
        self.i += 1
        return tok

    def accept(self, kind, value=None) -> bool:
        k, v = self.peek()
        if k == kind and (value is None or v == value):
            self.i += 1
            return True
        return False

    def expect(self, kind, value=None):
        k, v = self.next()
        if k != kind or (value is not None and v != value):
            raise ReferenceError_(f"expected {value or kind}, found {v!r}")
        return v

    # -- entity literal: Path::"id"
    def entity(self) -> Entity:
        path = self.expect("id")
        self.expect("op", "::")
        return Entity((path, self.expect("str")))

    # -- scope
    def scope_clause(self, var: str) -> dict:
        self.expect("id", var)
        clause = {"is": None, "in": None, "eq": None}
        if self.accept("id", "is"):
            clause["is"] = self.expect("id")
            if self.accept("id", "in"):
                clause["in"] = [self.entity()]
        elif self.accept("id", "in"):
            if self.accept("op", "["):
                members = []
                while not self.accept("op", "]"):
                    members.append(self.entity())
                    self.accept("op", ",")
                clause["in"] = members
            else:
                clause["in"] = [self.entity()]
        elif self.accept("op", "=="):
            clause["eq"] = self.entity()
        return clause

    def policy(self) -> dict:
        k, effect = self.next()
        if k != "id" or effect not in ("permit", "forbid"):
            raise ReferenceError_(f"expected permit or forbid, found {effect!r}")
        self.expect("op", "(")
        principal = self.scope_clause("principal")
        self.expect("op", ",")
        action = self.scope_clause("action")
        self.expect("op", ",")
        resource = self.scope_clause("resource")
        self.expect("op", ")")
        conditions = []
        while True:
            k, v = self.peek()
            if k == "id" and v in ("when", "unless"):
                self.next()
                self.expect("op", "{")
                conditions.append((v, self.expr()))
                self.expect("op", "}")
            else:
                break
        self.expect("op", ";")
        return {"effect": effect, "principal": principal, "action": action,
                "resource": resource, "conditions": conditions}

    # -- expressions, as closures over the request's environment
    def expr(self):
        left = self.and_()
        while self.accept("op", "||"):
            right = self.and_()
            left = _or(left, right)
        return left

    def and_(self):
        left = self.unary()
        while self.accept("op", "&&"):
            right = self.unary()
            left = _and(left, right)
        return left

    def unary(self):
        if self.accept("op", "!"):
            inner = self.unary()
            return lambda env: not _bool(inner(env))
        return self.relation()

    def relation(self):
        left = self.member()
        k, v = self.peek()
        if k == "op" and v in ("==", "!="):
            self.next()
            right = self.member()
            if v == "==":
                return lambda env: left(env) == right(env)
            return lambda env: left(env) != right(env)
        if k == "id" and v == "has":
            self.next()
            k2, attr = self.next()
            if k2 not in ("id", "str"):
                raise ReferenceError_(f"has: expected an attribute, found {attr!r}")
            return lambda env: _has(env, left(env), attr)
        if k == "id" and v == "in":
            self.next()
            right = self.member()
            return lambda env: _in(env, left(env), right(env))
        if k == "op" and v in ("<", "<=", ">", ">="):
            raise ReferenceError_("integer comparisons are not covered by this reference")
        return left

    def member(self):
        node = self.primary()
        while True:
            if self.accept("op", "."):
                name = self.expect("id")
                if self.accept("op", "("):
                    args = []
                    while not self.accept("op", ")"):
                        args.append(self.expr())
                        self.accept("op", ",")
                    node = _method(node, name, args)
                else:
                    node = _attr(node, name)
            elif self.peek() == ("op", "[") and self.peek(1)[0] == "str":
                self.next()
                name = self.expect("str")
                self.expect("op", "]")
                node = _attr(node, name)
            else:
                return node

    def primary(self):
        k, v = self.peek()
        if k == "str" or k == "int":
            self.next()
            return lambda env: v
        if k == "id":
            if v in ("true", "false"):
                self.next()
                val = v == "true"
                return lambda env: val
            if v in ("principal", "action", "resource", "context"):
                self.next()
                return lambda env: env[v]
            if self.peek(1) == ("op", "::"):
                ent = self.entity()
                return lambda env: ent
            raise ReferenceError_(f"unexpected identifier {v!r}")
        if self.accept("op", "("):
            inner = self.expr()
            self.expect("op", ")")
            return inner
        if self.accept("op", "["):
            items = []
            while not self.accept("op", "]"):
                items.append(self.expr())
                self.accept("op", ",")
            return lambda env: frozenset(it(env) for it in items)
        if self.accept("op", "{"):
            fields = []
            while not self.accept("op", "}"):
                k2, name = self.next()
                if k2 not in ("id", "str"):
                    raise ReferenceError_(f"record key expected, found {name!r}")
                self.expect("op", ":")
                fields.append((name, self.expr()))
                self.accept("op", ",")
            return lambda env: Record((n, f(env)) for n, f in fields)
        raise ReferenceError_(f"unexpected token {v!r}")


def _bool(v):
    if v is True or v is False:
        return v
    raise EvalError("type error: expected a boolean")


def _and(left, right):
    return lambda env: _bool(left(env)) and _bool(right(env))


def _or(left, right):
    return lambda env: _bool(left(env)) or _bool(right(env))


def _attrs_of(env, value) -> Record:
    if isinstance(value, Record):
        return value
    if isinstance(value, Entity):
        attrs = env["entities"].get(value)
        if attrs is None:
            raise EvalError(f"entity {value} does not exist")
        return attrs[0]
    raise EvalError("type error: attribute access on a non-record")


def _attr(node, name):
    return lambda env: _attrs_of(env, node(env)).get_attr(name)


def _has(env, value, name) -> bool:
    if isinstance(value, Entity) and value not in env["entities"]:
        return False
    return _attrs_of(env, value).has_attr(name)


def _ancestors(env, ent: Entity) -> frozenset:
    got = env["entities"].get(ent)
    return got[1] if got else frozenset()


def _in(env, left, right) -> bool:
    if not isinstance(left, Entity):
        raise EvalError("type error: in on a non-entity")
    targets = right if isinstance(right, frozenset) else (right,)
    for t in targets:
        if not isinstance(t, Entity):
            raise EvalError("type error: in on a non-entity")
        if left == t or t in _ancestors(env, left):
            return True
    return False


def _method(node, name, args):
    if name not in ("contains", "containsAll", "containsAny") or len(args) != 1:
        raise ReferenceError_(f"method {name} is not covered by this reference")
    arg = args[0]

    def call(env):
        s = node(env)
        a = arg(env)
        if not isinstance(s, frozenset) or isinstance(s, Record):
            raise EvalError(f"type error: {name} on a non-set")
        if name == "contains":
            return a in s
        if not isinstance(a, frozenset) or isinstance(a, Record):
            raise EvalError(f"type error: {name} needs a set")
        return a <= s if name == "containsAll" else bool(a & s)

    return call


def parse_policies(text: str) -> list:
    p = _Parser(tokenize(text))
    out = []
    while p.peek()[0] != "eof":
        out.append(p.policy())
    return out


# ------------------------------------------------------------- evaluation

def _scope(clause: dict):
    """A scope clause as (type or None, frozenset of entities or None): the
    entity's type must equal the first, and the entity or one of its
    ancestors must be in the second."""
    members = None
    if clause["eq"] is not None:
        members = frozenset([clause["eq"]])
    if clause["in"] is not None:
        members = frozenset(clause["in"])
    return clause["is"], members, clause["eq"] is not None


class Reference:
    """The policies of a directory store, and their answer to a request."""

    def __init__(self, files: dict, control: str = ""):
        if control and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        self.control = control
        self.policies = []
        for name in sorted(files):
            if not name.endswith(".cedar"):
                continue
            for i, p in enumerate(parse_policies(files[name])):
                self.policies.append((
                    f"{name}.policy{i}", p["effect"] == "forbid",
                    _scope(p["action"]), _scope(p["principal"]),
                    _scope(p["resource"]),
                    [(kind == "when", cond) for kind, cond in p["conditions"]],
                ))

    def evaluate(self, env: dict) -> tuple:
        """(``"allow"`` | ``"deny"`` | None, the determining policies' ids in
        store order) for one environment: ``principal``, ``action``,
        ``resource``, ``context`` and ``entities`` (entity -> (attributes,
        ancestors)), as the request's kind maps them."""
        # each scope variable as (entity, the entity with its ancestors)
        scoped = []
        for var in ("action", "principal", "resource"):
            ent = env[var]
            scoped.append((ent, _ancestors(env, ent) | {ent}))
        permits, forbids = [], []
        for pid, is_forbid, *scopes, conditions in self.policies:
            for (want_type, members, exact), (ent, closure) in zip(scopes, scoped):
                if want_type is not None and ent[0] != want_type:
                    break
                if members is not None and (
                    ent not in members if exact else members.isdisjoint(closure)
                ):
                    break
            else:
                try:
                    for want, cond in conditions:
                        if _bool(cond(env)) is not want:
                            break
                    else:
                        (forbids if is_forbid else permits).append(pid)
                except EvalError:
                    pass
        if self.control == "forbid_blind":
            forbids = []
        if forbids:
            decision, reasons = "deny", forbids
        elif permits:
            decision, reasons = "allow", permits
        else:
            return (None, [])
        if self.control == "first_reason_only":
            reasons = reasons[:1]
        return (decision, reasons)
