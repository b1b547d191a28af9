"""Long-running native-vs-interpreter fuzz soak (CPU backend).

The in-suite fuzz (tests/test_fuzz_differential.py) pins a handful of
seeds for CI speed; this tool runs the same generators over arbitrary
seed ranges for soak sessions. Round 5's first 150-seed run caught a real
compiler bug the fixed seeds missed (seed 1135: double-unless on one
attribute packed an unsatisfiable clause as a firing rule — commit
d7f75af), so keep soaking new ranges each round.

Usage:
  python tools/fuzz_soak.py
      [--mode single|multitier|admission|mutate|mutate-adm]
      [--start N] [--count N] [--requests N]

Modes single/multitier drive tests/test_fuzz_differential.py's policy +
SAR generators (random policy sets per seed); mode admission drives
tests/test_admission_native.py's AdmissionReview generator (random
request streams over the demo admission set) through the C++ object walk
vs the Python handler path.

Runs on the CPU backend whether or not the host has a chip (the compiler
and the native encoder — the planes fuzz has caught bugs in — are
device-independent; the device kernel is exercised identically on cpu).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time


_FLIPS = (7, "x", ["x"], {"k": "v"}, None, True, 3.5, [], {})


def _flip_nodes(rng, doc):
    """Structured mutation: randomly replace JSON nodes with other-typed
    values — the class byte mutation rarely produces (e.g. "request": 3.5,
    "groups": 7), which found the allow-on-error crash in round 5."""
    import copy

    doc = copy.deepcopy(doc)

    def walk(node):
        if isinstance(node, dict):
            for k in list(node.keys()):
                if rng.random() < 0.06:
                    node[k] = rng.choice(_FLIPS)
                else:
                    walk(node[k])
        elif isinstance(node, list):
            for i in range(len(node)):
                if rng.random() < 0.06:
                    node[i] = rng.choice(_FLIPS)
                else:
                    walk(node[i])

    walk(doc)
    return doc


def _mutate_bytes(rng, b):
    """Random byte-level corruption: splice, delete, overwrite, truncate."""
    b = bytearray(b)
    for _ in range(rng.randint(1, 3)):
        if not b:
            break
        k = rng.random()
        if k < 0.3:
            i = rng.randrange(len(b))
            b[i:i] = bytes(
                rng.randrange(256) for _ in range(rng.randint(1, 4))
            )
        elif k < 0.55:
            i = rng.randrange(len(b))
            del b[i:min(len(b), i + rng.randint(1, 6))]
        elif k < 0.8:
            b[rng.randrange(len(b))] = rng.randrange(256)
        else:
            del b[rng.randrange(len(b)):]
    return bytes(b)


def main() -> int:
    parser = argparse.ArgumentParser(prog="fuzz-soak")
    parser.add_argument("--mode", default="single",
                        choices=["single", "multitier", "admission",
                                 "mutate", "mutate-adm"])
    parser.add_argument("--start", type=int, default=1000)
    parser.add_argument("--count", type=int, default=100)
    parser.add_argument("--requests", type=int, default=60)
    args = parser.parse_args()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from cedar_tpu.jaxenv import force_cpu

    force_cpu()
    sys.path.insert(0, os.path.join(root, "tests"))
    from test_fuzz_differential import (  # noqa: E402
        _gen_attributes,
        _gen_policy,
        _sar_json,
    )

    from cedar_tpu.engine.evaluator import TPUPolicyEngine
    from cedar_tpu.engine.fastpath import SARFastPath
    from cedar_tpu.lang import PolicySet
    from cedar_tpu.native import native_available
    from cedar_tpu.server.authorizer import CedarWebhookAuthorizer
    from cedar_tpu.server.http import get_authorizer_attributes
    from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

    if not native_available():
        print("no C++ toolchain: the native lane cannot be soaked")
        return 2

    t0 = time.time()

    if args.mode == "mutate":
        # byte-mutation fuzz of the C++ parser: random corruptions of
        # valid SAR bodies through authorize_raw must (a) never crash and
        # (b) match the Python lane row for row — the round-5 campaign
        # caught two parser-parity classes this way (invalid UTF-8 and
        # raw control chars evaluated natively, decode-erroring in python)
        rng0 = random.Random(9)
        src = "\n".join(_gen_policy(rng0) for _ in range(20))
        engine = TPUPolicyEngine()
        engine.load([PolicySet.from_source(src, "mut")], warm="off")
        stores = TieredPolicyStores([MemoryStore.from_source("mut", src)])
        fast = SARFastPath(
            engine, CedarWebhookAuthorizer(stores, evaluate=engine.evaluate)
        )
        assert fast.available, "native lane unavailable"
        mutate = _mutate_bytes

        for seed in range(args.start, args.start + args.count):
            rng = random.Random(seed)
            bodies = []
            for i in range(args.requests):
                doc = _sar_json(_gen_attributes(rng))
                b = json.dumps(doc).encode()
                if i % 4 == 1:
                    b = mutate(rng, b)
                elif i % 4 == 2:
                    b = json.dumps(_flip_nodes(rng, doc)).encode()
                bodies.append(b)
            results = fast.authorize_raw(bodies)
            assert len(results) == len(bodies)
            for b, got in zip(bodies, results):
                want = fast._python_fallback(b)
                assert got[0] == want[0] and bool(got[2]) == bool(want[2]), (
                    f"seed={seed} body={b[:200]!r}\n"
                    f"native={got} python={want}"
                )
            done = seed - args.start + 1
            if done % 25 == 0:
                print(f"{done} mutate seeds ok, {time.time() - t0:.0f}s",
                      flush=True)
        print(
            f"SOAK PASS (mutate): {args.count} seeds ok, "
            f"{time.time() - t0:.0f}s"
        )
        return 0

    if args.mode == "mutate-adm":
        # admission twin of mutate: corrupted AdmissionReview bodies
        # (byte mutations AND structured type-flips) through the C++
        # object walk must match the Python handler path on the FULL
        # response document
        from test_admission_native import (  # noqa: E402
            _build,
            _oracle,
            gen_admission_bodies,
        )

        _engine, handler, fast = _build()
        assert fast.available, "native admission lane unavailable"
        for seed in range(args.start, args.start + args.count):
            rng = random.Random(seed)
            bodies = []
            for i, b in enumerate(
                gen_admission_bodies(rng, args.requests)
            ):
                if i % 4 == 1:
                    b = _mutate_bytes(rng, b)
                elif i % 4 == 2:
                    b = json.dumps(
                        _flip_nodes(rng, json.loads(b))
                    ).encode()
                bodies.append(b)
            results = fast.handle_raw(bodies)
            assert len(results) == len(bodies)
            for b, got in zip(bodies, results):
                want = _oracle(handler, b)
                g = got.to_admission_review()
                assert g == want, (
                    f"seed={seed} body={b[:200]!r}\n"
                    f"native={g}\npython={want}"
                )
            done = seed - args.start + 1
            if done % 25 == 0:
                print(
                    f"{done} mutate-adm seeds ok, {time.time() - t0:.0f}s",
                    flush=True,
                )
        print(
            f"SOAK PASS (mutate-adm): {args.count} seeds ok, "
            f"{time.time() - t0:.0f}s"
        )
        return 0

    if args.mode == "admission":
        # random AdmissionReview streams (per-seed rng) over the demo
        # admission set: the C++ object walk vs the Python handler path
        from test_admission_native import (  # noqa: E402
            _build,
            assert_parity,
            gen_admission_bodies,
        )

        _engine, handler, fast = _build()
        # without this, a dead native lane degrades handle_raw to the
        # Python path and the soak compares Python against itself
        assert fast.available, "native admission lane unavailable"
        for seed in range(args.start, args.start + args.count):
            bodies = gen_admission_bodies(
                random.Random(seed), args.requests
            )
            assert_parity(fast, handler, bodies)
            done = seed - args.start + 1
            if done % 25 == 0:
                print(f"{done} admission seeds ok, {time.time() - t0:.0f}s",
                      flush=True)
        print(
            f"SOAK PASS (admission): {args.count} seeds ok, "
            f"{time.time() - t0:.0f}s"
        )
        return 0

    ok = skip = 0
    for seed in range(args.start, args.start + args.count):
        rng = random.Random(seed)
        if args.mode == "multitier":
            n_tiers = rng.randint(2, 3)
            srcs = [
                "\n".join(
                    _gen_policy(rng) for _ in range(rng.randint(4, 15))
                )
                for _ in range(n_tiers)
            ]
        else:
            srcs = ["\n".join(_gen_policy(rng) for _ in range(rng.randint(5, 30)))]
        engine = TPUPolicyEngine()
        engine.load(
            [
                PolicySet.from_source(s, f"s{seed}t{i}")
                for i, s in enumerate(srcs)
            ],
            warm="off",
        )
        stores = TieredPolicyStores(
            [
                MemoryStore.from_source(f"s{seed}t{i}", s)
                for i, s in enumerate(srcs)
            ]
        )
        oracle = CedarWebhookAuthorizer(stores)
        fast = SARFastPath(
            engine, CedarWebhookAuthorizer(stores, evaluate=engine.evaluate)
        )
        if not fast.available:
            skip += 1
            continue
        attrs_list = [_gen_attributes(rng) for _ in range(args.requests)]
        sars = [_sar_json(a) for a in attrs_list]
        bodies = [json.dumps(s).encode() for s in sars]
        results = fast.authorize_raw(bodies)
        # a row-dropping bug must fail the soak, not shorten the zip
        assert len(results) == len(bodies), (seed, len(results), len(bodies))
        for sar, (decision, reason, _e) in zip(sars, results):
            want_dec, want_reason = oracle.authorize(
                get_authorizer_attributes(sar)
            )
            assert decision == want_dec, (
                f"seed={seed} native={decision} interp={want_dec}\n"
                f"sar={sar}\npolicies:\n" + "\n---tier---\n".join(srcs)
            )
            assert bool(reason) == bool(want_reason), (
                f"seed={seed} reason presence mismatch\nsar={sar}\n"
                "policies:\n" + "\n---tier---\n".join(srcs)
            )
        ok += 1
        if ok % 50 == 0:
            print(
                f"{ok} seeds ok, {skip} skipped, {time.time() - t0:.0f}s",
                flush=True,
            )
    print(
        f"SOAK PASS ({args.mode}): {ok} seeds ok, {skip} skipped, "
        f"{time.time() - t0:.0f}s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
