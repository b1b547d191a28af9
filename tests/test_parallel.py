"""Multi-chip sharding: the sharded evaluation steps must produce the same
results as the single-device paths on the 8-virtual-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cedar_tpu.compiler.lower import lower_tiers
from cedar_tpu.compiler.pack import pack
from cedar_tpu.lang import PolicySet
from cedar_tpu.ops.match import chunk_rules, match_rules_codes
from cedar_tpu.parallel.mesh import (
    make_mesh,
    shard_codes_tensors,
    sharded_codes_match_fn,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-virtual-device CPU mesh"
)


def _packed():
    import random

    rng = random.Random(5)
    pols = []
    for i in range(300):
        eff = "permit" if rng.random() < 0.8 else "forbid"
        pols.append(
            f'{eff} (principal, action == k8s::Action::"get",'
            " resource is k8s::Resource) when {"
            f' principal.name == "u{rng.randint(0, 40)}" &&'
            f' resource.resource == "r{rng.randint(0, 15)}" }};'
        )
    return pack(lower_tiers([PolicySet.from_source("\n".join(pols), "mesh")]))


def test_make_mesh_axes():
    mesh = make_mesh(8)
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("data", "policy")


def test_sharded_codes_step_matches_single_device():
    packed = _packed()
    table = packed.table
    rng = np.random.default_rng(3)
    B = 64
    codes = np.zeros((B, table.n_slots), dtype=np.int32)
    for s in range(table.n_slots):
        codes[:, s] = rng.integers(0, table.n_rows, size=B)
    extras = np.full((B, 8), packed.L, dtype=np.int32)
    extras[:, 0] = rng.integers(0, packed.L + 64, size=B)

    # single-device reference through the chunked production kernel
    thresh = packed.thresh.astype(np.int32)
    W3, t3, g3, p3 = chunk_rules(
        packed.W, thresh, packed.rule_group, packed.rule_policy,
    )
    ref_words, (ref_first, _ref_count) = match_rules_codes(
        jnp.asarray(codes, jnp.int16),
        jnp.asarray(extras, jnp.int16),
        jnp.asarray(table.rows),
        jnp.asarray(W3),
        jnp.asarray(t3),
        jnp.asarray(g3),
        jnp.asarray(p3),
        packed.n_tiers,
        True,
    )

    mesh = make_mesh(8)
    cargs = shard_codes_tensors(
        mesh,
        jnp.asarray(table.rows),
        jnp.asarray(packed.W),
        jnp.asarray(thresh),
        jnp.asarray(packed.rule_group),
        jnp.asarray(packed.rule_policy),
    )
    step = sharded_codes_match_fn(mesh, packed.n_tiers)
    words, first, _last = step(jnp.asarray(codes), jnp.asarray(extras), *cargs)

    assert (np.asarray(words) == np.asarray(ref_words)).all()
    assert (np.asarray(first) == np.asarray(ref_first)).all()


def _mesh_policy_sources():
    """A policy mix exercising every mesh-relevant plane: multi-match rows
    (bits path), an erroring policy (err groups), and an interpreter
    fallback (gate plane + hybrid merge)."""
    import random

    rng = random.Random(9)
    pols = []
    for i in range(200):
        eff = "permit" if rng.random() < 0.8 else "forbid"
        pols.append(
            f'{eff} (principal, action == k8s::Action::"get",'
            " resource is k8s::Resource) when {"
            f' principal.name == "u{rng.randint(0, 30)}" &&'
            f' resource.resource == "r{rng.randint(0, 10)}" }};'
        )
    # overlapping policies -> genuine multi-match reason sets
    pols.append(
        'permit (principal, action == k8s::Action::"get",'
        ' resource is k8s::Resource) when { resource.resource == "r1" };'
    )
    # error path: unguarded optional attribute access
    pols.append(
        'forbid (principal, action == k8s::Action::"get",'
        ' resource is k8s::Resource) when { resource.namespace == "locked" };'
    )
    # interpreter fallback: an ordered-DNF alternation product past the
    # spillover ceiling (2^12 > SPILL_MAX_CLAUSES) -> gate plane (negated
    # extension calls lower via the host-guard path now); each factor is
    # true for resource "r1", so the policy matches joiners GET r1 rows
    blowup = " && ".join(
        '(resource.resource == "r1" || resource.name == "never")'
        for _ in range(12)
    )
    pols.append(
        'permit (principal in k8s::Group::"joiners",'
        ' action == k8s::Action::"get", resource is k8s::Resource)'
        f" when {{ {blowup} }};"
    )
    return "\n".join(pols)


@pytest.mark.parametrize("shape", [(1, 8), (2, 4), (4, 2)])
def test_engine_mesh_matches_single_device(shape):
    """TPUPolicyEngine(mesh=...) must produce verdict-word and
    decision/diagnostic equality with the single-device engine across
    clean, multi-match, error, and gate-flagged rows."""
    import random

    from cedar_tpu.engine.evaluator import TPUPolicyEngine
    from cedar_tpu.entities.attributes import Attributes, UserInfo
    from cedar_tpu.server.authorizer import record_to_cedar_resource
    from cedar_tpu.compiler.table import encode_request_codes

    src = _mesh_policy_sources()
    tiers = [PolicySet.from_source(src, "meshdiff")]
    single = TPUPolicyEngine()
    single.load(tiers, warm="off")
    meshed = TPUPolicyEngine(mesh=make_mesh(8, shape=shape))
    meshed.load(tiers, warm="off")
    assert meshed.stats["fallback_policies"] == 1

    rng = random.Random(11)
    items = []
    for i in range(96):
        name = f"u{rng.randint(0, 32)}"
        items.append(
            record_to_cedar_resource(
                Attributes(
                    user=UserInfo(
                        name=name,
                        uid="u",
                        groups=("joiners",) if i % 4 == 0 else (),
                    ),
                    verb="get",
                    namespace="locked" if i % 7 == 0 else "default",
                    api_version="v1",
                    resource=f"r{rng.randint(0, 12)}",
                    name=name if i % 6 == 0 else f"x-{i}",
                    resource_request=True,
                )
            )
        )

    # full evaluation parity (decisions + exact reason sets, incl. the
    # interpreter-fallback hybrid merge behind the gate plane)
    got = meshed.evaluate_batch(items)
    want = single.evaluate_batch(items)
    for (g_d, g_diag), (w_d, w_diag) in zip(got, want):
        assert g_d == w_d
        assert {r.policy for r in g_diag.reasons} == {
            r.policy for r in w_diag.reasons
        }

    # raw verdict-word parity through match_arrays (the serving surface)
    packed = single._compiled.packed
    encoded = [
        encode_request_codes(packed.plan, packed.table, em, rq)
        for em, rq in items
    ]
    codes, extras = single._encode_batch_arrays(
        single._compiled, encoded, len(encoded)
    )
    w_single, _ = single.match_arrays(codes, extras)
    w_mesh, _ = meshed.match_arrays(codes, extras)
    assert (w_single == w_mesh).all()


class TestShardPartitionedPlanes:
    """Shard-aware mesh placement (parallel/mesh.py PartitionedPlanes):
    rule capacity scales with the policy-axis device count, decisions
    stay equivalent to the unsharded interpreter oracle, and an
    incremental one-policy edit re-places ONLY the dirty shard's device
    partition (transfer-counter-pinned)."""

    CAP = 256  # per-device packed rule-column budget for these tests

    def _corpus(self):
        from cedar_tpu.corpus.synth import synth_corpus

        return synth_corpus(400, 5, clusters=2)

    def test_capacity_scales_with_devices_and_oracle_equivalence(self):
        from cedar_tpu.corpus.synth import synth_corpus  # noqa: F401
        from cedar_tpu.engine.evaluator import TPUPolicyEngine
        from cedar_tpu.parallel.mesh import MeshCapacityError, make_mesh
        from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

        corpus = self._corpus()
        tiers = corpus.tiers()
        mesh = make_mesh(8)
        eng = TPUPolicyEngine(
            mesh=mesh, name="mesh-cap", mesh_device_rules=self.CAP
        )
        stats = eng.load(tiers, warm="off")
        # the set EXCEEDS one device's packed budget — it serves only
        # because the rule axis spans 8 partitions
        assert stats["R"] > self.CAP
        assert eng.compiled_set._mesh_planes.r_part <= self.CAP
        single = make_mesh(shape=(8, 1))  # all devices on data: 1 partition
        with pytest.raises(MeshCapacityError):
            TPUPolicyEngine(
                mesh=single, name="mesh-1p", mesh_device_rules=self.CAP
            ).load(tiers, warm="off")

        # decision equivalence (incl. exact reason sets through the
        # col_map bits decode) vs the unsharded interpreter oracle
        stores = TieredPolicyStores([MemoryStore("oracle", tiers[0])])
        items = corpus.sar_items(150, cluster=0, seed=11)
        got = eng.evaluate_batch(items)
        want = [stores.is_authorized(em, r) for em, r in items]
        for (g_d, g_diag), (w_d, w_diag) in zip(got, want):
            assert g_d == w_d
            assert {r.policy for r in g_diag.reasons} == {
                r.policy for r in w_diag.reasons
            }

    def test_one_policy_edit_replaces_only_dirty_partition(self):
        from cedar_tpu.engine.evaluator import TPUPolicyEngine
        from cedar_tpu.parallel.mesh import (
            make_mesh,
            mesh_step_build_count,
            placement_transfer_count,
        )
        from cedar_tpu.stores.store import MemoryStore, TieredPolicyStores

        corpus = self._corpus()
        mesh = make_mesh(8)
        eng = TPUPolicyEngine(
            mesh=mesh, name="mesh-edit", mesh_device_rules=self.CAP
        )
        eng.load(corpus.tiers(), warm="off")
        items = corpus.sar_items(60, cluster=0, seed=7)
        eng.evaluate_batch(items)  # compile the serving step pre-edit

        edited = corpus.with_edit()
        t0 = placement_transfer_count()
        s0 = mesh_step_build_count()
        stats = eng.load(edited.tiers(), warm="off")
        assert stats["compile_scope"] == "incremental"
        assert stats["dirty_shards"] == 1
        # ONE partition re-placed: its W/thresh/group/policy slices (the
        # effect flip keeps the activation table byte-identical, so the
        # replicated act_rows reuses its device pieces outright)
        assert placement_transfer_count() - t0 == 4
        # and zero fresh pjit steps — the swap is compile-free
        assert mesh_step_build_count() - s0 == 0
        assert stats["warm_skipped"] is True
        # the dirty shard stayed on its owning partition
        plane = eng.compiled_set.plane
        assert plane.shard_partition  # map exposed for /debug + tests

        # the edited plane answers exactly like the edited oracle (the
        # probe effect flipped; untouched shards' answers unchanged)
        stores = TieredPolicyStores(
            [MemoryStore("oracle2", edited.tiers()[0])]
        )
        probe = edited.probe_request()
        got = eng.evaluate_batch(items + [probe])
        want = [
            stores.is_authorized(em, r) for em, r in items + [probe]
        ]
        assert [g[0] for g in got] == [w[0] for w in want]


def test_graft_dryrun():
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)
