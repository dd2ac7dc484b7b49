"""Multi-chip sharded verification on the virtual 8-device CPU mesh.

Locks down the driver's ``dryrun_multichip`` path: the full dp-over-sets
shard_map kernel with cross-device G2-MSM + Fq12-product combines
(``lighthouse_tpu/bls/tpu_backend.py::verify_signature_sets_sharded``), the
semantics of ``crypto/bls/src/impls/blst.rs:37-119``: one valid batch passes,
one poisoned set fails the whole batch.
"""

import numpy as np
import pytest

import jax

from lighthouse_tpu.bls.tpu_backend import verify_signature_sets_sharded
from lighthouse_tpu.ops.bls import g2


# JAX compile-heavy tier (see pytest.ini).
pytestmark = pytest.mark.kernel


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import Mesh

    devs = jax.devices()
    assert len(devs) >= 8, "conftest must expose 8 virtual CPU devices"
    return Mesh(np.array(devs[:8]), axis_names=("sets",))


@pytest.fixture(scope="module")
def example_sets():
    from __graft_entry__ import _example_sets

    return _example_sets(8)


def test_dryrun_multichip_entrypoint():
    """The exact function the driver runs, on the virtual CPU mesh."""
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


def test_sharded_verify_accepts_valid_batch(mesh, example_sets):
    pk, sig, mx, my, _ = example_sets
    assert verify_signature_sets_sharded(pk, sig, mx, my, 8, mesh)


def test_sharded_verify_rejects_poisoned_set(mesh, example_sets):
    pk, sig, mx, my, _ = example_sets
    bad_sig = sig.at[3].set(g2.neg(sig[3]))  # negate one signature
    assert not verify_signature_sets_sharded(pk, bad_sig, mx, my, 8, mesh)


def test_sharded_verify_pads_ragged_batch(mesh, example_sets):
    """Batch smaller than the mesh is padded and masked, not rejected."""
    pk, sig, mx, my, _ = example_sets
    assert verify_signature_sets_sharded(pk[:5], sig[:5], mx[:5], my[:5], 5, mesh)


def test_sharded_verify_empty_batch_is_false(mesh, example_sets):
    pk, sig, mx, my, _ = example_sets
    assert not verify_signature_sets_sharded(pk, sig, mx, my, 0, mesh)


# ---------------------------------------------------------------------------
# Fused gather path (the gossip hot path) sharded over the mesh
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def indexed_fixture():
    from __graft_entry__ import _indexed_fixture

    return _indexed_fixture(16, n_validators=24)


def test_sharded_gather_accepts_ragged_batch(mesh, indexed_fixture):
    """16 sets with ragged key counts (1..3) over 8 devices, cache
    replicated — the mainnet gossip-batch layout at test scale."""
    from lighthouse_tpu.bls.tpu_backend import verify_indexed_sets_sharded

    cache, items = indexed_fixture
    assert verify_indexed_sets_sharded(cache, items, mesh)


def test_sharded_gather_rejects_poisoned_set(mesh, indexed_fixture):
    from lighthouse_tpu.bls.tpu_backend import verify_indexed_sets_sharded

    cache, items = indexed_fixture
    poisoned = list(items)
    ix, msg, _ = poisoned[11]
    _, _, other_sig = poisoned[0]
    poisoned[11] = (ix, msg, other_sig)
    assert not verify_indexed_sets_sharded(cache, poisoned, mesh)


def test_sharded_gather_agrees_with_single_chip(mesh, indexed_fixture):
    from lighthouse_tpu.bls.tpu_backend import (
        verify_indexed_sets_device,
        verify_indexed_sets_sharded,
    )

    cache, items = indexed_fixture
    assert verify_indexed_sets_sharded(cache, items, mesh) == \
        verify_indexed_sets_device(cache, items)


# ---------------------------------------------------------------------------
# Per-shard-verdict serving path (the multi-chip firehose tier, ISSUE 10)
# ---------------------------------------------------------------------------


def _as_shards(items, n_shards):
    per = len(items) // n_shards
    return [items[i * per:(i + 1) * per] for i in range(n_shards)]


class TestPerShardVerdicts:
    def test_valid_batch_all_shards_verify(self, mesh, indexed_fixture):
        from lighthouse_tpu.bls.tpu_backend import (
            verify_indexed_shards_pershard,
        )

        cache, items = indexed_fixture
        oks = verify_indexed_shards_pershard(cache, _as_shards(items, 8), mesh)
        assert oks.shape == (8,) and oks.all(), oks

    def test_poison_condemns_only_its_shard_and_matches_single_device(
        self, mesh, indexed_fixture
    ):
        """Shard-count parity: the per-shard verdict vector over 8 devices
        must be BIT-IDENTICAL to verifying each sub-batch alone on one
        device — including which shard a poisoned set condemns."""
        from lighthouse_tpu.bls.tpu_backend import (
            verify_indexed_sets_device,
            verify_indexed_shards_pershard,
        )

        cache, items = indexed_fixture
        poisoned = list(items)
        ix, msg, _ = poisoned[11]
        poisoned[11] = (ix, msg, poisoned[0][2])  # wrong signature
        shards = _as_shards(poisoned, 8)
        oks = verify_indexed_shards_pershard(cache, shards, mesh)
        bad_shard = 11 // 2  # 2 items per shard
        assert not oks[bad_shard]
        for s, sh in enumerate(shards):
            assert bool(oks[s]) == verify_indexed_sets_device(cache, sh), s

    def test_empty_shards_fail_closed_without_poisoning_others(
        self, mesh, indexed_fixture
    ):
        from lighthouse_tpu.bls.tpu_backend import (
            verify_indexed_shards_pershard,
        )

        cache, items = indexed_fixture
        shards = [items[:2]] + [[] for _ in range(7)]
        oks = verify_indexed_shards_pershard(cache, shards, mesh)
        assert bool(oks[0]) and not oks[1:].any()

    def test_aggregate_3set_groups_parity_with_per_set(self, mesh):
        """Aggregate-shaped 3-set groups through the sharded engine agree
        with per-set verification (the satellite's parity check): group
        atomicity means a shard verdict covers whole groups, and each
        shard's verdict equals the AND of its groups' per-set verdicts."""
        from __graft_entry__ import _indexed_fixture
        from lighthouse_tpu.bls.tpu_backend import (
            verify_indexed_sets_device,
            verify_indexed_shards_pershard,
        )
        from lighthouse_tpu.firehose.sharding import plan_shards

        cache, items = _indexed_fixture(24, n_validators=24)
        groups = [items[3 * g:3 * g + 3] for g in range(8)]  # 3-set groups
        # tamper one set of group 5 (its whole group must condemn)
        ix, msg, _ = groups[5][1]
        groups[5][1] = (ix, msg, groups[0][0][2])
        plan = plan_shards(groups, 8, cap_floor=4)
        oks = verify_indexed_shards_pershard(cache, plan.shard_items, mesh)
        for g, grp in enumerate(groups):
            shard_ok = bool(oks[plan.group_shard[g]])
            per_set = all(
                verify_indexed_sets_device(cache, [it]) for it in grp
            )
            assert shard_ok == per_set, (g, shard_ok, per_set)

    def test_sharded_submit_loop_zero_steady_state_recompiles(
        self, mesh, indexed_fixture
    ):
        """The recompile sentinel over the sharded stage/put/verify loop:
        fixed per-shard shapes mean the steady-state serving tick never
        recompiles (the satellite's sentinel rung)."""
        from lighthouse_tpu.analysis.recompile import steady_state_compiles
        from lighthouse_tpu.bls import tpu_backend as tb

        cache, items = indexed_fixture
        shards = _as_shards(items, 8)
        cap = tb.bucket(max(len(s) for s in shards))

        def step():
            staged = tb.stage_indexed_shards(shards, cap)
            staged = tb.put_staged(staged, mesh)
            oks = tb.verify_staged_pershard(cache, staged, mesh)
            assert oks.all()

        names = steady_state_compiles(step, warmup=1, steps=3)
        assert names == [], names


class TestGenericSeamMeshPath:
    """LIGHTHOUSE_MESH_DEVICES routes the generic ``bls.verify_signature_sets``
    seam over the mesh; verdicts agree with the single-device path."""

    @pytest.fixture()
    def sets(self):
        import hashlib

        from lighthouse_tpu import bls

        sk = bls.SecretKey.from_bytes((11).to_bytes(32, "big"))
        pk = sk.public_key()
        msgs = [
            hashlib.sha256(b"mesh-seam-%02d" % i).digest() for i in range(3)
        ]
        return [
            bls.SignatureSet.single_pubkey(sk.sign(m), pk, m) for m in msgs
        ]

    def test_seam_parity_valid_and_tampered(self, sets, monkeypatch):
        from lighthouse_tpu import bls

        assert bls.get_backend() == "tpu"
        monkeypatch.delenv("LIGHTHOUSE_MESH_DEVICES", raising=False)
        assert bls.verify_signature_sets(sets) is True
        monkeypatch.setenv("LIGHTHOUSE_MESH_DEVICES", "8")
        assert bls.verify_signature_sets(sets) is True
        tampered = [
            bls.SignatureSet.single_pubkey(
                bls.Signature(sets[1].signature.point),  # wrong msg's sig
                sets[0].signing_keys[0],
                sets[0].message,
            )
        ] + sets[1:]
        assert bls.verify_signature_sets(tampered) is False
        monkeypatch.delenv("LIGHTHOUSE_MESH_DEVICES", raising=False)
        assert bls.verify_signature_sets(tampered) is False


@pytest.mark.slow  # two extra cold compiles (~7 min); nightly tier
def test_sharded_gather_per_device_work_drops_with_mesh_size():
    """The SPMD module's per-device FLOPs must shrink as the mesh grows at
    fixed batch size: the sets axis is genuinely data-parallel, not
    replicated (SURVEY §2.4 ICI note)."""
    from jax.sharding import Mesh

    from lighthouse_tpu.bls import tpu_backend as tb

    import jax.numpy as jnp

    devs = jax.devices()
    n_pad, k_pad, n_val = 32, 4, 16
    u = jax.ShapeDtypeStruct((n_pad, 2, 25), jnp.uint64)
    flops = {}
    for n_dev in (2, 8):
        mesh = Mesh(np.array(devs[:n_dev]), axis_names=("sets",))
        total = 0.0
        # sum per-device cost over the sharded h2c + prep + miller stages
        # (every data-parallel stage of the staged kernel; the combine stage
        # is the replicated epilogue and is excluded on both sides)
        for lowered in (
            tb._sharded_h2c_stage(mesh, n_pad).lower(u, u),
            tb._sharded_prep_stage(mesh, n_pad, k_pad).lower(
                jax.ShapeDtypeStruct((n_val, 3, 25), jnp.uint64),
                jax.ShapeDtypeStruct((n_pad, k_pad), jnp.int32),
                jax.ShapeDtypeStruct((n_pad, k_pad), jnp.bool_),
                jax.ShapeDtypeStruct((n_pad, 25), jnp.uint64),
                jax.ShapeDtypeStruct((n_pad, 25), jnp.uint64),
                jax.ShapeDtypeStruct((n_pad,), jnp.uint64),
                jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
                jax.ShapeDtypeStruct((n_pad,), jnp.uint64),
                jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
            ),
            tb._sharded_miller_stage(mesh, n_pad).lower(
                jax.ShapeDtypeStruct((n_pad, 1, 25), jnp.uint64),
                jax.ShapeDtypeStruct((n_pad, 1, 25), jnp.uint64),
                u,
                u,
                jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
            ),
        ):
            cost = lowered.compile().cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            total += float(cost.get("flops", 0.0))
        flops[n_dev] = total
    assert flops[2] > 0 and flops[8] > 0
    # 4x the devices should cut per-device work substantially
    assert flops[2] / flops[8] > 2.0, flops
