"""Multi-chip BLS verification on the virtual 8-device CPU mesh.

Locks down the one multi-chip design, the per-shard-verdict path
(``lighthouse_tpu/bls/tpu_backend.py::verify_indexed_shards_pershard`` and
its staged form ``verify_staged_pershard``): signature sets split into one
sub-batch per device, the pubkey cache replicated, and each device's
sub-batch checked with its own final exponentiation, so a bad set condemns
only its own shard. Each shard's verdict must equal single-device
verification of that sub-batch (the semantics of
``crypto/bls/src/impls/blst.rs:37-119``).
``__graft_entry__.dryrun_multichip`` runs the same path.
"""

import numpy as np
import pytest

import jax


# JAX compile-heavy tier (see pytest.ini).
pytestmark = pytest.mark.kernel


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import Mesh

    devs = jax.devices()
    assert len(devs) >= 8, "conftest must expose 8 virtual CPU devices"
    return Mesh(np.array(devs[:8]), axis_names=("sets",))


@pytest.fixture(scope="module")
def indexed_fixture():
    from __graft_entry__ import _indexed_fixture

    return _indexed_fixture(16, n_validators=24)


def test_dryrun_multichip_entrypoint():
    """The exact function the driver runs, on the virtual CPU mesh."""
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


# ---------------------------------------------------------------------------
# Per-shard-verdict serving path (the multi-chip firehose tier)
# ---------------------------------------------------------------------------


def _as_shards(items, n_shards):
    per = len(items) // n_shards
    return [items[i * per:(i + 1) * per] for i in range(n_shards)]


def _split(items, sizes):
    """Consecutive shards of the given sizes."""
    ends = np.cumsum(sizes)
    return [items[e - n:e] for n, e in zip(sizes, ends)]


def _with_sig_of(items, i, j):
    """``items`` with set i carrying set j's signature (a wrong signature)."""
    out = list(items)
    ix, msg, _ = out[i]
    out[i] = (ix, msg, items[j][2])
    return out


def _verdicts(cache, shards, mesh):
    from lighthouse_tpu.bls.tpu_backend import verify_indexed_shards_pershard

    oks = verify_indexed_shards_pershard(cache, shards, mesh)
    assert oks.shape == (len(shards),), oks.shape
    return [bool(o) for o in oks]


# Each case runs at the shapes the rest of this file compiles (8 shards,
# cap 4, k_pad 4, the 24-key cache), so none adds a compile.


def _ragged_batch_is_padded(cache, items, mesh):
    shards = [[it] for it in items[:5]] + [[] for _ in range(3)]
    assert _verdicts(cache, shards, mesh) == [True] * 5 + [False] * 3


def _empty_batch_is_false(cache, items, mesh):
    assert _verdicts(cache, [[] for _ in range(8)], mesh) == [False] * 8


def _poisoned_set_condemns_its_shard(cache, items, mesh):
    # set 6 sits in shard 2 of the ragged layout; shard 3 is empty
    shards = _split(_with_sig_of(items, 6, 0), (3, 1, 4, 0, 2, 1, 3, 2))
    expect = [True, True, False, False, True, True, True, True]
    assert _verdicts(cache, shards, mesh) == expect


def _uneven_shards_agree_with_single_chip(cache, items, mesh):
    from lighthouse_tpu.bls.tpu_backend import verify_indexed_sets_device

    shards = _split(_with_sig_of(items, 12, 3), (1, 2, 3, 4, 1, 2, 1, 2))
    oks = _verdicts(cache, shards, mesh)
    assert oks == [verify_indexed_sets_device(cache, sh) for sh in shards]
    assert oks.count(False) == 1, oks


def _keyless_set_condemns_its_shard(cache, items, mesh):
    keyless = list(items)
    _, msg, sig = keyless[6]
    keyless[6] = ([], msg, sig)
    expect = [True] * 8
    expect[3] = False  # 2 sets a shard
    assert _verdicts(cache, _as_shards(keyless, 8), mesh) == expect


def _shard_count_must_match_mesh(cache, items, mesh):
    from lighthouse_tpu.bls.tpu_backend import verify_indexed_shards_pershard

    with pytest.raises(ValueError, match="7 shards for a 8-device mesh"):
        verify_indexed_shards_pershard(cache, _as_shards(items[:14], 7), mesh)


def _shard_over_cap_is_refused(cache, items, mesh):
    from lighthouse_tpu.bls.tpu_backend import stage_indexed_shards

    shards = [items[:5]] + [[] for _ in range(7)]
    with pytest.raises(ValueError, match="shard 0 holds 5 items > cap 4"):
        stage_indexed_shards(shards, 4)


VERDICT_CASES = (
    _ragged_batch_is_padded,
    _empty_batch_is_false,
    _poisoned_set_condemns_its_shard,
    _uneven_shards_agree_with_single_chip,
    _keyless_set_condemns_its_shard,
    _shard_count_must_match_mesh,
    _shard_over_cap_is_refused,
)


class TestPerShardVerdicts:
    @pytest.mark.parametrize(
        "case", VERDICT_CASES, ids=lambda f: f.__name__.lstrip("_")
    )
    def test_verdict_case(self, mesh, indexed_fixture, case):
        cache, items = indexed_fixture
        case(cache, items, mesh)

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
    """LIGHTHOUSE_MESH_DEVICES drives the chain's sharded tier only: the
    generic ``bls.verify_signature_sets`` seam gives the same verdicts with
    it set or unset."""

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
    """The SPMD modules' per-device FLOPs must shrink as the mesh grows at
    fixed batch size: the sets axis is genuinely data-parallel, not
    replicated (SURVEY §2.4 ICI note)."""
    from jax.sharding import Mesh

    from lighthouse_tpu.bls import tpu_backend as tb

    devs = jax.devices()
    n_pad, k_pad, n_val = 32, 4, 16
    flops = {}
    for n_dev in (2, 8):
        mesh = Mesh(np.array(devs[:n_dev]), axis_names=("sets",))
        total = 0.0
        # sum per-device cost over the h2c, prep and verdict stages, every
        # stage of the per-shard path (each device's one final
        # exponentiation is the only work that does not shrink)
        for _, lowered in tb.sharded_stage_lowerings(mesh, n_pad, k_pad, n_val):
            cost = lowered.compile().cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            total += float(cost.get("flops", 0.0))
        flops[n_dev] = total
    assert flops[2] > 0 and flops[8] > 0
    # 4x the devices should cut per-device work substantially
    assert flops[2] / flops[8] > 2.0, flops
