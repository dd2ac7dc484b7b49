"""Device-batched light-client serving engine — the third cryptosystem on
the plan compiler (ISSUE 17).

``verify_light_client_update`` runs one host pairing per session; this
engine folds a whole batch of heterogeneous sessions (distinct periods,
bitfields, attested roots) into ONE device dispatch (see ``ops/lc/verify``
for the math) behind the ``LIGHTHOUSE_LC_BACKEND = auto | device | host``
seam that mirrors the BLS / KZG / epoch / slasher seams:

* ``host``   — the per-session ``verify_light_client_update`` loop (the
  parity oracle).
* ``device`` — the batched graph: bitfield-masked committee aggregation
  over a device-resident per-period pubkey cache, device h2c for the
  signing roots, one shared-accumulator Miller product + one final
  exponentiation per batch. Data-parallel over period groups via the
  PR-10 shard planner when more than one local device is visible.
* ``auto``   — ``device`` iff JAX is backed by an accelerator.

The device path runs under the ``lc_device`` resilience domain (injection
stage ``lc.batch_verify``): ``device_full`` → ``device_reduced`` (split
halves) → ``cpu_oracle`` (the host loop). A fully faulted ladder reports
every session UNVERIFIED — light-client service FAILS CLOSED, a broken
device can never vouch for a session.
"""

from __future__ import annotations

import os
import secrets

import numpy as np

from ..resilience import SupervisedFault, lc_supervisor
from .verify import precheck_update, sync_signing_root, verify_light_client_update

_BACKEND = os.environ.get("LIGHTHOUSE_LC_BACKEND", "auto")
_AUTO_DECISION: bool | None = None


def set_lc_backend(name: str) -> None:
    global _BACKEND, _AUTO_DECISION
    if name not in ("auto", "device", "host"):
        raise ValueError(f"unknown lc backend {name!r}")
    _BACKEND = name
    _AUTO_DECISION = None


def get_lc_backend() -> str:
    return _BACKEND


def _accelerator_present() -> bool:
    global _AUTO_DECISION
    if _AUTO_DECISION is None:
        try:
            import jax

            _AUTO_DECISION = jax.devices()[0].platform in ("tpu", "gpu")
        except Exception:  # noqa: BLE001 — no jax / no devices: host path
            _AUTO_DECISION = False
    return _AUTO_DECISION


def device_backend_active() -> bool:
    if _BACKEND == "host":
        return False
    if _BACKEND == "device":
        return True
    return _accelerator_present()


# --------------------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------------------


class LcEngine:
    """Committee cache + jitted stages for one chain spec's geometry.

    Committee pubkeys are decompressed ONCE per sync committee (keyed by
    the committee's hash tree root) into host projective limb rows; the
    device cache ``[P_pad, C, 3, 25]`` stacks every known committee so a
    batch mixing periods gathers different rows in the same dispatch.
    Stages are jitted separately (the firehose staged-compile lesson —
    one fused program compiled superlinearly)."""

    def __init__(self, spec):
        self.spec = spec
        self.committee_size = int(spec.preset.SYNC_COMMITTEE_SIZE)
        self._rows: dict[bytes, int] = {}    # committee root -> cache row
        self._host_rows: list[np.ndarray] = []
        self._cache = None                   # device [P_pad, C, 3, 25]
        self._cache_rows = 0
        self._jit = {}                       # stage name -> jitted fn

    # -- committee cache ----------------------------------------------------

    def committee_row(self, committee) -> int:
        """Cache row for a sync committee, decompressing its pubkeys on
        first sight (bls.PublicKey validates encodings + subgroup)."""
        key = bytes(type(committee).hash_tree_root(committee))
        row = self._rows.get(key)
        if row is None:
            from .. import bls
            from ..ops.bls import g1

            pts = [
                bls.PublicKey.from_bytes(bytes(pk)).point
                for pk in committee.pubkeys
            ]
            arr = np.asarray(g1.from_oracle_batch(pts))
            row = len(self._host_rows)
            self._rows[key] = row
            self._host_rows.append(arr)
            self._cache = None               # rebuilt (padded) on next use
        return row

    def _cache_arr(self):
        import jax.numpy as jnp

        from ..firehose.sharding import _bucket

        p = len(self._host_rows)
        p_pad = _bucket(p, floor=4)
        if self._cache is None or self._cache_rows != p_pad:
            stacked = np.stack(self._host_rows)
            if p_pad > p:
                pad = np.zeros((p_pad - p,) + stacked.shape[1:], stacked.dtype)
                stacked = np.concatenate([stacked, pad])
            self._cache = jnp.asarray(stacked)
            self._cache_rows = p_pad
            from ..utils import metrics

            metrics.LC_COMMITTEE_CACHE_BYTES.set(stacked.nbytes)
        return self._cache

    # -- jitted stages ------------------------------------------------------

    def _stage(self, name: str):
        fn = self._jit.get(name)
        if fn is None:
            import jax

            from ..ops.lc import verify

            fn = jax.jit(getattr(verify, name))
            self._jit[name] = fn
        return fn

    # -- marshalling --------------------------------------------------------

    def _marshal(self, sessions, genesis_validators_root: bytes, n_pad: int):
        """(update, committee) pairs -> padded device arrays. Signing
        roots and committee rows are host work; pad rows broadcast row 0's
        hash residues (never hash dummy messages) and carry valid=False."""
        import jax.numpy as jnp

        from ..bls.serde import parse_g2_bytes
        from ..ops.bls import h2c
        from ..ops.bls_oracle.ciphersuite import DST

        n = len(sessions)
        c = self.committee_size
        pidx = np.zeros(n_pad, dtype=np.int32)
        bits = np.zeros((n_pad, c), dtype=bool)
        sig_bytes = np.zeros((n_pad, 96), dtype=np.uint8)
        roots = []
        for i, (update, committee) in enumerate(sessions):
            pidx[i] = self.committee_row(committee)
            bits[i] = np.asarray(
                update.sync_aggregate.sync_committee_bits, dtype=bool
            )
            sig_bytes[i] = np.frombuffer(
                bytes(update.sync_aggregate.sync_committee_signature),
                dtype=np.uint8,
            )
            roots.append(
                sync_signing_root(self.spec, update, genesis_validators_root)
            )

        parsed = parse_g2_bytes(sig_bytes)
        sig_wf = parsed["wf_ok"] & ~parsed["is_inf"]
        u0, u1 = h2c.hash_to_field_batch(roots, DST, n_pad)
        scalars = np.array(
            [secrets.randbits(64) or 1 for _ in range(n_pad)], dtype=np.uint64
        )
        valid = np.arange(n_pad) < n
        return (
            jnp.asarray(pidx), jnp.asarray(bits), u0, u1,
            jnp.asarray(parsed["x_c0"]), jnp.asarray(parsed["x_c1"]),
            jnp.asarray(parsed["s_flag"]), jnp.asarray(sig_wf),
            jnp.asarray(scalars), jnp.asarray(valid),
        )

    # -- verify -------------------------------------------------------------

    def _run_one(self, sessions, genesis_validators_root: bytes) -> bool:
        from ..firehose.sharding import _bucket

        n = len(sessions)
        if n == 0:
            return True
        n_pad = _bucket(n, floor=4)
        (pidx, bits, u0, u1, sxc0, sxc1, s_flag, sig_wf, scalars,
         valid) = self._marshal(sessions, genesis_validators_root, n_pad)
        cache = self._cache_arr()
        mxa, mya = self._stage("lc_h2c")(u0, u1)
        pkx, pky, sax, say, set_ok = self._stage("lc_prep")(
            cache, pidx, bits, sxc0, sxc1, s_flag, sig_wf, scalars, valid
        )
        ok = self._stage("lc_pair")(
            pkx, pky, sax, say, mxa, mya, set_ok, valid
        )
        return bool(np.asarray(ok))

    def verify_batch(self, sessions, genesis_validators_root: bytes) -> bool:
        """ONE combined pairing check for the whole batch of
        ``(update, committee)`` sessions — signature verdict only, the
        host prechecks (participation floor, merkle branches) are the
        dispatch layer's job. Splits into per-period-group shards when a
        multi-device mesh is visible (each shard still one check)."""
        n = len(sessions)
        if n == 0:
            return True
        try:
            import jax

            n_dev = jax.local_device_count()
        except Exception:  # noqa: BLE001 — no jax: host semantics
            n_dev = 1
        groups = _period_groups(
            [self.committee_row(c) for _, c in sessions]
        )
        if n_dev > 1 and len(groups) > 1:
            from ..firehose.sharding import plan_shards

            plan = plan_shards(groups, min(n_dev, len(groups)))
            for shard in plan.shard_items:
                if not shard:
                    continue
                if not self._run_one(
                    [sessions[i] for i in shard], genesis_validators_root
                ):
                    return False
            return True
        return self._run_one(sessions, genesis_validators_root)

    # -- instrumentation ----------------------------------------------------

    def compile_probe(self, batch: int, periods: int = 4) -> dict:
        """Trace (don't run) the composed batch graph and report what the
        LOWERED program contains: pairing checks per batch, pairs per
        check, masked aggregation sums. This is the 'one pairing check
        per batch' proof every bench --light-clients record embeds."""
        import functools as _ft

        import jax

        from ..firehose.sharding import _bucket
        from ..ops.bls import fq
        from ..ops.lc import verify

        n_pad, c = _bucket(batch, floor=4), self.committee_size
        u64, sd = np.uint64, jax.ShapeDtypeStruct
        specs = (
            sd((periods, c, 3, 25), u64),       # cache
            sd((n_pad,), np.int32),             # pidx
            sd((n_pad, c), bool),               # bits
            sd((n_pad, 2, 25), u64),            # u0
            sd((n_pad, 2, 25), u64),            # u1
            sd((n_pad, 25), u64),               # sxc0
            sd((n_pad, 25), u64),               # sxc1
            sd((n_pad,), u64),                  # s_flag
            sd((n_pad,), bool),                 # sig_wf
            sd((n_pad,), u64),                  # scalars
            sd((n_pad,), bool),                 # valid
        )
        before = dict(verify.PROBE)
        jax.jit(_ft.partial(verify.lc_batch_check)).lower(*specs)
        checks = verify.PROBE["pairing_checks"] - before["pairing_checks"]
        return {
            "batch": n_pad,
            "committee_size": c,
            "pairing_checks_per_batch_trace": checks,
            "pairs_per_check": (
                (verify.PROBE["pairs"] - before["pairs"]) // max(1, checks)
            ),
            "agg_sums_per_batch_trace": (
                verify.PROBE["agg_sums"] - before["agg_sums"]
            ),
            "conv_impl": fq.conv_backend(),
        }


def _period_groups(rows) -> list[list[int]]:
    """Group batch positions by committee cache row — the shard planner's
    whole-group unit (sessions of one period stay on one device)."""
    by_row: dict[int, list[int]] = {}
    for pos, r in enumerate(rows):
        by_row.setdefault(int(r), []).append(pos)
    return [by_row[r] for r in sorted(by_row)]


# --------------------------------------------------------------------------------------
# Module-level dispatch (the seam the serving tier and the bench call)
# --------------------------------------------------------------------------------------

_engines: dict[str, LcEngine] = {}


def get_engine(spec) -> LcEngine:
    eng = _engines.get(spec.preset.name)
    if eng is None:
        eng = _engines[spec.preset.name] = LcEngine(spec)
    return eng


def _device_verdicts(eng, spec, sessions, gvr, pre_ok, finality_required):
    """Per-session verdicts through the batched engine: host prechecks
    first (sessions failing them are False without touching the device),
    then one combined check over the rest; a failing batch bisects so one
    bad session cannot take honest neighbours down with it."""
    verdicts = list(pre_ok)
    live = [i for i, ok in enumerate(pre_ok) if ok]
    if not live:
        return verdicts

    def descend(idxs):
        if eng.verify_batch([sessions[i] for i in idxs], gvr):
            for i in idxs:
                verdicts[i] = True
            return
        if len(idxs) == 1:
            verdicts[idxs[0]] = False
            return
        mid = len(idxs) // 2
        descend(idxs[:mid])
        descend(idxs[mid:])

    descend(live)
    return verdicts


def verify_update_batch(
    spec, sessions, genesis_validators_root: bytes,
    finality_required: bool = False,
) -> list[bool]:
    """Backend-dispatched batch verification — THE serving entry point.
    ``sessions`` is a list of ``(update, sync_committee)`` pairs; returns
    one verdict per session. Host backend: the per-session oracle loop.
    Device backend: the batched engine under the ``lc_device`` degradation
    ladder; a fully faulted ladder FAILS CLOSED (every session reported
    unverified — never a false-verified session)."""
    gvr = bytes(genesis_validators_root)
    n = len(sessions)
    if n == 0:
        return []
    if not device_backend_active():
        return [
            verify_light_client_update(spec, u, c, gvr, finality_required)
            for u, c in sessions
        ]
    pre_ok = [
        precheck_update(spec, u, finality_required) for u, _ in sessions
    ]

    # engine construction (committee decompression + stage compiles) is
    # deferred INTO the device rungs: a ladder demoted to cpu_oracle — or
    # one whose device rungs fault before running — never pays it
    def device_full():
        return _device_verdicts(
            get_engine(spec), spec, sessions, gvr, pre_ok, finality_required
        )

    def device_reduced():
        # halved batches, fresh scalars: a shape-specific compile or
        # size-dependent numeric fault on the full graph doesn't take the
        # device path down with it
        eng = get_engine(spec)
        mid = max(1, n // 2)
        out = []
        for lo, hi in ((0, mid), (mid, n)):
            if lo == hi:
                continue
            out.extend(
                _device_verdicts(
                    eng, spec, sessions[lo:hi], gvr, pre_ok[lo:hi],
                    finality_required,
                )
            )
        return out

    def cpu_oracle():
        return [
            verify_light_client_update(spec, u, c, gvr, finality_required)
            for u, c in sessions
        ]

    try:
        return list(
            lc_supervisor().run_ladder(
                "lc.batch_verify",
                (
                    ("device_full", device_full),
                    ("device_reduced", device_reduced),
                    ("cpu_oracle", cpu_oracle),
                ),
            )
        )
    except SupervisedFault:
        return [False] * n  # fail CLOSED: never a false-verified session
