"""What may stand in the place of the chain's batch verifier: the control
and the planted faults that ``correct`` has to catch.

Each is a ``wrap(program_verify, keys) -> verify`` for ``harness.run_cell``.
``verify(items) -> bool`` takes the chain's ``(indices, message,
signature)`` triples, as ``BeaconChain._batch_verify_items`` does. None of
them is used by a benchmark run; ``readings.py`` runs them on the chip and
``tests/test_correctness.py`` on the CPU.
"""

from __future__ import annotations

import functools

from .reference import native


def control(program_verify, keys):
    """The reference in the program's place, with the guarantee that an
    invalid set is never accepted broken: batch verification without the
    random linear combination (every scalar 1), so two signatures whose
    errors cancel in the sum pass."""

    def verify(items) -> bool:
        pks = [native.pubkey_raw(keys.sk_sum(ix)) for ix, _, _ in items]
        return native.verify_sets(pks, [m for _, m, _ in items],
                                  [s for _, _, s in items], [1] * len(items))

    return verify


def half_batch(program_verify, keys):
    """Half of the batch left out: the program verifies the first half
    twice over, at the batch's own size, so the upper half is never read."""

    def verify(items) -> bool:
        kept = list(items[: (len(items) + 1) // 2])
        return program_verify((kept * 2)[: len(items)])

    return verify


def flipped(program_verify, keys):
    """An answer altered where it is produced: every verdict negated."""

    def verify(items) -> bool:
        return not program_verify(items)

    return verify


def no_subgroup_check(program_verify, keys):
    """The program with the G2 subgroup check of its prep stage dropped: a
    set's verdict no longer reads psi(Q) == [x]Q, the rest of the prologue
    (infinity rejection, random scaling, the signature sum) is as built.
    The stage is built anew under the patch, so its first call compiles
    (in the run's set-up, which warms it); every call swaps the patched
    stage in and out, so the program stays whole around it."""
    from lighthouse_tpu.bls import tpu_backend as tb
    from lighthouse_tpu.ops.bls import g1, g2

    prologue = tb._set_prologue

    def unchecked(pk_agg, sig, scalars, valid):
        _, pk_scaled, sig_sum = prologue(pk_agg, sig, scalars, valid)
        set_ok = ~valid | (~g1.is_inf(pk_agg) & ~g2.is_inf(sig))
        return set_ok, pk_scaled, sig_sum

    stage = functools.lru_cache(maxsize=None)(tb._prep_stage.__wrapped__)

    def verify(items) -> bool:
        saved = tb._prep_stage, tb._set_prologue
        tb._prep_stage, tb._set_prologue = stage, unchecked
        try:
            return program_verify(items)
        finally:
            tb._prep_stage, tb._set_prologue = saved

    return verify


WRAPS = {"control": control, "half_batch": half_batch, "flipped": flipped,
         "no_subgroup_check": no_subgroup_check}
