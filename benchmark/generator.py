"""The one traffic generator: a configuration file and a traffic-mix file in,
the run's work out, the same for the same seed.

A mix file (``traffic/<name>.json``) names its ``loop``. The one loop
there is, ``"closed"``, makes an epoch of aggregate attestations, one
signature set per committee (all its members, one aggregate signature),
cut into batches of the configuration's ``sets_per_batch``, as block
import sees them. The window replays the batches in order, pass after
pass.

The mix carries a fixed number of bad signatures, so that a verifier that
says yes to everything, reads part of a batch, or drops one of the checks
the configuration states, fails the comparison:

- ``wrong_key``: sets signed with the key + 1;
- ``cancelling_pairs``: two sets whose signatures are off by +D and -D; a
  pair's sum is right, so only the random linear combination catches it;
- ``non_subgroup``: sets whose signature is the right one plus a point T
  of order 13 (``twist.small_order_point``), on the curve and outside G2;
  only the G2 subgroup check rejects it for sure: a batch verifier without
  it accepts the set whenever its random scalar is a multiple of 13;
- ``infinity``: sets whose signature is the point at infinity.
"""

from __future__ import annotations

import hashlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from . import ethspec
from . import twist
from .keys import Keys
from .reference import native

EPOCH = 8  # the attested epoch; any epoch >= 2 has a decision slot


@dataclass
class Chain:
    """What the traffic's AttestationData refers to."""

    n: int
    slots_per_epoch: int
    committees_per_slot: int
    shuffled: np.ndarray            # validator at each shuffled position
    genesis_validators_root: bytes
    fork_version: bytes

    def committee(self, slot: int, index: int) -> np.ndarray:
        start, end = ethspec.committee_bounds(
            self.n, slot % self.slots_per_epoch, index,
            self.committees_per_slot, self.slots_per_epoch,
        )
        return self.shuffled[start:end]

    def block_root(self, slot: int) -> bytes:
        return hashlib.sha256(b"block" + slot.to_bytes(8, "little")
                              + self.genesis_validators_root).digest()

    def data(self, slot: int, index: int) -> dict:
        epoch = slot // self.slots_per_epoch
        return {
            "slot": slot, "index": index,
            "beacon_block_root": self.block_root(slot),
            "source": (epoch - 1, self.block_root((epoch - 1) * self.slots_per_epoch)),
            "target": (epoch, self.block_root(epoch * self.slots_per_epoch)),
        }

    def message(self, data: dict) -> bytes:
        root = ethspec.attestation_data_root(
            data["slot"], data["index"], data["beacon_block_root"],
            data["source"], data["target"],
        )
        domain = ethspec.attester_domain(self.fork_version,
                                         self.genesis_validators_root)
        return ethspec.signing_root(root, domain)


def make_chain(config: dict, seed: int) -> Chain:
    n = config["validators"]
    seed_bytes = seed.to_bytes(16, "little", signed=True)
    gvr = hashlib.sha256(b"genesis validators root" + seed_bytes).digest()
    shuffling_seed = hashlib.sha256(b"attester shuffling" + seed_bytes).digest()
    return Chain(
        n=n,
        slots_per_epoch=config["slots_per_epoch"],
        committees_per_slot=config["committees_per_slot"],
        shuffled=ethspec.shuffled_indices(
            n, shuffling_seed, config["shuffle_round_count"]
        ),
        genesis_validators_root=gvr,
        fork_version=bytes.fromhex(config["fork_version"]),
    )


@dataclass
class SigSet:
    """One signature set as the chain's batch verifier takes it."""

    indices: list
    message: bytes
    signature: bytes
    sk: int           # secret key of the aggregate public key
    bad: str = ""     # "" or the kind of bad signature (the mix's key)


@dataclass
class Work:
    config: dict
    mix: dict
    chain: Chain
    batches: list = field(default_factory=list)    # [[SigSet]]


def _bad_delta(rng: np.random.Generator) -> tuple[bytes, bytes]:
    """(D, -D): a random G2 point and its negation."""
    h = native.hash_to_g2(rng.bytes(32))
    d = int.from_bytes(rng.bytes(32), "big") % ethspec.R
    return native.g2_mul(h, d), native.g2_mul(h, ethspec.R - d)


def _sign_all(sets: list[SigSet], pool: ThreadPoolExecutor) -> None:
    points = {m: None for m in {s.message for s in sets}}
    for m, h in zip(points, pool.map(native.hash_to_g2, list(points))):
        points[m] = h
    sigs = pool.map(lambda s: native.g2_mul(points[s.message], s.sk), sets)
    for s, sig in zip(sets, sigs):
        s.signature = sig


def _spoil(sets: list[SigSet], bad: dict, rng: np.random.Generator) -> None:
    """Spoil the sets ``bad`` names: ``{kind: [set index, ...]}``, a
    cancelling pair as two consecutive indices."""
    for i in bad["wrong_key"]:
        s = sets[i]
        s.signature = native.g2_mul(native.hash_to_g2(s.message),
                                    (s.sk + 1) % ethspec.R)
    pairs = bad["cancelling_pairs"]
    for i, j in zip(pairs[::2], pairs[1::2]):
        plus, minus = _bad_delta(rng)
        sets[i].signature = native.g2_sum([sets[i].signature, plus])
        sets[j].signature = native.g2_sum([sets[j].signature, minus])
    if bad["non_subgroup"]:
        torsion = twist.compress(twist.small_order_point(rng))
        for i in bad["non_subgroup"]:
            sets[i].signature = native.g2_sum([sets[i].signature, torsion])
    for i in bad["infinity"]:
        sets[i].signature = twist.compress(None)
    for kind, idx in bad.items():
        for i in idx:
            sets[i].bad = kind


def _closed(config, mix, keys, chain, rng, pool) -> Work:
    spe, cps = chain.slots_per_epoch, chain.committees_per_slot
    sets = []
    for s in range(spe):
        slot = EPOCH * spe + s
        for ci in range(cps):
            members = np.sort(chain.committee(slot, ci))
            sets.append(SigSet(
                indices=[int(v) for v in members],
                message=chain.message(chain.data(slot, ci)),
                signature=b"", sk=keys.sk_sum(members),
            ))
    _sign_all(sets, pool)
    per = config["sets_per_batch"]
    if len(sets) % per:
        raise ValueError("an epoch's committees do not fill whole batches")
    n_batches = len(sets) // per
    # each bad set has a batch of its own (a pair shares one), in the first
    # half of the epoch, which every window reaches; a wrong key sits in the
    # upper half of its batch, so that reading half a batch misses it
    kinds = ("wrong_key", "cancelling_pairs", "non_subgroup", "infinity")
    counts = [mix[k] for k in kinds]
    chosen = iter(rng.choice(n_batches // 2, size=sum(counts), replace=False))
    bad = {}
    for kind, n in zip(kinds, counts):
        first = [int(next(chosen)) * per for _ in range(n)]
        if kind == "wrong_key":
            bad[kind] = [b + int(rng.integers(per // 2, per)) for b in first]
        elif kind == "cancelling_pairs":
            bad[kind] = [b + int(p) for b in first
                         for p in rng.choice(per, size=2, replace=False)]
        else:
            bad[kind] = [b + int(rng.integers(per)) for b in first]
    _spoil(sets, bad, rng)
    batches = [sets[i * per:(i + 1) * per] for i in range(n_batches)]
    return Work(config=config, mix=mix, chain=chain, batches=batches)


def build(config: dict, mix: dict, keys: Keys, seed: int,
          pool: ThreadPoolExecutor) -> Work:
    rng = np.random.default_rng([seed, 0x74726166])
    chain = make_chain(config, seed)
    if mix["loop"] == "closed":
        return _closed(config, mix, keys, chain, rng, pool)
    raise ValueError(f"unknown loop {mix['loop']!r}")
