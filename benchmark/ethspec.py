"""The consensus-spec arithmetic the traffic is made of, written for the
benchmark alone: committee shuffling and the SSZ signing root of an
AttestationData (consensus-specs phase0: ``compute_shuffled_index``,
``compute_committee``, ``hash_tree_root``, ``compute_domain``,
``compute_signing_root``). The program has its own versions; the benchmark
does not use them, so a change to the program cannot change the traffic or
the expected verdicts."""

from __future__ import annotations

import hashlib

import numpy as np

# BLS12-381 group order
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001

DOMAIN_BEACON_ATTESTER = b"\x01\x00\x00\x00"
_ZERO = bytes(32)


def _sha(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def shuffled_indices(n: int, seed: bytes, rounds: int) -> np.ndarray:
    """[compute_shuffled_index(i, n, seed) for i in range(n)], all indices
    at once: the spec's swap-or-not network, each round vectorised."""
    cur = np.arange(n, dtype=np.int64)
    n_windows = (n + 255) // 256
    for r in range(rounds):
        rb = bytes([r])
        pivot = int.from_bytes(_sha(seed + rb)[:8], "little") % n
        table = np.frombuffer(
            b"".join(_sha(seed + rb + w.to_bytes(4, "little"))
                     for w in range(n_windows)),
            dtype=np.uint8,
        ).reshape(n_windows, 32)
        flip = (pivot + n - cur) % n
        pos = np.maximum(cur, flip)
        byte = table[pos >> 8, (pos & 0xFF) >> 3]
        bit = (byte >> (pos & 7).astype(np.uint8)) & 1
        cur = np.where(bit == 1, flip, cur)
    return cur


def committee_bounds(n: int, slot_in_epoch: int, index: int,
                     committees_per_slot: int, slots_per_epoch: int):
    """[start, end) of committee (slot, index) in the shuffled order."""
    total = committees_per_slot * slots_per_epoch
    ci = slot_in_epoch * committees_per_slot + index
    return n * ci // total, n * (ci + 1) // total


def _u64(x: int) -> bytes:
    return x.to_bytes(8, "little") + bytes(24)


def _merkle(leaves: list[bytes]) -> bytes:
    while len(leaves) > 1:
        leaves = [_sha(leaves[i] + leaves[i + 1])
                  for i in range(0, len(leaves), 2)]
    return leaves[0]


def checkpoint_root(epoch: int, root: bytes) -> bytes:
    return _sha(_u64(epoch) + root)


def attestation_data_root(slot: int, index: int, block_root: bytes,
                          source: tuple[int, bytes],
                          target: tuple[int, bytes]) -> bytes:
    """hash_tree_root(AttestationData): five fields padded to eight leaves."""
    return _merkle([
        _u64(slot), _u64(index), block_root,
        checkpoint_root(*source), checkpoint_root(*target),
        _ZERO, _ZERO, _ZERO,
    ])


def attester_domain(fork_version: bytes, genesis_validators_root: bytes) -> bytes:
    fork_data_root = _sha(fork_version + bytes(28) + genesis_validators_root)
    return DOMAIN_BEACON_ATTESTER + fork_data_root[:28]


def signing_root(object_root: bytes, domain: bytes) -> bytes:
    return _sha(object_root + domain)
