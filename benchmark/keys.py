"""The validator registry of a run, made from ``--seed``.

Validator ``v`` has the secret key ``a[v // m] + b[v % m] (mod r)`` over two
lists of ``m = sqrt(N)`` random base keys. The reference derives every
public key and signature it needs from those numbers alone. The device
registry, the ``[N, 3, 25]`` array the program gathers from, is built the
way a node fills its pubkey cache in bulk (``device_pubkeys_from_raw``)
for the ``2m`` base keys, then widened to all ``N`` keys with one jitted
call of the program's own G1 addition, ``pk_v = A_i + B_j``, normalised to
the cache's affine form (z = 1) by one batched inversion per row. That
takes seconds where ``N`` native key derivations take minutes.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .ethspec import R
from .reference import native

# rows of A widened per step: at 4 x 1024 points a step's temporaries take
# about 0.55 GB on a v5e (compiled for a described chip), below what the
# verify path itself holds, so the registry build does not set the peak
ROWS_PER_STEP = 4


class Keys:
    """Secret keys of an ``n``-validator registry, by validator index."""

    def __init__(self, n: int, seed: int):
        m = math.isqrt(n)
        if m * m != n or m & (m - 1):
            raise ValueError(f"registry size {n} is not the square of a power of two")
        self.n, self.m = n, m
        rng = np.random.default_rng([seed, 0x6B657973])
        self.a = [int.from_bytes(rng.bytes(32), "big") % R for _ in range(m)]
        self.b = [int.from_bytes(rng.bytes(32), "big") % R for _ in range(m)]

    def sk(self, v: int) -> int:
        return (self.a[v // self.m] + self.b[v % self.m]) % R

    def sk_sum(self, members) -> int:
        """Secret key of the aggregate of ``members``: sum of their keys."""
        members = np.asarray(members, dtype=np.int64)
        ca = np.bincount(members // self.m, minlength=self.m)
        cb = np.bincount(members % self.m, minlength=self.m)
        total = sum(int(c) * x for c, x in zip(ca, self.a) if c)
        total += sum(int(c) * x for c, x in zip(cb, self.b) if c)
        return total % R

    def base_pubkeys_raw(self, pool: ThreadPoolExecutor) -> np.ndarray:
        """[2m, 96] raw affine pubkeys of the base keys a || b."""
        raw = list(pool.map(native.pubkey_raw, self.a + self.b))
        return np.frombuffer(b"".join(raw), dtype=np.uint8).reshape(-1, 96)


def _batch_inverse(z):
    """Inverses of ``z [..., L, 25]`` along axis -2 (L a power of two):
    Montgomery's trick as a product tree, one Fermat inversion per leading
    index instead of one per element."""
    import jax.numpy as jnp

    from lighthouse_tpu.ops.bls import fq

    levels = [z]
    while levels[-1].shape[-2] > 1:
        t = levels[-1]
        levels.append(fq.mont_mul(t[..., 0::2, :], t[..., 1::2, :]))
    inv = fq.inv(levels[-1])
    for t in reversed(levels[:-1]):
        left = fq.mont_mul(inv, t[..., 1::2, :])
        right = fq.mont_mul(inv, t[..., 0::2, :])
        inv = jnp.stack([left, right], axis=-2).reshape(t.shape)
    return inv


def widen(a, b):
    """``[m, 3, 25]`` points A and B -> ``[m * m, 3, 25]`` affine points
    ``A[i] + B[j]`` at row ``i * m + j`` (jit it)."""
    import jax

    from lighthouse_tpu.ops.bls import curve, fq, g1

    m = a.shape[0]
    rows = min(m, ROWS_PER_STEP)

    def step(a_rows):
        pts = g1.add(a_rows[:, None], b[None, :])      # [rows, m, 3, 25]
        zi = _batch_inverse(pts[..., 2, :])
        x = fq.canonical(fq.mont_mul(pts[..., 0, :], zi))
        y = fq.canonical(fq.mont_mul(pts[..., 1, :], zi))
        return curve.from_affine(1, x[..., None, :], y[..., None, :])

    steps = a.reshape(m // rows, rows, 3, a.shape[-1])
    return jax.lax.map(step, steps).reshape(m * m, 3, a.shape[-1])


def device_registry(keys: Keys, base_raw: np.ndarray):
    """The ``[N, 3, 25]`` device registry in the pubkey cache's form
    (canonical affine x, y and z = 1): row ``v`` holds ``A[v // m] + B[v % m]``."""
    import jax

    from lighthouse_tpu.beacon_chain.pubkey_cache import device_pubkeys_from_raw

    base = device_pubkeys_from_raw(base_raw)
    return jax.jit(widen)(base[:keys.m], base[keys.m:])
