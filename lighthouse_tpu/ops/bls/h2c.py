"""Hash-to-curve G2 on device (RFC 9380, suite BLS12381G2_XMD:SHA-256_SSWU_RO_).

Split mirrors the suite's structure: ``hash_to_field`` runs on the host
(9 SHA-256 calls per message) and yields Fq2 limb arrays; everything
algebraic — simplified SWU on the 3-isogenous curve, the 3-isogeny map, and
Budroni–Pintore psi-based cofactor clearing — runs branchless on device over
the whole message batch at once.

The hashing itself is cheap, about 1 ms for 64 messages on a CPU. What
costs is each array sent to the device: built as one small device array per
message and Fq2 element, then stacked and padded on the device, the same
values took about 100 ms of host time and 138 small device programs per
64-message batch on a TPU v5e host. So ``hash_to_field_host`` builds the
whole padded batch as two NumPy arrays, and a caller makes one transfer for
each.

Constants come from the oracle module (``ops.bls_oracle.hash_to_curve``),
which cross-validates them (h_eff vs psi clearing) in its own tests.
Parity: blst's hash-or-encode path used by the reference's sign/verify
(``/root/reference/crypto/bls/src/impls/blst.rs``).
"""

from __future__ import annotations

import hashlib

import jax.numpy as jnp
import numpy as np

from . import curve, fq, g2, plans, tower
from ..bls_oracle import hash_to_curve as _oh
from ..bls_oracle.fields import BLS_X, P, Fq2

# -- host: hash_to_field --------------------------------------------------------------

_L = _oh._L                               # bytes per field element (RFC 9380 5.1)
_LEN_IN_BYTES = 2 * 2 * _L                # two Fq2 elements of two coefficients
_ELL = _LEN_IN_BYTES // 32                # SHA-256 blocks of expand_message_xmd
_LIB_STR = _LEN_IN_BYTES.to_bytes(2, "big")
_Z_PAD = hashlib.sha256(bytes(64))        # the zero block every b_0 starts with
_LIMB_BYTES = fq.NLIMBS * fq.LIMB_BITS // 8


def _expand_message_xmd(msg: bytes, dst_prime: bytes) -> bytes:
    """The oracle's expand_message_xmd at ``_LEN_IN_BYTES``, with the zero
    block hashed once and b_0 ^ b_(i-1) taken on ints, not byte by byte."""
    h = _Z_PAD.copy()
    h.update(msg + _LIB_STR + b"\x00" + dst_prime)
    b0 = h.digest()
    b0_int = int.from_bytes(b0, "big")
    bi = hashlib.sha256(b0 + b"\x01" + dst_prime).digest()
    out = [bi]
    for i in range(2, _ELL + 1):
        x = (b0_int ^ int.from_bytes(bi, "big")).to_bytes(32, "big")
        bi = hashlib.sha256(x + bytes((i,)) + dst_prime).digest()
        out.append(bi)
    return b"".join(out)


def hash_to_field_host(msgs: list[bytes], dst: bytes, n_pad: int | None = None):
    """[n messages] -> (u0, u1), NumPy uint64 [n_pad, 2, 25] each: the
    oracle's ``hash_to_field_fq2(m, dst, 2)`` as canonical 16-bit limbs
    (every value < P, which ``map_to_curve_sswu_fraction`` relies on).
    Rows n..n_pad-1 repeat row 0. Creates no JAX array."""
    n = len(msgs)
    n_pad = n if n_pad is None else n_pad
    if n == 0 or n_pad < n:
        raise ValueError(f"hash_to_field_host: {n} messages, n_pad {n_pad}")
    if len(dst) > 255:
        raise ValueError("expand_message_xmd bounds")
    dst_prime = dst + bytes((len(dst),))
    parts = []
    for m in msgs:
        uniform = _expand_message_xmd(m, dst_prime)
        for off in range(0, _LEN_IN_BYTES, _L):
            e = int.from_bytes(uniform[off : off + _L], "big") % P
            parts.append(e.to_bytes(_LIMB_BYTES, "little"))
    u = np.empty((n_pad, 2, 2, fq.NLIMBS), dtype=np.uint64)
    u[:n] = np.frombuffer(b"".join(parts), "<u2").reshape(n, 2, 2, fq.NLIMBS)
    u[n:] = u[:1]
    u0, u1 = np.ascontiguousarray(np.moveaxis(u, 1, 0))
    return u0, u1


def hash_to_field_batch(msgs: list[bytes], dst: bytes, n_pad: int | None = None):
    """``hash_to_field_host`` as device arrays: one transfer for each."""
    u0, u1 = hash_to_field_host(msgs, dst, n_pad)
    return jnp.asarray(u0), jnp.asarray(u1)


# -- device constants -----------------------------------------------------------------


def _c2(v: Fq2):
    return tower.from_ints([v.c0, v.c1])


_A = _c2(_oh.ISO_A)
_B = _c2(_oh.ISO_B)
_Z = _c2(_oh.SSWU_Z)

_KX_NUM = [_c2(k) for k in _oh._K["x_num"]]
_KX_DEN = [_c2(k) for k in _oh._K["x_den"]]
_KY_NUM = [_c2(k) for k in _oh._K["y_num"]]
_KY_DEN = [_c2(k) for k in _oh._K["y_den"]]


def _bc(c, like):
    return jnp.broadcast_to(c, like.shape[:-2] + (2, fq.NLIMBS))


# -- device: simplified SWU on E' ----------------------------------------------------


def map_to_curve_sswu_fraction(u):
    """u [..., 2, 25] -> (xn, xd, y): x = xn/xd on E' as a FRACTION, y exact.

    The RFC 9380 appendix F.2 straight-line form of 6.6.2: the x-coordinate
    is never inverted (the 3-isogeny consumes the fraction and the final
    projective point absorbs the denominator), and ONE sqrt_ratio chain
    serves both candidates — gx2 = Z^3 u^6 gx1, so the non-square branch's
    root is tv1·u·y1 with no second exponentiation. Replaces the 6.6.2
    direct form's three sequential Fermat chains (inv0, a^((p-3)/4),
    (α+1)^((p-1)/2)) with a single joint chain (tower.fq2_sqrt_ratio).

    ``u`` must be canonical (hash_to_field outputs are) — sgn0(u) reads limb
    parity without a reduction walk."""
    A_M = _bc(_A, u)
    B_M = _bc(_B, u)
    u2 = tower.fq2_sqr(u)
    tv1 = tower.fq2_mul(_bc(_Z, u), u2)                     # Z u^2
    tv2 = plans.carry_norm(tower.fq2_sqr(tv1) + tv1)        # Z^2u^4 + Zu^2
    tv2_nz = ~tower.t_is_zero(tv2)
    one = tower.one(2, u.shape[:-2])
    tv3 = tower.fq2_mul(B_M, plans.carry_norm(tv2 + one))   # x1 numerator
    neg_tv2 = plans.carry_norm(tower.fq2_neg(tv2))
    tv4 = tower.fq2_mul(
        A_M, tower.t_select(tv2_nz, neg_tv2, _bc(_Z, u))
    )                                                       # x1 denominator
    tv3s, tv4s = tower.fq2_mul_many([(tv3, tv3), (tv4, tv4)])
    tv3c, tv4c, t34 = tower.fq2_mul_many(
        [(tv3s, tv3), (tv4s, tv4), (tv4s, tv3)]
    )
    a34, b4c = tower.fq2_mul_many([(t34, A_M), (tv4c, B_M)])
    gx1_num = plans.carry_norm(tv3c + a34 + b4c)  # tv3^3 + A tv3 tv4^2 + B tv4^3
    is_sq, y1 = tower.fq2_sqrt_ratio(gx1_num, tv4c)
    # candidate 2 (gx1 non-square): x2 = tv1 x1, y2 = tv1 u y1
    t1u = tower.fq2_mul(tv1, u)
    y2, x2n = tower.fq2_mul_many([(t1u, y1), (tv1, tv3)])
    xn = tower.t_select(is_sq, tv3, x2n)
    y = tower.t_select(is_sq, y1, y2)
    # u arrives canonical from hash_to_field (host from_ints) — its sgn0
    # needs no reduction walk; y is a fresh multiply output and does. The
    # negation works on the PUB-bounded y directly (borrow-inflated
    # constant): no canonicalization needed before it.
    flip = tower.fq2_sgn0_canon(u) != tower.fq2_sgn0(y)
    y = plans.carry_norm(tower.t_select(flip, tower.fq2_neg(y), y))
    return xn, tv4, y


def map_to_curve_sswu(u):
    """u [..., 2, 25] -> affine (x, y) on the isogenous curve E' (RFC 9380
    6.6.2 semantics). Affine convenience wrapper over the fraction form —
    the production path (map_to_g2) never divides."""
    xn, xd, y = map_to_curve_sswu_fraction(u)
    return tower.fq2_mul(xn, tower.fq2_inv(xd)), y


# -- device: 3-isogeny map ------------------------------------------------------------


def iso_map_fraction(xn, xd, y):
    """E' point with x = xn/xd (fraction) and exact y -> projective E2 point
    [..., 6, 25].

    Each Horner level homogenizes with the matching power of xd:
    P(xn/xd)·xd^3 = ((k3·xn + k2·xd)·xn + k1·xd^2)·xn + k0·xd^3 — the xd^3
    factor is shared by all four polynomials and cancels in the projective
    ratios, so the output formula is unchanged:
    (X:Y:Z) = (x_num' y_den', y y_num' x_den', x_den' y_den'). All four
    acc·xn products and all four k·xd^j products of a level run as ONE
    stacked kernel (fq2_mul_many)."""
    tables = [_KX_NUM, _KX_DEN, _KY_NUM, _KY_DEN]
    max_len = max(len(t) for t in tables)
    zero2 = tower.zero(2)
    tables = [t + [zero2] * (max_len - len(t)) for t in tables]
    xd2 = tower.fq2_sqr(xd)
    xd3 = tower.fq2_mul(xd2, xd)
    xd_pows = [None, xd, xd2, xd3]  # xd^(depth-level)
    accs = [_bc(t[-1], xn) for t in tables]
    for lvl in range(max_len - 2, -1, -1):
        pairs = [(a, xn) for a in accs] + [
            (_bc(t[lvl], xn), xd_pows[max_len - 1 - lvl]) for t in tables
        ]
        prods = tower.fq2_mul_many(pairs)
        accs = [
            plans.carry_norm(p + kx)
            for p, kx in zip(prods[:4], prods[4:])
        ]
    x_num, x_den, y_num, y_den = accs
    xz, yz, zz = tower.fq2_mul_many(
        [(x_num, y_den), (tower.fq2_mul(y, y_num), x_den), (x_den, y_den)]
    )
    return jnp.concatenate([xz, yz, zz], axis=-2)


def iso_map(x, y):
    """Affine E' point -> projective E2 point (degenerate-fraction wrapper)."""
    one = tower.one(2, x.shape[:-2])
    return iso_map_fraction(x, one, y)


# -- device: cofactor clearing (Budroni–Pintore) -------------------------------------


def clear_cofactor(p):
    """[x^2-x-1]P + [x-1]psi(P) + psi^2(2P) with x < 0:
    = [x]([x]P) - [x]P - P + [x]psi(P) - psi(P) + psi^2(2P)
    where [x]Q = -[|x|]Q. psi commutes with scalar multiplication
    ([x]psi(P) = psi([x]P)), so only TWO |x|-chains are needed — they are
    sequentially dependent (x^2 needs xP), which is exactly why this BP form
    beats the joint-axis [x^2-x-1 ; x-1] alternative here: |x| is weight-6
    sparse, so two wNAF chains cost 124 dbl + ~10 add total, the same
    doubling depth as one dense 127-bit chain but a third of its adds and at
    half the kernel width. Each chain runs as a compiled plan
    (chain_plans.scale_fixed_chain via curve.scale_fixed)."""
    xP = curve.scale_fixed(2, p, BLS_X)                # [x]P (sign in plan)
    xxP = curve.scale_fixed(2, xP, BLS_X)              # [x^2]P
    psiP = g2.psi(p)
    xpsiP = g2.psi(xP)                                 # [x]psi(P) = psi([x]P)
    psi2_2P = g2.psi(g2.psi(curve.point_dbl(2, p)))
    acc = curve.point_add(2, xxP, curve.point_neg(2, xP))
    acc = curve.point_add(2, acc, curve.point_neg(2, p))
    acc = curve.point_add(2, acc, xpsiP)
    acc = curve.point_add(2, acc, curve.point_neg(2, psiP))
    return curve.point_add(2, acc, psi2_2P)


# -- full pipeline --------------------------------------------------------------------


def map_to_g2(u0, u1):
    """Device map: two field elements per message -> projective G2 point.
    u0/u1 are stacked into one doubled leading batch so SSWU + the isogeny
    compile (and dispatch) ONCE instead of twice; x-coordinates stay in
    fraction form end-to-end (the projective output absorbs denominators)."""
    u = jnp.stack([u0, u1], axis=0)
    q = iso_map_fraction(*map_to_curve_sswu_fraction(u))
    return clear_cofactor(curve.point_add(2, q[0], q[1]))


def hash_to_curve_g2(msgs: list[bytes], dst: bytes):
    """[n messages] -> [n, 6, 25] projective G2 points (device)."""
    u0, u1 = hash_to_field_batch(msgs, dst)
    return map_to_g2(u0, u1)
