"""The BLS12-381 G2 twist E'(Fp2): y^2 = x^3 + 4(1 + i), in plain Python,
for what the reference library does not offer: points outside the
prime-order subgroup G2 (the traffic's non-subgroup signatures) and
affine sums (the tests' known-answer checks). Affine coordinates, one
inversion a step; a point is ``(x, y)`` with x, y in Fp2 as ``(c0, c1)``,
or None for the point at infinity. Nothing here runs in a measured window.
"""

from __future__ import annotations

from .ethspec import R

P = int("1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f624"
        "1eabfffeb153ffffb9feffffffffaaab", 16)
B = (4, 4)  # 4(1 + i)
# the cofactor of G2 in E'(Fp2): #E'(Fp2) = H2 * R
H2 = int("5d543a95414e7f1091d50792876a202cd91de4547085abaa68a205b2e5a7ddfa"
         "628f1cb4d9e82ef21537e293a6691ae1616ec6e786f0c70cf1c38e31c7238e5", 16)
SMALL_ORDER = 13  # the least prime that divides H2


def add2(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def sub2(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def mul2(a, b):
    return ((a[0] * b[0] - a[1] * b[1]) % P, (a[0] * b[1] + a[1] * b[0]) % P)


def inv2(a):
    d = pow((a[0] * a[0] + a[1] * a[1]) % P, -1, P)
    return (a[0] * d % P, -a[1] * d % P)


def pow2(a, e: int):
    out = (1, 0)
    while e:
        if e & 1:
            out = mul2(out, a)
        a = mul2(a, a)
        e >>= 1
    return out


def sqrt2(a):
    """A square root of ``a`` in Fp2 (p = 3 mod 4), or None."""
    a1 = pow2(a, (P - 3) // 4)
    alpha = mul2(mul2(a1, a1), a)
    x0 = mul2(a1, a)
    if alpha == (P - 1, 0):
        x = mul2((0, 1), x0)
    else:
        x = mul2(pow2(add2((1, 0), alpha), (P - 1) // 2), x0)
    return x if mul2(x, x) == (a[0] % P, a[1] % P) else None


def on_curve(q) -> bool:
    if q is None:
        return True
    x, y = q
    return mul2(y, y) == add2(mul2(mul2(x, x), x), B)


def add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    if p[0] == q[0]:
        if add2(p[1], q[1]) == (0, 0):
            return None
        lam = mul2(mul2((3, 0), mul2(p[0], p[0])), inv2(add2(p[1], p[1])))
    else:
        lam = mul2(sub2(q[1], p[1]), inv2(sub2(q[0], p[0])))
    x = sub2(sub2(mul2(lam, lam), p[0]), q[0])
    return (x, sub2(mul2(lam, sub2(p[0], x)), p[1]))


def mul(q, k: int):
    out = None
    while k:
        if k & 1:
            out = add(out, q)
        q = add(q, q)
        k >>= 1
    return out


def compress(q) -> bytes:
    """The 96-byte compressed form (x.c1 || x.c0, flag bits in the first
    byte), as the wire and the reference library encode G2 points."""
    if q is None:
        return b"\xc0" + bytes(95)
    x, y = q
    big = y[1] > (P - 1) // 2 if y[1] else y[0] > (P - 1) // 2
    out = bytearray(x[1].to_bytes(48, "big") + x[0].to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if big else 0)
    return bytes(out)


def random_point(rng):
    """A point of E'(Fp2) from ``rng`` (numpy Generator), almost surely
    outside G2: map to curve with no cofactor clearing."""
    while True:
        x = tuple(int.from_bytes(rng.bytes(64), "big") % P for _ in range(2))
        y = sqrt2(add2(mul2(mul2(x, x), x), B))
        if y is not None:
            return (x, y)


def small_order_point(rng):
    """A point of order ``SMALL_ORDER``: on the curve, outside G2, and
    killed by a scalar only where the scalar is a multiple of 13. The
    13-part of E'(Fp2) is Z/13 x Z/13 (13^2 divides H2), so the point's
    multiple by the group's order with every factor 13 taken out has
    order 13 or is the infinity."""
    n = H2 * R
    while n % SMALL_ORDER == 0:
        n //= SMALL_ORDER
    while True:
        t = mul(random_point(rng), n)
        if t is not None:
            return t
