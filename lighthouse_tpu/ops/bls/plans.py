"""Lane-plan compiler: flattens tower algebra into lincomb -> conv -> lincomb -> fold.

A multiplication in Fq2/Fq6/Fq12 is a bilinear map. Karatsuba decomposes it into L
independent base-field products whose operands are small integer linear combinations
of the input coefficients, and whose outputs recombine linearly. This module derives
those linear maps **symbolically at import time** and materializes a tower op as:

    A = lincomb(a)            # [..., L, 25]   (flat adds/subs, no carries)
    B = lincomb(b)
    T = fq._conv_product(A,B) # [..., L, 50]   unreduced accumulators
    out = wide-lincomb(T)     # [..., k, 51]   output map on UNREDUCED limbs
    out = fq.reduce_limbs(out)# congruence-fold reduction, ONE per output row

The output linear maps commute with modular reduction, so recombination happens on
the raw convolution accumulators and only the k output rows are reduced — an Fq12
multiply reduces 12 rows, not its 54 Karatsuba lanes. Reduction itself is the
fold pipeline in fq.py (no sequential Montgomery REDC, two trivial carry scans).

Why one wide kernel: emitting each base-field multiply as its own XLA op cost ~1s
of compile *per instance*; one stacked kernel compiles once and feeds the VPU a
[L * batch]-lane workload.

Subtraction never goes negative: a - b is computed as a + (C - b) where C is a
borrow-inflated multiple of p (every limb of C >= the static per-limb bound of b).
Static bounds (value in units of p, per-limb magnitude) are tracked through every
linear combination and asserted against the lazy operand budget
(value < 1200p, limbs < 2^22 — see fq.py docstring) at plan-build time.

Element layout (little-endian coefficient order, flat over the tower):
    fq2  = [..., 2, 25]   (c0, c1)
    fq6  = [..., 6, 25]   (a0.c0, a0.c1, a1.c0, a1.c1, a2.c0, a2.c1)
    fq12 = [..., 12, 25]  (b0 fq6 | b1 fq6)
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import fq
from ..bls_oracle.fields import P

# --------------------------------------------------------------------------------------
# Static bounds for public elements (enforced by carry_norm after every op)
# --------------------------------------------------------------------------------------

PUB_VALUE_P = 16          # public elements have value < 16 p
PUB_LIMB = fq.PUB_LIMB_TARGET  # ... and 17-bit limbs (limbs 0..23); exact
                          # 16-bit normalization only at comparison sites
PUB_TOP_LIMB = 2          # ... limb 24 <= 2 (value < 16p refines it)

# Lazy operand budget — the SAME constants fq.py's conv pipeline assumes
# (fq._IN_VALUE / fq._IN_LIMB); single source of truth in fq.py.
MAX_VALUE_P = 1200
assert MAX_VALUE_P * P == fq._IN_VALUE
MAX_LIMB = fq._IN_LIMB + 1  # strict bound: limbs < 2^22


class LC:
    """Integer linear combination over a basis (dict idx -> coeff)."""

    __slots__ = ("d",)

    def __init__(self, d=None):
        self.d = {k: v for k, v in (d or {}).items() if v}

    @staticmethod
    def basis(i):
        return LC({i: 1})

    def __add__(self, o):
        d = dict(self.d)
        for k, v in o.d.items():
            d[k] = d.get(k, 0) + v
        return LC(d)

    def __sub__(self, o):
        d = dict(self.d)
        for k, v in o.d.items():
            d[k] = d.get(k, 0) - v
        return LC(d)

    def __neg__(self):
        return LC({k: -v for k, v in self.d.items()})

    def scale(self, k: int):
        return LC({i: v * k for i, v in self.d.items()})

    def __repr__(self):
        return f"LC({self.d})"


# fq2 as [LC, LC]; fq6 as list of 6 LC; fq12 as list of 12 LC.

def v2_add(x, y):
    return [x[0] + y[0], x[1] + y[1]]


def v2_sub(x, y):
    return [x[0] - y[0], x[1] - y[1]]


def v2_nr(x):
    """Multiply by (u+1)."""
    return [x[0] - x[1], x[0] + x[1]]


def v2_neg(x):
    return [-x[0], -x[1]]


def v2_conj(x):
    return [x[0], -x[1]]


def v6_add(x, y):
    return [a + b for a, b in zip(x, y)]


def v6_sub(x, y):
    return [a - b for a, b in zip(x, y)]


def v6_nr(x):
    """Multiply by v: (c0, c1, c2) -> (nr(c2), c0, c1)."""
    return v2_nr(x[4:6]) + x[0:4]


def vbasis(n, off=0):
    return [LC.basis(off + i) for i in range(n)]


# --------------------------------------------------------------------------------------
# Plan builder
# --------------------------------------------------------------------------------------

class Plan:
    """a_rows/b_rows: LCs over the A/B input coefficient bases (B may reference a
    constant pool via indices >= n_b). out_rows: LCs over the lane basis."""

    def __init__(self, n_a: int, n_b: int, consts=None):
        self.n_a = n_a
        self.n_b = n_b
        self.consts = consts or []  # list of Python ints (plain residues)
        self.a_rows: list[LC] = []
        self.b_rows: list[LC] = []
        self.out_rows: list[LC] = []

    def lane(self, va: LC, vb: LC) -> LC:
        self.a_rows.append(va)
        self.b_rows.append(vb)
        return LC.basis(len(self.a_rows) - 1)

    @staticmethod
    def inp(i: int) -> LC:
        """Reference input coefficient i inside an out_row (input pass-through).
        Encoded as negative basis index; execute() remaps onto [lanes | a]."""
        return LC.basis(-(i + 1))

    def mul2(self, x, y):
        """3-lane Karatsuba Fq2 product; returns fq2 over lanes."""
        l0 = self.lane(x[0], y[0])
        l1 = self.lane(x[1], y[1])
        l2 = self.lane(x[0] + x[1], y[0] + y[1])
        return [l0 - l1, l2 - l0 - l1]

    def sqr2(self, x):
        """2-lane Fq2 square (same operand on both sides)."""
        l0 = self.lane(x[0] + x[1], x[0] - x[1])
        l1 = self.lane(x[0], x[1])
        return [l0, l1 + l1]

    def mul6(self, x, y):
        x0, x1, x2 = x[0:2], x[2:4], x[4:6]
        y0, y1, y2 = y[0:2], y[2:4], y[4:6]
        t0 = self.mul2(x0, y0)
        t1 = self.mul2(x1, y1)
        t2 = self.mul2(x2, y2)
        t12 = self.mul2(v2_add(x1, x2), v2_add(y1, y2))
        t01 = self.mul2(v2_add(x0, x1), v2_add(y0, y1))
        t02 = self.mul2(v2_add(x0, x2), v2_add(y0, y2))
        c0 = v2_add(v2_nr(v2_sub(v2_sub(t12, t1), t2)), t0)
        c1 = v2_add(v2_sub(v2_sub(t01, t0), t1), v2_nr(t2))
        c2 = v2_add(v2_sub(v2_sub(t02, t0), t2), t1)
        return c0 + c1 + c2

    def mul12(self, x, y):
        x0, x1 = x[0:6], x[6:12]
        y0, y1 = y[0:6], y[6:12]
        t0 = self.mul6(x0, y0)
        t1 = self.mul6(x1, y1)
        t2 = self.mul6(v6_add(x0, x1), v6_add(y0, y1))
        c0 = v6_add(t0, v6_nr(t1))
        c1 = v6_sub(v6_sub(t2, t0), t1)
        return c0 + c1


# --------------------------------------------------------------------------------------
# Borrow-inflated subtraction constants
# --------------------------------------------------------------------------------------

_SUBC_CACHE: dict[tuple[int, int], tuple[np.ndarray, int]] = {}


def _subc(limb_cover: int, top_cover: int):
    """A constant C = K*p whose borrow-inflated limb representation has every limb
    0..23 >= limb_cover and limb 24 >= top_cover (so C - x never underflows per
    limb for x within those bounds). Returns (limbs uint64[25], K)."""
    key = (limb_cover, top_cover)
    if key in _SUBC_CACHE:
        return _SUBC_CACHE[key]
    # borrow m from each limb into the one below: limbs 1..23 gain m*2^16 - m
    m = max(-(-limb_cover // ((1 << 16) - 1)), 1)
    K = 1
    while True:
        if (K * P).bit_length() > 400:
            raise AssertionError("subc constant exceeds 25 limbs")
        c = [int(v) for v in fq.int_to_limbs(K * P)]
        for i in range(1, 25):
            c[i - 1] += m << 16
            c[i] -= m
        if (
            all(v >= 0 for v in c)
            and all(c[i] >= limb_cover for i in range(24))
            and c[24] >= top_cover
        ):
            assert sum(v << (16 * i) for i, v in enumerate(c)) == K * P
            arr = np.array(c, dtype=np.uint64)
            _SUBC_CACHE[key] = (arr, K)
            return arr, K
        K += 1


# --------------------------------------------------------------------------------------
# Materializer
# --------------------------------------------------------------------------------------

class _Bound:
    """Static (value_p, limb, top_limb) bound triple with exact algebra: bounds
    compose through lazy adds/subs so every borrow-inflated constant provably
    dominates its subtrahend limb-by-limb."""

    __slots__ = ("value_p", "limb", "top")

    def __init__(self, value_p, limb, top):
        self.value_p = value_p
        self.limb = limb
        self.top = top

    def __add__(self, o: "_Bound") -> "_Bound":
        return _Bound(self.value_p + o.value_p, self.limb + o.limb, self.top + o.top)

    def __or__(self, o: "_Bound") -> "_Bound":
        """Elementwise max (either-of)."""
        return _Bound(
            max(self.value_p, o.value_p), max(self.limb, o.limb), max(self.top, o.top)
        )

    def scaled(self, k: int) -> "_Bound":
        return _Bound(self.value_p * k, self.limb * k, self.top * k)


def sub_bound(minuend: "_Bound", subtrahend: "_Bound") -> "_Bound":
    """Bound of minuend + (C - subtrahend) for the _subc constant that covers
    the subtrahend."""
    sc, K = _subc(subtrahend.limb, subtrahend.top)
    return _Bound(
        minuend.value_p + K,
        minuend.limb + int(max(sc[:24])),
        minuend.top + int(sc[24]),
    )


PUB_BOUND = _Bound(PUB_VALUE_P, PUB_LIMB, PUB_TOP_LIMB)
CANON_BOUND = _Bound(1, (1 << 16) - 1, 0)  # canonical values are exact 16-bit
# Lazy chain-interior bound, DERIVED from fq.py's named constants (the
# derivation — why 20-bit limbs / 64p re-enter the conv budget on every
# backend — lives in one place, next to fq.CHAIN_LIMB_TARGET). A fixed
# point of chain steps: outputs at this bound feed the next step's lincombs
# within the lazy budget, skipping the tail of the reduction walk (see
# fq.reduce_limbs). PUB_BOUND inputs are below it, so chains start from
# public values without renormalization.
CHAIN_BOUND = _Bound(
    fq.CHAIN_VALUE_P, fq.CHAIN_LIMB_TARGET, fq.chain_top_limb()
)
# Lazy fq12-interior bound for the pairing chains (Miller accumulator, the
# final exponentiation's cyclotomic runs). CHAIN_BOUND's 20-bit limbs are too
# wide here: the fq12/fq6 plans' input lincombs sum up to ~4 coefficient
# magnitudes plus a borrow-inflated constant, so 2^20-limb inputs would
# overflow the 2^22 conv-input budget. 18-bit limbs at the same 64p value
# compose through every fq12-level lincomb within budget (asserted per plan
# at build time, certified by analysis/bounds.py) while still trimming the
# tail of the reduction walk versus PUB_BOUND (value 64p vs 13p, limbs 2^18
# vs 2^17). The top-limb bound is the same derivation as chain_top_limb():
# limbs are non-negative, so limb 24 <= value >> 384.
F12_BOUND = _Bound(
    fq.CHAIN_VALUE_P,
    (1 << 18) - 1,
    min((1 << 18) - 1, fq.CHAIN_VALUE_LIMIT >> (16 * 24)),
)
assert F12_BOUND.limb <= fq.CHAIN_LIMB_TARGET <= fq._IN_LIMB


def f12_interior():
    """(in/out bound, out_bound kwarg) for fq12 chain interiors, by backend.

    On the digits backend the conv accumulator bound is set by the base-2^8
    digit split (~2^32.6) regardless of input limb width, so running chain
    interiors at F12_BOUND is free on the way in and trims the walk tail on
    the way out. On the f64 backend the accumulator bound grows with the
    input limbs (25 * limb^2): F12_BOUND's extra input bit costs MORE fold
    rounds than its looser target saves (measured ~15% slower per fq12 op),
    so interiors stay at PUB_BOUND and the walk kwarg stays default.

    The "pallas" backend shares the digit-split property (its in-kernel conv
    accumulator bound comes from the base-2^8 digit split, not the input
    limb width), so it takes the digits arm."""
    if fq.conv_backend() in ("digits", "pallas"):
        return F12_BOUND, F12_BOUND
    return PUB_BOUND, None


def _lincomb_bounds(rows: list[LC], bound_for, name: str):
    """Static bound walk of a lincomb: per-row (value_p, limb, top) plus the
    per-row borrow constant covering its negative part. Returns
    (neg_consts [n_rows, 25] uint64, worst _Bound)."""
    consts = np.zeros((len(rows), fq.NLIMBS), dtype=np.uint64)
    worst = _Bound(0, 0, 0)
    for r, lc in enumerate(rows):
        value_p = limb = top = 0
        n_limb = n_top = 0
        any_neg = False
        for idx, c in sorted(lc.d.items()):
            b = bound_for(idx)
            mag = abs(c)
            if c > 0:
                value_p += mag * b.value_p
                limb += mag * b.limb
                top += mag * b.top
            else:
                any_neg = True
                n_limb += mag * b.limb
                n_top += mag * b.top
        if any_neg:
            subc, K = _subc(n_limb, n_top)
            consts[r] = subc
            value_p += K
            limb += int(max(subc[:24]))
            top += int(subc[24])
        assert fq._cert(
            "lincomb_value_budget", value_p, MAX_VALUE_P - 1, note=name
        ), f"{name}: value bound {value_p}p exceeds budget"
        assert fq._cert(
            "lincomb_limb_budget", limb, MAX_LIMB - 1, note=name
        ), f"{name}: limb bound {limb} exceeds 2^22"
        worst.value_p = max(worst.value_p, value_p)
        worst.limb = max(worst.limb, limb)
        worst.top = max(worst.top, top)
    return consts, worst


def _lincomb_matrices(rows: list[LC], n_in: int):
    """Split the integer row matrix into positive / negative-magnitude halves
    (M_pos - M_neg)."""
    m_pos = np.zeros((len(rows), n_in), dtype=np.uint64)
    m_neg = np.zeros((len(rows), n_in), dtype=np.uint64)
    for r, lc in enumerate(rows):
        for idx, c in lc.d.items():
            if c > 0:
                m_pos[r, idx] = c
            else:
                m_neg[r, idx] = -c
    return m_pos, m_neg


def _contract(m, x, dt):
    """m @ x over x's row axis (-2), in dtype dt: [..., n, w] -> [..., r, w].
    u64 has no dot on TPU (XLA's X64 rewriter has no dot_general rule), so
    the u64 form is an unrolled broadcast-multiply-sum."""
    if dt == jnp.uint64:
        return sum(
            x[..., i : i + 1, :] * m[:, i, None]
            for i in range(m.shape[1])
            if m[:, i].any()
        )
    dn = (((1,), (x.ndim - 2,)), ((), ()))
    out = jax.lax.dot_general(
        jnp.asarray(m, dtype=dt), x.astype(dt), dn, preferred_element_type=dt
    )
    return jnp.moveaxis(out, 0, -2)


def _apply_matrices(m_pos, m_neg, consts, x, dt):
    """rows @ x as two constant-matrix contractions plus the borrow
    constants: out[..., r, :] = (M_pos @ x) + (C_r - M_neg @ x). The dot form
    emits ~5 HLO ops per lincomb where the term-by-term form emitted hundreds
    (slice + scale + add per coefficient) — program size was the r3 compile
    bottleneck.

    An f64 operand contracts in f64 (exact below 2^53, asserted by the
    callers) — the CPU pipeline stays off u64 multiplies, which have no SIMD
    path there. An integer operand contracts in ``dt``, which the caller
    picks from its own bounds; the result keeps x's dtype."""
    if x.dtype == jnp.float64:
        dt = jnp.float64
    pos = _contract(m_pos, x, dt)
    if m_neg.any():
        pos = pos + (jnp.asarray(consts, dtype=dt) - _contract(m_neg, x, dt))
    return pos.astype(x.dtype)


def lincomb(rows: list[LC], x, in_bound: _Bound, name: str = "", bound_for=None) -> tuple:
    """Materialize rows of linear combinations of x[..., n, 25]. Returns
    (stacked [..., L, 25], out_bound). ``bound_for(idx)`` optionally gives a
    per-index input bound (default: in_bound for all indices)."""
    bound_for = bound_for or (lambda _i: in_bound)
    consts, worst = _lincomb_bounds(rows, bound_for, name)
    m_pos, m_neg = _lincomb_matrices(rows, x.shape[-2])
    # an integer x contracts in u32: lincomb_limb_budget holds every partial
    # sum, borrow constant and read input below 2^22
    return _apply_matrices(m_pos, m_neg, consts, x, jnp.uint32), worst


def append_const_pool(plan: Plan, b):
    """Concatenate the plan's constant pool onto the B operand — the pool
    append ORDER defines what plan.b_rows indices >= n_b mean, so both
    executors (the XLA path below and pallas_kernels.execute_plan) must go
    through this one helper."""
    if not plan.consts:
        return b
    cpool = jnp.asarray(np.stack([fq.int_to_limbs(c) for c in plan.consts]))
    cpool = jnp.broadcast_to(cpool, b.shape[:-2] + cpool.shape)
    return jnp.concatenate([b, cpool.astype(b.dtype)], axis=-2)


def remap_passthrough_rows(plan: Plan, n_lanes: int) -> list[LC]:
    """Out rows with Plan.inp() pass-through references remapped onto the
    [lanes | a] concatenated basis (negative index -(i+1) -> n_lanes + i).
    The addressing convention is shared by both executors — one definition."""
    return [
        LC({(i if i >= 0 else n_lanes - 1 - i): c for i, c in lc.d.items()})
        for lc in plan.out_rows
    ]


# Raw (non-domain) limbs of 2^384 mod p: folds limb-24 excess back below 2^384.
# Works on Montgomery-coded values too — the fold is a congruence on the coded value.
_RT384 = jnp.asarray(fq.int_to_limbs((1 << 384) % P))


def _verify_carry_norm_schedule(n_folds: int) -> None:
    """Import-time proof that the carry_norm schedule lands on PUB_BOUND for
    ANY input within the lazy budget (limbs < 2^22, value < 1200p): walk the
    per-limb/value bounds through each round+fold with exact integers."""
    limbs = [MAX_LIMB - 1] * fq.NLIMBS
    value = MAX_VALUE_P * P
    rt = [int(v) for v in fq._RT384_NP]
    rt_val = fq._RT384_VAL
    for _ in range(n_folds):
        # carry-save round (width-preserving; value invariant)
        carried = [0] + [b >> 16 for b in limbs[:-1]]
        limbs = [min(b, 0xFFFF) + c for b, c in zip(limbs, carried)]
        limbs = [min(b, value >> (16 * i)) for i, b in enumerate(limbs)]
        # fold the 2^384 excess: new value <= (value below 2^384) + top * rt_val
        top = limbs[24]
        assert fq._cert(
            "carry_norm_fold_nowrap",
            top * max(rt) + max(limbs[:24]),
            (1 << 64) - 1,
            note="carry_norm",
        )
        lo_val = sum(b << (16 * i) for i, b in enumerate(limbs[:24]))
        value = min(lo_val, value) + top * rt_val
        limbs = [b + top * rt[i] for i, b in enumerate(limbs[:24])] + [
            top * rt[24]
        ]
        limbs = [min(b, value >> (16 * i)) for i, b in enumerate(limbs)]
    # final round
    carried = [0] + [b >> 16 for b in limbs[:-1]]
    limbs = [min(b, 0xFFFF) + c for b, c in zip(limbs, carried)]
    limbs = [min(b, value >> (16 * i)) for i, b in enumerate(limbs)]
    assert fq._cert(
        "carry_norm_value", value, PUB_VALUE_P * P - 1, note="carry_norm"
    ), f"carry_norm value bound {value / P}p"
    assert fq._cert(
        "carry_norm_limb", max(limbs), PUB_LIMB, note="carry_norm"
    ), f"carry_norm limb bound {max(limbs):#x}"
    assert fq._cert(
        "carry_norm_top_limb", limbs[24], PUB_TOP_LIMB, note="carry_norm"
    )


_CARRY_NORM_FOLDS = 3
_verify_carry_norm_schedule(_CARRY_NORM_FOLDS)


def carry_norm(x):
    """Restore public bounds (value < 16p, 17-bit limbs, top limb <= 2) for any
    input within the lazy budget: alternate width-preserving carry-save rounds
    with folds of the 2^384-and-up excess through (2^384 mod p). The schedule
    is proved at import time by _verify_carry_norm_schedule — and it is fully
    elementwise (~25 HLO ops), where the previous exact-walk version cost
    three lax.scans per call site."""
    for _ in range(_CARRY_NORM_FOLDS):
        x = fq._carry_rounds(x, 1)
        top = x[..., 24]
        x = x * fq._MASK_NO24 + top[..., None] * _RT384
    return fq._carry_rounds(x, 1)


_SUBC_WIDE_CACHE: dict[tuple[int, int], np.ndarray] = {}


def _subc_wide(n_limbs: int, cover: int) -> np.ndarray:
    """A constant == 0 mod p in n_limbs-limb space with every limb >= cover
    (subtraction cover for unreduced convolution accumulators)."""
    key = (n_limbs, cover)
    if key not in _SUBC_WIDE_CACHE:
        c = [cover] * n_limbs
        adj = (-sum(v << (16 * i) for i, v in enumerate(c))) % P
        for i in range(fq.NLIMBS):
            c[i] += (adj >> (16 * i)) & 0xFFFF
        assert sum(v << (16 * i) for i, v in enumerate(c)) % P == 0
        assert max(c) < 1 << 63
        _SUBC_WIDE_CACHE[key] = np.array(c, dtype=np.uint64)
    return _SUBC_WIDE_CACHE[key]


def execute(
    plan: Plan, a, b, in_bound_a=PUB_BOUND, in_bound_b=PUB_BOUND, name="",
    out_bound: "_Bound | None" = None,
):
    """Run a plan: returns [..., n_out, 25] public-bounded output.

    The output linear maps commute with reduction, so they run on the
    *unreduced* convolution accumulators: conv -> out-lincomb (wide limbs) ->
    ONE congruence-fold reduction per OUTPUT row. An Fq12 multiply reduces 12
    rows instead of its 54 Karatsuba lanes, and the fold reduction already
    lands on plans.PUB_BOUND — no trailing carry_norm.

    ``out_bound=CHAIN_BOUND`` requests the lazier chain-interior target
    instead (shorter reduction walk; see fq.reduce_limbs) — used by
    chain_plans for the interiors of fixed-exponent scans.

    On the f64 conv backend (CPU), at row counts where the f64 walk wins
    (fq.F64_WALK_MIN_ROWS), the ENTIRE pipeline — input lincombs,
    convolution, out-lincomb, reduction walk — runs in f64 and only the
    final reduced limbs are cast back to u64: u64 multiplies have no x86
    SIMD path and dominated the execute cost. Exactness: every intermediate
    bound is asserted below the 2^53 f64 integer cap.

    On the "pallas" backend the pipeline after the input lincombs — conv,
    out-lincomb, fold, carry — runs as ONE fused Pallas kernel
    (pallas_kernels.execute_plan); bounds are tracked in digit space there."""
    if fq.conv_backend() == "pallas":
        from . import pallas_kernels

        return pallas_kernels.execute_plan(
            plan, a, b, in_bound_a, in_bound_b, name, out_bound
        )
    lane_rows = fq._static_rows(a[..., 0, :]) * len(plan.a_rows)
    if fq.conv_backend() == "f64" and lane_rows >= fq.F64_WALK_MIN_ROWS:
        a = a.astype(jnp.float64)
        b = b.astype(jnp.float64)
    A, ba = lincomb(plan.a_rows, a, in_bound_a, name + ".A")
    b = append_const_pool(plan, b)
    B, bb = lincomb(plan.b_rows, b, in_bound_b, name + ".B")
    T = fq._conv_product_keep(A, B)  # [..., L, 50] unreduced accumulators
    conv_limb = max(fq.conv_limb_bounds(ba.limb, bb.limb))
    cap = fq._cap_of(T)
    assert fq._cert(
        "execute_conv_acc", conv_limb, (1 << 63) - 1, note=name
    ), f"{name}: conv accumulator overflow"
    # a carry round caps limbs (~2^33) so out-row accumulation and
    # subtraction covers stay inside the dtype cap (f64: 2^53) — SKIPPED
    # when the raw conv bounds already fit (common for lazy chain interiors,
    # whose tighter inputs leave headroom): a row's accumulator is at most
    # sum(|coeff|) * lane_limb for the positive part plus a borrow constant
    # that itself covers the negative part, so 2x the full coefficient sum
    # dominates both
    coeff_sum = max(
        (sum(abs(c) for c in lc.d.values()) for lc in plan.out_rows),
        default=1,
    )
    if 2 * coeff_sum * conv_limb + (1 << 20) < cap:
        lane_limb = conv_limb
    else:
        T = fq._carry_round_array(T)  # [..., L, 51]
        lane_limb = (1 << 16) + (conv_limb >> 16)
    n_wide = T.shape[-1]
    L = len(plan.a_rows)
    has_passthrough = any(i < 0 for lc in plan.out_rows for i in lc.d)
    if has_passthrough:
        # pass-through rows reference `a`: zero-pad it into the wide space
        pad = [(0, 0)] * (a.ndim - 1) + [(0, n_wide - a.shape[-1])]
        T = jnp.concatenate([T, jnp.pad(a, pad).astype(T.dtype)], axis=-2)
        out_rows = remap_passthrough_rows(plan, L)
    else:
        out_rows = plan.out_rows
    worst_limb = 0
    consts = np.zeros((len(out_rows), n_wide), dtype=np.uint64)
    for r, lc in enumerate(out_rows):
        limb = n_limb = 0
        any_neg = False
        for idx, c in sorted(lc.d.items()):
            lb = lane_limb if idx < L else in_bound_a.limb
            mag = abs(c)
            if c > 0:
                limb += mag * lb
            else:
                any_neg = True
                n_limb += mag * lb
        if any_neg:
            subc = _subc_wide(n_wide, n_limb)
            consts[r] = subc
            limb += int(subc.max())
        assert fq._cert(
            "execute_wide_acc", limb, cap - 1, note=name
        ), f"{name}: wide accumulator bound 2^{limb.bit_length()}"
        worst_limb = max(worst_limb, limb)
    m_pos, m_neg = _lincomb_matrices(out_rows, T.shape[-2])
    # execute_wide_acc bounds the wide accumulators by T's cap, not 2^32
    out = _apply_matrices(m_pos, m_neg, consts, T, jnp.uint64)
    value_bound = sum(worst_limb << (16 * i) for i in range(n_wide))
    if out_bound is None:
        return fq.reduce_limbs(out, [worst_limb] * n_wide, value_bound)
    # the declared top-limb bound must dominate what the walk guarantees
    assert fq._cert(
        "out_bound_top_sound",
        min(out_bound.limb, (out_bound.value_p * P) >> (16 * 24)),
        out_bound.top,
        note=name,
    ), "out_bound.top unsound for its value/limb bounds"
    return fq.reduce_limbs(
        out,
        [worst_limb] * n_wide,
        value_bound,
        out_bound.value_p * P,
        out_bound.limb,
    )


# --------------------------------------------------------------------------------------
# Prebuilt plans
# --------------------------------------------------------------------------------------

def _build_mul(k: int) -> Plan:
    p = Plan(k, k)
    x, y = vbasis(k), vbasis(k)
    if k == 2:
        p.out_rows = p.mul2(x, y)
    elif k == 6:
        p.out_rows = p.mul6(x, y)
    elif k == 12:
        p.out_rows = p.mul12(x, y)
    return p


MUL2 = _build_mul(2)
MUL6 = _build_mul(6)
MUL12 = _build_mul(12)


def _build_sqr2() -> Plan:
    p = Plan(2, 2)
    x = vbasis(2)
    p.out_rows = p.sqr2(x)
    # sqr plans put the same element on both sides; b_rows reference the A basis
    return p


SQR2 = _build_sqr2()


def _build_sqr12() -> Plan:
    """fq12 square via 2 fq6 products: t = a0*a1; s = (a0+a1)(a0 + nr(a1));
    c0 = s - t - nr(t); c1 = 2t."""
    p = Plan(12, 12)
    x = vbasis(12)
    a0, a1 = x[0:6], x[6:12]
    t = p.mul6(a0, a1)
    s = p.mul6(v6_add(a0, a1), v6_add(a0, v6_nr(a1)))
    c0 = v6_sub(v6_sub(s, t), v6_nr(t))
    c1 = v6_add(t, t)
    p.out_rows = c0 + c1
    return p


SQR12 = _build_sqr12()


def _build_cyc_sqr() -> Plan:
    """Granger-Scott cyclotomic square: 9 Fq2 squares (18 lanes) + linear glue."""
    p = Plan(12, 12)
    x = vbasis(12)
    # coefficient layout: fq12 = (c0=(z0,z4,z3), c1=(z2,z1,z5)) in fq2 slots
    z0, z4, z3 = x[0:2], x[2:4], x[4:6]
    z2, z1, z5 = x[6:8], x[8:10], x[10:12]
    # out-row references to the inputs use pass-through indices
    iz0, iz4, iz3 = [p.inp(0), p.inp(1)], [p.inp(2), p.inp(3)], [p.inp(4), p.inp(5)]
    iz2, iz1, iz5 = [p.inp(6), p.inp(7)], [p.inp(8), p.inp(9)], [p.inp(10), p.inp(11)]
    sq = {}
    for nm, (u, v) in {"a": (z0, z1), "b": (z2, z3), "c": (z4, z5)}.items():
        sq[nm + "0"] = p.sqr2(u)
        sq[nm + "1"] = p.sqr2(v)
        sq[nm + "x"] = p.sqr2(v2_add(u, v))

    def fq4(nm):
        t0, t1, txy = sq[nm + "0"], sq[nm + "1"], sq[nm + "x"]
        return (
            v2_add(v2_nr(t1), t0),
            v2_sub(v2_sub(txy, t0), t1),
        )

    t0, t1 = fq4("a")
    t2, t3 = fq4("b")
    t4, t5 = fq4("c")

    def tri_sub(t, z):
        d = v2_sub(t, z)
        return v2_add(v2_add(d, d), t)

    def tri_add(t, z):
        s = v2_add(t, z)
        return v2_add(v2_add(s, s), t)

    z0n = tri_sub(t0, iz0)
    z1n = tri_add(t1, iz1)
    z2n = tri_add(v2_nr(t5), iz2)
    z3n = tri_sub(t4, iz3)
    z4n = tri_sub(t2, iz4)
    z5n = tri_add(t3, iz5)
    p.out_rows = z0n + z4n + z3n + z2n + z1n + z5n
    return p


CYC_SQR = _build_cyc_sqr()


def _mont(c: int) -> int:
    return c * fq.R_MONT % P


def _build_frob12() -> Plan:
    """Power-1 Frobenius on fq12. Lanes multiply conjugated coefficients by the
    Frobenius constants (constant pool on the B side); z0-conj passes through a
    multiply by one to keep everything in one kernel."""
    from ..bls_oracle import fields as _of

    g6c1, g6c2, g12 = _of._FROB_FQ6_C1_1, _of._FROB_FQ6_C2_1, _of._FROB_FQ12_C1_1
    consts = []

    def cidx(val: int) -> LC:
        v = _mont(val)
        if v not in consts:
            consts.append(v)
        return LC.basis(12 + consts.index(v))

    p = Plan(12, 12)
    x = vbasis(12)

    def fq6_frob(sl, extra: "_of.Fq2 | None"):
        """Frobenius of an fq6 slice, optionally followed by * extra (fq12 gamma)."""
        cs = [v2_conj(sl[0:2]), v2_conj(sl[2:4]), v2_conj(sl[4:6])]
        gammas = [_of.Fq2(1, 0), g6c1, g6c2]
        out = []
        for coef, gam in zip(cs, gammas):
            g = gam * extra if extra is not None else gam
            # (c0 + c1 u) * (g0 + g1 u) with g constant:
            g0, g1 = cidx(g.c0), cidx(g.c1)
            l00 = p.lane(coef[0], g0)
            l11 = p.lane(coef[1], g1)
            lx = p.lane(coef[0] + coef[1], g0 + g1)
            out += [l00 - l11, lx - l00 - l11]
        return out

    c0 = fq6_frob(x[0:6], None)
    c1 = fq6_frob(x[6:12], g12)
    p.out_rows = c0 + c1
    p.consts = consts
    return p


FROB12 = _build_frob12()
