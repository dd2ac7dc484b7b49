"""Fq (BLS12-381 base field) arithmetic as JAX limb kernels.

Representation: little-endian 16-bit limbs in uint64 lanes, **25 limbs** (plain
residues — no Montgomery domain), shape ``[..., 25]``. The 25th limb buys ~19 bits of
headroom over the 381-bit modulus, which enables the properties the whole kernel
stack is built on:

  * **Lazy addition/subtraction.** ``add``/``sub``/``neg`` are pure elementwise limb
    ops — no carry propagation, no comparison, ~2 HLO ops each. Limbs grow beyond 16
    bits and values beyond p; that's fine. The operand budget (enforced statically by
    plans.lincomb) is: values < 1200p and limbs < 2^22. The schoolbook convolution is
    exact for limbs up to 2^22 (25 * 2^44 < 2^50 per uint64 accumulator). Convention:
    values crossing a public tower-op boundary satisfy plans.PUB_BOUND (17-bit limbs,
    value < 16p, top limb <= 2); lazy values live only between two multiplies. ``sub(a, b)``/``neg``
    here require a public-bounded subtrahend (any multiply output): they add a
    borrow-inflated multiple of p whose limbs dominate the public bound. The
    tower layer (plans/tower) uses bound-tracked inflated constants instead.

  * **Branchless congruence-fold reduction — no sequential REDC.** A 50-limb
    convolution output is reduced by *folding*: limbs at positions >= 25 multiply a
    precomputed constant matrix F[j] = limbs(2^(16(25+j)) mod p) and accumulate onto
    the low limbs — one small matmul, a congruence mod p, no data-dependent carries.
    Interleaved elementwise "carry rounds" (lo = t & mask; t = lo + shift(t >> 16))
    keep limbs inside uint64 headroom. The only lax.scan left in the multiply path
    is the trivial-body 16-bit carry walk; the serial 25-step Montgomery REDC (a
    dynamic-update-slice scan that dominated both XLA compile time and VPU runtime)
    is gone, and with it the Montgomery domain itself: values are plain residues,
    so serialization and hashing skip domain conversion entirely.

``mont_mul`` (name kept for call-site compatibility) returns a *public-bounded*
value: <= 13p (PUB_VALUE_LIMIT), 17-bit limbs (PUB_LIMB_TARGET), top limb <= 2
— inside plans.PUB_BOUND. Equality, parity and serialization go through
``canonical()`` which finishes the reduction to < p. Every bound claim in this
module is machine-checked: the limb-bound certifier (``analysis/bounds.py``,
``python -m lighthouse_tpu.analysis --bounds``) re-executes the op graphs
abstractly and proves each obligation per backend (BOUNDS_CERT.json).

Correctness is pinned against ``lighthouse_tpu.ops.bls_oracle`` on random inputs.
This layer is the TPU twin of the blst field backend the reference links against
(``/root/reference/crypto/bls/src/impls/blst.rs`` seam).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..bls_oracle.fields import P

jax.config.update("jax_enable_x64", True)

NLIMBS = 25
LIMB_BITS = 16
MASK = np.uint64(0xFFFF)

R_MONT = 1  # plain-residue domain (no Montgomery factor; see module docstring)

# --------------------------------------------------------------------------------------
# Certification sink (analysis/bounds.py)
#
# Every bound this module proves statically at trace time — conv-accumulator
# exactness, fold-accumulator wrap safety, reduction-walk targets — is both
# asserted (as before) and, when a sink is installed, RECORDED as a proof
# obligation (kind, proven bound, declared limit). The limb-bound certifier
# re-executes the op graphs abstractly (jax.eval_shape) with the sink
# installed and emits BOUNDS_CERT.json from the records; production traces
# pay one `is None` check per obligation.
# --------------------------------------------------------------------------------------

_CERT_SINK = None


def _cert(kind: str, proven: int, limit: int, note: str = "") -> bool:
    """Record (and return) the obligation ``proven <= limit``. With no sink
    installed this is just the comparison the surrounding assert uses."""
    ok = proven <= limit
    if _CERT_SINK is not None:
        _CERT_SINK.record(kind, proven, limit, note=note, ok=ok)
    return ok


def int_to_limbs(x: int) -> np.ndarray:
    """Host helper: Python int -> uint64[25] little-endian 16-bit limbs."""
    return np.array(
        [(x >> (LIMB_BITS * i)) & 0xFFFF for i in range(NLIMBS)], dtype=np.uint64
    )


def limbs_to_int(a) -> int:
    """Host helper: limb array (last axis 25, any limb values) -> Python int."""
    a = np.asarray(a, dtype=np.uint64)
    return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(a))


def _inflated_kp(limb_cover: int, top_cover: int) -> np.ndarray:
    """Limbs of the smallest K*p whose borrow-inflated representation has every
    limb 0..23 >= limb_cover and limb 24 >= top_cover (so C - x never
    underflows per limb for x within those bounds)."""
    m = max(-(-limb_cover // ((1 << LIMB_BITS) - 1)), 1)
    K = 1
    while True:
        c = [int(v) for v in int_to_limbs(K * P)]
        assert (K * P).bit_length() <= NLIMBS * LIMB_BITS
        for i in range(1, NLIMBS):
            c[i - 1] += m << LIMB_BITS
            c[i] -= m
        if (
            all(v >= 0 for v in c)
            and all(c[i] >= limb_cover for i in range(24))
            and c[24] >= top_cover
        ):
            assert sum(v << (LIMB_BITS * i) for i, v in enumerate(c)) == K * P
            return np.array(c, dtype=np.uint64)
        K += 1


P_LIMBS = jnp.asarray(int_to_limbs(P))
# Covers any plans.PUB_BOUND subtrahend (16-bit limbs, top limb <= 2) — in
# particular every multiply output.
SUBPUB = jnp.asarray(_inflated_kp((1 << 17) - 1, 2))  # covers plans.PUB_LIMB
SUB2P = SUBPUB  # historical name
ONE_M = jnp.asarray(int_to_limbs(1))  # multiplicative identity (plain domain)
ONE_RAW = jnp.zeros((NLIMBS,), dtype=jnp.uint64).at[0].set(1)


def from_int(x: int, mont: bool = True):
    """Host int -> device limbs. The domain is plain residues, so the ``mont``
    flag (kept for call-site compatibility) is a no-op."""
    return jnp.asarray(int_to_limbs(x % P))


def from_ints(xs, mont: bool = True):
    """Batch host conversion: list of ints -> uint64[len(xs), 25]."""
    return jnp.asarray(np.stack([int_to_limbs(x % P) for x in xs]))


def to_int(a, mont: bool = True) -> int:
    """Device limbs -> Python int. Accepts lazy (non-canonical) values; the
    ``mont`` flag is a no-op (plain domain)."""
    return limbs_to_int(np.asarray(a)) % P


def to_ints(a, mont: bool = True) -> list:
    arr = np.asarray(a)
    return [to_int(arr[i], mont) for i in range(arr.shape[0])]


# --------------------------------------------------------------------------------------
# Lazy ring operations (no normalization — see module docstring for the bounds)
# --------------------------------------------------------------------------------------

def add(a, b):
    return a + b


def sub(a, b):
    """a - b + Kp. b must be public-bounded (16-bit limbs, top <= 2 — any
    multiply output or canonical value); a may be lazy."""
    return a + (SUBPUB - b)


def neg(a):
    """Kp - a. a must be public-bounded."""
    return SUBPUB - a


def double(a):
    return a + a


# --------------------------------------------------------------------------------------
# Comparisons (canonical operands only)
# --------------------------------------------------------------------------------------

def is_zero(a):
    return jnp.all(a == 0, axis=-1)


def eq(a, b):
    return jnp.all(a == b, axis=-1)


def select(cond, a, b):
    """cond ? a : b, with cond of batch shape (no limb axis)."""
    return jnp.where(cond[..., None], a, b)


# --------------------------------------------------------------------------------------
# Multiplication: convolution + congruence-fold reduction (no sequential REDC)
# --------------------------------------------------------------------------------------

def _shift_up_one(t):
    """Shift limbs up one position (drop the top limb's value — caller
    guarantees it is statically zero)."""
    return jnp.concatenate([jnp.zeros_like(t[..., :1]), t[..., :-1]], axis=-1)


def _carry_lookahead(comb_g, comb_p):
    """Inclusive carry/borrow-lookahead over the limb axis: generate/propagate
    pairs composed with the standard associative carry operator. Log-depth
    elementwise ops — NO lax.scan/while (the serial carry walks used to emit a
    separate XLA while computation per call site, and with ~2 per plans.execute
    the fused verification kernels carried 600+ while ops; XLA CPU compiles
    every while body as its own computation, which dominated compile time —
    461 s at the 16x64 toy shape, VERDICT r3 #1/#2)."""

    def comb(a, b):
        ga, pa = a
        gb, pb = b
        return gb | (pb & ga), pb & pa

    return jax.lax.associative_scan(comb, (comb_g, comb_p), axis=-1)


def _carry_rounds(t, rounds: int):
    """Width-preserving carry-save rounds: limb bound b -> 0xFFFF + (b >> 16)
    per round (value invariant; the top limb's carry is statically zero when
    the value fits the width — limbs are non-negative so
    limb[-1] <= value >> (16*(n-1))). Dtype-generic (u64 masks / f64 floor)."""
    for _ in range(rounds):
        lo, hi = _split16(t)
        t = lo + _shift_up_one(hi)
    return t


def _carry_propagate(t, out_limbs: int):
    """Normalize to EXACT 16-bit limbs, dropping any final carry (caller
    guarantees the value fits out_limbs limbs). While-free: carry-save rounds
    bring limbs under 2^17, then one carry-lookahead finishes exactly. Only
    comparison/serialization sites need this; the multiply pipeline uses the
    cheaper approximate rounds (plans.PUB_BOUND allows 17-bit limbs)."""
    t = _carry_rounds(t[..., :out_limbs], 4)
    # exact finish: t = r + (g << 16) with g in {0,1}
    r = t & MASK
    gs = _shift_up_one(t >> np.uint64(LIMB_BITS))
    ssum = r + gs  # <= 0x10000
    G, _ = _carry_lookahead(ssum > MASK, ssum == MASK)
    cin = _shift_up_one(G.astype(t.dtype))
    return (ssum + cin) & MASK


def _sub_limbs(a, b):
    """a - b with borrow chain (canonical operands). Returns (diff, borrow_out).
    Borrow-lookahead (see _carry_lookahead) instead of a serial scan."""
    b = jnp.broadcast_to(b, a.shape)
    G, _ = _carry_lookahead(a < b, a == b)
    bin_ = _shift_up_one(G.astype(a.dtype))
    diff = (a - b - bin_) & MASK
    return diff, G[..., -1].astype(a.dtype)


def _cond_sub_p(a):
    """Subtract p when a >= p (a < 2p, canonical limbs on entry)."""
    diff, borrow = _sub_limbs(a, P_LIMBS)
    return jnp.where((borrow == 1)[..., None], a, diff)


def _conv_product_shear(a, b):
    """Schoolbook 25x25 convolution -> 50 uint64 accumulators. Exact for limbs up
    to 2^22 (25 * 2^44 < 2^50).

    The anti-diagonal sum T[s] = sum_{i+j=s} a_i b_j is materialized by the
    reshape *shear*: pad rows of the outer product to width 2*25, flatten, and
    re-slice at width 2*25-1 — row i then lands shifted by i columns, so a
    plain row-sum produces the convolution. ~6 HLO ops instead of the 25
    pad-and-add ops of the naive form (program size is compile time: the fused
    verification kernel inlines hundreds of these)."""
    a, b = jnp.broadcast_arrays(a, b)
    prod = a[..., :, None] * b[..., None, :]  # [..., 25, 25]
    batch = prod.shape[:-2]
    w = 2 * NLIMBS  # 50
    prod = jnp.pad(prod, [(0, 0)] * len(batch) + [(0, 0), (0, w - NLIMBS)])
    flat = prod.reshape(batch + (NLIMBS * w,))
    sheared = flat[..., : NLIMBS * (w - 1)].reshape(batch + (NLIMBS, w - 1))
    t = sheared.sum(axis=-2)  # [..., 49]; true limb 49 is always zero
    return jnp.pad(t, [(0, 0)] * len(batch) + [(0, 1)])


def _conv_product_f64(a, b):
    """Schoolbook convolution as a 25-term shifted-FMA chain in f64.

    Products are exact: conv inputs satisfy the lazy budget (limbs < 2^22), so
    every accumulator is < 25 * 2^44 < 2^49 < 2^53 (f64 integer exactness).
    The FMA chain fuses into one pass over the [..., 49] output — the
    shear-reshape form above materializes the full [..., 25, 50] outer
    product (160 MB at batch 16k) and is memory-bound at ~3x the runtime.
    Compile cost of the 25-term chain is ~0.2 s (the r3 compile blowup came
    from while-loops, not op count)."""
    af = a.astype(jnp.float64)
    bf = b.astype(jnp.float64)
    nb = [(0, 0)] * (a.ndim - 1)
    t = None
    for i in range(NLIMBS):
        term = jnp.pad(af[..., i : i + 1] * bf, nb + [(i, NLIMBS - 1 - i)])
        t = term if t is None else t + term
    # materialization fence: the chain is fully elementwise, and without the
    # barrier XLA CPU duplicates it into every consumer of the accumulators
    # inside large fused graphs (measured 1.7x slower map_to_g2)
    t = jax.lax.optimization_barrier(t)
    return jnp.pad(t, nb + [(0, 1)])


def _conv_product_f64_u64(a, b):
    return _conv_product_f64(a, b).astype(jnp.uint64)


# TPU digit path: base-2^8 digit split. Limb i (< 2^22) contributes bytes to
# digit positions 2i, 2i+1, 2i+2; overlapping chunks add, so digits are
# <= 255 + (limb >> 16) <= 318. 51 digits cover 25 limbs.
_N_DIGITS = 2 * NLIMBS + 1  # 51


def _digit_bound(limb_bound: int) -> int:
    return min(limb_bound, 255) + (limb_bound >> 16)


def _to_digits_f32(x):
    """u64 limbs [..., 25] -> f32 digits [..., 51] (base 2^8, overlap-added):
    digit[2i] = c0(i) + c2(i-1), digit[2i+1] = c1(i), digit[50] = c2(24)."""
    c0 = (x & jnp.uint64(0xFF)).astype(jnp.float32)
    c1 = ((x >> jnp.uint64(8)) & jnp.uint64(0xFF)).astype(jnp.float32)
    c2 = (x >> jnp.uint64(16)).astype(jnp.float32)
    nb = [(0, 0)] * (x.ndim - 1)
    # even digit slots 0..25: c0 padded with a tail slot + c2 shifted up one
    even = jnp.pad(c0, nb + [(0, 1)]) + jnp.pad(c2, nb + [(1, 0)])
    odd = jnp.pad(c1, nb + [(0, 1)])  # odd digit slots 1,3,..,49 (+ unused)
    inter = jnp.stack([even, odd], axis=-1)  # [..., 26, 2]
    d = inter.reshape(x.shape[:-1] + (2 * (NLIMBS + 1),))  # 52 slots
    return d[..., : _N_DIGITS]  # slot 51 (odd tail) is zero by construction


def _conv_product_digits(a, b):
    """TPU convolution: f32 digit-split shifted-FMA chain, recombined to the
    u64 16-bit-limb accumulator layout.

    TPUs have no fast 64-bit integer multiply (u64 lowers to multi-op u32
    emulation on the VPU) and f64 is software-emulated, but f32 FMA runs at
    full VPU rate. Digits are <= 318 (for 2^22-bounded limbs) so every conv
    accumulator is <= 51 * 318^2 < 2^23 — exact in f32. Recombined limb
    accumulators are < 2^30.4 pre-spill; limb 49 then absorbs the
    2^16-scaled spill of digit position 100 (see end of function), raising
    its bound to ~2^32.6 — still far tighter than the f64 path's 2^48.6,
    which shortens the fold schedule downstream (the fold walk uses the
    exact per-limb bounds from conv_limb_bounds, not these summaries)."""
    da = _to_digits_f32(a)
    db = _to_digits_f32(b)
    nb = [(0, 0)] * (a.ndim - 1)
    t = None
    for i in range(_N_DIGITS):
        term = jnp.pad(da[..., i : i + 1] * db, nb + [(i, _N_DIGITS - 1 - i)])
        t = term if t is None else t + term
    # digit accumulators [..., 101] -> u64 limbs: limb s = D[2s] + 2^8 D[2s+1]
    t = jnp.pad(t, nb + [(0, 1)])  # 102 digit slots = 51 limb pairs
    ti = t.astype(jnp.uint32).astype(jnp.uint64)
    pairs = ti.reshape(t.shape[:-1] + (_N_DIGITS, 2))
    limbs = pairs[..., 0] + (pairs[..., 1] << jnp.uint64(8))
    # digit position 100 (top-chunk x top-chunk) lands at limb 50, one past
    # the 50-limb accumulator layout; fold it into limb 49 (value-preserving,
    # bound ~2^32 — still far inside u64)
    spill = limbs[..., 2 * NLIMBS :] << jnp.uint64(LIMB_BITS)
    return jnp.concatenate(
        [limbs[..., : 2 * NLIMBS - 1], limbs[..., 2 * NLIMBS - 1 : 2 * NLIMBS] + spill],
        axis=-1,
    )


_CONV_IMPL = None


def conv_backend() -> str:
    """Which conv implementation the default backend gets: "pallas" on TPU
    (fused Pallas/Mosaic digit kernels — conv + congruence fold + carry as
    one kernel, see pallas_kernels.py), "f64" elsewhere (CPU SIMD FMA).
    Cached on first use; override via
    LIGHTHOUSE_CONV_IMPL=pallas|digits|f64|shear for testing ("pallas" off
    TPU runs the kernels in interpret mode — exact, but an emulator)."""
    global _CONV_IMPL
    if _CONV_IMPL is None:
        import os

        forced = os.environ.get("LIGHTHOUSE_CONV_IMPL")
        if forced in ("pallas", "digits", "f64", "shear"):
            _CONV_IMPL = forced
        else:
            _CONV_IMPL = "pallas" if jax.default_backend() == "tpu" else "f64"
    return _CONV_IMPL


def conv_limb_bounds(in_limb_a: int, in_limb_b: int | None = None) -> list[int]:
    """Static per-accumulator bounds of _conv_product for inputs with limbs
    <= in_limb_a / in_limb_b under the active conv backend, asserting
    float-exactness of the chosen path."""
    if in_limb_b is None:
        in_limb_b = in_limb_a
    # "pallas" shares the digit-split accumulator shape: these bounds apply
    # to its (rarely taken) _conv_product fallback; the fused kernels track
    # their own digit-domain bounds in pallas_kernels.py
    if conv_backend() in ("digits", "pallas"):
        da = _digit_bound(in_limb_a)
        db = _digit_bound(in_limb_b)
        # digit conv position d has min(d, 100-d, 50)+1 terms
        per_digit = [
            (min(d, 2 * _N_DIGITS - 2 - d, _N_DIGITS - 1) + 1) * da * db
            for d in range(2 * _N_DIGITS - 1)
        ] + [0]
        assert _cert(
            "conv_digit_f32_exact", max(per_digit), (1 << 24) - 1
        ), "digit conv exceeds f32 exactness"
        # the u32 cast of the digit accumulators is lossless iff they are
        # f32-exact (< 2^24 < 2^32) — same obligation, recorded explicitly
        _cert("conv_digit_u32_nowrap", max(per_digit), (1 << 32) - 1)
        limb_b = [
            per_digit[2 * s] + (per_digit[2 * s + 1] << 8)
            for s in range(_N_DIGITS)
        ]
        # limb 50 is folded into limb 49 by _conv_product_digits
        limb_b[2 * NLIMBS - 1] += limb_b[2 * NLIMBS] << LIMB_BITS
        assert _cert(
            "conv_digit_u64_acc", max(limb_b), (1 << 64) - 1
        ), "digit conv u64 recombination overflow"
        return limb_b[: 2 * NLIMBS]
    bounds = [
        max(1, min(i + 1, NLIMBS, 2 * NLIMBS - 1 - i)) * in_limb_a * in_limb_b
        for i in range(2 * NLIMBS)
    ]
    if conv_backend() == "f64":
        assert _cert(
            "conv_f64_exact", max(bounds), (1 << 53) - 1
        ), "f64 conv exceeds f64 exactness"
    else:
        # shear path: plain u64 accumulators must not wrap
        assert _cert(
            "conv_u64_acc", max(bounds), (1 << 64) - 1
        ), "shear conv u64 accumulator overflow"
    return bounds


def _conv_product(a, b):
    """Convolution product -> 50 u64 accumulators (platform-dispatched; see
    _conv_product_f64 / _conv_product_digits / _conv_product_shear). Inputs
    must satisfy the lazy budget: limbs < 2^22, value < 1200p.

    Under the "pallas" backend the HOT path never calls this — mont_mul /
    mont_mul_lazy / plans.execute dispatch to the fused pallas kernels
    (conv + fold + carry in one pallas_call); stray callers of the bare
    conv seam get the bit-equivalent u64 digit accumulators."""
    impl = conv_backend()
    if impl in ("digits", "pallas"):
        return _conv_product_digits(a, b)
    if impl == "f64":
        return _conv_product_f64_u64(a, b)
    return _conv_product_shear(a, b)


# Row threshold for keeping the reduction walk in f64 (f64 backend only).
# Originally 32: host-dispatched micro-benchmarks suggested the longer f64
# schedule (2^53 cap) loses below ~32 rows. Re-measured inside lax.scan
# bodies (where the pairing's batch-1 final-exponentiation chains actually
# run, and dispatch cost amortizes away) the u64 path's scalarized
# multiplies lose at EVERY row count — a 63-step cyclotomic-square scan at
# batch 1 ran 2.3x faster on the f64 path — so the threshold is now 0:
# the f64 backend keeps the whole execute pipeline in f64 SIMD at all
# shapes. Static per-call-site dispatch — both paths are exact.
F64_WALK_MIN_ROWS = 0


def _static_rows(a) -> int:
    n = 1
    for d in a.shape[:-1]:
        n *= int(d)
    return n


def _conv_product_keep(a, b):
    """_conv_product, but on the f64 backend (and at row counts where it
    wins — F64_WALK_MIN_ROWS) the accumulators STAY f64 so the downstream
    reduction walk runs in f64 as well. x86 has no vectorized 64-bit integer
    multiply — the u64 congruence-fold passes scalarize and dominated the
    execute pipeline (~60% of a point-double); the f64 walk is the same
    fold schedule (2^53 exactness cap, statically re-derived) on SIMD FMAs.
    reduce_limbs casts back to u64 at the end."""
    impl = conv_backend()
    if impl in ("digits", "pallas"):
        return _conv_product_digits(a, b)
    if impl == "f64":
        if max(_static_rows(a), _static_rows(b)) >= F64_WALK_MIN_ROWS:
            return _conv_product_f64(a, b)
        return _conv_product_f64_u64(a, b)
    return _conv_product_shear(a, b)


# Congruence-fold rows: _FOLD_ROWS[j] = 16-bit limbs of 2^(16*(25+j)) mod p.
# Folding limb 25+j through its row is an exact congruence mod p.
_N_FOLD = 40
_FOLD_NP = np.stack(
    [int_to_limbs((1 << (LIMB_BITS * (NLIMBS + j))) % P) for j in range(_N_FOLD)]
)
_FOLD_ROWS = jnp.asarray(_FOLD_NP)
_FOLD_ROWS_F64 = jnp.asarray(_FOLD_NP.astype(np.float64))
_FOLD_VALS = [(1 << (LIMB_BITS * (NLIMBS + j))) % P for j in range(_N_FOLD)]

PUB_VALUE_LIMIT = 13 * P  # reduce() output value bound (plans.PUB_BOUND holds)


class _RState:
    """Exact static bound state for reduce_limbs(): per-limb bounds (Python
    ints) plus a value bound, mutually refined — any limb t_i <= value >> 16i
    since limbs are non-negative. Every transform updates the state exactly, so
    uint64 overflow and carry-drop safety are proved at trace time."""

    __slots__ = ("limbs", "value")

    def __init__(self, limbs, value):
        limbs = list(limbs)
        value = min(
            value, sum(b << (LIMB_BITS * i) for i, b in enumerate(limbs))
        )
        self.limbs = [min(b, value >> (LIMB_BITS * i)) for i, b in enumerate(limbs)]
        self.value = value


def _is_f64(t) -> bool:
    return t.dtype == jnp.float64


def _cap_of(t) -> int:
    """Largest exactly-representable accumulator bound for t's dtype: integer
    f64 stays exact below 2^53; u64 wraps at 2^64."""
    return (1 << 53) if _is_f64(t) else (1 << 64)


def _split16(t):
    """(low 16 bits, value >> 16) in t's dtype. The f64 form is exact for
    integer t < 2^53 (scaling by 2^-16 and floor are exact)."""
    if _is_f64(t):
        hi = jnp.floor(t * (1.0 / 65536.0))
        return t - hi * 65536.0, hi
    return t & MASK, t >> np.uint64(LIMB_BITS)


def _carry_round_array(t):
    """One elementwise carry-save round (appends a limb; value unchanged)."""
    lo, hi = _split16(t)
    nb = [(0, 0)] * (t.ndim - 1)
    return jnp.pad(lo, nb + [(0, 1)]) + jnp.pad(hi, nb + [(1, 0)])


def _carry_round(t, s: _RState):
    t = _carry_round_array(t)
    lo_b = [min(b, int(MASK)) for b in s.limbs] + [0]
    hi_b = [0] + [b >> LIMB_BITS for b in s.limbs]
    return t, _RState([a + b for a, b in zip(lo_b, hi_b)], s.value)


def _fold_high(t, s: _RState):
    """Fold limbs >= 25 through the 2^(16k) mod p rows — an exact congruence
    mod p that shrinks the value by ~2^19x per live high limb. On the f64
    walk the fold is ONE [..., n_hi] x [n_hi, 25] dot_general (SIMD matmul —
    5x the unrolled FMA chain at chain widths); on integer walks it stays
    unrolled broadcast-FMA terms (not a .sum(-2) reduction) so XLA fuses the
    fold into the surrounding elementwise chain — the reduction form
    materialized the [..., n_hi, 25] intermediate and cost an extra memory
    pass (and u64 dots scalarize)."""
    n_hi = t.shape[-1] - NLIMBS
    acc = t[..., :NLIMBS]
    if _is_f64(t):
        acc = acc + jax.lax.dot_general(
            t[..., NLIMBS:],
            _FOLD_ROWS_F64[:n_hi],
            (((t.ndim - 1,), (0,)), ((), ())),
        )
    else:
        rows = _FOLD_ROWS
        for j in range(n_hi):
            acc = acc + t[..., NLIMBS + j : NLIMBS + j + 1] * rows[j]
    lo_b, hi_b = s.limbs[:NLIMBS], s.limbs[NLIMBS:]
    limbs = [
        b + sum(hb * int(_FOLD_NP[j, i]) for j, hb in enumerate(hi_b))
        for i, b in enumerate(lo_b)
    ]
    assert _cert(
        "fold_acc_nowrap", max(limbs), _cap_of(t) - 1
    ), "fold accumulator overflow"
    lo_val = sum(b << (LIMB_BITS * i) for i, b in enumerate(lo_b))
    value = min(s.value, lo_val) + sum(
        hb * _FOLD_VALS[j] for j, hb in enumerate(hi_b)
    )
    return acc, _RState(limbs, value)


_RT384_VAL = (1 << 384) % P
_RT384_NP = int_to_limbs(_RT384_VAL)
_RT384_ROW = jnp.asarray(_RT384_NP)
_RT384_ROW_F64 = jnp.asarray(_RT384_NP.astype(np.float64))
_RT381_VAL = (1 << 381) % P
_RT381_ROW = jnp.asarray(int_to_limbs(_RT381_VAL))
# keep bits < 381: full limbs 0..22, 13 bits of limb 23, none of limb 24
_MASK_LOW381 = jnp.asarray(
    np.array([0xFFFF] * 23 + [0x1FFF, 0], dtype=np.uint64)
)


# constant masks (static-index .at[].set lowers to scatter — thousands of
# scatter ops dominated XLA compile time; a mask multiply fuses for free)
_MASK_NO24 = jnp.asarray(
    np.array([1] * 24 + [0], dtype=np.uint64)
)
_MASK_NO24_F64 = jnp.asarray(np.array([1.0] * 24 + [0.0]))


def _fold_384(t, s: _RState):
    """Fold the 2^384-and-up excess of a 25-limb array through 2^384 mod p."""
    top = t[..., 24]
    if _is_f64(t):
        t = t * _MASK_NO24_F64 + top[..., None] * _RT384_ROW_F64
    else:
        t = t * _MASK_NO24 + top[..., None] * _RT384_ROW
    top_b = s.limbs[24]
    limbs = [
        b + top_b * int(_RT384_NP[i]) for i, b in enumerate(s.limbs[:24])
    ] + [top_b * int(_RT384_NP[24])]
    assert _cert(
        "fold384_acc_nowrap", max(limbs), _cap_of(t) - 1
    ), "fold384 accumulator overflow"
    lo_val = sum(b << (LIMB_BITS * i) for i, b in enumerate(s.limbs[:24]))
    return t, _RState(limbs, min(s.value, lo_val) + top_b * _RT384_VAL)


PUB_LIMB_TARGET = (1 << 17) - 1  # plans.PUB_LIMB: 17-bit limbs suffice publicly


def _propagate_approx(t, s: _RState, n_out: int, target: int = PUB_LIMB_TARGET):
    """Approximate carry walk: width-preserving carry-save rounds (statically
    scheduled from the bound state) until every limb bound is <= target.
    Value-invariant, elementwise, no scan — exactness is only needed at
    comparison/serialization sites (fq.canonical), not inside the multiply
    pipeline, whose public contract tolerates 17-bit limbs."""
    assert _cert(
        "carry_walk_width", s.value, (1 << (LIMB_BITS * n_out)) - 1
    ), "carry walk would drop value"
    if t.shape[-1] < n_out:
        t = jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, n_out - t.shape[-1])])
    limbs = list(s.limbs) + [0] * (n_out - len(s.limbs))
    limbs = [min(b, s.value >> (LIMB_BITS * i)) for i, b in enumerate(limbs)]
    for _ in range(8):
        if max(limbs) <= target:
            break
        t = _carry_rounds(t, 1)
        carried = [0] + [b >> LIMB_BITS for b in limbs[:-1]]
        limbs = [min(b, int(MASK)) + c for b, c in zip(limbs, carried)]
        limbs = [
            min(b, s.value >> (LIMB_BITS * i)) for i, b in enumerate(limbs)
        ]
    else:  # pragma: no cover - static schedule
        raise AssertionError("carry walk did not converge")
    return t, _RState(limbs, s.value)


def _drop_zero_tops(t, s: _RState):
    while t.shape[-1] > NLIMBS and s.limbs[t.shape[-1] - 1] == 0:
        t = t[..., : t.shape[-1] - 1]
        s = _RState(s.limbs[: t.shape[-1]], s.value)
    return t, s


def reduce_limbs(
    t,
    limb_bounds,
    value_bound: int,
    value_limit: int = PUB_VALUE_LIMIT,
    limb_target: int = PUB_LIMB_TARGET,
):
    """Reduce [..., N] (N >= 25) to value <= value_limit, limbs <= limb_target
    (defaults: plans.PUB_BOUND — value < 13p, 17-bit limbs, top limb <= 2).
    Statically scheduled congruence folds + elementwise carry rounds — fully
    while-free; bounds proved at trace time. Dtype-generic: an f64 input runs
    the whole walk in f64 (exactness cap 2^53 instead of 2^64 — a slightly
    longer schedule of cheaper, fusion-friendly FMA steps) and is cast to u64
    at the end.

    A LAZIER target (plans.CHAIN_BOUND: value < 64p, 20-bit limbs) trims the
    tail of the walk — fewer 2^384 folds and carry rounds. Fixed-exponent /
    fixed-scalar chains (chain_plans) run their interior ops at that target:
    the output re-enters the next convolution directly (limbs < 2^22, value
    < 1200p budget) and only the chain's final result pays the full
    normalization."""
    cap = _cap_of(t)
    s = _RState(list(limb_bounds), value_bound)
    # phase 1: fold down to 25 limbs
    for _ in range(64):
        t, s = _drop_zero_tops(t, s)
        if t.shape[-1] == NLIMBS:
            break
        n_hi = t.shape[-1] - NLIMBS
        prod = max(s.limbs[:NLIMBS]) + sum(
            hb * int(MASK) for hb in s.limbs[NLIMBS:]
        )
        if n_hi <= _N_FOLD and prod < cap:
            t, s = _fold_high(t, s)
        else:
            t, s = _carry_round(t, s)
    else:  # pragma: no cover - static schedule
        raise AssertionError("reduce_limbs: phase 1 did not converge")
    # phase 2: one approximate walk, wide enough that no carry is dropped
    n_out = max(NLIMBS + 1, -(-s.value.bit_length() // LIMB_BITS) + 1)
    t, s = _propagate_approx(t, s, n_out, limb_target)
    # phase 3: drain high limbs and the 2^384 excess — all elementwise
    for _ in range(64):
        t, s = _drop_zero_tops(t, s)
        if t.shape[-1] > NLIMBS:
            prod = max(s.limbs[:NLIMBS]) + sum(
                hb * int(MASK) for hb in s.limbs[NLIMBS:]
            )
            if prod < cap:
                t, s = _fold_high(t, s)
            else:
                t, s = _carry_round(t, s)
        elif s.value > value_limit:
            # fold only when it provably shrinks the value (the excess may sit
            # in low limbs after a previous fold — surface it with a carry)
            lo_val = sum(
                b << (LIMB_BITS * i) for i, b in enumerate(s.limbs[:24])
            )
            predicted = min(s.value, lo_val) + s.limbs[24] * _RT384_VAL
            safe = s.limbs[24] * int(MASK) + max(s.limbs[:24]) < cap
            if safe and predicted < s.value:
                t, s = _fold_384(t, s)
            else:
                t, s = _carry_round(t, s)
        else:
            break
    else:  # pragma: no cover - static schedule
        raise AssertionError("reduce_limbs: phase 3 did not converge")
    # phase 4: final approximate walk to limb_target-bit limbs (PUB target:
    # top <= 2 since value < 13p and limbs are non-negative:
    # limb24 <= value >> 384)
    t, s = _propagate_approx(t, s, NLIMBS, limb_target)
    assert _cert("reduce_value", s.value, value_limit)
    assert _cert("reduce_limb", max(s.limbs), limb_target)
    if value_limit == PUB_VALUE_LIMIT:
        assert _cert(
            "reduce_top_limb",
            min(s.limbs[24], s.value >> (LIMB_BITS * 24)),
            2,
        )
    if _is_f64(t):
        # materialization fence + exact cast (limbs <= limb_target < 2^53):
        # without the barrier XLA CPU duplicates the whole elementwise walk
        # into every consumer of the result (the conv chain's known
        # recompute pathology — measured 6x on a composed point_add)
        t = jax.lax.optimization_barrier(t).astype(jnp.uint64)
    return t


# Conv-input budget (the plans.lincomb contract): limbs < 2^22, value < 1200p.
_IN_LIMB = (1 << 22) - 1
_IN_VALUE = 1200 * P


def _conv_limb_bounds(lb: int):
    """Backend-independent worst-case accumulator bounds (the u64/f64 shape);
    retained for probes. Prefer conv_limb_bounds, which is backend-aware."""
    return [max(1, min(i + 1, NLIMBS, 49 - i)) * lb * lb for i in range(2 * NLIMBS)]


def mont_mul(a, b):
    """Product a*b mod p (plain domain — the historical name is kept for the
    call sites). Operands may be lazy up to _IN_VALUE (1200p) with limbs up to
    _IN_LIMB (2^22); output satisfies plans.PUB_BOUND (<= 13p, 17-bit limbs,
    top <= 2).

    The conv runs in f64 (CPU) / fused f32 digit kernels (TPU "pallas"
    backend: conv + congruence fold + carry inside ONE pallas_call — see
    pallas_kernels.fused_mul). On the f64 backend the fold walk stays in f64
    as well (u64 multiplies scalarize on x86 — see _conv_product_keep); the
    conv chain's optimization_barrier fences the graph so XLA does not
    recompute it per consumer (the historical all-f64 pathology)."""
    if conv_backend() == "pallas":
        from . import pallas_kernels

        return pallas_kernels.fused_mul(a, b, lazy=False)
    t = _conv_product_keep(a, b)
    return reduce_limbs(t, conv_limb_bounds(_IN_LIMB), _IN_VALUE * _IN_VALUE)


def mont_sqr(a):
    return mont_mul(a, a)


# Lazy chain target (see reduce_limbs): interior values of fixed-exponent /
# fixed-scalar chains run at this bound and only the chain's final result
# pays the full normalization walk. THE derivation (single source of truth —
# plans.CHAIN_BOUND and every docstring bound derive from these names):
#
#   CHAIN_LIMB_TARGET = 2^20 - 1, CHAIN_VALUE_P = 64 (value < 64p) because a
#   chain step's output must re-enter the next convolution directly, i.e.
#   sit inside the lazy conv budget (_IN_LIMB = 2^22 - 1, _IN_VALUE = 1200p)
#   AND keep the conv accumulators exact on every backend:
#     f64:    25 * (2^20)^2         = 25 * 2^40   < 2^53   (f64 exactness)
#     digits: 51 * (255 + 2^4)^2    ~  2^21.8     < 2^24   (f32 exactness)
#   (both re-checked per trace by conv_limb_bounds and certified by
#   analysis/bounds.py). The top-limb bound is not independent: limbs are
#   non-negative, so limb 24 <= value >> 384 — chain_top_limb() below.
CHAIN_VALUE_P = 64
CHAIN_LIMB_TARGET = (1 << 20) - 1
CHAIN_VALUE_LIMIT = CHAIN_VALUE_P * P


def chain_top_limb() -> int:
    """Provable limb-24 bound of a chain-interior value: min(limb bound,
    value >> 384) — for 64p that is 6 (tightens the former hand-written 7,
    which over-declared what the reduction walk guarantees)."""
    return min(CHAIN_LIMB_TARGET, CHAIN_VALUE_LIMIT >> (LIMB_BITS * 24))


# the chain fixed point must sit inside the conv-input budget, or interior
# outputs could not feed the next multiply without renormalization
assert CHAIN_LIMB_TARGET <= _IN_LIMB and CHAIN_VALUE_LIMIT <= _IN_VALUE


def mont_mul_lazy(a, b):
    """Chain-interior product: operands at (or below) the lazy chain bound
    (limbs <= CHAIN_LIMB_TARGET, value <= CHAIN_VALUE_LIMIT); output at the
    same bound — a fixed point, so chains of any length stay in budget.
    Shorter reduction walk than mont_mul (bound-precise conv inputs AND a
    lazier target)."""
    _cert("chain_in_budget_limb", CHAIN_LIMB_TARGET, _IN_LIMB)
    _cert("chain_in_budget_value", CHAIN_VALUE_LIMIT, _IN_VALUE)
    if conv_backend() == "pallas":
        from . import pallas_kernels

        return pallas_kernels.fused_mul(a, b, lazy=True)
    t = _conv_product_keep(a, b)
    return reduce_limbs(
        t,
        conv_limb_bounds(CHAIN_LIMB_TARGET),
        CHAIN_VALUE_LIMIT * CHAIN_VALUE_LIMIT,
        CHAIN_VALUE_LIMIT,
        CHAIN_LIMB_TARGET,
    )


def mont_sqr_lazy(a):
    return mont_mul_lazy(a, a)


def canonical(a):
    """Fully reduce to the canonical residue < p (comparisons, parity,
    serialization). Accepts anything within the lazy budget. On the f64
    backend (at winning row counts) the fold walk runs in f64 (see
    _conv_product_keep)."""
    if (
        conv_backend() == "f64"
        and not _is_f64(a)
        and _static_rows(a) >= F64_WALK_MIN_ROWS
    ):
        a = a.astype(jnp.float64)
    t = reduce_limbs(a, [_IN_LIMB] * a.shape[-1], _IN_VALUE)
    # reduce_limbs leaves 17-bit limbs (PUB_LIMB_TARGET); the 2^381 folds
    # below mask limbs to 16 bits (_MASK_LOW381), so an EXACT propagation
    # must come first or bit 16 of limbs 0..22 is silently dropped
    t = _carry_propagate(t, NLIMBS)
    # value < 13p: two sub-limb folds at the 2^381 boundary bring it under 2p
    for _ in range(2):
        hi = (t[..., 23] >> np.uint64(13)) + (t[..., 24] << np.uint64(3))
        t = (t & _MASK_LOW381) + hi[..., None] * _RT381_ROW
        t = _carry_propagate(t, NLIMBS)
    return _cond_sub_p(t)


def normalize(a):
    """Lazy -> canonical (< p), value unchanged mod p."""
    return canonical(a)


def from_mont(a):
    """Canonical plain residue (the domain IS plain; name kept for callers)."""
    return canonical(a)


# --------------------------------------------------------------------------------------
# Fixed-exponent powers (spec constants: inversion, sqrt)
# --------------------------------------------------------------------------------------

def pow_fixed_scan(a, e: int):
    """a^e for a fixed host-side exponent, compiled by the fixed-scalar plan
    machinery (chain_plans): windowed schedule with a log-depth table build
    and LAZY interior bounds — only the final result pays the full
    normalization walk. Accepts anything within the lazy budget: the base is
    first brought to the chain bound the interior ops' static schedules
    assume (limbs <= CHAIN_LIMB_TARGET, value <= CHAIN_VALUE_LIMIT)."""
    from . import chain_plans

    a = reduce_limbs(
        a, [_IN_LIMB] * a.shape[-1], _IN_VALUE,
        CHAIN_VALUE_LIMIT, CHAIN_LIMB_TARGET,
    )
    sched = chain_plans.compile_chains((int(e),), signed=False)
    out = chain_plans.run_field_chains(
        sched, a[None, ..., None, :], mont_sqr_lazy, mont_mul_lazy, ONE_M
    )[0, ..., 0, :]
    # restore the public bound (callers feed comparisons and PUB-contract
    # plan inputs)
    return reduce_limbs(out, [CHAIN_LIMB_TARGET] * NLIMBS, CHAIN_VALUE_LIMIT)


def inv(a):
    """Field inverse via Fermat (a^(p-2)); inv(0) = 0 (RFC 9380 inv0 semantics)."""
    return pow_fixed_scan(a, P - 2)


def sqrt_candidate(a):
    """a^((p+1)/4) — a square root when a is a QR (p = 3 mod 4). Caller checks
    candidate^2 == a."""
    return pow_fixed_scan(a, (P + 1) // 4)


def sgn0(a):
    """RFC 9380 sgn0 (parity) of a lazy plain-residue element."""
    return from_mont(a)[..., 0] & jnp.uint64(1)


def lex_gt_half_canon(canon):
    """x > (p-1)/2 on a *canonical plain-residue* limb array (MSB-first limb
    compare). Shared by the G1/G2 compressed-point sign-bit paths."""
    half = jnp.asarray(int_to_limbs((P - 1) // 2))
    gt = jnp.zeros(canon.shape[:-1], dtype=bool)
    decided = jnp.zeros(canon.shape[:-1], dtype=bool)
    for i in range(NLIMBS - 1, -1, -1):
        ai, hi = canon[..., i], half[i]
        gt = jnp.where(~decided & (ai > hi), True, gt)
        decided = decided | (ai != hi)
    return gt


def lex_gt_half(a):
    """y > (p-1)/2 on a lazy plain-residue element — the compressed-point sign bit
    (ZCash serialization convention used by the reference's pubkey/sig bytes)."""
    return lex_gt_half_canon(from_mont(a))
