"""Fused Pallas/Mosaic limb kernels: conv -> congruence-fold -> carry in one kernel.

The third conv backend (``LIGHTHOUSE_CONV_IMPL=pallas``, the TPU default).
The u64/f64 backends materialize the limb multiply pipeline as separate HLO
stages — ``fq._conv_product`` accumulators, the out-lincomb, then the
``fq.reduce_limbs`` fold/carry walk — and XLA re-stages each boundary through
memory per call. Here the WHOLE pipeline after the input lincombs runs as ONE
``pl.pallas_call`` per tower op:

* **Number format.** Everything inside the kernel is base-2^8 *digit planes*
  in f32 (Mosaic has no u64 — the same reasoning as
  ``fq._conv_product_digits``). A 25x16-bit-limb element is 51
  digits; digit bounds are tracked exactly (Python ints) and every
  intermediate is proven < 2^24, the f32 integer-exactness cap, so the whole
  kernel is EXACT integer arithmetic in float registers.

* **Lane planes.** Every in-kernel array is a 2-D ``[8, 128]`` f32 plane —
  one vreg: digit position d of a row sits in lane d. Lanes of a tower op
  ride in a leading axis (``[lanes, rows, 128]`` blocks) and the body walks
  the block 8 rows at a time, so Mosaic sees only lane rotations, lane
  broadcasts and leading-axis ref indexing (its layout inference refuses,
  e.g., a 4-D digit outer product reshaped to 2-D).

* **Convolution as a shifted-FMA chain.** ``t += A[:, i] * roll(B, i)`` for
  the 51 digits of A: a lane broadcast, a lane rotation and an FMA per step
  on the VPU. The 101 conv positions stay below lane 128, so no rotation
  wraps a non-zero digit.

* **Congruence fold.** Digit positions >= 48 (weight 2^384) fold through
  constant rows F8[h] = digits(2^(8*(48+h)) mod p) as broadcast-FMA terms.

* **The out-lincomb rides inside too** (``execute_plan``): a tower op's
  output linear map runs on the unreduced conv digits as one
  sum of lane planes with the integer coefficients unrolled into the body
  (negative coefficients via digit-space borrow constants == 0 mod p), so an
  fq12 multiply still reduces 12 rows, not 54 lanes — the plans.py
  contract, fused.

Every bound the schedule relies on is recorded as a trace-time ``fq._cert``
obligation (kinds ``pallas_*``) and proven per-graph by
``analysis/bounds.py`` under all three backends; a bound that does not hold
raises at trace time and the certifier records the unproven edge.

On non-TPU platforms the kernels run in Pallas **interpret mode** — the same
kernel program executed by the XLA emulator — which is how tier-1 proves
bit-exact parity (canonical values equal the digits/f64 backends and the
oracle) on the CPU dev box. Interpret mode is an emulator: it validates
numerics and schedules, not wall clock.
"""

from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fq
from ..bls_oracle.fields import P

_D = 51                 # digits per 25-limb element (base 2^8; fq._N_DIGITS)
_CONV_D = 2 * _D - 1    # 101 conv output digit positions
_FOLD_BASE = 48         # digit position of 2^384: everything above folds mod p
_F32_CAP = (1 << 24) - 1  # f32 integer exactness cap
_N_FOLD8 = 64           # fold rows provisioned (widths stay far below this)

_OUT_D = 50             # output digit positions (25 limbs)
_W = 128                # lanes of an in-kernel digit plane


def _int_to_digits(x: int, n: int) -> list[int]:
    return [(x >> (8 * i)) & 0xFF for i in range(n)]


# Congruence-fold rows in digit space: F8[h] = digits48(2^(8*(48+h)) mod p).
# Residues are < p < 2^381 — 48 digits each, entries <= 255; the rows are
# 128-lane planes whose lanes >= 48 hold zeros.
_FOLD8_NP = np.stack(
    [
        np.array(
            _int_to_digits((1 << (8 * (_FOLD_BASE + h))) % P, _W),
            dtype=np.float32,
        )
        for h in range(_N_FOLD8)
    ]
)
_FOLD8_INT = [
    [int(v) for v in _FOLD8_NP[h, :_FOLD_BASE]] for h in range(_N_FOLD8)
]
_FOLD8_VALS = [(1 << (8 * (_FOLD_BASE + h))) % P for h in range(_N_FOLD8)]


def _interpret() -> bool:
    """Interpret (emulate) the kernels off-TPU; override for testing."""
    forced = os.environ.get("LIGHTHOUSE_PALLAS_INTERPRET")
    if forced in ("0", "1"):
        return forced == "1"
    return jax.default_backend() != "tpu"


# VMEM accounting sink (analysis/memory.py, pass 6): with a list installed,
# every per-trace kernel launch records its tile signature + estimated
# per-grid-step VMEM working set. The hook lives HERE, at the host wrapper
# level, because _build_call is lru_cached — a hook inside it would fire
# once per static signature ever, not once per trace the certifier runs.
_VMEM_SINK: list | None = None


def _record_vmem(tile: int, L: int, rows_p: int, out_key) -> None:
    if _VMEM_SINK is None:
        return
    plane = tile * _W * 4  # one [tile, 128] f32 plane
    if out_key is not None:
        R, _, mneg_np, _, n_pass = _OUT_TABLE[out_key]
        n_rows_out = R
        const_b = R * _W * 4 if bool(mneg_np.any()) else 0
    else:
        n_rows_out, n_pass = L, 0
        const_b = 0
    const_b += _FOLD8_NP.nbytes
    blocks_in = (2 * L + n_pass) * plane
    block_out = n_rows_out * plane
    # the body works one 8-row chunk at a time; the lane results of a
    # chunk wait in a [L, 8, 128] scratch for the out-lincomb
    work = L * _CH * _W * 4
    _VMEM_SINK.append({
        "tile": tile,
        "lanes": L,
        "grid": rows_p // tile,
        "n_rows_out": n_rows_out,
        "n_pass": n_pass,
        "block_bytes": blocks_in + block_out,
        "const_bytes": const_b,
        "scratch_bytes": work,
        # grid-blocked operands double-buffer across grid steps
        "est_vmem_bytes": work + 2 * (blocks_in + block_out) + const_b,
    })


# --------------------------------------------------------------------------------------
# Exact digit-domain bound state (the _RState twin for base-2^8 planes)
# --------------------------------------------------------------------------------------


class _DState:
    """Per-digit-position bounds (Python ints) plus an exact value bound,
    mutually refined: digits are non-negative, so d_i <= value >> 8i. Every
    schedule op updates the state exactly — f32 exactness and the output
    value/limb targets are proven at trace time, like fq._RState."""

    __slots__ = ("digits", "value")

    def __init__(self, digits, value: int):
        digits = list(digits)
        value = min(value, sum(b << (8 * i) for i, b in enumerate(digits)))
        self.digits = [min(b, value >> (8 * i)) for i, b in enumerate(digits)]
        self.value = value


def _split_state(s: _DState) -> _DState:
    """One base-2^8 carry-save round: d -> (d & 0xFF) + (d_{i-1} >> 8),
    width + 1. Value-invariant; exact in f32 for digits < 2^24."""
    lo = [min(b, 0xFF) for b in s.digits] + [0]
    hi = [0] + [b >> 8 for b in s.digits]
    return _DState([a + b for a, b in zip(lo, hi)], s.value)


def _fold_state(s: _DState, name: str) -> _DState:
    """Fold positions >= 48 through the 2^(8k) mod p rows — exact congruence.
    Caller has checked the f32 budget; this records the obligation."""
    n_hi = len(s.digits) - _FOLD_BASE
    lo_b, hi_b = s.digits[:_FOLD_BASE], s.digits[_FOLD_BASE:]
    digits = [
        b + sum(hb * _FOLD8_INT[h][i] for h, hb in enumerate(hi_b))
        for i, b in enumerate(lo_b)
    ]
    assert fq._cert(
        "pallas_fold_f32_exact", max(digits), _F32_CAP, note=name
    ), f"{name}: pallas fold exceeds f32 exactness"
    lo_val = sum(b << (8 * i) for i, b in enumerate(lo_b))
    value = min(s.value, lo_val) + sum(
        hb * _FOLD8_VALS[h] for h, hb in enumerate(hi_b)
    )
    assert n_hi <= _N_FOLD8
    return _DState(digits, value)


def _fold_budget(s: _DState) -> int:
    """Worst post-fold digit if we folded now (f32-budget check)."""
    lo_b, hi_b = s.digits[:_FOLD_BASE], s.digits[_FOLD_BASE:]
    return max(
        b + sum(hb * _FOLD8_INT[h][i] for h, hb in enumerate(hi_b))
        for i, b in enumerate(lo_b)
    )


def _trim_state(s: _DState) -> _DState:
    digits = list(s.digits)
    while len(digits) > _FOLD_BASE and digits[-1] == 0:
        digits.pop()
    return _DState(digits, s.value)


def _reduce_schedule(
    s: _DState, value_limit: int, limb_target: int, name: str
) -> tuple[list, _DState]:
    """Static split/fold schedule bringing the state to value <= value_limit
    and recombined 16-bit limbs <= limb_target — the digit-domain twin of
    fq.reduce_limbs' phases, fully decided at trace time. Returns
    (ops, final state); ops are replayed verbatim by the kernel body.

    Positions 48-49 (the 25th limb) are LEGAL output positions: folding is
    only scheduled while the width exceeds the 50-digit output layout or the
    value target demands shrinking — a fold re-fattens the low digits by one
    row term, so folding past the value target would chase its own tail."""
    ops: list = []

    def limbs_fit(s: _DState) -> bool:
        if len(s.digits) > _OUT_D:
            return False
        d = list(s.digits) + [0] * (_OUT_D - len(s.digits))
        return all(
            d[2 * i] + (d[2 * i + 1] << 8) <= limb_target
            for i in range(_OUT_D // 2)
        )

    for _ in range(96):
        # dropping zero-bound top positions needs no kernel op: those lanes
        # already hold zeros
        s = _trim_state(s)
        w = len(s.digits)
        _lane_cert(w, name)
        if w > _OUT_D or (s.value > value_limit and w > _FOLD_BASE):
            if _fold_budget(s) <= _F32_CAP:
                s = _fold_state(s, name)
                ops.append(("fold", w - _FOLD_BASE))
            else:
                s = _split_state(s)
                ops.append(("split",))
        elif s.value > value_limit or not limbs_fit(s):
            # excess sits in low digits: surface it with a split; the next
            # iteration folds the spill at position >= 48 (always fits — the
            # digits are already carry-saved by then)
            s = _split_state(s)
            ops.append(("split",))
        else:
            break
    else:  # pragma: no cover - static schedule
        raise AssertionError(f"{name}: pallas reduce schedule did not converge")
    # final width must recombine into 25 limbs (positions 0..49)
    assert fq._cert(
        "pallas_out_width",
        sum(b << (8 * i) for i, b in enumerate(s.digits)),
        (1 << (8 * _OUT_D)) - 1,
        note=name,
    ), f"{name}: pallas output exceeds 25 limbs"
    return ops, s


def _final_certs(
    s: _DState, value_limit: int, limb_target: int, name: str
) -> None:
    """Record the output-contract obligations (value / limb / top limb)."""
    digits = list(s.digits) + [0] * (_OUT_D - len(s.digits))
    limbs = [
        digits[2 * i] + (digits[2 * i + 1] << 8) for i in range(_OUT_D // 2)
    ]
    assert fq._cert(
        "pallas_reduce_value", s.value, value_limit, note=name
    ), f"{name}: pallas value bound {s.value / P:.2f}p exceeds target"
    assert fq._cert(
        "pallas_reduce_limb", max(limbs), limb_target, note=name
    ), f"{name}: pallas limb bound {max(limbs):#x} exceeds target"
    # the f32 -> u32 recombination cast outside the kernel is lossless
    assert fq._cert(
        "pallas_digit_u32_nowrap", max(digits), (1 << 32) - 1, note=name
    )
    if value_limit == fq.PUB_VALUE_LIMIT:
        assert fq._cert(
            "pallas_reduce_top_limb",
            min(limbs[24], s.value >> (16 * 24)),
            2,
            note=name,
        )


# --------------------------------------------------------------------------------------
# Digit-space borrow constants for the fused out-lincomb
# --------------------------------------------------------------------------------------

_DSUBC_CACHE: dict[tuple[int, int], np.ndarray] = {}


def _dsubc_wide(n_digits: int, cover: int) -> np.ndarray:
    """A constant == 0 mod p in n_digits-digit space with every digit >=
    cover (subtraction cover for unreduced conv digit planes) — the base-2^8
    twin of plans._subc_wide."""
    key = (n_digits, cover)
    if key not in _DSUBC_CACHE:
        c = [cover] * n_digits
        adj = (-sum(v << (8 * i) for i, v in enumerate(c))) % P
        for i in range(_FOLD_BASE):
            c[i] += (adj >> (8 * i)) & 0xFF
        assert sum(v << (8 * i) for i, v in enumerate(c)) % P == 0
        _DSUBC_CACHE[key] = np.array(c, dtype=np.float32)
    return _DSUBC_CACHE[key]


# --------------------------------------------------------------------------------------
# Kernel construction
# --------------------------------------------------------------------------------------


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


# The kernel body works on one 8-row chunk ([8, 128] f32 — one vreg per
# plane) at a time; a grid step's block holds `tile` rows of every lane,
# with tile * lanes <= _TILE_ROWS (~0.5 MiB per operand block).
_CH = 8
_TILE_ROWS = 1024


def _row_tile(rows: int, lanes: int) -> int:
    """Row-tile size (a multiple of the 8-row chunk)."""
    tile = max(_CH, min(512, _pow2_floor(max(1, _TILE_ROWS // max(1, lanes)))))
    return min(tile, max(_CH, -(-rows // _CH) * _CH))


def _lane_cert(width: int, name: str) -> None:
    """A plane of ``width`` digit positions must fit the 128-lane register
    row with its top lane free: a carry-save split rolls every lane up one,
    and lane 127 wraps to lane 0."""
    assert fq._cert(
        "pallas_lane_width", width, _W - 1, note=name
    ), f"{name}: pallas digit plane exceeds {_W - 1} lanes"


def _roll(t, k: int):
    """Rotate the lanes of t up by k."""
    return pltpu.roll(t, k, 1)


def _bcast_lane(t, i: int):
    """Column ``i`` of t broadcast across the lanes."""
    return jnp.broadcast_to(t[:, i : i + 1], t.shape)


def _conv(A, B):
    """Digit convolution on [rows, 128] planes: sum_i A[:, i] * (B << i
    lanes). Operand digits live in lanes 0..50 and the 101 output positions
    stay below lane 128, so the lane rotation never wraps a non-zero digit.
    Exact: every product and partial sum is an integer < 2^24 (certified as
    pallas_conv_digit_f32_exact)."""
    t = _bcast_lane(A, 0) * B
    for i in range(1, _D):
        t = t + _bcast_lane(A, i) * _roll(B, i)
    return t


def _split_array(t):
    """In-kernel base-2^8 carry-save round (exact: digits < 2^24)."""
    hi = jnp.floor(t * (1.0 / 256.0))
    return (t - hi * 256.0) + _roll(hi, 1)


def _fold_array(t, n_hi: int, f8_ref):
    """Fold digit positions 48..48+n_hi through the 2^(8k) mod p rows:
    broadcast-FMA terms on the VPU, exact like the conv (every product is
    bounded by the certified post-fold digit, < 2^24)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    acc = jnp.where(lane < _FOLD_BASE, t, 0.0)
    for h in range(n_hi):
        acc = acc + _bcast_lane(t, _FOLD_BASE + h) * f8_ref[h : h + 1, :]
    return acc


def _replay(t, ops, f8_ref):
    """Apply a static reduce schedule to in-kernel digit planes."""
    for op in ops:
        if op[0] == "split":
            t = _split_array(t)
        else:
            t = _fold_array(t, op[1], f8_ref)
    return t


@functools.lru_cache(maxsize=512)
def _build_call(
    rows_p: int,
    tile: int,
    n_lanes: int,
    pre_ops: tuple,
    out_key,          # None | (R, mpos bytes-key, mneg key, oconst key, n_pass)
    post_ops: tuple,
    interpret: bool,
):
    """Build (and cache) the fused pallas_call for one static signature.

    Operands are digit planes ``[lanes, rows, 128]`` f32; a grid step takes
    ``tile`` rows of every lane. The out-lincomb's integer coefficients are
    unrolled into the kernel body (they are part of the cache key via
    ``out_key``); only its borrow constants ride in as an operand."""
    L = n_lanes
    grid = rows_p // tile
    has_out = out_key is not None
    if has_out:
        R, mpos_np, mneg_np, oconst_np, n_pass = _OUT_TABLE[out_key]
        has_neg = bool(mneg_np.any())
        n_rows_out = R
        terms = [
            (
                [(i, float(c)) for i, c in enumerate(mpos_np[r]) if c],
                [(i, float(c)) for i, c in enumerate(mneg_np[r]) if c],
            )
            for r in range(R)
        ]
    else:
        n_rows_out = L
        has_neg = False
        n_pass = 0

    def scaled(x, c):
        return x if c == 1.0 else x * c

    def body(*refs):
        a_ref, b_ref, f8_ref = refs[:3]
        rest = list(refs[3:])
        oconst_ref = rest.pop(0) if has_neg else None
        ain_ref = rest.pop(0) if n_pass else None
        o_ref, lanes_ref = rest

        def chunk(c, carry):
            rs = pl.ds(pl.multiple_of(c * _CH, _CH), _CH)

            def lane(i, carry):
                t = _replay(_conv(a_ref[i, rs, :], b_ref[i, rs, :]), pre_ops, f8_ref)
                if has_out:
                    lanes_ref[i] = t  # lint: allow(impure-closure) — ref store
                else:
                    o_ref[i, rs, :] = t  # lint: allow(impure-closure) — ref store
                return carry

            jax.lax.fori_loop(0, L, lane, 0)
            if not has_out:
                return carry
            src = [lanes_ref[i] for i in range(L)]
            src += [ain_ref[j, rs, :] for j in range(n_pass)]
            for r, (pos, neg) in enumerate(terms):
                acc = jnp.zeros((_CH, _W), jnp.float32)
                for i, c in pos:
                    acc = acc + scaled(src[i], c)
                if neg:
                    # C_r - sum(neg) stays in [0, C_r]: C_r covers the
                    # negative part digit by digit
                    cov = jnp.broadcast_to(oconst_ref[r : r + 1, :], (_CH, _W))
                    for i, c in neg:
                        cov = cov - scaled(src[i], c)
                    acc = acc + cov
                o_ref[r, rs, :] = _replay(acc, post_ops, f8_ref)  # lint: allow(impure-closure) — ref store
            return carry

        jax.lax.fori_loop(0, tile // _CH, chunk, 0)

    def bs(n):
        return pl.BlockSpec((n, tile, _W), lambda i: (0, i, 0))

    def const_bs(shape):
        return pl.BlockSpec(shape, lambda i, _n=len(shape): (0,) * _n)

    in_specs = [bs(L), bs(L), const_bs(_FOLD8_NP.shape)]
    # keep the constant operands as NUMPY in the cached closure: a jnp
    # constant materialized inside whatever trace first built this call
    # would be a trace-local tracer — caching it leaks it into every later
    # trace (UnexpectedTracerError). asarray at run time is a per-trace
    # constant, folded by XLA.
    operands_const = [_FOLD8_NP]
    if has_neg:
        oconst_w = np.zeros((R, _W), dtype=np.float32)
        oconst_w[:, : oconst_np.shape[1]] = oconst_np
        in_specs.append(const_bs(oconst_w.shape))
        operands_const.append(oconst_w)
    if n_pass:
        in_specs.append(bs(n_pass))

    call = pl.pallas_call(
        body,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=bs(n_rows_out),
        out_shape=jax.ShapeDtypeStruct((n_rows_out, rows_p, _W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((L, _CH, _W), jnp.float32)],
        interpret=interpret,
    )

    def run(A_d, B_d, Ain_d=None):
        args = [A_d, B_d] + [jnp.asarray(c) for c in operands_const]
        if n_pass:
            args.append(Ain_d)
        # the kernel is all f32/i32: traced under x64, its indices, loop
        # counters and shifts would be i64, which Mosaic does not lower
        with jax.enable_x64(False):
            return call(*args)

    return run


# side table: content-addressed out-map matrices (lru_cache keys must be
# hashable; the key is a digest of the matrix content, the table holds the
# arrays themselves)
_OUT_TABLE: dict = {}


def _out_key(R, mpos, mneg, oconst, n_pass):
    key = (R, mpos.tobytes(), mneg.tobytes(), oconst.tobytes(), n_pass)
    _OUT_TABLE[key] = (R, mpos, mneg, oconst, n_pass)
    return key


# --------------------------------------------------------------------------------------
# Host-side wrappers
# --------------------------------------------------------------------------------------


def _planes(x, n_lanes: int, rows_p: int):
    """u64 limbs [..., n_lanes, 25] -> f32 digit planes [n_lanes, rows_p, 128]
    (digit extraction stays in XLA: Mosaic has no u64)."""
    d = fq._to_digits_f32(x.astype(jnp.uint64)).reshape(-1, n_lanes, _D)
    d = jnp.moveaxis(d, 1, 0)
    return jnp.pad(d, ((0, 0), (0, rows_p - d.shape[1]), (0, _W - _D)))


def _limbs_of(d):
    """f32 digit planes [..., 50] -> u64 16-bit-limb planes [..., 25]
    (exact: the schedule proves digits < 2^24 < 2^32)."""
    di = d.astype(jnp.uint32).astype(jnp.uint64)
    pairs = di.reshape(d.shape[:-1] + (_OUT_D // 2, 2))
    return pairs[..., 0] + (pairs[..., 1] << jnp.uint64(8))


def _rows_of(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _run_fused(A, B, pre_ops, out_key, post_ops, Ain=None):
    """Run the cached call on u64 operands [rows, L, 25] (Ain: [rows,
    n_pass, 25]); returns limbs [rows, n_rows_out, 25]."""
    rows, L = A.shape[0], A.shape[1]
    tile = _row_tile(rows, L)
    rows_p = -(-rows // tile) * tile
    _record_vmem(tile, L, rows_p, out_key)
    run = _build_call(
        rows_p, tile, L, tuple(pre_ops), out_key, tuple(post_ops), _interpret()
    )
    ain = None if Ain is None else _planes(Ain, Ain.shape[1], rows_p)
    out = run(_planes(A, L, rows_p), _planes(B, L, rows_p), ain)
    return _limbs_of(jnp.moveaxis(out[:, :rows, :_OUT_D], 0, 1))


def fused_mul(a, b, lazy: bool = False):
    """The fused pallas twin of fq.mont_mul (lazy=False: operands within the
    lazy budget, output at plans.PUB_BOUND) / fq.mont_mul_lazy (lazy=True:
    chain-bound operands and output — the chain fixed point). One pallas_call:
    digit conv (VPU shifted-FMA chain) -> static fold/carry schedule, all
    in-register."""
    name = "pallas_mul_lazy" if lazy else "pallas_mul"
    if lazy:
        in_limb, in_value = fq.CHAIN_LIMB_TARGET, fq.CHAIN_VALUE_LIMIT
        value_limit, limb_target = fq.CHAIN_VALUE_LIMIT, fq.CHAIN_LIMB_TARGET
    else:
        in_limb, in_value = fq._IN_LIMB, fq._IN_VALUE
        value_limit, limb_target = fq.PUB_VALUE_LIMIT, fq.PUB_LIMB_TARGET
    a, b = jnp.broadcast_arrays(a, b)
    batch = a.shape[:-1]
    rows = _rows_of(batch)
    dig = fq._digit_bound(in_limb)
    conv = [
        (min(d, 2 * _D - 2 - d, _D - 1) + 1) * dig * dig
        for d in range(_CONV_D)
    ]
    assert fq._cert(
        "pallas_conv_digit_f32_exact", max(conv), _F32_CAP, note=name
    ), f"{name}: digit conv exceeds f32 exactness"
    state = _DState(conv, in_value * in_value)
    ops, state = _reduce_schedule(state, value_limit, limb_target, name)
    _final_certs(state, value_limit, limb_target, name)
    out = _run_fused(
        a.reshape(rows, 1, fq.NLIMBS), b.reshape(rows, 1, fq.NLIMBS), ops, None, ()
    )
    return out.reshape(batch + (fq.NLIMBS,))


def execute_plan(
    plan, a, b, in_bound_a, in_bound_b, name: str = "", out_bound=None
):
    """The full pallas arm of plans.execute: input lincombs (XLA u64 — they
    are constant-matrix dots the compiler already fuses), then ONE fused
    kernel for conv -> out-lincomb -> congruence-fold -> carry. Backend-
    independent entry (the certifier registers it under every backend);
    plans.execute dispatches here when conv_backend() == "pallas"."""
    from . import plans

    kname = name or "plan"
    A, ba = plans.lincomb(plan.a_rows, a, in_bound_a, kname + ".A")
    b = plans.append_const_pool(plan, b)
    B, bb = plans.lincomb(plan.b_rows, b, in_bound_b, kname + ".B")
    A, B = jnp.broadcast_arrays(A, B)
    batch = A.shape[:-2]
    rows = _rows_of(batch)
    L = len(plan.a_rows)

    # conv digit bounds per position, one lane-uniform state
    dig_a, dig_b = fq._digit_bound(ba.limb), fq._digit_bound(bb.limb)
    conv = [
        (min(d, 2 * _D - 2 - d, _D - 1) + 1) * dig_a * dig_b
        for d in range(_CONV_D)
    ]
    assert fq._cert(
        "pallas_conv_digit_f32_exact", max(conv), _F32_CAP, note=kname
    ), f"{kname}: digit conv exceeds f32 exactness"
    lane_value = (ba.value_p * P) * (bb.value_p * P)
    lane_state = _DState(conv, lane_value)

    # pass-through rows reference the raw input a
    has_pass = any(i < 0 for lc in plan.out_rows for i in lc.d)
    n_pass = a.shape[-2] if has_pass else 0
    pass_dig = fq._digit_bound(in_bound_a.limb)
    pass_value = in_bound_a.value_p * P
    if has_pass:
        out_rows = plans.remap_passthrough_rows(plan, L)
    else:
        out_rows = plan.out_rows

    # pre-split the conv lanes until the out-lincomb accumulators fit f32
    coeff_pos = [
        sum(c for c in lc.d.values() if c > 0) for lc in out_rows
    ]
    coeff_neg = [
        sum(-c for c in lc.d.values() if c < 0) for lc in out_rows
    ]
    pre_ops: list = []
    for _ in range(8):
        worst_lane = max(lane_state.digits)
        worst_in = max(worst_lane, pass_dig if has_pass else 0)
        cover = max(coeff_neg) * worst_in if any(coeff_neg) else 0
        budget = max(coeff_pos + [1]) * worst_in + cover + 255
        if budget <= _F32_CAP:
            break
        lane_state = _split_state(lane_state)
        pre_ops.append(("split",))
    else:  # pragma: no cover - static schedule
        raise AssertionError(f"{kname}: pallas out-lincomb does not fit f32")
    w = len(lane_state.digits)
    _lane_cert(w, kname)

    # out-row bound profiles + digit-space borrow constants
    def profile(idx):
        if idx < L:
            return lane_state.digits, lane_state.value
        return (
            [pass_dig] * _D + [0] * (w - _D),
            pass_value,
        )

    R = len(out_rows)
    mpos = np.zeros((R, L + n_pass), dtype=np.float32)
    mneg = np.zeros((R, L + n_pass), dtype=np.float32)
    oconst = np.zeros((R, w), dtype=np.float32)
    out_digits = [0] * w
    out_value = 0
    for r, lc in enumerate(out_rows):
        row_d = [0] * w
        row_v = 0
        n_cover = 0
        for idx, c in sorted(lc.d.items()):
            pdig, pval = profile(idx)
            if c > 0:
                mpos[r, idx] = c
                row_d = [x + c * y for x, y in zip(row_d, pdig)]
                row_v += c * pval
            else:
                mneg[r, idx] = -c
                n_cover += (-c) * max(pdig)
        if n_cover:
            subc = _dsubc_wide(w, n_cover)
            oconst[r] = subc
            row_d = [x + int(y) for x, y in zip(row_d, subc)]
            row_v += sum(int(y) << (8 * i) for i, y in enumerate(subc))
        assert fq._cert(
            "pallas_lincomb_f32_exact", max(row_d), _F32_CAP, note=kname
        ), f"{kname}: pallas out-row exceeds f32 exactness"
        out_digits = [max(x, y) for x, y in zip(out_digits, row_d)]
        out_value = max(out_value, row_v)

    out_state = _DState(out_digits, out_value)
    if out_bound is None:
        value_limit, limb_target = fq.PUB_VALUE_LIMIT, fq.PUB_LIMB_TARGET
    else:
        # the declared top-limb bound must dominate what the walk guarantees
        assert fq._cert(
            "pallas_out_bound_top_sound",
            min(out_bound.limb, (out_bound.value_p * P) >> (16 * 24)),
            out_bound.top,
            note=kname,
        ), "out_bound.top unsound for its value/limb bounds"
        value_limit, limb_target = out_bound.value_p * P, out_bound.limb
    post_ops, out_state = _reduce_schedule(
        out_state, value_limit, limb_target, kname
    )
    _final_certs(out_state, value_limit, limb_target, kname)

    Ain = None
    if has_pass:
        Ain = jnp.broadcast_to(a, batch + a.shape[-2:]).reshape(
            rows, n_pass, fq.NLIMBS
        )
    key = _out_key(R, mpos, mneg, oconst, n_pass)
    out = _run_fused(
        A.reshape(rows, L, fq.NLIMBS), B.reshape(rows, L, fq.NLIMBS),
        pre_ops, key, post_ops, Ain,
    )
    return out.reshape(batch + (R, fq.NLIMBS))
