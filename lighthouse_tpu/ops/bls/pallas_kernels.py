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

* **Three block layouts, chosen by the call's rows** (``layout_for``).

  - *Lane* (below ``DIGIT_MAJOR_MIN_ROWS``): every in-kernel array is a
    2-D ``[8, 128]`` f32 plane — one vreg: digit position d of a row sits
    in lane d. Lanes of a tower op ride in a leading axis (``[lanes, rows,
    128]`` blocks) and the body walks the block 8 rows at a time, so Mosaic
    sees only lane rotations, lane broadcasts and leading-axis ref indexing
    (its layout inference refuses, e.g., a 4-D digit outer product reshaped
    to 2-D). The conv is a shifted-FMA chain, ``t += A[:, i] * roll(B,
    i)``: a lane broadcast, a lane rotation and an FMA per step; the 101
    conv positions stay below lane 128, so no rotation wraps a non-zero
    digit.
  - *Digit-major* (from ``FULL_BLOCK_ROWS`` up): operands are ``[lanes,
    51, rows / 128, 128]``, so one vreg holds one digit of 1,024 rows and
    every schedule step is elementwise across digit planes: the conv is
    multiply-adds of whole planes, the fold multiplies by rows of an F8
    table, a carry moves one plane into the next. No op crosses lanes.
  - *Packed* (``DIGIT_MAJOR_MIN_ROWS`` up to ``FULL_BLOCK_ROWS``): the
    digit-major body on a block whose 8 sublanes hold G lanes of the plan,
    each on the 128-row sublanes its rows need, so every vector op still
    fills the vreg. The out-map mixes the G lanes into the sublanes of its
    out-rows with sublane rotations; the out-rows are reduced G at a time.
    With G = 1 it is the digit-major kernel on one block.

* **Congruence fold.** Digit positions >= 48 (weight 2^384) fold through
  constant rows F8[h] = digits(2^(8*(48+h)) mod p).

* **The out-lincomb rides inside too** (``execute_plan``): a tower op's
  output linear map runs on the unreduced conv digits with the integer
  coefficients of the plan (negative coefficients via digit-space borrow
  constants == 0 mod p), so an fq12 multiply still reduces 12 rows, not 54
  lanes — the plans.py contract, fused.

Every layout replays the same static schedule on the same exact integers, so
their outputs agree bit for bit.

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
from ...utils.tracing import PALLAS_KERNELS_TOTAL
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
        "layout": "lane",
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


def _record_dm_vmem(
    layout, sub, G, L, rows_p, pre_ops, out_key, post_ops
) -> None:
    """The digit-major twin of _record_vmem: one grid step holds a lane
    group's operand blocks, the out-groups' block, the F8 table and the
    conv / accumulator scratch."""
    if _VMEM_SINK is None:
        return
    wt, wa = _dm_scratch_widths(pre_ops, out_key, post_ops)
    if out_key is not None:
        R, _, _, _, n_pass = _OUT_TABLE[out_key]
    else:
        R, n_pass = L, 0
    est = _dm_vmem(G * sub, -(-R // G), -(-n_pass // G), wt, wa)
    _VMEM_SINK.append({
        "layout": layout,
        "tile": sub * _W,
        "lanes": L,
        "group": G,
        "grid": rows_p // (sub * _W) * -(-L // G),
        "n_rows_out": R,
        "n_pass": n_pass,
        **est,
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
    s: _DState, value_limit: int, limb_target: int, name: str, layout: str
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
        _width_cert(w, name, layout)
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


def _width_cert(width: int, name: str, layout: str) -> None:
    """A state of ``width`` digit positions must fit its layout. Lane: the
    128-lane register row with its top lane free (a carry-save split rolls
    every lane up one, and lane 127 wraps to lane 0). Digit-major: each
    plane a fold reads must have its F8 row (the table holds _N_FOLD8)."""
    if layout == "lane":
        assert fq._cert(
            "pallas_lane_width", width, _W - 1, note=name
        ), f"{name}: pallas digit plane exceeds {_W - 1} lanes"
    else:
        assert fq._cert(
            "pallas_digit_planes", width, _FOLD_BASE + _N_FOLD8, note=name
        ), f"{name}: pallas digit-major width exceeds the F8 rows"


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


# --------------------------------------------------------------------------------------
# Digit-major layout: rows across the vreg, digit positions on a leading axis
# --------------------------------------------------------------------------------------
#
# Operands are [L, D, rows / 128, 128] f32: plane d of a block holds digit d
# of S x 128 rows, so one [8, 128] vreg carries one digit of 1,024 rows and
# every step of the schedule is elementwise across planes. The conv is plain
# multiply-adds of whole planes, the fold multiplies the 48 low planes by
# rows of an F8 table held in VMEM, a split carries one plane into the next;
# nothing crosses lanes. The grid walks (row block, lane group); a plan's
# out-rows accumulate over the lane groups in VMEM and are reduced at the
# last one. Results are the lane kernel's, bit for bit: the schedule and its
# bounds are the same, and every intermediate is an exact integer below
# 2^24, so the order of the sums does not matter.
#
# A call under 1,024 rows needs S < 8 sublanes a lane, so its block packs G
# = 8 / S lanes: lane g of a group on sublanes g S .. g S + S - 1, out-row
# g of an out-group likewise. Conv, splits and
# folds do not care which lane a sublane holds. The out-map adds, for each
# rotation k of the group's sublanes by k S, the rotated planes times a
# plane of coefficients: sublane slot g of out-group o takes coefficient
# [o G + g, lane (g - k) mod G] (zero: the rotation is skipped).
#
# Measured on a TPU v5e (PERF.md): the F8 entries as constants in the code
# ran the kernel 6-71% faster than the table, but made it 5x larger to
# trace, lower and compile (6,659 equations against 1,266 for fused_mul),
# which every process that traces kernels pays at start; as SMEM scalars
# the first fold was 7x slower. Register blocks of 8 to 17 conv outputs
# time the same.

_DM_S = 8         # sublanes of a plane in a full block: 1,024 rows a grid step
_DM_KB = 13       # conv outputs accumulated in registers at once
# the F8 rows for the digit-major fold: entry [h, j] across the 128 lanes
_FOLD8_DM = np.ascontiguousarray(np.broadcast_to(
    _FOLD8_NP[:, :_FOLD_BASE, None, None], (_N_FOLD8, _FOLD_BASE, 1, _W)
))


def _dm_rows(rows: int) -> tuple[int, int]:
    """(sublanes S a lane takes in a block, padded row count) for the
    digit-major layouts: S = 8 (1,024 rows a grid step) from 1,024 rows up;
    below that one block of the next power of two of 128-row sublanes."""
    ns = -(-rows // _W)
    s = _DM_S if ns >= _DM_S else 1 << (ns - 1).bit_length()
    return s, -(-ns // s) * s * _W


def _dm_group(layout: str, sub: int, lanes: int, rows_out: int) -> int:
    """Lanes G a block packs: in the packed layout as many as its 8
    sublanes hold (zero lanes pad a small plan, so every sublane rotation
    is one of a whole vreg), unless one lane feeds one out-row; else 1."""
    if layout != "packed" or max(lanes, rows_out) == 1:
        return 1
    return _DM_S // sub


def _dm_widths(w: int, ops) -> tuple[int, int]:
    """(final width, widest plane count) of a schedule replayed on w planes:
    a split adds the top plane, a fold leaves the 48 low ones."""
    top = w
    for op in ops:
        if op[0] == "split":
            w += 1
        else:
            assert _FOLD_BASE + op[1] <= w
            w = _FOLD_BASE
        top = max(top, w)
    return w, top


def _dm_scratch_widths(pre_ops, out_key, post_ops) -> tuple[int, int]:
    """Planes of the conv scratch and of each out-row accumulator (0
    without an out-map) that a schedule needs."""
    w_conv, wt = _dm_widths(_CONV_D, pre_ops)
    wa = 0 if out_key is None else _dm_widths(w_conv, post_ops)[1]
    return wt, wa


def _dm_conv(a, b, t) -> None:
    """t[d] = sum_i a[i] * b[d - i] over the 51 x 51 digit planes of the
    refs a and b into planes 0..100 of t, _DM_KB outputs at a time: a
    block's accumulators and a window of b planes ride in registers
    through a loop over i, which loads one a plane and shifts in one b
    plane a step (zeros once past b's first digit). Exact like the lane
    conv (pallas_conv_digit_f32_exact)."""
    top = _D - 1
    for d0 in range(0, _CONV_D, _DM_KB):
        k_n = min(_DM_KB, _CONV_D - d0)
        lo, hi = max(0, d0 - top), min(top, d0 + k_n - 1)
        # zero planes laid out like loaded ones: Mosaic keeps a loop carry
        # in one layout, and a constant's is replicated
        zero = a[0] * 0.0
        # window[k] = b[d0 + k - i]
        win = tuple(b[d0 + k - lo] if d0 + k - lo <= top else zero
                    for k in range(k_n))

        def step(i, carry, d0=d0, shift_in=True):
            accs, win = carry
            ai = a[i]
            accs = tuple(
                jax.lax.add(acc, jax.lax.mul(ai, bk)) for acc, bk in zip(accs, win)
            )
            nb = b[d0 - i - 1] if shift_in else ai * 0.0
            return accs, (nb,) + win[:-1]

        carry = ((zero,) * k_n, win)
        mid = min(hi + 1, d0)  # b[d0 - i - 1] exists for i < d0
        if mid > lo:
            carry = jax.lax.fori_loop(lo, mid, step, carry)
        if hi + 1 > max(lo, mid):
            carry = jax.lax.fori_loop(
                max(lo, mid), hi + 1,
                functools.partial(step, d0=d0, shift_in=False), carry,
            )
        t[d0 : d0 + k_n] = jnp.stack(carry[0])


def _dm_split(t, w: int) -> None:
    """Carry-save round over planes 0..w of t: d -> (d mod 256) + (d_{-1}
    div 256), width + 1 (the digit-major twin of _split_array), as one
    array op over the planes."""
    x = t[0:w]
    hi = jnp.floor(x * (1.0 / 256.0))
    zero = jnp.zeros((1,) + x.shape[1:], jnp.float32)
    t[0 : w + 1] = jnp.concatenate([x - hi * 256.0, zero]) + jnp.concatenate(
        [zero, hi]
    )


def _dm_fold(t, n_hi: int, f8) -> None:
    """Fold planes 48..48+n_hi of t into the 48 low planes through the F8
    rows: one array op over the 48 planes for each folded plane, its F8
    row read from ``f8`` ([64, 48, 1, 128], entry [h, j] across the
    lanes)."""
    lo = t[0:_FOLD_BASE]
    hi = t[_FOLD_BASE : _FOLD_BASE + n_hi]
    for h in range(n_hi):
        lo = jax.lax.add(lo, jax.lax.mul(hi[h][None], f8[h]))
    t[0:_FOLD_BASE] = lo


def _dm_replay(t, w: int, ops, f8) -> int:
    """Apply a static reduce schedule to the first w planes of t in place;
    returns the final width (planes at and above it are dead)."""
    for op in ops:
        if op[0] == "split":
            _dm_split(t, w)
            w += 1
        else:
            _dm_fold(t, op[1], f8)
            w = _FOLD_BASE
    return w


def _dm_write(o, t, w: int) -> None:
    """The 50 output planes: t's first w, zeros above."""
    n = min(w, _OUT_D)
    o[0:n] = t[0:n]
    if n < _OUT_D:
        o[n:_OUT_D] = jnp.zeros((_OUT_D - n,) + o.shape[1:], jnp.float32)


def _dm_tables(G, sub, L, R, n_pass, coef, oconst):
    """The out-map's tables padded to whole groups: the coefficients
    [out-rows, lanes | pass-through inputs] (SMEM), lane group i in columns
    i G .. i G + G - 1 and pass group j after the last lane group; and the
    borrow constants [out-groups, G sub, 128] (VMEM), out-row g of a group
    on the sublanes of slot g with digit d in lane d."""
    n_in, n_pg, n_out = -(-L // G), -(-n_pass // G), -(-R // G)
    c = np.zeros((n_out * G, (n_in + n_pg) * G), np.float32)
    c[:R, :L] = coef[:, :L]
    c[:R, n_in * G : n_in * G + n_pass] = coef[:, L:]
    k = np.zeros((n_out * G, _W), np.float32)
    k[:R, : oconst.shape[1]] = oconst
    k = np.repeat(k.reshape(n_out, G, _W), sub, axis=1)
    return c, k


@functools.lru_cache(maxsize=512)
def _build_dm_call(
    layout: str,
    rows_p: int,
    sub: int,
    G: int,
    n_lanes: int,
    pre_ops: tuple,
    out_key,
    post_ops: tuple,
    interpret: bool,
):
    """Build (and cache) the digit-major pallas_call for one signature.

    Operands are digit planes ``[lane groups, D, G rows_p / 128, 128]``; the
    grid is (row block, lane group), a block holds ``sub`` x 128 rows of
    each of G lanes (G > 1 only in the packed layout, one row block); the
    kernel is named for its layout. Without an out-map each group's reduced
    planes are its output; with one, the groups' pre-split conv planes
    accumulate into the out-groups (borrow constants and pass-through terms
    seeded at the first group) and the out-groups are reduced and written
    at the last."""
    L = n_lanes
    S = G * sub
    n_in = -(-L // G)
    grid = (rows_p // (sub * _W), n_in)
    plane = (S, _W)
    wt, wa = _dm_scratch_widths(pre_ops, out_key, post_ops)
    has_out = out_key is not None
    if has_out:
        R, mpos_np, mneg_np, oconst_np, n_pass = _OUT_TABLE[out_key]
        coef_np, oconst_np = _dm_tables(
            G, sub, L, R, n_pass, (mpos_np - mneg_np).astype(np.float32),
            oconst_np,
        )
        n_out, n_pg = -(-R // G), -(-n_pass // G)
    else:
        n_out, n_pg = n_in, 0

    def body(*refs):
        a_ref, b_ref, f8_ref = refs[:3]
        rest = list(refs[3:])
        if has_out:
            coef_ref, oconst_ref = rest.pop(0), rest.pop(0)
        ain_ref = rest.pop(0) if n_pg else None
        o_ref, t_ref = rest[0], rest[1]

        _dm_conv(a_ref.at[0], b_ref.at[0], t_ref)
        w = _dm_replay(t_ref, _CONV_D, pre_ops, f8_ref)

        if not has_out:
            _dm_write(o_ref.at[0], t_ref, w)
            return

        acc_ref = rest[2]
        group = pl.program_id(1)
        sublane = jax.lax.broadcasted_iota(jnp.int32, plane, 0)

        def out_loop(fn):
            def step(o, c):
                fn(o)
                return c

            jax.lax.fori_loop(0, n_out, step, 0)

        def mix(o, src, n, col):
            """acc[o] += the out-map terms of the G lanes of src, whose
            coefficients sit in columns col .. col + G - 1."""
            for k in range(G):
                cs = [coef_ref[o * G + g, col + (g - k) % G] for g in range(G)]
                used = cs[0] != 0.0
                for c in cs[1:]:
                    used = jnp.logical_or(used, c != 0.0)

                @pl.when(used)
                def _(k=k, cs=cs):
                    x = src[0:n]
                    if k:  # lint: allow(tracer-branch) — k is the static rotation
                        x = pltpu.roll(x, k * sub, 1)
                    c = cs[0]
                    if G > 1:  # coefficient of slot g on its sublanes
                        c = jnp.full(plane, c, jnp.float32)
                        for g in range(1, G):
                            c = jnp.where(sublane >= g * sub, cs[g], c)
                    acc_ref[o, 0:n] = acc_ref[o, 0:n] + x * c  # lint: allow(impure-closure) — ref store

        @pl.when(group == 0)
        def _seed():
            # borrow constants (zero for rows without a negative term), then
            # the pass-through terms of the raw input
            def seed(o):
                oc = oconst_ref[o]
                acc_ref[o, 0:w] = jnp.stack(  # lint: allow(impure-closure) — ref store
                    [_bcast_lane(oc, d) for d in range(w)]
                )
                for j in range(n_pg):
                    mix(o, ain_ref.at[j], _D, (n_in + j) * G)

            out_loop(seed)

        out_loop(lambda o: mix(o, t_ref, w, group * G))

        @pl.when(group == n_in - 1)
        def _reduce():
            def reduce_out(o):
                row = acc_ref.at[o]
                _dm_write(o_ref.at[o], row, _dm_replay(row, w, post_ops, f8_ref))

            out_loop(reduce_out)

    def group_bs(n_dig):
        return pl.BlockSpec((1, n_dig, S, _W), lambda i, l: (l, 0, i, 0))

    in_specs = [
        group_bs(_D), group_bs(_D),
        pl.BlockSpec(_FOLD8_DM.shape, lambda i, l: (0, 0, 0, 0)),
    ]
    operands_const = [_FOLD8_DM]
    scratch = [pltpu.VMEM((wt, S, _W), jnp.float32)]
    if has_out:
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(oconst_np.shape, lambda i, l: (0, 0, 0)),
        ]
        operands_const += [coef_np, oconst_np]
        scratch.append(pltpu.VMEM((n_out, wa, S, _W), jnp.float32))
        out_spec = pl.BlockSpec((n_out, _OUT_D, S, _W), lambda i, l: (0, 0, i, 0))
    else:
        out_spec = group_bs(_OUT_D)
    if n_pg:
        in_specs.append(
            pl.BlockSpec((n_pg, _D, S, _W), lambda i, l: (0, 0, i, 0))
        )

    name = "fused_dm_packed" if layout == "packed" else "fused_digit_major"
    call = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_out, _OUT_D, G * rows_p // _W, _W), jnp.float32
        ),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_dm_vmem_limit(_dm_vmem(S, n_out, n_pg, wt, wa)),
        ),
        interpret=interpret,
        name=name,
    )

    def run(A_d, B_d, Ain_d=None):
        args = [A_d, B_d] + [jnp.asarray(c) for c in operands_const]
        if n_pg:
            args.append(Ain_d)
        with jax.enable_x64(False), jax.named_scope(name):
            return call(*args)

    return run


def _dm_vmem(S, n_out, n_pg, wt, wa) -> dict:
    """VMEM working set of one digit-major grid step, in bytes: blocks of
    S sublanes, n_out out-groups (or, without an out-map, lane groups) and
    n_pg pass groups."""
    plane = S * _W * 4
    blocks_in = (2 * _D + n_pg * _D) * plane
    block_out = n_out * _OUT_D * plane if wa else _OUT_D * plane
    scratch = (wt + n_out * wa) * plane
    # the F8 table, and with an out-map the borrow constants
    const = _FOLD8_DM.nbytes + (n_out * plane if wa else 0)
    return {
        "block_bytes": blocks_in + block_out,
        "const_bytes": const,
        "scratch_bytes": scratch,
        # every blocked operand, the F8 table too, double-buffers
        "est_vmem_bytes": scratch + 2 * (blocks_in + block_out + const),
    }


def _dm_vmem_limit(est: dict) -> int:
    """Scoped VMEM to ask Mosaic for: the working set plus room for its
    own temporaries, at least its 16 MiB default."""
    return max(16 << 20, est["est_vmem_bytes"] + (8 << 20))


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


def _dm_planes(x, n_lanes: int, rows_p: int, G: int):
    """u64 limbs [..., n_lanes, 25] -> f32 digit planes [lane groups, 51,
    G rows_p / 128, 128]: digit d of row 128 s + j of lane l at [l // G, d,
    (l % G) rows_p / 128 + s, j], lanes padded with zeros to whole groups
    (G > 1 only where rows_p / 128 sublanes are one block)."""
    d = fq._to_digits_f32(x.astype(jnp.uint64)).reshape(-1, n_lanes, _D)
    n_g = -(-n_lanes // G)
    d = jnp.pad(d, ((0, 0), (0, n_g * G - n_lanes), (0, 0)))
    d = jnp.transpose(d.reshape(-1, n_g, G, _D), (1, 3, 2, 0))
    d = jnp.pad(d, ((0, 0), (0, 0), (0, 0), (0, rows_p - d.shape[3])))
    return d.reshape(n_g, _D, G * rows_p // _W, _W)


# Calls of at least DIGIT_MAJOR_MIN_ROWS rows run a digit-major kernel, the
# others the lane kernel: the crossover measured on a TPU v5e (PERF.md, the
# sweep of both layouts by rows and lanes). Under FULL_BLOCK_ROWS a lane's
# rows fill less than a block's 8 sublanes, so the block packs lanes.
DIGIT_MAJOR_MIN_ROWS = 64
FULL_BLOCK_ROWS = _DM_S * _W


def layout_for(rows: int) -> str:
    """The kernel layout a call of ``rows`` rows runs in."""
    if rows < DIGIT_MAJOR_MIN_ROWS:
        return "lane"
    return "packed" if rows < FULL_BLOCK_ROWS else "digit_major"


def _run_fused(A, B, pre_ops, out_key, post_ops, Ain=None):
    """Run the cached call on u64 operands [rows, L, 25] (Ain: [rows,
    n_pass, 25]) in the layout its rows choose; returns limbs [rows,
    n_rows_out, 25]."""
    rows, L = A.shape[0], A.shape[1]
    layout = layout_for(rows)
    PALLAS_KERNELS_TOTAL.inc(layout=layout)
    n_pass = 0 if Ain is None else Ain.shape[1]
    pre_ops, post_ops = tuple(pre_ops), tuple(post_ops)
    if layout == "lane":
        tile = _row_tile(rows, L)
        rows_p = -(-rows // tile) * tile
        _record_vmem(tile, L, rows_p, out_key)
        run = _build_call(
            rows_p, tile, L, pre_ops, out_key, post_ops, _interpret()
        )
        ain = None if Ain is None else _planes(Ain, n_pass, rows_p)
        out = run(_planes(A, L, rows_p), _planes(B, L, rows_p), ain)
        return _limbs_of(jnp.moveaxis(out[:, :rows, :_OUT_D], 0, 1))
    sub, rows_p = _dm_rows(rows)
    R = L if out_key is None else _OUT_TABLE[out_key][0]
    G = _dm_group(layout, sub, L, R)
    _record_dm_vmem(layout, sub, G, L, rows_p, pre_ops, out_key, post_ops)
    run = _build_dm_call(
        layout, rows_p, sub, G, L, pre_ops, out_key, post_ops, _interpret()
    )
    ain = None if Ain is None else _dm_planes(Ain, n_pass, rows_p, G)
    out = run(_dm_planes(A, L, rows_p, G), _dm_planes(B, L, rows_p, G), ain)
    out = out.reshape(out.shape[0], _OUT_D, G, rows_p)[..., :rows]
    out = jnp.transpose(out, (3, 0, 2, 1)).reshape(rows, -1, _OUT_D)
    return _limbs_of(out[:, :R])


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
    ops, state = _reduce_schedule(
        state, value_limit, limb_target, name, layout_for(rows)
    )
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
    layout = layout_for(rows)
    _width_cert(w, kname, layout)

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
        neg_d = [0] * w
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
                neg_d = [x - c * y for x, y in zip(neg_d, pdig)]
                n_cover += (-c) * max(pdig)
        if n_cover:
            subc = _dsubc_wide(w, n_cover)
            oconst[r] = subc
            row_d = [x + int(y) for x, y in zip(row_d, subc)]
            row_v += sum(int(y) << (8 * i) for i, y in enumerate(subc))
        assert fq._cert(
            "pallas_lincomb_f32_exact", max(row_d), _F32_CAP, note=kname
        ), f"{kname}: pallas out-row exceeds f32 exactness"
        if layout != "lane":
            # the digit-major kernels add the signed terms in their own
            # order (by lane group and sublane rotation): any partial sum
            # lies in [-(negative terms), constant + positive terms]
            assert fq._cert(
                "pallas_out_terms_f32_exact", max(row_d + neg_d), _F32_CAP,
                note=kname,
            ), f"{kname}: a partial out-row sum exceeds f32 exactness"
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
        out_state, value_limit, limb_target, kname, layout
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
