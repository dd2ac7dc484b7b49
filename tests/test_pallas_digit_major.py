"""The digit-major fused Pallas kernels (ops/bls/pallas_kernels.py).

Calls of at least ``DIGIT_MAJOR_MIN_ROWS`` rows run a digit-major layout,
smaller ones the lane layout; under ``FULL_BLOCK_ROWS`` the digit-major block
packs several lanes of a plan into its sublanes. In interpret mode on the
CPU these tests show: the rule picks the layout from the rows alone (counted
by ``bls_pallas_kernels_total{layout}``); the three layouts give the same
output limbs bit for bit, and the oracle's values, on random and edge inputs,
for ``fused_mul`` both ways, an fq2 square, the G1/G2 add and double plans
and fq12 plans (a product, and one with pass-through rows) at row counts on
each side of the block sizes; and the bound certifier and the VMEM planner
cover both digit-major layouts, seeded mutations included.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lighthouse_tpu  # noqa: F401  (enables x64)
from lighthouse_tpu.analysis import bounds, memory as amem
from lighthouse_tpu.ops.bls import curve, fq, g1, g2, pallas_kernels as pk, plans
from lighthouse_tpu.ops.bls import tower as tw
from lighthouse_tpu.ops.bls_oracle import curves as OC, fields as of
from lighthouse_tpu.utils import tracing

pytestmark = pytest.mark.kernel

ROWS = pk.DIGIT_MAJOR_MIN_ROWS   # the smallest call the digit-major kernels take
FULL = pk.FULL_BLOCK_ROWS        # the smallest call with a full block a lane
LAYOUTS = ("lane", "packed", "digit_major")

rng = random.Random(0xD161)


@pytest.fixture(autouse=True)
def pallas_backend(monkeypatch):
    monkeypatch.setattr(fq, "_CONV_IMPL", "pallas")


def _force(monkeypatch, layout: str) -> None:
    """Route every call to ``layout`` (the rule's thresholds moved past or
    below every row count); forced digit-major, a call under a full block
    takes one lane a block."""
    lo, full = {
        "lane": (1 << 40, 1 << 40),
        "packed": (0, 1 << 40),
        "digit_major": (0, 0),
    }[layout]
    monkeypatch.setattr(pk, "DIGIT_MAJOR_MIN_ROWS", lo)
    monkeypatch.setattr(pk, "FULL_BLOCK_ROWS", full)


def _layout_counts() -> dict:
    return {k[0]: v for k, _, v in tracing.PALLAS_KERNELS_TOTAL.collect()}


def _each_layout(monkeypatch, fn, *args) -> np.ndarray:
    """fn's output in each layout, asserted equal bit for bit."""
    outs = {}
    for layout in LAYOUTS:
        with monkeypatch.context() as m:
            _force(m, layout)
            before = _layout_counts().get(layout, 0)
            outs[layout] = np.asarray(jax.jit(lambda *a: fn(*a))(*args))
            assert _layout_counts().get(layout, 0) > before, layout
    for layout in LAYOUTS[1:]:
        assert outs[layout].dtype == outs["lane"].dtype, layout
        assert (outs[layout] == outs["lane"]).all(), layout
    return outs["packed"]


def _tile(items, n):
    return [items[i % len(items)] for i in range(n)]


# -- routing -----------------------------------------------------------------


@pytest.mark.parametrize(
    "rows", [ROWS - 1, ROWS, 2 * ROWS + 3, FULL - 1, FULL]
)
def test_layout_chosen_by_rows(rows):
    """Below the crossover the lane kernel, from it to a full block the
    packed one, from there the digit-major one; the counter sees each
    traced call under its layout."""
    want = (
        "lane" if rows < ROWS else "packed" if rows < FULL else "digit_major"
    )
    assert pk.layout_for(rows) == want
    before = _layout_counts()
    x = jax.ShapeDtypeStruct((rows, 25), jnp.uint64)
    jax.eval_shape(lambda a, b: pk.fused_mul(a, b), x, x)
    after = _layout_counts()
    for layout in LAYOUTS:
        grew = after.get(layout, 0) - before.get(layout, 0)
        assert grew == (layout == want), layout


# -- parity: the three layouts and the oracle ----------------------------------------


def _max_digits(limb: int, value_limit: int) -> np.ndarray:
    """Every limb at ``limb`` and the top ones as high as ``value_limit``
    allows: the largest digits an input within that budget can carry."""
    raw = [limb] * 23 + [0, 0]

    def value(top, v):
        return fq.limbs_to_int(np.asarray(raw[:top] + [v] + raw[top + 1:], np.uint64))

    for top in (24, 23):
        lo, hi = 0, limb
        while lo < hi:  # the largest limb that keeps the value in budget
            mid = (lo + hi + 1) // 2
            lo, hi = (mid, hi) if value(top, mid) < value_limit else (lo, mid - 1)
        raw[top] = lo
    v = np.asarray(raw, np.uint64)
    assert fq.limbs_to_int(v) < value_limit
    return v


@pytest.mark.parametrize("rows", [ROWS - 1, ROWS])
@pytest.mark.parametrize("lazy", [False, True])
def test_fused_mul_parity(monkeypatch, lazy, rows):
    """fused_mul just below and at the crossover: random values, 0, 1, p - 1
    and an operand at the largest digits its input budget allows."""
    if lazy:
        edge = _max_digits(fq.CHAIN_LIMB_TARGET, fq.CHAIN_VALUE_LIMIT)
    else:
        edge = _max_digits(fq._IN_LIMB, fq._IN_VALUE)
    xs = [rng.randrange(of.P) for _ in range(rows - 4)] + [0, 1, of.P - 1, 0]
    ys = [rng.randrange(of.P) for _ in range(rows - 4)] + [of.P - 1, 1, of.P - 1, 0]
    a, b = np.array(fq.from_ints(xs)), np.array(fq.from_ints(ys))
    a[-1], b[-1] = edge, edge
    out = _each_layout(monkeypatch, lambda x, y: pk.fused_mul(x, y, lazy=lazy), a, b)
    got = [fq.to_int(out[i]) for i in range(rows)]
    vals = [(fq.to_int(a[i]), fq.to_int(b[i])) for i in range(rows)]
    assert got == [x * y % of.P for x, y in vals]


def _g1_points(n):
    pts = [OC.g1_mul(OC.g1_generator(), rng.randrange(1, 2**64)) for _ in range(3)]
    return _tile(pts + [None], n)


def _g2_points(n):
    pts = [OC.g2_mul(OC.g2_generator(), rng.randrange(1, 2**64)) for _ in range(3)]
    return _tile(pts + [None], n)


def _same_point(row, want, k: int) -> bool:
    """Projective limbs ``row`` ([3k, 25]) equal the oracle's affine point
    (None: infinity), compared on the host: X = x Z, Y = y Z."""
    v = [fq.to_int(row[i]) for i in range(3 * k)]
    if k == 1:
        X, Y, Z = v
        zero = 0
    else:
        X, Y, Z = (of.Fq2(v[2 * i], v[2 * i + 1]) for i in range(3))
        zero = of.Fq2(0, 0)
    if want is None:
        return Z == zero
    x, y = want
    if k == 1:
        return Z != 0 and X == x * Z % of.P and Y == y * Z % of.P
    return Z != zero and X == x * Z and Y == y * Z


PLAN_CASES = {
    "g1_add": (1, curve.point_add, OC.g1_add),
    "g1_dbl": (1, curve.point_dbl, lambda p: OC.g1_add(p, p)),
    "g2_add": (2, curve.point_add, OC.g2_add),
    "g2_dbl": (2, curve.point_dbl, lambda p: OC.g2_add(p, p)),
}


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_curve_plan_parity(monkeypatch, case):
    """The complete add and double formulas' two plans each (negative
    out-map terms, borrow constants) on the crossover's row count of
    points, infinity among them."""
    k, op, want = PLAN_CASES[case]
    gen = _g1_points if k == 1 else _g2_points
    mod = g1 if k == 1 else g2
    ps, qs = gen(ROWS), gen(ROWS + 1)[1:]
    P_, Q_ = mod.from_oracle_batch(ps), mod.from_oracle_batch(qs)
    if op is curve.point_add:
        out = _each_layout(monkeypatch, lambda p, q: op(k, p, q), P_, Q_)
        expect = [want(p, q) for p, q in zip(ps, qs)]
    else:
        out = _each_layout(monkeypatch, lambda p: op(k, p), P_)
        expect = [want(p) for p in ps]
    for i in range(8):   # the 4-point cycles repeat: check two periods
        assert _same_point(out[i], expect[i], k), i


def test_fq12_plan_parity(monkeypatch):
    """An fq12 plan with pass-through rows (cyclotomic squaring): 18 lanes
    into 12 out-rows, the raw input seeded into the accumulators."""
    def rfq2():
        return of.Fq2(rng.randrange(of.P), rng.randrange(of.P))

    vals = []
    for _ in range(2):
        a = of.Fq12(of.Fq6(rfq2(), rfq2(), rfq2()), of.Fq6(rfq2(), rfq2(), rfq2()))
        g = a.conjugate() * a.inv()
        vals.append(g.frobenius(2) * g)   # cyclotomic subgroup members
    x = jnp.stack([tw.fq12_from_oracle(v) for v in _tile(vals, ROWS)])
    out = _each_layout(monkeypatch, tw.fq12_cyclotomic_sqr, x)
    for i in range(2):   # the 2-element cycle repeats: compare on the host
        want = np.asarray(tw.fq12_from_oracle(vals[i].cyclotomic_square()))
        assert [fq.to_int(v) for v in out[i]] == [fq.to_int(v) for v in want]


def _rfq2():
    return of.Fq2(rng.randrange(of.P), rng.randrange(of.P))


def _rfq12():
    return of.Fq12(of.Fq6(_rfq2(), _rfq2(), _rfq2()), of.Fq6(_rfq2(), _rfq2(), _rfq2()))


def _cyclotomic(n):
    vals = []
    for _ in range(n):
        a = _rfq12()
        g = a.conjugate() * a.inv()
        vals.append(g.frobenius(2) * g)
    return vals


def _case_fused_mul(rows):
    xs = [rng.randrange(of.P) for _ in range(4)]
    ys = [rng.randrange(of.P) for _ in range(4)]
    a, b = fq.from_ints(_tile(xs, rows)), fq.from_ints(_tile(ys, rows))
    want = [x * y % of.P for x, y in zip(xs, ys)]
    return (
        lambda x, y: pk.fused_mul(x, y), (a, b),
        lambda out, i: fq.to_int(out[i]) == want[i % 4],
    )


def _case_fq2_sqr(rows):
    vals = [_rfq2() for _ in range(4)]
    x = jnp.stack([tw.fq2_from_oracle(v) for v in _tile(vals, rows)])
    return (
        tw.fq2_sqr, (x,),
        lambda out, i: tw.fq2_to_oracle(out[i]) == vals[i % 4] * vals[i % 4],
    )


def _case_g1_add(rows):
    ps, qs = _g1_points(rows), _g1_points(rows + 1)[1:]
    want = [OC.g1_add(p, q) for p, q in zip(ps, qs)]
    return (
        lambda p, q: curve.point_add(1, p, q),
        (g1.from_oracle_batch(ps), g1.from_oracle_batch(qs)),
        lambda out, i: _same_point(out[i], want[i], 1),
    )


def _case_g2_dbl(rows):
    ps = _g2_points(rows)
    return (
        lambda p: curve.point_dbl(2, p), (g2.from_oracle_batch(ps),),
        lambda out, i: _same_point(out[i], OC.g2_add(ps[i], ps[i]), 2),
    )


def _case_fq12_mul(rows):
    xs, ys = [_rfq12() for _ in range(2)], [_rfq12() for _ in range(2)]
    a = jnp.stack([tw.fq12_from_oracle(v) for v in _tile(xs, rows)])
    b = jnp.stack([tw.fq12_from_oracle(v) for v in _tile(ys, rows)])
    return (
        tw.fq12_mul, (a, b),
        lambda out, i: tw.fq12_to_oracle(out[i]) == xs[i % 2] * ys[i % 2],
    )


def _case_cyc_sqr(rows):
    vals = _cyclotomic(2)
    x = jnp.stack([tw.fq12_from_oracle(v) for v in _tile(vals, rows)])
    return (
        tw.fq12_cyclotomic_sqr, (x,),
        lambda out, i: (
            tw.fq12_to_oracle(out[i]) == vals[i % 2].cyclotomic_square()
        ),
    )


# (op, rows): lanes a block packs G = 8 at 64-128 rows, 4 at 129-256, 2 at
# 257-512; plans of 2 (fq2 square), 6 with negative out-map terms (G1
# add), 10 and 12 (G2 double), 54 (fq12 product) lanes and one with
# pass-through rows (cyclotomic squaring), most not a multiple of G
PACKED_OPS = {
    "fused_mul": _case_fused_mul, "fq2_sqr": _case_fq2_sqr,
    "g1_add": _case_g1_add, "g2_dbl": _case_g2_dbl,
    "fq12_mul": _case_fq12_mul, "cyc_sqr": _case_cyc_sqr,
}
PACKED_CASES = [
    ("fused_mul", 65), ("fused_mul", 512),
    ("fq2_sqr", 136), ("fq2_sqr", 256),
    ("g1_add", 64), ("g1_add", 512),
    ("g2_dbl", 136),
    ("fq12_mul", 65), ("fq12_mul", 256),
    ("cyc_sqr", 136),
]


@pytest.mark.parametrize("op,rows", PACKED_CASES)
def test_packed_parity(monkeypatch, op, rows):
    """Each op in the three layouts at row counts below a full block gives
    the same limbs bit for bit, and the oracle's values on the first and
    last rows."""
    fn, args, ok = PACKED_OPS[op](rows)
    out = _each_layout(monkeypatch, fn, *args)
    for i in (0, 1, 2, 3, rows - 1):
        assert ok(out, i), i


# -- the certifier and the VMEM planner on the digit-major path -----------------


def _e(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint64)


def test_fused_graphs_prove_clean_digit_major():
    """The kernel entry points at packed (64-row) and full-block (1,024-row)
    counts certify with no failed obligation; the plane-count bound stands
    in for the lane width, and the out-rows' partial sums in any order are
    proven exact."""
    rows = []
    before = _layout_counts()
    for n in (ROWS, FULL):
        for fn, specs in (
            (lambda a, b: pk.fused_mul(a, b, lazy=False), (_e(n, 25),) * 2),
            (lambda a, b: pk.fused_mul(a, b, lazy=True), (_e(n, 25),) * 2),
            (lambda a, b: pk.execute_plan(
                plans.MUL12, a, b, plans.PUB_BOUND, plans.PUB_BOUND, "m12"
            ), (_e(n, 12, 25),) * 2),
            (lambda a: pk.execute_plan(
                plans.CYC_SQR, a, a, plans.F12_BOUND, plans.F12_BOUND,
                "cyc", plans.F12_BOUND,
            ), (_e(n, 12, 25),)),
        ):
            got = bounds.certify_callable(fn, specs, backend="pallas")
            assert got and all(r["ok"] for r in got), [
                r for r in got if not r["ok"]
            ][:3]
            rows.extend(got)
    after = _layout_counts()
    for layout in ("packed", "digit_major"):
        assert after.get(layout, 0) > before.get(layout, 0), layout
    assert after.get("lane", 0) == before.get("lane", 0)
    kinds = {r["kind"] for r in rows}
    assert {
        "pallas_conv_digit_f32_exact", "pallas_fold_f32_exact",
        "pallas_lincomb_f32_exact", "pallas_reduce_value", "pallas_reduce_limb",
        "pallas_reduce_top_limb", "pallas_out_bound_top_sound",
        "pallas_digit_u32_nowrap", "pallas_out_width", "pallas_digit_planes",
        "pallas_out_terms_f32_exact",
    } <= kinds, kinds
    assert "pallas_lane_width" not in kinds


def test_wide_point_sum_certifies_on_the_digit_major_path():
    """The registry's >= 1,024-row point_sum proves clean under the pallas
    regime, every kernel of it digit-major."""
    before = _layout_counts()
    cert = bounds.certify(
        backends=("pallas",), batches=(1,), graphs=["curve.point_sum_g1_wide"]
    )
    assert cert["ok"] and cert["n_failed"] == 0 and cert["n_obligations"]
    kinds = {r["kind"] for r in cert["obligations"]}
    assert "pallas_digit_planes" in kinds and "pallas_lane_width" not in kinds
    after = _layout_counts()
    assert after.get("digit_major", 0) > before.get("digit_major", 0)
    assert after.get("lane", 0) == before.get("lane", 0)


def test_seeded_mutation_unsound_out_bound_fails_digit_major():
    """An out_bound whose top-limb claim the walk cannot keep turns the
    certificate red on the digit-major path too."""
    bad = plans._Bound(plans.F12_BOUND.value_p, plans.F12_BOUND.limb, 0)
    rows = bounds.certify_callable(
        lambda a, b: pk.execute_plan(
            plans.MUL12, a, b, plans.F12_BOUND, plans.F12_BOUND, "mut", bad,
        ),
        (_e(ROWS, 12, 25),) * 2,
        backend="pallas",
    )
    assert any(
        not r["ok"]
        and r["kind"] in ("pallas_out_bound_top_sound", "unproven_bound")
        for r in rows
    )


def test_seeded_mutation_wider_chain_limb_fails_digit_major(monkeypatch):
    """A wider chain limb target breaks the conv's f32 exactness on the
    digit-major path too."""
    monkeypatch.setattr(fq, "CHAIN_LIMB_TARGET", (1 << 27) - 1)
    monkeypatch.setattr(fq, "CHAIN_VALUE_LIMIT", (1 << 27) * of.P)
    rows = bounds.certify_callable(
        lambda a, b: pk.fused_mul(a, b, lazy=True),
        (_e(ROWS, 25),) * 2,
        backend="pallas",
    )
    assert any(
        not r["ok"]
        and r["kind"] in ("pallas_conv_digit_f32_exact", "unproven_bound")
        for r in rows
    )


def test_vmem_planner_accounts_digit_major_blocks():
    """Each digit-major launch records its blocks, the F8 table and the
    out-rows' borrow constants (a plane each) and its scratch: the 512-key
    tree sum's first level (G1 addition, 16,384 rows) fits the declared 16
    MiB VMEM tier; the widest plan (cyclotomic squaring: 12 out-rows, 12
    pass-through inputs) at a full block is counted with every operand."""
    plane = 8 * 128 * 4
    table = 64 * 48 * 128 * 4
    rows = amem.certify_graph_callable(
        lambda a, b: curve.point_add(1, a, b), (_e(16384, 3, 25),) * 2,
        backend="pallas",
    )
    vrows = [r for r in rows if r["kind"] == "vmem_tile"]
    assert len(vrows) == 2 and all(r["layout"] == "digit_major" for r in vrows)
    for r in vrows:
        assert r["tile"] == 1024 and r["lanes"] == 6 and r["grid"] == 16 * 6
        assert r["const_bytes"] == table + r["n_rows_out"] * plane
        assert r["ok"] and r["est_vmem_bytes"] <= 16 * 2**20
    rows = amem.certify_graph_callable(
        lambda a: pk.execute_plan(
            plans.CYC_SQR, a, a, plans.F12_BOUND, plans.F12_BOUND,
            "cyc", plans.F12_BOUND,
        ),
        (_e(1024, 12, 25),),
        backend="pallas",
    )
    (r,) = [r for r in rows if r["kind"] == "vmem_tile"]
    assert r["lanes"] == 18 and r["n_rows_out"] == 12 and r["n_pass"] == 12
    assert r["block_bytes"] == (2 * 51 + 12 * 51 + 12 * 50) * plane
    assert r["scratch_bytes"] >= (101 + 12 * 103) * plane
    assert r["const_bytes"] == table + 12 * plane
    assert r["est_vmem_bytes"] == (
        r["scratch_bytes"] + 2 * (r["block_bytes"] + r["const_bytes"])
    )


def test_vmem_planner_accounts_packed_blocks():
    """A packed launch records blocks of 8 sublanes holding G lanes: the
    cyclotomic squaring at 128 rows packs its 18 lanes, 12 out-rows and 12
    pass-through inputs 8 to a block (3 lane groups, 2 out-groups with a
    plane of borrow constants each, 2 pass groups) and fits the declared
    16 MiB VMEM tier."""
    plane = 8 * 128 * 4
    table = 64 * 48 * 128 * 4
    rows = amem.certify_graph_callable(
        lambda a: pk.execute_plan(
            plans.CYC_SQR, a, a, plans.F12_BOUND, plans.F12_BOUND,
            "cyc", plans.F12_BOUND,
        ),
        (_e(128, 12, 25),),
        backend="pallas",
    )
    (r,) = [r for r in rows if r["kind"] == "vmem_tile"]
    assert r["layout"] == "packed" and r["group"] == 8 and r["tile"] == 128
    assert r["lanes"] == 18 and r["grid"] == 3
    assert r["n_rows_out"] == 12 and r["n_pass"] == 12
    assert r["block_bytes"] == (2 * 51 + 2 * 51 + 2 * 50) * plane
    assert r["scratch_bytes"] >= (101 + 2 * 103) * plane
    assert r["const_bytes"] == table + 2 * plane
    assert r["ok"] and r["est_vmem_bytes"] <= 16 * 2**20
