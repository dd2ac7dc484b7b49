"""Pass 1 — the limb-bound certifier: static proofs for the BLS limb stack.

The kernel code already derives every bound it relies on statically at trace
time (``fq._RState`` walks the reduction schedule, ``plans._Bound`` composes
through lincombs) and asserts them. What it did NOT do is (a) surface those
proofs as an auditable artifact, or (b) run them for backends other than the
one the current process uses. This module does both:

* ``ops/bls/fq.py`` exposes a certification sink (``fq._CERT_SINK``); with a
  sink installed, every statically-proved obligation — conv-accumulator
  exactness (f64 < 2^53, f32 digits < 2^24), u32/u64 wrap safety, fold
  accumulators, carry-walk widths, reduction-walk value/limb/top targets,
  lincomb budgets, wide out-row accumulators, declared ``out_bound``
  soundness — is recorded as a ``(kind, proven, declared-limit)`` record.
* ``certify()`` re-executes the whole public op-graph surface (fq tower
  curve h2c chain_plans pairing) **abstractly** via ``jax.eval_shape`` — no
  compilation, no numerics, just the Python trace that runs the bound
  machinery — once per requested conv backend (``LIGHTHOUSE_CONV_IMPL``
  semantics) and per batch regime (bound propagation is shape-dependent).
  With ``fq.F64_WALK_MIN_ROWS = 0`` every default regime takes the all-f64
  walk; the still-invocable u64 walk schedule is certified by the
  forced-threshold run in ``tests/test_analysis.py``.
* An ``AssertionError`` raised by the bound machinery during a graph trace
  is NOT a certifier crash: it is recorded as an unproven edge and fails
  the certificate — this is how seeded mutations (e.g. a lazy interior
  widened by one squaring) and the known-bad fixture kernels are flagged.

The certificate is written to ``BOUNDS_CERT.json`` (see the README section
"Static analysis & kernel certification" for how to read it).
"""

from __future__ import annotations

import contextlib
import functools
import math

__all__ = ["certify", "certify_callable", "write_cert", "CertSink"]


def _bits(x: int) -> float:
    """log2 of a non-negative int, exact-ish for huge values."""
    if x <= 0:
        return 0.0
    return round(math.log2(x), 2) if x < 1 << 1000 else float(x.bit_length())


class CertSink:
    """Collects proof obligations recorded by ``fq._cert``; deduplicates
    identical (graph, kind, note, proven, limit) records into counts."""

    def __init__(self):
        self.obligations: dict[tuple, dict] = {}
        self._ctx: list[str] = []

    @property
    def graph(self) -> str:
        return "/".join(self._ctx) or "<module>"

    @contextlib.contextmanager
    def context(self, label: str):
        self._ctx.append(label)
        try:
            yield
        finally:
            self._ctx.pop()

    def record(self, kind: str, proven, limit, note: str = "", ok=None) -> None:
        proven = int(proven)
        limit = int(limit)
        if ok is None:
            ok = proven <= limit
        key = (self.graph, kind, note, proven, limit)
        rec = self.obligations.get(key)
        if rec is None:
            self.obligations[key] = {
                "graph": self.graph,
                "kind": kind,
                "site": note,
                "proven_bits": _bits(proven),
                "limit_bits": _bits(limit),
                "margin_bits": round(_bits(limit) - _bits(proven), 2),
                "ok": bool(ok),
                "count": 1,
            }
        else:
            rec["count"] += 1

    def fail(self, kind: str, error: str) -> None:
        """Record an unproven edge (a bound assert tripped mid-trace)."""
        key = (self.graph, kind, error, -1, -1)
        rec = self.obligations.setdefault(
            key,
            {
                "graph": self.graph,
                "kind": kind,
                "site": "",
                "error": error,
                "ok": False,
                "count": 0,
            },
        )
        rec["count"] += 1

    def rows(self) -> list[dict]:
        return sorted(
            self.obligations.values(),
            key=lambda r: (r["ok"], r.get("margin_bits", -1.0), r["graph"]),
        )


@contextlib.contextmanager
def _sink_installed(sink: CertSink):
    from ..ops.bls import fq

    prev = fq._CERT_SINK
    fq._CERT_SINK = sink
    try:
        yield
    finally:
        fq._CERT_SINK = prev


@contextlib.contextmanager
def _forced_backend(impl: str):
    """Force the conv backend for the duration (the certifier proves bounds
    for backends the current process does not run on)."""
    from ..ops.bls import fq

    prev = fq._CONV_IMPL
    fq._CONV_IMPL = impl
    try:
        yield
    finally:
        fq._CONV_IMPL = prev


# --------------------------------------------------------------------------------------
# Op-graph registry: the public kernel surface, per batch size
# --------------------------------------------------------------------------------------


def graph_registry(batch: int) -> list[tuple]:
    """(name, fn, arg-specs) for every op graph the certifier re-executes.
    Specs are ShapeDtypeStructs — eval_shape never materializes arrays."""
    import jax
    import jax.numpy as jnp

    from ..bls import tpu_backend as tb
    from ..ops.bls import curve, fq, h2c, pairing, pallas_kernels as pk, plans, tower
    from ..ops.bls_oracle.fields import BLS_X
    from ..ops.kzg import frops
    from ..ops.lc import verify as lcv

    u64 = jnp.uint64
    B = (batch,)

    def s(*shape):
        return jax.ShapeDtypeStruct(B + shape, u64)

    e1, e2, e6, e12 = s(25), s(2, 25), s(6, 25), s(12, 25)
    p1, p2 = s(3, 25), s(6, 25)
    sc = jax.ShapeDtypeStruct(B, u64)
    # epoch-sweep planes: minimum validator-axis bucket + scalar carries
    _v64 = jax.ShapeDtypeStruct((256,), u64)
    _vbool = jax.ShapeDtypeStruct((256,), jnp.bool_)
    _s64 = jax.ShapeDtypeStruct((), u64)

    def g(k, f):
        return functools.partial(f, k)

    return [
        # fq.py — base-field multiply pipeline, reductions, fixed chains
        ("fq.mont_mul", fq.mont_mul, (e1, e1)),
        ("fq.mont_sqr", fq.mont_sqr, (e1,)),
        ("fq.mont_mul_lazy", fq.mont_mul_lazy, (e1, e1)),
        ("fq.canonical", fq.canonical, (e1,)),
        ("fq.inv", fq.inv, (e1,)),
        ("fq.sqrt_candidate", fq.sqrt_candidate, (e1,)),
        ("fq.lex_gt_half", fq.lex_gt_half, (e1,)),
        # tower.py — fq2/fq6/fq12 plan-compiled ops + the sqrt chains
        ("tower.fq2_mul", tower.fq2_mul, (e2, e2)),
        ("tower.fq2_sqr", tower.fq2_sqr, (e2,)),
        ("tower.fq2_mul_lazy", tower.fq2_mul_lazy, (e2, e2)),
        ("tower.fq2_sqr_lazy", tower.fq2_sqr_lazy, (e2,)),
        ("tower.fq2_inv", tower.fq2_inv, (e2,)),
        ("tower.fq2_sqrt", tower.fq2_sqrt, (e2,)),
        ("tower.fq2_sqrt_ratio", tower.fq2_sqrt_ratio, (e2, e2)),
        ("tower.fq2_mul_many4", lambda a, b: tower.fq2_mul_many(
            [(a, b), (b, a), (a, a), (b, b)]), (e2, e2)),
        ("tower.fq6_mul", tower.fq6_mul, (e6, e6)),
        ("tower.fq6_inv", tower.fq6_inv, (e6,)),
        ("tower.fq12_mul", tower.fq12_mul, (e12, e12)),
        ("tower.fq12_sqr", tower.fq12_sqr, (e12,)),
        ("tower.fq12_inv", tower.fq12_inv, (e12,)),
        ("tower.fq12_frobenius1", tower.fq12_frobenius1, (e12,)),
        ("tower.fq12_cyclotomic_sqr", tower.fq12_cyclotomic_sqr, (e12,)),
        # both |x|-exponentiation variants, explicitly: the chain-plan scan
        # (lazy F12_BOUND interiors) and the Karabina compressed route —
        # the runtime default picks by backend, the certificate covers both
        ("tower.fq12_cyclotomic_exp_abs_x.chain",
         lambda a: tower.fq12_cyclotomic_exp_abs_x(a, compressed=False),
         (e12,)),
        ("tower.fq12_cyclotomic_exp_abs_x.karabina",
         lambda a: tower.fq12_cyclotomic_exp_abs_x(a, compressed=True),
         (e12,)),
        ("tower.fq12_mul_lazy", tower.fq12_mul_lazy, (e12, e12)),
        ("tower.fq12_sqr_lazy", tower.fq12_sqr_lazy, (e12,)),
        ("tower.fq12_cyclotomic_sqr_lazy",
         tower.fq12_cyclotomic_sqr_lazy, (e12,)),
        ("tower.fq12_compressed_sqr", tower.fq12_compressed_sqr, (s(8, 25),)),
        ("tower.fq12_compressed_sqr_lazy",
         tower.fq12_compressed_sqr_lazy, (s(8, 25),)),
        ("tower.fq12_decompress", tower.fq12_decompress, (s(8, 25),)),
        ("tower.t_eq12", tower.t_eq, (e12, e12)),
        # curve.py — complete formulas, scalar multiplication (chain_plans)
        ("curve.point_add.g1", g(1, curve.point_add), (p1, p1)),
        ("curve.point_dbl.g1", g(1, curve.point_dbl), (p1,)),
        ("curve.point_add.g2", g(2, curve.point_add), (p2, p2)),
        ("curve.point_dbl.g2", g(2, curve.point_dbl), (p2,)),
        # an aggregation level at 1,024 x batch rows: under the pallas
        # regime its kernels take the digit-major layout
        ("curve.point_sum_g1_wide", g(1, curve.point_sum),
         (jax.ShapeDtypeStruct((2, 1024) + B + (3, 25), u64),)),
        ("curve.point_eq.g2", g(2, curve.point_eq), (p2, p2)),
        ("curve.to_affine.g2", g(2, curve.to_affine), (p2,)),
        ("curve.scale_fixed_x.g2",
         lambda p: curve.scale_fixed(2, p, BLS_X), (p2,)),
        ("curve.scale_u64_with_fixed.g2",
         lambda p, r: curve.scale_u64_with_fixed(2, p, r, (-BLS_X,)),
         (p2, sc)),
        # h2c.py — SSWU fraction form, isogeny, cofactor clearing
        ("h2c.map_to_g2", h2c.map_to_g2, (e2, e2)),
        # pairing.py — planned Miller loop (doubling/addition step plans,
        # stacked line scaling, sparse 014/01245 folds), final exponentiation
        ("pairing.mul_by_014", pairing.mul_by_014, (e12, e6)),
        ("pairing.mul_by_01245", pairing.mul_by_01245, (e12, s(10, 25))),
        ("pairing.miller_loop", pairing.miller_loop, (e1, e1, e2, e2)),
        # the shared-accumulator batch-verify shape: the leading axis is the
        # pair axis, folded into ONE accumulator via cross-pair line trees
        ("pairing.miller_loop_product",
         pairing.miller_loop_product, (e1, e1, e2, e2)),
        ("pairing.final_exponentiation",
         pairing.final_exponentiation, (e12,)),
        ("pairing.fq12_prod3",
         lambda a, b, c: pairing.fq12_prod(jnp.stack([a, b, c])),
         (e12, e12, e12)),
        # bls/tpu_backend.py — the sharded serving tier's shard-LOCAL
        # bodies (ISSUE 10): what each device of the mesh executes per
        # tick. The shard_map wrapper only partitions data; the bound
        # obligations live entirely in these local compositions, so the
        # certifier proves them at the per-shard batch shape.
        ("tpu_backend.shard_local_prep",
         tb._local_prep_partials,
         (
             jax.ShapeDtypeStruct((64, 3, 25), u64),         # pubkey cache
             jax.ShapeDtypeStruct(B + (4,), jnp.int32),      # idx
             jax.ShapeDtypeStruct(B + (4,), jnp.bool_),      # mask
             e1, e1,                                         # sig x limbs
             sc,                                             # s_flag
             jax.ShapeDtypeStruct(B, jnp.bool_),             # sig_wf
             sc,                                             # scalars
             jax.ShapeDtypeStruct(B, jnp.bool_),             # valid
         )),
        ("tpu_backend.shard_local_pair_verdict",
         tb._local_pair_verdict,
         (
             s(1, 25), s(1, 25),                             # pkx, pky
             e2, e2,                                         # msg affine
             jax.ShapeDtypeStruct((6, 25), u64),             # sig partial
             jax.ShapeDtypeStruct((), jnp.bool_),            # ok_part
             jax.ShapeDtypeStruct(B, jnp.bool_),             # valid
         )),
        # ops/bls/pallas_kernels.py — the fused Pallas conv+fold+carry
        # kernels (ISSUE 13), certified EXPLICITLY and backend-independently:
        # their digit-domain schedules (conv f32 exactness, fold budgets,
        # out-lincomb covers, reduce value/limb/top targets) register
        # pallas_* obligations at trace time regardless of the active conv
        # backend, so the f64/digits regimes prove them too. Under the
        # "pallas" regime the whole tower/h2c/pairing surface above ALSO
        # routes through these kernels — this block pins the kernel
        # entry points by name even when that regime is restricted.
        ("pallas.fused_mul",
         lambda a, b: pk.fused_mul(a, b, lazy=False), (e1, e1)),
        ("pallas.fused_mul_lazy",
         lambda a, b: pk.fused_mul(a, b, lazy=True), (e1, e1)),
        ("pallas.execute_fq12_mul",
         lambda a, b: pk.execute_plan(
             plans.MUL12, a, b, plans.PUB_BOUND, plans.PUB_BOUND, "fq12_mul"
         ), (e12, e12)),
        # CYC_SQR covers the pass-through rows; the F12 out_bound covers the
        # lazy chain-interior target; FROB12 covers the constant pool
        ("pallas.execute_cyc_sqr_lazy",
         lambda a: pk.execute_plan(
             plans.CYC_SQR, a, a, plans.F12_BOUND, plans.F12_BOUND,
             "cyc_sqr_c", plans.F12_BOUND,
         ), (e12,)),
        ("pallas.execute_frob12",
         lambda a: pk.execute_plan(
             plans.FROB12, a, a, plans.PUB_BOUND, plans.PUB_BOUND, "frob12"
         ), (e12,)),
        # an fq12 product at 128 x batch rows: at batch 1 the packed
        # layout (8 lanes a block, out-rows mixed across sublanes), at 32
        # the full-block digit-major one
        ("pallas.execute_fq12_mul_packed",
         lambda a, b: pk.execute_plan(
             plans.MUL12, a, b, plans.PUB_BOUND, plans.PUB_BOUND,
             "fq12_mul_packed",
         ), (jax.ShapeDtypeStruct((128,) + B + (12, 25), u64),) * 2),
        # ops/kzg/frops.py — the Fr (scalar-field) limb stack of the
        # PeerDAS cell-proof engine (ISSUE 16), the SECOND field on the
        # shared fq conv seam: RLC weight products, the interpolation dot,
        # the batch-aggregation weighted sum, the wide fold/normalize
        # reduction and the on-device MSM bit extraction. Each records its
        # kzg.fr_* obligations (conv exactness, u64 accumulator headroom,
        # fold-table coverage) via fq._cert at trace time, under every conv
        # backend the six-pass CLI sweeps.
        ("kzg.fr_mul", frops.fr_mul, (e1, e1)),
        ("kzg.fr_dot", frops.fr_dot, (s(4, 25), s(4, 25))),
        ("kzg.fr_weighted_sum",
         lambda w, u: frops.fr_weighted_sum(w, u, batch), (e1, e1)),
        ("kzg.fr_wide_reduce",
         lambda t: frops.fr_wide_reduce(t, frops.R2_INT), (s(49),)),
        ("kzg.fr_bits", frops.fr_bits, (e1,)),
        # ops/lc/verify.py — the light-client mass-service tier (ISSUE 17):
        # B heterogeneous sync-committee update sessions settled in ONE
        # shared-accumulator pairing check. The stages are certified
        # separately (they are separate compile units at runtime) AND as
        # the lc_batch_check composition the compile probe lowers; the
        # masked committee aggregation (point_sum over the gathered cache),
        # the fused groupcheck+scaling pass and the B+1-pair Miller product
        # all record their obligations via fq._cert at trace time, under
        # every conv backend the six-pass CLI sweeps. Cache rows use a
        # small committee (C=8): the bound walk is per-lane, independent of
        # the committee/period extents, so the mainnet C=512 shape proves
        # the same obligations.
        ("lc.h2c", lcv.lc_h2c, (e2, e2)),
        ("lc.prep", lcv.lc_prep,
         (
             jax.ShapeDtypeStruct((4, 8, 3, 25), u64),       # pubkey cache
             jax.ShapeDtypeStruct(B, jnp.int32),             # pidx
             jax.ShapeDtypeStruct(B + (8,), jnp.bool_),      # bitfields
             e1, e1,                                         # sig x limbs
             sc,                                             # s_flag
             jax.ShapeDtypeStruct(B, jnp.bool_),             # sig_wf
             sc,                                             # scalars
             jax.ShapeDtypeStruct(B, jnp.bool_),             # valid
         )),
        ("lc.pair", lcv.lc_pair,
         (
             s(1, 25), s(1, 25),                             # pk affine
             jax.ShapeDtypeStruct((2, 25), u64),             # sig-sum x
             jax.ShapeDtypeStruct((2, 25), u64),             # sig-sum y
             e2, e2,                                         # msg affine
             jax.ShapeDtypeStruct(B, jnp.bool_),             # set_ok
             jax.ShapeDtypeStruct(B, jnp.bool_),             # valid
         )),
        ("lc.batch_check", lcv.lc_batch_check,
         (
             jax.ShapeDtypeStruct((4, 8, 3, 25), u64),       # pubkey cache
             jax.ShapeDtypeStruct(B, jnp.int32),             # pidx
             jax.ShapeDtypeStruct(B + (8,), jnp.bool_),      # bitfields
             e2, e2,                                         # u0/u1
             e1, e1,                                         # sig x limbs
             sc,                                             # s_flag
             jax.ShapeDtypeStruct(B, jnp.bool_),             # sig_wf
             sc,                                             # scalars
             jax.ShapeDtypeStruct(B, jnp.bool_),             # valid
         )),
        # slasher/kernels.py — the whole-registry surveillance sweep
        # (ISSUE 11): window roll + scatter + directional scans + candidate
        # flags over the span planes. Its obligations (u16 distance width,
        # int32 target-domain headroom under MAX_EPOCH, window width within
        # the distance encoding) are recorded by the kernel's own trace-time
        # `fq._cert` calls.
        ("slasher.sweep", _slasher_sweep_graph(),
         (
             jax.ShapeDtypeStruct((256, 64), jnp.uint16),    # min_d
             jax.ShapeDtypeStruct((256, 64), jnp.uint16),    # max_d
             jax.ShapeDtypeStruct((256, 64), jnp.uint32),    # vote_h
             jax.ShapeDtypeStruct((), jnp.int32),            # delta
             jax.ShapeDtypeStruct((batch * 4,), jnp.int32),  # vidx
             jax.ShapeDtypeStruct((batch * 4,), jnp.int32),  # src
             jax.ShapeDtypeStruct((batch * 4,), jnp.int32),  # tgt
             jax.ShapeDtypeStruct((batch * 4,), jnp.uint32), # vote tags
             jax.ShapeDtypeStruct((batch * 4,), jnp.bool_),  # valid
             jax.ShapeDtypeStruct((), jnp.int32),            # cur epoch
         )),
        # epoch_engine/kernels.py — the electra fused epoch sweep
        # (ISSUE 19): altair head + balance-churned registry updates +
        # pending-deposit scatter + consolidation scan + the per-validator
        # max-effective plane. Its obligations (int32 index domain, u64
        # prefix-sum/slashing headroom, fixed deposit-plane width) are
        # recorded by the kernel's own trace-time ``fq._cert`` calls. The
        # registry pins the minimum validator bucket (256); larger buckets
        # re-assert the same obligations at their own extent on every
        # runtime compile (the cert values scale with the traced shape).
        ("epoch.sweep_electra", _epoch_sweep_graph(),
         (
             {
                 "effective": _v64, "slashed": _vbool,
                 "activation": _v64, "exit": _v64,
                 "withdrawable": _v64, "eligibility": _v64,
                 "compounding": _vbool, "balances": _v64,
                 "inactivity": _v64,
                 "prev_part": jax.ShapeDtypeStruct((256,), jnp.uint8),
                 "cur_part": jax.ShapeDtypeStruct((256,), jnp.uint8),
                 "dep_amount": jax.ShapeDtypeStruct((16,), u64),
                 "dep_slot": jax.ShapeDtypeStruct((16,), u64),
                 "dep_index": jax.ShapeDtypeStruct((16,), jnp.int32),
                 "dep_valid": jax.ShapeDtypeStruct((16,), jnp.bool_),
                 "con_src": jax.ShapeDtypeStruct((8,), jnp.int32),
                 "con_tgt": jax.ShapeDtypeStruct((8,), jnp.int32),
                 "con_valid": jax.ShapeDtypeStruct((8,), jnp.bool_),
             },
             {
                 "cur_epoch": _s64, "finalized_epoch": _s64,
                 "prev_justified_epoch": _s64,
                 "cur_justified_epoch": _s64,
                 "bits": jax.ShapeDtypeStruct((4,), jnp.bool_),
                 "slash_sum": _s64,
                 "earliest_exit_epoch": _s64,
                 "exit_balance_to_consume": _s64,
                 "deposit_balance_to_consume": _s64,
                 "eth1_deposit_index": _s64,
                 "deposit_requests_start_index": _s64,
             },
         )),
    ]


def _slasher_sweep_graph():
    from ..slasher import kernels as slasher_kernels

    return functools.partial(slasher_kernels.sweep_impl, n=64)


def _epoch_sweep_graph():
    from ..epoch_engine import kernels as epoch_kernels
    from ..types.spec import mainnet_spec

    spec = mainnet_spec(
        altair_fork_epoch=0,
        bellatrix_fork_epoch=0,
        capella_fork_epoch=0,
        deneb_fork_epoch=0,
        electra_fork_epoch=0,
    )
    consts = epoch_kernels.consts_for(spec, "electra")
    return functools.partial(epoch_kernels._sweep_electra, consts)


# Batch regimes: bound propagation is shape-dependent (broadcast axes reach
# the lincomb/fold arithmetic), so certify a scalar-ish and a wide regime.
# NOTE with fq.F64_WALK_MIN_ROWS = 0 both regimes take the all-f64 walk;
# the u64 walk is covered by the forced-threshold test in test_analysis.py.
# The "pallas" regime re-executes the whole surface through the fused
# Pallas kernels (tracing the kernel bodies abstractly — interpret-mode
# pallas_call supports eval_shape), proving the digit-domain schedules on
# every graph shape the other backends prove their walks on.
_DEFAULT_BATCHES = (1, 32)
_DEFAULT_BACKENDS = ("f64", "digits", "pallas")


def _trace_graph(sink: CertSink, name: str, fn, specs) -> None:
    import jax

    with sink.context(name):
        try:
            # a fresh wrapper per trace: eval_shape's trace cache is keyed
            # by function identity + avals, NOT the forced conv backend —
            # passing `fn` directly would silently skip the re-trace (and
            # every obligation record) for each backend after the first
            jax.eval_shape(lambda *a: fn(*a), *specs)
        except AssertionError as e:
            sink.fail("unproven_bound", str(e) or "AssertionError")
        except Exception as e:  # noqa: BLE001 — a broken graph is a finding
            sink.fail("trace_error", f"{type(e).__name__}: {e}")


def certify_callable(fn, specs, backend: str = "f64") -> list[dict]:
    """Certify ONE callable's bound obligations under ``backend`` (fixture
    corpus / mutation tests). Returns the obligation rows."""
    sink = CertSink()
    with _sink_installed(sink), _forced_backend(backend):
        _trace_graph(sink, getattr(fn, "__name__", "callable"), fn, specs)
    return sink.rows()


def certify(
    backends=_DEFAULT_BACKENDS,
    batches=_DEFAULT_BATCHES,
    graphs=None,
) -> dict:
    """Run the full certificate: every registry graph x conv backend x batch
    regime. ``graphs`` optionally restricts to names containing any of the
    given substrings. Returns the certificate dict (see write_cert)."""
    from ..ops.bls import plans

    sink = CertSink()
    with _sink_installed(sink):
        # the carry_norm schedule proof (normally an import-time check)
        with sink.context("plans.carry_norm_schedule"):
            try:
                plans._verify_carry_norm_schedule(plans._CARRY_NORM_FOLDS)
            except AssertionError as e:
                sink.fail("unproven_bound", str(e))
        for backend in backends:
            with _forced_backend(backend):
                for batch in batches:
                    regime = f"{backend}@b{batch}"
                    for name, fn, specs in graph_registry(batch):
                        if graphs and not any(s in name for s in graphs):
                            continue
                        _trace_graph(sink, f"{regime}/{name}", fn, specs)
    rows = sink.rows()
    failed = [r for r in rows if not r["ok"]]
    margins = [r["margin_bits"] for r in rows if "margin_bits" in r]
    return {
        "version": 1,
        "tool": "python -m lighthouse_tpu.analysis --bounds",
        "backends": list(backends),
        "batches": list(batches),
        "ok": not failed,
        "n_obligations": len(rows),
        "n_failed": len(failed),
        "min_margin_bits": min(margins) if margins else None,
        "obligations": rows,
    }


def write_cert(cert: dict, path: str) -> None:
    import json

    with open(path, "w") as f:
        json.dump(cert, f, indent=1)
        f.write("\n")
