"""Pass 6 — the device-memory certifier & static footprint planner.

Every subsystem parks large device-resident state (epoch mirror columns at
1M validators, slasher span planes at 8 B/validator-epoch, the LC
per-period committee cache, KZG setup tables, double-buffered firehose
staging), yet nothing previously *proved* a configuration fits a device
before dispatch — OOM was handled reactively by the supervisor ladder, and
an un-certified over-budget shape on real hardware burns chip time per
attempt. This module makes residency the sixth certified pass:

* **Graph footprints** — every graph in ``bounds.graph_registry`` is
  re-executed abstractly under all three ``LIGHTHOUSE_CONV_IMPL`` backends
  x both batch regimes (one abstract ``make_jaxpr`` trace proves the
  output avals, a jaxpr liveness walk bounds arg/out/temp/peak bytes, and XLA's
  lowered-computation cost analysis cross-checks a representative subset —
  ``LIGHTHOUSE_MEMORY_XLA=full`` extends it to every row).
* **VMEM tile walk** — under the pallas regime every fused-kernel launch
  records its layout and tile signature (operand blocks, the lane or
  digit-plane scratch, constant pools) through ``pallas_kernels._VMEM_SINK``;
  each distinct signature is checked against the declared per-tier VMEM
  caps.
* **Subsystem residency models** — one static ``*_bytes(config)`` function
  per device-resident plane family (epoch mirror, slasher spans, LC
  committee cache, KZG tables, firehose staging), cross-checked in
  ``tests/test_analysis.py`` against actual ``device_put`` accounting.
* **Device tiers** — HBM/VMEM caps for representative TPU generations plus
  an unbounded CPU-proxy tier. A row that fits NO declared finite tier
  fails the certificate exactly like a tripped bound.
* **Planner** — ``max_safe_shape(graph, tier)`` derives the largest
  certified pow2 batch per tier, and ``rung_fit`` prices a bench rung's
  shape against a tier before it is dispatched.

The certificate is written to ``MEMORY_CERT.json`` (see the README section
"Memory certification & footprint planning"). The module imports neither
jax nor numpy at import time — residency models and rung verdicts are
evaluated without touching a device.
"""

from __future__ import annotations

import contextlib
import os

__all__ = [
    "DEVICE_TIERS",
    "certify_memory",
    "certify_graph_callable",
    "epoch_mirror_bytes",
    "slasher_span_bytes",
    "lc_committee_cache_bytes",
    "kzg_table_bytes",
    "firehose_staging_bytes",
    "max_safe_shape",
    "rung_fit",
    "fault_memory_context",
    "write_cert",
]

_GiB = 1 << 30
_MiB = 1 << 20

# Declared device tiers. HBM figures are per-chip for representative TPU
# generations; VMEM is the ~16 MiB/core on-chip budget (see
# /opt/skills/guides/pallas_guide.md — "VMEM ~16 MB/core"). The CPU proxy
# tier is unbounded: host runs certify shapes, never fail them.
DEVICE_TIERS: dict[str, dict] = {
    "tpu_v5e": {"hbm_bytes": 16 * _GiB, "vmem_bytes": 16 * _MiB},
    "tpu_v4": {"hbm_bytes": 32 * _GiB, "vmem_bytes": 16 * _MiB},
    "tpu_v5p": {"hbm_bytes": 95 * _GiB, "vmem_bytes": 16 * _MiB},
    "cpu_proxy": {"hbm_bytes": None, "vmem_bytes": None},
}

# The tier fault records report margins against when the runtime has no
# better information.
DEFAULT_TIER = os.environ.get("LIGHTHOUSE_MEMORY_TIER", "tpu_v5e")

_DEFAULT_BATCHES = (1, 32)
_DEFAULT_BACKENDS = ("f64", "digits", "pallas")

# Rows cross-checked against XLA's lowered-computation cost analysis by
# default (cheap compile units). LIGHTHOUSE_MEMORY_XLA=full extends the
# cross-check to every graph; =0 disables it (the jaxpr walk still runs).
_XLA_COST_GRAPHS = ("fq.mont_mul", "fq.mont_sqr", "tower.fq2_mul", "kzg.fr_mul")


def _pow2_bucket(n: int, floor: int) -> int:
    """Twin of the pow2 shape buckets used at every allocation site
    (epoch_engine.kernels.bucket, slasher.engine._bucket,
    firehose.sharding._bucket) — parity-pinned in tests/test_analysis.py
    so this module stays importable without jax."""
    b = max(1, int(floor))
    n = max(1, int(n))
    while b < n:
        b *= 2
    return b


# --------------------------------------------------------------------------------------
# Subsystem residency models (static; cross-checked against device_put
# accounting in tests/test_analysis.py)
# --------------------------------------------------------------------------------------

# epoch_engine/mirror.py _REG_DTYPES: five u64 columns (effective,
# activation, exit, withdrawable, eligibility) + two bool columns (slashed,
# compounding), each at the 256-floor pow2 validator bucket.
_MIRROR_COLUMN_BYTES = 5 * 8 + 2 * 1


def epoch_mirror_bytes(validators: int, include_epoch_planes: bool = True) -> int:
    """Device-resident bytes of the registry mirror at ``validators``.
    ``include_epoch_planes`` adds the per-epoch wholesale uploads (balances
    u64 + inactivity u64 + prev/cur participation u8) that are co-resident
    during a sweep; the registry-columns-only figure equals
    ``MirrorStats.host_to_device_bytes`` after one full gather."""
    n_pad = _pow2_bucket(validators, 256)
    per_row = _MIRROR_COLUMN_BYTES
    if include_epoch_planes:
        per_row += 8 + 8 + 1 + 1
    return n_pad * per_row


def slasher_span_bytes(
    validators: int, history: int | None = None, floor: int = 256
) -> int:
    """Device-resident bytes of the slasher span planes: u16 min-distance +
    u16 max-distance + u32 vote-history at [n_pad, history]
    (slasher/engine.py empty_planes_np). ``history`` defaults to the
    ``LIGHTHOUSE_SLASHER_HISTORY`` env knob, then the reference's 4096."""
    if history is None:
        raw = os.environ.get("LIGHTHOUSE_SLASHER_HISTORY", "").strip()
        history = int(raw) if raw else 4096
    n_pad = _pow2_bucket(validators, floor)
    return n_pad * int(history) * (2 + 2 + 4)


def lc_committee_cache_bytes(periods: int, committee_size: int = 512) -> int:
    """Device-resident bytes of the LC per-period committee cache:
    [P_pad, C, 3, 25] u64 (light_client/engine.py _cache_arr; P_pad is the
    4-floor pow2 bucket, C the SYNC_COMMITTEE_SIZE)."""
    p_pad = _pow2_bucket(periods, 4)
    return p_pad * int(committee_size) * 3 * 25 * 8


def kzg_table_bytes(cells: int = 128, k: int = 64) -> int:
    """Device-resident bytes of the KZG CellEngine verify tables
    (kzg/engine.py _build_tables): perm int32[k], idft u64[k,k,25],
    cinv u64[cells,k,25], dtab u64[cells,25], setup u64[k,3,25], the four
    g2 coordinate rows u64[2,25], and the coset-shift table
    _z2_tab u64[cells,6,25]."""
    cells, k = int(cells), int(k)
    return (
        4 * k                    # perm
        + 8 * 25 * k * k         # idft
        + 8 * 25 * cells * k     # cinv
        + 8 * 25 * cells         # dtab
        + 8 * 25 * 3 * k         # setup (g1 projective rows)
        + 4 * 8 * 25 * 2         # g2x / g2y / t2x / t2y
        + 8 * 25 * 6 * cells     # _z2_tab (g2 projective rows)
    )


# bls/tpu_backend.py stage_indexed_shards per-row device bytes, k_pad key
# columns: idx int32[k] + mask bool[k] + u0/u1 u64[2,25] each + x_c0/x_c1
# u64[25] each + s_flag u64 + sig_wf bool + scalars u64 + valid bool.
_STAGED_ROW_FIXED_BYTES = 2 * (2 * 25 * 8) + 2 * (25 * 8) + 8 + 1 + 8 + 1


def firehose_staging_bytes(
    max_batch: int = 64,
    prep_depth: int = 1,
    k_pad: int = 4,
    n_shards: int = 1,
) -> int:
    """Device-resident bytes of the firehose staged-buffer family: one
    tick's per-shard H2D arrays (each shard padded to the pow2 batch
    bucket), double-buffered ``prep_depth + 1`` deep (the prep thread
    stages tick N+1 while the device thread verifies tick N)."""
    n_pad = int(n_shards) * _pow2_bucket(max_batch, 4)
    tick = n_pad * (_STAGED_ROW_FIXED_BYTES + 5 * int(k_pad))
    return (int(prep_depth) + 1) * tick


def staged_tick_bytes(n_pad: int, k_pad: int) -> int:
    """One staged tick at explicit row/key padding (the parity-test twin of
    summing ``_STAGED_SET_KEYS`` array nbytes)."""
    return int(n_pad) * (_STAGED_ROW_FIXED_BYTES + 5 * int(k_pad))


# The residency ladder the certificate always covers (all five subsystem
# models; the epoch/slasher entries walk the 32k/262k/1M validator ladder).
def _residency_ladder() -> list[tuple[str, int]]:
    rows = []
    for v in (32_768, 262_144, 1_048_576):
        rows.append((f"residency/epoch_mirror@{v}", epoch_mirror_bytes(v)))
        rows.append((f"residency/slasher_spans@{v}", slasher_span_bytes(v)))
    for p in (4, 64):
        rows.append(
            (f"residency/lc_committee_cache@{p}p", lc_committee_cache_bytes(p))
        )
    rows.append(("residency/kzg_tables@mainnet", kzg_table_bytes()))
    rows.append(("residency/firehose_staging@64x1", firehose_staging_bytes()))
    rows.append(
        (
            "residency/firehose_staging@64x8shards",
            firehose_staging_bytes(n_shards=8),
        )
    )
    return rows


# --------------------------------------------------------------------------------------
# Tier arithmetic
# --------------------------------------------------------------------------------------


def _tier_fit(nbytes: int, tiers: dict) -> tuple[str | None, dict]:
    """(smallest finite tier that fits | None, per-tier margin map). The
    CPU proxy (cap None) never bounds a row and never satisfies the fit."""
    margins: dict[str, int | None] = {}
    best: tuple[int, str] | None = None
    for name, caps in tiers.items():
        cap = caps.get("hbm_bytes")
        if cap is None:
            margins[name] = None
            continue
        margins[name] = int(cap) - int(nbytes)
        if cap >= nbytes and (best is None or cap < best[0]):
            best = (cap, name)
    return (best[1] if best else None), margins


def _vmem_fit(nbytes: int, tiers: dict) -> bool:
    caps = [
        c.get("vmem_bytes") for c in tiers.values()
        if c.get("vmem_bytes") is not None
    ]
    return bool(caps) and int(nbytes) <= max(caps)


# --------------------------------------------------------------------------------------
# Graph footprints (jax.eval_shape + jaxpr liveness walk + XLA cost analysis)
# --------------------------------------------------------------------------------------


def _aval_bytes(aval) -> int:
    try:
        n = 1
        for d in aval.shape:
            n *= int(d)
        return n * aval.dtype.itemsize
    except (AttributeError, TypeError):
        return 0


def _sub_jaxprs(params: dict):
    from jax.extend import core as jcore

    for v in params.values():
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for x in v:
                if isinstance(x, jcore.ClosedJaxpr):
                    yield x.jaxpr
        elif hasattr(v, "eqns") and hasattr(v, "invars"):
            yield v


def _jaxpr_walk(jaxpr, _memo: dict | None = None) -> tuple[int, int]:
    """(temp bytes, peak live bytes) for one jaxpr by linear liveness scan.
    Arguments and constants are held live for the whole program (XLA may
    free them earlier; the walk stays on the conservative side). Call-like
    equations (pjit, scan, while, pallas_call, ...) recurse into their
    sub-jaxpr and charge its interior peak at that program point.

    The scan is strictly linear in the equation count: last uses are
    bucketed by equation index up front (a per-step dict sweep is O(n^2)
    and the composite graphs run to ~100k equations), and repeated
    sub-jaxpr objects (a scan body traced once, referenced per call) are
    walked once via the memo."""
    from jax.extend import core as jcore

    Literal = jcore.Literal
    if _memo is None:
        _memo = {}

    def _dropped(v) -> bool:
        # DropVar isn't exported through jax.extend.core
        return type(v).__name__ == "DropVar"

    n = len(jaxpr.eqns)
    last_use: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            last_use[v] = n
    expire_at: list[list] = [[] for _ in range(n)]
    for v, j in last_use.items():
        if j < n:
            expire_at[j].append(v)
    base = sum(_aval_bytes(v.aval) for v in jaxpr.invars)
    base += sum(_aval_bytes(v.aval) for v in jaxpr.constvars)
    live: dict = {}
    live_b = 0
    peak = base
    temps = 0
    for i, eqn in enumerate(jaxpr.eqns):
        inner_peak = 0
        for sub in _sub_jaxprs(eqn.params):
            key = id(sub)
            if key not in _memo:
                _memo[key] = _jaxpr_walk(sub, _memo)
            inner_peak = max(inner_peak, _memo[key][1])
        out_b = sum(
            _aval_bytes(v.aval) for v in eqn.outvars if not _dropped(v)
        )
        temps += out_b
        peak = max(peak, base + live_b + out_b + inner_peak)
        for v in eqn.outvars:
            if not _dropped(v) and v not in live:
                b = _aval_bytes(v.aval)
                live[v] = b
                live_b += b
        for v in expire_at[i]:
            live_b -= live.pop(v, 0)
    return temps, peak


def _spec_bytes(specs) -> int:
    import jax

    return sum(_aval_bytes(leaf) for leaf in jax.tree.leaves(specs))


@contextlib.contextmanager
def _vmem_sink(records: list):
    from ..ops.bls import pallas_kernels as pk

    prev = pk._VMEM_SINK
    pk._VMEM_SINK = records
    try:
        yield
    finally:
        pk._VMEM_SINK = prev


def _xla_cost_bytes(fn, specs) -> int | None:
    """Best-effort lowered-computation cost analysis ("bytes accessed"):
    the independent cross-check on the jaxpr walk. Lowering is heavier
    than tracing, so callers restrict it to a representative subset."""
    import jax

    try:
        lowered = jax.jit(lambda *a: fn(*a)).lower(*specs)
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            return None
        v = ca.get("bytes accessed")
        return int(v) if v is not None else None
    except Exception:  # noqa: BLE001 — the cross-check is advisory
        return None


def _xla_mode() -> str:
    return os.environ.get("LIGHTHOUSE_MEMORY_XLA", "subset")


def _trace_footprint(name: str, fn, specs, tiers: dict) -> list[dict]:
    """Footprint + VMEM rows for one graph trace under the ACTIVE conv
    backend (callers force it). A trace failure is a failed row, exactly
    like an unproven bound in pass 1."""
    import jax

    vmem_records: list[dict] = []
    try:
        with _vmem_sink(vmem_records):
            # fresh wrapper per trace: the trace caches are keyed by
            # function identity + avals, NOT the forced conv backend.
            # ONE abstract trace per row — make_jaxpr carries out_avals,
            # a separate eval_shape would double the trace cost.
            closed = jax.make_jaxpr(lambda *a: fn(*a))(*specs)
            out = closed.out_avals
    except Exception as e:  # noqa: BLE001 — a broken graph is a finding
        return [{
            "graph": name,
            "kind": "trace_error",
            "error": f"{type(e).__name__}: {e}"[:300],
            "ok": False,
        }]
    arg_b = _spec_bytes(specs)
    out_b = _spec_bytes(out)
    temp_b, peak_b = _jaxpr_walk(closed.jaxpr)
    peak_b = max(peak_b, arg_b + out_b)
    fit_tier, margins = _tier_fit(peak_b, tiers)
    row = {
        "graph": name,
        "kind": "graph_footprint",
        "arg_bytes": arg_b,
        "out_bytes": out_b,
        "temp_bytes": temp_b,
        "peak_bytes": peak_b,
        "min_tier": fit_tier,
        "margin_bytes": {k: v for k, v in margins.items() if v is not None},
        "ok": fit_tier is not None,
    }
    mode = _xla_mode()
    if mode != "0" and (
        mode == "full" or any(name.endswith(g) for g in _XLA_COST_GRAPHS)
    ):
        xla_b = _xla_cost_bytes(fn, specs)
        if xla_b is not None:
            row["xla_bytes_accessed"] = xla_b
    rows = [row]
    seen = set()
    for rec in vmem_records:
        key = (rec["layout"], rec["tile"], rec["lanes"], rec["n_rows_out"],
               rec["n_pass"])
        if key in seen:
            continue
        seen.add(key)
        est = rec["est_vmem_bytes"]
        rows.append({
            "graph": name,
            "kind": "vmem_tile",
            **rec,
            "ok": _vmem_fit(est, tiers),
        })
    return rows


def certify_graph_callable(
    fn, specs, backend: str = "f64", tiers: dict | None = None
) -> list[dict]:
    """Footprint-certify ONE callable under ``backend`` (fixture corpus /
    mutation tests — the memory twin of bounds.certify_callable)."""
    from .bounds import _forced_backend

    tiers = tiers or DEVICE_TIERS
    with _forced_backend(backend):
        return _trace_footprint(
            getattr(fn, "__name__", "callable"), fn, specs, tiers
        )


# --------------------------------------------------------------------------------------
# The certificate
# --------------------------------------------------------------------------------------


def certify_memory(
    backends=_DEFAULT_BACKENDS,
    batches=_DEFAULT_BATCHES,
    graphs=None,
    tiers: dict | None = None,
) -> dict:
    """Run the full memory certificate: every registry graph x conv backend
    x batch regime, the five subsystem residency models, and the per-tier
    planner. ``graphs`` optionally restricts to names containing any of the
    given substrings (the residency rows always run — they are arithmetic)."""
    from .bounds import _forced_backend, graph_registry

    tiers = tiers or DEVICE_TIERS
    rows: list[dict] = []
    for backend in backends:
        with _forced_backend(backend):
            for batch in batches:
                regime = f"{backend}@b{batch}"
                for name, fn, specs in graph_registry(batch):
                    if graphs and not any(s in name for s in graphs):
                        continue
                    rows.extend(
                        _trace_footprint(f"{regime}/{name}", fn, specs, tiers)
                    )
    for name, nbytes in _residency_ladder():
        fit_tier, margins = _tier_fit(nbytes, tiers)
        rows.append({
            "graph": name,
            "kind": "residency",
            "resident_bytes": int(nbytes),
            "min_tier": fit_tier,
            "margin_bytes": {
                k: v for k, v in margins.items() if v is not None
            },
            "ok": fit_tier is not None,
        })
    failed = [r for r in rows if not r["ok"]]
    peaks = _peak_table(rows)
    planner = {
        tier: {
            g: max_safe_shape_from_peaks(p, tiers[tier])
            for g, p in peaks.items()
        }
        for tier in tiers
    }
    return {
        "version": 1,
        "tool": "python -m lighthouse_tpu.analysis --memory",
        "backends": list(backends),
        "batches": list(batches),
        "tiers": {k: dict(v) for k, v in tiers.items()},
        "default_tier": DEFAULT_TIER,
        "ok": not failed,
        "n_rows": len(rows),
        "n_failed": len(failed),
        "peaks": peaks,
        "planner": planner,
        "rows": rows,
    }


def _peak_table(rows: list[dict]) -> dict:
    """{base graph name: {batch: max peak bytes across backends}} — the
    compact table the planner and ``rung_fit`` consume."""
    peaks: dict[str, dict] = {}
    for r in rows:
        if r.get("kind") != "graph_footprint":
            continue
        regime, _, base = r["graph"].partition("/")
        _, _, b = regime.partition("@b")
        try:
            batch = int(b)
        except ValueError:
            continue
        d = peaks.setdefault(base, {})
        d[str(batch)] = max(d.get(str(batch), 0), r["peak_bytes"])
    return peaks


def max_safe_shape_from_peaks(
    batch_peaks: dict, tier_caps: dict, max_batch: int = 1 << 20
) -> int | None:
    """Largest pow2 batch whose extrapolated peak fits ``tier_caps``. The
    peak model is affine in batch, fit through the two certified regimes
    (footprints are sums over batch-extended avals, so the extrapolation is
    exact up to padding). None = no certified data; an unbounded tier
    certifies the probe ceiling."""
    cap = tier_caps.get("hbm_bytes")
    pts = sorted((int(b), int(p)) for b, p in batch_peaks.items())
    if not pts:
        return None
    if cap is None:
        return max_batch
    if len(pts) == 1:
        b0, p0 = pts[0]
        slope = p0 / max(1, b0)
        base = 0.0
    else:
        (b0, p0), (b1, p1) = pts[0], pts[-1]
        slope = (p1 - p0) / max(1, b1 - b0)
        base = p0 - slope * b0
    if base > cap:
        return None
    best = None
    b = 1
    while b <= max_batch:
        if base + slope * b <= cap:
            best = b
        b *= 2
    return best


def max_safe_shape(
    graph: str, tier: str, cert: dict | None = None
) -> int | None:
    """Largest certified pow2 batch of ``graph`` on ``tier``. Reads the
    planner section of ``cert`` (or MEMORY_CERT.json at the repo root)."""
    cert = cert or _load_cert()
    if cert is None:
        return None
    planner = cert.get("planner", {}).get(tier)
    if planner is None:
        tiers = cert.get("tiers", DEVICE_TIERS)
        caps = tiers.get(tier)
        if caps is None:
            return None
        peaks = cert.get("peaks", {}).get(graph)
        return max_safe_shape_from_peaks(peaks, caps) if peaks else None
    return planner.get(graph)


def _repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def _load_cert(path: str | None = None) -> dict | None:
    import json

    path = path or os.path.join(_repo_root(), "MEMORY_CERT.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def write_cert(cert: dict, path: str) -> None:
    import json

    with open(path, "w") as f:
        json.dump(cert, f, indent=1)
        f.write("\n")


# --------------------------------------------------------------------------------------
# Rung gating + fault enrichment
# --------------------------------------------------------------------------------------

# Representative certified graph per bench rung mode: the rung's device
# working set is the graph peak extrapolated to the rung batch, plus the
# mode's resident planes. Validator-extent modes scale the 256-bucket
# registry-certified sweep peak by the rung's validator bucket.
_MODE_GRAPH = {
    "sets": "pairing.miller_loop_product",
    "firehose": "pairing.miller_loop_product",
    "overload": "pairing.miller_loop_product",
    "firehose_sharded": "tpu_backend.shard_local_pair_verdict",
    "h2c": "h2c.map_to_g2",
    "pairing": "pairing.miller_loop",
    "kzg_cells": "kzg.fr_dot",
    "light_clients": "lc.batch_check",
    "epoch": "epoch.sweep_electra",
    "epoch_sharded": "epoch.sweep_electra",
    "slasher": "slasher.sweep",
}

_VALIDATOR_MODES = ("epoch", "epoch_sharded", "slasher")


def _graph_peak_at(cert: dict | None, graph: str, batch: int) -> int | None:
    if cert is None:
        return None
    peaks = cert.get("peaks", {}).get(graph)
    if not peaks:
        return None
    pts = sorted((int(b), int(p)) for b, p in peaks.items())
    if len(pts) == 1:
        b0, p0 = pts[0]
        return int(p0 / max(1, b0) * max(1, batch))
    (b0, p0), (b1, p1) = pts[0], pts[-1]
    slope = (p1 - p0) / max(1, b1 - b0)
    return int(max(p0, p0 + slope * (batch - b0)))


def rung_fit(
    mode: str,
    sets: int,
    keys: int,
    validators: int,
    batch: int,
    tier: str = DEFAULT_TIER,
    cert: dict | None = None,
    tier_caps: dict | None = None,
) -> dict:
    """Static fit verdict for one bench ladder rung on ``tier``:
    {fits, domain, predicted_bytes, cap_bytes, margin_bytes, tier}. Pure
    arithmetic over the residency models plus the certificate's peak table
    when one is available — no jax and no device. Unknown tiers and missing
    certificates predict only the residency component (never block a rung
    on missing data; an over-budget RESIDENT plane is still caught)."""
    caps = tier_caps or (cert or {}).get("tiers", {}).get(tier) \
        or DEVICE_TIERS.get(tier, {})
    cap = caps.get("hbm_bytes")
    resident = 0
    if mode in ("epoch", "epoch_sharded"):
        resident += epoch_mirror_bytes(max(validators, 1))
    elif mode == "slasher":
        hist = int(os.environ.get("BENCH_SLASHER_HISTORY", "64"))
        resident += slasher_span_bytes(max(validators, 1), history=hist)
    elif mode == "kzg_cells":
        resident += kzg_table_bytes()
    elif mode == "light_clients":
        resident += lc_committee_cache_bytes(4)
    elif mode in ("firehose", "overload", "firehose_sharded"):
        shards = 8 if mode == "firehose_sharded" else 1
        resident += firehose_staging_bytes(
            max_batch=max(batch, 1), n_shards=shards
        )
    graph = _MODE_GRAPH.get(mode)
    peak = None
    if graph is not None:
        if mode in _VALIDATOR_MODES:
            # registry graphs certify the 256-bucket validator extent;
            # temps scale with the plane extent
            p256 = _graph_peak_at(cert, graph, 1)
            if p256 is not None:
                peak = int(p256 * _pow2_bucket(max(validators, 1), 256) / 256)
        else:
            peak = _graph_peak_at(cert, graph, max(batch, 1))
    predicted = resident + (peak or 0)
    fits = cap is None or predicted <= cap
    return {
        "fits": bool(fits),
        "tier": tier,
        "domain": mode,
        "graph": graph,
        "predicted_bytes": int(predicted),
        "resident_bytes": int(resident),
        "graph_peak_bytes": peak,
        "cap_bytes": cap,
        "margin_bytes": None if cap is None else int(cap) - int(predicted),
    }


# fault-domain -> (residency gauge metric name, cert graph) for OOM
# enrichment: when the classifier tags a device fault as ``oom``, the
# record carries what the static model predicted for that domain.
_DOMAIN_INFO = {
    "epoch_device": ("epoch_mirror_bytes", "epoch.sweep_electra"),
    "slasher_device": ("slasher_span_plane_bytes", "slasher.sweep"),
    "lc_device": ("lc_committee_cache_bytes", "lc.batch_check"),
    "kzg_device": ("kzg_table_bytes", "kzg.fr_dot"),
    "firehose": (None, "pairing.miller_loop_product"),
    "bls_device": (None, "pairing.miller_loop_product"),
}


def fault_memory_context(domain: str, tier: str | None = None) -> dict | None:
    """Static-memory context attached to an ``oom``-classified fault
    record: the domain's certified peak bytes (from MEMORY_CERT.json when
    present), its live device-resident bytes (from the residency gauges),
    and the margin against ``tier``. Best-effort: returns None for unknown
    domains, never raises."""
    try:
        info = _DOMAIN_INFO.get(domain)
        if info is None:
            return None
        gauge_name, graph = info
        tier = tier or DEFAULT_TIER
        cap = DEVICE_TIERS.get(tier, {}).get("hbm_bytes")
        resident = None
        if gauge_name is not None:
            from ..utils import metrics

            g = getattr(
                metrics, gauge_name.upper(), None
            )
            if g is not None:
                vals = [v for _, _, v in g.collect()]
                resident = int(max(vals)) if vals else None
        cert = _load_cert()
        peak = _graph_peak_at(cert, graph, 32) if cert else None
        out = {
            "tier": tier,
            "tier_hbm_bytes": cap,
            "certified_peak_bytes": peak,
            "resident_bytes": resident,
        }
        if cap is not None:
            used = (resident or 0) + (peak or 0)
            out["margin_bytes"] = int(cap) - int(used)
        return out
    except Exception:  # noqa: BLE001 — enrichment must never fail a record
        return None
