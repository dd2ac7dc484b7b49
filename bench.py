"""Headline benchmark: mainnet-shape batched BLS attestation verification.

Prints ONE JSON line. The record is self-describing: it carries the
platform and device kind the device path ran on, the shapes measured, and
a rough MFU estimate from XLA's own cost analysis of the fused kernel
(per-stage device time comes from a profiler trace of the program's own
spans and stage modules: ``benchmark/programtrace.py``). Every measurement runs on a TPU: an ``--inner`` process
that finds none exits non-zero, and bench.py then prints a failure record
and exits non-zero. The parent never touches JAX (a parent holding the chip
would lock its children out).

    metric        bls_attestation_sets_verified_per_s
    value         device-path sets/s (fused gather + h2c + decompress + RLC
                  kernel, lighthouse_tpu.bls.tpu_backend)
    vs_baseline   device / native-C++ single-core sets/s on THIS host
                  (lighthouse_tpu/native/bls12_381.cpp, the blst analog;
                  BASELINE.md calibrates native-vs-blst at ~6x and the
                  32-core north star at vs_baseline ~200)
    platform      jax platform the device path ran on ("tpu")
    shape         {sets, keys_per_set, validators, batch}
    mfu_estimate  fused-kernel FLOP/s (XLA cost analysis) / the device
                  kind's peak (null for a kind not in _PEAK_FLOPS) —
                  indicative only: the kernel is limb arithmetic, not bf16
                  matmuls, so this is a utilization proxy, not true MFU

Shape (BASELINE.json config #4, the epoch-replay shape): N_SETS aggregate
attestation signature sets, KEYS_PER_SET attesting pubkeys each (mainnet:
~64 committees x 32 slots = 2048 aggregates of ~450 attesters), validator
pubkeys resident in a decompressed device cache. Each side does the FULL
verification: per-set pubkey aggregation, hash-to-curve of the 32-byte
roots, signature decompression + subgroup checks, random-linear-combination
scaling, Miller loops, final exponentiation.

Fixtures (validator keys, signatures) are built once and cached in
.bench_cache/. Env overrides: BENCH_SETS, BENCH_KEYS, BENCH_VALIDATORS,
BENCH_BATCH.

Reference semantics being measured: blst's random-linear-combination batch
verify, /root/reference/crypto/bls/src/impls/blst.rs:37-119.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

N_SETS = int(os.environ.get("BENCH_SETS", "256"))
KEYS_PER_SET = int(os.environ.get("BENCH_KEYS", "448"))
N_VALIDATORS = int(os.environ.get("BENCH_VALIDATORS", "16384"))
BATCH = int(os.environ.get("BENCH_BATCH", "64"))  # gossip batch size (ref: 64)

_CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".bench_cache")
_FIXTURE = os.path.join(
    _CACHE_DIR, f"fixture_v{N_VALIDATORS}_s{N_SETS}_k{KEYS_PER_SET}.npz"
)

# Peak FLOP/s for the MFU proxy, keyed by jax device_kind. TPU v5e: 197
# TFLOP/s bf16 (Google Cloud documentation, "TPU v5e"). A kind that is not
# here gets mfu_estimate null, never an assumed peak.
_PEAK_FLOPS = {"TPU v5 lite": 197e12}


def _require_tpu() -> None:
    """Every measurement runs on the chip: no TPU, no record."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {dev.platform} devices)")


def run_inner(
    sets: int,
    keys: int,
    validators: int,
    batch: int,
    timeout: float,
    mode: str = "sets",
    mesh_devices: int = 0,
) -> tuple[dict | None, str]:
    """Run this file's --inner measurement in a subprocess at one shape.
    Returns (record | None, note). ``mode`` selects the measurement: "sets"
    (headline RLC batch verify), "firehose" (the streaming engine rung), or
    the ``*_sharded`` multi-chip variants (``mesh_devices`` devices)."""
    if mode.endswith("_sharded") and not mesh_devices:
        mesh_devices = int(os.environ.get("BENCH_MESH_DEVICES", "8"))
    env = dict(
        os.environ,
        BENCH_SETS=str(sets),
        BENCH_KEYS=str(keys),
        BENCH_VALIDATORS=str(validators),
        BENCH_BATCH=str(batch),
        BENCH_MODE=mode,
    )
    if mesh_devices:
        env["BENCH_MESH_DEVICES"] = str(mesh_devices)
    rss_before = _children_peak_rss_bytes()
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--inner"],
            env=env,
            capture_output=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        return None, f"shape ({sets}x{keys}) exceeded {timeout:.0f}s"
    stdout = out.stdout.decode(errors="replace")
    for ln in stdout.splitlines():
        if ln.startswith("#"):
            print(ln, file=sys.stderr)
    sys.stderr.write(out.stderr.decode(errors="replace")[-2000:])
    json_lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not json_lines:
        return None, (
            f"shape ({sets}x{keys}) rc={out.returncode}: "
            + out.stderr.decode(errors="replace")[-300:].strip()
        )
    try:
        rec = json.loads(json_lines[-1])
    except ValueError:
        return None, f"shape ({sets}x{keys}) emitted unparseable JSON"
    _stamp_memory(rec, rss_before)
    return rec, "ok"


def _children_peak_rss_bytes() -> int | None:
    try:
        import resource

        ru = resource.getrusage(resource.RUSAGE_CHILDREN)
        return int(ru.ru_maxrss) * 1024  # linux reports KiB
    except Exception:  # noqa: BLE001 — the stamp must never fail a record
        return None


def _stamp_memory(rec, rss_before):
    """Peak RSS of the inner subprocess that just ran the rung (ISSUE 20's
    measured host-memory counterpart). ru_maxrss is a high-water mark across
    ALL children: the delta is only attributable to this subprocess when it
    set a new high."""
    rss_after = _children_peak_rss_bytes()
    mem = {"child_peak_rss_bytes": rss_after}
    if rss_before is not None and rss_after is not None:
        mem["child_peak_rss_delta_bytes"] = max(0, rss_after - rss_before)
    rec["memory"] = mem


def _curve_order() -> int:
    from lighthouse_tpu.ops.bls_oracle.fields import R

    return R


def _build_fixture():
    """Registry of N_VALIDATORS keys + N_SETS aggregate sets.

    The aggregate signature of keys {sk_i} on message m equals the signature
    of (sum sk_i mod r) on m, so each set needs ONE native sign instead of
    KEYS_PER_SET — fixture construction stays minutes-free at mainnet shape.
    """
    from lighthouse_tpu.native.build import NativeBls

    nb = NativeBls()
    order = _curve_order()
    rng = np.random.default_rng(0xBEAC0)
    sks = [
        (int.from_bytes(rng.bytes(31), "big") + 1) % order or 1
        for _ in range(N_VALIDATORS)
    ]
    pks_comp = np.zeros((N_VALIDATORS, 48), dtype=np.uint8)
    pks_raw = np.zeros((N_VALIDATORS, 96), dtype=np.uint8)
    for i, sk in enumerate(sks):
        c = nb.sk_to_pk(sk.to_bytes(32, "big"))
        pks_comp[i] = np.frombuffer(c, dtype=np.uint8)
        pks_raw[i] = np.frombuffer(nb.pk_decompress(c), dtype=np.uint8)

    idx = np.zeros((N_SETS, KEYS_PER_SET), dtype=np.int32)
    msgs = np.zeros((N_SETS, 32), dtype=np.uint8)
    sigs = np.zeros((N_SETS, 96), dtype=np.uint8)
    for s in range(N_SETS):
        members = rng.choice(N_VALIDATORS, size=KEYS_PER_SET, replace=False)
        idx[s] = np.sort(members)
        msg = rng.bytes(32)
        msgs[s] = np.frombuffer(msg, dtype=np.uint8)
        agg_sk = sum(sks[int(i)] for i in idx[s]) % order
        sigs[s] = np.frombuffer(
            nb.sign(agg_sk.to_bytes(32, "big"), msg), dtype=np.uint8
        )
    os.makedirs(_CACHE_DIR, exist_ok=True)
    tmp = _FIXTURE + f".tmp{os.getpid()}.npz"
    np.savez_compressed(
        tmp, pks_comp=pks_comp, pks_raw=pks_raw, idx=idx, msgs=msgs, sigs=sigs
    )
    os.replace(tmp, _FIXTURE)


def _fixture():
    if not os.path.exists(_FIXTURE):
        t0 = time.perf_counter()
        _build_fixture()
        print(f"# fixture built in {time.perf_counter() - t0:.0f}s", flush=True)
    try:
        z = np.load(_FIXTURE)
        return z["pks_comp"], z["pks_raw"], z["idx"], z["msgs"], z["sigs"]
    except Exception:  # noqa: BLE001 — corrupt cache: rebuild once
        os.remove(_FIXTURE)
        _build_fixture()
        z = np.load(_FIXTURE)
        return z["pks_comp"], z["pks_raw"], z["idx"], z["msgs"], z["sigs"]


def _scalars(n):
    rng = np.random.default_rng(0x5CA1A5)
    return (rng.integers(1, 2**63, size=n, dtype=np.uint64) * 2 + 1).astype(
        np.uint64
    )


def _time_stage(fn, *args, iters: int = 3) -> float:
    """Milliseconds per call of a jitted stage (compile excluded)."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _kernel_flops(cache, items) -> float:
    """XLA's own FLOP estimate for the fused batch kernel (one dispatch)."""
    import jax.numpy as jnp

    from lighthouse_tpu.bls import tpu_backend as tb

    try:
        n_pad = tb.bucket(len(items))
        k_pad = tb.bucket(max(len(ix) for ix, _, _ in items))
        total = 0.0
        for _name, lowered in tb.stage_lowerings(
            n_pad, k_pad, int(cache.shape[0])
        ):
            cost = lowered.compile().cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            total += float(cost.get("flops", 0.0))
        return total
    except Exception as e:  # noqa: BLE001 — cost analysis is best-effort
        print(f"# cost_analysis unavailable: {e}", file=sys.stderr)
        return 0.0


def _bench_device(pks_raw, idx, msgs, sigs) -> tuple[float, float, str]:
    """Returns (sets/s, fused-kernel FLOPs/batch, platform)."""
    import jax

    from lighthouse_tpu.beacon_chain.pubkey_cache import device_pubkeys_from_raw
    from lighthouse_tpu.bls import tpu_backend as tb

    platform = jax.devices()[0].platform
    cache = device_pubkeys_from_raw(pks_raw)
    cache.block_until_ready()

    items_all = [
        (
            idx[s].tolist(),
            msgs[s].tobytes(),
            sigs[s].tobytes(),
        )
        for s in range(N_SETS)
    ]
    # warm up compile on the first batch shape
    t0 = time.perf_counter()
    assert tb.verify_indexed_sets_device(cache, items_all[:BATCH]), (
        "device path rejected valid sets"
    )
    print(
        f"# warmup (compile) {time.perf_counter() - t0:.0f}s on {platform}",
        flush=True,
    )
    t0 = time.perf_counter()
    for off in range(0, N_SETS, BATCH):
        ok = tb.verify_indexed_sets_device(cache, items_all[off : off + BATCH])
        assert ok, f"device batch at {off} rejected"
    dt = time.perf_counter() - t0
    flops = _kernel_flops(cache, items_all[:BATCH])
    return N_SETS / dt, flops, platform


def _bench_native(pks_raw, idx, msgs, sigs) -> float:
    from lighthouse_tpu.native.build import NativeBls

    nb = NativeBls()
    raw_bytes = [pks_raw[i].tobytes() for i in range(pks_raw.shape[0])]
    pk_sets = [[raw_bytes[int(i)] for i in idx[s]] for s in range(N_SETS)]
    msg_list = [msgs[s].tobytes() for s in range(N_SETS)]
    sig_list = [sigs[s].tobytes() for s in range(N_SETS)]
    scal = _scalars(N_SETS).tolist()
    t0 = time.perf_counter()
    for off in range(0, N_SETS, BATCH):
        ok = nb.verify_signature_sets_raw(
            pk_sets[off : off + BATCH],
            msg_list[off : off + BATCH],
            sig_list[off : off + BATCH],
            scal[off : off + BATCH],
        )
        assert ok, f"native batch at {off} rejected"
    dt = time.perf_counter() - t0
    return N_SETS / dt


def _backend_stamp() -> dict:
    """Device + conv-backend + jax-version stamp for every rung record
    (ISSUE 13): pallas / digits / f64 run DIFFERENT kernels with different
    perf envelopes, and a jax upgrade changes the pallas lowering — records
    from different backends must be distinguishable. Called in the --inner
    process only."""
    try:
        import jax

        from lighthouse_tpu.ops.bls import fq

        dev = jax.devices()[0]
        stamp = {
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "conv_impl": fq.conv_backend(),
            "jax_version": jax.__version__,
        }
        try:
            # device-side allocator stats when the runtime exposes them
            # (TPU/GPU; CPU returns None/raises) — ISSUE 20's measured
            # counterpart to the cert's predicted peak bytes
            ms = jax.devices()[0].memory_stats()
            if ms:
                stamp["device_memory_stats"] = {
                    k: int(v)
                    for k, v in ms.items()
                    if k in (
                        "bytes_in_use",
                        "peak_bytes_in_use",
                        "bytes_limit",
                        "largest_alloc_size",
                    )
                }
        except Exception:  # noqa: BLE001 — stats are best-effort
            pass
        return stamp
    except Exception:  # noqa: BLE001 — the stamp must never fail a record
        return {"conv_impl": "unknown", "jax_version": "unknown"}


def _resilience_summary() -> dict | None:
    """Fault-domain integrity stamp for every rung record (ISSUE 7): the
    supervisor snapshot proves whether any part of the measurement was
    served below the full device rung — a demoted / CPU-fallback run can
    never masquerade as a device-throughput record in BENCH_*.json."""
    try:
        from lighthouse_tpu.resilience import snapshot_all
    except Exception:  # noqa: BLE001 — the stamp must never fail a record
        return None
    snaps = snapshot_all()
    demotions = sum(s["demotions"] for s in snaps.values())
    fallback = sum(s["fallback_calls"] for s in snaps.values())
    try:
        from lighthouse_tpu.beacon_chain.recovery import (
            snapshot_recovery_totals,
        )

        recovery = snapshot_recovery_totals()
    except Exception:  # noqa: BLE001 — the stamp must never fail a record
        recovery = None
    return {
        "demotions": demotions,
        "fallback_calls": fallback,
        "watchdog_timeouts": sum(
            s["watchdog_timeouts"] for s in snaps.values()
        ),
        "degraded": bool(demotions or fallback),
        "supervisor_states": {k: v["state"] for k, v in snaps.items()},
        # crash-recovery integrity (ISSUE 12): a measurement that silently
        # restarted from disk mid-run (or replayed/truncated WAL records)
        # is visible in the record
        "recovery": recovery,
    }


def _inner():
    """Run the full native + device measurement at the env-given shapes and
    print the JSON record. Invoked in a SUBPROCESS by main() so a
    pathologically slow device compile is bounded by the parent's timeout
    instead of producing no record at all."""
    _require_tpu()
    pks_comp, pks_raw, idx, msgs, sigs = _fixture()
    native = _bench_native(pks_raw, idx, msgs, sigs)
    print(f"# native (C++ single-core): {native:.2f} sets/s", flush=True)
    dev, flops, platform = _bench_device(pks_raw, idx, msgs, sigs)

    import jax

    mfu = None
    peak = _PEAK_FLOPS.get(jax.devices()[0].device_kind)
    if flops and dev and peak:
        mfu = round(flops / (BATCH / dev) / peak, 5)

    print(
        json.dumps(
            {
                "metric": "bls_attestation_sets_verified_per_s",
                "value": round(dev, 2),
                "unit": "sets/s",
                "vs_baseline": round(dev / native, 3),
                "platform": platform,
                **_backend_stamp(),
                "shape": {
                    "sets": N_SETS,
                    "keys_per_set": KEYS_PER_SET,
                    "validators": N_VALIDATORS,
                    "batch": BATCH,
                },
                "native_cpu_sets_per_s": round(native, 2),
                "kernel_gflops_per_batch": round(flops / 1e9, 2) if flops else None,
                "mfu_estimate": mfu,
                "resilience": _resilience_summary(),
            }
        )
    )


# Serving-tier SLOs (BASELINE config #5 framing + "Performance of EdDSA and
# BLS Signatures in Committee-Based Consensus": batched throughput only
# counts for consensus if queue latency and drop rate hold under burst).
# Every firehose rung record reports measured values AGAINST these.
FIREHOSE_SLOS = {
    "p99_queue_latency_ms": 250.0,
    "max_drop_rate": 0.05,
}


def _pace_stream(engine, pool, rate: float, duration: float,
                 drain_timeout: float) -> tuple[int, float]:
    """Seeded synthetic gossip generator: pace ``rate`` att/s of pool items
    into the engine in 1 ms micro-bursts (the intake is non-blocking;
    overflow sheds inside the engine, never stalls the generator — the
    pool order is the fixture's seeded order, so every A/B run offers the
    identical stream). Returns (items offered, wall seconds incl. drain)."""
    t_start = time.perf_counter()
    n_stream = 0
    per_tick = max(1, int(rate / 1000))
    while True:
        elapsed = time.perf_counter() - t_start
        if elapsed >= duration:
            break
        target = min(int(rate * elapsed) + per_tick, int(rate * duration))
        while n_stream < target:
            engine.submit(pool[n_stream % len(pool)])
            n_stream += 1
        time.sleep(0.001)
    engine.stop(drain_timeout=drain_timeout)
    return n_stream, time.perf_counter() - t_start


def _slo_block(st, n_stream: int) -> dict:
    """Measured-vs-declared SLO block for a firehose stats snapshot."""
    drop_rate = st.dropped / n_stream if n_stream else 0.0
    p99_ms = (
        st.p99_latency_s * 1e3 if st.p99_latency_s is not None else None
    )
    return {
        "declared": dict(FIREHOSE_SLOS),
        "measured": {
            "p99_queue_latency_ms": round(p99_ms, 2) if p99_ms else p99_ms,
            "drop_rate": round(drop_rate, 4),
        },
        "met": {
            "p99_queue_latency_ms": (
                p99_ms is not None
                and p99_ms <= FIREHOSE_SLOS["p99_queue_latency_ms"]
            ),
            "drop_rate": drop_rate <= FIREHOSE_SLOS["max_drop_rate"],
        },
    }


def _inner_firehose():
    """Firehose rung (BASELINE.json config #5: "beacon_processor verifying a
    50k att/s stream with back-pressure"): pace a synthetic unaggregated-
    attestation stream into the firehose engine and report sustained
    verified attestations/sec, queue latency percentiles, drop rate and
    batches formed. The verify stage is the REAL device path
    (tb.verify_indexed_sets_device against a device-resident pubkey
    cache)."""
    _require_tpu()
    import jax

    from lighthouse_tpu.beacon_chain.pubkey_cache import device_pubkeys_from_raw
    from lighthouse_tpu.bls import tpu_backend as tb
    from lighthouse_tpu.firehose import FirehoseConfig, FirehoseEngine

    rate = float(os.environ.get("BENCH_FIREHOSE_RATE", "50000"))
    duration = float(os.environ.get("BENCH_FIREHOSE_SECONDS", "3.0"))
    fh_batch = BATCH
    intake = int(os.environ.get("BENCH_FIREHOSE_INTAKE", str(16 * fh_batch)))
    drain_timeout = float(os.environ.get("BENCH_FIREHOSE_DRAIN_S", "120"))

    platform = jax.devices()[0].platform
    pks_comp, pks_raw, idx, msgs, sigs = _fixture()
    cache = device_pubkeys_from_raw(pks_raw)
    cache.block_until_ready()
    # KEYS_PER_SET=1 fixture: one attester per set, the gossip shape
    pool = [
        (idx[s].tolist(), msgs[s].tobytes(), sigs[s].tobytes())
        for s in range(N_SETS)
    ]

    def verify(items):
        return tb.verify_indexed_sets_device(cache, items)

    t0 = time.perf_counter()
    assert verify(pool[:fh_batch]), "firehose warmup batch rejected"
    print(
        f"# firehose warmup (compile) {time.perf_counter() - t0:.0f}s "
        f"on {platform}",
        flush=True,
    )

    # the rung runs inside its own fault domain: watchdog + retry + the
    # full->halved ladder (no CPU-fallback rung — a demoted stream must
    # show up as errored/demoted in the record, not as fake throughput)
    from lighthouse_tpu.resilience import get_supervisor

    supervisor = get_supervisor("bench.firehose")
    engine = FirehoseEngine(
        prepare_fn=lambda payloads: [([p], None) for p in payloads],
        verify_items_fn=verify,
        config=FirehoseConfig(
            max_batch=fh_batch,
            deadline_s=0.010,
            intake_capacity=intake,
        ),
        supervisor=supervisor,
    )
    n_stream, wall = _pace_stream(engine, pool, rate, duration, drain_timeout)
    st = engine.stats()
    # offered = paced stream; accepted = past the intake gate; dropped counts
    # both gate rejections and later back-pressure evictions
    drop_rate = st.dropped / n_stream if n_stream else 0.0
    print(
        json.dumps(
            {
                "metric": "firehose_attestations_verified_per_s",
                "value": round(st.verified / wall, 2),
                "unit": "att/s",
                "platform": platform,
                **_backend_stamp(),
                "stream": {
                    "offered_att_per_s": rate,
                    "duration_s": duration,
                    "offered": n_stream,
                    "accepted": st.submitted,
                    "batch": fh_batch,
                    "intake_capacity": intake,
                    "validators": N_VALIDATORS,
                    "pool_sets": N_SETS,
                },
                "verified": st.verified,
                "rejected": st.rejected,
                "errored": st.errored,
                "dropped": st.dropped,
                "drop_rate": round(drop_rate, 4),
                "batches_formed": st.batches_formed,
                "device_faults": st.device_faults,
                "slo": _slo_block(st, n_stream),
                "resilience": _resilience_summary(),
                "queue_latency_p50_ms": (
                    round(st.p50_latency_s * 1e3, 2)
                    if st.p50_latency_s is not None
                    else None
                ),
                "queue_latency_p99_ms": (
                    round(st.p99_latency_s * 1e3, 2)
                    if st.p99_latency_s is not None
                    else None
                ),
                "wall_s": round(wall, 2),
            }
        )
    )


# Overload-protection SLOs (ISSUE 18): what the node must still deliver to
# HONEST traffic while an abusive peer floods it at 10x quota. Declared next
# to FIREHOSE_SLOS; every --overload record reports measured values against
# these (see also pytest.ini's overload knobs).
OVERLOAD_SLOS = {
    "honest_p99_e2e_ms": 5000.0,   # gossip->verdict p99 for admitted honest work
    "max_honest_drop_rate": 0.50,  # honest share shed under sustained abuse
}


def _inner_overload():
    """Sustained-abuse rung (ISSUE 18): an honest paced attestation stream
    plus a 10x malformed low-priority flood into the SAME firehose intake,
    with a LoadMonitor folding intake depth / drop rate / lag into an
    admission level. The record proves the overload-protection tier end to
    end: honest throughput + gossip->verdict p50/p99 under abuse, admission
    transitions, shed counts by priority, bounded queues, and an in-rung
    HTTP probe asserting P1 routes get 503 + Retry-After while P0 duty
    routes still get 200 at SATURATED. Zero false verifies is asserted from
    the abuse callbacks (an abusive payload must never earn verdict True)."""
    _require_tpu()
    import threading as _threading
    import urllib.error
    import urllib.request

    import jax

    from lighthouse_tpu.beacon_chain.pubkey_cache import device_pubkeys_from_raw
    from lighthouse_tpu.beacon_processor.processor import WorkType
    from lighthouse_tpu.bls import tpu_backend as tb
    from lighthouse_tpu.firehose import FirehoseConfig, FirehoseEngine
    from lighthouse_tpu.http_api import BeaconApiServer
    from lighthouse_tpu.loadshed import AdmissionLevel, LoadMonitor, deadline_for

    rate = float(os.environ.get("BENCH_OVERLOAD_RATE", "10000"))
    abuse_x = float(os.environ.get("BENCH_OVERLOAD_ABUSE_X", "10"))
    duration = float(os.environ.get("BENCH_OVERLOAD_SECONDS", "3.0"))
    fh_batch = BATCH
    intake = int(os.environ.get("BENCH_FIREHOSE_INTAKE", str(16 * fh_batch)))
    drain_timeout = float(os.environ.get("BENCH_FIREHOSE_DRAIN_S", "120"))

    platform = jax.devices()[0].platform
    pks_comp, pks_raw, idx, msgs, sigs = _fixture()
    cache = device_pubkeys_from_raw(pks_raw)
    cache.block_until_ready()
    pool = [
        (idx[s].tolist(), msgs[s].tobytes(), sigs[s].tobytes())
        for s in range(N_SETS)
    ]

    def prepare(payloads):
        # abusive payloads are malformed gossip: they fail decode before
        # any crypto (prep-stage Exception), the way real spam does
        return [
            ValueError("malformed gossip payload")
            if isinstance(p, tuple) and p and p[0] == "abuse"
            else ([p], None)
            for p in payloads
        ]

    def verify(items):
        return tb.verify_indexed_sets_device(cache, items)

    t0 = time.perf_counter()
    assert verify(pool[:fh_batch]), "overload warmup batch rejected"
    print(
        f"# overload warmup (compile) {time.perf_counter() - t0:.0f}s "
        f"on {platform}",
        flush=True,
    )

    from lighthouse_tpu.resilience import get_supervisor

    engine = FirehoseEngine(
        prepare_fn=prepare,
        verify_items_fn=verify,
        config=FirehoseConfig(
            max_batch=fh_batch,
            deadline_s=0.010,
            intake_capacity=intake,
        ),
        supervisor=get_supervisor("bench.overload"),
    )
    monitor = LoadMonitor()
    monitor.attach_batcher(engine.batcher)

    # HTTP admission probe target: a stub chain is enough — the gate runs
    # before any route handler, and the probed P0 route (node/syncing)
    # reads only head.slot / current_slot
    class _StubHead:
        slot = 0

    class _StubChain:
        lock = _threading.Lock()
        head = _StubHead()
        execution_layer = None

        def current_slot(self):
            return 0

    api = BeaconApiServer(_StubChain(), load_monitor=monitor).start()

    def _probe():
        out = {}
        try:
            with urllib.request.urlopen(
                api.url + "/eth/v1/node/version", timeout=10
            ) as r:
                out["p1_status"] = r.status
                out["p1_retry_after"] = None
        except urllib.error.HTTPError as e:
            out["p1_status"] = e.code
            out["p1_retry_after"] = e.headers.get("Retry-After")
        with urllib.request.urlopen(
            api.url + "/eth/v1/node/syncing", timeout=10
        ) as r:
            out["p0_status"] = r.status
        return out

    cb_lock = _threading.Lock()
    counts = {"honest_ok": 0, "honest_bad": 0, "false_verifies": 0,
              "abuse_refused": 0}

    def honest_cb(payload, ok, meta=None):
        with cb_lock:
            counts["honest_ok" if ok else "honest_bad"] += 1

    def abuse_cb(payload, ok, meta=None):
        with cb_lock:
            counts["false_verifies" if ok else "abuse_refused"] += 1

    abuse_rate = rate * abuse_x
    t_start = time.perf_counter()
    n_honest = n_abuse = 0
    honest_gate_drops = abuse_gate_drops = 0
    probe_result = None
    per_tick_h = max(1, int(rate / 1000))
    per_tick_a = max(1, int(abuse_rate / 1000))
    while True:
        elapsed = time.perf_counter() - t_start
        if elapsed >= duration:
            break
        now = time.monotonic()
        hd = deadline_for(WorkType.GossipAttestation, now=now)
        target_h = min(int(rate * elapsed) + per_tick_h, int(rate * duration))
        while n_honest < target_h:
            if not engine.submit(
                pool[n_honest % len(pool)],
                work_type=WorkType.GossipAttestation,
                callback=honest_cb, ingest_at=now, deadline=hd,
            ):
                honest_gate_drops += 1
            n_honest += 1
        ad = deadline_for(WorkType.GossipSyncSignature, now=now)
        target_a = min(
            int(abuse_rate * elapsed) + per_tick_a, int(abuse_rate * duration)
        )
        while n_abuse < target_a:
            if not engine.submit(
                ("abuse", n_abuse),
                work_type=WorkType.GossipSyncSignature,
                callback=abuse_cb, ingest_at=now, deadline=ad,
            ):
                abuse_gate_drops += 1
            n_abuse += 1
        if probe_result is None and monitor.level() is AdmissionLevel.SATURATED:
            probe_result = _probe()
        time.sleep(0.001)
    if probe_result is None and monitor.level() is AdmissionLevel.SATURATED:
        probe_result = _probe()
    engine.stop(drain_timeout=drain_timeout)
    wall = time.perf_counter() - t_start
    time.sleep(0.1)  # past the monitor's min sample interval: fresh level
    healthy_after = _probe()  # intake drained: P1 admitted again
    api.stop()
    st = engine.stats()

    # ---- in-rung assertions (the acceptance criteria, not post-hoc) ----
    assert probe_result is not None, (
        "monitor never reached SATURATED under a "
        f"{abuse_x:.0f}x abuse flood — admission control unproven"
    )
    assert probe_result["p1_status"] == 503, probe_result
    assert probe_result["p1_retry_after"] is not None, probe_result
    assert probe_result["p0_status"] == 200, probe_result
    assert counts["false_verifies"] == 0, counts
    assert engine.batcher.high_water <= intake, (
        engine.batcher.high_water, intake,
    )
    drops_by_type = {
        t.name: n for t, n in sorted(engine.batcher.dropped.items(),
                                     key=lambda kv: kv[0].value)
    }
    honest_dropped = drops_by_type.get("GossipAttestation", 0)
    abuse_dropped = drops_by_type.get("GossipSyncSignature", 0)
    honest_drop_rate = honest_dropped / n_honest if n_honest else 0.0
    abuse_drop_rate = abuse_dropped / n_abuse if n_abuse else 0.0
    # lowest-priority-first: the flood's type must shed at a strictly
    # higher rate than the honest (higher-priority) stream
    assert abuse_drop_rate >= honest_drop_rate, (
        abuse_drop_rate, honest_drop_rate,
    )

    p50_ms = st.p50_e2e_s * 1e3 if st.p50_e2e_s is not None else None
    p99_ms = st.p99_e2e_s * 1e3 if st.p99_e2e_s is not None else None
    print(
        json.dumps(
            {
                "metric": "overload_honest_atts_per_s",
                "value": round(counts["honest_ok"] / wall, 2),
                "unit": "att/s",
                "platform": platform,
                **_backend_stamp(),
                "stream": {
                    "honest_att_per_s": rate,
                    "abuse_multiplier": abuse_x,
                    "duration_s": duration,
                    "honest_offered": n_honest,
                    "abuse_offered": n_abuse,
                    "batch": fh_batch,
                    "intake_capacity": intake,
                    "validators": N_VALIDATORS,
                    "pool_sets": N_SETS,
                },
                "honest": {
                    "verified": counts["honest_ok"],
                    "rejected": counts["honest_bad"],
                    "dropped": honest_dropped,
                    "drop_rate": round(honest_drop_rate, 4),
                },
                "abuse": {
                    "refused": counts["abuse_refused"],
                    "false_verifies": counts["false_verifies"],
                    "dropped": abuse_dropped,
                    "drop_rate": round(abuse_drop_rate, 4),
                },
                "gossip_verdict_p50_ms": (
                    round(p50_ms, 2) if p50_ms is not None else None
                ),
                "gossip_verdict_p99_ms": (
                    round(p99_ms, 2) if p99_ms is not None else None
                ),
                "admission": {
                    "transitions": monitor.transitions(),
                    "final_level": monitor.level().name,
                    "probe_at_saturated": probe_result,
                    "probe_after_drain": healthy_after,
                },
                "shed": {
                    "intake_drops_by_type": drops_by_type,
                    "expired_by_type": {
                        t.name: n for t, n in engine.batcher.expired.items()
                    },
                    "evicted": engine.batcher.evicted,
                },
                "queues": {
                    "intake_high_water": engine.batcher.high_water,
                    "intake_capacity": intake,
                    "bounded": engine.batcher.high_water <= intake,
                },
                "slo": {
                    "declared": dict(OVERLOAD_SLOS),
                    "measured": {
                        "honest_p99_e2e_ms": (
                            round(p99_ms, 2) if p99_ms is not None else None
                        ),
                        "honest_drop_rate": round(honest_drop_rate, 4),
                    },
                    "met": {
                        "honest_p99_e2e_ms": (
                            p99_ms is not None
                            and p99_ms <= OVERLOAD_SLOS["honest_p99_e2e_ms"]
                        ),
                        "honest_drop_rate": (
                            honest_drop_rate
                            <= OVERLOAD_SLOS["max_honest_drop_rate"]
                        ),
                    },
                },
                "batches_formed": st.batches_formed,
                "device_faults": st.device_faults,
                "resilience": _resilience_summary(),
                "wall_s": round(wall, 2),
            }
        )
    )


def _mesh_devices_for_inner() -> int:
    """Resolve BENCH_MESH_DEVICES inside an --inner process against the
    chips the host has. Returns the power-of-two device count to use."""
    import jax

    n_req = int(os.environ.get("BENCH_MESH_DEVICES", "8"))
    avail = len(jax.devices())
    n = 1
    while n * 2 <= min(n_req, avail):
        n *= 2
    return n


def _inner_firehose_sharded():
    """Sustained-load serving-tier rung: the SHARDED firehose engine
    (per-shard sub-batches + per-shard verdicts over the device mesh,
    firehose/sharding.py) against the single-device engine at the same
    per-shard shape, same box, same seeded offered stream — the honest A/B
    the acceptance criteria ask for. The record stamps shard count and
    host core count. No CPU-oracle rung in the ladder: a demoted stream shows up as
    errored/demoted in the record, never as fake throughput."""
    _require_tpu()
    import jax

    from lighthouse_tpu.beacon_chain.pubkey_cache import device_pubkeys_from_raw
    from lighthouse_tpu.bls import mesh as bls_mesh
    from lighthouse_tpu.bls import tpu_backend as tb
    from lighthouse_tpu.firehose import FirehoseConfig, FirehoseEngine
    from lighthouse_tpu.firehose.sharding import MeshVerifier
    from lighthouse_tpu.resilience import get_supervisor

    platform = jax.devices()[0].platform
    n_dev = _mesh_devices_for_inner()
    rate = float(os.environ.get("BENCH_FIREHOSE_RATE", "50000"))
    duration = float(os.environ.get("BENCH_FIREHOSE_SECONDS", "3.0"))
    shard_batch = BATCH
    intake = int(
        os.environ.get("BENCH_FIREHOSE_INTAKE", str(16 * n_dev * shard_batch))
    )
    drain_timeout = float(os.environ.get("BENCH_FIREHOSE_DRAIN_S", "180"))

    pks_comp, pks_raw, idx, msgs, sigs = _fixture()
    cache = device_pubkeys_from_raw(pks_raw)
    cache.block_until_ready()
    pool = [
        (idx[s].tolist(), msgs[s].tobytes(), sigs[s].tobytes())
        for s in range(N_SETS)
    ]

    def verify(items):
        return tb.verify_indexed_sets_device(cache, items)

    backend = bls_mesh.make_mesh_backend(lambda: cache)
    verifier = MeshVerifier(
        n_dev,
        dispatch_fn=backend.dispatch,
        stage_fn=backend.stage,
        probe_fn=backend.probe,
        single_fn=verify,
        oracle_fn=None,          # no CPU rung: demotion must be visible
        cap_floor=shard_batch,
    )
    n_dev = verifier.n_devices   # pow2-floored

    t0 = time.perf_counter()
    assert verify(pool[:shard_batch]), "single-device warmup batch rejected"
    t_single_c = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = verifier.verify_groups(
        [[p] for p in pool[: n_dev * shard_batch]]
    )
    assert all(warm), "sharded warmup tick rejected"
    t_shard_c = time.perf_counter() - t0
    print(
        f"# warmup (compile) single {t_single_c:.0f}s + sharded "
        f"{t_shard_c:.0f}s on {platform} x{n_dev}",
        flush=True,
    )

    def run_engine(shard_planner):
        tick = (n_dev * shard_batch) if shard_planner else shard_batch
        engine = FirehoseEngine(
            prepare_fn=lambda payloads: [([p], None) for p in payloads],
            verify_items_fn=verify,
            config=FirehoseConfig(
                max_batch=tick, deadline_s=0.010, intake_capacity=intake
            ),
            supervisor=(
                None if shard_planner else get_supervisor("bench.firehose")
            ),
            shard_planner=shard_planner,
        )
        n_stream, wall = _pace_stream(
            engine, pool, rate, duration, drain_timeout
        )
        st = engine.stats()
        return {
            "att_per_s": round(st.verified / wall, 2),
            "offered": n_stream,
            "accepted": st.submitted,
            "verified": st.verified,
            "rejected": st.rejected,
            "errored": st.errored,
            "dropped": st.dropped,
            "batches_formed": st.batches_formed,
            "device_faults": st.device_faults,
            "per_dispatch_sets": tick,
            "wall_s": round(wall, 2),
            "slo": _slo_block(st, n_stream),
            "queue_latency_p50_ms": (
                round(st.p50_latency_s * 1e3, 2)
                if st.p50_latency_s is not None else None
            ),
            "queue_latency_p99_ms": (
                round(st.p99_latency_s * 1e3, 2)
                if st.p99_latency_s is not None else None
            ),
        }

    single_rec = run_engine(None)
    sharded_rec = run_engine(verifier)
    ratio = (
        round(sharded_rec["att_per_s"] / single_rec["att_per_s"], 3)
        if single_rec["att_per_s"]
        else None
    )

    # the structural data-parallel proof, platform-independent: XLA's own
    # cost analysis. An SPMD module's reported FLOPs are per PARTITION, so
    # "per_device_flops_vs_single_dispatch" ≈ 1 says each chip does the
    # same work per tick as a whole single-device dispatch while the tick
    # carries n_dev× the sets — i.e. "per_set_flops_vs_single" ≈ 1/n_dev
    # per-device work per set. Wall clock follows on any box with ≥ n_dev
    # real compute units; these ratios transfer to a pod slice unchanged.
    per_device_flops_vs_single = None
    per_set_flops_vs_single = None
    try:
        import jax.numpy as jnp

        mesh = bls_mesh.get_mesh(tuple(range(n_dev)))
        n_pad = n_dev * shard_batch
        kp = tb.bucket(1)  # the gossip shape's key bucket (same both sides)
        sd = jax.ShapeDtypeStruct
        u64 = jnp.uint64
        u = sd((n_pad, 2, 25), u64)

        def flops_of(lowered):
            cost = lowered.compile().cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            return float(cost.get("flops", 0.0))

        shard_flops = sum(
            flops_of(lw)
            for lw in (
                tb._sharded_h2c_stage(mesh, n_pad).lower(u, u),
                tb._sharded_prep_stage(mesh, n_pad, kp).lower(
                    sd((int(cache.shape[0]), 3, 25), u64),
                    sd((n_pad, kp), jnp.int32), sd((n_pad, kp), jnp.bool_),
                    sd((n_pad, 25), u64), sd((n_pad, 25), u64),
                    sd((n_pad,), u64), sd((n_pad,), jnp.bool_),
                    sd((n_pad,), u64), sd((n_pad,), jnp.bool_),
                ),
            )
        )
        single_flops = sum(
            flops_of(lw)
            for _, lw in tb.stage_lowerings(
                shard_batch, kp, int(cache.shape[0])
            )[:2]  # h2c + prep at the single-engine dispatch shape
        )
        if shard_flops and single_flops:
            per_device_flops_vs_single = round(shard_flops / single_flops, 3)
            per_set_flops_vs_single = round(
                shard_flops / (single_flops * n_dev), 4
            )
    except Exception as e:  # noqa: BLE001 — cost analysis is best-effort
        print(f"# cost_analysis unavailable: {e}", file=sys.stderr)

    print(
        json.dumps(
            {
                "metric": "firehose_attestations_verified_per_s",
                "value": sharded_rec["att_per_s"],
                "unit": "att/s",
                "platform": platform,
                **_backend_stamp(),
                "n_devices": n_dev,
                "shard_batch": shard_batch,
                "host_cpu_count": os.cpu_count(),
                "stream": {
                    "offered_att_per_s": rate,
                    "duration_s": duration,
                    "intake_capacity": intake,
                    "validators": N_VALIDATORS,
                    "pool_sets": N_SETS,
                },
                "sharded": sharded_rec,
                "single_device": single_rec,
                "aggregate_vs_single": ratio,
                "per_device_flops_vs_single_dispatch":
                    per_device_flops_vs_single,
                "per_set_flops_vs_single": per_set_flops_vs_single,
                "slo": sharded_rec["slo"],
                "mesh": verifier.snapshot(),
                "resilience": _resilience_summary(),
            }
        )
    )


def _inner_h2c():
    """h2c micro-rung: isolated hash-to-curve cost so scalar-chain work is
    measurable without a full firehose run. Reports h2c_points_per_s for the
    fused device map plus per-stage ms (host hashing, sswu fraction map,
    isogeny, cofactor clearing) at the gossip batch shape; parity against
    the Python oracle is asserted on the first message — the rung verifies
    while it measures."""
    _require_tpu()
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops.bls import curve, g2, h2c
    from lighthouse_tpu.ops.bls_oracle import hash_to_curve as oh
    from lighthouse_tpu.ops.bls_oracle.ciphersuite import DST

    n = BATCH
    iters = int(os.environ.get("BENCH_H2C_ITERS", "3"))
    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0x42C)
    msgs = [rng.bytes(32) for _ in range(n)]

    t0 = time.perf_counter()
    for _ in range(3):
        u0, u1 = h2c.hash_to_field_batch(msgs, DST)
    host_ms = (time.perf_counter() - t0) / 3 * 1e3

    map_fn = jax.jit(h2c.map_to_g2)
    t0 = time.perf_counter()
    pts = map_fn(u0, u1)
    jax.block_until_ready(pts)
    print(
        f"# h2c warmup (compile) {time.perf_counter() - t0:.0f}s on {platform}",
        flush=True,
    )
    assert g2.to_oracle(pts[0]) == oh.hash_to_curve_g2(msgs[0], DST), (
        "device h2c diverged from the oracle"
    )
    t0 = time.perf_counter()
    for _ in range(iters):
        pts = map_fn(u0, u1)
    jax.block_until_ready(pts)
    dt = time.perf_counter() - t0
    map_ms = dt / iters * 1e3

    u = jnp.stack([u0, u1], axis=0)
    sswu_fn = jax.jit(h2c.map_to_curve_sswu_fraction)
    stages = {"host_hash_to_field": host_ms}
    stages["sswu"] = _time_stage(sswu_fn, u)
    frac = sswu_fn(u)
    iso_fn = jax.jit(h2c.iso_map_fraction)
    stages["iso"] = _time_stage(iso_fn, *frac)
    q = iso_fn(*frac)
    qq = jax.jit(lambda q: curve.point_add(2, q[0], q[1]))(q)
    stages["cofactor"] = _time_stage(jax.jit(h2c.clear_cofactor), qq)
    stages["map_total"] = map_ms
    print(
        json.dumps(
            {
                "metric": "h2c_points_per_s",
                "value": round(n * iters / dt, 2),
                "unit": "points/s",
                "platform": platform,
                **_backend_stamp(),
                "shape": {"batch": n},
                "stages_ms_per_batch": {
                    k: round(v, 2) for k, v in stages.items()
                },
                "resilience": _resilience_summary(),
            }
        )
    )


def _inner_pairing():
    """Pairing micro-rung: the batched-verification endgame (Miller loops +
    final exponentiation) in isolation, so the chain-planned pairing work is
    measurable without a full verify run. Reports pairing_sets_per_s for the
    fused miller+final-exp pipeline at the gossip batch shape — n sets pair
    n pubkey/message points plus ONE shared signature point, exactly the
    verify kernel's (n+1)-pair layout — plus per-stage ms. Parity against
    the Python oracle is asserted on the WHOLE measured pipeline: the
    device product of all n+1 pairings (through the dispatched Miller
    stage AND the planned final exponentiation) must equal the oracle's
    multi-pairing of the same points — the rung verifies while it
    measures."""
    _require_tpu()
    import importlib

    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops.bls import fq, pairing, tower as tw
    from lighthouse_tpu.ops.bls_oracle import curves as oc, fields as of

    op = importlib.import_module("lighthouse_tpu.ops.bls_oracle.pairing")

    n = BATCH
    iters = int(os.environ.get("BENCH_PAIRING_ITERS", "3"))
    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0xBA17)

    ks1 = [1 + int.from_bytes(rng.bytes(32), "big") % (of.R - 1)
           for _ in range(n + 1)]
    ks2 = [1 + int.from_bytes(rng.bytes(32), "big") % (of.R - 1)
           for _ in range(n + 1)]
    g1_pts = [oc.g1_mul(oc.g1_generator(), k) for k in ks1]
    g2_pts = [oc.g2_mul(oc.g2_generator(), k) for k in ks2]
    px = jnp.stack([fq.from_int(p[0]) for p in g1_pts])
    py = jnp.stack([fq.from_int(p[1]) for p in g1_pts])
    qx = jnp.stack([tw.from_ints([q[0].c0, q[0].c1]) for q in g2_pts])
    qy = jnp.stack([tw.from_ints([q[1].c0, q[1].c1]) for q in g2_pts])

    # the verify-path pipeline: the backend-dispatched product Miller stage
    # (what multi_pairing_is_one runs) + one final exponentiation
    miller = jax.jit(pairing.miller_product)
    final = jax.jit(pairing.final_exponentiation)
    t0 = time.perf_counter()
    f = miller(px, py, qx, qy)
    out = final(f)
    jax.block_until_ready(out)
    print(
        f"# pairing warmup (compile) {time.perf_counter() - t0:.0f}s "
        f"on {platform}",
        flush=True,
    )
    # oracle parity of the WHOLE measured pipeline: the device product of
    # all n+1 pairings (one shared accumulator + one final exponentiation)
    # must equal the oracle's multi-pairing of the same points
    acc = op.miller_loop(g1_pts[0], g2_pts[0])
    for p, q in zip(g1_pts[1:], g2_pts[1:]):
        acc = acc * op.miller_loop(p, q)
    assert tw.fq12_to_oracle(out) == op.final_exponentiation(acc), (
        "device pairing product diverged from the oracle"
    )

    t0 = time.perf_counter()
    for _ in range(iters):
        out = final(miller(px, py, qx, qy))
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0

    stages = {"miller_loops": _time_stage(miller, px, py, qx, qy)}
    stages["final_exponentiation"] = _time_stage(final, f)
    print(
        json.dumps(
            {
                "metric": "pairing_sets_per_s",
                "value": round(n * iters / dt, 2),
                "unit": "sets/s",
                "platform": platform,
                **_backend_stamp(),
                "shape": {"batch": n, "pairs": n + 1},
                "stages_ms_per_batch": {
                    k: round(v, 2) for k, v in stages.items()
                },
                "resilience": _resilience_summary(),
            }
        )
    )


def _refill_epoch_deposits(state, rng, count: int = 8) -> None:
    """Top the electra pending-deposit queue back up to ``count`` top-up
    entries (known pubkeys, so both paths take the scatter-add lane). Keeps
    every steady-state bench epoch doing real deposit-sweep work instead of
    draining the queue on the warmup epoch."""
    from lighthouse_tpu.types.containers import for_preset

    ns = for_preset("mainnet")
    n = len(state.validators)
    pending = list(state.pending_deposits)
    while len(pending) < count:
        i = int(rng.integers(0, n))
        pending.append(
            ns.PendingDeposit(
                pubkey=bytes(state.validators[i].pubkey),
                withdrawal_credentials=bytes(
                    state.validators[i].withdrawal_credentials
                ),
                amount=10**9,
                signature=b"\x00" * 96,
                slot=1,
            )
        )
    state.pending_deposits = pending


def _build_epoch_state(spec, n: int, rng, fork: str = "electra"):
    """Synthetic mainnet-preset state with ``n`` validators for the
    epoch-replay rung (BASELINE config #4). Electra (the production fork)
    by default; ``fork="altair"`` keeps the pre-electra A/B shape.

    Electra states carry UNIQUE per-validator pubkeys: the device engine
    resolves pending-deposit pubkeys through the mirror's dict map (last
    occurrence wins) while the numpy twin linear-scans (first occurrence
    wins), so duplicate dummy keys would silently diverge the in-rung
    parity gate. Altair epoch processing never reads pubkeys (the bench
    epoch avoids the sync-committee rotation boundary, like any
    non-boundary mainnet epoch), so the shared dummy key stays."""
    from lighthouse_tpu.types.containers import Checkpoint, Validator, for_preset
    from lighthouse_tpu.types.spec import FAR_FUTURE_EPOCH

    ns = for_preset(spec.preset.name)
    p = spec.preset
    electra = fork == "electra"
    state = ns.BeaconStateElectra() if electra else ns.BeaconStateAltair()
    # epoch 101: (102 % EPOCHS_PER_ETH1_VOTING_PERIOD=64) != 0 and
    # (102 % EPOCHS_PER_SYNC_COMMITTEE_PERIOD=256) != 0 — no host-side
    # eth1/sync/historical boundary work pollutes the validator-axis number
    cur_epoch = 101
    state.slot = (cur_epoch + 1) * p.SLOTS_PER_EPOCH - 1
    pk = b"\x00" * 48
    far = FAR_FUTURE_EPOCH
    eff = np.full(n, 32 * 10**9, dtype=np.uint64)
    # a realistic trickle of ejectable validators (a storm would make the
    # numpy baseline quadratic in initiate_validator_exit's registry scans
    # — real epochs eject at most a handful)
    eff[rng.choice(n, size=min(32, n // 64), replace=False)] = 15 * 10**9
    # electra credential mix: ~1/16 compounding (0x02) rows exercise the
    # per-validator max-effective plane; the rest split 0x01/0x00
    creds = np.zeros(n, dtype=np.uint8)
    if electra:
        creds = rng.integers(0, 16, n).astype(np.uint8)
    validators = []
    for i in range(n):
        if electra:
            pk = i.to_bytes(48, "little")
            wc = (
                b"\x02" if creds[i] == 0 else b"\x01" if creds[i] < 8 else b"\x00"
            ) + b"\x00" * 31
        else:
            wc = b"\x00" * 32
        validators.append(
            Validator(
                pubkey=pk,
                withdrawal_credentials=wc,
                effective_balance=int(eff[i]),
                slashed=False,
                activation_eligibility_epoch=0,
                activation_epoch=0,
                exit_epoch=far,
                withdrawable_epoch=far,
            )
        )
    state.validators = validators
    state.balances = rng.integers(
        31 * 10**9, 33 * 10**9, n, dtype=np.int64
    ).astype(np.uint64)
    state.previous_epoch_participation = rng.integers(0, 8, n).astype(np.uint8)
    state.current_epoch_participation = rng.integers(0, 8, n).astype(np.uint8)
    state.inactivity_scores = np.zeros(n, dtype=np.uint64)
    for i in range(min(p.SLOTS_PER_HISTORICAL_ROOT, state.slot)):
        state.block_roots[i] = rng.bytes(32)
    state.finalized_checkpoint = Checkpoint(epoch=cur_epoch - 2, root=rng.bytes(32))
    state.previous_justified_checkpoint = Checkpoint(
        epoch=cur_epoch - 2, root=rng.bytes(32)
    )
    state.current_justified_checkpoint = Checkpoint(
        epoch=cur_epoch - 1, root=rng.bytes(32)
    )
    state.justification_bits = np.array([1, 1, 1, 1], dtype=bool)
    if electra:
        # EIP-6110 bridge done: every pending deposit clears the
        # transition gate and the sweep's churn budget does the gating
        state.eth1_deposit_index = n
        state.deposit_requests_start_index = 0
        state.deposit_balance_to_consume = 0
        state.earliest_exit_epoch = 0
        state.exit_balance_to_consume = 0
        _refill_epoch_deposits(state, rng)
        # constant-shape consolidation queue: every entry's source is
        # un-exited (withdrawable FAR), so the ordered scan stops at entry
        # 0 each epoch — steady per-epoch scan work, no queue drain
        state.pending_consolidations = [
            ns.PendingConsolidation(
                source_index=int(rng.integers(0, n)),
                target_index=int(rng.integers(0, n)),
            )
            for _ in range(4)
        ]
    return state


def _assert_epoch_parity(dev, twin, fork: str) -> None:
    """In-rung device-vs-numpy parity gate (ISSUE 19): a record whose sweep
    diverged from per_epoch.py is not a performance number, it is a bug.
    Compares the epoch-mutable planes (balances / inactivity / registry
    epochs) and the electra churn carries + queue shapes; participation and
    tree roots are excluded (the bench refreshes participation with fresh
    randomness and full tree hashing at rung scale would dominate the
    window)."""
    assert np.array_equal(
        np.asarray(dev.balances, dtype=np.uint64),
        np.asarray(twin.balances, dtype=np.uint64),
    ), "epoch parity: balances diverged"
    assert np.array_equal(
        np.asarray(dev.inactivity_scores, dtype=np.uint64),
        np.asarray(twin.inactivity_scores, dtype=np.uint64),
    ), "epoch parity: inactivity scores diverged"
    assert len(dev.validators) == len(twin.validators), (
        "epoch parity: registry length diverged"
    )
    for attr in (
        "effective_balance",
        "exit_epoch",
        "withdrawable_epoch",
        "activation_epoch",
        "activation_eligibility_epoch",
    ):
        a = np.fromiter(
            (int(getattr(v, attr)) for v in dev.validators), dtype=np.uint64
        )
        b = np.fromiter(
            (int(getattr(v, attr)) for v in twin.validators), dtype=np.uint64
        )
        assert np.array_equal(a, b), f"epoch parity: validator {attr} diverged"
    assert int(dev.finalized_checkpoint.epoch) == int(
        twin.finalized_checkpoint.epoch
    ), "epoch parity: finality diverged"
    if fork == "electra":
        for attr in (
            "deposit_balance_to_consume",
            "exit_balance_to_consume",
            "earliest_exit_epoch",
        ):
            assert int(getattr(dev, attr)) == int(getattr(twin, attr)), (
                f"epoch parity: {attr} diverged"
            )
        assert len(dev.pending_deposits) == len(twin.pending_deposits), (
            "epoch parity: pending_deposits queue diverged"
        )
        assert len(dev.pending_consolidations) == len(
            twin.pending_consolidations
        ), "epoch parity: pending_consolidations queue diverged"


def _inner_epoch():
    """Epoch-engine rung (BASELINE.json config #4, the 1M-validator epoch
    replay): advance a synthetic mainnet-shape state across epoch
    boundaries through the DEVICE epoch engine (lighthouse_tpu/epoch_engine)
    and report validators/sec, ms/epoch and the host<->device delta-update
    traffic. Electra (the production fork: pending-deposit scatter +
    consolidation scan + per-validator max-effective plane) by default;
    BENCH_EPOCH_FORK=altair keeps the pre-electra A/B shape. The numpy
    per_epoch.py path at the same shape is the baseline AND the in-rung
    parity gate — the twin's epoch must agree with the device sweep
    field-for-field before the timed loop counts (skipped at the
    million-validator rung, where the object gather alone takes minutes —
    the engine existing is the point)."""
    _require_tpu()
    import jax

    from lighthouse_tpu import epoch_engine
    from lighthouse_tpu.state_transition.per_epoch import process_epoch
    from lighthouse_tpu.types.spec import mainnet_spec

    n = N_VALIDATORS
    iters = int(os.environ.get("BENCH_EPOCH_ITERS", "3"))
    platform = jax.devices()[0].platform
    # sharded-mesh variant (BENCH_MODE=epoch_sharded): the registry mirror
    # lives sharded over a `validators` mesh axis and the fused sweep runs
    # SPMD under GSPMD — same record shape, stamped with the device count
    sharding = None
    n_dev = 1
    if os.environ.get("BENCH_MODE", "") == "epoch_sharded":
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        n_dev = _mesh_devices_for_inner()
        mesh = Mesh(
            np.array(jax.devices()[:n_dev]), axis_names=("validators",)
        )
        sharding = NamedSharding(mesh, PartitionSpec("validators"))
    fork = os.environ.get("BENCH_EPOCH_FORK", "electra")
    if fork == "electra":
        spec = mainnet_spec(
            altair_fork_epoch=0,
            bellatrix_fork_epoch=0,
            capella_fork_epoch=0,
            deneb_fork_epoch=0,
            electra_fork_epoch=0,
        )
    else:
        spec = mainnet_spec(altair_fork_epoch=0)
    rng = np.random.default_rng(0xE9_0C)
    t0 = time.perf_counter()
    state = _build_epoch_state(spec, n, rng, fork=fork)
    print(f"# built {n}-validator {fork} state in "
          f"{time.perf_counter() - t0:.0f}s", flush=True)

    epoch_engine.set_backend("device")
    if sharding is not None:
        epoch_engine.prepare_state(state, sharding=sharding)
    per_epoch_slots = spec.preset.SLOTS_PER_EPOCH

    def finish_epoch(s):
        s.slot += per_epoch_slots
        # keep participation live so every epoch does real reward work
        s.current_epoch_participation = rng.integers(0, 8, len(s.validators)).astype(
            np.uint8
        )
        if fork == "electra":
            # keep the deposit sweep fed: the warmup epoch consumed the
            # initial queue (8 x 1 ETH top-ups fit one epoch's churn)
            _refill_epoch_deposits(s, rng)

    def one_epoch(s):
        assert epoch_engine.maybe_process_epoch_on_device(
            spec, s, sharding=sharding
        ), (
            "epoch engine refused the bench state"
        )
        finish_epoch(s)

    t0 = time.perf_counter()
    # warmup (bind mirror + compile) — held open before the host
    # bookkeeping so the numpy twin below compares against exactly one
    # device epoch
    assert epoch_engine.maybe_process_epoch_on_device(
        spec, state, sharding=sharding
    ), "epoch engine refused the bench state"
    print(
        f"# warmup (bind + compile) {time.perf_counter() - t0:.0f}s on "
        f"{platform}",
        flush=True,
    )

    # numpy baseline at the same shape (one epoch; prohibitive at 1M) —
    # doubles as the in-rung parity gate against the device warmup epoch
    numpy_v_per_s = None
    if n <= 262144:
        epoch_engine.set_backend("numpy")
        twin = _build_epoch_state(
            spec, n, np.random.default_rng(0xE9_0C), fork=fork
        )
        t0 = time.perf_counter()
        process_epoch(spec, twin)
        numpy_dt = time.perf_counter() - t0
        numpy_v_per_s = n / numpy_dt if numpy_dt else None
        _assert_epoch_parity(state, twin, fork)
        print("# in-rung parity: device sweep == numpy twin", flush=True)
        epoch_engine.set_backend("device")
    finish_epoch(state)

    t0 = time.perf_counter()
    for _ in range(iters):
        one_epoch(state)
    dt = time.perf_counter() - t0
    stats = epoch_engine.engine_stats(state) or {}

    ms_per_epoch = dt / iters * 1e3
    value = n * iters / dt
    print(
        json.dumps(
            {
                "metric": "epoch_validators_per_s",
                "value": round(value, 2),
                "unit": "validators/s",
                "vs_baseline": (
                    round(value / numpy_v_per_s, 3) if numpy_v_per_s else None
                ),
                "platform": platform,
                **_backend_stamp(),
                "n_devices": n_dev,
                "sharded": sharding is not None,
                "shape": {
                    "validators": n,
                    "preset": "mainnet",
                    "fork": fork,
                    "epochs_timed": iters,
                },
                "ms_per_epoch": round(ms_per_epoch, 2),
                "numpy_validators_per_s": (
                    round(numpy_v_per_s, 2) if numpy_v_per_s else None
                ),
                "host_to_device_bytes_per_epoch": (
                    stats.get("last_host_to_device_bytes")
                ),
                "mirror": stats,
                "resilience": _resilience_summary(),
            }
        )
    )


def _inner_slasher():
    """Slasher-engine rung: whole-network slashable-behavior surveillance
    as one batched matrix sweep (lighthouse_tpu/slasher/engine.py). Drives
    the device-resident span store with mainnet-cadence honest traffic —
    every tick, ``pairs`` validators vote (cur-1, cur); the window rolls
    forward every ``ticks_per_epoch`` ticks INSIDE the jitted sweep — and
    reports ``slashable_checks_per_s`` (pair-checks swept per second). The
    numpy twin at the same shape is the baseline (skipped at 1M, where the
    whole-plane host scatter+scan alone is minutes). A final untimed tick
    carries seeded injected double/surround votes: the record proves 100%
    candidate detection and zero false positives over the honest stream,
    and the resilience stamp + span-store mode prove a numpy-demoted run
    cannot masquerade as a device record."""
    _require_tpu()
    import jax

    from lighthouse_tpu import slasher as slasher_pkg
    from lighthouse_tpu.slasher.engine import SpanStore, validator_sharding

    n = N_VALIDATORS
    history = int(os.environ.get("BENCH_SLASHER_HISTORY", "64"))
    pairs = int(os.environ.get("BENCH_SLASHER_PAIRS", str(min(n, 16384))))
    iters = int(os.environ.get("BENCH_SLASHER_TICKS", "16"))
    ticks_per_epoch = 4
    platform = jax.devices()[0].platform
    sharding = validator_sharding()
    n_dev = 1
    if sharding is not None:
        n_dev = int(np.prod(tuple(sharding.mesh.shape.values())))
    rng = np.random.default_rng(0x51A5)

    def honest_tick(t):
        cur = 100 + t // ticks_per_epoch
        vidx = rng.choice(n, size=pairs, replace=False).astype(np.int64)
        src = np.full(pairs, cur - 1, dtype=np.int64)
        tgt = np.full(pairs, cur, dtype=np.int64)
        vh = np.ones(pairs, dtype=np.uint32)
        return vidx, src, tgt, vh, cur

    def run(store, record_flags):
        false_pos = 0
        t0 = time.perf_counter()
        for t in range(iters):
            vidx, src, tgt, vh, cur = honest_tick(t)
            res = store.apply(vidx, src, tgt, vh, cur)
            if record_flags:
                false_pos += int(
                    res["min_flag"].sum() + res["max_flag"].sum()
                    + res["dbl_flag"].sum()
                )
        return time.perf_counter() - t0, false_pos

    # the rung measures the DEVICE engine (the numpy twin is the baseline
    # below)
    slasher_pkg.set_backend("device")
    store = SpanStore(history, sharding=sharding)
    store.ensure_capacity(n)
    t0 = time.perf_counter()
    run_warm = honest_tick(0)
    store.apply(*run_warm[:4], run_warm[4])  # bind planes + compile
    print(
        f"# warmup (bind + compile) {time.perf_counter() - t0:.0f}s on "
        f"{platform} ({n}x{history} planes, {pairs} pairs/tick)",
        flush=True,
    )
    dt, false_pos = run(store, record_flags=True)
    value = pairs * iters / dt if dt else 0.0

    # seeded slashable votes in one untimed tick: 8 validators vote
    # (cur-2, cur-1); the first 4 then also vote (cur-3, cur), which
    # SURROUNDS their (cur-2, cur-1) vote — 4 expected surround flags
    cur = 100 + iters // ticks_per_epoch + 1
    inj_v = rng.choice(n, size=8, replace=False).astype(np.int64)
    vidx = np.concatenate([inj_v, inj_v[:4]])
    src = np.concatenate(
        [np.full(8, cur - 2, np.int64), np.full(4, cur - 3, np.int64)]
    )
    tgt = np.concatenate(
        [np.full(8, cur - 1, np.int64), np.full(4, cur, np.int64)]
    )
    vh = np.concatenate([np.ones(8, np.uint32), np.full(4, 2, np.uint32)])
    res = store.apply(vidx, src, tgt, vh, cur)
    flagged_surround = int(res["min_flag"][8:].sum())
    # doubles: 4 validators also re-vote target cur-1 with a different tag
    vidx2, src2 = inj_v[4:], np.full(4, cur - 2, np.int64)
    tgt2, vh2 = np.full(4, cur - 1, np.int64), np.full(4, 3, np.uint32)
    res2 = store.apply(vidx2, src2, tgt2, vh2, cur)
    flagged_double = int(res2["dbl_flag"].sum())

    # numpy twin baseline at the same shape (prohibitive at 1M)
    numpy_c_per_s = None
    if n <= 262144:
        rng = np.random.default_rng(0x51A5)
        twin = SpanStore(history, use_device=False)
        twin.ensure_capacity(n)
        warm = honest_tick(0)
        twin.apply(*warm[:4], warm[4])
        twin_dt, _ = run(twin, record_flags=False)
        numpy_c_per_s = pairs * iters / twin_dt if twin_dt else None

    stats = store.stats()
    print(
        json.dumps(
            {
                "metric": "slashable_checks_per_s",
                "value": round(value, 2),
                "unit": "checks/s",
                "vs_baseline": (
                    round(value / numpy_c_per_s, 3) if numpy_c_per_s else None
                ),
                "platform": platform,
                **_backend_stamp(),
                "n_devices": n_dev,
                "sharded": sharding is not None,
                "shape": {
                    "validators": n,
                    "history_length": history,
                    "pairs_per_tick": pairs,
                    "ticks_timed": iters,
                },
                "ms_per_tick": round(dt / iters * 1e3, 3) if iters else None,
                "numpy_checks_per_s": (
                    round(numpy_c_per_s, 2) if numpy_c_per_s else None
                ),
                "detection": {
                    "injected_surround": 4,
                    "flagged_surround": flagged_surround,
                    "injected_double": 4,
                    "flagged_double": flagged_double,
                    "false_positives": false_pos,
                },
                # integrity stamp: a numpy-demoted run carries mode=host /
                # demotions>0 here and degraded=true in the resilience block
                "slasher_backend": stats["backend"],
                "slasher_mode": stats["mode"],
                "device_integrity": (
                    stats["backend"] == "device" and stats["demotions"] == 0
                ),
                "span": stats,
                "resilience": _resilience_summary(),
            }
        )
    )


def _inner_kzg_cells():
    """PeerDAS cell-proof rung (ISSUE 16): device-batched KZG cell
    verification — every cell of a mainnet-count blob block folded into ONE
    combined pairing check (2 pairs, one Miller product + one final exp).
    Reports ``kzg_cells_verified_per_s`` for the compiled engine batch at
    the test-scale domain, with the per-cell host loop (the exact
    ``CellContext`` oracle the dispatch seam falls back to) timed at the
    same workload as the twin baseline. The engine's ``compile_probe``
    record is embedded so the one-pairing-per-batch invariant is pinned in
    the measurement itself; verdict honesty is asserted in-rung (honest
    batch True, tampered proof False) before any timing lands."""
    _require_tpu()
    import jax

    from lighthouse_tpu import bls
    from lighthouse_tpu.kzg import engine
    from lighthouse_tpu.kzg.cells import CellContext
    from lighthouse_tpu.kzg.fr import bls_field_to_bytes
    from lighthouse_tpu.kzg.kzg import Kzg
    from lighthouse_tpu.kzg.setup import insecure_setup

    bls.set_backend("native")
    n = int(os.environ.get("BENCH_KZG_N", "64"))
    cells_per = int(os.environ.get("BENCH_KZG_CELLS", "16"))
    blobs_n = BATCH or 6  # mainnet Deneb max blobs per block
    iters = int(os.environ.get("BENCH_KZG_ITERS", "5"))
    k = 2 * n // cells_per
    platform = jax.devices()[0].platform
    ctx = CellContext(
        Kzg(insecure_setup(n, n_g2=k + 1)), cells_per_ext_blob=cells_per
    )

    rng = np.random.default_rng(0xDA5)
    commitments, cell_idx, cells, proofs = [], [], [], []
    t0 = time.perf_counter()
    for _ in range(blobs_n):
        blob = b"".join(
            bls_field_to_bytes(int(rng.integers(1, 2**62))) for _ in range(n)
        )
        comm = ctx.kzg.blob_to_kzg_commitment(blob)
        cs, ps = ctx.compute_cells_and_kzg_proofs(blob)
        commitments += [comm] * cells_per
        cell_idx += list(range(cells_per))
        cells += cs
        proofs += ps
    batch = len(cells)
    print(
        f"# fixture: {blobs_n} blobs -> {batch} cells "
        f"({time.perf_counter() - t0:.0f}s)",
        flush=True,
    )

    eng = engine.get_engine(ctx)
    probe = eng.compile_probe(batch)
    t0 = time.perf_counter()
    ok = eng.verify_batch(commitments, cell_idx, cells, proofs)
    print(
        f"# warmup (compile) {time.perf_counter() - t0:.0f}s on {platform}",
        flush=True,
    )
    assert ok, "honest cell batch rejected — engine broken, no record"
    tampered = list(proofs)
    tampered[1], tampered[cells_per] = tampered[cells_per], tampered[1]
    assert not eng.verify_batch(commitments, cell_idx, cells, tampered), (
        "tampered cell batch accepted — engine broken, no record"
    )

    t0 = time.perf_counter()
    for _ in range(iters):
        ok = eng.verify_batch(commitments, cell_idx, cells, proofs)
    dt = time.perf_counter() - t0
    value = batch * iters / dt if dt else 0.0

    # host twin: the per-cell oracle loop at the SAME workload (one pairing
    # check per cell — the cost the batched engine amortizes away)
    t0 = time.perf_counter()
    host_ok = all(
        ctx.verify_cell_kzg_proof(c, i, ce, p)
        for c, i, ce, p in zip(commitments, cell_idx, cells, proofs)
    )
    host_dt = time.perf_counter() - t0
    assert host_ok, "host oracle rejected the honest batch"
    host_value = batch / host_dt if host_dt else 0.0

    print(
        json.dumps(
            {
                "metric": "kzg_cells_verified_per_s",
                "value": round(value, 2),
                "unit": "cells/s",
                "vs_baseline": (
                    round(value / host_value, 3) if host_value else None
                ),
                "platform": platform,
                **_backend_stamp(),
                "kzg_backend": engine.get_kzg_backend(),
                "shape": {
                    "blobs": blobs_n,
                    "cells_per_blob": cells_per,
                    "batch_cells": batch,
                    "field_elements_per_blob": n,
                },
                "ms_per_batch": round(dt / iters * 1e3, 3) if iters else None,
                "host_loop_cells_per_s": round(host_value, 2),
                # the tentpole invariant, pinned inside the record: the whole
                # batch settles in ONE combined pairing check of 2 pairs
                "compile_probe": probe,
                "resilience": _resilience_summary(),
            }
        )
    )


def _inner_light_clients():
    """Light-client mass-service rung (ISSUE 17): a batch of heterogeneous
    sync-committee update sessions at the MAINNET committee size (512)
    folded into ONE combined pairing check on the device engine. Reports
    ``light_clients_served_per_s`` with the per-session host loop — the
    exact ``verify_light_client_update`` oracle, which re-decompresses
    every participant pubkey per session — timed at the same workload as
    the twin baseline. Session-for-session parity against that oracle is
    asserted in-rung on a batch with tampered members, and the engine's
    ``compile_probe`` record (one Miller product + one final exponentiation
    per batch, proven at trace time) is embedded in the measurement."""
    _require_tpu()
    import jax

    from lighthouse_tpu import bls
    from lighthouse_tpu.light_client import engine
    from lighthouse_tpu.light_client.verify import verify_light_client_update
    from lighthouse_tpu.testing import StateHarness
    from lighthouse_tpu.testing.lc_workload import (
        fabricate_lc_sessions,
        tamper_session,
    )
    from lighthouse_tpu.types.spec import mainnet_spec

    bls.set_backend("native")
    n_sessions = BATCH or int(os.environ.get("BENCH_LC_SESSIONS", "16"))
    validators = int(os.environ.get("BENCH_LC_VALIDATORS", "64"))
    iters = int(os.environ.get("BENCH_LC_ITERS", "5"))
    platform = jax.devices()[0].platform

    spec = mainnet_spec(altair_fork_epoch=0)
    t0 = time.perf_counter()
    harness = StateHarness(spec, validators)
    sessions, gvr = fabricate_lc_sessions(harness, n_sessions, seed=0x11C)
    committee_size = int(spec.preset.SYNC_COMMITTEE_SIZE)
    print(
        f"# fixture: {n_sessions} sessions x {committee_size}-key committee "
        f"({time.perf_counter() - t0:.0f}s)",
        flush=True,
    )

    engine.set_lc_backend("device")
    eng = engine.get_engine(spec)
    probe = eng.compile_probe(n_sessions)
    t0 = time.perf_counter()
    ok = eng.verify_batch(sessions, gvr)
    print(
        f"# warmup (compile) {time.perf_counter() - t0:.0f}s on {platform}",
        flush=True,
    )
    assert ok, "honest session batch rejected — engine broken, no record"
    tampered = list(sessions)
    tampered[1] = tamper_session(sessions[1], "signature")
    assert not eng.verify_batch(tampered, gvr), (
        "tampered session batch accepted — engine broken, no record"
    )
    # session-for-session parity vs the host oracle on the mixed batch (the
    # dispatch layer bisects the device verdicts down to per-session)
    mixed = list(sessions)
    mixed[1] = tamper_session(sessions[1], "signature")
    mixed[3] = tamper_session(sessions[3], "header")
    dev_verdicts = engine.verify_update_batch(spec, mixed, gvr)
    host_verdicts = [
        verify_light_client_update(spec, u, c, gvr) for u, c in mixed
    ]
    assert dev_verdicts == host_verdicts, (
        f"device/host verdict mismatch: {dev_verdicts} vs {host_verdicts}"
    )

    t0 = time.perf_counter()
    for _ in range(iters):
        ok = eng.verify_batch(sessions, gvr)
    dt = time.perf_counter() - t0
    value = n_sessions * iters / dt if dt else 0.0

    # host twin: the per-session oracle loop at the SAME workload (committee
    # pubkey decompression repaid on every session — the cost the device
    # cache amortizes away)
    t0 = time.perf_counter()
    host_ok = all(
        verify_light_client_update(spec, u, c, gvr) for u, c in sessions
    )
    host_dt = time.perf_counter() - t0
    assert host_ok, "host oracle rejected the honest batch"
    host_value = n_sessions / host_dt if host_dt else 0.0

    print(
        json.dumps(
            {
                "metric": "light_clients_served_per_s",
                "value": round(value, 2),
                "unit": "sessions/s",
                "vs_baseline": (
                    round(value / host_value, 3) if host_value else None
                ),
                "platform": platform,
                **_backend_stamp(),
                "lc_backend": engine.get_lc_backend(),
                "shape": {
                    "sessions": n_sessions,
                    "committee_size": committee_size,
                    "validators": validators,
                },
                "ms_per_batch": round(dt / iters * 1e3, 3) if iters else None,
                "host_loop_sessions_per_s": round(host_value, 2),
                # the tentpole invariant, pinned inside the record: the whole
                # batch settles in ONE combined pairing check of B+1 pairs
                "compile_probe": probe,
                "resilience": _resilience_summary(),
            }
        )
    )


# Shape ladder: (sets, keys, validators, batch, timeout_s). The first entry
# is the mainnet shape (BASELINE.json config #4); smaller rungs bound a
# pathological device compile so SOME record always lands. The JSON's
# `shape` field says which rung ran.
_LADDER = [
    (256, 448, 16384, 64, 2700.0),
    (64, 64, 4096, 16, 1200.0),
    (16, 16, 1024, 8, 900.0),
]

# Per-mode single rungs: (sets, keys, validators, batch, timeout_s). Only
# the fields a mode reads matter; the rest pass as 0 through run_inner.
_MODE_LADDERS = {
    # BASELINE.json config #5: keys=1 is the gossip unaggregated shape; the
    # stream rate/duration come from BENCH_FIREHOSE_* env (default 50k att/s)
    "firehose": [(256, 1, 4096, 16, 1800.0)],
    # ISSUE 18: the firehose shape with an honest paced stream plus a 10x
    # malformed low-priority flood (BENCH_OVERLOAD_* env)
    "overload": [(256, 1, 4096, 16, 1800.0)],
    # the sharded serving tier: n_devices fixed sub-batches of `batch` per
    # tick, per-shard verdicts, with the single-device A/B in the record
    "firehose_sharded": [(256, 1, 4096, 16, 2700.0)],
    # BASELINE.json config #4 (the 1M-validator epoch replay), largest first
    "epoch": [(0, 0, v, 0, t) for v, t in
              ((1048576, 2700.0), (262144, 1500.0), (32768, 900.0))],
    "epoch_sharded": [(0, 0, v, 0, t) for v, t in
                      ((1048576, 4050.0), (262144, 1800.0), (32768, 1350.0))],
    # ISSUE 11: history / pairs / ticks come from BENCH_SLASHER_* env
    "slasher": [(0, 0, v, 0, t) for v, t in
                ((1048576, 2700.0), (262144, 1500.0), (32768, 900.0))],
    # the scalar-chain and pairing stages in isolation: only `batch` matters
    "h2c": [(0, 0, 0, BATCH, 900.0)],
    "pairing": [(0, 0, 0, BATCH, 900.0)],
    # ISSUE 16: batch = blobs per block (mainnet Deneb max 6)
    "kzg_cells": [(0, 0, 0, 6, 2700.0)],
    # ISSUE 17: batch = sessions per dispatch at the mainnet committee size
    "light_clients": [(0, 0, 0, 16, 2700.0)],
}

_METRICS = {
    "firehose": ("firehose_attestations_verified_per_s", "att/s"),
    "overload": ("overload_honest_atts_per_s", "att/s"),
    "firehose_sharded": ("firehose_attestations_verified_per_s", "att/s"),
    "epoch": ("epoch_validators_per_s", "validators/s"),
    "epoch_sharded": ("epoch_validators_per_s", "validators/s"),
    "h2c": ("h2c_points_per_s", "points/s"),
    "pairing": ("pairing_sets_per_s", "sets/s"),
    "slasher": ("slashable_checks_per_s", "checks/s"),
    "kzg_cells": ("kzg_cells_verified_per_s", "cells/s"),
    "light_clients": ("light_clients_served_per_s", "sessions/s"),
}

_INNER = {
    "firehose": "_inner_firehose",
    "overload": "_inner_overload",
    "firehose_sharded": "_inner_firehose_sharded",
    "epoch": "_inner_epoch",
    "epoch_sharded": "_inner_epoch",
    "slasher": "_inner_slasher",
    "h2c": "_inner_h2c",
    "pairing": "_inner_pairing",
    "kzg_cells": "_inner_kzg_cells",
    "light_clients": "_inner_light_clients",
}


def main() -> int:
    mode = "sets"
    for m in _METRICS:
        if "--" + m.replace("_", "-") in sys.argv:
            mode = m
            break
    if "--inner" in sys.argv:
        inner_mode = os.environ.get("BENCH_MODE", mode)
        globals()[_INNER.get(inner_mode, "_inner")]()
        return 0
    if mode == "sets":
        ladder = _LADDER
        if "BENCH_SETS" in os.environ:
            ladder = [(N_SETS, KEYS_PER_SET, N_VALIDATORS, BATCH,
                       float(os.environ.get("BENCH_TIMEOUT", "2700")))]
    else:
        ladder = _MODE_LADDERS[mode]
        if "BENCH_VALIDATORS" in os.environ and mode in (
            "epoch", "epoch_sharded", "slasher"
        ):
            ladder = [(0, 0, N_VALIDATORS, 0,
                       float(os.environ.get("BENCH_TIMEOUT", "1350")))]
    last_err = ""
    for sets, keys, validators, batch, timeout in ladder:
        rec, note = run_inner(sets, keys, validators, batch, timeout, mode=mode)
        if rec is not None:
            print(json.dumps(rec))
            return 0
        last_err = note
        print(f"# {last_err}; trying next rung", file=sys.stderr)
    # every rung failed (no TPU among them): a failure record, and a failing
    # exit — the parent stays off JAX, so it names no device
    metric, unit = _METRICS.get(
        mode, ("bls_attestation_sets_verified_per_s", "sets/s")
    )
    print(json.dumps({
        "metric": metric,
        "value": None,
        "unit": unit,
        "error": last_err or "no shape rung completed",
    }))
    return 1


if __name__ == "__main__":
    sys.exit(main())
