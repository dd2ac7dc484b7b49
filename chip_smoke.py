"""Chip smoke check: batched BLS12-381 verification on one TPU at mainnet size.

Drives the main path once, through the entry points the beacon chain and
library users call, against a 1,048,576-validator registry (BASELINE.json's
"1M validators") held on the device:

1. Registry: keys from ``--seed`` on the native C++ backend (worker processes
   that never import JAX, running while 2 and 3 do), loaded with
   ``device_pubkeys_from_raw``.
2. Kernel parity: the fused Pallas field multiply and an fq12 multiply
   against host integer arithmetic on a small input.
3. Compile: the h2c / prep / pair stages at n_pad=64, k_pad=512, compiled by
   hand, all at once, for their seconds and ``memory_analysis()``.
4. Traffic: 4 batches of 64 aggregate sets x 512 attesters through the
   chain's ``_batch_verify_items_inner`` (the ``bls_supervisor()`` ladder),
   plus a batch with two signatures swapped. Every verdict must match the
   native backend; the supervisor must show no fault, retry, demotion or
   fallback.
5. Library seam: ``bls.verify_signature_sets`` on 8 wire-format sets and on
   a tampered copy.

``--four-chips`` runs only the sharded serving tier (the chain's mesh
planner with LIGHTHOUSE_MESH_DEVICES=4) on the same registry and batches,
compared with single-device ``verify_indexed_sets_device`` on device 0; it
compiles the sharded stages beside the single-device ones.

The last line of stdout is ``{"ok": true, "device": {...}}``. Any failure
raises and exits non-zero before it; so does a run where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import faulthandler
import importlib.util
import json
import multiprocessing
import os
import signal
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

N_VALIDATORS = 1 << 20
N_BATCHES = 4
SETS = 64
KEYS = 512  # 2^20 validators / 32 slots / 64 committees
LIB_SETS = 8

_ROOT = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def check(ok, msg: str) -> None:
    """A failed check ends the run (an assert would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def _native_module():
    """lighthouse_tpu/native/build.py loaded by path: importing the package
    would import JAX (its __init__ enables x64), and the key workers must
    stay off JAX."""
    path = os.path.join(_ROOT, "lighthouse_tpu", "native", "build.py")
    spec = importlib.util.spec_from_file_location("_smoke_native", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pubkeys(sk_rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Worker: [m, 32] secret keys -> ([m, 48] compressed, [m, 96] raw)."""
    nb = _native_module().NativeBls()
    comp = np.zeros((len(sk_rows), 48), dtype=np.uint8)
    raw = np.zeros((len(sk_rows), 96), dtype=np.uint8)
    for i, sk in enumerate(sk_rows):
        c = nb.sk_to_pk(sk.tobytes())
        comp[i] = np.frombuffer(c, dtype=np.uint8)
        raw[i] = np.frombuffer(nb.pk_decompress(c), dtype=np.uint8)
    return comp, raw


def build_registry(n: int, seed: int):
    """Secret keys [n, 32] (31 random bytes: below the group order r) and
    their compressed / raw pubkeys, made by spawned workers while the caller
    goes on. Returns a function that waits for them: (sks, comp, raw)."""
    workers = os.cpu_count() or 1
    rng = np.random.default_rng(seed)
    sks = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    sks[:, 0] = 0
    sks[:, 31] |= 1  # never the zero key
    _native_module().load_bls()  # build the library once, before the pool
    pool = multiprocessing.get_context("spawn").Pool(workers)
    parts = pool.map_async(_pubkeys, np.array_split(sks, workers * 4))

    def wait():
        with pool:
            got = parts.get()
        return (sks, np.concatenate([p[0] for p in got]),
                np.concatenate([p[1] for p in got]))

    return wait


def build_batches(sks, n_batches: int, sets: int, keys: int, seed: int):
    """``n_batches`` lists of (indices, message, signature) triples, one
    aggregate signature per set: the signature of the summed secret key."""
    from lighthouse_tpu.native.build import NativeBls
    from lighthouse_tpu.ops.bls_oracle.fields import R

    nb = NativeBls()
    rng = np.random.default_rng(seed + 1)
    batches = []
    for _ in range(n_batches):
        items = []
        for _ in range(sets):
            members = np.sort(rng.choice(len(sks), size=keys, replace=False))
            msg = rng.bytes(32)
            agg = sum(int.from_bytes(sks[i].tobytes(), "big") for i in members) % R
            sig = nb.sign(agg.to_bytes(32, "big"), msg)
            items.append(([int(i) for i in members], msg, sig))
        batches.append(items)
    return batches


def swapped(items):
    """The batch with the signatures of sets 0 and 1 exchanged."""
    bad = list(items)
    (i0, m0, s0), (i1, m1, s1) = bad[0], bad[1]
    bad[0], bad[1] = (i0, m0, s1), (i1, m1, s0)
    return bad


def native_verdict(raw, items) -> bool:
    from lighthouse_tpu.native.build import NativeBls

    return NativeBls().verify_signature_sets_raw(
        [[raw[i].tobytes() for i in ix] for ix, _, _ in items],
        [m for _, m, _ in items],
        [s for _, _, s in items],
        [int(x) | 1 for x in np.random.default_rng(7).integers(1, 2**63, len(items))],
    )


def registry_chain(cache):
    """The slice of ``BeaconChain`` its batch-verify path reads — the device
    pubkey registry and the mesh-planner slot — carrying the chain's own
    methods, so the smoke runs the chain's code without a 1M-validator
    beacon state. The oracle rung is not part of this check: reaching it
    raises."""
    import threading
    import types

    from lighthouse_tpu.beacon_chain.chain import BeaconChain

    class RegistryChain:
        _batch_verify_items_inner = BeaconChain._batch_verify_items_inner
        _mesh_planner = BeaconChain._mesh_planner
        _build_mesh_planner = BeaconChain._build_mesh_planner

        def __init__(self):
            self.pubkey_cache = types.SimpleNamespace(device_array=lambda: cache)
            self._mesh_planner_state = "unset"
            self._mesh_planner_lock = threading.Lock()

        def _verify_items_via_sets(self, items, oracle: bool = False) -> bool:
            raise AssertionError("the ladder reached its CPU oracle rung")

    return RegistryChain()


def check_supervisors() -> None:
    from lighthouse_tpu.resilience import snapshot_all

    for name, snap in snapshot_all().items():
        bad = {
            k: snap[k]
            for k in ("faults", "retries", "demotions", "fallback_calls",
                      "watchdog_timeouts")
            if snap[k]
        }
        check(not bad, f"supervisor {name}: {bad}")
    log(f"supervisors clean: {sorted(snapshot_all())}")


def kernel_parity() -> None:
    """Fused Pallas kernels against host integer arithmetic."""
    import jax

    from lighthouse_tpu.ops.bls import fq, tower as tw
    from lighthouse_tpu.ops.bls_oracle import fields as of

    rng = np.random.default_rng(11)
    xs = [int.from_bytes(rng.bytes(48), "big") % of.P for _ in range(256)]
    ys = [int.from_bytes(rng.bytes(48), "big") % of.P for _ in range(256)]
    out = jax.jit(fq.mont_mul)(fq.from_ints(xs), fq.from_ints(ys))
    check(fq.to_ints(out) == [x * y % of.P for x, y in zip(xs, ys)], "mont_mul")

    def rfq12():
        c = [int.from_bytes(rng.bytes(48), "big") % of.P for _ in range(12)]
        f2 = [of.Fq2(c[i], c[i + 1]) for i in range(0, 12, 2)]
        return of.Fq12(of.Fq6(*f2[:3]), of.Fq6(*f2[3:]))

    a, b = rfq12(), rfq12()
    got = jax.jit(tw.fq12_mul)(tw.fq12_from_oracle(a), tw.fq12_from_oracle(b))
    check(tw.fq12_to_oracle(got) == a * b, "fq12_mul")
    log("kernel parity: mont_mul x256, fq12_mul ok")


def compile_stages(lowerings) -> None:
    """Compile every (name, Lowered) by hand, all at once on host threads:
    the seconds and memory of each. The persistent compile cache then
    serves the jitted calls that follow."""

    def one(item):
        name, lowered = item
        t0 = time.perf_counter()
        ma = lowered.compile().memory_analysis()
        log(
            f"compile {name}: {time.perf_counter() - t0:.1f} s, "
            f"args {ma.argument_size_in_bytes} B, temp {ma.temp_size_in_bytes} B, "
            f"code {ma.generated_code_size_in_bytes} B"
        )

    log(f"compiling {[name for name, _ in lowerings]} concurrently ...")
    with ThreadPoolExecutor(len(lowerings)) as ex:
        list(ex.map(one, lowerings))


def library_seam(sks, comp) -> None:
    """``bls.verify_signature_sets`` on wire-format bytes, tpu backend."""
    from lighthouse_tpu import bls
    from lighthouse_tpu.native.build import NativeBls
    from lighthouse_tpu.ops.bls_oracle.fields import R

    bls.set_backend("tpu")
    nb = NativeBls()
    rng = np.random.default_rng(13)
    sets = []
    for s in range(LIB_SETS):
        members = rng.choice(len(sks), size=2, replace=False)
        msg = rng.bytes(32)
        agg = sum(int.from_bytes(sks[i].tobytes(), "big") for i in members) % R
        sig = bls.Signature.from_bytes(nb.sign(agg.to_bytes(32, "big"), msg))
        keys = [bls.PublicKey.from_bytes(comp[i].tobytes()) for i in members]
        sets.append(bls.SignatureSet.multiple_pubkeys(sig, keys, msg))
    check(bls.verify_signature_sets(sets), "library seam rejected valid sets")
    bad = list(sets)
    bad[3] = bls.SignatureSet(bad[3].signature, bad[3].signing_keys, bytes(32))
    check(not bls.verify_signature_sets(bad), "library seam took a tampered set")
    log(f"library seam: {LIB_SETS} sets ok, tampered copy rejected")


def run_one_chip(chain, raw, batches) -> None:
    times = []
    for b, items in enumerate(batches):
        t0 = time.perf_counter()
        ok = chain._batch_verify_items_inner(items)
        times.append(time.perf_counter() - t0)
        want = native_verdict(raw, items)
        check(ok is True and want is True, f"batch {b}: device {ok}, native {want}")
    bad = swapped(batches[0])
    ok, want = chain._batch_verify_items_inner(bad), native_verdict(raw, bad)
    check(ok is False and want is False, f"swapped: device {ok}, native {want}")
    log(f"batches: {len(batches)} x {len(batches[0])} valid, swapped rejected; "
        f"device verdicts match native")
    log(f"batch seconds: {[round(t, 3) for t in times]}")
    if len(times) > 1:
        steady = times[1:]
        log(f"informational: {len(batches[0]) * len(steady) / sum(steady):.1f} "
            f"sets/s after warm-up (host clock, 1 chip)")


def run_four_chips(chain, single, batches) -> None:
    """The chain's mesh path against single-device verification of the
    same batches on device 0 (``single``: the registry there)."""
    from lighthouse_tpu.bls import tpu_backend as tb

    placements = []
    real = tb.verify_staged_pershard

    def spy(cache_arr, staged, mesh):
        placements.append({s.device.id for s in staged["idx"].addressable_shards})
        return real(cache_arr, staged, mesh)

    tb.verify_staged_pershard = spy
    try:
        check(chain._mesh_planner() is not None, "mesh planner is off")
        for b, items in enumerate(batches + [swapped(batches[0])]):
            log(f"batch {b}: mesh ...")
            t0 = time.perf_counter()
            mesh_ok = chain._batch_verify_items_inner(items)
            dt = time.perf_counter() - t0
            t0 = time.perf_counter()
            one_ok = tb.verify_indexed_sets_device(single, items)
            dt1 = time.perf_counter() - t0
            want = b < len(batches)
            check(mesh_ok is want and one_ok is want, (
                f"batch {b}: mesh {mesh_ok}, single {one_ok}, want {want}"
            ))
            log(f"batch {b}: mesh {mesh_ok} == single {one_ok} "
                f"({dt:.3f} s mesh, {dt1:.3f} s single)")
    finally:
        tb.verify_staged_pershard = real
    check(placements and all(len(p) == 4 for p in placements), f"placements {placements}")
    log(f"shards placed on devices {sorted(placements[0])} in every dispatch")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2026)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded serving tier")
    args = ap.parse_args()
    # a run stopped by a time limit says where every thread was
    faulthandler.register(signal.SIGTERM, all_threads=True, chain=True)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform} devices")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        raise SystemExit(f"need {want} chips, JAX found {len(devices)}")
    log(f"device: {dev.device_kind} x {len(devices)} ({jax.__version__})")

    smoke(args.seed, args.four_chips)

    for d in devices[:want]:
        stats = d.memory_stats() or {}
        log(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


def smoke(seed: int, four_chips: bool) -> None:
    """Every phase of the check; any failure raises."""
    import jax

    if four_chips:
        os.environ["LIGHTHOUSE_MESH_DEVICES"] = "4"
    t0 = time.perf_counter()
    registry = build_registry(N_VALIDATORS, seed)

    from lighthouse_tpu.beacon_chain.pubkey_cache import device_pubkeys_from_raw
    from lighthouse_tpu.bls import mesh as bls_mesh, tpu_backend as tb
    from lighthouse_tpu.ops.bls import fq, pallas_kernels

    check(fq.conv_backend() == "pallas", f"conv backend {fq.conv_backend()}")
    check(not pallas_kernels._interpret(), "pallas kernels in interpret mode")

    # the stages compile while the workers make the keys
    lowerings = tb.stage_lowerings(SETS, KEYS, N_VALIDATORS)
    if four_chips:
        # the mesh ladder splits 64 one-item groups into 4 shards of 16
        mesh = bls_mesh.get_mesh(range(4))
        lowerings = [
            (f"mesh {name}", lowered) for name, lowered
            in tb.sharded_stage_lowerings(mesh, SETS, KEYS, N_VALIDATORS)
        ] + lowerings
    else:
        kernel_parity()
    compile_stages(lowerings)

    sks, comp, raw = registry()
    log(f"registry: {N_VALIDATORS} keys {time.perf_counter() - t0:.1f} s "
        f"after the workers started")

    t0 = time.perf_counter()
    batches = build_batches(sks, N_BATCHES, SETS, KEYS, seed)
    log(f"traffic: {N_BATCHES} x {SETS} sets x {KEYS} keys in "
        f"{time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    cache = device_pubkeys_from_raw(raw)
    cache.block_until_ready()
    check(cache.shape == (N_VALIDATORS, 3, 25), f"cache shape {cache.shape}")
    log(f"registry on device: {cache.nbytes} B in {time.perf_counter() - t0:.1f} s")

    if four_chips:
        from jax.sharding import NamedSharding, PartitionSpec

        replicated = jax.device_put(cache, NamedSharding(mesh, PartitionSpec()))
        run_four_chips(registry_chain(replicated), cache, batches)
    else:
        run_one_chip(registry_chain(cache), raw, batches)
        library_seam(sks, comp)
    check_supervisors()


if __name__ == "__main__":
    sys.exit(main())
