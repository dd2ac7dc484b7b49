"""The benchmark's plain reference: BLS12-381 on the CPU, through ctypes.

Builds ``bls12_381.cpp`` (beside this file) with g++ into
``<checkout>/.bench_cache/native/<host tag>/`` on first use and loads it.
Nothing here imports the program or JAX, so worker threads and processes
can use it freely; every call releases the GIL (ctypes ``CDLL``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "bls12_381.cpp")
_ROOT = os.path.dirname(os.path.dirname(_HERE))

_lock = threading.Lock()
_lib = None


def _host_tag() -> str:
    """Fingerprint of the CPU's feature flags: the library is built with
    -march=native and dies with SIGILL on a host with fewer features."""
    with open("/proc/cpuinfo") as fh:
        for line in fh:
            if line.startswith("flags"):
                return hashlib.sha256(line.encode()).hexdigest()[:12]
    raise RuntimeError("no CPU flags line in /proc/cpuinfo")


def _library_path() -> str:
    with open(_SRC, "rb") as fh:
        src_tag = hashlib.sha256(fh.read()).hexdigest()[:12]
    return os.path.join(
        _ROOT, ".bench_cache", "native", _host_tag(), f"libref_{src_tag}.so"
    )


def load() -> ctypes.CDLL:
    """Build (once per checkout and host) and initialise the library."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        path = _library_path()
        if not os.path.exists(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O3", "-march=native", "-fno-exceptions", "-fPIC",
                 "-shared", _SRC, "-o", tmp],
                check=True, capture_output=True, text=True,
            )
            os.replace(tmp, path)
        lib = ctypes.CDLL(path)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.bls_native_init.restype = ctypes.c_int
        lib.bls_sk_to_pk.argtypes = [u8p, u8p]
        lib.bls_sk_to_pk.restype = None
        lib.bls_hash_to_g2.argtypes = [u8p, ctypes.c_uint64, u8p]
        lib.bls_hash_to_g2.restype = None
        lib.bls_g2_mul.argtypes = [u8p, u8p, u8p]
        lib.bls_g2_mul.restype = ctypes.c_int
        lib.bls_pk_decompress.argtypes = [u8p, u8p]
        lib.bls_pk_decompress.restype = ctypes.c_int
        lib.bls_aggregate_signatures.argtypes = [ctypes.c_uint64, u8p, u8p]
        lib.bls_aggregate_signatures.restype = ctypes.c_int
        lib.bls_verify_signature_sets_raw.argtypes = [
            ctypes.c_uint64, u64p, u8p, u8p, u8p, u64p,
        ]
        lib.bls_verify_signature_sets_raw.restype = ctypes.c_int
        lib.bls_dbg_sswu_iso.argtypes = [u8p, u8p]
        lib.bls_dbg_sswu_iso.restype = ctypes.c_int
        lib.bls_dbg_clear.argtypes = [u8p, u8p]
        lib.bls_dbg_clear.restype = ctypes.c_int
        if lib.bls_native_init() != 0:
            raise RuntimeError("bls_native_init failed")
        _lib = lib
        return lib


def _buf(data: bytes):
    return (ctypes.c_uint8 * len(data)).from_buffer_copy(data)


def _sk_bytes(sk: int) -> bytes:
    return sk.to_bytes(32, "big")


def pubkey_raw(sk: int) -> bytes:
    """96-byte affine x||y (big-endian) of sk * G1."""
    lib = load()
    comp = (ctypes.c_uint8 * 48)()
    lib.bls_sk_to_pk(_buf(_sk_bytes(sk)), comp)
    raw = (ctypes.c_uint8 * 96)()
    if lib.bls_pk_decompress(comp, raw) != 0:
        raise ValueError("pubkey of a zero key")
    return bytes(raw)


def pubkey(sk: int) -> bytes:
    """48-byte compressed sk * G1."""
    lib = load()
    out = (ctypes.c_uint8 * 48)()
    lib.bls_sk_to_pk(_buf(_sk_bytes(sk)), out)
    return bytes(out)


def _fp_bytes(*xs: int) -> bytes:
    return b"".join(x.to_bytes(48, "big") for x in xs)


def map_to_curve(u: tuple[int, int]) -> tuple:
    """The simplified SWU map of the field element u = (c0, c1), through the
    3-isogeny onto E': the affine point ((x0, x1), (y0, y1)), cofactor not
    cleared. The steps of hash-to-curve, for known-answer checks."""
    lib = load()
    out = (ctypes.c_uint8 * 192)()
    if lib.bls_dbg_sswu_iso(_buf(_fp_bytes(*u)), out) != 0:
        raise ValueError("not a field element")
    x0, x1, y0, y1 = (int.from_bytes(bytes(out[i:i + 48]), "big")
                      for i in range(0, 192, 48))
    return ((x0, x1), (y0, y1))


def clear_cofactor(point: tuple) -> bytes:
    """Compressed h_eff * point for an affine point of E'."""
    lib = load()
    out = (ctypes.c_uint8 * 96)()
    (x0, x1), (y0, y1) = point
    if lib.bls_dbg_clear(_buf(_fp_bytes(x0, x1, y0, y1)), out) != 0:
        raise ValueError("not a field element")
    return bytes(out)


def hash_to_g2(msg: bytes) -> bytes:
    lib = load()
    out = (ctypes.c_uint8 * 96)()
    lib.bls_hash_to_g2(_buf(msg), len(msg), out)
    return bytes(out)


def g2_mul(point: bytes, sk: int) -> bytes:
    """Compressed sk * point; with point = hash_to_g2(msg) this is the
    signature of msg under sk."""
    lib = load()
    out = (ctypes.c_uint8 * 96)()
    if lib.bls_g2_mul(_buf(point), _buf(_sk_bytes(sk)), out) != 0:
        raise ValueError("invalid G2 point")
    return bytes(out)


def g2_sum(points: list[bytes]) -> bytes:
    lib = load()
    out = (ctypes.c_uint8 * 96)()
    if lib.bls_aggregate_signatures(len(points), _buf(b"".join(points)), out):
        raise ValueError("invalid G2 point")
    return bytes(out)


def verify_sets(pks_raw: list[bytes], msgs: list[bytes], sigs: list[bytes],
                scalars: list[int]) -> bool:
    """Random-linear-combination batch verification of one-key sets
    (aggregate pubkey, 32-byte message, signature): true iff
    prod e(r_i pk_i, H(m_i)) == e(g1, sum r_i sig_i) and every signature is
    a valid G2 subgroup point."""
    n = len(pks_raw)
    if not (n == len(msgs) == len(sigs) == len(scalars)) or n == 0:
        raise ValueError("set length mismatch")
    lib = load()
    rc = lib.bls_verify_signature_sets_raw(
        n,
        (ctypes.c_uint64 * n)(*([1] * n)),
        _buf(b"".join(pks_raw)),
        _buf(b"".join(msgs)),
        _buf(b"".join(sigs)),
        (ctypes.c_uint64 * n)(*scalars),
    )
    if rc < 0:
        raise ValueError("malformed signature set")
    return rc == 1
