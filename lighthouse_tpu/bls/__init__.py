"""Backend-pluggable BLS12-381 seam — the TPU twin of ``crypto/bls``.

The reference exposes generic wrapper types made concrete per backend by the
``define_mod!`` macro (``/root/reference/crypto/bls/src/lib.rs:87-142``) with
backends selected by cargo feature (blst / fake_crypto). Here the same seam is
a module-level backend registry: ``oracle`` (pure-Python, the trusted
reference implementation) and ``tpu`` (JAX device kernels). Everything above
this package is backend-blind: it sees ``PublicKey``/``Signature``/
``AggregateSignature``/``SecretKey``/``SignatureSet`` and the free function
``verify_signature_sets``.

Wire formats match the reference exactly: 48-byte compressed G1 pubkeys,
96-byte compressed G2 signatures, 32-byte secret keys
(``generic_public_key.rs``, ``generic_signature.rs``, ``generic_secret_key.rs``).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..ops.bls_oracle import ciphersuite as _cs
from ..ops.bls_oracle import curves as _oc
from ..ops.bls_oracle.fields import R as CURVE_ORDER

PUBLIC_KEY_BYTES_LEN = 48
SIGNATURE_BYTES_LEN = 96
SECRET_KEY_BYTES_LEN = 32

INFINITY_PUBLIC_KEY = b"\xc0" + b"\x00" * 47
INFINITY_SIGNATURE = b"\xc0" + b"\x00" * 95

_BACKEND = "tpu"


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in ("tpu", "oracle", "native"):
        raise ValueError(f"unknown bls backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def _native():
    from ..native.build import NativeBls

    return NativeBls()


class BlsError(Exception):
    """Deserialization / validation failure (reference: bls::Error)."""


@dataclass(frozen=True)
class PublicKey:
    """Validated G1 public key (decompressed, subgroup-checked on parse —
    key_validate semantics, blst.rs:75)."""

    point: tuple  # oracle affine G1 point

    @classmethod
    def from_bytes(cls, data: bytes) -> "PublicKey":
        if len(data) != PUBLIC_KEY_BYTES_LEN:
            raise BlsError(f"invalid pubkey length {len(data)}")
        try:
            pt = _oc.g1_decompress(data)
        except ValueError as e:
            raise BlsError(str(e)) from None
        if pt is None or not _oc.g1_in_subgroup(pt):
            raise BlsError("pubkey not a valid subgroup point")
        return cls(pt)

    def serialize(self) -> bytes:
        return _oc.g1_compress(self.point)

    def __hash__(self):
        return hash(self.point)


@dataclass(frozen=True)
class Signature:
    """G2 signature. Parsed lazily-strict: bytes must decode to an on-curve
    point (or infinity); subgroup check happens at verification time, matching
    the reference's deserialize-then-groupcheck split."""

    point: object  # oracle affine G2 point or None (infinity)

    @classmethod
    def from_bytes(cls, data: bytes) -> "Signature":
        if len(data) != SIGNATURE_BYTES_LEN:
            raise BlsError(f"invalid signature length {len(data)}")
        try:
            pt = _oc.g2_decompress(data)
        except ValueError as e:
            raise BlsError(str(e)) from None
        return cls(pt)

    def serialize(self) -> bytes:
        return _oc.g2_compress(self.point)

    def verify(self, pubkey: PublicKey, message: bytes) -> bool:
        # single-op dispatch: native backend verifies in C++; the tpu backend
        # delegates singles to the oracle (device round-trips only pay off in
        # batches — verify_signature_sets is the batched path)
        if _BACKEND == "native":
            return _native().verify(
                pubkey.serialize(), message, _oc.g2_compress(self.point)
            )
        return _cs.verify(pubkey.point, message, self.point)


@dataclass(frozen=True)
class AggregateSignature:
    point: object

    @classmethod
    def infinity(cls) -> "AggregateSignature":
        return cls(None)

    @classmethod
    def aggregate(cls, sigs) -> "AggregateSignature":
        acc = None
        for s in sigs:
            acc = _oc.g2_add(acc, s.point)
        return cls(acc)

    def add_assign(self, sig: Signature) -> "AggregateSignature":
        return AggregateSignature(_oc.g2_add(self.point, sig.point))

    def serialize(self) -> bytes:
        return _oc.g2_compress(self.point)

    @classmethod
    def from_bytes(cls, data: bytes) -> "AggregateSignature":
        return cls(Signature.from_bytes(data).point)

    def fast_aggregate_verify(self, message: bytes, pubkeys) -> bool:
        if _BACKEND == "native":
            return _native().fast_aggregate_verify(
                [pk.serialize() for pk in pubkeys],
                message,
                _oc.g2_compress(self.point),
            )
        return _cs.fast_aggregate_verify(
            [pk.point for pk in pubkeys], message, self.point
        )

    def aggregate_verify(self, messages, pubkeys) -> bool:
        return _cs.aggregate_verify(
            [pk.point for pk in pubkeys], messages, self.point
        )


@dataclass(frozen=True)
class SecretKey:
    scalar: int

    @classmethod
    def from_bytes(cls, data: bytes) -> "SecretKey":
        if len(data) != SECRET_KEY_BYTES_LEN:
            raise BlsError(f"invalid secret key length {len(data)}")
        sk = int.from_bytes(data, "big")
        if sk == 0 or sk >= CURVE_ORDER:
            raise BlsError("secret key out of range")
        return cls(sk)

    @classmethod
    def keygen(cls, ikm: bytes, key_info: bytes = b"") -> "SecretKey":
        return cls(_cs.keygen_from_ikm(ikm, key_info))

    def serialize(self) -> bytes:
        return self.scalar.to_bytes(32, "big")

    def public_key(self) -> PublicKey:
        return PublicKey(_cs.sk_to_pk(self.scalar))

    def sign(self, message: bytes) -> Signature:
        if _BACKEND == "native":
            return Signature.from_bytes(
                _native().sign(self.serialize(), message)
            )
        return Signature(_cs.sign(self.scalar, message))


@dataclass
class SignatureSet:
    """One batch-verification task (generic_signature_set.rs:61-72)."""

    signature: object       # Signature | AggregateSignature
    signing_keys: list      # list[PublicKey]
    message: bytes          # 32-byte signing root

    @classmethod
    def single_pubkey(cls, signature, signing_key, message) -> "SignatureSet":
        return cls(signature, [signing_key], message)

    @classmethod
    def multiple_pubkeys(cls, signature, signing_keys, message) -> "SignatureSet":
        return cls(signature, signing_keys, message)


def _verify_sets_oracle(sets) -> bool:
    return _cs.verify_signature_sets(
        [
            _cs.SignatureSet(
                s.signature.point, [pk.point for pk in s.signing_keys], s.message
            )
            for s in sets
        ]
    )


def _verify_sets_tpu(sets) -> bool:
    import jax.numpy as jnp

    from . import tpu_backend as tb
    from ..ops.bls import g1 as dg1, g2 as dg2
    from ..ops.bls import h2c as dh2c
    from ..ops.bls_oracle.ciphersuite import DST

    n = len(sets)
    if n == 0:
        return False
    for s in sets:
        if s.signature.point is None or not s.signing_keys:
            return False
    n_pad = tb.bucket(n)
    pk_pts = [
        dg1.from_oracle_batch([pk.point for pk in s.signing_keys]) for s in sets
    ]
    pk_agg = tb.aggregate_pubkeys_device(pk_pts)
    pk_agg = jnp.concatenate(
        [pk_agg, jnp.broadcast_to(pk_agg[:1], (n_pad - n,) + pk_agg.shape[1:])]
    ) if n_pad > n else pk_agg
    sig = dg2.from_oracle_batch([s.signature.point for s in sets])
    # device h2c: host SHA-256 hash_to_field; SSWU/isogeny/cofactor fuse into
    # the verification kernel (one jit) — no oracle pairing-tower hashing and
    # no eager op-by-op dispatch on the hot path
    u0, u1 = dh2c.hash_to_field_batch([s.message for s in sets], DST, n_pad)
    if n_pad > n:  # pad by broadcast, not by hashing dummy messages
        sig = jnp.concatenate(
            [sig, jnp.broadcast_to(sig[:1], (n_pad - n,) + sig.shape[1:])]
        )
    return tb.verify_signature_sets_device_h2c(pk_agg, sig, u0, u1, n)


def _verify_sets_native(sets) -> bool:
    import secrets

    from ..native.build import NativeBls
    from .tpu_backend import RAND_BITS

    nb = NativeBls()
    try:
        return nb.verify_signature_sets(
            [[pk.serialize() for pk in s.signing_keys] for s in sets],
            [s.message for s in sets],
            [s.signature.serialize() for s in sets],
            [secrets.randbits(RAND_BITS) or 1 for _ in sets],
        )
    except ValueError:
        return False


def verify_signature_sets(sets) -> bool:
    """Random-linear-combination batch verification over the active backend."""
    sets = list(sets)
    if _BACKEND == "oracle":
        return _verify_sets_oracle(sets)
    if _BACKEND == "native":
        return _verify_sets_native(sets)
    return _verify_sets_tpu(sets)


def verify_signature_sets_oracle(sets) -> bool:
    """Batch verification pinned to the pure-Python oracle regardless of the
    active backend — the degradation ladder's CPU rung of last resort
    (resilience.supervisor): always available, trusted, device-free."""
    return _verify_sets_oracle(list(sets))


def warmup(n_sets: int = 2) -> bool:
    """Pre-compile the active backend's verification kernels.

    On the device backend the first verify of each bucket shape triggers XLA
    compilation (tens of seconds on a cold TPU). Serving paths run this at
    startup (Client.start) so block publication never pays the compile inside
    an HTTP request — the analog of blst having no warm-up cost at all.
    Returns the verification verdict (True on a healthy backend)."""
    import hashlib

    sk = SecretKey.from_bytes((7).to_bytes(32, "big"))
    pk = sk.public_key()
    # messages must be 32-byte signing roots (the only shape the real
    # pipeline ever verifies; the native backend enforces it)
    msgs = [
        hashlib.sha256(b"lighthouse-tpu-warmup-%02d" % i).digest()
        for i in range(n_sets)
    ]
    sets = [
        SignatureSet.single_pubkey(sk.sign(m), pk, m) for m in msgs
    ]
    ok = verify_signature_sets(sets[:1])
    if n_sets > 1:
        ok = verify_signature_sets(sets) and ok
    return ok
