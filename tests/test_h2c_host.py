"""Host hash_to_field (ops/bls/h2c.hash_to_field_host): parity with the
oracle, the canonical limb layout the device map relies on, and no device
transfer on the verify path's host prep. No compile: tier-1."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lighthouse_tpu  # noqa: F401
from lighthouse_tpu.bls import tpu_backend as tb
from lighthouse_tpu.ops.bls import fq, h2c, tower
from lighthouse_tpu.ops.bls_oracle import hash_to_curve as oh
from lighthouse_tpu.ops.bls_oracle.ciphersuite import DST
from lighthouse_tpu.ops.bls_oracle.fields import P
from test_conformance import _RFC9380_DST, _RFC9380_G2_U0


def _ints(limbs) -> list:
    """[..., 2, 25] limbs -> [(c0, c1), ...] ints, no reduction."""
    return [tuple(fq.limbs_to_int(c) for c in row)
            for row in np.asarray(limbs).reshape(-1, 2, fq.NLIMBS)]


def _oracle(msgs, dst=DST) -> tuple:
    u = [oh.hash_to_field_fq2(m, dst, 2) for m in msgs]
    return ([(a.c0, a.c1) for a, _ in u], [(b.c0, b.c1) for _, b in u])


def _random_msgs(n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.bytes(int(k)) for k in rng.integers(0, 200, size=n)]


@pytest.mark.parametrize("length", [0, 1, 32, 100, 255])
def test_matches_oracle_by_message_length(length):
    msg = bytes(range(length))
    u0, u1 = h2c.hash_to_field_host([msg], DST)
    assert (_ints(u0), _ints(u1)) == _oracle([msg])


def test_matches_oracle_on_random_messages():
    msgs = _random_msgs(64, seed=0x4832)
    u0, u1 = h2c.hash_to_field_host(msgs, DST)
    assert (_ints(u0), _ints(u1)) == _oracle(msgs)


def test_rfc9380_u0_known_answer():
    msgs = list(_RFC9380_G2_U0)
    u0, _u1 = h2c.hash_to_field_host(msgs, _RFC9380_DST)
    assert _ints(u0) == [_RFC9380_G2_U0[m] for m in msgs]


@pytest.mark.parametrize("n, n_pad", [(1, 4), (5, 8), (64, 64)])
def test_layout_padding_and_canonical_limbs(n, n_pad):
    msgs = _random_msgs(n, seed=n)
    padded = h2c.hash_to_field_host(msgs, DST, n_pad)
    unpadded = h2c.hash_to_field_host(msgs, DST)
    for u, rows in zip(padded, unpadded):
        assert type(u) is np.ndarray and u.flags["C_CONTIGUOUS"]
        assert u.dtype == np.uint64 and u.shape == (n_pad, 2, fq.NLIMBS)
        assert (u < 1 << 16).all()
        assert all(c < P for row in _ints(u) for c in row)
        assert (u[:n] == rows).all()
        assert (u[n:] == u[0]).all()


@pytest.mark.parametrize("n, n_pad", [(0, None), (0, 4), (5, 4)])
def test_rejects_empty_or_short_pad(n, n_pad):
    with pytest.raises(ValueError):
        h2c.hash_to_field_host(_random_msgs(n, seed=1), DST, n_pad)


def test_batch_matches_per_message_device_arrays():
    """hash_to_field_batch against the per-message tower.from_ints +
    jnp.stack form it replaces, bit for bit, padding included."""
    msgs = _random_msgs(7, seed=7)
    old0, old1 = [], []
    for m in msgs:
        a, b = oh.hash_to_field_fq2(m, DST, 2)
        old0.append(tower.from_ints([a.c0, a.c1]))
        old1.append(tower.from_ints([b.c0, b.c1]))
    old0, old1 = jnp.stack(old0), jnp.stack(old1)
    u0, u1 = h2c.hash_to_field_batch(msgs, DST)
    p0, p1 = h2c.hash_to_field_batch(msgs, DST, 8)
    for new, pad, old in ((u0, p0, old0), (u1, p1, old1)):
        assert isinstance(new, jax.Array) and new.dtype == old.dtype
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
        np.testing.assert_array_equal(np.asarray(pad)[:7], np.asarray(old))
        np.testing.assert_array_equal(np.asarray(pad)[7], np.asarray(old)[0])


def test_host_helper_makes_no_device_transfer():
    msgs = _random_msgs(64, seed=64)
    with jax.transfer_guard("disallow_explicit"):
        h2c.hash_to_field_host(msgs, DST, 64)
        with pytest.raises(Exception, match="Disallowed host-to-device"):
            jnp.asarray(np.zeros(3, np.uint64))  # the guard is live here


def test_verify_path_hashes_to_host_arrays(monkeypatch):
    """verify_indexed_sets_device's bls.hash_to_field block runs under a
    transfer guard and yields NumPy arrays, which dispatch sends as the
    stages' u0, u1."""
    from lighthouse_tpu.utils import tracing

    made, sent = [], []
    host = h2c.hash_to_field_host

    def recording_host(*args):
        made.append(host(*args))
        return made[-1]

    @contextlib.contextmanager
    def guarded_span(name, *args, **attrs):
        with tracing.span(name, *args, **attrs):
            if name != "bls.hash_to_field":
                yield
                return
            with jax.transfer_guard("disallow_explicit"):
                yield

    def kernel(n_pad, k_pad):
        def run(*args):
            sent.append(args)
            return jnp.asarray(True)
        return run

    monkeypatch.setattr(h2c, "hash_to_field_host", recording_host)
    monkeypatch.setattr(tb, "span", guarded_span)
    monkeypatch.setattr(tb, "_gathered_kernel", kernel)
    rng = np.random.default_rng(5)
    items = [([1, 2, 3], rng.bytes(32), rng.bytes(96)) for _ in range(5)]
    assert tb.verify_indexed_sets_device(jnp.zeros((16, 3, 25), jnp.uint64), items)
    (u0, u1), = made
    assert type(u0) is np.ndarray and type(u1) is np.ndarray
    assert u0.shape == u1.shape == (8, 2, fq.NLIMBS)
    (args,) = sent
    np.testing.assert_array_equal(np.asarray(args[3]), u0)
    np.testing.assert_array_equal(np.asarray(args[4]), u1)
