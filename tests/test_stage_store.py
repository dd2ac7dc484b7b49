"""The stage store (``utils/stage_store.py``): device stages kept as
serialized programs, loaded by later processes instead of traced.

The chain's three BLS stages run at the smallest shapes (n_pad 4, k_pad
4, a 16-row registry): ``verify_indexed_sets_device`` resolves them once
from an empty store and once from the filled one, and each stage as both
runs resolved it matches the stage as traced, bit for bit. The key,
corruption and concurrency cases run the store on a tiny jitted function.
Every case keeps its store in a temporary directory; a new
``StoredStage`` (or a cleared factory cache) stands for a new process.
"""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lighthouse_tpu  # noqa: F401  (x64)
from lighthouse_tpu import bls
from lighthouse_tpu.bls import serde
from lighthouse_tpu.bls import tpu_backend as tb
from lighthouse_tpu.ops.bls import fq, g1, h2c
from lighthouse_tpu.ops.bls_oracle.ciphersuite import DST
from lighthouse_tpu.utils import stage_store, tracing

N_ROWS = 16
STAGES = {
    "h2c": (tb._h2c_stage, (4,)),
    "prep": (tb._prep_stage, (4, 4)),
    "pair": (tb._pair_stage, (4,)),
}


@pytest.fixture
def store(tmp_path, monkeypatch):
    monkeypatch.setattr(stage_store, "store_dir", lambda: str(tmp_path))
    return tmp_path


def _outcomes() -> dict:
    return {k: v for k, _, v in tracing.STAGE_STORE_TOTAL.collect()}


def _phases() -> dict:
    return tracing.COMPILE_PHASE_SECONDS.totals()


def _new(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


# -- the three BLS stages -------------------------------------------------------


@pytest.fixture(scope="module")
def signed():
    """A 16-key device registry and four 2-key aggregate sets over it; the
    last set's signature is over another message."""
    prev = bls.get_backend()
    bls.set_backend("native")
    try:
        sks = [bls.SecretKey.keygen(bytes([i + 1]) * 32) for i in range(N_ROWS)]
        cache = g1.from_oracle_batch([sk.public_key().point for sk in sks])
        items = []
        for i in range(4):
            ix = [i, i + 4]
            msg = bytes([i + 1]) * 32
            signed_msg = msg if i < 3 else b"\x77" * 32
            agg = bls.AggregateSignature.aggregate(
                [sks[j].sign(signed_msg) for j in ix])
            items.append((ix, msg, agg.serialize()))
    finally:
        bls.set_backend(prev)
    return cache, items


def _leaves(out) -> list:
    return [np.asarray(x) for x in jax.tree.leaves(out)]


@pytest.fixture(scope="module")
def traced(signed, runs):
    """Each stage's arguments at n_pad 4, k_pad 4, and its outputs from the
    plain jit of the stage (the pair stage takes the h2c and prep outputs).
    That jit is the one the fresh run exported, so its trace is reused and
    only lowered and compiled here."""
    cache, items = signed
    plain = {n: runs["fresh"]["stages"][n].jitted for n in STAGES}
    idx = np.zeros((4, 4), np.int32)
    mask = np.zeros((4, 4), bool)
    for i, (ix, _, _) in enumerate(items):
        idx[i, :len(ix)] = ix
        mask[i, :len(ix)] = True
    parsed = serde.parse_g2_bytes(
        np.stack([np.frombuffer(s, np.uint8) for _, _, s in items]))
    u0, u1 = h2c.hash_to_field_host([m for _, m, _ in items], DST, 4)
    valid = np.ones(4, bool)
    args = {
        "h2c": (u0, u1),
        "prep": (cache, idx, mask, parsed["x_c0"], parsed["x_c1"],
                 parsed["s_flag"], parsed["wf_ok"] & ~parsed["is_inf"],
                 np.arange(1, 5, dtype=np.uint64) * 0x9E3779B97F4A7C15,
                 valid),
    }
    outs = {name: plain[name](*args[name]) for name in ("h2c", "prep")}
    mxa, mya = outs["h2c"]
    pkx, pky, sax, say, set_ok = outs["prep"]
    args["pair"] = (pkx, pky, sax, say, mxa, mya, set_ok, valid)
    outs["pair"] = plain["pair"](*args["pair"])
    return {name: (args[name], _leaves(outs[name])) for name in STAGES}


@pytest.fixture(scope="module")
def runs(signed, tmp_path_factory):
    """``verify_indexed_sets_device`` on a batch with one bad set and on one
    without, twice, each time as a new process (no stage resolved yet):
    first from an empty store, then from the store the first run filled.
    Keeps each run's verdicts, the store outcomes and compile phases it
    added, and the three stages as it resolved them."""
    cache, items = signed
    factories = [f for f, _ in STAGES.values()]
    root = tmp_path_factory.mktemp("stages")
    runs = {}
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(stage_store, "store_dir", lambda: str(root))
            for run in ("fresh", "loaded"):
                for f in factories:
                    f.cache_clear()
                outcomes, phases = _outcomes(), _phases()
                verdicts = [tb.verify_indexed_sets_device(cache, items),
                            tb.verify_indexed_sets_device(cache, items[:3])]
                runs[run] = {
                    "verdicts": verdicts,
                    "outcomes": _new(outcomes, _outcomes()),
                    "phases": _new(phases, _phases()),
                    "stages": {n: f(*shape) for n, (f, shape) in STAGES.items()},
                }
        runs["entries"] = sorted(os.listdir(root))
        yield runs
    finally:
        for f in factories:
            f.cache_clear()


def test_verdicts_from_fresh_and_loaded_store(runs):
    fresh, loaded = runs["fresh"], runs["loaded"]
    assert fresh["outcomes"] == {(f"bls_{n}", "miss"): 1 for n in STAGES}
    assert loaded["outcomes"] == {(f"bls_{n}", "hit"): 1 for n in STAGES}
    assert len(runs["entries"]) == len(STAGES)
    # a loaded stage is never traced
    assert not [k for k in loaded["phases"] if k[0] == "trace"]
    for n in STAGES:
        assert loaded["phases"][("load", f"bls_{n}")] == 1
    assert fresh["verdicts"] == loaded["verdicts"] == [False, True]


@pytest.mark.parametrize("name", sorted(STAGES))
def test_loaded_stage_matches_traced(name, traced, runs):
    """The program each run resolved, stored (fresh) and loaded, on the
    traced stage's arguments: the same outputs, bit for bit, with nothing
    resolved, traced or compiled again."""
    args, want = traced[name]
    outcomes, phases = _outcomes(), _phases()
    stored = _leaves(runs["fresh"]["stages"][name](*args))
    loaded = _leaves(runs["loaded"]["stages"][name](*args))
    assert _outcomes() == outcomes
    assert _new(phases, _phases()) == {}
    for a, b, c in zip(want, stored, loaded, strict=True):
        assert a.dtype == b.dtype == c.dtype
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_factories_unwrap_to_the_plain_stage():
    """``__wrapped__`` builds the stage as written, never from the store:
    a patched build (the benchmark's planted faults) traces what it
    patched."""
    for factory, shape in STAGES.values():
        stage = factory(*shape)
        assert isinstance(stage, stage_store.StoredStage)
        assert stage.settings == (("conv", fq.conv_backend()),)
        plain = factory.__wrapped__(*shape)
        assert not isinstance(plain, stage_store.StoredStage)
        assert plain is not factory.__wrapped__(*shape)
        assert plain.__name__ == stage.__name__


# -- the store on a tiny function -------------------------------------------------


def _probe():
    @jax.jit
    def bls_store_probe(x, y):
        return x * 3 + y, jnp.sum(x)

    return bls_store_probe


X = np.arange(8, dtype=np.uint64)


def _spec(shape=(8,), dtype=jnp.uint64):
    return jax.ShapeDtypeStruct(shape, dtype)


def _run_probe(x=X):
    return _leaves(stage_store.StoredStage(_probe())(x, x))


def test_key_covers_shape_dtype_settings_and_versions(monkeypatch):
    key = stage_store.stage_key("bls_store_probe", [_spec()])
    assert key == stage_store.stage_key("bls_store_probe", [_spec()])
    others = [
        stage_store.stage_key("bls_store_probe", [_spec((16,))]),
        stage_store.stage_key("bls_store_probe", [_spec(dtype=jnp.uint32)]),
        stage_store.stage_key("bls_other", [_spec()]),
        stage_store.stage_key("bls_store_probe", [_spec()], (("conv", "f64"),)),
    ]
    monkeypatch.setattr(jax, "__version__", jax.__version__ + ".dev1")
    others.append(stage_store.stage_key("bls_store_probe", [_spec()]))
    monkeypatch.undo()
    monkeypatch.setattr(stage_store, "package_digest", lambda: "0" * 64)
    others.append(stage_store.stage_key("bls_store_probe", [_spec()]))
    assert len({key, *others}) == len(others) + 1


@pytest.mark.parametrize("var", ["LIGHTHOUSE_PAIRING_KARABINA",
                                 "LIGHTHOUSE_PALLAS_INTERPRET",
                                 "LIGHTHOUSE_A_LATER_SWITCH"])
def test_key_covers_the_package_switches(var, monkeypatch):
    """Every ``LIGHTHOUSE_*`` variable is in the key, those read at trace
    time and any added later; other variables are not."""
    monkeypatch.delenv(var, raising=False)
    key = stage_store.stage_key("bls_store_probe", [_spec()])
    monkeypatch.setenv("STAGE_STORE_UNRELATED", "1")
    assert stage_store.stage_key("bls_store_probe", [_spec()]) == key
    keys = set()
    for value in ("0", "1"):
        monkeypatch.setenv(var, value)
        keys.add(stage_store.stage_key("bls_store_probe", [_spec()]))
    assert key not in keys and len(keys) == 2


def test_sources_digest_covers_every_byte(tmp_path):
    pkg = tmp_path / "pkg"
    (pkg / "sub").mkdir(parents=True)
    (pkg / "a.py").write_bytes(b"A = 1\n")
    (pkg / "sub" / "b.py").write_bytes(b"B = 2\n")
    (pkg / "notes.txt").write_bytes(b"not a source\n")
    digest = stage_store.sources_digest(str(pkg))
    (pkg / "notes.txt").write_bytes(b"changed\n")
    assert stage_store.sources_digest(str(pkg)) == digest
    (pkg / "sub" / "b.py").write_bytes(b"B = 3\n")
    assert stage_store.sources_digest(str(pkg)) != digest
    assert len(stage_store.package_digest()) == 64


def _corrupt(path, how):
    data = bytearray(path.read_bytes())
    if how == "truncated":
        data = data[: len(data) // 2]
    elif how == "flipped":
        data[-100] ^= 0x01
    elif how == "bad_magic":
        data[0] ^= 0x01
    path.write_bytes(bytes(data))


@pytest.mark.parametrize("how", ["truncated", "flipped", "bad_magic"])
def test_bad_entry_is_counted_traced_again_and_rewritten(how, store):
    want = _run_probe()
    entry, = store.iterdir()
    _corrupt(entry, how)

    outcomes, phases = _outcomes(), _phases()
    got = _run_probe()
    assert _new(outcomes, _outcomes()) == {("bls_store_probe", "invalid"): 1}
    assert ("trace", "bls_store_probe") in _new(phases, _phases())
    for a, b in zip(want, got, strict=True):
        np.testing.assert_array_equal(a, b)
    assert stage_store.read_entry(str(entry))      # rewritten whole

    outcomes = _outcomes()
    _run_probe()
    assert _new(outcomes, _outcomes()) == {("bls_store_probe", "hit"): 1}


def test_entry_of_another_signature_is_invalid(store):
    """An entry whose program takes other shapes is never loaded, even
    under the right key."""
    _run_probe(np.arange(4, dtype=np.uint64))
    small, = store.iterdir()
    key = stage_store.stage_key("bls_store_probe", [_spec(), _spec()])
    stage_store.write_entry(
        stage_store.entry_path(str(store), "bls_store_probe", key),
        stage_store.read_entry(str(small)))

    outcomes = _outcomes()
    got = _run_probe()
    assert _new(outcomes, _outcomes()) == {("bls_store_probe", "invalid"): 1}
    np.testing.assert_array_equal(got[0], X * 3 + X)


def test_a_stage_that_fails_to_trace_raises(store):
    """A stage that cannot be traced (or exported) raises, as a plain jit
    does, and stores and counts nothing."""

    @jax.jit
    def bls_store_broken(x):
        return x @ x.T[:3]

    outcomes = _outcomes()
    with pytest.raises(TypeError):
        stage_store.StoredStage(bls_store_broken)(np.ones((2, 5), np.float32))
    assert _outcomes() == outcomes
    assert not list(store.iterdir())


def test_concurrent_writers_leave_one_whole_entry(store):
    """More writers than cores, each a new process's stage, resolve the
    same program at once: every one computes the right answer and the
    store ends with one entry that reads back, and no temporary file."""
    n = 2 * (os.cpu_count() or 4)
    want = _leaves(_probe()(X, X))
    results, errors = [None] * n, []
    start = threading.Barrier(n)

    def worker(i):
        try:
            start.wait(timeout=60)
            results[i] = _run_probe()
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not [t for t in threads if t.is_alive()]
    assert not errors
    for got in results:
        for a, b in zip(want, got, strict=True):
            np.testing.assert_array_equal(a, b)
    entry, = store.iterdir()
    key = stage_store.stage_key("bls_store_probe", [_spec(), _spec()])
    assert str(entry) == stage_store.entry_path(str(store), "bls_store_probe", key)
    assert stage_store.read_entry(str(entry))


def test_without_a_cache_directory_stages_trace(monkeypatch):
    monkeypatch.setattr(stage_store, "store_dir", lambda: None)
    stage = stage_store.StoredStage(_probe())
    outcomes, phases = _outcomes(), _phases()
    got = _leaves(stage(X, X))
    assert _outcomes() == outcomes
    assert ("trace", "bls_store_probe") in _new(phases, _phases())
    assert list(stage._programs.values()) == [stage.jitted]
    np.testing.assert_array_equal(got[0], X * 3 + X)


def test_store_lives_under_the_compile_cache():
    base = jax.config.jax_compilation_cache_dir
    if base:
        assert stage_store.store_dir() == os.path.join(base, "stages")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        assert stage_store.store_dir() is None
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
