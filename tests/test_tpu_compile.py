"""Compile the BLS hot path for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``on-chip-measurement`` guide §2): Mosaic refuses
layouts and VMEM use, and XLA:TPU refuses u64 ops it has no rewrite for,
which interpret mode on the CPU cannot show. Nothing runs: each case only
compiles at the shapes the verify path uses and checks the fused kernel is
in the program.

The topology is described inside a fixture, never at import time: one
process at a time may load libtpu, and xdist workers must all collect the
same tests. The cases steer the conv backend and Pallas interpret mode
themselves and restore them; the persistent compile cache is off around the
compiles (an entry written for a described chip cannot be read back here).
"""

import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import lighthouse_tpu  # noqa: F401  (enables x64)
from lighthouse_tpu.ops.bls import fq, plans, tower as tw

pytestmark = pytest.mark.kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_pallas(monkeypatch):
    """The TPU kernel path: pallas conv backend, interpret mode off, no
    persistent compile cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(fq, "_CONV_IMPL", "pallas")
    monkeypatch.setenv("LIGHTHOUSE_PALLAS_INTERPRET", "0")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _u64(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint64, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


KERNELS = {
    "mont_mul": (fq.mont_mul, [(1024, 25), (1024, 25)]),
    "mont_sqr": (fq.mont_sqr, [(1024, 25)]),
    "mont_mul_lazy": (fq.mont_mul_lazy, [(1024, 25), (1024, 25)]),
    "fq2_mul": (tw.fq2_mul, [(512, 2, 25), (512, 2, 25)]),
    "fq12_mul": (tw.fq12_mul, [(65, 12, 25), (65, 12, 25)]),
    "fq12_cyclotomic_sqr": (tw.fq12_cyclotomic_sqr, [(65, 12, 25)]),
    "fq12_frobenius1": (tw.fq12_frobenius1, [(65, 12, 25)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_fused_kernel_compiles(name, one_chip, tpu_pallas):
    """Each fused Pallas tower op compiles for v5e at verify-path rows
    (fq12 at the 64 sets + 1 of a batch's pairing)."""
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, *(_u64(one_chip, *s) for s in shapes))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_u64_lincomb_compiles(one_chip, tpu_pallas):
    """A u64-input lincomb (the fq12 plan's input map) compiles for TPU:
    XLA's X64 rewriter has no rule for a u64 dot_general, so the contraction
    must not be one."""
    def lin(x):
        out, _ = plans.lincomb(plans.MUL12.a_rows, x, plans.PUB_BOUND, "t")
        return out

    compiled = _compile(lin, _u64(one_chip, 64, 12, 25))
    hlo = compiled.as_text().splitlines()
    assert not [ln for ln in hlo if " dot(" in ln and "u64[" in ln]


@pytest.mark.slow
@pytest.mark.parametrize("stage", ["h2c", "prep", "pair"])
def test_verify_stage_compiles(stage, one_chip, tpu_pallas):
    """A chain verify stage at n_pad=64, k_pad=512 against a 2^20-row
    pubkey cache compiles for v5e and fits its 16 GB of HBM."""
    from lighthouse_tpu.bls import tpu_backend as tb

    builders = (tb._h2c_stage, tb._prep_stage, tb._pair_stage)
    for b in builders:
        b.cache_clear()
    try:
        lowered = dict(tb.stage_lowerings(64, 512, 1 << 20, sharding=one_chip))
        compiled = lowered[stage].compile()
    finally:
        for b in builders:
            b.cache_clear()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16 * 10**9


@pytest.mark.slow
@pytest.mark.parametrize("stage", ["h2c", "prep", "verdict"])
def test_sharded_stage_compiles(stage, topo, tpu_pallas):
    """A stage of the per-shard-verdict serving path compiles for a
    described 2x2 v5e mesh at 4 shards x 16 sets, 512 keys, with the 2^20
    registry replicated."""
    import numpy as np
    from jax.sharding import Mesh

    from lighthouse_tpu.bls import tpu_backend as tb

    mesh = Mesh(np.array(topo.devices), ("sets",))
    builders = (
        tb._sharded_h2c_stage, tb._sharded_prep_stage, tb._sharded_verdict_stage
    )
    try:
        lowered = dict(tb.sharded_stage_lowerings(mesh, 64, 512, 1 << 20))
        compiled = lowered[stage].compile()
    finally:
        for b in builders:
            b.cache_clear()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(env_dir, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the package leaves the cache
    there; unset, it sits at a fixed path in the checkout."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "LIGHTHOUSE_TPU_NO_JIT_CACHE")}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c",
         "import lighthouse_tpu, jax; print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd=root, capture_output=True, text=True, check=True,
    ).stdout.strip().splitlines()[-1]
    if env_dir:
        assert out == str(tmp_path)
    else:
        assert os.path.dirname(out) == os.path.join(root, ".jax_cache")
