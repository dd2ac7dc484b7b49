"""Spans, phase timers and the compile listener (``utils/tracing.py``).

The device stages are stubbed out: these tests trace the host side of the
chain's batch-verify path under a short CPU profiler session and read the
spans back from the trace, as the benchmark reads a chip trace.
"""

import contextvars
import glob
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu import bls
from lighthouse_tpu.beacon_chain.chain import BeaconChain
from lighthouse_tpu.bls import tpu_backend as tb
from lighthouse_tpu.resilience.supervisor import (
    BackendSupervisor,
    SupervisorConfig,
    run_with_deadline,
)
from lighthouse_tpu.utils import tracing

HOST_PREP_LEAVES = (
    "bls.pack", "bls.parse_signatures", "bls.hash_to_field", "bls.scalars",
)
PHASES = HOST_PREP_LEAVES + ("bls.dispatch", "bls.await_verdict")


class _Chain:
    """The chain slice the batch-verify path reads (no beacon state)."""

    _batch_verify_items = BeaconChain._batch_verify_items
    _batch_verify_items_inner = BeaconChain._batch_verify_items_inner
    _mesh_planner = BeaconChain._mesh_planner

    def __init__(self):
        self.pubkey_cache = types.SimpleNamespace(
            device_array=lambda: jnp.zeros((16, 3, 25), jnp.uint64))
        self._mesh_planner_state = None


def _items(n: int) -> list:
    rng = np.random.default_rng(n)
    return [([int(i) for i in rng.integers(0, 16, size=3)],
             rng.bytes(32), rng.bytes(96)) for _ in range(n)]


@pytest.fixture
def stubbed_device(monkeypatch):
    """The tpu backend with the three device stages replaced by a verdict
    of True; returns the shapes each call dispatched."""
    calls = []

    def kernel(n_pad, k_pad):
        def run(*args):
            calls.append((n_pad, k_pad))
            return jnp.asarray(True)
        return run

    monkeypatch.setattr(tb, "_gathered_kernel", kernel)
    prev = bls.get_backend()
    bls.set_backend("tpu")
    yield calls
    bls.set_backend(prev)


def _host_spans(trace_dir: str) -> list:
    """(name, start_ns, end_ns, stats, thread line) of the program's spans."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.split(".")[0] in ("chain", "resilience", "bls"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), k))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_spans_nest_in_order_with_one_batch_id(stubbed_device, tmp_path):
    chain = _Chain()
    assert chain._batch_verify_items(_items(3))       # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert chain._batch_verify_items(_items(5))
        assert chain._batch_verify_items(_items(6))
    finally:
        jax.profiler.stop_trace()
    assert stubbed_device == [(4, 4), (8, 4), (8, 4)]

    spans = _host_spans(str(tmp_path))
    calls = [s for s in spans if s[0] == "chain.batch_verify"]
    assert len(calls) == 2
    batches = [c[3]["batch"] for c in calls]
    assert batches[0] > 0 and batches[1] == batches[0] + 1
    order = ["chain.batch_verify", "resilience.rung", "bls.verify_indexed",
             "bls.host_prep", *HOST_PREP_LEAVES, "bls.dispatch",
             "bls.await_verdict"]
    parent = {"resilience.rung": "chain.batch_verify",
              "bls.verify_indexed": "resilience.rung",
              "bls.host_prep": "bls.verify_indexed",
              "bls.dispatch": "bls.verify_indexed",
              "bls.await_verdict": "bls.verify_indexed",
              **dict.fromkeys(HOST_PREP_LEAVES, "bls.host_prep")}
    for call, batch, n, n_pad in zip(calls, batches, (5, 6), (8, 8)):
        mine = [s for s in spans if s[3]["batch"] == batch]
        assert [s[0] for s in mine] == order
        by_name = {s[0]: s for s in mine}
        for child, up in parent.items():
            c, p = by_name[child], by_name[up]
            assert p[1] <= c[1] and c[2] <= p[2], (child, up)
        # siblings one after another
        for a, b in zip(order[4:], order[5:]):
            assert by_name[a][2] <= by_name[b][1], (a, b)
        assert by_name["bls.dispatch"][1] >= by_name["bls.host_prep"][2]
        # the watchdog hop: the rung runs on another thread than the call
        assert by_name["resilience.rung"][4] != call[4]
        assert by_name["resilience.rung"][3]["stage"] == "bls.batch_verify"
        assert by_name["resilience.rung"][3]["rung"] == "device_full"
        v = by_name["bls.verify_indexed"][3]
        assert (v["n"], v["n_pad"], v["k_pad"]) == (n, n_pad, 4)


def test_one_phase_observation_per_call(stubbed_device):
    before = tracing.VERIFY_PHASE_SECONDS.totals()
    chain = _Chain()
    for n in (2, 7, 9):
        assert chain._batch_verify_items(_items(n))
    after = tracing.VERIFY_PHASE_SECONDS.totals()
    assert {k[0] for k in after} >= set(PHASES)
    for phase in PHASES:
        assert after[(phase,)] - before.get((phase,), 0) == 3, phase
    assert set(after) == {(p,) for p in PHASES}


def test_stage_jits_have_stable_names():
    assert tb._h2c_stage(4).__name__ == "bls_h2c"
    assert tb._prep_stage(4, 4).__name__ == "bls_prep"
    assert tb._pair_stage(4).__name__ == "bls_pair"
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("sets",))
    assert tb._sharded_h2c_stage(mesh, 4).__name__ == "bls_h2c_sharded"
    assert tb._sharded_prep_stage(mesh, 4, 4).__name__ == "bls_prep_sharded"
    assert tb._sharded_verdict_stage(mesh, 4).__name__ == "bls_verdict_sharded"


def test_compile_listener_counts_one_compile_per_new_program():
    @jax.jit
    def tracing_probe_fn(x):
        return x * 3 + 1

    key = ("compile", "jit(tracing_probe_fn)")
    x = np.arange(5, dtype=np.int32)
    before = tracing.COMPILE_PHASE_SECONDS.totals().get(key, 0)
    tracing_probe_fn(x).block_until_ready()
    first = tracing.COMPILE_PHASE_SECONDS.totals()
    assert first.get(key, 0) - before == 1
    assert first.get(("trace", "tracing_probe_fn"), 0) >= 1
    tracing_probe_fn(x).block_until_ready()
    second = tracing.COMPILE_PHASE_SECONDS.totals()
    assert second.get(key, 0) == first[key]
    assert tracing.compile_counts()["jit(tracing_probe_fn)"] == first[key]


def test_nested_traces_are_not_counted_twice():
    @jax.jit
    def inner_probe(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer_probe(x):
        return inner_probe(x) + jnp.cumsum(x)

    before = tracing.COMPILE_PHASE_SECONDS.totals()
    outer_probe(np.ones(4, np.float32)).block_until_ready()
    after = tracing.COMPILE_PHASE_SECONDS.totals()
    new = {k: n - before.get(k, 0) for k, n in after.items()
           if n != before.get(k, 0)}
    assert new[("trace", "outer_probe")] == 1
    assert ("trace", "inner_probe") not in new
    assert new[("compile", "jit(outer_probe)")] == 1


def test_compile_phase_holds_the_traces_inside_it():
    @jax.jit
    def phase_probe(x):
        return x * 5

    before = tracing.COMPILE_PHASE_SECONDS.totals()
    with tracing.compile_phase("load", "phase_probe"):
        traced = phase_probe.trace(np.ones(3, np.float32))
    traced.lower().compile()
    after = tracing.COMPILE_PHASE_SECONDS.totals()
    new = {k: n - before.get(k, 0) for k, n in after.items()
           if n != before.get(k, 0)}
    assert new == {("load", "phase_probe"): 1,
                   ("lower", "jit(phase_probe)"): 1,
                   ("compile", "jit(phase_probe)"): 1}


_VAR = contextvars.ContextVar("test_tracing_var")


def test_context_follows_the_watchdog_hop():
    sup = BackendSupervisor("test-tracing", SupervisorConfig(deadline_s=30))
    token = _VAR.set("caller")
    try:
        seen = sup.run("test.stage", lambda: (_VAR.get(None),
                                              tracing._BATCH.get(0)))
        assert seen == ("caller", 0)
        assert run_with_deadline("test.probe", lambda: _VAR.get(None), 30) \
            == "caller"
        with tracing.batch_span("test.batch"):
            batch = tracing._BATCH.get(0)
            assert batch > 0
            assert sup.run("test.stage", lambda: tracing._BATCH.get(0)) == batch
        assert tracing._BATCH.get(0) == 0
    finally:
        _VAR.reset(token)


def test_pallas_kernel_layouts_counted_at_trace_time(monkeypatch):
    """Each fused Pallas kernel traced counts once under the layout its rows
    chose, and the counter is on the registry's exposition."""
    from lighthouse_tpu.ops.bls import fq, pallas_kernels as pk
    from lighthouse_tpu.utils.metrics import REGISTRY

    monkeypatch.setattr(fq, "_CONV_IMPL", "pallas")

    def counts():
        return {k[0]: v for k, _, v in tracing.PALLAS_KERNELS_TOTAL.collect()}

    before = counts()
    small = jax.ShapeDtypeStruct((pk.DIGIT_MAJOR_MIN_ROWS - 1, 25), jnp.uint64)
    mid = jax.ShapeDtypeStruct((pk.DIGIT_MAJOR_MIN_ROWS, 25), jnp.uint64)
    wide = jax.ShapeDtypeStruct((pk.FULL_BLOCK_ROWS, 25), jnp.uint64)
    jax.eval_shape(lambda a, b: fq.mont_mul(a, b), small, small)
    jax.eval_shape(lambda a, b: (fq.mont_mul(a, b), fq.mont_mul(b, a)), wide, wide)
    jax.eval_shape(lambda a, b: [fq.mont_mul(a, b) for _ in range(3)], mid, mid)
    after = counts()
    assert after.get("lane", 0) - before.get("lane", 0) == 1
    assert after.get("digit_major", 0) - before.get("digit_major", 0) == 2
    assert after.get("packed", 0) - before.get("packed", 0) == 3
    assert 'bls_pallas_kernels_total{layout="packed"}' in REGISTRY.render()
    assert 'bls_pallas_kernels_total{layout="digit_major"}' in REGISTRY.render()
