"""The system under test and the measured window.

``RegistryChain`` is the slice of ``BeaconChain`` that the batch-verify
path reads, carrying the chain's own methods: the device pubkey registry
and the mesh planner slot (off on one chip). It runs the chain's code
without a 2^20-validator beacon state. The benchmark wraps
``_batch_verify_items`` in a span of its own and reaches nothing below it.

``replay`` is the closed loop: one batch call after another.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from .generator import Work


@dataclass
class Spans:
    """The benchmark's host spans: (name, start, end) on the monotonic
    clock, also written into a running profiler trace."""

    records: list = field(default_factory=list)
    lock: threading.Lock = field(default_factory=threading.Lock)

    def run(self, name: str, fn, *args):
        import jax

        t0 = time.monotonic()
        try:
            with jax.profiler.TraceAnnotation(name):
                return fn(*args)
        finally:
            t1 = time.monotonic()
            with self.lock:
                self.records.append((name, t0, t1))

    def of(self, name: str, since: float = float("-inf")) -> list:
        with self.lock:
            return [r for r in self.records if r[0] == name and r[1] >= since]


def registry_chain(cache, work: Work, spans: Spans, wrap=None):
    """The chain slice over the device ``cache``; ``wrap(verify) -> verify``
    puts something else in the place of ``_batch_verify_items`` (the
    control and the fault tests)."""
    import functools
    import types

    from lighthouse_tpu.beacon_chain.chain import BeaconChain
    from lighthouse_tpu.types.spec import mainnet_spec

    spec = mainnet_spec()
    chain = work.chain
    if (spec.preset.SLOTS_PER_EPOCH != chain.slots_per_epoch
            or spec.genesis_fork_version != chain.fork_version):
        raise ValueError("the configuration is not the program's mainnet phase0")

    class RegistryChain:
        _batch_verify_items_inner = BeaconChain._batch_verify_items_inner
        _mesh_planner = BeaconChain._mesh_planner
        _build_mesh_planner = BeaconChain._build_mesh_planner

        def __init__(self):
            self.spec = spec
            # the pubkey cache as the device path reads it
            self.pubkey_cache = types.SimpleNamespace(device_array=lambda: cache)
            self._mesh_planner_state = "unset"
            self._mesh_planner_lock = threading.Lock()
            program = functools.partial(BeaconChain._batch_verify_items, self)
            self._verify = wrap(program) if wrap is not None else program

        def _batch_verify_items(self, items) -> bool:
            return spans.run("bench.verify_call", self._verify, items)

        def _verify_items_via_sets(self, items, oracle: bool = False) -> bool:
            raise RuntimeError("the ladder reached its CPU oracle rung")

    return RegistryChain()


def items_of(sets) -> list:
    return [(s.indices, s.message, s.signature) for s in sets]


def warm_replay(chain, work: Work) -> None:
    """Load (or compile) the replay's one shape and run it twice."""
    warm = items_of(work.batches[0])
    for _ in range(2):
        chain._batch_verify_items(warm)


@dataclass
class ReplayResult:
    calls: list        # [(batch index, verdict, t0, t1)]
    t_open: float
    t_close: float


def replay(chain, work: Work, seconds: float, capture=None,
           trace_from: float = 0.0) -> ReplayResult:
    """Batches back to back, in epoch order, until ``seconds`` have passed;
    the rate is taken up to the end of the last call. ``capture`` traces
    from ``trace_from`` seconds on to the end."""
    batches = [items_of(b) for b in work.batches]
    calls = []
    tracing = None
    t_open = time.monotonic()
    k = 0
    while True:
        if capture is not None and time.monotonic() - t_open >= trace_from:
            capture.start()
            tracing, capture = capture, None
        b = k % len(batches)
        t0 = time.monotonic()
        ok = chain._batch_verify_items(batches[b])
        t1 = time.monotonic()
        calls.append((b, ok, t0, t1))
        k += 1
        if t1 - t_open >= seconds:
            break
    if tracing is not None:
        tracing.stop()
    return ReplayResult(calls=calls, t_open=t_open, t_close=t1)
