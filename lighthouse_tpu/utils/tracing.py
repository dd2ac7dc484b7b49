"""Spans on the profiler's clock, and the compile-phase counter.

``span(name, **attrs)`` enters a ``jax.profiler.TraceAnnotation``: while a
profiler session runs (``jax.profiler.start_trace``), the span lands in the
same trace, on the same clock, as the device ops it dispatches; with no
session it costs a few microseconds (2.8 us on a TPU v5e host). There is no
span store, exporter or switch of its own: tracing is on exactly while a
profiler session runs.

Every span carries a ``batch`` attribute, the id of the chain batch it
serves. ``batch_span`` draws a new id and holds it in a ``ContextVar``, so
it follows the call into worker threads that run inside a copy of the
caller's context (``resilience.supervisor``). Outside a batch it is 0.

A leaf span may also time itself into a labelled histogram (``timer=``),
the operator's view of the same phases on ``/metrics``.

Names the program uses (the per-layer benchmark metrics read them from a
trace, ``benchmark/programtrace.py``):

    chain.batch_verify            beacon_chain.chain._batch_verify_items
      resilience.rung             each supervised attempt, on the watchdog
                                  worker thread (stage, rung)
        bls.verify_indexed        tpu_backend.verify_indexed_sets_device
                                  (n, n_pad, k_pad)
          bls.host_prep
            bls.pack              index / mask / signature byte arrays
            bls.parse_signatures  parse_g2_bytes
            bls.hash_to_field     hash_to_field_host: the padded batch's
                                  u0, u1 as host arrays
            bls.scalars           the random linear-combination scalars
          bls.dispatch            host->device transfers, the three stage
                                  calls as they are enqueued
          bls.await_verdict       the wait for the batch's verdict

The leaves under ``bls.verify_indexed`` observe
``bls_verify_phase_seconds{phase=<leaf name>}``.

The compile listener, installed when ``lighthouse_tpu`` is imported, feeds
``jax_compile_phase_seconds{phase, fun}`` from JAX's own monitoring events:
``trace`` (jaxpr tracing), ``lower`` (jaxpr -> MLIR) and ``compile`` (the
backend compile, or the load on a persistent-cache hit). ``fun`` is the
event's function name. JAX reports a trace event for every jitted function
traced inside another one; those nested events are part of the outermost
trace's time and are not observed again. A fourth phase, ``load``, is the
stage store's own (``utils/stage_store.py``): reading, writing and
(de)serializing a stored stage, and tracing the thin call around it, which
``compile_phase`` times under the stage's name. So the histogram's sum is
the time the process spent in the compile layer.

``bls_stage_store_total{stage, outcome}`` counts how each stored stage was
resolved in this process: ``hit`` (loaded, never traced), ``miss`` (traced
and stored) or ``invalid`` (a bad entry, deleted, traced and stored again).

``bls_pallas_kernels_total{layout}`` counts the fused Pallas kernel calls
traced in this process by the block layout their shape chose: ``lane``,
``packed`` or ``digit_major`` (``ops/bls/pallas_kernels.py``). It counts at
trace time, so a stage loaded from the store adds nothing.
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from contextlib import contextmanager

import jax

from .metrics import REGISTRY, Histogram

VERIFY_PHASE_SECONDS = REGISTRY.histogram(
    "bls_verify_phase_seconds",
    "Host time per phase of one batched BLS verify call "
    "(bls/tpu_backend.py verify_indexed_sets_device)",
    label_names=("phase",),
)
COMPILE_PHASE_SECONDS = REGISTRY.histogram(
    "jax_compile_phase_seconds",
    "JAX tracing, lowering and backend compile (or persistent-cache load), "
    "and stage-store loads, time per jitted function",
    label_names=("phase", "fun"),
    buckets=(0.01, 0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0),
)

STAGE_STORE_TOTAL = REGISTRY.counter(
    "bls_stage_store_total",
    "Device stages resolved from the stage store, by outcome "
    "(hit / miss / invalid; utils/stage_store.py)",
    label_names=("stage", "outcome"),
)

PALLAS_KERNELS_TOTAL = REGISTRY.counter(
    "bls_pallas_kernels_total",
    "Fused Pallas kernel calls traced, by block layout "
    "(lane / packed / digit_major; ops/bls/pallas_kernels.py)",
    label_names=("layout",),
)

_BATCH: contextvars.ContextVar[int] = contextvars.ContextVar("lighthouse_tpu_batch")
_batch_ids = itertools.count(1)


@contextmanager
def span(name: str, timer: Histogram | None = None, **attrs):
    """A profiler span ``name`` with ``attrs`` and the current batch id;
    ``timer`` also observes the span's duration under ``phase=name``."""
    with jax.profiler.TraceAnnotation(name, batch=_BATCH.get(0), **attrs):
        if timer is None:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            timer.observe(time.perf_counter() - t0, phase=name)


@contextmanager
def batch_span(name: str, **attrs):
    """``span`` under a new batch id, which every span inside it carries."""
    token = _BATCH.set(next(_batch_ids))
    try:
        with span(name, **attrs):
            yield
    finally:
        _BATCH.reset(token)


# -- the compile-phase listener ------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_PHASES = {
    _TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_trace_depth = threading.local()


def _on_enter(event: str, value: float, **kw) -> None:
    # JAX records a scalar (the start time) as it enters each phase
    if event == _TRACE_EVENT:
        _trace_depth.n = getattr(_trace_depth, "n", 0) + 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    phase = _COMPILE_PHASES.get(event)
    if phase is None:
        return
    if phase == "trace":
        depth = getattr(_trace_depth, "n", 1) - 1
        _trace_depth.n = max(depth, 0)
        if depth > 0:
            return  # nested in an enclosing trace, which counts its time
    COMPILE_PHASE_SECONDS.observe(
        seconds, phase=phase, fun=str(kw.get("fun_name", ""))
    )


jax.monitoring.register_scalar_listener(_on_enter)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


@contextmanager
def compile_phase(phase: str, fun: str):
    """Observe the block's duration as ``jax_compile_phase_seconds{phase,
    fun}``; a JAX trace inside it is part of that time and is not observed
    on its own."""
    _trace_depth.n = getattr(_trace_depth, "n", 0) + 1
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _trace_depth.n -= 1
        COMPILE_PHASE_SECONDS.observe(time.perf_counter() - t0, phase=phase,
                                      fun=fun)


def compile_counts() -> dict[str, int]:
    """Backend compiles (or persistent-cache loads) so far, per function."""
    return {
        fun: n
        for (phase, fun), n in COMPILE_PHASE_SECONDS.totals().items()
        if phase == "compile"
    }
