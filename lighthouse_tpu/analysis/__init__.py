"""Static-analysis subsystem: machine-checked kernel + concurrency
certification.

Six passes, run in tier-1 CI (``tests/test_analysis.py``) and by hand via
``python -m lighthouse_tpu.analysis``:

* **Pass 1 — limb-bound certifier** (``bounds.py``): re-executes every
  fq/fq2 op graph abstractly (``jax.eval_shape``) with a certification sink
  installed in ``ops/bls/fq.py``/``plans.py``, so every statically-derived
  bound — f64/f32 convolution exactness, u32/u64 accumulator wrap safety,
  reduction-walk targets, lazy ``CHAIN_BOUND`` fixed points — is recorded
  as a (proven, declared) proof obligation per conv backend. Emits
  ``BOUNDS_CERT.json``; any unproven edge fails the pass loudly.
* **Pass 2 — trace-hygiene linter** (``hygiene.py``): an AST pass over
  ``lighthouse_tpu/`` flagging jit anti-patterns (host syncs, Python
  branches on tracers, unhashable static-argnum values, impure closures)
  with a ``# lint: allow(<rule>)`` pragma and a checked-in baseline.
* **Pass 3 — recompilation sentinel** (``recompile.py``): a
  compilation-count hook (``jax_log_compiles`` capture) asserting that
  steady-state loops — the firehose verify pipeline, the epoch-engine
  sweep — trigger ZERO recompiles after warm-up; ``recompile_probe()``
  is the CLI's cheap in-process check of the capture plumbing.
* **Pass 4 — supervisor-transparency probe** (``supervised.py``): the
  resilience wrappers lint clean, add zero steady-state recompiles, and
  return the kernel's result bit for bit.
* **Pass 5 — concurrency certifier** (``concurrency.py``): lock-discipline
  proofs over every module importing ``threading`` (guard inference,
  unguarded shared mutations, thread-lifecycle joins), a package-wide
  acquires-while-holding lock-order graph that must stay acyclic with a
  blocking-call-under-lock rule, and an env-gated runtime lockdep wrapper
  (``LIGHTHOUSE_LOCKDEP=1``) whose observed acquisition orders are merged
  back into the static graph. Emits ``CONCURRENCY_CERT.json``.
* **Pass 6 — device-memory certifier & footprint planner** (``memory.py``):
  abstractly re-executes every registry graph under all three conv
  backends x both batch regimes, recording argument/output/temp/peak
  bytes per row (``jax.eval_shape`` + a jaxpr liveness walk, with XLA's
  lowered-computation cost analysis cross-checking a subset); walks every
  pallas VMEM tile signature against declared per-tier VMEM caps; models
  the five device-resident subsystem plane families (epoch mirror,
  slasher spans, LC committee cache, KZG tables, firehose staging) as
  static ``*_bytes(config)`` functions parity-pinned against real
  ``device_put`` accounting; and derives ``max_safe_shape(graph, tier)``,
  the largest certified batch per device tier.
  Emits ``MEMORY_CERT.json``; a row that fits no declared finite tier
  fails the certificate exactly like a tripped bound.
"""

from .bounds import certify, certify_callable, write_cert  # noqa: F401
from .concurrency import (  # noqa: F401
    certify_concurrency,
    lockdep_enabled,
    merge_observed,
)
from .hygiene import lint_tree  # noqa: F401
from .memory import (  # noqa: F401
    DEVICE_TIERS,
    certify_memory,
    epoch_mirror_bytes,
    fault_memory_context,
    firehose_staging_bytes,
    kzg_table_bytes,
    lc_committee_cache_bytes,
    max_safe_shape,
    rung_fit,
    slasher_span_bytes,
)
from .recompile import (  # noqa: F401
    CompilationSentinel,
    recompile_probe,
    steady_state_compiles,
)
