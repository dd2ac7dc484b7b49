"""Device-resident epoch engine: jit/sharded single-pass epoch processing.

The backend seam mirrors ``lighthouse_tpu.bls``: a module-level registry
selected by ``set_backend`` or the ``LIGHTHOUSE_EPOCH_BACKEND`` environment
variable, with everything above it (``per_epoch.process_epoch``, and through
it ``state_advance``/``beacon_chain``) backend-blind.

Backends:

* ``numpy``  — the columnar host path in ``state_transition/per_epoch.py``.
* ``device`` — the fused jitted sweep (``engine.py`` + ``kernels.py``) over
  a device-resident registry mirror (``mirror.py``). Covers every fork
  through electra (three kernel families: phase0 / altair-like / electra
  with its pending-deposit + consolidation queue stages); a fork newer
  than the kernel families falls back to numpy per-state.
* ``auto``   — the default: ``device`` when an accelerator platform (tpu/
  gpu) backs JAX, ``numpy`` otherwise, so CPU-only test tiers never pay
  kernel compiles they didn't ask for.

This module stays import-light (no jax) — the journal marks in block
processing must stay free when the engine is off.
"""

from __future__ import annotations

import os

from .deltas import (  # noqa: F401 — re-exported for the mutation sites
    invalidate_registry_journal,
    journal_of,
    mark_registry_delta,
)

_BACKEND = os.environ.get("LIGHTHOUSE_EPOCH_BACKEND", "auto")
_AUTO_DECISION: bool | None = None


def set_backend(name: str) -> None:
    global _BACKEND, _AUTO_DECISION
    if name not in ("auto", "device", "numpy"):
        raise ValueError(f"unknown epoch backend {name!r}")
    _BACKEND = name
    _AUTO_DECISION = None


def get_backend() -> str:
    return _BACKEND


def _accelerator_present() -> bool:
    """auto-mode probe, memoized: is JAX backed by an accelerator? Asks
    nothing beyond jax.devices() — callers in CPU-only tiers have already
    pinned JAX_PLATFORMS=cpu."""
    global _AUTO_DECISION
    if _AUTO_DECISION is None:
        try:
            import jax

            _AUTO_DECISION = jax.devices()[0].platform in ("tpu", "gpu")
        except Exception:  # noqa: BLE001 — no jax / no devices: numpy path
            _AUTO_DECISION = False
    return _AUTO_DECISION


def device_backend_active() -> bool:
    if _BACKEND == "numpy":
        return False
    if _BACKEND == "device":
        return True
    return _accelerator_present()


def maybe_process_epoch_on_device(spec, state, sharding=None) -> bool:
    """The ``process_epoch`` seam: True when the device engine fully handled
    the epoch transition, False when the numpy path should run.

    The device engine runs inside the ``epoch_device`` fault domain
    (resilience.supervisor): a faulted or quarantined sweep returns False
    with the state untouched, so the numpy twin owns that boundary —
    demotion, never a crashed slot. Exceptions from the *write-back* phase
    deliberately propagate: by then the state is partially mutated, and
    demoting to a second full numpy transition would apply the epoch twice
    (silent consensus corruption is strictly worse than a loud crash)."""
    if not device_backend_active():
        return False
    from .engine import process_epoch_on_device

    return process_epoch_on_device(spec, state, sharding=sharding)


def prepare_state(state, sharding=None):
    """Bind mirror + delta journal ahead of the first boundary (chain /
    state_advance warm-up). No-op unless the device backend is active."""
    if not device_backend_active():
        return None
    from .engine import prepare_state as _prep

    return _prep(state, sharding=sharding)


def engine_stats(state) -> dict | None:
    """Mirror counters for observability / the --epoch bench."""
    from .engine import mirror_of

    m = mirror_of(state)
    return None if m is None else m.stats.as_dict()
