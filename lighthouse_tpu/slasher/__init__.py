"""Slashing detection with device min/max target arrays (ref slasher/).

SURVEY.md §5 calls the reference's chunked 2D epoch x validator arrays "the
closest thing to blockwise attention" in the codebase; this package is that
workload rebuilt TPU-first — scatter + directional cumulative scans over
validator tiles instead of per-validator epoch walk loops.

Two implementations share the ``SlasherService`` surface:

* the seed per-row path (``slasher.py`` + ``arrays.py`` + ``db.py``):
  validator-chunk rows loaded through the KV store per batch — the
  DB-backed reference twin, kept as the parity oracle;
* the device-resident engine (``engine.py`` + ``kernels.py``): ONE
  ``[n_validators, history_length]`` span store living on device across
  ticks, per-batch update + double/surround detection as one fused sweep.

The backend seam mirrors ``LIGHTHOUSE_EPOCH_BACKEND``: ``set_backend`` or
the ``LIGHTHOUSE_SLASHER_BACKEND`` environment variable selects

* ``numpy``  — the engine on its field-for-field numpy twin (no jax import);
* ``device`` — the engine on the fused jitted sweep (``kernels.py``);
* ``auto``   — the default: ``device`` when an accelerator platform backs
  JAX, ``numpy`` otherwise, so CPU-only test tiers never pay kernel
  compiles they didn't ask for.

This module stays import-light (no jax, no engine import until
``make_slasher`` runs).
"""

from __future__ import annotations

import os

from .config import MAX_DISTANCE, SlasherConfig
from .db import SlasherDB
from .service import SlasherService
from .slasher import Slasher

__all__ = [
    "MAX_DISTANCE",
    "Slasher",
    "SlasherConfig",
    "SlasherDB",
    "SlasherService",
    "device_backend_active",
    "get_backend",
    "make_slasher",
    "set_backend",
]

_BACKEND = os.environ.get("LIGHTHOUSE_SLASHER_BACKEND", "auto")
_AUTO_DECISION: bool | None = None


def set_backend(name: str) -> None:
    global _BACKEND, _AUTO_DECISION
    if name not in ("auto", "device", "numpy"):
        raise ValueError(f"unknown slasher backend {name!r}")
    _BACKEND = name
    _AUTO_DECISION = None


def get_backend() -> str:
    return _BACKEND


def _accelerator_present() -> bool:
    """auto-mode probe, memoized (the epoch-engine pattern): asks nothing
    beyond jax.devices() — CPU-only tiers have already pinned
    JAX_PLATFORMS=cpu."""
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


def make_slasher(store=None, types=None, config: SlasherConfig | None = None,
                 **kw):
    """Construct the engine-backed slasher behind the backend seam (the
    client / local-network assembly point). ``store`` is accepted for
    call-site compatibility with the seed ``Slasher``; the engine keeps its
    record index in memory and prunes it with the window.

    With no explicit config, the surveillance window comes from
    ``LIGHTHOUSE_SLASHER_HISTORY`` (default: the reference's 4096 epochs).
    The engine's planes are DENSE — 8 bytes per validator-epoch cell — so
    a large registry should size the window to its memory budget (1M
    validators x 4096 epochs ~ 32 GB; x 512 ~ 4 GB); the drop window for
    old evidence shrinks with it, exactly like a reference node configured
    with a shorter ``--slasher-history-length``.
    """
    from .engine import EngineSlasher

    if config is None:
        raw = os.environ.get("LIGHTHOUSE_SLASHER_HISTORY", "").strip()
        history = int(raw) if raw else SlasherConfig().history_length
        config = SlasherConfig(history_length=history)
    slasher = EngineSlasher(store, types, config, **kw)
    if store is not None:
        # restart-from-disk: rehydrate the surveillance window from the
        # last checkpoint (engine.persist) so pre-restart votes still
        # convict a post-restart equivocator
        try:
            slasher.restore()
        except Exception as e:  # noqa: BLE001 — corrupt checkpoint: start fresh
            from ..utils.logging import get_logger

            get_logger("slasher").warning(
                "Slasher checkpoint restore failed", error=str(e)
            )
    return slasher
