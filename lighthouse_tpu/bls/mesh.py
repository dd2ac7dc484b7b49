"""Serving-mesh resolution + the jax glue for the sharded BLS serving tier.

The data-parallel serving tier (``firehose/sharding.py``) is deliberately
jax-free so its fault-domain logic stays unit-testable; everything that
touches devices lives here:

* **env knob** — ``LIGHTHOUSE_MESH_DEVICES`` selects the serving mesh size:
  unset/``0``/``1``/``off`` disables the mesh (the single-device engine,
  bit-identical to the pre-mesh code path), ``auto`` takes every visible
  device, an integer takes that many. The size is floored to a power of two
  (fixed-shape compile families; mesh halving stays shape-stable). It
  drives the chain's sharded tier only (its indexed batches and the
  firehose, through ``MeshVerifier``) and the slasher's validator
  sharding; the generic ``bls.verify_signature_sets`` seam always runs on
  one device.
* **mesh cache** — one ``jax.sharding.Mesh`` per device subset, so the
  degradation ladder's shrunken meshes (N -> N/2 -> ...) reuse compiled
  programs across calls.
* **dispatch glue** — ``make_mesh_backend`` binds the per-shard-verdict
  kernels (``tpu_backend.verify_staged_pershard``) into the ``stage`` /
  ``dispatch`` / ``probe`` callables the jax-free ``MeshVerifier`` consumes.
"""

from __future__ import annotations

import functools
import os

import numpy as np

ENV_VAR = "LIGHTHOUSE_MESH_DEVICES"


def pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def requested_mesh_devices() -> int | str:
    """Raw knob value: 0 (disabled), an int, or "auto"."""
    raw = os.environ.get(ENV_VAR, "").strip().lower()
    if raw in ("", "0", "1", "off", "none", "no"):
        return 0
    if raw == "auto":
        return "auto"
    try:
        return max(0, int(raw))
    except ValueError:
        return 0


def serving_mesh_size() -> int:
    """Resolved serving-mesh size: 1 when the mesh is disabled (the
    single-device engine — bit-identical to today), else the power-of-two
    floor of min(requested, visible devices). Never initiates a device
    probe beyond ``jax.devices()`` (callers have already pinned the
    platform)."""
    req = requested_mesh_devices()
    if req == 0:
        return 1
    try:
        import jax

        avail = len(jax.devices())
    except Exception:  # noqa: BLE001 — no usable backend: mesh off
        return 1
    n = avail if req == "auto" else min(req, avail)
    return pow2_floor(max(1, n))


@functools.lru_cache(maxsize=None)
def _mesh_for(device_ids: tuple) -> object:
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    return Mesh(np.array([devs[i] for i in device_ids]), axis_names=("sets",))


def get_mesh(device_ids) -> object:
    """Cached ``Mesh`` over the given device indices (``sets`` axis)."""
    return _mesh_for(tuple(int(i) for i in device_ids))


class MeshBackend:
    """The jax side of the serving tier: staging (host prep + per-shard
    async H2D), dispatch (the per-shard-verdict kernel family), and a
    per-device liveness probe for fault attribution. ``cache_fn`` resolves
    the device-resident pubkey cache at call time (it grows with the
    validator registry)."""

    def __init__(self, cache_fn):
        self.cache_fn = cache_fn

    def stage(self, shard_items, device_ids, shard_cap: int):
        """Host stage + sharded transfer for one tick's sub-batches —
        called from the firehose prep thread to double-buffer H2D against
        the device thread's in-flight verify."""
        from . import tpu_backend as tb

        mesh = get_mesh(device_ids)
        staged = tb.stage_indexed_shards(shard_items, shard_cap)
        return tb.put_staged(staged, mesh)

    def dispatch(self, shard_items, device_ids, staged=None,
                 shard_cap: int | None = None):
        """Per-shard verdicts for one tick. ``staged`` (from ``stage``)
        skips re-staging on the fast path; the ladder's re-staging rungs
        pass fresh ``shard_items``."""
        from . import tpu_backend as tb

        mesh = get_mesh(device_ids)
        if staged is None:
            staged = tb.stage_indexed_shards(
                shard_items,
                shard_cap or tb.bucket(
                    max((len(sh) for sh in shard_items), default=1)
                ),
            )
            staged = tb.put_staged(staged, mesh)
        oks = tb.verify_staged_pershard(self.cache_fn(), staged, mesh)
        return [bool(o) for o in np.asarray(oks)]

    def probe(self, device_id: int) -> None:
        """One tiny op pinned to one device — the fault-attribution probe
        the supervisor ladder runs after an unattributed mesh fault."""
        import jax
        import jax.numpy as jnp

        dev = jax.devices()[device_id]
        out = jax.device_put(jnp.arange(4, dtype=jnp.uint32), dev).sum()
        out.block_until_ready()


def make_mesh_backend(cache_fn) -> MeshBackend:
    return MeshBackend(cache_fn)
