"""``(dp, vp)`` device meshes for the lane-sharded verification program.

``verify/mesh_batch.py::mesh_verify_mask`` lays message lanes over the ``dp``
axis with ``shard_map`` (not GSPMD auto-partitioning: the EC ladder compiles
once for the *local* shard shape, and the per-shard program stays identical
to the single-chip one).  This module is the ONE place such a mesh is built.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = [
    "make_mesh",
    "mesh_context",
]


def make_mesh(
    n_devices: Optional[int] = None, *, vp: int = 1, devices=None
) -> Mesh:
    """A ``(dp, vp)`` mesh over ``n_devices`` devices.

    ``vp`` shards the validator table (for very large sets); the rest of
    the devices go to ``dp`` (message lanes).  A mesh the default platform
    cannot supply is an error — never a CPU mesh under a process that was
    given an accelerator.  Multi-chip layouts stay testable on any host by
    pinning the CPU (``JAX_PLATFORMS=cpu`` +
    ``--xla_force_host_platform_device_count``) or passing ``devices=``.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(f"need {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    n = len(devices)
    if n % vp:
        raise ValueError(f"{n} devices not divisible by vp={vp}")
    arr = np.asarray(devices).reshape(n // vp, vp)
    return Mesh(arr, ("dp", "vp"))


def mesh_context(
    dp: Optional[int] = None, *, vp: int = 1, devices=None
) -> Optional[Mesh]:
    """Best-effort ``(dp, vp)`` mesh over whatever devices are visible.

    The ONE mesh-construction path shared by
    :class:`~go_ibft_tpu.verify.mesh_batch.MeshBatchVerifier`, the
    ``__graft_entry__`` dryrun, and the test/bench harnesses — so device
    enumeration, the 1-device fallback, and platform pinning can never
    drift between them:

    * **Device enumeration.**  ``devices`` wins when given; otherwise
      ``jax.devices()`` under whatever platform pin is in force
      (``JAX_PLATFORMS`` / ``jax.config.update("jax_platforms", ...)`` —
      this function never overrides the ambient pin, and never reaches
      for the host CPU devices when the default platform is short: a
      sharded program silently running on the CPU under a TPU process is
      exactly the hidden fallback this path must not have).
    * **dp selection.**  ``dp=None`` takes every visible device (after
      reserving ``vp``); an explicit ``dp`` is clamped to what exists.
    * **1-device fallback.**  Returns ``None`` when no layout with more
      than one device exists — the signal for callers to degrade to the
      single-device path instead of paying shard_map overhead for a
      1-shard mesh.  A dead backend (``jax.devices()`` raising) also
      returns ``None``: mesh construction must never take a node down.
    """
    want = None if dp is None else dp * vp
    if devices is None:
        try:
            devices = jax.devices()
        except RuntimeError:
            return None
    n = len(devices) if want is None else min(want, len(devices))
    # Round dp down to what divides cleanly over vp.
    n -= n % max(vp, 1)
    if n // max(vp, 1) < 2:
        return None
    return make_mesh(n, vp=vp, devices=devices[:n])
