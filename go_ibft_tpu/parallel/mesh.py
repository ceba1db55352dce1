"""Sharded fused quorum kernels over a ``jax.sharding.Mesh`` (shard_map).

Single-program multi-chip via ``shard_map`` with *explicit* collectives —
each chip verifies its slice of the message lanes (``dp`` axis) against its
slice of the validator table (``vp`` axis), then three small ``psum``s
assemble the global answer over ICI:

1. membership: a sender is a validator if *any* table shard matches
   (psum over ``vp``);
2. counted-validators: a validator is counted if *any* lane shard carried
   its valid message (psum over ``dp``);
3. voting power: the exact split-halves sum over table shards
   (psum over ``vp``).

shard_map (not GSPMD auto-partitioning) is deliberate: the 256-step EC
ladder compiles once for the *local* shard shape — partitioning the whole
program would re-run SPMD propagation through the scan and multiply
compile time; the collectives here are three scalar-ish psums, trivially
placed by hand.  This mirrors the scaling-book recipe: pick the mesh,
annotate the data, let the per-shard program stay identical to the
single-chip one.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import quorum

__all__ = [
    "make_mesh",
    "mesh_context",
    "mesh_quorum_certify",
    "mesh_seal_quorum_certify",
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


def _finish(reached_inputs):
    ok, eq, powers_lo, powers_hi, thr_lo, thr_hi = reached_inputs
    # a validator row is counted if any of *this* lane-shard's valid
    # messages matched it; then OR across lane shards.
    counted_local = jnp.any(eq & ok[:, None], axis=0).astype(jnp.int32)
    counted = jax.lax.psum(counted_local, "dp") > 0  # (V_local,)
    lo = jax.lax.psum(jnp.sum(jnp.where(counted, powers_lo, 0)), "vp")
    hi = jax.lax.psum(jnp.sum(jnp.where(counted, powers_hi, 0)), "vp")
    hi = hi + (lo >> 16)
    lo = lo & 0xFFFF
    reached = (hi > thr_hi) | ((hi == thr_hi) & (lo >= thr_lo))
    return reached, lo, hi


def mesh_quorum_certify(mesh: Mesh):
    """Sharded :func:`~go_ibft_tpu.ops.quorum.quorum_certify` (same
    signature/outputs, bit-identical results)."""

    lane = P("dp")
    vrow = P("vp")
    rep = P()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(lane, lane, lane, lane, lane, lane, vrow, lane, vrow, vrow, rep, rep),
        out_specs=(lane, rep, rep, rep),
        check_vma=False,
    )
    def step(blocks, nblocks, r, s, v, sender_w, table_w, live,
             powers_lo, powers_hi, thr_lo, thr_hi):
        sig_ok = quorum.sender_sig_checks(blocks, nblocks, r, s, v, sender_w, live)
        eq = quorum.membership_eq(sender_w, table_w)  # (B_loc, V_loc)
        member = jax.lax.psum(jnp.any(eq, axis=-1).astype(jnp.int32), "vp") > 0
        ok = sig_ok & member
        reached, lo, hi = _finish((ok, eq, powers_lo, powers_hi, thr_lo, thr_hi))
        return ok, reached, lo, hi

    return jax.jit(step)


def mesh_seal_quorum_certify(mesh: Mesh):
    """Sharded :func:`~go_ibft_tpu.ops.quorum.seal_quorum_certify`."""

    lane = P("dp")
    vrow = P("vp")
    rep = P()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(lane, lane, lane, lane, lane, vrow, lane, vrow, vrow, rep, rep),
        out_specs=(lane, rep, rep, rep),
        check_vma=False,
    )
    def step(hash_zw, r, s, v, signer_w, table_w, live,
             powers_lo, powers_hi, thr_lo, thr_hi):
        sig_ok = quorum.seal_sig_checks(hash_zw, r, s, v, signer_w, live)
        eq = quorum.membership_eq(signer_w, table_w)
        member = jax.lax.psum(jnp.any(eq, axis=-1).astype(jnp.int32), "vp") > 0
        ok = sig_ok & member
        reached, lo, hi = _finish((ok, eq, powers_lo, powers_hi, thr_lo, thr_hi))
        return ok, reached, lo, hi

    return jax.jit(step)
