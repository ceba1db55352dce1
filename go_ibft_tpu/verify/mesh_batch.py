"""MeshBatchVerifier: the verify data plane sharded across the device mesh.

:class:`MeshBatchVerifier` is a
:class:`~go_ibft_tpu.verify.batch.DeviceBatchVerifier` whose dispatches
place packed lanes across a ``(dp, vp)`` mesh, with

* **lane-parallel sharding** — the lane axis is the data-parallel dim
  (``in_specs=P("dp")`` per lane array, validator table replicated); each
  device runs the UNCHANGED single-chip recovery ladder on its local lane
  slice, so the sharded program stays a thin shell around the single-chip
  one (the compile-budget pins enforce this per dp);
* **masked dummy-lane padding** — lane counts pad to ``bucket x dp`` so
  every shard gets an identical local shape; pad lanes are dead (``live``
  False) end to end, so no dummy verdict can leak into a quorum count
  (``tests/test_mesh_batch.py`` pins bit-identity to the sequential oracle
  at uneven remainders);
* **coalesced multi-drain dispatch** — the chunk capacity rises to
  ``largest bucket x dp``, so a multi-height sync range (or several
  chains' lanes) that used to cost dp sequential single-device dispatches
  is ONE sharded launch, still riding the double-buffered
  :class:`~go_ibft_tpu.verify.pipeline.VerifyPipeline`;
* **collective-free** — the sharded program returns the mask alone; the
  voting-power quorum is the caller's, on exact host ints, like every
  other route's;
* **transparent 1-device degradation** — when
  :func:`~go_ibft_tpu.parallel.mesh.mesh_context` finds a single device
  (or a dead backend) the instance behaves exactly as its
  ``DeviceBatchVerifier`` base: no shard_map program is ever built, no
  behavior changes.

Sharding choices mirror the SNIPPETS.md compile-plan harness: the jit
wrapper carries *explicit* ``in_shardings``/``out_shardings``
(``NamedSharding`` per ``in_specs``) so array placement is stated, not
inferred.  ``donate_argnums`` was re-evaluated for the sharded programs
and stays REJECTED, per the PR-1/PR-2 analysis which holds per shard: XLA
only aliases a donated input to an output of matching shape/dtype, and
these programs map ``(B, 20)`` limb vectors to a ``(B,)`` boolean mask —
nothing aliases, donation would emit a warning per compile and reuse
nothing.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import ledger as cost_ledger
from ..obs import trace
from ..ops import quorum
from ..ops import secp256k1 as sec
from ..parallel.mesh import mesh_context
from ..utils import metrics
from .batch import (
    _BATCH_BUCKETS,
    _bucket,
    EAGER_PUTS_KEY,
    DeviceBatchVerifier,
    ValidatorSource,
)

__all__ = ["MeshBatchVerifier", "mesh_verify_mask"]


def _mask_fn(zw, r, s, v, claimed, table, live):
    """Per-shard verification mask: the single-chip recovery ladder +
    membership compare, identical to ``batch._recover_fn`` — kept
    collective-free so the sharded program is embarrassingly parallel
    (the quorum is decided on the host)."""
    ok = quorum.sig_checks_zw(zw, r, s, v, claimed, live)
    member = jnp.any(quorum.membership_eq(claimed, table), axis=-1)
    return ok & member


# One compiled sharded-mask program per mesh (tests and the bench share
# meshes, so they share compiles; jit itself caches per input shape).
_MASK_KERNELS: Dict[Mesh, object] = {}


def mesh_verify_mask(mesh: Mesh):
    """Build (or reuse) the lane-sharded verification-mask program.

    ``shard_map`` over the mesh's ``dp`` axis: lane arrays shard on dim 0,
    the validator table replicates, the mask comes back lane-sharded.  The
    jit wrapper pins explicit ``in_shardings``/``out_shardings`` (the
    SNIPPETS.md compile-plan posture) so host numpy inputs are placed
    deterministically at the dispatch edge.
    """
    hit = _MASK_KERNELS.get(mesh)
    if hit is not None:
        return hit
    lane = P("dp")
    rep = P()
    in_specs = (lane, lane, lane, lane, lane, rep, lane)
    fn = shard_map(
        _mask_fn, mesh=mesh, in_specs=in_specs, out_specs=lane, check_vma=False
    )
    kernel = jax.jit(
        fn,
        in_shardings=tuple(NamedSharding(mesh, s) for s in in_specs),
        out_shardings=NamedSharding(mesh, lane),
        # donate_argnums deliberately empty: nothing aliases (see module
        # docstring) — stated explicitly so the decision is visible at the
        # compile plan, not implied by omission.
        donate_argnums=(),
    )
    _MASK_KERNELS[mesh] = kernel
    return kernel


class MeshBatchVerifier(DeviceBatchVerifier):
    """Lane-parallel sharded drain over the device mesh.

    Drop-in wherever a :class:`DeviceBatchVerifier` goes: the
    ``BatchVerifier`` protocol entry points (``verify_senders``,
    ``verify_committed_seals``, ``verify_seal_lanes``) inherit the
    parent's chunking/pipeline machinery and only the dispatch seam
    changes.

    ``mesh`` wins when given; otherwise :func:`mesh_context` enumerates
    devices (``dp``/``devices`` forwarded).  With one visible device the
    instance IS a ``DeviceBatchVerifier`` in behavior — ``self.mesh`` is
    ``None``, ``sharded`` False, and no shard_map program is built.
    """

    def __init__(
        self,
        validators_for_height: ValidatorSource,
        *,
        mesh: Optional[Mesh] = None,
        dp: Optional[int] = None,
        devices=None,
        cache_heights: int = 4,
    ):
        super().__init__(validators_for_height, cache_heights=cache_heights)
        if mesh is None:
            mesh = mesh_context(dp, devices=devices)
        if mesh is not None and mesh.devices.size < 2:
            mesh = None
        self.mesh = mesh
        self.dp = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
        if mesh is not None:
            self._mask_kernel = mesh_verify_mask(mesh)
            self._dispatch_cap = _BATCH_BUCKETS[-1] * self.dp
            self._route = "mesh"
            # The sharded mask program has its own compile-budget family
            # (the ``mesh_verify_mask_8l_dp*`` pins).
            self._program = "mesh_verify_mask"

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    # -- pad/placement seams --------------------------------------------

    def _pad_lanes(self, n: int) -> int:
        """Smallest ``bucket x dp`` lane count holding ``n`` lanes.

        The per-shard shape is the bucket of ``ceil(n / dp)``, so the
        local program compiles at the same lane buckets as the
        single-device kernels; the global pad lanes are dead (``live``
        False) and their verdicts are sliced off before any caller sees
        them."""
        if self.mesh is None or n == 0:
            return 0
        return _bucket((n + self.dp - 1) // self.dp, _BATCH_BUCKETS) * self.dp

    def _joint_lanes(self, n: int) -> int:
        """Sharded sender chunks keep their ``bucket x dp`` shape."""
        return 0 if self.mesh is not None else super()._joint_lanes(n)

    def _seal_riders(self, sub, height: int):
        """A sharded drain carries no seals: envelopes and seals keep their
        separate drains, as before ISSUE 32 (the sharded path has not been
        timed on a chip; what the joint dispatch buys is a single device's
        tile fill and one ladder executable).  The one-device degradation
        is the parent's."""
        return [] if self.mesh is not None else super()._seal_riders(sub, height)

    def _put_table(self, table: np.ndarray) -> jnp.ndarray:
        """Validator table replicated across the mesh (uploaded once a
        set, like the parent's single-device pin)."""
        if self.mesh is None:
            return super()._put_table(table)
        return jax.device_put(table, NamedSharding(self.mesh, P()))

    # -- dispatch -------------------------------------------------------

    def _dispatch_async(self, inputs, table):
        """Queue one sharded mask dispatch."""
        if self.mesh is None:
            return super()._dispatch_async(inputs, table)
        zw, r, s, v, claimed, live = inputs
        lanes = int(np.shape(live)[0])
        with cost_ledger.dispatch_span(
            self._program,
            route=self._route,
            live_mask=live,
            kernels=((self._program, self._mask_kernel),),
            block=False,
            site="verify/mesh_batch.py:_dispatch_async",
        ):
            with trace.span(
                "verify.shard",
                devices=self.dp,
                lanes=lanes,
                lanes_per_device=lanes // self.dp,
            ):
                with trace.span("verify.dispatch", route="mesh"):
                    # Placed one by one ahead of the call: the sharded
                    # route's own staging, not timed on a chip (counted).
                    metrics.inc_counter(EAGER_PUTS_KEY, 6)
                    return self._mask_kernel(
                        jnp.asarray(zw),
                        jnp.asarray(r),
                        jnp.asarray(s),
                        jnp.asarray(v),
                        jnp.asarray(claimed),
                        table,
                        jnp.asarray(live),
                    )

    def warmup(
        self,
        lanes: Sequence[int] = (8,),
        blocks: Sequence[int] = (2, 8),
        table_rows: int = 8,
    ) -> None:
        """Pre-compile the single-device kernels AND the sharded mask
        program at ``bucket x dp`` global shapes (a consensus engine must
        never stall mid-round on a shard_map compile)."""
        super().warmup(lanes=lanes, blocks=blocks, table_rows=table_rows)
        if self.mesh is None:
            return
        nl = sec.FIELD.nlimbs
        for bb in lanes:
            g = _bucket(bb, _BATCH_BUCKETS) * self.dp
            with cost_ledger.dispatch_span(
                self._program,
                route="warmup",
                padded=g,
                kernels=((self._program, self._mask_kernel),),
                site="verify/mesh_batch.py:warmup",
            ):
                self._mask_kernel(
                    jnp.zeros((g, 8), jnp.uint32),
                    jnp.zeros((g, nl), jnp.int32),
                    jnp.zeros((g, nl), jnp.int32),
                    jnp.zeros((g,), jnp.int32),
                    jnp.zeros((g, 5), jnp.uint32),
                    jax.device_put(
                        np.zeros((table_rows, 5), np.uint32),
                        NamedSharding(self.mesh, P()),
                    ),
                    jnp.zeros((g,), bool),
                ).block_until_ready()
