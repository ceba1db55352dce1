"""Device-resident BLS aggregation + batched multi-pairing verification.

ISSUE 12 tentpole.  PR 7 made a COMMIT quorum O(1) on the wire and ONE
pairing to verify — but *building* the aggregate was still a host-side
sequential ``g2_add`` loop, and every consumer (certifier, block-sync,
proof serving) verified one pairing per call, so a 1000-height catch-up
was 1000 independent pairing dispatches.  This module closes both gaps:

* **Vmapped merge trees** (:class:`G2MergeTree`,
  :func:`aggregate_signatures`, :func:`aggregate_pubkeys`): point
  aggregation routes through the scanned log-depth masked tree kernels
  (:func:`go_ibft_tpu.ops.bls12_381.g2_merge_tree` /
  ``g1_merge_tree``) — one dispatch merges a whole committee, and the
  batched form merges MANY disjoint groups per dispatch (the
  aggregation-tree pump's per-sweep combine).  The host loop
  (:func:`go_ibft_tpu.crypto.bls.aggregate_signatures`) remains the
  bit-parity oracle and the small-input / degraded route.

* **Batched multi-pairing** (:func:`multi_aggregate_check`,
  :class:`MultiPairVerifier`): MANY aggregate equations
  ``e(G1, S_i) == e(sum(pk), H_i)`` verify together instead of one
  pairing per call.  Three routes, every verdict pinned to the per-lane
  :func:`~go_ibft_tpu.verify.bls.aggregate_check` oracle:

  - ``device``: ONE staged dispatch
    (:func:`go_ibft_tpu.ops.bls12_381.multi_pairing_check`) — all 2N
    Miller loops ride one batched scan, one final exponentiation per
    lane through the SAME scan-staged hard part the single-certificate
    pipeline compiled;
  - ``mesh``: the device kernel dp-sharded over a
    :func:`~go_ibft_tpu.parallel.mesh.mesh_context` mesh with masked
    lane padding to ``bucket x dp`` (the PR-6 seam) — lanes are
    independent, so the shard_map needs NO collectives;
  - ``host``: the small-exponents batch test (Bellare-Garay-Rabin) on
    the pure-Python oracle tower — each lane's ratio
    ``miller(S_i, G1) * miller(H_i, -PK_i)`` is raised to a 64-bit
    exponent derived from verifier-private fresh randomness plus the
    whole lane set, the products combine, and ONE final exponentiation
    (the ~90% term of a host pairing) covers the whole batch.  A
    failing batch bisects (the
    :class:`~go_ibft_tpu.verify.bls.BLSAggregateVerifier` posture) down
    to per-lane oracle checks, so k bad lanes cost O(k log n) product
    equations and verdicts stay EXACT.

  Batch-soundness note (host route): the exponents mix per-batch
  ``os.urandom`` (unpredictable to the adversary — forging is an
  online 2^-64 gamble, never an offline grind) with a hash of the
  ENTIRE lane set (a compromised RNG degrades to the Fiat-Shamir
  bound, not to fixed exponents).  The device route checks every
  lane's equation individually (vmapped) and needs no randomization.
  Either way a *rejected* batch resolves through the per-lane oracle,
  so no accept/reject verdict ever depends on the batching shortcut
  alone beyond the 2^-64 host-batch term.

Degradation (:class:`MultiPairVerifier`): mesh -> device -> host-batch ->
per-lane python, demoting on faults with the transition counted — the
:class:`~go_ibft_tpu.verify.batch.ResilientBatchVerifier` ladder applied
to pairing work.
"""

from __future__ import annotations

import threading
from typing import List, Sequence, Tuple

import numpy as np

from ..crypto import bls as hbls
from ..crypto.keccak import keccak256
from ..obs import ledger as cost_ledger
from ..obs import trace
from ..utils import metrics
from .bls import PAIRING_EQS_KEY, aggregate_check, encode_seal

__all__ = [
    "G2MergeTree",
    "MultiPairVerifier",
    "aggregate_pubkeys",
    "aggregate_signatures",
    "multi_aggregate_check",
    "MERGE_DISPATCHES_KEY",
    "MERGE_POINTS_KEY",
    "MULTIPAIR_DISPATCHES_KEY",
    "MULTIPAIR_LANES_KEY",
]

# One count per batched merge dispatch / merged point (device route).
MERGE_DISPATCHES_KEY = ("go-ibft", "verify", "merge_dispatches")
MERGE_POINTS_KEY = ("go-ibft", "verify", "merge_points")
# One count per multi-pairing entry call + its lane total: the
# lanes-per-dispatch evidence obs/gates.py regression-gates (a batching
# regression shows up as dispatches growing against lanes).
MULTIPAIR_DISPATCHES_KEY = ("go-ibft", "verify", "multipair_dispatches")
MULTIPAIR_LANES_KEY = ("go-ibft", "verify", "multipair_lanes")

# Pad-to buckets: point-axis buckets for the merge trees (committee
# sizes), lane buckets for the multi-pairing kernel, group buckets for
# the batched pump combine.  Power-of-two ladders keep the compiled-shape
# set small across the mega-committee sweep (100 -> 128, 300 -> 512,
# 1000 -> 1024 validators; 8/64/256-lane multi-pairings per ISSUE 12).
MERGE_BUCKETS = (2, 8, 32, 128, 512, 1024)
MULTIPAIR_BUCKETS = (2, 8, 64, 256, 1024)
GROUP_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

# Below this many points a device merge dispatch costs more than the host
# adds it replaces (a g2_add is ~0.5 ms host; a dispatch floor is ~1 ms).
DEVICE_MERGE_CUTOVER = 8

# Lane type shared with verify.bls.aggregate_check: (proposal_hash,
# seal points, pubkeys).  A lane verifies True iff the aggregate of its
# points passes the ONE-equation check against the aggregate of its
# pubkeys over H2(proposal_hash).
Lane = Tuple[bytes, Sequence["hbls.PointG2"], Sequence["hbls.PointG1"]]


def _bucket(n: int, ladder: Sequence[int]) -> int:
    """Smallest ladder bucket >= n; beyond the ladder, the next power of
    two (no silent truncation — a 2000-lane call pads to 2048, it never
    drops lanes)."""
    for b in ladder:
        if n <= b:
            return b
    b = ladder[-1]
    while b < n:
        b *= 2
    return b


# -- merge trees ------------------------------------------------------------


def aggregate_signatures(
    points: Sequence["hbls.PointG2"], *, device: bool = False
) -> "hbls.PointG2":
    """Drop-in for :func:`crypto.bls.aggregate_signatures` with a device
    merge-tree route (``device=True``, above the dispatch cutover)."""
    if not device or len(points) < DEVICE_MERGE_CUTOVER:
        return hbls.aggregate_signatures(points)
    return _merge_g2_groups_device([list(points)])[0]


def aggregate_pubkeys(
    pks: Sequence["hbls.PointG1"], *, device: bool = False
) -> "hbls.PointG1":
    """Drop-in for :func:`crypto.bls.aggregate_pubkeys`, device-routable."""
    if not device or len(pks) < DEVICE_MERGE_CUTOVER:
        return hbls.aggregate_pubkeys(pks)
    return _merge_g1_groups_device([list(pks)])[0]


def _merge_g2_groups_device(groups: List[list]) -> list:
    """One vmapped merge-tree dispatch over many disjoint G2 groups."""
    import jax.numpy as jnp

    from ..ops import bls12_381 as dev

    g = _bucket(len(groups), GROUP_BUCKETS)
    v = _bucket(max((len(grp) for grp in groups), default=1), MERGE_BUCKETS)
    packed = []
    live = np.zeros((g, v), dtype=bool)
    for gi in range(g):
        grp = groups[gi] if gi < len(groups) else []
        pts = [p for p in grp]
        live[gi, : len(pts)] = [p is not None for p in pts]
        packed.append(dev.pack_g2_points(pts + [None] * (v - len(pts))))
    args = [
        jnp.asarray(np.stack([p[c] for p in packed])) for c in range(4)
    ]
    metrics.inc_counter(MERGE_DISPATCHES_KEY)
    metrics.inc_counter(MERGE_POINTS_KEY, int(live.sum()))
    # Ledger occupancy over point SLOTS (g x v): merge padding wastes
    # both dead groups and dead points within a group.
    with cost_ledger.dispatch_span(
        "bls_g2_merge_tree",
        route="device",
        live_mask=live,
        kernels=(("bls_g2_merge_tree", dev.g2_merge_tree),),
        site="verify/aggregate.py:_merge_g2_groups_device",
    ):
        limbs, inf = dev.g2_merge_tree(*args, jnp.asarray(live))
        return dev.unpack_g2_points(np.asarray(limbs), np.asarray(inf))[
            : len(groups)
        ]


def _merge_g1_groups_device(groups: List[list]) -> list:
    import jax.numpy as jnp

    from ..ops import bls12_381 as dev

    from ..ops import bls_fp

    g = _bucket(len(groups), GROUP_BUCKETS)
    v = _bucket(max((len(grp) for grp in groups), default=1), MERGE_BUCKETS)
    px = np.zeros((g, v, bls_fp.L), dtype=np.int32)
    py = np.zeros((g, v, bls_fp.L), dtype=np.int32)
    live = np.zeros((g, v), dtype=bool)
    for gi in range(g):
        grp = groups[gi] if gi < len(groups) else []
        if grp:
            x, y = dev.pack_g1_points(list(grp) + [None] * (v - len(grp)))
            px[gi], py[gi] = x, y
            live[gi, : len(grp)] = [p is not None for p in grp]
    metrics.inc_counter(MERGE_DISPATCHES_KEY)
    metrics.inc_counter(MERGE_POINTS_KEY, int(live.sum()))
    with cost_ledger.dispatch_span(
        "bls_g1_merge_tree",
        route="device",
        live_mask=live,
        kernels=(("bls_g1_merge_tree", dev.g1_merge_tree),),
        site="verify/aggregate.py:_merge_g1_groups_device",
    ):
        limbs, inf = dev.g1_merge_tree(
            jnp.asarray(px), jnp.asarray(py), jnp.asarray(live)
        )
        return dev.unpack_g1_points(np.asarray(limbs), np.asarray(inf))[
            : len(groups)
        ]


class G2MergeTree:
    """Batched G2 aggregation with transparent host degradation.

    ``merge_groups`` merges MANY disjoint point sets in ONE vmapped
    device dispatch (the aggregation-tree pump seam: every node's
    per-sweep slot merge becomes one combine per tree level instead of
    per-child Python adds).  Below ``cutover_points`` total points — or
    after a device fault (the breaker posture: demote, never raise) —
    groups merge through the host oracle loop, bit-identically.
    """

    def __init__(
        self,
        *,
        device: bool = True,
        cutover_points: int = DEVICE_MERGE_CUTOVER,
        logger=None,
    ) -> None:
        self._device = device
        self.cutover_points = cutover_points
        self._log = logger
        self._lock = threading.Lock()
        self.device_merges = 0
        self.host_merges = 0
        self.faults = 0

    @property
    def demoted(self) -> bool:
        return not self._device

    def merge(self, points: Sequence["hbls.PointG2"]) -> "hbls.PointG2":
        return self.merge_groups([list(points)])[0]

    def merge_groups(self, groups: Sequence[Sequence["hbls.PointG2"]]) -> list:
        """One merged point (or None for an empty/cancelled group) per
        group; device route when live and worth a dispatch."""
        groups = [list(g) for g in groups]
        total = sum(len(g) for g in groups)
        if not groups:
            return []
        with trace.span(
            "verify.merge", groups=len(groups), points=total
        ):
            if self._device and total >= self.cutover_points:
                try:
                    out = _merge_g2_groups_device(groups)
                    with self._lock:
                        self.device_merges += 1
                    return out
                except Exception as err:  # noqa: BLE001 - demote, never raise
                    with self._lock:
                        self.faults += 1
                        self._device = False
                    if self._log:
                        self._log.error(
                            "G2 merge tree demoted to host", err
                        )
                    trace.instant("verify.merge_demoted")
            with self._lock:
                self.host_merges += 1
            return [hbls.aggregate_signatures(g) for g in groups]

    def stats(self) -> dict:
        with self._lock:
            return {
                "device": self._device,
                "device_merges": self.device_merges,
                "host_merges": self.host_merges,
                "faults": self.faults,
            }


# -- batched multi-pairing --------------------------------------------------


def _lane_aggregates(lane: Lane):
    """(agg signature point, agg pubkey point) or None when the lane is
    vacuously False under the oracle semantics (no points / no pubkeys /
    cancelled-to-infinity aggregate).  Deliberately NO proposal-hash
    length gate: the python oracle (``aggregate_check`` ->
    ``hash_to_g2``) accepts any message bytes, and route verdicts must
    not diverge — 32-byte enforcement is the certifier's job
    (``BLSCertifier._lane_of``)."""
    phash, points, pubkeys = lane
    if not points or not pubkeys:
        return None
    agg = hbls.aggregate_signatures(list(points))
    if agg is None:
        return None
    pk = hbls.aggregate_pubkeys(list(pubkeys))
    if pk is None:
        return None
    return agg, pk


def _fs_exponents(
    lanes: Sequence[Lane], aggs: Sequence[tuple], salt: bytes
) -> List[int]:
    """64-bit batch exponents: verifier-private ``salt`` + whole-lane-set
    binding.

    The small-exponents test is only sound when the adversary cannot
    predict the exponents while crafting the statements.  ``salt`` is
    fresh ``os.urandom`` per batch (drawn by the caller), so every
    forgery attempt is an online 2^-64 gamble — content-only derivation
    would let an attacker grind lane tweaks offline until the per-lane
    errors cancel in the product.  The lane content still feeds the
    digest (belt and braces: even a compromised RNG degrades to the
    Fiat-Shamir bound, not to a fixed exponent set; ``None`` pubkeys are
    identity elements and contribute nothing, matching the oracle
    fold)."""
    digest = keccak256(
        b"go-ibft-multipair-fs-v2"
        + salt
        + b"".join(
            bytes(lane[0])
            + encode_seal(agg)
            + b"".join(
                hbls.pubkey_bytes(pk) for pk in lane[2] if pk is not None
            )
            for lane, (agg, _pk) in zip(lanes, aggs)
        )
    )
    out = []
    for i in range(len(lanes)):
        r = int.from_bytes(
            keccak256(digest + i.to_bytes(4, "big"))[:8], "big"
        )
        out.append(r | 1)  # never zero
    return out


# -- fast host Miller loop --------------------------------------------------
# The oracle Miller (crypto/bls.py::miller_raw) untwists into Fp12 and
# pays a full Fp12 inversion per line — deliberately slow-but-sure.  The
# batch route runs MANY Millers against ONE shared final exponentiation,
# so the Miller becomes the bottleneck; this is the device kernel's
# sparse-line Jacobian formulas (ops/bls12_381.py::_dbl_step/_add_step)
# ported to exact host ints: no inversions, lines land in w-basis slots
# (0, 3, 5), values differ from the oracle Miller only by Fp2-subfield
# scalings that the final exponentiation kills — pinned by
# tests/test_aggregate.py (final_exp(fast) == final_exp(oracle raw)).

_X_BITS_HOST = [int(b) for b in bin(hbls.BLS_X)[3:]]


def _f2_smul(a, k: int):
    """Fp2 element times an Fp integer scalar."""
    return (a[0] * k % hbls.P, a[1] * k % hbls.P)


def _line12(e0, e3, e5) -> "hbls.Fp12T":
    """Sparse w-basis line (slots 0, 3, 5) as a host Fp12 tuple."""
    return ((e0, hbls.F2_ZERO, hbls.F2_ZERO), (hbls.F2_ZERO, e3, e5))


def _host_dbl_step(T, px: int, py: int):
    """Tangent line at Jacobian T evaluated at (px, py), plus 2T."""
    X, Y, Z = T
    z2 = hbls.f2_sqr(Z)
    z3 = hbls.f2_mul(z2, Z)
    yz3 = hbls.f2_mul(Y, z3)
    e0 = hbls.f2_neg(hbls.f2_muli(hbls.f2_mul_xi(_f2_smul(yz3, py)), 2))
    y2 = hbls.f2_sqr(Y)
    x2 = hbls.f2_sqr(X)
    x3 = hbls.f2_mul(x2, X)
    e3 = hbls.f2_sub(hbls.f2_muli(y2, 2), hbls.f2_muli(x3, 3))
    e5 = hbls.f2_muli(_f2_smul(hbls.f2_mul(x2, z2), px), 3)
    a = x2
    b = y2
    c = hbls.f2_sqr(b)
    t = hbls.f2_sqr(hbls.f2_add(X, b))
    d = hbls.f2_muli(hbls.f2_sub(hbls.f2_sub(t, a), c), 2)
    e = hbls.f2_muli(a, 3)
    ff = hbls.f2_sqr(e)
    x3n = hbls.f2_sub(ff, hbls.f2_muli(d, 2))
    y3n = hbls.f2_sub(
        hbls.f2_mul(e, hbls.f2_sub(d, x3n)), hbls.f2_muli(c, 8)
    )
    z3n = hbls.f2_muli(hbls.f2_mul(Y, Z), 2)
    return _line12(e0, e3, e5), (x3n, y3n, z3n)


def _host_add_step(T, qx, qy, px: int, py: int):
    """Chord line through T and the affine twist point Q at (px, py),
    plus T + Q (mixed addition)."""
    X, Y, Z = T
    z2 = hbls.f2_sqr(Z)
    z3 = hbls.f2_mul(z2, Z)
    hh = hbls.f2_sub(hbls.f2_mul(qx, z2), X)
    r = hbls.f2_sub(hbls.f2_mul(qy, z3), Y)
    zh = hbls.f2_mul(Z, hh)
    e0 = hbls.f2_neg(hbls.f2_mul_xi(_f2_smul(zh, py)))
    e3 = hbls.f2_sub(hbls.f2_mul(qy, zh), hbls.f2_mul(r, qx))
    e5 = _f2_smul(r, px)
    hs = hbls.f2_sqr(hh)
    hc = hbls.f2_mul(hs, hh)
    v = hbls.f2_mul(X, hs)
    x3n = hbls.f2_sub(
        hbls.f2_sub(hbls.f2_sqr(r), hc), hbls.f2_muli(v, 2)
    )
    y3n = hbls.f2_sub(
        hbls.f2_mul(r, hbls.f2_sub(v, x3n)), hbls.f2_mul(Y, hc)
    )
    z3n = hbls.f2_mul(Z, hh)
    return _line12(e0, e3, e5), (x3n, y3n, z3n)


def fast_miller(q: "hbls.PointG2", p: "hbls.PointG1") -> "hbls.Fp12T":
    """f_{|x|, q}(p) up to Fp2-subfield line scalings (final-exp-legal).

    ~20x the oracle Miller's speed (no per-line Fp12 inversion); only
    valid for r-torsion ``q`` (the ate ladder then never meets an
    exceptional case), which every caller guarantees via decode_seal /
    hash_to_g2.
    """
    qx, qy = q
    T = (qx, qy, hbls.F2_ONE)
    f = hbls.F12_ONE
    for bit in _X_BITS_HOST:
        line, T = _host_dbl_step(T, p[0], p[1])
        f = hbls.f12_mul(hbls.f12_sqr(f), line)
        if bit:
            line, T = _host_add_step(T, qx, qy, p[0], p[1])
            f = hbls.f12_mul(f, line)
    return f


def _host_ratio(agg, pk, phash) -> "hbls.Fp12T":
    """miller(S, G1) * miller(H, -PK): the lane's pre-final-exp ratio
    (line-scaled; the scalings die under the shared final exp)."""
    h = hbls.hash_to_g2(bytes(phash))
    return hbls.f12_mul(
        fast_miller(agg, hbls.G1_GEN),
        fast_miller(h, hbls.g1_neg(pk)),
    )


def _host_batch_group(
    entries: List[Tuple[int, "hbls.Fp12T"]],
    exps: List[int],
    lanes: Sequence[Lane],
    out: np.ndarray,
) -> None:
    """Check one product equation over ``entries``; bisect on failure.

    ``entries`` carries (lane index, precomputed ratio); singletons fall
    through to the per-lane oracle (exact verdicts, same as
    BLSAggregateVerifier's bisect floor)."""
    if not entries:
        return
    if len(entries) == 1:
        i, _ratio = entries[0]
        phash, points, pubkeys = lanes[i]
        out[i] = aggregate_check(phash, points, pubkeys)
        return
    acc = hbls.F12_ONE
    for (i, ratio), r in zip(entries, exps):
        acc = hbls.f12_mul(acc, hbls.f12_pow(ratio, r))
    metrics.inc_counter(PAIRING_EQS_KEY)
    # fe(x) == 1 iff fe(inv(x)) == 1, so the negative-parameter
    # inversion the oracle pairing performs is unnecessary here.
    if hbls.final_exponentiation(acc) == hbls.F12_ONE:
        for i, _ratio in entries:
            out[i] = True
        return
    mid = len(entries) // 2
    _host_batch_group(entries[:mid], exps[:mid], lanes, out)
    _host_batch_group(entries[mid:], exps[mid:], lanes, out)


def _host_batch_check(lanes: Sequence[Lane]) -> np.ndarray:
    """Shared-final-exponentiation batch verification on the host tower."""
    out = np.zeros(len(lanes), dtype=bool)
    entries: List[Tuple[int, "hbls.Fp12T"]] = []
    aggs = []
    live_lanes = []
    for i, lane in enumerate(lanes):
        pair = _lane_aggregates(lane)
        if pair is None:
            continue  # oracle semantics: vacuous lane -> False
        aggs.append(pair)
        live_lanes.append(lane)
        entries.append((i, _host_ratio(pair[0], pair[1], lane[0])))
    if not entries:
        return out
    import os

    exps = _fs_exponents(live_lanes, aggs, os.urandom(32))
    _host_batch_group(entries, exps, lanes, out)
    return out


def _pack_lanes_device(lanes: Sequence[Lane], *, dp: int = 1):
    """Pack live lanes for the device kernel; returns (args, live index
    list) — vacuous lanes are excluded (verdict False host-side).

    ``dp``: the mesh's data-parallel extent — the lane bucket is raised
    to at least ``dp`` so the padded lane axis always shards cleanly
    (both are powers of two, so max() is the lcm)."""
    import jax.numpy as jnp

    from ..ops import bls12_381 as dev

    live_idx = []
    sig_pts = []
    h_pts = []
    pk_lists = []
    for i, lane in enumerate(lanes):
        phash, points, pubkeys = lane
        # Vacuity gates only (no hash-length gate — the oracle accepts
        # any message bytes) — the per-lane PUBKEY fold is the kernel's
        # job (_multi_g1_neg_aggregate_stage also derives the
        # cancelled-to-infinity flag, which masks the verdict False
        # exactly like the oracle's pk_agg-is-None case); re-folding it
        # here would serialize ~lanes x committee host G1 adds in front
        # of the one batched dispatch.
        if not points or not pubkeys:
            continue
        pks = [pk for pk in pubkeys if pk is not None]
        if not pks:
            continue
        agg = hbls.aggregate_signatures(list(points))
        if agg is None:
            continue
        live_idx.append(i)
        sig_pts.append(agg)
        h_pts.append(hbls.hash_to_g2(bytes(phash)))
        pk_lists.append(pks)
    if not live_idx:
        return None, []
    from ..ops import bls_fp

    b = max(_bucket(len(live_idx), MULTIPAIR_BUCKETS), dp)
    v = _bucket(max(len(p) for p in pk_lists), MERGE_BUCKETS)
    pad = b - len(live_idx)
    sx = dev.pack_g2_points(sig_pts + [None] * pad)
    hx = dev.pack_g2_points(h_pts + [None] * pad)
    pk_x = np.zeros((b, v, bls_fp.L), dtype=np.int32)
    pk_y = np.zeros((b, v, bls_fp.L), dtype=np.int32)
    pk_live = np.zeros((b, v), dtype=bool)
    for li, pks in enumerate(pk_lists):
        x, y = dev.pack_g1_points(pks + [None] * (v - len(pks)))
        pk_x[li], pk_y[li] = x, y
        pk_live[li, : len(pks)] = True
    lane_live = np.zeros(b, dtype=bool)
    lane_live[: len(live_idx)] = True
    args = (
        jnp.asarray(sx[0]),
        jnp.asarray(sx[1]),
        jnp.asarray(sx[2]),
        jnp.asarray(sx[3]),
        jnp.asarray(hx[0]),
        jnp.asarray(hx[1]),
        jnp.asarray(hx[2]),
        jnp.asarray(hx[3]),
        jnp.asarray(pk_x),
        jnp.asarray(pk_y),
        jnp.asarray(pk_live),
        jnp.asarray(lane_live),
    )
    return args, live_idx


def _device_batch_check(lanes: Sequence[Lane], mesh=None) -> np.ndarray:
    """ONE staged batched dispatch (optionally dp-sharded over ``mesh``)."""
    from ..ops import bls12_381 as dev

    out = np.zeros(len(lanes), dtype=bool)
    dp = mesh.shape["dp"] if mesh is not None else 1
    args, live_idx = _pack_lanes_device(lanes, dp=dp)
    if not live_idx:
        return out
    # args[-1] is the padded lane-live mask — its length is the bucket
    # the dispatch actually compiled for (occupancy denominator).
    with cost_ledger.dispatch_span(
        "bls_multipair_miller",
        route="mesh" if mesh is not None else "device",
        live=len(live_idx),
        padded=int(np.shape(args[-1])[0]),
        site="verify/aggregate.py:_device_batch_check",
    ):
        if mesh is not None:
            ok = _mesh_multi_pairing(mesh)(*args)
        else:
            ok = dev.multi_pairing_check(*args)
        metrics.inc_counter(PAIRING_EQS_KEY, len(live_idx))
        mask = np.asarray(ok, dtype=bool)
    for j, i in enumerate(live_idx):
        out[i] = mask[j]
    return out


# Weak-keyed: a retired mesh (device fault, topology resize) must not be
# pinned for process life by its cached compiled program.
_MESH_MULTIPAIR_CACHE = None


def _mesh_multi_pairing(mesh):
    """dp-sharded multi-pairing: the PR-6 masked-padding seam applied to
    pairing lanes.  Lanes are independent, so the shard_map needs no
    collectives — every input shards on its lane axis, the pubkey table
    rides with its lane, and the verdict vector shards back out.  The
    caller (``_pack_lanes_device(dp=...)``) raises the lane bucket to at
    least dp, so the padded lane axis always shards cleanly."""
    global _MESH_MULTIPAIR_CACHE
    if _MESH_MULTIPAIR_CACHE is None:
        import weakref

        _MESH_MULTIPAIR_CACHE = weakref.WeakKeyDictionary()
    hit = _MESH_MULTIPAIR_CACHE.get(mesh)
    if hit is not None:
        return hit

    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops import bls12_381 as dev

    lane = P("dp")

    def step(*args):
        return dev.multi_pairing_check(*args)

    fn = jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=(lane,) * 12,
            out_specs=lane,
            check_vma=False,
        )
    )
    _MESH_MULTIPAIR_CACHE[mesh] = fn
    return fn


def multi_aggregate_check(
    lanes: Sequence[Lane], *, route: str = "host", mesh=None
) -> np.ndarray:
    """Verify MANY aggregate equations as one batched operation.

    One logical multi-pairing dispatch per call (the dispatch-count
    contract block-sync pins: a whole catch-up range is ONE call);
    ``route`` picks the engine:

    * ``"python"`` — the per-lane :func:`aggregate_check` oracle loop
      (the semantics source of truth, one pairing equation per lane);
    * ``"host"`` — small-exponents batch on the host tower: ONE final
      exponentiation per batch, bisect-to-oracle on failure;
    * ``"device"`` — the staged batched kernel, one verdict per lane;
    * ``"mesh"`` — the device kernel dp-sharded over ``mesh``.

    Returns per-lane verdicts bit-identical to the python oracle (the
    host route's 2^-64 batch term resolves through the oracle on any
    rejection — see the module docstring).
    """
    lanes = list(lanes)
    metrics.inc_counter(MULTIPAIR_DISPATCHES_KEY)
    metrics.inc_counter(MULTIPAIR_LANES_KEY, len(lanes))
    with trace.span("verify.multipair", lanes=len(lanes), route=route):
        if not lanes:
            return np.zeros(0, dtype=bool)
        # Host/python lanes are never padded, so occupancy is 1.0 by
        # construction; the device/mesh routes record inside
        # _device_batch_check where the padded bucket is known.  ONE
        # ledger program family either way — the route says which engine
        # served the lanes, the program keys the attribution.
        if route == "python":
            with cost_ledger.dispatch_span(
                "bls_multipair_miller",
                route="python",
                live=len(lanes),
                padded=len(lanes),
                site="verify/aggregate.py:multi_aggregate_check",
            ):
                return np.asarray(
                    [
                        aggregate_check(phash, points, pubkeys)
                        for phash, points, pubkeys in lanes
                    ],
                    dtype=bool,
                )
        if route == "host":
            with cost_ledger.dispatch_span(
                "bls_multipair_miller",
                route="host",
                live=len(lanes),
                padded=len(lanes),
                site="verify/aggregate.py:multi_aggregate_check",
            ):
                return _host_batch_check(lanes)
        if route == "device":
            return _device_batch_check(lanes)
        if route == "mesh":
            if mesh is None:
                raise ValueError("route='mesh' requires a mesh")
            return _device_batch_check(lanes, mesh=mesh)
        raise ValueError(f"unknown multi-pairing route {route!r}")


class MultiPairVerifier:
    """Route-laddered multi-pairing with breaker-style degradation.

    Preference order: ``mesh`` (when a mesh was given) -> ``device``
    (when ``device=True``) -> ``host`` (batched) -> ``python`` (the
    per-lane oracle).  A fault on any rung demotes PAST it for the rest
    of the verifier's life (the
    :class:`~go_ibft_tpu.verify.batch.ResilientBatchVerifier` posture:
    verdicts never change across rungs, only cost does), with the
    transition counted and traced.
    """

    _LADDER = ("mesh", "device", "host", "python")

    def __init__(
        self,
        *,
        device: bool = False,
        mesh=None,
        host_batch: bool = True,
        logger=None,
    ) -> None:
        self.mesh = mesh
        self._log = logger
        self._lock = threading.Lock()
        rungs = []
        if mesh is not None:
            # An explicitly-attached mesh IS the request for the sharded
            # route — it must not silently depend on the device flag.
            rungs.append("mesh")
        if device:
            rungs.append("device")
        if host_batch:
            rungs.append("host")
        rungs.append("python")
        self._rungs = tuple(rungs)
        self._level = 0
        self.dispatches = 0
        self.lanes = 0
        self.demotions = 0

    @property
    def route(self) -> str:
        return self._rungs[self._level]

    def check(self, lanes: Sequence[Lane]) -> np.ndarray:
        """Per-lane verdicts through the highest live rung; a rung fault
        demotes and re-verifies on the next one (never raises past the
        python oracle, which cannot fault)."""
        lanes = list(lanes)
        with self._lock:
            self.dispatches += 1
            self.lanes += len(lanes)
            level = self._level
        while True:
            route = self._rungs[level]
            try:
                return multi_aggregate_check(
                    lanes, route=route, mesh=self.mesh
                )
            except Exception as err:  # noqa: BLE001 - demote, retry below
                if route == "python":
                    raise
                with self._lock:
                    level = max(level + 1, self._level + 1)
                    level = min(level, len(self._rungs) - 1)
                    self._level = level
                    self.demotions += 1
                if self._log:
                    self._log.error(
                        f"multi-pairing rung {route!r} demoted to "
                        f"{self._rungs[level]!r}",
                        err,
                    )
                trace.instant(
                    "verify.multipair_demoted", to=self._rungs[level]
                )

    def stats(self) -> dict:
        with self._lock:
            return {
                "route": self._rungs[self._level],
                "rungs": self._rungs,
                "dispatches": self.dispatches,
                "lanes": self.lanes,
                "demotions": self.demotions,
                "lanes_per_dispatch": (
                    round(self.lanes / self.dispatches, 2)
                    if self.dispatches
                    else None
                ),
            }
