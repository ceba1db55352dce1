"""BLS12-381 aggregate committed-seal verification (BatchVerifier-shaped).

BASELINE.md config #4: instead of one ECDSA recovery per COMMIT seal, the
whole quorum is certified with ONE pairing equation —
``e(G1, sum(sig_i)) == e(sum(pk_i), H2(proposal_hash))`` — so the COMMIT
phase cost is two masked point aggregations plus a validator-count-
independent pairing check.

Shape of the integration (same seam as the ECDSA path,
:class:`go_ibft_tpu.core.backend.BatchVerifier`): ``verify_committed_seals``
returns a per-seal boolean mask.  Aggregate verification is all-or-nothing,
so the fast path answers "all valid"; on failure the batch is BISECTED —
halves re-aggregate-verify independently, so ``k`` Byzantine seals in an
``n``-seal quorum cost ``O(k log n)`` pairing equations instead of ``n``
(the same quarantine posture as
:class:`~go_ibft_tpu.verify.batch.ResilientBatchVerifier`'s poison-batch
bisection, applied to cryptographic rather than operational faults).

Seal wire format: 192 bytes ``x0 || x1 || y0 || y1`` (uncompressed G2,
48-byte big-endian field elements).  Validator registry maps the 20-byte
consensus address to the BLS G1 public key.

Security posture (ISSUE 7 satellite): :func:`decode_seal` rejects G2
points outside the r-torsion subgroup — the twist's full group order is
``r * h2`` with a composite cofactor, so an on-curve check alone admits
small-subgroup points whose contribution to an aggregate is confined to a
tiny group (a classic malleability / key-leak primitive).  The check is
``[r]P == O`` (the subgroup definition), LRU-cached by seal bytes because
the same 192 bytes recur across drains and rounds.
"""

from __future__ import annotations

import time
from functools import lru_cache
from typing import Callable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from ..crypto import bls as hbls
from ..messages.helpers import CommittedSeal
from ..obs import ledger as cost_ledger
from ..utils import metrics

BLS_SEAL_BYTES = 192
_FE = 48  # bytes per Fp element

# One count per aggregate pairing EQUATION checked (host or device route);
# the bench's config #9 reads this to report what a drain actually cost.
PAIRING_EQS_KEY = ("go-ibft", "bls", "pairing_equations")

BLSKeySource = Callable[[int], Mapping[bytes, "hbls.PointG1"]]


def encode_seal(point: "hbls.PointG2") -> bytes:
    """G2 point -> 192-byte seal (x0 || x1 || y0 || y1, big-endian)."""
    if point is None:
        raise ValueError("cannot encode the point at infinity as a seal")
    (x0, x1), (y0, y1) = point
    return b"".join(v.to_bytes(_FE, "big") for v in (x0, x1, y0, y1))


@lru_cache(maxsize=8192)
def _decode_seal_cached(blob: bytes) -> Optional["hbls.PointG2"]:
    x0, x1, y0, y1 = (
        int.from_bytes(blob[i * _FE : (i + 1) * _FE], "big") for i in range(4)
    )
    if max(x0, x1, y0, y1) >= hbls.P:
        return None
    pt = ((x0, x1), (y0, y1))
    if not hbls.g2_on_curve(pt):
        return None
    # r-torsion membership: the on-curve check admits points of any order
    # dividing #E'(Fp2) = r * h2; a seal living in the h2 part would pass
    # curve validation yet aggregate maliciously.  [r]P == O is the
    # definition of membership (a ~255-bit ladder, ~10 ms host — absorbed
    # by this cache since seal bytes recur across drains).
    if hbls.g2_mul(hbls.R, pt) is not None:
        return None
    return pt


def decode_seal(blob: bytes) -> Optional["hbls.PointG2"]:
    """192-byte seal -> G2 point in the r-torsion subgroup, else None.

    Rejects: wrong length, non-canonical field elements, off-curve
    points, and on-curve points outside the r-order subgroup.
    """
    if len(blob) != BLS_SEAL_BYTES:
        return None
    return _decode_seal_cached(bytes(blob))


def aggregate_check(
    proposal_hash: bytes,
    points: Sequence["hbls.PointG2"],
    pubkeys: Sequence["hbls.PointG1"],
    *,
    device: bool = False,
) -> bool:
    """ONE pairing equation over a seal set (host oracle or device route).

    Shared by :class:`BLSAggregateVerifier`, the quorum-certificate
    verifier (:mod:`go_ibft_tpu.crypto.quorum_cert`) and the bench, so
    every aggregate consumer counts pairings through the same metric and
    can never drift in accept-set semantics.
    """
    metrics.inc_counter(PAIRING_EQS_KEY)
    if device:
        return _aggregate_check_device(proposal_hash, points, pubkeys)
    agg = hbls.aggregate_signatures(points)
    return hbls.aggregate_verify(list(pubkeys), proposal_hash, agg)


def _aggregate_check_device(proposal_hash, points, pubkeys) -> bool:
    import jax.numpy as jnp

    from ..ops import bls12_381 as dev

    n = len(points)
    v = 1
    while v < n:
        v *= 2
    v = max(v, 2)
    pk_x, pk_y = dev.pack_g1_points(list(pubkeys) + [None] * (v - n))
    sx0, sx1, sy0, sy1 = dev.pack_g2_points(list(points) + [None] * (v - n))
    h = hbls.hash_to_g2(proposal_hash)
    hx0, hx1, hy0, hy1 = dev.pack_g2_points([h])
    live = np.zeros(v, dtype=bool)
    live[:n] = True
    t0 = time.perf_counter()
    ok = dev.aggregate_verify_commit(
        jnp.asarray(pk_x),
        jnp.asarray(pk_y),
        jnp.asarray(sx0),
        jnp.asarray(sx1),
        jnp.asarray(sy0),
        jnp.asarray(sy1),
        jnp.asarray(hx0[0]),
        jnp.asarray(hx1[0]),
        jnp.asarray(hy0[0]),
        jnp.asarray(hy1[0]),
        jnp.asarray(live),
    )
    out = bool(np.asarray(ok))
    dt_ms = (time.perf_counter() - t0) * 1e3
    metrics.observe(("go-ibft", "device", "bls_aggregate_ms"), dt_ms)
    # The dispatch record landed inside aggregate_verify_commit
    # (block=False — it returns a device future); THIS is the seam that
    # blocks on the verdict, so it attributes the pairing's wall time.
    cost_ledger.add_wall_ms("bls_aggregate_verify", "device", dt_ms)
    return out


class BLSAggregateVerifier:
    """Aggregate-first committed-seal verifier.

    ``bls_keys_for_height`` maps height -> {consensus address: G1 pubkey}.
    The device path (:func:`go_ibft_tpu.ops.bls12_381.aggregate_verify_commit`)
    runs when ``device=True``; the host oracle pairing runs otherwise —
    identical accept-sets either way (conformance tests assert it).

    Unhappy path: aggregate-then-bisect.  A failing aggregate splits in
    half and each half re-verifies as its own aggregate; a single seal
    that still fails is condemned.  ``k`` bad seals therefore cost
    ``O(k log n)`` pairing equations — the byzantine-free round stays ONE.
    """

    def __init__(self, bls_keys_for_height: BLSKeySource, device: bool = True):
        self._keys = bls_keys_for_height
        self._device = device

    # -- the one-pairing happy path ------------------------------------

    def _aggregate_check(
        self,
        proposal_hash: bytes,
        points: Sequence["hbls.PointG2"],
        pubkeys: Sequence["hbls.PointG1"],
    ) -> bool:
        return aggregate_check(
            proposal_hash, points, pubkeys, device=self._device
        )

    # -- the bisect unhappy path ---------------------------------------

    def _bisect(
        self,
        proposal_hash: bytes,
        decoded: List[Tuple[int, "hbls.PointG2", "hbls.PointG1"]],
        out: np.ndarray,
    ) -> None:
        """Pinpoint bad seals by recursive aggregate halving.

        Called AFTER the whole-set aggregate failed, so the set is known
        to contain at least one bad seal.  Verdicts land in ``out``;
        sub-aggregates that pass mark their whole half True in one
        equation.

        Soundness note: "True" means *member of a verifying aggregate* —
        the same statement the happy path proves for the full set.  Two
        colluding signers whose seal errors cancel verify jointly at
        EVERY granularity their seals share a sub-aggregate (including
        the happy path itself); this is inherent to aggregate signatures
        and quorum-sound, because each claimed signer's registered (PoP-
        checked) pubkey participates in the equation.  For non-colluding
        corruption (bit flips, wrong-hash seals) the verdicts are
        bit-identical to the per-seal oracle, which the conformance
        tests pin.
        """
        if len(decoded) == 1:
            i, pt, pk = decoded[0]
            out[i] = aggregate_check(
                proposal_hash, [pt], [pk], device=self._device
            )
            return
        mid = len(decoded) // 2
        for half in (decoded[:mid], decoded[mid:]):
            if len(half) == 1:
                # one equation suffices; a failed pre-check would only be
                # re-checked by the recursion
                self._bisect(proposal_hash, half, out)
            elif self._aggregate_check(
                proposal_hash, [p for _, p, _ in half], [k for _, _, k in half]
            ):
                out[np.asarray([i for i, _, _ in half])] = True
            else:
                self._bisect(proposal_hash, half, out)

    # -- BatchVerifier-shaped seal interface ---------------------------

    def verify_committed_seals(
        self, proposal_hash: bytes, seals: Sequence[CommittedSeal], height: int
    ) -> np.ndarray:
        out = np.zeros(len(seals), dtype=bool)
        if not seals or len(proposal_hash) != 32:
            return out
        keys = self._keys(height)
        decoded: list[Tuple[int, "hbls.PointG2", "hbls.PointG1"]] = []
        for i, seal in enumerate(seals):
            pk = keys.get(seal.signer)
            if pk is None:
                continue  # not a validator at this height
            pt = decode_seal(seal.signature)
            if pt is None:
                continue  # malformed / off-curve / small-subgroup
            decoded.append((i, pt, pk))
        if not decoded:
            return out
        idxs = [i for i, _, _ in decoded]
        points = [p for _, p, _ in decoded]
        pks = [k for _, _, k in decoded]
        if self._aggregate_check(proposal_hash, points, pks):
            out[np.asarray(idxs)] = True
            return out
        # Unhappy path (requires an actively byzantine signer inside the
        # candidate set): aggregate-then-bisect — O(k log n) equations for
        # k bad seals instead of n per-seal pairings.
        self._bisect(proposal_hash, decoded, out)
        return out
