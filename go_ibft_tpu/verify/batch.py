"""BatchVerifier implementations: host oracle + device batch kernels.

The reference verifies one message at a time through embedder predicates
under the store lock (go-ibft messages/messages.go:183-198 calling
core/backend.go:37-56).  Here the same observable semantics — a validity
mask over a message set — are produced by one fixed-shape device batch:

    payload bytes --pack--> keccak blocks --digest--> ecrecover ladder
                 --> pubkey --keccak--> address --compare--> mask

Shapes are static per (batch-bucket, block-bucket, validator-bucket)
triple; each distinct triple compiles once and is cached.  Lanes added by
padding are masked out, so callers see exact-length numpy boolean masks.

Signature format (shared with :mod:`go_ibft_tpu.crypto.backend`):
65 bytes ``r(32, big-endian) || s(32, big-endian) || v(1)``, signing
``keccak256(payload_no_sig)`` for envelopes and the proposal hash directly
for committed seals.
"""

from __future__ import annotations

import threading
import time
from contextlib import ExitStack, closing
from dataclasses import dataclass
from collections import OrderedDict, deque
from typing import (
    Callable,
    Dict,
    FrozenSet,
    Iterable,
    Iterator,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import jax
import jax.numpy as jnp
import numpy as np

from ..core.validator_manager import calculate_quorum
from ..crypto import ecdsa as host_ecdsa
from ..obs import ledger as cost_ledger
from ..obs import trace
from ..crypto.keccak import keccak256, keccak256_many
from ..messages.helpers import CommittedSeal
from ..messages.wire import IbftMessage, MessageType, payloads_no_sig
from ..ops import fields
from ..ops import keccak as dk
from ..ops import pallas_ladder
from ..ops import quorum
from ..ops import secp256k1 as sec
from ..ops.fields import LIMB_BITS, LIMB_MASK
from ..utils import metrics
from .pipeline import (
    CircuitBreaker,
    PackCache,
    SenderPack,
    SenderRows,
    VerifyPipeline,
)

SIG_BYTES = 65  # r(32) || s(32) || v(1)

ADDRESS_BYTES = 20

# The height(s) a sync drain's lanes are judged at: one for every lane, or
# one a lane (a range that crosses validator-set boundaries).
LaneHeights = Union[int, Sequence[int]]


def _one_height(height: LaneHeights) -> bool:
    return isinstance(height, (int, np.integer))


def _heights_at(height: LaneHeights, idxs: Sequence[int]) -> LaneHeights:
    """``height`` as it applies to the lanes ``idxs`` of a drain."""
    return height if _one_height(height) else [height[i] for i in idxs]


class MalformedLaneError(ValueError):
    """A packer input lane has an invalid length, named by index.

    The vectorized packers build whole-batch ``frombuffer`` views, so a
    single wrong-length signature or address used to surface as an opaque
    numpy reshape error (or worse, silently misaligned lanes).  Length
    validation now runs up front and raises this instead — a ``ValueError``
    subclass, so callers that caught the loop packers' errors still do —
    carrying the offending lane so degraded-mode drains can quarantine
    exactly that lane and verify the rest
    (:class:`ResilientBatchVerifier`).
    """

    def __init__(self, lane: int, field: str, expected: int, got: int):
        self.lane = lane
        self.field = field
        self.expected = expected
        self.got = got
        super().__init__(
            f"lane {lane}: {field} must be {expected} bytes, got {got}"
        )

# Pad-to buckets: batch lanes, keccak blocks per message, validator-set size.
# Every (lane, block, table) triple is a separate XLA program, and the lane x
# table grid drives the expensive EC-ladder compiles, so the block and table
# sets are PRUNED to what the workloads actually hit: envelopes are 1-2
# keccak blocks (mid sizes ride the next bucket — keccak pad lanes are noise
# against the ladder), and table rows only feed the cheap membership
# compare.  Lane buckets stay fine-grained: lane count scales the ladder
# itself, where padding waste is real work.
_BATCH_BUCKETS = (8, 32, 128, 512, 1024, 2048)
# From this many lanes up a rung is a multiple of what ``ops/secp256k1.py``
# folds to full (8, 128) tiles.  A rung below it is a partial vector register
# on the v5e, by size: a dispatch at TWICE such a rung costs what the rung
# itself costs (PERF.md section 6, PR 32: 256 lanes against 128), so the
# sender floods run there, with the second half free for their COMMITs' seals
# (``DeviceBatchVerifier.verify_senders``), and a committee loads one ladder
# executable (15 s of a process's set-up each) where two widths would be two.
_FOLD_LANES = 256
_BLOCK_BUCKETS = (2, 8, 32)
_TABLE_BUCKETS = (8, 128, 512, 2048)


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"size {n} exceeds largest bucket {buckets[-1]}")


def _lane_count(n: int, pad_lanes: int = 0) -> int:
    """Static lane dimension for an ``n``-lane pack.

    The packers used to assume the caller's batch fits a bucket —
    ``max(_bucket(n), pad_lanes)`` — which made any explicitly-padded
    shape above the largest bucket (a mesh drain padding to a multiple of
    the device count, e.g. 4096 global lanes on dp=2) an error instead of
    a shape.  An explicit ``pad_lanes >= n`` now PINS the lane dimension
    exactly (the caller owns the padding policy; pad lanes are dead —
    ``live`` False — so no dummy verdict can leak into a quorum count);
    otherwise the next bucket serves as before."""
    if pad_lanes >= max(n, 1):
        return pad_lanes
    return max(_bucket(n, _BATCH_BUCKETS), pad_lanes)


def committee_lanes(n_validators: int) -> Tuple[int, ...]:
    """Widths of the recover program that one phase of a committee of
    ``n_validators`` makes the device route dispatch, for whoever warms
    shapes at start-up (``warmup(lanes=...)``): the phase's own rung (a
    seal drain that missed the verdict cache) and, under the fold width,
    twice it (the sender floods, each COMMIT's seal beside its envelope)."""
    lanes = _bucket(min(n_validators, _BATCH_BUCKETS[-1]), _BATCH_BUCKETS)
    return (lanes, 2 * lanes) if lanes < _FOLD_LANES else (lanes,)


def host_quorum_reached(
    validators_for_height: "ValidatorSource",
    valid_addrs: Iterable[bytes],
    height: int,
    threshold: Optional[int],
) -> bool:
    """Exact host-int voting-power quorum over a drain's valid addresses.

    The host-side quorum reduction beside a mask (the resilient ladder's
    early-exit fallback): distinct validators counted once, exact Python
    ints for any power range."""
    powers = validators_for_height(height)
    thr = (
        calculate_quorum(sum(powers.values()))
        if threshold is None
        else threshold
    )
    if thr <= 0:
        return True
    return sum(powers.get(a, 0) for a in set(valid_addrs)) >= thr


EARLY_EXIT_SKIPPED_KEY = ("go-ibft", "early_exit", "lanes_skipped")
EARLY_EXIT_DRAINS_KEY = ("go-ibft", "early_exit", "drains")

# Fixed-bucket drain-latency family for the live /metrics endpoint: one
# series per route (the key's 4th part renders as the ``tag`` label).
# Recorded only while metrics.enable_fixed_histograms() is on.
VERIFY_DRAIN_MS_KEY = ("go-ibft", "latency", "verify_drain_ms")

# The joint COMMIT dispatch: seal lanes that rode their envelope's dispatch,
# and what the seal drains then found (hit) or still had to verify (miss).
SEAL_LANES_CARRIED_KEY = ("go-ibft", "seal_verdicts", "carried")
SEAL_VERDICT_HITS_KEY = ("go-ibft", "seal_verdicts", "hits")
SEAL_VERDICT_MISSES_KEY = ("go-ibft", "seal_verdicts", "misses")
JOINT_FAULTS_KEY = ("go-ibft", "seal_verdicts", "joint_faults")

# Messages of a sender pack whose signed payload the bulk encoder handed to
# ``IbftMessage.encode`` (any shape but a plain PREPARE / COMMIT); the ones
# it built from its template are ``templated`` on the ``verify.pack`` span.
PACK_GENERIC_KEY = ("go-ibft", "pack", "generic")
# Host arrays a dispatch placed on the device one by one, ahead of its
# compiled call (``jnp.asarray`` / ``jax.device_put``), instead of handing
# them to the call: 0 on the device route, the sharded mesh's own placements.
EAGER_PUTS_KEY = ("go-ibft", "dispatch", "eager_puts")
# Validator tables copied host -> device: one a distinct address set (or a
# distinct union of sets, block sync), whatever heights select it.
TABLE_UPLOADS_KEY = ("go-ibft", "verify", "table_uploads")
# Launches of a program whose ladder is the Pallas kernel
# (``ops/pallas_ladder.py``; its two Fermat chains are kernels by the same
# rule); the ``verify.dispatch`` span of every device launch says which
# ladder its program has (``ladder``: "pallas" | "scan").
PALLAS_LADDER_DISPATCHES_KEY = ("go-ibft", "kernel", "pallas_ladder_dispatches")
# Seal lanes of a sync drain whose signature held and whose signer is not in
# the validator set of the lane's OWN height (though it may sit in a
# neighbouring epoch's set, on the same device table).
SYNC_OUT_OF_SET_LANES_KEY = ("go-ibft", "chain", "sync_out_of_set_lanes")

# Sized like the engine's own seal-verdict cache (core/ibft.py
# ``_seal_verdict_cap``): a constant, not an option.
_SEAL_VERDICT_CAP = 16384

SealKey = Tuple[bytes, bytes, bytes]  # (claimed signer, carried hash, seal bytes)


class SealVerdictCache:
    """``(height, claimed signer, carried proposal hash, seal signature
    bytes) -> bool``: what a joint COMMIT dispatch decided about a seal.

    A committed seal's verdict is a pure function of exactly that binding
    and the height's validator table, so a hit is the verdict the seal
    drain would compute and nothing else can reach it: another height,
    hash, signer or signature is another key.  Bounded: at most
    ``heights`` heights are kept (the lowest goes whole), on cap pressure
    heights below the newest go whole, oldest first, and the newest sheds
    FIFO (a seal-rewrite flood mints keys there).
    Thread-safe, like :class:`~go_ibft_tpu.verify.pipeline.PackCache`.
    """

    def __init__(self, heights: int = 4) -> None:
        self._lock = threading.Lock()
        self._by_height: Dict[int, Dict[SealKey, bool]] = {}
        self._count = 0
        self._heights = heights

    def __len__(self) -> int:
        with self._lock:
            return self._count

    def lookup(self, height: int, keys: Sequence[SealKey]) -> List[Optional[bool]]:
        with self._lock:
            bucket = self._by_height.get(height)
            if not bucket:
                return [None] * len(keys)
            return [bucket.get(k) for k in keys]

    def store(self, height: int, keys: Sequence[SealKey], verdicts) -> None:
        with self._lock:
            bucket = self._by_height.setdefault(height, {})
            for key, ok in zip(keys, verdicts):
                if key not in bucket:
                    self._count += 1
                bucket[key] = bool(ok)
            while len(self._by_height) > self._heights:
                self._count -= len(self._by_height.pop(min(self._by_height)))
            while self._count > _SEAL_VERDICT_CAP:
                oldest = min(self._by_height)
                bucket = self._by_height[oldest]
                if len(self._by_height) > 1:
                    self._count -= len(bucket)
                    del self._by_height[oldest]
                else:
                    del bucket[next(iter(bucket))]
                    self._count -= 1

    def drop_height(self, height: int) -> None:
        with self._lock:
            self._count -= len(self._by_height.pop(height, ()))


def _note_verdicts(kind: str, route: str, mask: np.ndarray, judged=None) -> None:
    """``verify.verdicts``: what one drain decided, as an instant inside its
    outer span (``verify.drain`` / ``verify.early_exit``).  ``live`` counts
    the lanes that got a verdict HERE (``judged`` marks them where a drain
    may stop early or serves lanes from the seal-verdict cache, which were
    noted when they were judged; all of ``mask`` otherwise), ``rejected``
    those of them it refused.  One predicate when tracing is off."""
    if not trace.enabled():
        return
    if judged is not None:
        mask = mask[judged]
        if not len(mask):
            return
    trace.instant(
        "verify.verdicts",
        kind=kind,
        route=route,
        live=len(mask),
        rejected=len(mask) - int(np.count_nonzero(mask)),
    )


@dataclass
class EarlyExitReport:
    """One early-exit seal drain's outcome.

    ``mask`` carries per-lane verdicts — ``False`` both for invalid lanes
    and for lanes the drain never reached; ``verified`` distinguishes
    them (True = the lane has a REAL verdict, bit-identical to the
    sequential oracle's).  ``reached`` is the exact voting-power quorum
    over the verified-valid distinct signers; ``skipped`` counts the
    lanes left unverified (the caller resolves them lazily off-path —
    typically via :class:`~go_ibft_tpu.verify.speculate.
    SpeculativeVerifier` — or synchronously if the early exit
    mispredicted).  Early-exit changes WHEN a lane verifies, never a
    verdict.
    """

    mask: np.ndarray
    verified: np.ndarray
    reached: bool
    skipped: int


class _PowerTally:
    """Exact incremental voting-power quorum (distinct signers counted
    once — the ``has_quorum`` / :func:`host_quorum_reached` semantics,
    fed one verdict at a time)."""

    def __init__(self, powers: Mapping[bytes, int], threshold: int):
        self.powers = powers
        self.threshold = threshold
        self.power = 0
        self._counted: set = set()

    @property
    def reached(self) -> bool:
        return self.power >= self.threshold

    def add(self, signer: bytes) -> bool:
        if signer not in self._counted:
            self._counted.add(signer)
            self.power += self.powers.get(signer, 0)
        return self.reached


def split_signature(sig: bytes) -> Tuple[int, int, int]:
    """65-byte ``r || s || v`` -> ints; raises on wrong length."""
    if len(sig) != SIG_BYTES:
        raise ValueError(f"signature must be {SIG_BYTES} bytes, got {len(sig)}")
    return (
        int.from_bytes(sig[:32], "big"),
        int.from_bytes(sig[32:64], "big"),
        sig[64],
    )


ValidatorSource = Callable[[int], Mapping[bytes, int]]


class HostBatchVerifier:
    """Sequential per-item verification over Python ints.

    Mirrors exactly what the reference does per message — the semantics
    oracle the device path must match, and the honest baseline denominator
    for BASELINE.md's >=30x target.
    """

    def __init__(
        self,
        validators_for_height: ValidatorSource,
        recover_fn: Optional[Callable] = None,
    ):
        self._validators = validators_for_height
        # ``recover_fn`` overrides the ecrecover primitive: the degraded-
        # mode ladder's bottom rung passes ``ecdsa.recover_pure`` so a
        # crashing native library can be routed around entirely.
        self._recover = recover_fn if recover_fn is not None else host_ecdsa.recover

    def _is_member(self, height: int, address: bytes) -> bool:
        return address in self._validators(height)

    def verify_senders(self, msgs: Sequence[IbftMessage]) -> np.ndarray:
        out = np.zeros(len(msgs), dtype=bool)
        t0 = time.perf_counter() if metrics.fixed_histograms_enabled() else None
        with trace.span(
            "verify.drain", kind="senders", route="host", lanes=len(msgs)
        ):
            # The flight-recorder phase structure mirrors the device path
            # (pack -> dispatch -> device-wait -> quorum) so every drain
            # renders the same way regardless of route; on the synchronous
            # host route "dispatch" is the recover loop and the wait is
            # empty by construction.
            with trace.span("verify.pack", lanes=len(msgs)):
                prepared = []
                for i, msg in enumerate(msgs):
                    if msg.view is None or len(msg.sender) != ADDRESS_BYTES:
                        continue
                    if len(msg.signature) != SIG_BYTES:
                        continue
                    r, s, v = split_signature(msg.signature)
                    digest = keccak256(msg.encode(include_signature=False))
                    prepared.append((i, msg, digest, r, s, v))
            with cost_ledger.dispatch_span(
                "ecdsa_recover",
                route="host",
                live=len(prepared),
                padded=len(prepared),
                site="verify/batch.py:HostBatchVerifier.verify_senders",
            ):
                with trace.span(
                    "verify.dispatch", route="host", lanes=len(prepared)
                ):
                    recovered = [
                        (i, msg, self._recover(digest, r, s, v))
                        for i, msg, digest, r, s, v in prepared
                    ]
            with trace.span("verify.device_wait", route="host"):
                pass  # nothing in flight on the synchronous route
            with trace.span("verify.quorum", lanes=len(recovered)):
                for i, msg, pub in recovered:
                    if pub is None:
                        continue
                    out[i] = (
                        host_ecdsa.pubkey_to_address(*pub) == msg.sender
                        and self._is_member(msg.view.height, msg.sender)
                    )
            _note_verdicts("senders", "host", out)
        if t0 is not None:
            metrics.observe_fixed(
                VERIFY_DRAIN_MS_KEY + ("host",),
                (time.perf_counter() - t0) * 1e3,
            )
        return out

    def verify_committed_seals(
        self, proposal_hash: bytes, seals: Sequence[CommittedSeal], height: int
    ) -> np.ndarray:
        out = np.zeros(len(seals), dtype=bool)
        # Same malformed-hash rejection as ECDSABackend.is_valid_committed_seal
        # and the device path (a seal signs a 32-byte keccak hash; the native
        # recover also reads exactly 32 digest bytes).
        if len(proposal_hash) != 32:
            return out
        t0 = time.perf_counter() if metrics.fixed_histograms_enabled() else None
        with trace.span(
            "verify.drain", kind="seals", route="host", lanes=len(seals)
        ):
            with trace.span("verify.pack", lanes=len(seals)):
                prepared = []
                for i, seal in enumerate(seals):
                    if (
                        len(seal.signer) != ADDRESS_BYTES
                        or len(seal.signature) != SIG_BYTES
                    ):
                        continue
                    prepared.append((i, seal, *split_signature(seal.signature)))
            with cost_ledger.dispatch_span(
                "ecdsa_recover",
                route="host",
                live=len(prepared),
                padded=len(prepared),
                site="verify/batch.py:HostBatchVerifier.verify_committed_seals",
            ):
                with trace.span(
                    "verify.dispatch", route="host", lanes=len(prepared)
                ):
                    recovered = [
                        (i, seal, self._recover(proposal_hash, r, s, v))
                        for i, seal, r, s, v in prepared
                    ]
            with trace.span("verify.device_wait", route="host"):
                pass  # nothing in flight on the synchronous route
            with trace.span("verify.quorum", lanes=len(recovered)):
                for i, seal, pub in recovered:
                    if pub is None:
                        continue
                    out[i] = (
                        host_ecdsa.pubkey_to_address(*pub) == seal.signer
                        and self._is_member(height, seal.signer)
                    )
            _note_verdicts("seals", "host", out)
        if t0 is not None:
            metrics.observe_fixed(
                VERIFY_DRAIN_MS_KEY + ("host",),
                (time.perf_counter() - t0) * 1e3,
            )
        return out

    def verify_seal_lanes(
        self, lanes: Sequence[Tuple[bytes, CommittedSeal]], height: LaneHeights
    ) -> np.ndarray:
        """Per-lane-hash seal verification (the block-sync drain shape).

        Each lane is ``(proposal_hash, seal)`` — sequential per-lane
        recovers against that lane's own hash, membership against the
        validator set of that lane's own height: ``height`` is one height
        for every lane or a height a lane.  This is the oracle the batched
        sync drain (DeviceBatchVerifier.verify_seal_lanes) is pinned to.
        """
        one = _one_height(height)
        out = np.zeros(len(lanes), dtype=bool)
        t0 = time.perf_counter() if metrics.fixed_histograms_enabled() else None
        with trace.span(
            "verify.drain", kind="seal_lanes", route="host", lanes=len(lanes)
        ):
            with trace.span("verify.pack", lanes=len(lanes)):
                prepared = []
                for i, (proposal_hash, seal) in enumerate(lanes):
                    if (
                        len(proposal_hash) != 32
                        or len(seal.signer) != ADDRESS_BYTES
                        or len(seal.signature) != SIG_BYTES
                    ):
                        continue
                    prepared.append(
                        (i, proposal_hash, seal, *split_signature(seal.signature))
                    )
            with cost_ledger.dispatch_span(
                "ecdsa_recover",
                route="host",
                live=len(prepared),
                padded=len(prepared),
                site="verify/batch.py:HostBatchVerifier.verify_seal_lanes",
            ):
                with trace.span(
                    "verify.dispatch", route="host", lanes=len(prepared)
                ):
                    recovered = [
                        (i, seal, self._recover(proposal_hash, r, s, v))
                        for i, proposal_hash, seal, r, s, v in prepared
                    ]
            with trace.span("verify.device_wait", route="host"):
                pass  # nothing in flight on the synchronous route
            with trace.span("verify.quorum", lanes=len(recovered)):
                for i, seal, pub in recovered:
                    if pub is None:
                        continue
                    out[i] = (
                        host_ecdsa.pubkey_to_address(*pub) == seal.signer
                        and self._is_member(
                            height if one else height[i], seal.signer
                        )
                    )
            _note_verdicts("seal_lanes", "host", out)
        if t0 is not None:
            metrics.observe_fixed(
                VERIFY_DRAIN_MS_KEY + ("host",),
                (time.perf_counter() - t0) * 1e3,
            )
        return out

    def verify_seals_early_exit(
        self,
        proposal_hash: bytes,
        seals: Sequence[CommittedSeal],
        height: int,
        threshold: Optional[int] = None,
    ) -> EarlyExitReport:
        """Arrival-order seal verification that RETURNS at quorum.

        Lanes verify sequentially in the order they arrived; the exact
        voting-power tally (distinct signers once) runs alongside, and
        the loop stops the moment accumulated verified power reaches
        ``threshold`` (the height's quorum when None).  Every verdict
        produced is bit-identical to :meth:`verify_committed_seals`'s for
        that lane; lanes past the cut are reported ``skipped`` for the
        caller to resolve lazily.  Malformed lanes cost no crypto and
        get their (False) verdict immediately, like the full drain.
        """
        n = len(seals)
        mask = np.zeros(n, dtype=bool)
        verified = np.zeros(n, dtype=bool)
        powers = self._validators(height)
        thr = (
            calculate_quorum(sum(powers.values()))
            if threshold is None
            else threshold
        )
        if len(proposal_hash) != 32:
            verified[:] = True  # batch-wide reject: every verdict is False
            return EarlyExitReport(mask, verified, thr <= 0, 0)
        tally = _PowerTally(powers, thr)
        done = 0
        t0 = time.perf_counter() if metrics.fixed_histograms_enabled() else None
        with trace.span(
            "verify.early_exit", route="host", kind="seals", lanes=n
        ):
            for i, seal in enumerate(seals):
                if tally.reached:
                    break
                verified[i] = True
                done = i + 1
                if (
                    len(seal.signer) != ADDRESS_BYTES
                    or len(seal.signature) != SIG_BYTES
                ):
                    continue
                r, s, v = split_signature(seal.signature)
                pub = self._recover(proposal_hash, r, s, v)
                if pub is None:
                    continue
                ok = (
                    host_ecdsa.pubkey_to_address(*pub) == seal.signer
                    and self._is_member(height, seal.signer)
                )
                mask[i] = ok
                if ok:
                    tally.add(seal.signer)
            _note_verdicts("seals", "host", mask, verified)
        skipped = n - done
        metrics.inc_counter(EARLY_EXIT_DRAINS_KEY)
        if skipped:
            metrics.inc_counter(EARLY_EXIT_SKIPPED_KEY, skipped)
        # Lane counts are only known at exit (the drain stops at quorum),
        # so the ledger record lands here rather than via a span.
        cost_ledger.record_dispatch(
            "ecdsa_recover", "host", live=done, padded=done
        )
        if t0 is not None:
            metrics.observe_fixed(
                VERIFY_DRAIN_MS_KEY + ("host",),
                (time.perf_counter() - t0) * 1e3,
            )
        return EarlyExitReport(mask, verified, tally.reached, skipped)


# ---------------------------------------------------------------------------
# Device kernels (shape-polymorphic via jit retrace per bucket triple)
# ---------------------------------------------------------------------------


# Two-dispatch pipeline: the digest program recompiles per payload-size
# bucket (cheap keccak scan); the recovery program — the expensive 256-step
# EC ladder — compiles once per lane bucket and serves BOTH envelope senders
# and committed seals.
#
# Buffer donation was evaluated for these kernels and REJECTED: XLA can
# only alias a donated input to an output of matching shape/dtype, and
# every verification program here maps big packed inputs ((B, 20) limb
# vectors, (B, nb, 17, 2) keccak blocks) to tiny boolean masks — nothing
# aliases, so donate_argnums performs no reuse and instead emits a
# "donated buffers were not usable" warning per compile.  The per-call
# inputs are freed by Python refcount right after dispatch regardless.
def digest_words(blocks, nblocks, hz=None):
    """:func:`go_ibft_tpu.ops.quorum.digest_words`, and behind its rows the
    hash rows ``hz`` of the seals that ride a sender chunk's dispatch: one
    batch's ``zw``, joined where the digest is computed (a launch of its own
    for the join cost a flood one more dispatch a drain)."""
    zw = quorum.digest_words(blocks, nblocks)
    return zw if hz is None else jnp.concatenate([zw, hz], axis=0)


_digest_kernel = jax.jit(digest_words)

# How a dispatch's rows reach the device: as the numpy arrays the packers
# wrote, handed to the compiled call itself, whose own argument path stages
# them (an eager ``jnp.asarray`` round each was 0.15 ms more of host time an
# array on the chip machine, the device idle through it: PERF.md section 6,
# PR 38).
# The runtime may read such an array after the call has returned, so nothing
# writes to an array once a launch has it: every pack allocates its own
# (:func:`_dead_rows`, the packers' ``blocks`` / ``counts`` / hash rows), and
# the pack cache, which keeps a drain's rows by reference, only reads them.


def _recover_fn(zw, r, s, v, claimed_w, table_w, live):
    ok = quorum.sig_checks_zw(zw, r, s, v, claimed_w, live)
    member = jnp.any(quorum.membership_eq(claimed_w, table_w), axis=-1)
    return ok & member


_recover_kernel = jax.jit(_recover_fn)


def _pack_scalars(values: List[int], pad_to: int) -> jnp.ndarray:
    """Python-int scalars -> padded limb array (reference packers only; the
    vectorized path limb-splits straight from signature bytes and never
    materializes Python ints)."""
    values = values + [0] * (pad_to - len(values))
    return jnp.asarray(fields.to_limbs(values, sec.FIELD.nlimbs))


def _signature_words(sigs: Sequence[bytes]) -> Tuple[np.ndarray, np.ndarray]:
    """N 65-byte signatures (lengths checked by the caller) -> ``(N, 2, 8)``
    uint32 little-endian value words, ``[:, 0]`` of s and ``[:, 1]`` of r
    (bytes 63..0 reversed in one copy), and ``v`` as ``(N,)`` int32."""
    flat = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(-1, SIG_BYTES)
    words = np.ascontiguousarray(flat[:, 63::-1]).view("<u4")
    return words.reshape(-1, 2, 8), flat[:, 64].astype(np.int32)


def _signature_rows(sigs: Sequence[bytes]):
    """``(r_limbs, s_limbs, v)`` of N length-checked signatures: r and s
    limb-split in ONE :func:`_words_to_limbs` pass over the stacked words."""
    words, v = _signature_words(sigs)
    limbs = _words_to_limbs(words, sec.FIELD.nlimbs)
    return limbs[:, 1], limbs[:, 0], v


def _address_rows(addresses: Sequence[bytes]) -> np.ndarray:
    """N length-checked 20-byte addresses -> ``(N, 5)`` uint32 words (the
    layout of ``ops/keccak.py::addresses_to_words``, without its loop)."""
    return np.frombuffer(b"".join(addresses), dtype="<u4").reshape(-1, 5)


def ladder_of(lanes: int) -> str:
    """``ladder`` of a ``verify.dispatch`` span: which ladder the recover
    program of ``lanes`` lanes was traced with, by the rule it was traced by;
    counted where it is the kernel."""
    ladder = pallas_ladder.kind(lanes)
    if ladder == pallas_ladder.PALLAS:
        metrics.inc_counter(PALLAS_LADDER_DISPATCHES_KEY)
    return ladder


def _host_arrays(inputs) -> int:
    """How many of a dispatch's rows are host arrays (``operands`` on its
    ``verify.dispatch`` span): all but a sender drain's device ``zw``."""
    return sum(isinstance(a, np.ndarray) for a in inputs)


def _dead_rows(lanes: int):
    """``(r, s, v, claimed, live)`` of ``lanes`` dead lanes: what a packer
    starts from, and what a dispatch's padding stays."""
    nl = sec.FIELD.nlimbs
    return (
        np.zeros((lanes, nl), dtype=np.int32),
        np.zeros((lanes, nl), dtype=np.int32),
        np.zeros((lanes,), dtype=np.int32),
        np.zeros((lanes, 5), dtype=np.uint32),
        np.zeros((lanes,), dtype=bool),
    )


def _split_signatures(
    sigs: Sequence[bytes],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized :func:`split_signature`: N sigs -> value words + v.

    Returns ``(r_words, s_words, v)`` with the words as ``(N, 8)`` uint32
    little-endian value words (the 32 big-endian bytes reversed and viewed
    as uint32) and ``v`` as ``(N,)`` int32.  One C-level join + one
    ``frombuffer`` for the whole batch; raises :class:`MalformedLaneError`
    on any wrong-length signature, naming the lane.
    """
    for i, sig in enumerate(sigs):
        if len(sig) != SIG_BYTES:
            raise MalformedLaneError(i, "signature", SIG_BYTES, len(sig))
    words, v = _signature_words(sigs)
    return words[:, 1], words[:, 0], v


def _words_to_limbs(words: np.ndarray, nlimbs: int) -> np.ndarray:
    """``(N, nw)`` uint32 LE value words -> ``(N, nlimbs)`` int32 limbs.

    The numpy twin of :func:`go_ibft_tpu.ops.keccak.words_le_to_limbs`
    (same shift schedule), replacing the per-value Python-int loop of
    ``fields.to_limbs`` with ``nlimbs`` whole-batch shift/mask ops.
    """
    nw = words.shape[-1]
    out = np.zeros(words.shape[:-1] + (nlimbs,), dtype=np.int32)
    for k in range(nlimbs):
        lo_bit = LIMB_BITS * k
        j, sh = divmod(lo_bit, 32)
        if j >= nw:
            break
        acc = words[..., j] >> np.uint32(sh)
        if sh + LIMB_BITS > 32 and j + 1 < nw:
            acc = acc | (words[..., j + 1] << np.uint32(32 - sh))
        out[..., k] = (acc & np.uint32(LIMB_MASK)).astype(np.int32)
    return out


def pack_validator_table(addresses: Sequence[bytes], bucket: bool = True) -> np.ndarray:
    """Addresses -> ``(V, 5)`` uint32 words, padded by repeating row 0."""
    addresses = [a for a in addresses if len(a) == ADDRESS_BYTES]
    if not addresses:
        raise ValueError("empty validator set")
    v = _bucket(len(addresses), _TABLE_BUCKETS) if bucket else len(addresses)
    table = np.zeros((v, 5), dtype=np.uint32)
    table[: len(addresses)] = dk.addresses_to_words(addresses)
    table[len(addresses) :] = table[0]  # padding adds no new member
    return table


def _signed_payloads(
    msgs: Sequence[IbftMessage],
    hits: Optional[Sequence[Optional[SenderPack]]],
    generic: Optional[list] = None,
) -> List[bytes]:
    """Each message's signed bytes: a cache hit's, else encoded in bulk
    (``messages/wire.py::payloads_no_sig``).  ``hits`` None: all fresh."""
    if hits is None:
        return payloads_no_sig(msgs, generic)
    payloads = [None if h is None else h.payload for h in hits]
    miss = [i for i, h in enumerate(hits) if h is None]
    for i, p in zip(miss, payloads_no_sig([msgs[i] for i in miss], generic)):
        payloads[i] = p
    return payloads


def pack_sender_batch(
    msgs: Sequence[IbftMessage],
    pad_lanes: int = 0,
    payloads: Optional[List[bytes]] = None,
    cache: Optional[PackCache] = None,
    cache_payloads: Optional[List[bytes]] = None,
    cache_hits: Optional[List[Optional[SenderPack]]] = None,
    rows=None,
):
    """Messages -> device-ready arrays for the sender-validity kernel.

    Returns ``(blocks, counts, r, s, v, senders, live)`` as numpy/jax
    arrays padded to bucketed static shapes.  A lane with a wrong-length
    sender or signature raises :class:`MalformedLaneError` naming the lane
    (validated up front, once — never a numpy reshape crash).  ``payloads``
    overrides the per-message signed bytes (the oversize-payload path
    substitutes empty payloads for lanes whose digest is computed on host).

    Built in bulk: nothing here runs once per message that can run once per
    drain.  The signed payloads come from the bulk encoder, signatures are
    split and limbed straight from bytes in one pass over r and s stacked
    (:func:`_signature_rows`), sender addresses are bulk-converted, and the
    keccak block packing is done once for the whole batch
    (``ops/keccak.py::pack_messages``).  Bit-identical to
    :func:`_pack_sender_batch_reference` (tests/test_pack_vectorized.py).

    ``rows`` hands in the ``(r, s, v, senders, live)`` arrays to write, dead
    (:func:`_dead_rows`) and of the pack's lane count: a caller that lays
    several packs out in one dispatch allocates once at the dispatch's lane
    count and passes each pack its slice, so that no row is copied again.

    ``cache`` (a :class:`~go_ibft_tpu.verify.pipeline.PackCache`) reuses a
    message's encoded payload + limb rows from an earlier pack and stores
    the fresh lanes, by reference and in one call; ``cache_payloads``
    supplies the TRUE payloads for cache stores when ``payloads`` carries
    substituted (oversize-lane) bytes — without it, an explicit
    ``payloads`` disables stores so a substituted payload can never poison
    the cache.  ``cache_hits`` passes lookups a caller already performed.

    Empty input returns a fully-dead padded batch (all ``live`` False,
    smallest block bucket) instead of raising — an empty drain is a no-op,
    not a crash.
    """
    n = len(msgs)
    sigs = [m.signature for m in msgs]
    addrs = [m.sender for m in msgs]
    # Length validation up front (the whole-batch frombuffer views below
    # would otherwise die in an opaque numpy reshape): the error names the
    # TRUE lane index so degraded-mode drains can quarantine exactly it.
    for i, (sig, addr) in enumerate(zip(sigs, addrs)):
        if len(sig) != SIG_BYTES:
            raise MalformedLaneError(i, "signature", SIG_BYTES, len(sig))
        if len(addr) != ADDRESS_BYTES:
            raise MalformedLaneError(i, "sender", ADDRESS_BYTES, len(addr))
    bb = _lane_count(n, pad_lanes)
    r_limbs, s_limbs, v, senders, live = _dead_rows(bb) if rows is None else rows
    if n == 0:
        blocks = np.zeros((bb, _BLOCK_BUCKETS[0], 17, 2), dtype=np.uint32)
        return blocks, np.ones((bb,), np.int32), r_limbs, s_limbs, v, senders, live

    hits = cache_hits
    if hits is None and cache is not None:
        hits = cache.lookup_many(msgs)
    if hits is not None and hits.count(None) == n:
        hits = None  # a flood's first pack: every lane is fresh
    if payloads is None:
        payloads = cache_payloads = _signed_payloads(msgs, hits)

    max_len = max(map(len, payloads))
    nb = _bucket((max_len + 1 + dk.RATE_BYTES - 1) // dk.RATE_BYTES, _BLOCK_BUCKETS)
    blocks = np.zeros((bb, nb, 17, 2), dtype=np.uint32)
    counts = np.ones((bb,), dtype=np.int32)
    pb, pc = dk.pack_messages(payloads, nb)
    blocks[:n] = pb
    counts[:n] = pc

    if hits is None:
        fresh: Sequence[int] = range(n)
        r_limbs[:n], s_limbs[:n], v[:n] = _signature_rows(sigs)
        senders[:n] = _address_rows(addrs)
    else:
        fresh = [i for i, h in enumerate(hits) if h is None]
        if fresh:
            idx = np.asarray(fresh)
            r_limbs[idx], s_limbs[idx], v[idx] = _signature_rows(
                [sigs[i] for i in fresh]
            )
            senders[idx] = _address_rows([addrs[i] for i in fresh])
        for i, h in enumerate(hits):
            if h is not None:
                r_limbs[i] = h.r_limbs
                s_limbs[i] = h.s_limbs
                v[i] = h.v
                senders[i] = h.sender_words
    if cache is not None and cache_payloads is not None and fresh:
        cache.store_rows(
            msgs if hits is None else [msgs[i] for i in fresh],
            SenderRows(cache_payloads, r_limbs, s_limbs, v, senders),
            fresh,
        )
    live[:n] = True
    return blocks, counts, r_limbs, s_limbs, v, senders, live


def pack_seal_batch(proposal_hash: bytes, seals: Sequence[CommittedSeal], pad_lanes: int = 0):
    """Seals -> device-ready arrays for the seal-validity kernel.

    Returns ``(hash_words, r, s, v, signers, live)``; the proposal hash is
    broadcast to every lane as little-endian value words.  Vectorized like
    :func:`pack_sender_batch`; an empty seal sequence returns a fully-dead
    padded batch.  Lengths are validated up front
    (:class:`MalformedLaneError` names the bad lane; a non-32-byte proposal
    hash is a plain ``ValueError`` — it is batch-wide, not a lane).
    """
    if len(proposal_hash) != 32:
        raise ValueError(
            f"proposal hash must be 32 bytes, got {len(proposal_hash)}"
        )
    for i, s in enumerate(seals):
        if len(s.signature) != SIG_BYTES:
            raise MalformedLaneError(i, "signature", SIG_BYTES, len(s.signature))
        if len(s.signer) != ADDRESS_BYTES:
            raise MalformedLaneError(i, "signer", ADDRESS_BYTES, len(s.signer))
    n = len(seals)
    bb = _lane_count(n, pad_lanes)
    hw = np.frombuffer(proposal_hash, ">u4")[::-1].astype(np.uint32)  # LE words
    hash_zw = np.broadcast_to(hw, (bb, 8)).copy()
    r_limbs, s_limbs, v, signers, live = _dead_rows(bb)
    if n:
        r_limbs[:n], s_limbs[:n], v[:n] = _signature_rows(
            [s.signature for s in seals]
        )
        signers[:n] = _address_rows([s.signer for s in seals])
        live[:n] = True
    return hash_zw, r_limbs, s_limbs, v, signers, live


def validate_seal_lanes(lanes: Sequence[Tuple[bytes, CommittedSeal]]) -> None:
    """Shape-validate (proposal_hash, seal) lanes, naming the bad lane.

    The ONE definition of what a well-formed sync lane is — shared by the
    per-lane packer and the resilient fallback rung so their
    :class:`MalformedLaneError` quarantine semantics can never drift."""
    for i, (proposal_hash, seal) in enumerate(lanes):
        if len(proposal_hash) != 32:
            raise MalformedLaneError(i, "proposal_hash", 32, len(proposal_hash))
        if len(seal.signature) != SIG_BYTES:
            raise MalformedLaneError(i, "signature", SIG_BYTES, len(seal.signature))
        if len(seal.signer) != ADDRESS_BYTES:
            raise MalformedLaneError(i, "signer", ADDRESS_BYTES, len(seal.signer))


def pack_seal_lanes(
    lanes: Sequence[Tuple[bytes, CommittedSeal]], pad_lanes: int = 0
):
    """(proposal_hash, seal) lanes -> device arrays with PER-LANE hashes.

    The block-sync drain verifies committed seals across a whole height
    RANGE at once — every height signs its own proposal hash, so unlike
    :func:`pack_seal_batch` (one hash broadcast to all lanes) each lane
    here carries its own 32-byte hash.  The device kernel already takes
    per-lane hash words (``hash_zw`` rows); only the packers assumed one
    hash per drain.  Returns the same ``(hash_words, r, s, v, signers,
    live)`` tuple; lengths are validated up front, once, with
    :class:`MalformedLaneError` naming the lane (a bad per-lane hash IS a
    lane fault here, not a batch-wide error).
    """
    validate_seal_lanes(lanes)
    return _seal_lane_rows(
        [h for h, _ in lanes],
        [s.signature for _, s in lanes],
        [s.signer for _, s in lanes],
        _lane_count(len(lanes), pad_lanes),
    )


def _seal_lane_rows(
    hashes: Sequence[bytes],
    sigs: Sequence[bytes],
    signers: Sequence[bytes],
    lanes: int,
    rows=None,
):
    """:func:`pack_seal_lanes` on length-checked fields, a list each, into
    ``rows`` where given (as :func:`pack_sender_batch` takes them)."""
    n = len(sigs)
    r_limbs, s_limbs, v, claimed, live = _dead_rows(lanes) if rows is None else rows
    hash_zw = np.zeros((lanes, 8), dtype=np.uint32)
    if n:
        # Same word layout as pack_seal_batch: 8 big-endian u32 words per
        # hash, reversed to little-endian value order — vectorized over
        # all lanes in one frombuffer.
        hash_zw[:n] = np.frombuffer(b"".join(hashes), ">u4").reshape(n, 8)[:, ::-1]
        r_limbs[:n], s_limbs[:n], v[:n] = _signature_rows(sigs)
        claimed[:n] = _address_rows(signers)
        live[:n] = True
    return hash_zw, r_limbs, s_limbs, v, claimed, live


# -- reference loop packers (parity oracles) ---------------------------------
# The original per-message implementations, kept verbatim so the vectorized
# packers above have bit-identity references to diff against
# (tests/test_pack_vectorized.py); not hot paths.


def _pack_sender_batch_reference(
    msgs: Sequence[IbftMessage],
    pad_lanes: int = 0,
    payloads: Optional[List[bytes]] = None,
):
    """Per-message loop twin of :func:`pack_sender_batch`."""
    n = len(msgs)
    bb = _lane_count(n, pad_lanes)
    if payloads is None:
        payloads = [m.encode(include_signature=False) for m in msgs]
    max_len = max(len(p) for p in payloads)
    nb = _bucket((max_len + 1 + dk.RATE_BYTES - 1) // dk.RATE_BYTES, _BLOCK_BUCKETS)
    blocks = np.zeros((bb, nb, 17, 2), dtype=np.uint32)
    counts = np.ones((bb,), dtype=np.int32)
    pb, pc = dk._pack_messages_reference(payloads, nb)
    blocks[:n] = pb
    counts[:n] = pc
    rs, ss, vs = [], [], []
    senders = np.zeros((bb, 5), dtype=np.uint32)
    for i, m in enumerate(msgs):
        r, s, v = split_signature(m.signature)
        rs.append(r)
        ss.append(s)
        vs.append(v)
        senders[i] = dk.address_to_words(m.sender)
    live = np.zeros((bb,), dtype=bool)
    live[:n] = True
    return (
        blocks,
        counts,
        np.asarray(_pack_scalars(rs, bb)),
        np.asarray(_pack_scalars(ss, bb)),
        np.pad(np.asarray(vs, np.int32), (0, bb - n)),
        senders,
        live,
    )


def _pack_seal_batch_reference(
    proposal_hash: bytes, seals: Sequence[CommittedSeal], pad_lanes: int = 0
):
    """Per-message loop twin of :func:`pack_seal_batch`."""
    n = len(seals)
    bb = _lane_count(n, pad_lanes)
    hw = np.frombuffer(proposal_hash, ">u4")[::-1].astype(np.uint32)  # LE words
    hash_zw = np.broadcast_to(hw, (bb, 8)).copy()
    rs, ss, vs = [], [], []
    signers = np.zeros((bb, 5), dtype=np.uint32)
    for i, seal in enumerate(seals):
        r, s, v = split_signature(seal.signature)
        rs.append(r)
        ss.append(s)
        vs.append(v)
        signers[i] = dk.address_to_words(seal.signer)
    live = np.zeros((bb,), dtype=bool)
    live[:n] = True
    return (
        hash_zw,
        np.asarray(_pack_scalars(rs, bb)),
        np.asarray(_pack_scalars(ss, bb)),
        np.pad(np.asarray(vs, np.int32), (0, bb - n)),
        signers,
        live,
    )


# Largest payload the device digest path can absorb; one byte is reserved
# for keccak padding in the last block.
MAX_DEVICE_PAYLOAD = _BLOCK_BUCKETS[-1] * dk.RATE_BYTES - 1


def pack_sender_digest_rows(
    msgs: Sequence[IbftMessage],
    *,
    cache=None,
    hits: Optional[list] = None,
    pad_lanes: int = 0,
    rows=None,
    span=None,
    hz=None,
):
    """The device sender-route pack sequence: cache-hit reuse, oversize
    payloads digested on host, everything else on the device digest
    kernel.

    A payload above the largest keccak block bucket (a PREPREPARE
    carrying a round-change certificate easily is) must NOT crash the
    packer — r05 observed exactly that taking a cluster down when a
    round change produced a 57-block proposal.  Such lanes get their
    digest from the (native) host keccak, injected into the ``zw`` rows;
    the expensive part — the recovery ladder — still runs on device for
    every lane.

    ONE implementation serves both the single-tenant plane
    (:meth:`DeviceBatchVerifier._sender_inputs`) and the multi-tenant
    coalesced dispatcher (``sched/dispatch.py``), so a fix to the
    oversize/cache path can never apply to one and silently miss the
    other.  ``cache`` is the store target for fresh packs (a
    :class:`PackCache`, or the scheduler's per-tenant routing shim);
    ``hits`` supplies pre-routed lookups (one ``lookup_many`` of ``cache``
    when omitted); ``rows`` is :func:`pack_sender_batch`'s.  ``span``, the
    caller's open ``verify.pack`` span, is told how many payloads the bulk
    encoder built from its template (``templated``); those it handed to
    ``IbftMessage.encode`` are counted under :data:`PACK_GENERIC_KEY`.
    ``hz`` are the hash rows of the seals that ride this chunk's dispatch
    (as many as the pack has lanes): ``zw`` is then both halves' rows, joined
    in the digest program (``joined`` on ``span``), or on the host where an
    oversize payload brought the digest rows there.  The blocks, the counts
    and ``hz`` go to the compiled call as the numpy arrays they are.
    Returns ``(zw, r, s, v, senders, live)``.
    """
    n = len(msgs)
    if hits is None and cache is not None:
        hits = cache.lookup_many(msgs)
    fresh = n if hits is None else hits.count(None)
    generic: List[int] = []
    payloads = _signed_payloads(msgs, None if fresh == n else hits, generic)
    if generic:
        metrics.inc_counter(PACK_GENERIC_KEY, len(generic))
    if span is not None:
        span.note(templated=fresh - len(generic))
    big = (
        [i for i, p in enumerate(payloads) if len(p) > MAX_DEVICE_PAYLOAD]
        if n and max(map(len, payloads)) > MAX_DEVICE_PAYLOAD
        else []
    )
    if big:
        device_payloads = list(payloads)
        for i in big:
            device_payloads[i] = b""
    else:
        device_payloads = payloads
    blocks, counts, r, s, v, senders, live = pack_sender_batch(
        msgs,
        pad_lanes=pad_lanes,
        payloads=device_payloads,
        cache=cache,
        cache_payloads=payloads,
        cache_hits=hits,
        rows=rows,
    )
    with cost_ledger.dispatch_span(
        "digest_words",
        route="device",
        live_mask=live,
        kernels=(("digest_words", _digest_kernel),),
        block=False,
        site="verify/batch.py:pack_sender_digest_rows",
    ):
        if hz is None or big:
            zw = _digest_kernel(blocks, counts)
        else:
            zw = _digest_kernel(blocks, counts, hz)
            if span is not None:
                span.note(joined=len(hz))
    if big:
        zw = np.array(zw)  # writable host copy (np.asarray can be RO)
        digests = keccak256_many([payloads[i] for i in big])
        for i, digest in zip(big, digests):
            zw[i] = np.frombuffer(digest, ">u4")[::-1].astype(np.uint32)
        if hz is not None:
            zw = np.concatenate([zw, hz])
    return zw, r, s, v, senders, live


class DeviceBatchVerifier:
    """One ``jit`` batch per phase on the active JAX backend.

    ``validators_for_height`` supplies the voting-power map (the engine's
    ``ValidatorBackend.get_voting_powers`` works directly); validator
    address tables are packed to device arrays once per distinct address
    set and cached; ``cache_heights`` bounds how many sets' tables (and how
    many heights' seal verdicts) are kept.
    """

    def __init__(self, validators_for_height: ValidatorSource, cache_heights: int = 4):
        from ..utils.jaxcache import enable_persistent_cache

        enable_persistent_cache()
        self._validators = validators_for_height
        # One full dispatch's lane capacity: floods above it chunk into
        # multiple dispatches riding the double-buffered pipeline.  The
        # mesh subclass raises it to ``largest bucket x dp`` so a multi-
        # height drain coalesces into ONE sharded dispatch instead of dp
        # sequential single-device ones.
        self._dispatch_cap = _BATCH_BUCKETS[-1]
        # Obs route label: the mesh subclass overrides to "mesh" so every
        # span a drain emits names the route that actually served it.
        self._route = "device"
        # What follows is keyed by what it HOLDS, never by a height: a table
        # by its address set.  A set seen before costs no pack and no upload
        # whatever height selects it (a fixed committee: one upload a
        # process; stakes that move inside the same addresses: the same
        # table), least recently used out first.
        self._tables: "OrderedDict[FrozenSet[bytes], Tuple[np.ndarray, List[bytes]]]" = OrderedDict()
        # Device-resident twins of the packed tables: uploaded once a set
        # and handed to every dispatch that is judged against it.
        self._tables_dev: Dict[FrozenSet[bytes], jnp.ndarray] = {}
        self._cache_sets = cache_heights
        # Per-message pack cache (round-scoped, like the engine's
        # seal-verdict cache): engine wakeups that re-verify the same
        # messages (certificate validation re-runs per wakeup) skip the
        # re-encode + re-limb entirely.
        self._pack_cache = PackCache()
        # What the joint COMMIT dispatches of ``verify_senders`` decided
        # about the seals that rode them; the seal drains look here first.
        self._seal_verdicts = SealVerdictCache(heights=cache_heights)

    def note_round(self, round_: int) -> None:
        """Engine hook: tag pack-cache entries with the live round (round
        advances drive the cache's oldest-round-first eviction)."""
        self._pack_cache.note_round(round_)

    def reset_pack_cache(self) -> None:
        """Engine hook: new sequence -> drop all cached packs."""
        self._pack_cache.clear()

    def _pack_caches(self) -> List["PackCache"]:
        """The lifecycle-scoped caches this verifier owns (EngineScope)."""
        return [self._pack_cache]

    def scoped(self, owner: str) -> "EngineScope":
        """A per-engine lifecycle facade for SHARING this verifier across
        engines: see :class:`EngineScope`."""
        return EngineScope(self, owner)

    def quarantine(self, msgs: Sequence[IbftMessage]) -> None:
        """Degraded-mode hook: lanes condemned by a quarantining drain.

        Evicts the lanes' cached packs so a corrected re-send (or a retry
        after a transient device fault) re-packs from the live bytes
        instead of being served the lane that was just condemned."""
        for m in msgs:
            self._pack_cache.evict(m)

    def warmup(
        self,
        lanes: Sequence[int] = (8,),
        blocks: Sequence[int] = (2, 8),
        table_rows: int = 8,
    ) -> None:
        """Pre-compile the kernels for the given shape buckets.

        A consensus engine must never stall mid-round on an XLA compile
        (the round timer would expire and tear the round down); call this
        once at node startup.  With the persistent cache, repeat processes
        pay only a cache load.
        """
        table = jnp.zeros((table_rows, 5), jnp.uint32)
        for bb in lanes:
            r, s, v, claimed, live = _dead_rows(bb)
            hashes = np.zeros((bb, 8), dtype=np.uint32)
            # Each program in the call forms the drains use: a dispatch's
            # rows are host arrays; its ``zw`` is a host array too (a seal
            # drain's hash rows) or the digest program's output (a sender
            # drain's).  A form the compiled call has not seen takes its
            # slow path once, and the cost ledger counts that as a compile.
            for zw in (hashes, jnp.asarray(hashes)):
                # route="warmup": startup compiles must not pollute the
                # production routes' occupancy, but their compile events ARE
                # the cost the ledger exists to measure (the AOT-manifest
                # baseline of ROADMAP item 5).
                with cost_ledger.dispatch_span(
                    "ecdsa_recover",
                    route="warmup",
                    padded=bb,
                    kernels=(("ecdsa_recover", _recover_kernel),),
                    site="verify/batch.py:warmup",
                ):
                    _recover_kernel(
                        zw, r, s, v, claimed, table, live
                    ).block_until_ready()
            digests = [(bb, ())]
            half = bb // 2
            if self._joint_lanes(half) == half:
                # A sender flood's width (twice a rung under the fold
                # width): the digest program joins its halves.
                digests.append((half, (np.zeros((half, 8), dtype=np.uint32),)))
            for nb in blocks:
                for width, hz in digests:
                    with cost_ledger.dispatch_span(
                        "digest_words",
                        route="warmup",
                        padded=width,
                        kernels=(("digest_words", _digest_kernel),),
                        site="verify/batch.py:warmup",
                    ):
                        _digest_kernel(
                            np.zeros((width, nb, 17, 2), dtype=np.uint32),
                            np.ones((width,), dtype=np.int32),
                            *hz,
                        ).block_until_ready()

    # -- validator table management ------------------------------------

    def _set_of(self, height: int) -> FrozenSet[bytes]:
        """The address set ``height`` selects (well-formed addresses only:
        no other can match a claimed signer)."""
        return frozenset(
            a for a in self._validators(height) if len(a) == ADDRESS_BYTES
        )

    def _table_and_addrs(
        self, members: FrozenSet[bytes]
    ) -> Tuple[np.ndarray, List[bytes]]:
        """Packed address table of the set ``members`` + the address list
        its rows follow."""
        hit = self._tables.get(members)
        if hit is not None:
            self._tables.move_to_end(members)
            return hit
        addrs = sorted(members)
        hit = self._tables[members] = (pack_validator_table(addrs), addrs)
        if len(self._tables) > self._cache_sets:
            evicted, _ = self._tables.popitem(last=False)
            self._tables_dev.pop(evicted, None)
        return hit

    def _put_table(self, table: np.ndarray) -> jnp.ndarray:
        """One host -> device copy of a packed table."""
        return jnp.asarray(table)

    def _table_dev_of(self, members: FrozenSet[bytes]) -> jnp.ndarray:
        """Device-resident packed table of ``members`` (uploaded once a set)."""
        table, _ = self._table_and_addrs(members)
        hit = self._tables_dev.get(members)
        if hit is None:
            hit = self._tables_dev[members] = self._put_table(table)
            metrics.inc_counter(TABLE_UPLOADS_KEY)
        return hit

    def _table_dev(self, height: int) -> jnp.ndarray:
        """Device-resident table of the set ``height`` selects."""
        return self._table_dev_of(self._set_of(height))

    # -- shared pack/dispatch scaffolding -------------------------------
    # One implementation of the idxs-filter -> pack -> kernel -> unpack ->
    # metrics pipeline serves all the public entry points.

    @staticmethod
    def _well_formed_sender(m: IbftMessage, height: Optional[int]) -> bool:
        return (
            m.view is not None
            and (height is None or m.view.height == height)
            and len(m.sender) == ADDRESS_BYTES
            and len(m.signature) == SIG_BYTES
        )

    @staticmethod
    def _well_formed_seal(seal: CommittedSeal) -> bool:
        return (
            len(seal.signer) == ADDRESS_BYTES
            and len(seal.signature) == SIG_BYTES
        )

    def _pad_lanes(self, n: int) -> int:
        """Minimum packed lane count for an ``n``-lane dispatch.

        0 on a single device (the packers bucket freely); the mesh
        subclass returns the smallest bucket-aligned multiple of the
        device count so every shard gets an identical local shape."""
        return 0

    # Cost-ledger program identity of a dispatch (the compile-budget family
    # names; the mesh subclass renames it to the sharded twin).
    _program = "ecdsa_recover"

    def _dispatch_async(self, inputs, table):
        """Queue the recover kernel.

        ``inputs`` = (zw, r, s, v, claimed, live): the packers' numpy rows
        (``zw`` the digest program's device rows in a sender drain), handed
        to the compiled call as they are, like ``table`` (device-resident,
        a validator set's); ``operands`` on the span counts the host arrays
        among ``inputs``, ``table_rows`` is the table rung the executable was
        built for.  Nothing may write to one of them from here on.
        Returns the mask as a device future WITHOUT blocking — JAX async
        dispatch lets the caller pack the next batch while this one
        executes (:mod:`go_ibft_tpu.verify.pipeline`).
        """
        with cost_ledger.dispatch_span(
            self._program,
            route=self._route,
            live_mask=inputs[5],
            kernels=((self._program, _recover_kernel),),
            block=False,
            site="verify/batch.py:_dispatch_async",
        ):
            zw, r, s, v, claimed, live = inputs
            with trace.span(
                "verify.dispatch",
                route="device",
                operands=_host_arrays(inputs),
                ladder=ladder_of(live.shape[0]),
                table_rows=table.shape[0],
            ):
                return _recover_kernel(zw, r, s, v, claimed, table, live)

    @staticmethod
    def _readback(mask_dev) -> np.ndarray:
        """Block on one :meth:`_dispatch_async` handle -> the host mask."""
        with trace.span("verify.device_wait", route="device"):
            return np.asarray(mask_dev)

    def _dispatch(self, inputs, table, metric: str) -> np.ndarray:
        """Synchronous pack->kernel->readback (single-batch callers)."""
        t0 = time.perf_counter()
        mask = self._readback(self._dispatch_async(inputs, table))
        dt_ms = (time.perf_counter() - t0) * 1e3
        metrics.observe(("go-ibft", "device", metric), dt_ms)
        metrics.observe_fixed(VERIFY_DRAIN_MS_KEY + ("device",), dt_ms)
        # The dispatch record itself landed in _dispatch_async (block=False
        # — queue time only); the synchronous path knows the full
        # block-until-ready wall, so attribute it here.
        cost_ledger.add_wall_ms(self._program, self._route, dt_ms)
        return mask

    # Largest payload the device digest path can absorb; one byte is
    # reserved for keccak padding in the last block.
    _MAX_DEVICE_PAYLOAD = MAX_DEVICE_PAYLOAD

    def _sender_inputs(
        self, msgs: List[IbftMessage], pad_lanes: int = 0, rows=None, hz=None
    ):
        """Pack envelopes; digest on device, oversize payloads on host.

        Payload encodings and limb rows come from the pack cache when this
        engine already packed the message (certificate re-validation runs
        per round-change wakeup over the same envelopes); fresh lanes pack
        in bulk and are stored back by reference.  Serves the per-phase
        dispatches and (via ``pad_lanes``, and ``rows`` for the slice of a
        dispatch's arrays to write, ``hz`` for the seals' hash rows to join
        behind the digest rows) the layouts that put envelopes and seals in
        one dispatch; the sequence itself lives in
        :func:`pack_sender_digest_rows` (shared with the multi-tenant
        coalesced dispatcher).
        """
        pad_lanes = max(pad_lanes, self._pad_lanes(len(msgs)))
        with trace.span("verify.pack", kind="senders", lanes=len(msgs)) as span:
            return pack_sender_digest_rows(
                msgs,
                cache=self._pack_cache,
                pad_lanes=pad_lanes,
                rows=rows,
                span=span,
                hz=hz,
            )

    def _seal_inputs(
        self, proposal_hash: bytes, seals: List[CommittedSeal], pad_lanes: int = 0
    ):
        pad_lanes = max(pad_lanes, self._pad_lanes(len(seals)))
        with trace.span("verify.pack", kind="seals", lanes=len(seals)):
            return pack_seal_batch(proposal_hash, seals, pad_lanes=pad_lanes)

    # -- BatchVerifier protocol ----------------------------------------

    def _chunk_pipeline(self):
        """The double-buffered executor of a chunked drain and the two seams
        it drives: ``pack(item)`` returns ``(item, inputs, table_dev)``."""
        # ledger_key: the pipeline attributes each chunk's readback wait
        # to the mask program (the dispatch records landed per chunk in
        # _dispatch_async; the wait is the only timing the async path
        # cannot observe itself).
        return (
            VerifyPipeline(depth=2, ledger_key=(self._program, self._route)),
            lambda p: (p[0], self._dispatch_async(p[1], p[2])),
            lambda h: (h[0], self._readback(h[1])),
        )

    def _run_chunk_pipeline(self, items, pack, metric: str):
        """Pipeline (pack -> async dispatch -> readback) over chunk items.

        ``items`` are opaque chunk descriptors; ``pack(item)`` returns
        ``(item, inputs, table_dev)``.  Host packing of chunk N+1 overlaps
        device execution of chunk N (double buffered) — a flood above the
        largest lane bucket no longer serializes pack/dispatch/readback
        per chunk.  Returns ``[(item, mask), ...]`` in item order.
        """
        t0 = time.perf_counter()
        pipeline, dispatch, readback = self._chunk_pipeline()
        report = pipeline.run(items, pack, dispatch=dispatch, readback=readback)
        metrics.observe(
            ("go-ibft", "device", metric), (time.perf_counter() - t0) * 1e3
        )
        return report.results

    def _stream_chunk_pipeline(self, items, pack, metric: str):
        """:meth:`_run_chunk_pipeline` as a generator: ``items`` is pulled a
        chunk at a time (what makes chunk N+1 runs while chunk N is on the
        device) and each ``(item, mask)`` is yielded as it is read back."""
        t0 = time.perf_counter()
        pipeline, dispatch, readback = self._chunk_pipeline()
        try:
            yield from pipeline.stream(
                items, pack, dispatch=dispatch, readback=readback
            )
        finally:
            metrics.observe(
                ("go-ibft", "device", metric), (time.perf_counter() - t0) * 1e3
            )

    # -- the joint COMMIT dispatch ---------------------------------------
    # A committed seal signs the proposal hash carried IN its COMMIT, so
    # its verdict is known the moment the message arrives: a pure function
    # of (carried hash, seal bytes, claimed signer, the height's table).
    # ``verify_senders`` therefore verifies every well-formed COMMIT's seal
    # in its envelope's drain — in the same dispatch where the rung is under
    # the fold width (``_FOLD_LANES``: twice the lanes are free there), else
    # as the drain's next chunk, in the same program, packed while the
    # envelopes run — and keeps the verdicts for the seal drains, which
    # look there before they pack.

    def _joint_lanes(self, n: int) -> int:
        """Lanes of each half of a sender chunk of ``n`` envelopes that runs
        at twice its rung, the second half for its COMMITs' seals: the rung
        itself where that is under the fold width (99 envelopes of a
        100-validator committee: 128 + 128 = 256 lanes, the first width
        that fills whole tiles, at the cost of 128).  0 from the fold
        width up, where twice the lanes cost twice (299 envelopes in 512
        lanes): there the seals are the drain's next chunk, at the
        envelopes' lane count."""
        if n > _BATCH_BUCKETS[-1]:
            return 0
        lanes = _bucket(n, _BATCH_BUCKETS)
        return lanes if lanes < _FOLD_LANES else 0

    @staticmethod
    def _seal_key(m: IbftMessage) -> SealKey:
        return (m.sender, m.commit_data.proposal_hash, m.commit_data.committed_seal)

    def _seal_riders(self, sub: List[IbftMessage], height: int) -> List[int]:
        """Positions in ``sub`` of the COMMITs whose seal rides along: a
        32-byte carried hash, a well-formed seal (the sender's length is
        the envelope's, checked already), no verdict yet."""
        riders = [
            j
            for j, m in enumerate(sub)
            if m.type == MessageType.COMMIT
            and m.commit_data is not None
            and len(m.commit_data.proposal_hash) == 32
            and len(m.commit_data.committed_seal) == SIG_BYTES
        ]
        if not riders:
            return riders
        known = self._seal_verdicts.lookup(
            height, [self._seal_key(sub[j]) for j in riders]
        )
        return [j for j, hit in zip(riders, known) if hit is None]

    def _rider_inputs(self, commits: List[IbftMessage], lanes: int, rows=None):
        """The seal rows of ``commits`` on ``lanes`` lanes: per-lane hash
        words (each COMMIT's own carried hash), claimed signer = sender.
        :meth:`_seal_riders` chose them by their lengths: not checked again."""
        with trace.span("verify.pack", kind="seals", lanes=len(commits)):
            return _seal_lane_rows(
                [m.commit_data.proposal_hash for m in commits],
                [m.commit_data.committed_seal for m in commits],
                [m.sender for m in commits],
                lanes,
                rows,
            )

    def _joint_inputs(self, sub: List[IbftMessage], riders: List[int], lanes: int):
        """Envelope rows in the first ``lanes`` lanes, the riders' seal rows
        in the second, in the riders' order: the joint layout.  The rows are
        allocated once, at the dispatch's lane count, and each half is packed
        into its slice: the seals first, because
        their hash rows go through the envelopes' digest launch, which joins
        them behind its own rows (one launch, and no eager JAX operation
        between the packers and the recover launch)."""
        rows = _dead_rows(2 * lanes)
        if riders:
            hz = self._rider_inputs(
                [sub[j] for j in riders], lanes, tuple(a[lanes:] for a in rows)
            )[0]
        else:  # a dead half: no pack span
            hz = np.zeros((lanes, 8), dtype=np.uint32)
        zw = self._sender_inputs(sub, lanes, tuple(a[:lanes] for a in rows), hz)[0]
        return (zw,) + rows

    def cached_seal_verdicts(
        self, proposal_hash: bytes, seals: Sequence[CommittedSeal], height: int
    ) -> List[Optional[bool]]:
        """Per lane, the verdict a joint dispatch left for exactly this
        (height, signer, hash, signature); ``None`` where none did."""
        return self._seal_verdicts.lookup(
            height, [(s.signer, proposal_hash, s.signature) for s in seals]
        )

    def _serve_cached_seals(self, proposal_hash, seals, idxs, height, out):
        """Write the cached verdicts of lanes ``idxs`` into ``out``; returns
        ``(served, missed)`` lane lists.  Counted, never re-noted: a
        ``verify.verdicts`` instant counts a lane where it was judged."""
        known = self.cached_seal_verdicts(
            proposal_hash, [seals[i] for i in idxs], height
        )
        served, missed = [], []
        for i, hit in zip(idxs, known):
            if hit is None:
                missed.append(i)
            else:
                out[i] = hit
                served.append(i)
        if served:
            metrics.inc_counter(SEAL_VERDICT_HITS_KEY, len(served))
        if missed:
            metrics.inc_counter(SEAL_VERDICT_MISSES_KEY, len(missed))
        return served, missed

    def verify_senders(self, msgs: Sequence[IbftMessage]) -> np.ndarray:
        """Envelope validity mask, one recover dispatch per chunk.

        A COMMIT's committed seal is verified in the SAME drain as its
        envelope.  A chunk whose rung is under the fold width runs at twice
        the rung, at the rung's cost, with the seals in the second half
        (:meth:`_joint_lanes`, :meth:`_joint_inputs`: 99 envelopes + 99
        seals are one 256-lane run instead of two 128-lane runs in series;
        a PREPARE flood leaves that half dead, so that a committee's floods
        are ONE program); from the fold width up the seals are the next
        chunk of the double-buffered pipeline, in the envelopes' program
        (299 + 299 at 300 validators: the seals pack while the envelopes
        run, and no engine wake-up lies between).  The seal
        drains that follow are served from the verdicts this leaves
        (:class:`SealVerdictCache`).  The mask returned is bit for bit the
        envelope-only one.  Messages of other types, malformed seals and
        seals that already have a verdict do not ride; if a dispatch that
        carries seals faults, nothing of it is kept, the envelopes run
        alone as before and the seals wait for their own drain."""
        if not msgs:
            return np.zeros(0, dtype=bool)
        out = np.zeros(len(msgs), dtype=bool)
        by_height: Dict[int, List[int]] = {}
        for i, m in enumerate(msgs):
            if self._well_formed_sender(m, None):
                by_height.setdefault(m.view.height, []).append(i)
        # Floods above the largest lane bucket run as multiple full
        # dispatches — a 2049-message burst costs two kernel launches, not
        # 2049 sequential host recovers (VERDICT r04 weak #6) — and the
        # chunks ride the double-buffered pipeline: chunk N+1 packs on host
        # while chunk N executes.
        # An item is ``(height, message idxs, riders, lanes)``: an envelope
        # chunk whose ``riders`` (positions in it) carry their seal in its
        # second ``lanes`` lanes, or — ``riders`` None — a chunk of seals
        # alone, on the ``lanes`` their envelopes' chunk ran at.
        items, seal_lanes = [], 0
        for height, idxs in by_height.items():
            for start in range(0, len(idxs), self._dispatch_cap):
                chunk = idxs[start : start + self._dispatch_cap]
                riders = self._seal_riders([msgs[i] for i in chunk], height)
                seal_lanes += len(riders)
                half = self._joint_lanes(len(chunk))
                items.append((height, chunk, riders if half else [], half))
                if riders and not half:
                    lanes = _lane_count(len(chunk), self._pad_lanes(len(chunk)))
                    items.append((height, [chunk[j] for j in riders], None, lanes))
        if not items:
            return out
        envelopes = [item for item in items if item[2] is not None]

        def pack(item):
            height, chunk, riders, lanes = item
            sub = [msgs[i] for i in chunk]
            if riders is None:
                inputs = self._rider_inputs(sub, lanes)
            elif lanes:  # with or without riders: one program a committee
                inputs = self._joint_inputs(sub, riders, lanes)
            else:
                inputs = self._sender_inputs(sub)
            return item, inputs, self._table_dev(height)

        with trace.span(
            "verify.drain",
            route=self._route,
            kind="senders",
            chunks=len(items),
            lanes=sum(len(chunk) for _, chunk, _, _ in envelopes),
            seal_lanes=seal_lanes,
        ):
            try:
                results = self._run_chunk_pipeline(items, pack, "verify_senders_ms")
            except Exception:
                if not seal_lanes:
                    raise
                # The fault may be the seal rows' and names no message: the
                # envelopes run as they did before the seals rode with them
                # (a fault that is theirs raises again, for the ladder to
                # bisect), the seals wait for their drain.
                metrics.inc_counter(JOINT_FAULTS_KEY)
                seal_lanes = 0
                items = [(height, chunk, [], 0) for height, chunk, _, _ in envelopes]
                results = self._run_chunk_pipeline(items, pack, "verify_senders_ms")
            # Mask-only drain: the voting-power reduction proper runs in
            # the caller (engine exact ints); this phase is the per-lane
            # verdict assembly.
            with trace.span("verify.quorum", route="mask"):
                carried = []
                for (height, chunk, riders, lanes), mask in results:
                    if riders is None:
                        commits, sealed = chunk, mask[: len(chunk)]
                    else:
                        out[np.asarray(chunk)] = mask[: len(chunk)]
                        commits = [chunk[j] for j in riders]
                        sealed = mask[lanes : lanes + len(riders)]
                    if commits:
                        carried.append(np.asarray(sealed, dtype=bool))
                        self._seal_verdicts.store(
                            height,
                            [self._seal_key(msgs[i]) for i in commits],
                            carried[-1],
                        )
            _note_verdicts("senders", self._route, out)
            if carried:
                metrics.inc_counter(SEAL_LANES_CARRIED_KEY, seal_lanes)
                _note_verdicts("seals", self._route, np.concatenate(carried))
        return out

    def verify_sender_rows(
        self,
        height: int,
        zw: np.ndarray,
        r: np.ndarray,
        s: np.ndarray,
        v: np.ndarray,
        claimed: np.ndarray,
        live: np.ndarray,
    ) -> np.ndarray:
        """Pre-digested rows -> per-lane sender-validity mask.

        The ICI tick drain (:meth:`go_ibft_tpu.net.ici
        .IciLockstepTransport.step`): the tick program already computed
        the payload digests on-device and gathered the
        signature/claimed-address rows, so this is ONE recover dispatch
        per call — no decode→re-encode→re-pack round trip.  ``zw`` is
        ``(n, 8)`` little-endian digest words; the remaining arrays
        follow :func:`pack_sender_batch` row layout."""
        n = int(zw.shape[0])
        if n == 0:
            return np.zeros(0, dtype=bool)
        bb = _lane_count(n, self._pad_lanes(n))
        if bb > n:
            pad = bb - n
            zw = np.concatenate([zw, np.zeros((pad,) + zw.shape[1:], zw.dtype)])
            r = np.concatenate([r, np.zeros((pad,) + r.shape[1:], r.dtype)])
            s = np.concatenate([s, np.zeros((pad,) + s.shape[1:], s.dtype)])
            v = np.concatenate([v, np.zeros((pad,), v.dtype)])
            claimed = np.concatenate(
                [claimed, np.zeros((pad,) + claimed.shape[1:], claimed.dtype)]
            )
            live = np.concatenate([live, np.zeros((pad,), dtype=bool)])
        mask = self._dispatch(
            (zw, r, s, v, claimed, live),
            self._table_dev(height),
            "verify_sender_rows_ms",
        )
        return np.asarray(mask[:n], dtype=bool)

    def verify_committed_seals(
        self, proposal_hash: bytes, seals: Sequence[CommittedSeal], height: int
    ) -> np.ndarray:
        """Seal validity mask.  Lanes whose COMMIT rode a joint
        :meth:`verify_senders` dispatch are served from the verdicts it
        left; only the others are packed and dispatched, and a drain whose
        lanes all hit dispatches nothing."""
        out = np.zeros(len(seals), dtype=bool)
        idxs = [i for i, s in enumerate(seals) if self._well_formed_seal(s)]
        if not idxs or len(proposal_hash) != 32:
            return out
        served, idxs = self._serve_cached_seals(
            proposal_hash, seals, idxs, height, out
        )
        items = [
            idxs[start : start + self._dispatch_cap]
            for start in range(0, len(idxs), self._dispatch_cap)
        ]

        def pack(chunk):
            return (
                chunk,
                self._seal_inputs(proposal_hash, [seals[i] for i in chunk]),
                self._table_dev(height),
            )

        with trace.span(
            "verify.drain",
            route=self._route,
            kind="seals",
            chunks=len(items),
            cached=len(served),
        ):
            if items:
                results = self._run_chunk_pipeline(items, pack, "verify_seals_ms")
                with trace.span("verify.quorum", route="mask"):
                    for chunk, mask in results:
                        out[np.asarray(chunk)] = mask[: len(chunk)]
            judged = np.ones(len(seals), dtype=bool)
            judged[served] = False
            _note_verdicts("seals", self._route, out, judged)
        return out

    def verify_seal_lanes(
        self, lanes: Sequence[Tuple[bytes, CommittedSeal]], height: LaneHeights
    ) -> np.ndarray:
        """Cross-height batched seal drain: per-lane proposal hashes.

        The block-sync catch-up path verifies EVERY committed seal of a
        fetched height range in one drain — each height signs its own
        proposal hash, so lanes carry their own hash words
        (:func:`pack_seal_lanes`); the recovery ladder and membership
        check are the same program as the single-hash drain.

        ``height`` is one height for every lane, or a height a lane.  This
        is :meth:`verify_seal_lane_stream` consumed: the whole list as one
        block, so a list call and a streamed range run one drain's code.
        """
        if not len(lanes):
            return np.zeros(0, dtype=bool)
        heights = {height} if _one_height(height) else set(height)
        return np.concatenate(
            [
                mask
                for masks in self._seal_lane_stream(
                    [(lanes, height)], heights, streamed=False
                )
                for mask in masks
            ]
        )

    def verify_seal_lane_stream(
        self,
        blocks: Iterable[Tuple[Sequence[Tuple[bytes, CommittedSeal]], LaneHeights]],
        heights: Iterable[int],
        lanes: int = 0,
    ) -> Iterator[List[np.ndarray]]:
        """The sync drain as a stream: a range's blocks in, their masks out.

        ``blocks`` yields ``(a block's lanes, its height)`` (one height, or a
        height a lane) and is pulled LAZILY, only as far as the next chunk
        of ``_dispatch_cap`` well-formed lanes needs: whatever makes a
        block's lanes (the sync client's proposal hash and lane tuples) runs
        while the chunk before is on the device.  ``heights`` are the
        heights the whole range selects, known up front: the ONE device
        table holds the union of their sets (the program's membership test
        is "the claimed signer is a row of the table", and the claimed
        signer is a host value).  A lane's verdict is ``signature valid AND
        signer in the set of the lane's OWN height``: where a height's set
        is narrower than the table, that last test is a host set lookup,
        made while the chunk packs.  A fixed committee is the case of one
        set: its table, nothing narrower.  ``lanes`` (how many the range
        has) is what a router above routes by; nothing here depends on it.

        Yields, after each read-back that completes a block, the list of
        the masks of the blocks it completed: every block gets exactly one
        mask, in order, as soon as the chunk that holds its last lane is
        back, with the next chunk on the device meanwhile (depth-2 double
        buffering, chunks cut ACROSS block boundaries; a malformed lane is
        skipped with verdict False and takes no room in a chunk).  It is
        ONE ``verify.drain`` of kind ``seal_lanes``, open from the first
        pull to the last mask.  Closed early, it reads back what is in
        flight and yields nothing more.
        """
        return self._seal_lane_stream(blocks, heights, streamed=True)

    def _seal_lane_chunks(self, blocks, pending: deque, heights_too: bool):
        """Chunks of ``_dispatch_cap`` well-formed lanes, cut across
        ``blocks`` as they come: ``(lanes, heights, parts)``, ``heights`` a
        lane where ``heights_too``, ``parts`` the ``(entry, where, lo, hi)``
        that say whose lanes ``[lo, hi)`` of the chunk are.  A block's entry
        ``[mask, lanes not read back, lanes]`` joins ``pending`` as it is
        pulled; ``where`` is None for a block that lies whole in one chunk
        (its mask is the chunk's slice), else its lanes' places in
        ``entry[0]``, which is made here."""
        cap = self._dispatch_cap
        chunk: list = []
        chunk_heights: list = []
        parts: list = []
        for lanes, height in blocks:
            n = len(lanes)
            good = [
                i
                for i, (proposal_hash, seal) in enumerate(lanes)
                if len(proposal_hash) == 32
                and len(seal.signer) == ADDRESS_BYTES
                and len(seal.signature) == SIG_BYTES
            ]
            whole = len(good) == n
            entry = [None, len(good), n]
            pending.append(entry)
            at = 0
            while at < len(good):
                take = min(cap - len(chunk), len(good) - at)
                lo = len(chunk)
                if whole:
                    where = None if take == n else slice(at, at + take)
                    chunk.extend(lanes if take == n else lanes[at : at + take])
                else:
                    where = good[at : at + take]
                    chunk.extend([lanes[i] for i in where])
                if where is not None and entry[0] is None:
                    entry[0] = np.zeros(n, dtype=bool)
                if heights_too:
                    if _one_height(height):
                        chunk_heights.extend([height] * take)
                    elif whole:
                        chunk_heights.extend(height[at : at + take])
                    else:
                        chunk_heights.extend([height[i] for i in where])
                parts.append((entry, where, lo, lo + take))
                at += take
                if len(chunk) == cap:
                    yield chunk, chunk_heights, parts
                    chunk, chunk_heights, parts = [], [], []
        if chunk:
            yield chunk, chunk_heights, parts

    def _seal_lane_stream(self, blocks, heights, streamed: bool):
        sets = {h: self._set_of(h) for h in set(heights)}
        table = frozenset().union(*sets.values())
        narrow = any(len(members) < len(table) for members in sets.values())
        pending: deque = deque()  # blocks pulled and not yet yielded

        def pack(item):
            chunk, chunk_heights, _parts = item
            with trace.span("verify.pack", kind="seal_lanes", lanes=len(chunk)):
                # The chunker took well-formed lanes only: no second check.
                inputs = _seal_lane_rows(
                    [proposal_hash for proposal_hash, _seal in chunk],
                    [seal.signature for _hash, seal in chunk],
                    [seal.signer for _hash, seal in chunk],
                    _lane_count(len(chunk), self._pad_lanes(len(chunk))),
                )
                # In the set of its own height, lane by lane, where some
                # height's set is not the whole table.
                own = None
                if narrow:
                    own = np.fromiter(
                        (
                            seal.signer in sets[h]
                            for (_hash, seal), h in zip(chunk, chunk_heights)
                        ),
                        dtype=bool,
                        count=len(chunk),
                    )
            return (item, own), inputs, self._table_dev_of(table)

        def ready():
            """The masks of the blocks at the head of ``pending`` that have
            every lane back (all False where none was well formed)."""
            nonlocal judged
            done = []
            while pending and not pending[0][1]:
                mask, _left, n = pending.popleft()
                done.append(np.zeros(n, dtype=bool) if mask is None else mask)
                judged += n
            return done

        chunks = judged = valid = outside = 0
        with trace.span(
            "verify.drain",
            route=self._route,
            kind="seal_lanes",
            streamed=streamed,
        ) as drain:
            results = self._stream_chunk_pipeline(
                self._seal_lane_chunks(blocks, pending, narrow),
                pack,
                "verify_seal_lanes_ms",
            )
            try:
                for ((chunk, _heights, parts), own), mask in results:
                    with trace.span("verify.quorum", route="mask"):
                        mask = mask[: len(chunk)]
                        if own is not None:
                            outside += int((mask & ~own).sum())
                            mask = mask & own
                        for entry, where, lo, hi in parts:
                            if where is None:
                                entry[0] = mask[lo:hi]
                            else:
                                entry[0][where] = mask[lo:hi]
                            entry[1] -= hi - lo
                        chunks += 1
                        valid += int(np.count_nonzero(mask))
                        done = ready()
                    if done:
                        yield done
                # Blocks with no well-formed lane, behind the last chunk.
                done = ready()
                if done:
                    yield done
                if trace.enabled():
                    trace.instant(
                        "verify.verdicts",
                        kind="seal_lanes",
                        route=self._route,
                        live=judged,
                        rejected=judged - valid,
                    )
            finally:
                # Closed early (the consumer refused a block): what is in
                # flight is read back, never abandoned.
                results.close()
                drain.note(chunks=chunks)
                if outside:
                    metrics.inc_counter(SYNC_OUT_OF_SET_LANES_KEY, outside)

    def verify_seals_early_exit(
        self,
        proposal_hash: bytes,
        seals: Sequence[CommittedSeal],
        height: int,
        threshold: Optional[int] = None,
    ) -> EarlyExitReport:
        """Power-ordered chunked seal drain that STOPS DISPATCHING at
        quorum.

        Lanes are ordered by claimed signer power (descending, stable)
        so the fewest chunks cover the threshold; the first chunk is the
        smallest lane bucket covering the claimed-power quorum prefix
        (optimistic: every lane valid), subsequent chunks double.  After
        each readback the exact host-int tally updates and the loop
        exits before the next dispatch once quorum is certain —
        remaining lanes are reported ``skipped``.  Verdicts for
        dispatched lanes are the kernel's usual mask, bit-identical to
        the sequential oracle; the mesh subclass shards each chunk like
        any other drain.

        Lanes whose COMMIT rode a joint :meth:`verify_senders` dispatch
        come first, from the verdicts it left (``verified`` true, their
        power in the tally): only the others are ordered, packed and
        dispatched, and a drain whose lanes all hit dispatches nothing.
        """
        n = len(seals)
        mask = np.zeros(n, dtype=bool)
        verified = np.zeros(n, dtype=bool)
        powers = self._validators(height)
        thr = (
            calculate_quorum(sum(powers.values()))
            if threshold is None
            else threshold
        )
        well_formed = [i for i, s in enumerate(seals) if self._well_formed_seal(s)]
        if len(proposal_hash) != 32:
            verified[:] = True
            return EarlyExitReport(mask, verified, thr <= 0, 0)
        # Malformed lanes have their (False) verdict without crypto.
        malformed = set(range(n)) - set(well_formed)
        if malformed:
            verified[np.asarray(sorted(malformed))] = True
        served, missed = self._serve_cached_seals(
            proposal_hash, seals, well_formed, height, mask
        )
        verified[served] = True
        tally = _PowerTally(powers, thr)
        claimed = _PowerTally(powers, thr)
        for i in served:
            if mask[i]:
                tally.add(seals[i].signer)
                claimed.add(seals[i].signer)
        # Power-ordered, stable: arrival order breaks ties so equal-power
        # sets (the common 1-power-each committee) drain in arrival order.
        order = sorted(missed, key=lambda i: -powers.get(seals[i].signer, 0))
        # First chunk: the claimed-power quorum prefix, bucket-padded —
        # the extra bucket lanes are verified for free (they pad anyway).
        prefix = 0
        for i in order:
            prefix += 1
            if claimed.add(seals[i].signer):
                break
        chunk = (
            min(_bucket(max(prefix, 1), _BATCH_BUCKETS), self._dispatch_cap)
            if order
            else 0
        )
        pos = 0
        with trace.span(
            "verify.early_exit",
            route=self._route,
            kind="seals",
            lanes=n,
            cached=len(served),
        ):
            while pos < len(order) and not tally.reached:
                take = order[pos : pos + chunk]
                cmask = self._dispatch(
                    self._seal_inputs(
                        proposal_hash, [seals[i] for i in take]
                    ),
                    self._table_dev(height),
                    "early_exit_ms",
                )
                for j, i in enumerate(take):
                    verified[i] = True
                    if cmask[j]:
                        mask[i] = True
                        tally.add(seals[i].signer)
                pos += len(take)
                chunk = min(chunk * 2, self._dispatch_cap)
            judged = verified.copy()
            judged[served] = False
            _note_verdicts("seals", self._route, mask, judged)
        skipped = len(order) - pos
        metrics.inc_counter(EARLY_EXIT_DRAINS_KEY)
        if skipped:
            metrics.inc_counter(EARLY_EXIT_SKIPPED_KEY, skipped)
        return EarlyExitReport(mask, verified, tally.reached, skipped)


QUARANTINED_LANES_KEY = ("go-ibft", "resilient", "quarantined_lanes")
DRAIN_FAULTS_KEY = ("go-ibft", "resilient", "drain_faults")
# Sync-drain streams a fast rung did not finish (it raised at some chunk):
# the blocks it had not answered went through the ladder as a list.
STREAM_FALLBACKS_KEY = ("go-ibft", "verify", "stream_fallbacks")


class _SourceFailed(Exception):
    """The CALLER's iterable of blocks raised while a rung's stream pulled
    from it (``__cause__``): not the rung's fault, and not to be mistaken
    for one."""

# Below this many lanes a sharded dispatch loses to one single-device
# dispatch: the mesh pads every drain to ``bucket x dp`` lanes and pays a
# multi-device launch, which only amortizes once the per-lane ladder work
# dominates.  Default = half the largest single-device bucket (a drain
# that nearly fills one device's biggest program is worth sharding);
# callers with a measured crossover pass their own.
MESH_CUTOVER_LANES = _BATCH_BUCKETS[-1] // 2


class ResilientBatchVerifier:
    """Degraded-mode drain: quarantine poison lanes, demote dead rungs.

    Implements the :class:`~go_ibft_tpu.core.backend.BatchVerifier`
    protocol over a fastest-first ladder of rungs — by default
    ``device -> host (native) -> pure Python``, with an optional
    ``mesh`` rung on top (lane-sharded drains; a mesh failure demotes to
    single-device exactly like a device failure demotes to host, and
    drains below ``mesh_cutover_lanes`` enter at the device rung
    directly) — governed by a
    :class:`~go_ibft_tpu.verify.pipeline.CircuitBreaker`:

    * **Poison batches never propagate.**  A drain whose rung raises
      (a device-side XLA ``RuntimeError``, a native verifier crash, a lane
      whose packing blows up) is bisected: halves re-verify independently,
      a single lane that still raises at this rung is retried one rung
      down, and only a lane no rung can process is condemned (mask False).
      :class:`MalformedLaneError` short-circuits the bisection — the
      packer already named the lane, so it quarantines immediately and the
      rest of the batch re-verifies in one piece.
    * **Circuit breaker.**  ``k`` consecutive faulted drains at a rung
      demote all traffic one rung down; after ``cooldown_s`` the breaker
      probes the faster rung with one live drain and climbs back on
      success.  Every transition is counted in
      :mod:`go_ibft_tpu.utils.metrics` (``("go-ibft", "breaker", ...)``).
    * **Quarantine eviction.**  Condemned sender lanes are reported to the
      fast rung's ``quarantine`` hook (when present), which evicts their
      :class:`~go_ibft_tpu.verify.pipeline.PackCache` entries so a
      corrected re-send is never served a stale packed lane.

    A drain therefore ALWAYS returns a verdict per lane and never raises —
    the liveness contract the chaos suites pin (ISSUE 3).
    """

    def __init__(
        self,
        device,
        host: Optional[HostBatchVerifier] = None,
        python: Optional[HostBatchVerifier] = None,
        *,
        mesh=None,
        mesh_cutover_lanes: Optional[int] = None,
        validators_for_height: Optional[ValidatorSource] = None,
        breaker: Optional["CircuitBreaker"] = None,
    ):
        if host is None or python is None:
            if validators_for_height is None:
                raise ValueError(
                    "validators_for_height required when host/python rungs "
                    "are not supplied"
                )
        if host is None:
            host = HostBatchVerifier(validators_for_height)
        if python is None:
            python = HostBatchVerifier(
                validators_for_height or host._validators,
                recover_fn=host_ecdsa.recover_pure,
            )
        # ``mesh`` (a MeshBatchVerifier or compatible) prepends a fourth,
        # fastest rung: a mesh fault demotes to single-device exactly like
        # a device fault demotes to host.  Drains below the lane cutover
        # enter at the device rung directly — sharding a handful of lanes
        # pays padding + multi-device launch for nothing.
        self._rungs = [("device", device), ("host", host), ("python", python)]
        self.mesh = mesh
        if mesh is not None:
            self._rungs.insert(0, ("mesh", mesh))
        self.mesh_cutover = (
            mesh_cutover_lanes
            if mesh_cutover_lanes is not None
            else MESH_CUTOVER_LANES
        )
        self.breaker = breaker if breaker is not None else CircuitBreaker(
            tuple(name for name, _ in self._rungs)
        )
        self.device = device
        self.host = host

    # -- engine hooks (forwarded to the fast rungs when they have them) --
    # The mesh and device rungs each own a PackCache and table cache, so
    # lifecycle hooks fan out to both.

    def _fast_rungs(self):
        return [self.device] if self.mesh is None else [self.mesh, self.device]

    def warmup(self, **kw) -> None:
        for rung in self._fast_rungs():
            if hasattr(rung, "warmup"):
                rung.warmup(**kw)

    def note_round(self, round_: int) -> None:
        for rung in self._fast_rungs():
            if hasattr(rung, "note_round"):
                rung.note_round(round_)

    def reset_pack_cache(self) -> None:
        for rung in self._fast_rungs():
            if hasattr(rung, "reset_pack_cache"):
                rung.reset_pack_cache()

    def _pack_caches(self) -> List["PackCache"]:
        return [
            cache
            for rung in self._fast_rungs()
            if hasattr(rung, "_pack_caches")
            for cache in rung._pack_caches()
        ]

    def scoped(self, owner: str) -> "EngineScope":
        """Per-engine lifecycle facade over the shared ladder (the whole
        rung stack stays shared; only round/sequence state splits)."""
        return EngineScope(self, owner)

    # -- BatchVerifier ---------------------------------------------------

    def verify_senders(self, msgs: Sequence[IbftMessage]) -> np.ndarray:
        msgs = list(msgs)
        return self._drain(
            msgs,
            lambda rung, idxs: rung.verify_senders([msgs[i] for i in idxs]),
            quarantinable=msgs,
        )

    def verify_committed_seals(
        self, proposal_hash: bytes, seals: Sequence[CommittedSeal], height: int
    ) -> np.ndarray:
        seals = list(seals)
        return self._drain(
            seals,
            lambda rung, idxs: rung.verify_committed_seals(
                proposal_hash, [seals[i] for i in idxs], height
            ),
        )

    def verify_seal_lanes(
        self, lanes: Sequence[Tuple[bytes, CommittedSeal]], height: LaneHeights
    ) -> np.ndarray:
        """Cross-height sync drain through the degradation ladder: poison
        lanes quarantine by bisection, a faulting device demotes to the
        host rungs — the block-sync catch-up path's fallback route."""
        return self._drain_seal_lanes(list(lanes), height)

    def _drain_seal_lanes(self, lanes, height, faulted: bool = False):
        return self._drain(
            lanes,
            lambda rung, idxs: self._run_seal_lanes(
                rung, [lanes[i] for i in idxs], _heights_at(height, idxs)
            ),
            faulted=faulted,
        )

    def verify_seal_lane_stream(
        self, blocks, heights: Iterable[int], lanes: int = 0
    ) -> Optional[Iterator[List[np.ndarray]]]:
        """The sync drain as a stream
        (:meth:`DeviceBatchVerifier.verify_seal_lane_stream`: blocks in,
        the masks of the blocks each read-back completed out) through the
        ladder, or ``None`` where the rung a drain of ``lanes`` lanes enters
        at has no stream (the host rungs): the caller then makes the list
        call, :meth:`verify_seal_lanes`.

        The stream is the fast rung's own.  If it raises before it ends, the
        masks already yielded stand (their chunks completed), and the blocks
        not yet answered are materialised and go through the ladder as one
        list drain: bisection, quarantine, every rung in its turn, and ONE
        breaker fault for the stream and that drain together.  No verdict is
        taken from a dispatch that did not complete.
        """
        level, probe = self._acquire(lanes)
        offer = getattr(self._rungs[level][1], "verify_seal_lane_stream", None)
        source = iter(blocks)
        waiting: deque = deque()  # pulled by the rung, no mask yet

        def pulled():
            try:
                for block in source:
                    waiting.append(block)
                    yield block
            except Exception as err:
                raise _SourceFailed() from err

        stream = None if offer is None else offer(pulled(), heights, lanes)
        if stream is None:
            if probe:
                self.breaker.abort_probe(level)
            return None
        return self._stream_then_ladder(level, probe, stream, source, waiting)

    def _stream_then_ladder(self, level, probe, stream, source, waiting):
        answered = False  # the breaker has heard of this drain
        try:
            with closing(stream):
                while True:
                    try:
                        masks = next(stream)
                    except StopIteration:
                        self.breaker.record_success(level)
                        answered = True
                        return
                    except _SourceFailed as err:
                        raise err.__cause__
                    except Exception:
                        break
                    for _ in masks:
                        waiting.popleft()
                    yield masks
            metrics.inc_counter(STREAM_FALLBACKS_KEY)
            if probe:
                # The probed rung ran and failed: the list drain below is
                # the demoted level's own, with its own record.
                self.breaker.record_fault(level)
            answered = True
            rest = list(waiting) + list(source)
            lanes = [lane for block, _height in rest for lane in block]
            heights: List[int] = []
            for block, height in rest:
                heights.extend(
                    [height] * len(block) if _one_height(height) else height
                )
            mask = self._drain_seal_lanes(lanes, heights, faulted=not probe)
            edges = np.cumsum([0] + [len(block) for block, _height in rest])
            yield [mask[lo:hi] for lo, hi in zip(edges, edges[1:])]
        finally:
            if probe and not answered:
                self.breaker.abort_probe(level)

    def verify_seals_early_exit(
        self,
        proposal_hash: bytes,
        seals: Sequence[CommittedSeal],
        height: int,
        threshold: Optional[int] = None,
    ) -> EarlyExitReport:
        """Early-exit drain through the degradation ladder.

        The breaker's active rung serves the early-exit shape when it
        has one (mesh/device/host all do); a rung fault — or a malformed
        lane, which the early-exit packers cannot bisect around — falls
        back to the FULL resilient drain (quarantine + breaker
        accounting intact), reported with ``skipped=0``.  Early-exit
        never weakens the ladder's liveness contract: a verdict per lane
        is always available, it just may arrive via the full drain.
        """
        seals = list(seals)
        level, probe = self._acquire(len(seals))
        rung = self._rungs[level][1]
        fn = getattr(rung, "verify_seals_early_exit", None)
        if fn is not None:
            try:
                report = fn(
                    proposal_hash, seals, height, threshold=threshold
                )
            except MalformedLaneError:
                # Input poison, not a rung fault: release a pending
                # probe; the full drain below quarantines the lane.
                self.breaker.abort_probe(level)
            except Exception:
                # ONE breaker fault per underlying failure: the
                # full-drain fallback below re-acquires this rung and
                # its own accounting records the fault (and the
                # DRAIN_FAULTS counter) exactly once.  A pending PROBE
                # is the exception — the probed rung genuinely ran and
                # failed, and leaving it unanswered would wedge the
                # breaker's single-probe slot forever.
                if probe:
                    self.breaker.record_fault(level)
            else:
                self.breaker.record_success(level)
                return report
        elif probe:
            self.breaker.abort_probe(level)
        # Full-resilient fallback: bisection/quarantine semantics, every
        # lane verified (no skip), exact host-int quorum over the valid
        # signers.
        mask = self.verify_committed_seals(proposal_hash, seals, height)
        reached = host_quorum_reached(
            self.host._validators,
            [s.signer for s, ok in zip(seals, mask) if ok],
            height,
            threshold,
        )
        return EarlyExitReport(
            mask, np.ones(len(seals), dtype=bool), reached, 0
        )

    @staticmethod
    def _run_seal_lanes(rung, lanes, height) -> np.ndarray:
        if hasattr(rung, "verify_seal_lanes"):
            return rung.verify_seal_lanes(lanes, height)
        # Rung without the per-lane-hash entry point (a bare BatchVerifier
        # protocol implementer): validate lane shapes FIRST so malformed
        # lanes raise with the drain-relative index the bisection expects,
        # then group by hash (and height) and reuse the single-hash drain
        # per group.
        validate_seal_lanes(lanes)
        out = np.zeros(len(lanes), dtype=bool)
        one = _one_height(height)
        groups: Dict[Tuple[bytes, int], List[int]] = {}
        for i, (proposal_hash, _seal) in enumerate(lanes):
            key = (proposal_hash, height if one else height[i])
            groups.setdefault(key, []).append(i)
        for (proposal_hash, at), idxs in groups.items():
            mask = np.asarray(
                rung.verify_committed_seals(
                    proposal_hash, [lanes[i][1] for i in idxs], at
                ),
                dtype=bool,
            )
            out[np.asarray(idxs)] = mask[: len(idxs)]
        return out

    # -- drain machinery -------------------------------------------------

    def _acquire(self, n: int) -> Tuple[int, bool]:
        """``(level, is_probe)`` of the rung one drain of ``n`` lanes enters
        at: the breaker's, but for the mesh's lane-count cutover."""
        level, probe = self.breaker.acquire()
        if self.mesh is not None and level == 0 and n < self.mesh_cutover:
            # Lane-count cutover: small drains skip the mesh rung (padding
            # to a dp multiple + a multi-device launch loses below it).  A
            # pending mesh probe cannot be answered by a drain that will
            # not run the mesh — release it so the next big drain gets it.
            # KNOWN TRADE-OFF: faults recorded at this forced device level
            # are no-ops while the breaker sits at the mesh level (the
            # breaker counts consecutive faults at its ACTIVE level only),
            # so a dead device rung under a healthy mesh never demotes for
            # small drains — each one pays the exception + bisection to
            # host, verdicts intact.  Accepted because the rungs share
            # hardware: a faulting single-device dispatch with a HEALTHY
            # mesh on the same devices is a corner (mesh faults demote 0->1
            # first, after which device faults count normally); per-rung
            # fault counters would be a CircuitBreaker redesign.
            if probe:
                self.breaker.abort_probe(level)
            return 1, False
        return level, probe

    def _drain(self, items, run, quarantinable=None, faulted=False) -> np.ndarray:
        """``faulted``: a fast rung's stream already raised over these items
        (``verify_seal_lane_stream``), and this drain carries its record."""
        n = len(items)
        out = np.zeros(n, dtype=bool)
        if n == 0 and not faulted:
            return out
        level, _probe = self._acquire(n)
        quarantined: List[int] = []
        faulted = [faulted]
        self._verify(level, list(range(n)), run, out, quarantined, faulted)
        if faulted[0]:
            metrics.inc_counter(DRAIN_FAULTS_KEY)
            self.breaker.record_fault(level)
        else:
            self.breaker.record_success(level)
        if quarantined:
            metrics.inc_counter(QUARANTINED_LANES_KEY, len(quarantined))
            if quarantinable is not None:
                condemned = [quarantinable[i] for i in quarantined]
                for rung in self._fast_rungs():
                    if hasattr(rung, "quarantine"):
                        rung.quarantine(condemned)
        return out

    def _verify(self, level, idxs, run, out, quarantined, faulted) -> None:
        """Verify ``idxs`` at rung ``level``, bisecting around failures.

        Writes verdicts into ``out``; lanes no rung can process land in
        ``quarantined`` (verdict stays False).  ``faulted`` records whether
        THIS drain hit any non-malformed rung failure — one breaker fault
        per drain, no matter how many bisection steps it took.
        """
        while idxs:
            try:
                mask = np.asarray(run(self._rungs[level][1], idxs), dtype=bool)
                out[np.asarray(idxs)] = mask[: len(idxs)]
                return
            except MalformedLaneError as err:
                # The packer named the lane: condemn it, retry the rest in
                # one piece (no bisection needed, no breaker fault — the
                # rung is healthy, the input was not).
                if not 0 <= err.lane < len(idxs):
                    quarantined.extend(idxs)
                    return
                quarantined.append(idxs[err.lane])
                idxs = idxs[: err.lane] + idxs[err.lane + 1 :]
            except Exception:
                faulted[0] = True
                if len(idxs) == 1:
                    if level + 1 < len(self._rungs):
                        self._verify(
                            level + 1, idxs, run, out, quarantined, faulted
                        )
                    else:
                        quarantined.extend(idxs)
                    return
                mid = len(idxs) // 2
                self._verify(level, idxs[:mid], run, out, quarantined, faulted)
                idxs = idxs[mid:]


class AdaptiveBatchVerifier:
    """Host/device router: tiny batches on host, large ones on device.

    SURVEY.md §7 hard part (d): a device round-trip has a fixed dispatch
    latency floor that dwarfs a handful of native per-message recovers, so
    a 4-validator cluster should never pay it — while a 100-validator
    quorum drain absolutely should.  Batches with fewer than
    ``cutover_lanes`` items run the sequential host path (native C++
    ecrecover); everything else dispatches the device kernels.  Both
    paths produce identical accept-sets (the differential suites pin this),
    so the route is invisible to the engine, which decides the quorum from
    the mask in exact Python ints.

    An optional ``mesh`` route (a
    :class:`~go_ibft_tpu.verify.mesh_batch.MeshBatchVerifier`) adds a
    second, upper lane-count cutover: drains at or above
    ``mesh_cutover_lanes`` dispatch lane-sharded across the device mesh
    first, with the single-device routes as their breaker-accounted
    fallback (ladder ``mesh -> device -> host -> python``).

    Device-routed drains ride a :class:`ResilientBatchVerifier` ladder: a
    poison batch (device raising mid-dispatch, a lane whose packing blows
    up) is bisected/quarantined instead of crashing the drain, and the
    shared circuit breaker demotes to the host rungs after repeated device
    faults (restoring after cooldown), so a consensus phase never loses
    its verdict to a device fault.
    """

    def __init__(
        self,
        validators_for_height: ValidatorSource,
        cutover_lanes: Optional[int] = None,
        device: Optional[DeviceBatchVerifier] = None,
        host: Optional[HostBatchVerifier] = None,
        breaker: Optional[CircuitBreaker] = None,
        *,
        mesh=None,
        mesh_cutover_lanes: Optional[int] = None,
    ):
        from ..utils import calibration

        self._validators = validators_for_height
        if cutover_lanes is None:
            # Measurement first (a persisted device-dispatch floor vs host
            # per-verify crossover for THIS platform; no writer at present,
            # utils/calibration.py), static default when none exists.
            cutover_lanes = (
                calibration.measured_cutover()
                or calibration.DEFAULT_CUTOVER_LANES
            )
        self.cutover = cutover_lanes
        self.device = device if device is not None else DeviceBatchVerifier(validators_for_height)
        self.host = host if host is not None else HostBatchVerifier(validators_for_height)
        # Optional mesh route (a MeshBatchVerifier): drains at or above
        # ``mesh_cutover_lanes`` try the sharded rung first; the resilient
        # ladder below becomes mesh -> device -> host -> python, so a mesh
        # failure demotes to single-device before host.  Deliberately NOT
        # auto-constructed — sharding is an explicit deployment decision
        # (embedders/bench opt in), and a surprise shard_map compile must
        # never land in a default engine.
        self._resilient = ResilientBatchVerifier(
            self.device,
            host=self.host,
            mesh=mesh,
            mesh_cutover_lanes=mesh_cutover_lanes,
            validators_for_height=validators_for_height,
            breaker=breaker,
        )
        self.mesh_cutover = self._resilient.mesh_cutover
        self.breaker = self._resilient.breaker

    def warmup(self, **kw) -> None:
        self._resilient.warmup(**kw)

    def note_round(self, round_: int) -> None:
        """Engine hook: forward round advances to the fast-rung pack caches."""
        self._resilient.note_round(round_)

    def reset_pack_cache(self) -> None:
        self._resilient.reset_pack_cache()

    def _pack_caches(self) -> List["PackCache"]:
        return self._resilient._pack_caches()

    def scoped(self, owner: str) -> "EngineScope":
        """Per-engine lifecycle facade over the shared adaptive router."""
        return EngineScope(self, owner)

    # -- host-side quorum (exact big ints) ------------------------------

    # -- BatchVerifier ---------------------------------------------------

    def _host_sized(self, n: int) -> bool:
        # Below the cutover the device dispatch floor loses to a handful
        # of native host recovers.  There is NO upper bound: floods above
        # the largest lane bucket stay on device as chunked full-bucket
        # dispatches (DeviceBatchVerifier.verify_senders) — 2049 messages
        # cost two launches, not ~0.7s of sequential host verifies
        # (VERDICT r04 weak #6).
        return n < self.cutover

    def verify_senders(self, msgs: Sequence[IbftMessage]) -> np.ndarray:
        if self._host_sized(len(msgs)):
            return self.host.verify_senders(msgs)
        # Device route rides the degradation ladder: poison batches
        # quarantine instead of raising, device faults demote to host.
        return self._resilient.verify_senders(msgs)

    def _cached_seal_split(
        self, proposal_hash: bytes, seals: Sequence[CommittedSeal], height: int
    ) -> Tuple[List[int], List[int]]:
        """``(hit, miss)`` lanes of a seal drain against the verdicts the
        device rung's joint COMMIT dispatches left.  The cutover counts
        lanes that need a recover, so a drain is routed by its misses: the
        hits go to the device rung, which dispatches nothing for them."""
        lookup = getattr(self.device, "cached_seal_verdicts", None)
        if lookup is None or len(proposal_hash) != 32:  # an injected stub
            return [], list(range(len(seals)))
        known = lookup(proposal_hash, seals, height)
        return (
            [i for i, hit in enumerate(known) if hit is not None],
            [i for i, hit in enumerate(known) if hit is None],
        )

    def verify_committed_seals(
        self, proposal_hash: bytes, seals: Sequence[CommittedSeal], height: int
    ) -> np.ndarray:
        hit, miss = self._cached_seal_split(proposal_hash, seals, height)
        if hit and miss:
            out = np.zeros(len(seals), dtype=bool)
            for idxs in (hit, miss):
                out[idxs] = self.verify_committed_seals(
                    proposal_hash, [seals[i] for i in idxs], height
                )
            return out
        if not hit and self._host_sized(len(seals)):
            return self.host.verify_committed_seals(proposal_hash, seals, height)
        return self._resilient.verify_committed_seals(proposal_hash, seals, height)

    def verify_seal_lanes(
        self, lanes: Sequence[Tuple[bytes, CommittedSeal]], height: LaneHeights
    ) -> np.ndarray:
        """Cross-height sync drain, routed like any other seal drain: tiny
        ranges on the sequential host path, everything else through the
        device ladder (the block-sync catch-up's normal route)."""
        if self._host_sized(len(lanes)):
            return self.host.verify_seal_lanes(lanes, height)
        return self._resilient.verify_seal_lanes(lanes, height)

    def verify_seal_lane_stream(
        self, blocks, heights: Iterable[int], lanes: int = 0
    ) -> Optional[Iterator[List[np.ndarray]]]:
        """The sync drain as a stream, routed like the list call by the
        range's lane count, which the caller knows before it has made one
        lane: ``None`` (make the list call: the host path) under the
        cutover, else the ladder's stream, or its ``None``."""
        if self._host_sized(lanes):
            return None
        return self._resilient.verify_seal_lane_stream(blocks, heights, lanes)

    def verify_seals_early_exit(
        self,
        proposal_hash: bytes,
        seals: Sequence[CommittedSeal],
        height: int,
        threshold: Optional[int] = None,
    ) -> EarlyExitReport:
        """Early-exit seal drain, routed like every other seal drain:
        tiny batches take the sequential host early-exit (arrival-order
        stop-at-quorum), larger ones the ladder's power-ordered chunked
        route (mesh/device with full breaker accounting).  "Tiny" counts
        the lanes no joint COMMIT dispatch has judged yet: those that one
        has come first, from its verdicts, and the rest is routed by its
        own size against what is left of the threshold."""
        hit, miss = self._cached_seal_split(proposal_hash, seals, height)
        if hit and miss:
            first = self.verify_seals_early_exit(
                proposal_hash, [seals[i] for i in hit], height, threshold
            )
            mask = np.zeros(len(seals), dtype=bool)
            verified = np.zeros(len(seals), dtype=bool)
            mask[hit], verified[hit] = first.mask, first.verified
            if first.reached:
                return EarlyExitReport(mask, verified, True, len(miss))
            powers = self._validators(height)
            if threshold is None:
                threshold = calculate_quorum(sum(powers.values()))
            have = {seals[i].signer for i in hit if mask[i]}
            rest = self.verify_seals_early_exit(
                proposal_hash,
                [seals[i] for i in miss],
                height,
                threshold - sum(powers.get(a, 0) for a in have),
            )
            mask[miss], verified[miss] = rest.mask, rest.verified
            return EarlyExitReport(mask, verified, rest.reached, rest.skipped)
        if not hit and self._host_sized(len(seals)):
            return self.host.verify_seals_early_exit(
                proposal_hash, seals, height, threshold=threshold
            )
        return self._resilient.verify_seals_early_exit(
            proposal_hash, seals, height, threshold=threshold
        )


class EngineScope:
    """Per-engine lifecycle facade over a SHARED verifier ladder.

    N engines (one per chain/tenant) may share one verifier so their
    drains land on one device data plane, but the engine lifecycle hooks
    carry per-sequence/per-round state: before this scope existed the
    ladder-wide reset assumed a single engine — engine A's
    ``reset_pack_cache()`` (sequence start) wiped engine B's live packs,
    and A's ``note_round(0)`` retagged the shared cache's live round out
    from under B's entries, demoting them to dead-round eviction fodder
    mid-round (ISSUE 8 satellite).

    ``ladder.scoped("chain-a")`` returns a drop-in ``BatchVerifier``
    whose verify calls attribute their pack-cache stores to the owner
    (:meth:`PackCache.owned`) and whose ``note_round`` /
    ``reset_pack_cache`` rotate/drop ONLY the owner's entries; every
    other attribute (``quarantine`` — already per-message — ``warmup``,
    breaker state) delegates to the shared parent.
    The :class:`~go_ibft_tpu.sched.TenantScheduler`'s handles are the
    fully-managed version of this (per-tenant queues, fairness and
    backpressure on top); a bare shared ladder with scopes is the
    minimal-correct one.
    """

    def __init__(self, parent, owner: str):
        if not owner:
            raise ValueError("EngineScope requires a non-empty owner label")
        self._parent = parent
        self._owner = owner

    @property
    def owner(self) -> str:
        return self._owner

    def __getattr__(self, name: str):
        return getattr(self._parent, name)

    def _caches(self) -> List["PackCache"]:
        fn = getattr(self._parent, "_pack_caches", None)
        return fn() if fn is not None else []

    def _owned(self) -> ExitStack:
        stack = ExitStack()
        for cache in self._caches():
            stack.enter_context(cache.owned(self._owner))
        return stack

    # -- owner-scoped engine lifecycle hooks -----------------------------

    def note_round(self, round_: int) -> None:
        for cache in self._caches():
            cache.note_round(round_, owner=self._owner)

    def reset_pack_cache(self) -> None:
        for cache in self._caches():
            cache.clear(owner=self._owner)

    # -- BatchVerifier (stores attributed to the owner) ------------------

    def verify_senders(self, msgs: Sequence[IbftMessage]) -> np.ndarray:
        with self._owned():
            return self._parent.verify_senders(msgs)

    def verify_committed_seals(
        self, proposal_hash: bytes, seals: Sequence[CommittedSeal], height: int
    ) -> np.ndarray:
        with self._owned():
            return self._parent.verify_committed_seals(
                proposal_hash, seals, height
            )

    def verify_seal_lanes(
        self, lanes: Sequence[Tuple[bytes, CommittedSeal]], height: LaneHeights
    ) -> np.ndarray:
        with self._owned():
            return self._parent.verify_seal_lanes(lanes, height)
