"""Block-sync / catch-up: aggregate-verified finalized-height transfer.

A validator that restarts behind its peers — or observes commit-quorum
evidence for a future height — cannot finish old heights through
consensus (its peers have left them; the reference documents block sync
as the embedder's job, core/ibft.go RunSequence contract).  This module
is that job, done the TPU-native way: a stranded node fetches the missing
``(proposal, committed seals)`` range from any peer and verifies ALL
committed seals across the whole range in ONE batched drain — per-lane
proposal hashes and per-lane heights, so the range may cross
validator-set boundaries, through the same recovery ladder as the live
COMMIT path, with the ``ResilientBatchVerifier`` breaker ladder as the
degraded route.  Where the verifier offers it the drain is a STREAM
(``verify_seal_lane_stream``): the client makes a chunk's lanes while
the chunk before is on the device and tallies a block while the chunk
after is, so the chip does not wait for the host at either end of a
range; any other verifier gets the whole range in one
``verify_seal_lanes`` call.  This
is the light-client primitive ("Practical Light Clients for
Committee-Based Blockchains", PAPERS.md): trust nothing from the peer,
re-derive every height's commit quorum from the seals alone.

One binding is deliberately the embedder's (as in the reference, where
block sync is wholly embedder-owned): committed seals sign
``keccak(raw_proposal, round)`` — the HEIGHT is not covered by the
signature, so the in-protocol check alone cannot catch a peer relabeling
a genuine block at a different height.  Real chains close this in the
proposal content (height/parent-hash inside the block bytes); the chain
runner therefore passes every synced proposal through the embedder's
``is_valid_proposal`` before inserting, which is where that content
check belongs (docs/CHAIN.md).

The peer seam is deliberately as thin as the consensus ``Transport``
(one-method multicast): a :class:`SyncSource` answers ``latest_height``
and ``get_blocks`` — :class:`~go_ibft_tpu.chain.runner.ChainRunner`
implements it from its in-memory chain, :class:`LoopbackSyncNetwork`
wires sources in-process (tests, single-host clusters), and a gRPC/DCN
implementation slots in for multi-host deployments exactly like
``net.GrpcTransport`` does for gossip.
"""

from __future__ import annotations

import threading
from collections import deque
from contextlib import closing
from typing import (
    Callable,
    Deque,
    Dict,
    Iterator,
    List,
    Mapping,
    Protocol,
    Sequence,
    Tuple,
)

import numpy as np

from ..core.validator_manager import calculate_quorum
from ..crypto.backend import proposal_hash_of
from ..messages.helpers import CommittedSeal
from ..obs import trace
from ..utils import metrics
from .wal import FinalizedBlock

__all__ = [
    "LoopbackSyncNetwork",
    "SyncClient",
    "SyncError",
    "SyncSource",
    "SYNCED_HEIGHTS_KEY",
    "SYNC_DRAINS_KEY",
    "SYNC_CERT_HEIGHTS_KEY",
    "SYNC_REGROUPED_KEY",
    "SYNC_STREAMED_KEY",
]

SYNCED_HEIGHTS_KEY = ("go-ibft", "chain", "synced_heights")
SYNC_DRAINS_KEY = ("go-ibft", "chain", "sync_drains")
SYNC_CERT_HEIGHTS_KEY = ("go-ibft", "chain", "sync_cert_heights")
# Ranges whose validator sets together needed more table rows than one
# drain's table holds, and were verified as more than one drain.
SYNC_REGROUPED_KEY = ("go-ibft", "chain", "sync_regrouped")

# Runs (drains) whose masks came through the verifier's stream: lanes made,
# chunks run and blocks tallied side by side.
SYNC_STREAMED_KEY = ("go-ibft", "chain", "sync_streamed")

# Lanes the producer makes at a time (whole blocks, one at least): one
# ``chain.sync.produce`` span and one mask.  An eighth of the largest lane
# bucket: the first launch waits for at most that much more than its chunk.
_PRODUCE_LANES = 256

# Rows of the verifier's largest validator table (the last of
# ``verify/batch.py``'s ``_TABLE_BUCKETS``; a test holds the two equal): what
# the address sets of ONE drain may need together.
MAX_TABLE_ROWS = 2048


class SyncError(RuntimeError):
    """Catch-up failed: no peer could serve the range, or verification
    rejected the fetched evidence."""


class SyncSource(Protocol):
    """What a peer serves to catch-up requests (the sync seam)."""

    def latest_height(self) -> int: ...

    def get_blocks(self, start: int, end: int) -> List[FinalizedBlock]: ...


class LoopbackSyncNetwork:
    """In-process sync peer registry (the test/single-host fabric).

    Mirrors ``core.LoopbackTransport``'s posture: registration order is
    deterministic, a node never serves itself, and a fault hook lets chaos
    suites drop or truncate responses per (requester, server).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sources: Dict[bytes, SyncSource] = {}
        # Optional fault hook: (requester_id, server_id) -> serve?
        self.should_serve: Callable[[bytes, bytes], bool] = lambda _r, _s: True

    def register(self, node_id: bytes, source: SyncSource) -> None:
        with self._lock:
            self._sources[node_id] = source

    def peers_of(self, node_id: bytes) -> List[Tuple[bytes, SyncSource]]:
        with self._lock:
            return [
                (peer_id, src)
                for peer_id, src in self._sources.items()
                if peer_id != node_id and self.should_serve(node_id, peer_id)
            ]


class SyncClient:
    """Fetch-and-verify catch-up for one node.

    ``verifier`` is any object with ``verify_seal_lanes(lanes, heights)``,
    a height a lane (Host/Device/Mesh/Resilient/Adaptive and the
    scheduler's tenant handle all implement it); verdicts are
    pinned to the sequential host oracle by the conformance tests, so a
    device route can never accept a range the reference semantics would
    reject.  One that also has ``verify_seal_lane_stream(blocks, heights,
    lanes)`` (Device/Mesh, and Resilient/Adaptive over them) is fed
    lazily and answers as its chunks come back; it may return ``None``
    for a range it would not stream (under the adaptive cutover, a ladder
    on its host rungs), and gets the list call then.  A :class:`~go_ibft_tpu.verify.mesh_batch.MeshBatchVerifier`
    (or an Adaptive ladder carrying one) coalesces a whole multi-height
    range into ONE sharded dispatch — its chunk capacity is ``largest
    lane bucket x device count`` — so catch-up cost scales down with the
    mesh instead of serializing per 2048-lane chunk.
    """

    def __init__(
        self,
        node_id: bytes,
        network: LoopbackSyncNetwork,
        verifier,
        validators_for_height: Callable[[int], Mapping[bytes, int]],
        *,
        cert_verifier=None,
        max_batch_heights: int = 4096,
    ) -> None:
        self.node_id = node_id
        self.network = network
        self.verifier = verifier
        self._validators = validators_for_height
        # Aggregate-certificate route (ISSUE 7/12): blocks served with an
        # AggregateQuorumCertificate instead of per-validator seals verify
        # through this (a BLSCertifier or compatible) — quorum power from
        # the signer bitmap, and the WHOLE range's pairing work in ONE
        # batched multi-pairing dispatch (``verify_many``) — instead of N
        # seal lanes per height through ``verifier``.
        self.cert_verifier = cert_verifier
        self.max_batch_heights = max_batch_heights
        self.max_table_rows = MAX_TABLE_ROWS

    # -- peer observation ----------------------------------------------

    def best_peer_height(self) -> int:
        """Highest finalized height any reachable peer advertises."""
        best = 0
        for _peer_id, source in self.network.peers_of(self.node_id):
            try:
                best = max(best, source.latest_height())
            except Exception:  # noqa: BLE001 - a dead peer is not an error
                continue
        return best

    # -- catch-up -------------------------------------------------------

    def catch_up(self, start: int, target: int) -> List[FinalizedBlock]:
        """Fetch ``[start, target]`` from peers and verify the whole range.

        Peers are tried in registration order; the first one serving a
        non-empty prefix wins (a peer that is itself behind serves what it
        has — the caller loops until caught up).  Raises :class:`SyncError`
        when no peer can serve ``start`` or verification rejects the
        evidence.
        """
        target = min(target, start + self.max_batch_heights - 1)
        blocks: List[FinalizedBlock] = []
        for _peer_id, source in self.network.peers_of(self.node_id):
            try:
                got = source.get_blocks(start, target)
            except Exception:  # noqa: BLE001 - try the next peer
                continue
            if got and got[0].height == start:
                blocks = got
                break
        if not blocks:
            raise SyncError(
                f"no peer could serve heights [{start}, {target}]"
            )
        expected = list(range(start, start + len(blocks)))
        if [b.height for b in blocks] != expected:
            raise SyncError("peer served a non-contiguous height range")
        self.verify_blocks(blocks)
        metrics.inc_counter(SYNCED_HEIGHTS_KEY, len(blocks))
        return blocks

    def verify_blocks(self, blocks: Sequence[FinalizedBlock]) -> None:
        """Verify every fetched block's commit evidence.

        Blocks carrying an aggregate quorum certificate verify on the
        O(1) route: one pairing equation per height-range entry (the
        certificate's proposal hash must match the block's proposal, the
        signer bitmap must reach quorum power — both checked inside the
        cert verifier — so a peer can never relabel a certificate onto a
        different proposal).  Requires ``cert_verifier``; a cert-carrying
        block without one is a :class:`SyncError`, never silently trusted.

        Seal-carrying blocks keep the batched lane route: ONE drain for the
        whole range (the verifier's stream where it offers one, else one
        ``verify_seal_lanes`` call), whatever validator sets its heights
        select.  Every lane carries its own height, and
        the verifier judges it ``signature valid AND signer in the set of
        that height`` (on the device: one table that holds the union of the
        range's sets, the own-set test a host lookup), exactly as the
        sequential oracle would block by block.  What decides a drain's
        shape is the ADDRESS sets: stakes that move inside the same
        addresses (the common epoch change) leave it alone, and a fixed
        committee is the case of one set.  Only a range whose sets
        together need more rows than the verifier's largest table
        (``max_table_rows``) is cut into runs of blocks that fit, a drain
        each (``sync_regrouped``).  What has to precede the first launch
        stays in front (the sets, the runs, the quorum a snapshot); a
        block's hash and lane tuples are made when the drain pulls them
        (:meth:`_produce`), and as soon as a block's mask is back its valid
        signers must reach that height's voting-power quorum, in exact
        integers and in height order: the first block short of it raises,
        and nothing of the range is returned.
        """
        cert_blocks = [b for b in blocks if b.cert is not None]
        if cert_blocks:
            self._verify_cert_blocks(cert_blocks)
        blocks = [b for b in blocks if b.cert is None]
        if not blocks:
            return
        snapshots = [self._validators(b.height) for b in blocks]
        # Each block's height stands for its address set: the first height
        # of the range that selected the same addresses (a lane's verdict
        # depends on its height through that set alone, so the verifier
        # looks up as many sets as the range has, not as many as blocks).
        first_with: Dict[frozenset, int] = {}
        stands_for = [
            first_with.setdefault(frozenset(powers), block.height)
            for block, powers in zip(blocks, snapshots)
        ]
        # Runs of blocks whose sets fit one table together: one run, but
        # for a range wider than the largest table.
        runs: List[List[int]] = [[]]
        seen: set = set()
        rows: set = set()
        table_rows = 0
        for i, height in enumerate(stands_for):
            if height not in seen:
                grown = rows | snapshots[i].keys()
                if seen and len(grown) > self.max_table_rows:
                    runs.append([])
                    seen, grown = set(), set(snapshots[i])
                seen.add(height)
                rows = grown
                table_rows = max(table_rows, len(rows))
            runs[-1].append(i)
        if len(runs) > 1:
            metrics.inc_counter(SYNC_REGROUPED_KEY)

        # The quorum a snapshot, in front of the first launch: Python
        # integers throughout (a stake in wei is above 2**60; nothing here
        # may round or saturate); a snapshot the source hands out for many
        # heights is summed once.
        quorums: Dict[int, int] = {}
        bigint = False
        for powers in snapshots:
            if id(powers) not in quorums:
                quorums[id(powers)] = calculate_quorum(sum(powers.values()))
                bigint = bigint or max(powers.values(), default=0) >= 1 << 31

        with trace.span(
            "chain.sync.verify",
            lanes=sum(len(b.seals) for b in blocks),
            heights=len(blocks),
            drains=len(runs),
            sets=len(first_with),
            table_rows=table_rows,
            streamed=False,
        ) as verify:
            for idxs in runs:
                slabs: Deque[Tuple[int, int]] = deque()
                batches = self._mask_batches(blocks, idxs, stands_for, slabs, verify)
                tallied = 0
                with closing(batches):
                    # A batch is the masks one read-back completed: its
                    # blocks are tallied while the next chunk runs (all of
                    # the run's at once where the verifier has no stream).
                    for batch in batches:
                        cuts = [slabs.popleft() for _ in batch]
                        with trace.span(
                            "chain.sync.tally",
                            blocks=sum(end - first for first, end in cuts),
                            bigint=bigint,
                        ):
                            for mask, (first, end) in zip(batch, cuts):
                                lo = 0
                                for i in idxs[first:end]:
                                    hi = lo + len(blocks[i].seals)
                                    self._hold_to_quorum(
                                        blocks[i],
                                        mask[lo:hi],
                                        snapshots[i],
                                        quorums[id(snapshots[i])],
                                    )
                                    lo = hi
                                tallied += end - first
                if tallied != len(idxs):
                    raise SyncError(
                        f"the verifier answered {tallied} of {len(idxs)} blocks"
                    )

    def _produce(
        self,
        blocks: Sequence[FinalizedBlock],
        idxs: Sequence[int],
        stands_for: Sequence[int],
        slabs: Deque[Tuple[int, int]],
    ) -> Iterator[Tuple[List[Tuple[bytes, CommittedSeal]], List[int]]]:
        """One run's lanes, made as they are asked for: ``(lanes, a height a
        lane)`` of the next blocks that hold ``_PRODUCE_LANES`` lanes
        together (one block at least), a proposal hash a block and a tuple
        a lane; which of ``idxs`` they are joins ``slabs``.  What pulls from
        here decides when the work is done: the verifier's stream pulls a
        chunk's worth while the chunk before is on the device."""
        at = 0
        while at < len(idxs):
            first = at
            lanes: List[Tuple[bytes, CommittedSeal]] = []
            heights: List[int] = []
            with trace.span("chain.sync.produce") as span:
                while at < len(idxs) and (at == first or len(lanes) < _PRODUCE_LANES):
                    block = blocks[idxs[at]]
                    proposal_hash = proposal_hash_of(block.proposal)
                    lanes.extend([(proposal_hash, seal) for seal in block.seals])
                    heights.extend([stands_for[idxs[at]]] * len(block.seals))
                    at += 1
                span.note(blocks=at - first, lanes=len(lanes))
            slabs.append((first, at))
            yield lanes, heights

    def _mask_batches(
        self, blocks, idxs, stands_for, slabs, verify
    ) -> Iterator[List[np.ndarray]]:
        """The masks of one run, a mask a slab of :meth:`_produce`, in the
        batches they become known in.  From the verifier's stream where it
        offers one for a range this size (``verify_seal_lane_stream``:
        lanes are made, chunks run and blocks are tallied side by side);
        else every lane is made first, ONE ``verify_seal_lanes`` call
        judges them, and its slices are one batch.  ONE batched drain
        either way, every lane at its height."""
        produced = self._produce(blocks, idxs, stands_for, slabs)
        lanes = sum(len(blocks[i].seals) for i in idxs)
        if not lanes:
            yield [np.zeros(0, dtype=bool) for _ in produced]
            return
        offer = getattr(self.verifier, "verify_seal_lane_stream", None)
        stream = None
        if offer is not None:
            stream = offer(produced, set(stands_for[i] for i in idxs), lanes)
        if stream is not None:
            verify.note(streamed=True)
            metrics.inc_counter(SYNC_STREAMED_KEY)
            yield from stream  # closed with this generator
        else:
            made = list(produced)
            mask = np.asarray(
                self.verifier.verify_seal_lanes(
                    [lane for slab, _heights in made for lane in slab],
                    [height for _slab, heights in made for height in heights],
                ),
                dtype=bool,
            )
            edges = np.cumsum([0] + [len(slab) for slab, _heights in made])
            yield [mask[lo:hi] for lo, hi in zip(edges, edges[1:])]
        metrics.inc_counter(SYNC_DRAINS_KEY)

    @staticmethod
    def _hold_to_quorum(
        block: FinalizedBlock, mask, powers: Mapping[bytes, int], quorum: int
    ) -> None:
        """The power of a block's valid signers against its own height's
        quorum, in exact integers."""
        valid_signers = {
            seal.signer for seal, ok in zip(block.seals, mask) if bool(ok)
        }
        got = sum(powers.get(a, 0) for a in valid_signers)
        if got < quorum:
            raise SyncError(
                f"height {block.height}: committed-seal power {got} < "
                f"quorum {quorum} ({int(mask.sum())}/{len(block.seals)} "
                "seals valid)"
            )

    def _verify_cert_blocks(self, blocks: Sequence[FinalizedBlock]) -> None:
        """Batched verification of certificate-carrying blocks.

        Structural gates run per block BEFORE any pairing work; the
        surviving certificates then verify through ONE batched
        multi-pairing dispatch (``cert_verifier.verify_many``, ISSUE 12)
        — a 1000-height catch-up range costs one dispatch instead of
        1000 independent pairing calls.  A verifier without
        ``verify_many`` (a custom embedder seam) keeps the per-height
        route, verdict-identically.
        """
        if self.cert_verifier is None:
            raise SyncError(
                "peer served aggregate-certificate blocks but this client "
                "has no cert_verifier to check them"
            )
        with trace.span(
            "chain.sync.cert_verify", heights=len(blocks)
        ):
            for block in blocks:
                cert = block.cert
                if block.seals:
                    # A cert block carries NO per-validator seals (the WAL
                    # writes them mutually exclusively); a peer serving
                    # both is smuggling seals past verification — this
                    # path checks only the certificate, and the runner
                    # would otherwise insert and re-serve the unchecked
                    # seal list as commit evidence.
                    raise SyncError(
                        f"height {block.height}: certificate block "
                        "carries a seal list (unverifiable evidence mix)"
                    )
                if (
                    cert.height != block.height
                    or cert.proposal_hash != proposal_hash_of(block.proposal)
                ):
                    raise SyncError(
                        f"height {block.height}: certificate does not bind "
                        "the served proposal"
                    )
            verify_many = getattr(self.cert_verifier, "verify_many", None)
            if verify_many is not None:
                mask = np.asarray(
                    verify_many([b.cert for b in blocks]), dtype=bool
                )
            else:
                mask = np.asarray(
                    [self.cert_verifier.verify(b.cert) for b in blocks],
                    dtype=bool,
                )
            for block, ok in zip(blocks, mask):
                if not bool(ok):
                    raise SyncError(
                        f"height {block.height}: aggregate quorum "
                        "certificate failed verification"
                    )
                metrics.inc_counter(SYNC_CERT_HEIGHTS_KEY)
