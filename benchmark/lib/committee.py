"""The committee and its pre-signed traffic (host only: never imports jax).

Copied from ``chip_smoke.py``'s ``Committee`` (PR 21) and changed where a
benchmark needs it: proposers rotate exactly as ``ECDSABackend.is_proposer``
says (``sorted(addresses)[(height + round) % n]``), so one height in ``n``
is proposed by the node under test; blocks are a pure function of the
height, so the peers' PREPAREs and COMMITs over the node's own proposal are
pre-signed too; and every height keeps its labels (which peers were
corrupted, the one valid seal each validator can produce), so a finalized
height is held to the guarantees by construction as well as by the
sequential host oracle.

Signing is deterministic (RFC-6979-style nonce from key and digest), so
the same seed gives the same bytes in every process.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from go_ibft_tpu import native
from go_ibft_tpu.chain.wal import FinalizedBlock
from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto import ecdsa as ec
from go_ibft_tpu.crypto.backend import (
    ECDSABackend,
    encode_signature,
    proposal_hash_of,
)
from go_ibft_tpu.crypto.keccak import keccak256
from go_ibft_tpu.messages.helpers import CommittedSeal
from go_ibft_tpu.messages.wire import (
    CommitMessage,
    IbftMessage,
    MessageType,
    Proposal,
    View,
)


class GuaranteeBroken(AssertionError):
    """A guarantee the configuration states did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise GuaranteeBroken(what)


def block_bytes(height: int) -> bytes:
    """The block at ``height``: a pure function of the height, so anyone
    (the node's ``build_proposal``, a pre-signing worker) builds the same."""
    return b"benchmark block %d" % height


def mangle(sig: bytes) -> bytes:
    out = bytearray(sig)
    out[5] ^= 0xFF  # inside r: recovers to another key, or to none
    return bytes(out)


@dataclass
class HeightTraffic:
    """One height of peer traffic and its labels."""

    height: int
    proposer: bytes
    preprepare: Optional[IbftMessage]  # None where the node itself proposes
    prepares: List[IbftMessage]
    commits: List[IbftMessage]
    phash: bytes
    bad: frozenset  # corrupted peers (envelope or seal)
    valid_seal: Dict[bytes, bytes] = field(default_factory=dict)


class Committee:
    """``n`` seeded validators, equal power; validator 0 is the node."""

    def __init__(self, n: int, seed: int) -> None:
        self.native = native.install()
        self.n = n
        self.seed = seed
        self.seeds = [f"benchmark-{seed}-{i}" for i in range(n)]
        self.keys = [PrivateKey.from_seed(s.encode()) for s in self.seeds]
        self.powers = {k.address: 1 for k in self.keys}
        self.src = ECDSABackend.static_validators(self.powers)
        self.key_of = {k.address: k for k in self.keys}
        self.ordered = sorted(self.powers)
        self.node_key = self.keys[0]
        self.node = self.node_key.address
        self.quorum = (2 * n) // 3 + 1
        self._backends: Dict[bytes, ECDSABackend] = {}

    def backend(self, addr: bytes) -> ECDSABackend:
        got = self._backends.get(addr)
        if got is None:
            got = self._backends[addr] = ECDSABackend(self.key_of[addr], self.src)
        return got

    def proposer(self, height: int, round_: int = 0) -> bytes:
        return self.ordered[(height + round_) % self.n]

    def seal(self, addr: bytes, phash: bytes) -> bytes:
        return encode_signature(*ec.sign(self.key_of[addr], phash))

    # -- live rounds ----------------------------------------------------

    def traffic(self, height: int, corrupt: int) -> HeightTraffic:
        """One height of peer traffic, ``corrupt`` peers' signatures bad.

        Every corrupted peer's PREPARE envelope signature is mangled.  Half
        of them also mangle their COMMIT envelope; the other half send a
        COMMIT whose envelope is VALID around a mangled committed seal, so
        both the ingress batch and the seal drain have lanes to reject.
        """
        view = View(height=height, round=0)
        proposer = self.proposer(height)
        proposal = Proposal(raw_proposal=block_bytes(height), round=0)
        phash = proposal_hash_of(proposal)
        preprepare = None
        if proposer != self.node:
            preprepare = self.backend(proposer).build_preprepare_message(
                proposal.raw_proposal, None, view
            )
        peers = [a for a in self.powers if a != self.node]
        followers = [a for a in peers if a != proposer]
        rng = np.random.default_rng([self.seed, height])
        bad = [
            followers[i]
            for i in rng.choice(len(followers), size=corrupt, replace=False)
        ]
        bad_envelope = set(bad[: corrupt // 2])
        bad_seal = set(bad[corrupt // 2 :])

        prepares = []
        for addr in followers:  # the proposer's PREPARE would void the quorum
            msg = self.backend(addr).build_prepare_message(phash, view)
            if addr in bad_envelope or addr in bad_seal:
                msg.signature = mangle(msg.signature)
            prepares.append(msg)
        commits = []
        valid_seal = {}
        for addr in peers:
            seal = self.seal(addr, phash)
            valid_seal[addr] = seal
            msg = IbftMessage(
                view=view.copy(),
                sender=addr,
                type=MessageType.COMMIT,
                commit_data=CommitMessage(
                    proposal_hash=phash,
                    committed_seal=mangle(seal) if addr in bad_seal else seal,
                ),
            )
            digest = keccak256(msg.encode(include_signature=False))
            msg.signature = encode_signature(*ec.sign(self.key_of[addr], digest))
            if addr in bad_envelope:
                msg.signature = mangle(msg.signature)
            commits.append(msg)
        valid_seal[self.node] = self.seal(self.node, phash)
        return HeightTraffic(
            height=height,
            proposer=proposer,
            preprepare=preprepare,
            prepares=prepares,
            commits=commits,
            phash=phash,
            bad=frozenset(bad_envelope | bad_seal),
            valid_seal=valid_seal,
        )

    def check_finalized(
        self, t: HeightTraffic, proposal, seals, *, oracle=None
    ) -> None:
        """A finalized height, held to the guarantees: round 0, the block
        of this height, quorum power from distinct validators, nothing from
        a corrupted peer, and every seal the one valid seal its signer can
        produce over this hash (signing is deterministic).  ``oracle`` (the
        sequential host verifier) checks the same seals again where given.
        """
        h = t.height
        check(proposal.round == 0, f"height {h} finalized in round {proposal.round}")
        check(
            proposal.raw_proposal == block_bytes(h),
            f"height {h}: finalized another block than the one proposed",
        )
        signers = [s.signer for s in seals]
        check(len(set(signers)) == len(signers), f"height {h}: a signer twice")
        check(
            sum(self.powers.get(a, 0) for a in signers) >= self.quorum,
            f"height {h}: finalized seals carry less than quorum power",
        )
        check(
            not set(signers) & t.bad,
            f"height {h}: a corrupted peer's seal was finalized",
        )
        for s in seals:
            check(
                t.valid_seal.get(s.signer) == s.signature,
                f"height {h}: a finalized seal is not its signer's valid seal",
            )
        if oracle is not None:
            mask = oracle.verify_committed_seals(t.phash, list(seals), h)
            check(bool(mask.all()), f"height {h}: the host oracle rejects a seal")

    # -- block sync -----------------------------------------------------

    def sync_blocks(
        self,
        first_height: int,
        blocks: int,
        seals_per_block: int,
        corrupt_every: int,
        corrupt_seals: int,
    ) -> Tuple[List[FinalizedBlock], List[np.ndarray]]:
        """``blocks`` finalized blocks from ``first_height`` on, each sealed
        by ``seals_per_block`` validators (a seeded choice per height).
        Where ``corrupt_every`` is above 0, a block whose height it divides
        has its first ``corrupt_seals`` seals mangled.  Returns the blocks
        and, per block, the mask a correct verifier must give."""
        out, want = [], []
        for i in range(blocks):
            h = first_height + i
            proposal = Proposal(raw_proposal=block_bytes(h), round=0)
            phash = proposal_hash_of(proposal)
            rng = np.random.default_rng([self.seed, h, 1])
            signers = [
                self.keys[j].address
                for j in rng.choice(self.n, size=seals_per_block, replace=False)
            ]
            n_bad = corrupt_seals if corrupt_every and h % corrupt_every == 0 else 0
            seals = []
            for j, addr in enumerate(signers):
                sig = self.seal(addr, phash)
                seals.append(
                    CommittedSeal(
                        signer=addr, signature=mangle(sig) if j < n_bad else sig
                    )
                )
            out.append(FinalizedBlock(height=h, proposal=proposal, seals=seals))
            mask = np.ones(seals_per_block, dtype=bool)
            mask[:n_bad] = False
            want.append(mask)
        return out, want


def corrupt_block(block: FinalizedBlock, keep_valid: int) -> FinalizedBlock:
    """``block`` with all but its last ``keep_valid`` seals mangled.  (A seal
    that is already mangled is mangled back: callers pass clean blocks.)"""
    cut = len(block.seals) - keep_valid
    seals = [
        CommittedSeal(signer=s.signer, signature=mangle(s.signature))
        if j < cut
        else s
        for j, s in enumerate(block.seals)
    ]
    return FinalizedBlock(height=block.height, proposal=block.proposal, seals=seals)


def seal_lanes(blocks: Sequence[FinalizedBlock]) -> list:
    """The ``(proposal hash, seal)`` lanes ``SyncClient`` makes of a range."""
    return [
        (proposal_hash_of(b.proposal), seal) for b in blocks for seal in b.seals
    ]
