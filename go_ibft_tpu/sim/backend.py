"""Deterministic simulation backend for cluster-scale lock-step runs.

The sim-crypto analogue of the test harness MockBackend, importable from
production drivers (``sim/cluster.py``, scripts/) without reaching into tests/:
proposals are a pure function of HEIGHT (never round), so two runs that
finalize every height produce byte-identical chains even when round
timers jittered differently along the way — the property the cluster
bench's chain-identity oracle and the chaos replay check both lean on.

Sender validity is delegate-checked (``is_valid_validator`` membership),
not signature-checked: every engine validates identically whichever
transport carried the message, which is what makes the lock-step vs
loopback comparison apples-to-apples.  Real-crypto cluster runs use
:class:`~go_ibft_tpu.core.backend.ECDSABackend` plus the tick-fused
verifier instead (tests/test_cluster_sim.py).
"""

from __future__ import annotations

import hashlib
from typing import Callable, List, Mapping, Optional, Sequence

from ..messages import (
    CommitMessage,
    IbftMessage,
    MessageType,
    Proposal,
    PrepareMessage,
    PrePrepareMessage,
    RoundChangeMessage,
    View,
)

_SIM_SIGNATURE = b"\x00" * 65


def sim_address(index: int) -> bytes:
    """Stable per-node address (not 20 bytes — sim crypto never packs)."""
    return b"sim-%05d" % index


def sim_block(height: int) -> bytes:
    """The canonical proposal for ``height`` — round-independent by
    design (see module docstring)."""
    return b"sim-block-%08d" % height


def sim_hash(raw_proposal: bytes) -> bytes:
    return hashlib.sha256(raw_proposal).digest()


class SimBackend:
    """Backend + MessageConstructor + Verifier for one sim node.

    ``commit_next_set`` (ISSUE 20, default off) makes every proposal carry
    a next-set commitment suffix (:mod:`go_ibft_tpu.lightsync.commitment`)
    over the NEXT height's validator set, and makes ``is_valid_proposal``
    require + check it against ``validators_for_height`` — the sim-side
    producer/enforcer pair for commitment-enforced proofs.  Off by
    default so the byte-identity oracles (chain-identity, chaos replay)
    keep their exact historical bytes.
    """

    def __init__(
        self,
        index: int,
        addresses: Sequence[bytes],
        *,
        commit_next_set: bool = False,
        validators_for_height: Optional[
            Callable[[int], Mapping[bytes, int]]
        ] = None,
    ) -> None:
        self.index = index
        self.addresses = list(addresses)
        self.address = self.addresses[index]
        self._members = frozenset(self.addresses)
        self.commit_next_set = commit_next_set
        self._validators_for_height = validators_for_height or (
            lambda _height: {a: 1 for a in self.addresses}
        )
        self.inserted: List[tuple] = []

    # -- MessageConstructor ---------------------------------------------

    def build_preprepare_message(self, raw_proposal, certificate, view: View):
        return IbftMessage(
            view=view.copy(),
            sender=self.address,
            signature=_SIM_SIGNATURE,
            type=MessageType.PREPREPARE,
            preprepare_data=PrePrepareMessage(
                proposal=Proposal(
                    raw_proposal=raw_proposal, round=view.round
                ),
                proposal_hash=sim_hash(raw_proposal),
                certificate=certificate,
            ),
        )

    def build_prepare_message(self, proposal_hash, view: View):
        return IbftMessage(
            view=view.copy(),
            sender=self.address,
            signature=_SIM_SIGNATURE,
            type=MessageType.PREPARE,
            prepare_data=PrepareMessage(proposal_hash=proposal_hash),
        )

    def build_commit_message(self, proposal_hash, view: View):
        return IbftMessage(
            view=view.copy(),
            sender=self.address,
            signature=_SIM_SIGNATURE,
            type=MessageType.COMMIT,
            commit_data=CommitMessage(
                proposal_hash=proposal_hash,
                committed_seal=b"seal:" + self.address,
            ),
        )

    def build_round_change_message(self, proposal, certificate, view: View):
        return IbftMessage(
            view=view.copy(),
            sender=self.address,
            signature=_SIM_SIGNATURE,
            type=MessageType.ROUND_CHANGE,
            round_change_data=RoundChangeMessage(
                last_prepared_proposal=proposal,
                latest_prepared_certificate=certificate,
            ),
        )

    # -- Verifier -------------------------------------------------------

    def is_valid_proposal(self, raw_proposal: bytes) -> bool:
        if not raw_proposal.startswith(b"sim-block-"):
            return False
        if not self.commit_next_set:
            return True
        # Commitment-enforced mode: the proposal must carry a next-set
        # commitment and it must match the set the proposer was obliged
        # to commit to (the height is parseable from the sim prefix, so
        # the sim seam can check the EXACT root, not just presence).
        from ..lightsync.commitment import extract_next_set, set_root, strip_next_set

        committed = extract_next_set(raw_proposal)
        if committed is None:
            return False
        try:
            height = int(strip_next_set(raw_proposal)[len(b"sim-block-"):])
        except ValueError:
            return False
        return committed == set_root(self._validators_for_height(height + 1))

    def is_valid_validator(self, msg: IbftMessage) -> bool:
        return msg.sender in self._members

    def is_proposer(self, validator_id: bytes, height: int, round_: int) -> bool:
        n = len(self.addresses)
        return validator_id == self.addresses[(height + round_) % n]

    def is_valid_proposal_hash(self, proposal: Proposal, hash_: bytes) -> bool:
        return hash_ == sim_hash(proposal.raw_proposal)

    def is_valid_committed_seal(
        self, proposal_hash, committed_seal, height: Optional[int] = None
    ) -> bool:
        return True

    # -- ValidatorBackend -----------------------------------------------

    def get_voting_powers(self, height: int) -> dict:
        return dict(self._validators_for_height(height))

    # -- Backend --------------------------------------------------------

    def build_proposal(self, view: View) -> bytes:
        raw = sim_block(view.height)
        if self.commit_next_set:
            from ..lightsync.commitment import embed_next_set, set_root

            raw = embed_next_set(
                raw, set_root(self._validators_for_height(view.height + 1))
            )
        return raw

    def insert_proposal(self, proposal: Proposal, committed_seals) -> None:
        self.inserted.append((proposal, list(committed_seals)))

    def id(self) -> bytes:
        return self.address

    # -- Notifier -------------------------------------------------------

    def round_starts(self, view: View) -> None:
        return None

    def sequence_cancelled(self, view: View) -> None:
        return None

    # -- results --------------------------------------------------------

    @property
    def chain(self) -> List[bytes]:
        """Finalized raw proposals in insertion order."""
        return [p.raw_proposal for p, _ in self.inserted]
