"""Embedder contract: message construction, verification, notifications.

Re-design of the reference's Backend interface split
(core/backend.go:12-85).  The shape is preserved — the engine owns consensus,
the embedder owns blocks, crypto and networking — with one TPU-native
addition: :class:`BatchVerifier`, which lets the engine drain a whole round's
message store in one fixed-shape device batch instead of per-message
sequential verifies (SURVEY.md §2 #10, BASELINE.md north star).
"""

from __future__ import annotations

from typing import Mapping, Optional, Protocol, Sequence, runtime_checkable

import numpy as np

from ..messages.helpers import CommittedSeal
from ..messages.wire import (
    IbftMessage,
    PreparedCertificate,
    Proposal,
    RoundChangeCertificate,
    View,
)


class MessageConstructor(Protocol):
    """Builds signed consensus messages (reference core/backend.go:12-34)."""

    def build_preprepare_message(
        self,
        raw_proposal: bytes,
        certificate: Optional[RoundChangeCertificate],
        view: View,
    ) -> IbftMessage: ...

    def build_prepare_message(self, proposal_hash: bytes, view: View) -> IbftMessage: ...

    def build_commit_message(self, proposal_hash: bytes, view: View) -> IbftMessage:
        """Must create a committed seal for the proposal hash."""
        ...

    def build_round_change_message(
        self,
        proposal: Optional[Proposal],
        certificate: Optional[PreparedCertificate],
        view: View,
    ) -> IbftMessage: ...


class Verifier(Protocol):
    """Expensive predicates injected by the embedder (reference core/backend.go:37-56)."""

    def is_valid_proposal(self, raw_proposal: bytes) -> bool: ...

    def is_valid_validator(self, msg: IbftMessage) -> bool:
        """Signature recovers to ``msg.sender`` AND the sender is a validator."""
        ...

    def is_proposer(self, validator_id: bytes, height: int, round_: int) -> bool: ...

    def is_valid_proposal_hash(self, proposal: Proposal, hash_: bytes) -> bool: ...

    def is_valid_committed_seal(
        self,
        proposal_hash: bytes,
        committed_seal: CommittedSeal,
        height: Optional[int] = None,
    ) -> bool:
        """Seal signature recovers to ``committed_seal.signer``.

        The engine always passes ``height`` (the height being finalized) so
        implementations can ALSO enforce validator-set membership — keeping
        the accept-set identical to the batched verifiers
        (:meth:`BatchVerifier.verify_committed_seals`).  The reference's
        two-argument shape (core/backend.go:50-55) is the ``height=None``
        case.
        """
        ...


class Notifier(Protocol):
    """Consensus execution callbacks (reference core/backend.go:59-65)."""

    def round_starts(self, view: View) -> None: ...

    def sequence_cancelled(self, view: View) -> None: ...


class ValidatorBackend(Protocol):
    """Voting-power source (reference core/validator_manager.go:17-20)."""

    def get_voting_powers(self, height: int) -> Mapping[bytes, int]: ...


@runtime_checkable
class BatchVerifier(Protocol):
    """TPU-native batched verification — the new capability of this build.

    A backend additionally implementing this protocol lets the engine replace
    the reference's per-message predicate loop (core/ibft.go:931-944 calling
    Verifier once per message under the store lock) with one device batch per
    phase.  Implementations return boolean masks aligned with the input
    order; the engine prunes exactly the ``False`` entries, preserving the
    observable semantics of GetValidMessages
    (reference messages/messages.go:169-199).
    """

    def verify_senders(self, msgs: Sequence[IbftMessage]) -> np.ndarray:
        """Mask of IsValidValidator over a message batch."""
        ...

    def verify_committed_seals(
        self,
        proposal_hash: bytes,
        seals: Sequence[CommittedSeal],
        height: int,
    ) -> np.ndarray:
        """Mask of IsValidCommittedSeal over a seal batch for one hash.

        ``height`` selects the validator set the signers must belong to.
        """
        ...


class Backend(
    MessageConstructor, Verifier, ValidatorBackend, Notifier, Protocol
):
    """Composite embedder interface (reference core/backend.go:69-85)."""

    def build_proposal(self, view: View) -> bytes: ...

    def insert_proposal(
        self, proposal: Proposal, committed_seals: Sequence[CommittedSeal]
    ) -> None:
        """Insert a finalized proposal.  ``proposal.round`` matters: each
        committed seal signed the tuple (raw_proposal, round)."""
        ...

    def id(self) -> bytes:
        """This validator's address."""
        ...
