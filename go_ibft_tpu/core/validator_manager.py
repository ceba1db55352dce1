"""Voting-power quorum math.

Re-design of the reference's ValidatorManager
(core/validator_manager.go:23-155).  Voting powers are arbitrary-precision
Python ints (parity with Go's big.Int); quorum = floor(2·total/3) + 1.
"""

from __future__ import annotations

import threading
from typing import Iterable, Mapping, Optional, Protocol, Sequence

from ..messages.wire import IbftMessage
from .state import StateName


class Logger(Protocol):
    """3-method logger injected by the embedder (reference core/ibft.go:16-20)."""

    def info(self, msg: str, *args) -> None: ...

    def debug(self, msg: str, *args) -> None: ...

    def error(self, msg: str, *args) -> None: ...


class ValidatorBackend(Protocol):
    """Voting-power source (reference core/validator_manager.go:17-20)."""

    def get_voting_powers(self, height: int) -> Mapping[bytes, int]:
        """Map of validator address -> voting power for ``height``."""
        ...


class VotingPowerError(ValueError):
    """Total voting power is zero or less (reference validator_manager.go:13)."""


def calculate_quorum(total_voting_power: int) -> int:
    """floor(2·total/3) + 1 (reference core/validator_manager.go:129-135)."""
    return (2 * total_voting_power) // 3 + 1


class ValidatorManager:
    """Per-height voting power and quorum (reference core/validator_manager.go:23-47)."""

    def __init__(self, backend: ValidatorBackend, logger: Logger) -> None:
        self._backend = backend
        self._log = logger
        self._lock = threading.RLock()
        self._quorum_size: int = 0
        self._voting_power: Optional[dict[bytes, int]] = None

    def init(self, height: int) -> None:
        """Load voting powers for a height (reference validator_manager.go:50-57).

        Raises VotingPowerError when the total voting power is not positive.
        """
        voting_power = dict(self._backend.get_voting_powers(height))
        self._set_current_voting_power(voting_power)

    def _set_current_voting_power(self, voting_power: dict[bytes, int]) -> None:
        total = sum(voting_power.values())
        if total <= 0:
            raise VotingPowerError("total voting power is zero or less")
        with self._lock:
            self._voting_power = voting_power
            self._quorum_size = calculate_quorum(total)

    @property
    def quorum_size(self) -> int:
        with self._lock:
            return self._quorum_size

    def power_of(self, address: bytes) -> int:
        """Voting power of one validator (0 for unknowns / before init)."""
        with self._lock:
            if self._voting_power is None:
                return 0
            return self._voting_power.get(address, 0)

    def has_quorum(self, sender_addresses: Iterable[bytes]) -> bool:
        """True when the senders' combined power reaches quorum
        (reference core/validator_manager.go:77-96).

        Unknown senders contribute zero.  Returns False before ``init``.
        """
        with self._lock:
            if self._voting_power is None:
                return False
            power = sum(
                self._voting_power.get(addr, 0) for addr in set(sender_addresses)
            )
            return power >= self._quorum_size

    def has_prepare_quorum(
        self,
        state_name: StateName,
        proposal_message: Optional[IbftMessage],
        msgs: Sequence[IbftMessage],
    ) -> bool:
        """Prepare-phase quorum rule (reference core/validator_manager.go:99-127).

        The proposer is counted via its proposal message; the proposer sending
        its own PREPARE is a protocol violation and voids the quorum.
        """
        if proposal_message is None:
            # Valid scenario unless we are already in the prepare state
            # (e.g. a PREPARE arrived before the proposal for the same view).
            if state_name == StateName.PREPARE:
                self._log.error("has_prepare_quorum: proposal message is not set")
            return False

        proposer = proposal_message.sender
        senders = {proposer}
        for message in msgs:
            if message.sender == proposer:
                self._log.error(
                    "has_prepare_quorum: proposer is among prepare signers"
                )
                return False
            senders.add(message.sender)

        return self.has_quorum(senders)


def senders_of(messages: Iterable[IbftMessage]) -> set[bytes]:
    """Messages -> unique sender set (reference validator_manager.go:147-155)."""
    return {m.sender for m in messages}
