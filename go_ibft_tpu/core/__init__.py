"""Consensus core: engine, state, quorum math, embedder contracts.

TPU-native re-design of the reference's L3+L4 (core/ package): see
SURVEY.md §1.  Control flow is asyncio on host; expensive verification is
delegated to a BatchVerifier draining device batches.
"""

from .backend import (
    Backend,
    BatchVerifier,
    MessageConstructor,
    Notifier,
    ValidatorBackend,
    Verifier,
)
from .ibft import (
    DEFAULT_BASE_ROUND_TIMEOUT,
    IBFT,
    RestoredState,
    get_round_timeout,
)
from .state import SequenceState, StateName
from .transport import BatchingIngress, LoopbackTransport, Transport
from .validator_manager import (
    Logger,
    ValidatorManager,
    VotingPowerError,
    calculate_quorum,
    senders_of,
)

__all__ = [
    "Backend",
    "BatchingIngress",
    "BatchVerifier",
    "DEFAULT_BASE_ROUND_TIMEOUT",
    "IBFT",
    "Logger",
    "LoopbackTransport",
    "MessageConstructor",
    "Notifier",
    "SequenceState",
    "StateName",
    "Transport",
    "ValidatorBackend",
    "ValidatorManager",
    "Verifier",
    "VotingPowerError",
    "calculate_quorum",
    "get_round_timeout",
    "senders_of",
]
