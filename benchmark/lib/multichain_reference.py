"""The plain reference for several independent chains behind one verifier
(host only: never imports jax, nothing under ``go_ibft_tpu/sched``,
``go_ibft_tpu/verify`` or ``go_ibft_tpu/chain/sync.py``).

For each chain ALONE, for each block in order, each seal one at a time:
recover the seal's public key over the block's own proposal hash
(``crypto/ecdsa``), compare its address with the claimed signer, look the
signer up in THAT chain's validator set at the block's height; the block is
accepted where the valid seals of distinct validators reach
``floor(2n / 3) + 1`` of the set's ``n`` (equal votes), and the range is
refused at the first block that does not.  No queue, no coalescing, no
table, nothing shared between chains: a seal signed by another chain's
validator is an unknown signer here, however valid its signature.

``validators_at(height)`` is one chain's ``GetVotingPowers(height)``
(go-ibft ``core/validator_manager.go:17-20``): address -> power, 1 each.
"""

from __future__ import annotations

from typing import Callable, List, Mapping, Optional, Sequence

from go_ibft_tpu.crypto import ecdsa as ec
from go_ibft_tpu.crypto.keccak import keccak256

ValidatorsAt = Callable[[int], Mapping[bytes, int]]


def quorum_of(n: int) -> int:
    """Seals of distinct validators a block of an ``n``-validator chain needs."""
    return (2 * n) // 3 + 1


def seal_valid(proposal_hash: bytes, seal, validators: Mapping[bytes, int]) -> bool:
    """One committed seal on one chain at one height: the signature is the
    claimed signer's over this hash, and the signer is in this chain's set."""
    sig = seal.signature
    if len(proposal_hash) != 32 or len(sig) != 65 or len(seal.signer) != 20:
        return False
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:64], "big")
    pub = ec.recover(proposal_hash, r, s, sig[64])
    if pub is None or ec.pubkey_to_address(*pub) != seal.signer:
        return False
    return seal.signer in validators


def block_mask(block, validators_at: ValidatorsAt) -> List[bool]:
    """The verdict of each of ``block``'s seals, at the block's own height."""
    proposal_hash = keccak256(block.proposal.encode())
    validators = validators_at(block.height)
    return [seal_valid(proposal_hash, seal, validators) for seal in block.seals]


def block_accepted(block, validators_at: ValidatorsAt) -> bool:
    """Whether the distinct valid signers of ``block`` reach its chain's quorum."""
    mask = block_mask(block, validators_at)
    signers = {seal.signer for seal, ok in zip(block.seals, mask) if ok}
    return len(signers) >= quorum_of(len(validators_at(block.height)))


def lane_mask(blocks: Sequence, validators_at: ValidatorsAt) -> List[bool]:
    """Every seal of one chain's ``blocks`` in order, one verdict each."""
    out: List[bool] = []
    for block in blocks:
        out.extend(block_mask(block, validators_at))
    return out


def first_refused(blocks: Sequence, validators_at: ValidatorsAt) -> Optional[int]:
    """The height of the first of one chain's ``blocks`` that is not
    accepted; ``None`` where the whole range is."""
    for block in blocks:
        if not block_accepted(block, validators_at):
            return block.height
    return None
