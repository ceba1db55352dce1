"""The plain reference for a stake-weighted committee that changes by epoch
(host only: never imports jax, nothing under ``go_ibft_tpu/verify`` or
``go_ibft_tpu/chain/sync.py``).

Block by block, seal by seal: recover the seal's public key over the block's
own proposal hash (``crypto/ecdsa``), compare its address with the claimed
signer, look the signer up in the voting powers of the block's OWN height,
sum the power of the distinct valid signers as Python integers, and compare
with ``floor(2 * total / 3) + 1`` of that height's total.  Nothing is
batched, nothing is cached, no table outlives a block.

``powers_at(height)`` is the deployment's ``GetVotingPowers(height)``
(go-ibft ``core/validator_manager.go:17-20``): address -> power.
"""

from __future__ import annotations

from typing import Callable, List, Mapping, Optional, Sequence

from go_ibft_tpu.crypto import ecdsa as ec
from go_ibft_tpu.crypto.keccak import keccak256

PowersAt = Callable[[int], Mapping[bytes, int]]


def seal_valid(proposal_hash: bytes, seal, powers: Mapping[bytes, int]) -> bool:
    """One committed seal at one height: the signature is the claimed
    signer's over this hash, and the signer is in this height's set."""
    sig = seal.signature
    if len(sig) != 65 or len(seal.signer) != 20:
        return False
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:64], "big")
    pub = ec.recover(proposal_hash, r, s, sig[64])
    if pub is None or ec.pubkey_to_address(*pub) != seal.signer:
        return False
    return seal.signer in powers


def block_mask(block, powers_at: PowersAt) -> List[bool]:
    """The verdict of each of ``block``'s seals, at the block's own height."""
    proposal_hash = keccak256(block.proposal.encode())
    powers = powers_at(block.height)
    return [seal_valid(proposal_hash, seal, powers) for seal in block.seals]


def block_power(block, powers_at: PowersAt) -> tuple:
    """``(power of the block's distinct valid signers, quorum)`` at its own
    height, in exact integers."""
    powers = powers_at(block.height)
    mask = block_mask(block, powers_at)
    signers = {seal.signer for seal, ok in zip(block.seals, mask) if ok}
    got = sum(int(powers[a]) for a in signers)
    return got, (2 * sum(int(p) for p in powers.values())) // 3 + 1


def lane_mask(blocks: Sequence, powers_at: PowersAt) -> List[bool]:
    """Every seal of ``blocks`` in order, one verdict each."""
    out: List[bool] = []
    for block in blocks:
        out.extend(block_mask(block, powers_at))
    return out


def first_refused(blocks: Sequence, powers_at: PowersAt) -> Optional[int]:
    """The height of the first block whose valid seals carry less than its
    height's quorum power; ``None`` where the whole range is accepted."""
    for block in blocks:
        got, quorum = block_power(block, powers_at)
        if got < quorum:
            return block.height
    return None
