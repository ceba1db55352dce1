"""The device side of batch verification: digests, signature checks, membership.

One compiled program (``verify/batch.py::_recover_fn``, and its lane-sharded
twin ``verify/mesh_batch.py::mesh_verify_mask``) takes a round's packed
lanes and answers which are valid: the signature recovers to the claimed
20-byte address and that address is a row of the validator table — a boolean
mask aligned with the batch.  The functions here are that program's parts.

Whether the valid set reaches quorum is decided on the host, in exact
Python integers (``core/validator_manager.py``: the reference's
``sum >= floor(2*total/3) + 1`` over big.Int, core/validator_manager.go:95-135),
from the mask and the claimed addresses: stakes in wei do not fit a device
integer, and a validator counts once however many of its messages a batch
carries (the store's one-message-per-sender dedup, reference
messages/messages.go:54-65).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import keccak as dk
from . import secp256k1 as sec

__all__ = [
    "digest_words",
    "sig_checks_zw",
    "membership_eq",
]


def _recover_address(z_limbs, r, s, v):
    qx, qy, ok = sec.ecdsa_recover(z_limbs, r, s, v)
    with jax.named_scope("recover.address"):
        return dk.pubkey_to_address_words(qx, qy), ok


def digest_words(blocks, nblocks):
    """Batched payload digests as little-endian value words ``(B, 8)``.

    Deliberately a SEPARATE program from the recovery ladder: its compiled
    shape depends on the keccak block bucket (payload sizes vary per phase
    — a round-N PREPREPARE carries a whole RCC), while the expensive ladder
    depends only on the lane bucket.  Splitting means a new payload bucket
    recompiles ~2s of keccak, not ~2min of EC scan.
    """
    digest = dk.keccak256_blocks(blocks, nblocks)  # (B, 8) stream words
    # digest stream words are big-endian value bytes -> little-endian words
    return dk.bswap32(digest[..., ::-1])


def sig_checks_zw(zw, r, s, v, claimed_w, live):
    """Signature checks against pre-computed digest words.

    Recovery succeeds AND the recovered address equals the claimed 20-byte
    address AND the lane is live.  Serves BOTH envelope senders (zw =
    payload digests) and committed seals (zw = the proposal hash) — one
    compiled program per lane bucket."""
    with jax.named_scope("recover.address"):
        z = dk.words_le_to_limbs(zw, sec.FIELD.nlimbs)
    addr, ok = _recover_address(z, r, s, v)
    with jax.named_scope("recover.address"):
        match = jnp.all(addr == claimed_w, axis=-1)
        return ok & match & live


def membership_eq(sender_w, table_w):
    """``(B, V)`` sender-to-validator-row equality matrix."""
    with jax.named_scope("recover.membership"):
        return jnp.all(sender_w[:, None, :] == table_w[None, :, :], axis=-1)
