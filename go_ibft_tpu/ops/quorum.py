"""Fused batch-verify + voting-power quorum certification.

The flagship device op of the framework (SURVEY.md §2 #2/#3): one compiled
program takes a round's packed messages and answers both questions the
engine cares about —

1. which messages are valid (signature recovers to the claimed sender, and
   the sender is a validator): a boolean mask aligned with the batch;
2. does the valid set reach quorum: the voting-power-weighted
   ``sum >= floor(2*total/3) + 1`` reduction of the reference's
   ValidatorManager (core/validator_manager.go:95-135), fused after the
   verification so the answer never leaves the device.

Voting-power arithmetic: the reference uses big.Int.  On device, powers are
split into 16-bit low / 15-bit high int32 halves and summed separately —
exact for per-validator powers < 2**31 and <= 2**16 lanes (carry headroom
analysis in :func:`power_reduce`).  Embedders with larger powers use the
host ValidatorManager path, which keeps exact Python ints.

Each validator counts at most once even if the batch (maliciously) carries
several messages from one sender — the reduction is over the *validator*
axis, not the message axis, so Byzantine duplicate-spam cannot inflate
power (the device analogue of the store's one-message-per-sender dedup,
reference messages/messages.go:54-65).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from . import keccak as dk
from . import secp256k1 as sec

__all__ = [
    "digest_words",
    "sig_checks_zw",
    "sender_sig_checks",
    "seal_sig_checks",
    "membership_eq",
    "sender_validity",
    "seal_validity",
    "power_reduce",
    "quorum_certify",
    "seal_quorum_certify",
    "round_certify",
    "split_power",
]


def split_power(power: int) -> Tuple[int, int]:
    """Host-side: split a voting power < 2**31 into (lo16, hi15) int32s."""
    if not 0 <= power < (1 << 31):
        raise ValueError("device quorum path requires powers < 2**31")
    return power & 0xFFFF, power >> 16


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


def sender_sig_checks(blocks, nblocks, r, s, v, sender_w, live):
    """Envelope checks from raw blocks (digest + recovery fused; used by the
    single-dispatch benchmark/entry path)."""
    return sig_checks_zw(digest_words(blocks, nblocks), r, s, v, sender_w, live)


def seal_sig_checks(hash_zw, r, s, v, signer_w, live):
    """Committed-seal checks: the signed digest is the proposal hash."""
    return sig_checks_zw(hash_zw, r, s, v, signer_w, live)


def membership_eq(sender_w, table_w):
    """``(B, V)`` sender-to-validator-row equality matrix."""
    with jax.named_scope("recover.membership"):
        return jnp.all(sender_w[:, None, :] == table_w[None, :, :], axis=-1)


def sender_validity(blocks, nblocks, r, s, v, sender_w, table_w, live):
    """Envelope validity over a packed batch.

    Returns ``(ok, eq)``: ``ok`` the per-lane validity mask and ``eq`` the
    ``(B, V)`` sender-to-validator equality matrix (reused by the fused
    quorum reduction).
    """
    sig_ok = sender_sig_checks(blocks, nblocks, r, s, v, sender_w, live)
    eq = membership_eq(sender_w, table_w)
    return sig_ok & jnp.any(eq, axis=-1), eq


def seal_validity(hash_zw, r, s, v, signer_w, table_w, live):
    """Committed-seal validity mask + equality matrix."""
    sig_ok = seal_sig_checks(hash_zw, r, s, v, signer_w, live)
    eq = membership_eq(signer_w, table_w)
    return sig_ok & jnp.any(eq, axis=-1), eq


def power_reduce(ok, eq, powers_lo, powers_hi, thr_lo, thr_hi):
    """Exact fused quorum reduction.

    ``ok``: (B,) validity mask; ``eq``: (B, V) sender equality; powers as
    (V,) int32 split halves; threshold as int32 split halves (hi may exceed
    15 bits — it is a sum bound, not a single power).

    Overflow headroom (int32 accumulators): lo-halves < 2**16 and hi-halves
    < 2**15 summed over V <= 2**14 validators stay < 2**30; the lo sum's
    carry is folded into the hi sum before comparing.  Returns
    ``(reached, got_lo, got_hi)`` with ``got = got_hi*2**16 + got_lo`` the
    exact valid voting power (got_lo < 2**16).
    """
    counted = jnp.any(eq & ok[:, None], axis=0)  # (V,) validator counted once
    lo = jnp.sum(jnp.where(counted, powers_lo, 0))
    hi = jnp.sum(jnp.where(counted, powers_hi, 0))
    carry = lo >> 16
    lo = lo & 0xFFFF
    hi = hi + carry
    reached = (hi > thr_hi) | ((hi == thr_hi) & (lo >= thr_lo))
    return reached, lo, hi


@jax.jit
def quorum_certify(
    blocks, nblocks, r, s, v, sender_w, table_w, live, powers_lo, powers_hi, thr_lo, thr_hi
):
    """One fused program: verify a message batch AND certify quorum.

    Returns ``(mask, reached, power_lo, power_hi)``.  This is the
    end-to-end "PREPARE/COMMIT phase check" the engine runs per signal —
    the reference's GetValidMessages + HasQuorum pair
    (core/ibft.go:855-889) collapsed into one device call.
    """
    ok, eq = sender_validity(blocks, nblocks, r, s, v, sender_w, table_w, live)
    reached, lo, hi = power_reduce(ok, eq, powers_lo, powers_hi, thr_lo, thr_hi)
    return ok, reached, lo, hi


@jax.jit
def seal_quorum_certify(
    hash_zw, r, s, v, signer_w, table_w, live, powers_lo, powers_hi, thr_lo, thr_hi
):
    """Fused COMMIT-phase check: seal batch validity + quorum reduction."""
    ok, eq = seal_validity(hash_zw, r, s, v, signer_w, table_w, live)
    reached, lo, hi = power_reduce(ok, eq, powers_lo, powers_hi, thr_lo, thr_hi)
    return ok, reached, lo, hi


@jax.jit
def round_certify(
    blocks,
    nblocks,
    pr,
    ps,
    pv,
    sender_w,
    plive,
    hash_zw,
    sr,
    ss,
    sv,
    signer_w,
    slive,
    table_w,
    powers_lo,
    powers_hi,
    thr_lo,
    thr_hi,
):
    """BOTH phases of a round in ONE device program.

    PREPARE envelopes and COMMIT seals share the identical recovery ladder,
    so their lanes are concatenated and verified in a single batch — one
    kernel launch where :func:`quorum_certify` + :func:`seal_quorum_certify`
    cost two (dispatch latency is material against a <2ms p50 target, and
    one 2B-lane batch vectorizes better than two serialized B-lane ones).
    This is the whole-round certification shape: validating a prepared
    certificate plus committed seals (reference core/ibft.go:1161-1231 +
    messages/helpers.go AreValidPCMessages) or a full round snapshot.

    Returns ``(prep_mask, prep_reached, seal_mask, seal_reached)``.
    """
    zw1 = digest_words(blocks, nblocks)
    zw = jnp.concatenate([zw1, hash_zw], axis=0)
    r = jnp.concatenate([pr, sr], axis=0)
    s = jnp.concatenate([ps, ss], axis=0)
    v = jnp.concatenate([pv, sv], axis=0)
    claimed = jnp.concatenate([sender_w, signer_w], axis=0)
    live = jnp.concatenate([plive, slive], axis=0)
    sig_ok = sig_checks_zw(zw, r, s, v, claimed, live)
    eq = membership_eq(claimed, table_w)
    ok = sig_ok & jnp.any(eq, axis=-1)
    b = zw1.shape[0]
    prep_ok, seal_ok = ok[:b], ok[b:]
    prep_reached, _, _ = power_reduce(
        prep_ok, eq[:b], powers_lo, powers_hi, thr_lo, thr_hi
    )
    seal_reached, _, _ = power_reduce(
        seal_ok, eq[b:], powers_lo, powers_hi, thr_lo, thr_hi
    )
    return prep_ok, prep_reached, seal_ok, seal_reached
