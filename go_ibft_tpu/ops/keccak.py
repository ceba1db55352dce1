"""Batched Keccak-256 on TPU (JAX/XLA), 64-bit lanes as uint32 pairs.

TPUs have no native 64-bit integer lanes in the VPU sweet spot, so the
Keccak-f[1600] state is kept as ``(..., 25, 2)`` uint32 — ``[..., 0]`` the
low half, ``[..., 1]`` the high half of each lane.  All rotation amounts are
static, so a 64-bit rotate is two shifts and an or per half; the 24 rounds
are unrolled into straight-line code and batched by broadcasting.

Two consumers:

* **address derivation** — recovered public keys (limb vectors from
  :mod:`.secp256k1`) are hashed to 20-byte Ethereum-style addresses
  entirely on device, so sender-identity checking
  (reference ``Verifier.IsValidValidator``, core/backend.go:40-44) never
  leaves the chip;
* **payload digests** — ``payload_no_sig`` bytes are packed host-side into
  fixed-bucket padded blocks and absorbed in one ``lax.scan``, one whole
  round's messages per call.

Byte conventions: Keccak absorbs bytes into lanes little-endian.  A
"stream word" here is a uint32 whose LSB is the earliest byte of the byte
stream; digests and addresses are returned as stream words and converted
with the host helpers at the edges.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .fields import LIMB_BITS

__all__ = [
    "RATE_BYTES",
    "keccak_f",
    "keccak256_blocks",
    "limbs_to_words_le",
    "words_le_to_limbs",
    "pubkey_to_address_words",
    "pack_messages",
    "bswap32",
    "digest_words_to_bytes",
    "address_to_words",
    "addresses_to_words",
]

RATE_BYTES = 136  # Keccak-256 rate (17 lanes)

_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]

_ROT = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]

Lane = Tuple[jnp.ndarray, jnp.ndarray]  # (lo, hi) uint32


def _rotl64(lane: Lane, n: int) -> Lane:
    lo, hi = lane
    n %= 64
    if n == 0:
        return lo, hi
    if n >= 32:
        lo, hi = hi, lo
        n -= 32
        if n == 0:
            return lo, hi
    return (
        (lo << n) | (hi >> (32 - n)),
        (hi << n) | (lo >> (32 - n)),
    )


def _xor(a: Lane, b: Lane) -> Lane:
    return a[0] ^ b[0], a[1] ^ b[1]


def _keccak_round(state: jnp.ndarray, rc: jnp.ndarray) -> jnp.ndarray:
    """One Keccak round on a ``(..., 25, 2)`` uint32 state.

    The 24 rounds run under ``lax.scan`` (see :func:`keccak_f`) so this body
    is traced and compiled exactly once — unrolling all rounds produces a
    multi-thousand-op elementwise graph that XLA:CPU compiles pathologically
    slowly.
    """
    a: List[Lane] = [(state[..., i, 0], state[..., i, 1]) for i in range(25)]
    # theta
    c = [
        (
            a[x][0] ^ a[x + 5][0] ^ a[x + 10][0] ^ a[x + 15][0] ^ a[x + 20][0],
            a[x][1] ^ a[x + 5][1] ^ a[x + 10][1] ^ a[x + 15][1] ^ a[x + 20][1],
        )
        for x in range(5)
    ]
    d = [_xor(c[(x - 1) % 5], _rotl64(c[(x + 1) % 5], 1)) for x in range(5)]
    a = [_xor(a[x + 5 * y], d[x]) for y in range(5) for x in range(5)]
    # rho + pi: B[y, 2x+3y] = rotl(A[x, y], r[x][y])
    b: List[Lane] = [None] * 25  # type: ignore[list-item]
    for x in range(5):
        for y in range(5):
            b[y + 5 * ((2 * x + 3 * y) % 5)] = _rotl64(a[x + 5 * y], _ROT[x][y])
    # chi
    a = [
        (
            b[x + 5 * y][0] ^ (~b[(x + 1) % 5 + 5 * y][0] & b[(x + 2) % 5 + 5 * y][0]),
            b[x + 5 * y][1] ^ (~b[(x + 1) % 5 + 5 * y][1] & b[(x + 2) % 5 + 5 * y][1]),
        )
        for y in range(5)
        for x in range(5)
    ]
    # iota
    a[0] = (a[0][0] ^ rc[0], a[0][1] ^ rc[1])
    lo = jnp.stack([lane[0] for lane in a], axis=-1)
    hi = jnp.stack([lane[1] for lane in a], axis=-1)
    return jnp.stack([lo, hi], axis=-1)


_RC_WORDS = np.asarray(
    [[rc & 0xFFFFFFFF, rc >> 32] for rc in _RC], dtype=np.uint32
)


def _pallas_mode() -> str:
    """'' (off) | 'compiled' | 'interpret' — the Pallas permutation switch.

    ``GO_IBFT_PALLAS=1`` selects the compiled Pallas kernel, which needs
    Mosaic: on a backend that cannot compile it the switch RAISES instead
    of silently running the XLA path under the kernel's name.
    ``GO_IBFT_PALLAS=interpret`` forces the interpreter on any backend
    (tests/debugging).
    """
    import os

    flag = os.environ.get("GO_IBFT_PALLAS", "")
    if flag == "interpret":
        return "interpret"
    if flag == "1":
        from .pallas_keccak import pallas_supported  # the single predicate

        if not pallas_supported():
            raise RuntimeError(
                "GO_IBFT_PALLAS=1 asks for the compiled Pallas keccak, but "
                f"the JAX backend is {jax.default_backend()!r}, not a TPU "
                "(use GO_IBFT_PALLAS=interpret to run the interpreter)"
            )
        return "compiled"
    return ""


def keccak_f(state: jnp.ndarray) -> jnp.ndarray:
    """Keccak-f[1600] on a ``(..., 25, 2)`` uint32 state (scan over rounds).

    With ``GO_IBFT_PALLAS`` set (see :func:`_pallas_mode`), 1-D batches
    route to the Pallas kernel's register-native layout instead
    (:mod:`.pallas_keccak`).
    """
    mode = _pallas_mode()
    if mode and state.ndim == 3 and state.shape[-2:] == (25, 2):
        from .pallas_keccak import keccak_f_pallas  # lazy: avoids a cycle

        return keccak_f_pallas(state, interpret=mode == "interpret")

    def body(st, rc):
        return _keccak_round(st, rc), None

    out, _ = jax.lax.scan(body, state, jnp.asarray(_RC_WORDS))
    return out


def keccak256_blocks(
    blocks: jnp.ndarray, num_blocks: jnp.ndarray
) -> jnp.ndarray:
    """Digest pre-padded rate blocks; returns ``(..., 8)`` uint32 stream words.

    ``blocks`` is ``(..., B, 17, 2)`` uint32 (17 lanes per 136-byte rate
    block, already multi-rate padded by :func:`pack_messages`);
    ``num_blocks`` is ``(...,)`` int32 in ``[1, B]``.  Blocks past
    ``num_blocks`` are ignored via a select, so one compiled program serves
    any message length up to the bucket.
    """
    bmax = blocks.shape[-3]
    batch = blocks.shape[:-3]
    state = jnp.zeros(batch + (25, 2), dtype=jnp.uint32)

    xs = jnp.moveaxis(blocks, -3, 0)  # (B, ..., 17, 2)

    def body(state, inp):
        i, blk = inp
        absorbed = state.at[..., :17, :].set(state[..., :17, :] ^ blk)
        nxt = keccak_f(absorbed)
        live = (i < num_blocks)[..., None, None]
        return jnp.where(live, nxt, state), None

    state, _ = jax.lax.scan(body, state, (jnp.arange(bmax), xs))
    # Digest = first 4 lanes, little-endian => stream words interleave lo/hi.
    out = state[..., :4, :]  # (..., 4, 2)
    return out.reshape(batch + (8,))


def bswap32(w: jnp.ndarray) -> jnp.ndarray:
    """Byte-swap each uint32 (big-endian <-> little-endian words)."""
    return (
        (w >> 24)
        | ((w >> 8) & jnp.uint32(0xFF00))
        | ((w << 8) & jnp.uint32(0xFF0000))
        | (w << 24)
    )


def limbs_to_words_le(limbs: jnp.ndarray, nwords: int = 8) -> jnp.ndarray:
    """Canonical 13-bit limbs -> little-endian uint32 words of the integer."""
    u = limbs.astype(jnp.uint32)
    words = []
    nl = limbs.shape[-1]
    for j in range(nwords):
        acc = jnp.zeros(limbs.shape[:-1], dtype=jnp.uint32)
        for k in range(nl):
            lo_bit = LIMB_BITS * k
            if lo_bit + LIMB_BITS <= 32 * j or lo_bit >= 32 * (j + 1):
                continue
            sh = lo_bit - 32 * j
            if sh >= 0:
                acc = acc | (u[..., k] << sh)  # uint32 << wraps = truncation
            else:
                acc = acc | (u[..., k] >> (-sh))
        words.append(acc)
    return jnp.stack(words, axis=-1)


def words_le_to_limbs(words: jnp.ndarray, nlimbs: int) -> jnp.ndarray:
    """Little-endian uint32 words -> canonical 13-bit int32 limbs."""
    limbs = []
    nw = words.shape[-1]
    mask = jnp.uint32((1 << LIMB_BITS) - 1)
    for k in range(nlimbs):
        lo_bit = LIMB_BITS * k
        j = lo_bit // 32
        sh = lo_bit - 32 * j
        acc = jnp.zeros(words.shape[:-1], dtype=jnp.uint32)
        if j < nw:
            acc = words[..., j] >> sh
            if sh + LIMB_BITS > 32 and j + 1 < nw:
                acc = acc | (words[..., j + 1] << (32 - sh))
        limbs.append((acc & mask).astype(jnp.int32))
    return jnp.stack(limbs, axis=-1)


def pubkey_to_address_words(
    qx_limbs: jnp.ndarray, qy_limbs: jnp.ndarray
) -> jnp.ndarray:
    """keccak256(X32 || Y32)[12:] on device; ``(..., 5)`` uint32 stream words.

    Input limbs must be canonical (:func:`go_ibft_tpu.ops.fields.canon`).
    Matches :func:`go_ibft_tpu.crypto.ecdsa.pubkey_to_address` byte-for-byte.
    """
    xw = limbs_to_words_le(qx_limbs)  # value words, little-endian
    yw = limbs_to_words_le(qy_limbs)
    # Big-endian serialization: stream word j of X = bswap(value word 7-j).
    stream = [bswap32(xw[..., 7 - j]) for j in range(8)]
    stream += [bswap32(yw[..., 7 - j]) for j in range(8)]
    batch = qx_limbs.shape[:-1]
    # One 64-byte message in a single 136-byte rate block, padded.
    lanes = jnp.zeros(batch + (17, 2), dtype=jnp.uint32)
    for t in range(8):
        lanes = lanes.at[..., t, 0].set(stream[2 * t])
        lanes = lanes.at[..., t, 1].set(stream[2 * t + 1])
    # padding: byte 64 = 0x01 (lane 8 lo byte 0), byte 135 = 0x80 (lane 16 hi
    # byte 3, i.e. top byte)
    lanes = lanes.at[..., 8, 0].set(jnp.uint32(0x01))
    lanes = lanes.at[..., 16, 1].set(jnp.uint32(0x80) << 24)
    digest = keccak256_blocks(
        lanes[..., None, :, :], jnp.ones(batch, dtype=jnp.int32)
    )  # (..., 8) stream words
    # Address = digest bytes 12..31 = stream words 3..7
    return digest[..., 3:]


# ---------------------------------------------------------------------------
# Host-side packing helpers (numpy, run once per batch at the edge)
# ---------------------------------------------------------------------------


def pack_messages(
    payloads: Sequence[bytes], max_blocks: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad byte strings to Keccak rate blocks as uint32 lane pairs.

    Returns ``(blocks, num_blocks)`` with ``blocks`` of shape
    ``(N, max_blocks, 17, 2)`` uint32 and ``num_blocks`` int32.  Raises if a
    payload exceeds the bucket (callers choose buckets; see
    ``verify.bucketing``).

    Vectorized: one flat ``(N, max_blocks * RATE)`` byte staging buffer, one
    row-memcpy per payload (a single C-level join when all payloads share a
    length — the common same-shape-envelopes case), and the multi-rate
    padding applied as two fancy-indexed XORs — ``b ^ 0x01`` at the payload
    end, ``b ^ 0x80`` at the block end, coinciding to ``0x81`` when the pad
    is one byte.  Bit-identical to :func:`_pack_messages_reference` (pinned
    by tests/test_pack_vectorized.py).
    """
    n = len(payloads)
    if n == 0:
        return (
            np.zeros((0, max_blocks, 17, 2), dtype=np.uint32),
            np.zeros((0,), dtype=np.int32),
        )
    lens = np.fromiter((len(p) for p in payloads), dtype=np.int64, count=n)
    nbs = lens // RATE_BYTES + 1  # padding always adds [1, RATE] bytes
    if (nbs > max_blocks).any():
        i = int(np.argmax(nbs))
        raise ValueError(
            f"payload of {int(lens[i])} bytes needs {int(nbs[i])} blocks "
            f"> bucket {max_blocks}"
        )
    buf = np.zeros((n, max_blocks * RATE_BYTES), dtype=np.uint8)
    width = int(lens[0])
    if width and (lens == width).all():
        flat = np.frombuffer(b"".join(payloads), dtype=np.uint8)
        buf[:, :width] = flat.reshape(n, width)
    else:
        for i, data in enumerate(payloads):
            if data:
                buf[i, : len(data)] = np.frombuffer(data, dtype=np.uint8)
    rows = np.arange(n)
    buf[rows, lens] ^= 0x01
    buf[rows, nbs * RATE_BYTES - 1] ^= 0x80
    lanes = buf.view("<u4").reshape(n, max_blocks, 34)
    blocks = np.empty((n, max_blocks, 17, 2), dtype=np.uint32)
    blocks[..., 0] = lanes[:, :, 0::2]
    blocks[..., 1] = lanes[:, :, 1::2]
    return blocks, nbs.astype(np.int32)


def _pack_messages_reference(
    payloads: Sequence[bytes], max_blocks: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Per-message loop packer — the parity oracle for :func:`pack_messages`.

    Kept verbatim (one bytearray + frombuffer per message) so the vectorized
    path has a bit-identity reference to diff against; not a hot path.
    """
    n = len(payloads)
    blocks = np.zeros((n, max_blocks, 17, 2), dtype=np.uint32)
    counts = np.zeros((n,), dtype=np.int32)
    for i, data in enumerate(payloads):
        padded = bytearray(data)
        pad_len = RATE_BYTES - (len(padded) % RATE_BYTES)
        if pad_len == 1:
            padded += b"\x81"
        else:
            padded += b"\x01" + b"\x00" * (pad_len - 2) + b"\x80"
        nb = len(padded) // RATE_BYTES
        if nb > max_blocks:
            raise ValueError(
                f"payload of {len(data)} bytes needs {nb} blocks > bucket {max_blocks}"
            )
        counts[i] = nb
        arr = np.frombuffer(bytes(padded), dtype="<u4").reshape(nb, 34)
        blocks[i, :nb, :, 0] = arr[:, 0::2]
        blocks[i, :nb, :, 1] = arr[:, 1::2]
    return blocks, counts


def digest_words_to_bytes(words: np.ndarray) -> bytes:
    """``(8,)`` uint32 stream words -> 32 digest bytes."""
    return np.asarray(words, dtype="<u4").tobytes()


def address_to_words(address: bytes) -> np.ndarray:
    """20-byte address -> ``(5,)`` uint32 stream words."""
    if len(address) != 20:
        raise ValueError("address must be 20 bytes")
    return np.frombuffer(address, dtype="<u4").copy()


def addresses_to_words(addresses: Sequence[bytes]) -> np.ndarray:
    """Bulk :func:`address_to_words`: ``N`` addresses -> ``(N, 5)`` uint32.

    One C-level join + one frombuffer instead of N per-address calls; raises
    on any address that is not exactly 20 bytes (same contract as the
    scalar helper, checked up front so the error names the offending lane).
    """
    for i, a in enumerate(addresses):
        if len(a) != 20:
            raise ValueError(f"address {i} must be 20 bytes, got {len(a)}")
    n = len(addresses)
    if n == 0:
        return np.zeros((0, 5), dtype=np.uint32)
    return (
        np.frombuffer(b"".join(addresses), dtype="<u4").reshape(n, 5).copy()
    )
