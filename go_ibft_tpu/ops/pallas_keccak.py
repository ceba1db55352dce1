"""Pallas TPU kernel for batched Keccak-f[1600].

The XLA path (:mod:`.keccak`) keeps the state as ``(B, 25, 2)`` uint32 —
fine for fusion, but the trailing axis of 2 is hostile to the VPU's
``(8, 128)`` register tiling: XLA must re-tile every round.  This kernel
flips the layout to **``(50, B)``**: each of the 50 uint32 half-lanes is a
row, and the *batch* rides the 128-wide lane axis — every theta/rho/pi/chi
step is then a plain full-width vector op on ``(B,)`` rows, the layout the
VPU actually wants.  One grid step processes a 128-message tile held in
VMEM for all 24 rounds (zero HBM traffic between rounds).

The 24 rounds run under a ``lax.fori_loop`` INSIDE the kernel with the
round constants streamed from a small input ref — one round's straight-line
body is traced once (the fully unrolled graph is pathological to compile on
XLA:CPU in interpret mode, the same reason ``keccak.keccak_f`` scans), and
the whole loop runs register/VMEM-resident with no per-round HBM traffic.

Wired into the digest path through :func:`go_ibft_tpu.ops.keccak.keccak_f`
when ``GO_IBFT_PALLAS=1`` (TPU backends — it raises elsewhere;
``GO_IBFT_PALLAS=interpret`` forces the interpreter on any backend for
tests/debugging).  Compiled by Mosaic and checked against
:func:`keccak_f_reference` on the chip by ``chip_smoke.py``.  Reference
scope: this accelerates the digest half of the embedder's ``Verifier``
seam (go-ibft core/backend.go:37-56); the state-machine semantics above
it are unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .keccak import _RC, _ROT

__all__ = ["keccak_f_pallas", "pallas_supported"]

_TILE = 128  # batch tile per grid step: the VPU lane width


def pallas_supported() -> bool:
    """True when the active backend can run this kernel compiled (TPU).

    This is THE predicate for compiled-vs-interpret dispatch; keccak's
    ``_pallas_mode`` and the A/B + warm scripts all route through it so
    they can never disagree about which variant actually runs.
    """
    return jax.default_backend() == "tpu"


def _rotl_halves(lo, hi, n: int):
    """64-bit rotate-left by a STATIC amount on (lo, hi) uint32 rows."""
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


def _keccak_f_kernel(rc_ref, st_ref, out_ref):
    """24 Keccak rounds (``fori_loop``) on a ``(50, B)`` uint32 VMEM block.

    Row ``2*i`` is the low half of lane ``i``, row ``2*i + 1`` the high
    half; lanes are indexed ``x + 5*y`` per the Keccak spec.  ``rc_ref``
    holds the 24 round constants as ``(24, 2)`` uint32 (lo, hi).
    """

    def round_body(r, st):
        a = [(st[2 * i], st[2 * i + 1]) for i in range(25)]
        # theta: column parities and the d-mix
        c = []
        for x in range(5):
            lo = a[x][0] ^ a[x + 5][0] ^ a[x + 10][0] ^ a[x + 15][0] ^ a[x + 20][0]
            hi = a[x][1] ^ a[x + 5][1] ^ a[x + 10][1] ^ a[x + 15][1] ^ a[x + 20][1]
            c.append((lo, hi))
        d = []
        for x in range(5):
            rlo, rhi = _rotl_halves(*c[(x + 1) % 5], 1)
            d.append((c[(x - 1) % 5][0] ^ rlo, c[(x - 1) % 5][1] ^ rhi))
        a = [
            (a[x + 5 * y][0] ^ d[x][0], a[x + 5 * y][1] ^ d[x][1])
            for y in range(5)
            for x in range(5)
        ]
        # rho + pi: B[y, 2x+3y] = rotl(A[x, y], r[x][y])
        b = [None] * 25
        for x in range(5):
            for y in range(5):
                b[y + 5 * ((2 * x + 3 * y) % 5)] = _rotl_halves(
                    *a[x + 5 * y], _ROT[x][y]
                )
        # chi
        a = [
            (
                b[x + 5 * y][0]
                ^ (~b[(x + 1) % 5 + 5 * y][0] & b[(x + 2) % 5 + 5 * y][0]),
                b[x + 5 * y][1]
                ^ (~b[(x + 1) % 5 + 5 * y][1] & b[(x + 2) % 5 + 5 * y][1]),
            )
            for y in range(5)
            for x in range(5)
        ]
        # iota
        a[0] = (a[0][0] ^ rc_ref[r, 0], a[0][1] ^ rc_ref[r, 1])
        return jnp.stack([half for lane in a for half in lane], axis=0)

    out_ref[:] = jax.lax.fori_loop(0, 24, round_body, st_ref[:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _keccak_f_rows(st: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """The compiled unit: ``(50, k*TILE)`` rows in, same shape out.

    Kept separate from the padding/layout wrapper so every batch size B
    that rounds up to the same tile count shares ONE compile (the unrolled
    24-round graph is expensive to build on XLA:CPU — don't retrace it per
    caller shape)."""
    from jax.experimental import pallas as pl

    rc = jnp.asarray(
        np.asarray([[c & 0xFFFFFFFF, c >> 32] for c in _RC], dtype=np.uint32)
    )
    return pl.pallas_call(
        _keccak_f_kernel,
        out_shape=jax.ShapeDtypeStruct(st.shape, jnp.uint32),
        grid=(st.shape[1] // _TILE,),
        in_specs=[
            pl.BlockSpec((24, 2), lambda i: (0, 0)),  # round constants
            pl.BlockSpec((50, _TILE), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((50, _TILE), lambda i: (0, i)),
        interpret=interpret,
    )(rc, st)


def keccak_f_pallas(state: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Keccak-f[1600] on a ``(B, 25, 2)`` uint32 state via the Pallas kernel.

    Drop-in for :func:`go_ibft_tpu.ops.keccak.keccak_f` on 1-D batches.
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU
    correctness tests); compiled mode requires a TPU backend.  The
    layout/padding transform runs eagerly; only the fixed-shape row kernel
    is jitted.
    """
    if state.ndim != 3 or state.shape[-2:] != (25, 2):
        raise ValueError(f"expected (B, 25, 2) state, got {state.shape}")
    b = state.shape[0]
    bpad = -(-b // _TILE) * _TILE
    # (B, 25, 2) -> (50, Bpad): half-lanes become rows, batch rides lanes.
    st = jnp.transpose(jnp.asarray(state).reshape(b, 50))
    st = jnp.pad(st, ((0, 0), (0, bpad - b)))
    out = _keccak_f_rows(st, interpret=interpret)
    return jnp.transpose(out)[:b].reshape(b, 25, 2)


def keccak_f_reference(state: np.ndarray) -> np.ndarray:
    """Pure-numpy uint64 oracle for the kernel tests."""
    lanes = (
        state[..., 0].astype(np.uint64) | (state[..., 1].astype(np.uint64) << 32)
    )  # (B, 25)
    out = np.empty_like(lanes)
    for row in range(lanes.shape[0]):
        a = list(lanes[row])
        for rc in _RC:
            c = [a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20] for x in range(5)]
            d = [
                c[(x - 1) % 5]
                ^ ((c[(x + 1) % 5] << np.uint64(1)) | (c[(x + 1) % 5] >> np.uint64(63)))
                for x in range(5)
            ]
            a = [a[x + 5 * y] ^ d[x] for y in range(5) for x in range(5)]
            b = [np.uint64(0)] * 25
            for x in range(5):
                for y in range(5):
                    r = _ROT[x][y]
                    v = a[x + 5 * y]
                    b[y + 5 * ((2 * x + 3 * y) % 5)] = (
                        ((v << np.uint64(r)) | (v >> np.uint64(64 - r)))
                        if r
                        else v
                    )
            a = [
                b[x + 5 * y] ^ (~b[(x + 1) % 5 + 5 * y] & b[(x + 2) % 5 + 5 * y])
                for y in range(5)
                for x in range(5)
            ]
            a[0] ^= np.uint64(rc)
        out[row] = a
    res = np.empty(state.shape, dtype=np.uint32)
    res[..., 0] = out & np.uint64(0xFFFFFFFF)
    res[..., 1] = out >> np.uint64(32)
    return res
