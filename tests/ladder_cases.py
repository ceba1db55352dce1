"""Python-integer model of ``ecmul2_base``'s GLV ladder, and the inputs that
press on its incomplete add (``ops/secp256k1.py::_point_add_core``).

Shared by ``test_ladder_structure.py`` (tier-1: integers only) and
``test_secp256k1.py`` (slow tier: the compiled ladder against these).
Imports no JAX: the split below is the exact-rounding formula the device
code implements (``test_secp256k1.py::test_glv_split_parity`` holds the two
together).
"""

from __future__ import annotations

import random
from typing import Iterator, List, Optional, Tuple

from go_ibft_tpu.crypto import ecdsa as host
from go_ibft_tpu.ops import secp256k1 as sec

N, P, LAMBDA, BETA = sec.N, sec.P, sec._LAMBDA, sec._BETA
NWIN = sec._GLV_NWIN  # 33 four-bit windows
G = (host.GX, host.GY)
Point = Optional[Tuple[int, int]]


def glv_split_int(k: int) -> Tuple[int, int]:
    """Signed half-scalars ``(k1, k2)`` with ``k == k1 + k2*LAMBDA (mod N)``,
    as ``sec.glv_split`` computes them for canonical ``k``."""
    c1 = (k * sec._GLV_G1 + (1 << 383)) >> 384
    c2 = (k * sec._GLV_G2 + (1 << 383)) >> 384
    return (
        k - c1 * sec._GLV_A1 - c2 * sec._GLV_A2,
        -c1 * sec._GLV_B1 - c2 * sec._GLV_B2,
    )


def from_halves(k1: int, k2: int) -> int:
    """The scalar whose split is ``(k1, k2)``; asserts that it is (the
    rounding returns a short pair unchanged)."""
    k = (k1 + k2 * LAMBDA) % N
    assert glv_split_int(k) == (k1, k2), (k1, k2)
    return k


def nibbles_msb(m: int) -> List[int]:
    """The ladder's digit stream of a half-scalar's magnitude."""
    assert 0 <= m < 1 << (4 * NWIN)
    return [(m >> (4 * j)) & 0xF for j in range(NWIN - 1, -1, -1)]


def ladder_steps(k: int) -> Iterator[Tuple[int, int]]:
    """``(m, d)`` at every add of both streams of scalar ``k``: the
    accumulator lane holds ``+-m*B`` before the step's four doublings
    (``+-16m*B`` after them) and the addend is ``+-d*B``, same sign."""
    for half in glv_split_int(k % N):
        m = 0
        for d in nibbles_msb(abs(half)):
            yield m, d
            m = 16 * m + d
        assert m == abs(half)


_F31 = (1 << 124) - 1  # digit stream 0, 0, then thirty-one 0xF


def edge_scalars() -> List[Tuple[str, int]]:
    """ISSUE 28's list: small, wrap-around, endomorphism and power-of-two
    scalars, and scalars built from chosen half-scalars."""
    out = [(str(k), k) for k in (0, 1, 2, 15, 16, 17)]
    out += [
        ("N-1", N - 1),
        ("N-2", N - 2),
        ("LAMBDA", LAMBDA),
        ("N-LAMBDA", N - LAMBDA),
        ("2^128-1", (1 << 128) - 1),
        ("2^128+1", (1 << 128) + 1),
        ("half1_zero", from_halves(0, (1 << 100) + 7)),
        ("half2_zero", from_halves((1 << 100) + 7, 0)),
        ("top_nibbles_zero", from_halves(0x123456789, 0xABCDEF)),
        ("all_f_streams", from_halves(_F31, _F31)),
        ("all_f_negative", from_halves(-_F31, -_F31)),
        ("negative_small", from_halves(-5, 3)),
        ("negative_mixed", from_halves((1 << 120) + 1, -((1 << 97) + 16))),
        ("digit_then_zeros", from_halves(15 << 120, 1 << 64)),
    ]
    return out


def phi(pt: Tuple[int, int]) -> Tuple[int, int]:
    return (BETA * pt[0] % P, pt[1])


def neg(pt: Tuple[int, int]) -> Tuple[int, int]:
    return (pt[0], P - pt[1])


def edge_points() -> List[Tuple[str, Tuple[int, int]]]:
    rand = host.scalar_mul(0x1D2C3B4A59687796A5B4C3D2E1F0 + (1 << 200), G)
    return [
        ("G", G),
        ("-G", neg(G)),
        ("2G", host.scalar_mul(2, G)),
        ("phiG", phi(G)),
        ("-phiG", neg(phi(G))),
        ("15G", host.scalar_mul(15, G)),
        ("rand", rand),
    ]


def ecmul2_cases() -> List[Tuple[str, int, int, Tuple[int, int]]]:
    """``(id, k1, k2, Q)`` lanes.  Every edge scalar meets every point as
    ``k1`` and (rotated by a stride coprime to the list) as ``k2``; then the
    sums that come out as infinity, and the lanes where the four streams
    agree pairwise so that ``recover.combine`` must double."""
    scalars, points = edge_scalars(), edge_points()
    cases = []
    for j, (pn, pt) in enumerate(points):
        for i, (sn, k1) in enumerate(scalars):
            s2n, k2 = scalars[(i + 3 * j + 1) % len(scalars)]
            cases.append((f"{sn}*G+{s2n}*{pn}", k1, k2, pt))
    rng = random.Random(28)
    k = rng.randrange(1, N)
    c = rng.randrange(2, N)
    cg = host.scalar_mul(c, G)
    cases += [
        # k1*G + k2*Q == infinity
        ("inf:k*G+k*(-G)", k, k, neg(G)),
        ("inf:k*G+(N-k)*G", k, N - k, G),
        ("inf:(N-k*c)*G+k*cG", (N - k * c) % N, k, cg),
        ("inf:1*G+(N-1)*G", 1, N - 1, G),
        ("inf:LAMBDA*G+(N-1)*phiG", LAMBDA, N - 1, phi(G)),
        # G half == Q half: combine adds a point to itself
        ("dbl:k*G+k*G", k, k, G),
        ("dbl:1*G+1*G", 1, 1, G),
        ("dbl:k*LAMBDA*G+k*phiG", k * LAMBDA % N, k, phi(G)),
        ("dbl:k*c*G+k*cG", k * c % N, k, cg),
    ]
    return cases


def expected_point(k1: int, k2: int, q: Tuple[int, int]) -> Point:
    return host._add(host.scalar_mul(k1, G), host.scalar_mul(k2, q))
