"""A/B the double-scalar ladder's 16-way table select on the active backend.

Variant A (shipped): one-hot einsum gather per scan step.
Variant B: branchless 4-level select tree (pure where ops, no dot_general).
Variant C: einsum with the one-hot built once for all 64 windows outside
the scan (trades VMEM for per-step one-hot construction).

Prints one JSON line per variant so the ladder's select strategy is chosen
from device data, not guesses.
"""

import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from go_ibft_tpu.utils.jaxcache import enable_persistent_cache

enable_persistent_cache()

from go_ibft_tpu.ops import fields, secp256k1 as sec
from go_ibft_tpu.ops.secp256k1 import (
    _G_TAB_X,
    _G_TAB_Y,
    _NWIN,
    _L,
    JacobianPoint,
    _scalar_nibbles_msb,
    _sel_pt,
    point_add,
    point_add_mixed,
    point_double,
    point_infinity,
)

FIELD = sec.FIELD
ORDER = sec.ORDER


def _tree_select(sel, table):
    """(16, ..., L) table, integer sel in [0,16): 4-level where tree."""
    b0 = (sel & 1).astype(bool)
    b1 = (sel & 2).astype(bool)
    b2 = (sel & 4).astype(bool)
    b3 = (sel & 8).astype(bool)
    t = [
        fields.select(b0, table[i + 1], table[i]) for i in range(0, 16, 2)
    ]
    t = [fields.select(b1, t[i + 1], t[i]) for i in range(0, 8, 2)]
    t = [fields.select(b2, t[i + 1], t[i]) for i in range(0, 4, 2)]
    return fields.select(b3, t[1], t[0])


def _ladder(k1, k2, qx, qy, select_fn):
    one = jnp.asarray(FIELD.const(1))
    batch = jnp.broadcast_shapes(k1.shape[:-1], k2.shape[:-1], qx.shape[:-1])
    qx = jnp.broadcast_to(qx, batch + (_L,))
    qy = jnp.broadcast_to(qy, batch + (_L,))
    q_pt = JacobianPoint(qx, qy, jnp.broadcast_to(one, batch + (_L,)))
    q_tab = [point_infinity(batch), q_pt]
    for d in range(2, 16):
        q_tab.append(point_add_mixed(q_tab[-1], qx, qy))
    qtx = jnp.stack([t.x for t in q_tab])
    qty = jnp.stack([t.y for t in q_tab])
    qtz = jnp.stack([t.z for t in q_tab])
    n1 = jnp.broadcast_to(
        _scalar_nibbles_msb(fields.canon(ORDER, k1)), (_NWIN,) + batch
    )
    n2 = jnp.broadcast_to(
        _scalar_nibbles_msb(fields.canon(ORDER, k2)), (_NWIN,) + batch
    )
    g_tab_x = jnp.asarray(_G_TAB_X)
    g_tab_y = jnp.asarray(_G_TAB_Y)

    def body(acc, inp):
        d1, d2 = inp
        acc = point_double(point_double(point_double(point_double(acc))))
        with_g = point_add_mixed(
            acc, select_fn(d1, g_tab_x), select_fn(d1, g_tab_y)
        )
        acc = _sel_pt(d1 == 0, acc, with_g)
        addq = JacobianPoint(
            select_fn(d2, qtx), select_fn(d2, qty), select_fn(d2, qtz)
        )
        acc = point_add(acc, addq)
        return acc, None

    acc, _ = jax.lax.scan(body, point_infinity(batch), (n1, n2))
    return acc


def _einsum_select(sel, table):
    oh = (jnp.arange(16) == sel[..., None]).astype(table.dtype)
    return jnp.einsum("...k,k...l->...l", oh, table)


def med(fn, *args, reps=10):
    """p50 with a real device->host readback each rep (one element on
    the host is a sync nothing can fake)."""

    def sync(out):
        leaf = jax.tree_util.tree_leaves(out)[0]
        np.asarray(leaf).ravel()[:1]

    sync(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        sync(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    return round(statistics.median(ts), 3)


def main():
    B = 256
    rng = np.random.default_rng(1)
    k1 = jnp.asarray(
        fields.to_limbs([int(rng.integers(1, 2**63)) for _ in range(B)], _L)
    )
    k2 = jnp.asarray(
        fields.to_limbs([int(rng.integers(1, 2**63)) for _ in range(B)], _L)
    )
    qx = jnp.broadcast_to(jnp.asarray(FIELD.const(sec.GX)), (B, _L))
    qy = jnp.broadcast_to(jnp.asarray(FIELD.const(sec.GY)), (B, _L))

    print(json.dumps({"platform": jax.devices()[0].platform, "lanes": B}), flush=True)

    a = jax.jit(lambda *xs: _ladder(*xs, _einsum_select))
    b = jax.jit(lambda *xs: _ladder(*xs, _tree_select))

    ra = a(k1, k2, qx, qy)
    rb = b(k1, k2, qx, qy)
    agree = all(
        bool(jnp.all(fields.canon(FIELD, x) == fields.canon(FIELD, y)))
        for x, y in zip(ra, rb)
    )
    print(json.dumps({"variants_agree": agree}), flush=True)

    print(json.dumps({"einsum_ms": med(a, k1, k2, qx, qy)}), flush=True)
    print(json.dumps({"tree_ms": med(b, k1, k2, qx, qy)}), flush=True)


if __name__ == "__main__":
    main()
