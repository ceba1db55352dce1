"""Decompose the headline kernel's TPU cost, stage by stage.

Times each sub-program of the fused round certification on the active
backend (trivial dispatch, keccak digest, recovery ladder, full
``round_certify``) so regressions and optimizations can be attributed to a
stage instead of guessed at.  Writes one JSON line per probe.

Usage: python scripts/profile_decompose.py [--lanes N]
"""

import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")


def med(fn, *args, reps: int = 10) -> float:
    """p50 wall time forcing a real device->host readback each rep.

    Materializing one element of the (possibly pytree) result on host is
    an end-to-end sync nothing can fake."""
    import jax
    import numpy as np

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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=100)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from go_ibft_tpu.utils.jaxcache import enable_persistent_cache

    enable_persistent_cache()

    from go_ibft_tpu.bench import build_round_workload
    from go_ibft_tpu.ops import fields
    from go_ibft_tpu.ops import keccak as dk
    from go_ibft_tpu.ops import quorum
    from go_ibft_tpu.ops import secp256k1 as sec

    def log(**kw):
        print(json.dumps(kw), flush=True)

    log(platform=jax.devices()[0].platform, lanes=args.lanes)

    w = build_round_workload(args.lanes)
    blocks, counts, pr, ps, pv, senders, plive = (
        jnp.asarray(a) for a in w.prepare
    )

    triv = jax.jit(lambda x: x + 1)
    log(stage="trivial_dispatch_ms", p50=med(triv, jnp.ones((8, 128), jnp.int32)))

    B = pr.shape[0]
    a = jnp.asarray(np.random.randint(0, 8191, (B, 20)).astype(np.int32))
    log(stage="field_mul_ms", p50=med(jax.jit(lambda x, y: fields.mul(sec.FIELD, x, y)), a, a))
    log(stage="field_inv_ms", p50=med(jax.jit(lambda x: fields.inv(sec.FIELD, x)), a))
    # r05 levers: the Montgomery product-tree inverse (one Fermat scan for
    # the whole batch) and the merged sqrt+inv dual scan.
    log(stage="batch_inv_ms", p50=med(jax.jit(lambda x: fields.batch_inv(sec.FIELD, x)), a))
    log(
        stage="pow_fixed2_ms",
        p50=med(
            jax.jit(
                lambda x, y: fields.pow_fixed2(
                    sec.FIELD, x, (sec.P + 1) // 4, sec.ORDER, y, sec.N - 2
                )
            ),
            a,
            a,
        ),
    )

    digest = jax.jit(quorum.digest_words)
    log(stage="digest_words_ms", p50=med(digest, blocks, counts))

    zw = digest(blocks, counts)
    z = jax.jit(lambda q: dk.words_le_to_limbs(q, sec.FIELD.nlimbs))(zw)

    qx = jnp.broadcast_to(jnp.asarray(sec.FIELD.const(sec.GX)), (B, 20))
    qy = jnp.broadcast_to(jnp.asarray(sec.FIELD.const(sec.GY)), (B, 20))
    log(stage="ecmul2_base_ms", p50=med(jax.jit(sec.ecmul2_base), pr, ps, qx, qy))
    # A/B: the pre-GLV Shamir ladder (64 steps, 2 streams) vs the GLV
    # ladder above (33 steps, 4 streams) — the r04 headline lever.
    log(
        stage="ecmul2_shamir_ms",
        p50=med(jax.jit(sec._ecmul2_base_shamir), pr, ps, qx, qy),
    )
    log(stage="glv_split_ms", p50=med(jax.jit(sec.glv_split), pr))

    log(stage="ecdsa_recover_ms", p50=med(jax.jit(sec.ecdsa_recover), z, pr, ps, pv))

    sig = jax.jit(quorum.sig_checks_zw)
    log(stage="sig_checks_zw_ms", p50=med(sig, zw, pr, ps, pv, senders, plive))

    cert = jax.jit(quorum.quorum_certify)
    pa = (
        blocks, counts, pr, ps, pv, senders,
        jnp.asarray(w.table), plive,
        jnp.asarray(w.powers_lo), jnp.asarray(w.powers_hi),
        jnp.int32(w.thr_lo), jnp.int32(w.thr_hi),
    )
    log(stage="quorum_certify_ms", p50=med(cert, *pa))


if __name__ == "__main__":
    main()
