"""Profile the host-side packing edge (messages -> device-ready arrays).

The fused kernels are only as fast as the host edge that feeds them: if
packing a 1000-message round costs more than the kernel, the end-to-end
p50 is host-bound.  This script times each packing stage separately so
optimization effort lands where the time actually goes, and diffs the
vectorized packers against the kept per-message reference loops
(``_pack_*_reference``).  XLA:CPU and host timings: never chip evidence.
"""

import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402 - sys.path setup must precede package imports

try:
    jax.config.update("jax_platforms", "cpu")
except RuntimeError:
    pass


def _timed(fn, reps: int = 5) -> float:
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 300

    from go_ibft_tpu.bench.workload import _keys
    from go_ibft_tpu.crypto.backend import ECDSABackend, proposal_hash_of
    from go_ibft_tpu.messages.helpers import extract_committed_seal
    from go_ibft_tpu.messages.wire import Proposal, View
    from go_ibft_tpu.verify.batch import (
        _pack_seal_batch_reference,
        _pack_sender_batch_reference,
        pack_seal_batch,
        pack_sender_batch,
        pack_validator_table,
    )
    from go_ibft_tpu.verify.pipeline import PackCache

    keys = _keys(n, 0)
    src = ECDSABackend.static_validators({k.address: 1 for k in keys})
    backends = [ECDSABackend(k, src) for k in keys]
    view = View(height=1, round=0)
    phash = proposal_hash_of(Proposal(raw_proposal=b"profile block", round=0))

    t0 = time.perf_counter()
    prepares = [b.build_prepare_message(phash, view) for b in backends]
    seals = [
        extract_committed_seal(b.build_commit_message(phash, view))
        for b in backends
    ]
    t_build = time.perf_counter() - t0

    t0 = time.perf_counter()
    payloads = [m.encode(include_signature=False) for m in prepares]
    t_encode = time.perf_counter() - t0

    # Apples-to-apples pure packing (payloads pre-encoded for both sides).
    t_ref = _timed(lambda: _pack_sender_batch_reference(prepares, payloads=payloads))
    t_vec = _timed(lambda: pack_sender_batch(prepares, payloads=payloads))

    # Full pack including the wire encode (what a cold drain pays) ...
    t_ref_full = _timed(lambda: _pack_sender_batch_reference(prepares))
    t_vec_full = _timed(lambda: pack_sender_batch(prepares))
    # ... and the steady-state engine shape: pack-cache warm, no re-encode.
    cache = PackCache()
    pack_sender_batch(prepares, cache=cache)
    t_cached = _timed(lambda: pack_sender_batch(prepares, cache=cache))

    t_seal_ref = _timed(lambda: _pack_seal_batch_reference(phash, seals))
    t_seal_vec = _timed(lambda: pack_seal_batch(phash, seals))
    t_table = _timed(lambda: pack_validator_table([k.address for k in keys]))

    print(f"n={n}")
    print(f"  build+sign (one-time)          : {t_build * 1e3:9.2f} ms")
    print(
        f"  wire encode (per cold pack)    : {t_encode * 1e3:9.2f} ms"
        f" [{len(payloads[0])}B each]"
    )
    print("  pack_sender_batch (pure pack, payloads given)")
    print(f"    reference loop               : {t_ref * 1e3:9.2f} ms")
    print(
        f"    vectorized                   : {t_vec * 1e3:9.2f} ms"
        f"   ({t_ref / t_vec:5.1f}x)"
    )
    print("  pack_sender_batch (full: encode + pack)")
    print(f"    reference loop               : {t_ref_full * 1e3:9.2f} ms")
    print(
        f"    vectorized                   : {t_vec_full * 1e3:9.2f} ms"
        f"   ({t_ref_full / t_vec_full:5.1f}x)"
    )
    print(
        f"    vectorized + warm pack cache : {t_cached * 1e3:9.2f} ms"
        f"   ({t_ref_full / t_cached:5.1f}x)"
    )
    print("  pack_seal_batch")
    print(f"    reference loop               : {t_seal_ref * 1e3:9.2f} ms")
    print(
        f"    vectorized                   : {t_seal_vec * 1e3:9.2f} ms"
        f"   ({t_seal_ref / t_seal_vec:5.1f}x)"
    )
    print(f"  pack_validator_table           : {t_table * 1e3:9.2f} ms")


if __name__ == "__main__":
    main()
