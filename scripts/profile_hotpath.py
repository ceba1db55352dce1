"""Break the happy-path height latency into engine/crypto components.

The r05 round recorded the 4-validator happy path at 0.86x of the
sequential host baseline (33.9 ms/height) with no attribution — this
script is the profiler that turns that one number into a budget.  It runs
a 4-validator cluster (real ECDSA, BatchingIngress gossip, adaptive
verifier; the shape ``tests/test_span_contract.py`` counts) with the hot seams
instrumented from OUTSIDE the engine:

* ``sign_ms``      — outbound envelope + seal signing (crypto.ecdsa.sign)
* ``verify_ms``    — inbound signature verification (batch verifier calls
                     + per-message backend predicates)
* ``hash_ms``      — proposal-hash recomputations (backend keccak)
* ``window_ms``    — time messages sat buffered in BatchingIngress before
                     their flush (the ingress window's latency cost)
* ``engine_ms``    — everything else on the wall clock: state machine,
                     store, signaling, event loop

Components are measured independently (sign/verify/hash nest inside the
height wall time; window overlaps the engine's awaits), so they are a
budget, not a partition.  Usage::

    python scripts/profile_hotpath.py [--validators 4] [--heights 7]

Prints one JSON object per run.  No device work: the 4-validator shape
routes to the native host path (the point of the adaptive cutover); pass
``--validators 100`` on a live backend to profile the device route, where
``verify_ms`` covers packing + dispatch + readback (see
``utils.metrics`` device observations printed alongside).
"""

import argparse
import asyncio
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


class Tally:
    """Named stopwatch accumulators."""

    def __init__(self) -> None:
        self.totals: dict = {}
        self.counts: dict = {}

    def add(self, key: str, seconds: float) -> None:
        self.totals[key] = self.totals.get(key, 0.0) + seconds

    def wrap(self, key: str, fn):
        def timed(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                self.add(key, time.perf_counter() - t0)
                self.counts[key] = self.counts.get(key, 0) + 1

        return timed

    def ms(self, key: str) -> float:
        return round(self.totals.get(key, 0.0) * 1e3, 3)


def _patch_crypto(tally: Tally) -> None:
    from go_ibft_tpu.crypto import backend as cb

    cb.ec.sign = tally.wrap("sign", cb.ec.sign)


def _patch_verifier(tally: Tally, verifier) -> None:
    verifier.verify_senders = tally.wrap("verify", verifier.verify_senders)
    verifier.verify_committed_seals = tally.wrap(
        "verify", verifier.verify_committed_seals
    )


def _patch_backend(tally: Tally, backend) -> None:
    backend.is_valid_validator = tally.wrap("verify", backend.is_valid_validator)
    backend.is_valid_committed_seal = tally.wrap(
        "verify", backend.is_valid_committed_seal
    )
    backend.is_valid_proposal_hash = tally.wrap(
        "hash", backend.is_valid_proposal_hash
    )


class WindowedIngress:
    """BatchingIngress wrapper measuring buffered wall time per message."""

    def __init__(self, inner, tally: Tally) -> None:
        self._inner = inner
        self._tally = tally
        self._stamps: list = []
        inner_flush = inner.flush

        def flush():
            now = time.perf_counter()
            for t0 in self._stamps:
                tally.add("window", now - t0)
            self._stamps.clear()
            inner_flush()

        inner.flush = flush

    def submit(self, message) -> None:
        self._stamps.append(time.perf_counter())
        self._inner.submit(message)

    def close(self) -> None:
        self._stamps.clear()
        self._inner.close()


def run_profile(n_validators: int, heights: int) -> dict:
    from go_ibft_tpu.core import IBFT, BatchingIngress
    from go_ibft_tpu.crypto import PrivateKey
    from go_ibft_tpu.crypto.backend import ECDSABackend
    from go_ibft_tpu.verify import AdaptiveBatchVerifier

    tally = Tally()
    _patch_crypto(tally)

    class _Null:
        def info(self, *a):
            pass

        debug = error = info

    keys = [PrivateKey.from_seed(b"profile-%d" % i) for i in range(n_validators)]
    powers = {k.address: 1 for k in keys}
    src = ECDSABackend.static_validators(powers)
    nodes: list = []

    def gossip(message):
        for _, ingress in nodes:
            ingress.submit(message)

    class _T:
        def multicast(self, message):
            gossip(message)

    for k in keys:
        backend = ECDSABackend(k, src)
        _patch_backend(tally, backend)
        verifier = AdaptiveBatchVerifier(src)
        _patch_verifier(tally, verifier)  # covers both routes (host + device)
        core = IBFT(_Null(), backend, _T(), batch_verifier=verifier)
        core.set_base_round_timeout(30.0)
        nodes.append(
            (core, WindowedIngress(BatchingIngress(core.add_messages), tally))
        )

    async def run() -> list:
        # Untimed warmup height: process-wide first-use costs (native-lib
        # registration, codec caches) land here, not in the profile.
        await asyncio.wait_for(
            asyncio.gather(*(core.run_sequence(1) for core, _ in nodes)), 60
        )
        tally.totals.clear()
        tally.counts.clear()
        per_height = []
        for h in range(2, heights + 2):
            t0 = time.perf_counter()
            await asyncio.wait_for(
                asyncio.gather(*(core.run_sequence(h) for core, _ in nodes)), 60
            )
            per_height.append((time.perf_counter() - t0) * 1e3)
        return per_height

    try:
        per_height = asyncio.run(run())
    finally:
        for core, ingress in nodes:
            ingress.close()
            core.messages.close()

    total_ms = sum(per_height)
    components = {
        "sign_ms": tally.ms("sign"),
        "verify_ms": tally.ms("verify"),
        "hash_ms": tally.ms("hash"),
        "window_ms": tally.ms("window"),
    }
    attributed = sum(components.values())
    return {
        "metric": "hotpath_profile",
        "validators": n_validators,
        "heights": heights,
        "height_p50_ms": round(statistics.median(per_height), 3),
        "total_ms": round(total_ms, 3),
        **components,
        "calls": dict(tally.counts),
        "engine_ms": round(max(total_ms - attributed, 0.0), 3),
        "note": (
            "components nest/overlap the wall clock (window runs under the "
            "engine's awaits) — budget, not partition"
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--validators", type=int, default=4)
    ap.add_argument("--heights", type=int, default=7)
    args = ap.parse_args()
    profile = run_profile(args.validators, args.heights)
    print(json.dumps(profile), flush=True)

    from go_ibft_tpu.utils import metrics

    device = {
        "/".join(k): v
        for k, v in getattr(metrics, "_observations", {}).items()
        if "device" in k
    }
    if device:
        print(json.dumps({"metric": "hotpath_device_observations", **device}))


if __name__ == "__main__":
    main()
