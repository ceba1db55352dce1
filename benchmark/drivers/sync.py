"""Driver ``sync``: a validator that is behind catches up.

The system under test is ``SyncClient.catch_up`` (the call
``ChainRunner._catch_up`` makes) over a default-constructed
``AdaptiveBatchVerifier``: a harness ``SyncSource`` on a
``LoopbackSyncNetwork`` serves pre-signed ``FinalizedBlock``s, and the
driver calls ``catch_up(start, target)`` in a closed loop, one call after
another, cycling over a few distinct pre-signed ranges.  A call is timed
around ``catch_up``, whose masks are on the host when it returns.
"""

from __future__ import annotations

import gc
import pickle
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib.committee import Committee, corrupt_block, mangle, seal_lanes
from benchmark.lib.harness import Probe
from benchmark.lib.signing import SigningPool

PEER = b"benchmark-sync-peer"


class _Source:
    """The peer: serves pre-signed blocks by height."""

    def __init__(self, st) -> None:
        self.st = st
        self.blocks = {}

    def add(self, blocks) -> None:
        for b in blocks:
            self.blocks[b.height] = b

    def latest_height(self) -> int:
        return max(self.blocks, default=0)

    def get_blocks(self, start: int, end: int):
        got = [self.blocks[h] for h in range(start, end + 1) if h in self.blocks]
        self.st.probe.phase("catch_up")
        return got


def window_corrupt_seals(traffic: dict, seals_per_block: int, quorum: int) -> int:
    """Mangled seals in a corrupted block of the window: the mix's number
    where that leaves quorum, else none."""
    n = traffic["corrupt_seals"]
    return n if seals_per_block - n >= quorum else 0


def setup(config: dict, traffic: dict, seed: int, seconds: float):
    from go_ibft_tpu.chain.sync import LoopbackSyncNetwork, SyncClient
    from go_ibft_tpu.utils import calibration
    from go_ibft_tpu.utils.jaxcache import enable_persistent_cache
    from go_ibft_tpu.verify import AdaptiveBatchVerifier

    n = config["validators"]
    spb = config["assumed"]["seals_per_block"]
    st = SimpleNamespace()
    st.config, st.traffic, st.seed = config, traffic, seed
    st.probe = Probe()
    st.committee = Committee(n, seed)  # builds the native library once
    st.blocks_per_call = traffic["lanes_per_call"] // spb
    st.check_blocks = max(1, traffic["check_lanes"] // spb)
    st.spb = spb
    spec = {
        "seals_per_block": spb,
        "corrupt_every": traffic["corrupt_every"],
        "corrupt_seals": window_corrupt_seals(traffic, spb, st.committee.quorum),
    }
    check_spec = dict(
        spec, corrupt_seals=min(traffic["corrupt_seals"], spb - st.committee.quorum)
    )
    st.pool = SigningPool()
    st.starts = []
    for k in range(traffic["ranges"]):
        first = 1 + k * st.blocks_per_call
        st.starts.append(first)
        st.pool.submit_sync(n, seed, first, st.blocks_per_call, spec)
    st.n_window_jobs = st.pool.submitted
    st.check_start = 1 + traffic["ranges"] * st.blocks_per_call
    st.pool.submit_sync(n, seed, st.check_start, st.check_blocks, check_spec)

    st.cache_dir = enable_persistent_cache()
    st.verifier = AdaptiveBatchVerifier(st.committee.src)
    st.source = _Source(st)
    network = LoopbackSyncNetwork()
    network.register(PEER, st.source)
    st.client = SyncClient(
        st.committee.node, network, st.verifier, st.committee.src
    )
    st.info = {
        "cutover": st.verifier.cutover,
        "cutover_source": (
            "default"
            if calibration.measured_cutover() is None
            else "calibration.json"
        ),
        "blocks_per_call": st.blocks_per_call,
        "seals_per_block": spb,
        "lanes_per_call": st.blocks_per_call * spb,
        "corrupt_seals_in_window_blocks": spec["corrupt_seals"],
        "signing_workers": st.pool.workers,
        "cache_dir": st.cache_dir,
    }
    return st


def _call(st, k: int) -> float:
    """One ``catch_up`` over range ``k``; its wall seconds."""
    first = st.starts[k]
    st.probe.phase("fetch")
    t0 = time.perf_counter()
    blocks = st.client.catch_up(first, first + st.blocks_per_call - 1)
    t1 = time.perf_counter()
    st.probe.phase("between_calls")
    if len(blocks) != st.blocks_per_call:
        raise RuntimeError(f"catch_up returned {len(blocks)} blocks")
    st.spans.append((t0, t1))
    return t1 - t0


def _one_dispatch(st) -> None:
    """One dispatch of the window's shape through the verifier's public
    method, on the first ``check_lanes`` signed lanes (a full chunk): what
    loads the shape in ``warm``, and a traced run's lead-in (a chunk, not a
    whole call)."""
    first, full = st.starts[0], st.traffic["check_lanes"]
    blocks = [st.source.blocks[h] for h in range(first, first - (-full // st.spb))]
    st.verifier.verify_seal_lanes(seal_lanes(blocks)[:full], first)


def warm(st) -> None:
    """Load the cell's shape through the verifier's public method on the
    first signed lanes; then an unmeasured ``catch_up``."""
    jobs, native = st.pool.collect()
    st.pool.close()
    st.info["native_signer"] = bool(native and st.committee.native)
    st.check_range, st.check_want = [], []
    for i, blob in enumerate(jobs):
        blocks, want = pickle.loads(blob)
        if i < st.n_window_jobs:
            st.source.add(blocks)
        else:
            st.check_range.extend(blocks)
            st.check_want.extend(want)
    _one_dispatch(st)
    st.spans = []
    st.info["warm_calls_s"] = [
        round(_call(st, 0), 4) for _ in range(st.traffic["warm_calls"])
    ]
    gc.collect()
    gc.freeze()


def measure(st, seconds: float):
    samples, lanes = [], 0
    st.spans = []
    attempted = failed = 0
    st.probe.phase("between_calls")
    start = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - start
        st.probe.boundary(elapsed)
        if elapsed >= seconds and not st.probe.wants_more():
            break
        if st.probe.leading():
            _one_dispatch(st)
            continue
        attempted += 1
        try:
            samples.append(_call(st, (attempted - 1) % len(st.starts)))
            lanes += st.blocks_per_call * st.spb
        except Exception as err:  # noqa: BLE001 - a failed call is counted
            failed += 1
            st.probe.phase("between_calls")
            st.info.setdefault("call_errors", []).append(repr(err)[:200])
    elapsed = time.perf_counter() - start
    st.probe.phase(None)
    counts = {
        "attempted": attempted,
        "failed": failed,
        "operations": len(samples),
        "elapsed_s": elapsed,
        "live_lanes": lanes,
        "op_spans": list(st.spans),
    }
    return samples, counts


def check(st) -> bool:
    """The verifier's mask of one range against the labels and against the
    sequential host oracle, lane for lane; and a range with a block one
    seal short of quorum must be refused."""
    from go_ibft_tpu.chain.sync import SyncError
    from go_ibft_tpu.chain.wal import FinalizedBlock
    from go_ibft_tpu.messages.helpers import CommittedSeal
    from go_ibft_tpu.verify import HostBatchVerifier

    c = st.committee
    lanes = seal_lanes(st.check_range)
    height = st.check_range[-1].height
    got = np.asarray(st.verifier.verify_seal_lanes(lanes, height), dtype=bool)
    want = np.concatenate(st.check_want)
    oracle = np.asarray(
        HostBatchVerifier(c.src).verify_seal_lanes(lanes, height), dtype=bool
    )
    st.info["check_lanes"] = len(lanes)
    st.info["check_invalid_lanes"] = int((~want).sum())
    compared = st.info["compared"] = {
        "mask_lanes_off_the_labels": {"value": int((got != want).sum()), "limit": 0},
        "oracle_lanes_off_the_labels": {"value": int((oracle != want).sum()), "limit": 0},
        "sub_quorum_ranges_accepted": {"value": 1, "limit": 0},
    }
    if not (got == want).all() or not (oracle == want).all():
        st.info["guarantee_broken"] = (
            f"mask mismatch: verifier {compared['mask_lanes_off_the_labels']['value']} lanes, "
            f"host oracle {compared['oracle_lanes_off_the_labels']['value']} lanes"
        )
        return False

    # The same range once more, clean (mangling twice restores a seal) but
    # for one block cut to quorum - 1.
    clean = [
        FinalizedBlock(
            height=b.height,
            proposal=b.proposal,
            seals=[
                s if ok else CommittedSeal(signer=s.signer, signature=mangle(s.signature))
                for s, ok in zip(b.seals, mask)
            ],
        )
        for b, mask in zip(st.check_range, st.check_want)
    ]
    mid = len(clean) // 2
    clean[mid] = corrupt_block(clean[mid], c.quorum - 1)
    st.source.add(clean)
    try:
        st.client.catch_up(st.check_start, st.check_start + len(clean) - 1)
    except SyncError as err:
        st.info["sub_quorum_refused"] = str(err)[:120]
        compared["sub_quorum_ranges_accepted"]["value"] = 0
        return True
    st.info["guarantee_broken"] = "a block with quorum - 1 valid seals was accepted"
    return False
