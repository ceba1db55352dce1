"""Driver ``sync_epochs``: a node of a proof-of-stake chain catches up across
epoch boundaries.

The system under test is ``SyncClient.catch_up`` over a default-constructed
``AdaptiveBatchVerifier``, as in driver ``sync``; what differs is the chain.
Its validator set is the largest stakes of a pool and changes at every
epoch's end, a validator's voting power is its stake in wei
(``benchmark/lib/rotating_committee.py``), and every range the driver asks
for crosses a dozen sets: each lane has to be judged at its own height's
set, each block tallied against its own height's powers and quorum.  The
driver calls ``catch_up(start, target)`` in a closed loop, one call after
another, cycling over a few pre-signed ranges that are signed AND decoded
before the window (a call allocates what ``catch_up`` allocates, nothing
else).  A call is timed around ``catch_up``.

The labels and the oracle are the driver's own: the generator's masks, the
plain reference ``benchmark/lib/pos_reference.py`` (seal by seal on the
host, nothing batched or cached) and ``HostBatchVerifier`` called block by
block at each block's own height.
"""

from __future__ import annotations

import gc
import pickle
import time
from types import SimpleNamespace

import numpy as np

from benchmark.drivers.sync import PEER, _call
from benchmark.lib import pos_reference
from benchmark.lib.committee import seal_lanes
from benchmark.lib.harness import Probe
from benchmark.lib.rotating_committee import PosSigningPool, RotatingCommittee


class _Source:
    """The peer: serves whole pre-signed ranges, decoded once."""

    def __init__(self, st) -> None:
        self.st = st
        self.ranges = {}

    def add(self, blocks) -> None:
        self.ranges[(blocks[0].height, blocks[-1].height)] = list(blocks)

    def latest_height(self) -> int:
        return max((last for _first, last in self.ranges), default=0)

    def get_blocks(self, start: int, end: int):
        got = self.ranges[(start, end)]
        self.st.probe.phase("catch_up")
        return got


# The program's counters the cell reads, by the name of their key in
# ``go_ibft_tpu/verify/batch.py``.
COUNTERS = {
    "table_uploads": "TABLE_UPLOADS_KEY",
    "out_of_set_lanes": "SYNC_OUT_OF_SET_LANES_KEY",
}


def _counters() -> dict:
    """Each counter's value now; ``None`` where the program has no such
    counter (the parent of the PR that brought it), and the metrics that read
    it are then left out of the line."""
    from go_ibft_tpu.utils import metrics
    from go_ibft_tpu.verify import batch

    return {
        name: metrics.get_counter(getattr(batch, key)) if hasattr(batch, key) else None
        for name, key in COUNTERS.items()
    }


def lane_heights(blocks) -> list:
    return [b.height for b in blocks for _ in b.seals]


def _takes_a_height_a_lane() -> bool:
    """Whether the program's ``verify_seal_lanes`` takes a height a lane (the
    parent of the PR that brought this cell takes one height a call)."""
    from go_ibft_tpu.verify import batch

    return hasattr(batch, "LaneHeights")


def range_mask(verifier, blocks) -> np.ndarray:
    """The verifier's mask of a range, every lane at its own height: one
    call where the program takes a height a lane, else a call a block."""
    if _takes_a_height_a_lane():
        got = verifier.verify_seal_lanes(seal_lanes(blocks), lane_heights(blocks))
        return np.asarray(got, dtype=bool)
    return np.concatenate(
        [
            np.asarray(verifier.verify_seal_lanes(seal_lanes([b]), b.height), dtype=bool)
            for b in blocks
        ]
    )


def setup(config: dict, traffic: dict, seed: int, seconds: float):
    from go_ibft_tpu.chain.sync import LoopbackSyncNetwork, SyncClient
    from go_ibft_tpu.utils import calibration
    from go_ibft_tpu.utils.jaxcache import enable_persistent_cache
    from go_ibft_tpu.verify import AdaptiveBatchVerifier

    st = SimpleNamespace()
    st.config, st.traffic, st.seed = config, traffic, seed
    st.probe = Probe()
    st.committee = RotatingCommittee(config, seed)  # builds the native library once
    st.spb = st.committee.spb
    st.blocks_per_call = traffic["lanes_per_call"] // st.spb
    st.starts = list(traffic["range_starts"])
    spec = {
        "corrupt_every": traffic["corrupt_every"],
        "corrupt_mangled": traffic["corrupt_mangled"],
        "corrupt_out_of_set": traffic["corrupt_out_of_set"],
    }
    st.pool = PosSigningPool()
    st.jobs_per_range = []
    for first in st.starts:
        before = st.pool.submitted
        st.pool.submit_range(config, seed, first, st.blocks_per_call, spec)
        st.jobs_per_range.append(st.pool.submitted - before)

    st.cache_dir = enable_persistent_cache()
    st.verifier = AdaptiveBatchVerifier(st.committee.src)
    st.source = _Source(st)
    network = LoopbackSyncNetwork()
    network.register(PEER, st.source)
    st.client = SyncClient(st.committee.node, network, st.verifier, st.committee.src)
    st.info = {
        "cutover": st.verifier.cutover,
        "cutover_source": (
            "default"
            if calibration.measured_cutover() is None
            else "calibration.json"
        ),
        "blocks_per_call": st.blocks_per_call,
        "seals_per_block": st.spb,
        "lanes_per_call": st.blocks_per_call * st.spb,
        "epoch_blocks": st.committee.epoch_blocks,
        "signing_workers": st.pool.workers,
        "cache_dir": st.cache_dir,
    }
    return st


def _one_dispatch(st) -> None:
    """One dispatch of the window's shape through the verifier's public
    method: a full chunk of range 0's lanes, taken from every fifth block so
    that it crosses every set of the range and rides the range's own device
    table.  What loads the shape in ``warm``, and a traced run's lead-in (a
    chunk, not a whole call)."""
    full = st.traffic["check_lanes"]
    every = max(1, st.blocks_per_call * st.spb // full)
    blocks = st.ranges[0][::every]
    lanes, heights = seal_lanes(blocks)[:full], lane_heights(blocks)[:full]
    st.verifier.verify_seal_lanes(
        lanes, heights if _takes_a_height_a_lane() else heights[0]
    )


def warm(st) -> None:
    """Decode the signed ranges, load the cell's shape on a chunk of the
    first, then one unmeasured ``catch_up`` a range (each range's device
    table is uploaded here, once)."""
    jobs, native = st.pool.collect()
    st.pool.close()
    st.info["native_signer"] = bool(native and st.committee.native)
    st.ranges, st.want, st.outside = [], [], []
    at = 0
    for n_jobs in st.jobs_per_range:
        blocks, want, outside = [], [], []
        for blob in jobs[at : at + n_jobs]:
            b, w, o = pickle.loads(blob)
            blocks.extend(b)
            want.extend(w)
            outside.extend(o)
        at += n_jobs
        st.source.add(blocks)
        st.ranges.append(blocks)
        st.want.append(np.concatenate(want))
        st.outside.append(int(sum(outside)))
    st.info["sets_per_range"] = [
        len({st.committee.members(e) for e in st.committee.epochs_of((r[0].height, r[-1].height))})
        for r in st.ranges
    ]
    st.info["out_of_set_lanes_per_range"] = list(st.outside)
    st.info["largest_power_share_epoch_0"] = round(
        max(st.committee.powers(0).values()) / sum(st.committee.powers(0).values()), 4
    )
    _one_dispatch(st)
    st.spans = []
    st.info["warm_calls_s"] = [
        round(_call(st, k), 4)
        for k in range(len(st.starts))
        for _ in range(st.traffic["warm_calls_per_range"])
    ]
    gc.collect()
    gc.freeze()


def measure(st, seconds: float):
    samples, lanes, outside = [], 0, 0
    st.spans = []
    attempted = failed = 0
    before, lead_in = _counters(), {}
    st.probe.phase("between_calls")
    start = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - start
        st.probe.boundary(elapsed)
        if elapsed >= seconds and not st.probe.wants_more():
            break
        if st.probe.leading():
            # The lead-in is no operation: what it counts is taken out.
            was = _counters()
            _one_dispatch(st)
            for name, now in _counters().items():
                if now is not None:
                    lead_in[name] = lead_in.get(name, 0) + now - was[name]
            continue
        attempted += 1
        k = (attempted - 1) % len(st.starts)
        try:
            samples.append(_call(st, k))
            lanes += st.blocks_per_call * st.spb
            outside += st.outside[k]
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
    # What the program counted over the window, where it counts it at all.
    for name, now in _counters().items():
        if now is not None:
            counts[name] = now - before[name] - lead_in.get(name, 0)
    st.outside_in_window = outside
    st.counts = counts
    return samples, counts


def _refused(st, blocks) -> tuple:
    """Whether ``catch_up`` over ``blocks`` (served by a peer of their own)
    raises ``SyncError``, and what it said."""
    from go_ibft_tpu.chain.sync import LoopbackSyncNetwork, SyncClient, SyncError

    source = _Source(st)
    source.add(blocks)
    network = LoopbackSyncNetwork()
    network.register(PEER, source)
    client = SyncClient(st.committee.node, network, st.verifier, st.committee.src)
    try:
        client.catch_up(blocks[0].height, blocks[-1].height)
    except SyncError as err:
        return True, str(err)[:160]
    return False, ""


def check(st) -> bool:
    """The verifier's mask of one range against the labels, the plain
    reference and the sequential host oracle at each block's own height,
    lane for lane; the window's out-of-set counter against the labels; and
    three ranges, each with one block under its height's quorum POWER, that
    must each be refused."""
    from go_ibft_tpu.verify import HostBatchVerifier

    c = st.committee
    blocks, want = st.ranges[0], st.want[0]
    got = range_mask(st.verifier, blocks)
    reference = np.asarray(pos_reference.lane_mask(blocks, c.src), dtype=bool)
    host = HostBatchVerifier(c.src)
    oracle = np.concatenate(
        [
            np.asarray(host.verify_seal_lanes(seal_lanes([b]), b.height), dtype=bool)
            for b in blocks
        ]
    )
    st.info["check_lanes"] = len(want)
    st.info["check_invalid_lanes"] = int((~want).sum())
    compared = st.info["compared"] = {
        "mask_lanes_off_the_labels": {"value": int((got != want).sum()), "limit": 0},
        "reference_lanes_off_the_labels": {"value": int((reference != want).sum()), "limit": 0},
        "oracle_lanes_off_the_labels": {"value": int((oracle != want).sum()), "limit": 0},
    }
    counted = getattr(st, "counts", {}).get("out_of_set_lanes")
    if counted is not None:
        compared["out_of_set_lanes_off_the_labels"] = {
            "value": abs(counted - st.outside_in_window),
            "limit": 0,
        }

    # One clean block of the range (its height is no multiple of
    # corrupt_every), three ways under its height's quorum power.
    every = st.traffic["corrupt_every"]
    at = len(blocks) // 2
    while blocks[at].height % every == 0:
        at += 1
    clean = blocks[at]
    span = (blocks[0].height, blocks[-1].height)
    heavy, heavy_valid = c.cut_by_weight(clean, heaviest=True)
    padded, outsiders = c.padded_with_outsiders(clean, span)
    light, light_valid = c.cut_by_weight(clean, heaviest=False)
    quorum = c.quorum(clean.height)
    st.info["traps"] = {
        "height": clean.height,
        "quorum_power": quorum,
        "clean_power": pos_reference.block_power(clean, c.src)[0],
        "old_count_rule": (2 * len(c.src(clean.height))) // 3 + 1,
    }
    traps = (
        ("heaviest_cut_ranges_accepted", heavy, {"valid_seals": heavy_valid}),
        ("outsider_padded_ranges_accepted", padded, {"seals": len(padded.seals), "outsiders": outsiders}),
        ("lightest_cut_ranges_accepted", light, {"valid_seals": light_valid}),
    )
    for name, block, what in traps:
        power = pos_reference.block_power(block, c.src)[0]
        refused, said = _refused(st, blocks[:at] + [block] + blocks[at + 1 :])
        # A trap that is no trap (the reference would accept it) reads as
        # accepted too: the generator's fault, and as loud.
        compared[name] = {"value": 0 if refused and power < quorum else 1, "limit": 0}
        st.info["traps"][name] = dict(what, power=power, refused=said)
    off = {k: v["value"] for k, v in compared.items() if v["value"] != v["limit"]}
    if off:
        st.info["guarantee_broken"] = f"off their limits: {off}"
        return False
    return True
