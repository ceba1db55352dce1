"""Driver ``sync_storm``: many chains behind one verifier catch up at once.

The system under test is ONE process-wide ``TenantScheduler`` (its own
``CoalescedDispatcher``, built from the configuration's ``sched`` block) with
one tenant a chain: every chain has its own ``SyncClient`` on its own
``TenantVerifierHandle``, its own ``SyncSource`` on a ``LoopbackSyncNetwork``
and its own worker thread, made once in ``setup``.  One operation is one
STORM: a barrier releases the workers, each calls ``catch_up`` over its
chain's range, and the storm's sample is the wall from the release to the
last call's return.  Closed loop, one storm after another, cycling over a few
distinct pre-signed ranges.  The scheduler is booted with its multi-tenant
boot (``TenantScheduler.warmup_tenants``); a program without one (the parent
of the PR that brought it) is refused in ``setup``, before anything compiles.
"""

from __future__ import annotations

import gc
import pickle
import threading
import time
from functools import partial
from types import SimpleNamespace

import numpy as np

from benchmark.lib import multichain_reference as reference
from benchmark.lib.committee import corrupt_block, mangle, seal_lanes
from benchmark.lib.harness import Probe
from benchmark.lib.multichain import Chains, StormSigningPool
from benchmark.lib.stats import median

PEER = b"benchmark-sync-peer"

# The program's counters the cell reads at the window's edges, by the name of
# their key in ``go_ibft_tpu/sched``; a program without one reads ``None``.
COUNTERS = {
    "shed_lanes": ("scheduler", "SHED_LANES_KEY"),
    "flush_faults": ("scheduler", "FLUSH_FAULTS_KEY"),
    "unpinned_launches": ("dispatch", "UNPINNED_LAUNCHES_KEY"),
    "table_cuts": ("dispatch", "TABLE_CUTS_KEY"),
}


def _counters() -> dict:
    from go_ibft_tpu import sched
    from go_ibft_tpu.utils import metrics

    out = {}
    for name, (module, key) in COUNTERS.items():
        key = getattr(getattr(sched, module), key, None)
        out[name] = None if key is None else metrics.get_counter(key)
    return out


class _Source:
    """One chain's peer: serves its pre-signed blocks by height."""

    def __init__(self) -> None:
        self.blocks = {}

    def add(self, blocks) -> None:
        for b in blocks:
            self.blocks[b.height] = b

    def latest_height(self) -> int:
        return max(self.blocks, default=0)

    def get_blocks(self, start: int, end: int):
        return [self.blocks[h] for h in range(start, end + 1) if h in self.blocks]


class _Recording:
    """A tenant's handle as its ``SyncClient`` sees it, keeping the mask of
    the last ``verify_seal_lanes`` call (a reference, no copy), so that a
    storm's own verdicts can be held to the labels after it."""

    def __init__(self, handle) -> None:
        self.handle = handle
        self.last_mask = None

    def verify_seal_lanes(self, lanes, height):
        self.last_mask = self.handle.verify_seal_lanes(lanes, height)
        return self.last_mask


class _Crew:
    """One worker thread a tenant, made once; ``run(jobs)`` releases them
    all at a barrier and returns when the last has ended its job."""

    def __init__(self, n: int) -> None:
        self.start = threading.Barrier(n + 1)
        self.done = threading.Barrier(n + 1)
        self.jobs = [None] * n
        self.out = [None] * n
        self.threads = [
            threading.Thread(target=self._work, args=(i,), name=f"storm-{i}", daemon=True)
            for i in range(n)
        ]
        for t in self.threads:
            t.start()

    def _work(self, i: int) -> None:
        while True:
            self.start.wait()
            job = self.jobs[i]
            if job is None:
                return
            got = err = None
            try:
                got = job()
            except Exception as exc:  # noqa: BLE001 - a failed call is counted
                err = exc
            self.out[i] = (time.perf_counter(), got, err)
            self.done.wait()

    def run(self, jobs) -> tuple:
        """``(release time, [(end time, result, error) a worker])``."""
        self.jobs[:] = jobs
        t0 = time.perf_counter()
        self.start.wait()
        self.done.wait()
        return t0, list(self.out)

    def close(self) -> None:
        self.jobs[:] = [None] * len(self.jobs)
        self.start.wait()
        for t in self.threads:
            t.join()


def setup(config: dict, traffic: dict, seed: int, seconds: float):
    from go_ibft_tpu.chain.sync import LoopbackSyncNetwork, SyncClient
    from go_ibft_tpu.sched import TenantScheduler

    knobs = config["sched"]
    st = SimpleNamespace()
    st.config, st.traffic, st.seed = config, traffic, seed
    st.probe = Probe()
    st.chains = Chains(config, seed)  # builds the native library once
    if len(st.chains) != traffic["tenants"]:
        raise RuntimeError(f"{len(st.chains)} chains for {traffic['tenants']} tenants")
    st.sched = TenantScheduler(
        route=knobs["route"],
        window_s=knobs["window_s"],
        quantum_lanes=knobs["quantum_lanes"],
        max_queue_lanes=knobs["max_queue_lanes"],
        max_dispatch_lanes=knobs["max_dispatch_lanes"],
    )
    if not hasattr(st.sched, "warmup_tenants"):
        # Before the pool, the threads and every compile: the parent of the
        # PR that brought the cell fails here, at once and cleanly.
        raise RuntimeError(
            "this program's TenantScheduler has no multi-tenant boot "
            "(warmup_tenants): the cell cannot run on it"
        )
    st.blocks = traffic["blocks_per_call"]
    st.spec = {k: traffic[k] for k in ("corrupt_every", "corrupt_seals", "cross_chain_seals")}
    st.pool = StormSigningPool()
    st.first = [
        [
            st.chains.first_height(k, r, st.blocks, traffic["height_stride"])
            for k in range(len(st.chains))
        ]
        for r in range(traffic["ranges"] + 1)  # the last is the check's
    ]
    st.jobs_of = [
        [
            st.pool.submit_range(config, seed, k, first, st.blocks, st.spec)
            for k, first in enumerate(firsts)
        ]
        for firsts in st.first
    ]

    st.tenants = [f"chain-{k:02d}-{n}v" for k, n in enumerate(st.chains.sizes)]
    st.handles, st.sources, st.clients = [], [], []
    for tid, chain in zip(st.tenants, st.chains.chains):
        handle = _Recording(st.sched.register(tid, chain.src))
        source = _Source()
        network = LoopbackSyncNetwork()
        network.register(PEER, source)
        st.handles.append(handle)
        st.sources.append(source)
        st.clients.append(SyncClient(chain.node, network, handle, chain.src))
    st.crew = _Crew(len(st.chains))
    st.lanes_per_storm = st.blocks * sum(st.chains.spb)
    st.quiet = [k for k, n in enumerate(st.chains.sizes) if n == min(st.chains.sizes)]
    st.info = {
        "tenants": len(st.chains),
        "committees": st.chains.sizes,
        "blocks_per_call": st.blocks,
        "lanes_per_storm": st.lanes_per_storm,
        "sched": {k: v for k, v in knobs.items() if k != "why"},
        "signing_workers": st.pool.workers,
    }
    return st


def _storm(st, r: int):
    """One storm over range ``r``: its wall seconds, the wall of each
    tenant's call, and what was off (``None``: every call returned its blocks
    and every mask equals the labels)."""
    st.probe.phase("storm")
    t0, out = st.crew.run(st.storm_jobs[r])
    st.probe.phase("between_storms")
    end = max(t for t, _got, _err in out)
    off = None
    for k, (_t, got, err) in enumerate(out):
        if err is not None:
            off = f"{st.tenants[k]}: {err!r}"[:200]
        elif len(got) != st.blocks:
            off = f"{st.tenants[k]}: catch_up returned {len(got)} blocks"
        elif not np.array_equal(st.handles[k].last_mask, st.want[r][k]):
            off = f"{st.tenants[k]}: a mask of the storm is off the labels"
    st.spans.append((t0, end))
    return end - t0, [t - t0 for t, _got, _err in out], off


def _one_dispatch(st) -> None:
    """One full dispatch through the scheduler (the first 100-validator
    tenant's first 2,048 lanes): a traced run's lead-in, counted as nothing."""
    first = st.first[0][0]
    cap = st.config["sched"]["max_dispatch_lanes"]
    lanes = seal_lanes([st.sources[0].blocks[h] for h in range(first, first + st.blocks)])
    st.handles[0].handle.verify_seal_lanes(lanes[:cap], first)


def warm(st) -> None:
    """Collect the signed ranges, start the scheduler, boot it for its
    sixteen tenants (every shape the window may launch), then an unmeasured
    storm."""
    jobs, native = st.pool.collect()
    st.pool.close()
    st.info["native_signer"] = bool(native and st.chains.native)
    at = iter(jobs)
    st.ranges, st.want = [], []
    for r, per_chain in enumerate(st.jobs_of):
        st.ranges.append([])
        st.want.append([])
        for k, n_jobs in enumerate(per_chain):
            blocks, want = [], []
            for _ in range(n_jobs):
                got_blocks, got_want = pickle.loads(next(at))
                blocks.extend(got_blocks)
                want.extend(got_want)
            st.ranges[r].append(blocks)
            st.want[r].append(np.concatenate(want))
            if r < st.traffic["ranges"]:
                st.sources[k].add(blocks)
    st.storm_jobs = [
        [
            partial(client.catch_up, first, first + st.blocks - 1)
            for client, first in zip(st.clients, firsts)
        ]
        for firsts in st.first[: st.traffic["ranges"]]
    ]
    st.sched.start()
    t0 = time.perf_counter()
    st.info["boot"] = st.sched.warmup_tenants(
        {tid: firsts for tid, firsts in zip(st.tenants, st.first[0])}
    )
    st.info["boot_s"] = round(time.perf_counter() - t0, 3)
    st.spans = []
    st.info["warm_storms_s"] = []
    for _ in range(st.traffic["warm_calls"]):
        wall, _calls, off = _storm(st, 0)
        if off is not None:
            raise RuntimeError(f"the warm storm failed: {off}")
        st.info["warm_storms_s"].append(round(wall, 4))
    gc.collect()
    gc.freeze()


def measure(st, seconds: float):
    samples, quiet_ms, lanes = [], [], 0
    st.spans = []
    attempted = failed = 0
    before, lead_in = _counters(), {}
    st.probe.phase("between_storms")
    start = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - start
        st.probe.boundary(elapsed)
        if elapsed >= seconds and not st.probe.wants_more():
            break
        if st.probe.leading():
            was = _counters()
            _one_dispatch(st)
            for name, now in _counters().items():
                if now is not None:
                    lead_in[name] = lead_in.get(name, 0) + now - was[name]
            continue
        attempted += 1
        wall, calls, off = _storm(st, (attempted - 1) % len(st.storm_jobs))
        if off is not None:
            failed += 1
            st.spans.pop()
            st.info.setdefault("storm_errors", []).append(off)
            continue
        samples.append(wall)
        lanes += st.lanes_per_storm
        quiet_ms.append(median([calls[k] for k in st.quiet]) * 1e3)
    elapsed = time.perf_counter() - start
    st.probe.phase(None)
    counts = {
        "attempted": attempted,
        "failed": failed,
        "operations": len(samples),
        "elapsed_s": elapsed,
        "live_lanes": lanes,
        "op_spans": list(st.spans),
        "quiet_tenant_ms": quiet_ms,
    }
    # What the program counted over the window, where it counts it at all.
    for name, now in _counters().items():
        if now is not None:
            counts[name] = now - before[name] - lead_in.get(name, 0)
    st.counts = counts
    return samples, counts


def check(st) -> bool:
    try:
        return _check(st)
    finally:
        st.crew.close()
        st.sched.stop()


def _check(st) -> bool:
    """Outside the window.  A fourth range of every tenant, all sixteen at
    once through their handles' ``verify_seal_lanes``: each mask against the
    labels, the plain reference and the sequential host oracle, lane for
    lane; every planted cross-chain seal refused where it was planted and
    accepted by its signer's own chain.  Then one tenant's range with a block
    cut to quorum - 1 must make ITS ``catch_up`` raise ``SyncError`` while
    every other tenant's concurrent call returns its blocks.  And the
    window's own counters: no shed lane, no flush fault, no launch outside
    the shapes boot loaded."""
    from go_ibft_tpu.chain.sync import SyncError
    from go_ibft_tpu.chain.wal import FinalizedBlock
    from go_ibft_tpu.messages.helpers import CommittedSeal
    from go_ibft_tpu.verify import HostBatchVerifier

    r = st.traffic["ranges"]
    ranges, want = st.ranges[r], st.want[r]
    chains = st.chains.chains

    lanes_of = [seal_lanes(blocks) for blocks in ranges]

    def masked(k: int):
        return np.asarray(
            st.handles[k].handle.verify_seal_lanes(lanes_of[k], st.first[r][k]), dtype=bool
        )

    _t0, out = st.crew.run([partial(masked, k) for k in range(len(chains))])
    off_labels = off_reference = off_oracle = cross = cross_accepted = own_refused = 0
    for k, (_t, got, err) in enumerate(out):
        if err is not None:
            st.info["guarantee_broken"] = f"{st.tenants[k]}: {err!r}"[:200]
            return False
        ref = np.asarray(reference.lane_mask(ranges[k], chains[k].src), dtype=bool)
        oracle = np.asarray(
            HostBatchVerifier(chains[k].src).verify_seal_lanes(lanes_of[k], st.first[r][k]),
            dtype=bool,
        )
        off_labels += int((got != want[k]).sum())
        off_reference += int((ref != want[k]).sum())
        off_oracle += int((oracle != want[k]).sum())
        # The planted cross-chain seals: refused here, and the same seal a
        # valid one for its signer's own chain (by the reference, that chain
        # alone: the signature holds, only the membership differs).
        partner = chains[st.chains.partner(k)]
        for at, (proposal_hash, seal) in enumerate(lanes_of[k]):
            if seal.signer not in chains[k].powers:
                cross += 1
                cross_accepted += int(got[at])
                own_refused += int(not reference.seal_valid(proposal_hash, seal, partner.powers))
    planted = sum(
        len(ranges[k]) // st.traffic["corrupt_every"]
        * min(
            st.traffic["cross_chain_seals"].get(str(n), 0),
            st.traffic["corrupt_seals"].get(str(n), 0),
        )
        for k, n in enumerate(st.chains.sizes)
    )
    st.info["check_lanes"] = int(sum(len(w) for w in want))
    st.info["check_invalid_lanes"] = int(sum((~w).sum() for w in want))
    st.info["check_cross_chain_lanes"] = cross
    compared = st.info["compared"] = {
        "mask_lanes_off_the_labels": {"value": off_labels, "limit": 0},
        "reference_lanes_off_the_labels": {"value": off_reference, "limit": 0},
        "oracle_lanes_off_the_labels": {"value": off_oracle, "limit": 0},
        "cross_chain_seals_not_planted": {"value": abs(planted - cross), "limit": 0},
        "cross_chain_seals_accepted": {"value": cross_accepted, "limit": 0},
        "cross_chain_seals_refused_by_their_own_chain": {"value": own_refused, "limit": 0},
        "sub_quorum_ranges_accepted": {"value": 1, "limit": 0},
        "other_tenants_failed_beside_the_refused_range": {"value": len(chains), "limit": 0},
    }

    # The same ranges once more through catch_up, clean (mangling twice
    # restores a seal; a cross-chain seal stays, and stays refused), the
    # first tenant's with one block cut to quorum - 1.
    for k, source in enumerate(st.sources):
        masks = np.split(want[k], len(ranges[k]))
        clean = [
            FinalizedBlock(
                height=b.height,
                proposal=b.proposal,
                seals=[
                    s
                    if ok or s.signer not in chains[k].powers
                    else CommittedSeal(signer=s.signer, signature=mangle(s.signature))
                    for s, ok in zip(b.seals, mask)
                ],
            )
            for b, mask in zip(ranges[k], masks)
        ]
        if k == 0:
            every = st.traffic["corrupt_every"]  # a block with no bad seal in it
            mid = next(i for i in range(len(clean) // 2, len(clean)) if clean[i].height % every)
            clean[mid] = corrupt_block(clean[mid], chains[k].quorum - 1)
            st.info["sub_quorum_height"] = clean[mid].height
        source.add(clean)
    _t0, out = st.crew.run(
        [
            partial(client.catch_up, first, first + st.blocks - 1)
            for client, first in zip(st.clients, st.first[r])
        ]
    )
    (_t, got, err), others = out[0], out[1:]
    if isinstance(err, SyncError):
        st.info["sub_quorum_refused"] = str(err)[:120]
        compared["sub_quorum_ranges_accepted"]["value"] = 0
    compared["other_tenants_failed_beside_the_refused_range"]["value"] = sum(
        1 for _t, got, err in others if err is not None or len(got) != st.blocks
    ) + (0 if isinstance(err, SyncError) or err is None else 1)

    # The window's own counters, and the scheduler's totals.
    stats = st.sched.stats()
    st.info["sched_stats"] = {
        k: stats[k] for k in ("dispatches", "coalesced_requests", "coalesce_ratio", "served")
    }
    counts = getattr(st, "counts", {})
    for name in ("shed_lanes", "flush_faults", "unpinned_launches", "table_cuts"):
        if counts.get(name) is not None:
            compared[f"window_{name}"] = {"value": counts[name], "limit": 0}
    compared["flush_faults"] = {"value": stats["flush_faults"], "limit": 0}
    compared["shed_lanes"] = {
        "value": sum(t["shed_lanes"] for t in stats["tenants"].values()),
        "limit": 0,
    }
    off = {k: v["value"] for k, v in compared.items() if v["value"] != v["limit"]}
    if off:
        st.info["guarantee_broken"] = f"off their limits: {off}"
        return False
    return True
