"""Many chains on one verifier (ISSUE 54): the scheduler held to the plain
reference on seeded keys, at a size XLA:CPU runs in seconds.

Four chains of 4, 4, 6 and 10 validators, 8 blocks each (192 lanes a storm),
every fourth block with a bad seal, one of them a CROSS-CHAIN seal on the
4- and 10-validator chains: a valid signature of another tenant's validator
over this chain's block.  ``benchmark/lib/multichain_reference.py`` judges
each chain alone, seal by seal; ``benchmark/lib/multichain.py`` keeps the
labels.  The device route runs the real 8-lane recover program (as
``tests/test_sched.py::test_device_route_parity_small`` does); where a test
is about SHAPES it runs a stub that records what it is launched with.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import multichain_reference as reference  # noqa: E402
from benchmark.lib.committee import corrupt_block, seal_lanes  # noqa: E402
from benchmark.lib.multichain import Chains  # noqa: E402

from go_ibft_tpu.chain.sync import LoopbackSyncNetwork, SyncClient, SyncError  # noqa: E402
from go_ibft_tpu.messages.helpers import CommittedSeal  # noqa: E402
from go_ibft_tpu.obs import ledger as cost_ledger  # noqa: E402
from go_ibft_tpu.obs import trace  # noqa: E402
from go_ibft_tpu.sched import CoalescedDispatcher, TenantScheduler, dispatch, scheduler  # noqa: E402
from go_ibft_tpu.utils import metrics  # noqa: E402
from go_ibft_tpu.verify.batch import _BATCH_BUCKETS, _TABLE_BUCKETS, _bucket  # noqa: E402

CONFIG = {
    "chains": [
        {"validators": 4, "count": 2},
        {"validators": 6, "count": 1},
        {"validators": 10, "count": 1},
    ],
    "assumed": {"seals_per_block": {"4": 4, "6": 6, "10": 10}},
}
SPEC = {
    "corrupt_every": 4,
    "corrupt_seals": {"4": 1, "6": 1, "10": 2},
    "cross_chain_seals": {"4": 1, "10": 1},
}
BLOCKS, STRIDE, SEED = 8, 1000, 2147483659
PEER = b"peer"


@pytest.fixture(scope="module")
def storm():
    """The chains, a range of each and its labels."""
    chains = Chains(CONFIG, SEED)
    firsts = [chains.first_height(k, 0, BLOCKS, STRIDE) for k in range(len(chains))]
    made = [chains.storm_blocks(k, first, BLOCKS, SPEC) for k, first in enumerate(firsts)]
    ranges = [blocks for blocks, _want in made]
    want = [np.concatenate(masks) for _blocks, masks in made]
    return chains, firsts, ranges, want


def _together(jobs):
    """Each job on a thread of its own, all released at once; what each
    returned, or the exception it raised."""
    out = [None] * len(jobs)
    gate = threading.Barrier(len(jobs))

    def work(i):
        gate.wait()
        try:
            out[i] = jobs[i]()
        except Exception as err:  # noqa: BLE001 - the test looks at it
            out[i] = err

    threads = [threading.Thread(target=work, args=(i,)) for i in range(len(jobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return out


def _registered(sched, chains, caps=None):
    """A tenant a chain; ``caps``: a queue cap of its own for chain ``k``."""
    return [
        sched.register(f"chain-{k}", chain.src, max_queue_lanes=(caps or {}).get(k))
        for k, chain in enumerate(chains.chains)
    ]


def _shed_lanes(sched) -> int:
    return sum(t["shed_lanes"] for t in sched.stats()["tenants"].values())


def test_the_generator_plants_what_the_reference_refuses(storm):
    chains, _firsts, ranges, want = storm
    assert chains.sizes == [4, 4, 6, 10] and [chains.partner(k) for k in range(4)] == [1, 0, 3, 0]
    assert [len(w) for w in want] == [32, 32, 48, 80]
    assert [int((~w).sum()) for w in want] == [2, 2, 2, 4]
    crossed = 0
    for k, chain in enumerate(chains.chains):
        got = np.asarray(reference.lane_mask(ranges[k], chain.src), dtype=bool)
        np.testing.assert_array_equal(got, want[k])
        assert reference.first_refused(ranges[k], chain.src) is None  # quorum kept
        home = chains.chains[chains.partner(k)]
        for proposal_hash, seal in seal_lanes(ranges[k]):
            if seal.signer not in chain.powers:
                crossed += 1
                # A valid signature by a member of ANOTHER chain: accepted
                # at home, an unknown signer here.
                assert reference.seal_valid(proposal_hash, seal, home.powers)
                assert not reference.seal_valid(proposal_hash, seal, chain.powers)
    assert crossed == 2 + 2 + 0 + 2


@pytest.mark.parametrize("route", ["host", "device"])
def test_every_tenants_mask_equals_the_reference_and_the_labels(storm, route):
    """All four tenants at once through one scheduler, booted for them: each
    mask lane for lane; the planted cross-chain seals refused; nothing shed,
    no flush fault; and on the device route no compile after the boot, no
    launch at a shape the boot did not load."""
    chains, firsts, ranges, want = storm
    # An 8-lane dispatch cap: the one recover program XLA:CPU has to build.
    sched = TenantScheduler(window_s=0.005, route=route, max_dispatch_lanes=8)
    handles = _registered(sched, chains)
    counters = (dispatch.UNPINNED_LAUNCHES_KEY, dispatch.TABLE_CUTS_KEY)
    before = [metrics.get_counter(k) for k in counters]
    cost_ledger.enable()
    trace.enable()
    try:
        with sched:
            boot = sched.warmup_tenants(dict(zip((f"chain-{k}" for k in range(4)), firsts)))
            compiles = cost_ledger.totals()["compiles"]
            got = _together(
                [
                    lambda k=k: handles[k].verify_seal_lanes(seal_lanes(ranges[k]), firsts[k])
                    for k in range(4)
                ]
            )
        launched = [r[5] for r in trace.recorder().snapshot() if r[1] == "verify.dispatch"]
        assert cost_ledger.totals()["compiles"] == compiles
    finally:
        trace.disable()
        cost_ledger.disable()
    # The host route loads nothing; the device route the one shape.
    assert boot["shapes"] == ([[8, 8]] if route == "device" else [])
    for k, chain in enumerate(chains.chains):
        np.testing.assert_array_equal(got[k], want[k])
        np.testing.assert_array_equal(
            got[k], np.asarray(reference.lane_mask(ranges[k], chain.src), dtype=bool)
        )
        for j, (_hash, seal) in enumerate(seal_lanes(ranges[k])):
            if seal.signer not in chain.powers:
                assert not got[k][j]
    stats = sched.stats()
    assert stats["flush_faults"] == 0 and _shed_lanes(sched) == 0
    assert [metrics.get_counter(k) for k in counters] == before
    if route == "device":
        assert set(stats["served"]) == {"device/8"} and launched
        assert {a["table_rows"] for a in launched} == {8}
        assert stats["coalesce_ratio"] >= 1.0
    else:
        assert set(stats["served"]) == {"host"} and not launched


@pytest.mark.parametrize("route", ["host", "device"])
def test_a_cross_chain_seal_is_refused_here_and_accepted_at_home_in_one_flush(storm, route):
    """Chain 0's corrupted block (its first seal signed by a validator of
    chain 1 over chain 0's hash) and a block of chain 1 (every validator's
    own seal) in ONE 8-lane flush: one table holds both chains' addresses,
    and only chain 0's own membership refuses the planted lane."""
    chains, firsts, ranges, want = storm
    here = next(b for b in ranges[0] if b.height % SPEC["corrupt_every"] == 0)
    planted = here.seals[0]
    assert planted.signer in chains.chains[1].powers
    home = ranges[1][0]
    assert planted.signer in {s.signer for s in home.seals}
    # Two tenants, both queued before the window can end: one flush.
    sched = TenantScheduler(window_s=0.5, route=route, max_dispatch_lanes=8)
    a = sched.register("chain-0", chains.chains[0].src)
    b = sched.register("chain-1", chains.chains[1].src)
    with sched:
        sched.warmup_tenants({"chain-0": here.height, "chain-1": home.height})
        got_here, got_home = _together(
            [
                lambda: a.verify_seal_lanes(seal_lanes([here]), here.height),
                lambda: b.verify_seal_lanes(seal_lanes([home]), home.height),
            ]
        )
    stats = sched.stats()
    assert (stats["dispatches"], stats["coalesced_requests"], stats["coalesced_lanes"]) == (1, 2, 8)
    assert list(got_here) == [False, True, True, True]
    assert got_home.all()
    assert [s.signer for s in home.seals].count(planted.signer) == 1


def test_a_block_cut_to_quorum_less_one_fails_that_tenants_catch_up_alone(storm):
    chains, firsts, ranges, _want = storm
    sched = TenantScheduler(window_s=0.002, route="host")
    handles = _registered(sched, chains)
    clients = []
    for k, chain in enumerate(chains.chains):
        blocks = list(ranges[k])
        if k == 3:  # the 10-validator chain: quorum 7, a clean block cut to 6
            at = next(i for i, b in enumerate(blocks) if b.height % SPEC["corrupt_every"])
            blocks[at] = corrupt_block(blocks[at], chain.quorum - 1)
            cut = blocks[at].height
            assert reference.first_refused(blocks, chain.src) == cut
        network = LoopbackSyncNetwork()
        network.register(PEER, _Source(blocks))
        clients.append(SyncClient(chain.node, network, handles[k], chain.src))
    with sched:
        got = _together(
            [
                lambda k=k: clients[k].catch_up(firsts[k], firsts[k] + BLOCKS - 1)
                for k in range(4)
            ]
        )
    assert isinstance(got[3], SyncError) and f"height {cut}" in str(got[3])
    for k in range(3):
        assert [b.height for b in got[k]] == list(range(firsts[k], firsts[k] + BLOCKS))
    assert sched.stats()["flush_faults"] == 0 and _shed_lanes(sched) == 0


def test_a_storm_with_a_backlog_behind_every_flush_is_launched_ahead_and_gives_the_same_verdicts(
    storm,
):
    """The look-ahead's own storm (ISSUE 55): every tenant's requests are
    queued before the first launch (the pause ``reconfigure`` takes), so each
    8-lane flush of the real recover program has the next one due behind it
    and the loop keeps two in flight.  Lane for lane the labels' and the
    reference's masks, the planted cross-chain seals refused; then the same
    ranges through ``catch_up`` with ONE block of the 10-validator chain cut
    to quorum - 1: that tenant's call alone raises."""
    chains, firsts, ranges, want = storm
    sched = TenantScheduler(window_s=0.002, route="device", max_dispatch_lanes=8)
    handles = _registered(sched, chains)
    requests = sum(len(w) // 8 for w in want)

    def backlog(jobs, queued: int):
        """``jobs`` at once, none flushed until ``queued`` requests wait."""
        with sched._cv:
            sched._paused = True
        out = []
        runner = threading.Thread(target=lambda: out.extend(_together(jobs)))
        runner.start()
        deadline = time.monotonic() + 60
        while sched._pending_reqs < queued and time.monotonic() < deadline:
            time.sleep(0.002)
        with sched._cv:
            waiting = sched._pending_reqs
            sched._paused = False
            sched._cv.notify_all()
        runner.join(timeout=120)
        assert not runner.is_alive() and waiting == queued
        return out

    clients = []
    for k, chain in enumerate(chains.chains):
        blocks = list(ranges[k])
        if k == 3:
            at = next(i for i, b in enumerate(blocks) if b.height % SPEC["corrupt_every"])
            blocks[at] = corrupt_block(blocks[at], chain.quorum - 1)
            cut = blocks[at].height
        network = LoopbackSyncNetwork()
        network.register(PEER, _Source(blocks))
        clients.append(SyncClient(chain.node, network, handles[k], chain.src))
    with sched:
        sched.warmup_tenants(dict(zip((f"chain-{k}" for k in range(4)), firsts)))
        got = backlog(
            [
                lambda k=k: handles[k].verify_seal_lanes(seal_lanes(ranges[k]), firsts[k])
                for k in range(4)
            ],
            requests,
        )
        masks = sched.stats()
        caught = backlog(
            [
                lambda k=k: clients[k].catch_up(firsts[k], firsts[k] + BLOCKS - 1)
                for k in range(4)
            ],
            requests,
        )
    for k, chain in enumerate(chains.chains):
        np.testing.assert_array_equal(got[k], want[k])
        np.testing.assert_array_equal(
            got[k], np.asarray(reference.lane_mask(ranges[k], chain.src), dtype=bool)
        )
        for j, (_hash, seal) in enumerate(seal_lanes(ranges[k])):
            if seal.signer not in chain.powers:
                assert not got[k][j]
    assert isinstance(caught[3], SyncError) and f"height {cut}" in str(caught[3])
    for k in range(3):
        assert [b.height for b in caught[k]] == list(range(firsts[k], firsts[k] + BLOCKS))
    # A backlog behind every flush but a storm's last: all but the first of
    # a busy period go out ahead of a read-back.
    assert masks["dispatches"] == requests and masks["launched_ahead"] >= requests // 2
    stats = sched.stats()
    assert stats["launched_ahead"] > masks["launched_ahead"]
    assert stats["flush_faults"] == 0 and _shed_lanes(sched) == 0
    assert set(stats["served"]) == {"device/8"}


class _Source:
    def __init__(self, blocks):
        self.blocks = {b.height: b for b in blocks}

    def latest_height(self):
        return max(self.blocks)

    def get_blocks(self, start, end):
        return [self.blocks[h] for h in range(start, end + 1) if h in self.blocks]


@pytest.fixture
def launches(monkeypatch):
    """The recover program replaced by a stub that accepts every live lane
    and records the ``(lanes, table rows)`` it is launched with; ``warmup``
    records what boot loads instead of compiling it."""
    launched, loaded = [], []

    def recover(zw, r, s, v, claimed, table, live):
        launched.append((live.shape[0], table.shape[0]))
        return np.asarray(live, dtype=bool)

    monkeypatch.setattr(dispatch, "RECOVER_KERNEL", recover)
    monkeypatch.setattr(
        CoalescedDispatcher,
        "warmup",
        lambda self, lanes=(8,), table_rows=8: loaded.extend((bb, table_rows) for bb in lanes),
    )
    return launched, loaded


def _storm_through(sched, chains, firsts, ranges, boot: bool):
    """Every tenant's range through ``sched``, one tenant after another and
    then all at once; what the boot said, where asked for."""
    handles = _registered(sched, chains)
    jobs = [
        lambda k=k: handles[k].verify_seal_lanes(seal_lanes(ranges[k]), firsts[k])
        for k in range(4)
    ]
    with sched:
        booted = sched.warmup_tenants(0) if boot else None
        for job in jobs:
            job()
        _together(jobs)
    return booted


def test_after_the_boot_no_shape_is_launched_that_the_boot_did_not_load(storm, launches):
    """A 128-lane dispatch cap: the four chains' 24 validators pin ONE shape,
    128 lanes x 128 rows.  Unpinned, the same traffic meets both table
    buckets and two lane widths (a 4-validator tenant alone: 32 x 8)."""
    chains, firsts, ranges, _want = storm
    launched, loaded = launches
    unpinned = TenantScheduler(window_s=0.002, route="device", max_dispatch_lanes=128)
    _storm_through(unpinned, chains, firsts, ranges, boot=False)
    assert {(32, 8), (128, 8), (128, 128)} <= set(launched) and not loaded
    del launched[:]

    sched = TenantScheduler(window_s=0.002, route="device", max_dispatch_lanes=128)
    before = metrics.get_counter(dispatch.UNPINNED_LAUNCHES_KEY)
    booted = _storm_through(sched, chains, firsts, ranges, boot=True)
    assert loaded == [(128, 128)] and booted["shapes"] == [[128, 128]]
    assert len(launched) >= 5 and set(launched) == set(loaded)
    assert metrics.get_counter(dispatch.UNPINNED_LAUNCHES_KEY) == before


def test_a_flush_whose_signers_outgrow_the_pinned_rows_is_cut_not_compiled_for(storm, launches):
    """Two 4-validator tenants pin 32 lanes x 8 rows; a peer then serves one
    of them 32 seals under 24 addresses of its own making: the launch is cut
    until each piece's signers fit, every piece at the pinned shape, and the
    verdicts are the reference's (no such signer is a member)."""
    chains, firsts, ranges, _want = storm
    launched, loaded = launches
    sched = TenantScheduler(window_s=0.002, route="device", max_dispatch_lanes=32)
    handle = sched.register("chain-0", chains.chains[0].src)
    sched.register("chain-1", chains.chains[1].src)
    lanes = seal_lanes(ranges[0])[:32]
    forged = [
        (h, CommittedSeal(signer=bytes([i + 1]) * 20, signature=s.signature) if i < 24 else s)
        for i, (h, s) in enumerate(lanes)
    ]
    keys = (dispatch.TABLE_CUTS_KEY, dispatch.UNPINNED_LAUNCHES_KEY)
    before = [metrics.get_counter(k) for k in keys]
    with sched:
        sched.warmup_tenants(firsts[0])
        got = handle.verify_seal_lanes(forged, firsts[0])
    assert loaded == [(32, 8)]
    assert len(launched) > 1 and set(launched) == {(32, 8)}
    cuts, unpinned = (metrics.get_counter(k) - b for k, b in zip(keys, before))
    assert cuts >= 1 and unpinned == 0
    # The stub accepts every signature: membership alone decides here (lane
    # 28 is the range's own cross-chain seal).
    powers = chains.chains[0].powers
    assert list(got) == [seal.signer in powers for _h, seal in forged]
    assert list(got) == [False] * 24 + [True] * 4 + [False] + [True] * 3


def _parents_pins(dispatcher, n: int, read_tier: bool):
    """What ``warmup_committee`` pinned before the multi-tenant boot (PR 53's
    ``sched/dispatch.py``), written out."""
    if read_tier:
        rungs = _BATCH_BUCKETS
    else:
        top = _bucket(min(n, _BATCH_BUCKETS[-1]), _BATCH_BUCKETS)
        low = _bucket(min(dispatcher.cutover, top), _BATCH_BUCKETS) if dispatcher.route == "auto" else 0
        rungs = tuple(bb for bb in _BATCH_BUCKETS if low <= bb <= top)
    return rungs, {bb: _bucket(min(bb, n), _TABLE_BUCKETS) for bb in rungs}


@pytest.mark.parametrize("read_tier", [False, True])
@pytest.mark.parametrize("route", ["auto", "device"])
@pytest.mark.parametrize("validators", [4, 24, 100, 300, 1000])
def test_warmup_committee_is_the_one_tenant_boot_and_pins_what_it_pinned(
    launches, validators, route, read_tier
):
    _launched, loaded = launches
    one = CoalescedDispatcher(route=route)
    one.warmup_committee(validators, read_tier=read_tier)
    rungs, tables = _parents_pins(one, validators, read_tier)
    assert (one._rungs, one._rung_tables) == (rungs, tables)
    assert loaded == [(bb, tables[bb]) for bb in rungs]
    same = CoalescedDispatcher(route=route)
    same.warmup_tenants([validators], read_tier=read_tier)
    assert (same._rungs, same._rung_tables) == (one._rungs, one._rung_tables)
    # ... and through the scheduler: one registered tenant boots the same.
    sched = TenantScheduler(dispatcher=CoalescedDispatcher(route=route))
    powers = {bytes([i % 251 + 1, i // 251]) + b"\0" * 18: 1 for i in range(validators)}
    sched.register("only", lambda _h: powers, priority="read" if read_tier else "consensus")
    assert sched.warmup_tenants(1)["widths"] == list(rungs)


@pytest.mark.parametrize(
    "committees, cap, want",
    [
        # Sixteen chains of 316 validators, the default dispatch cap: one
        # executable, 2,048 lanes against the 512-row table.
        ([100, 100, 30, 30] + [6] * 4 + [4] * 8, 2048, {2048: 512}),
        ([4, 4], 2048, {2048: 8}),
        ([4, 4, 6, 10], 128, {128: 128}),
        ([100, 100], 512, {512: 512}),
    ],
)
def test_several_tenants_pin_the_one_width_that_holds_the_dispatch_cap(launches, committees, cap, want):
    _launched, loaded = launches
    d = CoalescedDispatcher(route="auto")
    d.warmup_tenants(committees, max_lanes=cap)
    assert d._rung_tables == want and loaded == list(want.items())
    assert d.describe()["shapes"] == [list(s) for s in want.items()]
    with pytest.raises(ValueError):
        d.warmup_tenants([])


def test_no_lane_is_shed_under_the_cap_and_a_shed_lanes_verdict_is_exact(storm):
    """The 10-validator tenant's 80 lanes under a cap of 128: nothing shed.
    Under a cap of 64 with 32-lane requests, what does not fit goes to the
    handle's host oracle, and every verdict is still the reference's."""
    chains, firsts, ranges, want = storm
    lanes = seal_lanes(ranges[3])
    for cap, shed in ((128, False), (64, True)):
        sched = TenantScheduler(window_s=0.05, route="host", max_dispatch_lanes=32)
        handles = _registered(sched, chains, caps={3: cap})
        before = metrics.get_counter(scheduler.SHED_LANES_KEY)
        with sched:
            got = handles[3].verify_seal_lanes(lanes, firsts[3])
        np.testing.assert_array_equal(got, want[3])
        counted = metrics.get_counter(scheduler.SHED_LANES_KEY) - before
        assert counted == _shed_lanes(sched)
        assert (counted > 0) is shed and counted in (0, 16)


def test_what_served_the_flushes_is_on_counters_a_scrape_reads(storm):
    """``served()`` (what ``/statusz`` shows) and the counters say the same;
    the lanes a flush answered are counted by the tenant's class."""
    chains, firsts, ranges, _want = storm
    sched = TenantScheduler(window_s=0.002, route="host")
    handles = _registered(sched, chains)
    served = metrics.get_counter(dispatch.SERVED_KEY + ("host",))
    by_class = {
        n: metrics.get_counter(scheduler.SERVED_LANES_KEY + (f"{n}v",)) for n in (4, 6, 10)
    }
    with sched:
        for k in range(4):
            handles[k].verify_seal_lanes(seal_lanes(ranges[k]), firsts[k])
    stats = sched.stats()
    assert metrics.get_counter(dispatch.SERVED_KEY + ("host",)) - served == stats["served"]["host"] == 4
    for n, lanes in ((4, 64), (6, 48), (10, 80)):
        assert metrics.get_counter(scheduler.SERVED_LANES_KEY + (f"{n}v",)) - by_class[n] == lanes
    assert [stats["tenants"][f"chain-{k}"]["committee"] for k in range(4)] == [4, 4, 6, 10]
