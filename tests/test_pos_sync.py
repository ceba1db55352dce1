"""The ``ecdsa-100v-pos`` deployment against the plain reference, small, on
the CPU (ISSUE 45).

A stake-weighted committee that changes at every epoch (16 of a 20-account
pool, epochs of 3 blocks, stakes in wei: ``benchmark/lib/rotating_committee``)
and a node that catches up over a 30-block range through
``SyncClient.catch_up``: on the host route and on the device route (the 8-
and 32-lane recover programs, real kernels) the range is ONE drain, every
lane's verdict is the plain reference's at that lane's own height
(``benchmark/lib/pos_reference``: seal by seal, nothing batched or cached),
and the ranges the reference refuses are refused.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import pos_reference  # noqa: E402
from benchmark.lib.committee import block_bytes, seal_lanes  # noqa: E402
from benchmark.lib.rotating_committee import RotatingCommittee  # noqa: E402

from go_ibft_tpu.chain.sync import (  # noqa: E402
    SYNC_DRAINS_KEY,
    SYNC_REGROUPED_KEY,
    SYNC_STREAMED_KEY,
    LoopbackSyncNetwork,
    SyncClient,
    SyncError,
)
from go_ibft_tpu.chain.wal import FinalizedBlock  # noqa: E402
from go_ibft_tpu.crypto import PrivateKey  # noqa: E402
from go_ibft_tpu.crypto import ecdsa as ec  # noqa: E402
from go_ibft_tpu.crypto.backend import encode_signature, proposal_hash_of  # noqa: E402
from go_ibft_tpu.messages.helpers import CommittedSeal  # noqa: E402
from go_ibft_tpu.messages.wire import Proposal  # noqa: E402
from go_ibft_tpu.obs import trace  # noqa: E402
from go_ibft_tpu.utils import metrics  # noqa: E402
from go_ibft_tpu.verify import (  # noqa: E402
    AdaptiveBatchVerifier,
    DeviceBatchVerifier,
    HostBatchVerifier,
    batch,
)

CONFIG = {
    "validators": 16,
    "epoch_blocks": 3,
    "assumed": {
        "staked_pool": 20,
        "seals_per_block": 14,
        "stake_pareto_shape": 1.2,
        "stake_clip_tokens": [1, 1000000],
        "churn_accounts": 8,
        "churn_factor_permille": [500, 2000],
    },
}
SEED = 3450000101
FIRST, BLOCKS = 2, 30  # not epoch-aligned: partial epochs at both ends
SPAN = (FIRST, FIRST + BLOCKS - 1)


@pytest.fixture(scope="module")
def chain():
    """The committee and one signed range: a bad block every fourth height
    (one mangled seal, one valid seal of an account outside that height's
    set), its labels beside it."""
    c = RotatingCommittee(CONFIG, SEED)
    blocks, want, outside = c.sync_blocks(FIRST, BLOCKS, SPAN, 4, 1, 1)
    return c, blocks, np.concatenate(want), sum(outside)


class _Peer:
    def __init__(self, blocks):
        self.blocks = list(blocks)

    def latest_height(self):
        return self.blocks[-1].height

    def get_blocks(self, start, end):
        return [b for b in self.blocks if start <= b.height <= end]


class _Spy:
    """A verifier that keeps what it was asked and what it answered."""

    def __init__(self, inner):
        self.inner, self.calls = inner, []

    def verify_seal_lanes(self, lanes, height):
        mask = np.asarray(self.inner.verify_seal_lanes(lanes, height), dtype=bool)
        self.calls.append((len(lanes), list(height), mask))
        return mask


class _StreamSpy(_Spy):
    """The same, and it hands the inner verifier's stream on: a call is then
    a whole streamed drain (its lanes as they were pulled, its masks as they
    came back)."""

    def verify_seal_lane_stream(self, blocks, heights, lanes=0):
        pulled, masks = [], []

        def tee():
            for block in blocks:
                pulled.append(block)
                yield block

        def answers(stream):
            for batch in stream:
                masks.extend(batch)
                yield batch
            self.calls.append(
                (
                    sum(len(slab) for slab, _heights in pulled),
                    [h for _slab, slab_heights in pulled for h in slab_heights],
                    np.concatenate(masks),
                )
            )

        stream = self.inner.verify_seal_lane_stream(tee(), heights, lanes)
        return None if stream is None else answers(stream)


def _verifier(route: str, src):
    """``host``: the sequential oracle.  ``device``: the default-constructed
    adaptive verifier (a range this size takes its device ladder), its
    dispatches cut to 32 lanes so that the CPU runs the programs every other
    test loads and the drain is a dozen pipelined chunks."""
    if route == "host":
        return HostBatchVerifier(src)
    verifier = AdaptiveBatchVerifier(src)
    verifier.device._dispatch_cap = 32
    return verifier


def _client(blocks, verifier, src):
    net = LoopbackSyncNetwork()
    net.register(b"peer", _Peer(blocks))
    return SyncClient(b"me", net, verifier, src)


def _spans(records, name):
    return [r[5] for r in records if r[0] == "X" and r[1] == name]


@pytest.mark.parametrize("route", ["host", "device", "stream"])
def test_a_range_across_epochs_is_one_drain_with_the_references_verdicts(chain, route):
    """``host``: the oracle.  ``device``: the adaptive verifier behind a
    wrapper that offers no stream (ONE list call, one tally).  ``stream``:
    the same verifier handing its stream on (PR 53): the same ONE drain of a
    dozen chunks, the lanes made and the blocks tallied inside it, a tally a
    read-back that completed a slab of the client's producer."""
    c, blocks, want, outside = chain
    sets = {c.members(e) for e in c.epochs_of(SPAN)}
    assert len(sets) >= 6 and (~want).sum() >= 2 * (BLOCKS // 4) and outside >= BLOCKS // 4
    reference = np.asarray(pos_reference.lane_mask(blocks, c.src), dtype=bool)
    assert (reference == want).all()
    assert pos_reference.first_refused(blocks, c.src) is None

    streams = route == "stream"
    spy = (_StreamSpy if streams else _Spy)(_verifier("host" if route == "host" else "device", c.src))
    drains = metrics.get_counter(SYNC_DRAINS_KEY)
    regrouped = metrics.get_counter(SYNC_REGROUPED_KEY)
    streamed = metrics.get_counter(SYNC_STREAMED_KEY)
    out_of_set = metrics.get_counter(batch.SYNC_OUT_OF_SET_LANES_KEY)
    rec = trace.enable()
    try:
        got = _client(blocks, spy, c.src).catch_up(*SPAN)
        records = rec.snapshot()
    finally:
        trace.disable()
    assert [b.height for b in got] == list(range(SPAN[0], SPAN[1] + 1))
    # ONE drain: every lane of the range in one call, a height a lane.
    assert metrics.get_counter(SYNC_DRAINS_KEY) - drains == 1
    assert metrics.get_counter(SYNC_REGROUPED_KEY) == regrouped
    assert metrics.get_counter(SYNC_STREAMED_KEY) - streamed == int(streams)
    ((lanes, heights, mask),) = spy.calls
    assert lanes == len(want) == BLOCKS * c.spb
    assert {c.members(c.epoch_of(h)) for h in heights} == sets
    assert len(set(heights)) == len(sets)  # a height stands for its set
    assert (mask == reference).all()
    (verify,) = _spans(records, "chain.sync.verify")
    assert verify["drains"] == 1 and verify["sets"] == len(sets)
    assert verify["lanes"] == lanes and verify["heights"] == BLOCKS
    assert verify["table_rows"] == len(frozenset().union(*sets)) <= 20
    assert verify["streamed"] is streams
    tallies = _spans(records, "chain.sync.tally")
    assert all(t["bigint"] for t in tallies)  # stakes in wei
    assert sum(t["blocks"] for t in tallies) == BLOCKS
    made = _spans(records, "chain.sync.produce")
    assert sum(m["blocks"] for m in made) == BLOCKS
    assert sum(m["lanes"] for m in made) == lanes
    (drain,) = [r for r in records if r[:2] == ("X", "verify.drain")]
    inside = [
        drain[3] <= r[3] and r[3] + r[4] <= drain[3] + drain[4] + 1
        for r in records
        if r[0] == "X" and r[1] in ("chain.sync.tally", "chain.sync.produce")
    ]
    if streams:
        # 420 lanes in 32-lane chunks; the producer's slabs are 266 and 154
        # lanes (19 + 11 blocks), so two read-backs completed one each.
        assert drain[5]["chunks"] == 14 and drain[5]["streamed"] is True
        assert [t["blocks"] for t in tallies] == [m["blocks"] for m in made] == [19, 11]
        assert all(inside)
    else:
        assert len(tallies) == 1 and not any(inside)
    if route != "host":
        # The refused lanes that are validly signed by an account of another
        # epoch's set were seen as such, and nothing else was.
        assert metrics.get_counter(batch.SYNC_OUT_OF_SET_LANES_KEY) - out_of_set == outside
        assert all(
            r[5]["route"] == "device"
            for r in records
            if r[1] == "verify.drain" and r[0] == "X"
        )


def _traps(c, blocks):
    at = next(i for i in range(BLOCKS // 2, BLOCKS) if blocks[i].height % 4)
    clean = blocks[at]
    heavy, heavy_valid = c.cut_by_weight(clean, heaviest=True)
    padded, outsiders = c.padded_with_outsiders(clean, SPAN)
    light, _ = c.cut_by_weight(clean, heaviest=False)
    # By COUNT the first two are whole: the old rule (2n/3 + 1 heads) is met.
    heads = (2 * len(c.src(clean.height))) // 3 + 1
    assert heavy_valid >= heads and len(padded.seals) >= heads and outsiders >= 1
    return at, {"heaviest-cut": heavy, "outsiders-make-it-up": padded, "lightest-cut": light}


@pytest.mark.parametrize("route", ["host", "device"])
@pytest.mark.parametrize("trap", ["heaviest-cut", "outsiders-make-it-up", "lightest-cut"])
def test_a_block_under_its_heights_quorum_power_is_refused(chain, route, trap):
    c, blocks, _want, _outside = chain
    at, traps = _traps(c, blocks)
    # The device route gets the blocks round the trap (three epochs of
    # them): the verdict is the trap block's, and the CPU pays a lane 30 ms.
    lo, hi = (0, BLOCKS) if route == "host" else (at - 4, at + 5)
    served = blocks[lo:at] + [traps[trap]] + blocks[at + 1 : hi]
    height = blocks[at].height
    assert pos_reference.first_refused(served, c.src) == height
    got, quorum = pos_reference.block_power(traps[trap], c.src)
    assert got < quorum
    client = _client(served, _verifier(route, c.src), c.src)
    with pytest.raises(SyncError, match=f"height {height}: committed-seal power {got} < quorum {quorum}"):
        client.catch_up(served[0].height, served[-1].height)


@pytest.mark.parametrize("trap", ["heaviest-cut", "outsiders-make-it-up"])
def test_the_old_count_rule_would_have_accepted_what_power_refuses(chain, trap):
    """The control: the same range under a source that gives every validator
    of a height ONE vote (quorum and tally by heads) goes through, where a
    signature on the drain's table is all it takes; so it is the power, and
    the height's own set, that refuse it above."""
    c, blocks, _want, _outside = chain
    at, traps = _traps(c, blocks)
    served = blocks[:at] + [traps[trap]] + blocks[at + 1 :]
    heads = lambda h: {a: 1 for a in c.src(h)}  # noqa: E731
    pool = lambda h: {a: 1 for a in c.accounts}  # noqa: E731
    by_count = heads if trap == "heaviest-cut" else pool
    _client(served, HostBatchVerifier(by_count), heads).catch_up(*SPAN)
    with pytest.raises(SyncError):
        _client(served, HostBatchVerifier(c.src), c.src).catch_up(*SPAN)


def test_max_table_rows_is_the_verifiers_largest_table():
    client = SyncClient(b"me", LoopbackSyncNetwork(), None, lambda h: {})
    assert client.max_table_rows == batch._TABLE_BUCKETS[-1]


def test_a_range_wider_than_the_largest_table_is_cut_into_runs_and_counted(chain):
    c, blocks, want, _outside = chain
    spy = _Spy(HostBatchVerifier(c.src))
    drains = metrics.get_counter(SYNC_DRAINS_KEY)
    regrouped = metrics.get_counter(SYNC_REGROUPED_KEY)
    rec = trace.enable()
    try:
        # 16 rows: a run ends where the next set would need a 17th.
        client = _client(blocks, spy, c.src)
        client.max_table_rows = 16
        client.catch_up(*SPAN)
        (verify,) = _spans(rec.snapshot(), "chain.sync.verify")
    finally:
        trace.disable()
    assert metrics.get_counter(SYNC_REGROUPED_KEY) - regrouped == 1
    assert len(spy.calls) == verify["drains"] == metrics.get_counter(SYNC_DRAINS_KEY) - drains > 1
    assert verify["table_rows"] == 16
    for _lanes, heights, _mask in spy.calls:
        assert len(frozenset().union(*(c.src(h) for h in set(heights)))) <= 16
    assert (np.concatenate([mask for _l, _h, mask in spy.calls]) == want).all()


# -- what a table is keyed by -------------------------------------------------


def _keys(n, tag=b"table"):
    return [PrivateKey.from_seed(tag + b"-%d" % i) for i in range(n)]


def _block(height, signers):
    proposal = Proposal(raw_proposal=block_bytes(height), round=0)
    phash = proposal_hash_of(proposal)
    return FinalizedBlock(
        height=height,
        proposal=proposal,
        seals=[
            CommittedSeal(signer=k.address, signature=encode_signature(*ec.sign(k, phash)))
            for k in signers
        ],
    )


def test_a_fixed_committee_is_one_set_one_drain_and_one_table_upload():
    keys = _keys(4)
    powers = {k.address: 1 for k in keys}
    blocks = [_block(h, keys[:3]) for h in range(1, 4)]
    dev = DeviceBatchVerifier(lambda height: powers)
    client = _client(blocks, dev, lambda height: powers)
    uploads = metrics.get_counter(batch.TABLE_UPLOADS_KEY)
    drains = metrics.get_counter(SYNC_DRAINS_KEY)
    rec = trace.enable()
    try:
        client.catch_up(1, 3)
        client.catch_up(1, 3)
        verify = _spans(rec.snapshot(), "chain.sync.verify")
    finally:
        trace.disable()
    assert [v["sets"] for v in verify] == [1, 1] and [v["drains"] for v in verify] == [1, 1]
    assert metrics.get_counter(SYNC_DRAINS_KEY) - drains == 2
    # One upload for the set, whatever heights select it and however often.
    assert metrics.get_counter(batch.TABLE_UPLOADS_KEY) - uploads == 1
    assert dev.verify_committed_seals(proposal_hash_of(blocks[0].proposal), blocks[0].seals, 77).all()
    assert metrics.get_counter(batch.TABLE_UPLOADS_KEY) - uploads == 1 and len(dev._tables) == 1


def test_stakes_that_move_inside_the_same_addresses_share_a_table_and_a_drain():
    keys = _keys(4)
    # Two epochs, the same four addresses, other stakes (and another quorum).
    epochs = [
        {k.address: 10**18 * (i + 1) for i, k in enumerate(keys)},
        {k.address: 10**18 * (4 - i) + 7 for i, k in enumerate(keys)},
    ]
    src = lambda height: epochs[(height - 1) // 2]  # noqa: E731
    blocks = [_block(h, keys) for h in range(1, 5)]
    dev = DeviceBatchVerifier(src)
    uploads = metrics.get_counter(batch.TABLE_UPLOADS_KEY)
    drains = metrics.get_counter(SYNC_DRAINS_KEY)
    rec = trace.enable()
    try:
        _client(blocks, dev, src).catch_up(1, 4)
        (verify,) = _spans(rec.snapshot(), "chain.sync.verify")
    finally:
        trace.disable()
    assert verify["sets"] == 1 and verify["drains"] == 1 and verify["table_rows"] == 4
    assert metrics.get_counter(SYNC_DRAINS_KEY) - drains == 1
    assert metrics.get_counter(batch.TABLE_UPLOADS_KEY) - uploads == 1 and len(dev._tables) == 1
    # The tally is each height's own, on the host (every power is over
    # 2**31): the two lightest validators of epoch 0 hold 3 of 10 there and
    # are refused, the same two addresses hold 7 of 10 (and the dust) in
    # epoch 1 and pass.
    pair = [keys[0], keys[1]]
    with pytest.raises(SyncError, match="height 2"):
        _client([_block(1, keys), _block(2, pair)], dev, src).catch_up(1, 2)
    _client([_block(3, pair), _block(4, pair)], dev, src).catch_up(3, 4)


@pytest.mark.parametrize("route", ["host", "device"])
def test_an_account_in_one_epochs_set_and_out_of_the_next_is_judged_by_height_in_one_drain(route):
    keys = _keys(5)
    stays, leaves = keys[:4], keys[4]
    epochs = [
        {k.address: 10**18 for k in keys},  # heights 1-2: all five
        {k.address: 10**18 for k in stays},  # heights 3-4: ``leaves`` is out
    ]
    src = lambda height: epochs[(height - 1) // 2]  # noqa: E731
    blocks = [_block(h, keys) for h in range(1, 5)]
    verifier = HostBatchVerifier(src) if route == "host" else DeviceBatchVerifier(src)
    spy = _Spy(verifier)
    uploads = metrics.get_counter(batch.TABLE_UPLOADS_KEY)
    outside = metrics.get_counter(batch.SYNC_OUT_OF_SET_LANES_KEY)
    _client(blocks, spy, src).catch_up(1, 4)  # 4 of 4 valid where it matters
    ((lanes, heights, mask),) = spy.calls
    assert lanes == 20 and set(heights) == {1, 3}
    theirs = [i for i, (_h, seal) in enumerate(seal_lanes(blocks)) if seal.signer == leaves.address]
    assert [bool(mask[i]) for i in theirs] == [True, True, False, False]
    assert mask.sum() == 18
    if route == "device":
        # One table (the union, five rows), one upload, two lanes out of set.
        assert metrics.get_counter(batch.TABLE_UPLOADS_KEY) - uploads == 1
        assert metrics.get_counter(batch.SYNC_OUT_OF_SET_LANES_KEY) - outside == 2
        assert [len(k) for k in verifier._tables] == [5]
    # The same lanes one height at a time, as the oracle is defined.
    oracle = HostBatchVerifier(src)
    by_block = np.concatenate(
        [oracle.verify_seal_lanes(seal_lanes([b]), b.height) for b in blocks]
    )
    assert (by_block == mask).all()


def test_the_scheduler_handle_judges_each_lane_at_its_own_height():
    from go_ibft_tpu.sched import TenantScheduler

    keys = _keys(5)
    epochs = [{k.address: 1 for k in keys}, {k.address: 1 for k in keys[:4]}]
    src = lambda height: epochs[(height - 1) // 2]  # noqa: E731
    blocks = [_block(h, keys) for h in range(1, 5)]
    lanes = seal_lanes(blocks)
    heights = [b.height for b in blocks for _ in b.seals]
    want = HostBatchVerifier(src).verify_seal_lanes(lanes, heights)
    assert want.sum() == 18
    sched = TenantScheduler(route="host")
    sched.start()
    try:
        handle = sched.register("chain-a", src)
        assert (handle.verify_seal_lanes(lanes, heights) == want).all()
        assert handle.verify_seal_lanes(lanes, 1).all()
    finally:
        sched.stop()
