"""The sync drain as a stream (ISSUE 53): a range's lanes are made while the
chunk before runs, and a block is tallied while the chunk after runs.

``DeviceBatchVerifier.verify_seal_lane_stream`` pulls ``(lanes, heights)``
lazily, cuts chunks across them, and yields the masks a read-back completed;
``verify_seal_lanes`` is that stream consumed; ``SyncClient.verify_blocks``
feeds it from ``_produce`` and tallies what comes back.  Where ORDER is
asserted the dispatch is a stub (as ``tests/test_pipeline_overlap.py`` does:
an order of recorded events, never a clock); where VERDICTS are, the real
8-lane program runs against the 8-row table every other suite loads.
"""

import numpy as np
import pytest

from go_ibft_tpu.chain import sync as sync_module
from go_ibft_tpu.chain.sync import (
    SYNC_STREAMED_KEY,
    LoopbackSyncNetwork,
    SyncClient,
    SyncError,
)
from go_ibft_tpu.chain.wal import FinalizedBlock
from go_ibft_tpu.chaos import ChaoticVerifier, FaultConfig, FaultInjector
from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto import ecdsa as ec
from go_ibft_tpu.crypto.backend import encode_signature, proposal_hash_of
from go_ibft_tpu.messages.helpers import CommittedSeal
from go_ibft_tpu.messages.wire import Proposal
from go_ibft_tpu.obs import trace
from go_ibft_tpu.sched import TenantScheduler
from go_ibft_tpu.utils import metrics
from go_ibft_tpu.verify import (
    AdaptiveBatchVerifier,
    DeviceBatchVerifier,
    HostBatchVerifier,
    batch,
)

CAP = 8  # lanes a dispatch: the smallest lane bucket
KEYS = [PrivateKey.from_seed(b"sync-stream-%d" % i) for i in range(8)]
POWERS = {k.address: 1 for k in KEYS}
QUORUM = 6  # floor(2 x 8 / 3) + 1


def _src(_height):
    return POWERS


def _mangle(sig: bytes) -> bytes:
    return bytes([sig[0] ^ 0x55]) + sig[1:]


def _block(height, signers, mangled=(), fake=False):
    """A block sealed by ``signers`` (keys); the seals at ``mangled`` do not
    verify.  ``fake``: well-formed bytes no one signed (a stub judges them)."""
    proposal = Proposal(raw_proposal=b"stream block %d" % height, round=0)
    phash = proposal_hash_of(proposal)
    seals = []
    for j, key in enumerate(signers):
        sig = b"\x01" * 65 if fake else encode_signature(*ec.sign(key, phash))
        seals.append(CommittedSeal(key.address, _mangle(sig) if j in mangled else sig))
    return FinalizedBlock(height=height, proposal=proposal, seals=seals)


def _lanes(blocks):
    return [(proposal_hash_of(b.proposal), s) for b in blocks for s in b.seals]


def _client(blocks, verifier, src=_src):
    class _Peer:
        def latest_height(self):
            return blocks[-1].height

        def get_blocks(self, start, end):
            return [b for b in blocks if start <= b.height <= end]

    network = LoopbackSyncNetwork()
    network.register(b"peer", _Peer())
    return SyncClient(b"me", network, verifier, src)


def _device(src=_src, cap=CAP) -> DeviceBatchVerifier:
    verifier = DeviceBatchVerifier(src)
    verifier._dispatch_cap = cap
    return verifier


def _adaptive(src=_src, cap=CAP) -> AdaptiveBatchVerifier:
    verifier = AdaptiveBatchVerifier(src)
    verifier.device._dispatch_cap = cap
    return verifier


class _Tap:
    """Hands the inner verifier's stream on and keeps what went through it:
    the lanes as they were pulled, the masks as they came back."""

    def __init__(self, inner):
        self.inner, self.lanes, self.heights, self.masks = inner, [], [], []

    def verify_seal_lanes(self, lanes, height):
        raise AssertionError("the list call, where a stream was offered")

    def verify_seal_lane_stream(self, blocks, heights, lanes=0):
        def pulled():
            for slab, slab_heights in blocks:
                self.lanes.extend(slab)
                self.heights.extend(slab_heights)
                yield slab, slab_heights

        def answers(stream):
            for masks in stream:
                self.masks.extend(masks)
                yield masks

        return answers(self.inner.verify_seal_lane_stream(pulled(), heights, lanes))

    @property
    def mask(self):
        return np.concatenate(self.masks) if self.masks else np.zeros(0, dtype=bool)


@pytest.fixture
def block_slabs(monkeypatch):
    """A block a slab of the client's producer: the unit the stream answers
    in, at this file's sizes (the default, 256 lanes, is ``CAP`` times 32)."""
    monkeypatch.setattr(sync_module, "_PRODUCE_LANES", 1)


# -- (a) the order of events ---------------------------------------------------


def test_lanes_are_made_and_blocks_tallied_while_chunks_run(block_slabs, monkeypatch):
    """12 blocks of 3 lanes in chunks of 8 (blocks straddle chunk edges): the
    first launch comes before the range is hashed, chunk k + 1 is produced
    and chunk k - 1's blocks are tallied between launch k and read-back k,
    and never more than two handles are in flight."""
    blocks = [_block(h, KEYS[:3], fake=True) for h in range(1, 13)]
    powers = {k.address: 1 for k in KEYS[:3]}  # three votes a block: quorum 3
    n_chunks = -(-12 * 3 // CAP)
    # The chunk that holds a block's first lane, and the one with its last.
    first_chunk = {b.height: (3 * i) // CAP for i, b in enumerate(blocks)}
    last_chunk = {b.height: (3 * i + 2) // CAP for i, b in enumerate(blocks)}
    events = []
    dev = _device(lambda h: powers)

    def launch(inputs, table):
        k = sum(e[0] == "launch" for e in events)
        events.append(("launch", k))
        return k, np.asarray(inputs[5], dtype=bool)

    def readback(handle):
        events.append(("readback", handle[0]))
        return handle[1]

    real_hash = sync_module.proposal_hash_of
    by_proposal = {b.proposal.raw_proposal: b.height for b in blocks}

    def hashed(proposal):
        events.append(("hash", by_proposal[proposal.raw_proposal]))
        return real_hash(proposal)

    real_hold = SyncClient._hold_to_quorum

    def held(block, mask, powers_, quorum):
        events.append(("tally", block.height))
        return real_hold(block, mask, powers_, quorum)

    monkeypatch.setattr(dev, "_dispatch_async", launch)
    monkeypatch.setattr(dev, "_readback", readback)
    monkeypatch.setattr(sync_module, "proposal_hash_of", hashed)
    monkeypatch.setattr(SyncClient, "_hold_to_quorum", staticmethod(held))
    assert len(_client(blocks, dev, lambda h: powers).catch_up(1, 12)) == 12

    at = {e: i for i, e in enumerate(events)}
    assert [e for e in events if e[0] == "launch"] == [("launch", k) for k in range(n_chunks)]
    assert [e[1] for e in events if e[0] == "hash"] == list(range(1, 13))
    assert [e[1] for e in events if e[0] == "tally"] == list(range(1, 13))  # in height order
    # The first launch: chunk 0's blocks are hashed (the one that straddles
    # its edge among them), and no more than one beyond.
    chunk0 = [h for h, k in first_chunk.items() if k == 0]
    before = [e for e in events[: at[("launch", 0)]] if e[0] == "hash"]
    assert len(chunk0) <= len(before) <= len(chunk0) + 1 < len(blocks)
    in_flight = 0
    for kind, _what in events:
        in_flight += (kind == "launch") - (kind == "readback")
        assert 0 <= in_flight <= 2
    assert in_flight == 0
    for k in range(1, n_chunks - 1):
        between = events[at[("launch", k)] : at[("readback", k)]]
        # Chunk k + 1 is launched, its new blocks hashed, in there ...
        assert ("launch", k + 1) in between and ("readback", k - 1) in between
        fresh = [h for h in first_chunk if first_chunk[h] == k + 1]
        assert fresh and all(("hash", h) in between for h in fresh)
        # ... and the blocks chunk k - 1 completed are tallied, chunk k (and
        # k + 1) on the device.
        done = [h for h in last_chunk if last_chunk[h] == k - 1]
        assert done and all(("tally", h) in between for h in done)
    # No block is tallied before the read-back of the chunk with its last lane.
    for h, k in last_chunk.items():
        assert at[("readback", k)] < at[("tally", h)]


def test_the_stream_pulls_no_further_than_the_chunk_it_packs():
    """The verifier's side alone: blocks are pulled from the caller's
    iterable only as a chunk wants them, and a block's mask comes with the
    read-back of the chunk that holds its last lane."""
    blocks = [_block(h, KEYS[:3], fake=True) for h in range(1, 7)]  # 18 lanes
    dev = _device()
    pulled, events = [], []
    dev._dispatch_async = lambda inputs, table: (
        events.append(("launch", len(pulled))),
        np.asarray(inputs[5], dtype=bool),
    )[1]
    dev._readback = lambda handle: handle

    def source():
        for b in blocks:
            pulled.append(b.height)
            yield _lanes([b]), b.height

    got = []
    for masks in dev.verify_seal_lane_stream(source(), {1}, 18):
        got.append((len(pulled), [len(m) for m in masks]))
    # Chunks of 8, 8, 2 lanes: launched with 3, 6, 6 blocks pulled.
    assert events == [("launch", 3), ("launch", 6), ("launch", 6)]
    # Read-back 0 (after launch 1) completes blocks 1-2, read-back 1 blocks
    # 3-5, the tail the last.
    assert got == [(6, [3, 3]), (6, [3, 3, 3]), (6, [3])]


# -- (b) verdicts: the sequential host oracle's, lane for lane ------------------


def _two_epochs():
    """Heights 1-2: five validators; heights 3-4: the first four (the fifth
    still signs: validly, and out of its height's set)."""
    epochs = [
        {k.address: 10**18 + i for i, k in enumerate(KEYS[:5])},
        {k.address: 10**18 + i for i, k in enumerate(KEYS[:4])},
    ]
    src = lambda height: epochs[(height - 1) // 2]  # noqa: E731
    return src, [_block(h, KEYS[:5]) for h in range(1, 5)]


def _malformed_mid_range():
    """Malformed lanes in the second and fourth blocks: no room in a chunk
    is theirs, so every later chunk's cut shifts by one, then two."""
    blocks = [_block(h, KEYS[:7]) for h in range(1, 6)]
    seals = list(blocks[1].seals)
    seals[2] = CommittedSeal(seals[2].signer, seals[2].signature[:64])
    blocks[1] = FinalizedBlock(2, blocks[1].proposal, seals)
    seals = list(blocks[3].seals)
    seals[0] = CommittedSeal(seals[0].signer[:19], seals[0].signature)
    blocks[3] = FinalizedBlock(4, blocks[3].proposal, seals)
    return _src, blocks


RANGES = {
    "a fixed committee with mangled seals": lambda: (
        _src,
        [_block(h, KEYS[:7], mangled=(h % 7,) if h % 2 else ()) for h in range(1, 6)],
    ),
    "malformed lanes mid-range": _malformed_mid_range,
    "across two sets with an out-of-set signer": _two_epochs,
    "a range under one chunk": lambda: (_src, [_block(1, KEYS[:6])]),
    "a range of exactly two chunks": lambda: (_src, [_block(h, KEYS) for h in (1, 2)]),
}


@pytest.mark.parametrize("what", list(RANGES))
def test_catch_up_through_the_stream_gives_the_oracles_verdicts(what, block_slabs):
    src, blocks = RANGES[what]()
    lanes = _lanes(blocks)
    want = np.asarray(
        HostBatchVerifier(src).verify_seal_lanes(
            lanes, [b.height for b in blocks for _ in b.seals]
        ),
        dtype=bool,
    )
    tap = _Tap(_device(src))
    streamed = metrics.get_counter(SYNC_STREAMED_KEY)
    got = _client(blocks, tap, src).catch_up(1, len(blocks))
    assert [b.height for b in got] == [b.height for b in blocks]
    assert metrics.get_counter(SYNC_STREAMED_KEY) - streamed == 1
    assert tap.lanes == lanes  # every lane, in order, through the stream
    assert len(tap.masks) == len(blocks)  # a mask a block (a slab)
    assert (tap.mask == want).all()
    # The list call is the same drain: the same verdicts.
    listed = _device(src).verify_seal_lanes(lanes, tap.heights)
    assert (np.asarray(listed, dtype=bool) == want).all()


def test_a_block_with_no_seals_is_answered_and_refused_like_the_list_path(block_slabs):
    blocks = [_block(1, KEYS[:6]), _block(2, ()), _block(3, KEYS[:6])]
    tap = _Tap(_device())
    with pytest.raises(SyncError) as streamed:
        _client(blocks, tap).catch_up(1, 3)
    with pytest.raises(SyncError) as listed:
        _client(blocks, HostBatchVerifier(_src)).catch_up(1, 3)
    assert str(streamed.value) == str(listed.value)
    assert str(streamed.value).startswith("height 2: committed-seal power 0 < quorum 6 (0/0")
    # An empty block makes no slab of its own: it rides the next block's,
    # and the tally cuts that mask at the blocks' edges (0 lanes, then 6).
    assert [len(m) for m in tap.masks] == [6, 6] and tap.mask.all()


def test_a_range_with_no_seal_at_all_runs_no_drain():
    blocks = [_block(h, ()) for h in (1, 2)]
    rec = trace.enable()
    try:
        with pytest.raises(SyncError, match="height 1: committed-seal power 0"):
            _client(blocks, _device()).catch_up(1, 2)
        names = [r[1] for r in rec.snapshot()]
    finally:
        trace.disable()
    assert "verify.drain" not in names and names.count("chain.sync.tally") == 1


# -- (c) a block short of quorum mid-range -------------------------------------


def test_a_block_short_of_quorum_raises_the_list_paths_error_and_reads_every_handle(
    block_slabs,
):
    """Blocks 3 and 5 of six are a seal short: the error is the list path's,
    for the FIRST of them; what was in flight is read back; the chunks
    behind it are not run."""
    blocks = [
        _block(h, KEYS[:6], mangled=(0,) if h in (3, 5) else ()) for h in range(1, 7)
    ]
    dev = _device()
    launched, read = [], []
    launch, readback = dev._dispatch_async, dev._readback

    def counting_launch(inputs, table):
        handle = launch(inputs, table)
        launched.append(id(handle))
        return handle

    def counting_readback(handle):
        read.append(id(handle))
        return readback(handle)

    dev._dispatch_async, dev._readback = counting_launch, counting_readback
    rec = trace.enable()
    try:
        with pytest.raises(SyncError) as streamed:
            _client(blocks, dev).catch_up(1, 6)
        drains = [r[5] for r in rec.snapshot() if r[:2] == ("X", "verify.drain")]
    finally:
        trace.disable()
    with pytest.raises(SyncError) as listed:
        _client(blocks, HostBatchVerifier(_src)).catch_up(1, 6)
    assert str(streamed.value) == str(listed.value)
    assert str(streamed.value).startswith(
        "height 3: committed-seal power 5 < quorum 6 (5/6 seals valid)"
    )
    # 36 lanes are five chunks; block 3 ends in the third (lanes 12-17), whose
    # read-back comes with the fourth launched: the fifth never runs.
    assert launched == read and len(launched) == 4
    (drain,) = drains
    assert drain["chunks"] == 3 and drain["streamed"] is True


# -- (d) a stream that faults ends in the ladder --------------------------------


def test_a_stream_whose_third_chunk_faults_ends_in_the_ladder(block_slabs):
    blocks = [_block(h, KEYS[:7], mangled=(h % 7,)) for h in range(1, 7)]  # 42 lanes
    lanes = _lanes(blocks)
    want = np.asarray(HostBatchVerifier(_src).verify_seal_lanes(lanes, 1), dtype=bool)
    assert not want.all() and want.sum() == 36
    verifier = _adaptive()
    launch, launches = verifier.device._dispatch_async, []

    def dying_launch(inputs, table):
        launches.append(1)
        if len(launches) >= 3:
            raise RuntimeError("XLA: the device went away")
        return launch(inputs, table)

    verifier.device._dispatch_async = dying_launch
    tap = _Tap(verifier)
    before = {
        key: metrics.get_counter(key)
        for key in (batch.STREAM_FALLBACKS_KEY, batch.DRAIN_FAULTS_KEY, SYNC_STREAMED_KEY)
    }
    got = _client(blocks, tap).catch_up(1, 6)
    assert len(got) == 6
    moved = {key[-1]: metrics.get_counter(key) - was for key, was in before.items()}
    assert moved == {"stream_fallbacks": 1, "drain_faults": 1, "sync_streamed": 1}
    # Chunk 0's read-back (with chunk 1 in flight) had answered block 1: it
    # stands; the five others went through the ladder in one list drain, down
    # to the host rung.  The oracle's verdicts all the same, a mask a block.
    assert [len(m) for m in tap.masks] == [7] * 6
    assert (tap.mask == want).all()
    # ONE breaker fault: the ladder stays on its device rung (k = 3).
    assert verifier.breaker.level == 0 and verifier.breaker._faults == 1


def test_a_producer_that_raises_is_not_taken_for_a_device_fault():
    verifier = _adaptive()

    def source():
        yield _lanes([_block(1, KEYS)]) * 3, 1
        raise KeyError("the caller's own")

    keys = (batch.DRAIN_FAULTS_KEY, batch.STREAM_FALLBACKS_KEY)
    before = [metrics.get_counter(key) for key in keys]
    stream = verifier.verify_seal_lane_stream(source(), {1}, 48)
    with pytest.raises(KeyError, match="the caller's own"):
        list(stream)
    assert [metrics.get_counter(key) for key in keys] == before
    assert verifier.breaker._faults == 0


# -- (e) a verifier with no stream: the same tally loop -------------------------


def _tenant_handle():
    sched = TenantScheduler(window_s=0.001, route="host")
    return sched, sched.register("a", _src)


@pytest.mark.parametrize("kind", ["host", "tenant handle", "chaos wrapper", "under the cutover"])
def test_a_verifier_that_offers_no_stream_is_served_by_the_same_loop(kind):
    blocks = [_block(h, KEYS[:7], mangled=(1,) if h == 2 else ()) for h in range(1, 4)]
    short = blocks[:1] + [_block(2, KEYS[:7], mangled=(1, 2))] + blocks[2:]
    sched = None
    if kind == "host":
        verifier = HostBatchVerifier(_src)
    elif kind == "tenant handle":
        sched, verifier = _tenant_handle()
        sched.start()
    elif kind == "chaos wrapper":
        # Over a verifier that HAS a stream: the wrapper's gate is a call's.
        verifier = ChaoticVerifier(_adaptive(), FaultInjector(5, FaultConfig()), "verify:t")
        assert verifier.verify_seal_lane_stream(iter(()), {1}, 64) is None
    else:
        verifier = AdaptiveBatchVerifier(_src, cutover_lanes=64)  # 21 lanes: host
    streamed = metrics.get_counter(SYNC_STREAMED_KEY)
    rec = trace.enable()
    try:
        got = _client(blocks, verifier).catch_up(1, 3)
        records = [r for r in rec.snapshot() if r[0] == "X"]
        with pytest.raises(SyncError, match="height 2: committed-seal power 5 < quorum 6"):
            _client(short, verifier).catch_up(1, 3)
    finally:
        trace.disable()
        if sched is not None:
            sched.stop()
    assert [b.height for b in got] == [1, 2, 3]
    assert metrics.get_counter(SYNC_STREAMED_KEY) == streamed
    (verify,) = [r[5] for r in records if r[1] == "chain.sync.verify"]
    assert verify["streamed"] is False and verify["drains"] == 1
    # Every lane made, ONE call, one tally for the whole range.
    assert [r[5] for r in records if r[1] == "chain.sync.produce"] == [
        {"blocks": 3, "lanes": 21}
    ]
    assert [r[5] for r in records if r[1] == "chain.sync.tally"] == [
        {"blocks": 3, "bigint": False}
    ]


def test_a_verifier_that_answers_fewer_blocks_than_it_was_given_is_refused(block_slabs):
    """No block is accepted on a mask that never came."""

    class _Forgetful:
        def verify_seal_lane_stream(self, blocks, heights, lanes=0):
            for slab, _heights in blocks:
                yield [np.ones(len(slab), dtype=bool)]
                return

    blocks = [_block(h, KEYS[:6], fake=True) for h in (1, 2)]
    with pytest.raises(SyncError, match="answered 1 of 2 blocks"):
        _client(blocks, _Forgetful()).catch_up(1, 2)


# -- (f) the list call is the stream consumed -----------------------------------


def test_the_list_call_and_the_stream_leave_the_same_pack_and_dispatch_spans(monkeypatch):
    monkeypatch.setattr(
        batch, "_recover_kernel", lambda zw, r, s, v, claimed, table, live: np.asarray(live)
    )
    blocks = [_block(h, KEYS[:5], fake=True) for h in range(1, 6)]  # 25 lanes
    lanes = _lanes(blocks)

    def spans_of(run):
        rec = trace.enable()
        try:
            mask = run()
            records = [r for r in rec.snapshot() if r[0] == "X"]
        finally:
            trace.disable()
        return mask, {
            name: [r[5] for r in records if r[1] == name]
            for name in ("verify.pack", "verify.dispatch", "verify.device_wait", "verify.drain")
        }

    listed, as_list = spans_of(lambda: _device().verify_seal_lanes(lanes, 1))
    streamed, as_stream = spans_of(
        lambda: np.concatenate(
            [
                m
                for masks in _device().verify_seal_lane_stream(
                    ((_lanes([b]), b.height) for b in blocks), {1}, len(lanes)
                )
                for m in masks
            ]
        )
    )
    assert listed.all() and (listed == streamed).all() and len(listed) == 25
    assert as_list["verify.pack"] == [
        {"kind": "seal_lanes", "lanes": n} for n in (8, 8, 8, 1)
    ]
    for name in ("verify.pack", "verify.dispatch", "verify.device_wait"):
        assert as_list[name] == as_stream[name] and len(as_list[name]) == 4
    assert as_list["verify.drain"] == [
        {"route": "device", "kind": "seal_lanes", "streamed": False, "chunks": 4}
    ]
    assert as_stream["verify.drain"] == [
        {"route": "device", "kind": "seal_lanes", "streamed": True, "chunks": 4}
    ]
