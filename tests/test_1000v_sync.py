"""The ``ecdsa-1000v`` deployment against the plain reference, small, on the
CPU (ISSUE 51).

A 1,000-validator committee puts the verifier on the LAST rung of its
validator table, 2,048 rows, which no other test compiles against: here the
real recover program runs at the 8-lane bucket against that table (one
``(8, 2048)`` XLA:CPU compile, ~20 s cold, then the persistent cache's) and
its mask is held, lane for lane, to the generator's labels and to the
sequential ``HostBatchVerifier``; and one ``SyncClient.catch_up`` rides the
same executable with the dispatch cap taken down to 8 lanes, so that its
blocks straddle chunk edges and its last chunk is partly dead, as the cell
``ecdsa-1000v.sync-10h`` has them at 2,048.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.committee import Committee, block_bytes, mangle, seal_lanes  # noqa: E402
from benchmark.lib.harness import ledger_delta, ledger_sum  # noqa: E402

from go_ibft_tpu.chain import sync as sync_module  # noqa: E402
from go_ibft_tpu.chain.sync import (  # noqa: E402
    MAX_TABLE_ROWS,
    LoopbackSyncNetwork,
    SyncClient,
    SyncError,
)
from go_ibft_tpu.chain.wal import FinalizedBlock  # noqa: E402
from go_ibft_tpu.crypto import PrivateKey  # noqa: E402
from go_ibft_tpu.crypto import ecdsa as ec  # noqa: E402
from go_ibft_tpu.crypto.backend import (  # noqa: E402
    ECDSABackend,
    encode_signature,
    proposal_hash_of,
)
from go_ibft_tpu.messages.helpers import CommittedSeal  # noqa: E402
from go_ibft_tpu.messages.wire import Proposal  # noqa: E402
from go_ibft_tpu.obs import ledger as cost_ledger  # noqa: E402
from go_ibft_tpu.obs import trace  # noqa: E402
from go_ibft_tpu.verify import DeviceBatchVerifier, HostBatchVerifier, batch  # noqa: E402

VALIDATORS = 1000
SEED = 2147485151  # over 2**31, as the driver's seeds are
RUNG = 2048
CAP = 8  # lanes a dispatch in the catch_up below: the smallest lane bucket


@pytest.fixture(scope="module")
def committee():
    return Committee(VALIDATORS, SEED)


def _sign(key: PrivateKey, phash: bytes) -> bytes:
    return encode_signature(*ec.sign(key, phash))


def test_a_thousand_addresses_take_the_last_table_rung_padded_with_row_0(committee):
    table = batch.pack_validator_table(committee.ordered)
    assert table.shape == (RUNG, 5) and table.dtype == np.uint32
    assert batch._TABLE_BUCKETS[-1] == RUNG == MAX_TABLE_ROWS
    # The set's own rows, in the order handed in, then row 0 again and again:
    # padding adds no member.
    assert (table[:VALIDATORS] == batch._address_rows(committee.ordered)).all()
    assert (table[VALIDATORS:] == table[0]).all()
    assert len({row.tobytes() for row in table}) == VALIDATORS
    # One address more than 512 is already on this rung; 2,049 are on none.
    assert batch.pack_validator_table(committee.ordered[:513]).shape == (RUNG, 5)
    with pytest.raises(ValueError):
        batch.pack_validator_table([i.to_bytes(20, "big") for i in range(RUNG + 1)])


# What each lane of the drain below is, and the verdict a correct verifier
# owes it.  Rows are the table's: the committee's addresses, sorted.
LANES = (
    ("a valid seal of the validator in row 0", True),
    ("a valid seal of the validator in row 999", True),
    ("a mangled seal of a validator", False),
    ("a VALID signature by a key outside the committee", False),
    ("row 0's address (the one the pad rows repeat) over row 1's signature", False),
    ("row 0's address over an outsider's signature", False),
    ("a valid seal of the validator in row 500", True),
)


@pytest.fixture(scope="module")
def drain(committee):
    """Seven lanes against the 2,048-row table through the real 8-lane
    program, with the host oracle's mask beside it and the spans it left."""
    c = committee
    phash = proposal_hash_of(Proposal(raw_proposal=block_bytes(7), round=0))
    row = c.ordered
    outsider = PrivateKey.from_seed(b"not a validator of this committee")
    assert outsider.address not in c.powers
    lanes = [
        (phash, CommittedSeal(row[0], c.seal(row[0], phash))),
        (phash, CommittedSeal(row[999], c.seal(row[999], phash))),
        (phash, CommittedSeal(row[3], mangle(c.seal(row[3], phash)))),
        # Only the membership compare rejects this one: the signature holds
        # and recovers to the claimed address.
        (phash, CommittedSeal(outsider.address, _sign(outsider, phash))),
        (phash, CommittedSeal(row[0], c.seal(row[1], phash))),
        (phash, CommittedSeal(row[0], _sign(outsider, phash))),
        (phash, CommittedSeal(row[500], c.seal(row[500], phash))),
    ]
    verifier = DeviceBatchVerifier(c.src)
    assert not trace.enabled()
    recorder = trace.enable()
    try:
        got = np.asarray(verifier.verify_seal_lanes(lanes, 7), dtype=bool)
        spans = recorder.snapshot()
    finally:
        trace.disable()
    oracle = np.asarray(HostBatchVerifier(c.src).verify_seal_lanes(lanes, 7), dtype=bool)
    return got, oracle, spans


@pytest.mark.parametrize("lane", range(len(LANES)), ids=[what for what, _ in LANES])
def test_the_2048_row_program_gives_every_lane_the_oracles_verdict(drain, lane):
    got, oracle, _spans = drain
    assert got[lane] == LANES[lane][1] == oracle[lane]


def test_the_drain_was_one_8_lane_dispatch_of_the_2048_row_executable(drain):
    got, _oracle, spans = drain
    assert len(got) == len(LANES)
    (dispatch,) = [r[5] for r in spans if r[:2] == ("X", "verify.dispatch")]
    assert dispatch["route"] == "device" and dispatch["table_rows"] == RUNG
    assert dispatch["operands"] == 6 and dispatch["ladder"] == "scan"
    (pack,) = [r[5] for r in spans if r[:2] == ("X", "verify.pack")]
    assert pack == {"kind": "seal_lanes", "lanes": len(LANES)}


# -- catch_up across chunk edges -------------------------------------------
#
# A block of the real committee needs 667 valid seals, 84 dispatches of this
# program on XLA:CPU (0.15 s each).  So the range below keeps the committee's 1,000
# ADDRESSES (the table, the rung and the executable are the cell's) and gives
# 16 of them a stake of 490 where the others hold 1: total 8,824, quorum
# floor(2 x 8,824 / 3) + 1 = 5,883 = 12 x 490 + 3, so that a block of 12
# heavy and 3 light seals carries EXACTLY quorum, and one light seal less is
# quorum - 1.
HEAVY, HEAVY_STAKE = 16, 490
SEALS = (12, 3)  # heavy, light signers a block
BLOCKS = 2  # 30 lanes = 3 x 8 + 6: three full chunks and a tail with 2 dead lanes


@pytest.fixture(scope="module")
def staked(committee):
    c = committee
    heavy, light = c.ordered[:HEAVY], c.ordered[HEAVY:]
    powers = {a: HEAVY_STAKE for a in heavy}
    powers.update({a: 1 for a in light})
    total = sum(powers.values())
    quorum = 2 * total // 3 + 1
    assert (total, quorum) == (8824, 5883) and quorum == SEALS[0] * HEAVY_STAKE + SEALS[1]
    blocks = []
    for h in range(1, BLOCKS + 1):
        proposal = Proposal(raw_proposal=block_bytes(h), round=0)
        phash = proposal_hash_of(proposal)
        # Another dozen of the heavy each height; light signers from both
        # ends of the table, row 999 among them.
        signers = [heavy[(h + j) % HEAVY] for j in range(SEALS[0])]
        signers += [light[-1], light[h], light[len(light) // 2]]
        seals = [CommittedSeal(a, c.seal(a, phash)) for a in signers]
        blocks.append(FinalizedBlock(height=h, proposal=proposal, seals=seals))
    return ECDSABackend.static_validators(powers), blocks


def _client(src, blocks, verifier):
    class _Peer:
        def latest_height(self):
            return blocks[-1].height

        def get_blocks(self, start, end):
            return [b for b in blocks if start <= b.height <= end]

    network = LoopbackSyncNetwork()
    network.register(b"peer", _Peer())
    return SyncClient(b"me", network, verifier, src)


def _small_cap(src) -> DeviceBatchVerifier:
    verifier = DeviceBatchVerifier(src)
    verifier._dispatch_cap = CAP
    return verifier


def test_catch_up_rides_three_full_chunks_and_a_padded_tail_of_the_2048_row_program(
    staked, monkeypatch
):
    src, blocks = staked
    # A block a slab of the client's producer (as the cell's 800-seal blocks
    # are): a mask, and a tally, as soon as a block's last lane is back.
    monkeypatch.setattr(sync_module, "_PRODUCE_LANES", 1)
    client = _client(src, blocks, _small_cap(src))
    assert not trace.enabled() and not cost_ledger.enabled()
    recorder = trace.enable()
    cost_ledger.enable()
    try:
        before = cost_ledger.snapshot()
        got = client.catch_up(1, BLOCKS)
        after = cost_ledger.snapshot()
        spans = recorder.snapshot()
    finally:
        cost_ledger.disable()
        trace.disable()
    assert [b.height for b in got] == [1, 2]
    # ONE drain of four chunks; a block is 15 lanes, so the first ends inside
    # the second chunk and the second block runs over two chunk edges.
    (sync,) = [r[5] for r in spans if r[:2] == ("X", "chain.sync.verify")]
    assert sync == {
        "lanes": 30,
        "heights": 2,
        "drains": 1,
        "sets": 1,
        "table_rows": VALIDATORS,
        "streamed": True,
    }
    (outer,) = [r[5] for r in spans if r[:2] == ("X", "verify.drain")]
    assert outer == {"route": "device", "kind": "seal_lanes", "streamed": True, "chunks": 4}
    packs = [r[5]["lanes"] for r in spans if r[:2] == ("X", "verify.pack")]
    assert packs == [CAP, CAP, CAP, 30 - 3 * CAP]
    dispatches = [r[5] for r in spans if r[:2] == ("X", "verify.dispatch")]
    assert [d["table_rows"] for d in dispatches] == [RUNG] * 4
    rows = ledger_delta(before, after)
    moved = {
        f: ledger_sum(rows, f, "ecdsa_recover", "device")
        for f in ("dispatches", "live_lanes", "padded_lanes")
    }
    # 93.75% occupancy: the tail's two dead lanes (the cell reads 8000 / 8192).
    assert moved == {"dispatches": 4, "live_lanes": 30, "padded_lanes": 4 * CAP}
    # The tally saw a 1,000-entry power map a height and exactly quorum: a
    # span a chunk that completed a block (the second chunk and the tail),
    # the first while the third chunk was on the device, both inside the
    # drain; and each block's lanes were made as the chunks wanted them.
    tallies = [r for r in spans if r[:2] == ("X", "chain.sync.tally")]
    assert [r[5] for r in tallies] == [{"blocks": 1, "bigint": False}] * BLOCKS
    made = [r[5] for r in spans if r[:2] == ("X", "chain.sync.produce")]
    assert made == [{"blocks": 1, "lanes": 15}] * BLOCKS
    starts = sorted(r[3] for r in spans if r[:2] == ("X", "verify.dispatch"))
    waits = sorted(r[3] + r[4] for r in spans if r[:2] == ("X", "verify.device_wait"))
    assert starts[2] <= tallies[0][3] and tallies[0][3] + tallies[0][4] <= waits[2] + 1
    assert waits[3] <= tallies[1][3] + 1
    (drain,) = [r for r in spans if r[:2] == ("X", "verify.drain")]
    assert drain[3] <= tallies[0][3] and tallies[1][3] + tallies[1][4] <= drain[3] + drain[4] + 1


def test_catch_up_refuses_the_range_with_one_block_at_quorum_less_one(staked):
    src, blocks = staked
    short = blocks[1]
    # The last seal is a light validator's (stake 1), in the padded tail chunk.
    seals = list(short.seals)
    assert src(short.height)[seals[-1].signer] == 1
    seals[-1] = CommittedSeal(seals[-1].signer, mangle(seals[-1].signature))
    cut = [blocks[0], FinalizedBlock(short.height, short.proposal, seals)]
    with pytest.raises(SyncError, match="quorum"):
        _client(src, cut, _small_cap(src)).catch_up(1, BLOCKS)
    # The sequential oracle agrees on both ranges, lane for lane, with the
    # device route's masks.
    host = HostBatchVerifier(src)
    for range_, bad in ((blocks, None), (cut, 29)):
        lanes = seal_lanes(range_)
        want = np.ones(len(lanes), dtype=bool)
        if bad is not None:
            want[bad] = False
        assert (np.asarray(host.verify_seal_lanes(lanes, 1), dtype=bool) == want).all()
        assert (np.asarray(_small_cap(src).verify_seal_lanes(lanes, 1), dtype=bool) == want).all()
