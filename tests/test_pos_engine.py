"""The live engine under a stake-weighted committee that changes at an epoch
boundary, small, on the CPU (ISSUE 45; no benchmark cell runs this yet).

Seven validators of a nine-account pool, voting power = stake in wei (every
power over 2**60, so the device's fused quorum cannot hold it and the tally
is the host's exact integers), epochs of two blocks: heights 1-2 under one
set, 3-4 under another (one validator out, one in, every stake moved).  One
engine, the other validators scripted as in the benchmark's ``round0``
driver: once through ``BatchingIngress`` + the batch verifier with the
device route really taken, once message by message with NO batch verifier,
which is upstream's sequential path and this system's plain reference.  Both
finalize the same blocks in round 0 from the same PREPARE senders and the
same seals; and six heads of seven that do not carry quorum POWER finalize
nothing.
"""

import asyncio
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.committee import block_bytes  # noqa: E402
from benchmark.lib.harness import NullLogger  # noqa: E402

from go_ibft_tpu.core import IBFT, BatchingIngress  # noqa: E402
from go_ibft_tpu.core.validator_manager import calculate_quorum  # noqa: E402
from go_ibft_tpu.crypto import PrivateKey  # noqa: E402
from go_ibft_tpu.crypto.backend import ECDSABackend, proposal_hash_of  # noqa: E402
from go_ibft_tpu.messages.wire import MessageType, Proposal, View  # noqa: E402
from go_ibft_tpu.obs import ledger as cost_ledger  # noqa: E402
from go_ibft_tpu.utils import metrics  # noqa: E402
from go_ibft_tpu.verify import AdaptiveBatchVerifier, batch  # noqa: E402

WEI = 10**18
KEYS = [PrivateKey.from_seed(b"pos-engine-%d" % i) for i in range(9)]
A = [k.address for k in KEYS]
# Epoch 0 (heights 1-2): accounts 0-6, account 3 the whale (more than a third
# of the power: no quorum without it).  Epoch 1 (heights 3-4): account 6 is
# out, account 7 in, every stake moved; account 1 is the whale now.
EPOCHS = [
    {A[0]: 3 * WEI + 1, A[1]: 2 * WEI + 3, A[2]: 2 * WEI + 5, A[3]: 9 * WEI + 7,
     A[4]: 1 * WEI + 9, A[5]: 1 * WEI + 11, A[6]: 1 * WEI + 13},
    {A[0]: 2 * WEI + 2, A[1]: 11 * WEI + 4, A[2]: 3 * WEI + 6, A[3]: 2 * WEI + 8,
     A[4]: 2 * WEI + 10, A[5]: 1 * WEI + 12, A[7]: 1 * WEI + 14},
]
WHALE = {0: A[3], 1: A[1]}
HEIGHTS = (1, 2, 3, 4)
NODE = KEYS[0]


def src(height: int):
    return EPOCHS[(height - 1) // 2]


def _peers_messages(height: int, silent=()):
    """The PREPREPARE (``None`` where the node proposes), PREPAREs and
    COMMITs of the height's other validators, but the ``silent`` ones."""
    view = View(height=height, round=0)
    members = sorted(src(height))
    proposer = members[height % len(members)]
    key_of = {k.address: k for k in KEYS}
    backend = lambda a: ECDSABackend(key_of[a], src)  # noqa: E731
    raw = block_bytes(height)
    phash = proposal_hash_of(Proposal(raw_proposal=raw, round=0))
    preprepare = None
    if proposer != NODE.address:
        preprepare = backend(proposer).build_preprepare_message(raw, None, view)
    peers = [a for a in members if a != NODE.address and a not in silent]
    prepares = [
        backend(a).build_prepare_message(phash, view) for a in peers if a != proposer
    ]
    commits = [backend(a).build_commit_message(phash, view) for a in peers]
    return SimpleNamespace(
        height=height, proposer=proposer, preprepare=preprepare,
        prepares=prepares, commits=commits,
    )


class _Wire:
    """The engine's transport: the node's own messages come back through the
    ingress, and the peers answer its PREPARE (or its own PREPREPARE) with
    their PREPAREs and its COMMIT with their COMMITs, in the same turn."""

    def __init__(self, st):
        self.st = st

    def multicast(self, message):
        st, t = self.st, self.st.current
        st.submit(message)
        if t is None or message.view.height != t.height or message.view.round:
            return
        if message.type in (MessageType.PREPREPARE, MessageType.PREPARE):
            answers = t.prepares
        elif message.type == MessageType.COMMIT:
            answers = t.commits
        else:
            return
        for m in answers:
            st.submit(m)


def _engine(batched: bool):
    st = SimpleNamespace(current=None, locked={})
    backend = ECDSABackend(NODE, src, build_proposal_fn=lambda view: block_bytes(view.height))
    verifier = None
    if batched:
        # cutover 2: the one-lane PREPREPARE on the host, every flood on the
        # device (the 8-lane programs), the seals riding their envelopes.
        verifier = AdaptiveBatchVerifier(src, cutover_lanes=2)
    st.engine = IBFT(NullLogger(), backend, _Wire(st), batch_verifier=verifier)
    st.engine.set_base_round_timeout(60.0)
    st.engine.on_lock = lambda h, r, cert, proposal: st.locked.setdefault(
        h, frozenset(m.sender for m in cert.prepare_messages)
    )
    if batched:
        st.ingress = BatchingIngress(st.engine.add_messages)
        st.submit = st.ingress.submit
    else:
        st.ingress = None
        st.submit = st.engine.add_message  # upstream's AddMessage, one at a time
    st.backend, st.verifier = backend, verifier
    return st


async def _height(st, t, wait_s: float):
    st.current = t
    before = len(st.backend.inserted)
    sequence = asyncio.ensure_future(st.engine.run_sequence(t.height))
    await asyncio.sleep(0)
    if t.preprepare is not None:
        st.submit(t.preprepare)
    try:
        await asyncio.wait_for(sequence, wait_s)
    except asyncio.TimeoutError:
        pass
    st.current = None
    return st.backend.inserted[before:]


def _run(batched: bool, heights=HEIGHTS, silent_at=None, wait_s: float = 60.0):
    st = _engine(batched)
    out = {}

    async def drive():
        for h in heights:
            silent = (silent_at or {}).get(h, ())
            inserted = await _height(st, _peers_messages(h, silent), wait_s)
            out[h] = [
                (p.raw_proposal, p.round, st.locked.get(h), frozenset(s.signer for s in seals))
                for p, seals in inserted
            ]

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(drive())
    finally:
        if st.ingress is not None:
            st.ingress.close()
        st.engine.messages.close()
        loop.close()
    return st, out


def test_the_engine_finalizes_across_an_epoch_boundary_as_the_sequential_reference_does():
    uploads = metrics.get_counter(batch.TABLE_UPLOADS_KEY)
    cost_ledger.enable()
    try:
        st, batched = _run(True)
        rows = cost_ledger.snapshot()["dispatches"]
    finally:
        cost_ledger.disable()
    _st, sequential = _run(False)
    assert batched == sequential
    for h in HEIGHTS:
        ((raw, round_, lock_senders, signers),) = batched[h]
        powers = src(h)
        quorum = calculate_quorum(sum(powers.values()))
        assert raw == block_bytes(h) and round_ == 0
        # The finalized seals and the locked PREPAREs (with the proposer)
        # carry quorum POWER of the height's own set, in exact integers.
        assert signers <= set(powers) and sum(powers[a] for a in signers) >= quorum > 2**60
        proposer = sorted(powers)[h % len(powers)]
        assert lock_senders <= set(powers) - {proposer}
        assert sum(powers[a] for a in lock_senders | {proposer}) >= quorum
    # Account 6 seals in epoch 0 and never in epoch 1, account 7 the other way.
    assert A[6] in batched[2][0][3] and A[6] not in batched[3][0][3] | batched[4][0][3]
    assert A[7] in batched[3][0][3] and A[7] not in batched[1][0][3] | batched[2][0][3]
    # The floods really took the device route, and the two sets cost two
    # table uploads in four heights: one a SET, not one a height.
    recovers = [r for r in rows if r["program"] == "ecdsa_recover" and r["route"] == "device"]
    assert sum(r["dispatches"] for r in recovers) >= 2 * len(HEIGHTS)
    assert metrics.get_counter(batch.TABLE_UPLOADS_KEY) - uploads == 2
    # Powers in wei never reach the device: its programs return masks, and
    # every quorum above was reduced by the host in exact integers.
    assert {r["program"] for r in rows if r["route"] == "device"} == {"ecdsa_recover", "digest_words"}


@pytest.mark.parametrize("batched", [True, False], ids=["batched-device", "sequential"])
@pytest.mark.parametrize("height", [1, 4], ids=["epoch-0", "epoch-1"])
def test_a_quorum_of_heads_that_is_no_quorum_of_power_finalizes_nothing(batched, height):
    epoch = (height - 1) // 2
    powers = src(height)
    whale = WHALE[epoch]
    proposer = sorted(powers)[height % len(powers)]
    assert whale not in (NODE.address, proposer)
    rest = set(powers) - {whale}
    quorum = calculate_quorum(sum(powers.values()))
    # Six of seven validators vote: the count rule's quorum (5) is met twice
    # over, the power is not.
    assert len(rest) == 6 >= calculate_quorum(len(powers))
    assert sum(powers[a] for a in rest) < quorum
    _st, out = _run(batched, heights=(height,), silent_at={height: (whale,)}, wait_s=1.5)
    assert out[height] == []
    # ... and with the whale's votes the same height finalizes.
    _st, out = _run(batched, heights=(height,))
    ((_raw, round_, _lock, signers),) = out[height]
    assert round_ == 0 and sum(powers[a] for a in signers) >= quorum
