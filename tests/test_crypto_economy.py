"""Every signature is verified exactly once — the r05 phase economy.

Envelopes pay one batch verification at ingress; committed seals pay one
at first sight (engine verdict cache); repeat phase wakeups re-dispatch
NOTHING.  Until r04 the phases re-verified per wakeup, making a phase
O(n²) in signature checks and putting the adaptive cluster 15-30% behind
a plain host cluster (VERDICT r04 weak #2, a round-4 CPU run).
"""

from go_ibft_tpu.core import IBFT
from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto.backend import ECDSABackend
from go_ibft_tpu.messages import View
from go_ibft_tpu.verify import HostBatchVerifier

from harness import NullLogger


class CountingVerifier(HostBatchVerifier):
    def __init__(self, src):
        super().__init__(src)
        self.sender_lanes = 0
        self.seal_lanes = 0

    def verify_senders(self, msgs):
        self.sender_lanes += len(msgs)
        return super().verify_senders(msgs)

    def verify_committed_seals(self, proposal_hash, seals, height):
        self.seal_lanes += len(seals)
        return super().verify_committed_seals(proposal_hash, seals, height)

    def verify_seals_early_exit(self, proposal_hash, seals, height, threshold=None):
        # The early-exit drain (ISSUE 9) counts only the lanes it
        # actually VERIFIED — deferred lanes cost no crypto until they
        # resolve, which is exactly the economy this suite pins.
        report = super().verify_seals_early_exit(
            proposal_hash, seals, height, threshold=threshold
        )
        self.seal_lanes += int(report.verified.sum())
        return report


def _engine(n=4):
    keys = [PrivateKey.from_seed(b"econ-%d" % i) for i in range(n)]
    powers = {k.address: 1 for k in keys}
    src = ECDSABackend.static_validators(powers)
    backends = [ECDSABackend(k, src) for k in keys]

    class _T:
        def multicast(self, message):
            pass

    verifier = CountingVerifier(src)
    engine = IBFT(NullLogger(), backends[1], _T(), batch_verifier=verifier)
    engine.state.reset(1)
    engine.validator_manager.init(1)
    return engine, verifier, backends


async def test_prepare_wakeups_cost_no_crypto_after_ingress():
    engine, verifier, backends = _engine()
    view = View(height=1, round=0)
    proposer = next(b for b in backends if b.is_proposer(b.address, 1, 0))
    others = [b for b in backends if b is not proposer]
    pmsg = proposer.build_preprepare_message(b"block 1", None, view)
    engine._accept_proposal(pmsg)
    phash = pmsg.preprepare_data.proposal_hash

    engine.add_messages([b.build_prepare_message(phash, view) for b in others])
    # exactly one verification lane per envelope — `==` so that any
    # double-verification (the O(n^2) regression class) trips the test
    assert verifier.sender_lanes == len(others)

    # Wakeups cost zero additional signature work.
    before = (verifier.sender_lanes, verifier.seal_lanes)
    assert engine._handle_prepare(view)
    engine._handle_prepare(view)  # repeat wakeup
    assert (verifier.sender_lanes, verifier.seal_lanes) == before


async def test_each_seal_verified_exactly_once_across_wakeups():
    engine, verifier, backends = _engine()
    view = View(height=1, round=0)
    proposer = next(b for b in backends if b.is_proposer(b.address, 1, 0))
    others = [b for b in backends if b is not proposer]
    pmsg = proposer.build_preprepare_message(b"block 1", None, view)
    engine._accept_proposal(pmsg)
    phash = pmsg.preprepare_data.proposal_hash

    # two commits arrive; first wakeup verifies exactly those two seals
    engine.add_messages(
        [
            others[0].build_commit_message(phash, view),
            others[1].build_commit_message(phash, view),
        ]
    )
    sender_lanes_at_ingress = verifier.sender_lanes
    engine._handle_commit(view)  # below quorum: verdict False, seals cached
    assert verifier.seal_lanes == 2

    # repeat wakeups with the same store: no re-verification
    engine._handle_commit(view)
    engine._handle_commit(view)
    assert verifier.seal_lanes == 2

    # a third commit arrives: only the NEW seal is verified, quorum reached
    engine.add_messages([proposer.build_commit_message(phash, view)])
    assert engine._handle_commit(view)
    assert verifier.seal_lanes == 3
    assert len(engine.state.committed_seals) == 3
    # and the commit drain added no envelope re-verification beyond ingress
    assert verifier.sender_lanes == sender_lanes_at_ingress + 1  # 3rd ingress


def test_seal_verdict_cache_is_bounded():
    """A Byzantine sender rewriting its COMMIT with fresh seal bytes per
    delivery mints a new verdict-cache key each time (store last-write-wins
    dedup admits the rewrite); the ENGINE's drain must evict old entries —
    this drives _drain_valid_commits itself, not a re-implementation."""
    from go_ibft_tpu.crypto import ecdsa as ec
    from go_ibft_tpu.crypto import keccak256
    from go_ibft_tpu.crypto.backend import encode_signature
    from go_ibft_tpu.messages import CommitMessage, IbftMessage, MessageType

    engine, verifier, backends = _engine()
    engine._seal_verdict_cap = 3
    view = View(height=1, round=0)
    proposer = next(b for b in backends if b.is_proposer(b.address, 1, 0))
    byz = next(b for b in backends if b is not proposer)
    pmsg = proposer.build_preprepare_message(b"block 1", None, view)
    engine._accept_proposal(pmsg)
    phash = pmsg.preprepare_data.proposal_hash

    for i in range(10):  # 10 rewrites, each a distinct (invalid) seal
        rewrite = byz._sign_envelope(
            IbftMessage(
                view=view.copy(),
                sender=byz.address,
                type=MessageType.COMMIT,
                commit_data=CommitMessage(
                    proposal_hash=phash,
                    committed_seal=encode_signature(
                        *ec.sign(byz.key, keccak256(b"evil %d" % i))
                    ),
                ),
            )
        )
        engine.add_messages([rewrite])
        engine._handle_commit(view)
    assert verifier.seal_lanes == 10  # each distinct seal verified once
    assert len(engine._seal_verdicts) <= engine._seal_verdict_cap


def test_seal_verdict_key_carries_proposal_hash():
    """ADVICE r5 finding 1 regression: a cached True verdict is keyed by
    the proposal hash it verified AGAINST, so it can never validate the
    same seal bytes against a different hash (even if a future code path
    re-set the accepted proposal mid-round)."""
    engine, verifier, backends = _engine()
    view = View(height=1, round=0)
    proposer = next(b for b in backends if b.is_proposer(b.address, 1, 0))
    others = [b for b in backends if b is not proposer]
    pmsg = proposer.build_preprepare_message(b"block 1", None, view)
    engine._accept_proposal(pmsg)
    phash = pmsg.preprepare_data.proposal_hash

    engine.add_messages([b.build_commit_message(phash, view) for b in others])
    engine._handle_commit(view)
    round_cache = engine._seal_verdicts[0]
    assert round_cache, "drain cached no verdicts"
    for (sender, cached_hash, seal_bytes), verdict in round_cache.items():
        assert cached_hash == phash
        assert verdict is True
    # The same seal bytes looked up under a DIFFERENT proposal hash is a
    # cache miss by construction of the key.
    (sender, _, seal_bytes), _ = next(iter(round_cache.items()))
    assert (sender, b"\x00" * 32, seal_bytes) not in round_cache


def test_byzantine_flood_evicts_dead_rounds_before_live_verdicts():
    """ADVICE r5 finding 2 regression: a Byzantine seal-rewrite flood
    (fresh seal bytes per delivery mint fresh cache keys) must evict
    verdicts from rounds the engine already left BEFORE touching the live
    round's — so post-flood wakeups in the current view re-verify
    nothing."""
    from go_ibft_tpu.crypto import ecdsa as ec
    from go_ibft_tpu.crypto import keccak256
    from go_ibft_tpu.crypto.backend import encode_signature
    from go_ibft_tpu.messages import CommitMessage, IbftMessage, MessageType

    engine, verifier, backends = _engine()
    engine._seal_verdict_cap = 8
    proposer_r1 = next(b for b in backends if b.is_proposer(b.address, 1, 1))
    byz = next(b for b in backends if b is not proposer_r1)

    # Round 0: two verdicts land in the (soon-dead) round-0 bucket.
    view0 = View(height=1, round=0)
    proposer_r0 = next(b for b in backends if b.is_proposer(b.address, 1, 0))
    pmsg0 = proposer_r0.build_preprepare_message(b"block 1", None, view0)
    engine._accept_proposal(pmsg0)
    phash0 = pmsg0.preprepare_data.proposal_hash
    others0 = [b for b in backends if b is not proposer_r0][:2]
    engine.add_messages(
        [b.build_commit_message(phash0, view0) for b in others0]
    )
    engine._handle_commit(view0)
    assert len(engine._seal_verdicts[0]) == 2

    # Round moves to 1; honest commits fill the live bucket.
    engine._move_to_new_round(1)
    view1 = View(height=1, round=1)
    pmsg1 = proposer_r1.build_preprepare_message(b"block 1", None, view1)
    # round-1 proposals normally carry an RCC; bypass validation and
    # accept directly — this test drives the drain, not the proposal path
    engine._accept_proposal(pmsg1)
    phash1 = pmsg1.preprepare_data.proposal_hash
    honest = [b for b in backends if b is not proposer_r1 and b is not byz]
    engine.add_messages(
        [b.build_commit_message(phash1, view1) for b in honest]
    )
    engine._handle_commit(view1)
    live_before = dict(engine._seal_verdicts[1])
    assert live_before

    def flood(start, count):
        # Each rewrite REPLACES byz's stored commit (store dedup is
        # last-write-wins per sender) but mints a fresh verdict-cache key.
        for i in range(start, start + count):
            rewrite = byz._sign_envelope(
                IbftMessage(
                    view=view1.copy(),
                    sender=byz.address,
                    type=MessageType.COMMIT,
                    commit_data=CommitMessage(
                        proposal_hash=phash1,
                        committed_seal=encode_signature(
                            *ec.sign(byz.key, keccak256(b"flood %d" % i))
                        ),
                    ),
                )
            )
            engine.add_messages([rewrite])
            engine._handle_commit(view1)

    # Flood past the cap: the dead round-0 bucket must be the first thing
    # evicted, with every live (round 1) verdict untouched.
    flood(0, 5)
    assert engine._seal_verdict_count <= engine._seal_verdict_cap
    assert 0 not in engine._seal_verdicts
    for key, verdict in live_before.items():
        assert engine._seal_verdicts[1].get(key) == verdict, key

    # Survival is behavioral, not just structural: the post-flood wakeup
    # re-verifies only the flood's own latest rewrite, never the honest
    # seals (the flood competed with the dead round, not the live view).
    seal_lanes_before = verifier.seal_lanes
    flood(5, 1)
    assert verifier.seal_lanes == seal_lanes_before + 1

    # A sustained flood stays bounded (within the live round eviction is
    # FIFO — the flood ultimately competes with itself).
    flood(6, 14)
    assert engine._seal_verdict_count <= engine._seal_verdict_cap
    assert set(engine._seal_verdicts) == {1}


def test_cache_cleared_per_sequence():
    engine, verifier, backends = _engine()
    engine._seal_verdicts[(1, 0, b"x", b"y")] = True

    import asyncio

    async def run():
        task = asyncio.get_running_loop().create_task(engine.run_sequence(2))
        await asyncio.sleep(0.05)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass

    asyncio.run(run())
    assert engine._seal_verdicts == {}
