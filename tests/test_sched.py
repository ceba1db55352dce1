"""TenantScheduler unit suite (ISSUE 8).

Pins the multi-tenant verify plane's contracts:

* **oracle parity** — every tenant's verdicts are bit-identical to its
  own sequential :class:`HostBatchVerifier`, even when lanes from chains
  with different validator sets (and SHARED proposal hashes) coalesce
  into one dispatch, on both the host and device routes;
* **cache namespacing** (satellite) — two chains sharing a proposal hash
  at the same height/round can never alias packed lanes or seal
  verdicts, and one tenant's ``note_round`` / ``reset_pack_cache``
  cannot evict another tenant's live round state;
* **fairness** — the globally oldest request always ships (hard
  starvation bound) and DRR keeps a lane-hungry tenant from monopolizing
  a dispatch;
* **backpressure** — a full tenant queue sheds to the caller's local
  oracle without blocking the scheduler thread, and a stopped scheduler
  degrades to the oracle instead of wedging the consensus loop.
"""

import threading
import time

import numpy as np
import pytest

from go_ibft_tpu.bench.workload import build_seal_lane_workload, build_signed_round
from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto.backend import ECDSABackend
from go_ibft_tpu.sched import CoalescedDispatcher, TenantScheduler
from go_ibft_tpu.sched.scheduler import SchedQueueFull
from go_ibft_tpu.verify import HostBatchVerifier


def _src(seed: int, n: int):
    """The validator source matching build_signed_round's key space."""
    keys = [PrivateKey.from_seed(b"bench-%d-%d" % (seed, i)) for i in range(n)]
    return ECDSABackend.static_validators({k.address: 1 for k in keys})


def test_oracle_parity_mixed_tenants_host_route():
    """Three chains with different validator sets — one flooding corrupt
    seals — drain concurrently through one scheduler; every tenant's
    verdicts must equal its own sequential oracle."""
    rounds = {
        "a": (build_signed_round(4, seed=101), _src(101, 4)),
        "b": (build_signed_round(8, seed=202, corrupt_frac=0.5), _src(202, 8)),
        "c": (build_signed_round(6, seed=303, corrupt_frac=0.2), _src(303, 6)),
    }
    sched = TenantScheduler(window_s=0.002, route="host")
    handles = {
        tid: sched.register(tid, src) for tid, (_r, src) in rounds.items()
    }
    results = {}

    def run(tid):
        r, _src_ = rounds[tid]
        h = handles[tid]
        results[tid] = (
            h.verify_senders(r.prepares),
            h.verify_committed_seals(r.proposal_hash, r.seals, 1),
            h.verify_seal_lanes([(r.proposal_hash, s) for s in r.seals], 1),
        )

    with sched:
        threads = [
            threading.Thread(target=run, args=(tid,)) for tid in rounds
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for tid, (r, src) in rounds.items():
        oracle = HostBatchVerifier(src)
        senders, seals, lanes = results[tid]
        np.testing.assert_array_equal(
            senders, oracle.verify_senders(r.prepares)
        )
        np.testing.assert_array_equal(
            seals, oracle.verify_committed_seals(r.proposal_hash, r.seals, 1)
        )
        np.testing.assert_array_equal(seals, r.expected_seal_mask)
        np.testing.assert_array_equal(lanes, r.expected_seal_mask)
    assert sched.stats()["flush_faults"] == 0


def test_device_route_parity_small():
    """The device route (shared pinned kernels + claimed-address table)
    produces the same verdicts as the host oracle for a mixed-tenant
    flush, including cross-chain lanes sharing a proposal hash."""
    ra, rb = build_signed_round(4, seed=11), build_signed_round(
        4, seed=22, corrupt_frac=0.5
    )
    assert ra.proposal_hash == rb.proposal_hash  # same height, same block
    sched = TenantScheduler(window_s=0.005, route="device")
    ha = sched.register("a", _src(11, 4))
    hb = sched.register("b", _src(22, 4))
    out = {}

    def run(tid, h, r):
        out[tid] = (
            h.verify_senders(r.prepares),
            h.verify_committed_seals(r.proposal_hash, r.seals, 1),
        )

    with sched:
        ta = threading.Thread(target=run, args=("a", ha, ra))
        tb = threading.Thread(target=run, args=("b", hb, rb))
        ta.start()
        tb.start()
        ta.join()
        tb.join()
    for tid, r, seed in (("a", ra, 11), ("b", rb, 22)):
        oracle = HostBatchVerifier(_src(seed, 4))
        np.testing.assert_array_equal(
            out[tid][0], oracle.verify_senders(r.prepares)
        )
        np.testing.assert_array_equal(out[tid][1], r.expected_seal_mask)


def test_coalescing_shares_dispatches():
    """Concurrent tenant requests coalesce: strictly fewer dispatches
    than requests (coalesce_ratio > 1) when two tenants submit inside
    one window."""
    ra, rb = build_signed_round(4, seed=11), build_signed_round(8, seed=22)
    sched = TenantScheduler(window_s=0.05, route="host")
    ha = sched.register("a", _src(11, 4))
    hb = sched.register("b", _src(22, 8))
    with sched:
        ta = threading.Thread(
            target=lambda: ha.verify_senders(ra.prepares)
        )
        tb = threading.Thread(
            target=lambda: hb.verify_senders(rb.prepares)
        )
        ta.start()
        tb.start()
        ta.join()
        tb.join()
    stats = sched.stats()
    assert stats["dispatches"] == 1, stats
    assert stats["coalesced_requests"] == 2, stats
    assert stats["coalesce_ratio"] > 1.0, stats


def test_demand_aware_flush_single_hot_tenant():
    """An idle tenant never stalls a hot one: with one registered-but-idle
    tenant, the hot tenant's lone request flushes after the window, not
    after any participation from the idle tenant."""
    r = build_signed_round(4, seed=11)
    sched = TenantScheduler(window_s=0.002, route="host")
    hot = sched.register("hot", _src(11, 4))
    sched.register("idle", _src(22, 8))  # registered, never submits
    with sched:
        t0 = time.monotonic()
        mask = hot.verify_senders(r.prepares)
        elapsed = time.monotonic() - t0
    assert mask.all()
    assert elapsed < 0.5, f"flush waited {elapsed:.3f}s for an idle tenant"


def test_seal_verdict_cache_namespaced_by_tenant():
    """Satellite regression: two chains share a proposal hash at the same
    height/round (identical raw proposal, identical height).  Chain A's
    validator seal is True for A; the SAME (signer, hash, signature) is
    False for chain B — and B's verdict must be computed under B's
    namespace, never served from A's cached True."""
    ra = build_signed_round(4, seed=11)
    rb = build_signed_round(4, seed=22)
    assert ra.proposal_hash == rb.proposal_hash
    sched = TenantScheduler(window_s=0.001, route="host")
    ha = sched.register("chain-a", _src(11, 4))
    hb = sched.register("chain-b", _src(22, 4))
    with sched:
        mask_a = ha.verify_committed_seals(ra.proposal_hash, ra.seals, 1)
        assert mask_a.all()
        # A's verdicts are now cached under A.  Submitting A's seals to
        # CHAIN B (same hash, height, round, signer bytes) must produce
        # all-False — B's validator set does not contain A's signers.
        mask_b = hb.verify_committed_seals(rb.proposal_hash, ra.seals, 1)
        assert not mask_b.any(), "chain B served chain A's cached verdicts"
        # And B's own seals still verify under B.
        assert hb.verify_committed_seals(rb.proposal_hash, rb.seals, 1).all()


def test_note_round_and_reset_are_tenant_scoped():
    """Satellite regression: one tenant's round rotation / sequence reset
    must not evict another tenant's live round state."""
    sched = TenantScheduler(route="host")
    ha = sched.register("a", _src(11, 4))
    hb = sched.register("b", _src(22, 4))
    ta, tb = sched._tenants["a"], sched._tenants["b"]
    ha.note_round(7)
    assert ta.pack_cache._round == 7
    assert tb.pack_cache._round == 0, "tenant A's round rotated tenant B"
    # Seed both tenants' caches, then reset A only.
    rb = build_signed_round(4, seed=22)
    key_b = (b"s" * 20, b"h" * 32, b"sig", 1)
    ta.verdicts.store((b"x" * 20, b"h" * 32, b"sig", 1), True)
    tb.verdicts.store(key_b, True)
    from go_ibft_tpu.verify.pipeline import SenderPack

    pack = SenderPack(
        payload=b"p",
        r_limbs=np.zeros(20, np.int32),
        s_limbs=np.zeros(20, np.int32),
        v=0,
        sender_words=np.zeros(5, np.uint32),
    )
    ta.pack_cache.store(rb.prepares[0], pack)
    tb.pack_cache.store(rb.prepares[1], pack)
    ha.reset_pack_cache()
    assert len(ta.pack_cache) == 0
    assert len(tb.pack_cache) == 1, "tenant A's reset evicted tenant B"
    assert tb.verdicts.lookup(key_b) is True


def test_starvation_bound_oldest_request_always_ships():
    """A small tenant's request queued behind a flooding tenant is served
    within a bounded number of flushes: the globally oldest request ships
    first, and DRR grants the small tenant lanes every flush."""
    sched = TenantScheduler(
        window_s=0.001, max_dispatch_lanes=1024, quantum_lanes=64, route="host"
    )
    sched.register("hot", _src(11, 4))
    sched.register("cold", _src(22, 4))
    hot_tenant = sched._tenants["hot"]
    cold_tenant = sched._tenants["cold"]
    # Drive selection directly (no thread): deterministic fairness check.
    sched._running = True
    out = np.zeros(4096, dtype=bool)
    for i in range(8):
        sched.submit(
            hot_tenant, "seals", [("h", None)] * 512, 1, out, list(range(512))
        )
    cold_req = sched.submit(
        cold_tenant, "seals", [("h", None)] * 8, 1, out, list(range(8))
    )
    served_in = None
    for flush_no in range(1, 10):
        with sched._cv:
            batch = sched._select_locked()
        assert sum(r.lanes for r in batch) <= 1024
        if cold_req in batch:
            served_in = flush_no
            break
    assert served_in is not None and served_in <= 2, (
        f"cold tenant served in flush {served_in}"
    )


def test_backpressure_sheds_to_oracle_without_blocking():
    """A tenant at its queue cap sheds at submit time: verdicts still
    exact (local oracle), the scheduler thread never blocks, and other
    tenants keep flowing."""
    gate = threading.Event()
    inner = CoalescedDispatcher(route="host")

    class _GatedDispatcher:
        def launch(self, msgs, lanes, owners):
            gate.wait(5.0)
            return inner.launch(msgs, lanes, owners)

        collect = inner.collect

        def warmup(self, **kw):
            pass

    r = build_signed_round(4, seed=11)
    src = _src(11, 4)
    sched = TenantScheduler(
        window_s=0.001,
        max_queue_lanes=8,
        dispatcher=_GatedDispatcher(),
    )
    h = sched.register("a", src)
    done = []
    with sched:
        # Five concurrent 4-lane drains against an 8-lane queue cap while
        # the dispatcher is gated shut: one flush goes in-flight behind
        # the gate, two requests fill the queue, the rest MUST shed — and
        # the shed callers finish while the gate is still closed, proving
        # backpressure never blocks on the wedged dispatch.
        def drain():
            mask = h.verify_senders(r.prepares)
            assert mask.all()  # shed or scheduled, verdicts stay exact
            done.append(time.monotonic())

        threads = [threading.Thread(target=drain) for _ in range(5)]
        for t in threads:
            t.start()
            time.sleep(0.02)  # let each submit/flush land before the next
        deadline = time.monotonic() + 5.0
        while (
            sched.stats()["tenants"]["a"]["sheds"] == 0
            and time.monotonic() < deadline
        ):
            time.sleep(0.005)
        stats_gated = sched.stats()["tenants"]["a"]
        assert stats_gated["sheds"] >= 1, stats_gated
        assert done, "no shed drain completed while the dispatcher was gated"
        gate.set()
        for t in threads:
            t.join(5.0)
            assert not t.is_alive()
    stats = sched.stats()["tenants"]["a"]
    assert stats["shed_lanes"] >= 4, stats


def test_stopped_scheduler_degrades_to_oracle():
    """Submissions against a scheduler that is not running resolve via
    the local oracle — a dead scheduler can never wedge consensus."""
    r = build_signed_round(4, seed=11, corrupt_frac=0.25)
    sched = TenantScheduler(route="host")
    h = sched.register("a", _src(11, 4))
    mask = h.verify_committed_seals(r.proposal_hash, r.seals, 1)
    np.testing.assert_array_equal(mask, r.expected_seal_mask)
    assert sched.stats()["tenants"]["a"]["sheds"] >= 1


def test_large_request_chunks_to_dispatch_cap():
    """A drain above the dispatch cap chunks into multiple requests and
    still returns exact verdicts (the sync catch-up shape)."""
    w = build_seal_lane_workload(
        96, n_validators=8, heights=3, corrupt_frac=0.2, seed=7
    )
    sched = TenantScheduler(window_s=0.001, max_dispatch_lanes=32, route="host")
    h = sched.register("a", w.validators)
    with sched:
        mask = h.verify_seal_lanes(w.lanes, w.height)
    np.testing.assert_array_equal(mask, w.expected_mask)
    assert sched.stats()["dispatches"] >= 3


def test_malformed_lanes_masked_false_not_crashing():
    """Handle-level admission mirrors the oracle: malformed senders /
    seals / hashes get False verdicts, well-formed lanes still verify."""
    from go_ibft_tpu.messages.helpers import CommittedSeal

    r = build_signed_round(4, seed=11)
    src = _src(11, 4)
    sched = TenantScheduler(window_s=0.001, route="host")
    h = sched.register("a", src)
    bad_seal = CommittedSeal(signer=b"\x01" * 3, signature=b"\x02" * 10)
    with sched:
        lanes = [(r.proposal_hash, r.seals[0]), (b"short", r.seals[1]),
                 (r.proposal_hash, bad_seal)]
        mask = h.verify_seal_lanes(lanes, 1)
        short_hash = h.verify_committed_seals(b"nope", r.seals, 1)
    np.testing.assert_array_equal(mask, [True, False, False])
    assert not short_hash.any()


def test_queue_full_exception_surface():
    """SchedQueueFull is raised at submit for an over-cap request (the
    scheduler-side contract the handle's shed path relies on)."""
    sched = TenantScheduler(max_queue_lanes=4, route="host")
    sched.register("a", _src(11, 4))
    tenant = sched._tenants["a"]
    sched._running = True
    out = np.zeros(8, dtype=bool)
    sched.submit(tenant, "seals", [("h", None)] * 4, 1, out, list(range(4)))
    with pytest.raises(SchedQueueFull):
        sched.submit(tenant, "seals", [("h", None)] * 4, 1, out, list(range(4)))


# -- satellite: the shared-ladder lifecycle fix (EngineScope) ------------


def test_pack_cache_owner_scoped_lifecycle():
    """PackCache owner scoping: one owner's round rotation / reset touches
    only its own entries, and cap-pressure eviction protects EVERY
    owner's live round (not just a single global one)."""
    from go_ibft_tpu.verify.pipeline import PackCache, SenderPack

    def pack():
        return SenderPack(
            payload=b"p",
            r_limbs=np.zeros(20, np.int32),
            s_limbs=np.zeros(20, np.int32),
            v=0,
            sender_words=np.zeros(5, np.uint32),
        )

    class _Msg:  # weak-referenceable stand-in with the token fields
        sender = b"s" * 20
        signature = b"g" * 65

    cache = PackCache(cap=4)
    a_msgs, b_msgs = [_Msg() for _ in range(2)], [_Msg() for _ in range(2)]
    cache.note_round(3, owner="a")
    cache.note_round(8, owner="b")
    with cache.owned("a"):
        for m in a_msgs:
            cache.store(m, pack())
    with cache.owned("b"):
        for m in b_msgs:
            cache.store(m, pack())
    assert len(cache) == 4
    # A's rotation: only A's live round moves; B's entries stay live.
    cache.note_round(4, owner="a")
    # Cap pressure: A's round-3 entries are now DEAD and must evict before
    # either owner's live round gives anything up.
    extra = _Msg()
    with cache.owned("b"):
        cache.store(extra, pack())
    assert all(cache.lookup(m) is None for m in a_msgs), (
        "dead-round entries survived cap pressure"
    )
    assert all(cache.lookup(m) is not None for m in b_msgs), (
        "owner B's LIVE round was evicted by owner A's dead round"
    )
    # A's sequence reset drops only A's state; B's live round survives.
    with cache.owned("a"):
        cache.store(_Msg(), pack())
    cache.clear(owner="a")
    assert all(cache.lookup(m) is not None for m in b_msgs)
    assert cache.lookup(extra) is not None


def test_engine_scope_shared_ladder_isolates_lifecycle():
    """Satellite regression: two engines sharing ONE DeviceBatchVerifier
    through ``scoped()`` facades — engine A's reset_pack_cache/note_round
    (the ladder-wide reset that used to assume a single engine) cannot
    evict engine B's live packs, and both scopes' verdicts match the
    oracle."""
    ra = build_signed_round(4, seed=11)
    rb = build_signed_round(4, seed=22, corrupt_frac=0.25)
    from go_ibft_tpu.verify import DeviceBatchVerifier

    shared = DeviceBatchVerifier(_src(11, 4))
    scope_a = shared.scoped("chain-a")
    scope_b = shared.scoped("chain-b")
    cache = shared._pack_cache
    mask_a = scope_a.verify_senders(ra.prepares)
    assert mask_a.all()
    # B's verify runs under B's validator source via its own oracle check:
    # membership is the parent's (shared source), so only compare sig-valid
    # lanes against the sequential oracle of the SHARED source.
    oracle = HostBatchVerifier(_src(11, 4))
    np.testing.assert_array_equal(
        scope_b.verify_senders(rb.prepares),
        oracle.verify_senders(rb.prepares),
    )
    packed_b = [m for m in rb.prepares if cache.lookup(m) is not None]
    assert packed_b, "scope B stored no packs"
    scope_a.note_round(5)
    scope_a.reset_pack_cache()
    assert all(cache.lookup(m) is not None for m in packed_b), (
        "scope A's sequence reset evicted scope B's live packs"
    )
    assert all(cache.lookup(m) is None for m in ra.prepares)
    # The facade delegates the rest of the surface (warmup, quarantine).
    scope_b.quarantine(packed_b[:1])
    assert cache.lookup(packed_b[0]) is None


def test_speculation_cache_owner_scoped_per_tenant():
    """ISSUE 9 satellite: a SpeculationCache shared across tenants keys
    every verdict by owner — two tenants speculating the SAME bytes (one
    signed round, identical (height, round, hash, sender, signature))
    get their OWN verdicts (membership differs per tenant), one tenant's
    lifecycle hooks never touch the other's entries, and a lookup can
    never cross owners."""
    from go_ibft_tpu.verify import SpeculationCache, SpeculativeVerifier

    r = build_signed_round(4, seed=11)
    src_full = _src(11, 4)
    # Tenant B recognizes only the first two validators: byte-identical
    # seals, different membership -> different verdicts.
    keys = [PrivateKey.from_seed(b"bench-%d-%d" % (11, i)) for i in range(4)]
    src_partial = ECDSABackend.static_validators(
        {k.address: 1 for k in keys[:2]}
    )
    sched = TenantScheduler(window_s=0.001, route="host")
    ha = sched.register("a", src_full)
    hb = sched.register("b", src_partial)
    shared_cache = SpeculationCache()
    spec_a = SpeculativeVerifier(ha, cache=shared_cache, owner="a")
    spec_b = SpeculativeVerifier(hb, cache=shared_cache, owner="b")
    from go_ibft_tpu.crypto.backend import ECDSABackend as _EB
    from go_ibft_tpu.messages.wire import View

    backends = [_EB(k, src_full) for k in keys]
    commits = [
        b.build_commit_message(r.proposal_hash, View(height=1, round=0))
        for b in backends
    ]
    with sched:
        assert spec_a.submit_commit_messages(commits) == 4
        assert spec_b.submit_commit_messages(commits) == 4
        assert spec_a.drain(10.0) and spec_b.drain(10.0)
    from go_ibft_tpu.messages.helpers import extract_committed_seal

    for i, commit in enumerate(commits):
        seal = extract_committed_seal(commit)
        assert (
            spec_a.lookup_seal(
                1, 0, r.proposal_hash, commit.sender, seal.signature
            )
            is True
        )
        expected_b = i < 2  # only the first two are B's members
        assert (
            spec_b.lookup_seal(
                1, 0, r.proposal_hash, commit.sender, seal.signature
            )
            is expected_b
        ), i
    # A's lifecycle reset drops ONLY A's entries.
    spec_a.reset()
    seal0 = extract_committed_seal(commits[0])
    assert (
        spec_a.lookup_seal(
            1, 0, r.proposal_hash, commits[0].sender, seal0.signature
        )
        is None
    )
    assert (
        spec_b.lookup_seal(
            1, 0, r.proposal_hash, commits[0].sender, seal0.signature
        )
        is True
    )
    spec_a.stop()
    spec_b.stop()


@pytest.mark.parametrize(
    "tenants, expect_wait",
    [
        # A validator's scheduler has ONE consensus tenant, and its handle
        # blocks the engine's loop under every request: no company can come,
        # so the flush does not wait out the window (PR 39).
        ([("node", "consensus")], False),
        # An idle read tier never holds the consensus tier back either.
        ([("node", "consensus"), ("proofs", "read")], False),
        # A second consensus tenant that has nothing queued MAY still add to
        # the flush: the window is waited, as before.
        ([("node", "consensus"), ("other", "consensus")], True),
    ],
)
def test_a_flush_does_not_wait_for_company_that_cannot_come(tenants, expect_wait):
    r = build_signed_round(4, seed=11)
    window = 0.4
    sched = TenantScheduler(window_s=window, route="host", calibrate=False)
    handles = [
        sched.register(name, _src(11, 4), priority=priority)
        for name, priority in tenants
    ]
    with sched:
        t0 = time.monotonic()
        mask = handles[0].verify_senders(r.prepares)
        elapsed = time.monotonic() - t0
    assert mask.all()
    if expect_wait:
        assert elapsed >= window * 0.9, f"flushed after {elapsed:.3f}s, before the window"
    else:
        assert elapsed < window / 2, f"waited {elapsed:.3f}s for nobody"


@pytest.mark.parametrize("consensus_registered", [True, False])
def test_a_read_tenant_s_concurrent_callers_still_share_the_window(consensus_registered):
    """The read tier's tenant is called by a pool of workers at once: with
    the consensus tier idle (or none registered) its first request waits the
    window out, and the second caller's lanes ride in the same flush."""
    r = build_signed_round(4, seed=11)
    window = 0.4
    sched = TenantScheduler(window_s=window, route="host", calibrate=False)
    if consensus_registered:
        sched.register("node", _src(11, 4))
    proofs = sched.register("proofs", _src(11, 4), priority="read")
    masks = {}

    def call(name, messages):
        masks[name] = proofs.verify_senders(messages)

    with sched:
        t0 = time.monotonic()
        first = threading.Thread(target=call, args=("first", r.prepares))
        second = threading.Thread(target=call, args=("second", r.prepares))
        first.start()
        time.sleep(window / 4)
        second.start()
        first.join()
        elapsed = time.monotonic() - t0
        second.join()
        stats = sched.stats()
    assert masks["first"].all() and masks["second"].all()
    assert elapsed >= window * 0.9, f"flushed after {elapsed:.3f}s, before the window"
    assert stats["dispatches"] == 1 and stats["coalesced_requests"] == 2
