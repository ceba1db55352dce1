"""MeshBatchVerifier: sharded drains pinned to the sequential oracle.

ISSUE 6 acceptance suite, tier-1 runnable on CPU via the conftest's forced
8-virtual-device mesh (``XLA_FLAGS=--xla_force_host_platform_device_count``):

* sharded verdicts bit-identical to the sequential host oracle at uneven
  lane remainders, on dp in {1, 2, 8} (dp=1 = the transparent degradation
  to DeviceBatchVerifier);
* masked dummy-lane padding: no pad-lane verdict ever leaks into a
  caller-visible mask or a quorum count;
* coalesced multi-drain dispatch: the chunk capacity scales with dp, so a
  multi-height lane set that used to cost several single-device dispatches
  is one sharded launch;
* chaos: malformed lanes quarantine through the sharded route, a faulting
  mesh demotes mesh -> device -> host through the breaker ladder, and the
  PackCache interaction (hits on re-drain, eviction on quarantine) holds.

Real-kernel tests share two compiled shapes — (16 global lanes, dp=2) and
(64 global lanes, dp=8), both 8 lanes per shard with an 8-row table — via
module fixtures; everything structural runs against stub kernels.
"""

import copy

import jax
import numpy as np
import pytest

from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto.backend import ECDSABackend, proposal_hash_of
from go_ibft_tpu.messages.helpers import CommittedSeal, extract_committed_seal
from go_ibft_tpu.messages.wire import Proposal, View
from go_ibft_tpu.parallel import make_mesh, mesh_context
from go_ibft_tpu.utils import metrics
from go_ibft_tpu.verify import (
    CircuitBreaker,
    HostBatchVerifier,
    MeshBatchVerifier,
    ResilientBatchVerifier,
)
from go_ibft_tpu.verify.batch import (
    _BATCH_BUCKETS,
    _lane_count,
    QUARANTINED_LANES_KEY,
    host_quorum_reached,
)


def _signed(n, seed=0, heights=(1,)):
    """n validators; per height: PREPARE envelopes + committed seals."""
    keys = [PrivateKey.from_seed(b"mb-%d-%d" % (seed, i)) for i in range(n)]
    powers = {k.address: 1 for k in keys}
    src = ECDSABackend.static_validators(powers)
    backends = [ECDSABackend(k, src) for k in keys]
    rounds = {}
    for h in heights:
        phash = proposal_hash_of(
            Proposal(raw_proposal=b"mb block %d" % h, round=0)
        )
        view = View(height=h, round=0)
        prepares = [b.build_prepare_message(phash, view) for b in backends]
        seals = [
            extract_committed_seal(b.build_commit_message(phash, view))
            for b in backends
        ]
        rounds[h] = (phash, prepares, seals)
    return src, rounds


def _flip(msg):
    bad = copy.copy(msg)
    sig = bytearray(bad.signature)
    sig[5] ^= 0xFF
    bad.signature = bytes(sig)
    return bad


@pytest.fixture(scope="module")
def eight():
    return _signed(8, seed=1, heights=(1, 2))


@pytest.fixture(scope="module")
def mesh2(eight):
    src, _ = eight
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs 2 virtual devices")
    return MeshBatchVerifier(src, mesh=mesh_context(2, devices=devices[:2]))


@pytest.fixture(scope="module")
def mesh8(eight):
    src, _ = eight
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    return MeshBatchVerifier(src, mesh=mesh_context(8, devices=devices[:8]))


# -- pad math / mesh construction (no XLA dispatch) -------------------------


def test_lane_count_explicit_pad_bypasses_buckets():
    # pad >= n pins the shape exactly — including past the largest bucket
    # (the old packers raised here)
    assert _lane_count(4097, 8192) == 8192
    assert _lane_count(5, 16) == 16
    # no pad: bucket as before
    assert _lane_count(5) == 8
    assert _lane_count(2048) == _BATCH_BUCKETS[-1]
    with pytest.raises(ValueError):
        _lane_count(_BATCH_BUCKETS[-1] + 1)


def test_pad_lanes_bucket_aligned_multiple_of_dp(mesh2):
    assert mesh2.dp == 2
    assert mesh2._pad_lanes(0) == 0
    assert mesh2._pad_lanes(13) == 16  # ceil(13/2)=7 -> bucket 8 -> x2
    assert mesh2._pad_lanes(4096) == 4096  # exactly the dispatch cap
    # chunking keeps every per-dispatch n at or under the cap
    assert mesh2._dispatch_cap == _BATCH_BUCKETS[-1] * 2


def test_pad_lanes_remainder_4097_dp8(eight):
    src, _ = eight
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    mv = MeshBatchVerifier(src, mesh=mesh_context(8, devices=devices[:8]))
    # 4097 lanes on dp=8: ceil = 513 -> bucket 1024 -> 8192 global
    assert mv._pad_lanes(4097) == 8192
    assert mv._dispatch_cap == _BATCH_BUCKETS[-1] * 8


def test_mesh_context_one_device_returns_none():
    assert mesh_context(1, devices=jax.devices()[:1]) is None
    assert mesh_context(devices=jax.devices()[:1]) is None


def test_mesh_context_clamps_to_visible_devices():
    mesh = mesh_context(64, devices=jax.devices())
    assert mesh is not None
    assert mesh.devices.size == len(jax.devices())


def test_degrades_transparently_on_one_device(eight):
    src, rounds = eight
    mv = MeshBatchVerifier(src, devices=jax.devices()[:1])
    assert not mv.sharded and mv.mesh is None
    assert mv._pad_lanes(13) == 0
    assert mv._dispatch_cap == _BATCH_BUCKETS[-1]
    assert mv._route == "device"
    _phash, prepares, _seals = rounds[1]
    oracle = HostBatchVerifier(src).verify_senders(prepares)
    assert np.array_equal(mv.verify_senders(prepares), oracle)


# -- oracle parity through the REAL sharded kernels -------------------------


def test_sharded_sender_parity_uneven_remainder_dp2(eight, mesh2):
    """13 lanes on dp=2 (pads to 16, one dead lane per shard): verdicts
    bit-identical to the sequential oracle, corrupt lane masked."""
    src, rounds = eight
    _phash, prepares, _seals = rounds[1]
    msgs = prepares + prepares[:5]  # 13 lanes from 8 validators
    msgs[3] = _flip(msgs[3])
    oracle = HostBatchVerifier(src).verify_senders(msgs)
    assert not oracle[3] and oracle.sum() == 12
    got = mesh2.verify_senders(msgs)
    assert np.array_equal(got, oracle)
    assert got.shape == (13,)  # pad lanes never reach the caller


def test_sharded_seal_lanes_parity_multi_height_dp2(eight, mesh2):
    """The block-sync shape: one drain, lanes spanning TWO heights' hashes
    (per-lane hash words), uneven remainder, corrupt + foreign lanes."""
    src, rounds = eight
    phash1, _p1, seals1 = rounds[1]
    phash2, _p2, seals2 = rounds[2]
    lanes = [(phash1, s) for s in seals1] + [(phash2, s) for s in seals2[:5]]
    # seal signed for height 2's hash claimed against height 1's: invalid
    lanes[2] = (phash1, seals2[2])
    oracle = HostBatchVerifier(src).verify_seal_lanes(lanes, 1)
    assert not oracle[2] and oracle.sum() == 12
    got = mesh2.verify_seal_lanes(lanes, 1)
    assert np.array_equal(got, oracle)


def test_sharded_mask_equals_single_device_mask_dp2_quorum_on_host(eight, mesh2):
    """Both phases on the mesh route: the sharded masks are the
    single-device program's and the host oracle's, lane for lane, and the
    quorum over them is reduced by the host in exact ints (nothing on the
    mesh holds a power, so there is no collective and no power range)."""
    from go_ibft_tpu.verify import DeviceBatchVerifier

    src, rounds = eight
    phash, prepares, seals = rounds[1]
    msgs = list(prepares)
    msgs[1] = _flip(msgs[1])
    host, single = HostBatchVerifier(src), DeviceBatchVerifier(src)
    oracle = host.verify_senders(msgs)
    assert not oracle[1] and oracle.sum() == 7

    mask = mesh2.verify_senders(msgs)
    assert np.array_equal(mask, single.verify_senders(msgs))
    assert np.array_equal(mask, oracle)
    valid = [m.sender for m, ok in zip(msgs, mask) if ok]
    assert host_quorum_reached(src, valid, 1, None)  # 7 of 8 >= quorum 6
    assert not host_quorum_reached(src, valid[:5], 1, None)

    smask = mesh2.verify_committed_seals(phash, seals, height=1)
    assert np.array_equal(smask, single.verify_committed_seals(phash, seals, 1))
    assert smask.all()
    assert host_quorum_reached(src, [s.signer for s in seals], 1, None)
    assert mesh2._program == "mesh_verify_mask" and single._program == "ecdsa_recover"


def test_sharded_parity_dp8(eight, mesh8):
    """dp=8: 13 lanes pad to 64 (7 dead lanes on most shards) — verdicts
    still bit-identical to the oracle."""
    src, rounds = eight
    _phash, prepares, _seals = rounds[1]
    msgs = prepares + prepares[:5]
    msgs[7] = _flip(msgs[7])
    assert mesh8._pad_lanes(13) == 64
    oracle = HostBatchVerifier(src).verify_senders(msgs)
    got = mesh8.verify_senders(msgs)
    assert np.array_equal(got, oracle)


def test_malformed_lane_quarantine_through_sharded_route(eight, mesh2):
    """A truncated-signature lane raises MalformedLaneError from the pack
    seam of the SHARDED route; the resilient drain quarantines exactly it,
    re-verifies the rest through the real sharded kernels, and reports the
    quarantine to the mesh rung (PackCache eviction hook)."""
    from go_ibft_tpu.verify.batch import pack_sender_batch

    metrics.reset()
    src, rounds = eight
    _phash, prepares, _seals = rounds[1]
    msgs = [copy.copy(m) for m in prepares] + [copy.copy(m) for m in prepares[:5]]
    msgs[4].signature = msgs[4].signature[:30]  # malformed lane
    oracle = HostBatchVerifier(src).verify_senders(msgs)
    assert not oracle[4]

    class _Strict:
        """Strict-packing mesh rung: the vectorized pack runs up front, so
        a malformed lane raises the lane-named error instead of being
        silently well-formed-filtered."""

        def __init__(self, inner):
            self.inner = inner
            self.quarantined = []

        def verify_senders(self, batch):
            pack_sender_batch(list(batch))
            return self.inner.verify_senders(batch)

        def quarantine(self, batch):
            self.quarantined.extend(batch)
            self.inner.quarantine(batch)

    strict = _Strict(mesh2)
    resilient = ResilientBatchVerifier(
        strict,  # single-device rung shares the strict pack seam
        mesh=strict,
        mesh_cutover_lanes=1,
        validators_for_height=src,
    )
    got = resilient.verify_senders(msgs)
    assert np.array_equal(got, oracle)
    assert metrics.get_counter(QUARANTINED_LANES_KEY) >= 1
    assert any(m is msgs[4] for m in strict.quarantined)


def test_pack_cache_hits_on_re_drain(eight, mesh2):
    src, rounds = eight
    _phash, prepares, _seals = rounds[1]
    mesh2.reset_pack_cache()
    cache = mesh2._pack_cache
    mesh2.verify_senders(prepares)
    misses = cache.misses
    mesh2.verify_senders(prepares)  # same objects: packs served from cache
    assert cache.hits >= len(prepares)
    assert cache.misses == misses


# -- structural behavior against stub kernels (no XLA) ----------------------


def _fake_seal_lanes(n, n_heights=3):
    """Shape-valid (hash, seal) lanes without real crypto (packers only
    check lengths)."""
    lanes = []
    for i in range(n):
        h = i % n_heights
        lanes.append(
            (
                bytes([h]) * 32,
                CommittedSeal(
                    signer=bytes([i % 251]) * 20, signature=bytes(65)
                ),
            )
        )
    return lanes


def test_coalesced_multi_drain_dispatch_shapes(eight, mesh2):
    """5000 lanes on dp=2 (cap 4096): exactly TWO sharded dispatches, the
    tail padded to a bucket-aligned dp multiple — where the single-device
    cap would have cost three."""
    calls = []

    def fake_dispatch(inputs, table):
        live = inputs[-1]
        calls.append(int(np.shape(live)[0]))
        return np.asarray(live)

    mv = copy.copy(mesh2)
    mv._dispatch_async = fake_dispatch
    lanes = _fake_seal_lanes(5000)
    mask = mv.verify_seal_lanes(lanes, 1)
    assert calls == [4096, 1024]  # 4096 + (904 -> bucket 512 x 2)
    assert mask.shape == (5000,)
    assert mask.all()  # every LIVE lane "verified"; no pad verdict leaked


def test_pad_lanes_are_dead_in_packed_inputs(mesh2):
    """The pack seam marks every pad lane dead: a 13-lane pack on dp=2 has
    exactly 13 live lanes of 16."""
    from go_ibft_tpu.verify.batch import pack_seal_lanes

    lanes = _fake_seal_lanes(13)
    packed = pack_seal_lanes(lanes, pad_lanes=mesh2._pad_lanes(13))
    live = packed[-1]
    assert live.shape == (16,)
    assert live[:13].all() and not live[13:].any()


class _StubRung:
    """Protocol rung with togglable health + call counting."""

    def __init__(self, src, dead=False):
        self._host = HostBatchVerifier(src)
        self.dead = dead
        self.calls = 0

    def verify_senders(self, msgs):
        self.calls += 1
        if self.dead:
            raise RuntimeError("simulated mesh/XLA dispatch failure")
        return self._host.verify_senders(msgs)

    def verify_committed_seals(self, proposal_hash, seals, height):
        self.calls += 1
        if self.dead:
            raise RuntimeError("simulated mesh/XLA dispatch failure")
        return self._host.verify_committed_seals(proposal_hash, seals, height)


def test_breaker_demotes_mesh_to_device_to_host(eight):
    """k consecutive mesh faults demote to the single-device rung; device
    faults demote again to host — and verdicts stay correct throughout."""
    src, rounds = eight
    _phash, prepares, _seals = rounds[1]
    mesh_rung = _StubRung(src, dead=True)
    device_rung = _StubRung(src)
    now = [0.0]
    brk = CircuitBreaker(
        ("mesh", "device", "host", "python"),
        k=2,
        cooldown_s=60.0,
        clock=lambda: now[0],
    )
    resilient = ResilientBatchVerifier(
        device_rung,
        mesh=mesh_rung,
        mesh_cutover_lanes=1,
        validators_for_height=src,
        breaker=brk,
    )
    assert resilient.verify_senders(prepares).all()  # fault 1 (bisection saves it)
    assert resilient.verify_senders(prepares).all()  # fault 2 -> demote
    assert brk.level == 1 and brk.level_name == "device"

    calls_before = mesh_rung.calls
    assert resilient.verify_senders(prepares).all()
    assert mesh_rung.calls == calls_before  # mesh not touched while demoted
    assert device_rung.calls > 0

    device_rung.dead = True
    assert resilient.verify_senders(prepares).all()
    assert resilient.verify_senders(prepares).all()
    assert brk.level == 2 and brk.level_name == "host"

    # mesh heals; cooldown probes climb back one rung at a time
    mesh_rung.dead = device_rung.dead = False
    now[0] += 61.0
    assert resilient.verify_senders(prepares).all()  # probe device -> restore
    assert brk.level == 1
    now[0] += 61.0
    assert resilient.verify_senders(prepares).all()  # probe mesh -> restore
    assert brk.level == 0


def test_mesh_cutover_routes_small_drains_to_device(eight):
    """Below the lane cutover the mesh rung is skipped entirely (the
    padding + multi-device launch loses); at or above it the mesh serves."""
    src, rounds = eight
    _phash, prepares, _seals = rounds[1]
    mesh_rung = _StubRung(src)
    device_rung = _StubRung(src)
    resilient = ResilientBatchVerifier(
        device_rung,
        mesh=mesh_rung,
        mesh_cutover_lanes=6,
        validators_for_height=src,
    )
    assert resilient.verify_senders(prepares[:4]).all()  # 4 < 6: device rung
    assert mesh_rung.calls == 0 and device_rung.calls == 1
    assert resilient.verify_senders(prepares).all()  # 8 >= 6: mesh rung
    assert mesh_rung.calls == 1 and device_rung.calls == 1


def test_adaptive_mesh_route_and_fallback(eight):
    """AdaptiveBatchVerifier with a mesh: big drains ride the mesh rung; a
    mesh fault falls back (verdict intact) and k faults demote the ladder
    so traffic stops touching the mesh."""
    from go_ibft_tpu.verify import AdaptiveBatchVerifier

    src, rounds = eight
    phash, prepares, seals = rounds[1]
    mesh_rung, device_rung = _StubRung(src), _StubRung(src)
    brk = CircuitBreaker(("mesh", "device", "host", "python"), k=2)
    adaptive = AdaptiveBatchVerifier(
        src,
        cutover_lanes=2,
        device=device_rung,
        mesh=mesh_rung,
        mesh_cutover_lanes=4,
        breaker=brk,
    )
    assert adaptive.verify_senders(prepares).all()
    assert adaptive.verify_committed_seals(phash, seals, 1).all()
    assert mesh_rung.calls == 2 and device_rung.calls == 0  # the mesh served both
    assert adaptive.verify_senders(prepares[:3]).all()  # 3 < 4: single device
    assert mesh_rung.calls == 2 and device_rung.calls == 1

    mesh_rung.dead = True
    assert adaptive.verify_senders(prepares).all()  # fallback verdict intact
    assert adaptive.verify_committed_seals(phash, seals, 1).all()
    assert brk.level >= 1  # k=2 mesh faults demoted the ladder

    calls_before = mesh_rung.calls
    assert adaptive.verify_senders(prepares).all()
    assert mesh_rung.calls == calls_before  # demoted: mesh not touched


def test_sync_client_coalesces_range_through_mesh(eight, mesh2):
    """Block-sync catch-up through a MeshBatchVerifier: a 3-height range
    with a static validator set is exactly ONE sharded drain."""
    from go_ibft_tpu.chain.sync import LoopbackSyncNetwork, SyncClient
    from go_ibft_tpu.chain.wal import FinalizedBlock

    src, rounds = eight
    calls = []
    real_dispatch = type(mesh2)._dispatch_async
    mv = copy.copy(mesh2)

    def counting_dispatch(inputs, table):
        calls.append(int(np.shape(inputs[-1])[0]))
        return real_dispatch(mv, inputs, table)

    mv._dispatch_async = counting_dispatch

    blocks = []
    for h in (1, 2):
        phash, _prepares, seals = rounds[h]
        blocks.append(
            FinalizedBlock(h, Proposal(b"mb block %d" % h, 0), list(seals))
        )

    class _Source:
        def latest_height(self):
            return 2

        def get_blocks(self, start, end):
            return [b for b in blocks if start <= b.height <= end]

    net = LoopbackSyncNetwork()
    net.register(b"peer", _Source())
    client = SyncClient(b"me", net, mv, src)
    got = client.catch_up(1, 2)
    assert [b.height for b in got] == [1, 2]
    # 16 lanes over 2 heights, one validator-set snapshot -> ONE dispatch
    assert calls == [16]


def test_make_mesh_still_validates():
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    with pytest.raises(ValueError):
        make_mesh(8, vp=3, devices=devices)


# -- 4k-lane acceptance (slow tier: compiles a 1024-local-lane program) -----


@pytest.mark.slow
def test_sharded_parity_4k_lanes_uneven_dp8(eight):
    """ISSUE 6 acceptance: 4097 lanes on dp=8 (pads to 8192, 1024 lanes
    per shard) bit-identical to the sequential oracle, malformed lane
    quarantined through the sharded route."""
    src, rounds = eight
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    phash1, _p1, seals1 = rounds[1]
    phash2, _p2, seals2 = rounds[2]
    distinct = [(phash1, s) for s in seals1] + [(phash2, s) for s in seals2]
    lanes = (distinct * 257)[:4097]
    bad = CommittedSeal(signer=seals1[0].signer, signature=bytes(64))  # short
    lanes[1000] = (phash1, bad)

    oracle = HostBatchVerifier(src).verify_seal_lanes(lanes, 1)
    mv = MeshBatchVerifier(src, mesh=mesh_context(8, devices=devices[:8]))
    resilient = ResilientBatchVerifier(
        mv, mesh=mv, mesh_cutover_lanes=1, validators_for_height=src
    )
    got = resilient.verify_seal_lanes(lanes, 1)
    assert np.array_equal(got, oracle)
    assert not got[1000] and got.sum() == 4096
