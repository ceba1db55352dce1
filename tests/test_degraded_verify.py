"""Degraded-mode verify: quarantine bisection + circuit-breaker ladder.

ISSUE 3 acceptance suite: a poison batch (device raising mid-dispatch, a
lane whose packing blows up, outright garbage lanes) must never raise out
of a drain — honest lanes verify, corrupted lanes reject, exactly matching
the sequential reference oracle — and repeated device faults demote the
ladder to host verify, restoring the fast path after cooldown with every
transition visible in ``metrics.summarize``.
"""

import asyncio

import numpy as np
import pytest

from go_ibft_tpu.chaos import ChaoticVerifier, FaultConfig, FaultInjector
from go_ibft_tpu.core import IBFT, BatchingIngress
from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto.backend import ECDSABackend, proposal_hash_of
from go_ibft_tpu.messages.helpers import CommittedSeal, extract_committed_seal
from go_ibft_tpu.messages.wire import Proposal, View
from go_ibft_tpu.utils import metrics
from go_ibft_tpu.verify import (
    CircuitBreaker,
    HostBatchVerifier,
    ResilientBatchVerifier,
)
from go_ibft_tpu.verify.batch import (
    QUARANTINED_LANES_KEY,
    pack_sender_batch,
)
from go_ibft_tpu.verify.pipeline import BREAKER_TRANSITIONS_KEY

from harness import NullLogger


def _signed(n, seed=0, height=1):
    keys = [PrivateKey.from_seed(b"dv-%d-%d" % (seed, i)) for i in range(n)]
    powers = {k.address: 1 for k in keys}
    src = ECDSABackend.static_validators(powers)
    backends = [ECDSABackend(k, src) for k in keys]
    view = View(height=height, round=0)
    phash = proposal_hash_of(Proposal(raw_proposal=b"dv block", round=0))
    prepares = [b.build_prepare_message(phash, view) for b in backends]
    seals = [
        extract_committed_seal(b.build_commit_message(phash, view))
        for b in backends
    ]
    return prepares, seals, phash, src


@pytest.fixture(scope="module")
def hundred():
    return _signed(100)


class _FastRung:
    """Stand-in device rung: strict vectorized packing (so malformed lanes
    raise :class:`MalformedLaneError`) + host crypto for the mask, raising
    a simulated dispatch RuntimeError whenever the batch contains a
    'poison' signature — the lane-tied device fault shape."""

    def __init__(self, src, poison=()):
        self._host = HostBatchVerifier(src)
        self.poison = set(poison)
        self.calls = 0
        self.quarantined = []

    def verify_senders(self, msgs):
        self.calls += 1
        msgs = list(msgs)
        pack_sender_batch(msgs)
        if any(m.signature in self.poison for m in msgs):
            raise RuntimeError("simulated XLA dispatch failure (poison lane)")
        return self._host.verify_senders(msgs)

    def verify_committed_seals(self, proposal_hash, seals, height):
        self.calls += 1
        if any(s.signature in self.poison for s in seals):
            raise RuntimeError("simulated XLA dispatch failure (poison lane)")
        return self._host.verify_committed_seals(proposal_hash, seals, height)

    def quarantine(self, msgs):
        self.quarantined.extend(msgs)


def test_poison_batch_quarantine_100_lanes(hundred):
    """ISSUE 3 acceptance: a 100-lane drain with malformed AND bit-flipped
    AND device-poison lanes verifies all honest lanes, rejects exactly the
    corrupted ones, never raises, and matches the sequential oracle."""
    metrics.reset()
    prepares, _, _, src = hundred
    msgs = [m for m in prepares]

    malformed = (7, 42)
    flipped = (3, 55, 90)
    poison = (13, 77)  # bit-flipped AND the device chokes on their batch
    for i in malformed:
        msgs[i].signature = msgs[i].signature[:30]
    for i in flipped + poison:
        sig = bytearray(msgs[i].signature)
        sig[5] ^= 0xFF
        msgs[i].signature = bytes(sig)

    oracle = HostBatchVerifier(src).verify_senders(msgs)
    corrupted = set(malformed) | set(flipped) | set(poison)
    for i in range(100):
        assert bool(oracle[i]) == (i not in corrupted)

    fast = _FastRung(src, poison={msgs[i].signature for i in poison})
    resilient = ResilientBatchVerifier(fast, validators_for_height=src)
    got = resilient.verify_senders(msgs)  # must not raise

    assert np.array_equal(got, oracle)
    # the malformed lanes were quarantined (and reported to the fast rung)
    assert metrics.get_counter(QUARANTINED_LANES_KEY) >= len(malformed)
    assert {id(m) for m in fast.quarantined} >= {id(msgs[i]) for i in malformed}
    # restore the module fixture's signatures (deterministic re-sign)
    fresh, _, _, _ = _signed(100)
    for i in range(100):
        prepares[i].signature = fresh[i].signature


def test_seal_drain_survives_device_faults(hundred):
    _, seals, phash, src = hundred
    bad = list(seals)
    flipped_sig = bytearray(bad[4].signature)
    flipped_sig[5] ^= 0xFF
    bad[4] = CommittedSeal(signer=bad[4].signer, signature=bytes(flipped_sig))

    oracle = HostBatchVerifier(src).verify_committed_seals(phash, bad, 1)
    fast = _FastRung(src, poison={bad[4].signature})
    resilient = ResilientBatchVerifier(fast, validators_for_height=src)
    got = resilient.verify_committed_seals(phash, bad, 1)
    assert np.array_equal(got, oracle)
    assert not got[4] and got[:4].all() and got[5:].all()


def test_drain_never_raises_even_on_garbage_lanes():
    """A lane no rung can even read (None where a message should be) is
    condemned, not propagated — the drain's no-raise liveness contract."""
    prepares, _, _, src = _signed(3, seed=9)
    msgs = [prepares[0], None, prepares[2]]
    resilient = ResilientBatchVerifier(
        _FastRung(src), validators_for_height=src
    )
    mask = resilient.verify_senders(msgs)
    assert list(mask) == [True, False, True]


def test_breaker_demote_probe_restore_fake_clock():
    metrics.reset()
    now = [0.0]
    brk = CircuitBreaker(
        ("device", "host"), k=2, cooldown_s=10.0, clock=lambda: now[0]
    )
    assert brk.acquire() == (0, False)
    brk.record_fault(0)
    assert brk.level == 0  # k=2: one fault is not enough
    brk.record_fault(0)
    assert brk.level == 1  # demoted

    assert brk.acquire() == (1, False)  # cooldown not elapsed: stay demoted
    now[0] += 10.5
    level, probe = brk.acquire()
    assert (level, probe) == (0, True)
    brk.record_fault(0)  # probe failed: re-demote, cooldown restarts
    assert brk.level == 1
    assert brk.acquire() == (1, False)

    now[0] += 10.5
    level, probe = brk.acquire()
    assert (level, probe) == (0, True)
    brk.record_success(0)  # probe succeeded: fast path restored
    assert brk.level == 0

    # transitions visible in metrics.summarize (ISSUE 3 acceptance)
    summary = metrics.summarize(BREAKER_TRANSITIONS_KEY)
    assert summary is not None and summary["count"] == 2  # demote + restore
    assert metrics.get_counter(("go-ibft", "breaker", "demote")) == 1
    assert metrics.get_counter(("go-ibft", "breaker", "restore")) == 1
    assert metrics.get_counter(("go-ibft", "breaker", "probe_failed")) == 1
    assert metrics.get_gauge(("go-ibft", "breaker", "level")) == 0.0


def test_success_resets_consecutive_fault_count():
    brk = CircuitBreaker(("device", "host"), k=2, cooldown_s=10.0)
    brk.record_fault(0)
    brk.record_success(0)  # healthy drain in between
    brk.record_fault(0)
    assert brk.level == 0  # faults were not consecutive


class _TogglableDevice:
    """Device rung whose health the test flips explicitly."""

    def __init__(self, src):
        self._host = HostBatchVerifier(src)
        self.dead = False
        self.calls = 0

    def verify_senders(self, msgs):
        self.calls += 1
        if self.dead:
            raise RuntimeError("dead device")
        return self._host.verify_senders(msgs)

    def verify_committed_seals(self, proposal_hash, seals, height):
        self.calls += 1
        if self.dead:
            raise RuntimeError("dead device")
        return self._host.verify_committed_seals(proposal_hash, seals, height)


def test_resilient_demotes_then_restores():
    """Dead device -> verdicts still correct (per-lane escalation), breaker
    demotes after k faulted drains, traffic stops touching the device,
    and a cooldown probe restores it once healthy."""
    prepares, _, _, src = _signed(4, seed=3)
    now = [0.0]
    device = _TogglableDevice(src)
    brk = CircuitBreaker(
        ("device", "host", "python"), k=2, cooldown_s=5.0, clock=lambda: now[0]
    )
    resilient = ResilientBatchVerifier(
        device, validators_for_height=src, breaker=brk
    )

    device.dead = True
    assert resilient.verify_senders(prepares).all()  # drain 1: fault
    assert brk.level == 0
    assert resilient.verify_senders(prepares).all()  # drain 2: fault -> demote
    assert brk.level == 1

    calls_before = device.calls
    assert resilient.verify_senders(prepares).all()  # host rung serves
    assert device.calls == calls_before  # device not touched while demoted

    device.dead = False
    now[0] += 5.5
    assert resilient.verify_senders(prepares).all()  # cooldown probe
    assert brk.level == 0  # restored
    assert device.calls > calls_before


def test_full_ladder_reaches_pure_python():
    """Device AND host(native) rungs dead -> the pure-Python rung still
    produces correct verdicts (the bottom of the degradation ladder)."""
    prepares, _, _, src = _signed(2, seed=4)

    class _DeadHost(HostBatchVerifier):
        def verify_senders(self, msgs):
            raise RuntimeError("native library crashed")

        def verify_committed_seals(self, proposal_hash, seals, height):
            raise RuntimeError("native library crashed")

    device = _TogglableDevice(src)
    device.dead = True
    resilient = ResilientBatchVerifier(
        device,
        host=_DeadHost(src),
        validators_for_height=src,
        breaker=CircuitBreaker(("device", "host", "python"), k=100),
    )
    assert resilient.verify_senders(prepares).all()


# -- engine-level acceptance: demote, finalize, restore ----------------------


class _Gossip:
    def __init__(self):
        self.sinks = []

    def transport_for(self, submit):
        gossip = self

        class _T:
            def multicast(self, message):
                for sink in gossip.sinks:
                    sink(message)

        self.sinks.append(submit)
        return _T()


async def test_breaker_engine_demotes_finalizes_restores():
    """ISSUE 3 acceptance: injected device faults -> the pipeline demotes
    to host verify, consensus still finalizes the height, and the breaker
    restores the device path after cooldown, transitions visible in
    metrics.summarize."""
    metrics.reset()
    n = 4
    keys = [PrivateKey.from_seed(b"brk-%d" % i) for i in range(n)]
    powers = {k.address: 1 for k in keys}
    src = ECDSABackend.static_validators(powers)

    dead = FaultInjector(11, FaultConfig(device_error_rate=1.0))
    healthy = FaultInjector(11, FaultConfig())

    gossip = _Gossip()
    nodes = []
    for i, key in enumerate(keys):
        device = ChaoticVerifier(
            _TogglableDevice(src), dead, site=f"verify:{i}"
        )
        resilient = ResilientBatchVerifier(
            device,
            validators_for_height=src,
            breaker=CircuitBreaker(
                ("device", "host", "python"), k=2, cooldown_s=0.25
            ),
        )
        core = IBFT(
            NullLogger(),
            ECDSABackend(key, src),
            None,
            batch_verifier=resilient,
        )
        core.set_base_round_timeout(8.0)
        ingress = BatchingIngress(core.add_messages)
        core.transport = gossip.transport_for(ingress.submit)
        nodes.append((core, ingress, device, resilient))

    async def run_height(h):
        await asyncio.wait_for(
            asyncio.gather(*(core.run_sequence(h) for core, _, _, _ in nodes)),
            60,
        )

    try:
        # Height 1: every device dispatch raises.  Consensus must still
        # finalize (host escalation), and the breakers demote.
        await run_height(1)
        for core, _, _, _ in nodes:
            assert len(core.backend.inserted) == 1
        assert metrics.get_counter(("go-ibft", "breaker", "demote")) >= 1
        assert metrics.get_counter(("go-ibft", "chaos", "device_errors")) >= 1
        demoted = [r for _, _, _, r in nodes if r.breaker.level > 0]
        assert demoted, "at least one ladder should have demoted"

        # Device recovers; wait out the cooldown, then the next height's
        # probe drains restore the fast path.
        for _, _, device, _ in nodes:
            device._injector = healthy
        await asyncio.sleep(0.3)
        await run_height(2)
        for core, _, _, _ in nodes:
            assert len(core.backend.inserted) == 2
        assert metrics.get_counter(("go-ibft", "breaker", "restore")) >= 1
        summary = metrics.summarize(BREAKER_TRANSITIONS_KEY)
        assert summary is not None and summary["count"] >= 2
    finally:
        for _, ingress, _, _ in nodes:
            ingress.close()
        for core, _, _, _ in nodes:
            core.messages.close()


def test_breaker_abort_probe_releases_without_restoring():
    """An aborted probe (the probed rung never ran) must neither restore
    the ladder nor leak the probing flag — the next drain is offered a
    fresh probe immediately."""
    now = [0.0]
    brk = CircuitBreaker(
        ("device", "host", "python"), k=1, cooldown_s=1.0, clock=lambda: now[0]
    )
    brk.record_fault(0)
    brk.record_fault(1)
    assert brk.level == 2
    now[0] += 1.5
    assert brk.acquire() == (1, True)
    brk.abort_probe(1)
    assert brk.level == 2  # no restore on no evidence
    assert brk.acquire() == (1, True)  # probe offered again, not wedged
    brk.record_success(1)
    assert brk.level == 1
    # aborting a non-pending probe is a no-op
    brk.abort_probe(0)
    assert brk.level == 1


@pytest.mark.parametrize("drain", ["verify_senders", "verify_seals_early_exit"])
def test_a_demoted_ladder_releases_a_probe_its_drain_cannot_answer(drain):
    """A ladder demoted off its mesh rung is offered the mesh probe after
    the cooldown; a drain under the mesh cutover consumes that acquisition
    and will not run the mesh.  It must release the probe, not answer it:
    no restore on no evidence, and no wedged ``_probing`` (no probe would
    ever be offered again and the ladder would stay demoted for the life of
    the process).  The next drain big enough for the mesh gets the probe."""
    prepares, seals, phash, src = _signed(8, seed=8)
    now = [0.0]
    brk = CircuitBreaker(
        ("mesh", "device", "host", "python"), k=1, cooldown_s=1.0, clock=lambda: now[0]
    )
    mesh, device = HostBatchVerifier(src), _FastRung(src)
    mesh_calls = []
    for name in ("verify_senders", "verify_seals_early_exit"):
        fn = getattr(mesh, name)
        setattr(mesh, name, lambda *a, _fn=fn, **kw: mesh_calls.append(1) or _fn(*a, **kw))
    resilient = ResilientBatchVerifier(
        device, mesh=mesh, mesh_cutover_lanes=6, validators_for_height=src, breaker=brk
    )
    brk.record_fault(0)
    assert brk.level == 1  # demoted to the single-device rung
    now[0] += 1.5  # cooldown elapsed: the next acquire offers the mesh probe

    def run(n):
        """Every verdict the drain gave is True (an early exit stops at
        the quorum of 6 and leaves the rest unverified)."""
        if drain == "verify_senders":
            return resilient.verify_senders(prepares[:n]).all()
        report = resilient.verify_seals_early_exit(phash, seals[:n], 1)
        return (report.mask == report.verified).all() and report.verified.any()

    assert run(4) and not mesh_calls  # 4 < 6: served one rung down
    assert brk.level == 1  # the mesh did not run: nothing restored
    assert brk.acquire() == (0, True)  # the probe is offered again
    brk.abort_probe(0)
    assert run(8) and mesh_calls  # 8 >= 6: this drain answers it
    assert brk.level == 0
