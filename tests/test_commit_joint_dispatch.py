"""A COMMIT's committed seal rides its envelope's dispatch (ISSUE 32).

The device route's ``verify_senders`` gives every well-formed COMMIT a second
lane (its carried hash, its seal, claimed signer = its sender) in the same
recover dispatch, which runs at twice the envelopes' lane rung where that is
free (else in the drain's next chunk), keeps the seal verdicts under the full
binding (height,
signer, carried hash, seal bytes), and the seal drains look there before
they pack anything.  Held here, on XLA:CPU at small committees, to the
sequential host oracle lane for lane: the envelope mask, the verdicts, the
drains that follow, what a fault leaves behind, and what the trace counts.
"""

import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.committee import Committee, mangle  # noqa: E402

from go_ibft_tpu.crypto import ecdsa as ec  # noqa: E402
from go_ibft_tpu.crypto.backend import encode_signature  # noqa: E402
from go_ibft_tpu.crypto.keccak import keccak256  # noqa: E402
from go_ibft_tpu.messages.helpers import CommittedSeal  # noqa: E402
from go_ibft_tpu.messages.wire import (  # noqa: E402
    CommitMessage,
    IbftMessage,
    MessageType,
    View,
)
from go_ibft_tpu.obs import ledger as cost_ledger  # noqa: E402
from go_ibft_tpu.obs import trace  # noqa: E402
from go_ibft_tpu.utils import metrics  # noqa: E402
from go_ibft_tpu.verify import (  # noqa: E402
    AdaptiveBatchVerifier,
    DeviceBatchVerifier,
    HostBatchVerifier,
    MeshBatchVerifier,
)
from go_ibft_tpu.verify import batch as vbatch  # noqa: E402
from go_ibft_tpu.verify.batch import ResilientBatchVerifier  # noqa: E402

N = 12  # a phase is 11 messages: the 32-lane rung, a 64-lane sender dispatch
HEIGHT = 5


@pytest.fixture(scope="module")
def committee():
    return Committee(N, 32)


@pytest.fixture(scope="module")
def flood(committee):
    """One height's traffic, 4 corrupted peers: 2 bad envelopes (good seals
    behind them), 2 good envelopes around a bad seal."""
    return committee.traffic(HEIGHT, 4)


def _commit(c, addr, phash, seal, height=HEIGHT, sign_as=None):
    msg = IbftMessage(
        view=View(height=height, round=0),
        sender=addr,
        type=MessageType.COMMIT,
        commit_data=CommitMessage(proposal_hash=phash, committed_seal=seal),
    )
    digest = keccak256(msg.encode(include_signature=False))
    msg.signature = encode_signature(*ec.sign(c.key_of[sign_as or addr], digest))
    return msg


def _seals(commits):
    return [
        CommittedSeal(signer=m.sender, signature=m.commit_data.committed_seal)
        for m in commits
    ]


def _recovers(route="device"):
    """``(dispatches, live lanes, padded lanes)`` of the recover program on
    ``route`` since the ledger was enabled."""
    for r in cost_ledger.snapshot()["dispatches"]:
        if r["program"] == "ecdsa_recover" and r["route"] == route:
            return r["dispatches"], r["live_lanes"], r["padded_lanes"]
    return 0, 0, 0


def _dispatches(route="device") -> int:
    return _recovers(route)[0]


def _device(records, name):
    """Args of the device route's ``name`` events, in order."""
    return [r[5] for r in records if r[1] == name and r[5].get("route") == "device"]


def _verdicts(records):
    return [(a["kind"], a["live"], a["rejected"]) for a in _device(records, "verify.verdicts")]


@pytest.fixture(scope="module")
def oracle(committee, flood):
    """The sequential host verifier's masks over the flood's COMMITs and
    their seals (module scope: computed before any test's ledger is on)."""
    host = HostBatchVerifier(committee.src)
    senders = host.verify_senders(flood.commits).tolist()
    seals = host.verify_committed_seals(
        flood.phash, _seals(flood.commits), HEIGHT
    ).tolist()
    assert senders.count(False) == seals.count(False) == 2
    return senders, seals


@pytest.fixture
def ledger():
    cost_ledger.enable()
    try:
        yield
    finally:
        cost_ledger.disable()


@pytest.fixture
def recorder():
    rec = trace.enable()
    try:
        yield rec
    finally:
        trace.disable()


def _mixed_batch(c, t):
    """The flood's COMMITs and PREPAREs interleaved, and beside them: a seal
    by another validator than the sender, a COMMIT that carries (and seals)
    another hash than the accepted one, a seal of the wrong length."""
    a, b, d = (m.sender for m in t.commits[:3])
    other = keccak256(b"another proposal")
    crafted = [
        _commit(c, a, t.phash, c.seal(b, t.phash)),  # b's seal in a's COMMIT
        _commit(c, b, other, c.seal(b, other)),  # valid, for another hash
        _commit(c, d, t.phash, c.seal(d, t.phash)[:64]),  # malformed seal
    ]
    rest, prepares = t.commits[3:], t.prepares[:4]
    batch = []
    for i in range(len(rest)):
        batch += rest[i : i + 1] + prepares[i : i + 1]
    assert len(batch) + len(crafted) == 15
    return batch + crafted, crafted


def test_envelope_mask_and_seal_verdicts_equal_the_host_oracle(committee, flood):
    c, t = committee, flood
    dev, host = DeviceBatchVerifier(c.src), HostBatchVerifier(c.src)
    batch, crafted = _mixed_batch(c, t)
    got = dev.verify_senders(batch)
    want = host.verify_senders(batch)
    assert got.tolist() == want.tolist()
    assert not got.all() and got.any()  # the flood's bad envelopes are in it

    commits = [m for m in batch if m.type == MessageType.COMMIT]
    riders = [m for m in commits if len(m.commit_data.committed_seal) == 65]
    assert len(dev._seal_verdicts) == len(riders) == len(commits) - 1
    for m in riders:  # each verdict is the oracle's, under the hash it carried
        (seal,) = _seals([m])
        carried = m.commit_data.proposal_hash
        (cached,) = dev.cached_seal_verdicts(carried, [seal], HEIGHT)
        (oracle,) = host.verify_committed_seals(carried, [seal], HEIGHT)
        assert cached is not None and cached == bool(oracle), m.sender.hex()
    # Another validator's seal is refused for this sender; the seal over the
    # other hash is valid there and nowhere else.
    assert dev.cached_seal_verdicts(t.phash, _seals(crafted[:1]), HEIGHT) == [False]
    other = crafted[1].commit_data.proposal_hash
    assert dev.cached_seal_verdicts(other, _seals(crafted[1:2]), HEIGHT) == [True]
    assert dev.cached_seal_verdicts(t.phash, _seals(crafted[1:2]), HEIGHT) == [None]

    # The drains over the accepted hash: cached lanes, a lane that carried
    # another hash (a miss: dispatched, refused), a malformed lane.
    seals = _seals(commits)
    oracle = host.verify_committed_seals(t.phash, seals, HEIGHT)
    assert dev.verify_committed_seals(t.phash, seals, HEIGHT).tolist() == oracle.tolist()
    report = dev.verify_seals_early_exit(t.phash, seals, HEIGHT, threshold=len(seals))
    assert report.verified.all() and report.skipped == 0
    assert report.mask.tolist() == oracle.tolist()
    assert report.reached is False


def test_the_digest_program_joins_the_rows_the_parent_joined(
    committee, flood, monkeypatch
):
    """The mixed batch again, through the real programs: the ``zw`` the
    recover launch is handed is, word for word, what the parent built with a
    launch of its own (``concatenate([digest_words(blocks, counts), hz])``
    of eager device arrays), whether the digest program joined the halves or
    (an oversize payload among the envelopes) the host did; the operands are
    the packers' numpy arrays; the mask is still the host oracle's."""
    import jax
    import jax.numpy as jnp

    from go_ibft_tpu.messages.wire import PrePrepareMessage, Proposal
    from go_ibft_tpu.ops import quorum

    c, t = committee, flood
    batch, _ = _mixed_batch(c, t)
    handed = {"digest": [], "zw": []}
    digest, recover = vbatch._digest_kernel, vbatch._recover_kernel

    def noting_digest(*operands):
        assert all(type(a) is np.ndarray for a in operands)
        handed["digest"].append(operands)
        return digest(*operands)

    def noting_recover(zw, *rows):
        handed["zw"].append(zw)
        return recover(zw, *rows)

    monkeypatch.setattr(vbatch, "_digest_kernel", noting_digest)
    monkeypatch.setattr(vbatch, "_recover_kernel", noting_recover)
    host = HostBatchVerifier(c.src)
    parent_digest = jax.jit(quorum.digest_words)

    def parents_rows(blocks, counts, hz):
        zw = parent_digest(jnp.asarray(blocks), jnp.asarray(counts))
        return np.array(jnp.concatenate([zw, jnp.asarray(hz)], axis=0))

    dev = DeviceBatchVerifier(c.src)
    assert dev.verify_senders(batch).tolist() == host.verify_senders(batch).tolist()
    ((blocks, counts, hz),) = handed["digest"]
    (zw,) = handed["zw"]
    assert not isinstance(zw, np.ndarray) and zw.shape == (64, 8)
    assert hz.shape == (32, 8) and hz.any()
    assert np.array_equal(np.asarray(zw), parents_rows(blocks, counts, hz))

    # One envelope over the digest program's largest payload: its digest and
    # the join are the host's, and the rows are the same rows.
    big = IbftMessage(
        view=View(height=HEIGHT, round=0),
        sender=t.commits[0].sender,
        type=MessageType.PREPREPARE,
        preprepare_data=PrePrepareMessage(
            proposal=Proposal(
                raw_proposal=b"\x07" * (vbatch.MAX_DEVICE_PAYLOAD + 1), round=0
            ),
            proposal_hash=t.phash,
        ),
    )
    digest_ = keccak256(big.encode(include_signature=False))
    big.signature = encode_signature(*ec.sign(c.key_of[big.sender], digest_))
    del handed["digest"][:], handed["zw"][:]
    dev = DeviceBatchVerifier(c.src)
    oversize = batch[:5] + [big] + batch[5:]
    got = dev.verify_senders(oversize)
    assert got.tolist() == host.verify_senders(oversize).tolist() and got[5]
    ((blocks, counts),) = handed["digest"]  # no join on the device
    (zw,) = handed["zw"]
    assert type(zw) is np.ndarray and zw.shape == (64, 8)
    want = parents_rows(blocks, counts, zw[32:])
    want[5] = np.frombuffer(digest_, ">u4")[::-1]
    assert zw[32:].any() and np.array_equal(zw, want)


def test_a_seal_drain_after_the_joint_dispatch_dispatches_nothing(
    committee, flood, oracle, ledger, recorder
):
    c, t = committee, flood
    dev = DeviceBatchVerifier(c.src)
    n = len(t.commits)
    carried = metrics.get_counter(vbatch.SEAL_LANES_CARRIED_KEY)
    hits = metrics.get_counter(vbatch.SEAL_VERDICT_HITS_KEY)
    misses = metrics.get_counter(vbatch.SEAL_VERDICT_MISSES_KEY)

    assert dev.verify_senders(t.commits).tolist() == oracle[0]
    # One dispatch, twice the envelopes' rung, each half n live lanes.
    assert _recovers() == (1, 2 * n, 64)
    assert metrics.get_counter(vbatch.SEAL_LANES_CARRIED_KEY) == carried + n

    seals = _seals(t.commits)
    mask = dev.verify_committed_seals(t.phash, seals, HEIGHT)
    report = dev.verify_seals_early_exit(t.phash, seals, HEIGHT, threshold=c.quorum)
    assert _recovers() == (1, 2 * n, 64)  # nothing since the joint one
    assert mask.tolist() == report.mask.tolist() == oracle[1]
    assert report.verified.all() and report.skipped == 0 and report.reached
    assert metrics.get_counter(vbatch.SEAL_VERDICT_HITS_KEY) == hits + 2 * n
    assert metrics.get_counter(vbatch.SEAL_VERDICT_MISSES_KEY) == misses

    # The same flood again: every seal has its verdict, so none rides and
    # the envelopes are alone in their dispatch (the same program, its second
    # half dead).
    dev.verify_senders(t.commits)
    assert _recovers() == (2, 3 * n, 64 + 64)

    records = recorder.snapshot()
    drains = _device(records, "verify.drain")
    assert [
        (a["kind"], a.get("lanes"), a.get("seal_lanes"), a.get("cached"))
        for a in drains
    ] == [
        ("senders", n, n, None),
        ("seals", None, None, n),
        ("senders", n, 0, None),
    ]
    (early,) = _device(records, "verify.early_exit")
    assert (early["lanes"], early["cached"]) == (n, n)
    # Every lane judged is counted once, where it was judged: the seals
    # inside the joint drain, never again on a hit.
    assert _verdicts(records) == [
        ("senders", n, 2),
        ("seals", n, 2),
        ("senders", n, 2),
    ]


def test_a_verdict_is_unreachable_under_any_other_binding(committee, flood, ledger):
    c, t = committee, flood
    dev = DeviceBatchVerifier(c.src)
    dev.verify_senders(t.commits)
    good = next(m for m in t.commits if m.sender not in t.bad)
    (seal,) = _seals([good])
    assert dev.cached_seal_verdicts(t.phash, [seal], HEIGHT) == [True]
    peer = next(m.sender for m in t.commits if m.sender != good.sender)
    for phash, s, height in [
        (t.phash, seal, HEIGHT + 1),
        (keccak256(b"another proposal"), seal, HEIGHT),
        (t.phash, CommittedSeal(signer=peer, signature=seal.signature), HEIGHT),
        (t.phash, CommittedSeal(signer=seal.signer, signature=mangle(seal.signature)), HEIGHT),
    ]:
        assert dev.cached_seal_verdicts(phash, [s], height) == [None]
    # Asked under another hash the drain verifies for itself, and refuses.
    before = _dispatches()
    other = keccak256(b"another proposal")
    assert not dev.verify_committed_seals(other, [seal], HEIGHT).any()
    assert _dispatches() == before + 1


def test_the_cache_stays_under_its_cap_in_a_seal_rewrite_flood(monkeypatch):
    monkeypatch.setattr(vbatch, "_SEAL_VERDICT_CAP", 16)
    cache = vbatch.SealVerdictCache()
    signer, phash = b"\x01" * 20, b"\x02" * 32

    def keys(lo, hi):
        return [(signer, phash, i.to_bytes(65, "big")) for i in range(lo, hi)]

    # One sender rewriting its seal at one height: FIFO inside the height.
    for lo in range(0, 100, 10):
        cache.store(7, keys(lo, lo + 10), [True] * 10)
        assert len(cache) <= 16
    assert cache.lookup(7, keys(84, 100)) == [True] * 16
    assert cache.lookup(7, keys(0, 84)) == [None] * 84
    # A newer height: the older one goes whole before the newer sheds any.
    cache.store(8, keys(0, 10), [False] * 10)
    assert len(cache) == 10 and cache.lookup(7, keys(84, 100)) == [None] * 16
    assert cache.lookup(8, keys(0, 10)) == [False] * 10
    cache.drop_height(8)
    assert len(cache) == 0 and cache.lookup(8, keys(0, 1)) == [None]


def test_a_height_the_table_cache_drops_takes_its_verdicts_with_it(committee):
    c = committee
    dev = DeviceBatchVerifier(c.src, cache_heights=1)
    first, second = c.traffic(1, 0), c.traffic(2, 0)
    dev.verify_senders(first.commits)
    assert len(dev._seal_verdicts) == len(first.commits)
    dev.verify_senders(second.commits)
    assert len(dev._seal_verdicts) == len(second.commits)
    assert set(dev.cached_seal_verdicts(first.phash, _seals(first.commits), 1)) == {None}
    assert set(dev.cached_seal_verdicts(second.phash, _seals(second.commits), 2)) == {True}


def test_a_miss_still_verifies_through_the_seal_drain(
    committee, flood, oracle, ledger, recorder
):
    c, t = committee, flood
    dev = DeviceBatchVerifier(c.src)
    seals = _seals(t.commits)
    n = len(seals)
    # Seals that never rode (their COMMITs came by another route) ...
    assert dev.verify_committed_seals(t.phash, seals, HEIGHT).tolist() == oracle[1]
    assert _recovers() == (1, n, 32)
    # ... and seals whose verdicts were evicted since.
    dev.verify_senders(t.commits)
    dev._seal_verdicts.drop_height(HEIGHT)
    report = dev.verify_seals_early_exit(t.phash, seals, HEIGHT, threshold=n)
    assert report.mask.tolist() == oracle[1] and report.verified.all()
    assert _recovers() == (3, 4 * n, 32 + 64 + 32)
    assert _verdicts(recorder.snapshot()) == [
        ("seals", n, 2),  # the full drain
        ("senders", n, 2),
        ("seals", n, 2),  # rode; dropped afterwards
        ("seals", n, 2),  # judged again by the early exit: a real dispatch
    ]


def test_adaptive_routes_a_drain_by_the_lanes_still_to_verify(
    committee, flood, oracle, ledger
):
    """Nine COMMITs in a device-route flush, two stragglers under the
    cutover on the host: the drain over all eleven takes the nine from the
    verdicts and recovers two on the host, with no device dispatch."""
    c, t = committee, flood
    adaptive = AdaptiveBatchVerifier(c.src, cutover_lanes=4)
    early, late = t.commits[:9], t.commits[9:]
    mask = np.concatenate(
        [adaptive.verify_senders(early), adaptive.verify_senders(late)]
    )
    assert mask.tolist() == oracle[0]
    assert (_recovers("device"), _recovers("host")) == ((1, 18, 64), (1, 2, 2))

    seals = _seals(t.commits)
    assert adaptive.verify_committed_seals(t.phash, seals, HEIGHT).tolist() == oracle[1]
    assert (_recovers("device"), _recovers("host")) == ((1, 18, 64), (2, 4, 4))
    report = adaptive.verify_seals_early_exit(t.phash, seals, HEIGHT, threshold=len(seals))
    assert report.mask.tolist() == oracle[1]
    assert report.verified.all() and report.skipped == 0 and not report.reached
    assert (_recovers("device"), _recovers("host")) == ((1, 18, 64), (3, 6, 6))

    # Quorum inside the cached lanes: the stragglers are skipped, not paid.
    report = adaptive.verify_seals_early_exit(
        t.phash, seals, HEIGHT, threshold=oracle[1][:9].count(True)
    )
    assert report.reached and report.skipped == 2
    assert report.verified.tolist() == [True] * 9 + [False] * 2
    assert (_recovers("device"), _recovers("host")) == ((1, 18, 64), (3, 6, 6))


def _stub_kernels(monkeypatch, mask_fn):
    """The device programs replaced, so that the pack / scatter logic runs
    alone: digests are zeros (joined with ``hz`` as the real program joins
    them), the recover mask is ``mask_fn(live)``.  Returns the ``live`` rows
    the recover launches were handed."""
    handed = []

    def digest(blocks, counts, hz=None):
        zw = np.zeros((blocks.shape[0], 8), dtype=np.uint32)
        return zw if hz is None else np.concatenate([zw, hz])

    def recover(zw, r, s, v, claimed, table, live):
        handed.append(np.array(live))
        return mask_fn(np.array(live))

    monkeypatch.setattr(vbatch, "_digest_kernel", digest)
    monkeypatch.setattr(vbatch, "_recover_kernel", recover)
    return handed


@pytest.mark.parametrize("case", ["malformed_lanes", "rejected_lanes"])
def test_the_joint_layout_keeps_every_lane_at_its_index(
    committee, flood, monkeypatch, case
):
    """Four COMMITs in one 16-lane dispatch (envelopes in lanes 0-7, their
    seals in 8-15).  A malformed envelope never reaches the program and
    stays False at its own index, and a malformed seal does not ride; a lane
    the program refuses is refused at the position its message (its seal:
    its COMMIT) had in the caller's batch."""
    c, t = committee, flood
    commits = [copy.copy(m) for m in t.commits if m.sender not in t.bad][:4]
    dev = DeviceBatchVerifier(c.src)
    if case == "malformed_lanes":
        commits[1].signature = b"\x01" * 10
        commits[2] = _commit(
            c, commits[2].sender, t.phash, commits[2].commit_data.committed_seal[:64]
        )
        handed = _stub_kernels(monkeypatch, lambda live: live)
        want_envelopes = [True, False, True, True]
        want_seals = [True, None, None, True]
        want_live = [True] * 3 + [False] * 5 + [True] * 2 + [False] * 6
    else:

        def refuse(live):
            live[0] = live[8 + 1] = False  # first envelope, second seal
            return live

        handed = _stub_kernels(monkeypatch, refuse)
        want_envelopes = [False, True, True, True]
        want_seals = [True, False, True, True]
        want_live = ([True] * 4 + [False] * 4) * 2
    assert dev.verify_senders(commits).tolist() == want_envelopes
    (live,) = handed
    assert live.tolist() == want_live
    assert dev.cached_seal_verdicts(t.phash, _seals(commits), HEIGHT) == want_seals


@pytest.mark.parametrize("half", ["no_seals", "no_messages"])
def test_an_empty_half_of_a_round_is_no_special_case(
    committee, flood, monkeypatch, half
):
    """PREPAREs alone run in the same joint program with its second half
    dead and leave no verdict; seals whose COMMITs never came are a drain of
    their own at the rung, and no messages at all are no dispatch."""
    c, t = committee, flood
    dev = DeviceBatchVerifier(c.src)

    def refuse_first(live):
        live[0] = False
        return live

    handed = _stub_kernels(monkeypatch, refuse_first)
    if half == "no_seals":
        prepares = t.prepares[:4]
        assert dev.verify_senders(prepares).tolist() == [False, True, True, True]
        (live,) = handed
        assert live.tolist() == [True] * 4 + [False] * 12
        assert len(dev._seal_verdicts) == 0
    else:
        assert dev.verify_senders([]).tolist() == [] and handed == []
        seals = _seals(t.commits[:4])
        mask = dev.verify_committed_seals(t.phash, seals, HEIGHT)
        assert mask.tolist() == [False, True, True, True]
        (live,) = handed
        assert live.tolist() == [True] * 4 + [False] * 4


class _JointFaults(DeviceBatchVerifier):
    """A device on which the seal rows cannot be packed: every joint pack
    raises, and names no message."""

    def _joint_inputs(self, sub, riders, lanes):
        raise RuntimeError("the seal rows do not pack on this device")


def test_a_joint_fault_names_no_message_and_falls_back_to_two_drains(
    committee, flood, oracle, ledger
):
    c, t = committee, flood
    dev, host = _JointFaults(c.src), HostBatchVerifier(c.src)
    n = len(t.commits)
    faults = metrics.get_counter(vbatch.JOINT_FAULTS_KEY)
    got = ResilientBatchVerifier(dev, host=host, validators_for_height=c.src).verify_senders(t.commits)
    assert got.tolist() == oracle[0]
    assert metrics.get_counter(vbatch.JOINT_FAULTS_KEY) == faults + 1
    assert len(dev._seal_verdicts) == 0  # nothing is kept of a faulted dispatch
    assert _recovers() == (1, n, 32) and _recovers("host") == (0, 0, 0)
    seals = _seals(t.commits)
    assert dev.verify_committed_seals(t.phash, seals, HEIGHT).tolist() == oracle[1]
    assert _recovers() == (2, 2 * n, 2 * 32)  # the envelopes, then the seals: the two of before


def test_the_ladder_bisects_a_poisoned_joint_dispatch_to_its_message(
    committee, flood, oracle
):
    c, t = committee, flood
    poison = next(m for m in t.commits if m.sender not in t.bad)

    class Poisoned(DeviceBatchVerifier):
        def _sender_inputs(self, msgs, pad_lanes=0, rows=None, hz=None):
            if any(m is poison for m in msgs):
                raise RuntimeError("this message crashes the device rung")
            return super()._sender_inputs(msgs, pad_lanes, rows, hz)

    dev, host = Poisoned(c.src), HostBatchVerifier(c.src)
    quarantined = metrics.get_counter(vbatch.QUARANTINED_LANES_KEY)
    got = ResilientBatchVerifier(dev, host=host, validators_for_height=c.src).verify_senders(t.commits)
    assert got.tolist() == oracle[0]
    assert got[t.commits.index(poison)]  # verified one rung down, not condemned
    assert metrics.get_counter(vbatch.QUARANTINED_LANES_KEY) == quarantined
    # The halves the ladder re-ran carried their seals where their rung had
    # room; the poisoned message's seal has no verdict, every verdict there
    # is is the oracle's, and the seal drain judges the rest.
    seals = _seals(t.commits)
    known = dev.cached_seal_verdicts(t.phash, seals, HEIGHT)
    assert known[t.commits.index(poison)] is None
    assert any(k is not None for k in known)
    assert all(k is None or k == ok for k, ok in zip(known, oracle[1]))
    assert dev.verify_committed_seals(t.phash, seals, HEIGHT).tolist() == oracle[1]


def test_other_batches_take_the_envelope_only_path(committee, flood, ledger):
    c, t = committee, flood
    dev = DeviceBatchVerifier(c.src)
    dev.verify_senders(t.prepares)  # no COMMIT among them
    malformed = [
        _commit(c, m.sender, t.phash, m.commit_data.committed_seal[:64])
        for m in t.commits[:3]
    ]
    assert dev.verify_senders(malformed).all()  # envelopes fine, no seal to ride
    assert _recovers() == (2, len(t.prepares) + 3, 64 + 16)  # second halves dead
    assert len(dev._seal_verdicts) == 0


@pytest.mark.parametrize(
    "envelopes, half",
    [
        (3, 8),
        (9, 32),
        (32, 32),
        (99, 128),  # a 100-validator flood: 256 lanes, one full tile a row
        (128, 128),
        (129, 0),  # the 512 rung folds already: twice it costs twice
        (299, 0),
        (2048, 0),
        (5000, 0),
    ],
)
def test_a_sender_chunk_runs_at_twice_its_rung_under_the_fold_width(envelopes, half):
    assert DeviceBatchVerifier._joint_lanes(None, envelopes) == half
    assert 2 * half <= vbatch._FOLD_LANES == 256


@pytest.mark.parametrize(
    "validators, lanes",
    [(4, (8, 16)), (24, (32, 64)), (100, (128, 256)), (300, (512,)), (5000, (2048,))],
)
def test_committee_lanes_are_what_a_committee_dispatches(validators, lanes):
    assert vbatch.committee_lanes(validators) == lanes


def test_from_the_fold_width_up_the_seals_are_the_drains_next_chunk(
    monkeypatch, ledger, recorder
):
    """A rung that fills whole tiles costs twice at twice the lanes (a
    300-validator committee's 512): the seals are a second chunk of the
    SAME drain at the envelopes' lane count, and the seal drain still
    dispatches nothing.  Shown at the 32-lane rung, with the fold width
    brought down to it."""
    monkeypatch.setattr(vbatch, "_FOLD_LANES", 32)
    c = Committee(21, 7)
    t = c.traffic(HEIGHT, 4)
    host = HostBatchVerifier(c.src)
    cost_ledger.disable()  # the oracle's own recovers are not the subject
    senders = host.verify_senders(t.commits).tolist()
    oracle = host.verify_committed_seals(t.phash, _seals(t.commits), HEIGHT).tolist()
    cost_ledger.enable()
    dev = DeviceBatchVerifier(c.src)
    assert dev.verify_senders(t.commits).tolist() == senders
    assert _recovers() == (2, 40, 64) and len(dev._seal_verdicts) == 20
    report = dev.verify_seals_early_exit(
        t.phash, _seals(t.commits), HEIGHT, threshold=c.quorum
    )
    assert report.mask.tolist() == oracle and report.verified.all()
    assert _recovers() == (2, 40, 64)
    records = recorder.snapshot()
    (flood,) = _device(records, "verify.drain")
    assert (flood["chunks"], flood["lanes"], flood["seal_lanes"]) == (2, 20, 20)
    (drain,) = _device(records, "verify.early_exit")
    assert drain["cached"] == 20
    assert _verdicts(records) == [("senders", 20, 2), ("seals", 20, 2)]


def test_a_sharded_mesh_keeps_the_envelope_only_path(committee, flood):
    import jax

    from go_ibft_tpu.parallel.mesh import mesh_context

    sharded = MeshBatchVerifier(
        committee.src, mesh=mesh_context(2, devices=jax.devices()[:2])
    )
    assert sharded.sharded and sharded._seal_riders(flood.commits, HEIGHT) == []
    assert sharded._joint_lanes(len(flood.commits)) == 0
    alone = MeshBatchVerifier(committee.src, devices=jax.devices()[:1])
    assert not alone.sharded
    assert alone._seal_riders(flood.commits, HEIGHT) == list(range(len(flood.commits)))
