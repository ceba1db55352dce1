"""The host half of the span contract, and the counts a height implies.

``benchmark/layer_metrics/engine_self_ms.py`` subtracts the union of the
verifier's OUTER spans (``verify.drain`` / ``verify.early_exit``) from an
operation's wall, and the ``verify.pack`` / ``verify.device_wait`` readers
take those children per drain.  Both stand on one rule: every call of a
verifier entry point the engine or ``SyncClient`` makes emits exactly one
outer span, with the children its route owes inside it and on its track,
and one ``verify.verdicts`` instant inside it that counts what the call
decided (lanes with a verdict, lanes refused).
``tests/test_kernel_stages.py`` holds the device half (the ``recover.*``
scopes); this file holds the host half, on both routes, with the device
programs replaced by stubs so that no ladder compiles.

The second half pins what one finalized round-0 height costs in COUNTS on
a loopback cluster (verifier calls, lanes, ingress flushes, and since PR 37
the spans at the engine's seams: one ``engine.finalize``, the node's
``engine.send``s, an ``ingress.wait`` a flush, ``engine.wake`` at most once
a drain, all carrying the height): integers the protocol implies, never
wall times.
"""

import asyncio
import collections
import math

import numpy as np
import pytest

from go_ibft_tpu.bench import build_seal_lane_workload, build_signed_round
from go_ibft_tpu.chain import FinalizedBlock, LoopbackSyncNetwork, SyncClient
from go_ibft_tpu.core import IBFT, BatchingIngress
from go_ibft_tpu.core.validator_manager import calculate_quorum
from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto.backend import ECDSABackend, proposal_hash_of
from go_ibft_tpu.messages.helpers import extract_committed_seal
from go_ibft_tpu.messages.wire import Proposal, View
from go_ibft_tpu.obs import ledger as cost_ledger
from go_ibft_tpu.obs import trace
from go_ibft_tpu.utils import metrics
from go_ibft_tpu.verify import (
    AdaptiveBatchVerifier,
    DeviceBatchVerifier,
    HostBatchVerifier,
    batch,
)
from go_ibft_tpu.verify.batch import (
    EARLY_EXIT_DRAINS_KEY,
    EARLY_EXIT_SKIPPED_KEY,
)
from go_ibft_tpu.verify.mesh_batch import MeshBatchVerifier

# 24 validators: above the 16-lane default cutover, so the default
# ``AdaptiveBatchVerifier`` (what an engine gets) takes the device route
# with the whole round and the host route with a handful of it.
N_VALIDATORS = 24
HOST_LANES = 4

OUTER = ("verify.drain", "verify.early_exit")
CHILDREN = (
    "verify.pack",
    "verify.dispatch",
    "verify.device_wait",
    "verify.quorum",
)
DRAIN_CHILDREN = {name: 1 for name in CHILDREN}


@pytest.fixture
def recorder():
    assert not trace.enabled()
    rec = trace.enable()
    try:
        yield rec
    finally:
        trace.disable()


@pytest.fixture
def stub_kernels(monkeypatch):
    """The two served programs, replaced by shape-true stubs: every live
    lane is valid, every digest is zero.  The seams look the kernels up in
    the module at call time, so nothing of the ladder is traced."""

    def recover(zw, r, s, v, claimed, table, live):
        return np.asarray(live, dtype=bool)

    def digest(blocks, counts, hz=None):
        zw = np.zeros((np.shape(blocks)[0], 8), dtype=np.uint32)
        return zw if hz is None else np.concatenate([zw, hz])

    monkeypatch.setattr(batch, "_recover_kernel", recover)
    monkeypatch.setattr(batch, "_digest_kernel", digest)


@pytest.fixture(scope="module")
def signed_round():
    return build_signed_round(N_VALIDATORS, seed=29)


def _validators(signed):
    return ECDSABackend.static_validators(
        {m.sender: 1 for m in signed.prepares}
    )


def _outer_and_children(rec, outer_name):
    """The one outer span of ``rec`` and the count of each ``verify.*``
    child that lies inside it on its track.  The drain's one
    ``verify.verdicts`` instant is held to the same place."""
    assert rec.dropped == 0
    spans = [r for r in rec.snapshot() if r[0] == "X"]
    _verdicts_of(rec)
    outers = [r for r in spans if r[1] in OUTER]
    assert [r[1] for r in outers] == [outer_name], outers
    (outer,) = outers
    _, _, track, t0, dur, _ = outer
    inside = collections.Counter()
    for _, name, child_track, ts, child_dur, _ in spans:
        if name not in CHILDREN:
            continue
        # Every verify.* child belongs to the drain: same track, and
        # within it (timestamps are whole microseconds, hence the 1).
        assert child_track == track, (name, child_track, track)
        assert t0 <= ts and ts + child_dur <= t0 + dur + 1, (name, outer)
        inside[name] += 1
    return outer, dict(inside)


def _verdicts_of(rec) -> dict:
    """The args of the one ``verify.verdicts`` instant of ``rec``: inside
    the one outer span, on its track, named after its kind and route."""
    (outer,) = [r for r in rec.snapshot() if r[0] == "X" and r[1] in OUTER]
    (verdicts,) = [r for r in rec.snapshot() if r[1] == "verify.verdicts"]
    ph, _, track, ts, _, args = verdicts
    assert ph == "i" and track == outer[2]
    assert outer[3] <= ts <= outer[3] + outer[4] + 1
    assert set(args) == {"kind", "route", "live", "rejected"}
    assert (args["kind"], args["route"]) == (outer[5]["kind"], outer[5]["route"])
    assert 0 <= args["rejected"] <= args["live"]
    return args


def _call(verifier, entry, signed, lanes):
    if entry == "verify_senders":
        return verifier.verify_senders(signed.prepares[:lanes])
    if entry == "verify_committed_seals":
        return verifier.verify_committed_seals(
            signed.proposal_hash, signed.seals[:lanes], signed.height
        )
    if entry == "verify_seal_lanes":
        return verifier.verify_seal_lanes(
            [(signed.proposal_hash, s) for s in signed.seals[:lanes]],
            signed.height,
        )
    report = verifier.verify_seals_early_exit(
        signed.proposal_hash, signed.seals[:lanes], signed.height
    )
    return report.mask[report.verified]


# entry point -> (outer span, children owed on the host route, on the
# device route).  The host early exit is one sequential loop: it owes no
# phases.  The device early exit stops dispatching at quorum and leaves
# the tally to exact host ints outside any ``verify.quorum`` span; 24
# equal-power seals fit its first 32-lane chunk, so one of each.
ENTRY_POINTS = {
    "verify_senders": ("verify.drain", DRAIN_CHILDREN, DRAIN_CHILDREN),
    "verify_committed_seals": ("verify.drain", DRAIN_CHILDREN, DRAIN_CHILDREN),
    "verify_seal_lanes": ("verify.drain", DRAIN_CHILDREN, DRAIN_CHILDREN),
    "verify_seals_early_exit": (
        "verify.early_exit",
        {},
        {"verify.pack": 1, "verify.dispatch": 1, "verify.device_wait": 1},
    ),
}


@pytest.mark.parametrize("route", ["host", "device"])
@pytest.mark.parametrize("entry", list(ENTRY_POINTS))
def test_one_outer_span_with_the_children_its_route_owes(
    entry, route, signed_round, stub_kernels, recorder
):
    outer_name, host_children, device_children = ENTRY_POINTS[entry]
    verifier = AdaptiveBatchVerifier(_validators(signed_round))
    assert HOST_LANES < verifier.cutover <= N_VALIDATORS
    lanes = HOST_LANES if route == "host" else N_VALIDATORS
    mask = _call(verifier, entry, signed_round, lanes)
    outer, inside = _outer_and_children(recorder, outer_name)
    assert outer[5]["route"] == route
    assert inside == (host_children if route == "host" else device_children)
    # The host route really verified; the stub accepts every live lane.
    assert mask.all() and len(mask) > 0
    verdicts = _verdicts_of(recorder)
    assert (verdicts["live"], verdicts["rejected"]) == (lanes, 0)


@pytest.mark.parametrize("entry", list(ENTRY_POINTS))
def test_a_device_dispatch_says_what_it_handed_over(
    entry, signed_round, stub_kernels, recorder
):
    """ISSUE 38: ``verify.dispatch`` on the device route carries
    ``operands``, the host arrays among the six rows the compiled call was
    handed as they are (all six here: the stub's digest rows are a host
    array too); a sender pack whose digest launch joined the riders' hash
    rows behind its own carries ``joined`` (24 PREPAREs run at twice the
    32-lane rung, the second half dead); nothing is placed eagerly."""
    eager = metrics.get_counter(batch.EAGER_PUTS_KEY)
    verifier = AdaptiveBatchVerifier(_validators(signed_round))
    assert _call(verifier, entry, signed_round, N_VALIDATORS).all()
    spans = {r[1]: r[5] for r in recorder.snapshot() if r[0] == "X"}
    assert spans["verify.dispatch"]["route"] == "device"
    assert spans["verify.dispatch"]["operands"] == 6
    pack = spans["verify.pack"]
    if entry == "verify_senders":
        assert (pack["kind"], pack["joined"], pack["templated"]) == (
            "senders",
            32,
            N_VALIDATORS,
        )
    else:
        assert "joined" not in pack
    assert metrics.get_counter(batch.EAGER_PUTS_KEY) == eager


def test_a_device_dispatch_says_which_ladder_its_program_has(
    signed_round, stub_kernels, recorder, monkeypatch
):
    """ISSUE 48: ``ladder`` on ``verify.dispatch`` is the rule the program
    was traced by (``ops/pallas_ladder.py::kind``): the scan off the TPU at
    any width; the kernel on one where the lanes fold, counted."""
    from go_ibft_tpu.ops import pallas_ladder

    key = batch.PALLAS_LADDER_DISPATCHES_KEY
    before = metrics.get_counter(key)
    verifier = AdaptiveBatchVerifier(_validators(signed_round))
    assert _call(verifier, "verify_senders", signed_round, N_VALIDATORS).all()
    (dispatch,) = [r[5] for r in recorder.snapshot() if r[1] == "verify.dispatch"]
    assert dispatch["ladder"] == "scan" and metrics.get_counter(key) == before
    monkeypatch.setattr(pallas_ladder, "supported", lambda: True)
    assert [batch.ladder_of(n) for n in (32, 128, 256, 2048)] == ["scan", "scan", "pallas", "pallas"]
    assert metrics.get_counter(key) == before + 2


def test_a_host_dispatch_hands_nothing_over(signed_round, recorder):
    HostBatchVerifier(_validators(signed_round)).verify_senders(
        signed_round.prepares[:HOST_LANES]
    )
    (dispatch,) = [r[5] for r in recorder.snapshot() if r[1] == "verify.dispatch"]
    assert "operands" not in dispatch and "table_rows" not in dispatch


@pytest.mark.parametrize("validators, rung", [(100, 128), (1000, 2048)])
def test_a_device_dispatch_says_which_table_rung_its_executable_was_built_for(
    validators, rung, signed_round, stub_kernels, recorder
):
    """ISSUE 51: ``table_rows`` on the device route's ``verify.dispatch`` is
    the rung of the validator table the launched executable was built for
    (``table.shape[0]``), not the set's size, which ``chain.sync.verify``
    carries: 128 rows for a 100-validator source, 2,048, the last rung, for a
    1,000-validator one (the kernel stubbed: no ladder compiles)."""
    members = {m.sender: 1 for m in signed_round.prepares}
    for i in range(validators - len(members)):
        members[b"rung" + i.to_bytes(16, "big")] = 1
    assert len(members) == validators
    verifier = DeviceBatchVerifier(ECDSABackend.static_validators(members))
    assert _call(verifier, "verify_seal_lanes", signed_round, N_VALIDATORS).all()
    (dispatch,) = [r[5] for r in recorder.snapshot() if r[1] == "verify.dispatch"]
    assert dispatch["route"] == "device" and dispatch["table_rows"] == rung
    assert set(dispatch) == {"route", "operands", "ladder", "table_rows"}


@pytest.mark.parametrize("entry", list(ENTRY_POINTS))
def test_verdicts_count_the_lanes_a_drain_refused(entry, recorder):
    """Six corrupted validators of 24, host route (real recovers): the
    instant's ``rejected`` is the generator's count of bad lanes among the
    lanes that got a verdict.  The early exit cannot reach quorum on six
    lanes, so it judges all of them."""
    signed = build_signed_round(N_VALIDATORS, corrupt_frac=0.25, seed=31)
    lanes = 6
    bad = signed.expected_prepare_mask[:lanes]
    assert (bad == signed.expected_seal_mask[:lanes]).all()
    want = lanes - int(bad.sum())
    assert 0 < want < lanes  # the seed puts both kinds among the first six
    mask = _call(HostBatchVerifier(_validators(signed)), entry, signed, lanes)
    assert int(mask.sum()) == lanes - want
    verdicts = _verdicts_of(recorder)
    assert (verdicts["live"], verdicts["rejected"]) == (lanes, want)


def test_no_verdict_instant_when_tracing_is_off(signed_round, monkeypatch):
    """``--trace 0`` pays one predicate: the counting is behind it."""
    assert not trace.enabled()

    def instant(*a, **k):
        raise AssertionError("verify.verdicts built with tracing off")

    monkeypatch.setattr(trace, "instant", instant)
    for entry in ENTRY_POINTS:
        _call(
            HostBatchVerifier(_validators(signed_round)),
            entry,
            signed_round,
            HOST_LANES,
        )


def test_chunked_device_drain_is_one_span_with_a_set_of_phases_a_chunk(
    stub_kernels, recorder
):
    """A drain above the largest lane bucket is still ONE ``verify.drain``
    (what ``engine_self_ms`` subtracts once): a pack, a dispatch, a wait and
    (the sync drain is a stream since PR 53: a chunk's verdicts are
    assembled as it is read back) a ``verify.quorum`` per chunk; and the
    pipeline has packed the second chunk before it waits for the first.
    The list call is the stream consumed: ``streamed`` false, ``chunks``
    noted when the drain ends."""
    cap = batch._BATCH_BUCKETS[-1]
    w = build_seal_lane_workload(2 * cap + 4, n_validators=N_VALIDATORS)
    verifier = DeviceBatchVerifier(w.validators)
    mask = verifier.verify_seal_lanes(w.lanes, w.height)
    assert mask.all() and len(mask) == 2 * cap + 4
    outer, inside = _outer_and_children(recorder, "verify.drain")
    assert inside == {
        "verify.pack": 3,
        "verify.dispatch": 3,
        "verify.device_wait": 3,
        "verify.quorum": 3,
    }
    assert outer[5] == {
        "route": "device", "kind": "seal_lanes", "streamed": False, "chunks": 3
    }
    starts = collections.defaultdict(list)
    for _, name, _, ts, _, _ in recorder.snapshot():
        starts[name].append(ts)
    assert sorted(starts["verify.pack"])[1] <= min(starts["verify.device_wait"])


def test_mesh_route_owes_the_same_phases_inside_a_shard_span(
    stub_kernels, recorder
):
    w = build_seal_lane_workload(64, n_validators=N_VALIDATORS)
    verifier = MeshBatchVerifier(w.validators, dp=4)
    assert verifier.sharded

    def mask_kernel(zw, r, s, v, claimed, table, live):
        return np.asarray(live, dtype=bool)

    verifier._mask_kernel = mask_kernel
    eager = metrics.get_counter(batch.EAGER_PUTS_KEY)
    mask = verifier.verify_seal_lanes(w.lanes, w.height)
    assert mask.all() and len(mask) == 64
    outer, inside = _outer_and_children(recorder, "verify.drain")
    assert outer[5]["route"] == "mesh"
    assert inside == DRAIN_CHILDREN
    # The sharded route still places its six rows itself, and says so.
    assert metrics.get_counter(batch.EAGER_PUTS_KEY) == eager + 6
    (dispatch,) = [r[5] for r in recorder.snapshot() if r[1] == "verify.dispatch"]
    assert dispatch["route"] == "mesh" and "operands" not in dispatch
    shards = [r for r in recorder.snapshot() if r[1] == "verify.shard"]
    assert len(shards) == 1 and shards[0][5]["devices"] == 4


def test_catch_up_on_the_host_route_is_one_drain_a_validator_set(recorder):
    """``SyncClient.catch_up`` with a static validator set hands the whole
    range to ONE ``verify_seal_lanes`` call (the host oracle offers no
    stream: ``streamed`` false): one ``chain.sync.verify`` span holding one
    ``verify.drain`` (``sync_host_ms_per_call`` is the call's wall minus
    exactly that), with the lanes made in front of it
    (``chain.sync.produce``) and ONE tally behind it, the masks having come
    at once."""
    heights = 3
    keys = [PrivateKey.from_seed(b"span-sync-%d" % i) for i in range(4)]
    src = ECDSABackend.static_validators({k.address: 1 for k in keys})
    backends = [ECDSABackend(k, src) for k in keys]
    blocks = []
    for h in range(1, heights + 1):
        proposal = Proposal(raw_proposal=b"span block %d" % h, round=0)
        commits = [
            b.build_commit_message(proposal_hash_of(proposal), View(h, 0))
            for b in backends
        ]
        blocks.append(
            FinalizedBlock(h, proposal, [extract_committed_seal(c) for c in commits])
        )

    class _Source:
        def latest_height(self):
            return heights

        def get_blocks(self, start, end):
            return blocks[start - 1 : end]

    network = LoopbackSyncNetwork()
    network.register(b"peer", _Source())
    client = SyncClient(b"me", network, HostBatchVerifier(src), src)
    assert len(client.catch_up(1, heights)) == heights
    outer, inside = _outer_and_children(recorder, "verify.drain")
    assert outer[5]["lanes"] == heights * len(keys)
    assert inside == DRAIN_CHILDREN
    (sync,) = [r for r in recorder.snapshot() if r[1] == "chain.sync.verify"]
    assert sync[5] == {
        "lanes": heights * len(keys),
        "heights": heights,
        "drains": 1,
        "sets": 1,  # one address set, four rows
        "table_rows": len(keys),
        "streamed": False,
    }
    assert sync[3] <= outer[3] and outer[3] + outer[4] <= sync[3] + sync[4] + 1
    # The lanes are made before the drain, the tally has a span of its own
    # after it, both inside the call (equal votes: no power needs a big
    # integer).
    (produce,) = [r for r in recorder.snapshot() if r[1] == "chain.sync.produce"]
    assert produce[5] == {"blocks": heights, "lanes": heights * len(keys)}
    assert sync[3] <= produce[3] and produce[3] + produce[4] <= outer[3] + 1
    (tally,) = [r for r in recorder.snapshot() if r[1] == "chain.sync.tally"]
    assert tally[5] == {"blocks": heights, "bigint": False}
    assert outer[3] + outer[4] <= tally[3] + 1
    assert tally[3] + tally[4] <= sync[3] + sync[4] + 1


def test_catch_up_across_two_validator_sets_counts_what_it_did(recorder):
    """A range whose heights select two address sets, on the device route:
    ONE ``chain.sync.verify`` (``drains`` 1, ``sets`` 2, ``table_rows`` the
    union's, ``streamed``: the device verifier's stream), ONE streamed
    ``verify.drain`` of one chunk, so one ``chain.sync.tally`` (``bigint``:
    a stake in wei) INSIDE it, and the counters: one table upload for the
    union however often the range comes, the lanes validly signed out of
    their own height's set, no regrouping (that is for a range wider than
    the largest table), a streamed run a call."""
    from go_ibft_tpu.chain.sync import SYNC_REGROUPED_KEY, SYNC_STREAMED_KEY
    from go_ibft_tpu.verify import DeviceBatchVerifier

    keys = [PrivateKey.from_seed(b"span-pos-%d" % i) for i in range(5)]
    epochs = [
        {k.address: 10**18 + i for i, k in enumerate(keys)},
        {k.address: 10**18 + i for i, k in enumerate(keys[:4])},
    ]
    src = lambda height: epochs[(height - 1) // 2]  # noqa: E731
    blocks = []
    for h in range(1, 5):
        proposal = Proposal(raw_proposal=b"span block %d" % h, round=0)
        seals = [
            extract_committed_seal(
                ECDSABackend(k, src).build_commit_message(
                    proposal_hash_of(proposal), View(h, 0)
                )
            )
            for k in keys
        ]
        blocks.append(FinalizedBlock(h, proposal, seals))

    class _Source:
        def latest_height(self):
            return 4

        def get_blocks(self, start, end):
            return blocks[start - 1 : end]

    network = LoopbackSyncNetwork()
    network.register(b"peer", _Source())
    client = SyncClient(b"me", network, DeviceBatchVerifier(src), src)
    before = {
        key: metrics.get_counter(key)
        for key in (
            batch.TABLE_UPLOADS_KEY,
            batch.SYNC_OUT_OF_SET_LANES_KEY,
            SYNC_REGROUPED_KEY,
            SYNC_STREAMED_KEY,
            batch.STREAM_FALLBACKS_KEY,
        )
    }
    for _ in range(2):
        assert len(client.catch_up(1, 4)) == 4
    moved = {key[-1]: metrics.get_counter(key) - was for key, was in before.items()}
    # keys[4] sealed heights 3 and 4, where it is no validator: twice two lanes.
    assert moved == {
        "table_uploads": 1,
        "sync_out_of_set_lanes": 4,
        "sync_regrouped": 0,
        "sync_streamed": 2,
        "stream_fallbacks": 0,
    }
    records = [r for r in recorder.snapshot() if r[0] == "X"]
    syncs = [r for r in records if r[1] == "chain.sync.verify"]
    assert [r[5] for r in syncs] == [
        {
            "lanes": 20,
            "heights": 4,
            "drains": 1,
            "sets": 2,
            "table_rows": 5,
            "streamed": True,
        }
    ] * 2
    tallies = [r for r in records if r[1] == "chain.sync.tally"]
    assert [r[5] for r in tallies] == [{"blocks": 4, "bigint": True}] * 2
    drains = [r for r in records if r[1] == "verify.drain"]
    assert [r[5] for r in drains] == [
        {"route": "device", "kind": "seal_lanes", "streamed": True, "chunks": 1}
    ] * 2
    produced = [r for r in records if r[1] == "chain.sync.produce"]
    assert [r[5] for r in produced] == [{"blocks": 4, "lanes": 20}] * 2
    # Made and tallied INSIDE the drain, which lies inside the call.
    for sync, drain, made, tally in zip(syncs, drains, produced, tallies):
        assert sync[3] <= drain[3] <= made[3]
        assert made[3] + made[4] <= tally[3] + 1
        assert tally[3] + tally[4] <= drain[3] + drain[4] + 1
        assert drain[3] + drain[4] <= sync[3] + sync[4] + 1


# ---------------------------------------------------------------------------
# Counts per finalized height (host route, round 0, loopback, message delay 0)
# ---------------------------------------------------------------------------


class _NullLogger:
    def info(self, *a):
        pass

    debug = error = info


HEIGHTS = 2


def _run_cluster(n: int) -> dict:
    """``n`` engines on a loopback multicast, each behind its own
    ``BatchingIngress`` and a ``HostBatchVerifier``; returns what
    ``HEIGHTS`` finalized heights cost in counts, summed over the nodes."""
    keys = [PrivateKey.from_seed(b"span-%d-%d" % (n, i)) for i in range(n)]
    src = ECDSABackend.static_validators({k.address: 1 for k in keys})
    nodes = []
    flush_sizes = collections.defaultdict(list)

    class _Transport:
        def multicast(self, message):
            for _, ingress in nodes:
                ingress.submit(message)

    for i, key in enumerate(keys):
        core = IBFT(
            _NullLogger(),
            ECDSABackend(key, src),
            _Transport(),
            batch_verifier=HostBatchVerifier(src),
        )
        core.set_base_round_timeout(30.0)

        def add_messages(batch_, core=core, i=i):
            flush_sizes[i].append(len(batch_))
            core.add_messages(batch_)

        ingress = BatchingIngress(add_messages)
        # The counts below are those of a host that keeps up: a 16-lane host
        # flush that a loaded test box stretches past the held-back threshold
        # would make the next flush wait ``max_delay`` and take the
        # PREPREPARE together with the first PREPAREs.
        ingress.HELD_BACK_FACTOR = math.inf
        nodes.append((core, ingress))

    async def heights():
        try:
            for h in range(1, HEIGHTS + 1):
                await asyncio.wait_for(
                    asyncio.gather(*(c.run_sequence(h) for c, _ in nodes)), 60
                )
        finally:
            for core, ingress in nodes:
                ingress.close()
                core.messages.close()

    metrics.reset()
    cost_ledger.enable()
    assert not trace.enabled()
    ring = trace.enable()
    try:
        asyncio.run(heights())
        rows = cost_ledger.snapshot()["dispatches"]
    finally:
        cost_ledger.disable()
        trace.disable()
    assert ring.dropped == 0
    out = {
        "rows": rows,
        "records": ring.snapshot(),
        "tracks": [core._obs_track for core, _ in nodes],
        "flush_sizes": dict(flush_sizes),
        "early_exit_drains": metrics.get_counter(EARLY_EXIT_DRAINS_KEY),
        "early_exit_skipped": metrics.get_counter(EARLY_EXIT_SKIPPED_KEY),
        "rounds": {
            proposal.round
            for core, _ in nodes
            for proposal, _seals in core.backend.inserted
        },
        "inserted": [len(core.backend.inserted) for core, _ in nodes],
    }
    metrics.reset()
    return out


@pytest.fixture(scope="module", params=[4, 16])
def cluster_counts(request):
    return request.param, _run_cluster(request.param)


def test_verifier_calls_and_lanes_per_height(cluster_counts):
    """A node's round-0 height is FOUR verifier calls: the PREPREPARE's
    sender (1 lane), the other nodes' PREPAREs (n - 1: the proposer sends
    none), every node's COMMIT envelope (n), and the committed seals,
    which the host early exit stops at quorum (q lanes)."""
    n, counts = cluster_counts
    assert counts["inserted"] == [HEIGHTS] * n
    assert counts["rounds"] == {0}
    (row,) = counts["rows"]
    assert (row["program"], row["route"]) == ("ecdsa_recover", "host")
    node_heights = n * HEIGHTS
    assert row["dispatches"] == 4 * node_heights
    lanes = 1 + (n - 1) + n + calculate_quorum(n)
    assert row["live_lanes"] == row["padded_lanes"] == lanes * node_heights
    # One early-exit drain a node a height; it skips the seals past quorum.
    assert counts["early_exit_drains"] == node_heights
    assert counts["early_exit_skipped"] == (n - calculate_quorum(n)) * node_heights


def test_ingress_flushes_per_height(cluster_counts):
    """Message delay 0 on a loopback: each phase reaches a node within one
    event-loop turn, so its ingress flushes THREE times a height, with the
    PREPREPARE, the n - 1 PREPAREs and the n COMMITs."""
    n, counts = cluster_counts
    assert len(counts["flush_sizes"]) == n
    for sizes in counts["flush_sizes"].values():
        assert sizes == [1, n - 1, n] * HEIGHTS


# -- the spans at the engine's seams (PR 37) --------------------------------

ENGINE_SPANS = (
    "proposal.drain",
    "prepare.drain",
    "commit.drain",
    "engine.wake",
    "engine.send",
    "engine.teardown",
    "engine.finalize",
)
DRAINS = ("proposal.drain", "prepare.drain", "commit.drain")


def _by_track_and_height(counts):
    """{track: {height: Counter(span name)}} over the engine's spans."""
    out = collections.defaultdict(lambda: collections.defaultdict(collections.Counter))
    for ph, name, track, _ts, _dur, args in counts["records"]:
        if ph == "X" and name in ENGINE_SPANS:
            out[track][args["height"]][name] += 1
    return out


def test_engine_spans_per_height(cluster_counts):
    """A node's round-0 height: ONE ``engine.finalize`` (the idle report's
    count of heights) after one ``engine.teardown``; ``engine.send`` for its
    PREPARE and its COMMIT, TWO, but for the proposer, who sends no PREPARE;
    at least one drain a phase, and never more wake-ups than drains."""
    n, counts = cluster_counts
    spans = _by_track_and_height(counts)
    assert sorted(spans) == sorted(counts["tracks"])
    sends = []
    for track in counts["tracks"]:
        assert sorted(spans[track]) == list(range(1, HEIGHTS + 1))
        for height, names in spans[track].items():
            assert names["engine.finalize"] == 1, (track, height, names)
            assert names["engine.teardown"] == 1
            assert names["engine.send"] in (1, 2)
            sends.append(names["engine.send"])
            drains = sum(names[d] for d in DRAINS)
            # The proposer builds its proposal: it drains none.
            proposer = names["engine.send"] == 1
            assert (names["proposal.drain"] == 0) is proposer
            assert names["prepare.drain"] >= 1 and names["commit.drain"] >= 1
            assert 1 <= names["engine.wake"] <= drains, (track, height, names)
    # One proposer a height.
    assert sorted(sends) == [1] * HEIGHTS + [2] * (n - 1) * HEIGHTS
    types = collections.Counter(
        args["type"]
        for ph, name, _t, _ts, _d, args in counts["records"]
        if ph == "X" and name == "engine.send"
    )
    assert types == {1: (n - 1) * HEIGHTS, 2: n * HEIGHTS}  # PREPARE, COMMIT


def test_every_engine_span_lies_in_the_sequence_of_the_height_it_carries(
    cluster_counts,
):
    _, counts = cluster_counts
    window = {}  # (track, height) -> [sequence.start, sequence.done]
    for ph, name, track, ts, _dur, args in counts["records"]:
        if name == "sequence.start":
            window[(track, args["height"])] = [ts, None]
        elif name == "sequence.done":
            window[(track, args["height"])][1] = ts
    checked = 0
    for ph, name, track, ts, dur, args in counts["records"]:
        if ph != "X":
            continue
        if name in ENGINE_SPANS:
            lo, hi = window[(track, args["height"])]
            assert lo <= ts and ts + dur <= hi + 1, (name, args)
            checked += 1
        elif name in ("ingress.batch", "engine.store"):
            # The engine's height when the burst came: a burst of the NEXT
            # height that reaches a node still finalizing carries this one.
            assert 1 <= args["height"] <= HEIGHTS and track in counts["tracks"]
        elif name in ("ingress.wait", "ingress.flush"):
            # The ingress knows no engine: the height of the burst's first
            # message.
            assert 1 <= args["height"] <= HEIGHTS
    assert checked >= 8 * len(counts["tracks"]) * HEIGHTS


def test_an_ingress_wait_a_flush_with_its_why(cluster_counts):
    """Every flush ends one ``ingress.wait`` that began with the burst's
    first ``submit``: same lanes, same height, closed where the flush opens.
    A host that keeps up never waits ``held_back`` (the fixture switches
    the rule off), and a default ingress never cuts."""
    n, counts = cluster_counts
    spans = [r for r in counts["records"] if r[0] == "X"]
    waits = sorted((r for r in spans if r[1] == "ingress.wait"), key=lambda r: r[3] + r[4])
    flushes = sorted((r for r in spans if r[1] == "ingress.flush"), key=lambda r: r[3])
    assert len(waits) == len(flushes) == 3 * n * HEIGHTS
    for wait, flush in zip(waits, flushes):
        assert wait[5]["lanes"] == flush[5]["lanes"]
        assert wait[5]["height"] == flush[5]["height"]
        assert wait[5]["why"] in ("soon", "window")
        assert wait[3] + wait[4] <= flush[3] + 1
    assert sorted(w[5]["lanes"] for w in waits) == sorted(
        [1, n - 1, n] * n * HEIGHTS
    )


# ---------------------------------------------------------------------------
# the deployed node's round-0 height (PR 39): the WAL's and the scheduler's
# spans, at four validators on the host route, over real sockets
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def node_heights():
    """Two heights of an embedded ``ValidatorNode`` (``route = "auto"``: at
    four validators every flush is under the cutover), its three peers on
    loopback sockets, as the benchmark's driver ``node`` runs them."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_node_deployment_reference import node_run

    import shutil

    st = node_run(4, 39, "auto")
    shutil.rmtree(st.data_dir, ignore_errors=True)
    assert st.checked is True, st.info.get("guarantee_broken")
    return [r for r in st.records if r[0] == "X"]


def _end(record) -> int:
    return record[3] + record[4]


def test_a_node_height_appends_its_lock_then_its_finalize_inside_engine_finalize(
    node_heights,
):
    """``wal.append`` twice a height: ``kind`` lock when the PREPARE quorum
    lands, then ``kind`` finalize, which is over (fsynced: ``fsync_ms``)
    before ``engine.finalize`` returns."""
    appends = [r for r in node_heights if r[1] == "wal.append"]
    assert [(r[5]["kind"], r[5]["height"]) for r in appends] == [
        (k, h) for h in range(1, HEIGHTS + 1) for k in ("lock", "finalize")
    ]
    for h in range(1, HEIGHTS + 1):
        lock, finalize = (r for r in appends if r[5]["height"] == h)
        (outer,) = [
            r for r in node_heights if r[1] == "engine.finalize" and r[5]["height"] == h
        ]
        assert _end(lock) <= outer[3]  # locked before the height was final
        assert outer[3] <= finalize[3] and _end(finalize) <= _end(outer) + 1
        for record in (lock, finalize):
            assert 0 < record[5]["fsync_ms"] * 1e3 <= record[4] + 1


def test_a_node_never_dispatches_more_often_than_its_handle_queued(node_heights):
    """One ``sched.queue`` a request of the engine's handle, from the enqueue
    to the flush that takes it; a flush is one ``sched.dispatch``, which
    starts when the last of its requests has left the queue and carries
    their lanes."""
    queued = sorted((r for r in node_heights if r[1] == "sched.queue"), key=_end)
    dispatched = sorted((r for r in node_heights if r[1] == "sched.dispatch"), key=lambda r: r[3])
    assert 0 < len(dispatched) <= len(queued)
    assert sum(r[5]["lanes"] for r in queued) == sum(r[5]["lanes"] for r in dispatched)
    assert {r[5]["kind"] for r in queued} == {"senders", "seals"}
    assert all(r[5]["tier"] == "consensus" and r[5]["requests"] >= 1 for r in queued)
    # One tenant whose loop blocks under every request: a flush takes one.
    assert len(dispatched) == len(queued)
    for wait, flush in zip(queued, dispatched):
        assert _end(wait) <= flush[3] + 1 and wait[5]["lanes"] == flush[5]["lanes"]
    # Four verifier calls a height at least: the PREPREPARE, the PREPAREs,
    # the COMMITs' envelopes, their seals (more where the sockets spread a
    # phase over several flushes).
    assert len(queued) >= 4 * HEIGHTS


# -- the scheduler's own drain (ISSUE 54) ----------------------------------


@pytest.mark.parametrize("kind", ["senders", "seal_lanes"])
def test_a_device_route_flush_of_the_scheduler_names_every_part_of_itself(
    kind, signed_round, recorder, monkeypatch
):
    """``sched.coalesce`` holds ``sched.dispatch`` and, behind it,
    ``sched.complete``; ``sched.dispatch`` holds the dispatcher's
    ``verify.pack`` (rows AND claimed-address table: ``kind``, ``lanes``,
    ``table_rows``), ``verify.dispatch`` and the blocking read-back as
    ``verify.device_wait``: a flush's wall is the sum of its children."""
    from go_ibft_tpu.sched import TenantScheduler, dispatch

    monkeypatch.setattr(
        dispatch, "RECOVER_KERNEL", lambda zw, r, s, v, claimed, table, live: np.asarray(live, dtype=bool)
    )
    monkeypatch.setattr(
        dispatch, "DIGEST_KERNEL", lambda blocks, counts: np.zeros((np.shape(blocks)[0], 8), dtype=np.uint32)
    )
    sched = TenantScheduler(window_s=0.002, route="device")
    handle = sched.register("only", _validators(signed_round))
    with sched:
        if kind == "senders":
            mask = handle.verify_senders(signed_round.prepares)
        else:
            mask = handle.verify_seal_lanes(
                [(signed_round.proposal_hash, s) for s in signed_round.seals], signed_round.height
            )
    assert mask.all() and len(mask) == N_VALIDATORS
    assert recorder.dropped == 0
    spans = {}
    for ph, name, track, ts, dur, args in recorder.snapshot():
        if ph == "X" and name.startswith(("sched.", "verify.")):
            assert name not in spans, name  # one of each a flush
            spans[name] = (track, ts, ts + dur, args)
    assert set(spans) == {
        "sched.queue", "sched.coalesce", "sched.dispatch", "sched.complete",
        "verify.pack", "verify.dispatch", "verify.device_wait",
    }

    def inside(child, parent):
        (track, t0, t1, _), (ptrack, p0, p1, _) = spans[child], spans[parent]
        return track == ptrack and p0 <= t0 and t1 <= p1 + 1

    for child in ("sched.dispatch", "sched.complete"):
        assert inside(child, "sched.coalesce"), child
    for child in ("verify.pack", "verify.dispatch", "verify.device_wait"):
        assert inside(child, "sched.dispatch"), child
    order = ["verify.pack", "verify.dispatch", "verify.device_wait", "sched.complete"]
    assert [spans[n][1] for n in order] == sorted(spans[n][1] for n in order)
    assert spans["verify.pack"][3] == {"kind": kind, "lanes": N_VALIDATORS, "table_rows": 128}
    assert spans["verify.dispatch"][3]["table_rows"] == 128
    assert spans["verify.dispatch"][3]["route"] == spans["verify.device_wait"][3]["route"] == "device"
    assert spans["sched.complete"][3] == {"requests": 1, "lanes": N_VALIDATORS}
    coalesce = spans["sched.coalesce"][3]
    assert (coalesce["tenants"], coalesce["requests"], coalesce["lanes"]) == (1, 1, N_VALIDATORS)


def test_two_flushes_in_flight_name_every_part_of_themselves_once_each_on_a_row_each(
    signed_round, recorder, monkeypatch
):
    """Three one-request flushes queued before the first launch (ISSUE 55):
    the loop launches each ahead of the read-back of the one before.  Every
    flush still emits each of its six spans once, with the single flush's
    args plus ``ahead`` on ``sched.coalesce``, nested as the single flush's
    are; two flushes that overlap in time lie on a row each, so no span of
    one is a child (same row, inside it) of the other's."""
    import threading
    import time

    from go_ibft_tpu.sched import TenantScheduler, dispatch

    monkeypatch.setattr(
        dispatch, "RECOVER_KERNEL", lambda zw, r, s, v, claimed, table, live: np.asarray(live, dtype=bool)
    )
    sched = TenantScheduler(window_s=0.002, route="device", max_dispatch_lanes=N_VALIDATORS)
    handle = sched.register("only", _validators(signed_round))
    lanes = [(signed_round.proposal_hash, s) for s in signed_round.seals]
    masks = []
    callers = [
        threading.Thread(
            target=lambda: masks.append(handle.verify_seal_lanes(lanes, signed_round.height))
        )
        for _ in range(3)
    ]
    with sched:
        with sched._cv:  # the pause reconfigure takes: queue, flush nothing
            sched._paused = True
        for t in callers:
            t.start()
        deadline = time.monotonic() + 10
        while sched._pending_reqs < 3 and time.monotonic() < deadline:
            time.sleep(0.002)
        with sched._cv:
            sched._paused = False
            sched._cv.notify_all()
        for t in callers:
            t.join(10)
    assert len(masks) == 3 and all(m.all() for m in masks)
    assert sched.stats()["launched_ahead"] == 2
    assert recorder.dropped == 0
    parts = ("sched.dispatch", "verify.pack", "verify.dispatch", "verify.device_wait", "sched.complete")
    spans = [
        (name, track, ts, ts + dur, args)
        for ph, name, track, ts, dur, args in recorder.snapshot()
        if ph == "X" and name in parts + ("sched.coalesce",)
    ]
    flushes = sorted((s for s in spans if s[0] == "sched.coalesce"), key=lambda s: s[2])
    assert [f[4]["ahead"] for f in flushes] == [0, 1, 1]
    assert len(spans) == 3 * (1 + len(parts))
    for k, (_name, track, t0, t1, args) in enumerate(flushes):
        assert args == {"tenants": 1, "requests": 1, "lanes": N_VALIDATORS, "ahead": min(k, 1)}
        inside = [s for s in spans if s[1] == track and t0 <= s[2] and s[3] <= t1 + 1]
        mine = {s[0]: s for s in inside}
        # Its own six on its row inside it, and nothing of another flush.
        assert len(inside) == 6 and set(mine) == set(parts) | {"sched.coalesce"}, (k, inside)
        d0, d1 = mine["sched.dispatch"][2:4]
        for child in ("verify.pack", "verify.dispatch", "verify.device_wait"):
            assert d0 <= mine[child][2] and mine[child][3] <= d1 + 1, child
        order = ["verify.pack", "verify.dispatch", "verify.device_wait", "sched.complete"]
        assert [mine[n][2] for n in order] == sorted(mine[n][2] for n in order)
        assert mine["verify.pack"][4] == {"kind": "seal_lanes", "lanes": N_VALIDATORS, "table_rows": 128}
        assert mine["verify.dispatch"][4]["table_rows"] == 128
        assert mine["verify.device_wait"][4] == {"route": "device"}
        assert mine["sched.complete"][4] == {"requests": 1, "lanes": N_VALIDATORS}
    for (_n, track, t0, t1, _a), (_n2, track2, u0, _u1, _a2) in zip(flushes, flushes[1:]):
        assert t0 < u0 < t1  # the next was launched with this one in flight
        assert track != track2
    # No third in flight: flush 3 is launched after flush 1 is delivered.
    assert flushes[2][2] >= flushes[0][3] - 1
