"""The ``ecdsa-300v`` deployment against the plain reference, small, on the
CPU (ISSUE 31).

One validator of a committee whose other members are pre-signed peers, 30%
of them corrupted every height, driven by the benchmark's driver ``round0``
through its own entry points (``setup``, ``warm``, ``measure``, ``check``):
once through ``IBFT`` + ``BatchingIngress`` + the batch verifier with the
device route really taken (the 8- and 32-lane recover programs, real
kernels), once through an ``IBFT`` with NO batch verifier fed message by
message, which is upstream's sequential path and this system's plain
reference.  Both must finalize the same blocks in round 0 from the same
PREPARE senders and the same seals, none of them a corrupted peer's, and the
``verify.verdicts`` instants must count what the generator labelled.

The last case is a committee just over the ingress's old 256-message
ceiling, on the host route: every phase one flush.
"""

import gc
import math
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.drivers import round0 as driver  # noqa: E402

from go_ibft_tpu.obs import ledger as cost_ledger  # noqa: E402
from go_ibft_tpu.obs import trace  # noqa: E402
from go_ibft_tpu.verify import AdaptiveBatchVerifier, HostBatchVerifier  # noqa: E402

HEIGHTS = 2
CORRUPT_SHARE = 0.3  # benchmark/traffic/flood-byz30.json


def _run(n: int, seed: int, make_verifier, batched: bool = True):
    """``HEIGHTS`` heights of the deployment at ``n`` validators, as the
    driver runs a cell: its ``setup`` on a configuration and a traffic mix
    sized to sign exactly ``HEIGHTS`` heights, its ``warm`` (no warm
    heights), its ``measure`` until the signed heights run out, its
    ``check``.  The driver's default-constructed verifier is replaced by
    ``make_verifier``'s, so that floods this small still take the route
    under test.  Returns the driver's state and what the window recorded."""
    config = {"validators": n, "base_round_timeout_s": 60}
    mix = {
        "corrupt_share": CORRUPT_SHARE,
        "warm_heights": 0,
        "presign_msgs_per_s": 2 * n * HEIGHTS,
    }
    st = driver.setup(config, mix, seed, 1.0)
    assert st.heights == HEIGHTS and st.corrupt == driver.corrupted_peers(n, 0.3)
    st.verifier = make_verifier(st.committee.src)
    if batched:
        st.engine.batch_verifier = st.verifier
    else:
        # Upstream's AddMessage: one message, verified and stored, at a time.
        st.ingress.close()
        st.engine.batch_verifier = None
        st.ingress = SimpleNamespace(submit=st.engine.add_message)
    try:
        driver.warm(st)
        rec = trace.enable()
        cost_ledger.enable()
        try:
            st.samples, st.counts = driver.measure(st, math.inf)
            st.rows = cost_ledger.snapshot()["dispatches"]
            st.records = rec.snapshot()
            assert rec.dropped == 0
        finally:
            cost_ledger.disable()
            trace.disable()
            if batched:
                st.ingress.close()
    finally:
        gc.unfreeze()  # ``warm`` froze the heap for the measured window
    assert st.counts["ran_out_of_presigned"] and st.counts["attempted"] == HEIGHTS
    st.labels = [st.committee.traffic(h, st.corrupt) for h in range(1, HEIGHTS + 1)]
    st.heights_finalized = list(st.finalized)
    st.checked = driver.check(st)  # labels + the sequential host oracle
    return st


def _finalized(st) -> dict:
    """height -> (block, round, locked PREPARE senders, finalized signers)."""
    return {
        t.height: (
            proposal.raw_proposal,
            proposal.round,
            frozenset(st.locked[t.height]),
            frozenset(s.signer for s in seals),
        )
        for t, proposal, seals in st.heights_finalized
    }


def _verdicts(st, kind: str) -> list:
    return [
        (r[5]["live"], r[5]["rejected"])
        for r in st.records
        if r[1] == "verify.verdicts" and r[5]["kind"] == kind
    ]


@pytest.mark.parametrize("n, seed", [(10, 3000000131), (20, 77)])
def test_batched_device_route_equals_the_sequential_reference(n, seed):
    corrupt = driver.corrupted_peers(n, CORRUPT_SHARE)
    assert corrupt == {10: 3, 20: 6}[n]
    # cutover 2: the one-lane PREPREPARE stays on the host, every flood
    # takes the device route at twice the 32-lane bucket (ISSUE 32: the
    # second half is free there, and the COMMIT flood's seals ride in it),
    # and the engine's seal drain dispatches nothing.
    batched = _run(n, seed, lambda src: AdaptiveBatchVerifier(src, cutover_lanes=2))
    reference = _run(n, seed, HostBatchVerifier, batched=False)
    for st in (batched, reference):
        assert st.counts["failed"] == 0 and len(st.samples) == HEIGHTS
        assert st.checked is True, st.info.get("guarantee_broken")
    assert batched.counts["flush_sizes"] == [1, n - 1, n] * HEIGHTS
    got, want = _finalized(batched), _finalized(reference)
    assert got == want
    quorum = batched.committee.quorum
    for t in batched.labels:
        block, round_, prepared, signers = got[t.height]
        assert round_ == 0
        assert not prepared & t.bad and not signers & t.bad
        # Every honest follower's PREPARE is in the certificate beside the
        # node's own, and every valid seal is finalized.
        honest = {m.sender for m in t.prepares} - t.bad
        assert prepared == honest | {batched.committee.node}
        assert len(signers) == n - corrupt >= quorum

    # The device route was taken, and only the PREPREPAREs went to the host.
    device = [r for r in batched.rows if r["route"] == "device"]
    host = [r for r in batched.rows if r["route"] == "host"]
    assert sum(r["dispatches"] for r in host) == HEIGHTS
    (recovers,) = [r for r in device if r["program"] == "ecdsa_recover"]
    # After it a height is TWO recover dispatches: the PREPARE flood, and
    # the COMMIT flood with every COMMIT's seal in it.
    assert recovers["dispatches"] == 2 * HEIGHTS
    assert recovers["padded_lanes"] == 2 * 64 * HEIGHTS
    assert recovers["live_lanes"] == (n - 1 + 2 * n) * HEIGHTS

    # What the drains report is what the generator labelled: per height the
    # PREPREPARE, the PREPARE flood (the node's own among it; every
    # corrupted peer's envelope is mangled), the COMMIT flood (half of them
    # mangle that envelope too), and in the same drain the seal of EVERY
    # COMMIT of the flood (the other half carry a mangled seal; a seal
    # behind a mangled envelope is judged too, and never asked for).
    bad_envelopes = corrupt // 2
    assert _verdicts(batched, "senders") == [
        (1, 0),
        (n - 1, corrupt),
        (n, bad_envelopes),
    ] * HEIGHTS
    assert _verdicts(batched, "seals") == [(n, corrupt - bad_envelopes)] * HEIGHTS
    # The engine's seal drain asked for the seals of the COMMITs that
    # passed, and every one was a verdict the COMMIT flood had left.
    drains = [r[5] for r in batched.records if r[:2] == ("X", "verify.early_exit")]
    assert [(a["route"], a["lanes"], a["cached"]) for a in drains] == [
        ("device", n - bad_envelopes, n - bad_envelopes)
    ] * HEIGHTS
    floods = [
        (a["chunks"], a["lanes"], a["seal_lanes"])
        for r in batched.records
        if r[:2] == ("X", "verify.drain")
        for a in [r[5]]
        if a["route"] == "device"
    ]
    assert floods == [(1, n - 1, 0), (1, n, n)] * HEIGHTS
    assert not _verdicts(reference, "senders")  # no batch verifier, no drain


def test_a_committee_just_over_the_old_ceiling_flushes_each_phase_once():
    """259 validators on the host route: the PREPARE flood is 258 messages
    and the COMMIT flood 259, both over the 256 at which a default ingress
    used to cut.  Three flushes a height and four verifier calls, as
    ``tests/test_span_contract.py`` has it for 4 and 16."""
    n = 259
    st = _run(n, 11, HostBatchVerifier)
    assert st.counts["failed"] == 0 and len(st.samples) == HEIGHTS
    assert st.checked is True, st.info.get("guarantee_broken")
    assert st.counts["flush_sizes"] == [1, n - 1, n] * HEIGHTS
    flushes = [r[5] for r in st.records if r[:2] == ("X", "ingress.flush")]
    assert [(a["lanes"], a["cut"]) for a in flushes] == [
        (1, False),
        (n - 1, False),
        (n, False),
    ] * HEIGHTS
    (row,) = st.rows
    assert (row["program"], row["route"]) == ("ecdsa_recover", "host")
    assert row["dispatches"] == 4 * HEIGHTS
    corrupt = driver.corrupted_peers(n, CORRUPT_SHARE)
    assert corrupt == 77
    assert _verdicts(st, "senders") == [
        (1, 0),
        (n - 1, corrupt),
        (n, corrupt // 2),
    ] * HEIGHTS
    # The host early exit judges in arrival order and stops at quorum.
    for live, rejected in _verdicts(st, "seals"):
        assert live - rejected == st.committee.quorum == 173
        assert live < n - corrupt // 2
