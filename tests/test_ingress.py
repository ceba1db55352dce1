"""BatchingIngress: flush triggers (size, delay, explicit), close semantics,
and equivalence with N sequential add_message calls through a real engine."""

import asyncio
import time

import pytest

from go_ibft_tpu.core.transport import BatchingIngress, LoopbackTransport
from go_ibft_tpu.messages.wire import (
    IbftMessage,
    MessageType,
    PrepareMessage,
    View,
)

from go_ibft_tpu.obs import trace

from harness import Cluster


def _msg(i: int) -> IbftMessage:
    return IbftMessage(
        view=View(height=1, round=0),
        sender=b"s%02d" % i + b"-" * 16,
        signature=b"\x01" * 65,
        type=MessageType.PREPARE,
        prepare_data=PrepareMessage(proposal_hash=b"\x22" * 32),
    )


async def test_flush_on_max_batch():
    batches = []
    ing = BatchingIngress(batches.append, max_batch=3, max_delay=60.0)
    for i in range(7):
        ing.submit(_msg(i))
    # 3 + 3 flushed by size; 1 still buffered behind the long timer
    assert [len(b) for b in batches] == [3, 3]
    ing.flush()
    assert [len(b) for b in batches] == [3, 3, 1]
    ing.close()


async def test_flush_on_delay():
    batches = []
    ing = BatchingIngress(batches.append, max_batch=1000, max_delay=0.01)
    ing.submit(_msg(0))
    ing.submit(_msg(1))
    assert batches == []  # nothing yet: under both thresholds
    await asyncio.sleep(0.05)
    assert [len(b) for b in batches] == [2]
    ing.close()


async def test_close_drops_buffer_and_timer():
    batches = []
    ing = BatchingIngress(batches.append, max_batch=1000, max_delay=0.01)
    ing.submit(_msg(0))
    ing.close()
    await asyncio.sleep(0.05)
    assert batches == []  # timer cancelled, buffer dropped
    ing.flush()
    assert batches == []  # close is terminal for buffered content


async def test_batched_ingress_equivalent_to_sequential():
    """A cluster whose gossip rides BatchingIngress must finalize exactly
    like the sequential add_message path (observable-semantics parity,
    core/ibft.py add_messages contract)."""
    cluster = Cluster(4)
    loop = LoopbackTransport()
    ingresses = []
    try:
        for node in cluster.nodes:
            ing = BatchingIngress(node.core.add_messages, max_delay=0.002)
            ingresses.append(ing)
            loop.register(ing.submit)
            node.core.transport = loop
        await asyncio.wait_for(cluster.progress_to_height(2), 20)
        for node in cluster.nodes:
            assert len(node.inserted_blocks) == 2
    finally:
        for ing in ingresses:
            ing.close()
        cluster.shutdown()


# -- ``ingress.wait``: the queue wait of a burst, and why it ended (PR 37) ---


def _waits(rec):
    return [(r[5]["why"], r[5]["lanes"], r[4]) for r in rec.snapshot() if r[1] == "ingress.wait"]


@pytest.fixture
def ring():
    assert not trace.enabled()
    rec = trace.enable()
    try:
        yield rec
    finally:
        trace.disable()


@pytest.mark.parametrize("blocked_windows", [0.0, 1.0, 8.0, 12.0])
async def test_wait_is_held_back_exactly_after_a_flush_that_blocked_the_loop(
    blocked_windows, ring
):
    """A flush that blocks for more than ``HELD_BACK_FACTOR`` windows makes
    the NEXT burst wait the ``max_delay`` ceiling, and its ``ingress.wait``
    says ``held_back`` and lasts that long; under the factor the next burst
    flushes at the end of the loop turn and says ``soon``."""
    max_delay = 0.005

    def add_messages(batch):
        if len(batch) == 2:  # the first burst's flush blocks the loop
            time.sleep(blocked_windows * max_delay)

    ing = BatchingIngress(add_messages, max_delay=max_delay, eager_cutover=1000)
    assert ing.HELD_BACK_FACTOR == 4.0
    ing.submit(_msg(0))
    ing.submit(_msg(1))
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    for i in range(3):
        ing.submit(_msg(2 + i))
    await asyncio.sleep(4 * max_delay)
    ing.close()
    first, second = _waits(ring)
    assert first[:2] == ("soon", 2)
    held = blocked_windows > ing.HELD_BACK_FACTOR
    assert second[:2] == ("held_back" if held else "soon", 3)
    if held:
        assert second[2] >= max_delay * 1e6 * 0.9  # it waited the ceiling


async def test_wait_says_window_cut_and_closed(ring):
    # Device-sized flow: the calibrated window engages (fixed, uncalibrated).
    timed = BatchingIngress(
        lambda b: None, max_delay=0.005, eager_cutover=2, calibrate=False
    )
    timed.submit(_msg(0))
    timed.submit(_msg(1))  # joins the burst: no second wait
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    timed.submit(_msg(2))  # with the two just flushed, over the cutover
    await asyncio.sleep(0.02)
    timed.close()
    cut = BatchingIngress(lambda b: None, max_batch=2, max_delay=60.0)
    for i in range(3):
        cut.submit(_msg(i))
    cut.close()  # one message still buffered
    assert [w[:2] for w in _waits(ring)] == [
        ("soon", 2),  # device-sized when its turn ends: nothing to wait for
        ("window", 1),
        ("cut", 2),
        ("closed", 1),
    ]


@pytest.mark.parametrize(
    "path, before, after",
    [
        ("held_back", 0, 0),  # alone, after a flush that blocked the loop
        ("held_back", 0, 5),  # heading a sub-cutover burst
        ("held_back", 3, 2),  # inside one
        ("window", 0, 0),  # a device-sized flow: the timed window's path
        ("window", 0, 5),
        ("window", 3, 2),
        ("timer", 2, 1),  # a ceiling already being waited: it is cut short
    ],
)
async def test_a_preprepare_is_never_timed(path, before, after, ring):
    """One proposer a round, and everything waits for it: on every path a
    PREPREPARE goes out at the end of the turn it came in, with whatever is
    buffered, and its ``ingress.wait`` says ``soon`` (PR 41; under
    ``burst_hint`` since PR 39: the test below)."""
    batches = []
    ing = BatchingIngress(
        lambda b: batches.append(len(b)), max_delay=0.05, eager_cutover=16, calibrate=False
    )
    if path == "window":
        ing._recent.append((time.monotonic(), 16))
        ing._recent_n = 16
    else:
        ing._held_back = True
    if path == "timer":
        ing.submit(_typed(9, MessageType.COMMIT))
        await asyncio.sleep(0)
        assert isinstance(ing._handle, asyncio.TimerHandle) and batches == []
    for i in range(before):
        ing.submit(_typed(i, MessageType.PREPARE))
    ing.submit(_typed(20, MessageType.PREPREPARE))
    for i in range(after):
        ing.submit(_typed(30 + i, MessageType.PREPARE))
    assert batches == [] and not isinstance(ing._handle, asyncio.TimerHandle)
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    total = before + 1 + after + (path == "timer")
    assert batches == [total]
    assert [w[:2] for w in _waits(ring)] == [("soon", total)]
    # The same burst without the PREPREPARE waits, on each of these paths.
    ing._held_back = path != "window"  # (the quick flush above cleared it)
    for i in range(before + after + 1):
        ing.submit(_typed(i, MessageType.PREPARE))
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    assert batches == [total] and isinstance(ing._handle, asyncio.TimerHandle)
    ing.close()
    assert _waits(ring)[-1][:2] == ("closed", before + after + 1)


@pytest.mark.parametrize("trickle", [1, 6, 15])
async def test_a_trickle_after_a_blocking_flush_is_one_held_back_batch(trickle, ring):
    """What the held-back rule is for (beside ``tests/test_chip_smoke.py``):
    a backlog handed over one message a loop turn after a flush that blocked
    the loop is under the cutover when its first turn ends, so it waits the
    ceiling, lands in ONE batch and says ``held_back``."""
    batches = []

    def slow_add(batch):
        batches.append(len(batch))
        time.sleep(0.02)  # a device dispatch: 10 windows

    ing = BatchingIngress(slow_add, max_delay=0.002, eager_cutover=16)
    ing.submit("first")
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    assert batches == [1]
    for i in range(trickle):
        ing.submit(f"m{i}")
        await asyncio.sleep(0)  # one message per loop turn
    await asyncio.sleep(0.05)
    ing.close()
    assert batches == [1, trickle]
    (_first, second) = _waits(ring)
    assert second[:2] == ("held_back", trickle) and second[2] >= 0.002 * 1e6 * 0.9


async def test_a_direct_flush_does_not_repeat_the_last_bursts_why(ring):
    """``flush()`` called before the turn's decision (``sim/cluster.py``
    does, every tick) hands the burst over at once: ``soon``, whatever the
    burst before it waited for."""
    ing = BatchingIngress(lambda b: None, max_delay=0.002, eager_cutover=16)
    ing._held_back = True
    ing.submit(_msg(0))
    await asyncio.sleep(0.01)  # waited the ceiling
    ing.submit(_msg(1))
    ing.flush()
    ing.close()
    assert [w[:2] for w in _waits(ring)] == [("held_back", 1), ("soon", 1)]


async def test_the_tracing_predicate_runs_once_a_burst_not_once_a_message(
    monkeypatch,
):
    """``submit`` runs 200-900 times a height: it asks the tracer on the
    empty-to-non-empty edge only, and with tracing off that is all it does
    (the shared no-op; no span object, no clock read)."""
    assert not trace.enabled()
    asked = []
    real = trace.begin

    def begin(name, *a, **k):
        asked.append(name)
        return real(name, *a, **k)

    monkeypatch.setattr(trace, "begin", begin)
    monkeypatch.setattr(
        trace, "span", lambda name, *a, **k: asked.append(name) or trace.NULL_SPAN
    )
    batches = []
    ing = BatchingIngress(batches.append, max_delay=0.001)
    for burst in range(3):
        for i in range(300):
            ing.submit(_msg(i))
        assert ing._wait is trace.NULL_SPAN
        await asyncio.sleep(0.01)
    ing.close()
    assert [len(b) for b in batches] == [300, 300, 300]
    assert asked == ["ingress.wait", "ingress.flush"] * 3


def _typed(i: int, type_: MessageType) -> IbftMessage:
    m = _msg(i)
    m.type = type_
    return m


async def test_a_burst_hint_holds_the_first_vote_for_company():
    """PR 39: a validator whose flushes can reach a device tells its ingress
    how many votes a decision takes.  Votes that then trickle in one a loop
    turn, each too few to trip either flow detector, land in ONE flush once
    the transport goes quiet, not one flush each; a lone vote goes out after
    the same quiet gap, never held to the cap; a PREPREPARE is not held and
    flushes at the end of its tick with whatever is; without the hint, or
    with a hint under the cutover, nothing changes."""
    batches = []
    hinted = BatchingIngress(
        lambda b: batches.append(len(b)), max_delay=0.01, eager_cutover=16, burst_hint=66
    )
    assert hinted.burst_hint == 66
    for i in range(20):
        hinted.submit(_typed(i, MessageType.PREPARE))
        await asyncio.sleep(0)
        await asyncio.sleep(0)
    assert batches == []  # twenty loop turns on, still held
    await asyncio.sleep(0.15)  # quiet for BURST_QUIET_FACTOR windows, with room
    assert batches == [20]
    hinted.submit(_typed(9, MessageType.PREPREPARE))
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    assert batches == [20, 1]
    # One vote and then silence (its peers are down): out after the quiet
    # gap, long before the cap (64 windows = 0.64 s here).
    hinted.submit(_typed(10, MessageType.COMMIT))
    await asyncio.sleep(0.15)
    assert batches == [20, 1, 1]
    # A PREPREPARE takes what is held with it, at the end of its tick.
    hinted.submit(_typed(11, MessageType.COMMIT))
    hinted.submit(_typed(12, MessageType.PREPREPARE))
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    assert batches == [20, 1, 1, 2]
    hinted.close()

    for kwargs in ({}, {"burst_hint": 9}):  # no hint; a committee the host serves
        batches.clear()
        eager = BatchingIngress(
            lambda b: batches.append(len(b)), max_delay=0.01, eager_cutover=16, **kwargs
        )
        assert eager.burst_hint is None
        for i in range(3):
            eager.submit(_typed(i, MessageType.PREPARE))
            await asyncio.sleep(0)
            await asyncio.sleep(0)
        assert batches == [1, 1, 1]
        eager.close()


async def test_a_held_burst_is_handed_over_full_quiet_or_capped():
    """The three ends of a burst held under ``burst_hint``, each named on
    its ``ingress.wait``: enough to decide (as many votes as the hint, and
    the transport quiet for ONE window: with a third of the peers silent a
    phase waits for nobody, and stragglers on the quorum's heels share its
    flush), the transport quiet for ``BURST_QUIET_FACTOR`` windows whatever
    is held, or held ``BURST_HOLD_FACTOR`` windows while arrivals keep
    coming."""
    batches = []
    rec = trace.enable()
    try:
        ing = BatchingIngress(
            lambda b: batches.append(len(b)), max_delay=0.004, eager_cutover=16, burst_hint=32
        )
        quiet, hold = (f * ing.max_delay for f in (ing.BURST_QUIET_FACTOR, ing.BURST_HOLD_FACTOR))
        for i in range(32):
            ing.submit(_typed(i, MessageType.COMMIT))
        assert batches == []  # enough to decide: held for what is on its heels
        await asyncio.sleep(0)
        for i in range(3):
            ing.submit(_typed(i, MessageType.COMMIT))
        await asyncio.sleep(3 * ing.max_delay)  # under the quiet gap of four
        assert batches == [35]
        for i in range(5):  # a few of the next (most peers are down), then silence
            ing.submit(_typed(i, MessageType.COMMIT))
        await asyncio.sleep(6 * quiet)
        assert batches == [35, 5]
        ing.burst_hint = 4  # the set shrank under the cutover: no hold any more
        assert ing.burst_hint is None
        ing.close()
        drip = BatchingIngress(  # a drip that never pauses: quiet cannot end it
            lambda b: batches.append(len(b)), max_delay=0.004, eager_cutover=10**5, burst_hint=10**6
        )
        t0 = time.monotonic()
        while not batches[2:]:  # a steady drip, well inside the quiet gap
            drip.submit(_typed(0, MessageType.PREPARE))
            await asyncio.sleep(quiet / 8)
            assert time.monotonic() - t0 < 5.0
        held = time.monotonic() - t0
        drip.close()
    finally:
        trace.disable()
    waits = [r for r in rec.snapshot() if r[1] == "ingress.wait"]
    assert [w[5]["why"] for w in waits] == ["full", "quiet", "capped"]
    assert [w[5]["lanes"] for w in waits] == batches
    assert waits[1][4] >= 0.9e6 * quiet  # quiet that long after its last arrival
    assert 0.95 * hold <= held < 4 * hold  # held its limit, not for ever
