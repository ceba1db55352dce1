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
        ("soon", 2),  # decided at the first message, under the cutover of 2
        ("window", 1),
        ("cut", 2),
        ("closed", 1),
    ]


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
