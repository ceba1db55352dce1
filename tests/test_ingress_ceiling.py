"""The ingress's flush ceiling (ISSUE 31).

A phase of an n-validator committee reaches a node as n - 1 messages in
one event-loop turn.  A default-constructed ``BatchingIngress`` used to cut
such a burst at 256 messages; it now has no ceiling at all and flushes by
the timer or at the end of the turn only (above the verifier's largest
lane bucket the verifier splits the batch itself, overlapping pack and
device).  What an explicit ``max_batch=`` does, and what any burst under
256 does, is as it was (``tests/test_ingress.py`` and
``tests/test_calibration.py`` hold that, unedited): the cases below add the
sizes between and the span.
"""

import asyncio

import pytest

from go_ibft_tpu.core.transport import BatchingIngress
from go_ibft_tpu.obs import trace
from go_ibft_tpu.verify import batch

CAP = batch._BATCH_BUCKETS[-1]  # the verifier's largest single dispatch


def test_the_default_has_no_ceiling_and_keeps_the_fill_target():
    assert CAP == 2048
    ing = BatchingIngress(lambda b: None)
    assert ing.max_batch is None and BatchingIngress.FILL_TARGET == 256
    assert BatchingIngress(lambda b: None, max_batch=256).max_batch == 256
    # The calibrated window projects to the same batch either way.
    asked = []

    class Spy:
        def window(self, pending, target):
            asked.append(target)
            return 0.0

    for kwargs in ({}, {"max_batch": 256}, {"max_batch": 64}):
        ing = BatchingIngress(lambda b: None, **kwargs)
        ing.calibrator = Spy()
        ing._window()
    assert asked == [256, 256, 64]


def _burst(n: int, **kwargs):
    """``n`` submits in one loop turn, then the loop runs until the timers
    are through.  Returns the flush sizes, the ``ingress.flush`` spans'
    args, and whether the FIRST submit armed a timer (``call_later``)
    rather than the end of the turn (``call_soon``)."""
    sizes = []

    async def main():
        ing = BatchingIngress(lambda b: sizes.append(len(b)), **kwargs)
        timed = None
        for i in range(n):
            ing.submit(object())
            if i == 0:
                timed = isinstance(ing._handle, asyncio.TimerHandle)
        await asyncio.sleep(0.02)
        ing.close()
        return timed

    rec = trace.enable()
    try:
        timed = asyncio.run(main())
        spans = [r[5] for r in rec.snapshot() if r[:2] == ("X", "ingress.flush")]
    finally:
        trace.disable()
    return sizes, spans, timed


@pytest.mark.parametrize(
    "n, kwargs, want",
    [
        # Under the old ceiling: one flush at the end of the turn, as before.
        (1, {}, [(1, False)]),
        (99, {}, [(99, False)]),
        (255, {}, [(255, False)]),
        # A 300-validator phase: ONE batch (it was 256 + 44).
        (299, {}, [(299, False)]),
        (300, {}, [(300, False)]),
        # Over the verifier's largest dispatch: still one batch (the
        # verifier splits it, the ingress cuts nowhere).
        (CAP - 1, {}, [(CAP - 1, False)]),
        (CAP + 5, {}, [(CAP + 5, False)]),
        (2 * CAP + 1, {}, [(2 * CAP + 1, False)]),
        # An explicit max_batch is the ceiling, as it always was.
        (7, {"max_batch": 3}, [(3, True), (3, True), (1, False)]),
        (300, {"max_batch": 256}, [(256, True), (44, False)]),
        (255, {"max_batch": 256}, [(255, False)]),
    ],
)
def test_a_burst_in_one_turn(n, kwargs, want):
    sizes, spans, timed = _burst(n, **kwargs)
    assert sizes == [lanes for lanes, _cut in want]
    # The span says how many lanes it handed over and whether ``max_batch``
    # fired it (the timer and a direct flush() say False).
    assert [(a["lanes"], a["cut"]) for a in spans] == want
    # ``height`` is the burst's first message's (PR 37): these have none.
    assert all(set(a) == {"lanes", "cut", "height"} for a in spans)
    assert all(a["height"] is None for a in spans)
    # A fresh ingress flushes a first burst at the end of its turn.
    assert timed is False


@pytest.mark.parametrize(
    "n, cutover, why",
    [
        # A live phase after a device dispatch (100 and 300 validators, the
        # default cutover): whole in the buffer when its turn ends.
        (99, 16, "whole"),
        (299, 16, "whole"),
        (2 * CAP + 1, 16, "whole"),
        (16, 16, "whole"),  # at the cutover
        (64, 64, "whole"),
        # Under it the batch is not yet the device's: the backlog may still
        # be coming a message a turn, so the ceiling is waited.
        (15, 16, "held_back"),
        (1, 16, "held_back"),
        (63, 64, "held_back"),
        (299, 1000, "held_back"),
    ],
)
def test_a_held_back_ingress_decides_when_the_turn_ends(n, cutover, why):
    """After a flush that blocked the loop (a device dispatch) the next
    burst is judged at the END of the turn it began in (PR 41; at its first
    message before): device-sized as it stands, it is handed over there and
    no timer is armed; under the cutover it waits the ``max_delay`` ceiling.
    One batch either way."""
    sizes, max_delay = [], 0.005

    async def main():
        ing = BatchingIngress(
            lambda b: sizes.append(len(b)), max_delay=max_delay, eager_cutover=cutover
        )
        ing._held_back = True
        for _ in range(n):
            ing.submit(object())
        # Nothing is decided inside the turn, on either path.
        assert not isinstance(ing._handle, asyncio.TimerHandle) and sizes == []
        await asyncio.sleep(0)
        timed = isinstance(ing._handle, asyncio.TimerHandle)
        at_turn_end = list(sizes)
        await asyncio.sleep(6 * max_delay)
        ing.close()
        return timed, at_turn_end

    rec = trace.enable()
    try:
        timed, at_turn_end = asyncio.run(main())
        waits = [r for r in rec.snapshot() if r[:2] == ("X", "ingress.wait")]
    finally:
        trace.disable()
    assert sizes == [n]
    assert [(w[5]["why"], w[5]["lanes"]) for w in waits] == [(why, n)]
    assert timed is (why == "held_back")
    assert at_turn_end == ([n] if why == "whole" else [])
    if why == "held_back":
        assert waits[0][4] >= max_delay * 1e6 * 0.9  # the ceiling WAS waited


def test_no_flush_span_when_tracing_is_off():
    assert not trace.enabled()
    sizes = []

    async def main():
        ing = BatchingIngress(sizes.append, max_batch=2)
        ing.submit(object())
        ing.submit(object())
        ing.close()

    assert trace.span("ingress.flush", lanes=1, cut=False) is trace._NULL
    asyncio.run(main())
    assert [len(b) for b in sizes] == [2]
