"""Two flushes in flight (ISSUE 55): the scheduler's loop launches flush
N + 1 before it reads back N, and only then.

Every test drives the real ``TenantScheduler`` over a dispatcher of two
halves whose COLLECT half blocks on an event the test holds (``_Gated``, over
the host-route ``CoalescedDispatcher``, so every verdict is real): the order
of launches, collects and deliveries is read off its log, and nothing is
timed except where the test is about not sleeping.
"""

import contextlib
import threading
import time

import numpy as np
import pytest

from go_ibft_tpu.bench.workload import build_signed_round
from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto.backend import ECDSABackend
from go_ibft_tpu.obs import trace
from go_ibft_tpu.sched import CoalescedDispatcher, TenantScheduler, dispatch
from go_ibft_tpu.sched.scheduler import LAUNCHED_AHEAD_KEY
from go_ibft_tpu.utils import metrics

N = 4  # validators, and lanes a request: a dispatch cap of 4 is one request a flush
SEED = 11


def _src():
    keys = [PrivateKey.from_seed(b"bench-%d-%d" % (SEED, i)) for i in range(N)]
    return ECDSABackend.static_validators({k.address: 1 for k in keys})


def _round(height: int):
    """A round of its own a height: a flush is known by its lanes' height."""
    return build_signed_round(N, seed=SEED, height=height)


class _Pending:
    """What ``_Gated.launch`` hands to ``_Gated.collect``; never ``ready``:
    there is a read-back to wait for (the gate)."""

    ready = False

    def __init__(self, owner, height, inner):
        self.owner, self.height, self.inner = owner, height, inner


class _Gated:
    """A dispatcher of two halves over the host route's.  ``log`` holds
    ``(event, height)`` in order: ``launch`` when the launch half is entered,
    ``collect`` when the collect half is, ``collected`` when it returns.
    ``collect`` waits for the flush's gate unless ``open`` (all gates are).
    ``fail``: ``(half, height)`` pairs that raise."""

    def __init__(self, name="gated", open=False, fail=()):
        self.name = name
        self.inner = CoalescedDispatcher(route="host")
        self.open = open
        self.fail = set(fail)
        self.log = []
        self.at = {}  # event -> monotonic time
        self.gates = {}
        self._lock = threading.Lock()

    def gate(self, height: int) -> threading.Event:
        with self._lock:
            return self.gates.setdefault(height, threading.Event())

    def _note(self, event, height):
        self.log.append((event, height))
        self.at[(event, height)] = time.monotonic()

    def launch(self, msgs, lanes, owners):
        height = msgs[0].view.height
        self._note("launch", height)
        if ("launch", height) in self.fail:
            raise RuntimeError(f"launch of {height} poisoned")
        return _Pending(self, height, self.inner.launch(msgs, lanes, owners))

    def collect(self, pending):
        owner, height, inner = pending.owner, pending.height, pending.inner
        assert owner is self, f"flush {height} collected on {self.name}, launched on {owner.name}"
        self._note("collect", height)
        if not self.open:
            assert self.gate(height).wait(20.0), f"gate {height} never opened"
        if ("collect", height) in self.fail:
            raise RuntimeError(f"collect of {height} poisoned")
        out = self.inner.collect(inner)
        self._note("collected", height)
        return out

    def dispatch(self, msgs, lanes, owners):
        return self.collect(self.launch(msgs, lanes, owners))

    def describe(self):
        return {"route": self.name}


def _until(predicate, what: str, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not predicate():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def _stays(predicate, seconds=0.15):
    """``predicate`` holds now and keeps holding (the loop is blocked, not
    merely slow)."""
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        assert predicate()
        time.sleep(0.005)


@contextlib.contextmanager
def _held(sched):
    """Requests queue and nothing flushes (the pause ``reconfigure`` takes),
    so a test can have its whole backlog queued before the first launch."""
    with sched._cv:
        sched._paused = True
    try:
        yield
    finally:
        with sched._cv:
            sched._paused = False
            sched._cv.notify_all()


class _Callers:
    """One thread a call of ``handle.verify_senders``; ``order`` is the order
    the calls returned in, ``masks`` what each returned."""

    def __init__(self):
        self.threads, self.order, self.masks = [], [], {}

    def call(self, handle, height):
        def work():
            self.masks[height] = handle.verify_senders(_round(height).prepares)
            self.order.append(height)

        t = threading.Thread(target=work, daemon=True)
        t.start()
        self.threads.append(t)

    def join(self):
        for t in self.threads:
            t.join(20.0)
        assert not any(t.is_alive() for t in self.threads)


def _queued(sched, handle, heights, callers=None):
    """``heights`` queued in that order, one request each, nothing flushed."""
    callers = callers or _Callers()
    for k, height in enumerate(heights):
        before = sched._pending_reqs
        callers.call(handle, height)
        _until(lambda: sched._pending_reqs == before + 1, f"request {height} queued")
    return callers


def _sched(dispatcher, **kw):
    kw.setdefault("window_s", 0.001)
    return TenantScheduler(max_dispatch_lanes=N, dispatcher=dispatcher, **kw)


def test_flush_two_is_launched_before_one_is_read_back_and_three_waits_for_ones_delivery():
    gated = _Gated()
    sched = _sched(gated)
    node = sched.register("node", _src())
    ahead = metrics.get_counter(LAUNCHED_AHEAD_KEY)
    with sched:
        with _held(sched):
            callers = _queued(sched, node, [1, 2, 3])
        # 2 goes out BEFORE 1's collect returns; 3 does not: two in flight.
        blocked = [("launch", 1), ("launch", 2), ("collect", 1)]
        _until(lambda: len(gated.log) >= 3, "two launches and the first collect")
        _stays(lambda: gated.log == blocked and len(sched._inflight) == 2)
        assert not callers.order
        gated.gate(1).set()
        # 1 is delivered, THEN 3 is launched (ahead of 2's read-back).
        _until(lambda: len(gated.log) >= 6, "the third launch")
        assert gated.log == blocked + [("collected", 1), ("launch", 3), ("collect", 2)]
        _until(lambda: callers.order == [1], "the first caller's return")
        _stays(lambda: len(sched._inflight) == 2 and callers.order == [1])
        gated.gate(2).set()
        _until(lambda: callers.order == [1, 2], "the second caller's return")
        gated.gate(3).set()
        callers.join()
    assert callers.order == [1, 2, 3]  # delivered in launch order
    assert all(mask.all() for mask in callers.masks.values())
    stats = sched.stats()
    assert (stats["dispatches"], stats["launched_ahead"], stats["flush_faults"]) == (3, 2, 0)
    assert metrics.get_counter(LAUNCHED_AHEAD_KEY) - ahead == 2
    assert sched._inflight == []


@pytest.fixture
def recorder():
    rec = trace.enable(capacity=4096)
    try:
        yield rec
    finally:
        trace.disable()


@pytest.fixture
def stub_kernels(monkeypatch):
    """The device route with no compile: every live lane's signature holds."""
    monkeypatch.setattr(
        dispatch,
        "RECOVER_KERNEL",
        lambda zw, r, s, v, claimed, table, live: np.asarray(live, dtype=bool),
    )
    monkeypatch.setattr(
        dispatch,
        "DIGEST_KERNEL",
        lambda blocks, counts: np.zeros((np.shape(blocks)[0], 8), dtype=np.uint32),
    )


FLUSH_SPANS = (
    "sched.coalesce", "sched.dispatch", "verify.pack", "verify.dispatch",
    "verify.device_wait", "sched.complete",
)


def test_one_tenant_that_blocks_under_each_request_is_never_launched_ahead_of(
    recorder, stub_kernels
):
    """A validator's scheduler: ONE consensus tenant whose caller waits for
    each verdict before it asks again.  When a launch returns the queue is
    empty, so the loop reads back at once: no flush ahead, and each flush's
    spans nested and ordered as they were with one flush at a time."""
    sched = TenantScheduler(window_s=0.002, route="device")
    node = sched.register("node", _src())
    with sched:
        for height in (1, 2, 3):
            assert node.verify_senders(_round(height).prepares).all()
    stats = sched.stats()
    assert (stats["dispatches"], stats["launched_ahead"]) == (3, 0)
    spans = [
        (name, track, ts, ts + dur, args)
        for ph, name, track, ts, dur, args in recorder.snapshot()
        if ph == "X" and name in FLUSH_SPANS
    ]
    coalesces = sorted((s for s in spans if s[0] == "sched.coalesce"), key=lambda s: s[2])
    assert len(coalesces) == 3 and len(spans) == 3 * len(FLUSH_SPANS)
    assert len({s[1] for s in spans}) == 1  # one row: the flush thread's own
    for k, (_n, _track, c0, c1, args) in enumerate(coalesces):
        assert args["ahead"] == 0
        if k:
            assert c0 >= coalesces[k - 1][3]  # one flush at a time
        mine = {s[0]: s for s in spans if c0 <= s[2] and s[3] <= c1 + 1}
        assert set(mine) == set(FLUSH_SPANS)
        d0, d1 = mine["sched.dispatch"][2:4]
        for child in ("verify.pack", "verify.dispatch", "verify.device_wait"):
            assert d0 <= mine[child][2] and mine[child][3] <= d1 + 1, child
        order = ["verify.pack", "verify.dispatch", "verify.device_wait", "sched.complete"]
        assert [mine[n][2] for n in order] == sorted(mine[n][2] for n in order)
        assert mine["sched.complete"][2] >= d1 - 1


@pytest.mark.parametrize("consensus_registered", [True, False])
def test_a_read_tier_flush_is_never_launched_ahead_where_a_consensus_tenant_is_registered(
    consensus_registered,
):
    """A proof flood: three read-tier requests queued.  With a consensus
    tenant registered no read flush is launched ahead, so a consensus request
    that arrives behind the flood waits for ONE read flush and is the next
    launch.  With the read tier alone (a proof server) it is the highest
    class there is, and its flushes go two at a time."""
    gated = _Gated()
    sched = _sched(gated)
    node = sched.register("node", _src()) if consensus_registered else None
    proofs = sched.register("proofs", _src(), priority="read")
    with sched:
        with _held(sched):
            callers = _queued(sched, proofs, [1, 2, 3])
        if not consensus_registered:
            _until(lambda: len(gated.log) >= 3, "two launches and the first collect")
            _stays(lambda: gated.log == [("launch", 1), ("launch", 2), ("collect", 1)])
        else:
            _until(lambda: len(gated.log) >= 2, "the first launch and its collect")
            _stays(lambda: gated.log == [("launch", 1), ("collect", 1)] and len(sched._inflight) == 1)
            _queued(sched, node, [9], callers)  # a live round's request, behind the flood
            _stays(lambda: len(gated.log) == 2)
            gated.gate(1).set()
            _until(lambda: len(gated.log) >= 5, "the launch after the read flush")
            # One read flush of waiting, then the consensus request, alone in
            # flight: the read batch behind it is not launched ahead of it.
            assert gated.log[2:] == [("collected", 1), ("launch", 9), ("collect", 9)]
            _stays(lambda: len(gated.log) == 5 and len(sched._inflight) == 1)
        gated.open = True
        for gate in (1, 2, 3, 9):
            gated.gate(gate).set()
        callers.join()
    assert all(mask.all() for mask in callers.masks.values())
    stats = sched.stats()
    assert stats["launched_ahead"] == (0 if consensus_registered else 2)
    assert stats["dispatches"] == len(callers.masks) and stats["flush_faults"] == 0


def test_with_nothing_due_the_loop_reads_back_at_once_and_does_not_sleep_in_the_window():
    """Two consensus tenants (company MAY come), a 0.6 s window, a cap of six
    lanes: request 2 (four lanes) does not fit beside request 1, is not due
    when launch 1 returns (not aged, cap not full), and is not waited for:
    1 is collected at once; 2 goes out when its own window ends."""
    window = 0.6
    gated = _Gated(open=True)
    sched = TenantScheduler(
        window_s=window, calibrate=False, max_dispatch_lanes=6, dispatcher=gated
    )
    a = sched.register("a", _src())
    sched.register("b", _src())
    with sched:
        with _held(sched):
            callers = _queued(sched, a, [1, 2])
        callers.join()
    assert gated.log == [
        ("launch", 1), ("collect", 1), ("collected", 1),
        ("launch", 2), ("collect", 2), ("collected", 2),
    ]
    assert gated.at["collect", 1] - gated.at["launch", 1] < window / 3
    assert gated.at["launch", 2] - gated.at["launch", 1] >= window * 0.7
    assert sched.stats()["launched_ahead"] == 0


@pytest.mark.parametrize(
    "fail, served",
    [
        # N's collect raises: N's requests go to their callers' oracles and
        # N + 1, launched ahead of it, is read back and delivered.
        (("collect", 1), 2),
        # N + 1's launch raises with N outstanding: nothing of N is lost.
        (("launch", 2), 1),
    ],
)
def test_a_fault_in_either_half_fails_that_flush_alone(fail, served):
    gated = _Gated(fail=[fail])
    sched = _sched(gated)
    node = sched.register("node", _src())
    with sched:
        with _held(sched):
            callers = _queued(sched, node, [1, 2])
        _until(lambda: ("collect", 1) in gated.log, "the first collect")
        assert gated.log == [("launch", 1), ("launch", 2), ("collect", 1)]
        gated.open = True
        gated.gate(1).set()
        gated.gate(2).set()
        callers.join()
    # Exact verdicts either way: the faulted flush's from the host oracle.
    assert callers.masks[1].all() and callers.masks[2].all()
    assert ("collected", served) in gated.log
    assert ("collected", 3 - served) not in gated.log
    stats = sched.stats()
    assert (stats["dispatches"], stats["flush_faults"]) == (1, 1)
    assert stats["tenants"]["node"]["requests"] == 1  # the delivered flush's
    assert sched._inflight == []


def test_reconfigure_swaps_after_both_flushes_and_each_collects_where_it_was_launched():
    old, new = _Gated("old"), _Gated("new", open=True)
    sched = _sched(old)
    node = sched.register("node", _src())
    swapped = []
    with sched:
        with _held(sched):
            callers = _queued(sched, node, [1, 2, 3])
        _until(lambda: len(old.log) >= 3, "two flushes in flight")
        swap = threading.Thread(
            target=lambda: swapped.append(sched.reconfigure(dispatcher=new)), daemon=True
        )
        swap.start()
        _until(lambda: sched._paused, "the pause")
        _stays(lambda: not swapped and len(sched._inflight) == 2)
        old.gate(1).set()
        # 1 is delivered; 3 is NOT launched during the pause; 2 is collected
        # on the dispatcher that launched it, and only then is it swapped.
        _until(lambda: ("collect", 2) in old.log, "the second collect")
        _stays(lambda: not swapped and len(sched._inflight) == 1 and not new.log)
        old.gate(2).set()
        swap.join(10.0)
        callers.join()
    assert swapped and swapped[0]["new"] == {"route": "new"}
    assert old.log == [
        ("launch", 1), ("launch", 2), ("collect", 1), ("collected", 1),
        ("collect", 2), ("collected", 2),
    ]
    assert new.log == [("launch", 3), ("collect", 3), ("collected", 3)]
    # (Delivery order is the logs'; the callers' threads may wake in another.)
    assert sorted(callers.order) == [1, 2, 3] and all(m.all() for m in callers.masks.values())


def test_stop_completes_both_flushes_in_flight():
    gated = _Gated()
    sched = _sched(gated)
    node = sched.register("node", _src())
    sched.start()
    with _held(sched):
        callers = _queued(sched, node, [1, 2, 3])
    _until(lambda: len(gated.log) >= 3, "two flushes in flight")
    stopper = threading.Thread(target=sched.stop, daemon=True)
    stopper.start()
    _stays(lambda: stopper.is_alive() and not callers.order)
    gated.open = True
    for height in (1, 2, 3):
        gated.gate(height).set()
    stopper.join(10.0)
    assert not stopper.is_alive()
    callers.join()
    # Both in flight AND what was queued behind them: served, not shed.
    stats = sched.stats()
    assert (stats["dispatches"], stats["flush_faults"]) == (3, 0)
    assert stats["tenants"]["node"]["shed_lanes"] == 0
    assert [e for e in gated.log if e[0] == "collected"] == [("collected", h) for h in (1, 2, 3)]
    assert all(mask.all() for mask in callers.masks.values())


def test_a_cancelled_request_of_a_flush_in_flight_is_not_written_into():
    """The handle cancels a request it stopped waiting for: the flush that
    carries it, launched ahead or not, leaves the caller's array alone."""
    gated = _Gated()
    sched = _sched(gated)
    node = sched.register("node", _src())
    out = {h: np.zeros(N, dtype=bool) for h in (1, 2)}
    with sched:
        with _held(sched):
            reqs = {
                h: sched.submit(
                    node._tenant, "senders", _round(h).prepares, None, out[h], list(range(N))
                )
                for h in (1, 2)
            }
        _until(lambda: len(gated.log) >= 3, "two flushes in flight")
        reqs[2].cancelled = True  # the one launched ahead
        gated.open = True
        gated.gate(1).set()
        gated.gate(2).set()
        assert reqs[1].done.wait(10.0) and reqs[2].done.wait(10.0)
    assert out[1].all() and not out[2].any()
    assert reqs[1].error is None and reqs[2].error is None


def test_many_callers_never_put_a_third_flush_in_flight_and_lose_no_verdict():
    """Stress: more callers than cores on a short switch interval, two
    tenants, every gate open.  The log never shows a third launch before the
    oldest of two has been collected, every verdict is exact, every flush is
    accounted for and nothing stays in flight."""
    import sys

    gated = _Gated(open=True)
    sched = _sched(gated)
    handles = [sched.register(name, _src()) for name in ("a", "b")]
    want = _round(1).expected_prepare_mask
    calls, workers = 12, 16
    bad = []

    def work(k):
        for i in range(calls):
            height = 1 + (k * calls + i) % 7
            mask = handles[k % 2].verify_senders(_round_cache[height].prepares)
            if not np.array_equal(mask, want):
                bad.append((k, i))

    _round_cache = {h: _round(h) for h in range(1, 8)}
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with sched:
            threads = [threading.Thread(target=work, args=(k,), daemon=True) for k in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not bad
    in_flight = most = 0
    for event, _height in gated.log:
        in_flight += {"launch": 1, "collected": -1}.get(event, 0)
        most = max(most, in_flight)
    assert in_flight == 0 and most <= 2
    stats = sched.stats()
    assert stats["coalesced_requests"] == calls * workers and stats["flush_faults"] == 0
    assert stats["dispatches"] == sum(1 for e in gated.log if e[0] == "launch")
    assert sum(t["shed_lanes"] for t in stats["tenants"].values()) == 0
    assert sched._inflight == []
