"""Transport seam: multicast out, add_message in.

The reference's entire comm layer is a one-method interface
(core/transport.go:7-10); gossip lives in the embedder.  This build keeps the
seam; :class:`LoopbackTransport` below is the in-process fan-out for tests and
single-host clusters (the reference's test harness pattern,
core/helpers_test.go:227-231).  Further backends per SURVEY.md §5 — a
gRPC/DCN transport for multi-host deployments and the ICI lock-step
collective transport (multicast as an all_gather of fixed-size message
tensors) — plug into the same ``Transport`` protocol.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import Callable, Optional, Protocol, Sequence

from ..messages.wire import IbftMessage, MessageType
from ..obs import trace


class Transport(Protocol):
    """Fire-and-forget multicast (reference core/transport.go:7-10).

    Self-delivery is expected: nodes receive their own messages through the
    same path as everyone else's.
    """

    def multicast(self, message: IbftMessage) -> None: ...


class LoopbackTransport:
    """In-process multicast: deliver to every registered node, self included.

    Mirrors the reference test clusters' gossip closure
    (core/mock_test.go:546-550, core/helpers_test.go:227-231).  Delivery is
    synchronous and in registration order; a delivery hook lets fault tests
    drop or mutate messages per (sender, receiver).

    Telemetry: loopback dispatch hands the SAME stamped message object to
    every receiver, and each receiving engine records its own ``net.recv``
    instant at ingress (``IBFT._record_recv``) — the loopback delivery
    callback IS the engine ingress, so the trace context needs no wire
    framing here and the shared process clock makes every clock offset
    exactly zero.
    """

    def __init__(self) -> None:
        self._receivers: list[Callable[[IbftMessage], None]] = []
        # Optional fault hook: (message, receiver_index) -> deliver?
        self.should_deliver: Callable[[IbftMessage, int], bool] = lambda m, i: True

    def register(self, add_message: Callable[[IbftMessage], None]) -> None:
        self._receivers.append(add_message)

    def multicast(self, message: IbftMessage) -> None:
        for idx, deliver in enumerate(self._receivers):
            if self.should_deliver(message, idx):
                deliver(message)


class BatchingIngress:
    """Inbound micro-batcher: the TPU-native ingress shape.

    Gossip delivers messages one at a time; verifying each eagerly costs one
    device launch (or one host recover) per message — the reference's
    sequential AddMessage shape (core/ibft.go:1101-1123).  This collects a
    burst and flushes it through :meth:`IBFT.add_messages`, so sender
    signatures for the whole burst are verified in ONE device batch.

    Flushes at the end of the current event-loop tick / after ``max_delay``
    seconds, whichever the adaptive window picks (below), or on the spot
    when an explicit ``max_batch`` messages accumulate.  Event-loop affine
    (call :meth:`submit` from the loop thread); ``flush`` may be called
    directly for deterministic tests.

    **No ceiling by default.**  A phase of an n-validator committee arrives
    as n - 1 messages in one loop turn, and every cut of it costs a device
    dispatch (a 300-validator phase cut at 256 was a 512-lane dispatch and
    then a 128-lane one, in series), while a batch over the verifier's
    largest dispatch is split there, where pack and device overlap.  So a
    default-constructed ingress never cuts a burst; ``max_batch=`` asks for
    the cut, and is then also the batch the calibrated window projects to
    fill (``FILL_TARGET`` without it).

    **Adaptive window.**  The wall-clock window only earns its latency when
    the resulting batch is big enough to take the device route; below the
    adaptive verifier's cutover the batch is host-verified one message at a
    time anyway, so waiting ``max_delay`` for company is pure added latency
    — it put the 4-validator happy path ~2 ms/phase behind the sequential
    baseline (0.86x, a round-5 CPU run).  Small flows therefore flush with
    ``call_soon``: every message delivered in the same event-loop tick (a
    loopback multicast, a burst drained from one socket read) still lands
    in ONE batch, but the flush costs zero wall-clock.  The timed window
    engages when the flow is device-sized: either one flush carried
    ``>= eager_cutover`` messages, or the flushes of the last ``max_delay``
    of wall-clock add up to that many (a sustained flood arriving a few
    messages per tick — without the accumulation signal, sub-cutover eager
    flushes could never bootstrap into batching).  The window is a true
    sliding window: counts older than ``max_delay`` fall out, so a steady
    sub-cutover trickle never chains itself over the threshold, and any
    idle gap drops straight back to eager.

    **Arrival calibration (ISSUE 9).**  When the timed window engages it
    is no longer the fixed ``max_delay``: an
    :class:`~go_ibft_tpu.utils.calibration.ArrivalCalibrator` tracks the
    stream's EWMA inter-arrival gap and the wait becomes the PROJECTED
    time for the remaining ``max_batch`` lanes to arrive — a flood pays
    microseconds instead of the full 2 ms tail, and a stream measured too
    slow to fill the batch inside the ceiling flushes eagerly instead of
    idling.  ``max_delay`` stays the hard ceiling; pass
    ``calibrate=False`` for the fixed legacy window.

    **Held-back arrivals.**  A flush runs on the event loop, so while one
    blocks — a device dispatch is tens of milliseconds however few lanes
    it carries — the transport cannot deliver: a socket transport then
    hands over its backlog one message per loop turn, and an eager flush
    per message turns a 100-validator flood into 100 one-lane dispatches.
    (Both detectors above read the wall clock, so from inside that loop a
    flood held back by slow flushes looks like a trickle.)  After a flush
    that blocked for more than ``HELD_BACK_FACTOR`` windows the next burst,
    if it is still under ``eager_cutover`` when its turn ends, therefore
    waits the ``max_delay`` ceiling to let the backlog in (``held_back``) —
    a wait that is small against the flush that caused it, and one a
    sub-cutover host flush (fractions of a millisecond per lane) never
    triggers.

    **Decided when the turn ends.**  Which of these a burst gets is decided
    once, at the END of the loop turn its first message came in, from the
    buffer as the turn left it, not guessed from that first message.  A
    burst that is device-sized there (``>= eager_cutover``) was delivered
    whole, so there is no trickle to wait for and it is handed over at once:
    ``whole`` where the held-back ceiling would have been waited (a loopback
    phase after a device dispatch: 2 ms a flood, twice a height, until PR
    41), ``soon`` otherwise.  And a PREPREPARE is never timed, on any path
    (one proposer a round, everything waits for it and nothing can overtake
    it): it goes out at the end of its turn with whatever is buffered.

    **A burst worth waiting for.**  Over a socket transport a phase trickles
    in, an RPC a loop turn or two, and every flush blocks the loop under its
    verification: an eager flush a message is what both detectors above then
    see, since each flush holds the next arrival back for as long as it runs
    (a 100-validator phase became ~99 one-lane host flushes, the chip idle by
    policy).  An embedder whose verifier can reach a device, and that knows
    how many votes a decision takes (a validator: the fewest of the others'
    that complete a quorum with its own), passes that as ``burst_hint``.
    Where it is device-sized (``>= eager_cutover``) a vote that finds the
    buffer empty holds the flush, and what is held goes out at the first
    pause in the arrivals: of ``BURST_QUIET_FACTOR`` windows while fewer
    votes than the hint are held (``quiet``, whatever is held: the rest is
    not coming now), of ``BURST_DECIDING_QUIET_FACTOR`` once as many as the
    hint are (``full``: enough to decide, so with ``f`` peers silent a phase
    waits for nobody, and where every peer votes the stragglers that follow
    on the quorum's heels share its dispatch and not a second one), or when
    it has been held ``BURST_HOLD_FACTOR`` windows (``capped``: a drip that
    never pauses), whichever is first.  A PREPREPARE is not held either: it
    flushes at the end of its tick, with whatever is held (the paragraph
    above).  The gaps are sized by the one socket transport measured (TPU
    v5e host, 100 validators on loopback, PR 39: RPCs 0.25-0.5 ms apart,
    every flush a device dispatch that blocks the loop 8 ms or more; PERF.md
    §6 has the runs, with a third of the peers silent among them); the cap
    only bounds what a peer that drips can cost.
    ``burst_hint`` may be assigned later (a validator set that changes);
    without it nothing changes.
    """

    # A flush that blocked the loop for more than this many ``max_delay``
    # windows held arrivals back (see the class docstring).
    HELD_BACK_FACTOR = 4.0
    # The batch the calibrated window projects to fill where ``max_batch``
    # is left out.
    FILL_TARGET = 256
    # With ``burst_hint``: a held burst is handed over after this many
    # ``max_delay`` windows without an arrival (fewer votes than the hint
    # held; as many), and held at most this many.
    BURST_QUIET_FACTOR = 4.0
    BURST_DECIDING_QUIET_FACTOR = 1.0
    BURST_HOLD_FACTOR = 64.0

    def __init__(
        self,
        add_messages: Callable[[Sequence[IbftMessage]], None],
        *,
        max_batch: Optional[int] = None,
        max_delay: float = 0.002,
        eager_cutover: Optional[int] = None,
        calibrate: bool = True,
        burst_hint: Optional[int] = None,
    ) -> None:
        from ..utils import calibration

        if eager_cutover is None:
            eager_cutover = (
                calibration.measured_cutover() or calibration.DEFAULT_CUTOVER_LANES
            )
        self._add_messages = add_messages
        self._buffer: list[IbftMessage] = []
        self._handle: Optional[asyncio.Handle] = None
        self.max_batch = max_batch
        self.max_delay = max_delay
        self.eager_cutover = eager_cutover
        self.burst_hint = burst_hint
        self.calibrator = (
            calibration.ArrivalCalibrator(max_window_s=max_delay)
            if calibrate
            else None
        )
        # Sliding window of recent flushes [(monotonic t, n), ...] whose
        # total within the trailing ``max_delay`` is the device-sized-flow
        # detector.  A true window, not a chained sum: flushes spaced just
        # under ``max_delay`` apart must NOT accumulate forever (a slow
        # steady trickle would eventually cross the cutover and pay the
        # timed window for nothing).
        self._recent: deque = deque()
        self._recent_n = 0
        self._held_back = False
        # A burst held under ``burst_hint``: when its last message came, and
        # until when it may be held.
        self._holding = False
        self._last_arrival = 0.0
        self._hold_until = 0.0
        # ``ingress.wait``: from the submit that found the buffer empty to
        # the flush that hands the burst over, with why the flush came when
        # it did (``soon`` / ``whole`` / ``window`` / ``held_back``; ``full`` /
        # ``quiet`` / ``capped`` under ``burst_hint``; ``cut`` at flush).
        self._wait = trace.NULL_SPAN
        self._why = "soon"

    def _trim_recent(self, now: float) -> None:
        while self._recent and now - self._recent[0][0] > self.max_delay:
            self._recent_n -= self._recent.popleft()[1]

    def _window(self) -> float:
        """The timed-window wait: calibrated projection, ceiling-clamped."""
        if self.calibrator is None:
            return self.max_delay
        window = self.calibrator.window(
            len(self._buffer),
            self.FILL_TARGET if self.max_batch is None else self.max_batch,
        )
        trace.instant(
            "ingress.calibrate",
            window_us=round(window * 1e6, 1),
            pending=len(self._buffer),
        )
        return window

    def submit(self, message: IbftMessage) -> None:
        if not self._buffer:
            # Once a burst, not once a message: a flood submits hundreds.
            self._wait, self._why = trace.begin("ingress.wait"), "soon"
        self._buffer.append(message)
        if self.calibrator is not None:
            self.calibrator.observe()
        if self.max_batch is not None and len(self._buffer) >= self.max_batch:
            self._flush(cut=True)
        elif getattr(message, "type", None) == MessageType.PREPREPARE:
            # What the round waits for, and nothing can overtake it: never
            # timed or held, out at the end of this tick with what is buffered.
            if self._handle is not None:
                self._handle.cancel()
            self._holding = False
            self._why = "soon"
            self._handle = asyncio.get_running_loop().call_soon(self.flush)
        elif self._holding or (self._burst_hint is not None and self._handle is None):
            self._hold()
        elif self._handle is None:
            self._handle = asyncio.get_running_loop().call_soon(self._decide)

    def _decide(self) -> None:
        """The end of the loop turn a burst began in: hand it over as the
        turn left it, or wait for company (class docstring)."""
        self._trim_recent(time.monotonic())
        wait = 0.0
        if len(self._buffer) >= self.eager_cutover:  # device-sized as it stands
            self._why = "whole" if self._held_back else "soon"
        elif self._held_back:
            self._why, wait = "held_back", self.max_delay
        elif self._recent_n + len(self._buffer) >= self.eager_cutover:
            wait = self._window()
            self._why = "window" if wait > 0 else "soon"
        if wait > 0:
            self._handle = asyncio.get_running_loop().call_later(wait, self.flush)
        else:
            self._flush(cut=False)

    @property
    def burst_hint(self) -> Optional[int]:
        return self._burst_hint

    @burst_hint.setter
    def burst_hint(self, votes: Optional[int]) -> None:
        # Device-sized only: below the cutover the host serves the flush
        # whatever its size, and a hold would be latency for nothing.
        self._burst_hint = (
            votes if votes and votes >= self.eager_cutover else None
        )

    def _hold(self) -> None:
        """One more vote under ``burst_hint`` (class docstring)."""
        now = self._last_arrival = time.monotonic()
        if not self._holding:
            self._holding = True
            self._hold_until = now + self.BURST_HOLD_FACTOR * self.max_delay
            self._handle = asyncio.get_running_loop().call_later(
                self.max_delay, self._hold_timer
            )

    def _hold_timer(self) -> None:
        """Once a window while a burst is held: hand it over if the
        transport has been quiet long enough for what is held, or the hold
        has reached its limit."""
        now = time.monotonic()
        enough = not self._burst_hint or len(self._buffer) >= self._burst_hint
        gap = (
            self.BURST_DECIDING_QUIET_FACTOR if enough else self.BURST_QUIET_FACTOR
        ) * self.max_delay
        if now - self._last_arrival >= gap:
            self._why = "full" if enough else "quiet"
        elif now >= self._hold_until:
            self._why = "capped"
        else:
            self._handle = asyncio.get_running_loop().call_later(
                self.max_delay, self._hold_timer
            )
            return
        self._flush(cut=False)

    def flush(self) -> None:
        """Hand over what is buffered (the timer's callback; also called
        directly)."""
        self._flush(cut=False)

    def _flush(self, cut: bool) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
        if not self._buffer:
            return
        batch, self._buffer = self._buffer, []
        self._holding = False
        # The burst's height, for the spans: that of its first message.
        view = getattr(batch[0], "view", None)
        height = view.height if view is not None else None
        self._wait.end(
            lanes=len(batch), why="cut" if cut else self._why, height=height
        )
        now = time.monotonic()
        self._recent.append((now, len(batch)))
        self._recent_n += len(batch)
        self._trim_recent(now)
        # ``cut``: ``max_batch`` fired this flush in the middle of a burst
        # (the engine's ``ingress.batch`` span cannot know why it was fed).
        with trace.span("ingress.flush", lanes=len(batch), cut=cut, height=height):
            self._add_messages(batch)
        self._held_back = (
            time.monotonic() - now > self.HELD_BACK_FACTOR * self.max_delay
        )

    def close(self) -> None:
        """Drop buffered messages and cancel the pending flush timer."""
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
        if self._buffer:
            self._wait.end(lanes=len(self._buffer), why="closed")
        self._buffer.clear()
        self._holding = False
