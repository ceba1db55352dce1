"""Pipelined verification data plane: overlap host packing with device work.

JAX dispatch is asynchronous: a jitted call returns device futures
immediately and the host only blocks when it *reads* a result.  The
sequential shape ``pack -> dispatch -> read -> pack -> ...`` throws that
away — the host sits idle while the device runs, then the device sits idle
while the host packs the next batch.  :class:`VerifyPipeline` is the
double-buffered executor that keeps both sides busy: it packs item ``N+1``
on the host while the device executes item ``N``, reading results back only
when the in-flight window (``depth``, default 2 = classic double buffering)
is full.  The same executor drives a thread-pool "device" for the
host-routed benchmark variants (the native C++ verifier releases the GIL,
so host packing genuinely overlaps native verification).

Buffer discipline (measured, not assumed):

* **Host zero-copy packing.**  The packers build each batch in one flat
  staging buffer with ``frombuffer`` views and vectorized padding — no
  per-message bytearray churn (see ``ops/keccak.py::pack_messages``).
* **Device-resident validator tables.**  ``DeviceBatchVerifier`` pins each
  height's packed table (and quorum-power vectors) on device once and
  reuses the handle across every dispatch of the height — re-uploading
  them per call was a per-dispatch host->device copy for data that never
  changes within a height.
* **Buffer donation stays REJECTED** for the verification kernels (the
  PR-1 finding holds for the pipelined path too): XLA only aliases a
  donated input to an output of matching shape/dtype, and these programs
  map large packed inputs — ``(B, nb, 17, 2)`` keccak blocks, ``(B, 20)``
  limb vectors — to tiny ``(B,)`` masks.  Nothing aliases, so
  ``donate_argnums`` would perform no reuse and emit a warning per
  compile; the per-item inputs are freed by refcount right after dispatch
  regardless.

:class:`PackCache` is the second half of the data plane: a per-message
pack cache (message identity -> packed sender lane) with round-scoped
oldest-round-first eviction, mirroring the engine's seal-verdict cache, so
engine wakeups that re-verify the same messages (certificate validation
re-runs per round-change wakeup) never re-encode or re-limb a message they
already packed.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import (
    Any,
    Callable,
    Deque,
    Dict,
    Iterable,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from ..obs import ledger as cost_ledger
from ..obs import trace
from ..utils import metrics

__all__ = [
    "PACK_MS_KEY",
    "READBACK_WAIT_MS_KEY",
    "OVERLAP_EFFICIENCY_KEY",
    "BREAKER_LEVEL_KEY",
    "BREAKER_TRANSITIONS_KEY",
    "CircuitBreaker",
    "PipelineReport",
    "VerifyPipeline",
    "SenderPack",
    "PackCache",
    "observe_overlap_efficiency",
]

# First-class packing-attribution metric keys (satellite: pack_ms and
# overlap efficiency are round evidence, not debug prints).
PACK_MS_KEY = ("go-ibft", "pipeline", "pack_ms")
READBACK_WAIT_MS_KEY = ("go-ibft", "pipeline", "readback_wait_ms")
OVERLAP_EFFICIENCY_KEY = ("go-ibft", "pipeline", "overlap_efficiency")

# Degradation-ladder metric keys: the breaker's active level as a gauge and
# every transition as a histogram sample (value = the level transitioned TO),
# so ``metrics.summarize(BREAKER_TRANSITIONS_KEY)`` shows transition counts
# without a scrape sink.  Per-edge counters ride
# ``("go-ibft", "breaker", <demote|restore|probe|probe_failed>)``.
BREAKER_LEVEL_KEY = ("go-ibft", "breaker", "level")
BREAKER_TRANSITIONS_KEY = ("go-ibft", "breaker", "transitions")


class CircuitBreaker:
    """K-consecutive-fault demotion ladder with cooldown re-probe.

    ``levels`` names the rungs fastest-first (e.g. ``("device", "host",
    "python")``); traffic starts at level 0.  After ``k`` consecutive
    recorded faults at the active level the breaker demotes one rung; after
    ``cooldown_s`` seconds at a demoted level :meth:`acquire` offers the
    next-faster rung once as a *probe* — a successful probe restores one
    rung, a failed probe restarts the cooldown.  Restoration is therefore
    stepwise: a ladder that fell two rungs climbs back one cooldown at a
    time, each step proven by live traffic.

    Thread-safe; ``clock`` is injectable so tests control the cooldown.
    Every transition is counted in :mod:`go_ibft_tpu.utils.metrics`.
    """

    def __init__(
        self,
        levels: Sequence[str] = ("device", "host", "python"),
        *,
        k: int = 3,
        cooldown_s: float = 5.0,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        if not levels:
            raise ValueError("breaker needs at least one level")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.levels = tuple(levels)
        self.k = k
        self.cooldown_s = cooldown_s
        self._clock = clock
        self._lock = threading.Lock()
        self._level = 0
        self._faults = 0
        self._demoted_at: Optional[float] = None
        self._probing = False

    @property
    def level(self) -> int:
        with self._lock:
            return self._level

    @property
    def level_name(self) -> str:
        return self.levels[self.level]

    def acquire(self) -> Tuple[int, bool]:
        """Pick the level for one drain: ``(level, is_probe)``.

        At a demoted level past its cooldown, returns the next-faster level
        with ``is_probe=True`` (exactly one in-flight probe at a time; the
        caller MUST answer with :meth:`record_success` or
        :meth:`record_fault` for that level).
        """
        with self._lock:
            if (
                self._level > 0
                and not self._probing
                and self._demoted_at is not None
                and self._clock() - self._demoted_at >= self.cooldown_s
            ):
                self._probing = True
                metrics.inc_counter(("go-ibft", "breaker", "probe"))
                return self._level - 1, True
            return self._level, False

    def record_success(self, level: int) -> None:
        """A drain at ``level`` completed without a fault."""
        with self._lock:
            if self._probing and level == self._level - 1:
                self._probing = False
                self._transition(level, "restore")
            elif level == self._level:
                self._faults = 0

    def abort_probe(self, level: int) -> None:
        """Release a probe whose drain never exercised the probed rung
        (input poison aborted it pre-dispatch, or the work was routed to a
        different rung): the ladder stays demoted, no fault is recorded,
        and — the cooldown having already elapsed — the next drain is
        offered a fresh probe.  Recording success instead would restore
        the ladder on no evidence; recording a fault would punish a rung
        that never ran.  No-op unless ``level`` is the pending probe."""
        with self._lock:
            if self._probing and level == self._level - 1:
                self._probing = False

    def record_fault(self, level: int) -> bool:
        """A drain at ``level`` faulted; returns True when this demoted."""
        with self._lock:
            if self._probing and level == self._level - 1:
                # Probe failed: stay demoted, restart the cooldown clock.
                self._probing = False
                self._demoted_at = self._clock()
                metrics.inc_counter(("go-ibft", "breaker", "probe_failed"))
                return False
            if level != self._level:
                return False
            self._faults += 1
            if self._faults >= self.k and self._level + 1 < len(self.levels):
                self._transition(self._level + 1, "demote")
                return True
            return False

    def _transition(self, new_level: int, kind: str) -> None:
        # Callers hold self._lock.
        self._level = new_level
        self._faults = 0
        self._demoted_at = self._clock() if new_level > 0 else None
        metrics.inc_counter(("go-ibft", "breaker", kind))
        metrics.observe(BREAKER_TRANSITIONS_KEY, float(new_level))
        metrics.set_gauge(BREAKER_LEVEL_KEY, float(new_level))
        trace.instant(
            "breaker.transition", kind=kind, level=self.levels[new_level]
        )


def observe_overlap_efficiency(serial_s: float, pipelined_s: float) -> float:
    """Record and return the overlap efficiency of a pipelined run.

    ``1 - pipelined/serial`` — the fraction of the serial wall-clock the
    pipeline hid by overlapping host packing with device execution
    (0 = no overlap, 0.5 = packing fully hidden behind an equally-long
    device leg).  Clamped at 0 so measurement noise never reports a
    negative efficiency.
    """
    eff = 0.0 if serial_s <= 0 else max(0.0, 1.0 - pipelined_s / serial_s)
    metrics.observe(OVERLAP_EFFICIENCY_KEY, eff)
    return eff


@dataclass
class PipelineReport:
    """One pipelined run's results + host-side time attribution.

    ``pack_s``/``dispatch_s``/``wait_s`` partition the host thread's time:
    packing, (asynchronous) dispatch calls, and blocking on device results.
    Overlap shows up as ``wait_s`` shrinking — device time hidden behind
    packing never blocks the host.  ``wall_s`` is end-to-end.
    """

    results: List[Any]
    pack_s: float
    dispatch_s: float
    wait_s: float
    wall_s: float


class VerifyPipeline:
    """Double-buffered pack/dispatch executor over an async device.

    ``depth`` bounds the number of dispatched-but-unread items (2 = double
    buffering: while item N executes, item N+1 packs and dispatches; N is
    read back only when N+2 wants its slot).  The executor is agnostic to
    what "dispatch" means — a jitted JAX call (returns device futures), a
    ``ThreadPoolExecutor.submit`` (host-routed bench variants), or a test
    stub — as long as it returns quickly and ``readback`` blocks until the
    handle's work is done.
    """

    def __init__(
        self,
        depth: int = 2,
        ledger_key: Optional[Tuple[str, str]] = None,
    ):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.depth = depth
        # (program, route) for the cost ledger: the async dispatch seam
        # records the launch but cannot see its block-until-ready wall —
        # the pipeline owns the readback wait, so it attributes that leg
        # (no-op while the ledger is disabled).
        self.ledger_key = ledger_key

    def run(
        self,
        items: Sequence[Any],
        pack: Callable[[Any], Any],
        dispatch: Callable[[Any], Any],
        readback: Callable[[Any], Any],
    ) -> PipelineReport:
        """Run ``readback(dispatch(pack(item)))`` for every item, pipelined.

        Results are returned in item order.  Exceptions propagate after all
        in-flight work is drained (a dispatched batch is never abandoned
        mid-flight — device buffers must be consumed).
        """
        report = PipelineReport([], 0.0, 0.0, 0.0, 0.0)
        report.results.extend(self.stream(items, pack, dispatch, readback, report))
        return report

    def stream(
        self,
        items: Iterable[Any],
        pack: Callable[[Any], Any],
        dispatch: Callable[[Any], Any],
        readback: Callable[[Any], Any],
        report: Optional[PipelineReport] = None,
    ) -> Iterator[Any]:
        """:meth:`run` as a generator: each item's result, in item order,
        the moment it is read back.

        ``items`` is pulled one item at a time, each only when the pipeline
        is ready to pack it, so whatever MAKES an item (a generator's body)
        runs while the item before is on the device; and the consumer has
        item N's result while item N + 1 executes.  The order of events for
        a sequence is :meth:`run`'s: pack and dispatch N + 1, then read back
        N.  Closed early, or on an exception, it reads back what is in
        flight and drops it.  ``report`` gets the host time attribution
        (its ``wall_s`` includes what the consumer did between results).
        """
        inflight: Deque[Tuple[int, Any]] = deque()
        if report is None:
            report = PipelineReport([], 0.0, 0.0, 0.0, 0.0)
        t_start = time.perf_counter()

        def _finish_oldest() -> Any:
            idx, handle = inflight.popleft()
            t0 = time.perf_counter()
            with trace.span("pipeline.readback", item=idx):
                result = readback(handle)
            dt = time.perf_counter() - t0
            report.wait_s += dt
            metrics.observe(READBACK_WAIT_MS_KEY, dt * 1e3)
            if self.ledger_key is not None:
                cost_ledger.add_wall_ms(
                    self.ledger_key[0], self.ledger_key[1], dt * 1e3
                )
            return result

        try:
            for i, item in enumerate(items):
                t0 = time.perf_counter()
                with trace.span("pipeline.pack", item=i):
                    packed = pack(item)
                dt = time.perf_counter() - t0
                report.pack_s += dt
                metrics.observe(PACK_MS_KEY, dt * 1e3)

                t0 = time.perf_counter()
                with trace.span("pipeline.dispatch", item=i):
                    inflight.append((i, dispatch(packed)))
                report.dispatch_s += time.perf_counter() - t0

                while len(inflight) >= self.depth:
                    yield _finish_oldest()
            while inflight:
                yield _finish_oldest()
        finally:
            while inflight:
                _finish_oldest()
            report.wall_s = time.perf_counter() - t_start


@dataclass
class SenderPack:
    """One message's packed sender lane (everything per-message about it).

    ``payload`` is the canonical ``payload_no_sig`` encoding; the limb rows
    and word vectors are exactly the lane the batch packers would rebuild.
    """

    payload: bytes
    r_limbs: np.ndarray  # (nlimbs,) int32
    s_limbs: np.ndarray  # (nlimbs,) int32
    v: int
    sender_words: np.ndarray  # (5,) uint32


@dataclass(frozen=True)
class SenderRows:
    """One drain's sender lanes as its packer left them: message ``i`` of the
    drain is row ``i`` of each array (padding and cache-hit rows included,
    so a row index is a message's position)."""

    payloads: Sequence[bytes]
    r_limbs: np.ndarray  # (lanes, nlimbs) int32
    s_limbs: np.ndarray  # (lanes, nlimbs) int32
    v: np.ndarray  # (lanes,) int32
    sender_words: np.ndarray  # (lanes, 5) uint32

    def lane(self, i: int) -> SenderPack:
        return SenderPack(
            payload=self.payloads[i],
            r_limbs=self.r_limbs[i],
            s_limbs=self.s_limbs[i],
            v=int(self.v[i]),
            sender_words=self.sender_words[i],
        )


class PackCache:
    """Message identity -> :class:`SenderPack`, round-scoped eviction.

    Keyed on the message *object* (``id`` + a weak reference so a dead
    object's recycled id can never alias a stale entry) and guarded by a
    ``(sender, signature)`` token so in-place mutation of either field
    (tests and Byzantine harnesses do this) turns the entry into a miss.
    A drain stores its fresh lanes BY REFERENCE, in one call under one lock
    (:meth:`store_rows`): an entry is the drain's :class:`SenderRows` and a
    row index, and the :class:`SenderPack` is made on a hit.  The packers
    never write a row array again once it is packed
    (``tests/test_pack_vectorized.py`` holds them to it).  A dead message's
    entry is a miss from the moment it dies (its weak reference is gone) and
    is dropped when the cache is next counted, by eviction in its turn, or
    by ``clear()``: no callback runs per message.
    The payload itself is NOT re-checked on hit — the cache contract is the
    message-store contract: stored messages are replaced, never mutated
    (``messages/store.py`` dedup is last-write-wins on whole objects), and
    any same-object payload mutation also breaks the signature it was
    packed with, which ingress already verified.

    Eviction mirrors the engine's seal-verdict cache: entries are tagged
    with the round current at pack time (``note_round``); on cap pressure
    whole dead rounds evict before any live round gives up anything, and
    within a live round eviction is FIFO.  ``clear()`` runs per sequence.
    Thread-safe (ingress may pack from transport threads).

    **Owner scoping (ISSUE 8 satellite).**  A cache shared by several
    engines (one ladder serving N chains) must not let one engine's
    lifecycle rotate or reset another's live state: every entry is tagged
    with an *owner* — the thread-local label installed by
    :meth:`owned` while a scoped verify call packs — and ``note_round``
    / ``clear`` take an optional ``owner`` so a rotation retags, and a
    sequence reset drops, ONLY that owner's entries.  The legacy
    single-engine calls (no owner) keep their process-wide meaning: the
    default owner is ``""`` for ``note_round``, and an ownerless
    ``clear()`` still wipes everything (the sole-owner posture).  Each
    owner's live round is protected from cap-pressure eviction
    independently; dead rounds of any owner evict first, oldest round
    first.
    """

    def __init__(self, cap: int = 8192):
        self._lock = threading.RLock()
        # (owner, round) -> {id(msg) -> (weakref, token, source, row)}: the
        # source is a SenderPack (row None) or a drain's SenderRows.
        self._by_round: Dict[
            Tuple[str, int],
            Dict[int, Tuple[Any, Tuple[bytes, bytes], Any, Optional[int]]],
        ] = {}
        self._index: Dict[int, Tuple[str, int]] = {}  # id(msg) -> tag
        self._count = 0
        self._rounds: Dict[str, int] = {"": 0}  # owner -> live round
        self._tl = threading.local()
        self._cap = cap
        self.hits = 0
        self.misses = 0

    @property
    def _round(self) -> int:
        """Default owner's live round (single-engine posture)."""
        with self._lock:
            return self._rounds.get("", 0)

    @contextmanager
    def owned(self, owner: str):
        """Attribute stores on THIS thread to ``owner`` while active (the
        :class:`~go_ibft_tpu.verify.batch.EngineScope` verify wrapper)."""
        prev = getattr(self._tl, "owner", "")
        self._tl.owner = owner
        try:
            yield self
        finally:
            self._tl.owner = prev

    def note_round(self, round_: int, owner: str = "") -> None:
        """Tag ``owner``'s subsequent stores with ``round_`` (engine round
        advances).  Only that owner's eviction ordering moves."""
        with self._lock:
            self._rounds[owner] = round_

    def clear(self, owner: Optional[str] = None) -> None:
        """Drop cached packs: all of them (``owner=None`` — the
        single-engine sequence reset) or one owner's only."""
        with self._lock:
            if owner is None:
                self._by_round.clear()
                self._index.clear()
                self._count = 0
                self._rounds = {"": 0}
                return
            for tag in [t for t in self._by_round if t[0] == owner]:
                bucket = self._by_round.pop(tag)
                for mid in bucket:
                    del self._index[mid]
                self._count -= len(bucket)
            self._rounds.pop(owner, None)
            self._rounds.setdefault("", 0)

    def __len__(self) -> int:
        with self._lock:
            self._prune_dead()
            return self._count

    @property
    def cap(self) -> int:
        return self._cap

    def lookup(self, msg) -> Optional[SenderPack]:
        return self.lookup_many((msg,))[0]

    def lookup_many(self, msgs: Sequence[Any]) -> List[Optional[SenderPack]]:
        """A drain's lookups in one call, under one lock: per message its
        :class:`SenderPack`, or ``None`` on a miss."""
        out: List[Optional[SenderPack]] = [None] * len(msgs)
        with self._lock:
            index, found = self._index, 0
            if index:
                for i, msg in enumerate(msgs):
                    mid = id(msg)
                    tag = index.get(mid)
                    if tag is None:
                        continue
                    wref, token, source, row = self._by_round[tag][mid]
                    if wref() is msg and token == (msg.sender, msg.signature):
                        out[i] = source if row is None else source.lane(row)
                        found += 1
            self.hits += found
            self.misses += len(out) - found
        return out

    def store(self, msg, pack: SenderPack) -> None:
        self.store_rows((msg,), pack, (None,))

    def store_rows(self, msgs: Sequence[Any], rows, at: Sequence[Optional[int]]) -> None:
        """A drain's fresh lanes in one call, by reference: ``msgs[k]``'s
        lane is row ``at[k]`` of ``rows`` (a :class:`SenderRows`, which the
        caller never writes again; or, row ``None``, a whole
        :class:`SenderPack`)."""
        with self._lock:
            owner = getattr(self._tl, "owner", "")
            tag = (owner, self._rounds.setdefault(owner, 0))
            index, bucket = self._index, None
            for msg, row in zip(msgs, at):
                try:
                    wref = weakref.ref(msg)
                except TypeError:  # not weak-referenceable; skip caching
                    continue
                mid = id(msg)
                if mid in index:
                    self._remove(mid)
                    bucket = None  # it may have gone with its last entry
                if bucket is None:
                    bucket = self._by_round.setdefault(tag, {})
                bucket[mid] = (wref, (msg.sender, msg.signature), rows, row)
                index[mid] = tag
                self._count += 1
            self._evict()

    def evict(self, msg) -> None:
        """Drop a message's cached pack (degraded-mode quarantine hook).

        A quarantined lane's pack must not outlive the quarantine: if the
        sender corrects and re-sends, the verifier must re-pack from the
        fresh bytes rather than be served the lane that was just condemned.
        No-op for messages never cached."""
        with self._lock:
            self._remove(id(msg))

    # -- internals ------------------------------------------------------

    def _prune_dead(self) -> None:
        """Drop the entries whose message is gone (its id may be recycled)."""
        dead = [
            mid
            for bucket in self._by_round.values()
            for mid, entry in bucket.items()
            if entry[0]() is None
        ]
        for mid in dead:
            self._remove(mid)

    def _remove(self, mid: int) -> None:
        tag = self._index.pop(mid, None)
        if tag is None:
            return
        bucket = self._by_round.get(tag)
        if bucket is not None and bucket.pop(mid, None) is not None:
            self._count -= 1
            if not bucket:
                del self._by_round[tag]

    def _evict(self) -> None:
        while self._count > self._cap and self._by_round:
            # EVERY owner's live round is protected equally: dead rounds
            # (any owner, oldest round first) evict whole; only when no
            # dead round remains does the oldest live round shed FIFO.
            live = {(o, r) for o, r in self._rounds.items()}
            dead = [t for t in self._by_round if t not in live]
            pool = dead if dead else list(self._by_round)
            oldest = min(pool, key=lambda t: (t[1], t[0]))
            bucket = self._by_round[oldest]
            if not dead:
                mid = next(iter(bucket))
                del bucket[mid]
                del self._index[mid]
                self._count -= 1
                if not bucket:
                    del self._by_round[oldest]
            else:
                for mid in bucket:
                    del self._index[mid]
                self._count -= len(bucket)
                del self._by_round[oldest]
