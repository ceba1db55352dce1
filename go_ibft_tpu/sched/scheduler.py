"""TenantScheduler: process-wide verify scheduling for many chains.

Every ``ChainRunner`` so far owned a private verify ladder, so N
concurrent chains issued N small device dispatches per phase — exactly
the regime where batched signature verification wins (PAPERS.md
2302.00418) and exactly what "many chains, one device" must not do.  This
module lifts the verify data plane to PROCESS scope:

* each chain (tenant) registers once and receives a
  :class:`TenantVerifierHandle` — a drop-in
  :class:`~go_ibft_tpu.core.backend.BatchVerifier` that ``IBFT``,
  ``ChainRunner`` and ``SyncClient`` accept unchanged;
* handles submit verify requests into per-tenant queues; a dedicated
  scheduler thread coalesces queued lanes from ALL tenants into shared
  batched dispatches (:mod:`go_ibft_tpu.sched.dispatch` — the existing
  pinned kernels, one launch for many chains);
* **demand-aware flushing**: a flush fires when the coalesced batch
  reaches a full dispatch (bucket-full) or when the OLDEST queued request
  ages past the coalescing window — an idle tenant contributes nothing
  and therefore never stalls a hot one;
* **two flushes in flight** (ISSUE 55): a flush is the dispatcher's
  ``launch`` half and its ``collect`` half, and where the next batch is due
  when a launch returns, the loop coalesces and launches it BEFORE it reads
  the first one back, then delivers the first while the second runs on the
  device (:meth:`TenantScheduler._loop`: one
  :meth:`~go_ibft_tpu.verify.pipeline.VerifyPipeline.stream` a busy
  period); never a read-tier batch where a consensus tenant is registered,
  never during a ``reconfigure`` pause, never a third;
* **deficit-round-robin fairness with a hard starvation bound**: each
  flush serves the globally oldest queued request FIRST (so no request
  waits behind an unbounded stream of younger ones), then fills the
  dispatch by DRR over tenants (per-flush quantum, deficits capped), so a
  hot 100-validator tenant cannot crowd a 4-validator one out of the
  device;
* **priority classes (read-tier QoS, ISSUE 10)**: tenants register as
  ``"consensus"`` (default) or ``"read"``; selection is class-ordered —
  the oldest queued CONSENSUS request always ships first and consensus
  tenants fill the dispatch before any read-tier lane is considered, so
  the proof-serving read plane (:mod:`go_ibft_tpu.serve`) can flood the
  scheduler without ever starving a live round.  Within a class the
  oldest-first + DRR guarantees above hold unchanged; read lanes ride in
  whatever capacity consensus left unused (dispatches are 2048 lanes —
  consensus rounds rarely fill them);
* **per-chain backpressure**: each tenant's queue is bounded in lanes; a
  wedged or flooding tenant sheds load at SUBMIT time — the handle serves
  those verdicts from its local host oracle (exact, slower) — and the
  scheduler thread never blocks on any tenant (results are delivered by
  ``Event.set``, errors are handed back for the CALLER's thread to
  resolve against the oracle);
* **per-tenant observability**: ``sched.coalesce`` / ``sched.dispatch``
  spans, queue-depth gauge, per-tenant drain-latency histograms with
  p50/p99 in :meth:`TenantScheduler.stats` — the latency-SLO evidence
  bench config #10 records.

Cache namespacing (the correctness satellite): per-message packs and seal
verdicts become process-shared state here, so both are namespaced by
tenant — each handle owns a private
:class:`~go_ibft_tpu.verify.pipeline.PackCache` and a private
round-scoped seal-verdict cache, and the engine lifecycle hooks
(``note_round`` / ``reset_pack_cache`` / ``quarantine``) touch ONLY that
tenant's state.  Two chains sharing a proposal hash at the same
height/round can therefore never alias packed lanes or verdicts, and one
tenant's round rotation can never evict another's live round state
(tests/test_sched.py pins both).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from ..messages.helpers import CommittedSeal
from ..messages.wire import IbftMessage
from ..obs import trace
from ..utils import metrics
from ..verify.batch import HostBatchVerifier, _BATCH_BUCKETS
from ..verify.pipeline import PackCache, VerifyPipeline
from .dispatch import (
    CoalescedDispatcher,
    well_formed_seal_lane,
    well_formed_sender,
)

__all__ = [
    "PRIORITY_RANK",
    "SchedQueueFull",
    "TenantScheduler",
    "TenantVerifierHandle",
    "QUEUE_LANES_KEY",
    "SHED_LANES_KEY",
    "DISPATCHES_KEY",
    "COALESCED_REQUESTS_KEY",
    "DRAIN_MS_KEY",
    "FLUSH_FAULTS_KEY",
    "LAUNCHED_AHEAD_KEY",
    "SERVED_LANES_KEY",
]

QUEUE_LANES_KEY = ("go-ibft", "sched", "queue_lanes")
SHED_LANES_KEY = ("go-ibft", "sched", "shed_lanes")
DISPATCHES_KEY = ("go-ibft", "sched", "dispatches")
COALESCED_REQUESTS_KEY = ("go-ibft", "sched", "coalesced_requests")
DRAIN_MS_KEY = ("go-ibft", "sched", "drain_ms")
FLUSH_FAULTS_KEY = ("go-ibft", "sched", "flush_faults")
# Flushes launched while another was outstanding (its mask not yet read back).
LAUNCHED_AHEAD_KEY = ("go-ibft", "sched", "launched_ahead")
# Lanes a flush answered, by the tenant's class: ``SERVED_LANES_KEY +
# ("<validators>v",)``, the size of the set its last verdicts were held to.
SERVED_LANES_KEY = ("go-ibft", "sched", "served_lanes")
# Fixed-bucket per-tenant drain latency for the /metrics endpoint (the
# tenant chain id renders as the ``tag`` label; off unless
# metrics.enable_fixed_histograms() ran).
SCHED_DRAIN_MS_FIXED_KEY = ("go-ibft", "latency", "sched_drain_ms")


# Tenant QoS classes: lower rank is selected first (ISSUE 10).  Consensus
# traffic (live rounds, chain sync, overlap drains) outranks the
# proof-serving read tier by construction — see _select_locked.
PRIORITY_RANK = {"consensus": 0, "read": 1}


class SchedQueueFull(RuntimeError):
    """A tenant's queue is at its lane cap: the submission is refused so
    the scheduler never buffers unboundedly for a wedged or flooding
    tenant.  The handle resolves the request against its local host
    oracle instead (shed, not dropped — verdicts are never lost)."""


@dataclass
class _Request:
    """One queued verify request (one tenant, one kind, <= dispatch cap)."""

    tenant: "_Tenant"
    kind: str  # "senders" | "seals"
    items: list  # IbftMessage list, or (proposal_hash, seal) lane list
    height: Optional[int]  # membership height for seal lanes
    out: np.ndarray  # caller's full-length verdict array
    out_idxs: List[int]  # positions of ``items`` in ``out``
    lanes: int = 0
    submitted_at: float = 0.0
    done: threading.Event = field(default_factory=threading.Event)
    error: Optional[BaseException] = None
    cancelled: bool = False
    # ``sched.queue``: opened where the handle enqueues the request, closed
    # by the flush that takes it (on the scheduler's thread).
    queue_span: object = trace.NULL_SPAN

    def __post_init__(self) -> None:
        self.lanes = len(self.items)


class _SealVerdictCache:
    """Round-scoped (signer, hash, sig, height) -> verdict, one per tenant.

    The engine keeps its own per-sequence seal-verdict cache; this one
    lives at PROCESS scope (inside the scheduler's tenant state) and is
    therefore namespaced by construction — a verdict stored for chain A
    can never serve chain B, even for byte-identical (signer, proposal
    hash, seal) at the same height/round.  Eviction mirrors the engine's:
    dead rounds go first, the live round evicts FIFO within itself."""

    def __init__(self, cap: int = 4096):
        self._lock = threading.Lock()
        self._by_round: Dict[int, Dict[tuple, bool]] = {}
        self._count = 0
        self._round = 0
        self._cap = cap

    @property
    def cap(self) -> int:
        return self._cap

    def __len__(self) -> int:
        with self._lock:
            return self._count

    def note_round(self, round_: int) -> None:
        with self._lock:
            self._round = round_

    def clear(self) -> None:
        with self._lock:
            self._by_round.clear()
            self._count = 0
            self._round = 0

    def lookup(self, key: tuple) -> Optional[bool]:
        with self._lock:
            for bucket in self._by_round.values():
                if key in bucket:
                    return bucket[key]
            return None

    def store(self, key: tuple, verdict: bool) -> None:
        with self._lock:
            bucket = self._by_round.setdefault(self._round, {})
            if key not in bucket:
                self._count += 1
            bucket[key] = verdict
            while self._count > self._cap and self._by_round:
                oldest = min(self._by_round)
                bucket = self._by_round[oldest]
                if oldest == self._round:
                    bucket.pop(next(iter(bucket)))
                    self._count -= 1
                    if not bucket:
                        del self._by_round[oldest]
                else:
                    self._count -= len(bucket)
                    del self._by_round[oldest]


class _Tenant:
    """Per-registration scheduler state: queue, fairness, caches, stats."""

    def __init__(
        self,
        tid: str,
        chain_id: str,
        validators: Callable[[int], Mapping[bytes, int]],
        calibrator=None,
        priority: str = "consensus",
        max_queue_lanes: Optional[int] = None,
        pack_cache_cap: Optional[int] = None,
        verdict_cache_cap: Optional[int] = None,
    ):
        self.tid = tid
        self.chain_id = chain_id
        self.validators = validators
        self.priority = priority
        self.rank = PRIORITY_RANK[priority]
        # Per-tenant arrival model (ISSUE 9): EWMA inter-arrival rate,
        # summed across active tenants to project how fast the shared
        # dispatch will fill — the calibrated replacement for the fixed
        # coalescing window.
        self.calibrator = calibrator
        self.queue: Deque[_Request] = deque()
        self.queued_lanes = 0
        self.deficit = 0
        # Per-tenant budgets (ISSUE 16): an explicit queue-lane bound
        # overrides the scheduler-wide default, and the cache caps size
        # THIS tenant's slice of process memory — a 4-validator chain can
        # ride along a 100-validator one without inheriting its footprint.
        self.max_queue_lanes = max_queue_lanes
        # ``draining`` marks a tenant mid-removal: new submissions are
        # refused (the handle's host oracle serves them — shed, not
        # dropped) while already-queued work keeps flushing.
        self.draining = False
        # Namespaced caches (satellite: process-shared caches keyed by
        # tenant — lifecycle hooks touch only THIS tenant's state).
        self.pack_cache = (
            PackCache(cap=pack_cache_cap)
            if pack_cache_cap is not None
            else PackCache()
        )
        self.verdicts = (
            _SealVerdictCache(cap=verdict_cache_cap)
            if verdict_cache_cap is not None
            else _SealVerdictCache()
        )
        # SLO evidence.  ``slo_lock`` orders the scheduler thread's
        # sample appends (_complete) against stats() snapshots — a live
        # monitoring scrape must never crash on a mutating deque.
        self.slo_lock = threading.Lock()
        self.drain_ms: Deque[float] = deque(maxlen=4096)
        self.requests = 0
        self.lanes = 0
        self.shed_lanes = 0
        self.sheds = 0
        # Validators of the set this tenant's last verdicts were held to (0
        # until boot or a flush has read one): its class in the counters.
        self.committee = 0


def _percentile(samples: Sequence[float], q: float) -> Optional[float]:
    if not samples:
        return None
    ordered = sorted(samples)
    idx = min(len(ordered) - 1, max(0, int(round(q * (len(ordered) - 1)))))
    return ordered[idx]


class _Flight:
    """One flush from its selection to its delivery: the requests, what the
    dispatcher was handed and what it gave back, between the loop's steps."""

    __slots__ = (
        "batch", "ahead", "track", "dispatcher", "span",
        "sender_reqs", "seal_reqs", "msgs", "lanes", "owners",
        "launched", "masks", "error",
    )

    def __init__(
        self, batch: List[_Request], ahead: int, track: Optional[str], dispatcher
    ) -> None:
        self.batch = batch
        self.ahead = ahead  # flushes in flight when this one was selected
        self.track = track  # its timeline row; None: the flush thread's own
        self.dispatcher = dispatcher  # both halves run on this one
        self.span = trace.NULL_SPAN
        self.sender_reqs: List[_Request] = []
        self.seal_reqs: List[_Request] = []
        self.msgs: List[IbftMessage] = []
        self.lanes: List[Tuple[bytes, CommittedSeal]] = []
        self.owners: Dict[int, PackCache] = {}
        self.launched = None
        self.masks = None
        self.error: Optional[BaseException] = None

    @property
    def outstanding(self) -> bool:
        """Whether a launched flush has a read-back still to wait for: not
        after a fault, and not where the work was done when the launch
        returned (the host route)."""
        return self.error is None and not self.launched.ready


class TenantScheduler:
    """Coalesces verify lanes from N tenants into shared dispatches.

    ``window_s`` is the coalescing window (measured from the OLDEST
    queued request — demand-aware, never a periodic tick);
    ``max_dispatch_lanes`` caps one coalesced dispatch (default: the
    largest single-device lane bucket); ``max_queue_lanes`` is the
    per-tenant backpressure bound; ``quantum_lanes`` is the DRR quantum.
    ``route`` feeds the :class:`CoalescedDispatcher` ("auto" routes small
    flushes to the native host path and large ones to the device, like
    the adaptive single-tenant verifier).

    Use as a context manager, or call :meth:`start` / :meth:`stop`.
    """

    def __init__(
        self,
        *,
        window_s: float = 0.002,
        max_dispatch_lanes: int = _BATCH_BUCKETS[-1],
        max_queue_lanes: int = 8192,
        quantum_lanes: int = 256,
        route: str = "auto",
        dispatcher: Optional[CoalescedDispatcher] = None,
        request_timeout_s: float = 30.0,
        calibrate: bool = True,
    ):
        if max_dispatch_lanes < 1 or max_queue_lanes < 1 or quantum_lanes < 1:
            raise ValueError("scheduler bounds must be >= 1")
        self.window_s = window_s
        # Arrival-calibrated windows (ISSUE 9): ``window_s`` becomes the
        # CEILING; the actual wait for the oldest queued request is the
        # projected dispatch-fill time at the measured aggregate arrival
        # rate (per-tenant EWMA models, summed over tenants with queued
        # work).  A stream measured too slow to fill the dispatch inside
        # the ceiling flushes immediately instead of idling out the
        # window.  ``calibrate=False`` restores the fixed window.
        self.calibrate = calibrate
        self.max_dispatch_lanes = min(max_dispatch_lanes, _BATCH_BUCKETS[-1])
        self.max_queue_lanes = max_queue_lanes
        self.quantum_lanes = quantum_lanes
        self.request_timeout_s = request_timeout_s
        self._dispatcher = (
            dispatcher if dispatcher is not None else CoalescedDispatcher(route)
        )
        self._cv = threading.Condition()
        self._tenants: Dict[str, _Tenant] = {}
        self._rr: List[str] = []  # round-robin order (registration order)
        self._rr_next = 0
        self._pending_reqs = 0
        self._pending_lanes = 0
        self._running = False
        self._thread: Optional[threading.Thread] = None
        # Live-reconfiguration state (ISSUE 16): ``_inflight`` holds the
        # flushes between their selection and their delivery, in launch
        # order (at most two: the loop launches one ahead of the read-back
        # of another); ``_paused`` stops the loop from starting new ones
        # while :meth:`reconfigure` drains and swaps the dispatcher.
        # Submissions stay open throughout — queued work just waits out the
        # pause (the flushes in flight, two at most).
        self._inflight: List[_Flight] = []
        self._paused = False
        # Evidence counters (config #10 reads these via stats()).
        self.dispatches = 0
        self.coalesced_requests = 0
        self.coalesced_lanes = 0
        self.flush_faults = 0
        self.launched_ahead = 0

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "TenantScheduler":
        with self._cv:
            if self._running:
                return self
            self._running = True
        self._thread = threading.Thread(
            target=self._loop, name="tenant-sched", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        """Stop accepting work; the loop drains everything already queued
        before the thread exits (no request is ever abandoned)."""
        with self._cv:
            self._running = False
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def __enter__(self) -> "TenantScheduler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    @property
    def running(self) -> bool:
        with self._cv:
            return self._running

    def warmup(self, **kw) -> None:
        """Pre-compile the shared kernels (node startup; never mid-round)."""
        self._dispatcher.warmup(**kw)

    def warmup_committee(
        self, n_validators: int, read_tier: bool = False
    ) -> None:
        """Pre-compile every shape a committee of ``n_validators`` can make
        the dispatcher launch (node startup, while ``/readyz`` is 503);
        ``read_tier``: a proof-serving tenant coalesces up to a full
        dispatch, so every width.  The one-tenant case of
        :meth:`warmup_tenants`, for a node that knows its committee's size
        before its engine has registered."""
        self._dispatcher.warmup_committee(n_validators, read_tier)

    def warmup_tenants(self, heights) -> dict:
        """The multi-tenant boot: pin and pre-compile what the tenants
        registered SO FAR can make the dispatcher launch, and nothing else
        from then on (process startup, every chain registered, before the
        first of them verifies).  ``heights``: the height each tenant's
        validator set is read at (``{tenant id: height}``, or one height
        for all).  The lane widths follow from the committees' sizes and
        this scheduler's dispatch cap, the table rows from the distinct
        validators of all the tenants together
        (:meth:`CoalescedDispatcher.warmup_tenants`).  A tenant registered
        later is served from what is loaded: a flush its signers outgrow is
        cut, never compiled for.  Returns the dispatcher's description."""
        with self._cv:
            tenants = list(self._tenants.values())
        if not tenants:
            raise ValueError("no tenant is registered")
        sets = []
        for tenant in tenants:
            at = heights if isinstance(heights, int) else heights[tenant.tid]
            sets.append(frozenset(tenant.validators(at)))
            tenant.committee = len(sets[-1])
        self._dispatcher.warmup_tenants(
            [len(members) for members in sets],
            signers=len(frozenset().union(*sets)),
            max_lanes=self.max_dispatch_lanes,
            read_tier=any(t.rank == PRIORITY_RANK["read"] for t in tenants),
        )
        return self._dispatcher.describe()

    def reconfigure(
        self,
        *,
        dispatcher: Optional[CoalescedDispatcher] = None,
        route: Optional[str] = None,
        dp: Optional[int] = None,
        devices=None,
        warm_lanes: Optional[Sequence[int]] = None,
        table_rows: int = 8,
    ) -> dict:
        """Zero-downtime dispatcher swap / device-mesh resize (ISSUE 16).

        The replacement dispatcher is built — and, with ``warm_lanes``,
        pre-compiled — BEFORE the flush loop pauses, so every tenant keeps
        draining through the old data plane while the new mesh programs
        compile; the swap itself waits only for the flushes in flight (two
        at most: the loop launches nothing ahead during the pause, and each
        flush collects on the dispatcher that launched it).  ``dp`` / ``devices`` re-enter through
        :func:`~go_ibft_tpu.parallel.mesh.mesh_context` (a 1-device
        resolution degrades to the single-device kernels); an explicit
        ``dispatcher`` wins over all shape arguments.  Submissions stay
        open throughout and queued requests survive the swap untouched —
        no tenant misses a height.  Returns ``{"old", "new"}`` dispatcher
        descriptions (the churn-soak evidence)."""
        if dispatcher is None:
            kw = {}
            if dp is not None or devices is not None:
                kw = {"dp": dp, "devices": devices}
            dispatcher = CoalescedDispatcher(
                route if route is not None else self._dispatcher.route, **kw
            )
        if warm_lanes:
            dispatcher.warmup(lanes=warm_lanes, table_rows=table_rows)
        old = self._dispatcher
        with self._cv:
            self._paused = True
            try:
                while self._inflight:
                    self._cv.wait()
                self._dispatcher = dispatcher
            finally:
                self._paused = False
                self._cv.notify_all()
        desc = {"old": old.describe(), "new": dispatcher.describe()}
        trace.instant("sched.reconfigure", **desc["new"])
        return desc

    # -- tenants ---------------------------------------------------------

    def register(
        self,
        tenant_id: str,
        validators_for_height: Callable[[int], Mapping[bytes, int]],
        *,
        chain_id: Optional[str] = None,
        priority: str = "consensus",
        max_queue_lanes: Optional[int] = None,
        pack_cache_cap: Optional[int] = None,
        verdict_cache_cap: Optional[int] = None,
    ) -> "TenantVerifierHandle":
        """Register one tenant (typically one engine of one chain) and
        return its scheduler-backed verifier handle.  ``chain_id`` labels
        the chain for stats aggregation (defaults to the tenant id).
        ``priority`` is the QoS class: ``"consensus"`` (default) for live
        rounds, ``"read"`` for the proof-serving plane — read lanes only
        fill dispatch capacity consensus left unused, so a proof flood
        can never starve a finalizing chain.

        Per-tenant budgets (ISSUE 16): ``max_queue_lanes`` bounds THIS
        tenant's queue (overriding the scheduler-wide default), and
        ``pack_cache_cap`` / ``verdict_cache_cap`` size its private
        caches — all surfaced per tenant in :meth:`stats`."""
        if priority not in PRIORITY_RANK:
            raise ValueError(
                f"unknown priority {priority!r} "
                f"(expected one of {sorted(PRIORITY_RANK)})"
            )
        with self._cv:
            if tenant_id in self._tenants:
                raise ValueError(f"tenant {tenant_id!r} already registered")
            from ..utils.calibration import ArrivalCalibrator

            tenant = _Tenant(
                tenant_id,
                chain_id or tenant_id,
                validators_for_height,
                calibrator=(
                    ArrivalCalibrator(max_window_s=self.window_s)
                    if self.calibrate
                    else None
                ),
                priority=priority,
                max_queue_lanes=max_queue_lanes,
                pack_cache_cap=pack_cache_cap,
                verdict_cache_cap=verdict_cache_cap,
            )
            self._tenants[tenant_id] = tenant
            self._rr.append(tenant_id)
        return TenantVerifierHandle(self, tenant)

    def add_tenant(self, tenant_id, validators_for_height, **kw):
        """Zero-downtime registration (ISSUE 16 naming): identical to
        :meth:`register` — registration has always been safe while the
        flush loop runs (one lock-guarded map insert; the next selection
        pass sees the tenant), so adding a chain to a live scheduler
        costs no pause and no other tenant a height."""
        return self.register(tenant_id, validators_for_height, **kw)

    def remove_tenant(
        self,
        tenant_id: str,
        *,
        drain: bool = True,
        timeout_s: float = 30.0,
    ) -> bool:
        """Zero-downtime removal.  With ``drain`` (default) the tenant
        stops accepting NEW submissions immediately — its handle sheds
        them to the host oracle, so verdicts are never lost — while
        everything already queued keeps flushing through the shared
        dispatch; the tenant is dropped once its queue empties.
        ``drain=False`` (or a drain timeout, or a stopped scheduler)
        refuses the still-queued requests back to their callers' oracles,
        exactly like :meth:`unregister`.  Returns True when the queue
        drained clean.  Survivor tenants never miss a height either way:
        nothing pauses, their queued lanes keep shipping."""
        with self._cv:
            tenant = self._tenants.get(tenant_id)
            if tenant is None:
                return True
            tenant.draining = True
        if drain:
            deadline = time.monotonic() + timeout_s
            with self._cv:
                while tenant.queue and self._running:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    # Flush completions notify; the poll cap bounds the
                    # wait if one slips past between check and wait.
                    self._cv.wait(timeout=min(left, 0.05))
        with self._cv:
            drained = not tenant.queue
        self.unregister(tenant_id)
        trace.instant(
            "sched.remove_tenant", tenant=tenant_id, drained=drained
        )
        return drained

    def unregister(self, tenant_id: str) -> None:
        with self._cv:
            tenant = self._tenants.pop(tenant_id, None)
            if tenant is None:
                return
            self._rr.remove(tenant_id)
            # Outstanding requests are refused back to the handle's oracle
            # rather than silently dropped.
            for req in tenant.queue:
                self._pending_reqs -= 1
                self._pending_lanes -= req.lanes
                req.error = SchedQueueFull("tenant unregistered")
                req.done.set()
            tenant.queue.clear()
            tenant.queued_lanes = 0

    # -- submission (handle-side) ---------------------------------------

    def submit(
        self,
        tenant: _Tenant,
        kind: str,
        items: list,
        height: Optional[int],
        out: np.ndarray,
        out_idxs: List[int],
    ) -> _Request:
        if len(items) > self.max_dispatch_lanes:
            raise ValueError("request exceeds dispatch cap; chunk it first")
        req = _Request(tenant, kind, items, height, out, out_idxs)
        with self._cv:
            if not self._running:
                raise SchedQueueFull("scheduler is not running")
            if tenant.draining or self._tenants.get(tenant.tid) is not tenant:
                # Mid-removal (or an already-removed handle): refuse so
                # the caller's oracle serves the verdict immediately
                # instead of queueing work nothing will ever select.
                raise SchedQueueFull(f"tenant {tenant.tid!r} is draining")
            cap = (
                tenant.max_queue_lanes
                if tenant.max_queue_lanes is not None
                else self.max_queue_lanes
            )
            if tenant.queued_lanes + req.lanes > cap:
                raise SchedQueueFull(
                    f"tenant {tenant.tid!r} queue at {tenant.queued_lanes} "
                    f"lanes (cap {cap})"
                )
            req.submitted_at = time.monotonic()
            req.queue_span = trace.begin(
                "sched.queue", kind=kind, lanes=req.lanes, tier=tenant.priority
            )
            if tenant.calibrator is not None:
                tenant.calibrator.observe(req.lanes, now=req.submitted_at)
            tenant.queue.append(req)
            tenant.queued_lanes += req.lanes
            self._pending_reqs += 1
            self._pending_lanes += req.lanes
            metrics.set_gauge(QUEUE_LANES_KEY, float(self._pending_lanes))
            self._cv.notify_all()
        return req

    def note_shed(self, tenant: _Tenant, lanes: int) -> None:
        tenant.shed_lanes += lanes
        tenant.sheds += 1
        metrics.inc_counter(SHED_LANES_KEY, lanes)
        trace.instant("sched.shed", tenant=tenant.tid, lanes=lanes)

    # -- the flush loop --------------------------------------------------

    def _oldest_ts_locked(self) -> Optional[float]:
        ts = [t.queue[0].submitted_at for t in self._tenants.values() if t.queue]
        return min(ts) if ts else None

    def _no_company_due_locked(self) -> bool:
        """Whether the window can gain nothing for the consensus tier: a
        consensus tenant is an engine, whose ONE loop thread blocks under
        every request, so once every consensus tenant has work queued (a
        validator's scheduler has one: its own engine) no consensus lane is
        left to come, and each millisecond waited is a millisecond of the
        round.  The read tier rides in what that flush leaves.  Read tenants
        alone never end the window early: a proof server's workers call one
        tenant concurrently, and the window is what coalesces them."""
        consensus = [
            t for t in self._tenants.values() if t.rank == PRIORITY_RANK["consensus"]
        ]
        return bool(consensus) and all(t.queue for t in consensus)

    def _window_locked(self) -> float:
        """The coalescing window for the current backlog, from the
        measured AGGREGATE arrival rate (per-tenant EWMA models summed
        over tenants with queued work) through the shared
        :func:`~go_ibft_tpu.utils.calibration.calibrated_window` policy:
        the fill projection when the dispatch will fill inside the
        ``window_s`` ceiling, the ceiling when a sustained flood merely
        cannot fill ALL of it, eager (0) only when the ceiling would
        gain almost nothing.  Falls back to the fixed ``window_s`` when
        no rate has been measured yet."""
        if not self.calibrate:
            return self.window_s
        from ..utils.calibration import calibrated_window

        rate = 0.0
        for t in self._tenants.values():
            if t.queue and t.calibrator is not None:
                r = t.calibrator.rate_per_s()
                if r:
                    rate += r
        window = calibrated_window(
            rate if rate > 0 else None,
            self._pending_lanes,
            self.max_dispatch_lanes,
            self.window_s,
        )
        trace.instant(
            "ingress.calibrate",
            scope="sched",
            window_us=round(window * 1e6, 1),
            rate_per_s=round(rate, 1),
            pending=self._pending_lanes,
        )
        return window

    def _due_in_locked(self) -> float:
        """Seconds until what is queued is due a flush (<= 0: now).  At once
        at bucket-full or where no company can come; else when the oldest
        queued request has aged past the (arrival-calibrated) window.  Idle
        tenants contribute no requests and thus no delay."""
        if self._pending_lanes >= self.max_dispatch_lanes:
            return 0.0
        if self._no_company_due_locked():
            return 0.0
        oldest = self._oldest_ts_locked()
        if oldest is None:
            return 0.0
        return oldest + self._window_locked() - time.monotonic()

    def _may_launch_ahead_locked(self) -> bool:
        """Whether the next flush may be launched ahead of the read-back of
        the one in flight, by what the loop can see: never during a pause;
        only while that one has a mask outstanding (a host-route flush was
        done when its launch returned: delivering it comes first); only a
        batch that is due NOW (the loop does not sleep in the window with a
        mask outstanding); and only one whose first request is of the highest
        class that has a tenant registered, so a read-tier batch is never in
        flight beside another and a consensus request that arrives behind a
        proof flood waits for one flush of it, as before."""
        if self._paused or self._pending_reqs == 0:
            return False
        if not self._inflight[0].outstanding:
            return False
        queued = min(t.rank for t in self._tenants.values() if t.queue)
        if queued != min(t.rank for t in self._tenants.values()):
            return False
        # A stopped scheduler drains: everything queued is due.
        return not self._running or self._due_in_locked() <= 0

    def _loop(self) -> None:
        """The flush thread.  A flush is two halves (the dispatcher's
        ``launch`` and ``collect``) and at most TWO are in flight: one busy
        period is one :meth:`VerifyPipeline.stream` over the batches that are
        due (:meth:`_due_batches`), so flush N + 1 is coalesced and launched
        before N is read back, and N is delivered (membership, ``req.out``,
        ``req.done``) while N + 1 runs on the device.  With nothing due
        behind a launch the stream reads it back at once: one flush at a
        time, as it was before there were two."""
        pipeline = VerifyPipeline(depth=2)
        while True:
            with self._cv:
                while self._running and (
                    self._pending_reqs == 0 or self._paused
                ):
                    self._cv.wait()
                if self._pending_reqs == 0 and not self._running:
                    return
            for flight in pipeline.stream(
                self._due_batches(), self._coalesce, self._launch, self._collect
            ):
                self._deliver(flight)

    def _due_batches(self):
        """One busy period's flushes, each selected only when the stream is
        ready to launch it.  With nothing in flight this is the demand-aware
        window: flush at bucket-full, or when the oldest queued request ages
        past the window.  With a flush in flight it never sleeps: the next
        batch goes ahead if :meth:`_may_launch_ahead_locked` says so, else
        the period ends and the stream reads back what is outstanding."""
        while True:
            with self._cv:
                if self._inflight:
                    go = self._may_launch_ahead_locked()
                else:
                    while self._running and not self._paused:
                        wait = self._due_in_locked()
                        if wait <= 0:
                            break
                        self._cv.wait(timeout=wait)
                        if self._pending_reqs == 0:
                            break
                    # A running pause (reconfigure draining the dispatcher)
                    # selects nothing; stop() still drains everything.
                    go = not (self._paused and self._running)
                batch = self._select_locked() if go else []
                if not batch:
                    return
                # Two flushes in flight overlap in time without nesting: a
                # timeline row each (the thread's own for the one that found
                # it free), so no span of one reads as a child of the other's.
                track = None
                if any(f.track is None for f in self._inflight):
                    track = threading.current_thread().name + "+1"
                # ``_inflight`` holds every flush from here to its delivery
                # and ``reconfigure`` swaps when it is empty: both halves of
                # a flush run on the dispatcher read here.
                flight = _Flight(batch, len(self._inflight), track, self._dispatcher)
                self._inflight.append(flight)
                self.launched_ahead += flight.ahead
            if flight.ahead:
                metrics.inc_counter(LAUNCHED_AHEAD_KEY)
            yield flight

    def _select_locked(self) -> List[_Request]:
        """Pick one dispatch's worth of requests.

        Selection is CLASS-ORDERED first (read-tier QoS, ISSUE 10): the
        oldest queued request of the highest-priority class with queued
        work always ships first, and lower classes only fill capacity the
        higher ones left unused — so the consensus starvation bound is
        hard (a proof flood adds at most one in-flight flush of latency,
        never a queueing delay), while read traffic still drains through
        the spare lanes of every dispatch.

        Within a class the prior guarantees hold: the oldest queued
        request is never passed over in favor of younger same-class
        traffic, and the remaining capacity fills by deficit round robin
        — each non-empty tenant earns ``quantum_lanes`` per flush
        (capped at one dispatch) and spends it on whole requests, so
        lane-hungry tenants cannot monopolize consecutive flushes."""
        batch: List[_Request] = []
        lanes = 0
        active = [t for t in self._tenants.values() if t.queue]
        if not active:
            return batch

        def take(tenant: _Tenant) -> _Request:
            nonlocal lanes
            req = tenant.queue.popleft()
            tenant.queued_lanes -= req.lanes
            self._pending_reqs -= 1
            self._pending_lanes -= req.lanes
            lanes += req.lanes
            batch.append(req)
            return req

        top_rank = min(t.rank for t in active)
        oldest_tenant = min(
            (t for t in active if t.rank == top_rank),
            key=lambda t: t.queue[0].submitted_at,
        )
        take(oldest_tenant)
        n = len(self._rr)
        for class_rank in sorted({t.rank for t in self._tenants.values()}):
            for k in range(n):
                tid = self._rr[(self._rr_next + k) % n]
                tenant = self._tenants[tid]
                if tenant.rank != class_rank:
                    continue
                if not tenant.queue:
                    tenant.deficit = 0
                    continue
                tenant.deficit = min(
                    tenant.deficit + self.quantum_lanes, self.max_dispatch_lanes
                )
                while (
                    tenant.queue
                    and lanes + tenant.queue[0].lanes <= self.max_dispatch_lanes
                    and tenant.deficit >= tenant.queue[0].lanes
                ):
                    tenant.deficit -= tenant.queue[0].lanes
                    take(tenant)
                if lanes >= self.max_dispatch_lanes:
                    break
            if lanes >= self.max_dispatch_lanes:
                break
        # Idle tenants reset their deficit even when a full dispatch cut
        # the walk short of visiting them — the documented no-banked-
        # credit invariant must not depend on loop reachability.
        for tenant in self._tenants.values():
            if not tenant.queue:
                tenant.deficit = 0
        if n:
            self._rr_next = (self._rr_next + 1) % n
        metrics.set_gauge(QUEUE_LANES_KEY, float(self._pending_lanes))
        return batch

    def _coalesce(self, flight: "_Flight") -> "_Flight":
        """A flush's first step: its requests leave their queues' spans, the
        lanes of all its tenants are gathered by kind, and ``sched.coalesce``
        opens (``ahead``: 1 where another flush was outstanding); it closes
        in :meth:`_deliver`."""
        batch = flight.batch
        for req in batch:
            req.queue_span.end(requests=len(batch))
        flight.sender_reqs = [r for r in batch if r.kind == "senders"]
        flight.seal_reqs = [r for r in batch if r.kind == "seals"]
        for req in flight.sender_reqs:
            for m in req.items:
                flight.owners[id(m)] = req.tenant.pack_cache
            flight.msgs.extend(req.items)
        for req in flight.seal_reqs:
            flight.lanes.extend(req.items)
        flight.span = trace.begin(
            "sched.coalesce",
            track=flight.track,
            tenants=len({r.tenant.tid for r in batch}),
            requests=len(batch),
            lanes=len(flight.msgs) + len(flight.lanes),
            ahead=flight.ahead,
        )
        return flight

    def _launch(self, flight: "_Flight") -> "_Flight":
        """The dispatcher's launch half.  A fault is kept for
        :meth:`_deliver`: it fails THIS flush's requests, and the stream goes
        on to read back the other one in flight."""
        try:
            with trace.on_track(flight.track):
                flight.launched = flight.dispatcher.launch(
                    flight.msgs, flight.lanes, flight.owners
                )
        except Exception as err:  # noqa: BLE001 - handed back in _deliver
            flight.error = err
        return flight

    def _collect(self, flight: "_Flight") -> "_Flight":
        """The dispatcher's collect half: the blocking read-backs, on the
        dispatcher that launched the flush."""
        if flight.error is None:
            try:
                with trace.on_track(flight.track):
                    flight.masks = flight.dispatcher.collect(flight.launched)
            except Exception as err:  # noqa: BLE001 - handed back in _deliver
                flight.error = err
        return flight

    def _deliver(self, flight: "_Flight") -> None:
        """A flush's last step, with the next one already on the device:
        each request's membership and verdicts (``sched.complete``), or its
        fault handed back; then the flush leaves ``_inflight``."""
        batch = flight.batch
        lanes = len(flight.msgs) + len(flight.lanes)
        try:
            if flight.error is not None:
                # The scheduler thread resolves NOTHING itself: each
                # caller's thread falls back to its tenant's host oracle,
                # so one poisoned flush cannot stall every tenant behind
                # a slow sequential re-verify here.
                self.flush_faults += 1
                metrics.inc_counter(FLUSH_FAULTS_KEY)
                for req in batch:
                    req.error = flight.error
                    req.done.set()
                return
            self.dispatches += 1
            self.coalesced_requests += len(batch)
            self.coalesced_lanes += lanes
            metrics.inc_counter(DISPATCHES_KEY)
            metrics.inc_counter(COALESCED_REQUESTS_KEY, len(batch))
            sender_ok, seal_ok = flight.masks
            with trace.span(
                "sched.complete", track=flight.track, requests=len(batch), lanes=lanes
            ):
                for reqs, sig_ok in (
                    (flight.sender_reqs, sender_ok),
                    (flight.seal_reqs, seal_ok),
                ):
                    off = 0
                    for req in reqs:
                        self._complete(req, sig_ok[off : off + req.lanes])
                        off += req.lanes
        finally:
            flight.span.end()
            with self._cv:
                self._inflight.remove(flight)
                self._cv.notify_all()

    def _complete(self, req: _Request, sig_ok: np.ndarray) -> None:
        """Apply the tenant's membership check and deliver the verdicts."""
        try:
            validators = req.tenant.validators
            mask = np.zeros(req.lanes, dtype=bool)
            powers_by_height: Dict[int, Mapping[bytes, int]] = {}
            for i, item in enumerate(req.items):
                if not sig_ok[i]:
                    continue
                if req.kind == "senders":
                    height, claimed = item.view.height, item.sender
                else:
                    height, claimed = req.height, item[1].signer
                    if isinstance(height, list):  # a height a lane
                        height = height[i]
                powers = powers_by_height.get(height)
                if powers is None:
                    powers = powers_by_height[height] = validators(height)
                    req.tenant.committee = len(powers)
                mask[i] = claimed in powers
            if not req.cancelled:
                req.out[np.asarray(req.out_idxs)] = mask
        except Exception as err:  # noqa: BLE001 - caller resolves via oracle
            req.error = err
        finally:
            dt_ms = (time.monotonic() - req.submitted_at) * 1e3
            with req.tenant.slo_lock:
                req.tenant.drain_ms.append(dt_ms)
                req.tenant.requests += 1
                req.tenant.lanes += req.lanes
            metrics.observe(DRAIN_MS_KEY, dt_ms)
            metrics.observe_fixed(
                SCHED_DRAIN_MS_FIXED_KEY + (req.tenant.chain_id,), dt_ms
            )
            metrics.inc_counter(
                SERVED_LANES_KEY + (f"{req.tenant.committee}v",), req.lanes
            )
            req.done.set()

    # -- evidence --------------------------------------------------------

    def stats(self) -> dict:
        """Scheduler + per-tenant SLO snapshot (bench config #10 evidence)."""
        def tenant_row(t: _Tenant) -> dict:
            with t.slo_lock:  # vs the scheduler thread's sample appends
                samples = list(t.drain_ms)
                requests, lanes = t.requests, t.lanes
            return {
                "chain": t.chain_id,
                "priority": t.priority,
                "committee": t.committee,
                "queue_lanes": t.queued_lanes,
                "requests": requests,
                "lanes": lanes,
                "sheds": t.sheds,
                "shed_lanes": t.shed_lanes,
                "drain_p50_ms": _percentile(samples, 0.50),
                "drain_p99_ms": _percentile(samples, 0.99),
                "arrival": (
                    t.calibrator.stats() if t.calibrator is not None else None
                ),
                "draining": t.draining,
                # Per-tenant memory/queue budgets (ISSUE 16): live
                # occupancy vs cap for each namespaced resource.
                "budgets": {
                    "queue_lanes_cap": (
                        t.max_queue_lanes
                        if t.max_queue_lanes is not None
                        else self.max_queue_lanes
                    ),
                    "pack_entries": len(t.pack_cache),
                    "pack_cap": t.pack_cache.cap,
                    "verdict_entries": len(t.verdicts),
                    "verdict_cap": t.verdicts.cap,
                },
            }

        with self._cv:
            tenants = {
                tid: tenant_row(t) for tid, t in self._tenants.items()
            }
            dispatches = self.dispatches
            requests = self.coalesced_requests
            lanes = self.coalesced_lanes
            faults = self.flush_faults
            ahead = self.launched_ahead
        return {
            "tenants": tenants,
            "dispatches": dispatches,
            "coalesced_requests": requests,
            "coalesced_lanes": lanes,
            "coalesce_ratio": (
                round(requests / dispatches, 3) if dispatches else None
            ),
            "flush_faults": faults,
            # Flushes launched while another was outstanding.
            "launched_ahead": ahead,
            # Tests wrap the dispatcher in doubles without describe() /
            # served(); degrade rather than breaking stats().
            "dispatcher": (
                self._dispatcher.describe()
                if hasattr(self._dispatcher, "describe")
                else {"route": type(self._dispatcher).__name__}
            ),
            "served": (
                self._dispatcher.served()
                if hasattr(self._dispatcher, "served")
                else None
            ),
        }


class TenantVerifierHandle:
    """One tenant's drop-in ``BatchVerifier`` over the shared scheduler.

    Implements the verify surface the engine, the chain runner's overlap
    worker and the sync client use — ``verify_senders``,
    ``verify_committed_seals``, ``verify_seal_lanes`` — plus the engine
    lifecycle hooks (``note_round`` / ``reset_pack_cache`` /
    ``quarantine``), all scoped to THIS tenant.  Every verdict is exact
    against the tenant's own sequential host oracle: membership is
    evaluated over the tenant's validator source, and any shed / faulted
    / timed-out request is resolved by the oracle in the caller's thread
    (degraded latency, never degraded correctness, and never a blocked
    scheduler)."""

    def __init__(self, scheduler: TenantScheduler, tenant: _Tenant):
        self._sched = scheduler
        self._tenant = tenant
        self._oracle = HostBatchVerifier(tenant.validators)

    @property
    def tenant_id(self) -> str:
        return self._tenant.tid

    # -- engine lifecycle hooks (tenant-scoped by construction) ----------

    def note_round(self, round_: int) -> None:
        """Round advance for THIS tenant only: tags this tenant's pack
        and verdict caches; no other tenant's live round state moves."""
        self._tenant.pack_cache.note_round(round_)
        self._tenant.verdicts.note_round(round_)

    def reset_pack_cache(self) -> None:
        """New sequence for THIS tenant only."""
        self._tenant.pack_cache.clear()
        self._tenant.verdicts.clear()

    def quarantine(self, msgs: Sequence[IbftMessage]) -> None:
        for m in msgs:
            self._tenant.pack_cache.evict(m)

    def seed_seal_verdicts(self, entries) -> int:
        """Warm-start hook (ISSUE 16): pre-load seal verdicts replayed
        from the WAL into THIS tenant's verdict cache.  ``entries`` is an
        iterable of ``((signer, proposal_hash, signature, height), bool)``
        pairs — the exact cache key :meth:`verify_committed_seals` uses —
        so a restarted node's first seal drain after recovery is cache
        hits, not device (or oracle) lanes.  Sound because every seeded
        verdict comes from a finalized block the WAL already trusts
        (see go_ibft_tpu/boot/warmstart.py)."""
        n = 0
        verdicts = self._tenant.verdicts
        for key, verdict in entries:
            verdicts.store(tuple(key), bool(verdict))
            n += 1
        return n

    def warmup(self, **kw) -> None:
        self._sched.warmup(**kw)

    # -- BatchVerifier ---------------------------------------------------

    def verify_senders(self, msgs: Sequence[IbftMessage]) -> np.ndarray:
        msgs = list(msgs)
        out = np.zeros(len(msgs), dtype=bool)
        idxs = [i for i, m in enumerate(msgs) if well_formed_sender(m)]
        if idxs:
            self._run("senders", [msgs[i] for i in idxs], None, idxs, out)
        return out

    def verify_committed_seals(
        self, proposal_hash: bytes, seals: Sequence[CommittedSeal], height: int
    ) -> np.ndarray:
        seals = list(seals)
        out = np.zeros(len(seals), dtype=bool)
        if len(proposal_hash) != 32:
            return out
        fresh_idxs: List[int] = []
        fresh_keys: List[tuple] = []
        verdicts = self._tenant.verdicts
        for i, seal in enumerate(seals):
            if not well_formed_seal_lane(proposal_hash, seal):
                continue
            key = (seal.signer, proposal_hash, seal.signature, height)
            hit = verdicts.lookup(key)
            if hit is not None:
                out[i] = hit
            else:
                fresh_idxs.append(i)
                fresh_keys.append(key)
        if fresh_idxs:
            self._run(
                "seals",
                [(proposal_hash, seals[i]) for i in fresh_idxs],
                height,
                fresh_idxs,
                out,
            )
            for i, key in zip(fresh_idxs, fresh_keys):
                verdicts.store(key, bool(out[i]))
        return out

    def verify_seal_lanes(
        self, lanes: Sequence[Tuple[bytes, CommittedSeal]], height
    ) -> np.ndarray:
        """``height``: one for every lane, or one a lane (a sync range that
        crosses validator-set boundaries); membership is this tenant's, on
        the host, at each lane's own height."""
        lanes = list(lanes)
        out = np.zeros(len(lanes), dtype=bool)
        idxs = [
            i
            for i, (proposal_hash, seal) in enumerate(lanes)
            if well_formed_seal_lane(proposal_hash, seal)
        ]
        if idxs:
            if not isinstance(height, int):
                height = [height[i] for i in idxs]
            self._run("seals", [lanes[i] for i in idxs], height, idxs, out)
        return out

    # -- submission machinery -------------------------------------------

    def _run(
        self,
        kind: str,
        items: list,
        height: Optional[int],
        idxs: List[int],
        out: np.ndarray,
    ) -> None:
        cap = self._sched.max_dispatch_lanes
        pending: List[Tuple[_Request, list, List[int]]] = []
        for start in range(0, len(items), cap):
            chunk = items[start : start + cap]
            chunk_idxs = idxs[start : start + cap]
            at = height[start : start + cap] if isinstance(height, list) else height
            try:
                req = self._sched.submit(
                    self._tenant, kind, chunk, at, out, chunk_idxs
                )
            except SchedQueueFull:
                # Backpressure: serve locally, never block or drop.
                self._sched.note_shed(self._tenant, len(chunk))
                self._oracle_fill(kind, chunk, at, chunk_idxs, out)
                continue
            pending.append((req, chunk, chunk_idxs))
        for req, chunk, chunk_idxs in pending:
            if not req.done.wait(self._sched.request_timeout_s):
                # Defensive: a dead scheduler thread must not wedge the
                # consensus loop.  Mark the request so a late flush
                # cannot write into an array the caller already owns.
                req.cancelled = True
                self._sched.note_shed(self._tenant, len(chunk))
                self._oracle_fill(kind, chunk, req.height, chunk_idxs, out)
            elif req.error is not None:
                self._oracle_fill(kind, chunk, req.height, chunk_idxs, out)

    def _oracle_fill(
        self,
        kind: str,
        items: list,
        height: Optional[int],
        idxs: List[int],
        out: np.ndarray,
    ) -> None:
        if kind == "senders":
            mask = self._oracle.verify_senders(items)
        else:
            mask = self._oracle.verify_seal_lanes(items, height)
        out[np.asarray(idxs)] = np.asarray(mask, dtype=bool)[: len(idxs)]
