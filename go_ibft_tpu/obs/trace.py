"""Zero-dependency span API — the flight recorder's instrumentation face.

Usage at a hot seam::

    from go_ibft_tpu.obs import trace

    with trace.span("verify.pack", lanes=n):
        ...pack...

    trace.instant("round.timeout", round=r)

Design rules (ISSUE 4 tentpole):

* **Disabled mode is one predicate check.**  ``span()`` and ``instant()``
  read one module global each; when nothing listens they return a
  shared no-op context manager / return immediately.  No clock reads, no
  contextvar touches, no allocation beyond the caller's kwargs dict.
  ``tests/test_obs.py`` pins the shared no-op; what tracing costs when it
  is ON is the benchmark's ``--trace 1`` run beside its ``--trace 0`` run.
* **One clock with the device.**  While tracing is enabled every span is
  also a ``jax.profiler.TraceAnnotation`` named ``ibft:<span name>``: in
  any open profiler window (the benchmark's, ``/profilez``) the program's
  spans lie in the xplane's host plane, on the device events' clock, with
  the span's scalar args, ``ts_us`` (the ring's start of the same span) and
  ``ts_gap_ns`` (how far apart the two clocks were read) as the event's
  stats.  :mod:`~go_ibft_tpu.obs.idle` charges the device's
  idle time to them.  ``jax`` is imported at :func:`enable`; without it
  the ring works alone.  Instants stay ring-only.  An annotation cannot be
  back-dated, so a span opens at its cause and closes at its effect:
  :func:`begin` is for the ones whose effect is in another callback or
  task (``ingress.wait``, ``engine.wake``).
* **Thread-safe.**  The recorder is a lock-guarded ring
  (:class:`~go_ibft_tpu.obs.recorder.RingRecorder`); spans may open and
  close on transport threads, worker pools, and the engine loop
  concurrently.
* **Tracks.**  Every record carries a track name — the timeline row it
  renders on (one per consensus node, plus one per auxiliary thread).
  Resolution order: explicit ``track=`` argument, then the inherited
  track (a ``contextvars.ContextVar`` set by the nearest enclosing span
  that passed ``track=`` — drains instrumented inside the engine inherit
  the node's track automatically, including across ``create_task``
  boundaries), then the current thread name.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time
from typing import Optional

from .recorder import DEFAULT_CAPACITY, RingRecorder

__all__ = [
    "enable",
    "disable",
    "enabled",
    "recorder",
    "span",
    "begin",
    "bridged",
    "NULL_SPAN",
    "instant",
    "set_track",
    "on_track",
    "next_span_id",
]

# THE predicate of ``instant()`` (and of ``enabled()``): the ring.
_recorder: Optional[RingRecorder] = None
# THE predicate of ``span()``: ``None`` while nothing listens, else
# ``(ring or None, TraceAnnotation or None)``.  Every span site reads this
# one global and nothing else on the disabled path.
_sink: Optional[tuple] = None
# Open ``bridged()`` windows: they keep the bridge on where the ring is off.
_bridge_holds = 0
_state_lock = threading.Lock()

# Prefix of a span's profiler event.  The benchmark's reduction takes
# ``bench:`` events only, so it reads what it read.
ANNOTATION_PREFIX = "ibft:"
# What an annotation can carry as a stat; other args stay in the ring.
_SCALARS = (int, float, str, bool)

_track_var: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "go_ibft_obs_track", default=None
)


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, or ``None`` where jax is missing."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # noqa: BLE001 - the ring works without jax
        return None
    return TraceAnnotation


def _refresh() -> None:
    """Recompute ``_sink`` from the ring and the bridge holds (lock held)."""
    global _sink
    if _recorder is None and not _bridge_holds:
        _sink = None
    else:
        _sink = (_recorder, _annotation_class())


def enable(capacity: int = DEFAULT_CAPACITY) -> RingRecorder:
    """Install (and return) a fresh ring recorder; spans start recording,
    and appear in any open ``jax.profiler`` window."""
    global _recorder
    with _state_lock:
        _recorder = RingRecorder(capacity)
        _refresh()
        return _recorder


def disable() -> None:
    """Remove the recorder; every span site reverts to the no-op path
    (unless a :func:`bridged` window is open)."""
    global _recorder
    with _state_lock:
        _recorder = None
        _refresh()


@contextlib.contextmanager
def bridged():
    """Spans are profiler events for the duration, ring or no ring:
    :func:`go_ibft_tpu.obs.devprof.capture` wraps its window in this, so
    an operator's ``/profilez`` trace shows the program's spans without
    the flight recorder."""
    global _bridge_holds
    with _state_lock:
        _bridge_holds += 1
        _refresh()
    try:
        yield
    finally:
        with _state_lock:
            _bridge_holds -= 1
            _refresh()


def enabled() -> bool:
    return _recorder is not None


def recorder() -> Optional[RingRecorder]:
    return _recorder


_span_id_counter = itertools.count(1)


def next_span_id() -> int:
    """Fresh per-process span id (links a ``net.send`` to its ``net.recv``
    records across nodes; ``itertools.count.__next__`` is atomic under the
    GIL, so transport threads need no lock)."""
    return next(_span_id_counter)


def set_track(name: str) -> contextvars.Token:
    """Set the inherited track for the current context; returns the reset
    token.  Rarely needed directly — passing ``track=`` to the outermost
    span of a scope does the same and resets itself."""
    return _track_var.set(name)


class _OnTrack:
    __slots__ = ("_name", "_tok")

    def __init__(self, name: str) -> None:
        self._name = name

    def __enter__(self):
        self._tok = _track_var.set(self._name)
        return self

    def __exit__(self, *exc):
        _track_var.reset(self._tok)
        return False


def on_track(name: Optional[str]):
    """Spans opened inside the ``with`` that name no track of their own lie
    on ``name`` (``None``: wherever they would have).  For a caller that
    wants another module's spans on a row of its choosing; the shared no-op
    unless tracing is enabled."""
    if name is None or _sink is None:
        return _NULL
    return _OnTrack(name)


def _resolve_track(explicit: Optional[str]) -> str:
    if explicit is not None:
        return explicit
    inherited = _track_var.get()
    if inherited is not None:
        return inherited
    return threading.current_thread().name


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        pass

    def end(self, **args) -> None:
        pass


_NULL = _NullSpan()
# What ``span()`` and ``begin()`` return while tracing is disabled: a site
# that keeps a ``begin()`` handle starts from it.
NULL_SPAN = _NULL


class _Span:
    __slots__ = ("_rec", "_ann", "name", "track", "args", "_t0", "_gap", "_tok")

    def __init__(self, sink, name, track, args, pin=True):
        self._rec, annotation = sink
        self.name = name
        self.track = _resolve_track(track)
        self.args = args
        # ``pin``: spans opened inside this one inherit its track.  Not for
        # a span that closes in another context (the token could not be
        # reset there).
        self._tok = (
            _track_var.set(self.track) if pin and track is not None else None
        )
        # The annotation CLASS until ``__enter__`` makes the event.
        self._ann = annotation
        self._t0 = 0

    def __enter__(self):
        # No profiler window open: the ring alone (one C++ call to ask).
        if self._ann is None or not self._ann.is_enabled():
            self._ann = None
            self._t0 = time.perf_counter_ns()
            return self
        # A ``TraceMe`` reads its start clock where it is CONSTRUCTED (its
        # ``__enter__`` does nothing), so that is done here, between two
        # reads of the ring's clock: ``obs/idle.py`` checks that ring start
        # minus profiler start is one constant over a window, over the
        # spans whose reads were back to back (``ts_gap_ns`` small: the
        # thread was not descheduled, no collection ran between them).
        before = time.perf_counter_ns()
        self._ann = self._ann(ANNOTATION_PREFIX + self.name)
        self._t0 = time.perf_counter_ns()
        self._gap = self._t0 - before
        return self

    def note(self, **args) -> None:
        """Attributes known only once the span's work is under way."""
        self.args.update(args)

    def end(self, **args) -> None:
        """Close a span opened with :func:`begin`."""
        if args:
            self.args.update(args)
        self.__exit__(None, None, None)

    def __exit__(self, exc_type, exc, tb):
        now = time.perf_counter_ns()
        if exc_type is not None:
            # Record the failure on the span itself: a drain that died
            # mid-flight is exactly what a flight recorder must show.
            args = dict(self.args) if self.args else {}
            args["error"] = exc_type.__name__
            self.args = args
        if self._ann is not None:
            self._ann.set_metadata(
                ts_us=self._t0 // 1000,
                ts_gap_ns=self._gap,
                **{k: v for k, v in self.args.items() if type(v) in _SCALARS},
            )
            self._ann.__exit__(None, None, None)
        if self._rec is not None:
            self._rec.append(
                (
                    "X",
                    self.name,
                    self.track,
                    self._t0 // 1000,
                    (now - self._t0) // 1000,
                    self.args or None,
                )
            )
        if self._tok is not None:
            _track_var.reset(self._tok)
        return False


def span(name: str, track: Optional[str] = None, **args):
    """Open a span context manager (no-op unless tracing is enabled).

    ``track`` pins the timeline row and is inherited by spans opened
    within this one (contextvar scope); ``**args`` become the span's
    attributes in the exported trace.
    """
    sink = _sink
    if sink is None:
        return _NULL
    return _Span(sink, name, track, args)


def begin(name: str, track: Optional[str] = None, **args):
    """Open a span NOW that another callback or task closes with
    ``.end(**late_args)``: a wait, opened at its cause and closed at its
    effect.  ``track`` names its row and is not inherited by anything
    (the closing context is not the opening one).  The shared no-op
    unless tracing is enabled."""
    sink = _sink
    if sink is None:
        return _NULL
    return _Span(sink, name, track, args, pin=False).__enter__()


def instant(name: str, track: Optional[str] = None, **args) -> None:
    """Record a point event (no-op unless tracing is enabled)."""
    rec = _recorder
    if rec is None:
        return
    rec.append(
        (
            "i",
            name,
            _resolve_track(track),
            time.perf_counter_ns() // 1000,
            0,
            args or None,
        )
    )
