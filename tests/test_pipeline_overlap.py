"""VerifyPipeline overlap contract + the device verifier's chunked drains.

The acceptance pin: with a stubbed slow device dispatch, the pipeline packs
and dispatches item N + 1 before it blocks on item N's result — the
overlap as an ORDER of recorded events, never as a wall-clock comparison
(the wall-under-serial-sum form failed under loaded runners and went in PR
29).  The stub "device" is a timer thread, so nothing here needs a second
core.
"""

import threading

import numpy as np
import pytest

from go_ibft_tpu.utils import metrics
from go_ibft_tpu.verify.pipeline import (
    OVERLAP_EFFICIENCY_KEY,
    PACK_MS_KEY,
    READBACK_WAIT_MS_KEY,
    VerifyPipeline,
    observe_overlap_efficiency,
)

DEVICE_S = 0.02


class _StubDevice:
    """Async device stub: dispatch starts a timer, readback joins it.

    Mirrors JAX async dispatch — the call returns immediately and the
    result only blocks when read.  Tracks the in-flight high-water mark so
    the double-buffering bound is testable.
    """

    def __init__(self, device_s: float = DEVICE_S):
        self.device_s = device_s
        self.inflight = 0
        self.max_inflight = 0
        self._lock = threading.Lock()

    def dispatch(self, packed):
        with self._lock:
            self.inflight += 1
            self.max_inflight = max(self.max_inflight, self.inflight)
        done = threading.Event()
        timer = threading.Timer(self.device_s, done.set)
        timer.start()
        return packed, done

    def readback(self, handle):
        packed, done = handle
        done.wait()
        with self._lock:
            self.inflight -= 1
        return packed * 10


def test_second_pack_starts_before_first_readback_returns():
    """The overlap as an ORDER of events, which no clock can flake: with
    the device still busy on item 0, item 1 is packed and dispatched
    before the pipeline blocks on item 0's result, and no readback starts
    before the pack after it has."""
    events = []
    dev = _StubDevice(device_s=0.05)

    def pack(item):
        events.append(("pack", item))
        return item

    def readback(handle):
        out = dev.readback(handle)
        events.append(("readback", handle[0]))
        return out

    VerifyPipeline(depth=2).run(list(range(4)), pack, dev.dispatch, readback)
    assert events.index(("pack", 1)) < events.index(("readback", 0))
    # Steady state: item N is read back only once item N + 1 is packed.
    assert events == [
        ("pack", 0),
        ("pack", 1),
        ("readback", 0),
        ("pack", 2),
        ("readback", 1),
        ("pack", 3),
        ("readback", 2),
        ("readback", 3),
    ]


def test_double_buffering_bounds_inflight_dispatches():
    dev = _StubDevice(device_s=0.05)
    VerifyPipeline(depth=2).run(
        list(range(6)), lambda i: i, dev.dispatch, dev.readback
    )
    assert dev.max_inflight <= 2
    assert dev.inflight == 0  # fully drained

    dev = _StubDevice(device_s=0.01)
    VerifyPipeline(depth=3).run(
        list(range(6)), lambda i: i, dev.dispatch, dev.readback
    )
    assert dev.max_inflight <= 3


def test_pipeline_drains_inflight_on_pack_error():
    """A mid-stream pack failure propagates, but dispatched work is still
    consumed first (device buffers must never be abandoned)."""
    dev = _StubDevice(device_s=0.01)

    def pack(i):
        if i == 3:
            raise RuntimeError("pack failed")
        return i

    with pytest.raises(RuntimeError, match="pack failed"):
        VerifyPipeline(depth=2).run(list(range(6)), pack, dev.dispatch, dev.readback)
    assert dev.inflight == 0


def test_depth_validation():
    with pytest.raises(ValueError):
        VerifyPipeline(depth=0)


def test_pipeline_records_first_class_metrics():
    metrics.reset()
    dev = _StubDevice(device_s=0.005)
    VerifyPipeline(depth=2).run(
        list(range(4)), lambda i: i, dev.dispatch, dev.readback
    )
    pack_summary = metrics.summarize(PACK_MS_KEY)
    assert pack_summary is not None and pack_summary["count"] == 4
    assert metrics.summarize(READBACK_WAIT_MS_KEY)["count"] == 4
    eff = observe_overlap_efficiency(serial_s=2.0, pipelined_s=1.5)
    assert eff == pytest.approx(0.25)
    assert metrics.get_histogram(OVERLAP_EFFICIENCY_KEY) == [pytest.approx(0.25)]
    # clamped at zero: noise must never report negative efficiency
    assert observe_overlap_efficiency(1.0, 1.1) == 0.0
    metrics.reset()


# -- device verifier drains through the pipeline -----------------------------


def test_commit_flood_drains_envelopes_and_seals_through_one_pipeline(monkeypatch):
    """From the fold width up a COMMIT flood's seals are the NEXT CHUNK of
    the envelopes' drain: both phases share one pipeline run (the seals pack
    while the envelopes execute) and each chunk's mask scatters to its own
    phase, the envelopes' to the caller's rows, the seals' to the verdict
    cache (dispatch stubbed; the real-kernel differential is
    ``tests/test_commit_joint_dispatch.py``)."""
    from go_ibft_tpu.crypto import PrivateKey
    from go_ibft_tpu.crypto.backend import ECDSABackend, proposal_hash_of
    from go_ibft_tpu.messages.helpers import extract_committed_seal
    from go_ibft_tpu.messages.wire import Proposal, View
    from go_ibft_tpu.verify import DeviceBatchVerifier
    from go_ibft_tpu.verify import batch as vbatch

    keys = [PrivateKey.from_seed(b"vrc-%d" % i) for i in range(4)]
    src = ECDSABackend.static_validators({k.address: 1 for k in keys})
    backends = [ECDSABackend(k, src) for k in keys]
    view = View(height=2, round=0)
    phash = proposal_hash_of(Proposal(raw_proposal=b"vrc block", round=0))
    commits = [b.build_commit_message(phash, view) for b in backends]
    seals = [extract_committed_seal(m) for m in commits]
    # one wrong-length envelope: filtered out (mask False), never dispatched,
    # and its seal does not ride
    stray = backends[0].build_commit_message(phash, view)
    stray.signature = stray.signature[:30]
    msgs = commits + [stray]

    monkeypatch.setattr(vbatch, "_FOLD_LANES", 8)  # the 8-lane rung folds
    dev = DeviceBatchVerifier(src)
    kinds, runs = [], []

    def fake_async(inputs, table):
        live = np.asarray(inputs[-1])
        kinds.append(int(live.sum()))
        mask = live.copy()
        mask[0] = False  # first lane of each chunk rejected
        return mask

    real_run = vbatch.VerifyPipeline.run

    def counting_run(self, items, *args, **kwargs):
        runs.append(len(items))
        return real_run(self, items, *args, **kwargs)

    def rows(n, lanes=8):
        live = np.zeros(lanes, dtype=bool)
        live[:n] = True
        return (None,) * 5 + (live,)

    monkeypatch.setattr(vbatch.VerifyPipeline, "run", counting_run)
    monkeypatch.setattr(dev, "_dispatch_async", fake_async)
    monkeypatch.setattr(dev, "_sender_inputs", lambda ms: rows(len(ms)))
    monkeypatch.setattr(
        dev, "_rider_inputs", lambda ms, lanes, rows_=None: rows(len(ms), lanes)
    )
    sender_mask = dev.verify_senders(msgs)
    assert runs == [2] and kinds == [4, 4]  # one envelope chunk + one seal chunk
    assert list(sender_mask) == [False, True, True, True, False]
    assert dev.cached_seal_verdicts(phash, seals, 2) == [False, True, True, True]
