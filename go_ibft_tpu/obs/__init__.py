"""Observability subsystem: flight recorder, evidence writer, gates.

Three pillars (ISSUE 4):

* :mod:`~go_ibft_tpu.obs.trace` / :mod:`~go_ibft_tpu.obs.recorder` — a
  zero-dependency, thread-safe span API recording into a fixed-size ring
  buffer, instrumented at every hot seam (engine round phases, verify
  pack/dispatch/device-wait, transport sends/retries, chaos injection
  sites).  Disabled mode costs a single predicate check per call site.
* :mod:`~go_ibft_tpu.obs.export` — Chrome ``trace_event`` / Perfetto JSON
  export, so a multi-node height renders as a readable multi-track
  timeline (``bench.py --trace out.json``, ``scripts/chaos_replay.py
  --trace``).
* :mod:`~go_ibft_tpu.obs.evidence` — the append-only,
  per-record-flushed JSONL evidence writer, so every bench config leaves
  a record even when the run crashes mid-way.
* :mod:`~go_ibft_tpu.obs.ledger` / :mod:`~go_ibft_tpu.obs.devprof` —
  the runtime cost ledger (ISSUE 14): per-dispatch device-time
  attribution keyed by compile-budget program names, live-vs-padded
  lane occupancy, compile-event tracing into ``compile_ledger.jsonl``,
  and on-demand ``jax.profiler`` windows (``/profilez``,
  ``bench.py --device-trace``) merged into the Perfetto timeline.
* :mod:`~go_ibft_tpu.obs.gates` — regression gates comparing a fresh
  evidence file against the best prior ``BENCH_r*.json`` per config on the
  same backend (``scripts/obs_report.py`` / ``make obs-report``), so
  CPU-fallback rounds still catch regressions without a chip.
"""

from . import clock, devprof, ledger, trace
from .evidence import EvidenceWriter
from .export import to_chrome_trace, write_chrome_trace
from .gates import GateResult, gate_evidence, gate_slo_records, render_table
from .httpd import TelemetryServer
from .metrics_export import render_prometheus
from .recorder import RingRecorder

__all__ = [
    "clock",
    "devprof",
    "ledger",
    "trace",
    "EvidenceWriter",
    "to_chrome_trace",
    "write_chrome_trace",
    "GateResult",
    "gate_evidence",
    "gate_slo_records",
    "render_table",
    "RingRecorder",
    "TelemetryServer",
    "render_prometheus",
]
