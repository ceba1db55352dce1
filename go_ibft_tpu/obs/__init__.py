"""Observability subsystem: flight recorder, cost ledger, SLO gates.

* :mod:`~go_ibft_tpu.obs.trace` / :mod:`~go_ibft_tpu.obs.recorder` — a
  zero-dependency, thread-safe span API recording into a fixed-size ring
  buffer, instrumented at every hot seam (engine round phases, verify
  pack/dispatch/device-wait, transport sends/retries, chaos injection
  sites).  Disabled mode costs a single predicate check per call site.
* :mod:`~go_ibft_tpu.obs.export` — Chrome ``trace_event`` / Perfetto JSON
  export, so a multi-node height renders as a readable multi-track
  timeline (``scripts/chaos_replay.py --trace``; ``benchmark/run.py`` reads
  the ring itself).
* :mod:`~go_ibft_tpu.obs.ledger` / :mod:`~go_ibft_tpu.obs.devprof` —
  the runtime cost ledger (ISSUE 14): per-dispatch wall-time
  attribution keyed by compile-budget program names, live-vs-padded
  lane occupancy, compile-event tracing into ``compile_ledger.jsonl``,
  and on-demand ``jax.profiler`` windows (``/profilez``) merged into the
  Perfetto timeline.
* :mod:`~go_ibft_tpu.obs.idle` — the device's idle time charged to the
  span the host had open, and each span's self time, from one profiler
  window: while tracing is enabled every span is also a profiler event on
  the device events' clock (``scripts/idle_report.py``).
* :mod:`~go_ibft_tpu.obs.gates` — SLO gates: the soaks' records graded
  against absolute limits (``scripts/slo_gates.py`` / ``make slo-gates``).
"""

from . import clock, devprof, ledger, trace
from .export import to_chrome_trace, write_chrome_trace
from .gates import GateResult, gate_slo_records, render_table
from .httpd import TelemetryServer
from .metrics_export import render_prometheus
from .recorder import RingRecorder

__all__ = [
    "clock",
    "devprof",
    "ledger",
    "trace",
    "to_chrome_trace",
    "write_chrome_trace",
    "GateResult",
    "gate_slo_records",
    "render_table",
    "RingRecorder",
    "TelemetryServer",
    "render_prometheus",
]
