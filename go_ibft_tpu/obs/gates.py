"""Regression gates: fresh evidence vs the best prior round, per backend.

The VERDICT's round-6 mandate as a subsystem: every round produces an
evidence file (``bench.py``'s per-config JSONL), and this module compares
it against the best prior recorded value for the same config **on the
same backend** — so CPU-fallback rounds still catch packing/pipelining/
engine regressions without a chip, and a TPU round is never graded
against a CPU number (or vice versa).

Prior evidence sources, in the repo root:

* ``BENCH_r*.json`` — the driver's per-round artifacts: a JSON object
  whose ``tail`` field holds the run's JSONL lines (plus ``rc``);
* plain ``*.jsonl`` evidence files (``bench_evidence.jsonl``,
  ``evidence_tpu.jsonl``) — one JSON object per line.

Both parse into the same line dicts the bench prints.  Direction
(lower-is-better vs higher-is-better) derives from the metric's unit:
latencies and overhead ratios regress upward, throughputs regress
downward.  Thresholds: > 25% worse than the best prior on the same
backend fails, > 10% warns, anything else passes; configs with no prior
(or no fresh measurement where none was expected) report informationally.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = [
    "GateResult",
    "parse_artifact",
    "artifact_backend",
    "best_prior",
    "gate_evidence",
    "render_table",
    "WARN_PCT",
    "FAIL_PCT",
    "SloSpec",
    "DEFAULT_SLO_TABLE",
    "slo_record",
    "append_slo_records",
    "parse_slo_records",
    "gate_slo_records",
    "ledger_metric_lines",
    "gate_ledger_evidence",
    "LEDGER_WARN_PCT",
    "LEDGER_FAIL_PCT",
]

WARN_PCT = 10.0
FAIL_PCT = 25.0

# Metric keys where HIGHER is better; everything else (ms latencies,
# overhead multipliers) regresses upward.  Units double-check this: any
# per-second unit is a throughput.
_HIGHER_IS_BETTER = ("throughput",)

# Lines that are run diagnostics, not config measurements.
_NON_CONFIG_METRICS = frozenset(
    {
        "bench_platform",
        "bench_error",
        "bench_failures",
        "bench_evidence_gap",
        "backend_probe",
        "adaptive_cutover_calibration",
        "trace_export",
        "cost_ledger",
        "device_trace",
    }
)


def higher_is_better(metric: str, unit: Optional[str]) -> bool:
    if unit and "/s" in unit:
        return True
    return any(tag in metric for tag in _HIGHER_IS_BETTER)


def parse_artifact(path: str) -> List[dict]:
    """Parse one evidence artifact (driver wrapper JSON or raw JSONL)."""
    with open(path) as fh:
        text = fh.read()
    lines: List[dict] = []
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict) and "tail" in doc:
        text = doc["tail"]
    elif isinstance(doc, dict):
        return [doc]
    for raw in text.splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            line = json.loads(raw)
        except ValueError:
            continue
        if isinstance(line, dict) and "metric" in line:
            lines.append(line)
    return lines


def artifact_backend(lines: Iterable[dict]) -> str:
    """``tpu`` or ``cpu-fallback`` for a parsed artifact.

    New-schema lines carry an explicit ``backend`` field; older rounds are
    inferred from their ``bench_platform`` line (absence of one — or a
    CPU/fallback platform — means no TPU evidence).
    """
    lines = list(lines)
    for line in lines:
        backend = line.get("backend")
        if backend in ("tpu", "cpu-fallback"):
            return backend
    for line in lines:
        if line.get("metric") == "bench_platform":
            platform = str(line.get("value", ""))
            return "tpu" if platform == "tpu" else "cpu-fallback"
    return "cpu-fallback"


def config_lines(lines: Iterable[dict]) -> Dict[str, dict]:
    """metric-key -> line for the measurement lines of one artifact."""
    out: Dict[str, dict] = {}
    for line in lines:
        metric = line.get("metric")
        if metric in _NON_CONFIG_METRICS or metric is None:
            continue
        # Last line per key wins (a re-run within one artifact supersedes).
        if isinstance(line.get("value"), (int, float)):
            out[metric] = line
        else:
            out.setdefault(metric, line)
    return out


def _round_of(path: str) -> int:
    m = re.search(r"BENCH_r(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else -1


def best_prior(
    repo_dir: str, backend: str, exclude: Tuple[str, ...] = ()
) -> Dict[str, Tuple[float, str, dict]]:
    """Best prior value per config on ``backend`` across ``BENCH_r*.json``.

    Returns ``{config: (value, source_name, line)}`` where *best* is
    direction-aware (lowest latency / highest throughput recorded by any
    prior round on the same backend).
    """
    best: Dict[str, Tuple[float, str, dict]] = {}
    paths = sorted(glob.glob(os.path.join(repo_dir, "BENCH_r*.json")), key=_round_of)
    for path in paths:
        name = os.path.basename(path)
        if name in exclude:
            continue
        try:
            lines = parse_artifact(path)
        except OSError:
            continue
        if artifact_backend(lines) != backend:
            continue
        for metric, line in config_lines(lines).items():
            value = line.get("value")
            if not isinstance(value, (int, float)):
                continue
            prior = best.get(metric)
            better = higher_is_better(metric, line.get("unit"))
            if (
                prior is None
                or (better and value > prior[0])
                or (not better and value < prior[0])
            ):
                best[metric] = (float(value), name, line)
    return best


@dataclass
class GateResult:
    config: str
    backend: str
    status: str  # "pass" | "warn" | "fail" | "info"
    fresh: Optional[float]
    prior: Optional[float]
    prior_source: str
    change_pct: Optional[float]  # signed; positive = regression
    note: str = ""


def gate_evidence(
    fresh_lines: Iterable[dict],
    repo_dir: str = ".",
    *,
    backend: Optional[str] = None,
    warn_pct: float = WARN_PCT,
    fail_pct: float = FAIL_PCT,
    exclude: Tuple[str, ...] = (),
) -> List[GateResult]:
    """Compare a fresh evidence artifact against the best prior rounds.

    ``exclude`` names ``BENCH_r*.json`` basenames to drop from the prior
    pool (a fresh artifact that IS one of them must not compare against
    itself).
    """
    fresh_lines = list(fresh_lines)
    if backend is None:
        backend = artifact_backend(fresh_lines)
    fresh = config_lines(fresh_lines)
    prior = best_prior(repo_dir, backend, exclude=exclude)
    results: List[GateResult] = []
    for config in sorted(set(fresh) | set(prior)):
        fresh_line = fresh.get(config)
        fresh_value = fresh_line.get("value") if fresh_line else None
        prior_hit = prior.get(config)
        if prior_hit is None:
            results.append(
                GateResult(
                    config,
                    backend,
                    "info",
                    fresh_value,
                    None,
                    "-",
                    None,
                    note="no prior evidence on this backend (first measurement)",
                )
            )
            continue
        prior_value, source, prior_line = prior_hit
        if not isinstance(fresh_value, (int, float)):
            results.append(
                GateResult(
                    config,
                    backend,
                    "warn",
                    None,
                    prior_value,
                    source,
                    None,
                    note=str(
                        (fresh_line or {}).get("note")
                        or (fresh_line or {}).get("error")
                        or "config produced no measurement this run"
                    )[:80],
                )
            )
            continue
        better = higher_is_better(config, prior_line.get("unit"))
        if prior_value == 0:
            change = 0.0
        elif better:
            change = (prior_value - fresh_value) / abs(prior_value) * 100.0
        else:
            change = (fresh_value - prior_value) / abs(prior_value) * 100.0
        if change > fail_pct:
            status = "fail"
        elif change > warn_pct:
            status = "warn"
        else:
            status = "pass"
        results.append(
            GateResult(
                config,
                backend,
                status,
                float(fresh_value),
                prior_value,
                source,
                round(change, 1),
            )
        )
    return results


def render_table(results: List[GateResult]) -> str:
    """Fixed-width pass/warn/fail table for terminals and CI logs."""
    headers = ("config", "backend", "fresh", "best prior", "source", "Δ%", "status")
    rows = [headers]
    for r in results:
        rows.append(
            (
                r.config,
                r.backend,
                "-" if r.fresh is None else f"{r.fresh:g}",
                "-" if r.prior is None else f"{r.prior:g}",
                r.prior_source,
                "-" if r.change_pct is None else f"{r.change_pct:+.1f}",
                r.status.upper() + (f"  ({r.note})" if r.note else ""),
            )
        )
    widths = [
        max(len(row[i]) for row in rows) for i in range(len(headers) - 1)
    ]
    out = []
    for i, row in enumerate(rows):
        line = "  ".join(
            cell.ljust(widths[j]) for j, cell in enumerate(row[:-1])
        )
        out.append(line + "  " + row[-1])
        if i == 0:
            out.append("-" * len(out[0]))
    return "\n".join(out)


# ---------------------------------------------------------------------------
# Cost-ledger gates (ISSUE 14): per-config dispatch-count + occupancy
# ---------------------------------------------------------------------------
#
# Every evidence line carries a ``ledger`` block (the delta the config
# cost — see ``obs/evidence.py::EvidenceWriter._ledger_block``).  These
# gates grade the two values that regress silently: dispatch count (a
# batching regression shows up as MORE launches for the same work — the
# thresholds are tight because counts are near-deterministic per config)
# and live-lane occupancy (bucket-padding waste creeping up as buckets
# drift away from real lane counts).

LEDGER_WARN_PCT = 5.0
LEDGER_FAIL_PCT = 30.0


def ledger_metric_lines(lines: Iterable[dict]) -> List[dict]:
    """Synthesize gateable metric lines from evidence-line sub-fields.

    ``<config>.ledger_dispatches`` (lower is better) and
    ``<config>.ledger_occupancy`` (higher is better — the ``/s``-free
    unit is special-cased in :func:`gate_ledger_evidence`) from ledger
    blocks, plus ``<config>.boot_cold_ms`` / ``<config>.boot_cached_ms``
    (both lower-better walls) from the boot warm-start config's evidence
    line — a cached-boot regression fails CI exactly like a throughput
    regression.  Lines without these fields (pre-ISSUE-14/16 artifacts)
    yield nothing, so old rounds grade ``info``.
    """
    out: List[dict] = []
    for line in lines:
        metric = line.get("metric")
        if metric is None or metric in _NON_CONFIG_METRICS:
            continue
        block = line.get("ledger")
        if isinstance(block, dict):
            dispatches = block.get("dispatches")
            if isinstance(dispatches, (int, float)) and dispatches > 0:
                out.append(
                    {
                        "metric": f"{metric}.ledger_dispatches",
                        "value": dispatches,
                        "unit": "dispatches",
                        "backend": line.get("backend"),
                    }
                )
                occupancy = block.get("occupancy")
                if isinstance(occupancy, (int, float)):
                    out.append(
                        {
                            "metric": f"{metric}.ledger_occupancy",
                            "value": occupancy,
                            "unit": "fraction",
                            "backend": line.get("backend"),
                        }
                    )
        for field in ("boot_cold_ms", "boot_cached_ms"):
            value = line.get(field)
            if isinstance(value, (int, float)) and value > 0:
                out.append(
                    {
                        "metric": f"{metric}.{field}",
                        "value": value,
                        "unit": "ms",
                        "backend": line.get("backend"),
                    }
                )
    return out


def _ledger_higher_is_better(metric: str) -> bool:
    return metric.endswith(".ledger_occupancy")


def gate_ledger_evidence(
    fresh_lines: Iterable[dict],
    repo_dir: str = ".",
    *,
    backend: Optional[str] = None,
    warn_pct: float = LEDGER_WARN_PCT,
    fail_pct: float = LEDGER_FAIL_PCT,
    exclude: Tuple[str, ...] = (),
) -> List[GateResult]:
    """Grade fresh ledger blocks against the best prior round, same
    backend (the :func:`gate_evidence` posture applied to the synthetic
    ledger metrics).  Configs whose priors carry no ledger block report
    ``info`` — the gate arms itself as rounds accumulate."""
    fresh_lines = list(fresh_lines)
    if backend is None:
        backend = artifact_backend(fresh_lines)
    fresh = {
        line["metric"]: line for line in ledger_metric_lines(fresh_lines)
    }
    prior: Dict[str, Tuple[float, str]] = {}
    paths = sorted(
        glob.glob(os.path.join(repo_dir, "BENCH_r*.json")), key=_round_of
    )
    for path in paths:
        name = os.path.basename(path)
        if name in exclude:
            continue
        try:
            lines = parse_artifact(path)
        except OSError:
            continue
        if artifact_backend(lines) != backend:
            continue
        for synth in ledger_metric_lines(lines):
            metric, value = synth["metric"], float(synth["value"])
            hit = prior.get(metric)
            better = _ledger_higher_is_better(metric)
            if (
                hit is None
                or (better and value > hit[0])
                or (not better and value < hit[0])
            ):
                prior[metric] = (value, name)
    results: List[GateResult] = []
    for metric in sorted(set(fresh) | set(prior)):
        fresh_line = fresh.get(metric)
        fresh_value = fresh_line.get("value") if fresh_line else None
        hit = prior.get(metric)
        if hit is None or not isinstance(fresh_value, (int, float)):
            results.append(
                GateResult(
                    metric,
                    backend,
                    "info",
                    fresh_value,
                    hit[0] if hit else None,
                    hit[1] if hit else "-",
                    None,
                    note=(
                        "no prior ledger evidence on this backend"
                        if hit is None
                        else "config carried no ledger block this run"
                    ),
                )
            )
            continue
        prior_value, source = hit
        better = _ledger_higher_is_better(metric)
        if prior_value == 0:
            change = 0.0
        elif better:
            change = (prior_value - fresh_value) / abs(prior_value) * 100.0
        else:
            change = (fresh_value - prior_value) / abs(prior_value) * 100.0
        if change > fail_pct:
            status = "fail"
        elif change > warn_pct:
            status = "warn"
        else:
            status = "pass"
        results.append(
            GateResult(
                metric,
                backend,
                status,
                float(fresh_value),
                prior_value,
                source,
                round(change, 1),
            )
        )
    return results


# ---------------------------------------------------------------------------
# SLO soak gates (cross-process telemetry plane)
# ---------------------------------------------------------------------------
#
# The bench gates above compare a fresh measurement against the best PRIOR
# measurement; soak SLOs are absolute contracts instead — liveness either
# held or it did not, regardless of history.  The soaks (chain soak, chaos
# matrix, timeline smoke) emit one JSONL record per SLO::
#
#     {"slo": "missed_heights", "value": 0, "unit": "heights",
#      "warn": 0, "fail": 0, "context": {"nodes": 30, "heights": 20}}
#
# and this gate grades each record direction-aware against its limits
# (per-record limits win; DEFAULT_SLO_TABLE supplies the standing ones).
# ``value > fail`` (or ``< fail`` for higher-is-better SLOs) fails the
# run the same way a perf regression does — CI treats both alike.


@dataclass
class SloSpec:
    """Standing limits for one SLO family."""

    warn: Optional[float]
    fail: Optional[float]
    higher_is_better: bool = False
    unit: str = ""


DEFAULT_SLO_TABLE: Dict[str, SloSpec] = {
    # Liveness: ANY missed height is a failure — the cross-process
    # missed_heights=0 posture of bench config #12's QoS gate, applied to
    # every soak.
    "missed_heights": SloSpec(warn=0, fail=0, unit="heights"),
    # Safety proxy: divergent per-node chains (should be impossible; the
    # soaks also assert it directly, but the record makes CI evidence).
    "diverged_chains": SloSpec(warn=0, fail=0, unit="nodes"),
    # Latency: per-height finalize tail under chaos.  The standing limits
    # are deliberately loose (CI hosts vary wildly); individual soaks
    # pass tighter per-record limits scaled to their round timeout.
    "finalize_p99_ms": SloSpec(warn=10_000.0, fail=30_000.0, unit="ms"),
    # Degradation budgets: shed verify work and quarantined lanes are
    # legitimate under injected faults but a sudden flood of either is a
    # regression in disguise.
    "shed_lanes": SloSpec(warn=0, fail=None, unit="lanes"),
    "quarantined_lanes": SloSpec(warn=0, fail=None, unit="lanes"),
    # Sync should only ever repair stranded tails, never carry the soak.
    "sync_fraction": SloSpec(warn=0.25, fail=0.5, unit="fraction"),
    # Byzantine invariants (sim/invariants.py): a single violation of
    # agreement, validity, or post-GST bounded-rounds liveness fails the
    # run — these are the properties the f<N/3 argument promises, and a
    # violating seed is replayable from its CHAOS-REPLAY line.
    "invariant_agreement": SloSpec(warn=0, fail=0, unit="violations"),
    "invariant_validity": SloSpec(warn=0, fail=0, unit="violations"),
    "invariant_bounded_rounds": SloSpec(warn=0, fail=0, unit="violations"),
    # Clean/degraded heights-per-second ratio of the Byzantine soak
    # (bench config #16).  The expensive part is deterministic per seed
    # — round-timeout penalties where an adversary holds round 0 — so
    # the limits bound the seeded attack cost plus host noise, not a
    # tight perf promise.  Lower is better (unit has no "/s"), so a
    # regression is the ratio drifting UP.
    "byzantine_soak_overhead_x": SloSpec(warn=25.0, fail=200.0, unit="x"),
    # Multi-process fleet (bench config #17 / scripts/fleet.py): N real
    # validator processes over TCP under a concurrent proof-client
    # flood.  Chain divergence across processes and an uncut slowloris
    # socket are zero-tolerance; the proof-latency tail is bounded
    # loosely (1-core CI hosts serve hundreds of concurrent clients) and
    # tightened per-run by the harness flags.
    "fleet_diverged_chains": SloSpec(warn=0, fail=0, unit="nodes"),
    "fleet_slowloris_uncut": SloSpec(warn=0, fail=0, unit="sockets"),
    "fleet_proof_p99_ms": SloSpec(warn=10_000.0, fail=30_000.0, unit="ms"),
}


def slo_record(
    name: str,
    value: float,
    *,
    warn: Optional[float] = None,
    fail: Optional[float] = None,
    unit: Optional[str] = None,
    context: Optional[dict] = None,
) -> dict:
    """Build one SLO record (explicit limits override the table's)."""
    spec = DEFAULT_SLO_TABLE.get(name)
    record = {
        "slo": name,
        "value": value,
        "warn": warn if warn is not None else (spec.warn if spec else None),
        "fail": fail if fail is not None else (spec.fail if spec else None),
        "unit": unit if unit is not None else (spec.unit if spec else ""),
    }
    if context:
        record["context"] = context
    return record


def append_slo_records(path: Optional[str], records: Iterable[dict]) -> None:
    """Append records as JSONL (no-op on ``None`` path — soaks call this
    unconditionally and the env var decides whether evidence lands)."""
    if not path:
        return
    with open(path, "a") as fh:
        for record in records:
            fh.write(json.dumps(record) + "\n")


def parse_slo_records(path: str) -> List[dict]:
    """Parse one SLO JSONL file (lines without a ``slo`` key are skipped)."""
    records: List[dict] = []
    with open(path) as fh:
        for raw in fh:
            raw = raw.strip()
            if not raw.startswith("{"):
                continue
            try:
                line = json.loads(raw)
            except ValueError:
                continue
            if isinstance(line, dict) and "slo" in line:
                records.append(line)
    return records


def gate_slo_records(
    records: Iterable[dict],
    table: Optional[Dict[str, SloSpec]] = None,
) -> List[GateResult]:
    """Grade SLO records pass/warn/fail against absolute limits.

    Reuses :class:`GateResult` (and therefore :func:`render_table`):
    ``prior`` holds the fail limit, ``change_pct`` the headroom consumed.
    Records naming an SLO with no limits anywhere report ``info``.
    """
    table = DEFAULT_SLO_TABLE if table is None else table
    results: List[GateResult] = []
    for record in records:
        name = str(record.get("slo"))
        value = record.get("value")
        spec = table.get(name)
        higher = spec.higher_is_better if spec else False
        warn = record.get("warn", spec.warn if spec else None)
        fail = record.get("fail", spec.fail if spec else None)
        note = ""
        context = record.get("context")
        if context:
            note = str(context)[:60]
        if not isinstance(value, (int, float)):
            results.append(
                GateResult(
                    name, "slo", "warn", None, fail, "slo-limit", None,
                    note="record carries no numeric value",
                )
            )
            continue
        value = float(value)

        def breached(limit: Optional[float]) -> bool:
            if limit is None:
                return False
            return value < limit if higher else value > limit

        if breached(fail):
            status = "fail"
        elif breached(warn):
            status = "warn"
        elif warn is None and fail is None:
            status = "info"
        else:
            status = "pass"
        headroom = None
        if fail not in (None, 0):
            headroom = round(value / fail * 100.0, 1)
        results.append(
            GateResult(
                name,
                "slo",
                status,
                value,
                fail,
                "slo-limit",
                headroom,
                note=note,
            )
        )
    return results
