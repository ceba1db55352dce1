"""Evidence capture: the append-only JSONL writer.

Append-only JSONL, one line per record, flushed (and fsync'd) per record,
so a run killed mid-config still leaves every completed config's evidence
on disk.  Each line carries the required schema fields (``config``,
``metric``, ``backend``, ``probe``, ``ts``) on top of whatever the caller
measured; ``bench.py`` reserves rc=0 strictly for "every config produced
an evidence line".  ``backend`` names the platform the run read in
process (``jax.devices()[0].platform``: ``tpu``, or ``cpu-fallback`` for a
run that asked for the CPU); ``probe`` says how that was learned and is
``ok`` for an in-process read.
"""

from __future__ import annotations

import json
import os
import time
from typing import List, Optional, Sequence

__all__ = [
    "EvidenceWriter",
    "REQUIRED_EVIDENCE_FIELDS",
]

REQUIRED_EVIDENCE_FIELDS = ("config", "metric", "backend", "probe", "ts")


class EvidenceWriter:
    """Append-only, per-record-flushed JSONL evidence file.

    The file handle opens in append mode and every :meth:`record` call
    writes exactly one line, flushes, and fsyncs — a crash (or a driver
    SIGKILL) after config N loses nothing before N+1.  ``backend`` and
    ``probe`` (the schema's provenance fields) are stamped on every line
    from the platform the run read at start-up; callers may override per
    record.
    """

    def __init__(
        self,
        path: str,
        *,
        backend: str = "cpu-fallback",
        probe: str = "error",
        devices: Optional[int] = None,
        truncate: bool = False,
    ) -> None:
        self.path = path
        self.backend = backend
        self.probe = probe
        # Device count of the run's platform: stamped on every line so
        # mesh-config evidence distinguishes dp=1 from dp>1 runs.
        self.devices = devices
        self._fh = open(path, "w" if truncate else "a")
        self._configs: List[str] = []
        # Cost-ledger totals at the previous record (ISSUE 14): each line
        # carries the DELTA since the line before it, so per-config
        # dispatch counts and occupancy are readable straight off the
        # evidence and obs/gates.py can regression-gate their growth.
        self._ledger_last: Optional[dict] = None

    def set_provenance(
        self, backend: str, probe: str, devices: Optional[int] = None
    ) -> None:
        self.backend = backend
        self.probe = probe
        if devices is not None:
            self.devices = devices

    def record(self, config: str, line: Optional[dict] = None, **fields) -> dict:
        """Append one evidence line for ``config``; returns the full record."""
        rec = dict(line or {})
        rec.update(fields)
        rec.setdefault("metric", config)
        rec["config"] = config
        rec.setdefault("backend", self.backend)
        rec.setdefault("probe", self.probe)
        rec.setdefault("devices", self.devices)
        block = self._ledger_block()
        if block is not None:
            rec.setdefault("ledger", block)
        rec["ts"] = time.time()
        self._fh.write(json.dumps(rec) + "\n")
        self._fh.flush()
        try:
            os.fsync(self._fh.fileno())
        except OSError:
            pass
        self._configs.append(config)
        return rec

    def _ledger_block(self) -> Optional[dict]:
        """Cost-ledger delta since the previous record (None when the
        ledger is off): dispatches, device_ms, live/padded lanes + the
        derived occupancy, compiles, compile_ms — the per-config stamp
        ``scripts/obs_report.py`` / ``obs/gates.py`` regression-gate."""
        from . import ledger as cost_ledger

        cur = cost_ledger.totals()
        if cur is None:
            self._ledger_last = None
            return None
        prev = self._ledger_last or {}
        self._ledger_last = cur
        block = {
            key: round(cur[key] - prev.get(key, 0), 3)
            for key in (
                "dispatches",
                "live_lanes",
                "padded_lanes",
                "device_ms",
                "compiles",
                "compile_ms",
            )
        }
        block["occupancy"] = (
            round(block["live_lanes"] / block["padded_lanes"], 4)
            if block["padded_lanes"]
            else None
        )
        return block

    def covered(self) -> List[str]:
        """Configs recorded so far, in order."""
        return list(self._configs)

    def missing(self, expected: Sequence[str]) -> List[str]:
        seen = set(self._configs)
        return [c for c in expected if c not in seen]

    def close(self) -> None:
        try:
            self._fh.close()
        except OSError:
            pass

    def __enter__(self) -> "EvidenceWriter":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
