"""The AOT program store: restore compiled programs before the first round.

Artifacts live in two layers under the persistent cache directory
(``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.cache/xla`` —
resolved by :mod:`go_ibft_tpu.utils.jaxcache`):

* **XLA's persistent compilation cache** — jax keys entries on the HLO
  module + compile options + jax/XLA version + device topology, so a
  stale or cross-backend artifact can never *load* as a wrong program;
  at worst the key misses and the compile runs cold.
* **``<cache_dir>/aot/``** — this store's sidecars: one JSON per pinned
  program recording the :func:`fingerprint` (jax version, backend,
  device count, program family + shape suffix) plus the measured
  lower/compile wall, and optionally the ``jax.export``-serialized
  StableHLO artifact next to it.  The fingerprint gates *reporting and
  skip decisions*: a sidecar minted by a different jax/backend/topology
  marks the program stale, so boot tooling re-compiles it — a recorded
  cold compile, never a trusted stale artifact.

Cold vs cached classification is the persistent cache's own: a restore
during which jax reports ``/jax/compilation_cache/cache_misses`` (it
compiled the program and wrote the entry) is ``"cold"``; a cache hit is
``"cached"``, and so is a program below the persistence floor
(``GO_IBFT_CACHE_MIN_COMPILE_S``; the keccak digest pack), which jax
compiles without writing and which costs less than a load would.  No
duration decides it: a cache load on a busy host can take longer than a
small compile on an idle one.  Cold restores are recorded to the cost
ledger (``compile_ledger.jsonl`` when enabled), which is how the
second-boot zero-cold-compile proof in tests/test_boot.py reads its
evidence.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import tempfile
import time
from typing import Dict, Optional, Sequence, Tuple

from ..obs import ledger as cost_ledger
from ..utils.jaxcache import enable_persistent_cache, resolve_cache_dir
from .registry import ProgramUnavailable, program_registry

__all__ = [
    "AOTStore",
    "ProgramStatus",
    "family_of",
    "fingerprint",
    "load_manifest",
    "write_manifest",
]

# Shape-suffix stripper shared with scripts/cost_report.py's attribution:
# registry keys are ``<family>_<shape suffix>`` (``_8l``, ``_128v``,
# ``_dp4``); ledger events carry bare family names.
_SHAPE_SUFFIX = re.compile(r"(_dp\d+|_\d+[lv])$")

_cache_misses = 0
_listening = False


def _count_cache_misses() -> None:
    """Count the persistent cache's miss events for this process (jax has
    no call to remove a listener, so one is registered once)."""
    global _listening
    if _listening:
        return
    import jax

    def on_event(name: str, **_kw) -> None:
        global _cache_misses
        if name == "/jax/compilation_cache/cache_misses":
            _cache_misses += 1

    jax.monitoring.register_event_listener(on_event)
    _listening = True



def family_of(program: str) -> str:
    """Strip shape suffixes iteratively (``mesh_verify_mask_8l_dp4`` ->
    ``mesh_verify_mask``)."""
    while True:
        stripped = _SHAPE_SUFFIX.sub("", program)
        if stripped == program:
            return program
        program = stripped


def fingerprint() -> dict:
    """The artifact-validity key: jax version + backend + device count.

    Program family and shape suffix join this per sidecar (the sidecar
    file name IS the registry key), completing the ISSUE-16 key tuple.
    """
    import jax

    try:
        devices = jax.devices()
        backend = devices[0].platform
        count = len(devices)
    except RuntimeError:
        backend, count = "none", 0
    return {
        "jax": jax.__version__,
        "backend": backend,
        "device_count": count,
    }


@dataclasses.dataclass
class ProgramStatus:
    """One program's restore outcome."""

    program: str
    family: str
    status: str  # "cold" | "cached" | "skipped"
    compile_ms: float = 0.0
    lower_ms: float = 0.0
    reason: Optional[str] = None
    exported: bool = False


class AOTStore:
    """Lower + compile pinned program families through the persistent
    cache, with sidecar bookkeeping for skip/report decisions.

    ``cache_dir=None`` resolves through the jaxcache rule
    (``JAX_COMPILATION_CACHE_DIR`` where set, else the in-checkout
    default).  Note jax pins its compilation cache dir for the process on
    first enable — an explicit ``cache_dir`` differing from an
    already-enabled one affects only the sidecar store, so boot harnesses
    hand their children ``JAX_COMPILATION_CACHE_DIR`` instead.
    """

    def __init__(
        self,
        cache_dir: Optional[str] = None,
        *,
        site: str = "boot/aot.py",
    ) -> None:
        self.cache_dir = cache_dir or resolve_cache_dir()
        self.store_dir = os.path.join(self.cache_dir, "aot")
        self.site = site

    # -- sidecars --------------------------------------------------------

    def _sidecar_path(self, program: str) -> str:
        return os.path.join(self.store_dir, f"{program}.json")

    def read_sidecar(self, program: str) -> Optional[dict]:
        try:
            with open(self._sidecar_path(program)) as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None

    def _write_sidecar(self, program: str, payload: dict) -> None:
        """Atomic write, never raising (the probe-cache posture: a
        read-only store degrades to no bookkeeping, not a boot fault)."""
        try:
            os.makedirs(self.store_dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(
                dir=self.store_dir, prefix=f".{program}.", suffix=".tmp"
            )
            with os.fdopen(fd, "w") as fh:
                json.dump(payload, fh)
            os.replace(tmp, self._sidecar_path(program))
        except OSError:
            pass

    def cached_programs(self) -> set:
        """Registry keys whose sidecar fingerprint matches THIS process —
        programs a prior boot/warm run compiled into the same cache under
        the same jax/backend/topology.  A mismatched sidecar is stale:
        the caller re-compiles (recorded cold), never trusts it."""
        fp = fingerprint()
        out = set()
        for program in program_registry():
            side = self.read_sidecar(program)
            if (
                side is not None
                and side.get("fingerprint") == fp
                and side.get("status") in ("cold", "cached")
            ):
                out.add(program)
        return out

    # -- restore ---------------------------------------------------------

    def pinned_programs(self) -> Tuple[str, ...]:
        return tuple(program_registry())

    def ensure(
        self,
        programs: Optional[Sequence[str]] = None,
        *,
        record: bool = True,
        export: bool = False,
    ) -> Dict[str, ProgramStatus]:
        """Restore ``programs`` (default: every pinned family).

        Each program is lowered at its registry shape and compiled
        through the persistent cache: a warm cache makes ``.compile()``
        a load (classified ``"cached"``); a cold or stale one pays the
        real compile and writes the entry (classified ``"cold"`` by the
        cache's miss event, and recorded to the cost ledger when
        ``record``).
        ``export=True`` additionally serializes the ``jax.export``
        artifact next to the sidecar (provenance/ops tooling; the
        runtime always dispatches its own jit objects).
        """
        enable_persistent_cache()
        _count_cache_misses()
        out: Dict[str, ProgramStatus] = {}
        for program, build in program_registry(programs).items():
            family = family_of(program)
            try:
                t0 = time.perf_counter()
                fn, args = build()
                lowered = fn.lower(*args)
                t1 = time.perf_counter()
                misses = _cache_misses
                lowered.compile()
                t2 = time.perf_counter()
            except ProgramUnavailable as exc:
                out[program] = ProgramStatus(
                    program, family, "skipped", reason=str(exc)
                )
                continue
            compile_s = t2 - t1
            status = ProgramStatus(
                program,
                family,
                "cold" if _cache_misses > misses else "cached",
                compile_ms=compile_s * 1e3,
                lower_ms=(t1 - t0) * 1e3,
            )
            if status.status == "cold" and record:
                cost_ledger.record_compile(
                    family, status.compile_ms, site=self.site
                )
            if export:
                status.exported = self._export(program, fn, args)
            out[program] = status
            self._write_sidecar(
                program,
                {
                    "program": program,
                    "family": family,
                    "fingerprint": fingerprint(),
                    "status": status.status,
                    "compile_ms": round(status.compile_ms, 3),
                    "lower_ms": round(status.lower_ms, 3),
                    "exported": status.exported,
                    "ts": time.time(),
                },
            )
        return out

    def _export(self, program: str, fn, args) -> bool:
        """Serialize the ``jax.export`` artifact (best-effort: programs
        jax.export cannot serialize — shard_map shells on some versions —
        degrade to sidecar-only bookkeeping)."""
        try:
            from jax import export as jax_export

            blob = jax_export.export(fn)(*args).serialize()
            os.makedirs(self.store_dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(
                dir=self.store_dir, prefix=f".{program}.", suffix=".tmp"
            )
            with os.fdopen(fd, "wb") as fh:
                fh.write(blob)
            os.replace(tmp, os.path.join(self.store_dir, f"{program}.bin"))
            return True
        except Exception:  # noqa: BLE001 - export is provenance, not boot
            return False


# -- the machine-readable AOT manifest (scripts/warm_kernels.py emits,
# -- boot consumes) -----------------------------------------------------


def write_manifest(path: str, programs: Dict[str, dict]) -> dict:
    """Write the AOT manifest: measured per-family compile cost under a
    fingerprint.  ``programs`` maps family -> ``{"compile_ms": float,
    "events": int}`` (the cost-ledger snapshot's compile table)."""
    doc = {
        "fingerprint": fingerprint(),
        "generated_ts": time.time(),
        "programs": {
            name: {
                "compile_ms": round(float(acc.get("compile_ms", 0.0)), 3),
                "events": int(acc.get("events", 0)),
            }
            for name, acc in sorted(programs.items())
        },
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".aot_manifest.", suffix=".tmp")
    with os.fdopen(fd, "w") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    os.replace(tmp, path)
    return doc


def load_manifest(path: str) -> Optional[dict]:
    """Read a manifest; adds ``"stale"`` (fingerprint mismatch with THIS
    process — consumers must treat every family as a cold candidate)."""
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return None
    doc["stale"] = doc.get("fingerprint") != fingerprint()
    return doc
