"""The production boot layer (ROADMAP item 5: kill the cold boot).

A restarted node historically paid minutes of XLA:CPU compile before its
first round (~3 minutes for one ladder program alone in round 4) — fatal
for fleet operations where nodes restart constantly.  This package makes
restart cost a cache load instead:

* :mod:`~go_ibft_tpu.boot.registry` — the pinned program registry: one
  buildable ``(lowerable, args)`` per compile-budget family.  This is the
  SAME registry ``scripts/compile_budget.py`` lowers for its trace-size
  ratchet, so the AOT store and the budget guard can never drift apart.
* :mod:`~go_ibft_tpu.boot.aot` — the AOT program store: lowers and
  compiles every pinned family through JAX's persistent compilation
  cache (``utils/jaxcache.py``), classifies each restore cold vs cached
  by the cache's own miss events, and records cold compiles to the cost ledger.
* :mod:`~go_ibft_tpu.boot.warmstart` — warm-start: WAL replay +
  verdict-cache seeding + compiled-program restore, all *before* the
  first round opens.
* ``python -m go_ibft_tpu.boot`` — the restart-to-first-finalized
  harness bench config #14 measures (one full boot in a child process).
"""

from .aot import AOTStore, ProgramStatus, fingerprint, load_manifest, write_manifest
from .registry import ProgramUnavailable, program_registry
from .warmstart import WarmStartReport, seed_verdict_caches, warm_start

__all__ = [
    "AOTStore",
    "ProgramStatus",
    "ProgramUnavailable",
    "WarmStartReport",
    "fingerprint",
    "load_manifest",
    "program_registry",
    "seed_verdict_caches",
    "warm_start",
    "write_manifest",
]
