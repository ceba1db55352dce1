"""Spawn one real boot leg (``python -m go_ibft_tpu.boot``) and parse it.

Restart-to-first-finalized is measured by restarting the node FOR REAL: a
fresh interpreter, fresh jax, one shared compile cache handed to the child
as ``JAX_COMPILATION_CACHE_DIR``.  That process-spawning lives here, in
the boot package that owns the child entrypoint, for the restart cell to
use (``ecdsa-100v.restart``, ROADMAP C5); until that cell exists its only
caller is ``tests/test_chip_smoke.py``.
This module must stay import-light: the PARENT imports it, and pulling
jax in here would distort the very spawn cost the legs measure.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

__all__ = ["BootLegTimeout", "run_boot_leg"]


class BootLegTimeout(RuntimeError):
    """A boot leg exceeded its wall budget (the child was killed)."""

    def __init__(self, tag: str, timeout_s: float):
        super().__init__(f"boot leg {tag!r} exceeded {timeout_s:.0f}s")
        self.tag = tag
        self.timeout_s = timeout_s


def run_boot_leg(
    tag: str,
    family: str,
    cache_dir: str,
    ledger_path: str,
    *,
    timeout_s: float,
    cwd: str | None = None,
) -> dict:
    """Run one restart leg; return ``{spawn_ms, report, events}``.

    The child receives ``cache_dir`` as ``JAX_COMPILATION_CACHE_DIR``
    (the one rule of ``utils/jaxcache.py`` — a cold leg is a leg handed an
    empty directory, so no other cache can leak pre-warmed artifacts into
    it) and writes its compile ledger to ``ledger_path`` so the caller can
    assert the cached legs recorded ZERO compile events.
    Raises :class:`BootLegTimeout` when the wall budget runs out and
    ``RuntimeError`` on a nonzero child exit.
    """
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["GO_IBFT_COMPILE_LEDGER"] = ledger_path
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "go_ibft_tpu.boot", "--programs", family],
            capture_output=True,
            text=True,
            env=env,
            cwd=cwd,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        raise BootLegTimeout(tag, timeout_s) from None
    spawn_ms = (time.perf_counter() - t0) * 1e3
    if proc.returncode != 0:
        raise RuntimeError(
            f"boot leg {tag} rc={proc.returncode}: "
            + proc.stderr.strip()[-300:]
        )
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    events = []
    if os.path.exists(ledger_path):
        with open(ledger_path) as fh:
            events = [json.loads(ln) for ln in fh if ln.strip()]
    return {"spawn_ms": spawn_ms, "report": report, "events": events}
