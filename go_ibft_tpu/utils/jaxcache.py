"""Persistent XLA compilation cache management.

The verification kernels are 256-step EC ladders — minutes to compile cold,
milliseconds to load from the persistent cache.  A consensus engine cannot
stall mid-round for a compile (the round timer would expire, SURVEY.md §7
(d)), so anything constructing device verifiers should enable the cache and
pre-warm the hot shapes.

The cache directory follows ONE rule.  Where ``JAX_COMPILATION_CACHE_DIR``
is set (JAX reads it natively at import), that directory is the cache:
nothing else is set in code and it is never pruned — it belongs to whoever
placed it.  Where it is not set, the cache is ``<checkout>/.cache/xla``, a
fixed path beside the package, so every process of a run finds what an
earlier one compiled — never ``~``, ``/tmp``, a pid or a time.
That default directory is ours, so its growth is bounded: entries older
than ``GO_IBFT_CACHE_TTL_S`` are dropped, and when it exceeds
``GO_IBFT_CACHE_MAX_BYTES`` the oldest entries are evicted first.  JAX's
own cache key covers jax version / backend / XLA flags, so entries written
by an older jax can never be *loaded* as a wrong program — the TTL merely
stops them from squatting on disk after a version bump.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Tuple

import jax

# <checkout>/.cache/xla: this file is <checkout>/go_ibft_tpu/utils/jaxcache.py.
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".cache",
    "xla",
)

# Bounded-growth defaults: generous enough that a full warm_kernels sweep
# (every pinned family, multiple shape buckets, ~tens of MB each) never
# evicts itself, small enough that years of jax bumps cannot fill a disk.
DEFAULT_MAX_BYTES = 4 << 30  # 4 GiB
DEFAULT_TTL_S = 30 * 24 * 3600.0  # 30 days

_enabled = False


def resolve_cache_dir() -> str:
    """The cache directory ``enable_persistent_cache`` would select
    (``jax.config`` already holds ``JAX_COMPILATION_CACHE_DIR``: jax reads
    it at import)."""
    return jax.config.jax_compilation_cache_dir or _DEFAULT_DIR


def enable_persistent_cache() -> str:
    """Idempotently enable the JAX persistent compilation cache.

    A directory placed from outside (``JAX_COMPILATION_CACHE_DIR``, or a
    ``jax_compilation_cache_dir`` the embedder configured) is used as is
    and never pruned; otherwise the in-checkout default is created, pruned
    once per process (TTL + size bound) and handed to jax.  Returns the
    effective dir.
    """
    global _enabled
    if _enabled:
        return resolve_cache_dir()
    if not jax.config.jax_compilation_cache_dir:
        os.makedirs(_DEFAULT_DIR, exist_ok=True)
        prune_cache(_DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    # Floor below which compiles are not persisted (they cost less than
    # the disk round-trip).  ``GO_IBFT_CACHE_MIN_COMPILE_S=0`` persists
    # everything — the CI boot check uses it so even the sub-second
    # digest program proves a second-boot cache load.
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("GO_IBFT_CACHE_MIN_COMPILE_S", 1)),
    )
    _enabled = True
    return resolve_cache_dir()


def prune_cache(
    path: Optional[str] = None,
    *,
    max_bytes: Optional[int] = None,
    max_age_s: Optional[float] = None,
    now: Optional[float] = None,
) -> Tuple[int, int]:
    """Bound the in-checkout cache: drop stale entries, evict oldest-first.

    Runs once per process from :func:`enable_persistent_cache` for the
    default directory only (explicit calls always run; ``path`` defaults
    to the in-checkout directory, never to one placed from outside).
    Never raises — a concurrently-pruning sibling process or a read-only
    cache degrades to a no-op.  Returns ``(files_removed, bytes_removed)``.
    """
    target = path or _DEFAULT_DIR
    if max_bytes is None:
        max_bytes = int(
            os.environ.get("GO_IBFT_CACHE_MAX_BYTES", DEFAULT_MAX_BYTES)
        )
    if max_age_s is None:
        max_age_s = float(os.environ.get("GO_IBFT_CACHE_TTL_S", DEFAULT_TTL_S))
    ts = time.time() if now is None else now
    entries = []  # (mtime, size, path)
    try:
        for root, _dirs, files in os.walk(target):
            for name in files:
                p = os.path.join(root, name)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                entries.append((st.st_mtime, st.st_size, p))
    except OSError:
        return (0, 0)
    removed = freed = 0

    def _rm(size: int, p: str) -> None:
        nonlocal removed, freed
        try:
            os.remove(p)
        except OSError:
            return
        removed += 1
        freed += size

    live = []
    for mtime, size, p in entries:
        if max_age_s > 0 and ts - mtime > max_age_s:
            _rm(size, p)
        else:
            live.append((mtime, size, p))
    if max_bytes > 0:
        total = sum(size for _m, size, _p in live)
        for mtime, size, p in sorted(live):  # oldest first
            if total <= max_bytes:
                break
            _rm(size, p)
            total -= size
    return (removed, freed)
