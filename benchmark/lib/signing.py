"""Pre-signing pool: worker processes that sign traffic and never import jax.

The chip belongs to the process that measures; the workers are started with
``spawn`` (a fresh interpreter each), import only the host crypto, and hand
back pickled bytes.  Every job returns whether ``jax`` was in the worker's
``sys.modules`` and whether the native signer was in use: the harness
refuses traffic from a worker that imported jax.
"""

from __future__ import annotations

import multiprocessing
import os
import pickle
import sys
from concurrent.futures import ProcessPoolExecutor
from typing import List, Sequence, Tuple


def pool_size() -> int:
    """Workers: all cores but two (the measuring process and the OS), at
    least one, at most twelve."""
    return max(1, min(12, (os.cpu_count() or 2) - 2))


def _flood_job(n: int, seed: int, heights: Sequence[int], corrupt: int):
    from .committee import Committee

    committee = Committee(n, seed)
    blobs = [pickle.dumps(committee.traffic(h, corrupt)) for h in heights]
    return blobs, "jax" in sys.modules, committee.native


def _sync_job(n: int, seed: int, first_height: int, blocks: int, spec: dict):
    from .committee import Committee

    committee = Committee(n, seed)
    got = committee.sync_blocks(
        first_height,
        blocks,
        spec["seals_per_block"],
        spec["corrupt_every"],
        spec["corrupt_seals"],
    )
    return pickle.dumps(got), "jax" in sys.modules, committee.native


class SigningPool:
    """A pool that signs in the background while the caller warms the chip."""

    def __init__(self, workers: int | None = None) -> None:
        self.workers = workers or pool_size()
        self._pool = ProcessPoolExecutor(
            max_workers=self.workers,
            mp_context=multiprocessing.get_context("spawn"),
        )
        self._futures: list = []

    @property
    def submitted(self) -> int:
        """Jobs submitted and not yet collected."""
        return len(self._futures)

    def submit_flood(
        self, n: int, seed: int, heights: Sequence[int], corrupt: int
    ) -> None:
        """Sign ``heights`` in slices, a few to each worker."""
        heights = list(heights)
        per_job = max(1, -(-len(heights) // (self.workers * 4)))
        for i in range(0, len(heights), per_job):
            self._futures.append(
                self._pool.submit(
                    _flood_job, n, seed, heights[i : i + per_job], corrupt
                )
            )

    def submit_sync(
        self, n: int, seed: int, first_height: int, blocks: int, spec: dict
    ) -> None:
        """Sign one range of blocks in slices, a few to each worker."""
        per_job = max(1, -(-blocks // (self.workers * 2)))
        for i in range(0, blocks, per_job):
            self._futures.append(
                self._pool.submit(
                    _sync_job,
                    n,
                    seed,
                    first_height + i,
                    min(per_job, blocks - i),
                    spec,
                )
            )

    def collect(self) -> Tuple[List, bool]:
        """Everything submitted so far, in submission order, as the jobs'
        own results; and whether every worker used the native signer.
        Raises if a worker had imported jax."""
        results, native = [], True
        for f in self._futures:
            payload, saw_jax, used_native = f.result()
            if saw_jax:
                raise RuntimeError("a signing worker imported jax")
            native = native and bool(used_native)
            results.append(payload)
        self._futures = []
        return results, native

    def close(self) -> None:
        """Stop the workers and wait until each has ended."""
        self._pool.shutdown(wait=True, cancel_futures=True)
