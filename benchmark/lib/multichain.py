"""Several independent chains and their pre-signed catch-up traffic (host
only: never imports jax).

The deployment of ``benchmark/configs/ecdsa-16chains.json``: ``chains`` lists
classes of committees (``validators``, ``count``), and every chain is a
``Committee`` of its own, keyed from the run's seed and the chain's index, so
no two chains share a validator.  Chain ``k`` lives at heights above
``height_stride * (k + 1)``: blocks are a pure function of the height, so
disjoint heights are disjoint proposals and proposal hashes.

A catch-up range of a chain is ``Committee.sync_blocks``' (a seeded choice of
``seals_per_block`` signers a height, the first ``corrupt_seals`` seals of
every ``corrupt_every``-th height mangled), and of those bad seals the first
``cross_chain_seals`` are replaced by CROSS-CHAIN seals: a valid signature,
over this block's own hash, of a validator of the partner chain (the next
chain of the same class).  Such a seal is a valid signature by a member of
the flush's device table; only this chain's own membership refuses it.  The
labels say refused for every bad seal, of either kind.
"""

from __future__ import annotations

import pickle
import sys
from typing import List, Tuple

import numpy as np

from go_ibft_tpu.chain.wal import FinalizedBlock
from go_ibft_tpu.crypto.backend import proposal_hash_of
from go_ibft_tpu.messages.helpers import CommittedSeal

from .committee import Committee
from .signing import SigningPool


def chain_seed(seed: int, k: int) -> int:
    """The seed of chain ``k``'s committee: no two chains of a run, and no
    chain and a one-chain cell of the same ``--seed``, share a key."""
    return (seed << 8) | (k + 1)


def sizes_of(config: dict) -> List[int]:
    """The validators of each chain, in the order the configuration lists
    its classes."""
    return [c["validators"] for c in config["chains"] for _ in range(c["count"])]


class Chains:
    """The chains of one deployment, a ``Committee`` each."""

    def __init__(self, config: dict, seed: int) -> None:
        self.sizes = sizes_of(config)
        self.chains = [Committee(n, chain_seed(seed, k)) for k, n in enumerate(self.sizes)]
        per_block = config["assumed"]["seals_per_block"]
        self.spb = [per_block[str(n)] for n in self.sizes]
        self.native = all(c.native for c in self.chains)

    def __len__(self) -> int:
        return len(self.chains)

    def partner(self, k: int) -> int:
        """The chain whose validators sign chain ``k``'s cross-chain seals:
        the next of its class, or the next chain where it is alone in it."""
        same = [j for j, n in enumerate(self.sizes) if n == self.sizes[k]]
        ring = same if len(same) > 1 else list(range(len(self.sizes)))
        return ring[(ring.index(k) + 1) % len(ring)]

    def first_height(self, k: int, r: int, blocks: int, stride: int) -> int:
        """The first height of chain ``k``'s range ``r`` of ``blocks``."""
        return stride * (k + 1) + 1 + r * blocks

    def cross_seal(self, k: int, height: int, j: int, proposal_hash: bytes) -> CommittedSeal:
        """A valid seal over ``proposal_hash`` by a validator of ``k``'s
        partner chain (a seeded choice by height and position)."""
        other = self.chains[self.partner(k)]
        signer = other.keys[(height + j) % other.n].address
        return CommittedSeal(signer=signer, signature=other.seal(signer, proposal_hash))

    def storm_blocks(
        self, k: int, first_height: int, blocks: int, spec: dict
    ) -> Tuple[List[FinalizedBlock], List[np.ndarray]]:
        """``blocks`` finalized blocks of chain ``k`` and, per block, the
        mask a correct verifier must give.  ``spec``: ``corrupt_every``,
        and by class ``corrupt_seals`` and ``cross_chain_seals``."""
        n = str(self.sizes[k])
        bad = spec["corrupt_seals"].get(n, 0)
        cross = min(bad, spec["cross_chain_seals"].get(n, 0))
        got, want = self.chains[k].sync_blocks(
            first_height, blocks, self.spb[k], spec["corrupt_every"], bad
        )
        for i, (block, mask) in enumerate(zip(got, want)):
            if mask.all() or not cross:
                continue
            proposal_hash = proposal_hash_of(block.proposal)
            seals = list(block.seals)
            for j in range(cross):
                seals[j] = self.cross_seal(k, block.height, j, proposal_hash)
            got[i] = FinalizedBlock(height=block.height, proposal=block.proposal, seals=seals)
        return got, want


# -- pre-signing in worker processes --------------------------------------


def _storm_job(config: dict, seed: int, k: int, first_height: int, blocks: int, spec: dict):
    chains = Chains(config, seed)
    got = chains.storm_blocks(k, first_height, blocks, spec)
    return pickle.dumps(got), "jax" in sys.modules, chains.native


class StormSigningPool(SigningPool):
    """``SigningPool`` with the many-chain job."""

    JOB_SEALS = 1536  # about this many seals a job: a 100-validator range is four

    def submit_range(
        self, config: dict, seed: int, k: int, first_height: int, blocks: int, spec: dict
    ) -> int:
        """Sign one range of chain ``k`` in slices; how many jobs that was."""
        per_block = config["assumed"]["seals_per_block"][str(sizes_of(config)[k])]
        per_job = max(1, self.JOB_SEALS // per_block)
        before = self.submitted
        for i in range(0, blocks, per_job):
            self._futures.append(
                self._pool.submit(
                    _storm_job, config, seed, k, first_height + i, min(per_job, blocks - i), spec
                )
            )
        return self.submitted - before
