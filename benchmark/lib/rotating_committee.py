"""A stake-weighted committee that changes at every epoch, and its pre-signed
block-sync traffic (host only: never imports jax).

The deployment of ``benchmark/configs/ecdsa-100v-pos.json``: a staked pool of
accounts, each with a stake in wei (a Python ``int`` above 2**60); the
validator set of an epoch is the ``validators`` largest stakes (ties by
address) and a validator's voting power is its stake; at each epoch's end the
stakes of ``churn_accounts`` accounts are multiplied by a seeded factor, so
powers move every epoch and membership moves with them.  ``src(height)`` is
the embedder's ``GetVotingPowers(height)`` (go-ibft
``core/validator_manager.go:17-20``); quorum is ``floor(2 * total / 3) + 1``
over the active set's total (``:129-135``).

Everything is a pure function of ``(config, seed)``: keys, stakes, which
validators sealed a block, which seals are bad.  Signing is deterministic, so
the same seed gives the same bytes in every process.  Every block keeps its
labels: the mask a correct verifier gives its seals AT THE BLOCK'S OWN
HEIGHT, and how many of its refused seals are validly signed by an account
outside that height's set.
"""

from __future__ import annotations

import pickle
import sys
from typing import Dict, FrozenSet, List, Sequence, Tuple

import numpy as np

from go_ibft_tpu import native
from go_ibft_tpu.chain.wal import FinalizedBlock
from go_ibft_tpu.crypto import PrivateKey
from go_ibft_tpu.crypto import ecdsa as ec
from go_ibft_tpu.crypto.backend import encode_signature, proposal_hash_of
from go_ibft_tpu.messages.helpers import CommittedSeal
from go_ibft_tpu.messages.wire import Proposal

from .committee import block_bytes, mangle
from .signing import SigningPool

WEI = 10**18


def quorum_of(total: int) -> int:
    return (2 * total) // 3 + 1


class RotatingCommittee:
    """The staked pool, its epochs, and the blocks they sealed."""

    def __init__(self, config: dict, seed: int) -> None:
        self.native = native.install()
        a = config["assumed"]
        self.seed = seed
        self.active = config["validators"]
        self.epoch_blocks = config["epoch_blocks"]
        self.pool = a["staked_pool"]
        self.spb = a["seals_per_block"]
        self.shape = a["stake_pareto_shape"]
        self.clip = a["stake_clip_tokens"]
        self.churn = a["churn_accounts"]
        self.churn_permille = a["churn_factor_permille"]
        self.keys = [
            PrivateKey.from_seed(f"benchmark-pos-{seed}-{i}".encode())
            for i in range(self.pool)
        ]
        self.key_of = {k.address: k for k in self.keys}
        self.accounts = sorted(self.key_of)
        # The node that catches up: an account of the pool, validator or not.
        self.node = self.keys[0].address
        self._stakes: List[Dict[bytes, int]] = []
        self._powers: Dict[int, Dict[bytes, int]] = {}

    # -- stakes and sets ------------------------------------------------

    def epoch_of(self, height: int) -> int:
        """Heights 1..E are epoch 0, E+1..2E epoch 1: the set changes at
        each epoch's END."""
        return (height - 1) // self.epoch_blocks

    def epochs_of(self, heights: Tuple[int, int]) -> range:
        """The epochs a range of heights (first, last) crosses."""
        return range(self.epoch_of(heights[0]), self.epoch_of(heights[1]) + 1)

    def stakes(self, epoch: int) -> Dict[bytes, int]:
        """Every account's stake in wei during ``epoch``."""
        while len(self._stakes) <= epoch:
            e = len(self._stakes)
            rng = np.random.default_rng([self.seed, e, 2])
            if e == 0:
                lo, hi = self.clip
                tokens = np.clip(1.0 + rng.pareto(self.shape, self.pool), lo, hi)
                dust = rng.integers(0, 10**12, self.pool)
                self._stakes.append(
                    {
                        a: int(t * 10**6) * 10**12 + int(d)
                        for a, t, d in zip(self.accounts, tokens, dust)
                    }
                )
                continue
            nxt = dict(self._stakes[-1])
            moved = rng.choice(self.pool, size=self.churn, replace=False)
            lo, hi = self.churn_permille
            for i, f in zip(moved, rng.integers(lo, hi + 1, self.churn)):
                a = self.accounts[i]
                nxt[a] = max(WEI, nxt[a] * int(f) // 1000)
            self._stakes.append(nxt)
        return self._stakes[epoch]

    def powers(self, epoch: int) -> Dict[bytes, int]:
        """The active set of ``epoch``: the largest stakes, ties by address;
        voting power = stake.  One object an epoch."""
        got = self._powers.get(epoch)
        if got is None:
            stakes = self.stakes(epoch)
            top = sorted(stakes, key=lambda a: (-stakes[a], a))[: self.active]
            got = self._powers[epoch] = {a: stakes[a] for a in sorted(top)}
        return got

    def src(self, height: int) -> Dict[bytes, int]:
        """``GetVotingPowers(height)``."""
        return self.powers(self.epoch_of(height))

    def quorum(self, height: int) -> int:
        return quorum_of(sum(self.src(height).values()))

    def members(self, epoch: int) -> FrozenSet[bytes]:
        return frozenset(self.powers(epoch))

    def seal(self, addr: bytes, phash: bytes) -> bytes:
        return encode_signature(*ec.sign(self.key_of[addr], phash))

    # -- block sync -----------------------------------------------------

    def outsiders(self, height: int, epochs: Sequence[int]) -> List[bytes]:
        """Accounts that are NOT in ``height``'s set and ARE in the set of
        another epoch among ``epochs`` (a range's), the nearest epochs'
        first: they sit on the same device table as ``height``'s lanes."""
        own_epoch = self.epoch_of(height)
        own = self.members(own_epoch)
        out: List[bytes] = []
        for e in sorted(epochs, key=lambda e: (abs(e - own_epoch), e)):
            for a in sorted(self.members(e) - own):
                if a not in out:
                    out.append(a)
        return out

    def sync_blocks(
        self,
        first_height: int,
        blocks: int,
        range_heights: Tuple[int, int],
        corrupt_every: int,
        corrupt_mangled: int,
        corrupt_out_of_set: int,
    ) -> Tuple[List[FinalizedBlock], List[np.ndarray], List[int]]:
        """``blocks`` finalized blocks from ``first_height`` on, each sealed
        by ``seals_per_block`` accounts: a seeded choice of its own height's
        validators whose valid power reaches that height's quorum.  A block
        whose height ``corrupt_every`` divides carries ``corrupt_mangled``
        mangled seals of validators and ``corrupt_out_of_set`` VALID seals of
        accounts outside its height's set that are in another set of the
        range ``range_heights`` = (first, last) it is served in (of the
        pool at large where that range has none).  Returns the blocks, per
        block the mask a correct verifier must give, and per block how many
        refused seals are validly signed by accounts of the range's other
        sets: what the drain's out-of-set counter must read."""
        epochs = self.epochs_of(range_heights)
        out, want, outside = [], [], []
        for h in range(first_height, first_height + blocks):
            bad = bool(corrupt_every) and h % corrupt_every == 0
            block, mask, n_out = self._block(
                h,
                epochs,
                corrupt_mangled if bad else 0,
                corrupt_out_of_set if bad else 0,
            )
            out.append(block)
            want.append(mask)
            outside.append(n_out)
        return out, want, outside

    def _block(self, h: int, epochs, n_mangled: int, n_out: int):
        powers = self.src(h)
        members = sorted(powers)
        rng = np.random.default_rng([self.seed, h, 1])
        # Outsiders: accounts of the range's OTHER sets first (they are on
        # the device table of the drain that verifies this block); where the
        # range has too few, accounts of the pool that no set of it holds.
        strangers = self.outsiders(h, epochs)[:n_out]
        on_table = len(strangers)
        strangers += [
            a for a in self.accounts if a not in powers and a not in strangers
        ][: n_out - on_table]
        chosen = [
            members[j]
            for j in rng.choice(len(members), size=self.spb - n_out, replace=False)
        ]
        mangled, valid = chosen[:n_mangled], chosen[n_mangled:]
        # A seeded choice WHOSE POWER REACHES QUORUM: while it does not, the
        # lightest valid signer changes places with the heaviest validator
        # that is not signing validly (it did not sign, or its seal is one
        # of the mangled ones).
        quorum = quorum_of(sum(powers.values()))
        weight = lambda a: (powers[a], a)  # noqa: E731
        rest = sorted(set(members) - set(chosen))
        while sum(powers[a] for a in valid) < quorum:
            up, down = max(rest + mangled, key=weight), min(valid, key=weight)
            if weight(up) < weight(down):
                raise RuntimeError(
                    f"height {h}: {len(valid)} valid seals cannot carry quorum power"
                )
            valid[valid.index(down)] = up
            side = mangled if up in mangled else rest
            side[side.index(up)] = down
        proposal = Proposal(raw_proposal=block_bytes(h), round=0)
        phash = proposal_hash_of(proposal)
        rows = (
            [(a, True) for a in valid]
            + [(a, False) for a in mangled]
            + [(a, None) for a in strangers]
        )
        seals, mask = [], []
        for j in rng.permutation(len(rows)):
            addr, ok = rows[j]
            sig = self.seal(addr, phash)
            seals.append(
                CommittedSeal(signer=addr, signature=sig if ok is not False else mangle(sig))
            )
            mask.append(ok is True)
        block = FinalizedBlock(height=h, proposal=proposal, seals=seals)
        return block, np.asarray(mask, dtype=bool), on_table

    # -- ranges that must be refused --------------------------------------

    def _valid_power(self, block: FinalizedBlock, valid: Sequence[bytes]) -> int:
        powers = self.src(block.height)
        return sum(powers.get(a, 0) for a in set(valid))

    def cut_by_weight(self, block: FinalizedBlock, heaviest: bool):
        """``block`` (clean: every seal a validator's valid one) with its
        heaviest (or its lightest) signers' seals mangled, one by one, until
        the valid power is under the height's quorum.  Returns the block and
        how many of its seals are still valid."""
        powers = self.src(block.height)
        quorum = self.quorum(block.height)
        order = sorted(
            (s.signer for s in block.seals),
            key=lambda a: (powers[a], a),
            reverse=heaviest,
        )
        valid = list(order)
        while self._valid_power(block, valid) >= quorum:
            valid.pop(0)
        keep = set(valid)
        seals = [
            s
            if s.signer in keep
            else CommittedSeal(signer=s.signer, signature=mangle(s.signature))
            for s in block.seals
        ]
        return (
            FinalizedBlock(height=block.height, proposal=block.proposal, seals=seals),
            len(keep),
        )

    def padded_with_outsiders(self, block: FinalizedBlock, range_heights):
        """``block`` (clean) with its heaviest signers' seals taken out until
        the validators' power is under quorum, and as many VALID seals of
        accounts outside the height's set (but in another set of the range)
        put in their place as there are such accounts: by count nothing is
        missing, and a tally that let them count would be made whole.
        Returns the block and how many outsiders sealed it."""
        powers = self.src(block.height)
        quorum = self.quorum(block.height)
        epochs = self.epochs_of(range_heights)
        by_weight = sorted(block.seals, key=lambda s: (powers[s.signer], s.signer))
        gone = []
        while self._valid_power(block, [s.signer for s in by_weight]) >= quorum:
            gone.append(by_weight.pop())
        strangers = self.outsiders(block.height, epochs)[: len(gone)]
        phash = proposal_hash_of(block.proposal)
        replaced = {s.signer: a for s, a in zip(gone, strangers)}
        kept = {s.signer for s in by_weight}
        seals = []
        for s in block.seals:
            if s.signer in kept:
                seals.append(s)
            elif s.signer in replaced:
                a = replaced[s.signer]
                seals.append(CommittedSeal(signer=a, signature=self.seal(a, phash)))
        return (
            FinalizedBlock(height=block.height, proposal=block.proposal, seals=seals),
            len(strangers),
        )


# -- pre-signing in worker processes --------------------------------------


def _pos_job(config: dict, seed: int, first_height: int, blocks: int, spec: dict):
    committee = RotatingCommittee(config, seed)
    got = committee.sync_blocks(
        first_height,
        blocks,
        tuple(spec["range_heights"]),
        spec["corrupt_every"],
        spec["corrupt_mangled"],
        spec["corrupt_out_of_set"],
    )
    return pickle.dumps(got), "jax" in sys.modules, committee.native


class PosSigningPool(SigningPool):
    """``SigningPool`` with this committee's job."""

    def submit_range(
        self, config: dict, seed: int, first_height: int, blocks: int, spec: dict
    ) -> None:
        """Sign one range of blocks in slices, a few to each worker."""
        spec = dict(spec, range_heights=(first_height, first_height + blocks - 1))
        per_job = max(1, -(-blocks // (self.workers * 2)))
        for i in range(0, blocks, per_job):
            self._futures.append(
                self._pool.submit(
                    _pos_job,
                    config,
                    seed,
                    first_height + i,
                    min(per_job, blocks - i),
                    spec,
                )
            )
