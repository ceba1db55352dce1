"""Batched signature verification backends.

Interchangeable implementations of the
:class:`go_ibft_tpu.core.backend.BatchVerifier` protocol (SURVEY.md §7
stage 4):

* :class:`HostBatchVerifier` — sequential per-message verification (native
  C++ ecrecover when available, pure Python otherwise); the reference
  semantics oracle and the CI stand-in when no accelerator exists.
* :class:`DeviceBatchVerifier` — one ``jit`` batch per phase on whatever
  JAX backend is active (TPU in production, CPU in tests); the framework's
  headline capability.
* :class:`MeshBatchVerifier` — the same drains sharded lane-parallel
  across the device mesh (shard_map, collective-free); degrades
  transparently to :class:`DeviceBatchVerifier` on a 1-device host.
* :class:`AdaptiveBatchVerifier` — routes tiny batches to the host path
  and big ones to the device kernels (the dispatch-latency floor makes
  device batching a loss below ~a dozen lanes).
* :class:`ResilientBatchVerifier` — the degraded-mode drain: quarantines
  poison lanes by bisection and demotes a faulting device down the
  ``device -> host (native) -> pure Python`` ladder via a
  :class:`CircuitBreaker`, restoring after cooldown (docs/ROBUSTNESS.md).

All return identical boolean masks for identical inputs — determinism
across backends is part of the conformance suite.

Two cross-cutting latency planes ride on top (ISSUE 9):

* every rung serves ``verify_seals_early_exit`` — a seal drain that
  stops at the exact voting-power quorum and reports the unverified
  remainder (:class:`EarlyExitReport`) for lazy off-path resolution;
* :class:`SpeculativeVerifier` + :class:`SpeculationCache`
  (:mod:`go_ibft_tpu.verify.speculate`) verify cross-phase arrivals as
  they land, hash-bound so a verdict can never leak across a different
  (height, round, proposal hash, phase, sender, signature) binding.
"""

from .aggregate import G2MergeTree, MultiPairVerifier, multi_aggregate_check
from .batch import (
    AdaptiveBatchVerifier,
    DeviceBatchVerifier,
    EarlyExitReport,
    EngineScope,
    HostBatchVerifier,
    MalformedLaneError,
    ResilientBatchVerifier,
    SIG_BYTES,
)
from .mesh_batch import MeshBatchVerifier
from .pipeline import CircuitBreaker, PackCache, VerifyPipeline
from .speculate import SpeculationCache, SpeculativeVerifier

__all__ = [
    "AdaptiveBatchVerifier",
    "CircuitBreaker",
    "DeviceBatchVerifier",
    "EarlyExitReport",
    "EngineScope",
    "G2MergeTree",
    "HostBatchVerifier",
    "MalformedLaneError",
    "MeshBatchVerifier",
    "MultiPairVerifier",
    "PackCache",
    "ResilientBatchVerifier",
    "SpeculationCache",
    "SpeculativeVerifier",
    "VerifyPipeline",
    "SIG_BYTES",
    "multi_aggregate_check",
]
