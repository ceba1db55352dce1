"""The pinned program registry: every compile-budget family, buildable.

One entry per family in ``docs/compile_budget.json`` — the name IS the
manifest key (``<family>_<shape suffix>``), and the builder returns the
``(lowerable, args)`` pair that reproduces the family's engine-hot shape
exactly as ``scripts/compile_budget.py`` has always lowered it (that
script now consumes THIS registry, so the trace-size ratchet and the AOT
store can never pin different programs).

Builders are lazy: constructing the registry imports nothing heavy, and
each builder does its own imports + argument packing when called, so
restoring one small family (a boot harness on a budget) never pays the
BLS workload build.  A builder whose prerequisites are absent — a mesh
family on a host with fewer devices than its ``dp`` — raises
:class:`ProgramUnavailable`, which the AOT store records as a skip, not
a fault.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Optional, Sequence, Tuple

__all__ = ["ENGINE_LANES", "MESH_DPS", "ProgramUnavailable", "program_registry"]

# The engine-route lane bucket (the acceptance-tracked compile) and the
# dp sweep of the multi-chip pins — both mirrored from the compile-budget
# posture (see scripts/compile_budget.py for the why of each shape).
ENGINE_LANES = 8
MESH_DPS = (2, 4, 8)


class ProgramUnavailable(RuntimeError):
    """A builder's prerequisites are absent on this host (e.g. a mesh
    family needing more devices than exist); degrade to a recorded skip."""


def _engine_shapes() -> dict:
    import jax.numpy as jnp

    from ..ops import secp256k1 as sec

    B = ENGINE_LANES
    L = sec.FIELD.nlimbs
    return {
        "blocks": jnp.zeros((B, 2, 17, 2), jnp.uint32),
        "counts": jnp.ones((B,), jnp.int32),
        "limbs": jnp.zeros((B, L), jnp.int32),
        "v": jnp.zeros((B,), jnp.int32),
    }


def _build_bls_aggregate_verify():
    import jax

    from ..bench.bls_workload import build_bls_round_workload
    from ..ops.bls12_381 import aggregate_verify_commit
    import jax.numpy as jnp

    w = build_bls_round_workload(ENGINE_LANES, time_host=False)
    return jax.jit(aggregate_verify_commit), tuple(jnp.asarray(a) for a in w.args)


def _build_g2_merge_tree():
    import jax.numpy as jnp

    from ..ops.bls12_381 import g2_merge_tree

    fe30 = 30  # BLS Fp limb count
    m = jnp.zeros((128, fe30), jnp.int32)
    live = jnp.zeros((128,), bool)
    return g2_merge_tree, (m, m, m, m, live)


def _build_g1_merge_tree():
    import jax.numpy as jnp

    from ..ops.bls12_381 import g1_merge_tree

    fe30 = 30
    m = jnp.zeros((128, fe30), jnp.int32)
    live = jnp.zeros((128,), bool)
    return g1_merge_tree, (m, m, live)


def _build_digest_words():
    import jax

    from ..ops import quorum

    s = _engine_shapes()
    return jax.jit(quorum.digest_words), (s["blocks"], s["counts"])


def _build_multipair_miller():
    import jax.numpy as jnp

    from ..ops.bls12_381 import _multi_miller_stage

    fe30 = 30
    mm = jnp.zeros((2, ENGINE_LANES, fe30), jnp.int32)
    return _multi_miller_stage, (mm, mm, mm, mm, mm, mm)


def _build_ecdsa_recover():
    import jax

    from ..ops import secp256k1 as sec

    s = _engine_shapes()
    return jax.jit(sec.ecdsa_recover), (s["limbs"], s["limbs"], s["limbs"], s["v"])


def _build_ecmul2_base():
    import jax

    from ..ops import secp256k1 as sec

    s = _engine_shapes()
    return jax.jit(sec.ecmul2_base), (s["limbs"], s["limbs"], s["limbs"], s["limbs"])


def _cpu_devices(dp: int):
    import jax

    try:
        cpu = jax.devices("cpu")
    except RuntimeError as exc:
        raise ProgramUnavailable(f"no cpu backend for dp={dp} mesh: {exc}")
    if len(cpu) < dp:
        raise ProgramUnavailable(
            f"mesh family needs {dp} devices, host has {len(cpu)}"
        )
    return cpu[:dp]


def _build_mesh_verify_mask(dp: int):
    import jax.numpy as jnp

    from ..ops import secp256k1 as sec
    from ..parallel import make_mesh
    from ..verify.mesh_batch import mesh_verify_mask

    mesh = make_mesh(dp, devices=_cpu_devices(dp))
    g = ENGINE_LANES * dp  # 8 local lanes per shard
    L = sec.FIELD.nlimbs
    return mesh_verify_mask(mesh), (
        jnp.zeros((g, 8), jnp.uint32),
        jnp.zeros((g, L), jnp.int32),
        jnp.zeros((g, L), jnp.int32),
        jnp.zeros((g,), jnp.int32),
        jnp.zeros((g, 5), jnp.uint32),
        jnp.zeros((8, 5), jnp.uint32),
        jnp.zeros((g,), bool),
    )


def _build_ici_tick(n_nodes: int):
    """The ICI lock-step tick collective (rows variant): all_gather of
    the ``(N, M, B)`` staging tensor + on-shard payload digests + the
    gathered sender rows the verify kernels consume — ONE program per
    consensus tick (net/ici.py).  Pinned at the real-crypto cluster
    shape: ``n_nodes`` nodes on ``n_nodes`` host devices, 8 lanes of
    512-byte slots."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from ..net.ici import build_tick_program
    from ..ops import secp256k1 as sec

    mesh = Mesh(np.asarray(_cpu_devices(n_nodes)), ("node",))
    m_slots, b = ENGINE_LANES, 512
    lanes = n_nodes * m_slots
    L = sec.FIELD.nlimbs
    return build_tick_program(mesh, rows=True), (
        jnp.zeros((n_nodes, m_slots, b), jnp.uint8),
        jnp.zeros((lanes, 8, 17, 2), jnp.uint32),
        jnp.ones((lanes,), jnp.int32),
        jnp.zeros((lanes, L), jnp.int32),
        jnp.zeros((lanes, L), jnp.int32),
        jnp.zeros((lanes,), jnp.int32),
        jnp.zeros((lanes, 5), jnp.uint32),
        jnp.zeros((lanes,), bool),
    )


def program_registry(
    programs: Optional[Sequence[str]] = None,
) -> "OrderedDict[str, Callable[[], Tuple[object, tuple]]]":
    """``name -> builder`` for every pinned family (optionally filtered).

    Each builder returns ``(lowerable, args)`` where ``lowerable``
    supports ``.lower(*args)`` (a ``jax.jit`` object).  Unknown names in
    ``programs`` raise ``KeyError`` — a boot manifest naming a family
    this registry does not pin is a configuration error, not a skip.
    """
    defs: "OrderedDict[str, Callable]" = OrderedDict(
        (
            ("bls_aggregate_verify_8v", _build_bls_aggregate_verify),
            ("bls_g2_merge_tree_128v", _build_g2_merge_tree),
            ("bls_g1_merge_tree_128v", _build_g1_merge_tree),
            ("digest_words_8l", _build_digest_words),
            ("bls_multipair_miller_8l", _build_multipair_miller),
            ("ecdsa_recover_8l", _build_ecdsa_recover),
            ("ecmul2_base_8l", _build_ecmul2_base),
            ("ici_tick_8n", lambda: _build_ici_tick(8)),
        )
    )
    for dp in MESH_DPS:
        defs[f"mesh_verify_mask_8l_dp{dp}"] = (
            lambda dp=dp: _build_mesh_verify_mask(dp)
        )
    if programs is None:
        return defs
    out: "OrderedDict[str, Callable]" = OrderedDict()
    for name in programs:
        out[name] = defs[name]
    return out
