"""The recover program's named stages: every ``jax.named_scope`` of
``ops/secp256k1.py`` and ``ops/quorum.py`` reaches the optimized HLO's
``op_name``s, where ``benchmark/lib/stage_reduce.py`` reads them.

``_recover_fn`` is compiled once at 8 lanes with the persistent cache OFF:
scope names are not part of the cache key (the key is hashed after
``strip-debuginfo``), so a cached executable would show the names it was
first compiled with.
"""

import collections
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import stage_reduce as sr  # noqa: E402
from go_ibft_tpu.ops import secp256k1 as sec  # noqa: E402
from go_ibft_tpu.verify import batch  # noqa: E402

LANES = 8
# Instructions that do no work of their own, and mostly carry no metadata.
PLUMBING = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}


@pytest.fixture(scope="module")
def recover_text():
    """The optimized module of a FRESH compile.  Through ``_recover_kernel``
    itself a process that already ran the shape gets the executable it ran
    (the lowering and its executable are memoised), and that one may come
    from a persistent cache entry older than the scopes; a new function is
    traced, lowered and, with the persistent cache off, compiled anew."""
    from jax.experimental.compilation_cache import compilation_cache

    def fresh(*args):
        return batch._recover_fn(*args)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fresh).lower(*sr.recover_shapes(LANES, LANES)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def instructions(recover_text):
    return sr.parse_hlo_text(recover_text)


def test_scopes_in_the_program_are_the_stages_the_benchmark_reads():
    found = []
    for name in ("secp256k1.py", "quorum.py"):
        with open(os.path.join(ROOT, "go_ibft_tpu", "ops", name)) as fh:
            found += re.findall(r'jax\.named_scope\("([^"]+)"\)', fh.read())
    assert set(found) == set(sr.STAGES)
    # One call site a stage, except the address stage's three.
    counts = collections.Counter(found)
    assert counts.pop("recover.address") == 3 and set(counts.values()) == {1}


def test_all_nine_scope_names_reach_the_optimized_hlo(instructions):
    seen = collections.Counter(sr.stage_of(i.op_name) for i in instructions)
    for stage in sr.STAGES:
        assert seen[stage] > 0, stage
    assert set(seen) - {None} == set(sr.STAGES)


def test_every_while_resolves_to_exactly_one_stage(instructions):
    whiles = [i for i in instructions if i.opcode == "while"]
    assert len(whiles) >= 10
    for w in whiles:
        stages = {p for p in re.split("[/;]", w.op_name or "") if p.startswith("recover.")}
        assert len(stages) == 1 and stages <= set(sr.STAGES), (w.name, w.op_name)
        assert w.body
    by_stage = collections.Counter(sr.stage_of(w.op_name) for w in whiles)
    # The 33-step ladder and the 4 doublings inside its body; the Q table's
    # 14-step scan; keccak's 24 rounds; the merged sqrt / r^-1 scan.
    assert by_stage["recover.glv_ladder"] == 2
    assert by_stage["recover.q_table"] == 1
    assert by_stage["recover.address"] == 1
    assert by_stage["recover.lift_x"] >= 1 and by_stage["recover.to_affine"] >= 1


def test_instructions_of_the_entry_and_the_loop_bodies_name_their_stage(recover_text, instructions):
    top_level = {i.body for i in instructions if i.opcode == "while"}
    top_level.add(sr.entry_of(recover_text))
    top = [
        i for i in instructions if i.computation in top_level and i.opcode not in PLUMBING
    ]
    assert len(top) > 1000
    own = sum(sr.stage_of(i.op_name) is not None for i in top)
    assert own / len(top) >= 0.90, (own, len(top))


def test_scopes_do_not_nest(instructions):
    for i in instructions:
        parts = [p for p in re.split("[/;]", i.op_name or "") if p.startswith("recover.")]
        assert len(set(parts)) <= 1 or ";" in i.op_name, i.op_name


@pytest.mark.parametrize(
    "stage",
    ["recover.q_table", "recover.glv_split", "recover.glv_ladder", "recover.combine"],
)
def test_ecdsa_verify_gets_the_ladder_scopes_for_nothing(verify_text, stage):
    assert stage in verify_text


@pytest.fixture(scope="module")
def verify_text():
    limbs = jax.ShapeDtypeStruct((LANES, 20), jnp.int32)
    return sec.ecdsa_verify.lower(limbs, limbs, limbs, limbs, limbs).as_text(debug_info=True)


def test_ecdsa_verify_is_not_scoped_beyond_ecmul2_base(verify_text):
    for stage in ("recover.range_check", "recover.lift_x", "recover.to_affine", "recover.address"):
        assert stage not in verify_text


def test_all_nine_scope_names_reach_the_folded_program_on_the_kernel_path():
    """The other path (PR 48, PR 52): where the ladder and the two Fermat
    chains are Pallas kernels the nine scopes are still in the program, and
    each kernel's custom call stands under its stage's scope (down through
    ``to_affine``'s nested jit), which is how ``stage_reduce`` charges it to
    the stage.  Lowered for the TPU platform, no chip (last in the file: it
    drops the trace caches)."""
    import pallas_cases as pc

    with pc.traced_for_tpu():
        lowered = pc.Lowered(256)
    locs = [line for line in lowered.text.splitlines() if line.startswith("#loc")]
    seen = collections.Counter(sr.stage_of(m) for line in locs for m in re.findall(r'loc\("([^"]+)"', line))
    for stage in sr.STAGES:
        assert seen[stage] > 0, stage
    assert len([line for line in locs if "pallas_call" in line]) == 3
    assert {k: sr.stage_of(scope) for k, scope in lowered.kernel_scopes.items()} == {
        "lift_x_pow": "recover.lift_x",
        "glv_ladder": "recover.glv_ladder",
        "to_affine_inv": "recover.to_affine",
    }
