"""Readers of the folded recover program as a TPU process traces it, shared
by ``test_pallas_ladder.py`` and ``test_kernel_stages.py``: the jaxpr, the
Pallas kernels' bodies inside it, and the module lowered for the TPU platform
(Mosaic lowering included; no chip, nothing compiles).
"""

import contextlib
import math

import jax

from benchmark.lib import stage_reduce as sr
from go_ibft_tpu.ops import pallas_ladder as pk
from go_ibft_tpu.verify import batch


@contextlib.contextmanager
def traced_for_tpu():
    """Inside, programs are traced as on a TPU backend (the ladder's path is
    chosen by the backend, and the tests' is the CPU).  Traces remember
    their choice: the caches are dropped on the way in and on the way out."""
    was = pk.supported
    jax.clear_caches()
    pk.supported = lambda: True
    try:
        yield
    finally:
        pk.supported = was
        jax.clear_caches()


def sub_jaxprs(eqn):
    for v in eqn.params.values():
        inner = getattr(v, "jaxpr", v)
        if hasattr(inner, "eqns"):
            yield inner


def eqns_of(jaxpr):
    """Every equation, at any depth, a loop's body once."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in sub_jaxprs(eqn):
            yield from eqns_of(inner)


def count_eqns(jaxpr) -> int:
    return sum(1 for _ in eqns_of(jaxpr))


def scans(jaxpr):
    return [e for e in eqns_of(jaxpr) if e.primitive.name == "scan"]


def slab_products(jaxpr, sites: bool = False) -> int:
    """Field products of a kernel body in its own terms: ONE multiply of the
    outer product ``(k, L, L, 8, 128)`` is ``k`` products (``k`` operands
    stacked; no ``k``: one).  A loop's body counts once an iteration; with
    ``sites`` once, and a stack as one."""
    total = 0
    for eqn in jaxpr.eqns:
        times = eqn.params["length"] if eqn.primitive.name == "scan" and not sites else 1
        total += times * sum(slab_products(inner, sites) for inner in sub_jaxprs(eqn))
        shape = eqn.outvars[0].aval.shape if eqn.outvars else ()
        if eqn.primitive.name == "mul" and len(shape) >= 4 and shape[-4:-2] == (20, 20):
            total += 1 if sites else math.prod(shape[:-4])
    return total


class Lowered:
    """``_recover_kernel`` at ``lanes`` lanes, traced and lowered for the TPU."""

    def __init__(self, lanes: int, table_rows: int = 8):
        traced = batch._recover_kernel.trace(*sr.recover_shapes(lanes, table_rows))
        self.jaxpr = traced.jaxpr.jaxpr
        lowered = traced.lower(lowering_platforms=("tpu",))
        self.text = lowered.as_text(debug_info=True)
        self.bare_text = lowered.as_text()
        # A Pallas body by its kernel's name, and where its call stands: the
        # name stack down to it through every nested jit, which is the
        # ``op_name`` XLA gives the custom call and ``stage_reduce`` reads.
        self.kernels, self.kernel_scopes = {}, {}
        for scope, e in _scoped_eqns(self.jaxpr):
            if e.primitive.name == "pallas_call":
                name = e.params["name"]
                self.kernels[name], self.kernel_scopes[name] = e.params["jaxpr"], scope


def _scoped_eqns(jaxpr, outer=""):
    for eqn in jaxpr.eqns:
        scope = "/".join(filter(None, (outer, str(eqn.source_info.name_stack))))
        yield scope, eqn
        if eqn.primitive.name != "pallas_call":
            for inner in sub_jaxprs(eqn):
                yield from _scoped_eqns(inner, scope)
