"""Where a process's set-up seconds go before ``_recover_kernel`` first runs.

A validator is not ready until its recover programs are loaded, and what a
process pays for one program is five things, of which the persistent cache
removes ONE: importing the package, tracing the program in Python, lowering
the jaxpr to StableHLO (a Pallas body is lowered to Mosaic MLIR here too),
compiling it or loading the executable from the cache, and the first dispatch.
Trace and lower are paid by every process, cache or no cache: the cache key is
made from the lowered module.  PR 47's one-kernel ladder was refused for six
seconds of exactly that (``PERF.md`` section 6).

Usage:
    python scripts/setup_budget.py 256 2048            # one child process a lane count
    python scripts/setup_budget.py --repeat 2 256      # the second child loads what the first compiled
    python scripts/setup_budget.py --no-compile 256    # import, trace and lower only (seconds, any host)

Each child prints one JSON line: ``import_s``, ``trace_s``, ``lower_s``,
``compile_s`` (``how`` says ``compiled`` or ``cache_load``, from
``jax.monitoring``), ``dispatch_s`` (first call with zero rows, read back),
``hlo_lines`` / ``hlo_bytes`` of the lowered text, ``tpu_custom_calls`` in it
and ``platform``; ``kernels`` holds, for each Pallas body of a folded program
(``glv_ladder``, ``lift_x_pow``, ``to_affine_inv``) jitted alone, ``trace_s``,
``lower_s`` and ``compile_s``.  ``events`` holds jax's own durations by name
(they count the same seconds from inside; ``jaxpr_trace_duration`` is one entry a jitted
function, nested ones included).

With a TPU the program is lowered, compiled and run there.  Without one
(``JAX_PLATFORMS=cpu``, this sandbox) it is lowered for the TPU platform, which
needs no chip (Mosaic lowering included), and compiled ahead of time for a
DESCRIBED v5e (``jax.experimental.topologies``); nothing runs, ``dispatch_s``
is null.  The compile seconds of this host are not the chip machine's: read
trace and lower here, compile and dispatch there.
"""

import argparse
import json
import subprocess
import sys
import time

_T0 = time.perf_counter()

DURATION_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
    "/jax/compilation_cache/compile_time_saved_sec",
)
CACHE_HIT = "/jax/compilation_cache/cache_hits"
TABLE_ROWS = 128  # the 100-validator cells' table bucket


def _child(lanes: int, compile_it: bool) -> dict:
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    import jax
    import numpy as np
    from jax import monitoring

    from go_ibft_tpu.ops import pallas_ladder
    from go_ibft_tpu.utils import jaxcache
    from go_ibft_tpu.verify import batch

    from benchmark.lib import stage_reduce

    out = {"lanes": lanes, "import_s": time.perf_counter() - _T0}
    events = {name: 0.0 for name in DURATION_EVENTS}
    hits = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: events.__setitem__(name, events[name] + secs)
        if name in events
        else None
    )
    monitoring.register_event_listener(lambda name, **_: hits.append(name) if name == CACHE_HIT else None)
    jaxcache.enable_persistent_cache()

    on_chip = jax.default_backend() == "tpu"
    out["platform"] = jax.devices()[0].device_kind if on_chip else "described v5e"
    shapes = stage_reduce.recover_shapes(lanes, TABLE_ROWS)
    if not on_chip:
        # The program a TPU process would trace: the ladder's path is chosen
        # by the backend, and this host's is the CPU.
        pallas_ladder.supported = lambda: True
    if not on_chip and compile_it:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        shapes = tuple(jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one) for s in shapes)

    t = time.perf_counter()
    traced = batch._recover_kernel.trace(*shapes)
    out["trace_s"] = time.perf_counter() - t
    t = time.perf_counter()
    lowered = traced.lower() if on_chip else traced.lower(lowering_platforms=("tpu",))
    out["lower_s"] = time.perf_counter() - t
    text = lowered.as_text()
    out["hlo_lines"], out["hlo_bytes"] = text.count("\n"), len(text)
    out["tpu_custom_calls"] = text.count("tpu_custom_call")
    out["compile_s"] = out["how"] = out["dispatch_s"] = None
    if compile_it:
        t = time.perf_counter()
        compiled = lowered.compile()
        out["compile_s"] = time.perf_counter() - t
        out["how"] = "cache_load" if hits else "compiled"
        if on_chip:
            rows = [np.zeros(s.shape, s.dtype) for s in shapes]
            t = time.perf_counter()
            np.asarray(compiled(*rows))
            out["dispatch_s"] = time.perf_counter() - t
    out["events"] = {name.rsplit("/", 1)[1]: secs for name, secs in events.items()}
    out["kernels"] = _kernels(lanes, shapes[0].sharding, on_chip, compile_it)
    out["total_s"] = time.perf_counter() - _T0
    return out


def _kernels(lanes: int, sharding, on_chip: bool, compile_it: bool) -> dict:
    """The program's Pallas bodies one by one, each jitted alone on operands
    of the program's shapes: seconds to trace, to lower (Mosaic MLIR) and to
    compile (a second child finds them in the compile cache, as it finds the
    program).  Read after the program, so Pallas is imported."""
    import jax
    import jax.numpy as jnp

    from go_ibft_tpu.ops import pallas_ladder as pk
    from go_ibft_tpu.ops import secp256k1 as sec

    if lanes % 256:
        return {}

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    L, ladder_rows, power_rows = sec.FIELD.nlimbs, lanes // 32, 8 * -(-lanes // 1024)

    def power(name, *chains):
        moduli, exponents = zip(*chains)
        call = lambda a: pk.power_call(a, moduli=moduli, exponents=exponents, name=name)  # noqa: E731
        return call, (s(len(chains), L, power_rows, 128),)

    calls = {
        "glv_ladder": (
            lambda *a: pk.ladder_call(*a, field=sec.FIELD),
            (s(33, ladder_rows, 128), s(ladder_rows, 128)) + (s(16, L, ladder_rows, 128),) * 3,
        ),
        "lift_x_pow": power("lift_x_pow", (sec.FIELD, sec._SQRT_EXP), (sec.ORDER, sec.N - 2)),
        "to_affine_inv": power("to_affine_inv", (sec.FIELD, sec.P - 2)),
    }
    out = {}
    for name, (call, shapes) in calls.items():
        t = time.perf_counter()
        traced = jax.jit(call).trace(*shapes)
        row = {"trace_s": time.perf_counter() - t}
        t = time.perf_counter()
        lowered = traced.lower() if on_chip else traced.lower(lowering_platforms=("tpu",))
        row["lower_s"] = time.perf_counter() - t
        row["compile_s"] = None
        if compile_it:
            t = time.perf_counter()
            lowered.compile()
            row["compile_s"] = time.perf_counter() - t
        out[name] = row
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("lanes", type=int, nargs="+")
    ap.add_argument("--repeat", type=int, default=1, help="children a lane count, one after the other")
    ap.add_argument("--no-compile", action="store_true", help="stop after lowering")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        print(json.dumps(_child(args.lanes[0], not args.no_compile)), flush=True)
        return 0
    # One process a reading: a chip belongs to one process, and a second
    # trace in the same process finds the nested jits already traced.
    rc = 0
    for lanes in args.lanes:
        for _ in range(args.repeat):
            cmd = [sys.executable, __file__, "--child", str(lanes)]
            rc |= subprocess.call(cmd + (["--no-compile"] if args.no_compile else []))
    return rc


if __name__ == "__main__":
    sys.exit(main())
