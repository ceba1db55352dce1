"""Device time of the ``ecdsa_recover`` program in the profiler's window
over the padded lanes the cost ledger saw dispatched in that window (both
edges of it are operation boundaries, so nothing is cut), in microseconds."""

from benchmark.lib.harness import ledger_sum
from benchmark.lib.kernel_cost import module_prefixes
from benchmark.lib.trace_reduce import program_seconds


def read(ctx, spec):
    trace, rows = ctx.get("trace"), ctx.get("ledger_traced")
    if not trace or not rows:
        return None
    seconds, _runs = program_seconds(trace, module_prefixes("ecdsa_recover"))
    lanes = ledger_sum(rows, "padded_lanes", "ecdsa_recover", "device")
    if seconds <= 0 or lanes <= 0:
        return None
    return seconds * 1e6 / lanes
