"""The ``ecdsa_recover`` program's share of its memory roofline, in %.

Least time = HBM bytes in and out of one dispatch (from its shapes,
``kernel_cost.ecdsa_recover_bytes``) over the device's published HBM
bandwidth; share = least time over the program's device time per dispatch
in the profiler's window.  The 256-bit ladder is int32 vector work, for
which the chip has no published peak, so the compute side is not in it:
the number says how far from the MEMORY bound the program sits."""

from benchmark.lib.harness import ledger_sum
from benchmark.lib.kernel_cost import ecdsa_recover_bytes, module_prefixes, peaks_of
from benchmark.lib.trace_reduce import program_seconds

TABLE_BUCKETS = (8, 128, 512, 2048)  # go_ibft_tpu/verify/batch.py _TABLE_BUCKETS


def read(ctx, spec):
    trace, rows = ctx.get("trace"), ctx.get("ledger_traced")
    if not trace or not rows:
        return None
    seconds, runs = program_seconds(trace, module_prefixes("ecdsa_recover"))
    dispatches = ledger_sum(rows, "dispatches", "ecdsa_recover", "device")
    lanes = ledger_sum(rows, "padded_lanes", "ecdsa_recover", "device")
    if seconds <= 0 or dispatches <= 0 or lanes <= 0:
        return None
    validators = ctx["config"]["validators"]
    table_rows = next(b for b in TABLE_BUCKETS if b >= validators)
    bytes_moved = dispatches * ecdsa_recover_bytes(lanes // dispatches, table_rows)
    least_s = bytes_moved / peaks_of(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
