"""Bytes a kernel has to move, computed from its shapes (kept with the
benchmark so that no later change to the program can move the yardstick).

The shapes are those ``go_ibft_tpu/verify/batch.py`` hands ``_recover_fn``:
per lane a digest (8 x uint32), r and s (20 x int32 limbs each), v (int32),
the claimed address (5 x uint32) and a live flag (bool); per dispatch the
validator table (rows x 5 x uint32); out comes one bool per lane.  The
operations of the 256-bit ladder are int32 vector work, for which the chip
has no published peak, so only the memory bound is computed here.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def ecdsa_recover_bytes(lanes: int, table_rows: int) -> int:
    """HBM bytes in and out of one ``ecdsa_recover`` dispatch."""
    per_lane_in = 8 * 4 + 20 * 4 + 20 * 4 + 4 + 5 * 4 + 1
    per_lane_out = 1
    return lanes * (per_lane_in + per_lane_out) + table_rows * 5 * 4


def peaks_of(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in benchmark/lib/peaks.json"
        )
    return table[device_kind]


def module_prefixes(program: str) -> list:
    """XLA module-name prefixes of a cost-ledger program."""
    with open(os.path.join(_HERE, "programs.json")) as fh:
        return json.load(fh)["programs"][program]
