#!/usr/bin/env python
"""Charge a profiler window's device idle time to the program's spans.

    python scripts/idle_report.py <xplane file or directory>

Reads the ``.xplane.pb`` a ``jax.profiler`` window left (the benchmark's
``--trace 1`` run leaves one under ``.cache/benchmark_trace/<cell>``,
``GET /profilez`` under the ``dir`` it returns) and prints ONE JSON object:
``window_s``, ``busy_s``, ``idle_s``, ``heights``, ``idle_by_span``,
``self_ms_by_span``, ``longest_gaps``, ``waits``, ``clock``,
``device_clock``, ``threads`` (``go_ibft_tpu/obs/idle.py`` and
``docs/OBSERVABILITY.md`` §7 say what each is).  Exit code 1 where there
is no trace to read, 2 where the ring's clock and the profiler's are not
one clock (``clock.spread_us``): the object then holds ``clock`` and
``refused`` alone.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1 or argv[0] in ("-h", "--help"):
        print(__doc__, file=sys.stderr)
        return 0 if argv and argv[0] in ("-h", "--help") else 1
    from go_ibft_tpu.obs import idle

    path = idle.newest_xplane(argv[0])
    if path is None:
        print(f"idle_report: no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    out = idle.report(path)
    out["xplane"] = path
    print(json.dumps(out))
    return 2 if "refused" in out else 0


if __name__ == "__main__":
    sys.exit(main())
