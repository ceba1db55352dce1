"""Per operation (a height, a ``catch_up`` call): its wall minus the union
of the verifier's outer spans inside it; the median, in ms.  What is left
is the engine's (or the sync client's) own host time: event-loop turns,
store, quorum arithmetic, proposal hashes, lane lists."""

from benchmark.lib.stats import median, union_seconds

VERIFIER_SPANS = ("verify.drain", "verify.early_exit")


def read(ctx, spec):
    ops = ctx["counts"].get("op_spans") or []
    spans = [
        (r[3] / 1e6, (r[3] + r[4]) / 1e6)
        for r in ctx.get("spans") or []
        if r[0] == "X" and r[1] in VERIFIER_SPANS
    ]
    if not ops or not spans:
        return None
    spans.sort()
    out = []
    for t0, t1 in ops:
        inside = [(s, e) for s, e in spans if e > t0 and s < t1]
        out.append(((t1 - t0) - union_seconds(inside, t0, t1)) * 1e3)
    return median(out)
