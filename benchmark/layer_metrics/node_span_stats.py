"""Statistics over the window's program spans for the node cell's metrics:
one reader, told by the metric file's ``read`` which span and which number.

``name`` (or ``names``) picks the spans; ``arg`` one of their args; ``stat``:
``median`` / ``mean`` of the arg, ``sum_per_op`` of the arg over finalized
heights, ``count_per_op`` of the spans, ``sum_ms_per_op`` of their durations,
``union_ms_per_op`` of the wall they cover together.  A program without the
span (the parent of the PR that brought it) gives nothing to read: ``None``,
and the line leaves the metric out."""

from benchmark.lib.stats import median, union_seconds


def read(ctx, spec):
    names = spec.get("names") or [spec["name"]]
    spans = [
        r for r in ctx.get("spans") or [] if r[0] == "X" and r[1] in names
    ]
    heights = ctx["counts"].get("operations")
    stat = spec["stat"]
    if not spans or (stat.endswith("_per_op") and not heights):
        return None
    if stat == "count_per_op":
        return len(spans) / heights
    if stat == "sum_ms_per_op":
        return sum(r[4] for r in spans) / 1e3 / heights
    if stat == "union_ms_per_op":
        edges = [(r[3] / 1e6, (r[3] + r[4]) / 1e6) for r in spans]
        return union_seconds(edges) * 1e3 / heights
    values = [r[5][spec["arg"]] for r in spans if r[5] and spec["arg"] in r[5]]
    if not values:
        return None
    if stat == "median":
        return median(values)
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "sum_per_op":
        return sum(values) / heights
    raise ValueError(f"unknown stat {stat!r}")
