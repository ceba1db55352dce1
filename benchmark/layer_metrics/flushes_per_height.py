"""``ingress.flush`` spans of the window over finalized heights: how many
hand-overs to ``add_messages`` a height costs the ingress
(``go_ibft_tpu/core/transport.py`` ``BatchingIngress``).  Three where each
phase's flood is one flush.  A program without the span (before PR 31)
gives nothing to read: ``None``, and the line leaves the metric out."""


def read(ctx, spec):
    heights = ctx["counts"].get("operations")
    flushes = sum(
        1 for r in ctx.get("spans") or [] if r[0] == "X" and r[1] == "ingress.flush"
    )
    if not flushes or not heights:
        return None
    return flushes / heights
