"""The wall of some of the window's program spans a span of another name:
``names`` are summed, ``per`` is counted (the serial host parts of a flush over
the flushes).  A program without the span ``needs`` names (the parent of the
PR that brought it), or a window with no ``per`` span, gives nothing to read:
``None``, and the line leaves the metric out."""


def read(ctx, spec):
    spans = [r for r in ctx.get("spans") or [] if r[0] == "X"]
    per = sum(1 for r in spans if r[1] == spec["per"])
    if not per or not any(r[1] == spec["needs"] for r in spans):
        return None
    return sum(r[4] for r in spans if r[1] in spec["names"]) / 1e3 / per
