"""Lanes the verifier refused over the lanes it gave a verdict, in %, from
the ``verify.verdicts`` instants of the window (``go_ibft_tpu/verify/batch.py``:
one inside every ``verify.drain`` / ``verify.early_exit``, args ``live`` and
``rejected``).  A program without the instant (before PR 31) gives nothing
to read: ``None``, and the line leaves the metric out."""


def read(ctx, spec):
    live = rejected = 0
    for r in ctx.get("spans") or []:
        if r[0] == "i" and r[1] == "verify.verdicts" and r[5]:
            live += r[5].get("live", 0)
            rejected += r[5].get("rejected", 0)
    if not live:
        return None
    return 100.0 * rejected / live
