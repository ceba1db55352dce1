"""The general readers: how a metric file's ``read`` turns what a run
recorded into one number.  A reader that finds nothing to read returns
``None``, and the harness leaves that metric out of the line.

The context ``ctx`` a reader gets is a dict:

``samples``       the driver's samples (ms a height, s a call)
``counts``        the driver's counts plus the harness's (``setup_s``,
                  ``warm_s``, ``compiles_in_window``)
``spans``         ``go_ibft_tpu.obs.trace`` records of the window,
                  ``(ph, name, track, ts_us, dur_us, args)``
``ledger``        cost-ledger rows moved by the window (``ledger_delta``)
``ledger_traced`` the same for the profiler's sub-window
``trace``         ``trace_reduce.reduce`` of the profiler's sub-window
``config`` ``traffic`` ``device_kind``

Kinds: ``percentile`` (of samples), ``rate`` (a count over the samples'
sum, or over another count), ``count`` (a key of ``counts``, with ``stat: median`` for a list),
``span`` (records named ``name`` whose args match ``where``, ``*`` meaning
present; ``stat`` one of ``median_ms``, ``sum_ms``, ``count``), ``ledger``
(``num`` over ``den``, each a sum of a ledger ``field`` over the rows that
match ``program`` and ``route``, or ``{"count": key}``; times ``scale``),
and ``reader`` (a module beside the metric's file, ``<module>.py`` with
``read(ctx, spec)``: a metric's own small reader).
"""

from __future__ import annotations

import importlib.util
import os
from typing import Optional

from .harness import ledger_sum
from .stats import median, percentile


def span_matches(record, name: str, where: Optional[dict]) -> bool:
    if record[0] != "X" or record[1] != name:
        return False
    args = record[5] or {}
    for key, want in (where or {}).items():
        if key not in args or (want != "*" and args[key] != want):
            return False
    return True


def _span(ctx, spec):
    durs = [
        r[4] / 1e3
        for r in ctx.get("spans") or []
        if span_matches(r, spec["name"], spec.get("where"))
    ]
    if not durs:
        return None
    stat = spec.get("stat", "median_ms")
    if stat == "median_ms":
        return median(durs)
    if stat == "sum_ms":
        return sum(durs)
    if stat == "count":
        return len(durs)
    raise ValueError(f"unknown span stat {stat!r}")


def _ledger_term(ctx, term):
    if "count" in term:
        return ctx["counts"].get(term["count"])
    rows = ctx.get("ledger")
    if rows is None:
        return None
    return ledger_sum(
        rows, term["field"], term.get("program", "*"), term.get("route", "*")
    )


def _ledger(ctx, spec):
    num = _ledger_term(ctx, spec["num"])
    den = _ledger_term(ctx, spec["den"]) if "den" in spec else 1
    if num is None or not den:
        return None
    return spec.get("scale", 1) * num / den


def _count(ctx, spec):
    value = ctx["counts"].get(spec["key"])
    if value is None:
        return None
    if isinstance(value, (list, tuple)):
        if not value:
            return None
        if spec.get("stat", "median") != "median":
            raise ValueError(f"unknown count stat {spec['stat']!r}")
        return median(value)
    return value


def _percentile(ctx, spec):
    samples = ctx.get(spec.get("of", "samples"))
    return percentile(samples, spec["q"]) if samples else None


def _rate(ctx, spec):
    if spec["den"] == "sum_samples":
        den = sum(ctx["samples"])
    else:
        den = ctx["counts"].get(spec["den"])
    num = ctx["counts"].get(spec["num"])
    if not den or num is None:
        return None
    return num / den


def _module(ctx, spec, metric_file: str):
    name = spec.get("module") or os.path.splitext(os.path.basename(metric_file))[0]
    path = os.path.join(os.path.dirname(metric_file), name + ".py")
    module_spec = importlib.util.spec_from_file_location(f"_reader_{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read(ctx, spec)


KINDS = {
    "span": _span,
    "ledger": _ledger,
    "count": _count,
    "percentile": _percentile,
    "rate": _rate,
}


def read_metric(ctx: dict, metric: dict, metric_file: str):
    """The value of one metric file's ``read`` on ``ctx``, or ``None``."""
    spec = metric["read"]
    kind = spec["kind"]
    if kind == "reader":
        return _module(ctx, spec, metric_file)
    if kind not in KINDS:
        raise ValueError(f"{metric_file}: unknown reader kind {kind!r}")
    return KINDS[kind](ctx, spec)
