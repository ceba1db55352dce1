"""Inside the recover program: device time per named stage, and how much
of each stage is data movement between layouts.

The program (``go_ibft_tpu/ops/secp256k1.py``, ``ops/quorum.py``) wraps each
stage of ``_recover_fn`` in a ``jax.named_scope``; the names below are the
contract between the program and this file.  A scope name becomes one path
component of the ``op_name`` that XLA keeps in every instruction's metadata
(``jit(_recover_fn)/jit(ecdsa_recover)/jit(ecmul2_base)/recover.glv_ladder/
while/body/...``), and the stage of an ``op_name`` is its LAST component
that starts with ``recover.``.

``reduce_stages`` is the arithmetic, on plain events, so that the tests run
it on a hand-built trace: inside the intervals of the program's executions
each instant is charged to the innermost op open at it (as
``trace_reduce._leaf_seconds`` does), under the stage of that op or, where
the op has none of its own, of the innermost enclosing event that has one
(a compiler-inserted ``copy`` in the ladder's ``while`` is the ladder's);
with no such ancestor it is ``unscoped``.  Nothing is inherited from a
neighbour in time.  ``stages_from_xplane`` is the reading of one traced
window; ``stages_of_run`` finds this run's xplane and memoises the result per
process.

How an op event gets its stage (looked at on TPU v5 lite traces, JAX
0.9.0, PRs 24 and 33).  The device's ``XLA Ops`` events are named by the op's
whole HLO text, but that text carries no ``metadata={...}`` (0 of 11,799
distinct names), and an event's own stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``, no name stack.  So the
map comes from the compiled module: the short name the trace gives
(``copy.5878``) is the instruction's name in the optimized module, and
``_recover_kernel.lower(<the traced shapes>).compile().as_text()`` prints
every instruction with its metadata.  In the process that ran the program
that second compile hands back the executable that ran (1.3 s, no compile
request; on the chip in PR 33, on either tree, in either order, on its own
cache and on a copied one): it is the one source of the map.

What made the reading fail before PR 33 was the trace, not the text
(``PERF.md`` section 6): the device's op tracing goes live some milliseconds
after ``start_trace`` returns, so the probe runs a lead-in before the marked
window and the reduction keeps to the marks; and about one traced flood in
four has a few hundred of its 1.76 million op events under names of no
module (``region.6238``, a new one each time), which are charged like any op
without a stage of its own and counted by EVENTS, not by distinct names.

The ``kernel_stages`` line a traced run prints: ``lanes`` and ``dispatches``
are the cost ledger's count for the marked window, ``runs`` the program's
runs the trace has inside the marks (``runs_outside_the_marks``: the
lead-in's), ``module`` the one module they ran under, ``names_found`` the
share of the traced op EVENTS whose name the compiled text has
(``names_not_found`` and ``not_found`` count and show the distinct names it
has not), ``runs_whole`` the runs whose ops cover 99% of their time on ``XLA
Modules`` (the reading is theirs: seconds over their lanes) and ``runs_cut``
the coverage of each of the others.  The reading fails safe, and ``failed``
says why, where the runs are of more than one module or not as many as the
ledger's dispatches, where ``names_found`` is under 0.99, where fewer than
half of the runs are whole, or where no op carries a ``recover.*`` stage;
``compile_text_s`` and ``reduce_s`` are what the reading cost.
"""

from __future__ import annotations

import json
import math
import os
import re
import time
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

from . import trace_reduce
from .harness import ledger_sum
from .kernel_cost import module_prefixes

PROGRAM = "ecdsa_recover"  # the cost ledger's name of the program
STAGE_PREFIX = "recover."
STAGES = (
    "recover.range_check",
    "recover.lift_x",
    "recover.q_table",
    "recover.glv_split",
    "recover.glv_ladder",
    "recover.combine",
    "recover.to_affine",
    "recover.address",
    "recover.membership",
)
UNSCOPED = "unscoped"
_UNKNOWN = object()  # an op name a module's map has no entry for

# Data movement between layouts: part of the metric ``recover_layout_share``.
LAYOUT_OPCODES = frozenset(
    (
        "copy",
        "copy-start",
        "copy-done",
        "reshape",
        "transpose",
        "slice",
        "dynamic-slice",
        "dynamic-update-slice",
        "pad",
        "concatenate",
        "bitcast",
    )
)
TABLE_BUCKETS = (8, 128, 512, 2048)  # go_ibft_tpu/verify/batch.py _TABLE_BUCKETS
MIN_NAMES_FOUND = 0.99  # traced op EVENTS whose name the compiled text has
MIN_OPS_COVERAGE = 0.99  # a whole run: its ops' time over its time on ``XLA Modules``

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_ROOT = os.path.join(ROOT, ".cache", "benchmark_trace")


# --------------------------------------------------------------- HLO text


class Instruction(NamedTuple):
    name: str  # short: ``copy.5878``
    opcode: str
    op_name: Optional[str]  # the metadata's, where the line has one
    computation: str  # the computation the line stands in ("" for a bare line)
    body: Optional[str]  # a ``while``'s body computation


_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def _after_type(rest: str) -> str:
    """``rest`` of an instruction line behind its result type: a tuple type
    is one balanced pair of parentheses, any other type has no space."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[i + 1 :]
        return ""
    return rest.partition(" ")[2]


def parse_instruction(line: str, computation: str = "") -> Optional[Instruction]:
    """One line of HLO text, or a trace event's name, as an instruction."""
    m = _LINE.match(line)
    if m is None:
        return None
    opcode = _OPCODE.match(_after_type(m.group(2)))
    if opcode is None:
        return None
    op_name = _OP_NAME.search(line)
    body = _BODY.search(line) if opcode.group(1) == "while" else None
    return Instruction(
        m.group(1),
        opcode.group(1),
        op_name.group(1) if op_name else None,
        computation,
        body.group(1) if body else None,
    )


def parse_hlo_text(text: str) -> List[Instruction]:
    """Every instruction of a module's text (``compiled.as_text()``)."""
    out = []
    computation = ""
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m is not None:
                computation = m.group(1)
            continue
        inst = parse_instruction(line, computation)
        if inst is not None:
            out.append(inst)
    return out


def entry_of(text: str) -> Optional[str]:
    """The name of a module text's entry computation."""
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)\s", text, re.MULTILINE)
    return m.group(1) if m else None


def stage_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost ``recover.*`` component of an ``op_name`` (of its last
    one where XLA merged two ops and joined their names with ``;``)."""
    if not op_name:
        return None
    for part in reversed(re.split("[/;]", op_name)):
        if part.startswith(STAGE_PREFIX):
            return part
    return None


def is_layout(short: str, opcode: Optional[str] = None) -> bool:
    """Whether an op only moves data between layouts.  ``opcode`` is the
    HLO opcode where it is known; without it the kind of the short name
    stands in (``copy.5878`` -> ``copy``).  A fusion is one if its name is
    built only from such words: ``slice_pad_fusion``, not ``pad_add_fusion``
    and not a bare ``fusion``."""
    kind = trace_reduce.op_kind(short)
    if opcode in (None, "fusion") and kind.endswith("fusion"):
        words = [w for w in kind.split("_") if w != "fusion"]
        return bool(words) and all(w in LAYOUT_OPCODES for w in words)
    return (opcode or kind) in LAYOUT_OPCODES


# ----------------------------------------------------------- the charging


def reduce_stages(
    ops: Sequence[trace_reduce.Event],
    intervals: Iterable[Tuple[float, float]],
    stage_by_name: Dict[str, Optional[str]],
    layout_by_name: Optional[Dict[str, bool]] = None,  # absent: by the short name
) -> dict:
    """Device seconds per stage inside ``intervals`` (the program's runs).

    ``ops`` are ``(short name, start, duration)`` of one chip's ``XLA Ops``
    line; ``stage_by_name`` gives an op's own stage (absent or ``None``: it
    has none); ``layout_by_name`` says which ops are data movement (an op it
    does not list is judged by :func:`is_layout` of its short name).  Returns ``seconds`` and
    ``layout_seconds`` per stage (``unscoped`` among them), ``busy_s`` (their
    sum: the instants at which some op of the program was open), ``ops``
    (events charged), ``names`` (theirs), and ``unknown_ops`` / ``unknown_names``:
    the events, and their names, that ``stage_by_name`` has no entry for (they
    are charged like an op without a stage of its own: to the enclosing event's);
    and ``per_interval``: ``(seconds, layout_seconds)`` of each interval alone,
    in the order of their starts.
    """
    total = {s: 0.0 for s in STAGES + (UNSCOPED,)}
    per_interval = []
    moves_of = dict(layout_by_name or {})
    ordered = sorted((e for e in ops if e[2] > 0), key=lambda e: (e[1], -e[2]))
    charged = 0
    names = set()
    unknown_ops = 0
    unknown_names = set()
    at = 0
    for lo, hi in sorted(intervals):
        while at < len(ordered) and ordered[at][1] < lo:
            at += 1
        stack: List[Tuple[float, str, bool]] = []  # end, effective stage, layout
        clock = lo
        seconds = dict(total)
        layout = dict(total)
        per_interval.append((seconds, layout))

        def charge(upto: float) -> None:
            nonlocal clock
            upto = min(upto, hi)
            if stack and upto > clock:
                _end, stage, moves = stack[-1]
                seconds[stage] += upto - clock
                if moves:
                    layout[stage] += upto - clock
            clock = max(clock, upto)

        while at < len(ordered) and ordered[at][1] < hi:
            name, s, d = ordered[at]
            at += 1
            charged += 1
            names.add(name)
            while stack and stack[-1][0] <= s:
                charge(stack[-1][0])
                stack.pop()
            charge(s)
            clock = max(clock, s)
            stage = stage_by_name.get(name, _UNKNOWN)
            if stage is _UNKNOWN:
                unknown_ops += 1
                unknown_names.add(name)
                stage = None
            if stage is None:  # inherit from the enclosing event, and only it
                stage = stack[-1][1] if stack else UNSCOPED
            elif stage not in seconds:
                seconds[stage] = layout[stage] = 0.0
            moves = moves_of.get(name)
            if moves is None:
                moves = moves_of[name] = is_layout(name)
            stack.append((s + d, stage, moves))
        while stack:
            charge(stack[-1][0])
            stack.pop()
    seconds, layout = sum_stages(per_interval)
    return {
        "seconds": seconds,
        "layout_seconds": layout,
        "busy_s": sum(seconds.values()),
        "per_interval": per_interval,
        "ops": charged,
        "names": names,
        "unknown_ops": unknown_ops,
        "unknown_names": unknown_names,
    }


def sum_stages(per_interval) -> Tuple[Dict[str, float], Dict[str, float]]:
    """``(seconds, layout_seconds)`` per stage, summed over intervals."""
    seconds = {s: 0.0 for s in STAGES + (UNSCOPED,)}
    layout = dict(seconds)
    for part, moves in per_interval:
        for stage, value in part.items():
            seconds[stage] = seconds.get(stage, 0.0) + value
        for stage, value in moves.items():
            layout[stage] = layout.get(stage, 0.0) + value
    return seconds, layout


# ------------------------------------------------------------ the reading


def recover_shapes(lanes: int, table_rows: int) -> tuple:
    """The arguments of ``_recover_fn`` as shapes (``kernel_cost`` lists
    them): digest words, r, s, v, claimed address, validator table, live."""
    import jax
    import jax.numpy as jnp

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    return (
        s((lanes, 8), jnp.uint32),
        s((lanes, 20), jnp.int32),
        s((lanes, 20), jnp.int32),
        s((lanes,), jnp.int32),
        s((lanes, 5), jnp.uint32),
        s((table_rows, 5), jnp.uint32),
        s((lanes,), jnp.bool_),
    )


def compiled_text(lanes: int, table_rows: int) -> str:
    """The optimized module of the recover program at the traced shapes, as
    text with metadata.  In the process that ran it this is the executable
    that ran (the dispatch's own memoised computation)."""
    from go_ibft_tpu.verify import batch

    return batch._recover_kernel.lower(*recover_shapes(lanes, table_rows)).compile().as_text()


def program_runs(trace: trace_reduce.Trace, prefixes: Sequence[str]):
    """The program's runs inside the marked window as ``[(start, end), ...]``
    per chip, the distinct module names (name and program id: a shape) they
    ran under, and how many of its runs lie outside the marks (the probe's
    lead-in)."""
    lo, hi = trace_reduce.marked_window(trace) or (-math.inf, math.inf)
    per_chip, modules, outside = [], set(), 0
    for dev in trace.devices:
        spans = []
        for name, start, dur in dev.modules:
            module = trace_reduce.module_name(name)
            if dur <= 0 or not any(module.startswith(p) for p in prefixes):
                continue
            if start < lo or start + dur > hi:
                outside += 1
                continue
            spans.append((start, start + dur))
            modules.add(name)
        per_chip.append(spans)
    return per_chip, modules, outside


def stages_from_xplane(
    path_or_data, lanes: int, dispatches: int, table_rows: int, text_of=compiled_text
) -> dict:
    """The reduction of one traced window: ``lanes`` padded lanes in
    ``dispatches`` dispatches of one shape, as the cost ledger counted them
    between the window's marks, against a validator table of ``table_rows``.

    Fails safe, never guesses: where the trace's runs inside the marks are
    of more than one module or not as many as the ledger's dispatches, where
    the text cannot be had, where fewer than 99% of the traced op EVENTS
    carry a name the text has (another module than the one that ran), where
    no op carries a ``recover.*`` stage (a stale cache entry, compiled before
    the scopes), or where fewer than half of the runs are whole,
    ``us_per_lane`` is ``None`` and ``unscoped_share`` 100, with the reason
    under ``failed``.  A run is whole where its ops cover 99% of its time on
    ``XLA Modules``; the trace now and then drops some thousand op events of
    one run (one traced flood in seven, PR 33: 9,400 events, 1.2 ms of one
    run's 8.3), and such a run is left out, lanes and seconds: every run is
    the same program on the same shape, so the whole ones are the reading.
    ``layout_share`` needs no scope and is given all the same (by the opcodes
    of the compiled text, or by the ops' short names where there is none).
    """
    t0 = time.perf_counter()
    trace = trace_reduce.load(path_or_data)
    per_chip, modules, outside = program_runs(trace, module_prefixes(PROGRAM))
    runs = sum(len(spans) for spans in per_chip)
    out: dict = {
        "route": "c",
        "lanes": lanes,
        "dispatches": dispatches,
        "runs": runs,
        "runs_outside_the_marks": outside,
        "module": sorted(modules)[0] if len(modules) == 1 else None,
        "events_dropped": trace.dropped,
    }

    failed = None
    compiled: List[Instruction] = []
    if len(modules) != 1 or dispatches <= 0 or lanes % dispatches:
        failed = f"not one shape of the program: modules {sorted(modules)}"
    elif runs != dispatches:
        failed = f"the trace has {runs} runs of the program, the ledger {dispatches} dispatches"
    else:
        t_text = time.perf_counter()
        try:  # a reader never ends a run: what goes wrong here is reported
            compiled = parse_hlo_text(text_of(lanes // dispatches, table_rows))
        except Exception as exc:  # noqa: BLE001
            failed = f"no compiled text: {type(exc).__name__}: {exc}"[:300]
        out["compile_text_s"] = time.perf_counter() - t_text
    stage_by_name = {i.name: stage_of(i.op_name) for i in compiled}
    layout_by_name = {i.name: is_layout(i.name, i.opcode) for i in compiled}

    names: set = set()
    unknown_names: set = set()
    ops = unknown_ops = 0
    whole, cut = [], []  # the runs whose ops the trace has, and the others' coverage
    for dev, spans in zip(trace.devices, per_chip):
        got = reduce_stages(dev.ops, spans, stage_by_name, layout_by_name)
        names |= got["names"]
        unknown_names |= got["unknown_names"]
        ops += got["ops"]
        unknown_ops += got["unknown_ops"]
        for (start, end), part in zip(sorted(spans), got["per_interval"]):
            coverage = sum(part[0].values()) / (end - start)
            if coverage >= MIN_OPS_COVERAGE:
                whole.append(part)
            else:
                cut.append(coverage)
    seconds, layout = sum_stages(whole)
    busy = sum(seconds.values())
    out.update(ops=ops, busy_s=busy, runs_whole=len(whole))
    if cut:
        out["runs_cut"] = sorted(cut)
    if not ops or lanes <= 0:
        failed = "no op of the program in the trace"
    elif failed is None:
        out["names_found"] = 1.0 - unknown_ops / ops
        if unknown_names:
            out["names_not_found"] = len(unknown_names)
            out["not_found"] = sorted(unknown_names)[:8]
        if out["names_found"] < MIN_NAMES_FOUND:
            failed = "the compiled text is of another module than the one that ran"
        elif 2 * len(whole) < runs:
            failed = f"the trace lost ops in {len(cut)} of the program's {runs} runs"
        elif not any(stage_by_name.get(n) for n in names):
            failed = "no op carries a recover.* stage"
    out["layout_share"] = 100.0 * sum(layout.values()) / busy if busy > 0 else None
    if failed is not None:
        out.update(failed=failed, us_per_lane=None, unscoped_share=100.0)
    else:
        read = lanes // dispatches * len(whole)  # the whole runs' lanes
        out.update(
            us_per_lane={k: v * 1e6 / read for k, v in seconds.items()},
            layout_us_per_lane={k: v * 1e6 / read for k, v in layout.items()},
            unscoped_share=100.0 * seconds[UNSCOPED] / busy,
        )
    out["reduce_s"] = time.perf_counter() - t0
    return out


_MEMO: Dict[str, dict] = {}


def stages_of_run(ctx: dict) -> Optional[dict]:
    """The stage split of the traced run that ``ctx`` describes, or ``None``
    where the run has no device trace of the program (a ``--trace 0`` run, a
    CPU rehearsal).  Reduced once per process and memoised on the xplane's
    path (the metric files' reader module is executed anew for each metric;
    this module is not); prints the ``kernel_stages`` line when it does."""
    trace, rows = ctx.get("trace"), ctx.get("ledger_traced")
    if not trace or not rows:
        return None
    seconds, _runs = trace_reduce.program_seconds(trace, module_prefixes(PROGRAM))
    lanes = ledger_sum(rows, "padded_lanes", PROGRAM, "device")
    dispatches = ledger_sum(rows, "dispatches", PROGRAM, "device")
    if seconds <= 0 or lanes <= 0:
        return None
    path = trace_reduce.newest_xplane(TRACE_ROOT)
    if path is None:
        return None
    if path not in _MEMO:
        validators = ctx["config"]["validators"]
        table_rows = next(b for b in TABLE_BUCKETS if b >= validators)
        got = stages_from_xplane(path, lanes, dispatches, table_rows)
        got["program_s"] = seconds  # the module events' time: recover_us_per_lane's
        _MEMO[path] = got
        print(json.dumps({"kernel_stages": got}), flush=True)
    return _MEMO[path]


def metric(stages: Optional[dict], spec: dict) -> Optional[float]:
    """One metric file's number out of the split: ``{"stages": [...]}`` is
    their summed microseconds a lane, ``{"share": "layout" | "unscoped"}``
    a share of the program's busy time in %."""
    if stages is None:
        return None
    if "share" in spec:
        return stages[spec["share"] + "_share"]
    per_lane = stages["us_per_lane"]
    if per_lane is None:
        return None
    return sum(per_lane[STAGE_PREFIX + s] for s in spec["stages"])
