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
neighbour in time.  ``stages_of_run`` is the reading: it finds this run's
xplane, maps op events to stages, and memoises the result per process.

How an op event gets its stage (looked at on TPU v5 lite traces of both
lane counts, JAX 0.9.0, PR 24).  The device's ``XLA Ops`` events are named
by the op's whole HLO text, but that text carries no ``metadata={...}``
(route a of ISSUE 24: 0 of 11,799 distinct names), and an event's own stats
are ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``, no name stack (route b).  So the map comes from the compiled
module (route c): the short name the trace gives (``copy.5878``) is the
instruction's name in the optimized module, and ``_recover_kernel.lower(<the
traced shapes>).compile().as_text()`` prints every instruction with its
metadata; every traced name was found there (``names_found`` 1.0).
"""

from __future__ import annotations

import json
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
MIN_NAMES_FOUND = 0.99  # route c: traced op names found in the compiled text

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
    (events charged) and ``names`` (theirs).
    """
    seconds = {s: 0.0 for s in STAGES + (UNSCOPED,)}
    layout = dict(seconds)
    moves_of = dict(layout_by_name or {})
    ordered = sorted((e for e in ops if e[2] > 0), key=lambda e: (e[1], -e[2]))
    charged = 0
    names = set()
    at = 0
    for lo, hi in sorted(intervals):
        while at < len(ordered) and ordered[at][1] < lo:
            at += 1
        stack: List[Tuple[float, str, bool]] = []  # end, effective stage, layout
        clock = lo

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
            stage = stage_by_name.get(name)
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
    return {
        "seconds": seconds,
        "layout_seconds": layout,
        "busy_s": sum(seconds.values()),
        "ops": charged,
        "names": names,
    }


# ------------------------------------------------------------ the reading


def program_intervals(
    modules: Sequence[trace_reduce.Event], prefixes: Sequence[str]
) -> Tuple[List[Tuple[float, float]], set]:
    """The intervals of the program's executions on one chip, and the
    distinct module names (name and fingerprint) they ran under."""
    spans, names = [], set()
    for name, s, d in modules:
        module = trace_reduce.module_name(name)
        if d > 0 and any(module == p or module.startswith(p) for p in prefixes):
            spans.append((s, s + d))
            names.add(name)
    return spans, names


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
    text with metadata.  In the process that ran it, or with the persistent
    cache warm, this is the executable that ran."""
    from go_ibft_tpu.verify import batch

    return batch._recover_kernel.lower(*recover_shapes(lanes, table_rows)).compile().as_text()


def stages_from_xplane(
    path_or_data, lanes: int, runs: int, table_rows: int, text_of=compiled_text
) -> dict:
    """The reduction of one traced run: ``lanes`` padded lanes in ``runs``
    dispatches against a validator table of ``table_rows``.

    Fails safe: where no op of the program carries a ``recover.*`` stage (a
    stale cache entry, compiled before the scopes), where the runs were of
    more than one module or fewer than 99% of the traced op names are in the
    compiled text (another module than the one that ran), or where the text
    cannot be had, ``us_per_lane`` is ``None`` and ``unscoped_share`` 100.
    ``layout_share`` needs no scope and is given all the same (by the opcodes
    of the compiled text, or by the ops' short names where there is none).
    """
    t0 = time.perf_counter()
    trace = trace_reduce.load(path_or_data)
    prefixes = module_prefixes(PROGRAM)
    runs_of = [program_intervals(dev.modules, prefixes) for dev in trace.devices]
    modules = set().union(*(names for _spans, names in runs_of)) if runs_of else set()
    out: dict = {"route": "c", "lanes": lanes, "runs": runs, "events_dropped": trace.dropped}

    failed = None
    compiled: Dict[str, Instruction] = {}
    if len(modules) != 1 or runs <= 0 or lanes % runs:
        failed = f"not one shape of the program: modules {sorted(modules)}"
    else:
        t_text = time.perf_counter()
        try:  # a reader never ends a run: what goes wrong here is reported
            text = text_of(lanes // runs, table_rows)
            compiled = {i.name: i for i in parse_hlo_text(text)}
        except Exception as exc:  # noqa: BLE001
            failed = f"no compiled text: {type(exc).__name__}: {exc}"[:300]
        out["compile_text_s"] = time.perf_counter() - t_text
    stage_by_name = {n: stage_of(i.op_name) for n, i in compiled.items()}
    layout_by_name = {n: is_layout(n, i.opcode) for n, i in compiled.items()}

    seconds: Dict[str, float] = {}
    layout: Dict[str, float] = {}
    charged: set = set()
    for dev, (spans, _names) in zip(trace.devices, runs_of):
        got = reduce_stages(dev.ops, spans, stage_by_name, layout_by_name)
        for total, part in ((seconds, got["seconds"]), (layout, got["layout_seconds"])):
            for stage, value in part.items():
                total[stage] = total.get(stage, 0.0) + value
        charged |= got["names"]
        out["ops"] = out.get("ops", 0) + got["ops"]
    busy = sum(seconds.values())
    out["busy_s"] = busy
    if busy <= 0 or lanes <= 0:
        failed = "no op of the program in the trace"
    elif failed is None:
        out["names_found"] = sum(n in compiled for n in charged) / len(charged)
        if out["names_found"] < MIN_NAMES_FOUND:
            failed = "the compiled text is of another module than the one that ran"
        elif not any(stage_by_name.get(n) for n in charged):
            failed = "no op carries a recover.* stage"
    out["layout_share"] = 100.0 * sum(layout.values()) / busy if busy > 0 else None
    if failed is not None:
        out.update(failed=failed, us_per_lane=None, unscoped_share=100.0)
    else:
        out.update(
            us_per_lane={k: v * 1e6 / lanes for k, v in seconds.items()},
            layout_us_per_lane={k: v * 1e6 / lanes for k, v in layout.items()},
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
    seconds, runs = trace_reduce.program_seconds(trace, module_prefixes(PROGRAM))
    lanes = ledger_sum(rows, "padded_lanes", PROGRAM, "device")
    if seconds <= 0 or lanes <= 0:
        return None
    path = trace_reduce.newest_xplane(TRACE_ROOT)
    if path is None:
        return None
    if path not in _MEMO:
        validators = ctx["config"]["validators"]
        table_rows = next(b for b in TABLE_BUCKETS if b >= validators)
        got = stages_from_xplane(path, lanes, runs, table_rows)
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
