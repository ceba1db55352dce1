"""The stage reduction's arithmetic on a hand-built trace and name map, its
layout classifier, its ``op_name`` parser on lines cut from the recover
program's optimized HLO (8 lanes, XLA:CPU and a described v5e), and its
fail-safe.  No device number comes out of this file."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import stage_reduce as sr  # noqa: E402

LADDER, LIFT, ADDRESS = "recover.glv_ladder", "recover.lift_x", "recover.address"

# One recover run 1..4, one digest run 5..6, one recover run 8..9.
OPS = [
    ("fusion.1", 1.0, 0.25),  # lift_x by its own op_name
    ("while.7", 1.25, 2.25),  # the ladder's while, 1.25..3.5, body below
    ("fusion.2", 1.25, 0.5),  # own stage: the ladder's
    ("copy.3", 2.0, 0.5),  # no op_name: inherits the while's
    ("while.8", 2.5, 0.75),  # a nested while with no op_name, 2.5..3.25
    ("slice.4", 2.5, 0.5),  # no op_name, two levels down: still the ladder's
    ("copy.5", 3.5, 0.25),  # no op_name, nothing encloses it: unscoped
    ("fusion.6", 3.75, 0.25),  # address
    ("copy.3", 5.0, 1.0),  # the digest program's: not the recover program's
    ("fusion.6", 8.0, 1.0),  # second recover run
]
RUNS = [(1.0, 4.0), (8.0, 9.0)]
STAGE_BY_NAME = {
    "fusion.1": LIFT,
    "while.7": LADDER,
    "fusion.2": LADDER,
    "fusion.6": ADDRESS,
    "copy.3": None,
}


def test_leaf_charging_inside_a_while_and_inheritance_from_the_enclosing_event():
    got = sr.reduce_stages(OPS, RUNS, STAGE_BY_NAME)
    s = got["seconds"]
    assert s[LIFT] == pytest.approx(0.25)
    # fusion.2 0.5, the gap 1.75..2 (the while itself), copy.3 0.5, slice.4
    # 0.5, the nested while's rest 0.25, the outer while's rest 0.25.
    assert s[LADDER] == pytest.approx(2.25)
    assert s[ADDRESS] == pytest.approx(1.25)
    assert s[sr.UNSCOPED] == pytest.approx(0.25)  # copy.5 alone
    assert got["ops"] == 9  # the digest run's copy.3 is not among them


def test_an_op_without_a_stage_never_inherits_from_a_neighbour_in_time():
    # copy.5 starts where the ladder's while ends and before an address op.
    got = sr.reduce_stages(OPS, RUNS, STAGE_BY_NAME)
    assert got["layout_seconds"][sr.UNSCOPED] == pytest.approx(0.25)
    alone = sr.reduce_stages([("copy.5", 1.0, 1.0)], [(0.0, 3.0)], STAGE_BY_NAME)
    assert alone["seconds"][sr.UNSCOPED] == pytest.approx(1.0)
    assert sum(alone["seconds"].values()) == pytest.approx(1.0)


def test_ops_of_another_program_are_not_counted():
    got = sr.reduce_stages(OPS, RUNS, STAGE_BY_NAME)
    with_digest = sr.reduce_stages(OPS, RUNS + [(5.0, 6.0)], STAGE_BY_NAME)
    assert with_digest["busy_s"] - got["busy_s"] == pytest.approx(1.0)
    assert with_digest["seconds"][sr.UNSCOPED] - got["seconds"][sr.UNSCOPED] == pytest.approx(1.0)
    from benchmark.lib.trace_reduce import DevicePlane, Trace

    modules = [
        ("jit__recover_fn(111)", 1.0, 3.0),
        ("jit_digest_words(222)", 5.0, 1.0),
        ("jit__recover_fn(111)", 8.0, 1.0),
    ]
    per_chip, names, outside = sr.program_runs(
        Trace(devices=[DevicePlane("/device:TPU:0", modules=modules)]), ["jit__recover_fn"]
    )
    assert per_chip == [RUNS] and names == {"jit__recover_fn(111)"} and outside == 0


def test_stage_sums_and_unscoped_equal_the_programs_busy_seconds():
    got = sr.reduce_stages(OPS, RUNS, STAGE_BY_NAME)
    assert got["busy_s"] == pytest.approx(4.0)  # 1..4 and 8..9, every instant once
    assert sum(got["seconds"].values()) == pytest.approx(got["busy_s"])
    assert set(got["seconds"]) == set(sr.STAGES) | {sr.UNSCOPED}
    # Layout time is a part of each stage's time: copy.3 and slice.4.
    assert got["layout_seconds"][LADDER] == pytest.approx(1.0)
    assert got["layout_seconds"][LIFT] == 0.0
    for stage, seconds in got["layout_seconds"].items():
        assert seconds <= got["seconds"][stage] + 1e-12


def test_an_op_that_outlasts_its_run_is_cut_at_the_runs_end():
    got = sr.reduce_stages([("fusion.1", 0.5, 2.0)], [(0.0, 1.5)], STAGE_BY_NAME)
    assert got["seconds"][LIFT] == pytest.approx(1.0)


@pytest.mark.parametrize(
    "kind, moves",
    [  # the ten kinds of the ledger's breakdown (PR 22)
        ("copy", True),
        ("reshape", True),
        ("slice", True),
        ("pad", True),
        ("reduce_sum", False),
        ("pad_add_fusion", False),
        ("broadcast_multiply_fusion", False),
        ("slice_shift-right-arithmetic_fusion", False),
        ("fusion", False),
        ("while", False),
    ],
)
def test_layout_classifier_on_the_kinds_the_breakdown_lists(kind, moves):
    assert sr.is_layout(kind + ".5878") is moves


def test_layout_classifier_knows_fusions_built_only_from_layout_words():
    assert sr.is_layout("slice_pad_fusion.19", "fusion") is True
    assert sr.is_layout("dynamic-update-slice_fusion", "fusion") is True
    assert sr.is_layout("bitcast_reduce_fusion.2", "fusion") is False
    # The opcode decides where it is known, whatever JAX named the op.
    assert sr.is_layout("reduce_sum.3", "reduce") is False
    assert sr.is_layout("concatenate.9", "concatenate") is True
    assert sr.is_layout("copy-start.1", "copy-start") is True
    assert sr.is_layout("squeeze.4", "copy") is True
    assert sr.LAYOUT_OPCODES >= {"transpose", "dynamic-slice", "copy-done", "bitcast"}


# Lines cut from ``_recover_kernel.lower(8 lanes).compile().as_text()``.
CPU_WHILE = (
    "  %while.407 = (s32[], s32[4,8,20]{2,1,0}, s32[33,4,8]{2,1,0}, /*index=5*/s32[16,4,8,20]{3,2,1,0}, pred[4,8]{1,0}) "
    "while(%tuple.414), condition=%wide.region_266.353.clone, body=%wide.region_186.352.clone, "
    'metadata={op_name="jit(_recover_fn)/jit(ecdsa_recover)/jit(ecmul2_base)/recover.glv_ladder/while" stack_frame_id=3208}, '
    'backend_config={"known_trip_count":{"n":"33"}}'
)
CPU_FUSION = (
    "  %slice_pad_fusion.19 = s32[8,41]{1,0} fusion(%bitcast_reduce_fusion.2), kind=kLoop, calls=%fused_computation.76, "
    'metadata={op_name="jit(_recover_fn)/jit(ecdsa_recover)/recover.lift_x/while/body/closed_call/concatenate" stack_frame_id=318}'
)
CPU_ROOT = (
    "  ROOT %dynamic_update_slice.71 = s32[14,8,20]{2,1,0} dynamic-update-slice(%param_0.104, %bitcast.1588, %param_1.183), "
    'metadata={op_name="jit(_recover_fn)/jit(ecdsa_recover)/recover.lift_x/while/body/dynamic_update_slice" stack_frame_id=302}'
)
CPU_BARE = "  %copy.937 = s32[] copy(%get-tuple-element.1575)"
CPU_NESTED = (
    "  %while.403 = (s32[], s32[4,8,20]{2,1,0}) while(%tuple.345), condition=%region_209.287, body=%region_187.286, "
    'metadata={op_name="jit(_recover_fn)/jit(ecdsa_recover)/jit(ecmul2_base)/recover.glv_ladder/while/body/closed_call/while" stack_frame_id=3214}'
)
CPU_KECCAK = (
    "  %while.394 = (s32[], u32[8,25,2]{2,1,0}) while(%tuple.441), condition=%region_350.473.clone, body=%region_349.472.sunk.clone, "
    'metadata={op_name="jit(_recover_fn)/recover.address/while/body/closed_call/while" stack_frame_id=6281}'
)
# ... and from the same program compiled for a described v5e at 128 lanes.
TPU_COPY = (
    "  %copy.5854 = s32[4,128,1200]{1,0,2:T(4,128)S(1)} copy(%slice.39968), "
    'metadata={op_name="jit(_recover_fn)/jit(ecdsa_recover)/jit(ecmul2_base)/recover.glv_ladder/while/body/closed_call/while/body/closed_call/jit(point_double)/slice" stack_frame_id=3235}, '
    'backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":[]}}'
)
TPU_CUSTOM = (
    '  %custom-call.57 = s32[1024]{0:T(1024)} custom-call(%param_1.99), custom_call_target="AssumeGatherIndicesInBound", '
    'metadata={op_name="jit(_recover_fn)/jit(ecdsa_recover)/jit(ecmul2_base)/recover.combine/gather" stack_frame_id=5379}'
)


@pytest.mark.parametrize(
    "line, name, opcode, stage",
    [
        (CPU_WHILE, "while.407", "while", "recover.glv_ladder"),
        (CPU_FUSION, "slice_pad_fusion.19", "fusion", "recover.lift_x"),
        (CPU_ROOT, "dynamic_update_slice.71", "dynamic-update-slice", "recover.lift_x"),
        (CPU_BARE, "copy.937", "copy", None),
        (CPU_NESTED, "while.403", "while", "recover.glv_ladder"),
        (CPU_KECCAK, "while.394", "while", "recover.address"),
        (TPU_COPY, "copy.5854", "copy", "recover.glv_ladder"),
        (TPU_CUSTOM, "custom-call.57", "custom-call", "recover.combine"),
        # A trace event's name: the HLO text with the % and no metadata.
        ("%copy.5878 = s32[4,128,1200]{1,0,2:T(4,128)} copy(s32[4,128,1200]{1,2,0:T(8,128)} %slice.1)", "copy.5878", "copy", None),
    ],
)
def test_instruction_and_stage_parser_on_real_lines(line, name, opcode, stage):
    inst = sr.parse_instruction(line)
    assert (inst.name, inst.opcode) == (name, opcode)
    assert sr.stage_of(inst.op_name) == stage


def test_stage_is_the_innermost_recover_component():
    assert sr.stage_of("jit(f)/recover.address/jit(g)/recover.to_affine/while/body/mul") == "recover.to_affine"
    assert sr.stage_of("jit(_recover_fn)/jit(ecdsa_recover)") is None
    assert sr.stage_of("jit(f)/recovery/mul") is None
    assert sr.stage_of(None) is None and sr.stage_of("") is None


def test_module_text_is_parsed_by_computation_and_whiles_name_their_bodies():
    text = "\n".join(
        [
            "HloModule jit__recover_fn, entry_computation_layout={()->pred[8]{0}}",
            "",
            "%wide.region_186.352.clone (arg: (s32[], s32[4,8,20])) -> (s32[], s32[4,8,20]) {",
            CPU_NESTED,
            CPU_BARE,
            "}",
            "",
            "ENTRY %main.481 (zw.1: u32[8,8]) -> pred[8] {",
            "  %zw.1 = u32[8,8]{1,0} parameter(0)",
            CPU_WHILE,
            CPU_FUSION,
            "}",
        ]
    )
    insts = {i.name: i for i in sr.parse_hlo_text(text)}
    assert set(insts) == {"while.403", "copy.937", "zw.1", "while.407", "slice_pad_fusion.19"}
    assert insts["while.407"].body == "wide.region_186.352.clone"
    assert insts["copy.937"].computation == insts["while.407"].body
    assert insts["zw.1"].opcode == "parameter" and insts["zw.1"].computation == "main.481"
    assert sr.entry_of(text) == "main.481" and sr.entry_of(CPU_WHILE) is None


def _stages(us_per_lane):
    return {"us_per_lane": us_per_lane, "layout_share": 66.0, "unscoped_share": 2.5}


def test_metric_sums_the_stages_a_file_names_and_reads_the_shares():
    per_lane = {s: float(i + 1) for i, s in enumerate(sr.STAGES)}
    per_lane[sr.UNSCOPED] = 0.5
    st = _stages(per_lane)
    assert sr.metric(st, {"stages": ["range_check", "lift_x"]}) == 3.0
    assert sr.metric(st, {"stages": ["q_table", "glv_split", "glv_ladder", "combine"]}) == 18.0
    assert sr.metric(st, {"stages": ["to_affine"]}) == 7.0
    assert sr.metric(st, {"stages": ["address", "membership"]}) == 17.0
    assert sr.metric(st, {"share": "layout"}) == 66.0
    assert sr.metric(st, {"share": "unscoped"}) == 2.5
    # The four groups and the unscoped part are the whole program.
    assert 3.0 + 18.0 + 7.0 + 17.0 + 0.5 == sum(per_lane.values())
    assert sr.metric(None, {"stages": ["to_affine"]}) is None


def _xplane(op_names, second_module="jit_digest_words(222)"):
    """A small xplane: one recover run 0..3 ms holding ``op_names`` one
    after the other inside a ``while``, and one run of another module."""
    from jax.profiler import ProfileData

    def event(mid, offset_ps, duration_ps):
        return f"events {{ metadata_id: {mid} offset_ps: {offset_ps} duration_ps: {duration_ps} }}"

    names = ["jit__recover_fn(111)", second_module, "%while.7 = (s32[]) while(%t), body=%b"] + op_names
    meta = "\n".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}'
        for i, n in enumerate(names)
    )
    ms = 1_000_000_000
    ops = [event(3, 0, 3 * ms)] + [event(4 + i, i * ms, ms) for i in range(len(op_names))]
    ops.append(event(4, 5 * ms, ms))  # inside the other module's run
    text = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    {event(1, 0, 3 * ms)} {event(2, 5 * ms, ms)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    {" ".join(ops)} }}
  {meta}
}}
"""
    return ProfileData.from_text_proto(text)


OP_A = "%fusion.1 = s32[8,20]{1,0} fusion(s32[8,20]{1,0} %p), kind=kLoop"
OP_B = "%copy.3 = s32[4,8,1200]{1,0,2} copy(s32[4,8,1200]{1,2,0} %fusion.1)"
OP_C = "%fusion.6 = u32[8,5]{1,0} fusion(s32[8,20]{1,0} %copy.3), kind=kLoop"
COMPILED = "\n".join(
    [
        "ENTRY %main (p: s32[8,20]) -> u32[8,5] {",
        '  %while.7 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(_recover_fn)/recover.glv_ladder/while"}',
        '  %fusion.1 = s32[8,20]{1,0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(_recover_fn)/recover.lift_x/mul"}',
        "  %copy.3 = s32[4,8,1200]{1,0,2} copy(%fusion.1)",
        '  %fusion.6 = u32[8,5]{1,0} fusion(%copy.3), kind=kLoop, calls=%f6, metadata={op_name="jit(_recover_fn)/recover.address/while/body/xor"}',
        "}",
    ]
)


def test_xplane_to_stages_through_the_compiled_text():
    path = _xplane([OP_A, OP_B, OP_C])
    got = sr.stages_from_xplane(path, 8, 1, 8, text_of=lambda lanes, rows: COMPILED)
    assert got["route"] == "c" and got["names_found"] == 1.0
    assert got["busy_s"] == pytest.approx(3e-3) and got["ops"] == 4
    per_lane = got["us_per_lane"]
    assert per_lane[LIFT] == pytest.approx(1e3 / 8)
    assert per_lane[LADDER] == pytest.approx(1e3 / 8)  # the copy, by inheritance
    assert per_lane[ADDRESS] == pytest.approx(1e3 / 8)
    assert got["layout_us_per_lane"][LADDER] == pytest.approx(1e3 / 8)
    assert got["layout_share"] == pytest.approx(100 / 3)
    assert got["unscoped_share"] == 0.0
    assert sum(per_lane.values()) * 8 / 1e6 == pytest.approx(got["busy_s"])


@pytest.mark.parametrize(
    "compiled, why",
    [
        # a stale cache: the executable that ran was compiled without scopes
        (COMPILED.replace("recover.", "recovered_"), "no op carries a recover.* stage"),
        # another module than the one that ran: names not found
        (
            COMPILED.replace("fusion.", "fusion.9"),
            "the compiled text is of another module than the one that ran",
        ),
    ],
)
def test_fail_safe_reads_none_four_times_and_unscoped_100(compiled, why):
    path = _xplane([OP_A, OP_B, OP_C])
    got = sr.stages_from_xplane(path, 8, 1, 8, text_of=lambda lanes, rows: compiled)
    assert got["failed"] == why and got["us_per_lane"] is None
    for stages in (["range_check", "lift_x"], ["q_table", "glv_split", "glv_ladder", "combine"],
                   ["to_affine"], ["address", "membership"]):
        assert sr.metric(got, {"stages": stages}) is None
    assert sr.metric(got, {"share": "unscoped"}) == 100.0
    # What moves data needs no scope: still true.
    assert sr.metric(got, {"share": "layout"}) == pytest.approx(100 / 3)


def test_a_text_that_cannot_be_had_fails_safe_too():
    def broken(lanes, rows):
        raise RuntimeError("no such program")

    got = sr.stages_from_xplane(_xplane([OP_A]), 8, 1, 8, text_of=broken)
    assert got["us_per_lane"] is None and got["unscoped_share"] == 100.0
    assert "RuntimeError: no such program" in got["failed"]


def test_runs_of_two_shapes_of_the_program_fail_safe_and_compile_nothing():
    """A window of 128- and 256-lane runs is two modules: no text is asked
    for (the parent asked for a 192-lane program there)."""
    data = _xplane([OP_A], second_module="jit__recover_fn(333)")

    def never(lanes, rows):
        raise AssertionError("a compile for a shape that did not run")

    got = sr.stages_from_xplane(data, 384, 2, 8, text_of=never)
    assert got["us_per_lane"] is None and got["unscoped_share"] == 100.0
    assert got["failed"] == "not one shape of the program: modules ['jit__recover_fn(111)', 'jit__recover_fn(333)']"
    assert got["busy_s"] == pytest.approx(4e-3) and got["module"] is None  # both runs are the program's


def test_a_trace_that_lost_a_run_the_ledger_counted_fails_safe():
    """Seen on the chip in PR 33 (before the lead-in): 7 runs in the trace
    where the ledger counted 8 dispatches; a per-lane figure over the ledger's
    lanes would read an eighth low."""
    got = sr.stages_from_xplane(_xplane([OP_A, OP_B, OP_C]), 16, 2, 8, text_of=lambda lanes, rows: COMPILED)
    assert got["failed"] == "the trace has 1 runs of the program, the ledger 2 dispatches"
    assert got["us_per_lane"] is None and got["unscoped_share"] == 100.0 and "compile_text_s" not in got


def test_a_run_without_a_device_trace_of_the_program_reads_nothing():
    cfg = {"validators": 100}
    assert sr.stages_of_run({"trace": None, "ledger_traced": None, "config": cfg}) is None
    rows = [{"program": "ecdsa_recover", "route": "device", "dispatches": 1, "live_lanes": 3, "padded_lanes": 8}]
    rehearsal = {"programs": {}, "busy_s": 0.0}  # a CPU rehearsal's reduced trace
    assert sr.stages_of_run({"trace": rehearsal, "ledger_traced": rows, "config": cfg}) is None
    host_only = {"programs": {"jit__recover_fn": {"seconds": 1.0, "runs": 1}}}
    assert sr.stages_of_run({"trace": host_only, "ledger_traced": [], "config": cfg}) is None


# ------------------------------------------- the marks, and what a trace loses


def _traced(groups, marks=()):
    """An xplane: per group ``(module event name, [(op, start ms, ms)],
    [(run start ms, ms)])`` on one chip, and the harness's phase ``marks``
    ``(name, start ms, ms)`` on a host thread."""
    from jax.profiler import ProfileData

    ms = 1_000_000_000
    names, ids = [], {}

    def mid(name):
        if name not in ids:
            names.append(name)
            ids[name] = len(names)
        return ids[name]

    def event(name, start, dur):
        return f"events {{ metadata_id: {mid(name)} offset_ps: {int(start * ms)} duration_ps: {int(dur * ms)} }}"

    runs, ops = [], []
    for module, group_ops, group_runs in groups:
        runs += [event(module, s, d) for s, d in group_runs]
        ops += [event(op, s, d) for op, s, d in group_ops]
    meta = "\n".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}' for i, n in enumerate(names)
    )
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000 {" ".join(runs)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000 {" ".join(ops)} }}
  {meta}
}}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 7 name: "python" timestamp_ns: 1000
    {" ".join(f"events {{ metadata_id: {i + 1} offset_ps: {int(s0 * ms)} duration_ps: {int(d0 * ms)} }}" for i, (_n, s0, d0) in enumerate(marks))} }}
  {" ".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "bench:{n}" }} }}' for i, (n, _s, _d) in enumerate(marks))}
}}
"""
    return ProfileData.from_text_proto(text)


RUN = "jit__recover_fn(111)"
OP_D = "%fusion.2 = u32[8,5]{1,0} fusion(%p)"
TEXT = "\n".join(
    [
        "ENTRY %main (p: s32[8,20]) -> u32[8,5] {",
        '  %while.7 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(_recover_fn)/recover.glv_ladder/while"}',
        '  %fusion.1 = s32[8,20]{1,0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(_recover_fn)/recover.lift_x/mul"}',
        '  %fusion.2 = u32[8,5]{1,0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(_recover_fn)/recover.address/xor"}',
        "}",
    ]
)


def _text(lanes, rows):
    return TEXT


def test_a_lead_in_run_before_the_marks_is_left_out_cut_ops_and_all():
    """PR 33's diagnosis: the device's op tracing goes live some milliseconds
    after ``start_trace`` returns, and a dispatch running by then has its run
    on ``XLA Modules`` but not its first ops, the cut ones under names no
    module has (``region.N``).  The probe gives the profiler a lead-in; the
    reduction keeps to the marked window."""
    groups = [
        (
            RUN,
            # the lead-in run 0..2 ms: its first op missing, a cut one named region.6238
            [("%region.6238 = u32[8,5]{1,0} fusion(%p)", 1, 1)]
            # two whole runs inside the marks
            + [(OP_A, 10, 1), (OP_D, 11, 1), (OP_A, 20, 1), (OP_D, 21, 1)],
            [(0, 2), (10, 2), (20, 2)],
        )
    ]
    marks = [("between_heights", 9, 1), ("prepare_flood", 10, 10), ("commit_flood", 20, 3)]
    got = sr.stages_from_xplane(_traced(groups, marks), 256, 2, 128, text_of=_text)
    assert "failed" not in got and got["names_found"] == 1.0 and got["module"] == RUN
    assert got["runs_outside_the_marks"] == 1 and got["runs"] == 2 == got["dispatches"]
    assert got["busy_s"] == pytest.approx(4e-3) and got["runs_whole"] == 2 and "runs_cut" not in got
    assert got["us_per_lane"][LIFT] == pytest.approx(2e3 / 256)
    # Without the marks (the harness before PR 33 opened its window with the
    # profiler) the cut run is in: a millisecond of its ops is missing, which
    # fails the whole reading, and its one named event is of no module.
    cut = sr.stages_from_xplane(_traced(groups), 384, 3, 128, text_of=_text)
    assert cut["failed"] == "the compiled text is of another module than the one that ran"
    assert cut["names_found"] == pytest.approx(4 / 5) and cut["not_found"] == ["region.6238"]
    assert cut["runs_cut"] == [pytest.approx(0.5)] and cut["runs_whole"] == 2
    assert cut["us_per_lane"] is None and cut["unscoped_share"] == 100.0


def test_a_run_that_lost_ops_is_left_out_and_the_whole_runs_are_the_reading():
    """Seen on the chip in PR 33, WITH the lead-in: one traced flood in seven
    had 9,400 of its 1.76 million op events missing, 1.2 ms of one run's 8.3
    (every name known, all ten runs on ``XLA Modules``).  Every run is the same
    program on the same shape: the whole ones read what all would."""
    marks = [("prepare_flood", 9, 20)]
    lost = [(RUN, [(OP_A, 10.1, 0.9), (OP_D, 11, 1), (OP_A, 20, 1), (OP_D, 21, 1)], [(10, 2), (20, 2)])]
    got = sr.stages_from_xplane(_traced(lost, marks), 256, 2, 128, text_of=_text)
    assert "failed" not in got and got["names_found"] == 1.0
    assert got["runs"] == 2 and got["runs_whole"] == 1 and got["runs_cut"] == [pytest.approx(0.95)]
    per_lane = got["us_per_lane"]  # the whole run's 2 ms over ITS 128 lanes
    assert per_lane[LIFT] == pytest.approx(1e3 / 128) and per_lane[ADDRESS] == pytest.approx(1e3 / 128)
    assert got["busy_s"] == pytest.approx(2e-3) and got["unscoped_share"] == 0.0
    # Fewer than half of the runs whole: fails safe, never a guess.
    worse = [(RUN, [(OP_A, 10.1, 0.9), (OP_D, 11, 1), (OP_A, 20, 1), (OP_D, 21.5, 0.5), (OP_A, 30, 1), (OP_D, 31, 1)], [(10, 2), (20, 2), (30, 2)])]
    got = sr.stages_from_xplane(_traced(worse, [("prepare_flood", 9, 30)]), 384, 3, 128, text_of=_text)
    assert got["failed"] == "the trace lost ops in 2 of the program's 3 runs"
    assert got["us_per_lane"] is None and got["unscoped_share"] == 100.0 and got["runs_whole"] == 1


def test_a_few_events_under_names_of_no_module_are_charged_to_what_encloses_them():
    """Seen on the chip in PR 33: in one traced flood in four, a few hundred
    of 1.76 million op events carry names the module has not (``region.7170``,
    ``region.7171``, ..., another one each time: 2.5% of the DISTINCT names,
    which the reading before PR 33 counted, and 0.02% of the events).  They
    are charged like any op without a stage of its own; the share of EVENTS
    found says whether the text is of the module that ran."""
    ops = [("%while.7 = (s32[]) while(%t), body=%b", 0, 200)]
    ops += [(OP_A if i % 2 else OP_D, i, 1) for i in range(198)]  # 99 of each, inside the while
    ops += [("%region.7170 = u32[8,5]{1,0} fusion(%p)", 198, 1)]  # inside the while: the ladder's
    ops += [("%region.7171 = u32[8,5]{1,0} fusion(%p)", 201, 1)]  # after it: nothing encloses it
    got = sr.stages_from_xplane(_traced([(RUN, ops, [(0, 202)])]), 128, 1, 128, text_of=_text)
    assert "failed" not in got and got["names_found"] == pytest.approx(1 - 2 / 201)
    assert got["names_not_found"] == 2 and got["not_found"] == ["region.7170", "region.7171"]
    assert got["runs_whole"] == 1  # its ops cover 201 of its 202 ms
    per_lane = got["us_per_lane"]
    assert per_lane[LADDER] * 128 / 1e3 == pytest.approx(2.0)  # the while's own 199..200, and region.7170
    assert per_lane[sr.UNSCOPED] * 128 / 1e3 == pytest.approx(1.0)  # region.7171
    assert per_lane[LIFT] * 128 / 1e3 == pytest.approx(99.0)
    # Many events under unknown names are another module: fails safe.
    ops += [(f"%region.{8000 + i} = u32[8,5]{{1,0}} fusion(%p)", 201.0 + i / 100, 0.01) for i in range(1, 9)]
    got = sr.stages_from_xplane(_traced([(RUN, ops, [(0, 202)])]), 128, 1, 128, text_of=_text)
    assert got["names_found"] == pytest.approx(1 - 10 / 209) and "another module" in got["failed"]


def test_the_stages_divide_by_the_lanes_recover_us_per_lane_divides_by():
    """One lane count for the whole and its parts: the ledger's, of the marked
    window; where the trace does not have the ledger's dispatches the parts
    are left out (above), so they never sum to another whole."""
    ops = [(OP_A, 0, 1), (OP_D, 1, 1), (OP_A, 10, 1), (OP_D, 11, 1)]
    got = sr.stages_from_xplane(_traced([(RUN, ops, [(0, 2), (10, 2)])]), 512, 2, 128, text_of=_text)
    assert got["lanes"] == 512 and got["runs"] == 2
    assert sum(got["us_per_lane"].values()) == pytest.approx(4e3 / 512)  # the module events' 4 ms over 512
