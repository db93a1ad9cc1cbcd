"""``harness/step_phases.py``: the device's busy time by the part of the
compiled step, on a module and a trace written by hand (answers computed
by hand), on a cut recorded on the chip, on a program that hands out no
text, and through a traced toy cell."""
import json
import os
import types

import chipbench_toy as toy
import pytest

from chipbench.harness import step_phases as sp

READERS = ("step_forward_ms", "step_backward_ms", "step_update_ms",
           "step_collective_ms", "step_other_ms", "step_phase_mixed_share",
           "step_phase_unmatched_share")

# what ``reduce`` reads off ``step_phases_fixture.json``, ms a step
FIXTURE = {"phases_ms": {"forward": 0.0006305, "backward": 0.4872365,
                         "update": 0.5588095, "collective": 0.0,
                         "other": 0.11102},
           "mixed_ms": 0.516414, "other_copy_ms": 0.063145}

# what ``compiled.as_text()`` looks like on the TPU, cut to a few lines:
# fused computations (one nested in another), a reduction's region, a
# ``while`` with body and condition, ENTRY; a multi-output fusion, an
# instruction without metadata, names with and without ``%``
HLO = """\
HloModule jit_step_fn, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation.1 (p0: bf16[8,4], p1: bf16[4,4]) -> bf16[8,4] {
  %p0 = bf16[8,4]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[4,4]{1,0} parameter(1)
  ROOT %dot.1 = bf16[8,4]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(step_fn)/jvp(mla.project)/dot_general" stack_frame_id=3}
}

%region_1.2 (a: pred[], b: pred[]) -> pred[] {
  %a = pred[] parameter(0)
  %b = pred[] parameter(1)
  ROOT %and.9 = pred[] and(%a, %b), metadata={op_name="jit(step_fn)/trainer.guard/reduce_and"}
}

%fused_computation.2 (p0: bf16[8,4], p1: bf16[8,4]) -> (pred[], bf16[4,4]) {
  %p0 = bf16[8,4]{1,0} parameter(0)
  %p1 = bf16[8,4]{1,0} parameter(1)
  %relu.clone = bf16[8,4]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.2.clone, metadata={op_name="jit(step_fn)/jvp()/jit(relu)/max"}
  %dw.1 = bf16[4,4]{1,0} convolution(%relu.clone, %p1), dim_labels=fb_io->bf, metadata={op_name="jit(step_fn)/transpose(jvp(mla.project))/dot_general"}
  %is_finite.1 = pred[4,4]{1,0} is-finite(%dw.1), metadata={op_name="jit(step_fn)/trainer.guard/is_finite"}
  %constant.7 = pred[] constant(true), metadata={op_name="jit(step_fn)/jvp(jit(_var))"}
  %reduce_and.1 = pred[] reduce(%is_finite.1, %constant.7), dimensions={0,1}, to_apply=%region_1.2, metadata={op_name="jit(step_fn)/trainer.guard/reduce_and"}
  ROOT %tuple.1 = (pred[], bf16[4,4]{1,0}) tuple(%reduce_and.1, %dw.1)
}

%fused_computation.2.clone (p0: bf16[8,4]) -> bf16[8,4] {
  %p0 = bf16[8,4]{1,0} parameter(0)
  ROOT %max.1 = bf16[8,4]{1,0} maximum(%p0, %p0), metadata={op_name="jit(step_fn)/jvp()/jit(relu)/max"}
}

fused_computation.3 (p0: f32[4,4], p1: bf16[4,4], p2: pred[]) -> (bf16[4,4], f32[4,4]) {
  p0 = f32[4,4]{1,0} parameter(0)
  p1 = bf16[4,4]{1,0} parameter(1)
  p2 = pred[] parameter(2)
  convert.5 = f32[4,4]{1,0} convert(p1), metadata={op_name="jit(step_fn)/transpose(jvp())/convert_element_type"}
  sub.1 = f32[4,4]{1,0} subtract(p0, convert.5), metadata={op_name="jit(step_fn)/trainer.update/sub"}
  select.1 = f32[4,4]{1,0} select(p2, sub.1, p0), metadata={op_name="jit(step_fn)/trainer.update/jit(_where)/select_n"}
  cast.1 = bf16[4,4]{1,0} convert(select.1), metadata={op_name="jit(step_fn)/trainer.update/convert_element_type"}
  ROOT tuple.2 = (bf16[4,4]{1,0}, f32[4,4]{1,0}) tuple(cast.1, select.1)
}

%fused_computation.4 (p0: bf16[8,4]) -> bf16[8,4] {
  %p0 = bf16[8,4]{1,0} parameter(0)
  %neg.1 = bf16[8,4]{1,0} negate(%p0), metadata={op_name="jit(step_fn)/transpose(jvp(gmu))/neg"}
  %exp.1 = bf16[8,4]{1,0} exponential(%neg.1), metadata={op_name="jit(step_fn)/transpose(jvp(gmu))/exp"}
  ROOT %mul.1 = bf16[8,4]{1,0} multiply(%exp.1, %p0), metadata={op_name="jit(step_fn)/jvp(gmu)/mul"}
}

%body.1 (c: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {
  %c = (s32[], bf16[8,4]{1,0}) parameter(0)
  %gte.1 = bf16[8,4]{1,0} get-tuple-element(%c), index=1
  %fusion.7 = bf16[8,4]{1,0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.4
  %gte.0 = s32[] get-tuple-element(%c), index=0
  ROOT %tuple.3 = (s32[], bf16[8,4]{1,0}) tuple(%gte.0, %fusion.7)
}

%cond.1 (c: (s32[], bf16[8,4])) -> pred[] {
  %c = (s32[], bf16[8,4]{1,0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%c), index=0
  %constant.3 = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(%gte.2, %constant.3), direction=LT, metadata={op_name="jit(step_fn)/transpose(jvp(while))/cond/lt"}
}

ENTRY %main.9 (praws_0_.1: bf16[4,4], opt_raws_0__0_.1: f32[4,4], x.1: bf16[8,4]) -> (bf16[4,4], f32[4,4], pred[]) {
  %praws_0_.1 = bf16[4,4]{1,0} parameter(0)
  %opt_raws_0__0_.1 = f32[4,4]{1,0} parameter(1)
  %x.1 = bf16[8,4]{1,0} parameter(2)
  %copy.1 = f32[4,4]{0,1} copy(%opt_raws_0__0_.1), metadata={op_name="opt_raws[0][0]"}
  %copy-start.1 = (bf16[8,4]{1,0:S(1)}, bf16[8,4]{1,0}, u32[]) copy-start(%x.1)
  %copy-done.1 = bf16[8,4]{1,0:S(1)} copy-done(%copy-start.1)
  %fusion = bf16[8,4]{1,0} fusion(%copy-done.1, %praws_0_.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/jvp(mla.project)/dot_general" stack_frame_id=3}
  %tuple.9 = (s32[], bf16[8,4]{1,0}) tuple(%fusion)
  %while.1 = (s32[], bf16[8,4]{1,0}) while(%tuple.9), condition=%cond.1, body=%body.1, metadata={op_name="jit(step_fn)/transpose(jvp(while))"}
  %gte.5 = bf16[8,4]{1,0} get-tuple-element(%while.1), index=1
  %jvp_mla.attention_.6 = bf16[8,4]{1,0} custom-call(%gte.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(mla.attention)/pallas_call"}
  %is-finite_reduce_fusion.1 = (pred[], bf16[4,4]{1,0}) fusion(%jvp_mla.attention_.6, %fusion), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(step_fn)/transpose(jvp(mla.project))/dot_general"}
  %gte.6 = bf16[4,4]{1,0} get-tuple-element(%is-finite_reduce_fusion.1), index=1
  %all-reduce.1 = bf16[4,4]{1,0} all-reduce(%gte.6), replica_groups={}, to_apply=%region_1.2, metadata={op_name="jit(step_fn)/transpose(jvp(mla.project))/dot_general"}
  %gte.7 = pred[] get-tuple-element(%is-finite_reduce_fusion.1), index=0
  %ragged-dot-none.1 = bf16[4,4]{1,0} custom-call(%gte.6, /*index=1*/%praws_0_.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %subtract_convert_fusion = (bf16[4,4]{1,0}, f32[4,4]{1,0}) fusion(%copy.1, %all-reduce.1, %gte.7), kind=kLoop, calls=fused_computation.3, metadata={op_name="jit(step_fn)/trainer.update/jit(_where)/select_n"}
  %gte.8 = bf16[4,4]{1,0} get-tuple-element(%subtract_convert_fusion), index=0
  %gte.9 = f32[4,4]{1,0} get-tuple-element(%subtract_convert_fusion), index=1
  ROOT %tuple.10 = (bf16[4,4]{1,0}, f32[4,4]{1,0}, pred[]) tuple(%gte.8, %gte.9, %gte.7)
}
"""

OTHER_HLO = """\
HloModule jit__threefry_split, is_scheduled=true

ENTRY %main.2 (k.1: u32[2]) -> u32[2,2] {
  %k.1 = u32[2]{0} parameter(0)
  ROOT %copy.1 = u32[2,2]{1,0} fusion(%k.1), kind=kLoop, calls=%nothing, metadata={op_name="jit(_threefry_split)/threefry2x32"}
}
"""


def test_parse_reads_every_computation_that_is_not_fused():
    rows = sp.parse(HLO)
    # ENTRY, the while's body and condition, the reduction's region; not
    # the fused computations' own instructions
    assert {"fusion", "while.1", "fusion.7", "lt.1", "and.9", "copy.1",
            "subtract_convert_fusion", "tuple.10"} <= set(rows)
    assert not {"dot.1", "dw.1", "select.1", "max.1", "neg.1"} & set(rows)
    assert rows["fusion"] == {
        "op_name": "jit(step_fn)/jvp(mla.project)/dot_general",
        "opcode": "fusion:kOutput", "shape": "bf16[8,4]",
        "inside": ["forward"]}
    # a multi-output fusion: the FIRST result's shape, as a trace's short
    # name has it; inside, the nested fusion's own label, the product, the
    # guard's two; not the constant (it keeps whatever name made it first)
    assert rows["is-finite_reduce_fusion.1"]["shape"] == "pred[]"
    assert rows["is-finite_reduce_fusion.1"]["inside"] == [
        "forward", "backward", "guard", "guard"]
    # a computation printed without % is read the same
    assert rows["subtract_convert_fusion"]["opcode"] == "fusion:kLoop"
    assert rows["subtract_convert_fusion"]["shape"] == "bf16[4,4]"
    assert rows["subtract_convert_fusion"]["inside"] == [
        "backward", "update", "update", "update"]
    assert rows["copy-done.1"] == {"op_name": "", "opcode": "copy-done",
                                   "shape": "bf16[8,4]", "inside": []}
    assert rows["jvp_mla.attention_.6"]["opcode"] == \
        "custom-call:tpu_custom_call"
    assert rows["fusion.7"]["op_name"] == ""
    assert rows["fusion.7"]["inside"] == ["backward", "backward", "forward"]
    assert json.loads(json.dumps(rows)) == rows


@pytest.mark.parametrize("op_name,opcode,phase", [
    ("jit(step_fn)/transpose(jvp(mla.project))/dot_general", "all-reduce",
     "collective"),
    ("jit(step_fn)/trainer.update/sub", "reduce-scatter-start", "collective"),
    ("jit(step_fn)/trainer.update/jit(_where)/select_n", "fusion", "update"),
    ("jit(step_fn)/trainer.update/transpose(jvp(odd))/mul", "fusion",
     "update"),
    ("jit(step_fn)/transpose(jvp(mla.project))/dot_general", "fusion",
     "backward"),
    ("jit(step_fn)/transpose(jvp(checkpoint))/rematted_computation/jvp()/mul",
     "fusion", "backward"),
    ("jit(step_fn)/jvp(jit(relu))/max", "fusion", "forward"),
    ("jit(step_fn)/trainer.guard/reduce_and", "fusion", "other"),
    ("jit(step_fn)/jit(_threefry_split)/slice", "fusion", "other"),
    ("opt_raws[3][0]", "copy", "other"),
    ("", "copy-done", "other"),
], ids=["collective_by_opcode", "collective_before_update", "update",
        "update_before_transpose", "backward", "remat_is_backward",
        "forward", "guard_alone", "rng", "argument_copy", "no_metadata"])
def test_phase_of_row_by_row(op_name, opcode, phase):
    assert sp.phase_of(op_name, opcode) == phase


def test_a_fusion_takes_its_own_phase_or_its_majoritys():
    rows = sp.parse(HLO)
    got = {k: sp.phase_of_row(rows[k]) for k in (
        "fusion", "is-finite_reduce_fusion.1", "subtract_convert_fusion",
        "fusion.7", "all-reduce.1", "while.1", "copy.1", "copy-done.1",
        "jvp_mla.attention_.6")}
    assert got == {
        "fusion": "forward", "is-finite_reduce_fusion.1": "backward",
        "subtract_convert_fusion": "update",
        "fusion.7": "backward",            # no metadata: 2 backward, 1 forward
        "all-reduce.1": "collective", "while.1": "backward",
        "copy.1": "other", "copy-done.1": "other",
        "jvp_mla.attention_.6": "forward"}
    # no name of jax's and nothing inside: after the operands, the latest
    # phase among them with its scope, through other such instructions
    # (the kernel XLA makes of a ragged_dot <- a get-tuple-element <- the
    # dW fusion); behind a copy-done stand only arguments: other
    assert rows["gte.6"]["after"] == ["backward", "mla.project"]
    assert rows["ragged-dot-none.1"]["after"] == ["backward", "mla.project"]
    assert sp.phase_of_row(rows["ragged-dot-none.1"]) == "backward"
    assert sp.scope_of_row(rows["ragged-dot-none.1"]) == "mla.project"
    assert "after" not in rows["copy-done.1"] \
        and "after" not in rows["copy.1"] and "after" not in rows["fusion"]
    assert not [r for r in rows.values() if "operands" in r]
    assert [k for k in rows if sp.is_mixed(rows[k])] == [
        "fusion.7", "is-finite_reduce_fusion.1", "subtract_convert_fusion"]
    assert sp.scope_of(rows["fusion"]["op_name"]) == "mla.project"
    assert sp.scope_of(rows["jvp_mla.attention_.6"]["op_name"]) == \
        "mla.attention"
    assert sp.scope_of(rows["subtract_convert_fusion"]["op_name"]) == \
        "trainer.update"
    assert sp.scope_of("jit(step_fn)/transpose(jvp(gmu))/neg") == "gmu"
    assert sp.scope_of(
        "jit(step_fn)/jvp(attn.full)/jvp(lm.head_loss)/mul") == "lm.head_loss"
    assert sp.scope_of("opt_raws[3][0]") == sp.NO_SCOPE
    assert sp.scope_of("jit(step_fn)/jvp(jit(log_softmax))/exp") == \
        sp.NO_SCOPE


@pytest.fixture
def hand():
    """Two steps on two chips in a window [0, 1000) us. A step runs a
    small program (held by no text), then ``jit_step_fn``. Device 1 runs
    the same instructions 2 us later. ``copy.1`` is in BOTH programs; the
    small one's is a fusion, the step's a copy."""
    def step(t0):
        small = [["jit__threefry_split(11)", t0, 10]]
        main = [["jit_step_fn(22)", t0 + 20, 300]]
        ops = [
            ["copy.1 fusion:kLoop u32[2,2]", t0 + 1, 8],
            ["copy.1 copy f32[4,4]", t0 + 20, 10],
            ["copy-done.1 copy-done bf16[8,4]", t0 + 30, 5],
            ["fusion fusion:kOutput bf16[8,4]", t0 + 40, 50],
            # a while holds its body's instructions: 100 less 2 x 30 is its own
            ["while.1 while s32[]", t0 + 100, 100],
            ["fusion.7 fusion:kLoop bf16[8,4]", t0 + 110, 30],
            ["fusion.7 fusion:kLoop bf16[8,4]", t0 + 150, 30],
            ["jvp_mla.attention_.6 custom-call:tpu_custom_call bf16[8,4]",
             t0 + 200, 20],
            ["is-finite_reduce_fusion.1 fusion:kOutput pred[]", t0 + 220, 40],
            ["all-reduce.1 all-reduce bf16[4,4]", t0 + 260, 15],
            ["subtract_convert_fusion fusion:kLoop bf16[4,4]", t0 + 280, 25],
            # not in the text / another shape than the text's
            ["fusion.99 fusion:kLoop bf16[8,4]", t0 + 305, 4],
            ["fusion fusion:kOutput bf16[16,4]", t0 + 310, 6]]
        return small, main, ops

    def scaled(events, shift=0):
        return [[n, (s + shift) * 1000, d * 1000] for n, s, d in events]

    mods = {"0": [], "1": []}
    ops = {"0": [], "1": []}
    for t0 in (100, 500):
        small, main, step_ops = step(t0)
        for dev, shift in (("0", 0), ("1", 2)):
            mods[dev] += scaled(small + main, shift)
            ops[dev] += scaled(step_ops, shift)
    return {"window": [0, 1_000_000], "steps": 2, "ops": ops,
            "modules": mods,
            "maps": {"jit_step_fn": sp.parse(HLO)}}


def test_hand_written_trace_against_hand_computed_values(hand):
    got = sp.reduce(hand)
    # us a step, the same on both chips
    want = {"forward": 50 + 20, "backward": 40 + 40 + 2 * 30,
            "update": 25, "collective": 15,
            "other": 8 + 10 + 5 + 4 + 6}
    assert got["phases_ms"] == pytest.approx(
        {p: v / 1e3 for p, v in want.items()})
    assert got["steps"] == 2
    # a partition of busy time, to the nanosecond
    busy_ns = sum(d for events in hand["ops"].values()
                  for n, _, d in events if not n.startswith("while")) \
        + 4 * 40_000
    per_step = busy_ns / 2 / 2 / 1e6
    assert got["busy_ms"] * 4 * 1e6 == pytest.approx(busy_ns, abs=0.5)
    assert sum(got["phases_ms"].values()) == pytest.approx(per_step)
    assert got["mixed_ms"] == pytest.approx((60 + 40 + 25) / 1e3)
    assert got["unmatched_ms"] == pytest.approx((4 + 6) / 1e3)
    by = got["by_scope_ms"]
    assert by["mla.project"] == pytest.approx(
        {"forward": 0.05, "backward": 0.04, "collective": 0.015})
    assert by["mla.attention"] == pytest.approx({"forward": 0.02})
    assert by["trainer.update"] == pytest.approx({"update": 0.025})
    assert by[sp.NO_SCOPE] == pytest.approx(
        {"backward": 0.1, "other": 0.033})
    assert list(by) == ["(none)", "mla.project", "trainer.update",
                        "mla.attention"]      # largest first
    # what went to other, by opcode: the small program's fusion, the
    # unmatched fusions, the copies without a phase
    assert got["other_by_opcode_ms"] == pytest.approx(
        {"fusion": 0.018, "copy": 0.010, "copy-done": 0.005})
    assert got["programs_ms"] == {
        "jit_step_fn": {"ms": pytest.approx(0.275), "held": True},
        "jit__threefry_split": {"ms": pytest.approx(0.008), "held": False}}
    assert json.loads(json.dumps(got)) == got


def test_the_same_instruction_name_in_two_modules(hand):
    """``copy.1`` of the small program is not ``copy.1`` of the step: with
    both texts held each event is read in ITS module's map."""
    rows = sp.parse(OTHER_HLO)
    assert rows["copy.1"]["opcode"] == "fusion:kLoop"
    hand["maps"]["jit__threefry_split"] = rows
    got = sp.reduce(hand)
    assert got["unmatched_ms"] == pytest.approx(0.010)   # as before
    assert got["programs_ms"]["jit__threefry_split"]["held"] is True
    # looked up in the step's map the small program's copy.1 would differ
    # in shape and land in unmatched
    for events in hand["modules"].values():
        events[:] = [[n.replace("jit__threefry_split", "jit_step_fn"), s, d]
                     for n, s, d in events]
    assert sp.reduce(hand)["unmatched_ms"] == pytest.approx(0.018)


def test_a_shape_that_differs_lands_in_unmatched(hand):
    hand["maps"]["jit_step_fn"]["fusion"]["shape"] = "bf16[16,4]"
    got = sp.reduce(hand)
    # the 50 us product no longer matches, the 6 us one now does
    assert got["unmatched_ms"] == pytest.approx((4 + 50) / 1e3)
    assert got["phases_ms"]["forward"] == pytest.approx((6 + 20) / 1e3)
    assert sum(got["phases_ms"].values()) == pytest.approx(
        sum(sp.reduce(dict(hand, maps={}))["phases_ms"].values()))


def test_nothing_to_join_gives_none(hand):
    # a CPU trace has no device plane; a window without a traced step
    assert sp.reduce(dict(hand, ops={})) is None
    assert sp.reduce(dict(hand, steps=0)) is None
    # no text at all: everything is other, nothing unmatched
    bare = sp.reduce(dict(hand, maps={}))
    assert bare["unmatched_ms"] == 0 and bare["mixed_ms"] == 0
    assert bare["phases_ms"]["other"] == pytest.approx(bare["busy_ms"])


def test_cut_keeps_what_the_piece_needs(hand):
    piece = sp.cut(hand, 90_000, 450_000, 1, devices=["0"])
    assert piece["window"] == [0, 360_000] and piece["steps"] == 1
    assert list(piece["ops"]) == ["0"] and len(piece["ops"]["0"]) == 13
    assert piece["modules"]["0"] == [
        ["jit__threefry_split(11)", 10_000, 10_000],
        ["jit_step_fn(22)", 30_000, 300_000]]
    assert set(piece["maps"]["jit_step_fn"]) == {
        "copy.1", "copy-done.1", "fusion", "while.1", "fusion.7",
        "jvp_mla.attention_.6", "is-finite_reduce_fusion.1", "all-reduce.1",
        "subtract_convert_fusion"}
    # one chip's one step reads what two chips' two steps read a step
    assert sp.reduce(piece)["phases_ms"] == pytest.approx(
        sp.reduce(hand)["phases_ms"])
    assert json.loads(json.dumps(piece)) == piece


def test_recorded_cut_from_the_chip():
    """The end of one ``resnet50_train_1chip`` step, the two small
    programs behind it and the start of the next, as the v5e recorded
    them (the fixture's ``source``): every instruction of the cut is in
    the step's text with its shape, the partition sums to the busy time
    counted without any text, and the update sits where the guard's flag
    lets it, behind the last gradient."""
    with open(os.path.join(toy.BENCH, "harness",
                           "step_phases_fixture.json")) as f:
        data = json.load(f)
    assert os.path.getsize(f.name) < 250_000
    got = sp.reduce(data)
    assert got["steps"] == 2 and list(data["ops"]) == ["0"]
    assert got["unmatched_ms"] == 0
    assert got["phases_ms"] == pytest.approx(FIXTURE["phases_ms"])
    assert got["mixed_ms"] == pytest.approx(FIXTURE["mixed_ms"])
    assert got["other_by_opcode_ms"]["copy"] == pytest.approx(
        FIXTURE["other_copy_ms"])
    assert got["by_scope_ms"]["trainer.update"] == pytest.approx(
        {"update": FIXTURE["phases_ms"]["update"]})
    # busy time without any text: the union of the op intervals
    from chipbench.harness import trace_reduce

    busy = trace_reduce.busy_seconds(
        {"devices": data["ops"], "host": {}}, tuple(data["window"]))
    assert sum(got["phases_ms"].values()) * 2 / 1e3 == pytest.approx(busy)
    # the step's program is held, the small programs are not: all other
    assert [(n, p["held"]) for n, p in got["programs_ms"].items()] == [
        ("jit_step_fn", True), ("jit__threefry_split", False),
        ("jit__unstack", False)]
    # every update fusion of the cut starts after the last instruction
    # the backward named: the select waits for the flag over all gradients
    rows = data["maps"]["jit_step_fn"]
    first = [e for e in data["ops"]["0"] if e[1] < data["modules"]["0"][1][1]]
    named = [(sp.phase_of(rows[e[0].split(" ")[0]]["op_name"]), e[1])
             for e in first]      # by their own names, nothing taken after
    assert max(t for p, t in named if p == "backward") \
        < min(t for p, t in named if p == "update")
    # without the text the same events are all other, none unmatched
    bare = sp.reduce(dict(data, maps={}))
    assert bare["phases_ms"]["other"] == pytest.approx(got["busy_ms"])


@pytest.mark.parametrize("name", READERS)
def test_readers_apply_by_mode_and_chips_alone(name):
    from chipbench.harness import bench as hbench

    reader = hbench.load_module(os.path.join(
        toy.BENCH, "layer_metrics", f"{name}.py"))
    across = name == "step_collective_ms"
    assert reader.applies({"mode": "train", "chips": 4}) is True
    assert reader.applies({"mode": "train", "chips": 1}) is (not across)
    assert reader.applies({"mode": "serve", "chips": 4}) is False
    assert reader.MOVES == "train_samples_per_s"
    assert reader.UNIT == ("%" if "share" in name else "ms")


def _readers():
    from chipbench.harness import bench as hbench

    return [hbench.load_module(os.path.join(
        toy.BENCH, "layer_metrics", f"{name}.py")) for name in READERS]


def test_readers_read_the_reduction_once(hand, monkeypatch, capsys):
    """The first reader that asks computes and prints; the others share."""
    calls = []
    run = {"mode": "train", "chips": 4, "step_phases": None}
    assert all(r.compute(run) is None for r in _readers())
    reduced = sp.reduce(hand)
    monkeypatch.setattr(sp, "reduce", lambda data: calls.append(1))
    run["step_phases"] = reduced
    got = [r.compute(run) for r in _readers()]
    assert got[:5] == pytest.approx([0.07, 0.14, 0.025, 0.015, 0.033])
    assert got[5:] == pytest.approx([100 * 0.125 / 0.283,
                                     100 * 0.010 / 0.283])
    assert not calls and capsys.readouterr().out == ""


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_parent_without_program_texts_reads_as_nothing(
        tmp_path, monkeypatch):
    """What the parent commit gives: ``trainer.step`` in the trace and no
    ``compile.program_texts``. The readers return None and raise nothing;
    so does a run that took no trace at all."""
    import jax

    import mxnet_tpu.compile as mxcompile
    from chipbench.harness import trace_reduce

    log_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("trainer.step"):
            jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    trace = trace_reduce.load(trace_reduce.find_xplane(log_dir))
    # as if the chip had traced one op of the step's program
    trace["devices"] = {"0": [["fusion fusion:kOutput bf16[8,4]",
                               trace_reduce.window(trace)[0] + 10, 50]]}
    monkeypatch.delattr(mxcompile, "program_texts")
    run = {"mode": "train", "chips": 1, "trace": trace,
           "bench": types.SimpleNamespace(trace=True, _trace_dir=log_dir)}
    assert all(r.applies(run) and r.compute(run) is None
               for r in _readers() if r.applies(run))
    assert run["step_phases"] is None
    assert sp.of_run({"mode": "train", "bench": types.SimpleNamespace(
        trace=False)}) is None


def test_a_text_with_another_trees_names_reads_as_nothing(
        hand, monkeypatch):
    """jax's cache key leaves names out: a step loaded from a cache the
    parent filled carries the parent's ``op_name``s, with no
    ``trainer.update`` in them. Absent, not an update of 0.000 ms."""
    import mxnet_tpu.compile as mxcompile

    text = "HloModule jit_step_fn\n\nENTRY %main () -> f32[] {\n}\n"
    monkeypatch.setattr(mxcompile, "program_texts", lambda site: [
        {"token": "t", "module": "jit_step_fn", "text": text}])
    assert sp._maps() is None
    monkeypatch.setattr(mxcompile, "program_texts", lambda site: [
        {"token": "t", "module": "jit_step_fn", "text": text.replace(
            "{\n", '{\n  %a = f32[] add(), metadata={op_name="jit(step_fn)/'
            f'{sp.UPDATE}/add"}}\n')}])
    assert sp.phase_of_row(sp._maps()["jit_step_fn"]["a"]) == "update"


@pytest.fixture
def lifted(monkeypatch):
    restore = toy.lift_refusal(monkeypatch)
    yield
    restore()


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_traced_toy_cell_on_the_cpu_leaves_the_seven_out(
        tmp_path, lifted, capsys):
    """A CPU trace has no device plane: no ``# step_phases`` line, none of
    the seven on the result line, and nothing raised."""
    bench_dir = toy.toy_copy(str(tmp_path / "chipbench"))
    rc, last, lines = toy.run_cell(bench_dir, "resnet50_train_1chip", 1,
                                   capsys)
    assert rc == 0 and last["correct"] is True
    assert not [ln for ln in lines if ln.startswith("# step_phases")]
    assert not [m for m in last["metrics"] if m.startswith("step_")]
    assert "trainer_sync_ms" in last["metrics"]
