"""``chipbench/harness/trace_reduce.py`` against values worked out by hand:
first on a trace small enough to check by eye (collectives hidden and
exposed among them), then on ``trace_fixture.json``, a cut of a trace
recorded on the chip (PR 22, ``resnet50_train_1chip``: the end of one
training step, the idle stretch while the host finishes ``trainer.step``,
reads the loss and dispatches again, and the start of the next step),
where the totals are also recomputed by brute force on a nanosecond grid.
A one-chip step holds no collective; no four-chip machine could be had
for a cut of the dp4 trace (PERF.md, section 7)."""
import json
import os

import numpy as np
import pytest

from chipbench.harness import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(tr.__file__)),
                       "trace_fixture.json")


# ------------------------------------------------------------ by eye -----
# one device, times in ns:
#   fusion.1      [ 0, 40)
#   while.2       [50,150)  holding  fusion.3 [60,100) and all-reduce.4 [100,140)
#   all-reduce.5  [150,170) alone
#   (idle 170..200)
#   fusion.6      [200,230)
# and beside the instruction stream an async pair
#   all-gather-start.7 [20,25) ... all-gather-done.7 [225,240)

def _tiny():
    dev = [["fusion.1 fusion:kLoop f32[8]", 0, 40],
           ["while.2 while (f32[8])", 50, 100],
           ["fusion.3 fusion:kOutput f32[8]", 60, 40],
           ["all-reduce.4 all-reduce f32[8]", 100, 40],
           ["all-reduce.5 all-reduce f32[8]", 150, 20],
           ["fusion.6 fusion:kLoop f32[8]", 200, 30]]
    asyn = [["all-gather-start.7 all-gather-start f32[8]", 20, 5],
            ["all-gather-done.7 all-gather-done f32[8]", 225, 15]]
    host = {"python3": [[tr.WINDOW_SPAN, 0, 240],
                        ["train.step_call", 0, 160],
                        ["train.loss_read", 160, 35],
                        ["train.step_call", 196, 44]]}
    return {"devices": {"0": dev}, "async": {"0": asyn}, "host": host}


def test_intervals_by_eye():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 12), (20, 20)]) == \
        [(0, 4), (5, 12)]
    assert tr.total([(0, 4), (5, 12)]) == 11
    assert tr.clip([(0, 4), (5, 12)], 3, 6) == [(3, 4), (5, 6)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.overlap([(0, 4), (5, 12)], 3, 6) == 2


def test_busy_idle_and_ops_by_eye():
    t = _tiny()
    assert tr.window(t) == (0, 240)
    assert tr.busy(t) == {"0": [(0, 40), (50, 170), (200, 230)]}
    assert tr.busy_seconds(t) == pytest.approx(190e-9)
    assert tr.idle_share(t) == pytest.approx(1 - 190 / 240)
    # the while keeps its own 20 ns (100 less the 40 + 40 nested in it)
    assert dict(tr.self_times(t["devices"]["0"])) == {
        "fusion.1 fusion:kLoop f32[8]": 40, "while.2 while (f32[8])": 20,
        "fusion.3 fusion:kOutput f32[8]": 40,
        "all-reduce.4 all-reduce f32[8]": 40,
        "all-reduce.5 all-reduce f32[8]": 20,
        "fusion.6 fusion:kLoop f32[8]": 30}
    table = tr.op_table(t, top=3)
    assert [n.split(" ")[0] for n, _ in table] == \
        ["all-reduce.4", "fusion.1", "fusion.3"]     # ties: name order
    assert table[0][1] == pytest.approx(40e-9)
    assert tr.time_share(t, lambda n: tr.opcode(n) == "all-reduce") == \
        pytest.approx(60 / 190)


def test_gaps_go_to_the_span_that_covers_them():
    t = _tiny()
    spans = tr.host_spans(t)
    assert tr.attribute(40, 50, spans) == "train.step_call"
    # 170..200: 25 ns of loss_read against 4 ns of the next step_call
    assert tr.attribute(170, 200, spans) == "train.loss_read"
    assert tr.attribute(300, 310, spans) == tr.NO_SPAN
    gaps = dict(tr.idle_gaps(t))
    assert gaps == {"train.loss_read": pytest.approx(30e-9),
                    "train.step_call": pytest.approx(20e-9)}
    # step 1: wall 0..195 holds 160 busy ns -> 35; step 2 has no read
    assert tr.step_gaps_ms(t) == [pytest.approx(35e-6)]


def test_collectives_hidden_and_exposed_by_eye():
    t = _tiny()
    dev, asyn = t["devices"]["0"], t["async"]["0"]
    assert tr.collective_intervals(dev) == [(100, 140), (150, 170)]
    # the async all-gather is in flight from its start to its done
    assert tr.collective_intervals(dev + asyn) == [(20, 240)]
    # compute: the leaves that are not collectives
    assert tr.compute_intervals(dev) == [(0, 40), (60, 100), (200, 230)]
    split = tr.collective_split(t)
    # in flight 20..240 = 220; compute covers 20..40, 60..100, 200..230
    assert split["total_s"] == pytest.approx(220e-9)
    assert split["hidden_s"] == pytest.approx(90e-9)
    assert split["exposed_s"] == pytest.approx(130e-9)
    assert split["window_s"] == pytest.approx(240e-9)
    t["async"] = {}
    split = tr.collective_split(t)      # the two synchronous ones alone
    assert split["total_s"] == pytest.approx(60e-9)
    assert split["exposed_s"] == pytest.approx(60e-9)


def test_short_names_of_the_hlo_text_the_trace_prints():
    text = ('%fusion.120 = (bf16[256]{0:T(256)(128)(2,1)S(1)}, '
            'bf16[128,256,56,56]{1,0,3,2:T(8,128)(2,1)}) fusion(f32[256]'
            '{0:T(256)S(1)} %copy-done.755), kind=kOutput, '
            'calls=%fused_computation.196')
    assert tr.short_name(text) == "fusion.120 fusion:kOutput bf16[256]"
    text = ('%jvp__.19 = bf16[384,384,64]{2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(bf16[384,384,64]{2,1,0:T(8,128)(2,1)} '
            '%bitcast.4021), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={bf16[384,384,64]{2,1,0}}')
    name = tr.short_name(text)
    assert name == "jvp__.19 custom-call:tpu_custom_call bf16[384,384,64]"
    assert tr.PALLAS in name and tr.opcode(name) == "custom-call"
    assert tr.short_name("not hlo") == "not_hlo"
    assert tr.opcode("not_hlo") == ""


# ------------------------------------------------- the recorded cut -------
# Read off the file by hand (ns from the start of the cut):
#   45 events of step k end at 15,775; then nothing until the five events
#   of the random-key program (5,955,595..5,958,833 and 6,503,191..6,503,749);
#   step k+1 starts at 9,881,501 with convert_reduce_fusion.8 (445,237 ns).
#   Host: train.step_call [0, 5,940,555), train.loss_read [5,949,855,
#   6,624,505), train.step_call [6,658,035, 11,222,207).

WIN = (0, 11_222_207)


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_cut_busy_and_idle(recorded):
    dev = recorded["devices"]["0"]
    assert len(dev) == 71 and len(recorded["async"]["0"]) == 7
    grid = np.zeros(WIN[1], bool)
    for _, s, d in dev:
        grid[s:s + d] = True
    assert int(grid.sum()) == 1_358_287
    assert tr.total(tr.busy(recorded, WIN)["0"]) == 1_358_287
    assert tr.busy_seconds(recorded, WIN) == pytest.approx(1.358287e-3)
    assert tr.idle_share(recorded, WIN) == pytest.approx(
        1 - 1_358_287 / 11_222_207)
    # without a window span in the cut the window is the events' extent
    assert tr.window(recorded) == (203, 11_221_211)


def test_recorded_cut_op_table(recorded):
    table = tr.op_table(recorded, WIN, top=3)
    assert table == [
        ["fusion.141 fusion:kLoop bf16[128,64,112,112]",
         pytest.approx(621_250e-9)],
        ["convert_reduce_fusion.8 fusion:kOutput f32[64]",
         pytest.approx(445_237e-9)],
        ["fusion.30 fusion:kLoop f32[64]", pytest.approx(272_927e-9)]]
    # no event nests another here: self times add up to the busy time
    assert sum(ns for _, ns in tr.self_times(recorded["devices"]["0"])) \
        == 1_358_287
    assert tr.time_share(recorded, lambda n: tr.PALLAS in n, WIN) == 0


def test_recorded_cut_gaps_go_to_the_host_span(recorded):
    spans = tr.host_spans(recorded)
    # 15,775..5,955,595: step_call covers 5,924,780 of it, loss_read 5,740
    assert tr.attribute(15_775, 5_955_595, spans) == "train.step_call"
    # 5,958,833..6,503,191 lies inside loss_read
    assert tr.attribute(5_958_833, 6_503_191, spans) == "train.loss_read"
    # 6,503,749..9,881,501: loss_read 120,756, the next step_call 3,223,466
    assert tr.attribute(6_503_749, 9_881_501, spans) == "train.step_call"
    gaps = dict(tr.idle_gaps(recorded, WIN))
    assert gaps == {"train.step_call": pytest.approx(9_318_856e-9),
                    "train.loss_read": pytest.approx(545_064e-9)}
    assert sum(gaps.values()) == pytest.approx((11_222_207 - 1_358_287)
                                               * 1e-9)
    # step k: wall 0..6,624,505 holds 18,604 ns of device work
    assert tr.step_gaps_ms(recorded) == [pytest.approx(6.605901)]


def test_recorded_cut_has_no_collective_and_cuts_again(recorded):
    split = tr.collective_split(recorded, WIN)
    assert split["total_s"] == 0 and split["exposed_s"] == 0
    piece = tr.cut(recorded, 5_900_000, 6_700_000)
    assert [n.split(" ")[0] for n, _, _ in piece["devices"]["0"]] == [
        "fusion.16", "broadcast_add_fusion", "add_add_fusion",
        "pad_add_fusion", "slice_bitcast_fusion"]
    assert piece["devices"]["0"][0][1] == 5_955_595 - 5_900_000
    assert piece["host"]["python3"] == [
        ["train.step_call", 0, 40_555],
        ["train.loss_read", 49_855, 674_650],
        ["train.step_call", 758_035, 41_965]]
