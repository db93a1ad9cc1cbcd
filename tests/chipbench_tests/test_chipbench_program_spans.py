"""``harness/program_spans.py``: the device's idle time by the program's
own spans, on a trace written by hand (answers computed by hand), on a cut
recorded on the chip, on a trace that holds no program span, and through a
traced toy cell."""
import json
import os
import types

import chipbench_toy as toy
import pytest

from chipbench.harness import program_spans as ps

U = 1000   # the hand-written trace is in microseconds, the format in ns


def _step(t0, sync_end):
    """One step's spans as ``ShardedTrainer.step`` nests them."""
    return [["trainer.step", t0, sync_end + 30 - t0],
            ["trainer.put_batch", t0 + 10, 20],
            ["trainer.rng_key", t0 + 30, 20],
            ["trainer.scalars", t0 + 50, 20],
            ["trainer.gather", t0 + 70, 10],
            ["trainer.dispatch", t0 + 80, 80],
            ["compile.signature", t0 + 85, 20],
            ["compile.execute", t0 + 105, 50],
            ["trainer.commit", t0 + 160, 30],
            ["trainer.guard_sync", t0 + 190, sync_end - t0 - 190],
            ["trainer.bookkeeping", sync_end + 5, 20]]


def _scaled(events):
    return [[n, s * U, d * U] for n, s, d in events]


@pytest.fixture
def hand():
    """Two steps ([100, 500) and [600, 980)) in a window [0, 1000), a
    third step that leaves the window, two devices. Device 1 starts the
    first step's program late, inside ``trainer.commit``; every idle gap
    but the last straddles several spans; [500, 600) and [980, 1000) lie
    outside every step."""
    busy0 = [[0, 50], [140, 145], [160, 162], [240, 460],
             [640, 645], [660, 662], [740, 940]]
    busy1 = [[0, 60], [140, 145], [160, 162], [270, 465],
             [640, 645], [660, 662], [740, 940]]

    def modules(step1):
        return _scaled([["jit__threefry_split(111)", 140, 5],
                        ["jit_convert_element_type(222)", 160, 2],
                        ["jit_step_fn(333)", *step1],
                        ["jit__threefry_split(111)", 640, 5],
                        ["jit_convert_element_type(222)", 660, 2],
                        ["jit_step_fn(333)", 740, 200]])

    return {"window": [0, 1000 * U],
            "spans": _scaled(_step(100, 470) + _step(600, 950)
                             + [["trainer.step", 990, 200]]),
            "launches": _scaled([["_threefry_split", 132, 3],
                                 ["convert_element_type", 152, 3],
                                 ["step_fn", 206, 40],
                                 ["_threefry_split", 632, 3],
                                 ["convert_element_type", 652, 3],
                                 ["step_fn", 706, 40]]),
            "modules": {"0": modules((240, 220)), "1": modules((270, 195))},
            "busy": {"0": [[s * U, e * U] for s, e in busy0],
                     "1": [[s * U, e * U] for s, e in busy1]}}


def test_pieces_split_at_every_boundary_and_name_the_deepest():
    spans = [["a", 10, 80], ["b", 20, 30], ["c", 25, 10], ["d", 95, 10]]
    assert ps.pieces(spans, 0, 100) == [
        (0, 10, ()), (10, 20, ("a",)), (20, 25, ("a", "b")),
        (25, 35, ("a", "b", "c")), (35, 50, ("a", "b")),
        (50, 90, ("a",)), (90, 95, ()), (95, 100, ("d",))]
    # one sweep charges the idle intervals to the pieces they lie in: an
    # interval that straddles three pieces is split at both boundaries
    cuts = ps.pieces(spans, 0, 100)
    idle = [(5, 12), (18, 40), (60, 70), (92, 99)]
    assert ps.charge(idle, cuts) == [5, 4, 5, 10, 5, 10, 3, 4]
    assert ps.charge([], cuts) == [0] * 8
    assert ps.group_of(()) == "caller"
    assert ps.group_of(("io.h2d",)) == "caller"
    assert ps.group_of(("trainer.step",)) == "bookkeeping"
    assert ps.group_of(("trainer.step", "trainer.put_batch",
                        "io.h2d")) == "prepare"
    assert ps.group_of(("trainer.step", "trainer.dispatch",
                        "compile.execute")) == "dispatch"
    assert ps.group_of(("trainer.step", "trainer.release")) == "commit"
    assert ps.group_of(("trainer.step", "trainer.new_thing")) == \
        "bookkeeping"


def test_hand_written_trace_against_hand_computed_values(hand):
    got = ps.reduce(hand)
    assert got["steps"] == 2
    # idle ns by group, device 0 + device 1, over 2 devices x 2 steps;
    # e.g. dispatch: device 0 waits [180, 240) and [680, 740), device 1
    # [180, 260) and [680, 740); commit: device 1 alone, [260, 270)
    want = {"prepare": (126 + 126) / 4, "dispatch": (120 + 140) / 4,
            "commit": (0 + 10) / 4, "sync": (20 + 15) / 4,
            "bookkeeping": (80 + 80) / 4, "caller": (170 + 160) / 4}
    assert got["idle_ms"] == pytest.approx(
        {g: v * U / 1e6 for g, v in want.items()})
    # a partition: the six sum to the idle time a step, (516 + 531) / 4
    assert sum(got["idle_ms"].values()) == pytest.approx(
        (516 + 531) / 4 * U / 1e6)
    by_span = got["idle_by_span_ms"]
    assert by_span["compile.signature"] == pytest.approx(80 / 4 * U / 1e6)
    assert by_span["compile.execute"] == pytest.approx(155 / 4 * U / 1e6)
    assert by_span["trainer.dispatch"] == pytest.approx(25 / 4 * U / 1e6)
    assert by_span["trainer.step"] == pytest.approx(80 / 4 * U / 1e6)
    assert by_span[ps.CALLER] == pytest.approx(330 / 4 * U / 1e6)
    assert sum(by_span.values()) == pytest.approx(
        sum(got["idle_ms"].values()))
    assert list(by_span.values()) == sorted(by_span.values(),
                                            reverse=True)
    assert got["median_ms"]["trainer.dispatch"] == pytest.approx(0.08)
    assert got["median_ms"]["trainer.guard_sync"] == pytest.approx(0.16)
    assert got["median_ms"]["trainer.step (self)"] == pytest.approx(0.02)
    assert got["programs_per_step"] == 3.0
    assert got["programs"] == {"jit__threefry_split": 1.0,
                               "jit_convert_element_type": 1.0,
                               "jit_step_fn": 1.0}
    assert got["programs_per_step"] == sum(got["programs"].values())


def test_clock_brackets_the_offset_from_causality(hand):
    clock = ps.reduce(hand)["clock"]
    launch = clock["launch_to_start_us"]
    assert launch["jit__threefry_split"] == {"min": 8.0, "median": 8.0,
                                             "n": 4}
    # device 1 started the first step's program 64 us after its launch
    assert launch["jit_step_fn"] == {"min": 34.0, "median": 34.0, "n": 4}
    assert clock["end_to_wake_us"] == {"min": 5.0, "median": 10.0, "n": 4}
    assert clock["device_minus_host_us"] == [-5.0, 8.0]


def test_a_trace_without_step_spans_or_devices_gives_none(hand):
    assert ps.reduce(dict(hand, spans=[])) is None
    # a CPU trace: span durations are still read, nothing else is
    cpu = ps.reduce(dict(hand, modules={}, busy={}))
    assert cpu["steps"] == 2 and cpu["median_ms"]["trainer.commit"] > 0
    assert cpu["idle_ms"] is None and cpu["programs_per_step"] is None
    assert cpu["clock"] is None


def test_cut_keeps_two_steps_and_joins_the_busy_intervals(hand):
    hand["busy"]["0"][3:4] = [[240 * U, 300 * U], [300 * U + 400, 460 * U]]
    piece = ps.cut(hand, 90 * U, 600 * U)
    assert piece["window"] == [0, 510 * U]
    assert [e[0] for e in piece["spans"]].count("trainer.step") == 1
    assert piece["spans"][0] == ["trainer.step", 10 * U, 400 * U]
    assert piece["busy"]["0"] == [[50 * U, 55 * U], [70 * U, 72 * U],
                                  [150 * U, 370 * U]]
    assert len(piece["modules"]["1"]) == 3
    assert json.loads(json.dumps(piece)) == piece


def test_recorded_cut_from_the_chip():
    """Two steps of ``resnet50_train_dp4`` as the v5e recorded them (the
    fixture's ``source``): four devices, the small programs on device 0
    only, the donated inputs let go under ``trainer.release``."""
    with open(os.path.join(toy.BENCH, "harness",
                           "program_spans_fixture.json")) as f:
        data = json.load(f)
    assert os.path.getsize(f.name) < 100_000
    got = ps.reduce(data)
    assert got["steps"] == 2 and len(data["busy"]) == 4
    assert got["idle_ms"] == pytest.approx(
        {"prepare": 2.4124055, "dispatch": 2.6606405, "commit": 4.2009095,
         "sync": 2.49178, "bookkeeping": 0.3860055, "caller": 0.900674625})
    # the partition against the idle time counted without any span
    lo, hi = data["window"]
    idle = [(hi - lo) - sum(e - s for s, e in iv)
            for iv in data["busy"].values()]
    assert sum(got["idle_ms"].values()) == pytest.approx(
        sum(idle) / 4 / 2 / 1e6)
    by_span = got["idle_by_span_ms"]
    assert next(iter(by_span)) == "trainer.release"
    assert by_span["trainer.release"] == pytest.approx(
        got["idle_ms"]["commit"])
    assert by_span["trainer.rng_key"] + by_span["trainer.scalars"] \
        == pytest.approx(1.890015)
    # five programs a step on device 0, the step alone on the others
    assert got["programs"] == {
        "jit__threefry_split": 0.25, "jit__unstack": 0.25,
        "jit_convert_element_type": 0.5, "jit_step_fn": 1.0}
    assert got["programs_per_step"] == 2.0
    # the device's clock reads early: a program "starts" before the host
    # launched it, by 0.96 ms at least, and "ends" 2.4 ms before the wake
    clock = got["clock"]
    assert clock["launch_to_start_us"]["jit_convert_element_type"][
        "min"] == pytest.approx(-878.663)
    # the window's edge cut the first step's launch of this one off
    assert clock["launch_to_start_us"]["jit__threefry_split"]["n"] == 1
    assert clock["launch_to_start_us"]["jit_step_fn"]["n"] == 8
    assert clock["device_minus_host_us"] == pytest.approx(
        [-2412.881, -958.598])


def test_launches_pair_with_starts_across_a_cut_edge():
    assert ps.paired([10, 20, 30], [11, 21, 31]) == [
        (10, 11), (20, 21), (30, 31)]
    # the start of the first launch fell before the window
    assert ps.paired([10, 20, 30], [21, 31], near_ns=5) == [
        (20, 21), (30, 31)]
    assert ps.paired([20, 30], [1, 21, 31], near_ns=5) == [
        (20, 21), (30, 31)]
    assert ps.paired([1], [100], near_ns=5) == []
    assert ps.paired([], [3]) == []
    assert ps.outermost([["f", 10, 50], ["f", 12, 40], ["g", 70, 5]]) \
        == [["f", 10, 50], ["g", 70, 5]]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_program_without_the_spans_reads_as_nothing(tmp_path):
    """What the parent commit gives: a trace with the benchmark's spans
    only. The readers return None and raise nothing."""
    import jax

    from chipbench.harness import bench as hbench
    from chipbench.harness import trace_reduce

    log_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("train.step_call"):
            jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    run = {"mode": "train",
           "bench": types.SimpleNamespace(trace=True, _trace_dir=log_dir),
           "trace": trace_reduce.load(trace_reduce.find_xplane(log_dir))}
    folder = os.path.join(toy.BENCH, "layer_metrics")
    mine = [f for f in sorted(os.listdir(folder)) if f.startswith(
        ("trainer_idle_", "trainer_programs_"))]
    assert len(mine) == 7
    for fname in mine:
        reader = hbench.load_module(os.path.join(folder, fname))
        assert reader.applies(run) and reader.compute(run) is None
    # and a run that took no trace at all
    assert ps.of_run({"mode": "train", "bench": types.SimpleNamespace(
        trace=False)}) is None


@pytest.fixture
def lifted(monkeypatch):
    restore = toy.lift_refusal(monkeypatch)
    yield
    restore()


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_traced_toy_cell_prints_the_spans_above_the_last_line(
        tmp_path, lifted, capsys):
    bench_dir = toy.toy_copy(str(tmp_path / "chipbench"))
    rc, last, lines = toy.run_cell(bench_dir, "resnet50_train_1chip", 1,
                                   capsys)
    assert rc == 0 and last["correct"] is True
    notes = {ln[2:].split(":", 1)[0]: json.loads(ln.split(":", 1)[1])
             for ln in lines[:-1]}
    spans = notes["program_spans"]
    assert spans["steps"] == 3       # the toy cell's trace_steps
    assert set(spans["median_ms"]) == {
        "trainer.step", "trainer.step (self)", "trainer.put_batch",
        "trainer.rng_key", "trainer.scalars", "trainer.gather",
        "trainer.dispatch", "compile.signature", "compile.execute",
        "trainer.commit", "trainer.guard_sync", "trainer.release",
        "trainer.bookkeeping"}
    assert all(v >= 0 for v in spans["median_ms"].values())
    # the CPU trace has no device plane: no idle time, no program count,
    # no clock to compare, and the seven metrics are left out
    assert spans["idle_by_span_ms"] is None and spans["programs"] is None
    assert notes["clock"] is None
    assert not [m for m in last["metrics"] if m.startswith(
        ("trainer_idle_", "trainer_programs_"))]
