"""Median over the traced steps of (wall time from the start of the
``trainer.step`` call to the end of the blocking loss read) minus (the
device's busy time inside it): what the host adds to a step, in ms."""
from chipbench.harness import stats, trace_reduce

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    if not run["trace"]["devices"]:
        return None
    return stats.median(trace_reduce.step_gaps_ms(run["trace"]))
