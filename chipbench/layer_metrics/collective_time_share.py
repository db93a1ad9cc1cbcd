"""Time with a collective in flight (all-reduce, reduce-scatter,
all-gather ...) as a share of the traced window, averaged over the
devices."""
from chipbench.harness import trace_reduce

LAYER = "collectives"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train" and run["chips"] > 1


def compute(run):
    if not run["trace"]["devices"]:
        return None
    split = trace_reduce.collective_split(run["trace"])
    return 100.0 * split["total_s"] / split["window_s"]
