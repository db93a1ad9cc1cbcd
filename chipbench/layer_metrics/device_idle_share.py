"""1 - (union of the device-op intervals) / (traced window), averaged
over the chips, for a training cell."""
from chipbench.harness import trace_reduce

LAYER = "device"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    share = trace_reduce.idle_share(run["trace"])
    return None if share is None else 100.0 * share
