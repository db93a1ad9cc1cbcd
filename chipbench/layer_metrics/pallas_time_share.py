"""Share of the device's busy time spent inside Mosaic (Pallas) custom
calls: the trace shows them as ``custom-call`` instructions with the
target ``tpu_custom_call`` (read off a BERT trace by hand, PR 22).
Nothing to read where the step holds no such call."""
from chipbench.harness import trace_reduce

LAYER = "kernels"
MOVES = "train_samples_per_s"
UNIT = "%"


def is_pallas(name):
    return trace_reduce.PALLAS in name


def applies(run):
    return run["mode"] == "train"


def compute(run):
    if not run["trace"]["devices"]:
        return None
    share = trace_reduce.time_share(run["trace"], is_pallas)
    return 100.0 * share if share else None
