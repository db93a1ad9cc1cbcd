"""Share of the device's busy time spent inside the selective-scan
kernels, forward and backward: the self time of the Mosaic custom calls
whose first result has the shape the configuration's
``ssm_scan_kernel_cost`` names, over the busy time of the traced window.
Nothing to read where the configuration counts no scan, or the step holds
no such call (the scan ran as XLA code)."""
from chipbench.harness import trace_reduce

LAYER = "kernels"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    cost_of = getattr(run["model"], "ssm_scan_kernel_cost", None)
    if cost_of is None or not run["trace"]["devices"]:
        return None
    shape = cost_of(run["cfg"], run["traffic"])["shape"]
    share = trace_reduce.time_share(
        run["trace"], lambda name: trace_reduce.PALLAS in name
        and name.endswith(" " + shape))
    return 100.0 * share if share else None
