"""Roofline share of the selective-scan kernels: the least time the chip
could take for a step's scan calls, forward and backward, over their
measured self time a step in the device trace. The scan has no matmul and
``peaks.json`` has no vector peak, so BYTES bound it: the configuration's
``ssm_scan_kernel_cost`` counts x, the float32 step, B, C, the output and
their cotangents once each, over the HBM peak (the ``exp`` a (position,
channel, state) and the 4,096 sequential steps are what the kernel really
pays, so the share reads low by construction: it says how far the
recurrence is from streaming). The calls are the Mosaic custom calls
(``trace_reduce.PALLAS``) whose first result has the shape the cost names:
the forward's is the output, the backward's the cotangent of x. Nothing
to read where the configuration counts no scan, or the step holds no such
call (the scan ran as XLA code)."""
from chipbench.harness import peaks, trace_reduce

LAYER = "kernels"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    cost_of = getattr(run["model"], "ssm_scan_kernel_cost", None)
    if cost_of is None or not run["trace"]["devices"]:
        return None
    cost = cost_of(run["cfg"], run["traffic"])
    # self seconds in the window by name, averaged over the chips
    calls_s = sum(
        s for name, s in trace_reduce.op_table(run["trace"], top=None)
        if trace_reduce.PALLAS in name and name.endswith(" " + cost["shape"]))
    measured_s = calls_s / int(run["traffic"]["trace_steps"])
    if not measured_s:
        return None
    peak = peaks.lookup(run["device"]["kind"])
    least_s = cost["bytes"] / (peak["hbm_gbytes_per_s"] * 1e9)
    # the batch is split over the chips; each runs its share of the calls
    return 100.0 * least_s / run["chips"] / measured_s
