"""Roofline share of the Pallas attention kernel: the least time the
chip could take for a step's attention calls (the larger of operations
over the bf16 peak and bytes over the HBM peak, both from the
configuration's ``attention_kernel_cost``: only the pairs at or under the
diagonal, every operand once) over their measured self time a step in the
device trace. The calls are the Mosaic custom calls (``trace_reduce.
PALLAS``) whose result has the shape ``attention_kernel_cost`` names:
the grouped matmuls of an expert layer are Mosaic calls too, and are not
attention. Nothing to read where the configuration counts no attention
kernel, or the step holds none."""
from chipbench.harness import peaks, trace_reduce

LAYER = "kernels"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    cost_of = getattr(run["model"], "attention_kernel_cost", None)
    if cost_of is None or not run["trace"]["devices"]:
        return None
    cost = cost_of(run["cfg"], run["traffic"])
    # self seconds in the window by name, averaged over the chips
    calls_s = sum(
        seconds for name, seconds in trace_reduce.op_table(
            run["trace"], top=None)
        if trace_reduce.PALLAS in name
        and name.endswith(" " + cost["shape"]))
    measured_s = calls_s / int(run["traffic"]["trace_steps"])
    if not measured_s:
        return None
    peak = peaks.lookup(run["device"]["kind"])
    least_s = max(cost["flops"] / (peak["bf16_tflops"] * 1e12),
                  cost["bytes"] / (peak["hbm_gbytes_per_s"] * 1e9))
    # the batch is split over the chips; each runs its share of the calls
    return 100.0 * least_s / run["chips"] / measured_s
