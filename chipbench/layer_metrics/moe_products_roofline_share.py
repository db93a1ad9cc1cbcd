"""Roofline share of the expert layer's grouped products: the least time
the chip could take for a step's grouped products over their device self
time a step. The least time is their operations over the bf16 peak: 11
products a layer a step, each 2 x live rows x h x f, the live rows a call
from ``model.expert_load`` (``harness/grouped_products.py``). The time is
every grouped product's, whether XLA's ``ragged-dot-*`` kernel or the
``grouped_matmul`` family's Pallas kernel runs it, so a parent without the
kernel reads the same work. Nothing to read where the configuration has no
expert layer, the trace no device or the window no grouped product."""
from chipbench.harness import grouped_products, peaks

LAYER = "experts"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    split = grouped_products.self_seconds(run)
    if split is None:
        return None
    measured_s = sum(split) / int(run["traffic"]["trace_steps"])
    peak = peaks.lookup(run["device"]["kind"])
    least_s = grouped_products.step_flops(run) / (peak["bf16_tflops"] * 1e12)
    # the batch is split over the chips; each runs its share of the work
    return 100.0 * least_s / run["chips"] / measured_s
