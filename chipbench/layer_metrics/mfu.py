"""Model FLOP/s utilization: samples/s of the untraced steps x the
configuration's ``flops_per_sample`` (forward + backward, two per
multiply-accumulate, nothing recomputed) over chips x the published bf16
peak of the device kind. ``train_samples_per_s`` times a constant."""
from chipbench.harness import peaks

LAYER = "device"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    peak = peaks.lookup(run["device"]["kind"])["bf16_tflops"] * 1e12
    flops = run["model"].flops_per_sample(run["cfg"], run["traffic"])
    return 100.0 * run["samples_per_s"] * flops / (run["chips"] * peak)
