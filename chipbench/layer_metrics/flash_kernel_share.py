"""Kernel over (kernel + XLA) decisions of ``kernels.dispatch`` for the
``flash_attention`` family (a count at trace time that repeats exactly).
Nothing to read where the network never asks for attention."""
LAYER = "kernels"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    rec = run["dispatch_stats"].get("flash_attention")
    if not rec or not (rec["kernel"] + rec["xla"]):
        return None
    return 100.0 * rec["kernel"] / (rec["kernel"] + rec["xla"])
