"""Kernel over (kernel + XLA) decisions of ``kernels.dispatch`` for the
``flash_attention_bwd`` family: how often the attention backward ran as
the Pallas calls rather than the scanned XLA recurrence (a count at trace
time that repeats exactly; the twin of ``flash_kernel_share``). Nothing to
read where the program has no such family, or the step never
differentiates a flash forward."""
LAYER = "kernels"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    rec = run["dispatch_stats"].get("flash_attention_bwd")
    if not rec or not (rec["kernel"] + rec["xla"]):
        return None
    return 100.0 * rec["kernel"] / (rec["kernel"] + rec["xla"])
