"""Busiest held expert over the mean held expert: per expert layer the
busiest expert's pairs summed over the steps (``load_peak``) over the mean
expert's pairs summed over the steps, averaged over the layers
(``model.expert_load``). 1 is an even load; a grouped product waits for its
largest group. Nothing to read where the configuration has no expert
layer."""
LAYER = "experts"
MOVES = "train_samples_per_s"
UNIT = "x"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    read = getattr(run["model"], "expert_load", None)
    per_layer = [rec["peak"] / (sum(rec["pairs"]) / len(rec["pairs"]))
                 for rec in ((read() if read else None) or {}).values()
                 if sum(rec["pairs"])]
    return sum(per_layer) / len(per_layer) if per_layer else None
