"""Mean (token, expert) pairs a held expert got a training step, over the
expert layers and over every step since the network was built (warm-up
and window: the tokens a step are the same), from the layers' load
counters (``model.expert_load``). Nothing to read where the configuration
has no expert layer."""
LAYER = "experts"
MOVES = "train_samples_per_s"
UNIT = "count"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    read = getattr(run["model"], "expert_load", None)
    counted = [rec for rec in ((read() if read else None) or {}).values()
               if rec["calls"]]
    if not counted:
        return None
    per_layer = [sum(rec["pairs"]) / len(rec["pairs"]) / rec["calls"]
                 for rec in counted]
    return sum(per_layer) / len(per_layer)
