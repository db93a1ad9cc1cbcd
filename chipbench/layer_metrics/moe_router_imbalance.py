"""Busiest expert of the WHOLE router over the mean expert, held here or
not: per expert layer the largest of ``route_pairs`` (the pairs each of
the router's experts got, summed over the training steps) over their mean,
averaged over the layers (``model.expert_load``). What a selection bias
that balances itself holds near 1; ``moe_load_imbalance`` reads the held
experts alone. Nothing to read where no expert layer counts its whole
router (a layer whose bias stands still; the parent commit)."""
LAYER = "experts"
MOVES = "train_samples_per_s"
UNIT = "x"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    read = getattr(run["model"], "expert_load", None)
    routed = [rec["route_pairs"]
              for rec in ((read() if read else None) or {}).values()
              if sum(rec.get("route_pairs", ()))]
    per_layer = [max(pairs) * len(pairs) / sum(pairs) for pairs in routed]
    return sum(per_layer) / len(per_layer) if per_layer else None
