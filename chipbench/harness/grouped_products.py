"""The expert layer's grouped products in a device trace, for the readers
of the ``experts`` layer: whichever runs them, XLA's ``ragged-dot-*``
kernels or the ``grouped_matmul`` family's Pallas kernels, they are Mosaic
calls (``trace_reduce.PALLAS``) whose result is a (rows, h) or (rows, f)
row buffer, or a weight cotangent (held experts, h, f) / (held, f, h).
The widths come from the configuration (``hidden_size``,
``moe_intermediate_size``), the held experts and the live rows a call from
``model.expert_load``."""
from . import trace_reduce

XLA_PREFIX = "ragged-dot"


def layers(run):
    """``[(live pairs a call, held experts)]`` of every expert layer that
    ran, or an empty list where the configuration has none."""
    read = getattr(run["model"], "expert_load", None)
    counted = [rec for rec in ((read() if read else None) or {}).values()
               if rec["calls"]]
    return [(sum(rec["pairs"]) / rec["calls"], len(rec["pairs"]))
            for rec in counted]


def widths(run):
    """``(h, f)`` of the configuration's experts, or None."""
    cfg = run["cfg"]
    if "hidden_size" not in cfg or "moe_intermediate_size" not in cfg:
        return None
    return int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])


def _dims(name):
    shape = name.rsplit(" ", 1)[-1]
    if "[" not in shape or not shape.endswith("]"):
        return None
    dims = shape[shape.find("[") + 1:-1].split(",")
    return tuple(int(d) for d in dims) if all(d.isdigit() for d in dims) \
        else None


def is_product(name, h, f, held):
    """Whether a device event's short name is a grouped product."""
    if trace_reduce.PALLAS not in name:
        return False
    dims = _dims(name)
    if dims is None:
        return False
    if len(dims) == 2:
        return dims[1] in (h, f)
    return len(dims) == 3 and dims[0] in held and sorted(dims[1:]) \
        == sorted((h, f))


def self_seconds(run):
    """``(kernel s, XLA s)``: device self time of the grouped products in
    the traced window, averaged over the chips, split by who ran them; None
    where the run has no expert layer, no device in its trace or no grouped
    product in its window."""
    found, shape = layers(run), widths(run)
    if not found or shape is None or not run["trace"]["devices"]:
        return None
    held = {n for _, n in found}
    table = trace_reduce.op_table(run["trace"], top=None)
    split = [0.0, 0.0]
    for name, seconds in table:
        if is_product(name, *shape, held):
            split[name.startswith(XLA_PREFIX)] += seconds
    return tuple(split) if sum(split) else None


def step_flops(run):
    """Operations the grouped products need a training step: 11 products
    a layer (the forward's three, the two the backward recomputes, three
    input and three weight cotangents), each 2 x live rows x h x f."""
    h, f = widths(run)
    return sum(11 * 2 * live * h * f for live, _ in layers(run))
