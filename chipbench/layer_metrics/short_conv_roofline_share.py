"""Roofline share of what lies between a short convolution's two
projections (gate, taps, gate), forward and backward: the least time the
chip could take for a step's passes over the streams, over their measured
self time a step under the program's scope ``sconv.gate``
(``harness/scope_time.py``). A few products an element and no matmul, and
``peaks.json`` has no vector peak, so BYTES bound it: the configuration's
``short_conv_cost`` counts the three streams and the result in the
forward, those, the cotangent and three cotangents out in the backward,
each once, over the HBM peak. Nothing to read where the configuration
counts no short convolution or the step names no such scope."""
from chipbench.harness import peaks, scope_time

LAYER = "ops"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    cost_of = getattr(run["model"], "short_conv_cost", None)
    if cost_of is None:
        return None
    cost = cost_of(run["cfg"], run["traffic"])
    read = scope_time.scope_ms(run, cost["scope"])
    if read is None:
        return None
    peak = peaks.lookup(run["device"]["kind"])
    least_s = cost["bytes"] / (peak["hbm_gbytes_per_s"] * 1e9)
    # the batch is split over the chips; each runs its share of the work
    return 100.0 * least_s / run["chips"] / (read[0] / 1e3)
