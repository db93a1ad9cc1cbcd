"""Share of the device's busy time spent between a short convolution's two
projections (gate, taps, gate), forward and backward: the self time a step
under the program's scope the configuration's ``short_conv_cost`` names
(``sconv.gate``), from the by-scope table of ``harness/step_phases.py``,
over busy time a step. Nothing to read where the configuration counts no
short convolution, or the step's text names no such scope (the parent
commit; an executable another tree cached)."""
from chipbench.harness import scope_time

LAYER = "ops"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    cost_of = getattr(run["model"], "short_conv_cost", None)
    if cost_of is None:
        return None
    read = scope_time.scope_ms(
        run, cost_of(run["cfg"], run["traffic"])["scope"])
    return None if read is None else 100.0 * read[0] / read[1]
