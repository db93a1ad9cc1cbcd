"""Device self time in events of a module whose text the program handed out
and whose instruction name is not in it, or whose result shape differs,
over busy time: the health of the join behind ``step_*_ms`` (a stale or
wrong text; must read ~0) (``harness/step_phases.py``)."""
from chipbench.harness import step_phases

LAYER = "device"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return step_phases.share(run, "unmatched_ms")
