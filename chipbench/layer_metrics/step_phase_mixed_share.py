"""Device self time in fusions that hold instructions of more than one of
forward / backward / update, or the guard's ``is-finite`` beside any of
them, over busy time: how soft the partition of ``step_*_ms`` is
(``harness/step_phases.py``)."""
from chipbench.harness import step_phases

LAYER = "device"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return step_phases.share(run, "mixed_ms")
