"""Device programs launched a step: events of each chip's ``XLA Modules``
line inside the traced window over the traced ``trainer.step`` spans, mean
over the chips (``harness/program_spans.py``). One was expected; every
further one is a launch the device waits for."""
from chipbench.harness import program_spans

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "count"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return program_spans.programs_per_step(run)
