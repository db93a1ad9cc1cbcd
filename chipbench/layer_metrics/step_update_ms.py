"""Device self time a step, mean over the chips, in the instructions under
the step's scope ``trainer.update``: the optimizer rule, the guard's select and
the master's cast. One of five that sum to the device's busy time a step
(``harness/step_phases.py``), in ms."""
from chipbench.harness import step_phases

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return step_phases.phase_ms(run, "update")
