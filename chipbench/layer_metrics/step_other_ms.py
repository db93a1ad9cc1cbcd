"""The rest of the device's busy time a step, mean over the chips: layout
copies of arguments, the waits and slices with neither a name of jax's nor a
named operand, the guard's own reductions (``trainer.guard``), the rng split,
the two small programs beside the step. One of five that sum to
the device's busy time a step (``harness/step_phases.py``), in ms."""
from chipbench.harness import step_phases

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return step_phases.phase_ms(run, "other")
