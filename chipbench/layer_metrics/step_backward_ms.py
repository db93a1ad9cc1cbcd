"""Device self time a step, mean over the chips, in the instructions of the
compiled step that jax named ``transpose(jvp(...))``: the backward pass (a
``jax.checkpoint``ed forward recomputed there among them). One of five that
sum to the device's busy time a step (``harness/step_phases.py``), in ms."""
from chipbench.harness import step_phases

LAYER = "ops"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return step_phases.phase_ms(run, "backward")
