"""Device self time a step, mean over the chips, in the instructions of the
compiled step that jax named ``jvp(...)`` and not ``transpose(``: the forward
pass. One of five that sum to the device's busy time a step
(``harness/step_phases.py``: the trace's instruction names joined to the
``op_name`` metadata of ``compile.program_texts("trainer")``), in ms."""
from chipbench.harness import step_phases

LAYER = "ops"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return step_phases.phase_ms(run, "forward")
