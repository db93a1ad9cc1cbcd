"""Device self time a step, mean over the chips, in collective opcodes on the
``XLA Ops`` line (all-reduce, reduce-scatter, all-gather ...; by OPCODE:
GSPMD's all-reduce inherits the name of the product it sums). One of five
that sum to the device's busy time a step (``harness/step_phases.py``), in
ms."""
from chipbench.harness import step_phases

LAYER = "collectives"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train" and run["chips"] > 1


def compute(run):
    return step_phases.phase_ms(run, "collective")
