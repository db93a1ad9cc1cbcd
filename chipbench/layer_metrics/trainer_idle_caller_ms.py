"""Device idle time a step, mean over the chips, while the host was under
no ``trainer.step`` at all: the caller's loop between two steps, here the
benchmark's loss read and its own bookkeeping. One of six that sum to the
device's idle time a step (``harness/program_spans.py``), in ms."""
from chipbench.harness import program_spans

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return program_spans.idle_ms(run, "caller")
