"""Device idle time a step, mean over the chips, while the host was under
``trainer.guard_sync``, the nan-guard's blocking flag read: the wait before
the step's first operation and the wake-up after its last. One of six that
sum to the device's idle time a step (``harness/program_spans.py``), in ms."""
from chipbench.harness import program_spans

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return program_spans.idle_ms(run, "sync")
