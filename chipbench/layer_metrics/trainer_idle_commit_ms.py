"""Device idle time a step, mean over the chips, while the host was under
``trainer.commit`` (marking the donated buffers, rebinding the handles to the
step's outputs) or ``trainer.release`` (letting go of the donated inputs after
the guard's read: some 600 array destructors and poison-record expiries). One
of six that sum to the device's idle time a step
(``harness/program_spans.py``), in ms."""
from chipbench.harness import program_spans

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return program_spans.idle_ms(run, "commit")
