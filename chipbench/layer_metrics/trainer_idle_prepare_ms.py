"""Device idle time a step, mean over the chips, while the host was under
``trainer.put_batch``, ``trainer.rng_key``, ``trainer.scalars`` or
``trainer.gather``: placing the batch, drawing the PRNG key and making the
two scalar arguments (small device programs of their own), gathering the
handles' buffers into the step's arguments. One of six that sum to the
device's idle time a step (``harness/program_spans.py``), in ms."""
from chipbench.harness import program_spans

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return program_spans.idle_ms(run, "prepare")
