"""Device idle time a step, mean over the chips, while the host was under
``trainer.step`` but none of its eight work spans: ``watchdog.sync``,
``begin_step`` and ``trainer.bookkeeping`` (telemetry's ``end_step``: gauges,
histogram, flight records, memory sample), ROADMAP C10's number. One of six
that sum to the device's idle time a step (``harness/program_spans.py``),
in ms."""
from chipbench.harness import program_spans

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    return program_spans.idle_ms(run, "bookkeeping")
