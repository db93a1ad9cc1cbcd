"""99th percentile of (sent - due) of the benchmark's own generator. Not
the system's doing: a starved generator invalidates the run. Latency
counts from ``due``, so lateness lands in ``serve_p99_ms``."""
from chipbench.harness import stats

LAYER = "serving"
MOVES = "serve_p99_ms"
UNIT = "ms"


def applies(run):
    return run["mode"] == "serve_open"


def compute(run):
    return stats.percentile(run["late_ms"], 99)
