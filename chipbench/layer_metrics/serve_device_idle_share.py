"""1 - (union of the device-op intervals) / (traced window) for a serving
cell: how far the host path holds the chip back."""
from chipbench.harness import trace_reduce

LAYER = "device"
MOVES = "serve_p50_ms"
UNIT = "%"


def applies(run):
    return run["mode"] == "serve_open"


def compute(run):
    share = trace_reduce.idle_share(run["trace"])
    return None if share is None else 100.0 * share
