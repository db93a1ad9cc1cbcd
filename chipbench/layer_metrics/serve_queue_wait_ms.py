"""Median ``queue_wait`` of ``ServingFuture.breakdown()`` (host clock):
submit to the collector popping the request."""
from chipbench.harness import stats

LAYER = "serving"
MOVES = "serve_p99_ms"
UNIT = "ms"


def applies(run):
    return run["mode"] == "serve_open"


def compute(run):
    waits = [b["queue_wait_ms"] for b in run["breakdowns"]
             if b.get("queue_wait_ms") is not None]
    return stats.median(waits)
