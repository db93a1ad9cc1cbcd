"""Median ``sync`` phase of ``telemetry/steps.history()`` over the window
(host clock): the nan-guard's blocking flag read, which with the default
guard contains the device's step (ROADMAP A6's number)."""
from chipbench.harness import stats

LAYER = "trainer"
MOVES = "train_samples_per_s"
UNIT = "ms"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    syncs = [rec["phases"]["sync"] for rec in run["step_history"]
             if "sync" in rec.get("phases", {})]
    return stats.median(syncs)
