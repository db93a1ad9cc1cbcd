"""Peak footprint of the fullest chip in GiB: live arrays plus the
scratch memory reserved for loaded programs (``harness/device.py``). A
change that buys speed with memory shows here."""
LAYER = "device"
MOVES = "train_samples_per_s"
UNIT = "GiB"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
