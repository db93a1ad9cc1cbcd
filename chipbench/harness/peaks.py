"""The table of published peaks, keyed by ``device_kind``. A device that is
not in ``peaks.json`` is an error, not a default, and nothing in the
environment overrides an entry (``telemetry/costs.py``'s
``BENCH_PEAK_TFLOPS`` does not reach here)."""
import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def table():
    with open(_PATH) as f:
        return json.load(f)


def lookup(device_kind):
    peaks = table()
    if device_kind not in peaks:
        raise LookupError(
            f"no published peak for device kind {device_kind!r} in "
            f"{_PATH}; known: {sorted(peaks)}")
    return peaks[device_kind]
