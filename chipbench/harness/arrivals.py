"""The one general open-loop traffic generator. A traffic mix is data (the
``traffic`` object of a workload file); this module turns it and a seed
into a schedule, drawn whole before the window opens so that the work of a
run is fixed by the seed and not by how the run went.

Corrected copy of ``tools/loadgen.py`` ``run_inproc(mode="open")``: that
loop has a fixed period, no seed, and times a request from the moment of
``submit``; here arrivals are a seeded renewal process and every request
carries the instant it was DUE, so a stalled generator shows as latency
(and as ``late``), not as a quiet server.

Traffic keys read here::

    rate_per_s        mean arrivals per second (fixed in the cell)
    interarrival_cv   1.0 = exponential gaps (Poisson arrivals); above 1
                      gamma gaps with that coefficient of variation
                      (bursts), same mean rate
    rows              {"<rows per request>": probability, ...}
    pool              how many distinct seeded payload rows exist
"""
import numpy as np


def schedule(traffic, seed, seconds):
    """``(due_s, rows, offset)`` arrays for every request due inside
    ``[0, seconds)``: offsets from the window's start, rows per request,
    and the first payload-pool row of each request."""
    rate = float(traffic["rate_per_s"])
    cv = float(traffic.get("interarrival_cv", 1.0))
    sizes = sorted((int(k), float(p)) for k, p in traffic["rows"].items())
    total = sum(p for _, p in sizes)
    if rate <= 0 or cv <= 0 or abs(total - 1.0) > 1e-9:
        raise ValueError(
            f"traffic needs rate_per_s > 0, interarrival_cv > 0 and row "
            f"probabilities summing to 1 (got {rate}, {cv}, {total})")
    rng = np.random.default_rng([int(seed), 0xA221])
    # a gamma renewal process with shape 1/cv^2 has mean gap 1/rate and
    # coefficient of variation cv; shape 1 is the exponential
    shape = 1.0 / (cv * cv)
    n = int(rate * seconds * 1.5) + 64
    due = np.cumsum(rng.gamma(shape, 1.0 / (rate * shape), n))
    while due[-1] < seconds:  # a rare short draw: extend, same stream
        due = np.concatenate(
            [due, due[-1] + np.cumsum(rng.gamma(shape, 1.0 / (rate * shape),
                                                n))])
    due = due[due < seconds]
    rows = rng.choice([k for k, _ in sizes], size=len(due),
                      p=[p for _, p in sizes])
    offset = rng.integers(0, int(traffic["pool"]), size=len(due))
    return due, rows.astype(np.int64), offset.astype(np.int64)
