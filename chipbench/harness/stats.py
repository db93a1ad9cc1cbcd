"""Order statistics, unrounded. ``percentile`` is the nearest-rank rule of
``tools/loadgen.py._percentiles`` (``serving.metrics.percentile``) without
its rounding to three places: a value goes out as measured."""


def percentile(values, q):
    """Nearest-rank ``q``-th percentile (0..100) of ``values``; None for
    an empty sample."""
    if not values:
        return None
    xs = sorted(values)
    k = int(round(q / 100.0 * (len(xs) - 1)))
    return xs[max(0, min(len(xs) - 1, k))]


def median(values):
    return percentile(values, 50)


def spread(values):
    """Distance between the quartiles over the median: the run-to-run
    spread the driver reads off a set of runs."""
    med = median(values)
    if not med:
        return None
    return (percentile(values, 75) - percentile(values, 25)) / abs(med)
