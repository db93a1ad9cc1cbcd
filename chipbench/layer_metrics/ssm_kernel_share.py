"""Kernel over (kernel + XLA) decisions of ``kernels.dispatch`` for the
selective scan, forward (``selective_scan``) and backward
(``selective_scan_bwd``, decided inside the forward's ``custom_vjp``)
together: 100 where both run as Pallas kernels, 50 where the backward is
the chunked XLA recomputation (a count at trace time that repeats
exactly). Nothing to read where the program has no such family, or the
step holds no scan."""
LAYER = "kernels"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    recs = [run["dispatch_stats"].get(f)
            for f in ("selective_scan", "selective_scan_bwd")]
    kernel = sum(r["kernel"] for r in recs if r)
    total = kernel + sum(r["xla"] for r in recs if r)
    return 100.0 * kernel / total if total else None
