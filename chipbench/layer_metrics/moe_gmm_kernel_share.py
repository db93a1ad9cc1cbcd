"""The ``grouped_matmul`` family's Pallas kernels' share of the expert
layer's grouped products' device self time in the traced window, in %: how
much of the products' time the kernel took over from XLA's ``ragged-dot-*``
kernels (``harness/grouped_products.py``). It reads 0 where XLA runs every
product. Nothing to read where the configuration has no expert layer, the
trace no device or the window no grouped product."""
from chipbench.harness import grouped_products

LAYER = "experts"
MOVES = "train_samples_per_s"
UNIT = "%"


def applies(run):
    return run["mode"] == "train"


def compute(run):
    split = grouped_products.self_seconds(run)
    if split is None:
        return None
    kernel_s, xla_s = split
    return 100.0 * kernel_s / (kernel_s + xla_s)
