"""jax backend-compile events (a compile or a load from the persistent
cache) inside the measured window. Must be 0: anything else also makes
``correct`` false. Paired with ``setup_s`` because a change that skips
warm-up lowers one and raises the other."""
LAYER = "compile"
MOVES = "setup_s"
UNIT = "count"


def applies(run):
    return True


def compute(run):
    return run["bench"].window_compile["backend_compile"]["n"]
