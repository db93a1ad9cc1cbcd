"""Seconds of set-up inside jax backend-compile events: real compiles on
a cold start, loads from the persistent cache on a warm one."""
LAYER = "compile"
MOVES = "setup_s"
UNIT = "s"


def applies(run):
    return True


def compute(run):
    return run["bench"].setup_compile["backend_compile"]["s"]
