"""Device self time a step under one of the program's scopes, for readers
of a single layer: a row of the by-scope table ``step_phases`` makes of a
traced run, with the busy time it is a share of."""
from . import step_phases


def scope_ms(run, scope):
    """``(ms a step under scope, forward and backward together; busy ms a
    step)``, or None where the run has no joined trace or its step names
    no such scope (the parent commit; an executable another tree
    cached)."""
    reduced = step_phases.of_run(run)
    if reduced is None or not reduced["busy_ms"]:
        return None
    ms = sum(reduced["by_scope_ms"].get(scope, {}).values())
    return (ms, reduced["busy_ms"]) if ms else None
