"""jax's own compile events: the ground truth under ``compile.stats()``,
which cannot see a retrace inside an executable it already holds
(PERF.md, PR 21). ``backend_compile`` fires for a real compile AND for a
load from the persistent cache, so its seconds are "compile or cache
load"; ``trace`` and ``lower`` are the Python-side work no cache removes.
"""

EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
}


class CompileEvents:
    """Counts and seconds per event kind since construction; ``mark()``
    returns a snapshot, ``since(mark)`` the difference to now."""

    def __init__(self):
        import jax

        self._n = dict.fromkeys(EVENTS.values(), 0)
        self._s = dict.fromkeys(EVENTS.values(), 0.0)

        def on_event(event, duration, **_):
            kind = EVENTS.get(event)
            if kind is not None:
                self._n[kind] += 1
                self._s[kind] += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def mark(self):
        return dict(self._n), dict(self._s)

    def since(self, mark):
        n0, s0 = mark
        return {k: {"n": self._n[k] - n0[k], "s": self._s[k] - s0[k]}
                for k in self._n}
