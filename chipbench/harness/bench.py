"""One run of one cell: what every mode shares.

A ``Bench`` carries the cell's data (workload, configuration, the
configuration's ``model.py``), the clocks (set-up runs from process start
to ``setup_done()``), jax's compile events split into set-up and window,
and the profiler session of a ``--trace 1`` run. A mode (``modes/<mode>.py``)
drives the system under test and returns an ``Outcome``; ``run.py`` turns
it into the last line.
"""
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import time

from . import trace_reduce
from .compile_events import CompileEvents


def load_module(path):
    """Import the file at ``path`` under a name of its own (two cells'
    ``model.py`` never meet in ``sys.modules``)."""
    name = "chipbench_file_" + "_".join(
        os.path.normpath(path).split(os.sep)[-3:]).replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Outcome:
    """What a mode hands back. ``run`` is the bag of observations the
    per-layer readers pick from (``layer_metrics/<name>.py``)."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict   # metric name -> value, without setup_s
    run: dict
    notes: dict        # goes on an earlier line, never on the last


class Bench:
    def __init__(self, bench_dir, workload, seed, seconds, trace, t0):
        self.dir = bench_dir
        self.name = workload
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t0 = t0
        self.workload = load_json(
            os.path.join(bench_dir, "workloads", f"{workload}.json"))
        cfg_dir = os.path.join(bench_dir, "configs",
                               self.workload["config"])
        self.cfg = load_json(os.path.join(cfg_dir, "config.json"))
        self.model = load_module(os.path.join(cfg_dir, "model.py"))
        self.mode = load_module(os.path.join(
            bench_dir, "modes", f"{self.workload['mode']}.py"))
        self.traffic = self.workload["traffic"]
        self.chips = int(self.workload["chips"])
        self.events = CompileEvents()
        self._mark0 = self.events.mark()
        self._mark_setup = None
        self.setup_s = None
        self.setup_compile = None
        self.window_compile = None
        self._trace_dir = os.path.join(
            os.path.dirname(os.path.abspath(bench_dir)),
            ".chipbench_trace", workload)
        self._tracing = False
        self.reduced = None    # the reduced trace of a --trace 1 run

    # ----------------------------------------------------------- clocks --
    def setup_done(self):
        """Set-up ends here: import, weights, tracing, compile or cache
        load and warm-up are behind us; the window opens."""
        self.setup_s = time.perf_counter() - self.t0
        self._mark_setup = self.events.mark()
        self.setup_compile = self.events.since(self._mark0)

    def window_closed(self):
        """The window is over: whatever compiled since ``setup_done``
        compiled under measurement."""
        self.window_compile = self.events.since(self._mark_setup)

    # ---------------------------------------------------------- tracing --
    def span(self, name):
        """A host span on the profiler's clock while a trace is being
        taken; free otherwise."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def trace_start(self):
        import jax

        shutil.rmtree(self._trace_dir, ignore_errors=True)
        os.makedirs(self._trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        # no Python tracer (one event per Python call would swamp the
        # trace and slow the host threads the serving cell measures) and
        # host level 1: TraceAnnotation spans and the runtime's launch
        # events, not every internal of it
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self._tracing = True
        self._window = jax.profiler.TraceAnnotation(
            trace_reduce.WINDOW_SPAN)
        self._window.__enter__()

    def trace_stop(self):
        """Close the session and reduce it; the raw trace stays under
        ``.chipbench_trace/<cell>`` for reading by hand."""
        import jax

        self._window.__exit__(None, None, None)
        self._tracing = False
        jax.profiler.stop_trace()
        self.reduced = trace_reduce.load(
            trace_reduce.find_xplane(self._trace_dir))
        return self.reduced
