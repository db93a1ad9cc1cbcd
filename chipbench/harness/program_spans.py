"""The device's idle time of a traced training run, by what the PROGRAM
was doing: the reduction behind the ``trainer_idle_*_ms`` readers and
``trainer_programs_per_step``.

``trace_reduce`` charges every idle gap to the benchmark's own span around
the whole ``trainer.step`` call. The program times its host work itself
(``mxnet_tpu/telemetry/trace.py``: every ``trace.span`` enters a
``TraceAnnotation``), so under the benchmark's profiler session the spans
of ``ShardedTrainer.step`` sit in the trace's host plane, on the clock the
device events are on. This module reads, from the ``.xplane.pb`` the traced
run left,

* the host line that holds ``trainer.step``: the program's spans (names
  starting with one of ``PROGRAM_PREFIXES``) and jit's own launch events
  (``PjitFunction(<fn>)``);
* the ``XLA Modules`` line of each device plane: one event per device
  program launched, named ``jit_<fn>(<fingerprint>)``;

and takes the window and each device's busy intervals from the trace as
``trace_reduce`` already reduced it. Together they make a plain, JSON-able
dict (``program_spans_fixture.json`` beside this file is one, cut from a
recorded trace; times in ns)::

    {"window": [lo, hi],
     "spans": [[name, start, dur], ...],       # the step's host line
     "launches": [[fn, start, dur], ...],      # same line
     "modules": {"0": [[name, start, dur], ...], ...},
     "busy": {"0": [[start, end], ...], ...}}  # union of the op intervals

``reduce`` cuts the window at every span boundary, names each piece after
the DEEPEST span that covers it (a gap that straddles two spans is split
at the boundary), lays each device's idle intervals over the pieces,
averages over the devices and divides by the traced steps. The six groups
of ``IDLE_GROUPS`` are a partition: they sum to the device's idle time a
step. A program without these spans (a parent commit, a serving cell)
gives ``None`` everywhere and raises nothing; a CPU trace has no device
plane, so only the spans' own durations are read.

The host's and the device's clocks agree only to about a millisecond.
``clock`` measures it from causality: a program cannot start on the device
before the host launched it, and the host cannot wake from the guard's
blocking read before the device finished. The smallest launch-to-start
and end-to-wake delays bracket the offset; an idle metric smaller than the
bracket is indistinguishable from zero.
"""
import json
import re

from . import stats, trace_reduce

PROGRAM_PREFIXES = ("trainer.", "compile.", "io.")
STEP = "trainer.step"
SYNC = "trainer.guard_sync"
MODULES_LINE = "XLA Modules"
CALLER = "(caller)"
LAUNCH = re.compile(r"^PjitFunction\((.+)\)$")
MODULE = re.compile(r"^(.+?)(\(\d+\))?$")

#: the children of ``trainer.step`` each idle metric reads; what is under
#: ``trainer.step`` and under none of these is ``bookkeeping``, what is
#: outside every ``trainer.step`` is ``caller``
IDLE_GROUPS = {
    "prepare": ("trainer.put_batch", "trainer.rng_key", "trainer.scalars",
                "trainer.gather"),
    "dispatch": ("trainer.dispatch",),
    "commit": ("trainer.commit", "trainer.release"),
    "sync": (SYNC,),
    "bookkeeping": ("trainer.bookkeeping",),
    "caller": (),
}
_GROUP_OF = {name: group for group, names in IDLE_GROUPS.items()
             for name in names}


# ------------------------------------------------------------- reading ---

def load(path):
    """``spans``, ``launches`` and ``modules`` of the trace at ``path``
    (empty where the trace holds no ``trainer.step``)."""
    from jax.profiler import ProfileData

    out = {"spans": [], "launches": [], "modules": {}}
    for plane in ProfileData.from_file(path).planes:
        dev = trace_reduce.DEVICE_PLANE.match(plane.name)
        if dev:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    out["modules"][dev.group(1)] = sorted(
                        ([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                         for ev in line.events), key=lambda e: e[1])
        elif plane.name.startswith("/host:") and not out["spans"]:
            for line in plane.lines:
                spans, launches = [], []
                for ev in line.events:
                    launch = LAUNCH.match(ev.name)
                    if launch:
                        launches.append([launch.group(1), int(ev.start_ns),
                                         int(ev.duration_ns)])
                    elif ev.name.startswith(PROGRAM_PREFIXES):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
                if any(name == STEP for name, _, _ in spans):
                    out["spans"] = sorted(spans,
                                          key=lambda e: (e[1], -e[2]))
                    out["launches"] = outermost(launches)
                    break
    return out


def outermost(events):
    """``events`` sorted by start, less those that lie inside the one kept
    before them: jit names every call twice, one event inside the other."""
    out = []
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        if not out or ev[1] + ev[2] > out[-1][1] + out[-1][2]:
            out.append(ev)
    return out


def cut(data, lo, hi, coalesce_ns=1000):
    """A piece of ``data`` small enough to keep as a fixture: what lies
    wholly inside ``[lo, hi)``, rebased to ``lo``, with the busy intervals
    joined across gaps shorter than ``coalesce_ns`` (a step runs some
    5,000 instructions a few nanoseconds apart)."""
    def inside(events):
        return [[n, s - lo, d] for n, s, d in events
                if s >= lo and s + d <= hi]

    busy = {}
    for dev, intervals in data["busy"].items():
        joined = []
        for s, e in trace_reduce.clip([tuple(i) for i in intervals],
                                      lo, hi):
            if joined and s - lo - joined[-1][1] < coalesce_ns:
                joined[-1][1] = e - lo
            else:
                joined.append([s - lo, e - lo])
        busy[dev] = joined
    return {"window": [0, hi - lo], "spans": inside(data["spans"]),
            "launches": inside(data["launches"]),
            "modules": {dev: inside(events)
                        for dev, events in data["modules"].items()},
            "busy": busy}


# ----------------------------------------------------------- reduction ---

def pieces(spans, lo, hi):
    """``[(start, end, path)]``: ``[lo, hi)`` cut at every span boundary;
    ``path`` holds the names of the spans that cover the piece, outermost
    first (``()`` where none does)."""
    out, stack, cur = [], [], lo   # stack: open spans as (end, name)

    def emit(upto):
        nonlocal cur
        upto = min(upto, hi)
        if upto > cur:
            out.append((cur, upto, tuple(name for _, name in stack)))
            cur = upto

    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((s + d, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return out


def group_of(path):
    """The idle metric a piece with this span path is charged to."""
    if not path or path[0] != STEP:
        return "caller"
    if len(path) == 1:
        return "bookkeeping"
    return _GROUP_OF.get(path[1], "bookkeeping")


def charge(idle, cuts):
    """Nanoseconds of the ``idle`` intervals inside each piece of
    ``cuts`` (both sorted and disjoint): one sweep over the two, since a
    traced window holds some 10^5 gaps between instructions."""
    out, j = [0] * len(cuts), 0
    for i, (s, e, _) in enumerate(cuts):
        while j < len(idle) and idle[j][1] <= s:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < e:
            out[i] += min(idle[k][1], e) - max(idle[k][0], s)
            k += 1
        j = max(j, k - 1)   # the last one may reach into the next piece
    return out


def _median_ms(spans):
    """Median duration by span name, and ``trainer.step (self)``: a step
    less its direct children."""
    by_name = {}
    for name, _, d in spans:
        by_name.setdefault(name, []).append(d / 1e6)
    selfs = []
    for _, s, d in (e for e in spans if e[0] == STEP):
        inner = trace_reduce.union(
            [(cs, cs + cd) for n, cs, cd in spans
             if n != STEP and cs >= s and cs + cd <= s + d])
        selfs.append((d - trace_reduce.total(inner)) / 1e6)
    out = {name: stats.median(v) for name, v in sorted(by_name.items())}
    if selfs:
        out[f"{STEP} (self)"] = stats.median(selfs)
    return out


def _program(name):
    """``jit_step_fn`` of ``jit_step_fn(15338888243265898187)``."""
    return MODULE.match(name).group(1)


def paired(host, dev, near_ns=10_000_000):
    """The k-th launch with the k-th start, in order. The window's edge
    may have cut an event off either list (the clocks differ), so one may
    be dropped at either end; of the ways that leave every pair within
    ``near_ns`` (steps lie further apart), the closest. ``[]`` if none."""
    best = []
    for h0, h1, d0, d1 in ((0, 0, 0, 0), (1, 0, 0, 0), (0, 1, 0, 0),
                           (0, 0, 1, 0), (0, 0, 0, 1), (1, 0, 0, 1),
                           (0, 1, 1, 0)):
        h, d = host[h0:len(host) - h1], dev[d0:len(dev) - d1]
        if h and len(h) == len(d) and \
                all(abs(b - a) < near_ns for a, b in zip(h, d)):
            pairs = list(zip(h, d))
            if not best or (len(pairs), -sum(abs(b - a) for a, b in pairs)) \
                    > (len(best), -sum(abs(b - a) for a, b in best)):
                best = pairs
    return best


def clock(data):
    """How far the host's and the device's clocks can be apart, in
    microseconds: per program the smallest and the median delay from the
    host's launch (``PjitFunction(<fn>)`` start) to the start of its
    ``XLA Modules`` event, and from the end of the step's program to the
    return of the guard's blocking read. The device's clock reads
    ``offset`` later than the host's with ``-min(end_to_wake) <= offset <=
    min(launch_to_start)``. None where the trace has no device plane."""
    lo, hi = data["window"]
    launch, wake = {}, []
    for events in data["modules"].values():
        mods = [e for e in events if lo <= e[1] < hi]
        for fn in {fn for fn, _, _ in data["launches"]}:
            host = [s for f, s, _ in data["launches"]
                    if f == fn and lo <= s < hi]
            dev = [s for n, s, _ in mods if _program(n) == f"jit_{fn}"]
            launch.setdefault(f"jit_{fn}", []).extend(
                (d - h) / 1e3 for h, d in paired(host, dev))
        for _, s, d in (e for e in data["spans"] if e[0] == SYNC):
            ends = [ms + md for _, ms, md in mods
                    if ms < s + d and ms + md > s]
            if ends:
                wake.append((s + d - max(ends)) / 1e3)
    if not any(launch.values()) and not wake:
        return None
    out = {"launch_to_start_us": {
        name: {"min": min(v), "median": stats.median(v), "n": len(v)}
        for name, v in sorted(launch.items()) if v}}
    if wake:
        out["end_to_wake_us"] = {"min": min(wake),
                                 "median": stats.median(wake),
                                 "n": len(wake)}
    if out["launch_to_start_us"] and wake:
        out["device_minus_host_us"] = [
            -min(wake), min(v["min"]
                            for v in out["launch_to_start_us"].values())]
    return out


def reduce(data):
    """The numbers of one traced run::

        {"steps": traced steps (``trainer.step`` spans inside the window),
         "median_ms": {span name: median duration},
         "idle_ms": {group: device idle ms a step} or None,
         "idle_by_span_ms": {deepest span name: same} or None,
         "programs_per_step": device programs launched a step or None,
         "programs": {program: launches a step} or None,
         "clock": see ``clock``}

    None for a trace without a ``trainer.step`` inside the window."""
    lo, hi = data["window"]
    spans = [e for e in data["spans"] if e[1] >= lo and e[1] + e[2] <= hi]
    steps = sum(1 for name, _, _ in spans if name == STEP)
    if not steps:
        return None
    out = {"steps": steps, "median_ms": _median_ms(spans),
           "idle_ms": None, "idle_by_span_ms": None,
           "programs_per_step": None, "programs": None,
           "clock": clock(data)}
    if data["busy"]:
        cuts = pieces(spans, lo, hi)
        by_group = dict.fromkeys(IDLE_GROUPS, 0)
        by_span = {}
        for intervals in data["busy"].values():
            idle = trace_reduce.subtract(
                [(lo, hi)], [tuple(i) for i in intervals])
            for (_, _, path), ns in zip(cuts, charge(idle, cuts)):
                if ns:
                    by_group[group_of(path)] += ns
                    leaf = path[-1] if path else CALLER
                    by_span[leaf] = by_span.get(leaf, 0) + ns
        per = len(data["busy"]) * steps * 1e6
        out["idle_ms"] = {g: ns / per for g, ns in by_group.items()}
        out["idle_by_span_ms"] = {
            n: ns / per for n, ns in
            sorted(by_span.items(), key=lambda kv: (-kv[1], kv[0]))}
    if data["modules"]:
        counts = {}
        for events in data["modules"].values():
            for name, s, _ in events:
                if lo <= s < hi:
                    counts[_program(name)] = counts.get(
                        _program(name), 0) + 1
        per = len(data["modules"]) * steps
        out["programs"] = {n: c / per for n, c in sorted(counts.items())}
        out["programs_per_step"] = sum(counts.values()) / per
    return out


# ------------------------------------------------------- for the readers --

def of_run(run):
    """``reduce`` of the trace this run took (None where it took none or
    the program emitted no step span). Read once, kept in the run's bag
    for the other readers; prints the ``# program_spans`` and ``# clock``
    lines when it reads."""
    if "program_spans" in run:
        return run["program_spans"]
    bench = run.get("bench")
    reduced = None
    if bench is not None and bench.trace:
        try:
            data = load(trace_reduce.find_xplane(bench._trace_dir))
        except FileNotFoundError:
            data = {"spans": []}
        if data["spans"]:
            win = trace_reduce.window(run["trace"])
            data["window"] = list(win)
            data["busy"] = trace_reduce.busy(run["trace"], win)
            reduced = reduce(data)
    if reduced is not None:
        note = {k: reduced[k] for k in (
            "steps", "median_ms", "idle_by_span_ms", "programs")}
        print(f"# program_spans: {json.dumps(note)}", flush=True)
        print(f"# clock: {json.dumps(reduced['clock'])}", flush=True)
    run["program_spans"] = reduced
    return reduced


def idle_ms(run, group):
    """Device idle ms a step under the spans of ``IDLE_GROUPS[group]``."""
    reduced = of_run(run)
    if reduced is None or reduced["idle_ms"] is None:
        return None
    return reduced["idle_ms"][group]


def programs_per_step(run):
    reduced = of_run(run)
    return None if reduced is None else reduced["programs_per_step"]
