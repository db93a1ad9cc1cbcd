"""From a jax profiler trace to numbers: the one reduction every PR shares.

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into a plain, JSON-able dict (``trace_fixture.json`` beside this file is
one such dict, cut from a recorded trace; ``tests/chipbench_tests`` checks
every function below on it against hand-computed values)::

    {"devices": {"0": [[name, start_ns, dur_ns], ...], ...},   # "XLA Ops"
     "async":   {"0": [[name, start_ns, dur_ns], ...], ...},   # "Async XLA Ops"
     "host":    {"<thread line>": [[name, start_ns, dur_ns], ...], ...}}

Device events are the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane:
one event per executed HLO instruction. The trace prints the instruction's
whole HLO text as its name; ``short_name`` keeps the instruction's own
name, its opcode (with a fusion's kind or a custom call's target) and its
result shape, e.g. ``fusion.103 fusion:kLoop bf16[128,256,56,56]`` or
``jvp__.19 custom-call:tpu_custom_call bf16[384,384,64]``. ``async`` holds
the ``Async XLA Ops`` line (transfers and collectives that run beside the
instruction stream). Host events are the benchmark's own
``TraceAnnotation`` spans (names starting with one of ``SPAN_PREFIXES``).
The profiler stamps host and device on one clock, to about a millisecond
(a chip probe, PR 22, showed the device a millisecond early against the
host call that launched it).

All intervals are ``(start_ns, end_ns)``, half-open, merged and sorted
where a function says "union".
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIXES = ("chipbench.", "train.", "serve.")
WINDOW_SPAN = "chipbench.traced_window"
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
    r"(-start|-done)?$")
PALLAS = "custom-call:tpu_custom_call"
NO_SPAN = "(no benchmark span)"
EMPTY = {"devices": {}, "async": {}, "host": {}}   # a run that took no trace

_INSTR = re.compile(r"^%?([^\s=]+) = .*?\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r" = \(?([a-z0-9]+\[[0-9,]*\])")
_DETAIL = {"fusion": re.compile(r"kind=(k\w+)"),
           "custom-call": re.compile(r'custom_call_target="([^"]+)"')}


def short_name(text):
    """``<instruction> <opcode>[:<detail>] <result shape>`` of the HLO
    text a device event is named with; where it does not parse, the
    text itself, cut, as one word (so that it has no opcode field)."""
    m = _INSTR.match(text)
    if not m:
        return text[:80].replace(" ", "_")
    instr, opcode = m.groups()
    detail = _DETAIL.get(opcode)
    detail = detail.search(text) if detail else None
    shape = _SHAPE.search(text)
    return (f"{instr} {opcode}{':' + detail.group(1) if detail else ''}"
            f"{' ' + shape.group(1) if shape else ''}")


def opcode(name):
    """The opcode field of a short name (``fusion``, ``all-reduce`` ...),
    without its detail."""
    parts = name.split(" ")
    return parts[1].split(":")[0] if len(parts) > 1 else ""


# ------------------------------------------------------------- reading ---

def find_xplane(log_dir):
    """The one ``.xplane.pb`` a ``jax.profiler`` session left under
    ``log_dir``."""
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path):
    """Read ``path`` into the plain dict described above."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = {"devices": {}, "async": {}, "host": {}}
    names = {}   # every step repeats the same few thousand instructions

    def short(text):
        if text not in names:
            names[text] = short_name(text)
        return names[text]

    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                kind = {OPS_LINE: "devices", ASYNC_LINE: "async"}.get(
                    line.name)
                if kind:
                    trace[kind].setdefault(m.group(1), []).extend(
                        [short(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)] for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIXES)]
                if spans:
                    trace["host"].setdefault(line.name, []).extend(spans)
    for group in trace.values():
        for events in group.values():
            events.sort(key=lambda e: (e[1], -e[2]))
    return trace


def describe(path, top=12):
    """What a trace holds, for reading one by hand: every plane and line
    with its event count and most frequent names."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            names = Counter(ev.name for ev in line.events)
            lines.append({"line": line.name, "events": sum(names.values()),
                          "top_names": names.most_common(top)})
        out.append({"plane": plane.name, "lines": lines})
    return out


def cut(trace, lo, hi, devices=None):
    """A piece of ``trace`` small enough to keep as a fixture: the device
    events that lie wholly inside ``[lo, hi)`` (of ``devices`` only, where
    given), the host spans clipped to it, every time rebased to ``lo``."""
    out = {"devices": {}, "async": {}, "host": {}}
    for kind in ("devices", "async"):
        for dev, events in trace.get(kind, {}).items():
            if devices is not None and dev not in devices:
                continue
            kept = [[n, s - lo, d] for n, s, d in events
                    if s >= lo and s + d <= hi]
            if kept:
                out[kind][dev] = kept
    for line, events in trace["host"].items():
        kept = [[n, max(s, lo) - lo, min(s + d, hi) - max(s, lo)]
                for n, s, d in events if s + d > lo and s < hi]
        if kept:
            out["host"][line] = kept
    return out


# ----------------------------------------------------------- intervals ---

def union(intervals):
    """Merged, sorted union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of union ``a`` that union ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(intervals, lo, hi):
    return total(clip(intervals, lo, hi))


def _spans(events):
    return [(s, s + d) for _, s, d in events]


# ------------------------------------------------------------- window ----

def window(trace):
    """The traced window ``(start, end)``: the benchmark's own
    ``chipbench.traced_window`` span where it is in the trace, else the
    extent of the device events."""
    for events in trace["host"].values():
        for name, s, d in events:
            if name == WINDOW_SPAN:
                return s, s + d
    starts = [ev[1] for evs in trace["devices"].values() for ev in evs]
    ends = [ev[1] + ev[2] for evs in trace["devices"].values()
            for ev in evs]
    if not starts:
        raise ValueError("the trace holds no device event and no "
                         f"{WINDOW_SPAN} span")
    return min(starts), max(ends)


# --------------------------------------------------------- busy / idle ---

def busy(trace, win=None):
    """``{device: union of its op intervals inside the window}``."""
    lo, hi = win or window(trace)
    return {dev: clip(union(_spans(events)), lo, hi)
            for dev, events in trace["devices"].items()}


def busy_seconds(trace, win=None):
    """Seconds in which an operation ran, averaged over the devices."""
    per = busy(trace, win)
    if not per:
        return 0.0
    return sum(total(iv) for iv in per.values()) / len(per) / 1e9


def idle_share(trace, win=None):
    """1 - busy / window, averaged over the devices; None without a
    device plane."""
    if not trace["devices"]:
        return None
    lo, hi = win or window(trace)
    return 1.0 - busy_seconds(trace, (lo, hi)) / ((hi - lo) / 1e9)


# ---------------------------------------------------------- op table -----

def self_times(events):
    """``[(name, self_ns)]``: each event's duration less the part its
    directly nested events cover (a ``while`` keeps only its own
    overhead, its body's instructions keep theirs)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    self_ns = [d for _, _, d in order]
    stack = []  # indices of open events
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            p_end = order[p][1] + order[p][2]
            self_ns[p] -= max(0, min(s + d, p_end) - s)
        stack.append(i)
    return [(order[i][0], max(0, self_ns[i])) for i in range(len(order))]


def _in_window(events, lo, hi):
    return [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
            for n, s, d in events if min(s + d, hi) > max(s, lo)]


def op_table(trace, win=None, top=10):
    """``[[name, seconds]]`` of the ``top`` device operations by self
    time inside the window, summed over occurrences and averaged over
    the devices."""
    lo, hi = win or window(trace)
    acc = {}
    for events in trace["devices"].values():
        for name, ns in self_times(_in_window(events, lo, hi)):
            acc[name] = acc.get(name, 0) + ns
    n_dev = max(1, len(trace["devices"]))
    table = sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))[:top]
    return [[name, ns / n_dev / 1e9] for name, ns in table]


def time_share(trace, match, win=None):
    """Self time of the device events whose name satisfies ``match``,
    as a share of device busy time inside the window; None when nothing
    ran."""
    lo, hi = win or window(trace)
    hit = all_ns = 0
    for events in trace["devices"].values():
        for name, ns in self_times(_in_window(events, lo, hi)):
            all_ns += ns
            if match(name):
                hit += ns
    return hit / all_ns if all_ns else None


# ------------------------------------------------------ gap attribution --

def host_spans(trace, exclude=(WINDOW_SPAN,)):
    """Every benchmark span of every host thread: ``[(name, s, e)]``."""
    return [(n, s, s + d) for events in trace["host"].values()
            for n, s, d in events if n not in exclude]


def attribute(lo, hi, spans):
    """The span name covering most of ``[lo, hi)``; ``NO_SPAN`` when no
    span touches it. Ties go to the name that sorts first."""
    cover = {}
    for name, s, e in spans:
        ov = min(e, hi) - max(s, lo)
        if ov > 0:
            cover[name] = cover.get(name, 0) + ov
    if not cover:
        return NO_SPAN
    return min(cover, key=lambda n: (-cover[n], n))


def idle_gaps(trace, win=None, top=10):
    """``[[span name, seconds]]``: the device's idle time inside the
    window by what the host was doing, averaged over the devices,
    largest first. A gap belongs to the benchmark span that covers most
    of it."""
    lo, hi = win or window(trace)
    spans = host_spans(trace)
    acc = {}
    for iv in busy(trace, (lo, hi)).values():
        for s, e in subtract([(lo, hi)], iv):
            name = attribute(s, e, spans)
            acc[name] = acc.get(name, 0) + (e - s)
    n_dev = max(1, len(trace["devices"]))
    table = sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))[:top]
    return [[name, ns / n_dev / 1e9] for name, ns in table]


def step_gaps_ms(trace, call="train.step_call", read="train.loss_read"):
    """Per step: wall time from the start of the ``call`` span to the end
    of the ``read`` span that follows it, less the device's busy time
    inside it (averaged over the devices), in milliseconds."""
    per_dev = list(busy(trace, (0, 2 ** 62)).values())
    out = []
    for events in trace["host"].values():
        calls = [(s, s + d) for n, s, d in events if n == call]
        reads = [(s, s + d) for n, s, d in events if n == read]
        for cs, ce in calls:
            after = [r for r in reads if r[0] >= ce]
            if not after or not per_dev:
                continue
            end = after[0][1]
            on = sum(overlap(iv, cs, end) for iv in per_dev) / len(per_dev)
            out.append(((end - cs) - on) / 1e6)
    return out


# --------------------------------------------------------- collectives ---

def collective_intervals(events):
    """Intervals in which a collective is in flight on one device: a
    synchronous ``all-reduce`` is its own event; an asynchronous one runs
    from the start of its ``all-reduce-start`` to the end of the
    ``all-reduce-done`` that follows it."""
    out = []
    open_starts = {}
    for name, s, d in sorted(events, key=lambda e: e[1]):
        m = COLLECTIVE.match(opcode(name))
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            open_starts.setdefault(kind, []).append(s)
            out.append((s, s + d))
        elif phase == "-done":
            pending = open_starts.get(kind)
            out.append((pending.pop(0) if pending else s, s + d))
        else:
            out.append((s, s + d))
    return union(out)


def compute_intervals(events):
    """Union of the non-collective instructions that nest no other
    event (a ``while`` or a ``call`` is its body, not compute)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    leaves = []
    for i, (name, s, d) in enumerate(order):
        nxt = order[i + 1] if i + 1 < len(order) else None
        has_child = nxt is not None and nxt[1] < s + d and \
            nxt[1] + nxt[2] <= s + d and (nxt[1], nxt[2]) != (s, d)
        if not has_child and not COLLECTIVE.match(opcode(name)):
            leaves.append((s, s + d))
    return union(leaves)


def collective_split(trace, win=None):
    """``{"total_s", "exposed_s", "hidden_s", "window_s"}`` averaged over
    the devices: time with a collective in flight, and the part of it
    during which no compute instruction runs on that device."""
    lo, hi = win or window(trace)
    tot = exp = 0
    for dev, events in trace["devices"].items():
        beside = trace.get("async", {}).get(dev, [])
        coll = clip(collective_intervals(events + beside), lo, hi)
        comp = clip(compute_intervals(events), lo, hi)
        tot += total(coll)
        exp += total(subtract(coll, comp))
    n_dev = max(1, len(trace["devices"]))
    return {"total_s": tot / n_dev / 1e9, "exposed_s": exp / n_dev / 1e9,
            "hidden_s": (tot - exp) / n_dev / 1e9,
            "window_s": (hi - lo) / 1e9}
