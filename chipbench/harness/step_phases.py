"""The device's BUSY time of a traced training run, by the part of the
compiled step each instruction belongs to: the reduction behind the
``step_*_ms`` and ``step_phase_*_share`` readers (``program_spans`` holds
the idle half).

A TPU trace's ``XLA Ops`` events are named with the instruction's HLO text
and carry no ``op_name``; ``trace_reduce.short_name`` keeps the
instruction's own name (``fusion.103``, ``jvp_mla.attention_.6``). The
optimized HLO the program hands out (``mxnet_tpu.compile.program_texts``:
the text of the executable jit made of the recorded step) carries ``metadata={op_name="jit(step_fn)/.../transpose(jvp(...))/
..."}`` on its instructions. jax marks forward (``jvp(``) and backward
(``transpose(jvp(``) itself, custom-vjp kernels included; the step names
its update and its guard (``trainer.update``, ``trainer.guard``:
``sharded_trainer.UPDATE_SCOPE`` / ``GUARD_SCOPE``). ``parse`` turns a text
into a map by instruction name, ``phase_of`` names a phase, ``reduce``
joins a trace's events to the map of the module they ran in. Its data is a
plain, JSON-able dict (``step_phases_fixture.json`` beside this file is
one, cut from a recorded run; times in ns)::

    {"window": [lo, hi], "steps": traced steps,
     "ops": {"0": [[short name, start, dur], ...], ...},     # "XLA Ops"
     "modules": {"0": [[name, start, dur], ...], ...},       # "XLA Modules"
     "maps": {"jit_step_fn": parse(text), ...}}

**The rule** (``phase_of``), in this order: a collective by OPCODE
(GSPMD's all-reduce inherits the dW product's name) -> ``collective``;
``trainer.update`` in the name -> ``update``; ``transpose(`` -> ``backward``
(a ``jax.checkpoint``ed forward recomputed there counts as backward: it
runs there); ``jvp(`` -> ``forward``; everything else -> ``other``: the
guard's stand-alone reductions, the step's rng split, and every instruction
without metadata. A fusion takes the phase of ITS OWN metadata; where it
has none, the phase most of its inner instructions with metadata carry.
An instruction jax gave no name at all (no ``jit(`` in it: ``""``, XLA's own
``ragged-dot-none``) and no fusion gives one takes after its OPERANDS: the
latest phase among them (update after backward after forward), with that
operand's scope, through chains of such instructions; whoever consumes a
cotangent runs in the backward.
What chose it, read off steps compiled for a described v5e (nothing ran):
the fusion instruction's own metadata names the product's phase (``fusion``
-> ``jvp()/dot_general``, ``fusion.8`` -> ``transpose(jvp())/...``, the
update's ``subtract_convert_fusion*`` with results ``(bf16, f32)`` ->
``trainer.update/...``); XLA fuses the guard's ``is-finite`` + ``reduce``
into the dW product that makes the gradient (``is-finite_reduce_fusion.N``:
own phase backward, inside it one ``convolution`` of the backward and the
guard's reduction), which is why ``mixed`` exists; ``copy-done``,
``slice-done`` and a bare ``custom-call`` carry none, and a layout copy of
an argument carries the argument's name (``opt_raws[3][0]``), so both are
``other``. *What the chip's traces added* (my chip run, PR 34): the
experts' grouped matmuls are Mosaic calls XLA itself makes out of
``ragged_dot`` and names ``ragged-dot-none``, 29.7 ms a step of the language
model's 284 with no jax name on them, which is what the operand clause is
for; a ``copy-done`` of a prefetched argument has only arguments behind it
and stays ``other``.

``reduce`` returns, per step in ms, the five phases (a PARTITION of device
busy time: events of a module whose text the program did not hand out, the
two small programs beside the step, go to ``other``), ``mixed`` (time in
fusions whose inner instructions carry more than one of forward / backward
/ update, or the guard beside any of them: how soft the partition is) and
``unmatched`` (time in events of a module whose text IS held and whose name
is not in it, or whose result shape differs: a stale or wrong join, which
must read ~0), and ``by_scope``: self time a step by the innermost program
scope in the name x phase.

A program without ``program_texts`` (a parent commit), a text without the
step's own names (an executable jax loaded from a cache another tree
filled), a trace without a device plane (the CPU) or without a
``trainer.step`` gives ``None`` everywhere and raises nothing, as
``program_spans`` does.
"""
import bisect
import gc
import json
import re
import time

from . import program_spans, trace_reduce

UPDATE = "trainer.update"
GUARD = "trainer.guard"
PHASES = ("forward", "backward", "update", "collective", "other")
NO_SCOPE = "(none)"
SITE = "trainer"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)]+)")
_OPERAND = re.compile(r"%([^\s,()]+)")
_LATEST = ("update", "backward", "forward")
# a program scope is a dotted lower-case name (``mla.attention``) or one of
# the bare ones below; jax's own path entries (``jit(_where)``,
# ``dot_general``, ``transpose(jvp(...))``) hold no dot
# what sits in a fused computation and computes nothing: a constant keeps
# the name of whoever made it first, a broadcast that of its constant
_NO_WORK = ("parameter", "constant", "iota", "broadcast", "bitcast")
_SCOPE = re.compile(r"(?<![\w.])([a-z][a-z0-9_]*(?:\.[a-z][a-z0-9_]*)+|gmu)"
                    r"(?![\w.\[])")


def phase_of(op_name, opcode=""):
    """The phase of one instruction from its ``op_name`` and its opcode:
    the rule of the module's docstring."""
    if trace_reduce.COLLECTIVE.match(opcode):
        return "collective"
    if UPDATE in op_name:
        return "update"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "other"


def scope_of(op_name):
    """The innermost program scope in ``op_name`` (``mla.attention``,
    ``ssm.scan``, ``trainer.update`` ...), else ``NO_SCOPE``."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else NO_SCOPE


def _label(op_name):
    """What an inner instruction adds to its fusion's ``inside``: its
    phase, or ``guard`` for the guard's own."""
    return "guard" if GUARD in op_name else phase_of(op_name)


def parse(text):
    """``{instruction: {"op_name", "opcode", "shape", "inside"}}`` for
    every instruction of every computation of the HLO ``text`` that is not
    a fused computation (ENTRY, ``while`` bodies and conditions, called
    computations: names are unique in a module). ``opcode`` and ``shape``
    are the fields of ``trace_reduce.short_name`` (``fusion:kLoop``, the
    FIRST result's shape), ``op_name`` is ``""`` without metadata, and
    ``inside`` lists, for a fusion, one label (a phase, or ``guard``) for
    every instruction with metadata of the computation it ``calls=``
    (less ``_NO_WORK``). An instruction with no name of jax's and nothing
    inside that takes a phase after its operands carries it as ``"after":
    [phase, scope]``."""
    computations, body = {}, None
    for line in text.splitlines():
        if body is None:
            head = _COMPUTATION.match(line)
            if head:
                body = computations.setdefault(head.group(1), [])
        elif line.startswith("}"):
            body = None
        else:
            body.append(line.strip().removeprefix("ROOT "))
    fused, rows = set(), {}
    for name, lines in computations.items():
        for line in lines:
            fields = trace_reduce.short_name(line).split(" ")
            if len(fields) < 2:
                continue
            op_name = _OP_NAME.search(line)
            row = {"op_name": op_name.group(1) if op_name else "",
                   "opcode": fields[1],
                   "shape": fields[2] if len(fields) > 2 else "",
                   "inside": []}
            if fields[1].split(":")[0] == "fusion":
                called = _CALLS.search(line)
                if called:
                    row["calls"] = called.group(1)
                    fused.add(called.group(1))
            if "jit(" not in row["op_name"]:
                # the operand list: up to the parenthesis that closes it
                args = line[line.find(fields[1].split(":")[0] + "("):]
                row["operands"] = _OPERAND.findall(args[:args.find(")")])
            rows.setdefault(name, {})[fields[0]] = row
    out = {}
    for name, instructions in rows.items():
        if name in fused:
            continue
        for instr, row in instructions.items():
            inner = rows.get(row.pop("calls", None), {})
            row["inside"] = [_label(r["op_name"]) for r in inner.values()
                             if r["op_name"] and r["opcode"] not in _NO_WORK]
            out[instr] = row
    # operands stand before their users in a computation's text, so one
    # pass in order sees every operand's phase settled
    for row in out.values():
        operands = row.pop("operands", ())
        if operands and phase_of_row(row) == "other":
            found = [(_handed_on(out[o]), scope_of_row(out[o]))
                     for o in operands if o in out]
            best = min((f for f in found if f[0] in _LATEST),
                       key=lambda f: _LATEST.index(f[0]), default=None)
            if best is not None:
                row["after"] = list(best)
    return out


def _handed_on(row):
    """The phase a consumer without a name takes from ``row``: its own, a
    collective's by its name (it sums what a backward product made)."""
    phase = phase_of_row(row)
    return phase_of(row["op_name"]) if phase == "collective" else phase


def scope_of_row(row):
    """The scope of a parsed instruction: its own name's, else the one it
    took with its phase from an operand."""
    scope = scope_of(row["op_name"])
    return row["after"][1] if scope == NO_SCOPE and "after" in row else scope


def phase_of_row(row):
    """The phase of a parsed instruction: its own name's, for a fusion
    without metadata the phase most of its labelled inner instructions
    carry (ties to the first of ``PHASES``), and where neither gives one
    the phase it took after its operands."""
    code = row["opcode"].split(":")[0]
    if row["op_name"] or not row["inside"] \
            or trace_reduce.COLLECTIVE.match(code):
        phase = phase_of(row["op_name"], code)
    else:
        inner = ["other" if p == "guard" else p for p in row["inside"]]
        phase = max(PHASES,
                    key=lambda p: (inner.count(p), -PHASES.index(p)))
    return row["after"][0] if phase == "other" and "after" in row else phase


def is_mixed(row):
    """More than one of forward / backward / update inside one fusion, or
    the guard's reduction beside any of them."""
    inside = set(row["inside"])
    parts = inside & {"forward", "backward", "update"}
    return len(parts) > 1 or ("guard" in inside and bool(parts))


def cut(data, lo, hi, steps, devices=None):
    """A piece of ``data`` small enough to keep as a fixture: the ops that
    lie wholly inside ``[lo, hi)`` (``trace_reduce.cut``; of ``devices``
    only, where given), the module events clipped to it, every time
    rebased to ``lo``, the maps restricted to the instructions left, and
    ``steps`` as the caller counts them in the piece."""
    ops = trace_reduce.cut({"devices": data["ops"], "async": {}, "host": {}},
                           lo, hi, devices)["devices"]
    names = {e[0].split(" ")[0] for events in ops.values() for e in events}
    return {"window": [0, hi - lo], "steps": steps, "ops": ops,
            "modules": {
                dev: [[n, max(s, lo) - lo, min(s + d, hi) - max(s, lo)]
                      for n, s, d in data["modules"].get(dev, [])
                      if s + d > lo and s < hi] for dev in ops},
            "maps": {program: {k: v for k, v in rows.items() if k in names}
                     for program, rows in data["maps"].items()}}


# ----------------------------------------------------------- reduction ---

def _classify(rows, name):
    """``(phase, scope, mixed, matched)`` of the event ``name`` (a short
    name) against its module's map ``rows`` (None: no text is held)."""
    if rows is None:
        return "other", NO_SCOPE, False, True
    fields = name.split(" ")
    row = rows.get(fields[0])
    if row is None or row["shape"] != (fields[2] if len(fields) > 2 else ""):
        return "other", NO_SCOPE, False, False
    return phase_of_row(row), scope_of_row(row), is_mixed(row), True


def reduce(data):
    """The numbers of one traced run (module docstring), or None for a
    trace without a device plane or a traced step::

        {"steps", "busy_ms", "phases_ms": {phase: ms a step},
         "mixed_ms", "unmatched_ms", "by_scope_ms": {scope: {phase: ms}},
         "other_by_opcode_ms": {opcode of what went to ``other``: ms},
         "programs_ms": {program: {"ms": ms a step, "held": its text is}}}"""
    if not data["ops"] or not data["steps"]:
        return None
    lo, hi = data["window"]
    ns = dict.fromkeys(PHASES, 0)
    mixed = unmatched = 0
    by_scope, programs, other = {}, {}, {}
    known = {}   # (program, event name) -> _classify: a step repeats itself
    for dev, events in data["ops"].items():
        modules = sorted(data["modules"].get(dev, []), key=lambda e: e[1])
        starts = [m[1] for m in modules]
        names = [program_spans.MODULE.match(m[0]).group(1) for m in modules]
        order = sorted(([n, max(s, lo), min(s + d, hi) - max(s, lo)]
                        for n, s, d in events if min(s + d, hi) > max(s, lo)),
                       key=lambda e: (e[1], -e[2]))
        selfs = trace_reduce.self_times(order)
        for (name, start, _), (_, self_ns) in zip(order, selfs):
            if not self_ns:
                continue
            # the XLA Modules event that holds the op's start, if any
            i = bisect.bisect_right(starts, start) - 1
            program = names[i] if i >= 0 and \
                start < starts[i] + modules[i][2] else None
            rows = data["maps"].get(program)
            seen = programs.setdefault(
                program or NO_SCOPE, {"ns": 0, "held": rows is not None})
            seen["ns"] += self_ns
            if (program, name) not in known:
                known[program, name] = _classify(rows, name)
            phase, scope, is_mix, matched = known[program, name]
            mixed += self_ns * is_mix
            unmatched += self_ns * (not matched)
            ns[phase] += self_ns
            if phase == "other":
                code = trace_reduce.opcode(name) or name
                other[code] = other.get(code, 0) + self_ns
            cell = by_scope.setdefault(scope, {})
            cell[phase] = cell.get(phase, 0) + self_ns
    per = len(data["ops"]) * data["steps"] * 1e6
    return {
        "steps": data["steps"], "busy_ms": sum(ns.values()) / per,
        "phases_ms": {p: v / per for p, v in ns.items()},
        "mixed_ms": mixed / per, "unmatched_ms": unmatched / per,
        "by_scope_ms": {
            scope: {p: v / per for p, v in sorted(
                cell.items(), key=lambda kv: PHASES.index(kv[0]))}
            for scope, cell in sorted(
                by_scope.items(), key=lambda kv: -sum(kv[1].values()))},
        "other_by_opcode_ms": {
            code: v / per for code, v in sorted(
                other.items(), key=lambda kv: (-kv[1], kv[0]))},
        "programs_ms": {
            name: {"ms": seen["ns"] / per, "held": seen["held"]}
            for name, seen in sorted(programs.items(),
                                     key=lambda kv: -kv[1]["ns"])}}


# ------------------------------------------------------- for the readers --

def _maps():
    """``{module name: parse(text)}`` of what the program's trainer site
    ran; None where the program hands out no texts (a parent commit) or
    none of them names the step's update. The second is a step jax took
    from a cache another tree filled: its key leaves names out, so the
    executable carries the names of whoever compiled it first, and a
    parent's would read an update of 0.000 ms where absent is the truth.
    A module name two texts share cannot be told apart in a trace and is
    left out: its time reads as ``other``."""
    import mxnet_tpu.compile as mxcompile

    if not hasattr(mxcompile, "program_texts"):
        return None
    texts = mxcompile.program_texts(SITE)
    if not any(UPDATE in entry["text"] for entry in texts):
        return None
    maps, twice = {}, set()
    for entry in texts:
        if entry["module"] in maps:
            twice.add(entry["module"])
        maps[entry["module"]] = parse(entry["text"])
    return {name: rows for name, rows in maps.items() if name not in twice}


def of_run(run):
    """``reduce`` of the trace this run took, joined to the program's own
    texts (None where there is nothing to join). Computed once, kept in
    the run's bag for the other readers; prints the ``# step_phases`` line
    when it reads, with the seconds the texts, the parse and the join
    took."""
    if "step_phases" in run:
        return run["step_phases"]
    reduced = None
    spans = program_spans.of_run(run)
    if spans is not None and run["trace"]["devices"]:
        gc.collect()   # the run's trainer is garbage by now: free its chip
        t0 = time.perf_counter()
        maps = _maps()
        t1 = time.perf_counter()
        if maps is not None:
            loaded = program_spans.load(
                trace_reduce.find_xplane(run["bench"]._trace_dir))
            reduced = reduce({
                "window": list(trace_reduce.window(run["trace"])),
                "steps": spans["steps"], "ops": run["trace"]["devices"],
                "modules": loaded["modules"], "maps": maps})
        if reduced is not None:
            reduced["texts_s"] = t1 - t0
            reduced["join_s"] = time.perf_counter() - t1
            print(f"# step_phases: {json.dumps(reduced)}", flush=True)
    run["step_phases"] = reduced
    return reduced


def phase_ms(run, phase):
    """Device self time a step in the instructions of ``phase``, in ms."""
    reduced = of_run(run)
    return None if reduced is None else reduced["phases_ms"][phase]


def share(run, key):
    """``mixed_ms`` or ``unmatched_ms`` as a share of busy time, in %."""
    reduced = of_run(run)
    if reduced is None or not reduced["busy_ms"]:
        return None
    return 100.0 * reduced[key] / reduced["busy_ms"]
