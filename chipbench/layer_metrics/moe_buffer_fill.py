"""Live (token, expert) pairs over the rows of the expert-ordered buffer
that holds them, in %: the mean pairs a held expert layer computed a call
(the layers' load counters, ``model.expert_load``, over every step since
the network was built) over the mean rows of the buffer a call (the traced
window's grouped products: every ``ragged-dot-none`` Mosaic call with a
two-dimensional result has the buffer's rows; the weights' cotangents are
three-dimensional). 100 is a buffer with no dead row. A program whose
buffer has a row for every pair reads the live share of all pairs; one
that sizes it by the live count reads how close its rung came. Prints the
rows the calls had, ``# moe_buffer_rows: {rows: calls}``. Nothing to read
where the configuration has no expert layer or the trace no device."""
import json

from chipbench.harness import trace_reduce

LAYER = "experts"
MOVES = "train_samples_per_s"
UNIT = "%"
GROUPED = "ragged-dot-none"


def applies(run):
    return run["mode"] == "train"


def buffer_rows(trace):
    """``{rows: calls}`` of the grouped products with a (rows, width)
    result that ran inside the traced window, over the devices."""
    lo, hi = trace_reduce.window(trace)
    seen = {}
    for events in trace["devices"].values():
        for name, start, _dur in events:
            if not (name.startswith(GROUPED) and lo <= start < hi):
                continue
            shape = name.rsplit(" ", 1)[-1]
            dims = shape[shape.find("[") + 1:-1].split(",")
            if len(dims) == 2 and dims[0].isdigit():
                seen[int(dims[0])] = seen.get(int(dims[0]), 0) + 1
    return seen


def compute(run):
    read = getattr(run["model"], "expert_load", None)
    counted = [rec for rec in ((read() if read else None) or {}).values()
               if rec["calls"]]
    if not counted or not run["trace"]["devices"]:
        return None
    seen = buffer_rows(run["trace"])
    if not seen:
        return None
    print(f"# moe_buffer_rows: {json.dumps(seen)}", flush=True)
    live = sum(sum(rec["pairs"]) / rec["calls"] for rec in counted)
    rows = sum(r * n for r, n in seen.items()) / sum(seen.values())
    return 100.0 * live / len(counted) / rows
