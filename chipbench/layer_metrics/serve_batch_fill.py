"""Rows run over bucket rows run in the window, from the delta of
``server.stats()``: how much of each padded batch was real work."""
LAYER = "serving"
MOVES = "serve_p50_ms"
UNIT = "%"


def applies(run):
    return run["mode"] == "serve_open"


def compute(run):
    d = run["stats_delta"]
    ran = d["rows"] + d["padded_rows"]
    return 100.0 * d["rows"] / ran if ran else None
