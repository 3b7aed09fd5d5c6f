"""Rows (or edges) acknowledged per second of INSERT through `ds.execute()`."""

NAME, UNIT, LAYER, MOVES, SOURCE = "load.rows_per_s", "rows/s", "storage", "setup_s", "host_clock"


def read(ctx):
    p = ctx["phases"]
    if not p.get("insert_s"):
        return None
    return p["acknowledged"] / p["insert_s"]
