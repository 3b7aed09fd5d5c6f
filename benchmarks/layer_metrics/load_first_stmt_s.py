"""Wall time of the first statement after the bulk load, over the wire: it
builds the vector mirror, or composes the graph's dense operator."""

NAME, UNIT, LAYER, MOVES, SOURCE = "load.first_stmt_s", "s", "mirrors", "setup_s", "host_clock"


def read(ctx):
    return ctx["phases"].get("first_stmt_s")
