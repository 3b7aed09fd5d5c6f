"""`column_prepare` of a tagged grouped statement: from the columnar
pipeline's entry to the dispatch submit (the cached lowering's rebind, the
mirror's staleness check, the route's rule, the constants' place among each
column's distinct values). Median over the tagged statements that have the
span; a run with none reports nothing."""

from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "col.prepare_ms", "ms", "mirrors", "p50_ms", "program_span"


def read(ctx):
    xs = [
        s["dur_ms"]
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "column_prepare"
    ]
    return median(xs) if xs else None
