"""`graph_filter` of a tagged count statement: inside the count's preparation,
the look-up of what its predicate contributes (the end weights of the bound
values, cached a (pair, predicate, values) while the operator's generation
and the column mirror stand) or, on a first sight, their making: the mask
over the column mirror, the bincount over the last pair's passing paths, the
upload. Median over the tagged statements that have the span; a run with
none (a bare-count cell, a program older than the span) reports nothing."""

from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.filter_prepare_ms", "ms", "mirrors", "p50_ms", "program_span"


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        d = [s["dur_ms"] for s in t["doc"]["spans"] if s["name"] == "graph_filter"]
        if d:
            xs.append(sum(d))
    return median(xs) if xs else None
