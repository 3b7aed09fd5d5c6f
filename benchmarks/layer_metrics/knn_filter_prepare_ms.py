"""`knn_filter` of a tagged search: inside the search's preparation, the
look-up of what its WHERE contributes (the slot mask, its popcount and the
passing slots on the device, cached a (predicate, bound values, column
mirror, vector snapshot)) or, on a first sight, their making: the predicate
over the column mirror, the permutation into slot order, the upload. Median
over the tagged statements that have the span; a run with none (an unfiltered
cell, a program older than the span) reports nothing."""

from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "knn.filter_prepare_ms", "ms", "mirrors", "p50_ms", "program_span"


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        d = [s["dur_ms"] for s in t["doc"]["spans"] if s["name"] == "knn_filter"]
        if d:
            xs.append(sum(d))
    return median(xs) if xs else None
