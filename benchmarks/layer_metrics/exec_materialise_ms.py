"""`materialise` of a tagged request: from the device operator's return (the
kNN search, the graph count) to the statement's rows, fetched and projected."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "exec.materialise_ms", "ms", "parse/plan + executor", "p50_ms", "program_span"


def read(ctx):
    xs = [sum(d) for t in ctx["tagged"] if (d := spans.durations_ms(t["doc"], "materialise"))]
    return median(xs) if xs else None
