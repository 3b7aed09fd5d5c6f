"""The search's stretch of a tagged hybrid statement: from the start of its
`knn_prepare` span to the end of the search's `dispatch_collect`
(harness/hybrid.py::stages). Median over the tagged statements that have a
search and set riders after it: a cell with no graph part reports nothing."""

from harness import hybrid
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "hybrid.knn_stage_ms", "ms", "parse/plan + executor", "p50_ms", "program_span"


def read(ctx):
    xs = [st[1] - st[0] for t in ctx["tagged"] if (st := hybrid.stages(t["doc"])) is not None]
    return median(xs) if xs else None
