"""The graph's stretch of a tagged hybrid statement: from the submit of its
first set rider (the first `dispatch_queue_wait` after the search's collect)
to the end of its last `dispatch_collect` (harness/hybrid.py::stages): one
launch and one collect where a statement's rows ride together, ten round trips
one after another where each row's expression waits for its own. Median over
the tagged hybrid statements."""

from harness import hybrid
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "hybrid.reach_stage_ms", "ms", "parse/plan + executor", "p50_ms", "program_span"


def read(ctx):
    xs = [st[3] - st[2] for t in ctx["tagged"] if (st := hybrid.stages(t["doc"])) is not None]
    return median(xs) if xs else None
