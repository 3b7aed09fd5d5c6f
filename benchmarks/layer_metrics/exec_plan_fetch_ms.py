"""`plan_fetch` of a tagged request: the plan cache's look-up of the
statement's text, or its parse where the cache had no template."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "exec.plan_fetch_ms", "ms", "parse/plan + executor", "p50_ms", "program_span"


def read(ctx):
    xs = [sum(d) for t in ctx["tagged"] if (d := spans.durations_ms(t["doc"], "plan_fetch"))]
    return median(xs) if xs else None
