"""Self time of a tagged request's root span outside its dispatch spans
(queue wait, and the launch and collect spans the dispatch leader stamps onto
every rider): parse, plan cache, executor and result encoding on the host."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "exec.host_ms", "ms", "parse/plan + executor", "p50_ms", "program_span"


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        r = spans.root(t["doc"])
        if r is not None:
            xs.append(r["dur_ms"] - spans.covered_ms(t["doc"], spans.DISPATCH_SPANS))
    return median(xs) if xs else None
