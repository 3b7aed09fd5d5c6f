"""`dispatch_launch` of a tagged request: the leader building the batch's
operands and enqueueing the kernel, stamped onto every rider."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.launch_ms", "ms", "dispatch", "p50_ms", "program_span"


def read(ctx):
    xs = [sum(d) for t in ctx["tagged"] if (d := spans.durations_ms(t["doc"], "dispatch_launch"))]
    return median(xs) if xs else None
