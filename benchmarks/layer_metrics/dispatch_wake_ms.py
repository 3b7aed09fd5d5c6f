"""`dispatch_wake` of a tagged request: from the leader's handing the result
out to the submitting thread's running again (a rider's wake-up; for a leader,
its bucket chores after the collect), summed over the request's dispatches."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.wake_ms", "ms", "dispatch", "p95_ms", "program_span"


def read(ctx):
    xs = [sum(d) for t in ctx["tagged"] if (d := spans.durations_ms(t["doc"], "dispatch_wake"))]
    return median(xs) if xs else None
