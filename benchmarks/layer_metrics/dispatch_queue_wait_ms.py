"""Median time a tagged request's payload waited in the dispatch queue
before a leader launched its batch."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.queue_wait_ms", "ms", "dispatch", "p95_ms", "program_span"


def read(ctx):
    xs = [d for t in ctx["tagged"] for d in spans.durations_ms(t["doc"], "dispatch_queue_wait")]
    return median(xs) if xs else None
