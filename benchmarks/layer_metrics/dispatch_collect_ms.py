"""`dispatch_collect` of a tagged request: the leader waiting for the batch's
result and reading it back; less `kernel.ms_per_dispatch` it is the download
and the host's wake-up."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.collect_ms", "ms", "dispatch", "p50_ms", "program_span"


def read(ctx):
    xs = [sum(d) for t in ctx["tagged"] if (d := spans.durations_ms(t["doc"], "dispatch_collect"))]
    return median(xs) if xs else None
