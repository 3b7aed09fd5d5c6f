"""`ws_write` of a tagged request: the reply frame handed to the event loop
until its last byte is accepted by the socket (the loop's wake-up included)."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "wire.write_ms", "ms", "wire", "p50_ms", "program_span"


def read(ctx):
    xs = [sum(d) for t in ctx["tagged"] if (d := spans.durations_ms(t["doc"], "ws_write"))]
    return median(xs) if xs else None
