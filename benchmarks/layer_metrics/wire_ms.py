"""Client latency of a tagged request less the server's root `ws_rpc` span:
what the WebSocket, the frame codecs, the server's event loop and its hand-off
to an executor thread cost, both ways."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "wire.ms", "ms", "wire", "p50_ms", "program_span"


def read(ctx):
    xs = [
        (t["record"]["t1"] - t["record"]["t0"]) * 1e3 - spans.root(t["doc"])["dur_ms"]
        for t in ctx["tagged"]
        if spans.root(t["doc"]) is not None
    ]
    return median(xs) if xs else None
