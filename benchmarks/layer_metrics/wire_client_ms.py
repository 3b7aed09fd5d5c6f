"""Client latency of a tagged request less the server's own stretch of it,
from the start of `ws_decode` (the frame complete in the read buffer) to the
end of `ws_write` (the reply's last byte accepted by the socket): the two
sockets, the kernel's wake-ups and the client's codec."""

from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "wire.client_ms", "ms", "wire", "p50_ms", "program_span"


def served_ms(doc):
    """`ws_decode` start to `ws_write` end, or None for a doc without them."""
    by = {s["name"]: s for s in doc["spans"]}
    if "ws_decode" not in by or "ws_write" not in by:
        return None
    return by["ws_write"]["start_ms"] + by["ws_write"]["dur_ms"] - by["ws_decode"]["start_ms"]


def read(ctx):
    xs = [
        (t["record"]["t1"] - t["record"]["t0"]) * 1e3 - served_ms(t["doc"])
        for t in ctx["tagged"]
        if served_ms(t["doc"]) is not None
    ]
    return median(xs) if xs else None
