"""What no span names of a tagged request: the server's stretch of it
(`ws_decode` start to `ws_write` end; client latency less `wire.client_ms`)
less the union of its spans inside that stretch, the containers left out
(spans that only hold others: their own time is what is being asked for)."""

from harness.stats import median, union_seconds

NAME, UNIT, LAYER, MOVES, SOURCE = "stmt.unattributed_ms", "ms", "all", "p50_ms", "program_span"
CONTAINERS = ("ws_rpc", "rpc", "rpc_method", "execute", "statement", "knn_search")


def unattributed_ms(doc):
    by = {s["name"]: s for s in doc["spans"]}
    if "ws_decode" not in by or "ws_write" not in by:
        return None
    lo, hi = by["ws_decode"]["start_ms"], by["ws_write"]["start_ms"] + by["ws_write"]["dur_ms"]
    inside = [
        (max(s["start_ms"], lo), min(s["start_ms"] + s["dur_ms"], hi))
        for s in doc["spans"]
        if s["name"] not in CONTAINERS and s["start_ms"] < hi and s["start_ms"] + s["dur_ms"] > lo
    ]
    return (hi - lo) - union_seconds(inside)


def read(ctx):
    xs = [u for t in ctx["tagged"] if (u := unattributed_ms(t["doc"])) is not None]
    return median(xs) if xs else None
