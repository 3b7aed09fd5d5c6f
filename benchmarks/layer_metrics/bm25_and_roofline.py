"""Share of its roofline the conjunctive BM25 top-k kernel reached in the
traced slice."""

from harness import roofline

NAME, UNIT, LAYER, MOVES, SOURCE = "bm25_and_roofline", "%", "kernels", "p50_ms", "device_trace"


def read(ctx):
    return roofline.share_pct(ctx, "bm25_and")
