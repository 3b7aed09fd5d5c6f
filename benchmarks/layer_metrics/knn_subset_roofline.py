"""Share of its roofline the exact search over a filter's passing rows
reached in the traced slice."""

from harness import roofline

NAME, UNIT, LAYER, MOVES, SOURCE = "knn_subset_roofline", "%", "kernels", "p50_ms", "device_trace"


def read(ctx):
    return roofline.share_pct(ctx, "knn_subset")
