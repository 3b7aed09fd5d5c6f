"""Share of its roofline the sparse path-count kernel reached in the traced slice."""

from harness import roofline

NAME, UNIT, LAYER, MOVES, SOURCE = "graph_csc_roofline", "%", "kernels", "p50_ms", "device_trace"


def read(ctx):
    return roofline.share_pct(ctx, "graph_csc")
