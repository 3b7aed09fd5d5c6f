"""Share of its roofline the masked set-expansion kernel reached in the traced slice."""

from harness import roofline

NAME, UNIT, LAYER, MOVES, SOURCE = "graph_reach_roofline", "%", "kernels", "p50_ms", "device_trace"


def read(ctx):
    return roofline.share_pct(ctx, "graph_reach")
