"""Share of its roofline the grouped-aggregate kernel reached in the traced
slice."""

from harness import roofline

NAME, UNIT, LAYER, MOVES, SOURCE = "column_agg_roofline", "%", "kernels", "p50_ms", "device_trace"


def read(ctx):
    return roofline.share_pct(ctx, "column_agg")
