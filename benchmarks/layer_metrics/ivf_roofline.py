"""Share of its roofline the IVF search kernel reached in the traced slice."""

from harness import roofline

NAME, UNIT, LAYER, MOVES, SOURCE = "ivf_roofline", "%", "kernels", "p50_ms", "device_trace"


def read(ctx):
    return roofline.share_pct(ctx, "ivf")
