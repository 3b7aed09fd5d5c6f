"""Device time of the search program (`jit__ivf_search`, by the name the
profiler trace shows) a launch of the traced slice, in a cell whose
configuration names another kernel (the sweep): `kernel.ms_per_dispatch`
reads only that one. Nothing where the slice saw no search."""

import re

NAME, UNIT, LAYER, MOVES, SOURCE = "hybrid.knn_kernel_ms", "ms", "kernels", "p50_ms", "device_trace"
MODULE = re.compile(r"^jit__ivf_search")


def read(ctx):
    s = ctx.get("slice")
    if not s:
        return None
    found = [m for name, m in s["reduced"]["modules"].items() if MODULE.search(name)]
    launches = sum(m["launches"] for m in found)
    return sum(m["seconds"] for m in found) * 1e3 / launches if launches else None
