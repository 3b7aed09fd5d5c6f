"""Share of its roofline the set kernel reached in the traced slice of a cell
whose statements ride TWO kernels. `harness/roofline.share` hands a kernel's
`need()` the slice's riders and launches of every family, which here would
count each search launch as a sweep of the adjacency. This reader calls the
cell's `need()` (kernels/graph_reach.py) with the sweep program's OWN launches
(`kernel_launches`: the modules the trace shows under the kernel's name) and
with the set riders' share of the slice's riders: of a statement's
`dispatches`, all but the one search."""

from harness import roofline

NAME, UNIT, LAYER, MOVES, SOURCE = "hybrid_reach_roofline", "%", "kernels", "p50_ms", "device_trace"


def read(ctx):
    k, s = ctx.get("kernel"), ctx.get("slice")
    if not k or k["name"] != "graph_reach" or not s or s["reduced"]["kernel_s"] <= 0:
        return None
    per = [st["dispatches"] for st in ctx["cfg"]["statements"].values() if st["dispatches"] > 1]
    if not per:
        return None
    riders = float(s["dispatch"]["submitted"]) * (per[0] - 1) / per[0]
    need = k["need"](k["shapes"], riders, float(s["reduced"]["kernel_launches"]))
    return 100.0 * roofline.least_seconds(need, ctx["peaks"])[0] / s["reduced"]["kernel_s"]
