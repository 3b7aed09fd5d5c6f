"""Device time of the cell's kernel per dispatch where a kernel runs for
longer than the traced slice can count: the kernel's share of the slice's
recorded time, times the window's time from one dispatch to the next.

`kernel.ms_per_dispatch` divides the slice's kernel seconds by the dispatches
the counter saw in the slice; at a second or more a kernel that is two or
three, so it is off by up to a launch in three. Here the dispatches are told
apart over the whole window, by the end of the `dispatch_collect` span each
leaves on its riders (one stamp a batch; a batch's collect ends when its
kernel has): the median distance between consecutive batches is the period,
and the share of the slice in which the kernel's module ran turns the period
into device time. The share is taken from the first device operation of the
slice to the last: the profiler records the device from some tens of
milliseconds after the opening annotation to some before the closing one,
and a kernel cut there is no idle chip. It holds where nearly every dispatch
carries a tagged request, so it lists only cells whose dispatches outlast the
tag interval."""

from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "kernel.window_ms_per_dispatch", "ms", "kernels", "p50_ms", "device_trace"

SAME_BATCH_MS = 1.0  # riders of one batch carry one stamp, each on its own trace's clock


def read(ctx):
    s = ctx.get("slice")
    if not s or s["reduced"]["kernel_launches"] == 0:
        return None
    ends = sorted(
        t["doc"]["ts"] * 1e3 + sp["start_ms"] + sp["dur_ms"]
        for t in ctx["tagged"] for sp in t["doc"]["spans"] if sp["name"] == "dispatch_collect"
    )
    batches = [e for i, e in enumerate(ends) if i == 0 or e - ends[i - 1] > SAME_BATCH_MS]
    if len(batches) < 3:
        return None
    period = median([b - a for a, b in zip(batches, batches[1:])])
    r = s["reduced"]
    # `gaps` holds (start, seconds) of the stretches with no operation, from the slice's start
    edges = sum(d for g, d in r["gaps"] if g == 0.0 or abs(g + d - r["window_s"]) < 1e-6)
    return period * r["kernel_s"] / (r["window_s"] - edges)
