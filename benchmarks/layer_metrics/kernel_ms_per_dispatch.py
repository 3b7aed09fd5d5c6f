"""Device time of the cell's kernel (the jitted program its configuration
names, by the name the profiler trace shows) per dispatch of the traced slice."""

NAME, UNIT, LAYER, MOVES, SOURCE = "kernel.ms_per_dispatch", "ms", "kernels", "p50_ms", "device_trace"


def read(ctx):
    s = ctx.get("slice")
    if not s or s["dispatch"]["dispatches"] <= 0 or s["reduced"]["kernel_launches"] == 0:
        return None
    return s["reduced"]["kernel_s"] * 1e3 / s["dispatch"]["dispatches"]
