"""Milliseconds a dispatch that the leader waited, inside its collect, until the
batch's outputs were ready on the device (each array's `block_until_ready()`):
the kernels in front of this one, this kernel, and the runtime's launch and
completion latency. A collect whose closure names no outputs counts here
whole. The window's delta of `ready_wait_s` over its dispatches, both from the
dispatcher's `stats()` (`ready_wait_s + fetch_s = collect_s`). A program
without the key, or a window without a dispatch, reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.ready_wait_ms", "ms", "dispatch", "p50_ms", "program_counter"


def read(ctx):
    d = ctx["window"]["dispatch"]
    if "ready_wait_s" not in d or d["dispatches"] <= 0:
        return None
    return d["ready_wait_s"] / d["dispatches"] * 1e3
