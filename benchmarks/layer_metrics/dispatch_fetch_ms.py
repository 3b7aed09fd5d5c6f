"""Milliseconds a dispatch from the outputs being ready on the device to the
results as host values: what is left of the copy the launch started, and the
decode. The window's delta of `fetch_s` over its dispatches, both from the
dispatcher's `stats()` (`ready_wait_s + fetch_s = collect_s`). A program
without the key, or a window without a dispatch, reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.fetch_ms", "ms", "dispatch", "p50_ms", "program_counter"


def read(ctx):
    d = ctx["window"]["dispatch"]
    if "fetch_s" not in d or d["dispatches"] <= 0:
        return None
    return d["fetch_s"] / d["dispatches"] * 1e3
