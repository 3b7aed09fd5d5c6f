"""Statements per device dispatch over the window: how far the dispatch
queue coalesced concurrent statements into one launch."""

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.width_mean", "stmt/dispatch", "dispatch", "stmt_per_s", "program_counter"


def read(ctx):
    d = ctx["window"]["dispatch"]
    return d["submitted"] / d["dispatches"] if d["dispatches"] > 0 else None
