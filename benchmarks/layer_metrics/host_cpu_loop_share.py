"""Share of the window in which the event-loop threads held the interpreter:
`cpu_loop_s` of `DispatchQueue.stats()` (socket reads, frame decode, admission,
the hand-off to the pool, reply writes) over the window's wall time as the
program counted it (the state clock's four sums). Every frame and every reply
of every session passes through these threads, so their share is the part of
`host.cpu_busy_share` that no executor worker can take. A program without the
keys reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "host.cpu_loop_share", "ratio", "host runtime", "p50_ms", "program_counter"
STATES = ("fed_s", "launching_s", "handoff_s", "empty_s")


def read(ctx):
    d = ctx["window"]["dispatch"]
    if any(k not in d for k in STATES + ("cpu_loop_s",)):
        return None
    wall = sum(d[k] for k in STATES)
    return d["cpu_loop_s"] / wall if wall > 0 else None
