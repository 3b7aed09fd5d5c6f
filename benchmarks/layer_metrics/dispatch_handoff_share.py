"""Share of the window in which requests were queued, nothing was in flight and
no leader was launching: promotion, wake-up, the depth semaphore, the
interpreter lock. From the dispatcher's state clock (`dbs/dispatch.py`):
`stats()` carries four sums, `fed_s`, `launching_s`, `handoff_s`, `empty_s`,
and every second is in exactly one, so their deltas over the window add up to
the window's wall time as the program counted it. A program without the clock
reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "dispatch.handoff_share", "ratio", "dispatch", "p95_ms", "program_counter"
STATES = ("fed_s", "launching_s", "handoff_s", "empty_s")


def read(ctx):
    d = ctx["window"]["dispatch"]
    if any(k not in d for k in STATES):
        return None
    wall = sum(d[k] for k in STATES)
    return d["handoff_s"] / wall if wall > 0 else None
