"""Share of the tagged searches' `knn_filter` spans that say `outcome=build`:
the slot filter was made in that statement (a window's first sight of a bound
value, or the first search after a write to the table), the tail the others
(`hit`) do not pay. A run with no such span reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "knn.filter_build_share", "ratio", "mirrors", "p95_ms", "program_span"


def read(ctx):
    outcomes = [
        s["labels"].get("outcome")
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "knn_filter"
    ]
    return outcomes.count("build") / len(outcomes) if outcomes else None
