"""Share of the tagged count statements' `graph_filter` spans that say
`outcome=build`: the predicate's mask and end weights were made in that
statement (a window's first sight of a bound value, or the first count after
a write to either mirror), the tail the others (`hit`) do not pay. A run with
no such span reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.filter_build_share", "ratio", "mirrors", "p95_ms", "program_span"


def read(ctx):
    outcomes = [
        s["labels"].get("outcome")
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "graph_filter"
    ]
    return outcomes.count("build") / len(outcomes) if outcomes else None
