"""`ft_prepare` of a tagged full-text search: from the iterator's entry to
the dispatch submit (analysis of the query, the route's rule, the mirror's
generation, the term look-ups, the ladder step, the payload). Median over
the tagged statements that have the span; a run with none reports
nothing."""

from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "ft.prepare_ms", "ms", "mirrors", "p50_ms", "program_span"


def read(ctx):
    xs = [
        s["dur_ms"]
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "ft_prepare"
    ]
    return median(xs) if xs else None
