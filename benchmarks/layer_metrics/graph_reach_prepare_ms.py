"""`graph_prepare` of a tagged statement that asks for the set a graph chain
reaches, all its `array::distinct(<chain>)` expressions together: for the one
that fills the statement's ring memo, the host work from the expression's entry
to the dispatch submit (hop specs, operand look-ups, the first operator's row,
the mask's look-up or making, the dispatch key); for those the memo serves, the
look-up. The spans carry a `memo` label, which a count's does not. A
host-served expression (`form=host`) closes its span at the end of its walk, so
there the span is the whole answer and not a preparation: it is left out."""

from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.reach_prepare_ms", "ms", "mirrors", "p50_ms", "program_span"


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        d = [s["dur_ms"] for s in t["doc"]["spans"]
             if s["name"] == "graph_prepare" and "memo" in s["labels"] and s["labels"].get("form") != "host"]
        if d:
            xs.append(sum(d))
    return median(xs) if xs else None
