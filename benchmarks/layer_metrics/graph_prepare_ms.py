"""`graph_prepare` of a tagged count statement that a dispatch served: the
host work from the chain's entry to the dispatch submit (hop specs, start
frontier, work estimate, the dense form's refusal or its operator look-up,
the CSC operand look-ups, the dispatch key). A host-served count (`form=host`)
closes the span at the end of its walk, so there the span is the whole count
and not a preparation: it is left out."""

from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.prepare_ms", "ms", "mirrors", "p50_ms", "program_span"


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        d = [s["dur_ms"] for s in t["doc"]["spans"]
             if s["name"] == "graph_prepare" and s["labels"].get("form") != "host"]
        if d:
            xs.append(sum(d))
    return median(xs) if xs else None
