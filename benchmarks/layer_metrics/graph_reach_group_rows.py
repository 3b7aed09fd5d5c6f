"""Riders a tagged statement's rows brought to the dispatch queue in ONE call:
the `riders` label of its `graph_reach_group` span (the program defers the
projection of a SELECT whose field list holds `array::distinct(<chain>)` and
that collected two or more rows, prepares the chain once and submits every
row's frontier together), summed over the statement's groups, mean over the
tagged statements that have such a span. A program that walks the rows one by
one has no such span and reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.reach_group_rows", "riders/stmt", "kernels", "p50_ms", "program_span"


def read(ctx):
    per = [
        sum(int(s["labels"].get("riders", 0)) for s in t["doc"]["spans"] if s["name"] == "graph_reach_group")
        for t in ctx["tagged"]
        if any(s["name"] == "graph_reach_group" for s in t["doc"]["spans"])
    ]
    return sum(per) / len(per) if per else None
