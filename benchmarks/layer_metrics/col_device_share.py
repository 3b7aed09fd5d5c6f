"""Of the tagged grouped statements, the share the device served: the
`route` label the program puts on a statement's `column_prepare` span
(`device`: one dispatch of the grouped-aggregate kernel over the columns'
planes in HBM; `host`: NumPy over the column mirror's arrays; `row`: the
record-at-a-time path). A run with no such span (another kind of cell, a
program older than the span) reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "col.device_share", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    routes = [
        s["labels"].get("route")
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "column_prepare"
    ]
    return routes.count("device") / len(routes) if routes else None
