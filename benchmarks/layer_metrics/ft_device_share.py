"""Of the tagged full-text searches, the share the device served: the
`route` label the program puts on a search's `ft_prepare` span (`device`:
one dispatch of the conjunctive top-k kernel over the postings in HBM;
`host`: the mirror's NumPy intersection; `kv`: a transaction's own writes,
searched over the KV). A run with no such span (another kind of cell, a
program older than the span) reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "ft.device_share", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    routes = [
        s["labels"].get("route")
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "ft_prepare"
    ]
    return routes.count("device") / len(routes) if routes else None
