"""Of the tagged count statements whose chain has a WHERE, the share whose
predicate rode the count: the `filter` label the program puts on a count's
`graph_prepare` span (`none`: no WHERE on the chain; `fused`: the predicate on
the final node part was a mask over the node table's column mirror, as end
weights of the device count or over the host's last frontier; `host`: the
chain fell back to the KV walk, a record fetch and a WHERE a walk). Of the
spans whose `filter` is not `none`, the share with `fused`. A run with no
such span (a bare-count cell, a program older than the label) reports
nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.filter_fused_share", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    routes = [
        s["labels"]["filter"]
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "graph_prepare" and s["labels"].get("filter", "none") != "none"
    ]
    return routes.count("fused") / len(routes) if routes else None
