"""Share of the tagged count statements whose sparse CSC kernel swept composed
node->node operators, from the labels the program puts on a count's
`graph_prepare` span: `form` (`dense`, `csc` or `host`) and, on a `csc` count,
`operand` (`composed`: one hop a `->edge->node` pair over the node table's
compact ids; `records`: one hop a spec over the id space persons and edge
records share). Of the spans that have a `form`, the share with `form=csc` and
`operand=composed`: a span without `operand` counts as not composed, so a
program older than the label reads 0.0 wherever it reads a form, and a run
with no `form` label at all reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.csc_composed_share", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    labels = [
        s["labels"]
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "graph_prepare" and "form" in s["labels"]
    ]
    composed = [l for l in labels if l["form"] == "csc" and l.get("operand") == "composed"]
    return len(composed) / len(labels) if labels else None
