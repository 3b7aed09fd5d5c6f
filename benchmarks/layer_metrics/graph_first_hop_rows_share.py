"""Share of the tagged sparse count statements whose first hop was read from
the operator's rows on the host and not swept for on the device, from the
labels the program puts on a count's `graph_prepare` span: `form` (`dense`,
`csc` or `host`) and, on a `csc` count that launches, `first_hop` (`rows`: the
seeds' rows of the first composed operator, sliced from its source-sorted
arrays, entered the kernel, which swept one hop less; `sweep`: the kernel swept
from the seeds). Of the spans with `form=csc`, the share with `first_hop=rows`:
a `csc` span without the label counts as swept, so a program older than the
label reads 0.0 wherever it serves the sparse form, and a run with no `csc`
span at all (the dense form, no `form` label) reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.first_hop_rows_share", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    hops = [
        s["labels"].get("first_hop")
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "graph_prepare" and s["labels"].get("form") == "csc"
    ]
    return hops.count("rows") / len(hops) if hops else None
