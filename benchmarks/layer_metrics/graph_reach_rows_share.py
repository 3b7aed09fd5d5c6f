"""Share of the tagged set statements whose LAST hop the kernel read from the
frontier's rows of the operator and did not sweep for, from the labels the
program puts on the `graph_prepare` span of the expression that ran a chain
family (`memo=fill`; a row's own expressions say `hit`): `form` (`csc` or
`host`) and, on a `csc` run that launches, `last_hop` (`rows`: the pair of
operators bounds every two-step walk by a pad, so a lane gathers its walk's
destinations, a slot each; `sweep`: the kernel passed every slot of the
operators and the node space a hop). Of the spans with `memo=fill` and
`form=csc`, the share with `last_hop=rows`: a span without the label counts as
swept, so a program older than the label reads 0.0 wherever its set chains
reach the device, and a run with no such span (no set statement, or every set
walked by the host) reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.reach_rows_share", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    hops = [
        s["labels"].get("last_hop")
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "graph_prepare" and s["labels"].get("memo") == "fill" and s["labels"].get("form") == "csc"
    ]
    return hops.count("rows") / len(hops) if hops else None
