"""Share of the tagged count statements that the sparse CSC form served, from
the `form` label (`dense`, `csc` or `host`) the program puts on a count's
`graph_prepare` span where it counts `graph_count_form`. 1.0 where the node
table is past what a dense operator may span, 0.0 where every count rides the
dense form. A span without the label (a program older than the label) is not
counted, so such a run reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.count_form_csc_share", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    forms = [
        s["labels"]["form"]
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "graph_prepare" and "form" in s["labels"]
    ]
    return forms.count("csc") / len(forms) if forms else None
