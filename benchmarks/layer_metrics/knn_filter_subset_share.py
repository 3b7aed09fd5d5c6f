"""Of the tagged searches that have a WHERE beside the kNN operator, the share
whose passing rows were scored exactly: the `filter` label the program puts
on a search's `knn_prepare` span (`none`: no residual WHERE; `subset`: the
passing rows' slots, cached on the device, gathered and scored; `widened`: the
IVF probe with the mask and more lists; `masked`: an exact scan of every row
with the mask; `post`: the column mirror could not answer, the search ran
unfiltered and the executor filtered its top-k, which may answer short). Of
the spans whose `filter` is not `none`, the share with `subset`. A run with no
such span (an unfiltered cell, a program older than the label) reports
nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "knn.filter_subset_share", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    routes = [
        s["labels"]["filter"]
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "knn_prepare" and s["labels"].get("filter", "none") != "none"
    ]
    return routes.count("subset") / len(routes) if routes else None
