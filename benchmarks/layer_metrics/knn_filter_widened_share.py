"""Of the tagged searches that have a WHERE beside the kNN operator, the share
served by the WIDENED route: the IVF probe with the filter's slot mask applied
and its probes multiplied by the inverse of the passing share
(idx/ivf.py::filtered_route), the `filter` label of the search's `knn_prepare`
span (knn.filter_subset_share lists the label's values). A run with no
filtered search reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "knn.filter_widened_share", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    routes = [
        s["labels"]["filter"]
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "knn_prepare" and s["labels"].get("filter", "none") != "none"
    ]
    return routes.count("widened") / len(routes) if routes else None
