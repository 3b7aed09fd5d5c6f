"""Share of the lanes a batched graph count swept that carried a rider, from
the labels the program puts on every rider's `dispatch_launch` span: `batch`
(the riders of the dispatch) and, since the lane count follows the batch,
`lanes` (what the runner padded them to: 8, 16, 32 or 64). The mean of
`batch / lanes` over DISPATCHES: a batch of five stamps its span onto five
riders, so a tagged rider's span stands for a fifth of its dispatch and is
weighted `1 / batch`; unweighted, the wide batches would count once a rider
and the fill would read high. At one lane count that is
`dispatch.width_mean / lanes`. A run whose spans carry no `lanes` (a program
whose lane count was a fixed floor, a kNN cell) reports nothing."""

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.lane_fill", "ratio", "kernels", "p50_ms", "program_span"


def read(ctx):
    launches = [
        (int(s["labels"]["batch"]), int(s["labels"]["lanes"]))
        for t in ctx["tagged"]
        for s in t["doc"]["spans"]
        if s["name"] == "dispatch_launch" and "lanes" in s["labels"]
    ]
    if not launches:
        return None
    return sum(1.0 / lanes for _, lanes in launches) / sum(1.0 / batch for batch, _ in launches)
