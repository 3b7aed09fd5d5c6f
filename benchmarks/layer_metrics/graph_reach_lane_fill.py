"""`graph.lane_fill`'s reading (its file says how a dispatch is weighted) of
the set chains' dispatches: the share of the lanes `chain_reach_batch` swept
that carried a rider, from `batch` and `lanes` on the `dispatch_launch` span
of every tagged statement that asked for the set a chain reaches (its
`graph_prepare` spans carry `memo`, which a count's do not). An empty lane
costs the two sweeps a full one costs. A run with no such statement, or a
program whose set chains make no dispatch, reports nothing."""

from layer_metrics import graph_lane_fill as count

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.reach_lane_fill", "ratio", "kernels", "p50_ms", "program_span"


def sets_only(ctx) -> dict:
    """The tagged statements with an `array::distinct(<chain>)` the program noted."""
    return {**ctx, "tagged": [
        t for t in ctx["tagged"]
        if any(s["name"] == "graph_prepare" and "memo" in s["labels"] for s in t["doc"]["spans"])
    ]}


def read(ctx):
    return count.read(sets_only(ctx))
