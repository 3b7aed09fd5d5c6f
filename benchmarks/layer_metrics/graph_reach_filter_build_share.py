"""`graph.filter_build_share`'s reading of the set chains: the share of the
`graph_filter` spans of tagged statements that asked for the set a chain
reaches which say `outcome=build`: the name's bit mask was made in that
statement (a window's first sight of a bound name, or the first set after a
write to either mirror), the tail the others (`hit`) do not pay. A run with no
such span reports nothing."""

from layer_metrics import graph_filter_build_share as count
from layer_metrics.graph_reach_lane_fill import sets_only

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.reach_filter_build_share", "ratio", "mirrors", "p95_ms", "program_span"


def read(ctx):
    return count.read(sets_only(ctx))
