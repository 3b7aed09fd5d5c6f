"""`graph.filter_prepare_ms`'s reading of the set chains: the `graph_filter`
span of a tagged statement that asked for the set a chain reaches, inside its
preparation: the look-up of the predicate's bit mask (kept on the device a
(pair, predicate, bound values) while the operator's generation and the column
mirror stand) or, on a first sight of a bound name, its making: the mask over
the column mirror, its packing, the upload. Median over the statements that
have the span; a run with none reports nothing."""

from layer_metrics import graph_filter_prepare_ms as count
from layer_metrics.graph_reach_lane_fill import sets_only

NAME, UNIT, LAYER, MOVES, SOURCE = "graph.reach_filter_prepare_ms", "ms", "mirrors", "p50_ms", "program_span"


def read(ctx):
    return count.read(sets_only(ctx))
