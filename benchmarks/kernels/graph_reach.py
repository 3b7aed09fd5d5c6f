"""What one masked set expansion needs from the device, from its shapes.

Counted for the algorithm, not for today's kernel: the rings of a walk of 1 to
`hops` edges from one start node are hops-1 boolean sparse products of a
frontier over the nodes with the node->node adjacency (a column index an edge
and a row pointer a node, int32: an AND and an OR an edge), the first ring being
the start node's own row. A dispatch reads the adjacency once a hop, whatever
the number of statements riding it; each statement reads and writes its own
frontier a hop (a byte a node), reads its mask (a bit a node) and writes
`hops` masked rings (a bit a node each). Today's `chain_reach_batch` carries
the frontier as int32 and runs a cumulative sum over every padded slot of the
operator in every lane; counting that would make a later frontier-sized kernel
read as a loss of roofline share.
"""

MODULE = r"^jit_chain_reach_batch"


def need(shapes: dict, statements: float, dispatches: float) -> dict:
    nodes, edges, hops = shapes["nodes"], shapes["edges"], shapes["hops"]
    adjacency = 4.0 * (edges + nodes + 1)
    return {
        "flops": statements * 2.0 * (hops - 1) * edges,
        "bytes": dispatches * (hops - 1) * adjacency
        + statements * ((hops - 1) * 2 * 1.0 * nodes + (hops + 1) * nodes / 8.0),
    }
