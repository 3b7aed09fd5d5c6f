"""What one sparse path count needs from the device, from its shapes.

Counted for the algorithm, not for today's kernel: a count of `hops`-edge
walks from one start node is hops-1 sparse products of a frontier vector over
the nodes with the node->node adjacency (a column index an edge and a row
pointer a node, int32) and one dot product with the out-degrees. A dispatch
reads the adjacency once a hop, whatever the number of statements riding it;
each statement reads and writes its own int32 frontier a hop. Today's
`chain_count_batch` walks the edge records as nodes of their own (two hops a
`->edge->node` pair) and runs a cumulative sum over every padded edge slot in
every lane; counting that would make a later frontier-sized kernel read as a
loss of roofline share.
"""

MODULE = r"^jit_chain_count_batch"


def need(shapes: dict, statements: float, dispatches: float) -> dict:
    nodes, edges, products = shapes["nodes"], shapes["edges"], shapes["hops"] - 1
    adjacency = 4.0 * (edges + nodes + 1)
    return {
        "flops": statements * 2.0 * (products * edges + nodes),
        "bytes": dispatches * (products * adjacency + 4.0 * nodes)
        + statements * products * 2 * 4.0 * nodes,
    }
