"""What one filtered search needs from the device when the rows that pass are
scored exactly, from its shapes.

Counted for the algorithm, not for today's kernel: a statement reads the
slots of its passing rows and each of those rows once, and multiplies them
with the query. Today's `knn_subset_search` gathers the slot array padded to
a power of two (8,192 for 5,000 rows); counting the pad would make a later
tighter pad read as a loss of roofline share.
"""

MODULE = r"^jit_knn_subset_search"


def need(shapes: dict, statements: float, dispatches: float) -> dict:
    dim, rows = shapes["dim"], shapes["pass_rows"]
    byts = statements * rows * (dim * shapes["corpus_elem_bytes"] + shapes["slot_bytes"])
    flops = statements * 2.0 * dim * rows
    return {"flops": flops, "bytes": byts}
