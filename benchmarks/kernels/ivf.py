"""What one IVF search needs from the device, from its shapes.

Counted for the algorithm, not for today's kernel: a dispatch reads the
centroid table once, and each query in it reads the rows of the lists it
really probes (unpadded) once and multiplies them, and the centroids, with the
query. Today's `_ivf_search` gathers nprobe x L rows padded to the longest
list's power of two; counting that would make a later bound on the lists read
as a loss of roofline share.
"""

MODULE = r"^jit__ivf_search"


def need(shapes: dict, statements: float, dispatches: float) -> dict:
    dim, nlists = shapes["dim"], shapes["nlists"]
    rows = shapes["rows_probed_mean"]
    byts = (
        dispatches * nlists * dim * shapes["centroid_elem_bytes"]
        + statements * rows * dim * shapes["corpus_elem_bytes"]
    )
    flops = statements * 2.0 * dim * (nlists + rows)
    return {"flops": flops, "bytes": byts}
