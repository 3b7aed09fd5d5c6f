"""What one conjunctive BM25 top-k search needs from the device, from the
pool's means (the deployment's `kernel_shapes`: real list lengths from the
reference's index, no padding).

Counted for the algorithm, rarest-first conjunction, and not for a layout:
a statement reads its rarest term's postings (doc id and tf: the
candidates), looks once at each other term for every candidate (a doc id
and a tf again, wherever the program keeps them), reads the length of
every passage that matched, and writes k scores and k ids; ten operations a
(candidate, term) cover the membership test and the BM25 term. A kernel
that holds frequent terms as dense rows, one that searches a skip table and
one that binary-searches the lists are all judged on this one need.
"""

MODULE = r"^jit_bm25_and_topk"


def need(shapes: dict, statements: float, dispatches: float) -> dict:
    posting = 4 + shapes["tf_bytes"]
    pairs = shapes["candidates_mean"] + shapes["lookups_mean"]  # (candidate, term) pairs a statement
    byts = statements * (pairs * posting + shapes["matches_mean"] * 4 + shapes["k"] * 8)
    return {"flops": statements * 10.0 * pairs, "bytes": byts}
