"""Deployment kind `graph_count`: a node table and a symmetric edge table,
loaded in bulk, and `count(->edge->node ...)` chains asked from one start node.

The generator, the NumPy path count, the control and the comparison are here
and read nothing the program made. The loader is `chip_smoke.py`'s (PR 21),
with one statement asked before the edges, and goes through `ds.execute()`.
"""

from __future__ import annotations

import time

import numpy as np

KIND = "graph_count"
INGEST_BATCH = 20_000


# ------------------------------------------------------------------ data
def knows_pairs(rng, nodes: int, pairs: int, sigma: float, cap: float) -> np.ndarray:
    """`pairs` distinct unordered pairs of different nodes, both ends drawn in
    proportion to a log-normal weight a node (Chung-Lu): a node's expected
    degree is 2 * pairs * its share of the weight, held under `cap`."""
    w = rng.lognormal(0.0, sigma, nodes)
    for _ in range(8):
        w = np.minimum(w, cap * w.sum() / (2.0 * pairs))
    p = w / w.sum()
    keys = np.empty(0, dtype=np.int64)
    while keys.size < pairs:
        d = rng.choice(nodes, size=(pairs + pairs // 2, 2), p=p)
        a, b = d.min(axis=1), d.max(axis=1)
        seen = np.concatenate([keys, (a * nodes + b)[a != b]])
        _, first = np.unique(seen, return_index=True)
        keys = seen[np.sort(first)]  # first occurrences, in the order drawn
    keys = keys[:pairs]
    return np.stack([keys // nodes, keys % nodes], axis=1)


def generate(cfg: dict, sizes: dict, seed: int) -> dict:
    """A symmetric friendship graph and a pool of start nodes, from the seed.
    Every pair is loaded as two edge records, one each way, in one shuffled
    order."""
    g = cfg["generator"]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFFFFFF, 29]))
    nodes = int(sizes["nodes"])
    und = knows_pairs(rng, nodes, int(sizes["pairs"]), float(g["degree_sigma"]), float(g["degree_cap"]))
    pairs = rng.permutation(np.concatenate([und, und[:, ::-1]]))
    # start nodes: persons who know someone (one with no edge is never sent to
    # the device, and its count is 0 whatever the program does)
    known = np.flatnonzero(np.bincount(pairs[:, 0], minlength=nodes))
    starts = known[rng.integers(0, known.size, size=int(sizes["pool"]))]
    return {"pairs": pairs, "starts": starts, "nodes": nodes}


def pool(cfg: dict, data: dict) -> list:
    return [int(s) for s in data["starts"]]


# ------------------------------------------------------------------ reference
def path_counts(pairs: np.ndarray, nodes: int, starts: np.ndarray, hops: int) -> np.ndarray:
    """Paths of `hops` edges from each start node. Every edge record is its
    own step (a duplicate pair counts twice: the multiplicity the engine
    keeps). Dense adjacency in float64: every product and sum here stays far
    below 2**53, so the arithmetic is exact."""
    adj = np.zeros((nodes, nodes), dtype=np.float64)
    np.add.at(adj, (pairs[:, 0], pairs[:, 1]), 1.0)
    outdeg = adj.sum(axis=1)
    if hops == 1:
        return np.rint(outdeg[starts]).astype(np.int64)
    x = adj[starts]
    for _ in range(hops - 2):
        x = x @ adj
    return np.rint(x @ outdeg).astype(np.int64)


def as_bfloat16(counts: np.ndarray) -> np.ndarray:
    """The counts as a bfloat16 accumulator would hold them (round to nearest
    even on the upper 16 bits of the float32): the control."""
    bits = counts.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return np.rint(bits.astype(np.uint32).view(np.float32)).astype(np.int64)


def reference(cfg: dict, data: dict) -> dict:
    counts = path_counts(data["pairs"], data["nodes"], data["starts"], int(cfg["hops"]))
    return {"counts": counts, "counts_control": as_bfloat16(counts)}


# ------------------------------------------------------------------ load
def load(ds, cfg: dict, data: dict, execute_ok) -> dict:
    from surrealdb_tpu.sql.value import Thing

    node_tb, edge_tb, pairs = cfg["node_table"], cfg["edge_table"], data["pairs"]
    for ddl in cfg["ddl"]:
        execute_ok(ds, ddl)
    for i in range(0, data["nodes"], INGEST_BATCH):
        rows = [{"id": j} for j in range(i, min(i + INGEST_BATCH, data["nodes"]))]
        execute_ok(ds, f"INSERT INTO {node_tb} $rows RETURN NONE", {"rows": rows})
    # a server that is already answering when the edges arrive (the configuration's `load` says why)
    execute_ok(ds, cfg["load"]["ask_before_edges"])
    secs = 0.0
    for i in range(0, pairs.shape[0], INGEST_BATCH):
        rows = [
            {"in": Thing(node_tb, int(a)), "out": Thing(node_tb, int(b))}
            for a, b in pairs[i : i + INGEST_BATCH]
        ]
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT RELATION INTO {edge_tb} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    return {"acknowledged": int(pairs.shape[0]), "insert_s": secs, "unit": "edges"}


def count_sql(cfg: dict) -> list:
    return [(f"SELECT count() AS c FROM {cfg['edge_table']} GROUP ALL", None)]


def release(data: dict) -> None:
    data["edges"] = int(data.pop("pairs").shape[0])


def wait_background(ds, cfg: dict, timeout: float) -> dict:
    """Wait for the ingest-armed mirror build and count-kernel prewarm."""
    from surrealdb_tpu import bg

    t0 = time.perf_counter()
    if not ds.graph_mirrors.wait_prewarm(timeout):
        raise RuntimeError(f"graph prewarm still running after {timeout:.0f}s")
    if not bg.wait_idle(timeout, owner=id(ds)):
        raise RuntimeError(f"background tasks still running after {timeout:.0f}s")
    return {"state": {}, "line": {"prewarm_wait_s": time.perf_counter() - t0}}


def kernel_shapes(cfg: dict, data: dict, state: dict) -> dict:
    """The graph a count walks: nodes, directed node->node edges, hops."""
    return {"nodes": int(data["nodes"]), "edges": int(data["edges"]), "hops": int(cfg["hops"])}


# ------------------------------------------------------------------ check
def check(cfg: dict, ref: dict, records: list) -> dict:
    """Every count of the window against the NumPy path count: equal or wrong."""
    field = cfg["count_field"]
    wrong = wrong_control = compared = 0
    for r in records:
        if r["status"] != "OK":
            continue
        got = r["values"].get(field, [None])[0]
        compared += 1
        wrong += got != int(ref["counts"][r["q"]])
        wrong_control += int(ref["counts_control"][r["q"]]) != int(ref["counts"][r["q"]])
    return {
        "numbers": [["count_mismatches", wrong if compared else 1, "<=", 0]],
        "control": {"count_mismatches_bf16": wrong_control},
        "metrics": {},
        "compared": {"answers": compared},
    }
