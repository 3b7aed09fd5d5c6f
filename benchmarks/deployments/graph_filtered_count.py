"""Deployment kind `graph_filtered_count`: `graph_count`'s node and edge
tables, the nodes carrying LDBC Person's scalar attributes, and a count chain
whose final node part has a predicate on one of them (`firstName`), asked
from one start node with one bound name (LDBC SNB Interactive's IC1, as a
walk count).

The graph is `graph_count`'s own generator call, so a seed gives this kind
the graph it gives that one; the names, the pool, the NumPy reference and
the loader's probe are here and read nothing the program made. The loader
goes through `ds.execute()` and ends by asking one statement of the pool: a
program that serves it by the host's record-at-a-time walk is refused there,
before any client starts (such a walk is up to 14 million records a
statement at SF3, and a run of them would be killed at its time limit).
"""

from __future__ import annotations

import time

import numpy as np

from deployments import graph_count as base

KIND = "graph_filtered_count"
INGEST_BATCH = base.INGEST_BATCH
SYLLABLES = [c + v for c in "bdfgklmnprstvz" for v in "aeiou"]


# ------------------------------------------------------------------ data
def dictionary(rng, size: int) -> list:
    """`size` distinct capitalised names of two to four syllables."""
    names, seen = [], set()
    while len(names) < size:
        n = "".join(rng.choice(SYLLABLES, size=int(rng.integers(2, 5)))).capitalize()
        if n not in seen:
            seen.add(n)
            names.append(n)
    return names


def generate(cfg: dict, sizes: dict, seed: int) -> dict:
    """`graph_count`'s graph and start persons for this seed, and on top of
    them (from a second stream of the seed, so the graph stays that kind's):
    a dictionary of first names, a first name a person drawn with weight
    1 / rank, and the name each pool entry asks for, which is the first name
    of a person drawn uniformly (so names arrive in proportion to their
    frequency and every name asked exists)."""
    data = base.generate(cfg, sizes, seed)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFFFFFF, 31]))
    names = dictionary(rng, int(sizes["names"]))
    weight = 1.0 / np.arange(1, len(names) + 1) ** float(cfg["generator"]["name_exponent"])
    first = rng.choice(len(names), size=data["nodes"], p=weight / weight.sum())
    asked = first[rng.integers(0, data["nodes"], size=data["starts"].size)]
    return {**data, "names": names, "last_names": dictionary(rng, 256), "first": first, "asked": asked,
            "seed": int(seed)}


def pool(cfg: dict, data: dict) -> list:
    return [{"p": int(p), "fn": data["names"][int(a)]} for p, a in zip(data["starts"], data["asked"])]


def person(data: dict, i: int) -> dict:
    """Person `i` with LDBC Person's scalar attributes; all but `firstName`
    are a function of `i` and the seed, and nothing reads them."""
    h = (i * 2654435761 + data["seed"]) & 0xFFFFFFFF
    return {
        "id": i,
        "firstName": data["names"][int(data["first"][i])],
        "lastName": data["last_names"][h % len(data["last_names"])],
        "gender": "female" if h & 1 else "male",
        "birthday": f"{1950 + h % 50:04d}-{1 + (h >> 8) % 12:02d}-{1 + (h >> 12) % 28:02d}",
        "creationDate": f"{2010 + (h >> 4) % 3:04d}-{1 + (h >> 16) % 12:02d}-{1 + (h >> 20) % 28:02d}T{(h >> 3) % 24:02d}:{(h >> 7) % 60:02d}:{(h >> 11) % 60:02d}.000Z",
        "locationIP": f"{1 + h % 223}.{(h >> 8) & 255}.{(h >> 16) & 255}.{(h >> 24) & 255}",
        "browserUsed": ("Firefox", "Chrome", "Internet Explorer", "Safari", "Opera")[h % 5],
    }


# ------------------------------------------------------------------ reference
def filtered_path_counts(pairs: np.ndarray, nodes: int, starts: np.ndarray, hops: int,
                         first: np.ndarray, asked: np.ndarray) -> np.ndarray:
    """Walks of `hops` edge records from each start node that end at a node
    whose name is the one asked. Dense adjacency in float64 (`x @ adj @ adj`,
    then the masked sum): every product and sum stays far below 2**53, so
    the arithmetic is exact."""
    adj = np.zeros((nodes, nodes), dtype=np.float64)
    np.add.at(adj, (pairs[:, 0], pairs[:, 1]), 1.0)
    x = adj[starts]
    for _ in range(hops - 1):
        x = x @ adj
    return np.rint((x * (first[None, :] == asked[:, None])).sum(axis=1)).astype(np.int64)


def reference(cfg: dict, data: dict) -> dict:
    counts = filtered_path_counts(data["pairs"], data["nodes"], data["starts"], int(cfg["hops"]),
                                  data["first"], data["asked"])
    return {"counts": counts, "counts_control": base.as_bfloat16(counts)}


# ------------------------------------------------------------------ load
def load(ds, cfg: dict, data: dict, execute_ok) -> dict:
    from surrealdb_tpu.sql.value import Thing

    node_tb, edge_tb, pairs = cfg["node_table"], cfg["edge_table"], data["pairs"]
    statement = cfg["statements"][cfg["load"]["probe"]]
    for ddl in cfg["ddl"]:
        execute_ok(ds, ddl)
    secs = 0.0
    for i in range(0, data["nodes"], INGEST_BATCH):
        rows = [person(data, j) for j in range(i, min(i + INGEST_BATCH, data["nodes"]))]
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT INTO {node_tb} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    # every acknowledged person row is read back before the first count
    got = int(execute_ok(ds, f"SELECT count() AS c FROM {node_tb} GROUP ALL")[-1]["result"][0]["c"])
    if got != data["nodes"]:
        raise RuntimeError(f"{got} {node_tb} rows read back of {data['nodes']} acknowledged")
    # a server that is already answering when the edges arrive (the configuration's `load` says why)
    execute_ok(ds, cfg["load"]["ask_before_edges"],
               {statement["bind"]: {"p": 0, "fn": data["names"][int(data["first"][0])]}})
    for i in range(0, pairs.shape[0], INGEST_BATCH):
        rows = [
            {"in": Thing(node_tb, int(a)), "out": Thing(node_tb, int(b))}
            for a, b in pairs[i : i + INGEST_BATCH]
        ]
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT RELATION INTO {edge_tb} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    probe(ds, cfg, data, statement, execute_ok)
    return {"acknowledged": int(data["nodes"] + pairs.shape[0]), "insert_s": secs, "unit": "rows"}


def probe(ds, cfg: dict, data: dict, statement: dict, execute_ok) -> None:
    """One statement of the pool, the one whose start person knows the
    fewest (the cheapest for a host walk to answer): a program that does not
    hand it to the device cannot serve the cell, and is refused here."""
    degree = np.bincount(data["pairs"][:, 0], minlength=data["nodes"])
    entry = pool(cfg, data)[int(np.argmin(degree[data["starts"]]))]
    before = ds.dispatch.stats()["submitted"]
    execute_ok(ds, statement["sql"], {statement["bind"]: entry})
    made = ds.dispatch.stats()["submitted"] - before
    if made != statement["dispatches"]:
        raise RuntimeError(
            f"the filtered count was served by the host walk: {made} device dispatches for the "
            f"loader's probe {entry} where the statement makes {statement['dispatches']}"
        )


def count_sql(cfg: dict) -> list:
    return [(f"SELECT count() AS c FROM {tb} GROUP ALL", None) for tb in (cfg["node_table"], cfg["edge_table"])]


release = base.release
wait_background = base.wait_background
kernel_shapes = base.kernel_shapes
check = base.check
