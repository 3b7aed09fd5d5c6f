"""Deployment kind `vector_knn_filtered`: `vector_knn`'s table and index,
every row carrying an int field beside its vector, searched with
`<|k,ef|>` AND a bound threshold on that field that lets a small share of
the rows through (VectorDBBench's filtering search case).

The corpus and the query pool are `vector_knn`'s own generator call, so a
configuration with that kind's generator and `corpus_seed` holds its first
rows; the threshold, the exact FILTERED reference, the loader and the two
numbers that hold the guarantee (no returned row fails the filter, no answer
is short while k rows pass) are here and read nothing the program made.
"""

from __future__ import annotations

import time

import numpy as np

from deployments import vector_knn as base

KIND = "vector_knn_filtered"
INGEST_BATCH = base.INGEST_BATCH


# ------------------------------------------------------------------ data
def generate(cfg: dict, sizes: dict, seed: int) -> dict:
    """`vector_knn`'s corpus and queries, the filtered field of every row
    (its id) and the one threshold every pool entry binds: the last
    `pass_rows` ids pass."""
    data = base.generate(cfg, sizes, seed)
    rows = data["corpus"].shape[0]
    return {**data, "n": np.arange(rows, dtype=np.int64), "lo": rows - int(sizes["pass_rows"])}


def pool(cfg: dict, data: dict) -> list:
    lo = int(data["lo"])
    return [{"v": q, "lo": lo} for q in data["queries"].astype(np.float64).tolist()]


# ------------------------------------------------------------------ reference
def reference(cfg: dict, data: dict) -> dict:
    """`vector_knn`'s reference over the rows that pass (`n >= lo`, by
    NumPy): per pool query the exact float32 neighbours among them, nearest
    first, their float64 squared distances and the int8 control's."""
    passing = np.flatnonzero(data["n"] >= data["lo"])
    ref = base.reference(cfg, {"corpus": data["corpus"][passing], "queries": data["queries"]})
    return {**ref, "ids": passing[ref["ids"]], "lo": int(data["lo"]), "pass_rows": int(passing.size)}


# ------------------------------------------------------------------ load
def load(ds, cfg: dict, data: dict, execute_ok) -> dict:
    """`vector_knn`'s loader with the filtered field in every row."""
    tb, field, corpus, n = cfg["table"], cfg["filter_field"], data["corpus"], data["n"]
    for ddl in cfg["ddl"]:
        execute_ok(ds, ddl)
    secs = 0.0
    for i in range(0, corpus.shape[0], INGEST_BATCH):
        blk = corpus[i : i + INGEST_BATCH]
        rows = [{"id": i + j, "emb": blk[j], field: int(n[i + j])} for j in range(blk.shape[0])]
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT INTO {tb} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    return {"acknowledged": int(corpus.shape[0]), "insert_s": secs, "unit": "rows"}


def count_sql(cfg: dict) -> list:
    """Every acknowledged row's filtered field is read back: no row of the
    generator has a negative one."""
    return [(f"SELECT count() AS c FROM {cfg['table']} WHERE {cfg['filter_field']} >= 0 GROUP ALL", None)]


release = base.release
wait_background = base.wait_background


def kernel_shapes(cfg: dict, data: dict, state: dict) -> dict:
    """What one search needs from the device: the rows that pass."""
    return {
        "dim": int(cfg["dim"]),
        "pass_rows": int((data["n"] >= data["lo"]).sum()),
        "corpus_elem_bytes": int(cfg["device_elem_bytes"]),
        "slot_bytes": 4,
    }


# ------------------------------------------------------------------ check
def check(cfg: dict, ref: dict, records: list) -> dict:
    """`vector_knn`'s three numbers against the filtered truth, and the
    guarantee: returned ids whose field fails the threshold (a row's id is
    its field), and answers shorter than k while k rows pass."""
    out = base.check(cfg, ref, records)
    lim, full = cfg["correct"], min(int(cfg["k"]), ref["pass_rows"])
    ok = [r for r in records if r["status"] == "OK"]
    violations = sum(1 for r in ok for rid in r["ids"] if not (isinstance(rid, int) and rid >= ref["lo"]))
    short = sum(1 for r in ok if len(r["ids"]) < full)
    out["numbers"] += [
        ["filter_violations", violations, "<=", lim["filter_violations_max"]],
        ["short_answers", short, "<=", lim["short_answers_max"]],
    ]
    return out
