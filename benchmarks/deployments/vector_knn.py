"""Deployment kind `vector_knn`: one table with a vector index, loaded in
bulk and searched with `<|k,ef|>`.

Everything that decides `correct` for such a deployment is here and reads
nothing the program made: the seeded generator, the exact float32 reference,
the lower-precision control and the comparison. The generator and the loader
are copied from `chip_smoke.py` (PR 21), which ran them at full size on the
chip; the loader goes through `ds.execute()` exactly as the smoke's does.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

KIND = "vector_knn"
REF_DEPTH = 64  # exact neighbours kept per query, so a served id finds its distance
GEN_BLOCK = 65_536
INGEST_BATCH = 20_000


# ------------------------------------------------------------------ data
def generate(cfg: dict, sizes: dict, seed: int) -> dict:
    """Clustered corpus (mixture of gaussians round seeded centres) in
    float32, block by block from generators spawned off the configuration's
    `corpus_seed` (so threads can draw them side by side and the result does
    not depend on timing): one fixed data set, the same in every run. The
    run's seed draws the pool of queries: corpus rows plus noise."""
    g = cfg["generator"]
    n, dim = int(sizes["rows"]), int(cfg["dim"])
    root = np.random.SeedSequence([int(g["corpus_seed"]), 23])
    head, *blocks = root.spawn(1 + (n + GEN_BLOCK - 1) // GEN_BLOCK)
    centers = np.random.default_rng(head).standard_normal((int(sizes.get("centres", g["centres"])), dim), dtype=np.float32)
    corpus = np.empty((n, dim), dtype=np.float32)

    def fill(i: int) -> None:
        lo, r = i * GEN_BLOCK, np.random.default_rng(blocks[i])
        out = corpus[lo : lo + GEN_BLOCK]
        cid = r.integers(0, centers.shape[0], size=out.shape[0])
        r.standard_normal(out=out, dtype=np.float32)
        out *= np.float32(g["sigma"])
        out += centers[cid]

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(len(blocks))))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFFFFFF, 31]))
    pick = rng.integers(0, n, size=int(sizes["pool"]))
    queries = corpus[pick] + np.float32(g["query_noise"]) * rng.standard_normal(
        (pick.shape[0], dim), dtype=np.float32
    )
    return {"corpus": corpus, "queries": queries}


def pool(cfg: dict, data: dict) -> list:
    """What the load generator binds, one entry per pool query."""
    return data["queries"].astype(np.float64).tolist()


# ------------------------------------------------------------------ reference
def exact_neighbours(corpus: np.ndarray, qs: np.ndarray, depth: int) -> np.ndarray:
    """Ids of the `depth` nearest corpus rows of each query by float32
    euclidean distance. Chunked BLAS as in the smoke's `exact_topk`
    (|x|^2 - 2 q.x per block; |q|^2 is the same for every row of a query, so
    it is added on the threshold's side); of each block only the rows that
    beat a query's worst kept distance are merged into its kept list."""
    nq, depth = qs.shape[0], min(depth, corpus.shape[0])
    q2 = (qs.astype(np.float64) ** 2).sum(axis=1).astype(np.float32)
    best_d = np.full((nq, depth), np.inf, dtype=np.float32)
    best_i = np.zeros((nq, depth), dtype=np.int64)
    kept_q = np.repeat(np.arange(nq), depth)
    step = 32_768
    for lo in range(0, corpus.shape[0], step):
        blk = corpus[lo : lo + step]
        d = qs @ blk.T
        d *= np.float32(-2.0)
        d += np.einsum("ij,ij->i", blk, blk)[None, :]
        if np.isfinite(best_d[:, -1]).all():
            rows, cols = np.nonzero(d < (best_d[:, -1] - q2)[:, None])
        else:  # the kept lists are not full yet: the block's own nearest are the candidates
            take = min(depth, blk.shape[0])
            cols = np.argpartition(d, take - 1, axis=1)[:, :take].ravel()
            rows = np.repeat(np.arange(nq), take)
        all_q = np.concatenate([kept_q, rows])
        all_d = np.concatenate([best_d.ravel(), d[rows, cols] + q2[rows]])
        all_i = np.concatenate([best_i.ravel(), cols + lo])
        order = np.lexsort((all_d, all_q))  # by query, nearest first
        all_q, all_d, all_i = all_q[order], all_d[order], all_i[order]
        rank = np.arange(all_q.size) - np.searchsorted(all_q, np.arange(nq))[all_q]
        keep = rank < depth
        best_d, best_i = all_d[keep].reshape(nq, depth), all_i[keep].reshape(nq, depth)
    return best_i


def squared_distances(rows: np.ndarray, qs: np.ndarray) -> np.ndarray:
    """[nq, depth] float64 squared distances of rows [nq, depth, dim]."""
    diff = rows.astype(np.float64) - qs.astype(np.float64)[:, None, :]
    return np.einsum("qrd,qrd->qr", diff, diff)


def as_int8(rows: np.ndarray) -> np.ndarray:
    """The rows as an int8 store would hold them (one absmax scale a row),
    back in float32: the control's corpus."""
    scale = np.abs(rows).max(axis=-1, keepdims=True) / 127.0
    scale[scale == 0] = 1.0
    return (np.clip(np.rint(rows / scale), -127, 127) * scale).astype(np.float32)


def reference(cfg: dict, data: dict) -> dict:
    """Per pool query: the exact neighbours' ids, nearest first, their
    float64 squared distances from the float32 rows, and the same distances
    from an int8 copy of those rows (the control)."""
    corpus, qs = data["corpus"], data["queries"]
    ids = exact_neighbours(corpus, qs, REF_DEPTH)
    d2 = np.empty(ids.shape, dtype=np.float64)
    d2_control = np.empty(ids.shape, dtype=np.float64)
    for lo in range(0, qs.shape[0], 128):
        rows = corpus[ids[lo : lo + 128]]
        d2[lo : lo + 128] = squared_distances(rows, qs[lo : lo + 128])
        d2_control[lo : lo + 128] = squared_distances(as_int8(rows), qs[lo : lo + 128])
    order = np.argsort(d2, axis=1, kind="stable")
    take = lambda a: np.take_along_axis(a, order, axis=1)  # noqa: E731
    return {"ids": take(ids), "d2": take(d2), "d2_control": take(d2_control)}


# ------------------------------------------------------------------ load
def load(ds, cfg: dict, data: dict, execute_ok) -> dict:
    """DEFINE the table and its index, then INSERT the corpus through the
    embedded entry point in batches; rows acknowledged and INSERT seconds."""
    tb, corpus = cfg["table"], data["corpus"]
    for ddl in cfg["ddl"]:
        execute_ok(ds, ddl)
    secs = 0.0
    for i in range(0, corpus.shape[0], INGEST_BATCH):
        blk = corpus[i : i + INGEST_BATCH]
        rows = [{"id": i + j, "emb": blk[j]} for j in range(blk.shape[0])]
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT INTO {tb} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    return {"acknowledged": int(corpus.shape[0]), "insert_s": secs, "unit": "rows"}


def count_sql(cfg: dict) -> list:
    return [(f"SELECT count() AS c FROM {cfg['table']} GROUP ALL", None)]


def release(data: dict) -> None:
    """The corpus has been loaded and referred to: give its bytes back."""
    data.pop("corpus", None)


def wait_background(ds, cfg: dict, timeout: float) -> dict:
    """Wait for the quantizer to be trained and for every shape warmer; what
    the trained state looks like, for the phase line and the kernel count."""
    from surrealdb_tpu import bg
    from surrealdb_tpu.idx.ivf import default_nprobe

    mirror = ds.index_stores.get(cfg["ns"], cfg["db"], cfg["table"], cfg["index"])
    t0 = time.perf_counter()
    if not mirror.wait_ivf(timeout):
        raise RuntimeError(f"IVF training did not finish in {timeout:.0f}s")
    trained_s = time.perf_counter() - t0
    if not bg.wait_idle(timeout, owner=id(ds)):
        raise RuntimeError(f"shape warmers still running after {timeout:.0f}s")
    ivf = mirror.ivf
    sizes = np.asarray([len(l) for l in ivf.lists], dtype=np.int64)
    state = {
        "centroids": np.asarray(ivf.centroids, dtype=np.float32),
        "list_sizes": sizes,
        "nprobe": int(default_nprobe(ivf.nlists, int(cfg["ef"]))),
    }
    line = {
        "ivf_wait_s": trained_s,
        "nlists": int(ivf.nlists),
        "longest_list": int(sizes.max()),
        "mean_list": float(sizes.mean()),
        "nprobe": state["nprobe"],
    }
    return {"state": state, "line": line}


def kernel_shapes(cfg: dict, data: dict, state: dict) -> dict:
    """What one search needs from the device, from the trained state: the
    rows in the lists each pool query probes, unpadded, by the benchmark's
    own NumPy (nearest centroids by float32 euclidean distance)."""
    cents, sizes, nprobe = state["centroids"], state["list_sizes"], state["nprobe"]
    qs = data["queries"]
    d = (
        (qs**2).sum(axis=1)[:, None]
        + (cents**2).sum(axis=1)[None, :]
        - 2.0 * (qs @ cents.T)
    )
    probes = np.argpartition(d, nprobe - 1, axis=1)[:, :nprobe]
    return {
        "dim": int(cfg["dim"]),
        "nlists": int(cents.shape[0]),
        "nprobe": nprobe,
        "rows_probed_mean": float(sizes[probes].sum(axis=1).mean()),
        "longest_list": int(sizes.max()),
        "corpus_elem_bytes": int(cfg["device_elem_bytes"]),
        "centroid_elem_bytes": 4,
    }


# ------------------------------------------------------------------ check
def check(cfg: dict, ref: dict, records: list) -> dict:
    """Every answer of the window against the reference: recall@k of the ids,
    and the served distances against the float64 distances of the float32
    rows, as the root mean square of the squared distances' errors over the
    mean squared distance. The control's reading of the same pairs is given
    beside it. `numbers` are [name, value, relation, limit]."""
    k = int(cfg["k"])
    lim = cfg["correct"]
    hits = total = 0
    errs, errs_control, refs = [], [], []
    unmatched = pairs = 0
    for r in records:
        if r["status"] != "OK":
            continue
        q = r["q"]
        truth = ref["ids"][q]
        pos = {int(t): j for j, t in enumerate(truth)}
        hits += len(set(r["ids"]) & set(int(t) for t in truth[:k]))
        total += k
        served = r["values"].get(cfg["distance_field"], [])
        for rid, d in zip(r["ids"], served):
            pairs += 1
            j = pos.get(rid)
            if j is None:
                unmatched += 1
                continue
            refs.append(ref["d2"][q, j])
            errs.append(float(d) ** 2 - ref["d2"][q, j])
            errs_control.append(ref["d2_control"][q, j] - ref["d2"][q, j])
    recall = hits / total if total else 0.0
    scale = float(np.mean(refs)) if refs else 1.0
    rms = float(np.sqrt(np.mean(np.square(errs)))) / scale if errs else float("inf")
    rms_control = (
        float(np.sqrt(np.mean(np.square(errs_control)))) / scale if errs else float("nan")
    )
    return {
        "numbers": [
            ["recall_at_10", recall, ">=", lim["recall_at_10_min"]],
            ["distance_rms_rel", rms, "<=", lim["distance_rms_rel_max"]],
            ["unmatched_id_share", unmatched / pairs if pairs else 1.0, "<=", lim["unmatched_id_share_max"]],
        ],
        "control": {"distance_rms_rel_int8": rms_control},
        "metrics": {"recall_at_10": recall},
        "compared": {"answers": total // k if k else 0, "pairs": len(errs)},
    }
