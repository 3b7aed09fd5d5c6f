"""Batched vector-distance kernels (JAX / XLA, MXU-friendly).

Role of the reference's per-pair Distance::calculate loop (reference:
core/src/idx/trees/vector.rs:541-550) re-designed TPU-first: instead of one
scalar distance per candidate, the whole candidate set is a device-resident
[N, D] matrix and distances to the query batch [Q, D] compute as one fused
matmul-shaped op on the MXU (cosine/euclidean/dot decompose into X @ Q^T),
followed by an on-device top-k. This is the exact seam named by SURVEY §2.5
("pairwise distance matmul" + "top-k kernel").

All functions are jittable with static metric/k; shapes are padded by the
callers (idx/knn.py) to tile boundaries to avoid recompilation churn.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# distance names supported (reference vector.rs Distance enum)
METRICS = (
    "euclidean",
    "cosine",
    "manhattan",
    "chebyshev",
    "hamming",
    "jaccard",
    "pearson",
)


def _minkowski_order(metric: str) -> float:
    return float(metric.split(":", 1)[1])


@functools.partial(jax.jit, static_argnames=("metric",))
def pairwise_distance(q: jax.Array, x: jax.Array, metric: str = "euclidean") -> jax.Array:
    """Distances between each query row and each corpus row.

    q: [Q, D] float32/bfloat16 queries
    x: [N, D] corpus
    -> [Q, N] float32 distances
    """
    if metric == "euclidean":
        # ||q - x||^2 = ||q||^2 + ||x||^2 - 2 q·x  — the q·x term is one MXU
        # matmul over the whole batch.
        qq = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)  # [Q,1]
        xx = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)  # [N]
        qx = jnp.dot(q, x.T, preferred_element_type=jnp.float32)  # [Q,N] MXU
        d2 = qq + xx[None, :] - 2.0 * qx
        return jnp.sqrt(jnp.maximum(d2, 0.0))
    if metric == "cosine":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-30)
        xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-30)
        sim = jnp.dot(qn, xn.T, preferred_element_type=jnp.float32)  # MXU
        return 1.0 - sim
    if metric == "manhattan":
        return jnp.sum(jnp.abs(q[:, None, :] - x[None, :, :]), axis=-1).astype(jnp.float32)
    if metric == "chebyshev":
        return jnp.max(jnp.abs(q[:, None, :] - x[None, :, :]), axis=-1).astype(jnp.float32)
    if metric == "hamming":
        return jnp.sum(q[:, None, :] != x[None, :, :], axis=-1).astype(jnp.float32)
    if metric == "jaccard":
        # treat vectors as weighted sets: 1 - sum(min)/sum(max)
        mn = jnp.sum(jnp.minimum(q[:, None, :], x[None, :, :]), axis=-1)
        mx = jnp.sum(jnp.maximum(q[:, None, :], x[None, :, :]), axis=-1)
        return (1.0 - mn / jnp.maximum(mx, 1e-30)).astype(jnp.float32)
    if metric == "pearson":
        qc = q - jnp.mean(q, axis=-1, keepdims=True)
        xc = x - jnp.mean(x, axis=-1, keepdims=True)
        qn = qc / jnp.maximum(jnp.linalg.norm(qc, axis=-1, keepdims=True), 1e-30)
        xn = xc / jnp.maximum(jnp.linalg.norm(xc, axis=-1, keepdims=True), 1e-30)
        corr = jnp.dot(qn, xn.T, preferred_element_type=jnp.float32)  # MXU
        return 1.0 - corr
    if metric.startswith("minkowski"):
        p = _minkowski_order(metric)
        diff = jnp.abs(q[:, None, :] - x[None, :, :]).astype(jnp.float32)
        return jnp.sum(diff**p, axis=-1) ** (1.0 / p)
    raise ValueError(f"unknown distance metric {metric!r}")


def gather_budget_bytes() -> int:
    """Most gathered-candidate bytes one launch may hold at a time: a
    sixteenth of the device's memory limit as the runtime reports it
    (`memory_stats()["bytes_limit"]`), and of 16 GiB on a backend that
    reports none (CPU). About 1 GiB on a 16 GB v5e, which is the one point
    that has run on a chip (PR 21: peak HBM 2.65 GB with pipeline depth 2
    and the 1/8/64-wide warmers in flight); no larger share was tried."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 16 << 30)) // 16


def map_queries(one, q, probes, cand_rows: int, x):
    """`one(query, its probes)` over a query tile of a gather-then-rerank
    kernel (IVF, single-device and per shard). The gather holds
    `cand_rows` rows of `x` per query in the corpus dtype plus the f32
    working copy the distance matmul may make of them. IVF lists pad to
    the power of two above the LONGEST one, which training holds to twice
    the mean (1M x 768 on the chip, PR 32: ~2,100 lists, none over 1,955
    rows, pad 2,048: 6 probes are 12,288 rows, 57 MB per query, where the
    unbounded hub lists of PR 21 padded to 32,768 and a query held
    906 MB), so a tile that fits `gather_budget_bytes()` is one vmap and a
    wider one runs as sequential sub-batches INSIDE the same executable:
    same tile shapes, same results, bounded HBM."""
    per_query = cand_rows * int(x.shape[1]) * (x.dtype.itemsize + 4)
    batch = max(1, gather_budget_bytes() // per_query)
    if batch >= q.shape[0]:
        return jax.vmap(one)(q, probes)
    return jax.lax.map(lambda qp: one(*qp), (q, probes), batch_size=batch)


@functools.partial(jax.jit, static_argnames=("metric", "k"))
def knn_search(
    q: jax.Array, x: jax.Array, mask: jax.Array, metric: str, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Fused distance + top-k over a padded corpus.

    q: [Q, D] queries; x: [N, D] padded corpus; mask: [N] bool valid-rows
    -> (dists [Q, k], idxs [Q, k]); padded rows surface as +inf
    """
    d = pairwise_distance(q, x, metric)
    d = jnp.where(mask[None, :], d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)  # top_k is max-k; negate for min-k
    return -neg, idx


@functools.partial(jax.jit, static_argnames=("metric", "k"))
def knn_subset_search(
    q: jax.Array, x: jax.Array, slots: jax.Array, n_pass: jax.Array, metric: str, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Exact distance + top-k over the corpus rows a filter lets through.

    q: [Q, D] queries; x: [N, D] corpus; slots: [S] int32 corpus slots of
    the passing rows, the first `n_pass` (a scalar on the device, so a
    slice that grows inside its padded size keeps this program) real and
    the rest pad. One gather serves the whole query tile.
    -> (dists [Q, k], corpus slots [Q, k]); misses surface as +inf / -1
    """
    cand = x[slots]  # [S, D] in the corpus dtype
    d = pairwise_distance(q, cand, metric)
    d = jnp.where(jnp.arange(slots.shape[0])[None, :] < n_pass, d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, jnp.where(neg > -jnp.inf, slots[idx], -1)


def pad_rows(arr: np.ndarray, multiple: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pad [N, D] to the next row-count multiple; returns (padded, mask)."""
    n = arr.shape[0]
    target = max(multiple, ((n + multiple - 1) // multiple) * multiple)
    mask = np.zeros(target, dtype=bool)
    mask[:n] = True
    if target == n:
        return arr, mask
    pad = np.zeros((target - n,) + arr.shape[1:], dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0), mask


def knn_search_host(
    q: np.ndarray, x: np.ndarray, metric: str, k: int, x_sq_norms=None
) -> Tuple[np.ndarray, np.ndarray]:
    """numpy twin of knn_search for corpora below the device-dispatch
    threshold (cnf.TPU_KNN_ONDEVICE_THRESHOLD) — a dispatch round trip
    costs more than scanning a few thousand rows on host. Pass cached
    `x_sq_norms` (mirror host_search_view) to skip the per-call corpus
    pass for euclidean."""
    # float32 BLAS: the strongest single-thread CPU formulation (an f64 cast
    # would copy the whole corpus per call and halve gemm throughput)
    q = np.asarray(q, dtype=np.float32)
    x = np.asarray(x, dtype=np.float32)
    if metric == "euclidean":
        xx = x_sq_norms if x_sq_norms is not None else (x**2).sum(1)
        d = np.sqrt(
            np.maximum(
                (q**2).sum(1)[:, None] + xx[None, :] - 2.0 * (q @ x.T),
                0.0,
            )
        )
    elif metric == "cosine":
        qn = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-30)
        xn = x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-30)
        d = 1.0 - qn @ xn.T
    else:
        d = np.stack([[distance_single(a, b, metric) for b in x] for a in q])
    kk = min(k, x.shape[0])
    part = np.argpartition(d, kk - 1, axis=1)[:, :kk]
    row = np.arange(q.shape[0])[:, None]
    order = np.argsort(d[row, part], axis=1)
    idx = part[row, order]
    return d[row, idx].astype(np.float32), idx.astype(np.int64)


# -------------------------------------------------------------- single-pair
def distance_single(a, b, metric: str) -> float:
    """Scalar convenience for the vector:: functions (host path for tiny
    inputs; the batched kernels above are the real compute path)."""
    an = np.asarray(a, dtype=np.float64)
    bn = np.asarray(b, dtype=np.float64)
    if an.shape != bn.shape:
        from surrealdb_tpu.err import InvalidArgumentsError

        raise InvalidArgumentsError(
            "vector::distance", "The two vectors must be of the same dimension."
        )
    if metric == "euclidean":
        return float(np.linalg.norm(an - bn))
    if metric == "cosine":
        na = np.linalg.norm(an)
        nb = np.linalg.norm(bn)
        if na == 0 or nb == 0:
            return 1.0
        return float(1.0 - np.dot(an, bn) / (na * nb))
    if metric == "manhattan":
        return float(np.sum(np.abs(an - bn)))
    if metric == "chebyshev":
        return float(np.max(np.abs(an - bn)))
    if metric == "hamming":
        return float(np.sum(an != bn))
    if metric == "jaccard":
        mx = np.sum(np.maximum(an, bn))
        if mx == 0:
            return 0.0
        return float(1.0 - np.sum(np.minimum(an, bn)) / mx)
    if metric == "pearson":
        ac = an - an.mean()
        bc = bn - bn.mean()
        na, nb = np.linalg.norm(ac), np.linalg.norm(bc)
        if na == 0 or nb == 0:
            return 1.0
        return float(1.0 - np.dot(ac, bc) / (na * nb))
    if metric.startswith("minkowski"):
        p = _minkowski_order(metric)
        return float(np.sum(np.abs(an - bn) ** p) ** (1.0 / p))
    raise ValueError(f"unknown distance metric {metric!r}")
