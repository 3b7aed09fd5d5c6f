"""Multi-chip sharded execution over a jax.sharding.Mesh.

Role of the reference's distributed scale-out (reference: kvs/tikv/, kvs/fdb/
— scale via a distributed KV cluster; SURVEY §2.5) re-designed TPU-first:
compute-side scale-out shards the device-resident index mirrors (vector
matrices, CSR edge tables) across chips over ICI and uses XLA collectives
instead of KV-client RPC:

- vector kNN: corpus rows sharded over the 'data' mesh axis; each chip
  computes distances + a local top-k on its shard (MXU matmul), then one
  all-gather of k·n_devices candidates and a tiny global top-k. Collective
  payload is O(k·devices), not O(N).
- graph frontier expansion: CSR edge arrays sharded by source-node range;
  frontier gathers are local, results concatenate via all_gather.

Everything here is pure jax — it runs identically on a virtual
`--xla_force_host_platform_device_count=8` CPU mesh (tests) and a real TPU
slice (deployment).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from surrealdb_tpu.ops.distances import map_queries, pairwise_distance


def make_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (axis,))


def shard_corpus(mesh: Mesh, x: np.ndarray, axis: str = "data") -> jax.Array:
    """Place a [N, D] corpus row-sharded across the mesh. N must divide by
    the device count — callers pad with masked rows first."""
    sharding = NamedSharding(mesh, P(axis, None))
    return jax.device_put(x, sharding)


@functools.lru_cache(maxsize=64)
def _knn_searcher(mesh, k, metric, axis):
    """Jitted sharded exact kNN, cached per (mesh, params): one compiled
    executable per query-tile shape. Called bare, a shard_map function runs
    eagerly — re-traced and dispatched op by op on every statement."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    def _knn(x_local, m_local, q):
        d = pairwise_distance(q, x_local, metric)  # [Q, N/n]
        d = jnp.where(m_local[None, :], d, jnp.inf)
        shard_rows = x_local.shape[0]
        kk = min(k, shard_rows)
        neg, idx_local = jax.lax.top_k(-d, kk)  # [Q, kk]
        # globalize indices: this shard's row-offset
        shard_id = jax.lax.axis_index(axis)
        idx_global = idx_local + shard_id * shard_rows
        # gather every shard's candidates -> [n_dev*kk] per query
        d_all = jax.lax.all_gather(-neg, axis, axis=1, tiled=True)  # [Q, n*kk]
        i_all = jax.lax.all_gather(idx_global, axis, axis=1, tiled=True)
        neg2, pos = jax.lax.top_k(-d_all, k)  # [Q, k]
        return -neg2, jnp.take_along_axis(i_all, pos, axis=1)

    return jax.jit(_knn)


def sharded_knn(
    mesh: Mesh,
    corpus: jax.Array,
    mask: jax.Array,
    queries: jax.Array,
    k: int,
    metric: str = "euclidean",
    axis: str = "data",
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN over a row-sharded corpus.

    corpus: [N, D] sharded (axis, None); mask: [N] sharded; queries: [Q, D]
    replicated. Returns (dists [Q, k], global_idx [Q, k]).

    Per-shard local top-k (all MXU work stays on-chip), then an all_gather of
    the k-candidate sets — the ICI payload is tiny.
    """
    return _knn_searcher(mesh, k, metric, axis)(corpus, mask, queries)


@functools.lru_cache(maxsize=64)
def _subset_searcher(mesh, k, metric, axis):
    """Jitted sharded exact kNN over a filter's passing slots, cached per
    (mesh, params) as `_knn_searcher` is."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(None), P(), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    def _subset(x_local, slots, n_pass, q):
        # every chip scores the passing slots that fall in ITS rows: the
        # gather is local, the rest of the slot array is masked out
        shard_rows = x_local.shape[0]
        local = slots - jax.lax.axis_index(axis) * shard_rows
        mine = (local >= 0) & (local < shard_rows) & (jnp.arange(slots.shape[0]) < n_pass)
        cand = x_local[jnp.clip(local, 0, shard_rows - 1)]
        d = jnp.where(mine[None, :], pairwise_distance(q, cand, metric), jnp.inf)
        neg, idx = jax.lax.top_k(-d, k)  # [Q, k]
        found = jnp.where(neg > -jnp.inf, slots[idx], -1)
        d_all = jax.lax.all_gather(-neg, axis, axis=1, tiled=True)  # [Q, n*k]
        i_all = jax.lax.all_gather(found, axis, axis=1, tiled=True)
        neg2, pos = jax.lax.top_k(-d_all, k)
        return -neg2, jnp.take_along_axis(i_all, pos, axis=1)

    return jax.jit(_subset)


def sharded_subset_knn(
    mesh: Mesh,
    corpus: jax.Array,
    slots: jax.Array,
    n_pass: jax.Array,
    queries: jax.Array,
    k: int,
    metric: str = "euclidean",
    axis: str = "data",
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN among the corpus rows a filter lets through, over a
    row-sharded corpus (the mesh composition of
    ops/distances.py::knn_subset_search): `slots` [S] are their global
    slots, replicated, the first `n_pass` real. Per-shard local top-k, then
    the O(k*devices) all-gather `sharded_knn` makes. Returns
    (dists [Q, k], global slots [Q, k]); misses surface as +inf / -1."""
    return _subset_searcher(mesh, k, metric, axis)(corpus, slots, n_pass, queries)


def sharded_knn_2d(
    mesh: Mesh,
    corpus: jax.Array,
    mask: jax.Array,
    queries: jax.Array,
    k: int,
    data_axis: str = "data",
    feat_axis: str = "model",
) -> Tuple[jax.Array, jax.Array]:
    """Exact euclidean kNN over a 2-D sharded corpus [N/d_data, D/d_model].

    The feature axis is tensor-parallel: each chip holds a D-slice, computes
    partial q·x and partial squared norms, and a psum over the 'model' axis
    reconstructs full distances (the TP analog of sharded matmul). The row
    axis then does the data-parallel local-top-k + all_gather as in
    sharded_knn. Queries are sharded on features, replicated on rows.
    """
    n_dev = mesh.shape[data_axis]
    n_total = corpus.shape[0]
    shard_rows = n_total // n_dev

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(data_axis, feat_axis), P(data_axis), P(None, feat_axis)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    def _knn(x_local, m_local, q_local):
        # partial distance terms over the local feature slice
        qq = jnp.sum(q_local.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        xx = jnp.sum(x_local.astype(jnp.float32) ** 2, axis=-1)
        qx = jnp.dot(q_local, x_local.T, preferred_element_type=jnp.float32)
        d2 = qq + xx[None, :] - 2.0 * qx
        d2 = jax.lax.psum(d2, feat_axis)  # TP collective over ICI
        d = jnp.sqrt(jnp.maximum(d2, 0.0))
        d = jnp.where(m_local[None, :], d, jnp.inf)
        kk = min(k, x_local.shape[0])
        neg, idx_local = jax.lax.top_k(-d, kk)
        shard_id = jax.lax.axis_index(data_axis)
        idx_global = idx_local + shard_id * shard_rows
        d_all = jax.lax.all_gather(-neg, data_axis, axis=1, tiled=True)
        i_all = jax.lax.all_gather(idx_global, data_axis, axis=1, tiled=True)
        neg2, pos = jax.lax.top_k(-d_all, k)
        return -neg2, jnp.take_along_axis(i_all, pos, axis=1)

    return _knn(corpus, mask, queries)


@functools.lru_cache(maxsize=64)
def _ivf_searcher(mesh, k, nprobe, kk, k_out, metric, probe_metric, axis):
    """Jitted sharded IVF probe+rerank, cached per (mesh, params) so repeated
    dispatches reuse one compiled executable instead of re-tracing."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(None, None),        # centroids, replicated
            P(axis, None, None),  # per-shard list rows [n_dev, C, L]
            P(axis, None, None),  # per-shard list masks
            P(axis, None),        # corpus rows, sharded
            P(axis),              # per-slot residual prefilter, sharded
            P(None, None),        # queries, replicated
        ),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    def _search(c, lr3, lm3, x_local, sok_local, q):
        lr, lm = lr3[0], lm3[0]  # this shard's [C, L] slab
        shard_rows = x_local.shape[0]
        dc = pairwise_distance(q, c, probe_metric)  # [Q, C]
        probes = jax.lax.top_k(-dc, nprobe)[1]  # [Q, nprobe]
        shard_id = jax.lax.axis_index(axis)

        def one(qi, pr):
            rows = lr[pr].reshape(-1)  # [nprobe*L] local row offsets
            rows_c = jnp.clip(rows, 0, shard_rows - 1)
            # the columnar residual-WHERE mask ANDs in per local slot, so
            # top-k is computed among MATCHING rows only (parity with the
            # single-chip ivf/ivf-host strategies)
            m = lm[pr].reshape(-1) & sok_local[rows_c]
            cand = x_local[rows_c]
            d = pairwise_distance(qi[None, :], cand, metric)[0]
            d = jnp.where(m, d, jnp.inf)
            neg, idx = jax.lax.top_k(-d, kk)
            g = jnp.where(neg > -jnp.inf, rows[idx] + shard_id * shard_rows, -1)
            return -neg, g

        d_loc, i_loc = map_queries(
            one, q, probes, nprobe * int(lr.shape[1]), x_local
        )  # [Q, kk]
        # gather every shard's k candidates — ICI payload O(k*devices)
        d_all = jax.lax.all_gather(d_loc, axis, axis=1, tiled=True)
        i_all = jax.lax.all_gather(i_loc, axis, axis=1, tiled=True)
        neg2, pos = jax.lax.top_k(-d_all, k_out)
        return -neg2, jnp.take_along_axis(i_all, pos, axis=1)

    return jax.jit(_search)


def sharded_ivf_search(
    mesh: Mesh,
    cents: jax.Array,
    list_rows: jax.Array,
    list_mask: jax.Array,
    corpus: jax.Array,
    queries: jax.Array,
    k: int,
    nprobe: int,
    metric: str = "euclidean",
    probe_metric: str = "euclidean",
    axis: str = "data",
    slot_ok: "jax.Array" = None,
) -> Tuple[jax.Array, jax.Array]:
    """Sharded IVF ANN search (the mesh composition of idx/ivf.py).

    Centroids + queries replicated; the corpus row-sharded; the inverted
    lists pre-partitioned by owning shard into [n_dev, C, L] local-row
    tables (IvfState._device_sharded). Each chip probes the same nprobe
    lists but gathers/reranks only ITS members, then one all-gather merges
    per-shard top-k — same O(k*devices) collective as sharded_knn, but
    sublinear per-shard work (the fix for VERDICT r3 weak #1: ANN now
    composes with multi-chip sharding instead of falling back to exact).
    `slot_ok` [corpus rows] is the per-slot residual prefilter (columnar
    WHERE mask), sharded alongside the corpus; None searches every slot.
    Returns (dists [Q, k_out], global slots [Q, k_out]); k_out ≤ k when the
    probed lists cannot yield k candidates.
    """
    import jax.numpy as jnp

    n_dev = mesh.shape[axis]
    L = int(list_rows.shape[2])
    kk = min(k, nprobe * L)
    k_out = min(k, n_dev * kk)
    if slot_ok is None:
        slot_ok = jnp.ones(int(corpus.shape[0]), dtype=bool)
    run = _ivf_searcher(mesh, k, nprobe, kk, k_out, metric, probe_metric, axis)
    return run(cents, list_rows, list_mask, corpus, slot_ok, queries)


# ------------------------------------------------------------------ graph
def sharded_frontier_hop(
    mesh: Mesh,
    indptr: jax.Array,
    indices: jax.Array,
    frontier: jax.Array,
    frontier_mask: jax.Array,
    max_degree: int,
    axis: str = "data",
) -> Tuple[jax.Array, jax.Array]:
    """One BFS hop over a replicated CSR with a sharded frontier.

    indptr: [N+1], indices: [E] (replicated; edge tables are far smaller than
    vector matrices). frontier: [F] node ids padded to a multiple of the
    device count, frontier_mask: [F]. Each device expands its frontier slice
    with a fixed-width (max_degree) gather — compiler-friendly static shapes —
    then results all_gather back. Returns (neighbors [F*max_degree], mask).
    Dedup happens host-side between hops (sort-unique on small id sets).
    """

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None), P(None), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    def _hop(ptr, idx, fr, fm):
        starts = ptr[fr]  # [f]
        degs = ptr[fr + 1] - starts
        offs = jnp.arange(max_degree)[None, :]  # [1, max_degree]
        take = starts[:, None] + offs  # [f, max_degree]
        valid = (offs < degs[:, None]) & fm[:, None]
        take = jnp.clip(take, 0, idx.shape[0] - 1)
        nb = idx[take]  # [f, max_degree]
        return nb.reshape(-1), valid.reshape(-1)

    return _hop(indptr, indices, frontier, frontier_mask)


def graftcheck_sites():
    """Audit contracts of the mesh runners (compile_log subsystems
    `knn_sharded` / `ivf_sharded`). These are the kernels the ROADMAP's
    multi-host refactor rides on: scripts/graftcheck lowers them under a
    simulated 8-device mesh and asserts the ONLY collective in the
    StableHLO is the declared O(k·devices) top-k merge all-gather — XLA
    silently inserting an all-gather of the corpus (or a gather-then-
    dynamic-slice reshard) is exactly the 10x regression the SNIPPETS
    [2]/[3] HLO assertion exists to catch."""
    n_dev, dim, cap, k = 8, 64, 2048, 10
    C, L, nprobe = 64, 32, 8

    def build_knn(shape):
        mesh = make_mesh(n_dev)
        args = (
            jax.ShapeDtypeStruct((cap, dim), jnp.float32),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
            jax.ShapeDtypeStruct((shape["tile"], dim), jnp.float32),
        )
        metric, kk = shape["metric"], shape["k"]
        return (
            lambda c, m, q: sharded_knn(mesh, c, m, q, kk, metric),
            args,
        )

    def build_ivf(shape):
        mesh = make_mesh(n_dev)
        args = (
            jax.ShapeDtypeStruct((C, dim), jnp.float32),
            jax.ShapeDtypeStruct((n_dev, C, L), jnp.int32),
            jax.ShapeDtypeStruct((n_dev, C, L), jnp.bool_),
            jax.ShapeDtypeStruct((cap, dim), jnp.float32),
            jax.ShapeDtypeStruct((shape["tile"], dim), jnp.float32),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
        )
        metric, kk = shape["metric"], shape["k"]
        # mirror the serving path (idx/ivf.py search_batch_sharded): the
        # probe metric follows the serving metric when the quantizer can
        # probe in it — auditing euclidean probes under a cosine serve
        # would bless a lowering the engine never compiles
        from surrealdb_tpu.idx.ivf import _PROBE_METRICS

        probe_metric = metric if metric in _PROBE_METRICS else "euclidean"

        def run(cents, rows, mask, corpus, q, slot_ok):
            return sharded_ivf_search(
                mesh, cents, rows, mask, corpus, q, kk, nprobe,
                metric=metric, probe_metric=probe_metric, slot_ok=slot_ok,
            )

        return run, args

    def build_subset(shape):
        mesh = make_mesh(n_dev)
        args = (
            jax.ShapeDtypeStruct((cap, dim), jnp.float32),
            jax.ShapeDtypeStruct((1024,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((shape["tile"], dim), jnp.float32),
        )
        metric, kk = shape["metric"], shape["k"]
        return (
            lambda c, s, n, q: sharded_subset_knn(mesh, c, s, n, q, kk, metric),
            args,
        )

    def tiles():
        from surrealdb_tpu.utils.num import warm_tile_sizes

        return warm_tile_sizes()

    knn_shapes = [
        {"label": f"t{t}_d{dim}_c{cap}_{m}_k{k}_mesh{n_dev}",
         "tile": t, "metric": m, "k": k}
        for t, m in [(t, "euclidean") for t in tiles()] + [(8, "cosine")]
    ]
    ivf_shapes = [
        {"label": f"t{t}_d{dim}_c{cap}_C{C}_L{L}_p{nprobe}_{m}_k{k}_mesh{n_dev}",
         "tile": t, "metric": m, "k": k}
        for t, m in [(t, "euclidean") for t in tiles()] + [(8, "cosine")]
    ]
    subset_shapes = [
        {"label": f"t{t}_d{dim}_c{cap}_s1024_{m}_k{k}_mesh{n_dev}",
         "tile": t, "metric": m, "k": k}
        for t, m in [(t, "euclidean") for t in tiles()] + [(8, "cosine")]
    ]
    return [
        {
            "subsystem": "knn_sharded",
            "module": __name__,
            "kind": "sharded",
            "mesh_devices": n_dev,
            # the intentional top-k candidate merge (O(k·devices) payload)
            "allowed_collectives": ("all-gather",),
            "out_dtypes": ("float32", "int32"),
            "shapes": knn_shapes,
            "build": build_knn,
        },
        {
            "subsystem": "ivf_sharded",
            "module": __name__,
            "kind": "sharded",
            "mesh_devices": n_dev,
            "allowed_collectives": ("all-gather",),
            "out_dtypes": ("float32", "int32"),
            "shapes": ivf_shapes,
            "build": build_ivf,
        },
        {
            "subsystem": "knn_subset_sharded",
            "module": __name__,
            "kind": "sharded",
            "mesh_devices": n_dev,
            "allowed_collectives": ("all-gather",),
            "out_dtypes": ("float32", "int32"),
            "shapes": subset_shapes,
            "build": build_subset,
        },
    ]


@functools.partial(jax.jit, static_argnames=("n_nodes",))
def dedup_frontier(nodes: jax.Array, mask: jax.Array, n_nodes: int):
    """On-device frontier dedup via a dense visited bitmap scatter.

    Returns (unique_sorted_nodes [padded with n_nodes], new_mask). Fixed
    output shape = input shape, so jit-stable across hops.
    """
    marks = jnp.zeros(n_nodes + 1, dtype=jnp.bool_)
    safe = jnp.where(mask, nodes, n_nodes)
    marks = marks.at[safe].set(True)
    marks = marks.at[n_nodes].set(False)
    present = jnp.nonzero(marks, size=nodes.shape[0], fill_value=n_nodes)[0]
    return present, present < n_nodes
