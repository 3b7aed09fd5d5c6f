"""IVF (inverted-file) ANN index — the TPU-native answer to HNSW/MTree.

Role of the reference's graph ANN structures (reference:
core/src/idx/trees/hnsw/mod.rs:337-416 layered beam search, trees/mtree.rs:135
ball-tree kNN) re-designed TPU-first: pointer-chasing beam searches are a poor
fit for the MXU, so `DEFINE INDEX … HNSW|MTREE` executes as a ScaNN-style
IVF: a k-means coarse quantizer (trained on device, MXU matmuls) partitions
the corpus into C lists; a query probes the nprobe nearest lists and exactly
reranks only their members — one fused gather + distance-matmul + top-k
kernel. Sublinear work (nprobe/C of the corpus), tunable recall via the
operator's ef (reference `<|k,ef|>` Ann operator, sql/operator.rs:65).

Quality floors are asserted by recall-vs-brute-force tests
(tests/test_ivf.py), mirroring the reference's hnsw recall suite
(trees/hnsw/mod.rs:828-951).
"""

from __future__ import annotations

import functools
import math
import time as _time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from surrealdb_tpu.ops import distances as D
from surrealdb_tpu.utils.num import next_pow2 as _next_pow2

# metrics whose geometry the coarse quantizer can probe directly; the rest
# probe in euclidean space and rely on exact rerank for the final order
_PROBE_METRICS = {"euclidean", "cosine", "manhattan", "chebyshev"}


def _start_host_copy(*arrs) -> None:
    """Kick the device→host transfer without blocking, so the download
    overlaps remaining device work."""
    for a in arrs:
        a.copy_to_host_async()


def _ivf_shape_key(tile, cents, list_rows, matrix, metric, probe_metric, k, nprobe):
    """Compile-cache key of the fused probe+rerank kernel: every static dim
    XLA keys its executable cache on (compile_log attribution)."""
    return (
        tile, int(matrix.shape[1]), int(matrix.shape[0]), str(matrix.dtype),
        int(cents.shape[0]), int(list_rows.shape[1]), metric, probe_metric,
        k, nprobe,
    )


def default_nlists(n: int) -> int:
    """C ≈ sqrt(N), pow2-clamped to [8, 4096]."""
    return min(max(_next_pow2(int(math.sqrt(max(n, 1)))), 8), 4096)


def default_nprobe(nlists: int, ef: Optional[int]) -> int:
    """Map the HNSW-style ef beam width onto probed-list count. Training
    leaves no list longer than twice the mean of its first assignment
    (`IvfState.train` re-clusters what passes that), so a probe examines
    at most ~2·N/C candidates and ef/10 probes lands near
    the reference's beam-width semantics (search ef=80 → 8 probes ≈ 99%
    recall on clustered data, see tests/test_ivf.py)."""
    if ef is not None and ef > 0:
        return min(max(4, round(ef / 10)), nlists)
    return min(max(4, nlists // 16), nlists)


def subset_size(passing: int) -> int:
    """Slots the subset search of `passing` rows gathers: the power of two
    above them, so that steady writes to a filtered slice keep one compiled
    shape, and never under 1,024 (below that a gather costs a launch's
    fixed time whatever its height)."""
    return max(_next_pow2(passing), 1024)


def filtered_route(passing: int, alive: int, nlists: int, nprobe: int, pad: int) -> Tuple[str, int]:
    """Which of two searches serves a kNN whose WHERE `passing` of the
    `alive` rows pass, by the rows each would gather: the SUBSET search
    scores every passing row exactly (`subset_size(passing)` slots), the
    WIDENED search probes the IVF lists with the mask applied, its probes
    multiplied by the inverse of the passing share so that it still sees as
    many passing candidates as an unfiltered search sees rows, and rounded
    up to a power of two (`min(nlists, next_pow2(ceil(nprobe * alive /
    passing)))` lists of `pad` slots): the probe count is a compiled shape
    of `_ivf_search`, so a threshold that varies meets log2(nlists)
    programs at most, not one a passing share. The smaller wins:
    ("subset", slots) or ("widened", probes). Read by every IVF strategy
    (`ivf`, `ivf-sharded`, `ivf-host`), from two row counts the program
    already has; nothing else chooses the route."""
    slots = subset_size(passing)
    probes = min(nlists, _next_pow2(-(-nprobe * alive // max(passing, 1))))
    if slots <= probes * pad:
        return "subset", slots
    return "widened", probes


_ASSIGN_CHUNK = 65536  # rows a call of `_assign_chunk`: one compiled shape
# re-clustering rounds of one training: the pool shrank by ~0.55 a round where
# it was measured (8 rounds at 1M x 768), so none is left long before this
_MAX_ROUNDS = 32


def _nearest(chunk, cents, n_cents, n_rows):
    """Nearest of the first `n_cents` centroids for every row of a tile
    (euclidean), with the per-centroid sums and counts of the tile's first
    `n_rows` rows. Both tables are padded (the centroid table to the first
    training's count, a tile to its fixed height) and the two counts say
    how much of each is real, so every k-means step and every assignment
    of one training runs the same two compiled programs."""
    import jax.numpy as jnp

    c = cents.shape[0]
    d = D.pairwise_distance(chunk, cents, "euclidean")
    a = jnp.argmin(jnp.where(jnp.arange(c)[None, :] < n_cents, d, jnp.inf), axis=1)
    w = (jnp.arange(chunk.shape[0]) < n_rows).astype(jnp.float32)
    sums = jax.ops.segment_sum(chunk.astype(jnp.float32) * w[:, None], a, num_segments=c)
    return a, sums, jax.ops.segment_sum(w, a, num_segments=c)


_assign_chunk = jax.jit(_nearest)


@jax.jit
def _kmeans_step(xs, c, n_cents, n_rows):
    import jax.numpy as jnp

    _, sums, cnts = _nearest(xs, c, n_cents, n_rows)
    # empty clusters keep their previous centroid
    return jnp.where(cnts[:, None] > 0, sums / jnp.maximum(cnts[:, None], 1.0), c.astype(jnp.float32))


def _kmeans_xs(xs, n_rows: int, nlists: int, pad_lists: int, rng, iters: int = 8):
    """Device k-means over the first `n_rows` rows of an already-device-
    resident sample [n, D]: `nlists` centroids, seeded from sample rows, in
    a table of `pad_lists` rows (only the first `nlists` mean anything)."""
    import jax.numpy as jnp

    from surrealdb_tpu.utils.num import pad_tail

    seeds = rng.choice(n_rows, size=nlists, replace=False).astype(np.int32)
    cents = xs[jnp.asarray(pad_tail(seeds, pad_lists))]
    for _ in range(iters):
        cents = _kmeans_step(xs, cents, nlists, n_rows)
    return cents


def _group(slots: np.ndarray, assign: np.ndarray, k: int) -> List[np.ndarray]:
    """The slots of each of `k` lists, in the order they came."""
    order = np.argsort(assign, kind="stable")
    bounds = np.searchsorted(assign[order], np.arange(k + 1))
    return [slots[order[lo:hi]] for lo, hi in zip(bounds[:-1], bounds[1:])]


class IvfState:
    """Trained quantizer + inverted lists over mirror row slots.

    Host-authoritative: `lists` maps centroid → row slots; device arrays are
    compacted lazily (numpy only — never a KV rescan). Training leaves no
    list longer than `cap`: it re-clusters the lists that pass it. An
    incremental add assigns to the nearest existing centroid, and a list
    may pass `cap` that way until the retrain, which happens when the
    corpus outgrows the trained size by 50%.
    """

    def __init__(self, centroids: np.ndarray, lists: List[List[int]], trained_n: int, cap: int):
        self.centroids = centroids  # [C, D] float32
        self.lists = lists  # C lists of row slots
        self.slot_list: Dict[int, int] = {s: i for i, l in enumerate(lists) for s in l}
        self.trained_n = trained_n
        self.cap = cap  # the longest list training leaves: twice the mean of its first assignment
        self._n = len(self.slot_list)  # O(1) size, maintained by add/remove
        self.dirty = True
        self._dev = None  # (cents, list_rows, list_mask)
        self._mut = 0  # bumped on every list mutation; sharded cache keys off it
        self._sharded_cache = None  # (key, (cents, rows, mask, shard_rows))
        self._warmed: set = set()  # (tile, k, nprobe, metric, pad) combos compiled
        self._pad = None  # (_mut it was read at, pad)

    @property
    def nlists(self) -> int:
        return self.centroids.shape[0]

    # ------------------------------------------------------------ build
    @staticmethod
    def train(
        data: np.ndarray,
        alive: np.ndarray,
        nlists: Optional[int] = None,
        matrix=None,
    ) -> "IvfState":
        """Train the quantizer: k-means over a sample, every row to its
        nearest centroid, then the bound on a list's length (`cap`: twice
        the mean of that assignment) enforced by POOLED RE-CLUSTERING. On clustered data
        in many dimensions an under-trained centroid averages several
        clusters and is the nearest one to every row whose own cluster got
        none: a hub list of twenty times the mean, which every search then
        probes (1M x 768: 108 of 1,024 lists held 594,143 rows, PERF.md
        section 6, PR 32). The rows of ALL lists over the cap are pooled,
        those lists and centroids dropped, `ceil(pool / (cap / 2))` new
        centroids trained over a sample of the pool, the pool assigned to
        them, and every non-empty one becomes a list whose centroid is the
        mean of its rows; again on what is still over the cap, until nothing
        is. Pooled, not list by list: a natural cluster scattered over
        several equidistant hubs stays scattered over their children, and
        recall pays for it. A round that separates nothing (duplicates), or
        the `_MAX_ROUNDS`-th, is cut by order into lists of at most the cap.

        When `matrix` (the mirror's device-resident [cap, D] array) is
        given, samples and assignment tiles gather rows ON DEVICE — only
        index vectors, assignments and [C, D] centroid tables cross the slow
        host<->device link."""
        import jax.numpy as jnp

        from surrealdb_tpu import telemetry
        from surrealdb_tpu.utils.num import pad_tail, tile_slices

        iters = 8
        t_train = _time.perf_counter()
        rows = np.nonzero(alive)[0]
        c = nlists or default_nlists(rows.size)
        cap = max(2 * (rows.size + c - 1) // c, 8)
        rng = np.random.default_rng(7)
        if matrix is not None:
            take = lambda slots: matrix[jnp.asarray(slots.astype(np.int32))]  # noqa: E731
        else:
            take = lambda slots: jnp.asarray(data[slots], dtype=jnp.float32)  # noqa: E731
        # one sample height and one centroid-table height for the first
        # training and every round after it: nothing compiles anew per round
        sample_n = min(rows.size, max(c * 64, 16384))

        def kmeans(pool: np.ndarray, k: int):
            n = min(pool.size, max(k * 64, 16384))
            sample = rng.choice(pool, size=n, replace=False) if n < pool.size else pool
            return _kmeans_xs(take(pad_tail(sample, sample_n)), n, k, c, rng, iters)

        def assign(pool: np.ndarray, cents, k: int):
            """(nearest of the k centroids per pooled row, the lists' means)"""
            out = np.empty(pool.size, dtype=np.int32)
            sums = cnts = 0.0  # summed on the device: one download a call
            for lo, hi in tile_slices(pool.size, _ASSIGN_CHUNK):
                a, s, n = _assign_chunk(
                    take(pad_tail(pool[lo:hi], _ASSIGN_CHUNK)), cents, k, hi - lo
                )
                out[lo:hi] = np.asarray(a)[: hi - lo]
                sums, cnts = sums + s, cnts + n
            return out, np.asarray(sums) / np.maximum(np.asarray(cnts), 1.0)[:, None]

        cents_dev = kmeans(rows, c)
        cents_dev.block_until_ready()  # the assignment waits for it anyway
        t_assign = _time.perf_counter()
        first, _ = assign(rows, cents_dev, c)
        t_lists = _time.perf_counter()
        telemetry.stage(
            "ivf_train", t_train, t_assign - t_train, rows=rows.size, lists=c, iters=iters
        )
        telemetry.stage("ivf_assign", t_assign, t_lists - t_assign, rows=rows.size)

        def sift(pool: np.ndarray, a: np.ndarray, k: int):
            """(the lists that fit, which of the k they are, those over the
            cap). A list with no rows is dropped wherever it arises: its
            centroid could only cost a search one of its probes."""
            groups = _group(pool, a, k)
            sizes = np.array([g.size for g in groups])
            fits = (sizes > 0) & (sizes <= cap)
            return [g for g, f in zip(groups, fits) if f], fits, [g for g in groups if g.size > cap]

        kept, fits, over = sift(rows, first, c)
        kept_cents = [np.asarray(cents_dev, dtype=np.float32)[fits]]
        split, pooled, rounds = len(over), sum(g.size for g in over), 0
        while over:
            pool = np.concatenate(over)
            rounds += 1
            k = -(-2 * pool.size // cap)
            a, means = assign(pool, kmeans(pool, k), k)
            lists, fits, over = sift(pool, a, k)
            kept.extend(lists)
            kept_cents.append(means[:k][fits])
            if sum(g.size for g in over) == pool.size or rounds == _MAX_ROUNDS:
                for g in over:  # nothing separated: cut by order
                    for lo, hi in tile_slices(g.size, cap):
                        kept.append(g[lo:hi])
                        kept_cents.append(data[g[lo:hi]].mean(axis=0, dtype=np.float32)[None, :])
                break
        state = IvfState(
            np.concatenate(kept_cents).astype(np.float32), [g.tolist() for g in kept], rows.size, cap
        )
        if split:
            telemetry.inc("ivf_list_splits", split, at="train")
        telemetry.stage(
            "ivf_lists", t_lists, _time.perf_counter() - t_lists, rows=rows.size,
            lists=state.nlists, split=split, pooled=pooled, rounds=rounds,
            longest=max((len(l) for l in state.lists), default=0),
        )
        return state

    # ------------------------------------------------------------ writes
    def add(self, slot: int, vec: np.ndarray) -> None:
        """Assign a new row to its nearest centroid. Nothing bounds the list
        here: the count of lists, and so every compiled shape but the pad,
        stays what training made it, and the retrain at 1.5x growth pools
        whatever has passed `cap` by then."""
        if slot in self.slot_list:
            return  # idempotent (reconciliation may revisit a slot)
        a = int(((self.centroids - vec[None, :]) ** 2).sum(1).argmin())
        self.lists[a].append(slot)
        self.slot_list[slot] = a
        self._n += 1
        self.dirty = True
        self._mut += 1

    def remove(self, slot: int, vec=None) -> None:
        a = self.slot_list.pop(slot, None)
        if a is not None:
            try:
                self.lists[a].remove(slot)
                self._n -= 1
            except ValueError:
                pass
        self.dirty = True
        self._mut += 1

    def size(self) -> int:
        return self._n

    def needs_retrain(self) -> bool:
        return self.size() > 1.5 * max(self.trained_n, 1)

    # ------------------------------------------------------------ search
    def pad(self) -> int:
        """Slots a probed list costs a search: the power of two above the
        longest list (the second dimension of the device's list tables)."""
        got = self._pad
        if got is None or got[0] != self._mut:
            got = self._pad = (
                self._mut, _next_pow2(max(max((len(l) for l in self.lists), default=1), 1))
            )
        return got[1]

    def _device(self):
        import jax.numpy as jnp

        if not self.dirty and self._dev is not None:
            return self._dev
        c = self.nlists
        maxlen = self.pad()
        list_rows = np.zeros((c, maxlen), dtype=np.int32)
        list_mask = np.zeros((c, maxlen), dtype=bool)
        for i, l in enumerate(self.lists):
            list_rows[i, : len(l)] = l
            list_mask[i, : len(l)] = True
        self._dev = (
            jnp.asarray(self.centroids),
            jnp.asarray(list_rows),
            jnp.asarray(list_mask),
        )
        self.dirty = False
        return self._dev

    def search_host(
        self, qs: np.ndarray, data: np.ndarray, metric: str, k: int, nprobe: int,
        slot_mask: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """CPU twin of `search_batch`: the same probe+exact-rerank recipe in
        numpy over the host mirror. This is the honest CPU-ANN baseline the
        device numbers are judged against (a sublinear competitor, not an
        exact full scan) — same role as the reference's CPU HNSW search
        (reference: core/src/idx/trees/hnsw/mod.rs:337-416).

        qs: [Q, D]; data: host [cap, D] mirror rows. Returns
        (dists [Q, k], slots [Q, k]); misses surface as +inf/-1.
        """
        if metric not in ("euclidean", "cosine"):
            raise ValueError(f"search_host supports euclidean/cosine, not {metric!r}")
        from surrealdb_tpu import telemetry

        _t_probe = _time.perf_counter()
        qs = np.asarray(qs, dtype=np.float32)
        nq = qs.shape[0]
        cents = self.centroids
        cn = (cents**2).sum(1)
        nprobe = min(nprobe, self.nlists)
        out_d = np.full((nq, k), np.inf, dtype=np.float32)
        out_i = np.full((nq, k), -1, dtype=np.int64)
        # one BLAS call probes every query at once: [Q, C] + |q|^2 constant,
        # so the ordering equals true euclidean distance per row
        d2c = cn[None, :] - 2.0 * (qs @ cents.T)
        probes = np.argpartition(d2c, nprobe - 1, axis=1)[:, :nprobe]
        # concatenate every query's probed lists into ONE flat candidate
        # array with owner segments — the rerank then runs as a handful of
        # vectorized numpy calls over all queries together instead of a
        # per-query python loop (GIL thrash under concurrent clients was a
        # measured contributor to the scale-1.0 concurrent-kNN collapse)
        cand_per_q: List[np.ndarray] = []
        for qi in range(nq):
            cl = [self.lists[int(p)] for p in probes[qi]]
            total = sum(len(l) for l in cl)
            c = np.fromiter((s for l in cl for s in l), dtype=np.int64, count=total)
            if slot_mask is not None:
                # columnar residual prefilter: rerank only matching slots —
                # top-k among rows that satisfy the WHERE, the same
                # condition-checker semantics as the exact strategies
                inb = c < slot_mask.shape[0]
                c = c[inb & slot_mask[np.minimum(c, slot_mask.shape[0] - 1)]]
            cand_per_q.append(c)
            telemetry.observe_hist(
                "ivf_candidates", int(c.size), buckets=telemetry.COUNT_BUCKETS, path="host"
            )
        counts = np.array([c.size for c in cand_per_q], dtype=np.int64)
        q2 = (qs**2).sum(1)
        qn = np.maximum(np.sqrt(q2), 1e-30)
        # bound the gather: query blocks capped at ~128k candidate rows, so
        # a wide batch over a big corpus can't materialize a multi-GB
        # [T, D] temporary (the per-query peak stays what the old loop had)
        cand_block = 1 << 17
        qi0 = 0
        while qi0 < nq:
            qi1 = qi0 + 1
            tot = int(counts[qi0])
            while qi1 < nq and tot + int(counts[qi1]) <= cand_block:
                tot += int(counts[qi1])
                qi1 += 1
            if tot == 0:
                qi0 = qi1
                continue
            cand_all = np.concatenate(cand_per_q[qi0:qi1])
            owner = np.repeat(np.arange(qi0, qi1), counts[qi0:qi1])
            x = data[cand_all]  # [T, D] gather, one fancy-index per block
            dots = np.einsum("ij,ij->i", x, qs[owner])
            xn2 = np.einsum("ij,ij->i", x, x)
            if metric == "cosine":
                xn = np.maximum(np.sqrt(xn2), 1e-30)
                d = 1.0 - dots / (xn * qn[owner])
            else:
                d = xn2 - 2.0 * dots  # + |q|^2 applied after top-k below
            # per-query top-k over its segment: the remaining python loop
            # does only O(T_q) selection work, no distance math
            off = 0
            for qi in range(qi0, qi1):
                t = int(counts[qi])
                if t == 0:
                    continue
                seg = d[off : off + t]
                kk = min(k, t)
                sel = np.argpartition(seg, kk - 1)[:kk] if kk < t else np.arange(t)
                sel = sel[np.argsort(seg[sel])]
                if metric == "cosine":
                    out_d[qi, :kk] = seg[sel]
                else:
                    out_d[qi, :kk] = np.sqrt(np.maximum(seg[sel] + q2[qi], 0.0))
                out_i[qi, :kk] = cand_all[off + sel]
                off += t
            qi0 = qi1
        # probe-level node under the active request's knn_search span + a
        # path-labeled duration histogram (host twin of the device probe)
        from surrealdb_tpu import telemetry, tracing

        _dur = _time.perf_counter() - _t_probe
        telemetry.observe("ivf_probe", _dur, path="host")
        tracing.record_span_into(
            tracing.current(), "ivf_probe",
            {"path": "host", "nq": int(qs.shape[0]), "nprobe": int(nprobe)},
            _t_probe, _dur,
        )
        return out_d, out_i

    def search(
        self, q: np.ndarray, matrix, metric: str, k: int, nprobe: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Probe nprobe lists, exact-rerank their members on device.

        q: [D] query; matrix: device [N*, D] mirror matrix.
        Returns (dists [k], row slots [k]); misses surface as +inf/-1.
        """
        d, r = self.search_batch(q[None, :], matrix, metric, k, nprobe)
        return d[0], r[0]

    def search_batch_launch(
        self, qs: np.ndarray, matrix, metric: str, k: int, nprobe: int,
        tile: Optional[int] = None, owner=None, slot_mask=None,
    ):
        """Async probe+rerank: enqueue every tile's kernel + start the
        device→host copies, return a collect() closure that blocks on the
        results. Lets the dispatch queue overlap the next batch's upload
        with this batch's compute/download (double buffering). `slot_mask`
        [cap] restricts the rerank to matching corpus slots (the columnar
        residual prefilter): a device array as the statement's cached slot
        filter holds it (idx/knn.py, nothing is uploaded a dispatch), or a
        host mask, uploaded here."""
        import jax.numpy as jnp

        cents, list_rows, list_mask = self._device()
        if slot_mask is None:
            slot_ok = jnp.ones(int(matrix.shape[0]), dtype=bool)
        elif isinstance(slot_mask, jax.Array):
            slot_ok = slot_mask
        else:
            pad = int(matrix.shape[0]) - int(slot_mask.shape[0])
            if pad > 0:
                slot_mask = np.concatenate([slot_mask, np.zeros(pad, dtype=bool)])
            slot_ok = jnp.asarray(slot_mask[: int(matrix.shape[0])])
        probe_metric = metric if metric in _PROBE_METRICS else "euclidean"
        nprobe = min(nprobe, self.nlists)
        # the kernel can return at most nprobe·L candidates per query
        k = min(k, nprobe * int(list_rows.shape[1]))
        from surrealdb_tpu.utils.num import pad_tail, tile_slices

        from surrealdb_tpu.utils.num import dispatch_tile

        qs = np.asarray(qs, dtype=np.float32)
        # small tile vocabulary: every distinct padded shape is a separate
        # XLA compile; {1, 8, tile} bounds compiles AND padding waste
        nq = qs.shape[0]
        tile = dispatch_tile(nq, tile)
        from surrealdb_tpu import telemetry

        # per-query probed-candidate ceiling (the kernel scans whole lists)
        telemetry.observe_hist(
            "ivf_candidates",
            nprobe * int(list_rows.shape[1]),
            buckets=telemetry.COUNT_BUCKETS,
            path="device",
        )
        from surrealdb_tpu import compile_log

        pending = []
        with compile_log.tracked(
            "ivf",
            _ivf_shape_key(tile, cents, list_rows, matrix, metric, probe_metric, k, nprobe),
        ):
            for lo, hi in tile_slices(nq, tile):
                d, r = _ivf_search(
                    jnp.asarray(pad_tail(qs[lo:hi], tile)), cents, list_rows,
                    list_mask, matrix, slot_ok,
                    metric=metric, probe_metric=probe_metric, k=k, nprobe=nprobe,
                )
                _start_host_copy(d, r)
                pending.append((lo, hi, d, r))

        def collect() -> Tuple[np.ndarray, np.ndarray]:
            dd = np.empty((nq, k), dtype=np.float32)
            rr = np.empty((nq, k), dtype=np.int64)
            for lo, hi, d, r in pending:
                dd[lo:hi] = np.asarray(d)[: hi - lo]
                rr[lo:hi] = np.asarray(r)[: hi - lo]
            return dd, rr

        collect.outputs = tuple(a for _, _, d, r in pending for a in (d, r))
        self._warm_tiles(qs.shape[1], cents, list_rows, list_mask, matrix,
                         metric, probe_metric, k, nprobe, tile, owner)
        return collect

    def _warm_tiles(self, dim, cents, list_rows, list_mask, matrix,
                    metric, probe_metric, k, nprobe, served_tile, owner=None) -> None:
        """Background-compile the OTHER dispatch tile shapes for these query
        params: a burst of concurrent queries coalesces into 8/64-wide
        batches whose first dispatch would otherwise stall seconds on XLA
        compilation (the r3 concurrent-qps killer). Zero-queries through the
        same kernel carry no correctness risk — results are discarded."""
        from surrealdb_tpu.utils.num import warm_tile_sizes

        # the pad is the one table shape an insert can move between retrains
        # (a list grown past a power of two): the other tiles warm again then
        pad = int(list_rows.shape[1])
        todo = []
        for t in warm_tile_sizes():
            key = (t, k, nprobe, metric, pad)
            if t != served_tile and key not in self._warmed:
                self._warmed.add(key)
                todo.append(t)
        self._warmed.add((served_tile, k, nprobe, metric, pad))
        if not todo:
            return

        def warm():
            import jax.numpy as jnp

            from surrealdb_tpu import compile_log

            for t in todo:
                try:
                    with compile_log.tracked(
                        "ivf",
                        _ivf_shape_key(
                            t, cents, list_rows, matrix, metric, probe_metric,
                            k, nprobe,
                        ),
                        prewarmed=True,
                    ):
                        _ivf_search(
                            jnp.zeros((t, dim), jnp.float32), cents, list_rows,
                            list_mask, matrix,
                            jnp.ones(int(matrix.shape[0]), dtype=bool),
                            metric=metric, probe_metric=probe_metric, k=k,
                            nprobe=nprobe,
                        )
                except Exception:
                    from surrealdb_tpu import telemetry

                    # a failed tile warm = an on-demand compile inside some
                    # future request; count it so cold latency is attributable
                    telemetry.inc("prewarm_errors", subsystem="ivf")

        from surrealdb_tpu import bg

        bg.spawn("shape_warm", f"ivf:k{k}:p{nprobe}", warm, owner=owner)

    def search_batch(
        self, qs: np.ndarray, matrix, metric: str, k: int, nprobe: int,
        tile: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched probe+rerank: qs [Q, D] → (dists [Q, k], slots [Q, k]).

        Queries are tiled so the [tile, nprobe·L, D] candidate gather stays
        within memory; each tile is ONE device dispatch (the cross-query
        batching seam — amortizes dispatch latency across queries).
        """
        return self.search_batch_launch(qs, matrix, metric, k, nprobe, tile)()


    # -------------------------------------------------------- mesh search
    def _device_sharded(self, mesh, n_total: int, axis: str = "data"):
        """Per-shard inverted-list tables for sharded_ivf_search: bucket each
        list's slots by owning shard (slot // shard_rows) into a
        [n_dev, C, L] local-row table placed sharded over the mesh axis —
        each chip holds only ITS slab, aligned with its corpus rows."""
        import jax as _jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        n_dev = mesh.shape[axis]
        shard_rows = n_total // n_dev
        key = (self._mut, id(mesh), n_total)
        if self._sharded_cache is not None and self._sharded_cache[0] == key:
            return self._sharded_cache[1]
        c = self.nlists
        per: List[List[List[int]]] = [[[] for _ in range(c)] for _ in range(n_dev)]
        for ci, l in enumerate(self.lists):
            for s in l:
                d = min(s // shard_rows, n_dev - 1)
                per[d][ci].append(s - d * shard_rows)
        maxlen = max((len(pl) for shard in per for pl in shard), default=1)
        maxlen = _next_pow2(max(maxlen, 1))
        rows = np.zeros((n_dev, c, maxlen), dtype=np.int32)
        mask = np.zeros((n_dev, c, maxlen), dtype=bool)
        for d in range(n_dev):
            for ci in range(c):
                pl = per[d][ci]
                rows[d, ci, : len(pl)] = pl
                mask[d, ci, : len(pl)] = True
        sh = NamedSharding(mesh, P(axis, None, None))
        dev = (
            jnp.asarray(self.centroids),
            _jax.device_put(rows, sh),
            _jax.device_put(mask, sh),
            shard_rows,
        )
        self._sharded_cache = (key, dev)
        return dev

    def search_batch_sharded(
        self, qs: np.ndarray, mesh, matrix, metric: str, k: int, nprobe: int,
        tile: Optional[int] = None, slot_mask: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched sharded probe+rerank over a mesh-sharded mirror matrix.
        Same contract as search_batch; misses surface as +inf/-1.
        `slot_mask` is the columnar residual prefilter over corpus slots (a
        host mask, or the cached device array already sharded as the
        corpus rows are): it rides into the kernel row-sharded alongside
        the corpus so top-k is computed among MATCHING rows only."""
        from surrealdb_tpu.parallel.mesh import sharded_ivf_search
        from surrealdb_tpu.utils.num import pad_tail, tile_slices
        import jax as _jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as _P

        from surrealdb_tpu.utils.num import dispatch_tile

        from surrealdb_tpu import compile_log

        cents, list_rows, list_mask, _ = self._device_sharded(mesh, matrix.shape[0])
        probe_metric = metric if metric in _PROBE_METRICS else "euclidean"
        nprobe = min(nprobe, self.nlists)
        qs = np.asarray(qs, dtype=np.float32)
        cap = int(matrix.shape[0])
        if isinstance(slot_mask, _jax.Array):
            slot_dev = slot_mask
        else:
            if slot_mask is not None:
                sm = np.asarray(slot_mask, dtype=bool)
                if sm.shape[0] < cap:  # pad slots are dead anyway
                    sm = np.concatenate([sm, np.zeros(cap - sm.shape[0], dtype=bool)])
                sm = sm[:cap]
            else:
                # placed ONCE here (not per tile-slice launch inside the loop,
                # and never as a replicated jnp.ones the shard_map must reshard)
                sm = np.ones(cap, dtype=bool)
            slot_dev = _jax.device_put(
                sm, NamedSharding(mesh, _P(mesh.axis_names[0]))
            )
        tile = dispatch_tile(qs.shape[0], tile)
        dd = np.full((qs.shape[0], k), np.inf, dtype=np.float32)
        rr = np.full((qs.shape[0], k), -1, dtype=np.int64)
        def one_slice(lo, hi):
            d, r = sharded_ivf_search(
                mesh, cents, list_rows, list_mask, matrix,
                jnp.asarray(pad_tail(qs[lo:hi], tile)),
                k, nprobe, metric=metric, probe_metric=probe_metric,
                slot_ok=slot_dev,
            )
            k_out = int(np.asarray(d).shape[1])
            dd[lo:hi, :k_out] = np.asarray(d)[: hi - lo]
            rr[lo:hi, :k_out] = np.asarray(r)[: hi - lo]

        # the sharded probe+rerank compiles per (tile, corpus, k, nprobe,
        # metrics): only the FIRST slice can compile, so only it is tracked
        # — wrapping the whole loop would log N tile executions as one
        # giant phantom "compile" (graftlint GL002)
        slices = list(tile_slices(qs.shape[0], tile))
        with compile_log.tracked(
            "ivf_sharded",
            (tile, int(matrix.shape[1]), int(matrix.shape[0]), k, nprobe,
             metric, probe_metric),
        ):
            one_slice(*slices[0])
        for lo, hi in slices[1:]:
            one_slice(lo, hi)
        return dd, rr


def graftcheck_sites():
    """Audit contract of the fused IVF probe+rerank kernel (compile_log
    subsystem `ivf`): the warm-tile query shapes over a representative
    (C lists × L members) quantizer, euclidean + the cosine/rerank mix."""
    from surrealdb_tpu.utils.num import warm_tile_sizes

    dim, cap, k = 64, 2048, 10
    C, L, nprobe = 64, 32, 8

    def build(shape):
        import jax as _jax
        import jax.numpy as jnp

        args = (
            _jax.ShapeDtypeStruct((shape["tile"], dim), jnp.float32),
            _jax.ShapeDtypeStruct((C, dim), jnp.float32),
            _jax.ShapeDtypeStruct((C, L), jnp.int32),
            _jax.ShapeDtypeStruct((C, L), jnp.bool_),
            _jax.ShapeDtypeStruct((cap, dim), jnp.float32),
            _jax.ShapeDtypeStruct((cap,), jnp.bool_),
        )
        metric = shape["metric"]
        probe_metric = metric if metric in _PROBE_METRICS else "euclidean"

        def run(q, cents, rows, mask, x, slot_ok):
            return _ivf_search(
                q, cents, rows, mask, x, slot_ok,
                metric=metric, probe_metric=probe_metric,
                k=shape["k"], nprobe=nprobe,
            )

        return run, args

    shapes = [
        {"label": f"t{t}_d{dim}_c{cap}_C{C}_L{L}_p{nprobe}_{m}_k{k}",
         "tile": t, "metric": m, "k": k}
        for t, m in (
            [(t, "euclidean") for t in warm_tile_sizes()] + [(8, "cosine")]
        )
    ]
    return [
        {
            "subsystem": "ivf",
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            "out_dtypes": ("float32", "int32"),
            "shapes": shapes,
            "build": build,
        }
    ]


@functools.partial(jax.jit, static_argnames=("metric", "probe_metric", "k", "nprobe"))
def _ivf_search(q, cents, list_rows, list_mask, x, slot_ok, metric, probe_metric, k, nprobe):
    """q [Q, D] → (dists [Q, k], row slots [Q, k]); vmapped per query.
    `slot_ok` [cap] masks corpus slots (all-true without a prefilter): the
    columnar residual-WHERE mask ANDs in here, so top-k is computed among
    MATCHING rows only (the condition-checker semantics the exact
    strategies already had)."""
    import jax.numpy as jnp

    dc = D.pairwise_distance(q, cents, probe_metric)  # [Q, C]
    probes = jax.lax.top_k(-dc, nprobe)[1]  # [Q, nprobe]

    def one(qi, pr):
        rows = list_rows[pr].reshape(-1)  # [nprobe*L]
        rows_c = jnp.clip(rows, 0, x.shape[0] - 1)
        mask = list_mask[pr].reshape(-1) & slot_ok[rows_c]
        cand = x[rows_c]  # gather [nprobe*L, D]
        d = D.pairwise_distance(qi[None, :], cand, metric)[0]
        d = jnp.where(mask, d, jnp.inf)
        kk = min(k, int(rows.shape[0]))
        neg, idx = jax.lax.top_k(-d, kk)
        return -neg, jnp.where(neg > -jnp.inf, rows[idx], -1)

    return D.map_queries(one, q, probes, nprobe * int(list_rows.shape[1]), x)
