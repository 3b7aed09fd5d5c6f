"""kNN query plans: device-batched exact and index-backed search.

Role of the reference's kNN plumbing (reference: core/src/idx/planner/knn.rs,
checker.rs, trees/knn.rs, and the brute-force CollectKnn→BuildKnn workflow
planner/mod.rs:208-232) re-designed TPU-first: instead of a priority queue
fed one distance at a time, the candidate vectors live in a device-resident
padded matrix (generation-swapped mirror of the KV state, like the
reference's TreeCache) and one fused kernel computes all distances + top-k.

The plan object doubles as the per-statement QueryExecutor for the
`<|k|>` operator (reference planner/executor.rs knn :282): records admitted
by the plan evaluate the operator to true and expose their distance to
vector::distance::knn().
"""

from __future__ import annotations

import itertools
import threading
from surrealdb_tpu.utils import locks as _locks
import time as _time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from surrealdb_tpu import cnf
from surrealdb_tpu.err import TypeError_
from surrealdb_tpu.sql.path import get_path
from surrealdb_tpu.sql.value import Thing, is_nullish

from surrealdb_tpu.ops import distances as D
from surrealdb_tpu.utils.byte_cache import ByteBudgetCache
from surrealdb_tpu.utils.num import next_pow2 as _pow2


def _target_vector(target) -> List[float]:
    if not isinstance(target, (list, tuple)):
        raise TypeError_("kNN operator expects a vector on the right-hand side")
    return [float(x) for x in target]


def _rid_key(rid) -> Any:
    return (rid.tb, repr(rid.id)) if isinstance(rid, Thing) else rid


class VectorMirror:
    """Device-resident [N, D] matrix mirroring a vector index's KV rows.

    Built ONCE with a single scan, then maintained incrementally: committed
    writes apply per-row deltas (append / overwrite / tombstone a slot) via
    the transaction's vector-delta buffer — no corpus rescans (VERDICT r1
    item 4; improves on the reference's generation-swap full reload,
    trees/store/cache.rs:28-60). Device arrays recompact lazily with pow2
    row padding so steady writes don't change kernel shapes. Dead slots are
    compacted away once they exceed a quarter of capacity.

    An optional IVF state (idx/ivf.py) rides on the same slot space and is
    kept in sync by the same deltas.
    """

    def __init__(self):
        self.built = False
        self.rids: List[Any] = []  # slot -> rid
        self.slot_of: Dict[Any, int] = {}
        self.data: Optional[np.ndarray] = None  # [cap, D] float32
        self.alive: Optional[np.ndarray] = None  # [cap] bool
        self.n_slots = 0
        self.dirty = True
        self.gen = 0  # bumped on every mutation; caches key off it
        self.matrix = None  # device jnp [cap, D]
        self.mask: Optional[np.ndarray] = None
        self._dev_matrix = None
        self._dev_mask = None  # sharded mask (mesh placement only)
        self._mesh = None  # mesh the device arrays are placed over
        self._upload_t0 = None  # start of an upload nobody has timed yet
        self.ivf = None  # IvfState, built on demand
        self._ivf_building = False
        self._ivf_done = threading.Event()  # signals a finished train round
        self._train_touched: Optional[set] = None  # slots mutated mid-train
        self._renumber = 0  # bumped when compaction renumbers slots
        self._pending: Optional[List[tuple]] = None  # deltas during build
        self._host_cache = None  # (contig data, sq-norms, rids) for host search
        self._host_live = None  # (gen, alive[:n_slots] as of it) for host_view
        # (predicate binding, slot count, mesh) -> _SlotFilter: what a residual
        # WHERE contributes to the searches of one snapshot, oldest first
        self._filters = ByteBudgetCache()
        self._lock = _locks.RLock("idx.knn.state")
        self._build_lock = _locks.Lock("idx.knn.build")
        self.label = ""  # "<table>.<index>", set on build (task attribution)
        self._owner = None  # id(ds), for bg teardown scoping

    # ------------------------------------------------------------ build
    def ensure_built(self, ctx, ix: dict) -> None:
        """One scan builds the mirror. The scan runs on a FRESH snapshot
        opened after delta-buffering starts, so (a) no committed write can
        fall between the scan and the built flag, and (b) the querying
        transaction's own uncommitted writes never leak into the shared
        mirror (they are served by the exact overlay path instead)."""
        from surrealdb_tpu import telemetry
        from surrealdb_tpu.idx.vector_index import scan_vectors

        if self.built:
            return
        with self._build_lock:
            if self.built:
                return
            t_build = _time.perf_counter()
            with self._lock:
                self._pending = []
            ns, db = ctx.ns_db()
            tb, name = ix["table"], ix["name"]
            self.label = f"{tb}.{name}"
            self._owner = id(ctx.ds())
            txn = ctx.ds().transaction(False)
            try:
                rids, rows = [], []
                for rid, vec in scan_vectors(txn, ns, db, tb, name):
                    rids.append(rid)
                    rows.append(vec)
            finally:
                txn.cancel()
            t_stack = _time.perf_counter()
            telemetry.stage("mirror_scan", t_build, t_stack - t_build, rows=len(rows))
            with self._lock:
                dim = len(rows[0]) if rows else int(ix["index"].get("dimension") or 0)
                cap = max(_pow2(len(rows)), cnf.TPU_BATCH_MIN_TILE)
                self.data = np.zeros((cap, max(dim, 1)), dtype=np.float32)
                self.alive = np.zeros(cap, dtype=bool)
                if rows:
                    self.data[: len(rows)] = np.asarray(rows, dtype=np.float32)
                    self.alive[: len(rows)] = True
                self.rids = rids
                self.slot_of = {_rid_key(r): i for i, r in enumerate(rids)}
                self.n_slots = len(rids)
                telemetry.stage(
                    "mirror_stack", t_stack, _time.perf_counter() - t_stack, rows=len(rows)
                )
                self.dirty = True
                self.gen += 1
                self.built = True
                pending, self._pending = self._pending, None
                # replay INSIDE the lock (RLock): a delta committed after
                # built flips must order after the buffered ones, never
                # be overwritten by a stale replay
                for rid, vec in pending:
                    self.apply(rid, vec)
            telemetry.observe(
                "vector_mirror_build", _time.perf_counter() - t_build
            )

    # ------------------------------------------------------------ deltas
    def apply(self, rid, vec) -> None:
        """One committed row change; vec=None tombstones the record.
        Idempotent, so a build-window delta replayed over a scan that
        already saw the row is harmless."""
        with self._lock:
            if self._pending is not None:
                self._pending.append((rid, vec))
                return
            if not self.built:
                return
            k = _rid_key(rid)
            slot = self.slot_of.get(k)
            if vec is None:
                if slot is not None:
                    self.alive[slot] = False
                    if self.ivf is not None:
                        self.ivf.remove(slot, self.data[slot])
                    del self.slot_of[k]
                self.dirty = True
                self.gen += 1
                return
            v = np.asarray(vec, dtype=np.float32)
            if slot is not None:  # overwrite in place
                if self.ivf is not None:
                    self.ivf.remove(slot, self.data[slot])
                self.data[slot] = v
                if self.ivf is not None:
                    self.ivf.add(slot, v)
                if self._train_touched is not None:
                    self._train_touched.add(slot)
                self.dirty = True
                self.gen += 1
                return
            if self.n_slots >= self.data.shape[0] or v.shape[0] != self.data.shape[1]:
                self._grow(v.shape[0])
            slot = self.n_slots
            self.n_slots += 1
            self.data[slot] = v
            self.alive[slot] = True
            if slot < len(self.rids):
                self.rids[slot] = rid
            else:
                self.rids.append(rid)
            self.slot_of[k] = slot
            if self.ivf is not None:
                self.ivf.add(slot, v)
            self.dirty = True
            self.gen += 1

    def apply_many(self, rids, vecs) -> None:
        """One committed bulk block ([B, D] float32): the all-new-rows fast
        path appends the whole block under ONE lock hold with one array
        copy — the per-row path cost B lock round-trips and B numpy row
        writes per bulk statement. Rows that already have a slot (or a
        building mirror) fall back to the per-row apply, which is always
        correct."""
        with self._lock:
            if self._pending is not None:
                self._pending.extend(zip(rids, vecs))
                return
            if not self.built:
                return
            vecs = np.asarray(vecs, dtype=np.float32)
            if (
                vecs.ndim != 2
                or len(rids) != vecs.shape[0]
                or self.data is None
                or (self.data.shape[1] not in (vecs.shape[1], 1) and self.n_slots)
            ):
                for rid, vec in zip(rids, vecs):
                    self.apply(rid, vec)
                return
            n0, B = self.n_slots, len(rids)
            if len(self.rids) != n0 or any(
                _rid_key(r) in self.slot_of for r in rids
            ):
                for rid, vec in zip(rids, vecs):
                    self.apply(rid, vec)
                return
            if n0 + B > self.data.shape[0] or vecs.shape[1] != self.data.shape[1]:
                self._grow(vecs.shape[1], need=n0 + B)
            self.data[n0 : n0 + B] = vecs
            self.alive[n0 : n0 + B] = True
            self.rids.extend(rids)
            for i, r in enumerate(rids):
                self.slot_of[_rid_key(r)] = n0 + i
            if self.ivf is not None:
                for i in range(B):
                    self.ivf.add(n0 + i, vecs[i])
            self.n_slots = n0 + B
            self.dirty = True
            self.gen += 1

    def _grow(self, dim: int, need: Optional[int] = None) -> None:
        cap = max(_pow2(max(self.n_slots + 1, need or 0)), cnf.TPU_BATCH_MIN_TILE)
        d = max(dim, self.data.shape[1])
        data = np.zeros((cap, d), dtype=np.float32)
        data[: self.data.shape[0], : self.data.shape[1]] = self.data
        alive = np.zeros(cap, dtype=bool)
        alive[: self.alive.shape[0]] = self.alive
        self.data, self.alive = data, alive

    def _maybe_compact(self) -> None:
        """Drop dead slots once they dominate; pure numpy, no KV."""
        dead = self.n_slots - int(self.alive[: self.n_slots].sum())
        if dead <= self.n_slots // 4 or dead < 256:
            return
        live = np.nonzero(self.alive[: self.n_slots])[0]
        cap = max(_pow2(live.size), cnf.TPU_BATCH_MIN_TILE)
        data = np.zeros((cap, self.data.shape[1]), dtype=np.float32)
        data[: live.size] = self.data[live]
        alive = np.zeros(cap, dtype=bool)
        alive[: live.size] = True
        self.rids = [self.rids[i] for i in live.tolist()]
        self.slot_of = {_rid_key(r): i for i, r in enumerate(self.rids)}
        self.data, self.alive, self.n_slots = data, alive, live.size
        self.gen += 1  # slot space renumbered
        self._renumber += 1
        self.ivf = None  # slot space changed; retrain on next ANN query

    # ------------------------------------------------------------ views
    def count(self) -> int:
        with self._lock:
            return int(self.alive[: self.n_slots].sum()) if self.built and self.alive is not None else 0

    def device_view(self, mesh=None):
        """(jnp matrix [cap, D], host mask [cap]) for the fused kernels.

        On accelerator backends the matrix uploads as cnf.TPU_VECTOR_DTYPE
        (bf16 by default: half the host->device transfer, MXU-native
        matmuls; distance accumulation stays f32 via
        preferred_element_type). CPU keeps f32 exactness. With a device
        mesh the matrix is placed row-SHARDED over the 'data' axis (cap is
        pow2, so it divides across any pow2 device count) and the mask is
        sharded alongside — the distributed-kNN layout (parallel/mesh.py)."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            self._maybe_compact()
            if self.dirty or self._dev_matrix is None or self._mesh is not mesh:
                data = self.data
                if (
                    cnf.TPU_VECTOR_DTYPE == "bfloat16"
                    and jax.devices()[0].platform != "cpu"
                ):
                    import ml_dtypes

                    from surrealdb_tpu import telemetry

                    t_cast = _time.perf_counter()
                    data = data.astype(ml_dtypes.bfloat16)  # host-side cast
                    dt = _time.perf_counter() - t_cast
                    telemetry.observe("vector_mirror_cast", dt)
                    telemetry.stage("mirror_cast", t_cast, dt, bytes=data.nbytes)
                self._upload_t0 = _time.perf_counter()
                if mesh is not None:
                    from jax.sharding import NamedSharding, PartitionSpec as P

                    axis = mesh.axis_names[0]
                    self._dev_matrix = jax.device_put(
                        data, NamedSharding(mesh, P(axis, None))
                    )
                    self._dev_mask = jax.device_put(
                        self.alive, NamedSharding(mesh, P(axis))
                    )
                else:
                    self._dev_matrix = jnp.asarray(data)
                    self._dev_mask = None
                self._mesh = mesh
                self.mask = self.alive.copy()
                self.dirty = False
            return self._dev_matrix, self.mask

    def device_snapshot(self, mesh=None):
        """(matrix, mask, rids) captured atomically: `rids` is the list
        OBJECT tied to this matrix's slot numbering. A later compaction
        installs a NEW list (never renumbering this one in place — appends
        only), so resolving kernel slots through this snapshot stays correct
        even if the mirror compacts while the batch is on device."""
        with self._lock:
            m, mask = self.device_view(mesh)
            rids = self.rids
            t_up, self._upload_t0 = self._upload_t0, None
        if t_up is not None:
            # the statement that uploaded waits for the transfer here, with
            # the lock released (its kernel would wait for it anyway), so
            # the upload is timed under its own name and holds no writer
            from surrealdb_tpu import telemetry

            m.block_until_ready()
            dt = _time.perf_counter() - t_up
            telemetry.observe("vector_mirror_upload", dt)
            telemetry.stage("mirror_upload", t_up, dt, bytes=m.nbytes)
        return m, mask, rids

    def device_sharded_mask(self):
        with self._lock:
            return self._dev_mask

    def host_view(self):
        """(data [n, D], alive [n], rids) — numpy views for small corpora.
        `alive` is a copy as of this generation, the SAME object until the
        next mutation: like the device view's mask, its identity names the
        snapshot's live state (a slot filter is good while it stands)."""
        with self._lock:
            if self._host_live is None or self._host_live[0] != self.gen:
                self._host_live = (self.gen, self.alive[: self.n_slots].copy())
            return self.data[: self.n_slots], self._host_live[1], self.rids

    def host_search_view(self):
        """(contiguous live rows [m, D] f32, their squared norms [m], live
        rids) cached across queries, keyed off the mutation generation —
        the CPU search path must not re-copy the corpus or recompute norms
        per query (it IS the baseline the device path is judged against,
        so it gets the same care)."""
        with self._lock:
            if self._host_cache is None or self._host_cache[0] != self.gen:
                n = self.n_slots
                live = np.nonzero(self.alive[:n])[0]
                if live.size == n:
                    # fully-live slot space (the common bulk-ingest case):
                    # serve the mirror array itself — a fancy-index here
                    # would copy the whole corpus (GBs) for nothing
                    data = np.ascontiguousarray(self.data[:n], dtype=np.float32)
                    rids = list(self.rids[:n])
                else:
                    data = np.ascontiguousarray(self.data[live], dtype=np.float32)
                    rids = [self.rids[i] for i in live.tolist()]
                # f64 accumulation without materializing an f64 corpus copy
                norms = np.einsum(
                    "ij,ij->i", data, data, dtype=np.float64
                ).astype(np.float32)
                self._host_cache = (self.gen, data, norms, rids)
            return self._host_cache[1:]

    def ensure_ivf(self, matrix=None):
        """Return the current IVF state WITHOUT ever blocking the query:
        a missing or outgrown quantizer kicks a background training thread
        and the caller serves this query from the stale IVF (or, when None,
        the exact fused kernel). No query pays the multi-second training
        cliff (reference analog: the async builder, kvs/index.rs:28-41)."""
        with self._lock:
            ivf = self.ivf
            if ivf is not None and not ivf.needs_retrain():
                return ivf
            if self._ivf_building or matrix is None:
                return ivf
            self._ivf_building = True
            self._ivf_done.clear()
            self._train_touched = set()
            alive = self.alive[: self.n_slots].copy()
            data = self.data
            renum0 = self._renumber
        from surrealdb_tpu import bg

        # flight-recorder record: the multi-second training cliff is now an
        # attributable task (linked to the query that kicked it), named so
        # stack dumps say WHICH index is training
        task_id = bg.register("ivf_train", target=self.label, owner=self._owner)
        bg.start_thread(task_id, self._train_ivf, data, alive, matrix, renum0, task_id)
        return ivf

    def _train_ivf(self, data, alive, matrix, renum0: int, task_id=None) -> None:
        from surrealdb_tpu import bg
        from surrealdb_tpu.idx.ivf import IvfState

        try:
            if task_id is None:
                task_id = bg.register("ivf_train", target=self.label, trace_id=None)
            with bg.run(task_id):
                new = IvfState.train(data[: alive.size], alive, matrix=matrix)
        except BaseException:
            with self._lock:
                self._ivf_building = False
                self._train_touched = None
                self._ivf_done.set()
            raise
        with self._lock:
            self._ivf_building = False
            touched, self._train_touched = self._train_touched, None
            self._ivf_done.set()
            if self._renumber != renum0:
                return  # slot space renumbered mid-train; next query re-kicks
            # reconcile rows that changed while training ran on the snapshot
            cur = self.alive[: self.n_slots]
            for slot in range(alive.size, self.n_slots):  # appended rows
                if cur[slot]:
                    new.add(slot, self.data[slot])
            for slot in np.nonzero(~cur[: alive.size] & alive)[0]:  # tombstoned
                new.remove(int(slot), None)
            for slot in touched or ():  # overwritten in place mid-train
                new.remove(slot, None)
                if slot < self.n_slots and cur[slot]:
                    new.add(slot, self.data[slot])
            self.ivf = new

    def wait_ivf(self, timeout: float = 60.0) -> bool:
        """Block until the in-flight training round (if any) finishes —
        test determinism helper, never used on the query path."""
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            with self._lock:
                if self.ivf is not None and not self._ivf_building:
                    return True
                building = self._ivf_building
            if not building:
                return False  # nothing training and no ivf (e.g. never kicked)
            self._ivf_done.wait(min(1.0, timeout))
        return False

    def ivf_status(self) -> dict:
        """INFO FOR INDEX 'ann' section."""
        with self._lock:
            if self._ivf_building:
                state = "training"
            elif self.ivf is None:
                state = "none"
            elif self.ivf.needs_retrain():
                state = "stale"
            else:
                state = "ready"
            out = {"state": state}
            if self.ivf is not None:
                out["nlists"] = self.ivf.nlists
                out["trained_n"] = self.ivf.trained_n
            return out





def _filter_route(route: str) -> None:
    """Count how a statement's residual WHERE was served: `none` (it has
    none), `subset` (the passing rows scored exactly), `widened` (IVF with
    the mask and more probes), `masked` (an exact scan of every row with
    the mask), `post` (the column mirror could not answer: the search ran
    unfiltered and the executor filters its top-k, as before)."""
    from surrealdb_tpu import telemetry

    telemetry.inc("knn_filter_route", route=route)


def _submit_prepared(ds, t_iter: float, key, q, runner, route: str = "none"):
    """Hand one query to the dispatch queue, closing its statement's
    `knn_prepare` span: what it did on the host since `t_iter` before the
    device could have its work (mirror and quantizer look-ups, the slot
    filter, the key). The span's `filter` label and the route counter come
    from the one argument."""
    from surrealdb_tpu import tracing

    tracing.record_span_into(
        tracing.current(), "knn_prepare", {"filter": route}, t_iter,
        _time.perf_counter() - t_iter,
    )
    _filter_route(route)
    return ds.dispatch.submit(key, q, runner)


_FILTER_SERIAL = itertools.count(1)


class _SlotFilter:
    """What a residual WHERE contributes to the searches of one vector
    snapshot: the mask over its slots of the rows that pass AND live
    (`host`), how many they are (`rows`), their slots (`slot_ids`, made when
    a host strategy first asks) and, for the device strategies, the mask
    (`ok`), the passing slots padded to `size` (`slots`) and their count
    (`n_pass`) ON the device. Made once a (predicate text, bound values,
    column-mirror object, snapshot) and looked up afterwards; `serial`
    names it in a dispatch key, so riders under one filter share a launch
    and riders under different ones never do."""

    __slots__ = ("serial", "col", "rids", "live", "mesh", "host", "rows", "size",
                 "_slot_ids", "ok", "slots", "n_pass")

    def __init__(self, col, rids, live, mesh, host: np.ndarray):
        from surrealdb_tpu.idx.ivf import subset_size

        self.serial = next(_FILTER_SERIAL)
        self.col, self.rids, self.live, self.mesh, self.host = col, rids, live, mesh, host
        self.rows = int(np.count_nonzero(host))
        self.size = subset_size(self.rows)
        self._slot_ids = self.ok = self.slots = self.n_pass = None

    @property
    def slot_ids(self) -> np.ndarray:
        if self._slot_ids is None:
            self._slot_ids = np.flatnonzero(self.host).astype(np.int32)
        return self._slot_ids

    def nbytes(self) -> int:
        """Host and device bytes together: the mask on each side, the
        padded slots on the device, the passing slots a host strategy made."""
        held = 0 if self._slot_ids is None else self._slot_ids.nbytes
        return self.host.nbytes + held + (0 if self.ok is None else self.host.nbytes + 4 * self.size)

    def upload(self, cap: int) -> None:
        """Put the mask (padded to the matrix's `cap` rows; sharded as the
        corpus rows are on a mesh) and the padded passing slots on the
        device, once."""
        import jax
        import jax.numpy as jnp

        from surrealdb_tpu import telemetry
        from surrealdb_tpu.utils.num import pad_tail

        t0 = _time.perf_counter()
        ok = pad_tail(self.host, cap) if self.host.shape[0] < cap else self.host[:cap]
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            ok_dev = jax.device_put(ok, NamedSharding(self.mesh, P(self.mesh.axis_names[0])))
        else:
            ok_dev = jnp.asarray(ok)
        slots = jnp.asarray(pad_tail(np.flatnonzero(self.host).astype(np.int32), self.size))
        self.n_pass = jnp.asarray(self.rows, dtype=jnp.int32)
        self.slots, self.ok = slots, ok_dev
        telemetry.stage(
            "knn_filter_upload", t0, _time.perf_counter() - t0,
            bytes=int(ok.nbytes + 4 * self.size),
        )


def _subset_shape_key(tile: int, matrix, size: int, metric: str, k: int):
    """Compile-cache key of the subset kernel: the static dims XLA keys
    its executable cache on."""
    return (tile, int(matrix.shape[1]), int(matrix.shape[0]), str(matrix.dtype), size, metric, k)


def _zipped(collect):
    """A launch's collect() as the dispatch queue wants it: one
    (dists, slots) pair a rider, and the device arrays it will read
    (`outputs`, dbs/dispatch.py) carried along."""

    def finish():
        dd, rr = collect()
        return list(zip(dd, rr))

    finish.outputs = getattr(collect, "outputs", None)
    return finish


def _exact_device_launch(qs: np.ndarray, matrix, mask, metric: str, k: int, owner=None, subset=None):
    """Async fused exact distance+top-k over a [Q, D] query batch, Q padded
    to a pow2 tile (≤64) so coalesced batches of any size reuse one compiled
    kernel shape: over every row `mask` lets live, or with `subset` (a
    _SlotFilter) over the rows it lets through, gathered by their cached
    slots (ops/distances.py::knn_subset_search). Returns a collect()
    closure (two-phase dispatch)."""
    import jax.numpy as jnp

    from surrealdb_tpu.idx.ivf import _start_host_copy
    from surrealdb_tpu.utils.num import dispatch_tile, pad_tail, tile_slices

    from surrealdb_tpu import compile_log

    nq = qs.shape[0]
    tile = dispatch_tile(nq)
    mj = None if subset is not None else jnp.asarray(mask)
    pending = []
    # every distinct (tile, dim, cap, k, metric[, padded slots]) is one XLA
    # executable: the first call through a new shape IS the compile —
    # record + attribute it
    if subset is None:
        tracked = compile_log.tracked("knn_exact", _exact_shape_key(tile, matrix, metric, k))
    else:
        tracked = compile_log.tracked(
            "knn_subset", _subset_shape_key(tile, matrix, subset.size, metric, k)
        )
    with tracked:
        for lo, hi in tile_slices(nq, tile):
            qt = pad_tail(qs[lo:hi], tile)
            if subset is None:
                d, r = D.knn_search(qt, matrix, mj, metric, k)
            else:
                d, r = D.knn_subset_search(qt, matrix, subset.slots, subset.n_pass, metric, k)
            _start_host_copy(d, r)
            pending.append((lo, hi, d, r))

    def collect():
        dd = np.empty((nq, k), dtype=np.float32)
        rr = np.empty((nq, k), dtype=np.int64)
        for lo, hi, d, r in pending:
            dd[lo:hi] = np.asarray(d)[: hi - lo]
            rr[lo:hi] = np.asarray(r)[: hi - lo]
        return dd, rr

    collect.outputs = tuple(a for _, _, d, r in pending for a in (d, r))
    _warm_exact_tiles(qs.shape[1], matrix, mj, metric, k, tile, owner, subset=subset)
    return collect


def _subset_sharded_launch(qs: np.ndarray, mesh, matrix, flt: _SlotFilter, metric: str, k: int):
    """`_exact_device_launch(subset=flt)` over a row-sharded matrix
    (parallel/mesh.py), run on the leader's thread as the sharded scans are."""
    from surrealdb_tpu import compile_log
    from surrealdb_tpu.parallel.mesh import sharded_subset_knn
    from surrealdb_tpu.utils.num import dispatch_tile, pad_tail, tile_slices

    nq = qs.shape[0]
    tile = dispatch_tile(nq)
    dd = np.empty((nq, k), dtype=np.float32)
    rr = np.empty((nq, k), dtype=np.int64)

    def one_slice(lo, hi):
        d, r = sharded_subset_knn(
            mesh, matrix, flt.slots, flt.n_pass, pad_tail(qs[lo:hi], tile), k, metric
        )
        dd[lo:hi] = np.asarray(d)[: hi - lo]
        rr[lo:hi] = np.asarray(r)[: hi - lo]

    # only the FIRST slice can compile, so only it is tracked (graftlint GL002)
    slices = list(tile_slices(nq, tile))
    with compile_log.tracked(
        "knn_subset_sharded", _subset_shape_key(tile, matrix, flt.size, metric, k)
    ):
        one_slice(*slices[0])
    for lo, hi in slices[1:]:
        one_slice(lo, hi)
    return list(zip(dd, rr))


_EXACT_WARMED: set = set()


def _exact_shape_key(tile: int, matrix, metric: str, k: int):
    """Compile-cache key of the exact fused kernel: the static dims XLA
    keys its own executable cache on."""
    return (tile, int(matrix.shape[1]), int(matrix.shape[0]), str(matrix.dtype), metric, k)


def _warm_exact_tiles(dim, matrix, mask_j, metric, k, served_tile, owner=None, subset=None) -> None:
    """Background-compile the other dispatch tile shapes of an exact fused
    kernel (same rationale as IvfState._warm_tiles): the scan of every row
    under `mask_j`, or with `subset` (a _SlotFilter) the search over its
    passing rows at its padded size. The warm set tracks the dispatcher's
    width cap, so every width the coalescer can hand a runner has a
    compiled shape waiting."""
    from surrealdb_tpu.utils.num import warm_tile_sizes

    subsystem = "knn_exact" if subset is None else "knn_subset"
    size = None if subset is None else subset.size
    todo = []
    for t in warm_tile_sizes():
        key = (t, id(matrix), metric, k, size)
        if t != served_tile and key not in _EXACT_WARMED:
            _EXACT_WARMED.add(key)
            todo.append(t)
    _EXACT_WARMED.add((served_tile, id(matrix), metric, k, size))
    if not todo:
        return

    def warm():
        import jax.numpy as jnp

        from surrealdb_tpu import compile_log

        for t in todo:
            shape = (
                _exact_shape_key(t, matrix, metric, k) if subset is None
                else _subset_shape_key(t, matrix, size, metric, k)
            )
            try:
                with compile_log.tracked(subsystem, shape, prewarmed=True):
                    q0 = jnp.zeros((t, dim), jnp.float32)
                    if subset is None:
                        D.knn_search(q0, matrix, mask_j, metric, k)
                    else:
                        D.knn_subset_search(q0, matrix, subset.slots, subset.n_pass, metric, k)
            except Exception:
                from surrealdb_tpu import telemetry

                # a failed tile warm means the first real query at this
                # width pays the XLA compile — count it so a cold p99 is
                # attributable from metrics alone
                telemetry.inc("prewarm_errors", subsystem=subsystem)

    from surrealdb_tpu import bg

    bg.spawn("shape_warm", f"{subsystem}:k{k}", warm, owner=owner)


def _exact_device_batch(qs: np.ndarray, matrix, mask, metric: str, k: int):
    return _exact_device_launch(qs, matrix, mask, metric, k)()


def graftcheck_sites():
    """Audit contract of the exact fused distance+top-k kernel
    (compile_log subsystem `knn_exact`; scripts/graftcheck lowers every
    shape here to StableHLO and checks GC001–GC004). The shape matrix is
    the dispatcher's warm-tile vocabulary — the same shapes the background
    warmers pre-compile — over the serving metrics, plus the bf16 corpus
    variant the accelerator upload path uses."""
    from surrealdb_tpu.utils.num import warm_tile_sizes

    dim, cap, k = 64, 2048, 10

    def build(shape):
        import jax
        import jax.numpy as jnp

        from surrealdb_tpu.ops.distances import knn_search

        if shape["dtype"] == "bfloat16":
            import ml_dtypes

            cdt = jnp.dtype(ml_dtypes.bfloat16)
        else:
            cdt = jnp.float32
        args = (
            jax.ShapeDtypeStruct((shape["tile"], dim), jnp.float32),
            jax.ShapeDtypeStruct((cap, dim), cdt),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
        )
        metric, kk = shape["metric"], shape["k"]
        return (lambda q, x, m: knn_search(q, x, m, metric, kk)), args

    shapes = [
        {"label": f"t{t}_d{dim}_c{cap}_{m}_k{k}_{dt}",
         "tile": t, "metric": m, "k": k, "dtype": dt}
        for t, m, dt in (
            [(t, "euclidean", "float32") for t in warm_tile_sizes()]
            + [(8, "cosine", "float32"), (8, "euclidean", "bfloat16")]
        )
    ]
    def build_subset(shape):
        import jax
        import jax.numpy as jnp

        from surrealdb_tpu.ops.distances import knn_subset_search

        args = (
            jax.ShapeDtypeStruct((shape["tile"], dim), jnp.float32),
            jax.ShapeDtypeStruct((cap, dim), jnp.float32),
            jax.ShapeDtypeStruct((1024,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
        )
        metric, kk = shape["metric"], shape["k"]
        return (lambda q, x, s, n: knn_subset_search(q, x, s, n, metric, kk)), args

    return [
        {
            "subsystem": "knn_exact",
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            "out_dtypes": ("float32", "int32"),
            "shapes": shapes,
            "build": build,
        },
        {
            # the exact search over a filter's passing slots, at the
            # smallest padded size and the dispatcher's tile vocabulary
            "subsystem": "knn_subset",
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            "out_dtypes": ("float32", "int32"),
            "shapes": [
                {"label": f"t{t}_d{dim}_c{cap}_s1024_{m}_k{k}", "tile": t, "metric": m, "k": k}
                for t, m in [(t, "euclidean") for t in warm_tile_sizes()] + [(8, "cosine")]
            ],
            "build": build_subset,
        },
    ]


class _KnnResult:
    """Admitted record set for the operator check (reference KnnPriorityList)."""

    def __init__(self):
        self.dists: Dict[Any, float] = {}

    def key(self, rid) -> Any:
        return (rid.tb, repr(rid.id)) if isinstance(rid, Thing) else rid

    def add(self, rid, dist: float) -> None:
        self.dists[self.key(rid)] = dist

    def contains(self, rid) -> bool:
        return self.key(rid) in self.dists

    def dist(self, rid) -> Optional[float]:
        return self.dists.get(self.key(rid))


class _KnnExecutorMixin:
    """QueryExecutor protocol for the `<|k|>` operator and distance fn."""

    result: _KnnResult

    def knn(self, ctx, doc, op) -> bool:
        rid = doc.rid
        return rid is not None and self.result.contains(rid)

    def matches(self, ctx, doc, op) -> bool:
        return False

    def knn_distance(self, rid) -> Optional[float]:
        return self.result.dist(rid)

    def score(self, ctx, doc, ref=None):
        return None


class KnnPlan(_KnnExecutorMixin):
    """`<|k[,ef]|>` against a DEFINEd HNSW/MTREE index.

    Above TPU_ANN_MIN_ROWS the search is approximate-but-reranked IVF
    (idx/ivf.py — sublinear, recall governed by ef→nprobe, floors asserted
    like the reference's trees/hnsw/mod.rs:828-951 suite). Below it, exact
    fused distance+top-k (recall 1.0). A transaction with uncommitted writes
    to this index searches an exact overlay merge instead.
    """

    def __init__(self, tb: str, ix: dict, op, target):
        self.tb = tb
        self.ix = ix
        self.op = op
        self.k = op.k
        self.ef = getattr(op, "ef", None)
        self.target = _target_vector(target)
        self.result = _KnnResult()
        self.strategy = "?"
        # residual-WHERE mask lowered onto the table's column mirror
        # (set by the planner): exact strategies prefilter with it
        self.prefilter = None

    def _slot_filter(self, ctx, mirror, rids, live, mesh=None, device=True):
        """The statement's residual WHERE as a filter over the vector
        snapshot's slots (`_SlotFilter`), looked up in the mirror's cache or
        made and kept there — or None when the statement has none, or the
        column mirror can't serve this reader exactly (the search then runs
        unfiltered and the executor filters its top-k). `live` is the
        snapshot's live mask over its slots (the matrix's rows for the
        device strategies, which also get the device arrays; None where
        `rids` holds live rows only, a new list a generation): a slot passes
        when its record's committed fields pass AND it lives, so a slot a
        DELETE or an UNSET of the vector left behind (its rid stays until
        compaction) is never gathered. The key is what the mask depends on:
        the predicate's text and bound constants, the slot count and the
        placement; an entry is good while the ColumnMirror object, the
        snapshot's rids list and its live mask are the ones it was made
        from, so an acknowledged write to the table (a new ColumnMirror), a
        mutation of the vector mirror (a new live mask: the views make one
        a generation) or a compaction (a new rids list) is seen by the next
        search. The statement's `knn_filter` span says which it was, and how
        many rows pass."""
        if self.prefilter is None:
            return None
        from surrealdb_tpu import telemetry
        from surrealdb_tpu.idx.column_mirror import columnar_mask, serveable_mirror

        t0 = _time.perf_counter()
        col = serveable_mirror(ctx, self.tb)
        if col is None:
            telemetry.inc("knn_prefilter", outcome="unavailable")
            return None
        cap = len(rids) if live is None else len(live)
        key = (self.prefilter.binding_key(), cap, None if mesh is None else id(mesh))
        with mirror._lock:
            flt = mirror._filters.get(key)
        built = (
            flt is None or flt.col is not col or flt.rids is not rids
            or flt.live is not live or flt.mesh is not mesh
        )
        if built:
            t1 = _time.perf_counter()
            res = columnar_mask(ctx, self.tb, self.prefilter, col)
            if res is None:
                telemetry.inc("knn_prefilter", outcome="unavailable")
                return None
            mask, needs_row, _ = res
            if needs_row.any():
                # the mask abstained on mixed-type rows: post-filter semantics
                # stay (dropping those rows from the search would be wrong)
                telemetry.inc("knn_prefilter", outcome="mixed_rows")
                return None
            perm = col.slot_permutation(rids, cap)
            ok = perm >= 0 if live is None else (perm >= 0) & live
            host = np.zeros(cap, dtype=bool)
            host[ok] = mask[perm[ok]]
            flt = _SlotFilter(col, rids, live, mesh, host)
            telemetry.stage(
                "knn_filter_build", t1, _time.perf_counter() - t1, bytes=int(host.nbytes)
            )
        kept = not built
        if device and flt.ok is None:
            flt.upload(cap)
            kept = False  # holds more bytes than the cache counted
        if not kept:
            with mirror._lock:
                # what was made from an older column mirror or snapshot can
                # never be served again, and holds that mirror alive
                mirror._filters.forget(
                    lambda _, e: e.col is not col or e.rids is not rids or e.live is not live
                )
                mirror._filters.put(key, flt, flt.nbytes())
        telemetry.inc("knn_prefilter", outcome="applied")
        telemetry.stage(
            "knn_filter", t0, _time.perf_counter() - t0,
            outcome="build" if built else "hit", rows=flt.rows,
        )
        return flt

    def _masked_route(self, flt) -> str:
        """The `filter` label of an exact scan of every row."""
        return "none" if self.prefilter is None else "post" if flt is None else "masked"

    def _ivf_route(self, flt, n: int, ivf, ef, k: int):
        """(route, its argument, k) of an IVF strategy's search over `n`
        live rows: the bare search's probes without a filter (`none`, or
        `post` where the column mirror could not answer), else what
        idx/ivf.py::filtered_route chooses from the rows that pass:
        `subset` and its padded size, or `widened` and its probes. Where
        fewer than `k` rows pass, the answer is those."""
        from surrealdb_tpu.idx.ivf import default_nprobe, filtered_route

        nprobe = default_nprobe(ivf.nlists, ef)
        if flt is None:
            return self._masked_route(None), nprobe, k
        how, arg = filtered_route(flt.rows, n, ivf.nlists, nprobe, ivf.pad())
        return how, arg, min(k, flt.rows)

    def _submit(self, ds, t_iter, key, q, runner, route: str, k: int):
        """One IVF-strategy statement to the dispatch queue; a filter
        nothing passes has nothing to search."""
        if k == 0:
            _filter_route(route)
            return np.empty(0), np.empty(0)
        return _submit_prepared(ds, t_iter, key, q, runner, route)

    def _exact_device(self, ds, t_iter, mirror, matrix, mask, flt, metric, k, q):
        """The exact fused scan of every row of the device matrix, the
        filter's mask ANDed into the live one."""
        key = ("knn-exact", id(matrix), metric, k)
        if flt is not None:
            mask = flt.host  # made from this very live mask: passing AND live
            key = key + (flt.serial,)

        def runner(qs):
            return _zipped(_exact_device_launch(
                np.stack(qs), matrix, mask, metric, k, owner=mirror._owner,
            ))

        return _submit_prepared(ds, t_iter, key, q, runner, self._masked_route(flt))

    def explain(self) -> dict:
        idx = self.ix["index"]
        return {
            "index": self.ix["name"],
            "operator": f"<|{self.k}|>",
            "ann": {"type": idx["type"], "dist": idx.get("dist", "euclidean")},
        }

    def _pending_overlay(self, ctx, ns, db) -> Optional[Dict[Any, Any]]:
        """Uncommitted vector writes of this txn against this index."""
        deltas = getattr(ctx.txn(), "vector_deltas", None)
        if not deltas:
            return None
        want = (ns, db, self.tb, self.ix["name"])
        overlay = {}
        for ns_, db_, tb_, name_, rid, vec in deltas:
            if (ns_, db_, tb_, name_) != want:
                continue
            if isinstance(rid, list):
                # bulk block (vector_bulk_delta): rid is the rid LIST and
                # vec the [B, D] matrix — expand to per-row entries
                for r, v in zip(rid, vec):
                    overlay[_rid_key(r)] = (r, v)
            else:
                overlay[(_rid_key(rid))] = (rid, vec)
        return overlay or None

    def iterate(self, ctx):
        t_iter = _time.perf_counter()
        ctx.qe = self
        ds = ctx.ds()
        ns, db = ctx.ns_db()
        mirror = ds.index_stores.get_or_create(
            ns, db, self.tb, self.ix["name"], VectorMirror
        )
        mirror.ensure_built(ctx, self.ix)
        metric = self.ix["index"].get("dist", "euclidean")
        overlay = self._pending_overlay(ctx, ns, db)
        if overlay is not None:
            yield from self._exact_overlay(mirror, overlay, metric)
            return
        n = mirror.count()
        if n == 0:
            return
        k = min(self.k, n)
        from surrealdb_tpu import telemetry, tracing

        # kernel-level node in the request's span tree: opened BEFORE the
        # serving-path chain so the dispatch spans it triggers nest under it
        t_search = _time.perf_counter()
        _trace_tok = tracing.push()
        _search_err: Optional[BaseException] = None
        q = np.asarray(self.target, dtype=np.float32)
        try:
            # MTREE preserves the reference's exactness contract
            # (core/src/idx/trees/mtree.rs:135 — an exact metric tree): it
            # always takes the exact fused distance+top-k paths; only HNSW
            # indexes may serve approximate IVF results
            approx_ok = self.ix["index"]["type"] != "mtree"
            # ANN pays off only when k is a small fraction of the corpus; a big-k
            # query gets the exact fused kernel (IVF would cap results at the
            # probed-candidate count)
            mesh = None if cnf.TPU_DISABLE else ds.mesh()
            if mesh is not None and n >= cnf.TPU_KNN_ONDEVICE_THRESHOLD:
                # multi-chip: the mirror shards row-wise over the mesh. ANN
                # composes with the mesh (VERDICT r3 weak #1): centroids are
                # replicated, inverted-list members sharded by slot range —
                # per-shard probe + rerank, then an O(k*devices) all-gather
                # (parallel/mesh.py sharded_ivf_search). While the quantizer
                # trains in the background (or for big-k queries where IVF
                # can't pay off) the exact per-shard distance+top-k path
                # (sharded_knn) serves instead — never a latency cliff.
                matrix, mask, rids = mirror.device_snapshot(mesh)
                mask_dev = mirror.device_sharded_mask()
                want_ivf = approx_ok and n >= cnf.TPU_ANN_MIN_ROWS and self.k * 4 <= n
                ivf = mirror.ensure_ivf(matrix) if want_ivf else None
                flt = self._slot_filter(
                    ctx, mirror, rids, mask, mesh=mesh, device=ivf is not None
                )
                if ivf is not None:
                    ef = self.ef or self.ix["index"].get("efc")
                    how, arg, k = self._ivf_route(flt, n, ivf, ef, k)
                    if how == "subset":
                        # the passing rows themselves, scored exactly on the
                        # shard that holds each (parallel/mesh.py)
                        self.strategy = "exact-subset-sharded"
                        key = ("knn-subset-sharded", id(matrix), flt.serial, metric, k, flt.size)

                        def runner(qs):
                            return _subset_sharded_launch(np.stack(qs), mesh, matrix, flt, metric, k)

                    else:
                        self.strategy = "ivf-sharded"
                        nprobe = arg
                        key = ("knn-ivf-sharded", id(matrix), id(ivf), metric, k, nprobe)
                        # columnar residual prefilter (parity with ivf/ivf-host):
                        # the cached slot mask is sharded alongside the corpus
                        # rows, and the dispatch key names the filter so riders
                        # with different $param bindings never share a
                        # leader's mask
                        slot_mask = None
                        if flt is not None:
                            slot_mask = flt.ok
                            key = key + (flt.serial,)

                        def runner(qs):
                            qm = np.stack(qs)

                            def collect():
                                dd, rr = ivf.search_batch_sharded(
                                    qm, mesh, matrix, metric, k, nprobe,
                                    slot_mask=slot_mask,
                                )
                                return list(zip(dd, rr))

                            return collect

                    dists, slots = self._submit(ds, t_iter, key, q, runner, how, k)
                else:
                    self.strategy = (
                        "exact-sharded(ivf-training)" if want_ivf else "exact-sharded"
                    )
                    key = ("knn-sharded", id(matrix), metric, k)
                    # columnar residual prefilter, as every other strategy:
                    # the matching slots AND the live ones, sharded as the
                    # live mask is, the filter's name in the dispatch key
                    if flt is not None:
                        import jax

                        mask_dev = jax.device_put(flt.host, mask_dev.sharding)
                        key = key + (flt.serial,)

                    def runner(qs):
                        from surrealdb_tpu import compile_log
                        from surrealdb_tpu.parallel.mesh import sharded_knn
                        from surrealdb_tpu.utils.num import dispatch_tile, pad_tail, tile_slices

                        qs_m = np.stack(qs)
                        nq = qs_m.shape[0]
                        tile = dispatch_tile(nq)
                        dd = np.empty((nq, k), dtype=np.float32)
                        rr = np.empty((nq, k), dtype=np.int64)

                        def one_slice(lo, hi):
                            d, r = sharded_knn(
                                mesh, matrix, mask_dev, pad_tail(qs_m[lo:hi], tile), k, metric
                            )
                            dd[lo:hi] = np.asarray(d)[: hi - lo]
                            rr[lo:hi] = np.asarray(r)[: hi - lo]

                        # one executable per (tile, corpus dims, metric, k)
                        # on the mesh: only the FIRST slice can compile, so
                        # only it is tracked — wrapping the whole loop would
                        # log N tile executions as one giant phantom
                        # "compile" (graftlint GL002)
                        slices = list(tile_slices(nq, tile))
                        with compile_log.tracked(
                            "knn_sharded",
                            (tile, int(matrix.shape[1]), int(matrix.shape[0]),
                             metric, k),
                        ):
                            one_slice(*slices[0])
                        for lo, hi in slices[1:]:
                            one_slice(lo, hi)
                        return list(zip(dd, rr))

                    dists, slots = _submit_prepared(
                        ds, t_iter, key, q, runner, self._masked_route(flt)
                    )
            elif (
                not cnf.TPU_DISABLE
                and approx_ok
                and n >= cnf.TPU_ANN_MIN_ROWS
                and self.k * 4 <= n
            ):
                self.strategy = "ivf"
                # snapshot first: device_view may compact dead slots, which
                # renumbers the slot space and invalidates any trained IVF; the
                # snapshot's rids list is tied to this matrix's numbering
                matrix, mask, rids = mirror.device_snapshot()
                ivf = mirror.ensure_ivf(matrix)
                flt = self._slot_filter(ctx, mirror, rids, mask, device=ivf is not None)
                if ivf is None:
                    # quantizer still training in the background: serve this
                    # query exactly (no latency cliff, full recall)
                    self.strategy = "exact-device(ivf-training)"
                    dists, slots = self._exact_device(ds, t_iter, mirror, matrix, mask, flt, metric, k, q)
                else:
                    ef = self.ef or self.ix["index"].get("efc")
                    how, arg, k = self._ivf_route(flt, n, ivf, ef, k)
                    if how == "subset":
                        # fewer rows pass than a widened probe would gather:
                        # score them all, exactly, from the cached slot array
                        self.strategy = "exact-subset"
                        key = ("knn-subset", id(matrix), flt.serial, metric, k, flt.size)

                        def runner(qs):
                            return _zipped(_exact_device_launch(
                                np.stack(qs), matrix, None, metric, k,
                                owner=mirror._owner, subset=flt,
                            ))

                    else:
                        nprobe = arg
                        # concurrent same-shape queries coalesce into one kernel
                        # launch (dbs/dispatch.py — the cross-query PARALLEL seam).
                        # Keyed by the matrix/ivf identities so a batch never mixes
                        # slot numberings.
                        key = ("knn-ivf", id(matrix), id(ivf), metric, k, nprobe)
                        # residual-WHERE prefilter (parity with the exact
                        # strategies): the cached device mask rides into the
                        # probe+rerank kernel so top-k is computed among
                        # MATCHING rows; the key names the filter so riders
                        # with different $param bindings never share a
                        # leader's tighter mask. Without a prefilter the key
                        # and the program are the bare search's.
                        slot_mask = None
                        if flt is not None:
                            slot_mask = flt.ok
                            key = key + (flt.serial,)

                        def runner(qs):
                            return _zipped(ivf.search_batch_launch(
                                np.stack(qs), matrix, metric, k, nprobe,
                                owner=mirror._owner, slot_mask=slot_mask,
                            ))

                    dists, slots = self._submit(ds, t_iter, key, q, runner, how, k)
            elif not cnf.TPU_DISABLE and n >= cnf.TPU_KNN_ONDEVICE_THRESHOLD:
                self.strategy = "exact-device"
                matrix, mask, rids = mirror.device_snapshot()
                flt = self._slot_filter(ctx, mirror, rids, mask, device=False)
                dists, slots = self._exact_device(ds, t_iter, mirror, matrix, mask, flt, metric, k, q)
            else:
                # CPU serving path: an already-trained quantizer serves ANN on
                # host too (probe + exact rerank, idx/ivf.py search_host) — the
                # same sublinear contract as the device path. Never trains
                # here (training needs the device matrix); exact scan
                # otherwise.
                ivf = mirror.ivf
                if (
                    approx_ok
                    and ivf is not None
                    and not ivf.needs_retrain()
                    and metric in ("euclidean", "cosine")
                    and n >= cnf.TPU_ANN_MIN_ROWS
                    and self.k * 4 <= n
                ):
                    self.strategy = "ivf-host"
                    ef = self.ef or self.ix["index"].get("efc")
                    data, alive, rids = mirror.host_view()
                    flt = self._slot_filter(ctx, mirror, rids, alive, device=False)
                    how, arg, k = self._ivf_route(flt, n, ivf, ef, k)
                    _filter_route(how)
                    if how == "subset":
                        self.strategy = "exact-subset-host"
                        dists = slots = np.empty(0)
                        if k:
                            dists, li = D.knn_search_host(q[None, :], data[flt.slot_ids], metric, k)
                            dists, slots = dists[0], flt.slot_ids[li[0]]
                    else:
                        dists, li = ivf.search_host(
                            q[None, :], data, metric, k, arg,
                            slot_mask=None if flt is None else flt.host,
                        )
                        dists, slots = dists[0], li[0]
                else:
                    self.strategy = "exact-host"
                    data, norms, rids = mirror.host_search_view()
                    flt = self._slot_filter(ctx, mirror, rids, None, device=False)
                    _filter_route(self._masked_route(flt))
                    if flt is not None:
                        sel = flt.slot_ids
                        if sel.size == 0:
                            return
                        data, norms = data[sel], norms[sel]
                        rids = [rids[int(i)] for i in sel]
                        k = min(k, sel.size)
                    dists, li = D.knn_search_host(
                        q[None, :], data, metric, k, x_sq_norms=norms
                    )
                    dists, slots = dists[0], np.asarray(li)[0]
        except BaseException as e:
            _search_err = e
            raise
        finally:
            dur = _time.perf_counter() - t_search
            telemetry.observe("knn_search", dur, strategy=self.strategy)
            if _trace_tok is not None:
                tracing.pop(
                    _trace_tok, "knn_search",
                    {"strategy": self.strategy, "n": n, "k": k},
                    t_search, dur, _search_err,
                )
            ctx.executor.op_end = t_search + dur
        self._count_strategy(n)
        for d, s in zip(np.asarray(dists), np.asarray(slots)):
            if not np.isfinite(d) or s < 0 or s >= len(rids):
                continue
            rid = rids[int(s)]
            if not isinstance(rid, Thing):
                rid = Thing(self.tb, rid)
            self.result.add(rid, float(d))
            yield rid, None, {"dist": float(d)}

    def _count_strategy(self, n: int) -> None:
        """Record which serving path answered this kNN query: the strategy
        counter attributes recall/latency anomalies per path, and the
        fallback counter isolates queries that LOST their sublinear path
        (quantizer still training → exact serve)."""
        from surrealdb_tpu import telemetry

        telemetry.inc("knn_strategy", strategy=self.strategy)
        if "(ivf-training)" in self.strategy:
            telemetry.inc("knn_fallbacks", cause="ivf_training")
        telemetry.note_plan(
            {"knn": self.strategy, "index": self.ix["name"], "k": self.k, "n": n}
        )

    def _exact_overlay(self, mirror, overlay, metric):
        """Merge uncommitted rows over the mirror and search exactly."""
        self.strategy = "exact-overlay"
        self._count_strategy(mirror.count())
        data, alive, rids = mirror.host_view()
        rows, out_rids = [], []
        for i in np.nonzero(alive)[0].tolist():
            key = _rid_key(rids[i])
            if key in overlay:
                continue  # superseded by the pending write
            rows.append(data[i])
            out_rids.append(rids[i])
        for key, (rid, vec) in overlay.items():
            if vec is not None:
                rows.append(np.asarray(vec, dtype=np.float32))
                out_rids.append(rid)
        if not rows:
            return
        mat = np.stack(rows)
        k = min(self.k, len(rows))
        dists, idxs = D.knn_search_host(
            np.asarray([self.target], dtype=np.float32), mat, metric, k
        )
        for d, i in zip(dists[0], idxs[0]):
            if not np.isfinite(d):
                continue
            rid = out_rids[int(i)]
            if not isinstance(rid, Thing):
                rid = Thing(self.tb, rid)
            self.result.add(rid, float(d))
            yield rid, None, {"dist": float(d)}


class BruteForceKnnPlan(_KnnExecutorMixin):
    """`<|k,DIST|>` with no matching index: one streamed pass gathers the
    field vectors, then a single fused device kernel does distance + top-k
    (replaces the reference's two-stage CollectKnn→BuildKnn workflow
    planner/mod.rs:208-232 with one batched pass)."""

    def __init__(self, tb: str, op, target):
        self.tb = tb
        self.op = op
        self.k = op.k
        self.metric = (op.dist or "euclidean").lower()
        self.target = _target_vector(target)
        self.result = _KnnResult()

    def explain(self) -> dict:
        return {
            "operator": f"<|{self.k},{self.metric.upper()}|>",
            "table": self.tb,
            "strategy": "brute-force (device batch)",
        }

    def iterate(self, ctx):
        ctx.qe = self
        from surrealdb_tpu.dbs.iterator import scan_table

        field = self.op.l
        rids: List[Thing] = []
        rows: List[List[float]] = []
        docs: Dict[Any, dict] = {}
        dim = len(self.target)
        for rid, doc in scan_table(ctx, self.tb):
            with ctx.with_doc_value(doc, rid=rid) as c:
                v = field.compute(c)
            if not isinstance(v, (list, tuple)) or len(v) != dim:
                continue
            try:
                rows.append([float(x) for x in v])
            except (TypeError, ValueError):
                continue
            rids.append(rid)
            docs[(rid.tb, repr(rid.id))] = doc
        if not rows:
            return
        from surrealdb_tpu import telemetry

        telemetry.inc("knn_strategy", strategy="brute-force")
        telemetry.note_plan({"knn": "brute-force", "table": self.tb, "n": len(rows)})
        k = min(self.k, len(rids))
        q = np.asarray([self.target], dtype=np.float32)
        if cnf.TPU_DISABLE or len(rids) < cnf.TPU_KNN_ONDEVICE_THRESHOLD:
            dists, idxs = D.knn_search_host(q, np.asarray(rows, dtype=np.float32), self.metric, k)
        else:
            mat, mask = D.pad_rows(np.asarray(rows, dtype=np.float32), cnf.TPU_BATCH_MIN_TILE)
            dists, idxs = D.knn_search(q, mat, mask, self.metric, k)
        dists = np.asarray(dists)[0]
        idxs = np.asarray(idxs)[0]
        for d, i in zip(dists, idxs):
            if not np.isfinite(d) or i >= len(rids):
                continue
            rid = rids[int(i)]
            self.result.add(rid, float(d))
            yield rid, docs[(rid.tb, repr(rid.id))], {"dist": float(d)}
