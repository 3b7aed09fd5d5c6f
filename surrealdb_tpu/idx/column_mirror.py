"""Columnar table mirror: typed column arrays + the vectorized scan plan.

Role of the per-row `scan_table` → `cond.compute` hot loop (dbs/iterator.py)
re-designed batch-at-a-time, the same proven pattern as idx/ft_mirror.py and
idx/graph_csr.py: hot tables' scalar fields are materialized into typed
numpy columns (tag/num/str triples per dotted path) plus a row-id map, so a
simple `SELECT ... WHERE` becomes ONE vectorized mask evaluation
(ops/predicates.py) over the whole table, with `unpack` paid only for the
surviving rows and the statement deadline checked per block instead of per
row. The r07 slowest trace showed 161.8s of `execute` wrapping 16.6s of
`knn_search` — this module attacks exactly that GIL-bound per-row gap.

Staleness protocol (the part that must be airtight):

- Every committed record write bumps the table's entry in
  `ColumnMirrors.versions` BEFORE the backend commit, inside the
  datastore's commit lock (kvs/tx.py). A build atomically captures
  (version, fresh snapshot) under the same lock. A reader therefore serves
  the mirror ONLY when (a) its own transaction has no uncommitted writes to
  the table, (b) the mirror's build version still equals the table's
  current version, and (c) the reader's snapshot is at least as new as the
  table's last commit as of the build, else as the build snapshot itself
  (a commit to ANOTHER table between a reader's snapshot and the build it
  triggers must not send that reader to the row path after it has paid for
  the build). The floor is the last commit's only under this invariant,
  which the build checks and does not assume: NO COMMIT TO THE TABLE LIES
  IN (last_commit, build snapshot]. Every commit records its store version
  in `last_commit` beside the counter it bumped the table to, after it
  lands and under the same lock; the build uses the record only when that
  counter is still the table's. A commit that bumped and recorded nothing
  (it failed, it dropped the table's scope, its backend names no commit
  version) leaves the counters apart, and the floor is the snapshot. Any
  commit that could make the mirror wrong for that
  reader is guaranteed to have bumped the version before the reader's
  snapshot even opened — a stale mask can never serve.
- Commits into a mirrored table also arm a debounced background rebuild
  (pattern of GraphMirrors' ingest-time prewarm) so the post-ingest first
  query finds a fresh mirror; query-time rebuilds are rate-limited by the
  same window, falling back to the row path while writes are hot.

The KV state stays authoritative; results are always identical to the row
path (rows the predicate compiler can't judge are re-checked per row).
"""

from __future__ import annotations

import itertools
import threading
from surrealdb_tpu.utils import locks as _locks
import time as _time
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from surrealdb_tpu import cnf
from surrealdb_tpu import key as keys
from surrealdb_tpu.key.encode import prefix_end
from surrealdb_tpu.ops.predicates import (
    TAG_BOOL,
    TAG_DATETIME,
    TAG_FLOAT,
    TAG_INT,
    TAG_NONE,
    TAG_NULL,
    TAG_OTHER,
    TAG_STR,
    F64_EXACT_INT,
    CompiledPredicate,
)
from surrealdb_tpu.sql.value import Datetime, Thing, is_none, is_null
from surrealdb_tpu.utils.num import path_slots as _path_slots
from surrealdb_tpu.utils.ser import unpack


# ------------------------------------------------------------------ columns
class Column:
    """One dotted path's values over the table's row order."""

    __slots__ = ("tags", "nums", "_strs", "_nonempty", "_i64")

    def __init__(
        self,
        tags: np.ndarray,
        nums: np.ndarray,
        strs: Optional[np.ndarray],
        i64: Optional[np.ndarray] = None,
    ):
        self.tags = tags
        self.nums = nums
        self._strs = strs  # object-dtype, "" where not a string
        self._nonempty: Optional[np.ndarray] = None
        # exact integer plane: datetime nanos (epoch nanos overflow the f64
        # mantissa — ~1.7e18 vs 2^53 — so they compare on int64)
        self._i64 = i64

    def i64(self) -> np.ndarray:
        if self._i64 is None:
            self._i64 = np.zeros(len(self.tags), dtype=np.int64)
        return self._i64

    def str_eq(self, c: str) -> np.ndarray:
        if self._strs is None:
            return np.zeros(len(self.tags), dtype=bool)
        return np.asarray(self._strs == c, dtype=bool)

    def str_cmp(self, c: str) -> Tuple[np.ndarray, np.ndarray]:
        if self._strs is None:
            z = np.zeros(len(self.tags), dtype=bool)
            return z, z
        return (
            np.asarray(self._strs < c, dtype=bool),
            np.asarray(self._strs > c, dtype=bool),
        )

    def str_array(self) -> np.ndarray:
        """The string plane ("" where not a string) — the pipeline's sort /
        group-key rank source and cell reconstruction."""
        if self._strs is None:
            self._strs = np.full(len(self.tags), "", dtype=object)
        return self._strs

    def str_nonempty(self) -> np.ndarray:
        if self._nonempty is None:
            if self._strs is None:
                self._nonempty = np.zeros(len(self.tags), dtype=bool)
            else:
                self._nonempty = np.asarray(self._strs != "", dtype=bool)
        return self._nonempty

    def str_contains(self, c: str) -> np.ndarray:
        """Substring containment per STRING cell (`field CONTAINS 'sub'`).
        Object-dtype columns have no vectorized substring kernel; the
        generator pass is still one C-level loop over python strings —
        far from the row path's full per-row cond.compute machinery."""
        if self._strs is None:
            return np.zeros(len(self.tags), dtype=bool)
        return np.fromiter(
            (c in s for s in self._strs), dtype=bool, count=len(self.tags)
        )


def _all_none_column(n: int) -> Column:
    return Column(np.zeros(n, dtype=np.int8), np.zeros(n, dtype=np.float64), None)


class _ColBuilder:
    """Growable column during the build scan; rows before first sight
    backfill as NONE (missing field == NONE, get_path semantics)."""

    __slots__ = ("tags", "nums", "str_rows", "str_vals", "i64_rows", "i64_vals", "n")

    def __init__(self, cap: int, backfill: int):
        self.tags = np.zeros(cap, dtype=np.int8)
        self.nums = np.zeros(cap, dtype=np.float64)
        self.str_rows: List[int] = []
        self.str_vals: List[str] = []
        self.i64_rows: List[int] = []  # datetime cells (nanos, exact)
        self.i64_vals: List[int] = []
        self.n = backfill  # rows already covered (as NONE)

    def grow(self, cap: int) -> None:
        if len(self.tags) < cap:
            t = np.zeros(cap, dtype=np.int8)
            t[: len(self.tags)] = self.tags
            m = np.zeros(cap, dtype=np.float64)
            m[: len(self.nums)] = self.nums
            self.tags, self.nums = t, m

    def put(self, row: int, v: Any) -> None:
        tag, num, s, i64 = _classify(v)
        self.tags[row] = tag
        if num is not None:
            self.nums[row] = num
        if s is not None:
            self.str_rows.append(row)
            self.str_vals.append(s)
        if i64 is not None:
            self.i64_rows.append(row)
            self.i64_vals.append(i64)
        self.n = row + 1

    def finalize(self, n: int) -> Column:
        tags = self.tags[:n].copy()
        nums = self.nums[:n].copy()
        strs = None
        if self.str_vals:
            strs = np.full(n, "", dtype=object)
            strs[self.str_rows] = self.str_vals
        i64 = None
        if self.i64_vals:
            i64 = np.zeros(n, dtype=np.int64)
            i64[self.i64_rows] = self.i64_vals
        return Column(tags, nums, strs, i64)


def _classify(v) -> Tuple[int, Optional[float], Optional[str], Optional[int]]:
    """(tag, numeric value, string value, int64 value) for one scalar cell;
    anything the mask algebra can't reproduce exactly is OTHER (per-row
    fallback)."""
    if is_none(v):
        return TAG_NONE, None, None, None
    if is_null(v):
        return TAG_NULL, None, None, None
    if isinstance(v, bool):
        return TAG_BOOL, 1.0 if v else 0.0, None, None
    if isinstance(v, int):
        if -F64_EXACT_INT <= v <= F64_EXACT_INT:
            return TAG_INT, float(v), None, None
        return TAG_OTHER, None, None, None
    if isinstance(v, float):
        return TAG_FLOAT, v, None, None
    if isinstance(v, str) and type(v) is str:
        return TAG_STR, None, v, None
    if isinstance(v, Datetime):
        return TAG_DATETIME, None, None, v.nanos
    return TAG_OTHER, None, None, None


# ------------------------------------------------------------------ device form
_TAG_CELL = {TAG_NONE: "none", TAG_NULL: "null", TAG_FLOAT: "float", TAG_OTHER: "other"}
_INT32 = np.iinfo(np.int32)
_MIRROR_SERIAL = itertools.count(1)


class DeviceColumn:
    """One column's device form, as long-lived as the mirror object it
    belongs to (one build version): `plane`, int32 [path_slots(rows)] in
    HBM in record-key order, the pad 0. Form `value`: the cells themselves
    (every cell an int inside int32; `lo` / `hi` their least and greatest).
    Form `code`: dense codes of `values`, the host's sorted distinct cells
    (ints, bools, strings, or datetime nanos: `tag` says which), so that a
    comparison with a constant is a comparison of codes after a
    `searchsorted` on the host, exact for any ordered type."""

    __slots__ = ("form", "plane", "values", "tag", "lo", "hi")

    def __init__(self, form: str, plane, values, tag: int, lo: int = 0, hi: int = 0):
        self.form, self.plane, self.values, self.tag, self.lo, self.hi = form, plane, values, tag, lo, hi


def _encode_device(col: Column, form: str, path: str):
    """(form, int32 cells, sorted distinct values or None, tag, lo, hi) of
    one column's device form (`form` asked: `value`, `code`, or `any`: the
    values where they fit, else codes), or the reason (text) it has none:
    the rule is by column, read once."""
    tags = col.tags
    if not len(tags):
        return f"empty:{path}"
    tag = int(tags[0])
    if not (tags == tag).all():
        odd = next((name for t, name in _TAG_CELL.items() if (tags == t).any()), "mixed")
        return f"{odd}_cell:{path}"
    if tag in _TAG_CELL:
        return f"{_TAG_CELL[tag]}_cell:{path}"
    if form != "code" and tag == TAG_INT:
        lo, hi = int(col.nums.min()), int(col.nums.max())
        if _INT32.min <= lo and hi <= _INT32.max:
            return "value", col.nums.astype(np.int32), None, tag, lo, hi
    if form == "value":
        return f"{'int_range' if tag == TAG_INT else 'not_int'}:{path}"
    if tag == TAG_STR:
        cells = col.str_array().tolist()
        values = np.asarray(sorted(set(cells)), dtype=object)
        code_of = {v: i for i, v in enumerate(values.tolist())}
        codes = np.fromiter(map(code_of.__getitem__, cells), dtype=np.int32, count=len(tags))
    else:
        cells = col.i64() if tag == TAG_DATETIME else col.nums.astype(np.int64)
        values, codes = np.unique(cells, return_inverse=True)
        codes = codes.reshape(-1).astype(np.int32)
    return "code", codes, values, tag, 0, len(values) - 1


# ------------------------------------------------------------------ mirror
class ColumnMirror:
    """One table's columns, frozen at (built_version, build snapshot)."""

    __slots__ = (
        "serial",
        "_device",
        "_device_lock",
        "placements",
        "ids",
        "enc_keys",
        "columns",
        "nested_unsafe",
        "overflow",
        "n",
        "built_version",
        "built_store_version",
        "build_time",
        "delta_fed",
        "_order",
        "_virtual",
        "_id_index",
        "_slot_perm",
    )

    def __init__(self):
        self.ids: List[Any] = []  # row -> record id (key-scan order)
        self.enc_keys: List[bytes] = []  # row -> enc_value_key(id)
        self.columns: Dict[str, Column] = {}
        # top-level fields holding a list/record-link in ANY row: a nested
        # path under them can't default to all-NONE (get_path distributes
        # over lists and fetches through Things)
        self.nested_unsafe: Set[str] = set()
        self.overflow = False  # field budget exceeded: unknown paths exist
        self.n = 0
        self.built_version = -1
        self.built_store_version = -1
        self.build_time = 0.0
        self.delta_fed = False  # rows appended by a bulk delta (not key order)
        # row indices in key order when delta-fed (None = already key order);
        # computed lazily on the first scan that streams rows out
        self._order: Optional[np.ndarray] = None
        self._virtual: Dict[str, Column] = {}
        self._id_index: Optional[Dict[str, int]] = None
        # (id(rids list), n_slots) -> row permutation for the kNN prefilter
        self._slot_perm: Optional[Tuple[Tuple[int, int], int, np.ndarray]] = None
        # the device forms of this build's columns: (path, form it has) ->
        # DeviceColumn, or (path, form asked) -> the reason there is none.
        # They belong to this object, so to one build version: a write
        # installs another mirror, and a reader that may not serve this one
        # never sees them
        self.serial = next(_MIRROR_SERIAL)
        self._device: Dict[Tuple[str, str], Any] = {}
        # what ops/pipeline.py::grouped_route worked out over these planes (a
        # statement shape -> its DevicePlan, or the reason it has none)
        self.placements: Dict[Any, Any] = {}
        self._device_lock = _locks.Lock("idx.column.device")

    def device_columns(self, want) -> Tuple[Optional[Dict[Tuple[str, str], DeviceColumn]], Optional[str]]:
        """The device forms of `want` ((path, form) pairs), encoded and
        uploaded on first need, or (None, reason) where a column has none
        (a NONE / NULL / float / OTHER cell in any row, a `value` form of a
        column that is no int inside int32). The references are taken under
        the lock; the caller computes outside it."""
        from surrealdb_tpu import telemetry

        want = sorted(set(want))
        with self._device_lock:
            new = [w for w in want if self._held(w) is None]
            if new:
                cols = self.columns_for({p for p, _ in new})
                if cols is None:
                    return None, "columns"
                t0 = _time.perf_counter()
                order, slots = self.key_order(), _path_slots(self.n)
                made = {}
                for path, form in new:
                    enc = _encode_device(cols[path], form, path)
                    if isinstance(enc, str):
                        made[(path, form)] = enc
                    elif (path, enc[0]) not in made:  # `any` and the form it resolves to, both new: one plane
                        plane = np.zeros(slots, dtype=np.int32)
                        plane[: self.n] = enc[1] if order is None else enc[1][order]
                        made[(path, enc[0])] = (enc[0], plane) + enc[2:]  # DeviceColumn's fields, the plane still on the host
                dt = _time.perf_counter() - t0
                telemetry.observe("column_device_encode", dt)
                telemetry.stage("column_device_encode", t0, dt, columns=len(made), rows=self.n)
                encoded = [k for k, e in made.items() if not isinstance(e, str)]
                if encoded:
                    import jax.numpy as jnp

                    t0 = _time.perf_counter()
                    for k in encoded:
                        form, plane, *rest = made[k]
                        made[k] = DeviceColumn(form, jnp.asarray(plane), *rest)
                    dt = _time.perf_counter() - t0
                    telemetry.observe("column_device_upload", dt)
                    telemetry.stage("column_device_upload", t0, dt, bytes=4 * slots * len(encoded))
                self._device.update(made)
            refs = {w: self._held(w) for w in want}
        for d in refs.values():
            if isinstance(d, str):
                return None, d
        return refs, None

    def _held(self, w: Tuple[str, str]):
        """What `_device` holds for one (path, form) asked: the DeviceColumn,
        the reason there is none, or None where it is yet to be encoded. A
        plane is kept under the form it HAS, so `any` (the values where
        they fit, else codes) finds what `value` or `code` made and makes
        no second copy."""
        path, form = w
        if form != "any":
            return self._device.get(w)
        for f in ("value", "code"):
            d = self._device.get((path, f))
            if isinstance(d, DeviceColumn):
                return d
        return self._device.get(w)  # the reason the column has no form at all

    def key_order(self) -> Optional[np.ndarray]:
        """Row indices in record-key order, or None when rows are already
        key-ordered (every fully-built mirror; delta appends break it).
        Scans stream surviving rows in this order so columnar output stays
        byte-identical to the row path's key-ordered scan."""
        if not self.delta_fed:
            return None
        if self._order is None:
            self._order = np.argsort(
                np.asarray(self.enc_keys, dtype=object), kind="stable"
            )
        return self._order

    def columns_for(self, paths: Set[str]) -> Optional[Dict[str, Column]]:
        """Resolve every path to a column; a path never seen is all-NONE
        when that default is provably exact, else None (row path)."""
        out: Dict[str, Column] = {}
        for p in paths:
            col = self.columns.get(p)
            if col is None:
                if self.overflow:
                    return None
                head = p.split(".", 1)[0]
                if "." in p and head in self.nested_unsafe:
                    return None
                col = self._virtual.get(p)
                if col is None:
                    col = self._virtual[p] = _all_none_column(self.n)
            out[p] = col
        return out

    def id_index(self) -> Dict[str, int]:
        """repr(record id) -> row, for aligning foreign slot spaces."""
        if self._id_index is None:
            self._id_index = {repr(i): r for r, i in enumerate(self.ids)}
        return self._id_index

    def slot_permutation(self, rids: List[Any], cap: int) -> np.ndarray:
        """perm[slot] = column row of the vector-mirror slot's record (or -1),
        cached per (rids identity and length, slot count) — rebuilding the
        mirror installs a new ColumnMirror object, and a vector mirror that
        took a commit's rows after this mirror took them (kvs/tx.py applies
        the column delta first) has a longer list, so the cache can't go
        stale."""
        name = (id(rids), len(rids))
        cached = self._slot_perm
        if cached is not None and cached[0] == name and cached[1] == cap:
            return cached[2]
        idx = self.id_index()
        perm = np.full(cap, -1, dtype=np.int64)
        for slot, rid in enumerate(rids[: min(cap, name[1])]):
            rid_id = rid.id if isinstance(rid, Thing) else rid
            row = idx.get(repr(rid_id))
            if row is not None:
                perm[slot] = row
        self._slot_perm = (name, cap, perm)
        return perm


class ColumnMirrors:
    """Per-datastore registry: (ns, db, tb) -> ColumnMirror + the commit
    version counters the staleness protocol hangs off."""

    def __init__(self):
        self._lock = _locks.RLock("idx.column.registry")
        self.versions: Dict[Tuple[str, str, str], int] = {}
        # table -> (its version counter as that commit bumped it, the store
        # version the commit landed at), where the backend gave one
        # (kvs/tx.py, under the commit lock): a built mirror's floor
        self.last_commit: Dict[Tuple[str, str, str], Tuple[int, int]] = {}
        self._mirrors: Dict[Tuple[str, str, str], ColumnMirror] = {}
        self._build_locks: Dict[Tuple[str, str, str], threading.Lock] = {}
        self._ds = None  # weakref to the owning Datastore
        self._timers: Dict[Tuple[str, str, str], threading.Timer] = {}
        self._deadlines: Dict[Tuple[str, str, str], float] = {}
        self._running: Set[Tuple[str, str, str]] = set()
        # flight-recorder task ids of armed rebuilds (bg.py lifecycle)
        self._task_ids: Dict[Tuple[str, str, str], int] = {}
        self._owner: Optional[int] = None  # id(ds), for bg teardown scoping

    # ------------------------------------------------------------ plumbing
    def bind_ds(self, ds) -> None:
        import weakref

        self._ds = weakref.ref(ds)
        self._owner = id(ds)

    def get(self, key3) -> Optional[ColumnMirror]:
        with self._lock:
            return self._mirrors.get(key3)

    # ------------------------------------------------------------ invalidation
    def invalidate(self, tables, scopes=()) -> None:
        """Bump version counters for touched tables / dropped scopes. Called
        by the committing transaction BEFORE its backend commit, under the
        datastore commit lock — see the module docstring for why that
        ordering closes every stale-serve window."""
        with self._lock:
            for k in tables:
                self.versions[k] = self.versions.get(k, 0) + 1
            for scope in scopes:
                w = len(scope)
                for k in list(self.versions):
                    if k[:w] == tuple(scope):
                        self.versions[k] += 1
                for k in list(self._mirrors):
                    if k[:w] == tuple(scope):
                        self.versions[k] = self.versions.get(k, 0) + 1

    def committed(self, tables, commit_version) -> None:
        """The backend commit that `invalidate` announced has landed at
        `commit_version` (None: the backend names none), still under the
        datastore commit lock: kept beside the version counter it bumped
        the table to, the floor of the next mirror built at that counter."""
        with self._lock:
            for k in tables:
                if commit_version is None:
                    self.last_commit.pop(k, None)
                else:
                    self.last_commit[k] = (self.versions.get(k, 0), commit_version)

    def drop_table(self, ns: str, db: str, tb: str) -> None:
        with self._lock:
            self._mirrors.pop((ns, db, tb), None)

    def drop_db(self, ns: str, db: str) -> None:
        with self._lock:
            for k in [k for k in self._mirrors if k[:2] == (ns, db)]:
                del self._mirrors[k]

    def drop_ns(self, ns: str) -> None:
        with self._lock:
            for k in [k for k in self._mirrors if k[0] == ns]:
                del self._mirrors[k]

    def clear(self) -> None:
        with self._lock:
            self._mirrors.clear()

    # ------------------------------------------------------------ rebuild
    def schedule_rebuild(self, tables) -> None:
        """Debounced background rebuild for committed-into mirrored tables
        (deadline-advance debounce, the GraphMirrors prewarm pattern)."""
        from surrealdb_tpu import bg

        if self._ds is None:
            return
        delay = cnf.COLUMN_REBUILD_DEBOUNCE_SECS
        now = _time.monotonic()
        with self._lock:
            armed = []
            for key3 in tables:
                if key3 not in self._mirrors:
                    continue  # never queried columnar — nothing to refresh
                self._deadlines[key3] = now + delay
                if key3 not in self._timers:
                    armed.append(key3)
                else:
                    tid = self._task_ids.get(key3)
                    if tid is not None:
                        bg.touch(tid)  # debounce deadline advanced
            for key3 in armed:
                # flight-recorder record: scheduled now, running when the
                # debounce fires, linked to the committing request's trace
                self._task_ids[key3] = bg.register(
                    "column_mirror", target=".".join(key3), owner=self._owner
                )
                self._arm_timer(key3, delay)

    def _arm_timer(self, key3, delay: float) -> None:
        from surrealdb_tpu import bg

        timer = bg.timer(
            delay, self._rebuild_cb, key3, None,
            task_id=self._task_ids.get(key3),
            name=f"bg:column_mirror:{key3[2]}", start=False,
        )
        timer.args = (key3, timer)
        self._timers[key3] = timer
        timer.start()

    def _rebuild_cb(self, key3, timer) -> None:
        from surrealdb_tpu import bg

        with self._lock:
            if self._timers.get(key3) is not timer:
                return
            remaining = self._deadlines.get(key3, 0.0) - _time.monotonic()
            if remaining > 0.001:
                self._arm_timer(key3, remaining)
                return
            del self._timers[key3]
            self._deadlines.pop(key3, None)
            self._running.add(key3)
            task_id = self._task_ids.pop(key3, None)
        if task_id is None:
            task_id = bg.register(
                "column_mirror", target=".".join(key3), owner=self._owner,
                trace_id=None,
            )
        try:
            with bg.run(task_id):
                ds = self._ds() if self._ds is not None else None
                if ds is not None:
                    from surrealdb_tpu import telemetry

                    telemetry.inc("column_mirror_rebuilds", cause="ingest_prewarm")
                    self.build(ds, *key3)
        except Exception:  # noqa: BLE001 — best-effort; query path stays intact
            from surrealdb_tpu import telemetry

            # counted, not silent: a repeatedly-failing prewarm shows up on
            # /metrics instead of vanishing (the bg task record has details)
            telemetry.inc("prewarm_errors", subsystem="column_mirror")
        finally:
            with self._lock:
                self._running.discard(key3)

    def wait_rebuild(self, timeout: float = 30.0) -> bool:
        """Block until no rebuild timer or build is pending (test
        determinism helper, never used on the query path)."""
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            with self._lock:
                if not self._timers and not self._running:
                    return True
            _time.sleep(0.01)
        return False

    def shutdown(self, timeout: float = 10.0) -> None:
        """Teardown on Datastore.close(): cancel armed timers (resolving
        their flight-recorder records) and wait out in-flight builds, so
        no rebuild thread outlives its datastore."""
        from surrealdb_tpu import bg

        with self._lock:
            timers = list(self._timers.values())
            self._timers.clear()
            self._deadlines.clear()
            task_ids = list(self._task_ids.values())
            self._task_ids.clear()
        for t in timers:
            t.cancel()
        for tid in task_ids:
            bg.cancel(tid, "cancelled: datastore closed")
        self.wait_rebuild(timeout)

    # ------------------------------------------------------------ delta feed
    def apply_bulk(self, key3, parts, n_bumps: int, commit_version) -> bool:
        """Append a bulk op's decoded rows straight onto an up-to-date
        mirror (the ingest delta-feed): `parts` is the commit-ordered list
        of (ids, enc_keys, docs) blocks this flush wrote to the table and
        `n_bumps` how many version bumps those commits performed. Applies
        ONLY when the mirror was exactly current before this flush
        (built_version == current - n_bumps) — then the merged mirror
        installs at the CURRENT version and serves immediately, and the
        100k-row re-scan rebuild never queues. Any other shape (schema
        drift past the field budget, interleaved row-level writes, no
        commit version from the backend) returns False and the caller
        falls back to the debounced rebuild. Must run under the datastore
        commit lock — the version capture is only atomic there."""
        from surrealdb_tpu import faults, telemetry

        def _decline(reason: str) -> bool:
            telemetry.inc("column_mirror_delta", outcome=reason)
            return False

        # chaos hook: an injected failure here proves the decline contract —
        # the commit stays durable, the caller falls back to the debounced
        # rebuild, and a stale mirror cannot serve (version mismatch)
        faults.fire("column.delta_apply")
        if not cnf.COLUMN_DELTA_FEED:
            return _decline("disabled")
        if commit_version is None:
            return _decline("no_commit_version")
        ds = self._ds() if self._ds is not None else None
        if ds is not None:
            _locks.assert_held(ds.commit_lock, "column_mirror.delta apply")
        with self._lock:
            m = self._mirrors.get(key3)
            cur = self.versions.get(key3, 0)
        if m is None:
            return _decline("no_mirror")
        if m.built_version != cur - n_bumps:
            return _decline("stale_base")
        if m.overflow:
            return _decline("overflow_base")
        ids: List[Any] = []
        enc_keys: List[bytes] = []
        docs: List[Any] = []
        for p_ids, p_keys, p_docs in parts:
            ids.extend(p_ids)
            enc_keys.extend(p_keys)
            docs.extend(p_docs)
        bn = len(docs)
        if bn == 0:
            return _decline("empty")
        blk, blk_unsafe = _build_block(docs)
        if blk.overflow:
            return _decline("overflow_block")
        paths = set(m.columns) | set(blk.columns)
        if len(paths) > max(cnf.COLUMN_MIRROR_MAX_FIELDS, 1):
            return _decline("overflow_union")
        nm = ColumnMirror()
        nm.n = m.n + bn
        nm.ids = m.ids + ids
        nm.enc_keys = m.enc_keys + enc_keys
        nm.delta_fed = True
        # incremental key order: the old prefix is already key-ordered (or
        # carries a computed order), so merging the B appended keys costs
        # O(N + B log N) here instead of a full O(N log N) object argsort
        # on the next scan — sustained ingest would otherwise re-sort the
        # whole table's keys after every bulk statement
        old_order = m.key_order()
        old_keys = np.asarray(m.enc_keys, dtype=object)
        if old_order is not None:
            old_rows = old_order
            old_keys = old_keys[old_order]
        else:
            old_rows = np.arange(m.n, dtype=np.int64)
        blk_keys = np.asarray(enc_keys, dtype=object)
        bidx = np.argsort(blk_keys, kind="stable")
        pos = np.searchsorted(old_keys, blk_keys[bidx])
        nm._order = np.insert(old_rows, pos, m.n + bidx)
        nm.built_version = cur
        nm.built_store_version = commit_version
        nm.build_time = m.build_time
        nm.nested_unsafe = m.nested_unsafe | blk.nested_unsafe
        cols: Dict[str, Column] = {}
        for p in paths:
            a = m.columns.get(p)
            b = blk.columns.get(p)
            tags = np.concatenate(
                [
                    a.tags if a is not None else np.zeros(m.n, dtype=np.int8),
                    b.tags if b is not None else np.zeros(bn, dtype=np.int8),
                ]
            )
            nums = np.concatenate(
                [
                    a.nums if a is not None else np.zeros(m.n, dtype=np.float64),
                    b.nums if b is not None else np.zeros(bn, dtype=np.float64),
                ]
            )
            strs = None
            if (a is not None and a._strs is not None) or (
                b is not None and b._strs is not None
            ):
                strs = np.full(nm.n, "", dtype=object)
                if a is not None and a._strs is not None:
                    strs[: m.n] = a._strs
                if b is not None and b._strs is not None:
                    strs[m.n :] = b._strs
            i64 = None
            if (a is not None and a._i64 is not None) or (
                b is not None and b._i64 is not None
            ):
                i64 = np.zeros(nm.n, dtype=np.int64)
                if a is not None and a._i64 is not None:
                    i64[: m.n] = a._i64
                if b is not None and b._i64 is not None:
                    i64[m.n :] = b._i64
            if a is None and "." in p and p.split(".", 1)[0] in m.nested_unsafe:
                # a nested path first seen in this batch, under a parent that
                # held lists/record-links in old rows: those old cells are
                # not provably NONE — re-check them per row
                tags[: m.n] = TAG_OTHER
            cols[p] = Column(tags, nums, strs, i64)
        # nested columns under a parent that held a list/record-link in a
        # BATCH row abstain there (same marking the full build applies) —
        # including columns only the old mirror materialized
        for parent, rows_u in blk_unsafe.items():
            off = np.asarray(rows_u, dtype=np.int64) + m.n
            for p, col in cols.items():
                if p.startswith(parent + "."):
                    col.tags[off] = TAG_OTHER
        nm.columns = cols
        with self._lock:
            if self.versions.get(key3, 0) != cur:
                return _decline("raced")
            self._mirrors[key3] = nm
        telemetry.inc("column_mirror_delta", outcome="applied")
        telemetry.observe_hist(
            "column_mirror_delta_rows", bn, buckets=telemetry.COUNT_BUCKETS
        )
        return True

    # ------------------------------------------------------------ serve
    def serveable(self, ctx, key3) -> Optional[ColumnMirror]:
        """The mirror, iff it is provably exact for this reader's snapshot;
        triggers a (rate-limited) synchronous rebuild when stale."""
        txn = ctx.txn()
        if key3 in getattr(txn, "touched_tables", ()):  # own uncommitted writes
            return None
        snap = getattr(txn.tr, "snapshot", None)
        if snap is None:
            return None
        with self._lock:
            m = self._mirrors.get(key3)
            cur = self.versions.get(key3, 0)
        if m is None or m.built_version != cur:
            if m is not None and (
                _time.monotonic() - m.build_time < cnf.COLUMN_REBUILD_DEBOUNCE_SECS
            ):
                return None  # writes still hot: row path; debounce will rebuild
            m = self.build(ctx.ds(), *key3)
            if m is None:
                return None
        if snap < m.built_store_version:
            return None  # reader's snapshot predates the table's state as built
        return m

    # ------------------------------------------------------------ build
    def build(self, ds, ns: str, db: str, tb: str) -> Optional[ColumnMirror]:
        key3 = (ns, db, tb)
        with self._lock:
            bl = self._build_locks.setdefault(key3, _locks.Lock("idx.column.build"))
        with bl:
            with self._lock:
                m = self._mirrors.get(key3)
                cur = self.versions.get(key3, 0)
            if m is not None and m.built_version == cur:
                return m  # a racing build already refreshed it
            from surrealdb_tpu import telemetry

            # atomically capture (version, snapshot): commits bump the
            # version and apply their backend writes as one unit under this
            # same lock, so no commit can land between the two reads
            with ds.commit_lock:
                with self._lock:
                    v0 = self.versions.get(key3, 0)
                    last = self.last_commit.get(key3)
                txn = ds.transaction(False)
            t0 = _time.perf_counter()
            mirror = ColumnMirror()
            try:
                mirror.built_version = v0
                # a reader serves from here on: the table's last commit,
                # where the counter says that commit was the last to bump it
                # (no commit to the table lies in (that commit, this
                # snapshot]: every one bumps the counter before it lands and
                # is recorded after, under the lock held above; a dropped
                # scope or a commit that failed bumps and records nothing,
                # and the counters then differ), else the snapshot itself
                snap = getattr(txn.tr, "snapshot", -1)
                known = last is not None and last[0] == v0
                mirror.built_store_version = min(snap, last[1]) if known else snap
                self._scan(txn, ns, db, tb, mirror)
            except Exception:
                telemetry.inc("column_mirror_rebuilds", cause="build_failed")
                return None
            finally:
                txn.cancel()
            mirror.build_time = _time.monotonic()
            telemetry.observe("column_mirror_build", _time.perf_counter() - t0)
            telemetry.observe_hist(
                "column_mirror_rows", mirror.n, buckets=telemetry.COUNT_BUCKETS
            )
            with self._lock:
                self._mirrors[key3] = mirror
            return mirror

    @staticmethod
    def _scan(txn, ns: str, db: str, tb: str, mirror: ColumnMirror) -> None:
        max_fields = max(cnf.COLUMN_MIRROR_MAX_FIELDS, 1)
        nested_depth = cnf.COLUMN_MIRROR_MAX_DEPTH
        pre = keys.thing_prefix(ns, db, tb)
        builders: Dict[str, _ColBuilder] = {}
        # parent field -> rows where it held a list/record-link: nested
        # columns under it must abstain there (get_path distributes over
        # lists and fetches through Things — all-NONE would be wrong)
        unsafe_rows: Dict[str, List[int]] = {}
        ids: List[Any] = []
        enc_keys: List[bytes] = []
        npre = len(pre)
        cap = 1024
        row = 0
        for chunk in txn.batch(pre, prefix_end(pre), cnf.NORMAL_FETCH_SIZE):
            if row + len(chunk) > cap:
                while row + len(chunk) > cap:
                    cap *= 2
                for b in builders.values():
                    b.grow(cap)
            docs = []
            for k, raw in chunk:
                ids.append(keys.decode_thing_id(k, ns, db, tb))
                enc_keys.append(k[npre:])
                docs.append(unpack(raw))
            if not _put_block(builders, docs, row, cap, max_fields, mirror, unsafe_rows):
                for j, doc in enumerate(docs):
                    if isinstance(doc, dict):
                        for name, v in doc.items():
                            _put_cell(
                                builders, name, v, row + j, cap, max_fields,
                                nested_depth, mirror, unsafe_rows,
                            )
            row += len(docs)
        mirror.ids = ids
        mirror.enc_keys = enc_keys
        mirror.n = row
        mirror.columns = {p: b.finalize(row) for p, b in builders.items()}
        for parent, rows_u in unsafe_rows.items():
            for p, col in mirror.columns.items():
                if p.startswith(parent + "."):
                    col.tags[rows_u] = TAG_OTHER


def _build_block(docs) -> Tuple[ColumnMirror, Dict[str, List[int]]]:
    """Classify one bulk batch's decoded rows into a block of columns (the
    delta-feed unit): the same `_put_cell` machinery the full build scan
    runs, minus the KV scan and unpack — the bulk path already decoded the
    rows once. Returns (block, unsafe parent -> block rows)."""
    blk = ColumnMirror()
    max_fields = max(cnf.COLUMN_MIRROR_MAX_FIELDS, 1)
    nested_depth = cnf.COLUMN_MIRROR_MAX_DEPTH
    builders: Dict[str, _ColBuilder] = {}
    unsafe_rows: Dict[str, List[int]] = {}
    cap = max(len(docs), 1)
    if not _put_block(builders, docs, 0, cap, max_fields, blk, unsafe_rows):
        for row, doc in enumerate(docs):
            if isinstance(doc, dict):
                for name, v in doc.items():
                    _put_cell(
                        builders, name, v, row, cap, max_fields,
                        nested_depth, blk, unsafe_rows,
                    )
    blk.n = len(docs)
    blk.columns = {p: b.finalize(blk.n) for p, b in builders.items()}
    return blk, unsafe_rows


def _put_block(builders, docs, row0: int, cap: int, max_fields: int, mirror, unsafe_rows) -> bool:
    """Classify a block of documents (rows row0 ...) a COLUMN at a time, by
    whole-array conversion, where the block is homogeneous: every document a
    dict of the first one's fields, every field's cells of one exact type
    among int (inside the f64-exact window), float, bool, str, Datetime and
    Thing (the record's own `id`: OTHER, and unsafe to look under).
    The same builders in the same order and the same cells as `_put_cell` a
    cell (tests/test_column_device.py holds the two to each other); any
    other block returns False untouched, and the caller takes the per-cell
    path for it. 26 -> ~10 us a row of 16 fields on the build's scan."""
    first = docs[0] if docs else None
    if type(first) is not dict or not first:
        return False
    names, k, m = first.keys(), len(first), len(docs)
    for d in docs:
        if type(d) is not dict or len(d) != k or d.keys() != names:
            return False
    if len(builders) + sum(1 for name in first if name not in builders) > max_fields:
        return False
    ready = []
    for name in first:
        vals = [d[name] for d in docs]
        kinds = set(map(type, vals))
        if len(kinds) != 1:
            return False
        kind = kinds.pop()
        if kind is int:
            try:
                arr = np.fromiter(vals, dtype=np.int64, count=m)
            except OverflowError:
                return False
            if int(arr.min()) < -F64_EXACT_INT or int(arr.max()) > F64_EXACT_INT:
                return False
            ready.append((name, TAG_INT, arr.astype(np.float64), None, None))
        elif kind is float:
            ready.append((name, TAG_FLOAT, np.fromiter(vals, dtype=np.float64, count=m), None, None))
        elif kind is bool:
            ready.append((name, TAG_BOOL, np.fromiter(vals, dtype=np.float64, count=m), None, None))
        elif kind is str:
            ready.append((name, TAG_STR, None, vals, None))
        elif kind is Datetime:
            ready.append((name, TAG_DATETIME, None, None, [v.nanos for v in vals]))
        elif kind is Thing:
            ready.append((name, TAG_OTHER, None, None, None))
        else:
            return False
    rows = range(row0, row0 + m)
    for name, tag, nums, strs, i64 in ready:
        b = builders.get(name)
        if b is None:
            b = builders[name] = _ColBuilder(cap, row0)
        b.tags[row0 : row0 + m] = tag
        if nums is not None:
            b.nums[row0 : row0 + m] = nums
        if strs is not None:
            b.str_rows.extend(rows)
            b.str_vals.extend(strs)
        if i64 is not None:
            b.i64_rows.extend(rows)
            b.i64_vals.extend(i64)
        if tag == TAG_OTHER:
            mirror.nested_unsafe.add(name)
            unsafe_rows.setdefault(name, []).extend(rows)
        b.n = row0 + m
    return True


def _put_cell(builders, name, v, row, cap, max_fields, nested_depth, mirror, unsafe_rows):
    """Classify one top-level cell, descending one level into dicts."""
    b = _builder_for(builders, name, row, cap, max_fields, mirror)
    if b is not None:
        b.put(row, v)
    if isinstance(v, (list, tuple, Thing)):
        mirror.nested_unsafe.add(name)
        unsafe_rows.setdefault(name, []).append(row)
    if isinstance(v, dict) and nested_depth >= 2:
        for cn, cv in v.items():
            cb = _builder_for(
                builders, f"{name}.{cn}", row, cap, max_fields, mirror
            )
            if cb is not None:
                cb.put(row, cv)  # dicts/lists classify OTHER (exact fallback)


def _builder_for(builders, path, row, cap, max_fields, mirror):
    b = builders.get(path)
    if b is None:
        if len(builders) >= max_fields:
            mirror.overflow = True
            return None
        b = builders[path] = _ColBuilder(cap, row)
    return b


# ------------------------------------------------------------------ shared mask
def serveable_mirror(ctx, tb: str) -> Optional[ColumnMirror]:
    """`tb`'s mirror as THIS reader may see it, or None (stale inside its
    rebuild debounce, empty, written by the reader's own transaction)."""
    ns, db = ctx.ns_db()
    registry = getattr(ctx.ds(), "column_mirrors", None)
    if registry is None:
        return None
    mirror = registry.serveable(ctx, (ns, db, tb))
    return mirror if mirror is not None and mirror.n else None


def columnar_mask(ctx, tb: str, compiled: CompiledPredicate, mirror: Optional[ColumnMirror] = None):
    """Evaluate a compiled predicate over `tb`'s mirror for THIS reader
    (`mirror`: what `serveable_mirror` gave the caller, else looked up).
    Returns (mask, needs_row, mirror) or None when the mirror can't serve
    (stale, too small, unresolvable paths, txn writes...)."""
    if mirror is None:
        mirror = serveable_mirror(ctx, tb)
    if mirror is None:
        return None
    cols = mirror.columns_for(compiled.paths)
    if cols is None:
        return None
    mask, needs_row = compiled.evaluate(cols)
    return mask, needs_row, mirror


# ------------------------------------------------------------------ plan
class ColumnScanPlan:
    """Planner-selected vectorized table scan: one mask evaluation, then
    surviving rows stream out in key order, docs fetched per block. The
    iterator skips re-evaluating the WHERE (`cond_satisfied`) — rows the
    mask algebra can't judge are re-checked here, per row, before yielding,
    so output is always identical to the row path.

    With `order_specs` (the planner lowered the statement's ORDER BY onto
    mirror columns) survivors stream in the statement's ORDER instead of
    key order and the plan advertises `provides_order`: the iterator's
    LIMIT fast path then stops pulling after start+limit rows (late
    materialization — only the top rows' documents decode) and the
    postprocess skips the re-sort. If the mirror cannot serve, the promised
    order is unkeepable — OrderPushdownBailout re-runs the statement on the
    plain scan + post-sort path."""

    cond_satisfied = True

    def __init__(self, tb: str, stm, compiled: Optional[CompiledPredicate],
                 order_specs=None):
        self.tb = tb
        self.stm = stm
        self.compiled = compiled
        self.order_specs = order_specs or None
        self.provides_order = bool(order_specs)

    def explain(self) -> dict:
        out: Dict[str, Any] = {"table": self.tb}
        if self.order_specs:
            out["strategy"] = "columnar-pipeline"
            out["stages"] = ["mask", "sort", "materialize"]
            out["order"] = [
                {"key": s.path, "direction": "ASC" if s.asc else "DESC"}
                for s in self.order_specs
            ]
        else:
            out["strategy"] = "columnar-scan"
        if self.compiled is not None:
            out["predicate"] = self.compiled.source
        return out

    def iterate(self, ctx):
        from surrealdb_tpu import telemetry

        with telemetry.span("scan_columnar", table=self.tb):
            res = self._mask(ctx)
        if res is None:
            if self.order_specs:
                # the promised ORDER cannot be produced — re-plan row path
                from surrealdb_tpu.idx.planner import OrderPushdownBailout

                raise OrderPushdownBailout()
            telemetry.inc("scan_strategy", strategy="row_fallback")
            yield from self._row_scan(ctx)
            return
        mask, needs_row, mirror = res
        telemetry.inc("scan_strategy", strategy="columnar")
        # the mask evaluation examined every mirrored row — tally the same
        # rows_scanned the row path's chunked scan_table would have
        from surrealdb_tpu import accounting

        accounting.tally(rows_scanned=float(mask.size))
        n_fb = int(needs_row.sum())
        if n_fb:
            telemetry.observe_hist(
                "columnar_fallback_rows", n_fb, buckets=telemetry.COUNT_BUCKETS
            )
        ns, db = ctx.ns_db()
        txn = ctx.txn()
        ids = mirror.ids
        want = mask | needs_row
        order = mirror.key_order()
        if order is None:
            cand = np.nonzero(want)[0]
        else:
            # delta-appended rows sit past the key-ordered prefix: stream
            # survivors in record-key order so output matches the row path
            cand = order[want[order]]
        t_sort = _time.perf_counter()
        doc_cache: dict = {}
        if self.order_specs:
            from surrealdb_tpu.ops.pipeline import order_permutation

            cand = order_permutation(
                ctx, self.tb, mirror, cand, self.order_specs, doc_cache,
                value_mode=getattr(self.stm, "value_mode", False),
            )
            if cand is None:
                from surrealdb_tpu.idx.planner import OrderPushdownBailout

                raise OrderPushdownBailout()
        note = {
            "table": self.tb,
            "plan": "ColumnScanPlan",
            "strategy": "columnar-pipeline" if self.order_specs else "columnar-scan",
            "stages": {
                "mask": {"rows": int(cand.size)},
            },
        }
        if self.order_specs:
            note["stages"]["sort"] = {
                "rows": int(cand.size),
                "keys": [s.path for s in self.order_specs],
                "ms": round((_time.perf_counter() - t_sort) * 1e3, 3),
            }
        block = max(cnf.COLUMN_BLOCK_SIZE, 1)
        from surrealdb_tpu.sql.value import truthy

        cond = self.stm.cond
        yielded = 0
        t_mat = _time.perf_counter()
        try:
            for lo in range(0, cand.size, block):
                ctx.check_deadline()
                for i in cand[lo : lo + block]:
                    i = int(i)
                    rid = Thing(self.tb, ids[i])
                    doc = doc_cache.get(i)
                    if doc is None:
                        doc = txn.get_record(ns, db, self.tb, ids[i])
                    if doc is None:
                        continue
                    if needs_row[i]:
                        # mixed-type row: the mask abstained — row-path check
                        with ctx.with_doc_value(doc, rid=rid) as c:
                            if not truthy(cond.compute(c)):
                                continue
                    yielded += 1
                    yield rid, doc, None
        finally:
            note["stages"]["materialize"] = {
                "rows": yielded,
                "ms": round((_time.perf_counter() - t_mat) * 1e3, 3),
            }
            telemetry.note_plan(note)

    def _mask(self, ctx):
        """(mask, needs_row, mirror) — the cond-less variant serves an
        all-true mask so ORDER BY+LIMIT pushdown works without a WHERE."""
        if self.compiled is not None:
            return columnar_mask(ctx, self.tb, self.compiled)
        ns, db = ctx.ns_db()
        registry = getattr(ctx.ds(), "column_mirrors", None)
        if registry is None:
            return None
        mirror = registry.serveable(ctx, (ns, db, self.tb))
        if mirror is None or mirror.n == 0:
            return None
        ones = np.ones(mirror.n, dtype=bool)
        return ones, np.zeros(mirror.n, dtype=bool), mirror

    def _row_scan(self, ctx):
        """Exact row-path twin (mirror unavailable): scan + per-row WHERE,
        here because the iterator was told the cond is already satisfied."""
        from surrealdb_tpu.dbs.iterator import scan_table
        from surrealdb_tpu.sql.value import truthy

        cond = self.stm.cond
        for rid, doc in scan_table(ctx, self.tb):
            if cond is not None:
                with ctx.with_doc_value(doc, rid=rid) as c:
                    if not truthy(cond.compute(c)):
                        continue
            yield rid, doc, None


def try_columnar_count(ctx, stm, sources) -> Optional[list]:
    """`SELECT count() FROM tb WHERE ... GROUP ALL` without ever touching a
    document: the answer is the mask's popcount (plus a per-row check of the
    rows the mask abstained on). Returns None to keep the ordinary path."""
    from surrealdb_tpu.dbs.iterator import ITable
    from surrealdb_tpu.sql.ast import FunctionCall
    from surrealdb_tpu.sql.path import Idiom as _Idiom

    if len(sources) != 1 or not isinstance(sources[0], ITable):
        return None
    if not getattr(stm, "group_all", False) or getattr(stm, "group", None):
        return None
    fields = getattr(stm, "fields", None) or []
    if len(fields) != 1 or getattr(fields[0], "all", False):
        return None
    f = fields[0]
    expr = f.expr
    if not (isinstance(expr, FunctionCall) and expr.name == "count" and not expr.args):
        return None
    if f.alias is None:
        name = "count"
    elif isinstance(f.alias, _Idiom) and f.alias.simple_name() is not None:
        name = f.alias.simple_name()
    else:
        return None
    for attr in ("split", "fetch", "omit", "order", "limit", "start"):
        if getattr(stm, attr, None):
            return None
    if getattr(stm, "value_mode", False):
        return None
    plan = column_scan_plan(ctx, stm, sources[0].tb)
    if plan is None:
        return None
    tb = sources[0].tb
    from surrealdb_tpu import telemetry

    with telemetry.span("scan_columnar", table=tb):
        res = columnar_mask(ctx, tb, plan.compiled)
    if res is None:
        return None
    mask, needs_row, mirror = res
    telemetry.inc("scan_strategy", strategy="columnar_count")
    # mask popcount still examined every mirrored row (tenant meter parity
    # with the iterator path's per-chunk rows_scanned tally)
    from surrealdb_tpu import accounting

    accounting.tally(rows_scanned=float(mask.size))
    total = int((mask & ~needs_row).sum())
    fb = np.nonzero(needs_row)[0]
    if fb.size:
        from surrealdb_tpu.sql.value import truthy

        ns, db = ctx.ns_db()
        txn = ctx.txn()
        cond = stm.cond
        for i in fb:
            ctx.check_deadline()
            i = int(i)
            doc = txn.get_record(ns, db, tb, mirror.ids[i])
            if doc is None:
                continue
            with ctx.with_doc_value(doc, rid=Thing(tb, mirror.ids[i])) as c:
                if truthy(cond.compute(c)):
                    total += 1
    if total == 0:
        return []  # GROUP ALL over zero rows yields no group (row path)
    return [{name: total}]


def column_scan_plan(ctx, stm, tb: str):
    """Planner hook: a ColumnScanPlan when the WHERE lowers onto columns and
    the table is big enough to pay for mirroring; None keeps the row path.
    When the statement's ORDER BY also lowers (plain multi-key paths with
    no grouping/splitting), the plan sorts survivors columnar and
    advertises `provides_order` — the iterator's LIMIT fast path then
    composes with the pushed sort instead of re-sorting (ISSUE 13)."""
    if not cnf.COLUMN_MIRROR:
        return None
    cond = getattr(stm, "cond", None)
    from surrealdb_tpu.iam.check import perms_apply

    if perms_apply(ctx):
        return None  # per-record PERMISSIONS must see every document
    compiled = None
    if cond is not None:
        from surrealdb_tpu.ops.predicates import compile_where

        compiled = compile_where(ctx, cond)
        if compiled is None:
            return None
    order_specs = None
    if (
        getattr(stm, "order", None)
        and not getattr(stm, "group", None)
        and not getattr(stm, "group_all", False)
        and not getattr(stm, "split", None)
    ):
        from surrealdb_tpu.ops.pipeline import resolve_order_specs

        specs = resolve_order_specs(stm)
        if specs:
            order_specs = specs
    if compiled is None and not order_specs:
        return None  # nothing lowers: keep the plain scan
    registry = getattr(ctx.ds(), "column_mirrors", None)
    if registry is None:
        return None
    from surrealdb_tpu.ops.pipeline import mirror_floor_ok

    if not mirror_floor_ok(ctx, registry, tb):
        return None
    if order_specs:
        from surrealdb_tpu import telemetry

        telemetry.inc("column_pipeline", outcome="order_planned")
    return ColumnScanPlan(tb, stm, compiled, order_specs)
