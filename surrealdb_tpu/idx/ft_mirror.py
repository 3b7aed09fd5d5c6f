"""Full-text mirror: CSR postings on the host and in HBM, and the search
routes over them.

Role of the reference's per-query posting B-tree walks (reference:
core/src/idx/ft/postings.rs, termdocs.rs, scorer.rs:13-92) re-designed
TPU-first, the same way idx/knn.py mirrors vectors and idx/graph_csr.py
mirrors edges: the inverted index's postings are packed into CSR arrays
(term -> sorted doc ids + term frequencies) kept in sync with committed
writes.

What lives where:

- On the HOST: the term dictionary (`term_ids`), the doc id -> record id
  chunks, the base postings as three flat arrays sorted by (term, doc id),
  the overlay of single-document changes, and each generation's CSR
  (`indptr`, doc ids, tfs, document lengths) for the host route and for
  cluster mode's statistics. A term look-up is host work by nature.
- On the DEVICE, once a generation (`_Generation.device`): the posting doc
  ids (int32), the posting tfs (uint8 / uint16 / int32, the narrowest that
  holds the largest tf), each posting's document length (f32, so that a
  sparse step reads lengths as a slice and gathers nothing), the document
  lengths (f32) over the doc slots, and the head: the most frequent terms (those whose list outgrows the
  ladder's first step, at most HEAD_ROWS_MAX of them) again as dense tf
  rows over the doc slots. Arrays are padded by utils/num.py::path_slots,
  so growth changes a compiled shape every 6-12% and not every commit.

A generation is one compaction: immutable, built under the mirror's lock
when a search finds the mirror dirty (so the next search after a commit
sees it), then read by any number of searches OUTSIDE the lock. The
device route (`search_device`) is one submit to the datastore's dispatch
queue: riders of one generation, one ladder step, one term-slot count and
one k share a launch of ops/bm25.py::bm25_and_topk. The host route
(`search`) is the NumPy intersection with the float64 scorer, exact, for
statements the device route does not take.

The mirror's base state is the bulk ingest's packed chunks
(idx/ft_index.py P/L/R keys) decoded wholesale: every chunk's bytes are
joined and read as ONE word array, no per-term or per-chunk array is made.
Single-document changes land in one overlay dict (tf 0 = tombstone) and are
folded into the base at the next compaction.

The KV inverted index stays authoritative/durable; this is the compute
replica (reference analog: TreeCache generation swap,
trees/store/cache.rs, improved to incremental deltas, VERDICT r1 item 4).
"""

from __future__ import annotations

import bisect
import contextvars
import itertools
import time as _time
from surrealdb_tpu.utils import locks as _locks
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from surrealdb_tpu import compile_log, key as keys, telemetry, tracing
from surrealdb_tpu.key.encode import dec_u64, prefix_end
from surrealdb_tpu.sql.value import Thing
from surrealdb_tpu.utils.num import next_pow2, path_slots
from surrealdb_tpu.utils.ser import unpack
from surrealdb_tpu.idx.ft_index import rid_chunk_get, unpack_lens, unpack_posting

HEAD_ROWS_MAX = 2048  # dense rows of the head, at most
HEAD_BYTES_MAX = 2 << 30  # and no more of HBM than this
_GEN_SERIAL = itertools.count(1)
_INT32_MAX = np.iinfo(np.int32).max
_EMPTY = (np.empty(0, np.int64), np.empty(0, np.float32))


def routed(route: str, t_iter: float, terms: int, slots: int = 0) -> None:
    """One `@@` statement served by `route` (`device`, `host` or `kv`): the
    `ft_search_route` counter and the labels of the statement's `ft_prepare`
    span come from this one argument (the pattern of graph_csr._served).
    The span runs from MatchesPlan.iterate's entry to here: the dispatch
    submit of a `device` statement (`slots`: its ladder step), the start of
    the search of the other two."""
    telemetry.inc("ft_search_route", route=route)
    tracing.record_span_into(
        tracing.current(), "ft_prepare", {"route": route, "terms": terms, "slots": slots},
        t_iter, _time.perf_counter() - t_iter,
    )


def _tf_dtype(largest: int):
    return np.uint8 if largest <= 0xFF else np.uint16 if largest <= 0xFFFF else np.int32


def _pad(arr: np.ndarray, size: int, fill=0) -> np.ndarray:
    out = np.full(size, fill, dtype=arr.dtype)
    out[: arr.size] = arr
    return out


class _Generation:
    """One compaction of a mirror: the CSR on the host, what the device
    route needs to place a rider on the ladder, and (made on first need,
    once) the device arrays."""

    def __init__(self, indptr, dids, tfs, doclen, dc: int, tl: float):
        from surrealdb_tpu.ops.bm25 import SLOTS_MIN, sparse_steps

        self.serial = next(_GEN_SERIAL)
        self.tf_dtype = _tf_dtype(int(tfs.max(initial=0)))
        self.indptr, self.dids, self.tfs, self.doclen = indptr, dids, tfs.astype(self.tf_dtype), doclen
        self.dc, self.tl = dc, tl
        self.n_terms = len(indptr) - 1
        df = np.diff(indptr)
        self.d_slots = max(path_slots(len(doclen)), 2 * SLOTS_MIN)  # above the first sparse step
        # the head: the terms whose list outgrows the first step, most
        # frequent first, as many as the row and byte caps allow
        fit = HEAD_BYTES_MAX // (self.d_slots * np.dtype(self.tf_dtype).itemsize)
        cand = np.flatnonzero(df > SLOTS_MIN)
        cand = cand[np.argsort(-df[cand], kind="stable")][: min(HEAD_ROWS_MAX, fit)]
        self.head_tids = cand
        self.head_rows = max(next_pow2(cand.size), 8)  # the head array's rows: a compiled shape
        self.head_row = np.full(self.n_terms, -1, dtype=np.int32)
        self.head_row[cand] = np.arange(cand.size, dtype=np.int32)
        outside = df[self.head_row < 0]
        self.steps = sparse_steps(int(outside.max(initial=0)))
        self.p_slots = max(path_slots(dids.size), self.steps[-1])
        self.device_ok = len(doclen) <= _INT32_MAX and self.steps[-1] < self.d_slots
        self._dev = None
        self._dev_lock = _locks.Lock("idx.ft.upload")
        self._warmed: set = set()

    def shape_key(self, slots: int, tn: int, riders: int, kk: int) -> tuple:
        """Compile-cache key of one program: the ladder's coordinates and
        the operand shapes XLA keys on."""
        return (slots, tn, riders, kk, self.p_slots, self.d_slots, self.head_rows, np.dtype(self.tf_dtype).name)

    def device(self):
        """(dids, tfs, posting-aligned lengths, doclen, head) on the device, uploaded once."""
        with self._dev_lock:
            if self._dev is None:
                import jax.numpy as jnp

                t0 = _time.perf_counter()
                head = np.zeros((self.head_rows, self.d_slots), dtype=self.tf_dtype)
                for r, t in enumerate(self.head_tids.tolist()):
                    s, e = self.indptr[t], self.indptr[t + 1]
                    head[r, self.dids[s:e]] = self.tfs[s:e]
                arrays = (
                    _pad(self.dids.astype(np.int32, copy=False), self.p_slots, _INT32_MAX),
                    _pad(self.tfs, self.p_slots),
                    _pad(self.doclen[self.dids], self.p_slots),
                    _pad(self.doclen, self.d_slots),
                    head,
                )
                self._dev = tuple(jnp.asarray(a) for a in arrays)
                dt = _time.perf_counter() - t0
                telemetry.observe("ft_mirror_upload", dt)
                telemetry.stage("ft_mirror_upload", t0, dt, bytes=int(sum(a.nbytes for a in arrays)))
            return self._dev


class FtMirror:
    """One search index's postings: sorted base arrays + overlay dict,
    compacted into a _Generation when a search finds them dirty (pattern of
    idx/graph_csr.py)."""

    def __init__(self):
        self.built = False
        self.term_ids: Dict[str, int] = {}  # term -> local tid
        # base postings sorted by (tid, did); segments appended since (bulk
        # batches), each (tid, did, tf) arrays; overlay {tid << 32 | did: tf}
        self._base = (np.empty(0, np.int64), np.empty(0, np.int64), np.empty(0, np.int64))
        self._segs: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self.overlay: Dict[int, int] = {}
        # doc lengths: [(start, lens f32)] + overlay {did: len} (-1 = absent)
        self.len_chunks: List[Tuple[int, np.ndarray]] = []
        self.len_overlay: Dict[int, float] = {}
        # did -> rid: [(start, rid list)] + overlay {did: rid | None}
        self.rid_chunks: List[Tuple[int, list]] = []
        self.rid_overlay: Dict[int, Optional[Thing]] = {}
        self._chunk_starts: set = set()  # bulk idempotence guard
        self.next_did = 0
        self.dc = 0
        self.tl = 0.0
        self.dirty = True
        self._gen: Optional[_Generation] = None
        self._pending: Optional[List[tuple]] = None
        # filtered-stats cache (replicated clusters): the responsibility
        # mask depends only on (generation, liveness view), so one
        # O(corpus) rid/ring walk serves every BM25 query until a mutation
        # makes a new generation or the live set changes
        self._stats_mask: Optional[Tuple[tuple, np.ndarray]] = None
        self._lock = _locks.RLock("idx.ft.state")
        self._build_lock = _locks.Lock("idx.ft.build")

    # ------------------------------------------------------------ build
    def ensure_built(self, ctx, ix: dict) -> None:
        """One scan over the index's KV state builds the mirror. Runs on a
        fresh snapshot opened after delta buffering starts (same protocol as
        idx/knn.py VectorMirror.ensure_built)."""
        if self.built:
            return
        with self._build_lock:
            if self.built:
                return
            with self._lock:
                self._pending = []
            t0 = _time.perf_counter()
            ns, db = ctx.ns_db()
            tb, name = ix["table"], ix["name"]
            txn = ctx.ds().transaction(False)
            try:
                base = keys.index_state(ns, db, tb, name, b"")
                st_raw = txn.get(base + b"s")
                st = unpack(st_raw) if st_raw else {"dc": 0, "tl": 0, "nt": 0, "nd": 0}
                # terms: t{term} -> {id, df}; local tids in the order of the
                # KV's own, so the packed chunks below arrive sorted
                pre = base + b"t"
                found = []
                for chunk in txn.batch(pre, prefix_end(pre), 4096):
                    for k, v in chunk:
                        meta = unpack(v)
                        if meta.get("df", 0) > 0:
                            found.append((meta["id"], self._dec_term(k, len(pre))))
                found.sort()
                term_ids = {term: i for i, (_, term) in enumerate(found)}
                local_of = np.full(max(int(st["nt"]), found[-1][0] + 1 if found else 0), -1, dtype=np.int64)
                local_of[[kv for kv, _ in found]] = np.arange(len(found))
                # packed posting chunks: P{tid}{start}, all decoded at once
                pre = base + b"P"
                tails, vals = [], []
                for batch in txn.batch(pre, prefix_end(pre), 8192):
                    for k, v in batch:
                        tails.append(k[len(pre) : len(pre) + 8])
                        vals.append(v)
                postings = _decode_chunks(tails, vals, local_of)
                # posting overlay: p{tid}{did}
                overlay: Dict[int, int] = {}
                pre = base + b"p"
                for batch in txn.batch(pre, prefix_end(pre), 8192):
                    for k, v in batch:
                        tid, off = dec_u64(k, len(pre))
                        did, _ = dec_u64(k, off)
                        local = local_of[tid] if tid < local_of.size else -1
                        if local >= 0:
                            overlay[int(local) << 32 | did] = int(unpack_posting(v)["tf"])
                # doc lengths
                len_chunks: List[Tuple[int, np.ndarray]] = []
                pre = base + b"L"
                for batch in txn.batch(pre, prefix_end(pre), 1024):
                    for k, v in batch:
                        start, _ = dec_u64(k, len(pre))
                        len_chunks.append((start, unpack_lens(v)))
                len_overlay: Dict[int, float] = {}
                pre = base + b"l"
                for batch in txn.batch(pre, prefix_end(pre), 8192):
                    for k, v in batch:
                        did, _ = dec_u64(k, len(pre))
                        len_overlay[did] = float(unpack(v))
                # rid chunks stay raw bytes until a result lands in them
                # (rid_for decodes on demand: searches touch few chunks)
                rid_chunks: List[Tuple[int, Any]] = []
                pre = base + b"R"
                for batch in txn.batch(pre, prefix_end(pre), 256):
                    for k, v in batch:
                        start, _ = dec_u64(k, len(pre))
                        rid_chunks.append((start, v))
                rid_overlay: Dict[int, Optional[Thing]] = {}
                pre = base + b"r"
                for batch in txn.batch(pre, prefix_end(pre), 8192):
                    for k, v in batch:
                        did, _ = dec_u64(k, len(pre))
                        rid_overlay[did] = unpack(v)
            finally:
                txn.cancel()
            len_chunks.sort(key=lambda c: c[0])
            rid_chunks.sort(key=lambda c: c[0])
            dt = _time.perf_counter() - t0
            telemetry.observe("ft_mirror_scan", dt)
            telemetry.stage("ft_mirror_scan", t0, dt, terms=len(term_ids), postings=int(postings[0].size))
            with self._lock:
                self.term_ids = term_ids
                self._base, self._segs = (postings[0][:0],) * 3, [postings]
                self.overlay = overlay
                self.len_chunks = len_chunks
                self.len_overlay = len_overlay
                self.rid_chunks = rid_chunks
                self.rid_overlay = rid_overlay
                self._chunk_starts = {s for s, _ in len_chunks}  # every bulk batch wrote one
                self.next_did = st["nd"]
                self.dc = st["dc"]
                self.tl = float(st["tl"])
                self.dirty = True
                self.built = True
                pending, self._pending = self._pending, None
                for tag, args in pending:
                    if tag == "doc":
                        self.apply_ft(*args)
                    else:
                        self.apply_ft_bulk(*args)

    @staticmethod
    def _dec_term(k: bytes, off: int) -> str:
        from surrealdb_tpu.key.encode import dec_str

        return dec_str(k, off)[0]

    # ------------------------------------------------------------ deltas
    def _tid_for(self, term: str) -> int:
        tid = self.term_ids.get(term)
        if tid is None:
            tid = self.term_ids[term] = len(self.term_ids)
        return tid

    def _len_of(self, did: int) -> Optional[float]:
        """Current doc length, or None when the doc is not indexed. The
        overlay stores -1.0 as its removal tombstone so a present zero-token
        doc (length 0) stays distinguishable from an absent one: dc/tl
        accounting depends on that distinction."""
        v = self.len_overlay.get(did)
        if v is not None:
            return None if v < 0 else v
        i = bisect.bisect_right(self.len_chunks, did, key=lambda c: c[0]) - 1
        if i >= 0:
            start, lens = self.len_chunks[i]
            off = did - start
            if 0 <= off < len(lens):
                return float(lens[off])
        return None

    def apply_ft(
        self,
        rid,
        did: int,
        old_tf: Optional[Dict[str, int]],
        new_tf: Optional[Dict[str, int]],
        new_len: int,
    ) -> None:
        """One committed document change. old/new term-frequency maps follow
        idx/ft_index.py index_document's diff semantics; None = absent."""
        with self._lock:
            if self._pending is not None:
                self._pending.append(("doc", (rid, did, old_tf, new_tf, new_len)))
                return
            if not self.built:
                return
            if old_tf is not None:
                for term in old_tf:
                    tid = self.term_ids.get(term)
                    if tid is not None:
                        self.overlay[tid << 32 | did] = 0
                prev = self._len_of(did)
                if prev is not None:
                    self.tl -= prev
                    self.dc -= 1
                self.len_overlay[did] = -1.0
            if new_tf is not None:
                # idempotence (the build-window replay protocol relies on
                # it): a delta whose doc the build scan already loaded must
                # not double-count dc/tl
                prev = self._len_of(did)
                if prev is not None:
                    self.tl -= prev
                    self.dc -= 1
                for term, tf in new_tf.items():
                    self.overlay[self._tid_for(term) << 32 | did] = int(tf)
                self.len_overlay[did] = float(new_len)
                self.rid_overlay[did] = rid
                self.dc += 1
                self.tl += new_len
                if did >= self.next_did:
                    self.next_did = did + 1
            elif old_tf is not None:
                self.rid_overlay[did] = None
            self.dirty = True

    def apply_ft_bulk(self, start: int, terms: Dict[str, tuple], lens, rids) -> None:
        """One committed bulk batch: its postings become one more segment
        of the base (three concatenates, no per-doc work)."""
        with self._lock:
            if self._pending is not None:
                self._pending.append(("bulk", (start, terms, lens, rids)))
                return
            if not self.built:
                return
            if start in self._chunk_starts:
                return  # the build scan already loaded this batch
            self._chunk_starts.add(start)
            if terms:
                tids = np.fromiter((self._tid_for(t) for t in terms), np.int64, count=len(terms))
                sizes = np.fromiter((len(d) for d, _ in terms.values()), np.int64, count=len(terms))
                self._segs.append((
                    np.repeat(tids, sizes),
                    np.concatenate([np.asarray(d, dtype=np.int64) for d, _ in terms.values()]),
                    np.concatenate([np.asarray(f).astype(np.int64) for _, f in terms.values()]),
                ))
            lens = np.asarray(lens, dtype=np.float32)
            self.len_chunks.append((start, lens))
            self.rid_chunks.append((start, list(rids)))
            self.dc += len(lens)
            self.tl += float(lens.sum())
            if start + len(lens) > self.next_did:
                self.next_did = start + len(lens)
            self.dirty = True

    # ------------------------------------------------------------ rid map
    def rid_for(self, did: int) -> Optional[Thing]:
        with self._lock:
            if did in self.rid_overlay:
                return self.rid_overlay[did]
            i = bisect.bisect_right(self.rid_chunks, did, key=lambda c: c[0]) - 1
            if i >= 0:
                start, rids = self.rid_chunks[i]
                if isinstance(rids, bytes):
                    rids = unpack(rids)  # columnar dict or generic list
                    self.rid_chunks[i] = (start, rids)
                return rid_chunk_get(rids, did - start)
            return None

    # ------------------------------------------------------------ compaction
    def generation(self) -> _Generation:
        """The current generation, compacted first where a commit has
        landed since the last: the mirror's lock is held for that and for
        no search."""
        with self._lock:
            if self.dirty or self._gen is None:
                self._gen = self._compact()
                self.dirty = False
            return self._gen

    def _compact(self) -> _Generation:
        """Segments and overlay folded into the base, whole arrays at a
        time: one concatenate, one stable sort by (term, doc id) where the
        arrival order was not already that, the overlay's entries replacing
        or deleting by one searchsorted."""
        t0 = _time.perf_counter()
        tid, did, tf = (np.concatenate([a] + [s[i] for s in self._segs]) for i, a in enumerate(self._base))
        key = tid << 32 | did
        if key.size and not (key[1:] > key[:-1]).all():
            order = np.argsort(key, kind="stable")
            key, tf = key[order], tf[order]
        if self.overlay:
            n = len(self.overlay)
            ov_key = np.fromiter(self.overlay.keys(), np.int64, count=n)
            ov_tf = np.fromiter(self.overlay.values(), np.int64, count=n)
            keep = np.ones(key.size, dtype=bool)
            if key.size:
                pos = np.minimum(np.searchsorted(key, ov_key), key.size - 1)
                keep[pos[key[pos] == ov_key]] = False
            live = ov_tf > 0
            key = np.concatenate([key[keep], ov_key[live]])
            tf = np.concatenate([tf[keep], ov_tf[live]])
            order = np.argsort(key, kind="stable")
            key, tf = key[order], tf[order]
            self.overlay = {}
        tid, did = key >> 32, key & 0xFFFFFFFF
        self._base, self._segs = (tid, did, tf), []
        terms = len(self.term_ids)
        indptr = np.zeros(terms + 1, dtype=np.int64)
        np.cumsum(np.bincount(tid, minlength=terms), out=indptr[1:])
        cap = max(self.next_did, 1)
        dl = np.zeros(cap, dtype=np.float32)
        for start, lens in self.len_chunks:
            dl[start : start + len(lens)] = lens
        if self.len_overlay:
            idx = np.fromiter(self.len_overlay.keys(), np.int64, count=len(self.len_overlay))
            val = np.fromiter(self.len_overlay.values(), np.float32, count=len(self.len_overlay))
            ok = idx < cap
            dl[idx[ok]] = np.maximum(val[ok], 0.0)  # -1 tombstone scores as 0
        dids = did.astype(np.int32) if cap <= _INT32_MAX else did
        gen = _Generation(indptr, dids, tf, dl, int(self.dc), float(self.tl))
        dt = _time.perf_counter() - t0
        telemetry.observe("ft_mirror_compact", dt)
        telemetry.stage("ft_mirror_compact", t0, dt, terms=terms, postings=int(key.size))
        return gen

    # ------------------------------------------------------------ search
    def term_stats(self, terms: List[str], doc_ok=None, filter_key=None):
        """Local corpus statistics for a term set: (doc count, total doc
        length, {term: document frequency}), phase one of the cluster's
        two-phase BM25 (cluster/rpc.py ft_stats). Unknown terms report 0.

        `doc_ok(rid) -> bool` restricts the stats to a responsibility
        subset (replicated clusters: each node reports only the docs it is
        the first live replica of, so a doc counts once globally); pass a
        hashable `filter_key` describing what doc_ok depends on (live-node
        set + rf) and the O(corpus) mask is cached until a new generation
        or the key changes. The filtered path counts live docs from the
        length array, so a zero-length doc is excluded: empty bodies carry
        no BM25 mass."""
        gen = self.generation()
        tids = {t: self._tid_in(gen, t) for t in dict.fromkeys(terms)}
        if doc_ok is None:
            return gen.dc, gen.tl, {
                t: 0 if tid is None else int(gen.indptr[tid + 1] - gen.indptr[tid])
                for t, tid in tids.items()
            }
        cache_key = (gen.serial, filter_key) if filter_key is not None else None
        with self._lock:
            cached = self._stats_mask
        if cached is not None and cached[0] == cache_key:
            mask = cached[1]
        else:
            mask = np.zeros(len(gen.doclen), dtype=bool)
            for did in np.nonzero(gen.doclen > 0)[0]:
                rid = self.rid_for(int(did))
                if rid is not None and doc_ok(rid):
                    mask[did] = True
            if cache_key is not None:
                with self._lock:
                    self._stats_mask = (cache_key, mask)
        df = {
            t: 0 if tid is None else int(np.count_nonzero(mask[gen.dids[gen.indptr[tid] : gen.indptr[tid + 1]]]))
            for t, tid in tids.items()
        }
        return int(np.count_nonzero(mask)), float(gen.doclen[mask].sum()), df

    def _tid_in(self, gen: _Generation, term: str) -> Optional[int]:
        """The term's tid if `gen` holds postings of it."""
        tid = self.term_ids.get(term)
        if tid is None or tid >= gen.n_terms or gen.indptr[tid + 1] == gen.indptr[tid]:
            return None
        return tid

    def _resolve(self, gen: _Generation, terms: List[str], stats_override):
        """The query's distinct terms as tids, rarest first (a term outside
        the head before one inside it at equal length), with the df and the
        corpus statistics BM25 scores with; None when a term has no
        postings. `stats_override` ({dc, tl, df: {term: n}}) swaps the
        statistics: the cluster executor passes the merged GLOBAL ones so
        every shard scores exactly as one single-node corpus would."""
        uniq = list(dict.fromkeys(terms))
        tids = [self._tid_in(gen, t) for t in uniq]
        if not uniq or None in tids:
            return None
        size = lambda t: int(gen.indptr[t + 1] - gen.indptr[t])  # noqa: E731
        order = sorted(range(len(tids)), key=lambda i: (size(tids[i]), gen.head_row[tids[i]] >= 0))
        tids = [tids[i] for i in order]
        df = [float(size(t)) for t in tids]
        dc, tl = float(gen.dc), gen.tl
        if isinstance(stats_override, dict):
            odf = stats_override.get("df") or {}
            df = [float(odf.get(uniq[i], d)) for i, d in zip(order, df)]
            dc = float(stats_override.get("dc", dc))
            tl = float(stats_override.get("tl", tl))
        return tids, np.asarray(df), dc, tl

    def search(self, terms: List[str], k1: float, b: float, stats_override=None):
        """The host route: AND-match the analyzed query terms over the
        generation's CSR with NumPy, score in float64; (dids, scores) of
        every match in doc-id order, empty when a term is unknown."""
        from surrealdb_tpu.ops.bm25 import bm25_scores_host

        gen = self.generation()
        got = self._resolve(gen, terms, stats_override)
        if got is None:
            return _EMPTY
        tids, df, dc, tl = got
        rows = [
            (gen.dids[gen.indptr[t] : gen.indptr[t + 1]], gen.tfs[gen.indptr[t] : gen.indptr[t + 1]])
            for t in tids
        ]
        cand = rows[0][0]
        tf_cols = [rows[0][1]]
        for dids, tfs in rows[1:]:
            pos = np.clip(np.searchsorted(dids, cand), 0, len(dids) - 1)
            mask = dids[pos] == cand
            cand = cand[mask]
            tf_cols = [c[mask] for c in tf_cols]
            tf_cols.append(tfs[pos[mask]])
            if cand.size == 0:
                return _EMPTY
        scores = bm25_scores_host(np.stack(tf_cols, axis=1), df, gen.doclen[cand], dc, tl, k1, b)
        return cand.astype(np.int64), scores

    def place(self, gen: _Generation, terms: List[str], k: int, stats_override=None):
        """Where a query rides: (ladder step, term slots, k slots, distinct
        terms, payload, what _resolve found), or None when a term has no
        postings. The step is the smallest that holds the query's longest
        list outside the head; the dense step (the doc slots) when every
        term is in the head. The payload is the rider's row of every
        per-rider operand of ops/bm25.py::bm25_and_topk."""
        from surrealdb_tpu.ops.bm25 import idf_of, k_slots, term_slots

        got = self._resolve(gen, terms, stats_override)
        if got is None:
            return None
        tids, df, dc, tl = got
        tn = term_slots(len(tids))
        rows = gen.head_row[tids]
        lens = gen.indptr[np.asarray(tids) + 1] - gen.indptr[tids]
        outside = lens[rows < 0]
        slots = gen.d_slots if outside.size == 0 else next(s for s in gen.steps if s >= outside.max())
        payload = (
            _pad(gen.indptr[tids].astype(np.int32), tn), _pad(lens.astype(np.int32), tn),
            _pad(rows, tn, -1), _pad(idf_of(dc, df).astype(np.float32), tn),
            len(tids), max(tl / max(dc, 1.0), 1e-6),
        )
        return slots, tn, k_slots(k), len(tids), payload, got

    def search_device(self, ds, terms: List[str], k: int, k1: float, b: float, stats_override, t_iter: float):
        """The device route: the k best matches of the AND of `terms` as
        (dids, scores, matched), best first, from ONE submit to the
        dispatch queue; the rider shares a launch with the riders of the
        same generation, step, term slots and k slots. Closes the
        statement's `ft_prepare` span at the submit. The device matches,
        scores in float32 and picks the k; the scores handed back are the
        host scorer's for those k documents (_exact_scores), so that
        `search::score()` reads the same whichever route served, bit for
        bit. None where this generation cannot ride (doc ids past int32):
        the caller takes the host route."""
        gen = self.generation()
        if not gen.device_ok:
            return None
        placed = self.place(gen, terms, k, stats_override)
        if placed is None:
            routed("device", t_iter, len(set(terms)))
            return np.empty(0, np.int64), np.empty(0, np.float32), 0
        slots, tn, kk, n, payload, found = placed
        dev = gen.device()  # the first statement of a generation pays the upload, and says so in its trace
        self._warm(ds, gen, dev, kk)
        routed("device", t_iter, n, slots)
        _, dids, matched = ds.dispatch.submit(
            ("ft", gen.serial, slots, tn, kk), payload, _runner(gen, dev, slots, tn, kk, k1, b))
        dids = dids[: min(k, matched)].astype(np.int64)
        scores = _exact_scores(gen, *found, dids, k1, b)
        order = np.lexsort((dids, -scores))  # as the host route's stable sort over doc-id order has them
        return dids[order], scores[order], matched

    def _warm(self, ds, gen: _Generation, dev: tuple, kk: int) -> None:
        """Compile, in one background task a (ladder step, term slots),
        every program of `gen`'s ladder at `kk` k slots (each sparse step
        and, where there is a head, the dense step; each term-slot count; 1
        rider and a tile), once: so that no statement after the first
        compiles. Empty lanes are harmless."""
        from surrealdb_tpu import bg
        from surrealdb_tpu.ops.bm25 import RIDER_TILE, TERM_SLOTS

        with self._lock:
            if kk in gen._warmed:
                return
            gen._warmed.add(kk)

        def warm(slots: int, tn: int):
            for riders in (1, RIDER_TILE):
                try:
                    _launch(gen, dev, slots, tn, kk, 1.2, 0.75, [], riders, prewarmed=True)
                except Exception:  # noqa: BLE001 — a failed warm costs a later statement its compile
                    telemetry.inc("prewarm_errors", subsystem="bm25")

        for slots in gen.steps + ((gen.d_slots,) if gen.head_tids.size else ()):
            for tn in TERM_SLOTS:
                # under the arming statement's context: what a warm has to say
                # (a wait behind a statement's own compile) lands in that trace
                bg.spawn("ft_warm", f"gen{gen.serial}.k{kk}.s{slots}.t{tn}", contextvars.copy_context().run,
                         warm, slots, tn, owner=id(ds))

    def count(self) -> int:
        with self._lock:
            return self.dc


def _exact_scores(gen: _Generation, tids, df, dc, tl, dids: np.ndarray, k1: float, b: float) -> np.ndarray:
    """The host scorer (float64 arithmetic, handed back as float32) over the
    few documents `dids`, every one of which holds every term of `tids`."""
    from surrealdb_tpu.ops.bm25 import bm25_scores_host

    tf = np.empty((dids.size, len(tids)))
    for j, t in enumerate(tids):
        lst = gen.dids[gen.indptr[t] : gen.indptr[t + 1]]
        tf[:, j] = gen.tfs[gen.indptr[t] : gen.indptr[t + 1]][np.searchsorted(lst, dids)]
    return bm25_scores_host(tf, df, gen.doclen[dids], dc, tl, k1, b)


def _decode_chunks(tails: list, vals: list, local_of: np.ndarray):
    """Every packed posting chunk of the index (idx/ft_index.py
    pack_plist: u32 n, i64 base, n u32 offsets, n u32 tfs; 12 + 8n bytes,
    so the joined values are whole little-endian words) as three flat
    arrays (local tid, did, tf) in the scan's order. `tails` are the keys'
    8 bytes after the prefix, the KV's term id, big-endian; `local_of` maps
    a KV term id to its local one (-1: the term has no live document)."""
    if not vals or not local_of.size:
        return (np.empty(0, np.int64),) * 3
    kv_tid = np.frombuffer(b"".join(tails), dtype=">u8").astype(np.int64)
    words = np.frombuffer(b"".join(vals), dtype="<u4")
    nwords = np.fromiter(map(len, vals), np.int64, count=len(vals)) // 4
    w0 = np.cumsum(nwords) - nwords
    n = words[w0].astype(np.int64)
    base = words[w0 + 1].astype(np.int64) | words[w0 + 2].astype(np.int64) << 32
    local = np.where(kv_tid < local_of.size, local_of[np.minimum(kv_tid, local_of.size - 1)], -1)
    chunk = np.repeat(np.arange(n.size), n)
    at = w0[chunk] + 3 + np.arange(chunk.size) - np.repeat(np.cumsum(n) - n, n)
    tid, did, tf = local[chunk], base[chunk] + words[at], words[at + n[chunk]].astype(np.int64)
    live = tid >= 0
    if not live.all():
        tid, did, tf = tid[live], did[live], tf[live]
    return tid, did, tf


def _launch(gen: _Generation, dev: tuple, slots: int, tn: int, kk: int, k1: float, b: float, payloads,
            riders: int, prewarmed: bool = False):
    """One call of the kernel over `gen`'s device arrays `dev` for up to
    `riders` payloads (the rest of the lanes empty); the device output."""
    from surrealdb_tpu.ops.bm25 import bm25_and_topk, pack_riders

    with compile_log.tracked("bm25", gen.shape_key(slots, tn, riders, kk), prewarmed=prewarmed):
        return bm25_and_topk(*dev, pack_riders(payloads, riders, tn, k1, b), slots=slots, k=kk)


def _runner(gen: _Generation, dev: tuple, slots: int, tn: int, kk: int, k1: float, b: float):
    """The dispatch runner of one bucket (generation, step, term slots, k
    slots): the batch as one launch of 1 rider, or of tiles of RIDER_TILE.
    The `ft_postings` / `ft_slots` counters (the riders' real candidate
    postings; the padded slots the lanes swept) and the `slots` label of
    every rider's `dispatch_launch` span come from here."""
    from surrealdb_tpu.ops.bm25 import RIDER_TILE, unpack_results

    def run(payloads):
        tile = 1 if len(payloads) == 1 else RIDER_TILE
        outs = [
            _launch(gen, dev, slots, tn, kk, k1, b, payloads[lo : lo + tile], tile)
            for lo in range(0, len(payloads), tile)
        ]
        telemetry.inc("ft_postings", by=float(sum(int(p[1][0]) for p in payloads)))
        telemetry.inc("ft_slots", by=float(len(outs) * tile * slots))

        def collect():
            res = []
            for out in outs:
                vals, dids, matched = unpack_results(out, kk)
                res += [(vals[i], dids[i], int(matched[i])) for i in range(vals.shape[0])]
            return res[: len(payloads)]

        collect.launch_labels = {"slots": slots}
        collect.outputs = tuple(outs)
        return collect

    return run
