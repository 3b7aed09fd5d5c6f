"""Persistent inverted index + its exact KV search.

Role of the reference's FtIndex (reference: core/src/idx/ft/ — terms.rs
dictionary, postings.rs, doclength.rs, termdocs.rs, offsets.rs,
docids.rs). TPU-first redesign: the KV layout is flat ordered keys rather
than B-trees (the host store is already ordered), and the KV search here
(a transaction's own writes) scores the whole candidate set at once in
NumPy; committed state is searched through idx/ft_mirror.py.

Keyspace (under the index's state prefix `+{ix}!m`):
    s                      stats {dc, tl, nt, nd}
    t{term}                term meta {id, df}
    p{tid}{did}            posting {tf, os: [[s,e],...]} (offsets if highlights)
    l{did}                 doc length
    d{rid}                 rid -> doc id
    r{did}                 doc id -> rid
    P{tid}{start}          packed posting chunk: did-offsets + tfs for one
                           bulk batch (u32 arrays; see pack_plist)
    L{start}               packed doc lengths for dids [start, start+n)
    R{start}               packed rid list for dids [start, start+n)

Bulk ingest writes ONE packed chunk per (term, batch) instead of one KV key
per (term, doc): 1M docs x 12 terms collapses from 12M posting keys to
(vocab x batches) chunk keys, which is what makes commit and the mirror
build vectorizable. The per-doc `p`/`l`/`r` keys remain as an OVERLAY for
single-document updates: an overlay entry overrides the packed chunks, and
a tf<=0 posting / length 0 / rid None is a tombstone. Search and the device
mirror merge base chunks + overlay.
"""

from __future__ import annotations

import struct
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from surrealdb_tpu import key as keys
from surrealdb_tpu.key.encode import enc_str, enc_u64, dec_u64, enc_value_key, prefix_end
from surrealdb_tpu.sql.value import Thing, is_nullish
from surrealdb_tpu.utils.ser import pack, unpack

from .ft_analyzer import Analyzer, analyzer_for


def pack_posting(tf: int, offs=None) -> bytes:
    """Posting codec: without highlight offsets a posting is a bare 4-byte
    LE term frequency (the hot bulk-ingest write); with offsets it is the
    msgpack dict the highlighter consumes. Offset-less msgpack postings are
    never 4 bytes, so the decoder keys off length."""
    if offs is None:
        return struct.pack("<I", tf)
    return pack({"tf": tf, "os": offs})


def unpack_posting(raw: bytes) -> dict:
    if len(raw) == 4:
        return {"tf": struct.unpack("<I", raw)[0]}
    return unpack(raw)


# ------------------------------------------------------------ chunk codecs
def pack_plist(base: int, offs: np.ndarray, tfs: np.ndarray) -> bytes:
    """One term's postings for one bulk batch: did = base + offset."""
    return (
        struct.pack("<Iq", len(offs), base)
        + offs.astype("<u4", copy=False).tobytes()
        + tfs.astype("<u4", copy=False).tobytes()
    )


def unpack_plist(raw: bytes) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (dids int64 ascending, tfs float32)."""
    n, base = struct.unpack_from("<Iq", raw)
    offs = np.frombuffer(raw, dtype="<u4", count=n, offset=12)
    tfs = np.frombuffer(raw, dtype="<u4", count=n, offset=12 + 4 * n)
    return base + offs.astype(np.int64), tfs.astype(np.float32)


def pack_rids(rids: list) -> Any:
    """R-chunk payload: columnar {tb, packed int64 ids} when the batch is
    uniform int-id Things (the common bulk shape — decodes in O(1) instead
    of unpacking tens of thousands of Thing exts per chunk), else the
    generic rid list."""
    if rids and all(
        isinstance(r, Thing) and isinstance(r.id, int) and r.tb == rids[0].tb
        for r in rids
    ):
        try:
            ids = np.asarray([r.id for r in rids], dtype="<i8")
        except OverflowError:
            return list(rids)  # an id beyond int64: generic payload
        return {"t": rids[0].tb, "i": ids.tobytes()}
    return list(rids)


def rid_chunk_get(decoded, off: int) -> Optional[Thing]:
    """Index into a decoded R-chunk payload (columnar or list form)."""
    if isinstance(decoded, dict):
        ids = decoded["i"]
        if 0 <= off * 8 < len(ids):
            return Thing(decoded["t"], struct.unpack_from("<q", ids, off * 8)[0])
        return None
    return decoded[off] if 0 <= off < len(decoded) else None


def pack_lens(lens: np.ndarray) -> bytes:
    return struct.pack("<I", len(lens)) + lens.astype("<u4", copy=False).tobytes()


def unpack_lens(raw: bytes) -> np.ndarray:
    n = struct.unpack_from("<I", raw)[0]
    return np.frombuffer(raw, dtype="<u4", count=n, offset=4).astype(np.float32)


def _tf(tokens) -> Dict[str, Tuple[int, List[List[int]]]]:
    """Aggregate analyzed tokens into term -> (frequency, offsets)."""
    out: Dict[str, Tuple[int, List[List[int]]]] = {}
    for text, s, e in tokens:
        count, offs = out.get(text, (0, []))
        out[text] = (count + 1, offs + [[s, e]])
    return out


class FtIndex:
    def __init__(self, tb: str, ix: dict):
        self.tb = tb
        self.ix = ix
        self.name = ix["name"]
        self.highlights = bool(ix["index"].get("highlights"))
        self._pref: Optional[Tuple[Tuple[str, str], bytes]] = None

    @staticmethod
    def for_index(ctx, ix: dict) -> "FtIndex":
        return FtIndex(ix["table"], ix)

    def analyzer(self, ctx) -> Analyzer:
        return analyzer_for(ctx, self.ix["index"].get("analyzer"))

    # ------------------------------------------------------------ keys
    def _k(self, ctx, sub: bytes) -> bytes:
        ns, db = ctx.ns_db()
        if self._pref is None or self._pref[0] != (ns, db):
            self._pref = ((ns, db), keys.index_state_prefix(ns, db, self.tb, self.name))
        return self._pref[1] + sub

    def _stats(self, ctx) -> dict:
        raw = ctx.txn().get(self._k(ctx, b"s"))
        return unpack(raw) if raw else {"dc": 0, "tl": 0, "nt": 0, "nd": 0}

    def _put_stats(self, ctx, st: dict) -> None:
        ctx.txn().set(self._k(ctx, b"s"), pack(st))

    # ------------------------------------------------------------ doc ids
    def _doc_id(self, ctx, rid: Thing, st: dict, create: bool) -> Optional[int]:
        txn = ctx.txn()
        k = self._k(ctx, b"d" + enc_value_key(rid))
        raw = txn.get(k)
        if raw is not None:
            return unpack(raw)
        if not create:
            return None
        did = st["nd"]
        st["nd"] += 1
        txn.set(k, pack(did))
        txn.set(self._k(ctx, b"r" + enc_u64(did)), pack(rid))
        return did

    def _rid_resolver(self, ctx):
        """did -> rid resolver for one search: R chunk KEYS are read once
        (raw bytes, cheap), but a chunk's rid list is msgpack-decoded only
        when a candidate actually lands in it — searches resolve a handful
        of top candidates out of millions of mappings."""
        import bisect as _bisect

        txn = ctx.txn()
        pre = self._k(ctx, b"R")
        starts: List[int] = []
        raws: List[Any] = []  # raw bytes until first hit, then the list
        for chunk in txn.batch(pre, prefix_end(pre), 256):
            for k, v in chunk:
                start, _ = dec_u64(k, len(pre))
                starts.append(start)
                raws.append(v)
        rpre = self._k(ctx, b"r")

        def resolve(did: int) -> Optional[Thing]:
            raw = txn.get(rpre + enc_u64(did))
            if raw is not None:
                return unpack(raw)  # may be a None tombstone
            i = _bisect.bisect_right(starts, did) - 1
            if i >= 0:
                dec = raws[i]
                if isinstance(dec, bytes):
                    dec = raws[i] = unpack(dec)
                return rid_chunk_get(dec, did - starts[i])
            return None

        return resolve

    # -------------------------------------------------- chunk+overlay reads
    def _term_postings(self, ctx, tid: int) -> Tuple[np.ndarray, np.ndarray]:
        """One term's live postings: packed chunks merged with the per-doc
        overlay (overlay wins; tf<=0 entries are tombstones). Returns
        (dids int64 ascending, tfs float32)."""
        txn = ctx.txn()
        parts_d, parts_t = [], []
        pre = self._k(ctx, b"P" + enc_u64(tid))
        for chunk in txn.batch(pre, prefix_end(pre), 1024):
            for _k, v in chunk:
                d, t = unpack_plist(v)
                parts_d.append(d)
                parts_t.append(t)
        if parts_d:
            dids = np.concatenate(parts_d)
            tfs = np.concatenate(parts_t)
        else:
            dids = np.empty(0, np.int64)
            tfs = np.empty(0, np.float32)
        pre = self._k(ctx, b"p" + enc_u64(tid))
        ov: Dict[int, int] = {}
        for k, raw in txn.scan(pre, prefix_end(pre)):
            did, _ = dec_u64(k, len(pre))
            ov[did] = unpack_posting(raw)["tf"]
        if ov:
            ov_d = np.fromiter(ov.keys(), np.int64, count=len(ov))
            ov_t = np.fromiter(ov.values(), np.float32, count=len(ov))
            if dids.size:
                keep = ~np.isin(dids, ov_d)
                dids, tfs = dids[keep], tfs[keep]
            live = ov_t > 0
            dids = np.concatenate([dids, ov_d[live]])
            tfs = np.concatenate([tfs, ov_t[live]])
            order = np.argsort(dids, kind="stable")
            dids, tfs = dids[order], tfs[order]
        return dids, tfs

    def _cand_lens(self, ctx, cand: np.ndarray) -> np.ndarray:
        """Doc lengths for the (sorted) candidate dids: slice the covering
        packed L chunks, then per-did overlay point gets."""
        txn = ctx.txn()
        out = np.zeros(len(cand), dtype=np.float32)
        pre = self._k(ctx, b"L")
        for chunk in txn.batch(pre, prefix_end(pre), 1024):
            for k, v in chunk:
                start, _ = dec_u64(k, len(pre))
                lens = unpack_lens(v)
                lo = np.searchsorted(cand, start)
                hi = np.searchsorted(cand, start + len(lens))
                if lo < hi:
                    out[lo:hi] = lens[cand[lo:hi] - start]
        lpre = self._k(ctx, b"l")
        for i, did in enumerate(cand):
            raw = txn.get(lpre + enc_u64(int(did)))
            if raw is not None:
                out[i] = max(unpack(raw), 0)  # -1 tombstone scores as 0
        return out

    # ------------------------------------------------------------ terms
    def _term(self, ctx, term: str) -> Optional[dict]:
        raw = ctx.txn().get(self._k(ctx, b"t" + enc_str(term)))
        return unpack(raw) if raw else None

    def _put_term(self, ctx, term: str, meta: dict) -> None:
        ctx.txn().set(self._k(ctx, b"t" + enc_str(term)), pack(meta))

    # ------------------------------------------------------------ write side
    def index_document(self, ctx, rid: Thing, old_vals, new_vals) -> None:
        st = self._stats(ctx)
        txn = ctx.txn()
        az = self.analyzer(ctx)

        old_tokens = self._tokens_of(az, old_vals)
        new_tokens = self._tokens_of(az, new_vals)
        if old_tokens is None and new_tokens is None:
            return

        did = self._doc_id(ctx, rid, st, create=new_tokens is not None)
        if did is None:
            return

        # remove the old posting set: tombstones, not deletes — the old
        # postings may live inside packed bulk chunks the overlay overrides
        old_tf = _tf(old_tokens) if old_tokens is not None else None
        if old_tokens is not None:
            for term in old_tf:
                meta = self._term(ctx, term)
                if meta is None:
                    continue
                txn.set(
                    self._k(ctx, b"p" + enc_u64(meta["id"]) + enc_u64(did)),
                    pack_posting(0),
                )
                meta["df"] -= 1
                self._put_term(ctx, term, meta)
            lraw = txn.get(self._k(ctx, b"l" + enc_u64(did)))
            if lraw is not None:
                st["tl"] -= max(unpack(lraw), 0)
            else:
                st["tl"] -= int(self._chunk_len_of(ctx, did))
            # -1 = removal tombstone, distinct from a present zero-token doc
            txn.set(self._k(ctx, b"l" + enc_u64(did)), pack(-1))
            st["dc"] -= 1

        # write the new posting set
        tfs = _tf(new_tokens) if new_tokens is not None else None
        if new_tokens is not None:
            for term, (count, offs) in tfs.items():
                meta = self._term(ctx, term)
                if meta is None:
                    meta = {"id": st["nt"], "df": 0}
                    st["nt"] += 1
                meta["df"] += 1
                self._put_term(ctx, term, meta)
                txn.set(
                    self._k(ctx, b"p" + enc_u64(meta["id"]) + enc_u64(did)),
                    pack_posting(count, offs if self.highlights else None),
                )
            length = len(new_tokens)
            txn.set(self._k(ctx, b"l" + enc_u64(did)), pack(length))
            st["tl"] += length
            st["dc"] += 1
        else:
            # document no longer has the field: drop the id mapping
            # (rid map tombstone: the did may live in a packed R chunk)
            txn.delete(self._k(ctx, b"d" + enc_value_key(rid)))
            txn.set(self._k(ctx, b"r" + enc_u64(did)), pack(None))

        self._put_stats(ctx, st)
        # buffered mirror delta, applied on commit (idx/ft_mirror.py)
        ns, db = ctx.ns_db()
        txn.ft_delta(
            ns,
            db,
            self.tb,
            self.name,
            rid,
            did,
            {t: c for t, (c, _) in old_tf.items()} if old_tf is not None else None,
            {t: c for t, (c, _) in tfs.items()} if tfs is not None else None,
            len(new_tokens) if new_tokens is not None else 0,
        )

    def _chunk_len_of(self, ctx, did: int) -> float:
        """Doc length for a bulk-chunk-indexed doc (no per-doc l key):
        the covering L chunk is the last one with start <= did."""
        txn = ctx.txn()
        pre = self._k(ctx, b"L")
        last = None
        for k, v in txn.scan(pre, pre + enc_u64(did) + b"\xff"):
            last = (k, v)
        if last is None:
            return 0.0
        start, _ = dec_u64(last[0], len(pre))
        lens = unpack_lens(last[1])
        off = did - start
        return float(lens[off]) if 0 <= off < len(lens) else 0.0

    def index_documents_bulk(self, ctx, batch) -> None:
        """Index a batch of NEW documents (no prior posting sets — the bulk
        insert path verified the records did not exist). The offset-free
        path writes ONE packed chunk per touched term (plus one lengths +
        one rid chunk) instead of per-(term, doc) keys; highlight-enabled
        indexes need per-posting offsets and keep the per-doc layout."""
        if self.highlights:
            return self._bulk_with_offsets(ctx, batch)
        from collections import Counter

        st = self._stats(ctx)
        txn = ctx.txn()
        az = self.analyzer(ctx)
        ns, db = ctx.ns_db()
        base = self._k(ctx, b"")
        tset = txn.set

        start = st["nd"]
        term_offs: Dict[str, List[int]] = {}
        term_tfs: Dict[str, List[int]] = {}
        lens: List[int] = []
        rids: List[Thing] = []
        for rid, vals in batch:
            terms = self._terms_of_fast(az, vals)
            if terms is None:
                continue
            tf_counts = Counter(terms)
            # records on this path are verified-new (the bulk inserter
            # checked existence), so the id mapping cannot exist
            did = st["nd"]
            st["nd"] += 1
            tset(base + b"d" + enc_value_key(rid), pack(did))
            off = did - start
            for term, count in tf_counts.items():
                lo = term_offs.get(term)
                if lo is None:
                    lo = term_offs[term] = []
                    term_tfs[term] = []
                lo.append(off)
                term_tfs[term].append(count)
            lens.append(len(terms))
            rids.append(rid)

        if rids:
            delta_terms: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
            for term, offs in term_offs.items():
                meta = self._term(ctx, term)
                if meta is None:
                    meta = {"id": st["nt"], "df": 0}
                    st["nt"] += 1
                meta["df"] += len(offs)
                self._put_term(ctx, term, meta)
                offs_a = np.asarray(offs, dtype=np.uint32)
                tfs_a = np.asarray(term_tfs[term], dtype=np.uint32)
                tset(
                    base + b"P" + enc_u64(meta["id"]) + enc_u64(start),
                    pack_plist(start, offs_a, tfs_a),
                )
                delta_terms[term] = (
                    start + offs_a.astype(np.int64),
                    tfs_a.astype(np.float32),
                )
            lens_a = np.asarray(lens, dtype=np.uint32)
            tset(base + b"L" + enc_u64(start), pack_lens(lens_a))
            tset(base + b"R" + enc_u64(start), pack(pack_rids(rids)))
            st["tl"] += int(lens_a.sum())
            st["dc"] += len(rids)
            txn.ft_bulk_delta(
                ns, db, self.tb, self.name,
                start, delta_terms, lens_a.astype(np.float32), rids,
            )
        self._put_stats(ctx, st)

    def _bulk_with_offsets(self, ctx, batch) -> None:
        """Per-doc bulk path for highlight indexes (postings carry offsets)."""
        st = self._stats(ctx)
        txn = ctx.txn()
        az = self.analyzer(ctx)
        ns, db = ctx.ns_db()
        term_cache: Dict[str, Optional[dict]] = {}
        tid_enc: Dict[str, bytes] = {}  # term -> enc_u64(term id), batch-local
        touched: set = set()
        base = self._k(ctx, b"")
        pbase = base + b"p"
        tset = txn.set
        ft_delta = txn.ft_delta

        for rid, vals in batch:
            tokens = self._tokens_of(az, vals)
            if tokens is None:
                continue
            tfs_full = _tf(tokens)
            tf_counts: Dict[str, int] = {t: c for t, (c, _) in tfs_full.items()}
            length = len(tokens)
            did = st["nd"]
            st["nd"] += 1
            did_enc = enc_u64(did)
            tset(base + b"d" + enc_value_key(rid), pack(did))
            tset(base + b"r" + did_enc, pack(rid))
            for term, count in tf_counts.items():
                meta = term_cache.get(term)
                if meta is None and term not in term_cache:
                    meta = self._term(ctx, term)
                    term_cache[term] = meta
                if meta is None:
                    meta = {"id": st["nt"], "df": 0}
                    st["nt"] += 1
                    term_cache[term] = meta
                meta["df"] += 1
                touched.add(term)
                te = tid_enc.get(term)
                if te is None:
                    te = tid_enc[term] = enc_u64(meta["id"])
                tset(pbase + te + did_enc, pack_posting(count, tfs_full[term][1]))
            tset(base + b"l" + did_enc, pack(length))
            st["tl"] += length
            st["dc"] += 1
            ft_delta(ns, db, self.tb, self.name, rid, did, None, dict(tf_counts), length)

        for term in touched:
            self._put_term(ctx, term, term_cache[term])
        self._put_stats(ctx, st)

    def _tokens_of(self, az: Analyzer, vals) -> Optional[list]:
        if vals is None:
            return None
        out = []
        found = False
        for v in vals:
            items = v if isinstance(v, list) else [v]
            for item in items:
                if isinstance(item, str):
                    found = True
                    out.extend(az.analyze(item))
        return out if found else None

    def _terms_of_fast(self, az: Analyzer, vals) -> Optional[list]:
        """Offset-free twin of _tokens_of (term strings only)."""
        if vals is None:
            return None
        out: List[str] = []
        found = False
        for v in vals:
            items = v if isinstance(v, list) else [v]
            for item in items:
                if isinstance(item, str):
                    found = True
                    out.extend(az.terms_fast(item))
        return out if found else None

    # ------------------------------------------------------------ search
    def search(self, ctx, query: str) -> "FtResults":
        """AND-match all analyzed query terms over the KV postings, score
        the candidate set in float64 NumPy."""
        az = self.analyzer(ctx)
        terms = az.terms(query)
        txn = ctx.txn()
        st = self._stats(ctx)

        term_metas = []
        for t in dict.fromkeys(terms):
            m = self._term(ctx, t)
            if m is None or m["df"] <= 0:
                return FtResults(self, {}, terms)  # a missing term → no matches
            term_metas.append((t, m))
        if not term_metas:
            return FtResults(self, {}, terms)

        # postings per term (packed chunks + overlay), rarest first for
        # cheap sorted-array intersection
        term_metas.sort(key=lambda tm: tm[1]["df"])
        rows = [self._term_postings(ctx, meta["id"]) for _, meta in term_metas]
        cand = rows[0][0]
        tf_cols = [rows[0][1]]
        for r_dids, r_tfs in rows[1:]:
            if cand.size == 0 or r_dids.size == 0:
                return FtResults(self, {}, terms)
            pos = np.searchsorted(r_dids, cand)
            pos_c = np.clip(pos, 0, len(r_dids) - 1)
            mask = r_dids[pos_c] == cand
            cand = cand[mask]
            tf_cols = [c[mask] for c in tf_cols]
            tf_cols.append(r_tfs[pos_c[mask]])
        if cand.size == 0:
            return FtResults(self, {}, terms)

        dids = [int(d) for d in cand]
        tf_mat = np.stack(tf_cols, axis=1)
        df = np.asarray([m["df"] for _, m in term_metas], dtype=np.float32)
        lens = self._cand_lens(ctx, cand)

        k1 = float(self.ix["index"].get("k1", 1.2))
        b = float(self.ix["index"].get("b", 0.75))
        from surrealdb_tpu.ops.bm25 import bm25_scores_host

        scores = bm25_scores_host(tf_mat, df, lens, st["dc"], st["tl"], k1, b)
        resolve = self._rid_resolver(ctx)
        by_rid: Dict[Tuple[str, str], Tuple[Thing, float]] = {}
        for did, s in zip(dids, scores):
            rid = resolve(did)
            if rid is not None:
                by_rid[(rid.tb, repr(rid.id))] = (rid, float(s))
        return FtResults(self, by_rid, terms)

    # ------------------------------------------------------------ highlight
    def offsets_for(self, ctx, rid: Thing, terms: List[str]) -> List[Tuple[int, int]]:
        if not self.highlights:
            return []
        txn = ctx.txn()
        raw = txn.get(self._k(ctx, b"d" + enc_value_key(rid)))
        if raw is None:
            return []
        did = unpack(raw)
        offs: List[Tuple[int, int]] = []
        for t in dict.fromkeys(terms):
            meta = self._term(ctx, t)
            if meta is None:
                continue
            p = txn.get(self._k(ctx, b"p" + enc_u64(meta["id"]) + enc_u64(did)))
            if p is not None:
                offs.extend((s, e) for s, e in unpack_posting(p).get("os", []))
        return sorted(set(offs))


class FtResults:
    """Matched doc set + scores for one MATCHES evaluation."""

    def __init__(self, index: FtIndex, by_rid: dict, terms: List[str]):
        self.index = index
        self.by_rid = by_rid  # (tb, repr(id)) -> (Thing, score)
        self.terms = terms

    def __iter__(self):
        return iter(self.by_rid.values())

    def contains(self, rid: Thing) -> bool:
        return (rid.tb, repr(rid.id)) in self.by_rid

    def score(self, rid: Thing) -> Optional[float]:
        v = self.by_rid.get((rid.tb, repr(rid.id)))
        return v[1] if v else None
