"""MATCHES (@@) query plan over the inverted index.

Role of the reference's MatchesThingIterator + per-doc matches()/score()/
highlight() hooks (reference: core/src/idx/planner/iterators.rs:849-904,
executor.rs:878-1102, fnc/search.rs). The plan object implements the
QueryExecutor protocol consulted by the MATCHES operator and the search::
functions during document processing.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from surrealdb_tpu.sql.value import NONE, Thing

from .ft_index import FtIndex


class MatchesPlan:
    def __init__(self, tb: str, ix: dict, op, query):
        self.tb = tb
        self.ix = ix
        self.op = op
        self.query = query if isinstance(query, str) else str(query)
        self.ft = FtIndex.for_index(None, ix)
        self.results = None  # FtResults after iterate()
        self.provides_order = False  # set by the planner (score-order pushdown)
        self.order_pushed = False  # set by stmt_exec when it's the only source
        self.top_k = None  # limit + start where the planner could read them (with the score order)

    def explain(self) -> dict:
        return {
            "index": self.ix["name"],
            "operator": f"@{self.op.ref if self.op.ref is not None else ''}@",
            "query": self.query,
        }

    # ------------------------------------------------------------ iteration
    def _route(self, ctx, terms) -> str:
        """The one rule that decides how the statement is served. `kv`: the
        transaction holds uncommitted writes to this index (the exact KV
        search sees them; the shared mirror must not). `device`: the score
        order is pushed down with a LIMIT (k = limit + start, at most the
        ladder's last k slot), the query has 1 to 8 distinct terms and the
        device is not disabled: one dispatch, whatever the candidates
        number. `host` otherwise: the mirror's NumPy route."""
        from surrealdb_tpu import cnf
        from surrealdb_tpu.ops.bm25 import k_slots, term_slots

        ns, db = ctx.ns_db()
        want = (ns, db, self.tb, self.ix["name"])
        pending = getattr(ctx.txn(), "ft_deltas", None)
        if pending and any(d[1:5] == want for d in pending):
            return "kv"
        if (
            self.order_pushed
            and self.top_k
            and k_slots(self.top_k) is not None
            and term_slots(len(set(terms))) is not None
            and terms
            and not cnf.TPU_DISABLE
        ):
            return "device"
        return "host"

    def iterate(self, ctx):
        import time

        import numpy as np

        from .ft_index import FtResults
        from .ft_mirror import FtMirror, routed

        t_iter = time.perf_counter()
        ctx.qe = self
        terms = self.ft.analyzer(ctx).terms(self.query)
        route = self._route(ctx, terms)
        if route == "kv":
            routed("kv", t_iter, len(set(terms)))
            self.results = self.ft.search(ctx, self.query)
            for rid, score in sorted(self.results, key=lambda rs: -rs[1]):
                yield rid, None, {"score": score}
            return
        ns, db = ctx.ns_db()
        mirror = ctx.ds().index_stores.get_or_create(ns, db, self.tb, self.ix["name"], FtMirror)
        mirror.ensure_built(ctx, self.ix)
        k1 = float(self.ix["index"].get("k1", 1.2))
        b = float(self.ix["index"].get("b", 0.75))
        # cluster mode: the coordinator injects merged GLOBAL corpus
        # stats so per-shard scoring matches one single-node corpus
        # (cluster/executor.py two-phase BM25)
        stats = ctx.get_param("__cluster_ft_stats")
        stats = stats if isinstance(stats, dict) else None
        self.results = FtResults(self.ft, {}, terms)
        by_rid = self.results.by_rid

        def ranked(dids, scores, skip=()):
            for did, s in zip(dids.tolist(), scores.tolist()):
                rid = None if did in skip else mirror.rid_for(did)
                if rid is not None:
                    by_rid[(rid.tb, repr(rid.id))] = (rid, s)
                    yield rid, None, {"score": s}

        served = ()
        if route == "device":
            got = mirror.search_device(ctx.ds(), terms, self.top_k, k1, b, stats, t_iter)
            if got is not None:
                dids, scores, matched = got
                ctx.executor.op_end = time.perf_counter()  # the `materialise` span starts here
                yield from ranked(dids, scores)
                if matched <= len(dids):
                    return
                # the consumer wants more than the LIMIT's k rows (it dropped
                # some: a residual WHERE, a permission): the rest by the host
                served = set(dids.tolist())
        if not served:
            routed("host", t_iter, len(set(terms)))
        dids, scores = mirror.search(terms, k1, b, stats_override=stats)
        order = np.argsort(-scores, kind="stable")
        if self.order_pushed:
            # single-source score-ordered scan: LIMIT stops iteration
            # after a handful of rows, so rids are resolved and the score
            # lookup filled as docs are yielded (only yielded docs are ever
            # probed by matches()/score())
            yield from ranked(dids[order], scores[order], served)
            return
        for _ in ranked(dids[order], scores[order]):
            pass
        for rid, score in by_rid.values():
            yield rid, None, {"score": score}

    # ------------------------------------------------------------ executor protocol
    def matches(self, ctx, doc, op) -> bool:
        if self.results is None or doc.rid is None:
            return False
        return self.results.contains(doc.rid)

    def knn(self, ctx, doc, op) -> bool:
        return False

    def knn_distance(self, rid) -> Optional[float]:
        return None

    def score(self, ctx, doc, ref=None) -> Optional[float]:
        if self.results is None or doc.rid is None:
            return None
        return self.results.score(doc.rid)

    def highlight(self, ctx, doc, prefix: str, suffix: str, ref=None):
        if self.results is None or doc.rid is None:
            return NONE
        offs = self.ft.offsets_for(ctx, doc.rid, self.results.terms)
        if not offs:
            return NONE
        # apply to the indexed field's current value
        field = self.op.l
        with ctx.with_doc_value(doc.current, rid=doc.rid) as c:
            text = field.compute(c)
        if not isinstance(text, str):
            return NONE
        out = []
        last = 0
        for s, e in offs:
            if s < last or e > len(text):
                continue
            out.append(text[last:s])
            out.append(prefix + text[s:e] + suffix)
            last = e
        out.append(text[last:])
        return "".join(out)

    def offsets(self, ctx, doc, ref=None):
        if self.results is None or doc.rid is None:
            return NONE
        offs = self.ft.offsets_for(ctx, doc.rid, self.results.terms)
        return {"0": [{"s": s, "e": e} for s, e in offs]} if offs else NONE
