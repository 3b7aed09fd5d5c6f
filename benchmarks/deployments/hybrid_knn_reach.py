"""Deployment kind `hybrid_knn_reach`: one table of papers, each with a
vector, a publication year and the papers it cites, asked in ONE statement for
the k nearest papers since a year and, under each, the set of papers since that
year that lie exactly two `cites` records away (BASELINE.json config 4: vector
kNN prefilter + 2-hop graph expand + WHERE filter, on MAG240M's shapes).

The corpus, the pool's vectors, the exact FILTERED top-k and the hits' check
are `vector_knn_filtered`'s own calls (a paper's id is that kind's int field
`n`, and the years are laid so that `year >= y` passes exactly the ids that
`n >= lo` passes: generate() holds the two to each other), so a configuration
with `vec1m768`'s generator and `corpus_seed` holds that corpus's first rows.
The years, the citation graph, the NumPy reach of two steps, the loader with
its probe and the numbers that hold the graph half of the guarantee are here
and read nothing the program made.

The reply's arrays never reach the window's check (`harness/loadgen.py::
read_answer` keeps ids and numeric fields), so the statement carries
`array::len` of each row's set beside it, and the window holds that count to
the reference for WHICHEVER paper the search returned; the arrays themselves
are compared as whole sets by the loader's probe, through `ds.execute()`
before any client starts.
"""

from __future__ import annotations

import time

import numpy as np

from deployments import vector_knn_filtered as base

KIND = "hybrid_knn_reach"
INGEST_BATCH = base.INGEST_BATCH
PROBES = 32
FIRST_YEAR, YEARS = 1960, 60


# ------------------------------------------------------------------ data
def years(papers: int) -> np.ndarray:
    """Paper i's publication year: non-decreasing in i, YEARS of them."""
    return FIRST_YEAR + (np.arange(papers, dtype=np.int64) * YEARS) // papers


def citations(g: dict, papers: int, cites: int, lo: int) -> np.ndarray:
    """`cites` (citing, cited) records of a citation DAG over `papers`
    papers, from the configuration's `corpus_seed` (one fixed graph, as the
    corpus is one fixed data set). A paper cites lower ids only. The length
    of a reference list is log-normal with the mean the sizes give, at
    least 1 from paper `lo` on (the passing half: every hit cites
    something), at most `refs_cap` and at most the papers below it; the
    lengths are then moved by one here and there until they add up to
    `cites`. A cited paper is drawn among the lower ids in proportion to a
    heavy-tailed weight a paper (the fitness form of preferential
    attachment: a sequential rich-get-richer draw is not a whole-array
    one) or, for `recent_share` of the records, uniformly among the
    `recent_window` ids just below the citing paper. Drawn with
    replacement: a paper may cite another twice (two records), and two of
    a paper's references may cite the same third (a diamond). Returned in
    one shuffled order."""
    rng = np.random.default_rng(np.random.SeedSequence([int(g["corpus_seed"]), 37]))
    sigma, cap = float(g["refs_sigma"]), int(g["refs_cap"])
    below = np.arange(papers, dtype=np.int64)
    least = (below >= lo).astype(np.int64)
    most = np.minimum(cap, below)
    length = np.rint(rng.lognormal(np.log(cites / papers) - sigma**2 / 2.0, sigma, papers)).astype(np.int64)
    length = np.clip(length, least, most)
    while (diff := cites - int(length.sum())) != 0:
        room = np.flatnonzero(length < most) if diff > 0 else np.flatnonzero(length > least)
        length[rng.choice(room, size=min(abs(diff), room.size), replace=False)] += 1 if diff > 0 else -1
    citing = np.repeat(below, length)
    weight = rng.pareto(float(g["fitness_alpha"]), papers) + 1.0
    cum = np.concatenate([[0.0], np.cumsum(weight)])
    by_weight = np.searchsorted(cum, rng.random(cites) * cum[citing], side="right") - 1
    window = np.minimum(int(g["recent_window"]), citing)
    recent = citing - 1 - (rng.random(cites) * window).astype(np.int64)
    cited = np.where(rng.random(cites) < float(g["recent_share"]), recent, by_weight)
    cited = np.clip(cited, 0, citing - 1)
    return rng.permutation(np.stack([citing, cited], axis=1))


def generate(cfg: dict, sizes: dict, seed: int) -> dict:
    """`vector_knn_filtered`'s corpus, pool and id threshold over `papers`
    rows of which `pass_share` pass, the papers' years, the one year `y`
    every pool entry binds, and the citation records."""
    papers = int(sizes["papers"])
    passing = int(round(papers * float(sizes["pass_share"])))
    data = base.generate(cfg, {**sizes, "rows": papers, "pass_rows": passing}, seed)
    year = years(papers)
    y = int(year[data["lo"]])
    if not np.array_equal(year >= y, data["n"] >= data["lo"]):
        raise RuntimeError(f"year >= {y} does not pass exactly the papers from {data['lo']} on")
    pairs = citations(cfg["generator"], papers, int(sizes["cites"]), int(data["lo"]))
    return {**data, "year": year, "y": y, "pairs": pairs, "papers": papers}


def pool(cfg: dict, data: dict) -> list:
    y = int(data["y"])
    return [{"v": q, "y": y} for q in data["queries"].astype(np.float64).tolist()]


# ------------------------------------------------------------------ reference
class Cites:
    """The `cites` records as a CSR by citing paper, and what two steps
    along them reach."""

    def __init__(self, pairs: np.ndarray, papers: int, passes: np.ndarray):
        order = np.argsort(pairs[:, 0], kind="stable")
        self.cited = pairs[order, 1]
        self.indptr = np.concatenate([[0], np.cumsum(np.bincount(pairs[:, 0], minlength=papers))])
        self.passes = passes

    def row(self, p: int) -> np.ndarray:
        return self.cited[self.indptr[p] : self.indptr[p + 1]]

    def walks2(self, p: int) -> np.ndarray:
        """Where every walk of exactly two records from `p` ends: a paper
        once a walk (the multiset)."""
        mid = self.row(p)
        return np.concatenate([self.row(int(m)) for m in mid]) if mid.size else mid

    def reach2(self, p: int) -> np.ndarray:
        """The papers since `y` that a walk of exactly two `cites` records
        from `p` ends at, each once, ascending."""
        ends = np.unique(self.walks2(p))
        return ends[self.passes[ends]]


def reference(cfg: dict, data: dict) -> dict:
    """`vector_knn_filtered`'s reference (per pool query the exact float32
    neighbours among the papers since `y`, nearest first, their float64
    squared distances and the int8 control's) and the citation CSR, whose
    `reach2` the check and the loader's probe ask of whichever paper a
    search returned. The hits go back into `data` (`hits`): the loader
    picks its probe's pool entries by them."""
    ref = base.reference(cfg, data)
    data["hits"] = ref["ids"][:, : int(cfg["k"])]
    return {**ref, "cites": Cites(data["pairs"], data["papers"], data["year"] >= data["y"])}


# ------------------------------------------------------------------ load
def load(ds, cfg: dict, data: dict, execute_ok) -> dict:
    """The papers (vector and year a row), their count read back, the
    `cites` records, then the probe."""
    from surrealdb_tpu.sql.value import Thing

    tb, edge_tb, corpus, year, pairs = cfg["table"], cfg["edge_table"], data["corpus"], data["year"], data["pairs"]
    for ddl in cfg["ddl"]:
        execute_ok(ds, ddl)
    secs = 0.0
    for i in range(0, corpus.shape[0], INGEST_BATCH):
        blk = corpus[i : i + INGEST_BATCH]
        rows = [{"id": i + j, "emb": blk[j], "year": int(year[i + j])} for j in range(blk.shape[0])]
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT INTO {tb} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    got = int(execute_ok(ds, f"SELECT count() AS c FROM {tb} GROUP ALL")[-1]["result"][0]["c"])
    if got != corpus.shape[0]:
        raise RuntimeError(f"{got} {tb} rows read back of {corpus.shape[0]} acknowledged")
    for i in range(0, pairs.shape[0], INGEST_BATCH):
        rows = [{"in": Thing(tb, int(a)), "out": Thing(tb, int(b))} for a, b in pairs[i : i + INGEST_BATCH]]
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT RELATION INTO {edge_tb} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    probed = probe(ds, cfg, data, execute_ok)
    return {"acknowledged": int(corpus.shape[0] + pairs.shape[0]), "insert_s": secs, "unit": "rows", "probe": probed}


def probe_entries(data: dict) -> list:
    """The pool entries the probe asks, PROBES of them: the one with the hit
    whose reference list is the longest, the one with the hit whose list is
    the shortest, those with a hit that cites one of the most-cited papers
    (the widest second step), then the pool's first."""
    hits, pairs, papers = data["hits"], data["pairs"], data["papers"]
    refs = np.bincount(pairs[:, 0], minlength=papers)
    cited = np.bincount(pairs[:, 1], minlength=papers)
    top = np.argsort(-cited, kind="stable")[: max(papers // 1000, 1)]
    citers = np.zeros(papers, dtype=bool)
    citers[pairs[np.isin(pairs[:, 1], top), 0]] = True
    chosen = [int(np.argmax(refs[hits].max(axis=1))), int(np.argmin(refs[hits].min(axis=1)))]
    chosen += np.flatnonzero(citers[hits].any(axis=1)).tolist() + list(range(hits.shape[0]))
    return list(dict.fromkeys(chosen))[:PROBES]


def row_faults(cfg: dict, cites: Cites, row: dict) -> list:
    """What is wrong with one row of a reply: its set against the
    reference's for the row's own paper, as whole sets, and its count."""
    rid = int(row["id"].id)
    want = cites.reach2(rid).tolist()
    got = sorted(int(t.id) for t in row[cfg["reach_field"]])
    faults = []
    if got != want:
        faults.append(f"the set of paper {rid} is not the reference's: {len(got)} papers for {len(want)}, "
                      f"{len(set(got) ^ set(want))} in one and not the other")
    if row[cfg["count_field"]] != len(want):
        faults.append(f"paper {rid} counts {row[cfg['count_field']]} papers two steps away where the reference has {len(want)}")
    return faults


def probe(ds, cfg: dict, data: dict, execute_ok) -> dict:
    """The timed statement for PROBES pool entries, a statement at a time:
    k rows, every row's paper since `y`, every row's set equal to NumPy's as
    a whole set, and `dispatch.submitted` up by the statement's
    `dispatches`. The first of them builds the mirrors. A program that
    serves the sets by expanding the chains, or the search on the host, is
    refused here by the dispatch count."""
    statement, entries = cfg["statements"]["primary"], pool(cfg, data)
    cites = Cites(data["pairs"], data["papers"], data["year"] >= data["y"])
    chosen, rows, longest = probe_entries(data), 0, 0
    for q in chosen:
        before = ds.dispatch.stats()["submitted"]
        out = execute_ok(ds, statement["sql"], {statement["bind"]: entries[q]})
        made = ds.dispatch.stats()["submitted"] - before
        reply = out[-1]["result"]
        if made != statement["dispatches"]:
            raise RuntimeError(f"{made} device dispatches for the loader's probe of pool entry {q} "
                               f"where the statement makes {statement['dispatches']}")
        if len(reply) != int(cfg["k"]):
            raise RuntimeError(f"{len(reply)} rows for the loader's probe of pool entry {q}, not {cfg['k']}")
        for row in reply:
            if data["year"][int(row["id"].id)] < data["y"]:
                raise RuntimeError(f"the loader's probe of pool entry {q} returned paper {row['id']} of before {data['y']}")
            faults = row_faults(cfg, cites, row)
            if faults:
                raise RuntimeError(f"the loader's probe of pool entry {q}: {faults[0]}")
            longest = max(longest, len(row[cfg["reach_field"]]))
        rows += len(reply)
    return {"statements": len(chosen), "rows": rows, "longest_set": longest}


def count_sql(cfg: dict) -> list:
    """Every acknowledged paper's year and every `cites` record are read back."""
    return [(f"SELECT count() AS c FROM {cfg['table']} WHERE year >= 0 GROUP ALL", None),
            (f"SELECT count() AS c FROM {cfg['edge_table']} GROUP ALL", None)]


def release(data: dict) -> None:
    data.pop("corpus", None)
    data["edges"] = int(data.pop("pairs").shape[0])


def wait_background(ds, cfg: dict, timeout: float) -> dict:
    """`vector_knn`'s wait (the quantizer trained, every shape warmer done)
    and the graph mirrors' prewarm."""
    t0 = time.perf_counter()
    if not ds.graph_mirrors.wait_prewarm(timeout):
        raise RuntimeError(f"graph prewarm still running after {timeout:.0f}s")
    prewarm_s = time.perf_counter() - t0
    out = base.wait_background(ds, cfg, timeout)
    return {"state": out["state"], "line": {**out["line"], "prewarm_wait_s": prewarm_s}}


def kernel_shapes(cfg: dict, data: dict, state: dict) -> dict:
    """The graph a rider sweeps: papers, `cites` records, steps."""
    return {"nodes": int(data["papers"]), "edges": int(data["edges"]), "hops": int(cfg["hops"])}


# ------------------------------------------------------------------ check
def check(cfg: dict, ref: dict, records: list) -> dict:
    """`vector_knn_filtered`'s five numbers of the hits (recall against the
    exact filtered top-k, the distances, the ids that fail the filter, the
    short answers) and `wrong_reach_counts`: the rows whose count differs
    from the reference's set of the row's own paper. Beside it, what two
    controls would have counted for the same rows: `unmasked` (the set
    without the year) and `multiset` (`array::len` of the walks' ends, a
    paper once a walk)."""
    out = base.check(cfg, ref, records)
    cites, field = ref["cites"], cfg["count_field"]
    known: dict = {}
    wrong = rows = 0
    control = {"unmasked": 0, "multiset": 0}
    for r in records:
        if r["status"] != "OK":
            continue
        counts = r["values"].get(field, [])
        wrong += abs(len(r["ids"]) - len(counts))
        for rid, n in zip(r["ids"], counts):
            if rid not in known:
                ends = cites.walks2(rid) if isinstance(rid, int) and 0 <= rid < cites.passes.size else np.empty(0, np.int64)
                once = np.unique(ends)
                known[rid] = (int(cites.passes[once].sum()), int(once.size), int(cites.passes[ends].sum()))
            want, unmasked, multiset = known[rid]
            rows += 1
            wrong += n != want
            control["unmasked"] += unmasked != want
            control["multiset"] += multiset != want
    out["numbers"].append(["wrong_reach_counts", wrong if rows else 1, "<=", cfg["correct"]["wrong_reach_counts_max"]])
    out["control"].update({f"wrong_reach_counts_{kind}": v for kind, v in control.items()})
    out["compared"]["rows"] = rows
    return out
