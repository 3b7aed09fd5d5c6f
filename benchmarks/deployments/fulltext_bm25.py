"""Deployment kind `fulltext_bm25`: one table of text passages under a
`SEARCH ANALYZER ... BM25` index, loaded in bulk and searched with
`@1@` + `search::score(1)`, score order and LIMIT (the conjunctive top-k of
an application's search box).

Everything that decides `correct` is here and reads nothing the program
made: the seeded generator (synthetic passages of `w<rank>` words under a
Zipf-like law), the plain reference (tokenise by blanks, lowercase, an
inverted index built with NumPy, AND-match, BM25 in float64 with
upstream's formula, the best k by score then doc id), the bfloat16-scored
control and the comparison. No JAX, nothing of `surrealdb_tpu` (the loader
gets the datastore handed in).
"""

from __future__ import annotations

import operator
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

KIND = "fulltext_bm25"
GEN_BLOCK = 65_536  # passages a generator block
INGEST_BATCH = 50_000  # a batch writes one posting chunk a distinct term: fewer, larger batches, fewer chunks


# ------------------------------------------------------------------ data
def word_law(vocabulary: int, shift: float) -> np.ndarray:
    """Cumulative probabilities of the ranks 1..vocabulary under p(r) ~ 1 / (r + shift)."""
    p = 1.0 / (np.arange(1, vocabulary + 1, dtype=np.float64) + shift)
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    return cdf


def render(tokens: np.ndarray, offsets: np.ndarray, vocabulary: int) -> list:
    """The passages as text: token rank r is the word `w<r>`, single blanks
    between words."""
    words = [""] + [f"w{r}" for r in range(1, vocabulary + 1)]
    toks, cut = tokens.tolist(), offsets.tolist()
    pick = operator.itemgetter  # a passage's words in one call (it has 8 tokens or more)
    return [" ".join(pick(*toks[a:b])(words)) for a, b in zip(cut[:-1], cut[1:])]


def generate(cfg: dict, sizes: dict, seed: int) -> dict:
    """One fixed corpus from the configuration's `corpus_seed` (block by
    block from spawned generators, so threads can draw side by side and the
    result does not depend on timing): passage lengths log-normal with the
    stated mean, clipped; tokens by inverse CDF of the word law. The pool
    is drawn from `corpus_seed` too (the source's query set is as fixed as
    its passages; with a pool a seed the cell's p50 spread by 2.55% over
    six chip runs, past half its bound): a query is t distinct tokens drawn
    uniformly from the token list of one uniformly drawn passage, t by the
    stated shares. The run's seed draws each client's order through the
    pool (harness/loadgen.py) and nothing here."""
    g = cfg["generator"]
    rows, vocabulary = int(sizes["rows"]), int(sizes["vocabulary"])
    cdf = word_law(vocabulary, float(g["rank_shift"]))
    root = np.random.SeedSequence([int(g["corpus_seed"]), 41])
    blocks = root.spawn((rows + GEN_BLOCK - 1) // GEN_BLOCK)
    mu = np.log(float(g["length_mean"])) - float(g["length_sigma"]) ** 2 / 2.0

    def draw(i: int):
        r = np.random.default_rng(blocks[i])
        n = min(GEN_BLOCK, rows - i * GEN_BLOCK)
        lens = np.clip(np.rint(r.lognormal(mu, float(g["length_sigma"]), n)), g["length_min"], g["length_max"])
        lens = lens.astype(np.int64)
        ranks = np.searchsorted(cdf, r.random(int(lens.sum())), side="right") + 1
        return lens, np.minimum(ranks, vocabulary).astype(np.int32)

    with ThreadPoolExecutor(max_workers=8) as pool:
        parts = list(pool.map(draw, range(len(blocks))))
    lens = np.concatenate([p[0] for p in parts])
    tokens = np.concatenate([p[1] for p in parts])
    offsets = np.concatenate([[0], np.cumsum(lens)])

    rng = np.random.default_rng(np.random.SeedSequence([int(g["corpus_seed"]), 43]))
    shares = g["query_terms"]
    counts = rng.choice([int(t) for t in shares], size=int(sizes["pool"]), p=[float(p) for p in shares.values()])
    queries = []
    for t in counts.tolist():
        d = int(rng.integers(0, rows))
        mine = tokens[offsets[d] : offsets[d + 1]]
        picked = list(dict.fromkeys(mine[rng.permutation(mine.size)].tolist()))[:t]
        queries.append(" ".join(f"w{r}" for r in picked))
    return {"tokens": tokens, "offsets": offsets, "bodies": render(tokens, offsets, vocabulary), "queries": queries}


def pool(cfg: dict, data: dict) -> list:
    """What the load generator binds to `$q`, one query text a pool entry."""
    return list(data["queries"])


# ------------------------------------------------------------------ reference
class Inverted:
    """term -> sorted doc ids and tfs, with the document lengths: what a
    BM25 score of any (query, passage) is computed from."""

    def __init__(self, bodies: list):
        words, docs = [], []
        for body in bodies:
            mine = body.lower().split()
            words += mine
            docs.append(len(mine))
        self.lengths = np.asarray(docs, dtype=np.float64)
        self.vocab, term = np.unique(np.asarray(words, dtype=object).astype(str), return_inverse=True)
        self._build(term, np.repeat(np.arange(len(bodies)), docs))

    @classmethod
    def of_tokens(cls, tokens: np.ndarray, offsets: np.ndarray) -> "Inverted":
        """The same index from the generator's token ranks (the word `w<r>`
        tokenises to itself), without a pass over 28M Python strings."""
        self = cls.__new__(cls)
        lens = np.diff(offsets)
        self.lengths = lens.astype(np.float64)
        seen = np.zeros(int(tokens.max(initial=0)) + 1, dtype=bool)
        seen[tokens] = True
        ranks = np.flatnonzero(seen)
        self.vocab = np.asarray([f"w{r}" for r in ranks.tolist()])
        order = np.argsort(self.vocab, kind="stable")  # by word, as the text form sorts
        term_of_rank = np.zeros(seen.size, dtype=np.int64)
        term_of_rank[ranks[order]] = np.arange(order.size)
        self.vocab = self.vocab[order]
        self._build(term_of_rank[tokens], np.repeat(np.arange(lens.size), lens))
        return self

    def _build(self, term: np.ndarray, doc: np.ndarray) -> None:
        key = np.sort(term.astype(np.int64) << 32 | doc)
        first = np.concatenate([[True], key[1:] != key[:-1]])
        at = np.flatnonzero(first)
        self.post_doc = (key[at] & 0xFFFFFFFF).astype(np.int64)
        self.post_tf = np.diff(np.concatenate([at, [key.size]])).astype(np.float64)
        self.indptr = np.searchsorted(key[at] >> 32, np.arange(len(self.vocab) + 1))
        self.docs = float(len(self.lengths))
        self.avg_len = max(float(self.lengths.sum()) / max(self.docs, 1.0), 1e-6)

    def lists(self, query: str):
        """[(doc ids, tfs)] of the query's distinct terms, rarest first;
        None where a term is in no passage."""
        out = []
        for w in dict.fromkeys(query.lower().split()):
            i = int(np.searchsorted(self.vocab, w))
            if i >= len(self.vocab) or self.vocab[i] != w:
                return None
            out.append((self.post_doc[self.indptr[i] : self.indptr[i + 1]], self.post_tf[self.indptr[i] : self.indptr[i + 1]]))
        return sorted(out, key=lambda lt: lt[0].size) or None

    def tfs_of(self, lists, docs: np.ndarray) -> np.ndarray:
        """[docs, terms] term frequencies (0: the passage lacks the term)."""
        out = np.zeros((docs.size, len(lists)))
        for j, (d, f) in enumerate(lists):
            pos = np.minimum(np.searchsorted(d, docs), d.size - 1)
            out[:, j] = np.where(d[pos] == docs, f[pos], 0.0)
        return out

    def scores(self, lists, docs: np.ndarray, tfs: np.ndarray, k1: float, b: float, rounded=None) -> np.ndarray:
        """Upstream's BM25 (core/src/idx/ft/scorer.rs): idf ln(1 + (N - df +
        0.5) / (df + 0.5)), tf (k1 + 1) / (tf + k1 (1 - b + b len / avg)),
        summed over the terms, in float64; with `rounded`, every
        intermediate is passed through it (the control's precision)."""
        r = rounded or (lambda x: x)
        df = np.asarray([d.size for d, _ in lists], dtype=np.float64)
        idf = r(np.log1p((self.docs - df + 0.5) / (df + 0.5)))
        norm = r(k1 * r(1.0 - b + r(b * r(self.lengths[docs] / self.avg_len))))[:, None]
        tf = r(tfs)
        per_term = r(r(idf[None, :] * r(tf * r(k1 + 1.0))) / r(tf + norm))
        total = np.zeros(docs.size)
        for j in range(per_term.shape[1]):
            total = r(total + per_term[:, j])
        return total

    def matches(self, lists) -> np.ndarray:
        docs = lists[0][0]
        for d, _ in lists[1:]:
            pos = np.minimum(np.searchsorted(d, docs), d.size - 1)
            docs = docs[d[pos] == docs]
        return docs


def as_bfloat16(x) -> np.ndarray:
    """Values as bfloat16 holds them (round to nearest even on the upper 16
    bits of the float32), back in float64."""
    bits = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def reference(cfg: dict, data: dict) -> dict:
    """Per pool query: the passages holding every term, scored, the best k
    by (score descending, doc id ascending) with their float64 scores and
    the same passages' bfloat16-scored control; the index is kept so that
    `check` can score any passage a search returns. Also leaves the pool's
    means for the kernel's count in `data["shapes"]` (real list lengths, no
    padding)."""
    index = Inverted.of_tokens(data["tokens"], data["offsets"])
    k, k1, b = int(cfg["k"]), float(cfg["k1"]), float(cfg["b"])
    answers, cands, looks, matched = [], [], [], []
    for q in data["queries"]:
        lists = index.lists(q)
        docs = index.matches(lists)
        tfs = index.tfs_of(lists, docs)
        s = index.scores(lists, docs, tfs, k1, b)
        top = np.lexsort((docs, -s))[:k]
        answers.append({
            "ids": docs[top], "scores": s[top], "matches": int(docs.size),
            "control": index.scores(lists, docs[top], tfs[top], k1, b, rounded=as_bfloat16),
        })
        cands.append(lists[0][0].size)
        looks.append(lists[0][0].size * (len(lists) - 1))
        matched.append(docs.size)
    data["shapes"] = {
        "candidates_mean": float(np.mean(cands)), "lookups_mean": float(np.mean(looks)),
        "matches_mean": float(np.mean(matched)), "tf_bytes": 1 if index.post_tf.max(initial=0) <= 255 else 2,
        "k": k,
    }
    return {"index": index, "answers": answers, "queries": list(data["queries"])}


# ------------------------------------------------------------------ load
def load(ds, cfg: dict, data: dict, execute_ok) -> dict:
    """DEFINE the analyzer, the table and its SEARCH index, then INSERT the
    passages through the embedded entry point in batches; passages
    acknowledged and INSERT seconds."""
    tb, field, bodies = cfg["table"], cfg["field"], data["bodies"]
    for ddl in cfg["ddl"]:
        execute_ok(ds, ddl)
    secs = 0.0
    for i in range(0, len(bodies), INGEST_BATCH):
        rows = [{"id": i + j, field: body} for j, body in enumerate(bodies[i : i + INGEST_BATCH])]
        t0 = time.perf_counter()
        execute_ok(ds, f"INSERT INTO {tb} $rows RETURN NONE", {"rows": rows})
        secs += time.perf_counter() - t0
    return {"acknowledged": len(bodies), "insert_s": secs, "unit": "passages"}


def count_sql(cfg: dict) -> list:
    return [(f"SELECT count() AS c FROM {cfg['table']} GROUP ALL", None)]


def release(data: dict) -> None:
    """The corpus has been loaded and referred to: give its bytes back."""
    for key in ("tokens", "offsets", "bodies"):
        data.pop(key, None)


def wait_background(ds, cfg: dict, timeout: float) -> dict:
    """Wait for the ladder's background compiles; what the mirror's
    generation looks like, for the phase line."""
    from surrealdb_tpu import bg

    t0 = time.perf_counter()
    if not bg.wait_idle(timeout, owner=id(ds)):
        raise RuntimeError(f"background tasks still running after {timeout:.0f}s")
    line = {"warm_wait_s": time.perf_counter() - t0}
    mirror = ds.index_stores.get(cfg["ns"], cfg["db"], cfg["table"], cfg["index"])
    gen = getattr(mirror, "generation", None)
    if gen is not None:  # a program that keeps generations says what this one holds
        g = gen()
        line.update(postings=int(g.dids.size), posting_slots=int(g.p_slots), doc_slots=int(g.d_slots),
                    head_rows=int(g.head_tids.size), sparse_steps=list(g.steps))
    return {"state": {}, "line": line}


def kernel_shapes(cfg: dict, data: dict, state: dict) -> dict:
    """What one search needs from the device: the pool's means, from the
    reference's index."""
    return dict(data["shapes"])


# ------------------------------------------------------------------ check
def check(cfg: dict, ref: dict, records: list) -> dict:
    """Every answer of the window against the reference. `and_violations`:
    returned passages that lack a term of the query. `short_answers`:
    answers with fewer than k passages while k match. `missed_better`:
    passages of the reference's best k that are absent although their score
    exceeds the served last score by more than the tolerance (two passages
    closer than that may stand in either order). `score_rel_err`: the
    largest |served - reference| / reference over every returned passage,
    the reference scoring that very passage in float64; beside it the
    bfloat16-scored control's reading over the reference's own best k.
    `numbers` are [name, value, relation, limit]."""
    lim, k = cfg["correct"], int(cfg["k"])
    k1, b, field, tol = float(cfg["k1"]), float(cfg["b"]), cfg["score_field"], float(cfg["correct"]["score_rel_err_max"])
    index = ref["index"]
    lists_of = {}
    violations = short = missed = pairs = answers = 0
    err = err_control = 0.0
    for r in records:
        if r["status"] != "OK":
            continue
        answers += 1
        truth = ref["answers"][r["q"]]
        ids = np.asarray([i if isinstance(i, int) else -1 for i in r["ids"]], dtype=np.int64)
        served = np.asarray(r["values"].get(field, []), dtype=np.float64)
        short += ids.size < min(k, truth["matches"])
        if served.size != ids.size:
            violations += ids.size  # a passage without its score cannot be held to anything
            continue
        lists = lists_of.get(r["q"])
        if lists is None:
            lists = lists_of[r["q"]] = index.lists(ref["queries"][r["q"]])
        inside = (ids >= 0) & (ids < index.lengths.size)
        tfs = index.tfs_of(lists, np.where(inside, ids, 0))
        holds = inside & (tfs > 0).all(axis=1)
        violations += int((~holds).sum())
        if holds.any():
            exact = index.scores(lists, ids[holds], tfs[holds], k1, b)
            err = max(err, float(np.max(np.abs(served[holds] - exact) / exact)))
            pairs += int(holds.sum())
        last = served.min() if served.size >= min(k, truth["matches"]) and served.size else -np.inf
        absent = ~np.isin(truth["ids"], ids)
        missed += int((absent & (truth["scores"] > last * (1.0 + tol))).sum())
        if truth["scores"].size:
            err_control = max(err_control, float(np.max(np.abs(truth["control"] - truth["scores"]) / truth["scores"])))
    return {
        "numbers": [
            ["and_violations", violations, "<=", lim["and_violations_max"]],
            ["short_answers", int(short), "<=", lim["short_answers_max"]],
            ["missed_better", missed, "<=", lim["missed_better_max"]],
            ["score_rel_err", err if pairs else float("inf"), "<=", lim["score_rel_err_max"]],
        ],
        "control": {"score_rel_err_bfloat16": err_control},
        "metrics": {},
        "compared": {"answers": answers, "pairs": pairs},
    }

