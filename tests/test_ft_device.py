"""The full-text device route (ISSUE 38): `@N@` with the score order pushed
down and a LIMIT is ONE dispatch of ops/bm25.py::bm25_and_topk over the
mirror's postings on the device, held here to the benchmark's plain
reference (benchmarks/deployments/fulltext_bm25.py: NumPy, float64, no
JAX, nothing of surrealdb_tpu) on seeded Zipf corpora, route by route."""

import importlib.util
import os
import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import bg, cnf, compile_log, telemetry, tracing
from surrealdb_tpu.dbs.dispatch import DispatchQueue
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.idx import ft_mirror
from surrealdb_tpu.kvs.ds import Datastore
from surrealdb_tpu.ops import bm25

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-6  # float32 scores against the float64 reference, relative
DOCS, VOCAB, K1, B = 4000, 5000, 1.2, 0.75
SQL = "SELECT id, search::score(1) AS s FROM passage WHERE body @1@ $q ORDER BY s DESC LIMIT {k}"
DDL = (
    "DEFINE ANALYZER simple TOKENIZERS blank FILTERS lowercase; DEFINE TABLE passage SCHEMALESS; "
    "DEFINE INDEX passage_body ON passage FIELDS body SEARCH ANALYZER simple BM25;"
)


def load_reference():
    path = os.path.join(ROOT, "benchmarks", "deployments", "fulltext_bm25.py")
    spec = importlib.util.spec_from_file_location("fulltext_bm25_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()


def zipf_bodies(n: int, seed: int, start: int = 0) -> dict:
    """{id: text} of `n` passages of ~30 words `w<rank>` under p(r) ~ 1 / (r + 2.7)."""
    rng = np.random.default_rng(seed)
    cdf = REF.word_law(VOCAB, 2.7)
    out = {}
    for i in range(start, start + n):
        size = int(np.clip(rng.lognormal(np.log(30), 0.5), 8, 128))
        ranks = np.searchsorted(cdf, rng.random(size), side="right") + 1
        out[i] = " ".join(f"w{r}" for r in ranks.tolist())
    return out


class Corpus:
    """A datastore with the index, the passages as loaded, and the
    reference's index over the same passages (rebuilt when they change)."""

    def __init__(self):
        self.ds = Datastore("memory")
        self.s = Session.owner("t", "t")
        self.run(DDL)
        self.bodies = {}
        self.insert(zipf_bodies(DOCS, 11))

    def run(self, sql, **vars):
        out = self.ds.execute(sql, self.s, vars=vars or None)
        assert all(r["status"] == "OK" for r in out), out
        return out[-1]["result"]

    def insert(self, bodies: dict) -> None:
        self.run("INSERT INTO passage $rows RETURN NONE", rows=[{"id": i, "body": b} for i, b in bodies.items()])
        self.bodies.update(bodies)
        self.index = None

    def reference(self):
        if self.index is None:
            self.ids = np.asarray(sorted(self.bodies))
            self.index = REF.Inverted([self.bodies[i] for i in self.ids.tolist()])
        return self.index

    def truth(self, query: str, k: int):
        """(ids, scores, matches) of the reference's best k."""
        index = self.reference()
        lists = index.lists(query)
        if lists is None:
            return [], np.empty(0), 0
        docs = index.matches(lists)
        s = index.scores(lists, docs, index.tfs_of(lists, docs), K1, B)
        top = np.lexsort((docs, -s))[:k]
        return self.ids[docs[top]].tolist(), s[top], int(docs.size)

    def exact(self, query: str, ids: list) -> np.ndarray:
        """The reference's score of each passage of `ids` (0 where one lacks a term)."""
        index = self.reference()
        lists = index.lists(query)
        docs = np.searchsorted(self.ids, np.asarray(ids, dtype=np.int64))
        tfs = index.tfs_of(lists, docs)
        return np.where((tfs > 0).all(axis=1), index.scores(lists, docs, tfs, K1, B), 0.0)

    def ask(self, query: str, k: int = 10, tid=None):
        """(ids, scores, spans by name) of the statement."""
        if tid is None:
            rows, spans = self.run(SQL.format(k=k), q=query), []
        else:
            with tracing.request("ft", trace_id=tid):
                rows = self.run(SQL.format(k=k), q=query)
            spans = tracing.get_trace(tid)["spans"]
        by_name = {}
        for sp in spans:
            by_name.setdefault(sp["name"], []).append(sp["labels"])
        return [int(r["id"].id) for r in rows], np.asarray([r["s"] for r in rows], dtype=np.float64), by_name

    def holds(self, query: str, k: int = 10, tid=None):
        """The served answer is the reference's: every score within TOL of
        the reference's for that very passage, best first, as many as match
        up to k, and no passage left out that scores above the served last
        by more than TOL (passages closer than that may swap)."""
        ids, scores, spans = self.ask(query, k, tid)
        want, want_scores, matches = self.truth(query, k)
        assert len(ids) == min(k, matches) == len(want), (query, ids, want)
        if ids:
            exact = self.exact(query, ids)
            assert (exact > 0).all(), (query, ids)
            np.testing.assert_allclose(scores, exact, rtol=TOL)
            assert (np.diff(scores) <= 0).all()
            for i, (a, b_) in enumerate(zip(ids, want)):
                assert a == b_ or abs(scores[i] - want_scores[i]) <= TOL * want_scores[i], (query, i, ids, want)
        return ids, scores, spans

    def query_of(self, rng, terms: int) -> str:
        """`terms` distinct words of one passage: it matches at least that one."""
        while True:
            words = list(dict.fromkeys(self.bodies[int(rng.choice(sorted(self.bodies)))].split()))
            if len(words) >= terms:
                return " ".join(rng.permutation(words)[:terms].tolist())

    def mirror(self):
        return self.ds.index_stores.get("t", "t", "passage", "passage_body")


@pytest.fixture(scope="module")
def corpus():
    c = Corpus()
    c.ask("w1 w2")  # builds the mirror, uploads the generation, starts the ladder's warm
    assert bg.wait_idle(300, owner=id(c.ds))
    yield c
    c.ds.close()


@pytest.fixture()
def fresh(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    telemetry.reset()
    tracing.store_reset()
    yield
    tracing.store_reset()


def routes() -> dict:
    return {dict(k)["route"]: int(v) for k, v in telemetry.counters_matching("ft_search_route").items()}


# ------------------------------------------------------------------ the device route against the reference
@pytest.mark.parametrize("terms", [1, 2, 3, 4, 8])
def test_the_device_route_answers_as_the_reference_does(corpus, fresh, terms):
    rng = np.random.default_rng(100 + terms)
    before = corpus.ds.dispatch.stats()["submitted"]
    for i in range(12):
        _, _, spans = corpus.holds(corpus.query_of(rng, terms), tid=f"ref-{terms}-{i}")
        (prep,) = spans["ft_prepare"]
        assert prep["route"] == "device" and int(prep["terms"]) == terms
        assert spans["dispatch_launch"][0]["slots"] == prep["slots"]  # the launch says its step
    assert routes() == {"device": 12}
    assert corpus.ds.dispatch.stats()["submitted"] - before == 12  # one dispatch a statement


@pytest.mark.parametrize("k", [1, 100])
def test_any_k_of_the_ladder(corpus, fresh, k):
    rng = np.random.default_rng(200 + k)
    for _ in range(6):
        corpus.holds(corpus.query_of(rng, 2), k=k)
    corpus.holds("w1 w2 w3", k=k)
    assert routes() == {"device": 7}


def test_fewer_than_k_matches_come_back_short_not_padded(corpus, fresh):
    rng = np.random.default_rng(7)
    short = 0
    for _ in range(40):
        q = corpus.query_of(rng, 4)
        ids, _, _ = corpus.holds(q)
        short += len(ids) < 10
    assert short > 10  # four words of one passage seldom meet in ten
    assert corpus.ask("w1 nosuchword")[0] == [] and corpus.ask("nosuchword")[0] == []
    assert routes() == {"device": 42}


@pytest.mark.parametrize("branch", ["all_frequent", "none_frequent", "mixed"])
def test_every_branch_of_the_layout(corpus, fresh, branch):
    """All terms in the head: the dense step over the doc slots. None: a
    sparse step, every other term a local list. Mixed: a sparse step whose
    frequent terms are gathers from the head's rows."""
    gen = corpus.mirror().generation()
    assert gen.head_tids.size >= 4 and gen.steps == (1024,) and gen.d_slots == 4096
    head = [w for w, t in corpus.mirror().term_ids.items() if t < gen.n_terms and gen.head_row[t] >= 0]
    rng = np.random.default_rng(5)
    tail = [w for w in corpus.query_of(rng, 12).split() if w not in head]
    queries = {
        "all_frequent": [" ".join(head[:2]), " ".join(head[:4]), " ".join(head[1:4])],
        "none_frequent": [" ".join(tail[:2]), " ".join(tail[:3]), tail[0]],
        "mixed": [f"{head[0]} {tail[0]}", f"{tail[0]} {head[1]} {head[2]} {tail[1]}", f"{head[3]} {tail[2]}"],
    }[branch]
    for i, q in enumerate(queries):
        slots, tn, kk, n, payload, _ = corpus.mirror().place(gen, q.split(), 10)
        assert slots == (gen.d_slots if branch == "all_frequent" else 1024)
        rows = payload[2][:n]
        assert {"all_frequent": (rows >= 0).all(), "none_frequent": (rows < 0).all(),
                "mixed": (rows >= 0).any() and (rows < 0).any()}[branch]
        _, _, spans = corpus.holds(q, tid=f"branch-{branch}-{i}")
        assert int(spans["ft_prepare"][0]["slots"]) == slots


def test_ties_go_to_the_lower_doc_id_as_on_the_host(fresh):
    ds, s = Datastore("memory"), Session.owner("t", "t")
    try:
        ds.execute(DDL, s)
        ds.execute("INSERT INTO passage $rows RETURN NONE", s,
                   vars={"rows": [{"id": i, "body": "same words here"} for i in range(40)]})
        out = ds.execute(SQL.format(k=10), s, vars={"q": "same words"})
        assert [r["id"].id for r in out[-1]["result"]] == list(range(10))
        assert routes() == {"device": 1}
    finally:
        ds.close()


# ------------------------------------------------------------------ the other routes
def test_nine_terms_take_the_host_route(corpus, fresh):
    rng = np.random.default_rng(9)
    before = corpus.ds.dispatch.stats()["submitted"]
    _, _, spans = corpus.holds(corpus.query_of(rng, 9), tid="nine")
    assert spans["ft_prepare"] == [{"route": "host", "terms": "9", "slots": "0"}]
    assert routes() == {"host": 1} and corpus.ds.dispatch.stats()["submitted"] == before


@pytest.mark.parametrize("sql", [
    "SELECT id, search::score(1) AS s FROM passage WHERE body @1@ $q ORDER BY s DESC",  # no LIMIT
    "SELECT id, search::score(1) AS s FROM passage WHERE body @1@ $q LIMIT 10",  # no pushed order
    "SELECT id, search::score(1) AS s FROM passage WHERE body @1@ $q ORDER BY s DESC LIMIT 2000",  # k past the ladder
])
def test_without_a_pushed_order_and_a_limit_the_host_serves(corpus, fresh, sql):
    rows = corpus.run(sql, q="w1 w2")
    want, _, matches = corpus.truth("w1 w2", 2000)
    assert routes() == {"host": 1}
    if "ORDER" in sql:
        assert [r["id"].id for r in rows] == want[: len(rows)] and len(rows) == min(matches, 2000)
    else:
        assert {r["id"].id for r in rows} <= set(want)


def test_a_score_reads_the_same_whichever_route_served_bit_for_bit(corpus, fresh):
    """The device picks the k; their scores are the host scorer's, so a
    LIMIT changes no score (cluster mode's shards, which take the host
    route, answer byte for byte as one node does)."""
    rng = np.random.default_rng(77)
    for terms in (1, 2, 3, 4):
        q = corpus.query_of(rng, terms)
        ids, scores, _ = corpus.ask(q, k=10)
        rows = corpus.run("SELECT id, search::score(1) AS s FROM passage WHERE body @1@ $q ORDER BY s DESC", q=q)
        assert [r["id"].id for r in rows[: len(ids)]] == ids
        assert [r["s"] for r in rows[: len(ids)]] == scores.tolist()
    assert routes() == {"device": 4, "host": 4}


def test_the_device_is_disabled_by_the_one_switch(corpus, fresh, monkeypatch):
    monkeypatch.setattr(cnf, "TPU_DISABLE", True)
    corpus.holds("w1 w2")
    assert routes() == {"host": 1}


def test_rows_a_residual_where_drops_are_made_up_by_the_host(corpus, fresh):
    """The device returns the LIMIT's k; where the executor drops some of
    them the iterator goes on in the host route's order, so the answer is
    what it was before the device route: exact."""
    want, _, _ = corpus.truth("w1 w2", 400)
    even = [i for i in want if i % 2 == 0][:10]
    rows = corpus.run(
        "SELECT id, search::score(1) AS s FROM passage WHERE body @1@ $q AND id.id() % 2 = 0 ORDER BY s DESC LIMIT 10",
        q="w1 w2")
    assert [r["id"].id for r in rows] == even
    assert routes() == {"device": 1}


def test_a_transactions_own_writes_take_the_kv_route(corpus, fresh):
    out = corpus.ds.execute(
        "BEGIN; CREATE passage:900001 SET body = 'w1 w2 zulu'; "
        + SQL.format(k=10).replace("$q", "'w1 zulu'") + "; COMMIT;", corpus.s)
    assert [r["id"].id for r in out[-1]["result"]] == [900001]  # seen before the commit, by the KV search
    assert routes() == {"kv": 1}
    corpus.bodies[900001], corpus.index = "w1 w2 zulu", None
    assert corpus.holds("w1 zulu")[0] == [900001]  # and after it by the mirror's next generation
    assert routes() == {"kv": 1, "device": 1}


# ------------------------------------------------------------------ writes: overlay, a new generation
def test_update_delete_and_a_second_bulk_batch_are_seen_by_the_next_search():
    c = Corpus()
    try:
        c.ask("w1 w2")
        first = c.mirror().generation()
        top, _, _ = c.ask("w1 w2")
        # UPDATE the best match away from the query, DELETE the second, add a batch that holds new matches
        c.run(f"UPDATE passage:{top[0]} SET body = 'w9 w10 w11'")
        c.bodies[top[0]] = "w9 w10 w11"
        c.run(f"DELETE passage:{top[1]}")
        del c.bodies[top[1]]
        c.index = None
        assert top[0] not in c.holds("w1 w2")[0] and top[1] not in c.ask("w1 w2")[0]
        second = c.mirror().generation()
        assert second.serial > first.serial and not c.mirror().overlay  # folded into the base
        c.insert(zipf_bodies(1500, 12, start=DOCS))
        c.insert({DOCS + 1500: "w1 w2 " * 20})  # a tf past the first batch's largest
        telemetry.reset()
        rng = np.random.default_rng(3)
        for terms in (1, 2, 3, 4):
            for _ in range(5):
                c.holds(c.query_of(rng, terms))
        assert DOCS + 1500 in c.holds("w1 w2")[0]
        assert c.mirror().generation().serial > second.serial
        assert routes() == {"device": 21}
        # cluster mode's statistics ride along as values
        dc, tl, df = c.mirror().term_stats(["w1", "w2", "nosuchword"])
        index = c.reference()
        assert (dc, tl) == (int(index.docs), float(index.lengths.sum())) and df["nosuchword"] == 0
        assert df["w1"] == index.lists("w1")[0][0].size
    finally:
        c.ds.close()


def test_a_tf_past_a_byte_widens_the_postings_type():
    ds, s = Datastore("memory"), Session.owner("t", "t")
    try:
        ds.execute(DDL, s)
        rows = [{"id": i, "body": "often " * (300 if i == 3 else 1) + f"rare{i}"} for i in range(8)]
        ds.execute("INSERT INTO passage $rows RETURN NONE", s, vars={"rows": rows})
        out = ds.execute(SQL.format(k=10), s, vars={"q": "often"})
        gen = ds.index_stores.get("t", "t", "passage", "passage_body").generation()
        assert gen.tf_dtype == np.uint16 and int(gen.tfs.max()) == 300
        index = REF.Inverted([r["body"] for r in rows])
        lists = index.lists("often")
        docs = index.matches(lists)
        want = index.scores(lists, docs, index.tfs_of(lists, docs), K1, B)
        got = {r["id"].id: r["s"] for r in out[-1]["result"]}
        np.testing.assert_allclose([got[i] for i in docs.tolist()], want, rtol=TOL)
    finally:
        ds.close()


# ------------------------------------------------------------------ the ladder: launches, compiles, precision
class HeldBuckets(DispatchQueue):
    """A dispatch queue in which the first launch of every bucket waits:
    what is submitted meanwhile queues behind it, bucket by bucket."""

    def __init__(self):
        super().__init__()
        self.release, self.seen, self.guard = threading.Event(), set(), threading.Lock()

    def submit(self, key, payload, runner):
        def held(payloads):
            with self.guard:
                first = key not in self.seen
                self.seen.add(key)
            if first:
                assert self.release.wait(60)
            return runner(payloads)

        return super().submit(key, payload, held)

    def queued(self) -> int:
        return sum(len(b.queue) for b in list(self._buckets.values()))


@pytest.mark.parametrize("steps", ["one_step", "two_steps"])
def test_riders_of_one_step_share_a_launch_and_those_of_two_never_do(corpus, fresh, monkeypatch, steps):
    gen = corpus.mirror().generation()
    head = [w for w, t in corpus.mirror().term_ids.items() if t < gen.n_terms and gen.head_row[t] >= 0]
    rng = np.random.default_rng(17)
    tail = [w for w in corpus.query_of(rng, 12).split() if w not in head]
    sparse = [f"{tail[i]} {head[i]}" for i in range(4)]  # two term slots, the 1,024 step
    dense = [f"{head[i]} {head[i + 1]}" for i in range(3)]  # two term slots, the dense step
    queries = sparse + (dense if steps == "two_steps" else [])
    alone = [corpus.ask(q)[0] for q in queries]
    held, got = HeldBuckets(), {}
    monkeypatch.setattr(corpus.ds, "dispatch", held)

    def rider(i):
        got[i] = corpus.ask(queries[i], tid=f"rider-{steps}-{i}")

    threads = [threading.Thread(target=rider, args=(i,)) for i in range(len(queries))]
    for t in threads:
        t.start()
    buckets = 2 if steps == "two_steps" else 1
    deadline = time.monotonic() + 60
    while held.queued() < len(queries) - buckets and time.monotonic() < deadline:
        time.sleep(0.002)
    held.release.set()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads) and len(got) == len(queries)
    assert [got[i][0] for i in range(len(queries))] == alone
    # a bucket's leader went alone; everything queued behind it rode ONE launch
    assert held.width_distribution() == ({1: 2, 2: 1, 3: 1} if steps == "two_steps" else {1: 1, 3: 1})
    assert len(held._buckets) == buckets
    slots = {got[i][2]["dispatch_launch"][0]["slots"] for i in range(len(queries))}
    assert slots == ({"1024", str(gen.d_slots)} if steps == "two_steps" else {"1024"})


def test_after_the_warm_no_statement_compiles(corpus, fresh):
    assert bg.wait_idle(300, owner=id(corpus.ds))
    rng = np.random.default_rng(23)
    since = time.time()
    for i in range(200):
        corpus.ask(corpus.query_of(rng, 1 + i % 4))
    corpus.ask("w1 w2 w3 w4 w5 w6")  # eight term slots, the dense step
    assert compile_log.events(since=since) == []
    filled = telemetry.get_counter("ft_postings") / telemetry.get_counter("ft_slots")
    assert 0.0 < filled < 1.0  # real candidates over padded slots
    assert routes() == {"device": 201}


def test_the_warm_covers_the_ladder_and_nothing_else(corpus):
    gen = corpus.mirror().generation()
    shapes = {e["shape"] for e in compile_log.events() if e["subsystem"] == "bm25"}
    want = {
        "x".join(str(v) for v in gen.shape_key(slots, tn, riders, 16))
        for slots in gen.steps + (gen.d_slots,) for tn in bm25.TERM_SLOTS for riders in (1, bm25.RIDER_TILE)
    }
    assert want <= shapes
    assert bm25.term_slots(1) == 2 and bm25.term_slots(8) == 8 and bm25.term_slots(9) is None
    assert bm25.k_slots(10) == 16 and bm25.k_slots(1024) == 1024 and bm25.k_slots(1025) is None
    assert bm25.sparse_steps(0) == (1024,) and bm25.sparse_steps(5000) == (1024, 2048, 4096, 8192)


def test_a_bfloat16_scored_control_differs_beyond_the_tolerance(corpus):
    gen = corpus.mirror().generation()
    rng = np.random.default_rng(31)
    worst = {"float32": 0.0, "bfloat16": 0.0}
    for _ in range(10):
        q = corpus.query_of(rng, 3)
        slots, tn, kk, n, payload, _ = corpus.mirror().place(gen, q.split(), 10)
        for dtype in worst:
            out = bm25.bm25_and_topk(*gen.device(), bm25.pack_riders([payload], 1, tn, K1, B),
                                     slots=slots, k=kk, score_dtype=dtype)
            vals, dids, _ = (a[0] for a in bm25.unpack_results(out, kk))
            ids = dids[dids >= 0].tolist()
            exact = corpus.exact(q, ids)
            worst[dtype] = max(worst[dtype], float(np.max(np.abs(vals[: len(ids)] - exact) / exact)))
    assert worst["float32"] <= TOL < 1e-3 < worst["bfloat16"]


# ------------------------------------------------------------------ no search holds the mirror's lock while it computes
@pytest.mark.parametrize("held_route", ["device", "host"])
def test_two_searches_of_one_index_overlap(corpus, fresh, monkeypatch, held_route):
    """One search is stopped in the middle of its computation (the kernel's
    launch, or the host route's scoring); a search of the other route
    starts and ends meanwhile."""
    inside, release = threading.Event(), threading.Event()
    if held_route == "device":
        real = ft_mirror._launch

        def gate(*a, **kw):
            inside.set()
            assert release.wait(60)
            return real(*a, **kw)

        monkeypatch.setattr(ft_mirror, "_launch", gate)
        slow, quick = "w1 w2", "w1 w2 w3 w4 w5 w6 w7 w8 w9"
    else:
        real = bm25.bm25_scores_host

        def gate(*a, **kw):
            inside.set()
            assert release.wait(60)
            return real(*a, **kw)

        monkeypatch.setattr(bm25, "bm25_scores_host", gate)
        slow, quick = "w1 w2 w3 w4 w5 w6 w7 w8 w9", "w3 w4"
    got = {}
    t = threading.Thread(target=lambda: got.update(slow=corpus.ask(slow)[0]))
    t.start()
    assert inside.wait(60)
    if held_route == "host":
        monkeypatch.setattr(bm25, "bm25_scores_host", real)
    quick_ids = corpus.ask(quick)[0]  # returns while the other search is still inside
    assert t.is_alive() and "slow" not in got
    release.set()
    t.join(60)
    assert not t.is_alive()
    assert quick_ids == corpus.truth(quick, 10)[0] and got["slow"] == corpus.truth(slow, 10)[0]


# ------------------------------------------------------------------ the build: whole arrays
def test_the_packed_chunks_decode_as_one_word_array():
    from surrealdb_tpu.idx.ft_index import pack_plist, unpack_plist

    rng = np.random.default_rng(41)
    chunks, tails, vals = [], [], []
    for kv_tid, start in [(0, 0), (0, 500), (3, 0), (7, 500), (9, 0)]:
        n = int(rng.integers(1, 40))
        offs = np.sort(rng.choice(400, size=n, replace=False)).astype(np.uint32)
        tfs = rng.integers(1, 9, size=n).astype(np.uint32)
        chunks.append((kv_tid, start, offs, tfs))
        tails.append(kv_tid.to_bytes(8, "big"))
        vals.append(pack_plist(start, offs, tfs))
    local_of = np.asarray([0, -1, -1, 1, -1, -1, -1, -1, -1, 2])  # the KV's term 7 has no live document
    tid, did, tf = ft_mirror._decode_chunks(tails, vals, local_of)
    want = [(local_of[k], unpack_plist(v)) for (k, _, _, _), v in zip(chunks, vals) if local_of[k] >= 0]
    assert tid.tolist() == [t for t, (d, _) in want for _ in d]
    assert did.tolist() == [int(x) for _, (d, _) in want for x in d]
    assert tf.tolist() == [int(x) for _, (_, f) in want for x in f]
    assert [a.size for a in ft_mirror._decode_chunks([], [], local_of)] == [0, 0, 0]


def test_the_threshold_and_the_per_shape_scorers_are_gone():
    assert not hasattr(cnf, "TPU_FT_ONDEVICE_THRESHOLD")
    assert not hasattr(bm25, "bm25_scores") and not hasattr(bm25, "bm25_topk")
    with open(os.path.join(ROOT, "surrealdb_tpu", "cnf.py")) as f:
        assert "FT_ONDEVICE" not in f.read()
