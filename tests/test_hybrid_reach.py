"""The north star's hybrid statement on the normal path (ISSUE 47): a filtered
kNN whose every hit carries `array::distinct(<2-step chain>)`. A SELECT whose
field list holds such calls and that collected two or more rows projects after
collection, and each chain family runs ONCE for all rows: one test that it
rides, one compiled predicate, one look-up of the operators, one mask, and
every row's frontier to the dispatch queue in one call. On a seeded citation
DAG (the benchmark's own generator at 2,000 papers of 16 dimensions: hubs,
leaves, papers that cite nothing, duplicate records, diamonds) the rows equal
the plain reference's (`benchmarks/deployments/hybrid_knn_reach.py`: NumPy,
nothing of the program): the hits in order, every `ctx` as a whole set, every
`n`."""

import copy
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import cnf, compile_log, telemetry, tracing
from surrealdb_tpu.dbs.dispatch import DispatchQueue
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.idx import graph_csr
from surrealdb_tpu.sql.value import Thing
from surrealdb_tpu.syn.parser import parse_query

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import manifest as mf  # noqa: E402

CELL, CONFIG = "magcite150k.knn2hop_c8", "magcite150k"
SIZES = {"papers": 2000, "cites": 21300, "pool": 24, "centres": 16, "pass_share": 0.5}
SEED = 2**31 + 47
DIM = 16
CHAIN = "->cites->paper->cites->(paper WHERE year >= $q.y)"
ONE_STEP = "->cites->(paper WHERE year >= $q.y)"
BARE = "->cites->paper->cites->paper"


@pytest.fixture(scope="module")
def full_cfg():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg(full_cfg):
    """The configuration at the tests' width: 16 dimensions, the rest as it is."""
    small = copy.deepcopy(full_cfg)
    small["dim"] = DIM
    small["ddl"] = [d.replace("DIMENSION 768", f"DIMENSION {DIM}") for d in small["ddl"]]
    return small


@pytest.fixture(scope="module")
def kind():
    return mf.load_modules(BENCH, "deployments", "KIND")["hybrid_knn_reach"]


@pytest.fixture(scope="module")
def world(cfg, kind):
    data = kind.generate(cfg, SIZES, SEED)
    ref = kind.reference(cfg, data)
    return data, ref, kind.pool(cfg, data)


def execute_ok(ds, sql, vars=None):
    out = ds.execute(sql, Session.owner("bench", "bench"), vars=vars)
    assert all(r["status"] == "OK" for r in out), out
    return out


@pytest.fixture
def served(ds, cfg, kind, world, monkeypatch):
    """The deployment's rows loaded and the loader's probe passed: 32
    statements, every row's set against the reference's as a whole set,
    every statement held to ten set riders and the search's own (an exact
    search of 2,000 rows may be served without a dispatch: 10 or 11, as the
    first statement shows)."""
    telemetry.reset()
    compile_log.reset()
    tracing.store_reset()
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    data, _, pool = world
    load_plain(ds, cfg, data)
    statement = copy.deepcopy(cfg)["statements"]["primary"]
    before = ds.dispatch.stats()["submitted"]
    execute_ok(ds, statement["sql"], {"q": pool[0]})
    statement["dispatches"] = ds.dispatch.stats()["submitted"] - before
    assert statement["dispatches"] in (10, 11)
    probed = kind.probe(ds, {**cfg, "statements": {"primary": statement}}, data, execute_ok)
    assert probed["statements"] == len(pool) and probed["rows"] == 10 * len(pool)
    telemetry.reset()  # the loader's own statements are not the test's
    yield ds
    tracing.store_reset()


def load_plain(ds, cfg, data):
    """The deployment's rows, as its loader inserts them."""
    for ddl in cfg["ddl"]:
        execute_ok(ds, ddl)
    execute_ok(ds, "INSERT INTO paper $rows RETURN NONE", {"rows": [
        {"id": i, "emb": data["corpus"][i].tolist(), "year": int(data["year"][i])} for i in range(data["papers"])]})
    execute_ok(ds, "INSERT RELATION INTO cites $rows RETURN NONE", {"rows": [
        {"in": Thing("paper", int(a)), "out": Thing("paper", int(b))} for a, b in data["pairs"]]})


def spans_of(tid):
    return tracing.get_trace(tid)["spans"]


def ask(ds, sql, q, tid):
    """(rows, the request's spans)"""
    with tracing.request("hybrid", trace_id=tid):
        out = execute_ok(ds, sql, {"q": q})
    return out[-1]["result"], spans_of(tid)


def named(spans, name):
    return [s["labels"] for s in spans if s["name"] == name]


def sweeps(spans):
    """The labels of the set kernel's launches in a request's span tree."""
    return [l for l in named(spans, "dispatch_launch") if "slots" in l]


def ids_of(things) -> list:
    return sorted(int(t.id) for t in things)


# ------------------------------------------------------------------ the graph is what the tests are about
def test_the_seeded_dag_has_hubs_leaves_papers_citing_nothing_duplicates_and_diamonds(world):
    data, ref, _ = world
    pairs, n = data["pairs"], data["papers"]
    assert pairs.shape == (SIZES["cites"], 2) and (pairs[:, 0] > pairs[:, 1]).all()  # a DAG: lower ids only
    refs, cited = np.bincount(pairs[:, 0], minlength=n), np.bincount(pairs[:, 1], minlength=n)
    assert (refs == 0).any() and (refs[data["lo"]:] >= 1).all() and refs.max() >= 40  # papers citing nothing; every hit cites
    assert (cited == 0).any() and cited.max() >= 100  # leaves nobody cites, and hubs
    assert np.unique(pairs[:, 0] * n + pairs[:, 1]).size < pairs.shape[0]  # a paper cites another twice
    cites = ref["cites"]
    diamonds = [p for p in range(data["lo"], n) if np.unique(cites.walks2(p)).size < cites.walks2(p).size]
    assert len(diamonds) > 50  # two walks ending at one paper
    assert (data["year"][1:] >= data["year"][:-1]).all() and int((data["year"] >= data["y"]).sum()) == n // 2


def test_the_reference_s_two_steps_are_a_brute_force_loop_s(world):
    data, ref, _ = world
    out: dict = {}
    for a, b in data["pairs"].tolist():
        out.setdefault(a, []).append(b)
    for p in [0, 1, 17, data["lo"], data["lo"] + 3, data["papers"] - 1] + data["hits"][0].tolist():
        walks = [w for v in out.get(p, []) for w in out.get(v, [])]
        assert sorted(ref["cites"].walks2(p).tolist()) == sorted(walks)
        assert ref["cites"].reach2(p).tolist() == sorted({w for w in walks if data["year"][w] >= data["y"]})


# ------------------------------------------------------------------ the timed statement
def test_the_timed_statement_s_rows_are_the_reference_s(served, cfg, world):
    data, ref, pool = world
    sql = cfg["statements"]["primary"]["sql"]
    for q in range(len(pool)):
        rows, _ = ask(served, sql, pool[q], f"timed-{q}")
        assert [int(r["id"].id) for r in rows] == ref["ids"][q, :10].tolist()  # exact at this size, nearest first
        for r in rows:
            want = ref["cites"].reach2(int(r["id"].id)).tolist()
            assert ids_of(r["ctx"]) == want and len(r["ctx"]) == len(want) == r["n"]
            assert data["year"][int(r["id"].id)] >= data["y"]
            assert abs(r["d"] ** 2 - ref["d2"][q, [int(x) for x in ref["ids"][q]].index(int(r["id"].id))]) < 1e-3


def test_k_hits_are_k_riders_of_one_launch_and_one_group_span(served, cfg, world):
    _, _, pool = world
    sql = cfg["statements"]["primary"]["sql"]
    ask(served, sql, pool[0], "warm")  # compiled
    before, w0 = served.dispatch.stats(), served.dispatch.width_distribution()
    rows, spans = ask(served, sql, pool[1], "one-launch")
    after, w1 = served.dispatch.stats(), served.dispatch.width_distribution()
    assert len(rows) == 10 and w1.get(10, 0) - w0.get(10, 0) == 1  # one batch, ten wide
    assert after["submitted"] - before["submitted"] in (10, 11)  # and the search's own, where it dispatches
    (group,) = named(spans, "graph_reach_group")
    assert {k: group[k] for k in ("rows", "riders", "families", "launches", "form", "filter", "depth")} == {
        "rows": "10", "riders": "10", "families": "1", "launches": "1", "form": "csc", "filter": "fused", "depth": "2"}
    (launch,) = sweeps(spans)
    assert launch["batch"] == "10" and launch["lanes"] == "16"
    # the family's fill says so once; each row's two expressions read the memo
    prepares = named(spans, "graph_prepare")
    assert [p["memo"] for p in prepares].count("fill") == 1 and [p["memo"] for p in prepares].count("hit") == 20
    assert len(named(spans, "graph_filter")) == 1  # one mask for ten rows


class HeldQueue(DispatchQueue):
    """A dispatch queue whose first launch of a group waits: whatever is
    submitted meanwhile queues behind it."""

    def __init__(self):
        super().__init__()
        self.started, self.release = threading.Event(), threading.Event()

    def submit_many(self, key, payloads, runner, **bucket):
        if key[0] != "greach":
            return super().submit_many(key, payloads, runner, **bucket)

        def held(batch):
            if not self.started.is_set():
                self.started.set()
                assert self.release.wait(60)
            return runner(batch)

        return super().submit_many(key, payloads, held, **bucket)

    def queued(self) -> int:
        return sum(len(b.queue) for b in list(self._buckets.values()))


def test_two_threads_statements_share_a_launch(served, cfg, world, monkeypatch):
    _, ref, pool = world
    sql = cfg["statements"]["primary"]["sql"]
    ask(served, sql, pool[0], "warm")
    q = HeldQueue()
    monkeypatch.setattr(served, "dispatch", q)
    got = {}

    def session(i):
        got[i] = ask(served, sql, pool[i], f"thread-{i}")

    threads = [threading.Thread(target=session, args=(i,)) for i in (1, 2, 3)]
    threads[0].start()
    assert q.started.wait(60)
    for t in threads[1:]:
        t.start()
    deadline = time.monotonic() + 60
    while q.queued() < 20 and time.monotonic() < deadline:
        time.sleep(0.002)
    q.release.set()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads) and len(got) == 3
    widths = q.width_distribution()
    assert widths.get(10) == 1 and widths.get(20) == 1  # the held statement's ten, then both others' twenty in ONE launch
    for i in (2, 3):
        rows, spans = got[i]
        (launch,) = sweeps(spans)
        assert launch["batch"] == "20" and launch["lanes"] == "32"
        assert [int(r["id"].id) for r in rows] == ref["ids"][i, :10].tolist()
        assert all(ids_of(r["ctx"]) == ref["cites"].reach2(int(r["id"].id)).tolist() for r in rows)


# ------------------------------------------------------------------ one row is what it was
def test_one_row_takes_the_direct_path(served, world):
    data, ref, pool = world
    p = int(data["hits"][0, 0])
    sql = f"SELECT id, array::distinct({CHAIN}) AS ctx FROM ONLY type::thing('paper', $q.p)"
    before = served.dispatch.stats()["submitted"]
    row, spans = ask(served, sql, {"p": p, "y": data["y"]}, "one-row")
    assert served.dispatch.stats()["submitted"] - before == 1
    assert ids_of(row["ctx"]) == ref["cites"].reach2(p).tolist()
    assert named(spans, "graph_reach_group") == []
    assert [(l["memo"], l["form"], l["filter"]) for l in named(spans, "graph_prepare")] == [("fill", "csc", "fused")]
    # a LIMIT 1 over the search is one row too
    rows, spans = ask(served, f"SELECT id, array::distinct({CHAIN}) AS ctx FROM paper WHERE emb <|1,64|> $q.v AND year >= $q.y",
                      pool[0], "one-hit")
    assert len(rows) == 1 and named(spans, "graph_reach_group") == []


def test_ic1_s_statement_is_still_one_dispatch_with_its_spans(ds, monkeypatch):
    """`snbsf3ic1d`'s timed statement: one row, so nothing of it is deferred."""
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    execute_ok(ds, "DEFINE TABLE person SCHEMALESS; DEFINE TABLE knows SCHEMALESS")
    execute_ok(ds, "INSERT INTO person $rows RETURN NONE",
               {"rows": [{"id": i, "firstName": "Ann" if i % 2 else "Bob", "lastName": f"L{i}"} for i in range(40)]})
    pairs = [(i, (i * 7 + j) % 40) for i in range(40) for j in (1, 2, 3)]
    execute_ok(ds, "INSERT RELATION INTO knows $rows RETURN NONE",
               {"rows": [{"in": Thing("person", a), "out": Thing("person", b)} for a, b in pairs]})
    with open(os.path.join(BENCH, "configs", "snbsf3ic1d.json")) as f:
        sql = json.load(f)["statements"]["primary"]["sql"]
    before = ds.dispatch.stats()["submitted"]
    rows, spans = ask(ds, sql, {"p": 3, "fn": "Ann"}, "ic1")
    assert ds.dispatch.stats()["submitted"] - before == 1 and rows
    assert named(spans, "graph_reach_group") == [] and len(named(spans, "dispatch_launch")) == 1
    assert sorted(l["memo"] for l in named(spans, "graph_prepare")) == ["fill", "hit", "hit"]


# ------------------------------------------------------------------ families
def test_two_chains_are_two_families_and_the_same_chain_twice_is_one(served, world):
    data, ref, pool = world
    two = (f"SELECT id, array::distinct({CHAIN}) AS ctx, array::distinct({BARE}) AS every "
           "FROM paper WHERE emb <|6,64|> $q.v AND year >= $q.y")
    ask(served, two, pool[0], "warm")
    before = served.dispatch.stats()
    rows, spans = ask(served, two, pool[2], "two")
    after = served.dispatch.stats()
    groups = named(spans, "graph_reach_group")
    assert [(g["rows"], g["riders"], g["families"], g["launches"]) for g in groups] == [("6", "6", "2", "1")] * 2
    assert sorted(g["filter"] for g in groups) == ["fused", "none"] and len(sweeps(spans)) == 2
    assert after["submitted"] - before["submitted"] in (12, 13)
    for r in rows:
        p = int(r["id"].id)
        assert ids_of(r["ctx"]) == ref["cites"].reach2(p).tolist()
        assert ids_of(r["every"]) == np.unique(ref["cites"].walks2(p)).tolist()
    # a prefix of the chain is of its family: ring 1 is the row of the first operator, no second launch
    both = (f"SELECT id, array::distinct({ONE_STEP}) AS near, array::distinct({CHAIN}) AS ctx, "
            f"array::len(array::distinct({CHAIN})) AS n FROM paper WHERE emb <|6,64|> $q.v AND year >= $q.y")
    rows, spans = ask(served, both, pool[2], "prefix")
    assert len(named(spans, "graph_reach_group")) == 1 and len(sweeps(spans)) == 1
    for r in rows:
        row = ref["cites"].row(int(r["id"].id))
        assert ids_of(r["near"]) == sorted({int(v) for v in row if data["year"][v] >= data["y"]}) and r["n"] == len(r["ctx"])


# ------------------------------------------------------------------ rows that cannot ride
def test_papers_that_cite_nothing_answer_beside_papers_that_ride(served, world):
    data, ref, _ = world
    sql = f"SELECT id, array::distinct({CHAIN}) AS ctx FROM paper LIMIT 60"
    before = served.dispatch.stats()["submitted"]
    rows, spans = ask(served, sql, {"y": 1961}, "scan")
    passes = data["year"] >= 1961
    refs = np.bincount(data["pairs"][:, 0], minlength=data["papers"])
    assert [int(r["id"].id) for r in rows] == list(range(60)) and (refs[:60] == 0).any()
    for r in rows:
        ends = np.unique(ref["cites"].walks2(int(r["id"].id)))
        assert ids_of(r["ctx"]) == ends[passes[ends]].tolist()
    (group,) = named(spans, "graph_reach_group")
    riders = int((refs[:60] > 0).sum())
    assert (group["rows"], group["riders"]) == ("60", str(riders)) and 0 < riders < 60
    assert served.dispatch.stats()["submitted"] - before == riders


def test_rows_past_the_pad_take_the_host_s_walk_inside_the_group(ds, cfg, kind, world, monkeypatch):
    data, ref, pool = world
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    monkeypatch.setattr(graph_csr, "ROW_PAD_MAX", 4)  # the operator has a longer row: no row is read at a pad
    load_plain(ds, cfg, data)
    rows, spans = ask(ds, cfg["statements"]["primary"]["sql"], pool[0], "hubs")
    assert [int(r["id"].id) for r in rows] == ref["ids"][0, :10].tolist()
    for r in rows:
        assert ids_of(r["ctx"]) == ref["cites"].reach2(int(r["id"].id)).tolist() and r["n"] == len(r["ctx"])
    (group,) = named(spans, "graph_reach_group")
    assert (group["rows"], group["riders"], group["launches"]) == ("10", "0", "0") and sweeps(spans) == []


UNFUSED = {
    "unlowerable": "->cites->paper->cites->(paper WHERE math::abs(year) >= $q.y)",
    "middle_part": "->cites->(paper WHERE year >= $q.y)->cites->paper",
}


@pytest.mark.parametrize("case", sorted(UNFUSED))
def test_a_family_that_cannot_ride_falls_back_row_by_row_and_says_filter_host(served, world, case):
    data, ref, pool = world
    sql = f"SELECT id, array::distinct({UNFUSED[case]}) AS ctx FROM paper WHERE emb <|5,64|> $q.v AND year >= $q.y"
    rows, spans = ask(served, sql, pool[4], case)
    assert named(spans, "graph_reach_group") == [] and sweeps(spans) == []
    assert [(l["form"], l["filter"]) for l in named(spans, "graph_prepare")] == [("host", "host")] * 5
    cites, passes = ref["cites"], data["year"] >= data["y"]
    for r in rows:
        p = int(r["id"].id)
        if case == "unlowerable":
            want = cites.reach2(p).tolist()
        else:
            want = sorted({int(w) for v in cites.row(p) if passes[v] for w in cites.row(int(v))})
        assert ids_of(r["ctx"]) == want


def test_with_the_device_off_every_row_walks_the_host_s_sets(served, cfg, world, monkeypatch):
    _, ref, pool = world
    monkeypatch.setattr(cnf, "TPU_DISABLE", True)
    before = served.dispatch.stats()["submitted"]
    rows, spans = ask(served, cfg["statements"]["primary"]["sql"], pool[5], "off")
    assert named(spans, "graph_reach_group") == [] and sweeps(spans) == []
    assert served.dispatch.stats()["submitted"] - before <= 1
    assert all(ids_of(r["ctx"]) == ref["cites"].reach2(int(r["id"].id)).tolist() for r in rows)


# ------------------------------------------------------------------ committed data, and only it
def test_an_acknowledged_update_of_year_and_a_relate_change_the_next_answer(served, cfg, world):
    data, ref, pool = world
    sql = cfg["statements"]["primary"]["sql"]
    q = next(i for i in range(len(pool)) if ref["cites"].reach2(int(ref["ids"][i, 0])).size >= 2)
    hit = int(ref["ids"][q, 0])
    ring = ref["cites"].reach2(hit).tolist()
    rows, _ = ask(served, sql, pool[q], "before")
    assert ids_of(rows[0]["ctx"]) == ring
    # a paper of the set turns out older: gone from the next answer, its count one less
    gone = ring[0]
    execute_ok(served, "UPDATE type::thing('paper', $p) SET year = 1900", {"p": gone})
    rows, _ = ask(served, sql, pool[q], "older")
    assert int(rows[0]["id"].id) == hit or gone == hit
    by_id = {int(r["id"].id): r for r in rows}
    assert gone not in by_id and ids_of(by_id[hit]["ctx"]) == ring[1:] and by_id[hit]["n"] == len(ring) - 1
    # the hit cites a paper that cites a recent one nobody reached: there at once
    mid = int(ref["cites"].row(hit)[0])
    new = next(p for p in range(data["papers"] - 1, data["lo"], -1) if p not in ring and p != gone)
    execute_ok(served, "RELATE $a->cites->$b", {"a": Thing("paper", mid), "b": Thing("paper", new)})
    rows, spans = ask(served, sql, pool[q], "related")
    by_id = {int(r["id"].id): r for r in rows}
    assert ids_of(by_id[hit]["ctx"]) == sorted(ring[1:] + [new])
    assert len(sweeps(spans)) == 1 and len(named(spans, "graph_reach_group")) == 1


# ------------------------------------------------------------------ the last hop from the rows, at the operators' pad (ISSUE 48)
def cites_operator(ds) -> dict:
    (op,) = [op for key, op in ds.graph_mirrors._csc.items() if key[2] == "paper" and key[4] == "cites"]
    return op


def longest_walk(cites) -> int:
    deg = np.diff(cites.indptr)
    return max(int(deg[cites.row(p)].sum()) for p in range(len(deg)))


def test_every_rider_reads_its_last_hop_from_the_rows_at_the_pad_the_operators_fix(served, cfg, world):
    data, ref, pool = world
    pad = graph_csr._row_pad(longest_walk(ref["cites"]))
    assert 0 < pad <= graph_csr.ROW_PAD_MAX and cites_operator(served)["walk_pad"][1] == pad
    sql = cfg["statements"]["primary"]["sql"]
    for q in range(4):
        before = served.dispatch.stats()["submitted"]
        rows, spans = ask(served, sql, pool[q], f"rows-{q}")
        assert served.dispatch.stats()["submitted"] - before in (10, 11)  # ten set riders and the search's own
        for row in rows:
            assert ids_of(row["ctx"]) == ref["cites"].reach2(int(row["id"].id)).tolist() and row["n"] == len(row["ctx"])
        (launch,) = sweeps(spans)  # whatever the hits' walks number: one bucket, one program
        assert launch["lanes"] == "16" and launch["slots"] == str(pad) and launch["batch"] == "10"
        fills = [p for p in named(spans, "graph_prepare") if p["memo"] == "fill"]
        assert [(p["form"], p["last_hop"]) for p in fills] == [("csc", "rows")]
        assert all("last_hop" not in p for p in named(spans, "graph_prepare") if p["memo"] == "hit")
    shapes = {e["shape"] for e in compile_log.events() if e["subsystem"] == "graph_reach"}
    assert shapes and all(s.endswith(f"xrowsx{pad}") for s in shapes), shapes
    reached = {tuple(sorted(dict(k).items())): int(v) for k, v in telemetry.counters_matching("graph_reach").items()}
    assert reached[(("filter", "fused"), ("form", "csc"), ("last_hop", "rows"), ("operand", "composed"))] == 4
    # the rows the kernel gathers from went to the device once, with the operator's generation
    assert cites_operator(served)["dst_rows"].shape[0] == graph_csr.path_slots(len(data["pairs"]))


def test_the_deployment_s_own_graph_bounds_every_walk_by_4096(full_cfg, kind):
    """`magcite150k` at full size (the generator's one fixed graph: reference
    lists capped at 256): the longest two-step walk, duplicates counted, is
    3,095 records over all papers and 2,867 over those that can be a hit."""
    papers, cites = full_cfg["sizes"]["papers"], full_cfg["sizes"]["cites"]
    pairs = kind.citations(full_cfg["generator"], papers, cites, papers // 2)
    order = np.argsort(pairs[:, 0], kind="stable")
    indptr = np.concatenate([[0], np.cumsum(np.bincount(pairs[:, 0], minlength=262144))]).astype(np.int32)
    op = {"by_src": (indptr, pairs[order, 1].astype(np.int32)), "key": ("cites",), "gen": (1,),
          "row_pad": graph_csr._row_pad(int(np.diff(indptr).max()))}
    walks = np.bincount(pairs[:, 0], weights=np.diff(indptr)[pairs[:, 1]], minlength=papers)
    assert (int(walks.max()), int(walks[papers // 2:].max()), int(np.median(walks[papers // 2:]))) == (3095, 2867, 75)
    assert op["row_pad"] == 256 and graph_csr._walk_pad(op, op) == 4096 == graph_csr.ROW_PAD_MAX


def test_one_relate_that_lengthens_a_walk_past_the_pad_makes_the_next_statement_sweep(served, cfg, world):
    data, ref, pool = world
    cites, sql = ref["cites"], cfg["statements"]["primary"]["sql"]
    one = "SELECT VALUE array::distinct(" + CHAIN + ") FROM ONLY type::thing('paper', $q.p)"
    hit = int(ref["ids"][0, 0])
    found, spans = ask(served, one, {"p": hit, "y": data["y"]}, "alone")
    assert ids_of(found) == cites.reach2(hit).tolist()
    assert [p["last_hop"] for p in named(spans, "graph_prepare")] == ["rows"]
    # one RELATE: the hit cites the most-citing papers until its walk passes ROW_PAD_MAX
    deg = np.diff(cites.indptr)
    more, walk = [], int(deg[cites.row(hit)].sum())
    for p in np.argsort(-deg, kind="stable").tolist():
        if walk > graph_csr.ROW_PAD_MAX:
            break
        more.append(p)
        walk += int(deg[p])
    assert walk > graph_csr.ROW_PAD_MAX and len(more) < 200
    execute_ok(served, "RELATE $a->cites->$b", {"a": Thing("paper", hit), "b": [Thing("paper", p) for p in more]})
    passes = data["year"] >= data["y"]
    want = np.unique(np.concatenate([cites.row(int(m)) for m in cites.row(hit).tolist() + more]))
    want = want[passes[want]].tolist()
    compile_log.reset()
    found, spans = ask(served, one, {"p": hit, "y": data["y"]}, "longer")
    assert ids_of(found) == want and cites_operator(served)["walk_pad"][1] == 0
    (launch,) = sweeps(spans)
    assert int(launch["slots"]) == int(cites_operator(served)["csrc"].shape[0]) > graph_csr.ROW_PAD_MAX
    assert [p["last_hop"] for p in named(spans, "graph_prepare")] == ["sweep"]
    assert "dst_rows" not in cites_operator(served)  # no rows uploaded for a generation that sweeps
    # and the timed statement, every row of it, through the same sweep
    before = served.dispatch.stats()["submitted"]
    rows, spans = ask(served, sql, pool[0], "group-swept")
    assert served.dispatch.stats()["submitted"] - before in (10, 11)
    by_id = {int(r["id"].id): r for r in rows}
    assert ids_of(by_id[hit]["ctx"]) == want and by_id[hit]["n"] == len(want)
    for p, row in by_id.items():
        assert p == hit or ids_of(row["ctx"]) == cites.reach2(p).tolist()
    assert [p["last_hop"] for p in named(spans, "graph_prepare") if p["memo"] == "fill"] == ["sweep"]
    shapes = {e["shape"] for e in compile_log.events() if e["subsystem"] == "graph_reach"}
    assert shapes and not any("rows" in s for s in shapes), shapes  # the sweep's shape key, as before the pad


def test_uncommitted_edge_writes_take_the_kv_walk_for_every_row(served, world):
    data, ref, pool = world
    hit = int(ref["ids"][0, 0])
    mid = int(ref["cites"].row(hit)[0])
    new = data["papers"] - 1
    assert new not in ref["cites"].reach2(hit).tolist()
    sql = ("BEGIN; RELATE $a->cites->$b; "
           f"SELECT id, array::distinct({CHAIN}) AS ctx FROM paper WHERE emb <|4,64|> $q.v AND year >= $q.y; COMMIT")
    before = served.dispatch.stats()
    with tracing.request("hybrid", trace_id="txn"):
        out = execute_ok(served, sql, {"q": pool[0], "a": Thing("paper", mid), "b": Thing("paper", new)})
    by_id = {int(r["id"].id): r for r in out[-1]["result"]}
    assert ids_of(by_id[hit]["ctx"]) == sorted(ref["cites"].reach2(hit).tolist() + [new])
    assert named(spans_of("txn"), "graph_reach_group") == [] and sweeps(spans_of("txn")) == []
    assert served.dispatch.stats()["submitted"] - before["submitted"] <= 1  # the search's own at most


# ------------------------------------------------------------------ ORDER BY / LIMIT / START
ORDERS = {
    "by_n_desc": ("ORDER BY n DESC, id", lambda rows: sorted(rows, key=lambda r: (-r[1], r[0]))),
    "by_id_start_limit": ("ORDER BY id LIMIT 4 START 3", lambda rows: sorted(rows)[3:7]),
    "limit_alone": ("LIMIT 3", lambda rows: rows[:3]),
    "start_alone": ("START 7", lambda rows: rows[7:]),
}


@pytest.mark.parametrize("case", sorted(ORDERS))
def test_order_limit_and_start_over_a_deferred_projection(served, world, case):
    data, ref, pool = world
    clause, cut = ORDERS[case]
    head = f"SELECT id, array::len(array::distinct({CHAIN})) AS n, array::distinct({CHAIN}) AS ctx "
    rows, spans = ask(served, head + "FROM paper WHERE emb <|10,64|> $q.v AND year >= $q.y " + clause, pool[6], case)
    hits = ref["ids"][6, :10].tolist()
    want = cut([(p, int(ref["cites"].reach2(p).size)) for p in hits])
    assert [(int(r["id"].id), r["n"]) for r in rows] == want
    if len(want) > 1:
        assert len(named(spans, "graph_reach_group")) == 1
    # the same rows as each hit gives when asked alone by its record id (the one-row path)
    for r in rows:
        alone, alone_spans = ask(served, head + "FROM ONLY type::thing('paper', $q.p)",
                                 {"p": int(r["id"].id), "y": data["y"]}, f"{case}-alone")
        assert named(alone_spans, "graph_reach_group") == []
        assert (alone["n"], ids_of(alone["ctx"])) == (r["n"], ids_of(r["ctx"]))


# ------------------------------------------------------------------ errors stay where they are
def test_an_error_in_one_row_s_expression_does_not_poison_the_others(served, world):
    data, ref, pool = world
    hits = ref["ids"][7, :10].tolist()
    bad = hits[3]
    sql = (f"SELECT id, array::distinct({CHAIN}) AS ctx, "
           "(IF id = type::thing('paper', $q.bad) THEN <int> 'x' ELSE 1 END) AS one "
           "FROM paper WHERE emb <|10,64|> $q.v AND year >= $q.y")
    with tracing.request("hybrid", trace_id="bad"):
        out = served.execute(sql, Session.owner("bench", "bench"), vars={"q": {**pool[7], "bad": bad}})
    assert out[-1]["status"] == "ERR"  # as the row-by-row projection reports it
    # the statement's memo is the statement's: the next one finds nothing of it and answers
    rows, spans = ask(served, sql, {**pool[7], "bad": -1}, "good")
    assert [int(r["id"].id) for r in rows] == hits and all(r["one"] == 1 for r in rows)
    assert all(ids_of(r["ctx"]) == ref["cites"].reach2(int(r["id"].id)).tolist() for r in rows)
    # and a row that is no record (a value) is projected beside records that are filled
    rows, _ = ask(served, f"SELECT id, array::distinct({CHAIN}) AS ctx FROM [$q.a, 5, $q.b]",
                  {"a": Thing("paper", hits[0]), "b": Thing("paper", hits[1]), "y": data["y"]}, "values")
    assert len(rows) == 3


# ------------------------------------------------------------------ the parser's note
def test_the_parser_notes_a_field_list_s_own_chain_sets():
    stm = parse_query(f"SELECT id, array::distinct({CHAIN}) AS a, array::len(array::distinct({CHAIN})) AS n, "
                      f"(SELECT VALUE array::distinct({BARE}) FROM ONLY $parent.id) AS sub FROM paper").statements[0]
    assert len(stm.reach_calls) == 2 and all(c.reach is stm.reach_calls[0].args[0] or c.reach is c.args[0] for c in stm.reach_calls)
    assert len({id(c.reach) for c in stm.reach_calls}) == 1  # one family
    assert parse_query("SELECT id FROM paper").statements[0].reach_calls == ()
    assert parse_query(f"SELECT count({BARE}) AS c FROM paper").statements[0].reach_calls == ()


# ------------------------------------------------------------------ the dispatch queue's entry point
def test_submit_many_is_k_riders_of_one_launch_in_order():
    q = DispatchQueue()
    seen = []

    def runner(payloads):
        seen.append(list(payloads))
        return [p * 2 for p in payloads]

    rode = []
    assert q.submit_many("k", [1, 2, 3, 4, 5], runner, rode=rode) == [2, 4, 6, 8, 10]
    assert seen == [[1, 2, 3, 4, 5]] and len(set(rode)) == 1
    s = q.stats()
    assert (s["submitted"], s["dispatches"], s["batched"]) == (5, 1, 4) and q.width_distribution() == {5: 1}
    assert q.submit_many("k", [], runner) == [] and q.submit("k", 7, runner) == 14


def test_a_group_wider_than_the_queue_s_width_leaves_in_consecutive_launches():
    q = DispatchQueue(max_width=4)
    seen = []

    def runner(payloads):
        seen.append(len(payloads))
        return lambda: [p + 1 for p in payloads]  # two-phase, as the served runners are

    rode = []
    assert q.submit_many("k", list(range(10)), runner, depth=1, gather=True, rode=rode) == list(range(1, 11))
    assert seen == [4, 4, 2] and [rode.count(n) for n in sorted(set(rode))] == [4, 4, 2]
    assert q.stats()["splits"] == 0 and q.stats()["submitted"] == 10


def test_a_rider_s_error_is_raised_when_every_rider_is_done():
    q = DispatchQueue()

    def runner(payloads):
        raise ValueError("no")

    with pytest.raises(ValueError):
        q.submit_many("k", [1, 2, 3], runner)
    assert q.stats()["failures"] == 1 and q.submit_many("k", [1], lambda ps: ps) == [1]


def test_two_callers_groups_share_a_launch_and_each_gets_its_own():
    q, gate, started = DispatchQueue(), threading.Event(), threading.Event()

    def runner(payloads):
        if not started.is_set():
            started.set()
            assert gate.wait(30)
        return [p * 10 for p in payloads]

    got = {}

    def caller(name, payloads):
        got[name] = q.submit_many("k", payloads, runner)

    threads = [threading.Thread(target=caller, args=(n, p)) for n, p in
               (("a", [1]), ("b", [2, 3, 4]), ("c", [5, 6]))]
    threads[0].start()
    assert started.wait(30)
    for t in threads[1:]:
        t.start()
    deadline = time.monotonic() + 30
    while sum(len(b.queue) for b in q._buckets.values()) < 5 and time.monotonic() < deadline:
        time.sleep(0.002)
    gate.set()
    for t in threads:
        t.join(60)
    assert got == {"a": [10], "b": [20, 30, 40], "c": [50, 60]}
    assert q.width_distribution() == {1: 1, 5: 1}


# ------------------------------------------------------------------ the check and its controls
def record(q, ids, ns, ds_):
    return {"status": "OK", "q": q, "ids": ids, "values": {"n": ns, "d": ds_}}


def test_the_check_counts_a_wrong_set_size_for_whichever_paper_came_back(cfg, kind, world):
    data, ref, _ = world
    hits = ref["ids"][:, :10]
    d = np.sqrt(ref["d2"][:, :10])
    good = [record(q, hits[q].tolist(), [int(ref["cites"].reach2(int(p)).size) for p in hits[q]], d[q].tolist())
            for q in range(len(hits))]
    out = kind.check(cfg, ref, good)
    by = {n[0]: n for n in out["numbers"]}
    assert [by[k][1] for k in ("filter_violations", "short_answers", "wrong_reach_counts")] == [0, 0, 0]
    assert by["recall_at_10"][1] == 1.0 and out["compared"]["rows"] == 10 * len(hits)
    assert out["control"]["wrong_reach_counts_unmasked"] > 0 and out["control"]["wrong_reach_counts_multiset"] > 0
    # a paper the search should not have returned is still held to ITS set
    other = int(data["lo"] + 1)
    swapped = copy.deepcopy(good)
    swapped[0]["ids"][9], swapped[0]["values"]["n"][9] = other, int(ref["cites"].reach2(other).size)
    assert {n[0]: n[1] for n in kind.check(cfg, ref, swapped)["numbers"]}["wrong_reach_counts"] == 0
    swapped[0]["values"]["n"][9] += 1
    assert {n[0]: n[1] for n in kind.check(cfg, ref, swapped)["numbers"]}["wrong_reach_counts"] == 1
    # a row without its count, a paper of before the year, a short answer
    swapped[1]["values"]["n"] = swapped[1]["values"]["n"][:9]
    swapped[2]["ids"][0] = 3
    swapped[3]["ids"], swapped[3]["values"] = swapped[3]["ids"][:9], {k: v[:9] for k, v in swapped[3]["values"].items()}
    got = {n[0]: n[1] for n in kind.check(cfg, ref, swapped)["numbers"]}
    assert got["wrong_reach_counts"] >= 2 and got["filter_violations"] == 1 and got["short_answers"] == 1
    assert {n[0]: n[1] for n in kind.check(cfg, ref, [])["numbers"]}["wrong_reach_counts"] == 1  # nothing compared is not correct


def test_the_probe_refuses_a_set_that_is_not_the_reference_s(cfg, kind, world):
    data, ref, _ = world
    cites = ref["cites"]
    p = int(data["hits"][0, 0])
    ring = cites.reach2(p).tolist()
    row = {"id": Thing("paper", p), "ctx": [Thing("paper", i) for i in ring], "n": len(ring)}
    assert kind.row_faults(cfg, cites, row) == []
    assert len(kind.row_faults(cfg, cites, {**row, "ctx": row["ctx"][1:]})) == 1
    assert len(kind.row_faults(cfg, cites, {**row, "n": len(ring) + 1})) == 1
    entries = kind.probe_entries(data)
    assert len(entries) == len(set(entries)) == min(kind.PROBES, len(data["hits"]))
    refs = np.bincount(data["pairs"][:, 0], minlength=data["papers"])
    assert refs[data["hits"][entries[0]]].max() == refs[data["hits"]].max()


# ------------------------------------------------------------------ the manifest and the configuration
READERS = ["graph.reach_group_rows", "graph.reach_group_launches", "hybrid.knn_stage_ms", "hybrid.reach_stage_ms",
           "hybrid.knn_kernel_ms", "knn.filter_widened_share", "hybrid_reach_roofline"]


def test_the_manifest_has_the_deployment_its_cell_and_its_seven_readers(full_cfg):
    manifest = mf.load()
    assert mf.problems(manifest) == []
    assert manifest["configs"][-1] == {**manifest["configs"][-1], "name": CONFIG, "source": full_cfg["source"],
                                       "file": f"benchmarks/configs/{CONFIG}.json", "reduced": ["rows"]}
    assert len(full_cfg["source"]) <= 200 and "config 4" in full_cfg["source"] and "MAG240M" in full_cfg["source"]
    assert manifest["workloads"][-1] == {**manifest["workloads"][-1], "name": CELL, "config": CONFIG,
                                         "traffic": "ws_closed_c8", "chips": 1}
    assert len(manifest["configs"]) == 9 and len(manifest["workloads"]) == 9
    assert {m["name"] for m in mf.metrics_of(manifest, "end_to_end", CELL)} == {
        "setup_s", "stmt_per_s", "p50_ms", "p95_ms", "recall_at_10"}
    (recall,) = [m for m in manifest["end_to_end"] if m["name"] == "recall_at_10"]
    assert recall["workloads"] == ["vec1m768.knn_c1", "vec500k768f.knn99p_c1", CELL]
    # together and in order after those that were there, found BY NAME: later PRs append (PR 48 the share of
    # sets read from the rows, PR 49 the interpreter's books)
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(READERS[0])
    assert names[at:at + 8] == READERS + ["graph.reach_rows_share"] and at > names.index("exec.predicate_compiles")
    for m in manifest["per_layer"][at:at + 7]:
        assert m["workloads"] == [CELL] and m["moves"] == "p50_ms"
    assert manifest["per_layer"][at + 7] == {
        "name": "graph.reach_rows_share", "unit": "ratio", "better": "higher", "source": "program_span",
        "layer": "kernels", "moves": "p50_ms", "workloads": [CELL, "snbsf3ic1d.near20_c8"]}
    mine = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    theirs = {m["name"] for m in mf.metrics_of(manifest, "per_layer", "vec1m768.knn_c1")}
    assert mine - theirs == set(READERS) | {"graph.reach_rows_share"} and theirs - mine == {"ivf_roofline", "ivf.longest_list"}


def test_the_configuration_states_the_source_s_shapes_and_cuts_only_the_rows(full_cfg):
    c = full_cfg
    with open(os.path.join(BENCH, "configs", "vec1m768.json")) as f:
        bare = json.load(f)
    assert (c["kind"], c["kernel"], c["dim"], c["k"], c["ef"], c["hops"]) == ("hybrid_knn_reach", "graph_reach", 768, 10, 64, 2)
    assert c["sizes"] == {"papers": 150_000, "cites": 1_597_500, "pool": 1024, "pass_share": 0.5}
    assert c["sizes"]["cites"] / c["sizes"]["papers"] == 10.65 and c["reduced"] == ["rows"]
    for key in ("centres", "sigma", "query_noise", "corpus_seed"):
        assert c["generator"][key] == bare["generator"][key], key
    assert c["ddl"][2] == bare["ddl"][1].replace("item", "paper")
    st = c["statements"]["primary"]
    assert (st["bind"], st["dispatches"]) == ("q", 11) and "<|10,64|> $q.v AND year >= $q.y" in st["sql"]
    assert st["sql"].count(f"array::distinct({CHAIN})") == 2 and "array::len(" in st["sql"]
    assert c["expected_strategies"] == ["ivf"]
    limits = {k: v for k, v in c["correct"].items() if k != "why"}
    assert limits == {"recall_at_10_min": 0.95, "distance_rms_rel_max": 0.0006, "unmatched_id_share_max": 0.05,
                      "filter_violations_max": 0, "short_answers_max": 0, "wrong_reach_counts_max": 0}
    assert "0.000274-0.000288" in c["correct"]["why"] and "0.001234-0.001270" in c["correct"]["why"]  # the limit's two readings
    assert any("MAG240M's own degree sequence is not in the repository" in a for a in c["assumed"])
    assert {"longest_reference_list", "largest_in_degree", "share_of_citations_to_the_most_cited_1_percent",
            "hit_n_median", "hit_n_p95"} <= set(c["generator"]["measured"])
    assert any("SET, not the multiset" in g for g in c["guarantees"]) and any("11 device dispatches" in g for g in c["guarantees"])


def test_the_plain_reference_imports_nothing_of_the_program():
    for name in ("hybrid_knn_reach", "vector_knn_filtered"):
        with open(os.path.join(BENCH, "deployments", name + ".py")) as f:
            text = f.read()
        top = [l for l in text.splitlines() if l.startswith(("import ", "from "))]
        assert not any("surrealdb_tpu" in l or "jax" in l for l in top), top
    from deployments import hybrid_knn_reach as dep

    src = open(dep.__file__).read()
    ref_fn = src[src.index("def reference("):src.index("# ------------------------------------------------------------------ load")]
    assert "surrealdb_tpu" not in ref_fn and "jax" not in ref_fn


# ------------------------------------------------------------------ the readers, on hand-written docs
def span(name, start, dur, **labels):
    return {"name": name, "start_ms": start, "dur_ms": dur, "labels": {k: str(v) for k, v in labels.items()}, "parent": 1}


def hybrid_doc(launches):
    """A tagged statement: a search, then `launches` of the set kernel."""
    spans = [span("knn_prepare", 1.0, 0.5, filter="widened"),
             span("dispatch_queue_wait", 1.5, 0.2, batch=1), span("dispatch_launch", 1.7, 0.3, batch=1),
             span("dispatch_collect", 2.0, 1.0, batch=1)]
    t = 3.5
    if launches == 1:
        spans.append(span("graph_reach_group", 3.2, 0.3, rows=10, riders=10, families=1, launches=1))
    for _ in range(launches):
        spans += [span("dispatch_queue_wait", t, 0.5, batch=10), span("dispatch_launch", t + 0.5, 1.0, batch=10, lanes=16, slots=99),
                  span("dispatch_collect", t + 1.5, 2.0, batch=10)]
        t += 4.0
    return {"doc": {"ts": 0.0, "spans": spans}}


def test_the_span_readers_tell_a_group_from_ten_round_trips():
    readers = mf.load_modules(BENCH, "layer_metrics", "NAME")
    grouped, alone = {"tagged": [hybrid_doc(1)] * 3}, {"tagged": [hybrid_doc(10)] * 3}
    assert readers["graph.reach_group_rows"].read(grouped) == 10.0 and readers["graph.reach_group_rows"].read(alone) is None
    assert readers["graph.reach_group_launches"].read(grouped) == 1.0 and readers["graph.reach_group_launches"].read(alone) == 10.0
    assert readers["hybrid.knn_stage_ms"].read(grouped) == readers["hybrid.knn_stage_ms"].read(alone) == pytest.approx(2.0)
    assert readers["hybrid.reach_stage_ms"].read(grouped) == pytest.approx(3.5)
    assert readers["hybrid.reach_stage_ms"].read(alone) == pytest.approx(39.5)
    assert readers["knn.filter_widened_share"].read(grouped) == 1.0
    bare = {"tagged": [{"doc": {"ts": 0.0, "spans": [span("knn_prepare", 0, 1, filter="none"), span("dispatch_collect", 1, 1)]}}]}
    for name in READERS[:4] + ["knn.filter_widened_share"]:
        assert readers[name].read(bare) is None and readers[name].read({"tagged": []}) is None
    # riders of one launch stamped on one statement more than once (a program that does not share the span) count once
    twice = hybrid_doc(1)
    twice["doc"]["spans"] += [s for s in twice["doc"]["spans"] if s["name"] == "dispatch_launch"]
    assert readers["graph.reach_group_launches"].read({"tagged": [twice]}) == 1.0


def test_the_device_readers_count_the_sweep_s_own_launches_and_invent_nothing(full_cfg):
    readers = mf.load_modules(BENCH, "layer_metrics", "NAME")
    kernels = mf.load_modules(BENCH, "kernels", None)
    shapes = {"nodes": 150_000, "edges": 1_597_500, "hops": 2}
    reduced = {"kernel_s": 0.5, "kernel_launches": 100,
               "modules": {"jit_chain_reach_batch": {"seconds": 0.5, "launches": 100},
                           "jit__ivf_search": {"seconds": 0.04, "launches": 200}}}
    ctx = {"cfg": full_cfg, "tagged": [], "kernel": {"name": "graph_reach", "need": kernels["graph_reach"].need, "shapes": shapes},
           "slice": {"reduced": reduced, "dispatch": {"submitted": 1100, "dispatches": 300}},
           "peaks": {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}}
    assert readers["hybrid.knn_kernel_ms"].read(ctx) == pytest.approx(0.2)
    need = kernels["graph_reach"].need(shapes, 1000.0, 100.0)  # ten elevenths of the riders, the sweep's own launches
    want = 100.0 * max(need["flops"] / 1.97e14, need["bytes"] / 8.19e11) / 0.5
    assert readers["hybrid_reach_roofline"].read(ctx) == pytest.approx(want) and 0 < want < 100
    for name in ("hybrid.knn_kernel_ms", "hybrid_reach_roofline"):
        assert readers[name].read({**ctx, "slice": None}) is None
    assert readers["hybrid_reach_roofline"].read({**ctx, "kernel": {**ctx["kernel"], "name": "ivf"}}) is None
    assert readers["hybrid.knn_kernel_ms"].read({**ctx, "slice": {**ctx["slice"], "reduced": {**reduced, "modules": {}}}}) is None
