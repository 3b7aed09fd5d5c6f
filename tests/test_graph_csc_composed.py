"""The sparse count form sweeps composed node->node operators where a chain
is one of single-table `->edge->node` pairs, and the record-level mirrors
where it is not: one kernel, two operands, the same int32 (ISSUE 28).
References are int64 NumPy walks over the edge list."""

import numpy as np
import pytest

from surrealdb_tpu import cnf, compile_log, key as keys, telemetry, tracing
from surrealdb_tpu.dbs.dispatch import DispatchQueue
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.idx import graph_csr
from surrealdb_tpu.idx.graph_csr import GraphMirrors
from surrealdb_tpu.sql.value import Thing
from surrealdb_tpu.utils.num import count_lane_set
from test_graph_dense_exact import (
    DB, NS, PAIR, as_int32, device_count, forms, lognormal_hub, mirrors_of, near_complete, walk_count,
)

SQL = "SELECT count(->knows->person->knows->person->knows->person) AS c FROM person:{}"


@pytest.fixture(autouse=True)
def fresh_counters():
    telemetry.reset()
    compile_log.reset()
    tracing.store_reset()


def operands() -> dict:
    return {dict(k)["operand"]: int(v) for k, v in telemetry.counters_matching("graph_csc_operand").items()}


def parallel_ring(n: int = 12, copies: int = 300) -> np.ndarray:
    """Every person has `copies` knows records to the next one and two to the
    third next: a multiplicity bf16 rounds, so the dense form refuses it."""
    i = np.arange(n)
    src = np.concatenate([np.repeat(i, copies), np.repeat(i, 2)])
    dst = np.concatenate([np.repeat((i + 1) % n, copies), np.repeat((i + 3) % n, 2)])
    return np.stack([src, dst], axis=1)


GRAPHS = {
    "near_complete_120": lambda: (120, near_complete(120)),
    "parallel_ring_300": lambda: (12, parallel_ring()),
}
# 119**3 x 5,301 and 302**3 x 5,301 both pass 2**31: the int32 sums wrap
SEEDS = {3: 5000, 4: 1, 7: 300}


def without_composition(gm, monkeypatch):
    monkeypatch.setattr(gm, "_csc_pair", lambda *a, **k: None)
    return gm


@pytest.mark.parametrize("pairs", [1, 2, 3])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_the_three_forms_give_the_same_int32(monkeypatch, graph, pairs):
    n, edges = GRAPHS[graph]()
    want = as_int32(walk_count(n, edges, SEEDS, pairs))
    if pairs == 3:
        assert walk_count(n, edges, SEEDS, pairs) >= 2**31
    gm, persons = mirrors_of(n, edges)
    dense_holds_it = gm._dense_pair(NS, DB, *PAIR) is not None
    assert dense_holds_it == (graph == "near_complete_120")
    by_default = device_count(gm, persons, SEEDS, pairs)
    assert forms() == ({"dense": 1} if dense_holds_it else {"csc": 1})
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", n - 1)
    gm2, persons2 = mirrors_of(n, edges)
    composed = device_count(gm2, persons2, SEEDS, pairs)
    assert operands() == {"composed": 2 - dense_holds_it}
    gm3, persons3 = mirrors_of(n, edges)
    records = device_count(without_composition(gm3, monkeypatch), persons3, SEEDS, pairs)
    assert operands() == {"composed": 2 - dense_holds_it, "records": 1}
    assert by_default == composed == records == want
    assert forms().get("csc") == 3 - dense_holds_it
    # one start person with weight one: no wrap, the plain walk count
    one = walk_count(n, edges, {5: 1}, pairs)
    assert device_count(gm2, persons2, {5: 1}, pairs) == device_count(gm3, persons3, {5: 1}, pairs) == one


def test_the_vectorised_composition_holds_the_paths_the_dense_operator_counts():
    n, edges = 400, lognormal_hub(400, 300, seed=11)
    gm, persons = mirrors_of(n, edges)
    dense = gm._dense_pair(NS, DB, *PAIR)
    m1 = gm.get(NS, DB, "person", keys.DIR_OUT, "knows")
    m2 = gm.get(NS, DB, "knows", keys.DIR_OUT, "person")
    space = gm.table_space(NS, DB, "person")
    ls, ld = graph_csr._compose_coo(*m1.host_arrays(), *m2.host_arrays(), space, space, max_paths=len(edges))
    assert ls.size == len(edges) and graph_csr._compose_coo(
        *m1.host_arrays(), *m2.host_arrays(), space, space, max_paths=len(edges) - 1) is None
    # as multisets: a cell of the dense operator is how often the path occurs
    A = np.asarray(dense["A"].astype(np.float32)).astype(np.int64)
    cells, mult = np.unique(ls * A.shape[1] + ld, return_counts=True)
    assert np.array_equal(cells, np.flatnonzero(A)) and np.array_equal(mult, A.reshape(-1)[cells])
    assert mult.max() > 1  # the random draw repeats some pairs
    assert np.array_equal(np.bincount(ls, minlength=A.shape[0]), np.asarray(dense["outdeg"]))
    # and against the edge list itself
    local = {int(g): i for i, g in enumerate(space["globals"])}
    want = sorted((local[int(persons[a])], local[int(persons[b])]) for a, b in edges.tolist())
    assert sorted(zip(ls.tolist(), ld.tolist())) == want


def test_the_composed_arrays_are_shaped_as_a_mirror_s_csc():
    n, edges = 5, np.asarray([(0, 1), (0, 2), (1, 2), (3, 2), (3, 2), (4, 0)])
    gm, _ = mirrors_of(n, edges)
    op = gm._csc_pair(NS, DB, *PAIR)
    assert op["n_pad"] == 8 and op["src_tb"] == op["dst_tb"] == "person"
    assert np.asarray(op["cptr"]).tolist() == [0, 1, 2, 6, 6, 6, 6, 6, 6]
    assert np.asarray(op["csrc"]).tolist() == [4, 0, 0, 1, 3, 3, 8, 8]  # dst-sorted, stable; pad slots at the sentinel
    assert np.asarray(op["indptr"]).tolist() == [0, 2, 3, 3, 5, 6, 6, 6, 6]
    assert all(str(op[k].dtype) == "int32" for k in ("cptr", "csrc", "indptr"))
    assert gm._csc_pair(NS, DB, *PAIR) is op and gm._dense == {}  # a dict of its own


def loaded(ds, monkeypatch, n, edges):
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 1)
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", n - 1)
    sess = Session.owner(NS, DB)
    ds.execute("DEFINE TABLE person SCHEMALESS; DEFINE TABLE knows SCHEMALESS", sess)
    ds.execute("INSERT INTO person $rows RETURN NONE", sess, {"rows": [{"id": i} for i in range(n)]})
    rows = [{"id": j, "in": Thing("person", int(a)), "out": Thing("person", int(b))} for j, (a, b) in enumerate(edges)]
    (res,) = ds.execute("INSERT RELATION INTO knows $rows RETURN NONE", sess, {"rows": rows})
    assert res["status"] == "OK", res
    return sess


def traced_count(ds, sess, start, tid):
    with tracing.request("count", trace_id=tid):
        (res,) = ds.execute(SQL.format(start), sess)
    assert res["status"] == "OK", res
    return res["result"][0]["c"], [s for s in tracing.get_trace(tid)["spans"]]


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_a_relate_and_a_delete_between_two_counts_change_the_second(ds, monkeypatch):
    n, edges = 60, lognormal_hub(60, 40, seed=2)
    sess = loaded(ds, monkeypatch, n, edges)
    first, spans = traced_count(ds, sess, 0, "gen-1")
    assert first == walk_count(n, edges, {0: 1}, 3)
    assert len(named(spans, "graph_csc_build")) == len(named(spans, "graph_csc_upload")) == 1
    # the same generation: nothing composed, nothing uploaded
    again, spans = traced_count(ds, sess, 0, "gen-1-again")
    assert again == first and not named(spans, "graph_csc_build") and not named(spans, "graph_csc_upload")
    built = dict(ds.graph_mirrors._csc)
    assert len(built) == 1
    (res,) = ds.execute("RELATE person:0->knows->person:1", sess)
    assert res["status"] == "OK", res
    related = res["result"][0]["id"]
    grown = np.concatenate([edges, [(0, 1)]])
    second, spans = traced_count(ds, sess, 0, "gen-2")
    assert second == walk_count(n, grown, {0: 1}, 3) != first
    assert len(named(spans, "graph_csc_build")) == len(named(spans, "graph_csc_upload")) == 1
    (op,) = ds.graph_mirrors._csc.values()
    assert op is not next(iter(built.values())) and op["fits"]
    for res in ds.execute("DELETE $related; DELETE knows:0", sess, {"related": related}):
        assert res["status"] == "OK", res
    third, _ = traced_count(ds, sess, 0, "gen-3")
    assert third == walk_count(n, edges[1:], {0: 1}, 3)
    assert third != second
    assert operands() == {"composed": 4} and forms() == {"csc": 4}


@pytest.mark.parametrize("removed", ["REMOVE DATABASE t", "REMOVE NAMESPACE t"])
@pytest.mark.parametrize("dense_max", [1, 16384], ids=["csc", "dense"])
def test_a_graph_loaded_under_a_removed_one_s_name_is_counted_anew(ds, monkeypatch, dense_max, removed):
    """A composed operator's generation counts from its mirrors' versions and
    its tables' sizes, which start again after a REMOVE: the operator goes
    with the mirrors, or the old graph's count would be served."""
    old, new = lognormal_hub(60, 40, seed=2), lognormal_hub(60, 40, seed=3)
    sess = loaded(ds, monkeypatch, 60, old)
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", dense_max)
    assert traced_count(ds, sess, 0, "old")[0] == walk_count(60, old, {0: 1}, 3)
    (res, *_) = ds.execute(removed + "; DEFINE NAMESPACE t; DEFINE DATABASE t", sess)  # what is still there stays
    assert res["status"] == "OK", res
    loaded(ds, monkeypatch, 60, new)
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", dense_max)
    assert walk_count(60, new, {0: 1}, 3) != walk_count(60, old, {0: 1}, 3)
    assert traced_count(ds, sess, 0, "new")[0] == walk_count(60, new, {0: 1}, 3)
    assert forms() == {"csc" if dense_max == 1 else "dense": 2}


def test_span_and_counter_name_one_operand_and_the_form_stays_csc(ds, monkeypatch):
    n, edges = 60, lognormal_hub(60, 40, seed=4)
    sess = loaded(ds, monkeypatch, n, edges)
    for i, start in enumerate((0, 3, 9)):
        got, spans = traced_count(ds, sess, start, f"operand-{i}")
        assert got == walk_count(n, edges, {start: 1}, 3)
        assert [s["labels"] for s in named(spans, "graph_prepare")] == [
            {"form": "csc", "filter": "none", "operand": "composed", "first_hop": "rows"}]
    # a person nobody relates from is not in the table's space: no seed, no dispatch, still a csc count
    assert forms() == {"csc": 3} and operands() == {"composed": 3}
    assert {e["subsystem"] for e in compile_log.events()} == {"graph_csc"}
    text = telemetry.render_prometheus()
    assert 'surreal_graph_csc_operand_total{operand="composed"} 3' in text
    assert 'surreal_graph_count_form_total{form="csc"} 3' in text  # one label: no second series for csc


def test_a_dense_or_host_count_names_no_operand(ds, monkeypatch):
    n, edges = 60, lognormal_hub(60, 40, seed=4)
    sess = loaded(ds, monkeypatch, n, edges)
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", 16384)
    _, spans = traced_count(ds, sess, 0, "dense")
    assert [s["labels"] for s in named(spans, "graph_prepare")] == [{"form": "dense", "filter": "none"}]
    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 10**15)
    monkeypatch.setattr(cnf, "TPU_GRAPH_ONDEVICE_THRESHOLD", 10**9)
    _, spans = traced_count(ds, sess, 0, "host")
    assert [s["labels"] for s in named(spans, "graph_prepare")] == [{"form": "host", "filter": "none"}]
    assert forms() == {"dense": 1, "host": 1} and operands() == {}


def relate(gm, it, src_ids, edge_tb, dst_ids, edges):
    """`edges` ([E, 2] local ids) as records of `edge_tb`, in both mirrors of
    the pair src -> edge_tb -> dst, as a table build leaves them."""
    records = [it.intern(Thing(edge_tb, j)) for j in range(len(edges))]
    out: dict = {}
    for j, (a, _) in enumerate(edges.tolist()):
        out.setdefault(src_ids[a], []).append(records[j])
    src_tb, dst_tb = it.node_of[src_ids[0]].tb, it.node_of[dst_ids[0]].tb
    gm._get_or_create(NS, DB, src_tb, keys.DIR_OUT, edge_tb).load(out)
    gm._get_or_create(NS, DB, edge_tb, keys.DIR_OUT, dst_tb).load(
        {records[j]: [dst_ids[b]] for j, (_, b) in enumerate(edges.tolist())})


def two_edge_tables(n: int, knows: np.ndarray, follows: np.ndarray):
    """person -> knows | follows -> person: one hop over two edge tables."""
    gm = GraphMirrors()
    it = gm.interner(NS, DB)
    persons = [it.intern(Thing("person", i)) for i in range(n)]
    relate(gm, it, persons, "knows", persons, knows)
    relate(gm, it, persons, "follows", persons, follows)
    return gm, np.asarray(persons, dtype=np.int32)


def test_a_hop_over_two_edge_tables_sweeps_the_records_exactly(monkeypatch):
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", 1)
    n = 40
    knows, follows = lognormal_hub(n, 30, seed=6), lognormal_hub(n, 20, seed=7)
    gm, persons = two_edge_tables(n, knows, follows)
    pair = [(["person"], [keys.DIR_OUT], ["follows", "knows"]), (["follows", "knows"], [keys.DIR_OUT], ["person"])]
    seeds = {0: 3, 5: 1}
    frontier = np.asarray(sorted(persons[s] for s in seeds), dtype=np.int32)
    counts = np.asarray([seeds[s] for s in sorted(seeds)], dtype=np.int32)
    with tracing.request("count", trace_id="two-tables"):
        got = gm._device_chain(NS, DB, frontier, counts, pair * 2, count_only=True, dispatch=DispatchQueue(),
                               t_enter=0.0)
    assert got == walk_count(n, np.concatenate([knows, follows]), seeds, 2)
    (span,) = named(tracing.get_trace("two-tables")["spans"], "graph_prepare")
    assert span["labels"] == {"form": "csc", "filter": "none", "operand": "records", "first_hop": "sweep"}
    assert operands() == {"records": 1} and gm._csc == {}


def supply_chain(people: int = 20, firms: int = 40, goods: int = 200, seed: int = 8):
    """person -> works_at -> firm -> ships -> good: two pairs whose node
    tables pad to different spaces (64 and 256)."""
    rng = np.random.default_rng(seed)
    works = np.stack([rng.integers(0, people, 90), rng.integers(0, firms, 90)], axis=1)
    ships = np.stack([rng.integers(0, firms, 700), rng.integers(0, goods, 700)], axis=1)
    gm = GraphMirrors()
    it = gm.interner(NS, DB)
    ids = {tb: [it.intern(Thing(tb, i)) for i in range(k)]
           for tb, k in (("person", people), ("firm", firms), ("good", goods))}
    relate(gm, it, ids["person"], "works_at", ids["firm"], works)
    relate(gm, it, ids["firm"], "ships", ids["good"], ships)
    return gm, ids, works, ships


def test_pairs_padded_to_different_spaces_sweep_the_records_exactly(monkeypatch):
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", 1)
    gm, ids, works, ships = supply_chain()
    specs = [(["person"], [keys.DIR_OUT], ["works_at"]), (["works_at"], [keys.DIR_OUT], ["firm"]),
             (["firm"], [keys.DIR_OUT], ["ships"]), (["ships"], [keys.DIR_OUT], ["good"])]
    # each pair composes alone, at its own padded space
    assert gm._csc_pair(NS, DB, *specs[:2])["n_pad"] == 64 and gm._csc_pair(NS, DB, *specs[2:])["n_pad"] == 256
    start = 3
    frontier, counts = np.asarray([ids["person"][start]], dtype=np.int32), np.asarray([7], dtype=np.int32)
    got = gm._device_chain(NS, DB, frontier, counts, specs, count_only=True, dispatch=DispatchQueue())
    goods_of_firm = np.bincount(ships[:, 0], minlength=40)
    assert got == 7 * int(goods_of_firm[works[works[:, 0] == start][:, 1]].sum()) > 0
    assert operands() == {"records": 1}
    # one pair alone is a chain of its own: the composed degree product
    got = gm._device_chain(NS, DB, frontier, counts, specs[:2], count_only=True, dispatch=DispatchQueue())
    assert got == 7 * int((works[:, 0] == start).sum()) and operands() == {"records": 1, "composed": 1}


def test_a_hop_through_a_node_table_is_refused_and_the_refusal_is_remembered():
    # knows -> person -> knows: paths multiply in- by out-degrees, past the records the mirrors hold
    n, edges = 30, near_complete(30)
    gm, _ = mirrors_of(n, edges)
    through_person = [(["knows"], [keys.DIR_OUT], ["person"]), (["person"], [keys.DIR_OUT], ["knows"])]
    assert gm._csc_pair(NS, DB, *through_person) is None
    (refusal,) = gm._csc.values()
    assert refusal["fits"] is False and gm._csc_pair(NS, DB, *through_person) is None
    assert next(iter(gm._csc.values())) is refusal  # not recomposed a statement


def test_warm_count_kernels_leaves_no_compile_for_a_first_composed_count(monkeypatch):
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", 1)
    n, edges = 50, lognormal_hub(50, 30, seed=9)
    gm, persons = mirrors_of(n, edges)
    gm.warm_count_kernels(NS, DB)
    warmed = compile_log.events()
    assert warmed and {e["mode"] for e in warmed} == {"prewarm"} and {e["subsystem"] for e in warmed} == {"graph_csc"}
    assert {int(e["shape"].split("x")[0]) for e in warmed} == set(count_lane_set()) == {8, 16, 32, 64}
    assert telemetry.counters_matching("prewarm_errors") == {}
    for pairs in (1, 2, 3):
        assert device_count(gm, persons, {0: 1}, pairs) == walk_count(n, edges, {0: 1}, pairs)
    assert operands() == {"composed": 3}
    assert compile_log.events() == warmed  # served at 8 lanes from what the warm-up compiled
    hits = {dict(k)["outcome"] for k in telemetry.counters_matching("compile_cache")}
    assert "hit" in hits
