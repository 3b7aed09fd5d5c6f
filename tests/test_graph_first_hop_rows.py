"""A sparse count over composed operators reads its first hop from the first
operator's source-sorted rows and sweeps one hop less (ISSUE 42): the same
int32 as the sweep from the seed and as an int64 NumPy walk, on multigraphs
with repeated edges and self loops, from a seed of degree 0 and from the
longest row, over 1 to 4 pairs, bare and ending in a predicate, past int32's
range; seeds whose rows pass the operator's pad are swept from and say so;
riders of any degree share one bucket and one program; an acknowledged RELATE
makes rows and operator anew together. The reader of the label
(`benchmarks/layer_metrics/graph_first_hop_rows_share.py`) is checked here on
hand-written docs, beside the manifest's entry."""

import importlib.util
import json
import os

import numpy as np
import pytest

from surrealdb_tpu import cnf, compile_log, key as keys, telemetry, tracing
from surrealdb_tpu.dbs.dispatch import DispatchQueue
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.idx import graph_csr
from surrealdb_tpu.sql.value import Thing
from surrealdb_tpu.utils.num import count_lanes, next_pow2
from test_graph_count_lanes import launch_labels, serve_batch
from test_graph_csc_composed import loaded, named, traced_count
from test_graph_dense_exact import DB, NS, PAIR, as_int32, device_count, forms, mirrors_of, walk_count

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_counters(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", 1)  # every count here is the sparse form
    telemetry.reset()
    compile_log.reset()
    tracing.store_reset()
    yield
    tracing.store_reset()


def first_hops() -> dict:
    return {dict(k)["how"]: int(v) for k, v in telemetry.counters_matching("graph_csc_first_hop").items()}


def swept_from_the_seed(monkeypatch):
    """The path every count took before: no rows, so the kernel sweeps every pair but the last."""
    monkeypatch.setattr(graph_csr, "_seed_rows", lambda *a, **k: None)


# ------------------------------------------------------------------ graphs
N, HUB, LONGEST, LONELY = 200, 7, 100, 11


def multigraph(seed: int = 42) -> np.ndarray:
    """A random multigraph on N persons: every drawn edge may repeat (some are
    laid down three times on purpose), a fifth of the persons know
    themselves, person HUB has the longest row (LONGEST records, 30 of them
    to one friend), and person LONELY knows nobody but is known."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(1, 12, N)
    src = np.repeat(np.arange(N), deg)
    dst = rng.integers(0, N, src.size)
    loops = np.arange(0, N, 5)
    tripled = np.stack([src[:40], dst[:40]], axis=1).repeat(3, axis=0)
    hub = np.stack([np.full(LONGEST, HUB), np.concatenate([np.full(30, 3), rng.integers(0, N, LONGEST - 30)])], axis=1)
    edges = np.concatenate([np.stack([src, dst], axis=1), np.stack([loops, loops], axis=1), tripled, hub])
    edges = edges[(edges[:, 0] != LONELY) & (edges[:, 0] != HUB) | (np.arange(len(edges)) >= len(edges) - LONGEST)]
    return rng.permutation(np.concatenate([edges, [(0, LONELY), (HUB, LONELY)]]))


@pytest.fixture(scope="module")
def graph():
    edges = multigraph()
    deg = np.bincount(edges[:, 0], minlength=N)
    assert deg[LONELY] == 0 and deg.argmax() == HUB and deg[HUB] == LONGEST + 1
    assert len(np.unique(edges, axis=0)) < len(edges) and (edges[:, 0] == edges[:, 1]).sum() >= N // 5 - 2
    return edges


# seeds: person -> weight. `rows`: whether their rows fit the operator's pad (128: the longest row is 101)
SEEDS = {
    "one": ({5: 1}, True),
    "degree_0": ({LONELY: 3}, True),
    "the_longest_row": ({HUB: 1}, True),
    "heavy_weights_wrap": ({HUB: 2_000_003, 9: 70_001}, True),
    "several_under_the_pad": ({5: 2, 9: 1, 10: 7, LONELY: 1, 20: 1}, True),
    "several_at_the_pad_s_edge": ({HUB: 1, 0: 1, 1: 1, 2: 1, 4: 1}, None),  # decided from the degrees below
    "several_over_the_pad": ({HUB: 1, 5: 1, 9: 1, 10: 1, 12: 3, 13: 1, 14: 1, 15: 1, 16: 1, 17: 1}, False),
    "a_whole_table": ({i: 100_003 + i for i in range(N)}, False),
}


@pytest.mark.parametrize("pairs", [2, 3, 4])
@pytest.mark.parametrize("case", sorted(SEEDS))
def test_the_count_by_rows_is_the_count_by_sweep_and_the_walk(monkeypatch, graph, case, pairs):
    seeds, by_rows = SEEDS[case]
    gm, persons = mirrors_of(N, graph)
    op = gm._csc_pair(NS, DB, *PAIR)
    assert op["row_pad"] == 128
    together = int(np.bincount(graph[:, 0], minlength=N)[list(seeds)].sum())
    if by_rows is None:
        by_rows = together <= 128
    assert by_rows == (together <= op["row_pad"])
    want = as_int32(walk_count(N, graph, seeds, pairs))
    if case in ("heavy_weights_wrap", "a_whole_table") and pairs >= 3:
        assert walk_count(N, graph, seeds, pairs) >= 2**31  # the int32 sums wrap
    with tracing.request("count", trace_id="rows"), telemetry.span("statement"):
        got = device_count(gm, persons, seeds, pairs)
    assert got == want
    assert first_hops() == ({"rows": 1} if by_rows else {"sweep": 1})
    (launch,) = named(tracing.get_trace("rows")["spans"], "dispatch_launch")
    assert launch["labels"] == {"batch": "1", "lanes": "8", "sweeps": str(pairs - 2 if by_rows else pairs - 1)}
    (event,) = compile_log.events()
    slots = int(op["csrc"].shape[0])
    # lanes x frontier pad x n_cap x (cptr, csrc a swept hop) x (indptr,)
    pad = 128 if by_rows else next_pow2(max(len(seeds), cnf.TPU_GRAPH_FRONTIER_PAD))
    hops = (257, slots) * (pairs - 2 if by_rows else pairs - 1)
    assert event["shape"] == f"8x{pad}x256x{hops}x(257,)"
    # and the sweep from the seed, as every count was served before, gives the same bits
    swept_from_the_seed(monkeypatch)
    assert device_count(gm, persons, seeds, pairs) == want
    assert first_hops() == ({"rows": 1, "sweep": 1} if by_rows else {"sweep": 2})
    assert forms() == {"csc": 2} and gm._csc_pair(NS, DB, *PAIR) is op


def test_a_chain_of_one_pair_has_nothing_to_sweep_and_keeps_its_seeds(graph):
    gm, persons = mirrors_of(N, graph)
    with tracing.request("count", trace_id="one-pair"), telemetry.span("statement"):
        assert device_count(gm, persons, {HUB: 2, 5: 1}, 1) == walk_count(N, graph, {HUB: 2, 5: 1}, 1)
    assert first_hops() == {"sweep": 1}
    (launch,) = named(tracing.get_trace("one-pair")["spans"], "dispatch_launch")
    assert launch["labels"] == {"batch": "1", "lanes": "8", "sweeps": "0"}
    (event,) = compile_log.events()
    assert event["shape"] == f"8x{next_pow2(cnf.TPU_GRAPH_FRONTIER_PAD)}x256x()x(257,)"


def test_the_records_operand_and_the_dense_form_leave_from_the_seed(monkeypatch, graph):
    gm, persons = mirrors_of(N, graph)
    monkeypatch.setattr(gm, "_csc_pair", lambda *a, **k: None)
    assert device_count(gm, persons, {5: 1}, 3) == walk_count(N, graph, {5: 1}, 3)
    assert first_hops() == {"sweep": 1}
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", 16384)
    gm2, persons2 = mirrors_of(N, graph)
    assert device_count(gm2, persons2, {5: 1}, 3) == walk_count(N, graph, {5: 1}, 3)
    assert forms() == {"csc": 1, "dense": 1} and first_hops() == {"sweep": 1}  # a dense count names no first hop


# ------------------------------------------------------------------ the rows
def test_the_operator_keeps_its_rows_by_source_beside_the_swept_arrays():
    edges = np.asarray([(0, 1), (0, 2), (1, 2), (3, 2), (3, 2), (4, 0), (3, 3), (0, 1)])
    gm, _ = mirrors_of(5, edges)
    m1, m2 = (gm.get(NS, DB, tb, keys.DIR_OUT, ft) for tb, ft in (("person", "knows"), ("knows", "person")))
    space = gm.table_space(NS, DB, "person")
    ls, ld = graph_csr._compose_coo(*m1.host_arrays(), *m2.host_arrays(), space, space, max_paths=100)
    assert (np.diff(ls) >= 0).all()  # source order: a row is a slice
    op = gm._csc_pair(NS, DB, *PAIR)
    indptr, dst = op["by_src"]
    assert indptr.tolist() == np.asarray(op["indptr"]).tolist() == [0, 3, 4, 4, 7, 8, 8, 8, 8]
    assert dst.dtype == np.int32 and [sorted(dst[a:b].tolist()) for a, b in zip(indptr[:5], indptr[1:6])] == [
        [1, 1, 2], [2], [], [2, 2, 3], [0]]  # a repeated record stays repeated, a self loop is a row's entry
    assert op["row_pad"] == 4  # the power of two at or above the longest row (3)
    # the same paths as the destination-sorted arrays the kernel sweeps
    cptr, csrc = op["by_dst"]
    by_dst = sorted((int(s), v) for v in range(5) for s in csrc[cptr[v]:cptr[v + 1]])
    assert by_dst == sorted((s, int(d)) for s in range(5) for d in dst[indptr[s]:indptr[s + 1]])


def test_seed_rows_lays_row_after_row_with_the_seed_s_weight():
    indptr = np.asarray([0, 3, 4, 4, 7, 8], dtype=np.int32)
    dst = np.asarray([1, 2, 1, 2, 2, 3, 2, 0], dtype=np.int32)

    def rows(pad, seeds, weights):
        return graph_csr._seed_rows({"by_src": (indptr, dst), "row_pad": pad, "n_pad": 99}, np.asarray(seeds), np.asarray(weights))

    fr, cw = rows(8, [0, 2, 3], [5, 9, 2])
    assert fr.tolist() == [1, 2, 1, 2, 3, 2, 99, 99] and cw.tolist() == [5, 5, 5, 2, 2, 2, 0, 0]
    assert fr.dtype == cw.dtype == np.int32
    # a seed of degree 0 alone: nothing but pad; rows one entry past the pad: refused
    fr, cw = rows(4, [2], [7])
    assert fr.tolist() == [99] * 4 and not cw.any()
    assert rows(4, [0, 1], [1, 1]) is not None and rows(4, [0, 1, 4], [1, 1, 1]) is None


@pytest.mark.parametrize("longest, pad", [
    (0, 1), (1, 1), (3, 4), (101, 128), (977, 1024), (1024, 1024), (1025, 2048), (2533, 4096), (4096, 4096),
    (4097, 0), (36_864, 0),  # a hub's row past ROW_PAD_MAX: no pad, every count sweeps from its seeds
], ids=["empty", "one", "tiny", "test_graph", "snb_sf3", "at_a_power", "over_a_power", "synthetic_sf3", "at_the_ceiling",
        "one_past_the_ceiling", "a_hub_at_sf3"])
def test_the_row_pad_is_the_power_of_two_over_the_longest_row_up_to_its_ceiling(longest, pad):
    assert graph_csr.ROW_PAD_MAX == 4096
    assert graph_csr._row_pad(longest) == pad


def test_an_operator_with_a_hub_row_past_its_ceiling_sweeps_from_every_seed():
    n, hub = 6000, 5000
    rng = np.random.default_rng(5)
    others = np.stack([np.arange(1, n), rng.integers(0, n, n - 1)], axis=1)
    edges = np.concatenate([np.stack([np.zeros(hub, dtype=np.int64), rng.integers(1, n, hub)], axis=1), others])
    gm, persons = mirrors_of(n, edges)
    op = gm._csc_pair(NS, DB, *PAIR)
    assert op["row_pad"] == 0 and int(np.diff(op["by_src"][0]).max()) > graph_csr.ROW_PAD_MAX
    for seeds in ({0: 1}, {17: 2}):  # the hub itself, and a seed whose own row is one entry
        assert device_count(gm, persons, seeds, 3) == walk_count(n, edges, seeds, 3)
    assert first_hops() == {"sweep": 2}
    assert {e["shape"].split("x")[1] for e in compile_log.events()} == {str(next_pow2(cnf.TPU_GRAPH_FRONTIER_PAD))}
    gm.warm_count_kernels(NS, DB)  # and the warm-up compiles what such an operator is served by: nothing new at 8 lanes
    assert telemetry.counters_matching("prewarm_errors") == {}
    served = [e for e in compile_log.events() if e["mode"] != "prewarm"]
    assert len(served) == 1


# ------------------------------------------------------------------ the shape rule
@pytest.mark.parametrize("riders", [2, 8, 9])
def test_riders_of_degree_one_and_of_the_longest_row_share_one_dispatch_and_one_program(graph, riders):
    deg = np.bincount(graph[:, 0], minlength=N)
    thin = [int(p) for p in np.flatnonzero(deg == 1)] + [int(p) for p in np.flatnonzero(deg == 2)]
    seed_sets = [{HUB: 1}] + [{HUB if i % 2 else thin[i]: 1 + i} for i in range(riders)]
    assert {int(deg[next(iter(s))]) for s in seed_sets[1:]} >= {1, LONGEST + 1}
    gm, persons = mirrors_of(N, graph)
    got, q = serve_batch(gm, persons, seed_sets, 3)
    assert got == [walk_count(N, graph, s, 3) for s in seed_sets]
    # the lead alone, then every other rider in ONE dispatch of one bucket, whatever its seed's degree
    assert q.width_distribution() == {1: 1, riders: 1}
    assert first_hops() == {"rows": riders + 1}
    for i in range(1, riders + 1):
        assert launch_labels(i) == {"batch": str(riders), "lanes": str(count_lanes(riders)), "sweeps": "1"}
    # one graph_csc shape a generation and lane count
    shapes = [e["shape"] for e in compile_log.events()]
    slots = int(gm._csc_pair(NS, DB, *PAIR)["csrc"].shape[0])
    assert sorted(shapes) == sorted({f"{lanes}x128x256x(257, {slots})x(257,)" for lanes in {8, count_lanes(riders)}})


def test_the_warm_up_compiles_both_entries_of_a_count_the_rows_and_the_sweep_from_the_seeds(graph):
    gm, persons = mirrors_of(N, graph)
    gm.warm_count_kernels(NS, DB)
    warmed = compile_log.events()
    slots = int(gm._csc_pair(NS, DB, *PAIR)["csrc"].shape[0])
    fsz = next_pow2(cnf.TPU_GRAPH_FRONTIER_PAD)
    hop, two = f"(257, {slots})", f"(257, {slots}, 257, {slots})"
    # from the seeds: one, two and three pairs; from their rows, at the operator's pad and a sweep less: two and three
    bare = {f"8x{fsz}x256x()x(257,)", f"8x{fsz}x256x{hop}x(257,)", f"8x{fsz}x256x{two}x(257,)",
            "8x128x256x()x(257,)", f"8x128x256x{hop}x(257,)"}
    person_pair = [e["shape"] for e in warmed if e["shape"].split("x")[2] == "256"]  # n_cap: the person table's
    assert {s for s in person_pair if s.startswith("8x") and not s.endswith("w")} == bare
    assert len(person_pair) == 2 * 4 * len(bare)  # bare and weighted, at each lane count
    assert telemetry.counters_matching("prewarm_errors") == {}
    for pairs in (1, 2, 3):
        assert device_count(gm, persons, {HUB: 1}, pairs) == walk_count(N, graph, {HUB: 1}, pairs)
    # seeds whose rows pass the pad are swept from, on a warmed program too
    many, _ = SEEDS["several_over_the_pad"]
    for pairs in (2, 3):
        assert device_count(gm, persons, many, pairs) == as_int32(walk_count(N, graph, many, pairs))
    assert first_hops() == {"rows": 2, "sweep": 3}
    assert compile_log.events() == warmed  # nothing compiled by a served count


# ------------------------------------------------------------------ one generation
def small_world(n: int = 60, seed: int = 2) -> np.ndarray:
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), rng.integers(1, 6, n))
    return np.stack([src, rng.integers(0, n, src.size)], axis=1)


def test_after_a_relate_the_next_count_reads_rows_and_operator_of_the_new_generation(ds, monkeypatch):
    n, edges = 60, small_world()
    sess = loaded(ds, monkeypatch, n, edges)
    first, spans = traced_count(ds, sess, 0, "gen-1")
    assert first == walk_count(n, edges, {0: 1}, 3)
    assert [s["labels"]["first_hop"] for s in named(spans, "graph_prepare")] == ["rows"]
    (old,) = ds.graph_mirrors._csc.values()
    # person 0's new friend is somebody it did not know: the edge is in the FIRST hop, which the rows serve
    friend = next(i for i in range(1, n) if i not in set(edges[edges[:, 0] == 0][:, 1].tolist()))
    (res,) = ds.execute(f"RELATE person:0->knows->person:{friend}", sess)
    assert res["status"] == "OK", res
    grown = np.concatenate([edges, [(0, friend)]])
    second, spans = traced_count(ds, sess, 0, "gen-2")
    assert second == walk_count(n, grown, {0: 1}, 3) != first
    assert [s["labels"]["first_hop"] for s in named(spans, "graph_prepare")] == ["rows"]
    (new,) = ds.graph_mirrors._csc.values()
    assert new is not old and new["gen"] != old["gen"]
    # rows and swept arrays were made together: the new generation's rows hold the edge, the old one's never will
    space = ds.graph_mirrors.table_space(NS, DB, "person")["inv"]
    it = ds.graph_mirrors.interner(NS, DB)
    local = {i: space[it.lookup(Thing("person", i))] for i in (0, friend)}

    def row(op, s):
        indptr, dst = op["by_src"]
        return dst[indptr[s]:indptr[s + 1]].tolist()

    assert local[friend] in row(new, local[0]) and local[friend] not in row(old, local[0])
    assert len(row(new, local[0])) == len(row(old, local[0])) + 1
    assert int(np.asarray(new["indptr"])[-1]) == len(grown) == new["by_src"][1].size


@pytest.mark.parametrize("how", ["drop_table", "drop_db", "clear"])
def test_the_host_rows_go_with_the_operator_when_its_mirrors_go(graph, how):
    gm, persons = mirrors_of(N, graph)
    assert device_count(gm, persons, {5: 1}, 3) == walk_count(N, graph, {5: 1}, 3)
    (op,) = gm._csc.values()
    assert op["by_src"][1].size == len(graph)
    {"drop_table": lambda: gm.drop_table(NS, DB, "knows"), "drop_db": lambda: gm.drop_db(NS, DB), "clear": gm.clear}[how]()
    assert gm._csc == {}  # _forget_derived: the rows are the operator's, and went with it


# ------------------------------------------------------------------ ending in a predicate
NAMES = ["Anna", "Bo", "Chen"]
BY_NAME = "SELECT count({}(person WHERE firstName = $fn)) AS c FROM type::thing('person', $p)"


@pytest.fixture
def named_persons(ds, monkeypatch):
    n, edges = 60, small_world(seed=7)
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 1)
    sess = Session.owner(NS, DB)
    ds.execute("DEFINE TABLE person SCHEMALESS; DEFINE TABLE knows SCHEMALESS", sess)
    rows = [{"id": i, "firstName": NAMES[i % 3]} for i in range(n)]
    ds.execute("INSERT INTO person $rows RETURN NONE", sess, {"rows": rows})
    rel = [{"in": Thing("person", int(a)), "out": Thing("person", int(b))} for a, b in edges]
    (res,) = ds.execute("INSERT RELATION INTO knows $rows RETURN NONE", sess, {"rows": rel})
    assert res["status"] == "OK", res
    return ds, sess, n, edges


def walk_ending(n, edges, start, pairs, name) -> int:
    x = np.zeros(n, dtype=np.int64)
    x[start] = 1
    for _ in range(pairs):
        y = np.zeros(n, dtype=np.int64)
        np.add.at(y, edges[:, 1], x[edges[:, 0]])
        x = y
    return int(x[[i for i in range(n) if NAMES[i % 3] == name]].sum())


@pytest.mark.parametrize("pairs", [1, 2, 3, 4])
def test_a_count_that_ends_in_a_predicate_changes_in_the_same_way(named_persons, monkeypatch, pairs):
    ds, sess, n, edges = named_persons
    sql = BY_NAME.format("->knows->person" * (pairs - 1) + "->knows->")
    got = {}
    for how in ("rows", "sweep"):
        if how == "sweep":
            swept_from_the_seed(monkeypatch)
        for start, name in ((0, "Anna"), (7, "Chen")):
            with tracing.request("count", trace_id=f"{how}-{start}"):
                (res,) = ds.execute(sql, sess, {"p": start, "fn": name})
            assert res["status"] == "OK", res
            got[how, start] = res["result"][0]["c"]
            assert got[how, start] == walk_ending(n, edges, start, pairs, name)
            spans = tracing.get_trace(f"{how}-{start}")["spans"]
            (prepare,) = named(spans, "graph_prepare")
            by_rows = how == "rows" and pairs > 1
            assert prepare["labels"] == {"form": "csc", "filter": "fused", "operand": "composed",
                                         "first_hop": "rows" if by_rows else "sweep"}
            (launch,) = named(spans, "dispatch_launch")
            assert launch["labels"]["sweeps"] == str(max(pairs - 2, 0) if by_rows else pairs - 1)
            # the end weights are over the LAST pair and ride as before: built once a bound value, found by
            # the swept count too
            (made,) = named(spans, "graph_filter")
            assert made["labels"]["outcome"] == ("build" if how == "rows" else "hit")
    assert any(got.values())


# ------------------------------------------------------------------ the reader
def reader():
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", "graph_first_hop_rows_share.py")
    spec = importlib.util.spec_from_file_location("graph_first_hop_rows_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ctx_of(*label_sets):
    docs = []
    for labels in label_sets:
        spans = [{"id": 1, "parent": None, "name": "ws_rpc", "labels": {}, "start_ms": 0.0, "dur_ms": 9.0, "error": None}]
        if labels is not None:
            spans.append({"id": 7, "parent": 1, "name": "graph_prepare", "labels": labels, "start_ms": 0.6,
                          "dur_ms": 0.2, "error": None})
        docs.append({"record": {"t0": 100.0, "t1": 100.013}, "doc": {"trace_id": "t", "ts": 0.0, "spans": spans}})
    return {"tagged": docs}


ROWS = {"form": "csc", "operand": "composed", "filter": "none", "first_hop": "rows"}
SWEEP = {**ROWS, "first_hop": "sweep"}
OLDER = {"form": "csc", "operand": "composed", "filter": "none"}


@pytest.mark.parametrize("spans, share", [
    ([ROWS], 1.0),
    ([SWEEP], 0.0),
    ([OLDER], 0.0),  # the parent's program: a csc count with no label was swept
    ([ROWS, ROWS, SWEEP, OLDER], 0.5),
    ([ROWS, {"form": "dense", "filter": "none"}, {"form": "host", "filter": "none"}, None], 1.0),  # of the csc counts
    ([{"form": "dense", "filter": "none"}], None),
    ([{"form": "dense", "first_hop": "rows"}], None),  # only a csc count has a first hop to read
    ([{}], None),
    ([None, None], None),
    ([], None),
], ids=["rows", "sweep", "older_program", "mixed", "other_forms_do_not_dilute", "dense_alone", "label_on_dense",
        "no_form", "no_span", "no_statement"])
def test_the_reader_on_hand_written_docs(spans, share):
    got = reader().read(ctx_of(*spans))
    assert got is None if share is None else got == pytest.approx(share)


def test_the_manifest_has_the_reader_s_entry_after_those_that_were_there():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mod = reader()
    entry = {
        "name": mod.NAME, "unit": mod.UNIT, "better": "higher", "source": mod.SOURCE, "layer": mod.LAYER,
        "moves": mod.MOVES, "workloads": ["snbsf3.hop3_c8", "snbsf3ic1.name3_c8"]}
    names = [m["name"] for m in manifest["per_layer"]]
    assert entry in manifest["per_layer"] and names.count(mod.NAME) == 1
    assert names.index(mod.NAME) > names.index("col.prepare_ms")  # PR 40's, the last one the parent had
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        "graph.first_hop_rows_share", "ratio", "kernels", "p50_ms", "program_span")
    assert set(entry["workloads"]) <= {w["name"] for w in manifest["workloads"]}


def test_a_served_statement_s_span_is_what_the_reader_reads(ds, monkeypatch):
    n, edges = 60, small_world()
    sess = loaded(ds, monkeypatch, n, edges)
    docs = []
    for i, start in enumerate((0, 3, 9)):
        _, spans = traced_count(ds, sess, start, f"served-{i}")
        docs.append({"record": {}, "doc": {"spans": spans}})
    assert reader().read({"tagged": docs}) == 1.0
    swept_from_the_seed(monkeypatch)
    _, spans = traced_count(ds, sess, 5, "served-swept")
    assert reader().read({"tagged": docs + [{"record": {}, "doc": {"spans": spans}}]}) == 0.75
    assert first_hops() == {"rows": 3, "sweep": 1}
    text = telemetry.render_prometheus()
    assert 'surreal_graph_csc_first_hop_total{how="rows"} 3' in text


# ------------------------------------------------------------------ the bucket
@pytest.mark.parametrize("pairs, rows, paced", [
    (1, True, False), (2, True, False),  # nothing swept: a small program, the queue's own depth
    (3, True, True),                     # from the rows, one sweep left: one deep, and it gathers
    (4, True, False),                    # two sweeps left: the device clocks it, the queue's depth keeps it fed
    (2, False, False), (3, False, False),  # swept from the seeds: the bucket it always had
])
def test_a_count_from_the_rows_with_one_sweep_left_is_one_deep_and_gathers(monkeypatch, graph, pairs, rows, paced):
    from surrealdb_tpu.dbs import dispatch

    if not rows:
        swept_from_the_seed(monkeypatch)
    gm, persons = mirrors_of(N, graph)
    q = DispatchQueue()
    frontier, counts = np.asarray([persons[HUB]], dtype=np.int32), np.asarray([1], dtype=np.int32)
    got = gm._device_chain(NS, DB, frontier, counts, PAIR * pairs, count_only=True, dispatch=q)
    assert got == walk_count(N, graph, {HUB: 1}, pairs)
    (bucket,) = q._buckets.values()
    assert (bucket.depth, bucket.gather) == ((dispatch.SWEEP_DEPTH, True) if paced else (q._depth(), False))
    assert dispatch.SWEEP_DEPTH == 1 and q._depth() == cnf.DISPATCH_PIPELINE_DEPTH == 2
