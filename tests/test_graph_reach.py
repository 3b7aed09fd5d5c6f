"""`array::distinct(<graph chain>)` is a device path (ISSUE 44): the set a
chain reaches comes off the mirrors hop by hop and never as the expanded
multiset. On small seeded SNB-shaped graphs (the benchmark's own generator:
triangles, walks that return to their start, isolated and degree-1 persons,
hubs) the rings equal the plain reference's sets
(`benchmarks/deployments/graph_filtered_reach.py`: NumPy, nothing of the
program) at every depth, with and without a WHERE; the timed statement's rows
are IC1's; a statement's three expressions are ONE dispatch through the
statement's ring memo; riders of different starts and names share a batch and
each get their own answer; an acknowledged UPDATE of the filtered field and an
acknowledged RELATE are seen by the next statement; what cannot ride is walked
as before and says `filter=host`; the multiset of a bare expansion is what it
was; and the kernel lowered for a TPU at SF3's shapes holds no reduce-window
over the slots."""

import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import cnf, compile_log, telemetry, tracing
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.idx import graph_csr
from surrealdb_tpu.sql.value import Thing
from surrealdb_tpu.syn.parser import parse_query
from test_graph_count_lanes import HeldQueue

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import manifest as mf  # noqa: E402

CELL, CONFIG = "snbsf3ic1d.near20_c8", "snbsf3ic1d"
SIZES = {"nodes": 300, "pairs": 2400, "pool": 24, "names": 6}
SEED = 2**31 + 44
RING = "array::distinct({chain})"
CHAINS = ["->knows->", "->knows->person->knows->", "->knows->person->knows->person->knows->"]
NAMED, BARE = "(person WHERE firstName = $q.fn)", "person"
FROM = " FROM ONLY type::thing('person', $q.p)"


def three_fields(last: str) -> str:
    return "SELECT " + ", ".join(f"{RING.format(chain=c + last)} AS d{i + 1}" for i, c in enumerate(CHAINS)) + FROM


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    return mf.load_modules(BENCH, "deployments", "KIND")["graph_filtered_reach"]


@pytest.fixture(scope="module")
def world(cfg, kind):
    data = kind.generate(cfg, SIZES, SEED)
    return data, kind.reference(cfg, data), kind.pool(cfg, data)


def execute_ok(ds, sql, vars=None):
    out = ds.execute(sql, Session.owner("bench", "bench"), vars=vars)
    assert all(r["status"] == "OK" for r in out), out
    return out


def serve(ds, cfg, kind, data, monkeypatch, prewarm=False):
    """The deployment loaded as a run loads it, the loader's two probes included."""
    telemetry.reset()
    compile_log.reset()
    tracing.store_reset()
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", prewarm)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    loaded = kind.load(ds, cfg, dict(data), execute_ok)
    telemetry.reset()  # the loader's own statements are not the test's
    return loaded


@pytest.fixture
def served(ds, cfg, kind, world, monkeypatch):
    serve(ds, cfg, kind, world[0], monkeypatch)
    yield ds
    tracing.store_reset()


def ask(ds, sql, q, tid):
    """(the last statement's result, the labels of the request's graph_prepare spans, its dispatch_launch labels)"""
    with tracing.request("reach", trace_id=tid):
        out = execute_ok(ds, sql, {"q": q})
    spans = tracing.get_trace(tid)["spans"]
    return (out[-1]["result"], [s["labels"] for s in spans if s["name"] == "graph_prepare"],
            [s["labels"] for s in spans if s["name"] == "dispatch_launch"])


def ids_of(things) -> list:
    """The persons' ids, ascending: inside a ring the program's order is the
    order its mirror met the persons in (the load's), which no reference has.
    A person twice stays twice."""
    return sorted(int(t.id) for t in things)


def reached() -> dict:
    return {tuple(sorted(dict(k).items())): int(v) for k, v in telemetry.counters_matching("graph_reach").items()}


# ------------------------------------------------------------------ the graph is what the tests are about
def test_the_seeded_graph_has_triangles_returning_walks_isolated_persons_leaves_and_hubs(world):
    data, ref, pool = world
    pairs, n = data["pairs"], data["nodes"]
    degree = np.bincount(pairs[:, 0], minlength=n)
    assert (degree == 0).any() and (degree == 1).any() and degree.max() >= 64
    adj = np.zeros((n, n), dtype=bool)
    adj[pairs[:, 0], pairs[:, 1]] = True
    assert (adj == adj.T).all() and np.trace(adj.astype(np.int64) @ adj @ adj) > 0  # symmetric, with triangles
    # a start person of the asked name is in its own ring 2: the walk returns
    own = [q for q, e in enumerate(pool) if data["names"][int(data["first"][e["p"]])] == e["fn"]]
    assert own and all(pool[q]["p"] in ref["rings"][q][1].tolist() for q in own)
    assert ref["start_in_ball"] == len(own)
    assert len({e["fn"] for e in pool}) >= 3


# ------------------------------------------------------------------ rings as whole sets
@pytest.mark.parametrize("where", ["named", "bare"])
def test_the_three_rings_are_the_reference_s_sets_and_one_dispatch(served, kind, world, where):
    data, ref, pool = world
    walks = kind.walks_by_steps(data["pairs"], data["nodes"], data["starts"], 3)
    for q in range(len(pool)):
        before = served.dispatch.stats()["submitted"]
        row, prepares, launches = ask(served, three_fields(NAMED if where == "named" else BARE), pool[q], f"{where}-{q}")
        assert served.dispatch.stats()["submitted"] - before == 1 == len(launches)
        for h in range(3):
            want = ref["rings"][q][h].tolist() if where == "named" else np.flatnonzero(walks[h][q] > 0).tolist()
            assert ids_of(row[f"d{h + 1}"]) == want  # each once
        # the first expression runs the deepest chain, the other two read its rings
        assert [(p["memo"], p["depth"]) for p in prepares] == [("fill", "1"), ("hit", "2"), ("hit", "3")]
        assert all(p["form"] == "csc" and p["operand"] == "composed" and p["rings"] == "3" for p in prepares)
        assert all(p["filter"] == ("fused" if where == "named" else "none") for p in prepares)
        assert [int(p["ids"]) for p in prepares] == [len(row[f"d{h}"]) for h in (1, 2, 3)]
        assert launches[0]["lanes"] == "8" and int(launches[0]["slots"]) > 0
    how = "fused" if where == "named" else "none"
    # the expression that ran the chain says where its last hop came from: three pairs sweep
    assert reached() == {
        (("filter", how), ("form", "csc"), ("last_hop", "sweep"), ("operand", "composed")): len(pool),
        (("filter", how), ("form", "csc"), ("last_hop", "none"), ("operand", "composed")): 2 * len(pool),
    }


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("where", ["named", "bare"])
def test_one_chain_alone_is_its_ring_and_a_chain_of_one_pair_makes_no_dispatch(served, kind, world, depth, where):
    data, ref, pool = world
    walks = kind.walks_by_steps(data["pairs"], data["nodes"], data["starts"], depth)
    sql = f"SELECT VALUE {RING.format(chain=CHAINS[depth - 1] + (NAMED if where == 'named' else BARE))}" + FROM
    before = served.dispatch.stats()["submitted"]
    for q in range(8):
        got, prepares, launches = ask(served, sql, pool[q], f"alone-{q}")
        want = ref["rings"][q][depth - 1] if where == "named" else np.flatnonzero(walks[depth - 1][q] > 0)
        assert ids_of(got) == want.tolist()
        assert [(p["memo"], p["depth"], p["rings"]) for p in prepares] == [("fill", str(depth), str(depth))]
        assert len(launches) == (depth > 1)
    assert served.dispatch.stats()["submitted"] - before == 8 * (depth > 1)


def test_a_start_nobody_knows_and_a_name_nobody_has_reach_nothing(served, world):
    data, _, pool = world
    lonely = int(np.flatnonzero(np.bincount(data["pairs"][:, 0], minlength=data["nodes"]) == 0)[0])
    before = served.dispatch.stats()["submitted"]
    row, prepares, _ = ask(served, three_fields(NAMED), {"p": lonely, "fn": pool[0]["fn"]}, "lonely")
    assert row == {"d1": [], "d2": [], "d3": []} and [p["memo"] for p in prepares] == ["fill", "hit", "hit"]
    assert served.dispatch.stats()["submitted"] == before  # a record no edge leaves is no dispatch
    row, _, launches = ask(served, three_fields(NAMED), {"p": pool[0]["p"], "fn": "Nobody"}, "nobody")
    assert row == {"d1": [], "d2": [], "d3": []} and len(launches) == 1


# ------------------------------------------------------------------ the timed statement
def test_the_timed_statement_s_rows_are_ic1_s(served, cfg, kind, world):
    data, ref, pool = world
    sql = cfg["statements"]["primary"]["sql"]
    short = 0
    for q in range(len(pool)):
        before = served.dispatch.stats()["submitted"]
        rows, prepares, launches = ask(served, sql, pool[q], f"timed-{q}")
        assert served.dispatch.stats()["submitted"] - before == 1 == len(launches)
        ids = [int(r["id"].id) for r in rows]
        every, dist = ref["ball"][q]
        assert len(ids) == min(20, every.size) and kind.judge(ids, ref["ball"][q], 20) == dict.fromkeys(kind.NUMBERS, 0)
        # nearest first: the distances are the ball's first ones, ring by ring
        assert [int(dist[every.tolist().index(i)]) for i in ids] == dist[: len(ids)].tolist()
        assert [r["lastName"] for r in rows] == [kind.person(data, i)["lastName"] for i in ids]
        assert [p["memo"] for p in prepares] == ["fill", "hit", "hit"]
        short += len(ids) < 20
    assert 0 < short < len(pool)  # both kinds of answer are in the pool


def test_the_materialise_span_runs_from_the_rings_to_the_outer_statement_s_rows(served, cfg, world):
    _, _, pool = world
    with tracing.request("reach", trace_id="spans"):
        execute_ok(served, cfg["statements"]["primary"]["sql"], {"q": pool[0]})
    spans = tracing.get_trace("spans")["spans"]
    inner, outer = [s for s in spans if s["name"] == "materialise"]
    (fetch,) = [s for s in spans if s["name"] == "dispatch_fetch"]
    # the inner statement's span starts when the rings are back, the outer's where the inner's ends
    assert fetch["start_ms"] + fetch["dur_ms"] <= inner["start_ms"] + 1e-6
    assert abs(inner["start_ms"] + inner["dur_ms"] - outer["start_ms"]) < 0.5


# ------------------------------------------------------------------ batches
def serve_batch(ds, monkeypatch, requests):
    """Every request through ds.execute(), the first alone (it holds the
    bucket) and the rest as ONE batch behind it."""
    q, got = HeldQueue(), {}
    monkeypatch.setattr(ds, "dispatch", q)

    def rider(i, sql, bound):
        got[i] = ask(ds, sql, bound, f"rider-{i}")

    threads = [threading.Thread(target=rider, args=(i, sql, b)) for i, (sql, b) in enumerate(requests)]
    threads[0].start()
    assert q.started.wait(60)
    for t in threads[1:]:
        t.start()
    deadline = time.monotonic() + 60
    while q.queued() < len(requests) - 1 and time.monotonic() < deadline:
        time.sleep(0.002)
    q.release.set()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads) and len(got) == len(requests)
    return [got[i] for i in range(len(requests))], q


@pytest.mark.parametrize("riders", [1, 5, 8, 9])
def test_riders_of_different_starts_and_names_share_one_dispatch(served, world, monkeypatch, riders):
    _, ref, pool = world
    answers, q = serve_batch(served, monkeypatch, [(three_fields(NAMED), pool[i]) for i in range(riders + 1)])
    assert len({(e["p"], e["fn"]) for e in pool[1 : riders + 1]}) >= min(riders, 2)
    for i, (row, prepares, launches) in enumerate(answers):
        assert [ids_of(row[f"d{h}"]) for h in (1, 2, 3)] == [r.tolist() for r in ref["rings"][i]]
        assert len(launches) == 1
    assert q.width_distribution() == ({1: 2} if riders == 1 else {1: 1, riders: 1})
    assert [a[2][0]["batch"] for a in answers[1:]] == [str(riders)] * riders
    assert [a[2][0]["lanes"] for a in answers[1:]] == ["16" if riders == 9 else "8"] * riders
    # a masked and an unmasked statement ride one program too: the key has no value and no ending
    compiled = {e["shape"] for e in compile_log.events() if e["subsystem"] == "graph_reach" and e["mode"] != "prewarm"}
    assert len(compiled) == (1 if riders < 9 else 2)


def test_a_masked_and_a_bare_statement_share_a_batch(served, kind, world, monkeypatch):
    data, ref, pool = world
    walks = kind.walks_by_steps(data["pairs"], data["nodes"], data["starts"], 3)
    requests = [(three_fields(NAMED), pool[0]), (three_fields(NAMED), pool[1]), (three_fields(BARE), pool[2])]
    answers, q = serve_batch(served, monkeypatch, requests)
    assert q.width_distribution() == {1: 1, 2: 1}
    assert ids_of(answers[1][0]["d3"]) == ref["rings"][1][2].tolist()
    assert ids_of(answers[2][0]["d3"]) == np.flatnonzero(walks[2][2] > 0).tolist()


# ------------------------------------------------------------------ no stale mask, no stale operand
def test_an_acknowledged_update_and_an_acknowledged_relate_are_seen_by_the_next_statement(served, world):
    data, ref, pool = world
    q = next(i for i, e in enumerate(pool) if ref["rings"][i][2].size >= 2 and ref["rings"][i][0].size == 0)
    entry, ring3 = pool[q], ref["rings"][q][2].tolist()
    row, _, _ = ask(served, three_fields(NAMED), entry, "before")
    assert ids_of(row["d3"]) == ring3
    # a person of ring 3 takes another name: gone from the next answer
    gone = ring3[0]
    execute_ok(served, "UPDATE type::thing('person', $p) SET firstName = 'Renamed'", {"p": gone})
    row, prepares, _ = ask(served, three_fields(NAMED), entry, "renamed")
    assert ids_of(row["d3"]) == sorted(ring3[1:]) and gone not in ids_of(row["d1"]) + ids_of(row["d2"])
    # and is found under the new name
    row, _, _ = ask(served, three_fields(NAMED), {"p": entry["p"], "fn": "Renamed"}, "found")
    assert gone in ids_of(row["d3"])
    # the start person meets a person of the name who stood in ring 3 alone: ring 1 has it at once
    far = next(i for i in ring3[1:] if i not in ref["rings"][q][1].tolist())
    execute_ok(served, "RELATE $a->knows->$b", {"a": Thing("person", entry["p"]), "b": Thing("person", far)})
    row, _, launches = ask(served, three_fields(NAMED), entry, "related")
    assert ids_of(row["d1"]) == [far] and len(launches) == 1


def test_uncommitted_edge_writes_take_the_kv_walk(served, world):
    _, ref, pool = world
    entry = pool[0]
    new = 10_000
    sql = (f"BEGIN; CREATE person:{new} SET firstName = $q.fn; RELATE $a->knows->person:{new}; "
           + three_fields(NAMED) + "; COMMIT")
    before = served.dispatch.stats()["submitted"]
    out = execute_ok(served, sql, {"q": entry, "a": Thing("person", entry["p"])})
    assert ids_of(out[-1]["result"]["d1"]) == sorted(ref["rings"][0][0].tolist() + [new])
    assert served.dispatch.stats()["submitted"] == before


# ------------------------------------------------------------------ what cannot ride
UNFUSED = {
    "unlowerable": "->knows->person->knows->(person WHERE string::len(firstName) = $q.n)",
    "middle_part": "->knows->(person WHERE firstName = $q.fn)->knows->person",
    "edge_part": "->(knows WHERE since > 3)->person",
}


@pytest.mark.parametrize("case", sorted(UNFUSED))
def test_a_where_that_cannot_ride_is_walked_and_says_filter_host(served, world, case):
    data, _, pool = world
    entry = {**pool[3], "n": len(pool[3]["fn"])}
    before = served.dispatch.stats()["submitted"]
    got, prepares, launches = ask(served, f"SELECT VALUE {RING.format(chain=UNFUSED[case])}" + FROM, entry, case)
    out: dict = {}
    for a, b in data["pairs"].tolist():
        out.setdefault(a, []).append(b)
    name = lambda i: data["names"][int(data["first"][i])]  # noqa: E731
    if case == "unlowerable":
        want = {w for v in out[entry["p"]] for w in out[v] if len(name(w)) == entry["n"]}
    elif case == "middle_part":
        want = {w for v in out[entry["p"]] if name(v) == entry["fn"] for w in out[v]}
    else:
        want = set()
    assert sorted(ids_of(got)) == sorted(want) and len(got) == len(want)
    assert launches == [] and served.dispatch.stats()["submitted"] == before
    assert [(p["form"], p["filter"], p["memo"]) for p in prepares] == [("host", "host", "fill")]
    assert reached() == {(("filter", "host"), ("form", "host"), ("last_hop", "none"), ("operand", "none")): 1}


def test_with_the_device_off_the_host_walks_sets_and_never_the_multiset(served, world, monkeypatch):
    _, ref, pool = world
    monkeypatch.setattr(cnf, "TPU_DISABLE", True)
    laid_out = []
    monkeypatch.setattr(graph_csr.GraphMirrors, "chain", lambda *a, **k: laid_out.append(a) or [])
    before = served.dispatch.stats()["submitted"]
    for q in range(6):
        row, prepares, launches = ask(served, three_fields(NAMED), pool[q], f"host-{q}")
        assert [ids_of(row[f"d{h}"]) for h in (1, 2, 3)] == [r.tolist() for r in ref["rings"][q]]
        assert [(p["form"], p["filter"], p["memo"]) for p in prepares] == [
            ("host", "fused", "fill"), ("host", "fused", "hit"), ("host", "fused", "hit")]
        assert launches == []
    assert served.dispatch.stats()["submitted"] == before and laid_out == []


def serve_hubs(ds, cfg, kind, data, monkeypatch):
    """The deployment loaded, then its operator composed again with a pad
    that its longest row passes (`row_pad` 0): the loader's own probe holds a
    statement to one dispatch and would refuse the load."""
    serve(ds, cfg, kind, data, monkeypatch)
    monkeypatch.setattr(graph_csr, "ROW_PAD_MAX", 8)
    with ds.graph_mirrors._lock:
        ds.graph_mirrors._csc.clear()
    compile_log.reset()


def test_a_hub_whose_row_passes_the_pad_is_walked_by_the_host_as_sets(ds, cfg, kind, world, monkeypatch):
    data, ref, pool = world
    serve_hubs(ds, cfg, kind, data, monkeypatch)
    before = ds.dispatch.stats()["submitted"]
    for q in range(4):
        row, prepares, launches = ask(ds, three_fields(NAMED), pool[q], f"hub-{q}")
        assert [ids_of(row[f"d{h}"]) for h in (1, 2, 3)] == [r.tolist() for r in ref["rings"][q]]
        assert launches == [] and [(p["form"], p["memo"]) for p in prepares] == [
            ("host", "fill"), ("host", "hit"), ("host", "hit")]
    assert ds.dispatch.stats()["submitted"] == before
    assert not [e for e in compile_log.events() if e["subsystem"] == "graph_reach"]


@pytest.mark.parametrize("where", ["named", "bare"])
def test_one_pair_alone_over_a_hub_is_the_operator_s_row_whatever_the_pad(ds, cfg, kind, world, monkeypatch, where):
    data, ref, pool = world
    serve_hubs(ds, cfg, kind, data, monkeypatch)
    degree = np.bincount(data["pairs"][:, 0], minlength=data["nodes"])
    hubs = [q for q in range(len(pool)) if degree[data["starts"][q]] > 8]
    assert hubs
    walks = kind.walks_by_steps(data["pairs"], data["nodes"], data["starts"], 1)
    sql = "SELECT VALUE " + RING.format(chain=CHAINS[0] + (NAMED if where == "named" else BARE)) + FROM
    for q in hubs[:4]:
        found, prepares, launches = ask(ds, sql, pool[q], f"hub1-{where}-{q}")
        want = ref["rings"][q][0] if where == "named" else np.flatnonzero(walks[0][q] > 0)
        assert ids_of(found) == want.tolist()
        assert launches == [] and [(p["form"], p["depth"]) for p in prepares] == [("csc", "1")]


# ------------------------------------------------------------------ the multiset is what it was
def test_a_bare_expansion_s_multiset_is_unchanged_and_its_distinct_is_the_ring(served, kind, world):
    data, _, pool = world
    walks = kind.walks_by_steps(data["pairs"], data["nodes"], data["starts"], 2)
    for q in range(4):
        e = pool[q]
        (flat,) = [r["result"] for r in execute_ok(served, "SELECT VALUE ->knows->person->knows->person" + FROM, {"q": e})]
        counts = np.bincount(ids_of(flat), minlength=data["nodes"])
        assert (counts == walks[1][q]).all()  # every walk an entry: flatten without dedup
        (dist,) = [r["result"] for r in execute_ok(
            served, "SELECT VALUE array::distinct(->knows->person->knows->person)" + FROM, {"q": e})]
        assert ids_of(dist) == np.flatnonzero(counts).tolist()
    # an array that is no graph chain over the current record is the dialect's own distinct
    assert execute_ok(served, "RETURN array::distinct([3, 1, 3, 2, 1])")[-1]["result"] == [3, 1, 2]
    assert execute_ok(served, "RETURN array::distinct(person:0->knows->person) = array::distinct(person:0->knows->person)")[-1]["result"] is True


def test_the_dialect_s_distinct_of_record_ids_is_one_pass_and_the_scan_s_answer(monkeypatch):
    """A ring is thousands of record ids: `array::distinct` over them may not
    compare each with every one before it (3,200 ids were five million
    `value_eq` calls and seconds of interpreter a statement on the chip)."""
    from surrealdb_tpu.fnc import array_fns

    things = [Thing("person", i % 700) for i in range(3000)] + [Thing("person", 1.0), Thing("t", [1, "a"]), Thing("t", [1, "a"])]
    calls, scan = [], array_fns.value_eq
    monkeypatch.setattr(array_fns, "value_eq", lambda a, b: calls.append(1) or scan(a, b))
    got = array_fns.distinct(None, things)
    assert calls == [] and len(got) == 701 and got[:700] == things[:700] and got[700] == Thing("t", [1, "a"])
    # anything that is not record ids alone keeps the scan, with `=`'s coercions: person:1 = "person:1"
    mixed = [Thing("person", 1), "person:1", 1, 1.0, True, Thing("person", 1)]
    assert array_fns.distinct(None, mixed) == [Thing("person", 1), 1, True] and calls


# ------------------------------------------------------------------ the parser's families and the plan cache
def calls_of(sql: str) -> list:
    from surrealdb_tpu.sql.ast import FunctionCall, walk_exprs

    found = []

    def visit(node):
        # once a node: a SELECT's note of its field list's calls (reach_calls) repeats them
        if isinstance(node, FunctionCall) and node.name == "array::distinct" and all(node is not f for f in found):
            found.append(node)
        if type(node).__name__ == "Subquery":
            walk_exprs(node.stmt, visit)

    walk_exprs(parse_query(sql).statements[0], visit)
    return found


def test_the_parser_notes_the_deepest_chain_a_call_is_a_prefix_of(cfg):
    calls = calls_of(cfg["statements"]["primary"]["sql"])
    assert len(calls) == 4 and calls[0].reach is None  # the outer distinct is of a concat, not of a chain
    assert [len(c.args[0].parts) for c in calls[1:]] == [2, 4, 6]
    assert all(c.reach is calls[3].args[0] for c in calls[1:])
    # another name, another direction or a WHERE in the middle is another family
    apart = calls_of(
        "SELECT array::distinct(->knows->(person WHERE firstName = 'a')) AS a, "
        "array::distinct(->knows->person->knows->(person WHERE firstName = 'b')) AS b, "
        "array::distinct(<-knows<-person) AS c, array::distinct(->knows->person) AS d, "
        "array::distinct(->knows->(person WHERE age > 3)->knows->person) AS e, "
        "array::distinct(->knows->person->knows->person) AS f FROM person")
    assert [c.reach is c.args[0] for c in apart] == [True, True, True, False, True, True]
    assert apart[3].reach is apart[5].args[0]
    # a statement's calls are marked for that statement alone
    two = parse_query("SELECT array::distinct(->knows->person) FROM person:1; "
                      "SELECT array::distinct(->knows->person->knows->person) FROM person:1")
    for stm in two.statements:
        (call,) = [f.expr for f in stm.fields]
        assert call.reach is call.args[0]


def test_literal_names_bound_apart_in_a_cached_template_do_not_share_rings(served, world):
    """`'x'` three times is one family when parsed; the plan cache makes the
    three literals slots, and a later text binds them apart."""
    data, ref, pool = world
    by_name = {}
    for q, e in enumerate(pool):
        by_name.setdefault((e["p"], e["fn"]), q)
    sql = ("SELECT array::distinct(->knows->(person WHERE firstName = '{a}')) AS d1, "
           "array::distinct(->knows->person->knows->(person WHERE firstName = '{b}')) AS d2, "
           "array::distinct(->knows->person->knows->person->knows->(person WHERE firstName = '{c}')) AS d3 "
           "FROM ONLY person:{p}")
    start = pool[0]["p"]
    names = sorted({e["fn"] for e in pool})[:3]
    walks_named = {}
    for fn in names:
        frontier = np.zeros(data["nodes"], dtype=bool)
        frontier[start] = True
        rings = []
        for _ in range(3):
            nxt = np.zeros(data["nodes"], dtype=bool)
            nxt[data["pairs"][frontier[data["pairs"][:, 0]], 1]] = True
            frontier = nxt
            rings.append(np.flatnonzero(frontier & (np.asarray(data["names"])[data["first"]] == fn)).tolist())
        walks_named[fn] = rings
    for round_ in range(12):  # past the cache's install and trust thresholds
        a, b, c = (names[(round_ + k) % 3] for k in range(3)) if round_ % 2 else (names[round_ % 3],) * 3
        before = served.dispatch.stats()["submitted"]
        (res,) = execute_ok(served, sql.format(a=a, b=b, c=c, p=start))
        row = res["result"]
        assert ids_of(row["d1"]) == walks_named[a][0]
        assert ids_of(row["d2"]) == walks_named[b][1]
        assert ids_of(row["d3"]) == walks_named[c][2]
        made = served.dispatch.stats()["submitted"] - before
        assert made == (1 if a == b == c else 2)  # bound apart: the 2- and the 3-pair chain each run their own


def prepared_once(served, monkeypatch) -> dict:
    """Spies on what a family's preparation is made of: the operators' look-up and the chain's hop specs."""
    seen = {"csc_ops": 0, "chain_specs": 0}
    gm = served.graph_mirrors
    for name in ("_csc_ops", "_chain_specs"):
        real = getattr(gm, name)

        def spy(*a, _real=real, _name=name.lstrip("_"), **kw):
            seen[_name] += 1
            return _real(*a, **kw)

        monkeypatch.setattr(gm, name, spy)
    return seen


@pytest.mark.parametrize("statement", ["primary", "probe"])
def test_a_statement_s_family_is_prepared_once_and_its_siblings_read_the_memo(served, cfg, kind, world, monkeypatch, statement):
    """ISSUE 46: one compiled predicate and one look-up of the operators a
    statement, where each of its three expressions made their own (and two
    more predicates to compare bindings); three `graph_prepare` spans, one
    `fill`, as before; the reply is the reference's."""
    data, ref, pool = world
    sql = cfg["statements"][statement]["sql"]
    seen = prepared_once(served, monkeypatch)
    for q in range(6):
        seen.update(csc_ops=0, chain_specs=0)
        tid = f"once-{statement}-{q}"
        before = served.dispatch.stats()["submitted"]
        with tracing.request("reach", trace_id=tid):
            (res,) = execute_ok(served, sql, {"q": pool[q]})
        spans = tracing.get_trace(tid)["spans"]
        assert served.dispatch.stats()["submitted"] - before == 1
        assert len([s for s in spans if s["name"] == "predicate_compile"]) == 1
        assert seen == {"csc_ops": 1, "chain_specs": 1}
        prepares = [s["labels"] for s in spans if s["name"] == "graph_prepare"]
        assert [(p["memo"], p["depth"]) for p in prepares] == [("fill", "1"), ("hit", "2"), ("hit", "3")]
        if statement == "probe":
            assert [ids_of(res["result"][f"d{h + 1}"]) for h in range(3)] == [ref["rings"][q][h].tolist() for h in range(3)]
        else:
            ids = [int(r["id"].id) for r in res["result"]]
            assert kind.judge(ids, ref["ball"][q], 20) == dict.fromkeys(kind.NUMBERS, 0)


def name_rings(data, start: int, fn: str) -> list:
    """The three rings of `start` among the persons named `fn`, by boolean steps over the edge list."""
    named = np.asarray(data["names"])[data["first"]] == fn
    frontier = np.zeros(data["nodes"], dtype=bool)
    frontier[start] = True
    rings = []
    for _ in range(3):
        nxt = np.zeros(data["nodes"], dtype=bool)
        nxt[data["pairs"][frontier[data["pairs"][:, 0]], 1]] = True
        frontier = nxt
        rings.append(np.flatnonzero(frontier & named).tolist())
    return rings


@pytest.mark.parametrize("bound,dispatches,compiles", [
    ("aaa", 1, 1),  # one family as bound: one run, the siblings read it
    ("abb", 1, 2),  # the first is alone (one pair: no dispatch), the second runs the family's chain for the third
    ("aba", 2, 2),  # the first runs the family's chain for the third; the second is alone
    ("aab", 2, 3),  # nobody's binding is the deepest chain's but its own
    ("abc", 2, 3),
])
def test_one_family_s_slots_bound_apart_give_each_ring_its_own_predicate_s_set(served, world, monkeypatch, bound, dispatches, compiles):
    """A cached template's literals are slots: three WHEREs that were one
    text when parsed, so one family of the parser's, are bound as `bound`
    says on this serve. The memo's key holds the slots' values, so no ring
    is read under another name, and no predicate is compiled to find out."""
    from surrealdb_tpu.sql.ast import Literal, SlotLiteral

    data, _, pool = world
    start = pool[0]["p"]
    names = dict(zip("abc", sorted({e["fn"] for e in pool})[:3]))
    query = parse_query(three_fields("(person WHERE firstName = 'x')").replace(FROM, f" FROM ONLY person:{start}"))
    calls = [f.expr for f in query.statements[0].fields]
    assert all(c.reach is calls[2].args[0] for c in calls)
    for slot, call in enumerate(calls):  # what plan_cache._parameterize does to a text whose three names differ
        cond = call.args[0].parts[-1].cond
        assert type(cond.r) is Literal
        cond.r = SlotLiteral(slot, cond.r.value)
    before = served.dispatch.stats()["submitted"]
    with tracing.request("reach", trace_id=f"apart-{bound}"):
        (res,) = served.process(query, Session.owner("bench", "bench"), slot_values=tuple(names[b] for b in bound))
    assert res["status"] == "OK", res
    for h, b in enumerate(bound):
        assert ids_of(res["result"][f"d{h + 1}"]) == name_rings(data, start, names[b])[h]
    assert served.dispatch.stats()["submitted"] - before == dispatches
    spans = tracing.get_trace(f"apart-{bound}")["spans"]
    assert len([s for s in spans if s["name"] == "predicate_compile"]) == compiles


RING2 = "array::distinct(->knows->person->knows->(person WHERE firstName = $n))"
REBOUND = {
    # a FOR binds the name anew every turn, inside ONE top-level statement: one memo, one family node
    "for": ("FOR $n IN $names {{ UPSERT type::thing('ring', $n) SET got = (SELECT VALUE {ring} FROM ONLY $start) }}",
            "SELECT VALUE got FROM [type::thing('ring', $names[0]), type::thing('ring', $names[1]), type::thing('ring', $names[2])]"),
    # two expressions of one text are one family of the parser's; a LET between them binds the name anew
    "lets": ("RETURN {{ LET $n = $names[0]; LET $a = (SELECT VALUE {ring} FROM ONLY $start); LET $n = $names[1]; "
             "LET $b = (SELECT VALUE {ring} FROM ONLY $start); LET $n = $names[2]; "
             "RETURN [$a, $b, (SELECT VALUE {ring} FROM ONLY $start)] }}", None),
    # a closure's argument
    "closure": ("RETURN array::map($names, |$n| (SELECT VALUE {ring} FROM ONLY $start))", None),
}


@pytest.mark.parametrize("how", list(REBOUND))
def test_a_name_bound_anew_inside_one_statement_gives_each_ring_its_own_name_s_set(served, world, how):
    """The memo lives a top-level statement and its key holds the WHERE's
    constants as bound at each evaluation: a parameter that a FOR, a LET or
    a closure rebinds between two evaluations of one family reads no ring
    of the name before (REVIEW, PR 46: the slots alone were in the key)."""
    data, _, pool = world
    start = pool[0]["p"]
    names = sorted({e["fn"] for e in pool})[:3]
    want = [name_rings(data, start, fn)[1] for fn in names]
    assert len({tuple(w) for w in want}) > 1  # the names' rings differ: a stale one shows
    sql, read = REBOUND[how]
    vars = {"names": names, "start": Thing("person", start)}
    before = served.dispatch.stats()["submitted"]
    out = execute_ok(served, sql.format(ring=RING2), vars)
    got = out[-1]["result"] if read is None else execute_ok(served, read, vars)[-1]["result"]
    assert [ids_of(g) for g in got] == want
    assert served.dispatch.stats()["submitted"] - before == 3  # each name its own run
    # and one name three times is one run: the memo still answers where the binding is the same
    before = served.dispatch.stats()["submitted"]
    same = {"names": [names[0]] * 3, "start": Thing("person", start)}
    out = execute_ok(served, sql.format(ring=RING2), same)
    got = out[-1]["result"] if read is None else execute_ok(served, read, same)[-1]["result"]
    assert [ids_of(g) for g in got] == [want[0]] * 3
    assert served.dispatch.stats()["submitted"] - before == 1  # (the FOR's own write is to another table)


@pytest.mark.parametrize("write", ["update", "relate"])
def test_a_write_of_the_statement_s_own_between_two_evaluations_is_seen_by_the_second(served, world, write):
    """One FOR, the same name both turns, and between the two evaluations
    the statement's own transaction renames a person of the ring, or adds
    an edge: the second turn is no reading of the memo (the column mirror
    refuses a reader that wrote its table, the mirrors one with edge
    deltas: _chain_rides asks both before the memo is asked)."""
    data, ref, pool = world
    q = next(i for i, e in enumerate(pool) if ref["rings"][i][1].size >= 2)
    entry, ring2 = pool[q], ref["rings"][q][1].tolist()
    gone = next(i for i in ring2 if i != entry["p"])
    change = ("UPDATE type::thing('person', $gone) SET firstName = 'Renamed'" if write == "update"
              else "RELATE $start->knows->person:10001")
    sql = ("FOR $turn IN [0, 1] { LET $n = $fn; UPDATE type::thing('seen', $turn) SET got = (SELECT VALUE "
           + RING2 + " FROM ONLY $start); " + change + " }")
    execute_ok(served, "CREATE seen:0, seen:1; CREATE person:10001 SET firstName = 'Far'; "
                       "CREATE person:10002 SET firstName = $fn; RELATE person:10001->knows->person:10002", {"fn": entry["fn"]})
    execute_ok(served, sql, {"fn": entry["fn"], "start": Thing("person", entry["p"]), "gone": gone})
    first, second = (r["got"] for r in execute_ok(served, "SELECT got FROM seen:0, seen:1")[-1]["result"])
    assert ids_of(first) == ring2
    assert ids_of(second) == (sorted(set(ring2) - {gone}) if write == "update" else sorted(ring2 + [10002]))


ACCEPTED = [  # WHEREs that compile_where lowers: the vars that bind them, one var changed, the constants in the key
    ("firstName = $n", {"n": "Ann"}, {"n": "Bob"}, 1),
    ("firstName = $q.fn", {"q": {"fn": "Ann", "p": 1}}, {"q": {"fn": "Bob", "p": 1}}, 1),
    ("$n = firstName AND lastName != $m AND gender = 'Zed'", {"n": "Ann", "m": "X"}, {"n": "Ann", "m": "Y"}, 2),
    ("firstName IN [$n, $m]", {"n": "Ann", "m": "Cy"}, {"n": "Bob", "m": "Cy"}, 1),  # an array is one constant
    ("firstName IN $ns", {"ns": ["Ann"]}, {"ns": ["Ann", "Bob"]}, 1),
    ("!(birthday < <datetime> $d) OR firstName CONTAINS $n", {"d": "2000-01-01T00:00:00Z", "n": "A"},
     {"d": "2001-01-01T00:00:00Z", "n": "A"}, 2),
    ("age > $a", {"a": 1}, {"a": 1.0}, 1),  # by type and repr: `1` and `1.0` are different constants
]


@pytest.mark.parametrize("text,vars,other,constants", ACCEPTED, ids=[a[0] for a in ACCEPTED])
def test_every_where_that_lowers_has_a_key_of_its_constants_as_bound(ds, text, vars, other, constants):
    """ops/predicates.py bound_constants and compile_where agree on what a
    constant is (one _is_const): two bindings have one key where the
    compiled predicates have one binding_key, and different keys where
    they differ. A plain literal is the text's and in no key."""
    from surrealdb_tpu.dbs.context import Context
    from surrealdb_tpu.dbs.executor import Executor
    from surrealdb_tpu.ops.predicates import bound_constants, compile_where

    cond = parse_query(f"SELECT * FROM person WHERE {text}").statements[0].cond
    ex = Executor(ds, Session.owner("bench", "bench"))

    def bind(vs):
        ctx = Context(ex, ex.session)
        for k, v in vs.items():
            ctx.set_param(k, v)
        return compile_where(ctx, cond).binding_key(), bound_constants(ctx, cond)

    (ck, bk), (ck2, bk2), (ck3, bk3) = bind(vars), bind(dict(vars)), bind(other)
    assert ck == ck2 and bk == bk2 and ck != ck3 and bk != bk3
    assert len(bk) == constants and "Zed" not in repr(bk)


# ------------------------------------------------------------------ warm-up and audit
def test_the_first_statement_warms_every_lane_count_in_the_background(ds, cfg, kind, world, monkeypatch):
    from surrealdb_tpu import bg

    data, _, pool = world
    serve(ds, cfg, kind, data, monkeypatch, prewarm=True)
    assert ds.graph_mirrors.wait_prewarm(120) and bg.wait_idle(120, owner=id(ds))
    shapes = {e["shape"] for e in compile_log.events() if e["subsystem"] == "graph_reach"}
    assert {s.split("x")[0] for s in shapes} == {"8", "16", "32", "64"}
    assert len({s.split("x", 1)[1] for s in shapes}) == 1  # one frontier pad, node space and operand, at every lane count
    for riders in (1, 9):  # whatever the batch's width, nothing is left to compile
        serve_batch(ds, monkeypatch, [(three_fields(NAMED), pool[i]) for i in range(riders + 1)])
    assert {e["shape"] for e in compile_log.events() if e["subsystem"] == "graph_reach"} == shapes


def test_graftcheck_audits_the_set_kernel_as_served():
    from scripts.graftcheck import lowering, registry, rules

    assert compile_log.KERNEL_SITES["graph_reach"] == "surrealdb_tpu.idx.graph_csr:graftcheck_sites"
    (contract,) = registry.resolve_contracts(["graph_reach"])
    # every lane count, swept (two hops) and read from the rows (one hop at a walk pad)
    assert [(s["lanes"], s.get("walk_pad", 0)) for s in contract["shapes"]] == [
        (lanes, pad) for pad in (0, 512) for lanes in (8, 16, 32, 64)]
    for shape in contract["shapes"]:
        low = lowering.lower_site(contract, shape)
        assert rules.check(contract, shape, low) == [] and low.collectives == {}


def test_the_kernel_packs_a_bit_a_node_and_a_padding_lane_reaches_nothing():
    import jax.numpy as jnp

    graph_csr._kernels()
    kernel = graph_csr._JITTED["chain_reach_batch"]
    # a path 0 -> 1 -> 2 -> ... -> 39 over a node space of 40 (no multiple of 32: two words)
    n_cap, src, dst = 40, np.arange(39), np.arange(1, 40)
    cptr, csrc = graph_csr._csc_arrays(src, dst, n_cap)
    hop = ((jnp.asarray(cptr), jnp.asarray(csrc)),)
    frs = np.full((8, 4), n_cap, dtype=np.int32)
    frs[0, :2], frs[1, 0] = [0, 30], 5
    everyone = graph_csr._pack_mask(np.ones(n_cap, dtype=bool), n_cap)
    odd = graph_csr._pack_mask(np.arange(n_cap) % 2 == 1, n_cap)
    out = np.asarray(kernel((hop, hop), frs, (everyone, odd) + (everyone,) * 6, n_cap=n_cap))
    assert out.shape == (8, 2, 2) and out.dtype == np.uint32
    assert [graph_csr._ring_ids(out[0, h]).tolist() for h in (0, 1)] == [[1, 31], [2, 32]]
    assert [graph_csr._ring_ids(out[1, h]).tolist() for h in (0, 1)] == [[], [7]]  # 6 is even: masked
    assert not out[2:].any()


# ------------------------------------------------------------------ the one hop read from the rows (ISSUE 48)
ROWS_N, ROWS_CAP = 200, 256


def rows_graph():
    """A seeded graph over 200 nodes (node space 256) as the composed
    operator's arrays: up to 6 records a node among the first 180, drawn with
    replacement (duplicate records; two of a node's neighbours reach the same
    third: diamonds), and by hand: 190 with no record (a start with an empty
    row); 191 -> 192, 193 and 192 with none (a frontier node with an empty
    row); 194 -> 32 nodes, the longest row (a frontier at exactly the pad);
    195 -> 100..115, each of which has 16 records: a walk of 256, the longest
    (a walk at exactly the walk pad); 196 -> 197, 198 -> 199 (a diamond)."""
    rng = np.random.default_rng(48)
    src = np.repeat(np.arange(180), rng.integers(0, 7, 180))
    src = src[(src < 100) | (src > 115)]
    pairs = [(int(a), int(rng.integers(0, 180))) for a in src]
    pairs += [(a, int(b)) for a in range(100, 116) for b in rng.integers(0, 100, 16)]
    pairs += [(191, 192), (191, 193), (193, 5), (193, 6), (193, 5)]
    pairs += [(194, int(b)) for b in rng.integers(0, 100, 32)] + [(195, b) for b in range(100, 116)]
    pairs += [(196, 197), (196, 198), (197, 199), (198, 199)]
    pairs = np.array(pairs)[rng.permutation(len(pairs))]
    order = np.argsort(pairs[:, 0], kind="stable")
    ls, ld = pairs[order, 0], pairs[order, 1]
    indptr = np.zeros(ROWS_CAP + 1, dtype=np.int32)
    np.cumsum(np.bincount(ls, minlength=ROWS_CAP), out=indptr[1:])
    op = {"by_src": (indptr, ld.astype(np.int32)), "row_pad": graph_csr._row_pad(int(np.diff(indptr).max())),
          "key": ("op",), "gen": (1,), "by_dst": graph_csr._csc_arrays(ls, ld, ROWS_CAP)}
    return op, pairs


def host_walk2(pairs, start: int, mask) -> list:
    out: dict = {}
    for a, b in pairs.tolist():
        out.setdefault(a, []).append(b)
    return sorted({w for v in out.get(start, ()) for w in out.get(v, ()) if mask[w]})


ROWS_CASES = {
    # the case's own starts (every batch also carries a few random ones) and its mask
    "duplicate_records": ([3, 17, 42], "half"),
    "a_diamond": ([196], "all"),
    "a_start_with_an_empty_row": ([190], "all"),
    "a_frontier_node_with_an_empty_row": ([191], "all"),
    "a_frontier_at_exactly_the_pad": ([194], "half"),
    "a_walk_at_exactly_the_walk_pad": ([195], "all"),
    "a_mask_that_passes_nothing": ([195, 194, 7], "none"),
    "a_mask_that_passes_all": ([195, 194, 7], "all"),
}


@pytest.mark.parametrize("lanes", [8, 64])
@pytest.mark.parametrize("case", sorted(ROWS_CASES))
def test_the_hop_read_from_the_rows_is_the_sweep_s_ring_and_the_host_s_walk(case, lanes):
    op, pairs = rows_graph()
    (indptr, dst), (cptr, csrc), fsz = op["by_src"], op["by_dst"], op["row_pad"]
    walk_pad = graph_csr._walk_pad(op, op)
    deg = np.diff(indptr)
    longest = max(int(deg[dst[indptr[s]:indptr[s + 1]]].sum()) for s in range(ROWS_N))
    assert (fsz, walk_pad, longest) == (32, 256, 256) and int(deg[194]) == 32
    assert len({tuple(p) for p in pairs.tolist()}) < len(pairs)  # duplicate records
    starts, how = ROWS_CASES[case]
    rng = np.random.default_rng(lanes)
    starts = starts + rng.integers(0, 180, (lanes - 3) - len(starts)).tolist()  # three lanes stay empty
    mask = {"all": np.ones(ROWS_CAP, dtype=bool), "none": np.zeros(ROWS_CAP, dtype=bool),
            "half": np.arange(ROWS_CAP) % 2 == 0}[how]
    frs = np.full((lanes, fsz), ROWS_CAP, dtype=np.int32)
    for i, s in enumerate(starts):
        row = dst[indptr[s]:indptr[s + 1]]
        frs[i, : row.size] = row
    graph_csr._kernels()
    kernel = graph_csr._JITTED["chain_reach_batch"]
    padded = np.full(graph_csr.path_slots(dst.size), ROWS_CAP, dtype=np.int32)
    padded[: dst.size] = dst
    masks = (graph_csr._pack_mask(mask, ROWS_CAP),) * lanes
    walked = np.asarray(kernel((((indptr, padded),),), frs, masks, n_cap=ROWS_CAP, walk_pad=walk_pad))
    swept = np.asarray(kernel((((cptr, csrc),),), frs, masks, n_cap=ROWS_CAP))
    assert walked.shape == (lanes, 1, walk_pad) and walked.dtype == np.int32 and swept.dtype == np.uint32
    for i, s in enumerate(starts):
        ring = graph_csr._ring_ids(walked[i, 0])
        assert ring.tolist() == graph_csr._ring_ids(swept[i, 0]).tolist() == host_walk2(pairs, s, mask), (case, s)
    assert (walked[len(starts):] == -1).all() and not swept[len(starts):].any()  # an empty lane reaches nothing
    if how == "none":
        assert (walked == -1).all()
    if case == "a_walk_at_exactly_the_walk_pad":
        assert (walked[0, 0] >= 0).all()  # every slot of the pad holds a destination


def test_the_walk_pad_is_the_operators_longest_two_step_walk_padded_or_zero(monkeypatch):
    op, _ = rows_graph()
    assert graph_csr._walk_pad(op, op) == 256 and op["walk_pad"] == ((("op",), (1,)), 256)
    # a walk longer than the pad rows are read at: every rider of the pair sweeps
    monkeypatch.setattr(graph_csr, "ROW_PAD_MAX", 255)
    later = {**op, "gen": (2,)}
    assert graph_csr._walk_pad(op, later) == 0 and op["walk_pad"][0] == (("op",), (2,))
    # an operator whose longest row passes the pad reads no row at all
    assert graph_csr._walk_pad({**op, "row_pad": 0}, op) == 0


def knows_operator(ds) -> dict:
    (op,) = [op for key, op in ds.graph_mirrors._csc.items() if key[2] == "person" and key[4] == "knows"]
    return op


def test_a_three_pair_chain_and_sf3_s_operator_keep_the_sweep_and_its_shape_key(served, cfg, kind, world):
    data, _, pool = world
    compile_log.reset()
    _, prepares, launches = ask(served, three_fields(NAMED), pool[0], "three-pairs")
    # two hops after the first operator's row: no pad bounds them, whatever the operators' walks
    assert prepares[0]["last_hop"] == "sweep" and all("last_hop" not in p for p in prepares[1:])
    op = knows_operator(served)
    hops = (((op["cptr"], op["csrc"]),),) * 2
    assert int(launches[0]["slots"]) == 2 * int(op["csrc"].shape[0])
    key = (8, op["row_pad"], op["n_pad"], (int(op["cptr"].shape[0]), int(op["csrc"].shape[0])) * 2)
    assert graph_csr._reach_shape_key(8, op["row_pad"], op["n_pad"], hops) == key  # as before the walk pad
    assert {e["shape"] for e in compile_log.events() if e["subsystem"] == "graph_reach"} == {"x".join(map(str, key))}
    # SNB SF3's own `knows` (24,328 persons, ~46 a row): a two-step walk is far past the pad, so the
    # benchmark's graph cells would sweep a chain of two pairs too
    sf3 = kind.generate(cfg, cfg["sizes"], SEED)["pairs"]
    order = np.argsort(sf3[:, 0], kind="stable")
    indptr = np.concatenate([[0], np.cumsum(np.bincount(sf3[:, 0], minlength=32768))]).astype(np.int32)
    knows = {"by_src": (indptr, sf3[order, 1].astype(np.int32)), "key": ("knows",), "gen": (1,),
             "row_pad": graph_csr._row_pad(int(np.diff(indptr).max()))}
    assert knows["row_pad"] == 1024 and graph_csr._walk_pad(knows, knows) == 0


# ------------------------------------------------------------------ the program lowered for a TPU
SF3_SLOTS = 1_179_648


@pytest.mark.parametrize("lanes", [8, 16])
def test_the_tpu_lowering_at_sf3_holds_no_reduce_window_over_the_slots(lanes):
    import jax
    import jax.numpy as jnp
    from jax import export

    graph_csr._kernels()
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    hop = ((i32(32769), i32(SF3_SLOTS)),)
    masks = (jax.ShapeDtypeStruct((1024,), jnp.uint32),) * lanes
    try:
        exported = export.export(graph_csr._JITTED["chain_reach_batch"], platforms=("tpu",))(
            (hop, hop), i32(lanes, 1024), masks, n_cap=32768)
    except Exception as e:  # a JAX that cannot lower for a platform it does not run on
        pytest.skip(f"no TPU lowering without a chip here: {e!r}"[:200])
    text = exported.mlir_module()
    assert "gather" in text and f"tensor<{lanes}x2x1024xui32>" in text
    operands = re.findall(r"stablehlo\.reduce_window.*?\((tensor<[^>]*>)", text, flags=re.S)
    dims = [[int(d) for d in re.findall(r"(\d+)x", t)] for t in operands]
    assert all(max(d) <= SF3_SLOTS // 128 for d in dims), dims


@pytest.mark.parametrize("lanes", [8, 64])
def test_the_tpu_lowering_of_the_rows_read_at_magcite_s_shapes_scatters_nothing_and_passes_no_operator(lanes):
    import jax
    import jax.numpy as jnp
    from jax import export

    graph_csr._kernels()
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    slots, n_cap, fsz, walk_pad = 1_703_936, 262_144, 256, 4096
    masks = (jax.ShapeDtypeStruct((n_cap // 32,), jnp.uint32),) * lanes
    try:
        exported = export.export(graph_csr._JITTED["chain_reach_batch"], platforms=("tpu",))(
            (((i32(n_cap + 1), i32(slots)),),), i32(lanes, fsz), masks, n_cap=n_cap, walk_pad=walk_pad)
    except Exception as e:  # a JAX that cannot lower for a platform it does not run on
        pytest.skip(f"no TPU lowering without a chip here: {e!r}"[:200])
    text = exported.mlir_module()
    assert f"tensor<{lanes}x1x{walk_pad}xi32>" in text and "stablehlo.gather" in text
    assert "stablehlo.scatter" not in text and "stablehlo.sort" not in text
    # the one running sum is over the frontier's pad
    operands = re.findall(r"stablehlo\.reduce_window.*?\((tensor<[^>]*>)", text, flags=re.S)
    assert operands and all(t.startswith(f"tensor<{lanes}x{fsz}xi32") for t in operands), operands
    # the operator's slots and the node space are operands to gather from, never an axis of a lane's work
    assert not re.findall(rf"tensor<{lanes}x(?:{slots}|{n_cap}|{n_cap + 1})x", text)
    assert not re.findall(rf"tensor<{lanes}x\d+x(?:{slots}|{n_cap}|{n_cap + 1})x", text)


# ------------------------------------------------------------------ the benchmark's side
def brute_rings(pairs, first, start: int, name_id: int) -> list:
    out: dict = {}
    for a, b in pairs.tolist():
        out.setdefault(a, set()).add(b)
    frontier, rings = {start}, []
    for _ in range(3):
        frontier = {w for v in frontier for w in out.get(v, ())}
        rings.append(sorted(w for w in frontier if first[w] == name_id))
    return rings


def test_the_reference_is_the_brute_force_walk_by_sets(kind, world):
    data, ref, _ = world
    for q in range(len(data["starts"])):
        want = brute_rings(data["pairs"], data["first"], int(data["starts"][q]), int(data["asked"][q]))
        assert [r.tolist() for r in ref["rings"][q]] == want
        every, dist = ref["ball"][q]
        seen, order = set(), []
        for h, ring in enumerate(want, start=1):
            order += [(i, h) for i in ring if i not in seen]
            seen |= set(ring)
        assert list(zip(every.tolist(), dist.tolist())) == order


def record(q: int, ids: list) -> dict:
    return {"status": "OK", "q": q, "ids": ids, "values": {}}


def test_the_check_holds_an_answer_to_the_four_numbers_and_the_controls_fail_them(cfg, kind, world):
    data, ref, _ = world
    sound = [record(q, ref["ball"][q][0][:20].tolist()) for q in range(len(ref["ball"]))]
    verdict = kind.check(cfg, ref, sound)
    assert verdict["numbers"] == [[n, 0, "<=", 0] for n in kind.NUMBERS]
    assert verdict["compared"]["answers"] == len(sound) and verdict["compared"]["rows"] == sum(len(r["ids"]) for r in sound)
    assert verdict["control"]["wrong_ids_unmasked"] > 0  # the rings without the name return other names
    q = next(i for i, b in enumerate(ref["ball"]) if b[0].size > 20 and len(set(b[1][:21].tolist())) > 1)
    every, dist = ref["ball"][q]
    cases = {
        "wrong_ids": [int(np.flatnonzero(data["first"] != data["asked"][q])[0])] + every[:19].tolist(),
        "duplicates": every[:19].tolist() + [int(every[0])],
        "ring_violations": every[:20].tolist()[::-1],
        "short_answers": every[:19].tolist(),
    }
    for name, ids in cases.items():
        got = dict((n, v) for n, v, _, _ in kind.check(cfg, ref, [record(q, ids)])["numbers"])
        assert got[name] >= 1, (name, got)
    # a nearer ring left out: the farthest 20 alone
    far = every[dist == dist.max()][:20].tolist()
    if len(far) == 20 and dist.min() < dist.max():
        assert kind.judge(far, ref["ball"][q], 20)["ring_violations"] == 1
    # inside a ring an answer may come in any order
    dist_of = dict(zip(every.tolist(), dist.tolist()))
    turned = sorted(every[:20].tolist(), key=lambda i: (dist_of[i], -i))
    assert turned != every[:20].tolist() and kind.judge(turned, ref["ball"][q], 20) == dict.fromkeys(kind.NUMBERS, 0)


def test_the_u8_control_loses_a_person_reached_by_256_walks(cfg, kind):
    # 256 two-step walks from 0 to 300 (through 1..256), one to 301 (through 1): both named alike
    n = 302
    pairs = np.array([(0, m) for m in range(1, 257)] + [(m, 300) for m in range(1, 257)] + [(1, 301)])
    data = {"pairs": pairs, "nodes": n, "starts": np.array([0]), "first": np.where(np.arange(n) >= 300, 1, 0),
            "asked": np.array([1]), "names": ["a", "b"]}
    ref = kind.reference({**cfg, "hops": 3}, data)
    assert [r.tolist() for r in ref["rings"][0]] == [[], [300, 301], []]
    assert ref["control_answers"]["u8"][0] == [301]
    verdict = kind.check(cfg, ref, [record(0, [300, 301])])
    assert verdict["numbers"] == [[name, 0, "<=", 0] for name in kind.NUMBERS]
    assert verdict["control"]["short_answers_u8"] == 1 and verdict["control"]["wrong_ids_unmasked"] >= 1
    assert verdict["control"]["pool_entries_with_a_whole_ring_wrong_u8"] == 1 == verdict["control"][
        "probed_entries_with_a_whole_ring_wrong_u8"]
    assert kind.probe_entries(data) == [0]


def test_the_loader_refuses_a_program_that_expands_the_chains(ds, cfg, kind, world, monkeypatch):
    # the parent commit: no call is ever noted, so every ring is the dialect's distinct of the expansion
    monkeypatch.setattr("surrealdb_tpu.sql.path.mark_chain_families", lambda calls: None)
    with pytest.raises(RuntimeError, match="0 device dispatches for the loader's probe"):
        serve(ds, cfg, kind, world[0], monkeypatch)


def test_the_loader_asks_the_probe_from_eight_sessions_at_once_over_the_websocket(ds, cfg, kind, world, monkeypatch):
    loaded = serve(ds, cfg, kind, world[0], monkeypatch)
    asked = loaded["probe_sessions"]
    assert asked["statements"] == kind.SESSIONS * len(kind.probe_entries(world[0]))
    widths = {int(w): n for w, n in asked["widths"].items()}
    assert sum(w * n for w, n in widths.items()) == asked["statements"] and max(widths) >= 2
    # the listener the probe opened is closed, and the datastore serves on
    assert execute_ok(ds, "RETURN 1")[-1]["result"] == 1


def test_a_fault_between_the_lanes_of_a_batch_is_refused_by_the_sessions_and_by_no_statement_alone(
        ds, cfg, kind, world, monkeypatch):
    """Each rider is handed its neighbour's rings: right in a batch of one,
    which is all that a statement at a time ever makes."""
    real = graph_csr._collect_rings

    def rotated(out, riders, lanes, slots):
        collect = real(out, riders, lanes, slots)

        def handed_on():
            rings = collect()
            return rings[1:] + rings[:1]

        handed_on.launch_labels, handed_on.outputs = collect.launch_labels, collect.outputs
        return handed_on

    monkeypatch.setattr(graph_csr, "_collect_rings", rotated)
    data = world[0]
    monkeypatch.setattr(kind, "probe_sessions", lambda *a: {})
    serve(ds, cfg, kind, data, monkeypatch)  # the statement-at-a-time probes pass
    monkeypatch.undo()
    monkeypatch.setattr(graph_csr, "_collect_rings", rotated)
    with pytest.raises(RuntimeError, match="faults in .* statements from 8 sessions at once.*is not the reference's"):
        kind.probe_sessions(ds, cfg, dict(data))


def test_the_wait_before_the_window_is_the_count_cells_own(served, cfg, kind):
    """No collection of the interpreter is arranged before the window: a
    long one is the program's to cure (telemetry.freeze_long_lived)."""
    waited = kind.wait_background(served, cfg, 30.0)
    assert kind.wait_background is kind.base.wait_background
    assert set(waited["line"]) == {"prewarm_wait_s"} and waited["state"] == {}


def test_the_manifest_has_the_deployment_its_cell_and_its_seven_readers(cfg):
    manifest = mf.load()
    assert mf.problems(manifest) == []
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    cell = mf.cell(manifest, CELL)
    assert entry == {**entry, "name": CONFIG, "source": cfg["source"], "reduced": ["tables"],
                     "file": f"benchmarks/configs/{CONFIG}.json"} and len(entry["source"]) <= 200
    assert cell == {**cell, "name": CELL, "config": CONFIG, "traffic": "ws_closed_c8", "chips": 1}
    assert manifest["configs"].index(entry) == 7 == manifest["workloads"].index(cell)  # the eighth of each; PR 47 appends a ninth
    names = ["graph_reach_roofline", "graph.reach_device_share", "graph.reach_prepare_ms", "graph.reach_ids_mean",
             "graph.reach_lane_fill", "graph.reach_filter_prepare_ms", "graph.reach_filter_build_share"]
    # together and in order after those that were there (not "at the end": the next PR appends)
    at = [m["name"] for m in manifest["per_layer"]].index(names[0])
    mine = manifest["per_layer"][at:at + 7]
    assert [m["name"] for m in mine] == names and at > 40
    assert all(m["workloads"] == [CELL] for m in mine)
    assert [m["moves"] for m in mine] == ["p50_ms"] * 6 + ["p95_ms"]
    # of the older lists none names the new cell (the readers without a list cover it as they are); of the
    # later ones PR 45's does (the set chain's bucket gathers), PR 46's (a statement's compiled predicates)
    # and PR 48's (where a set's last hop came from: swept here, 0.0)
    assert [m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])] == (
        names + ["dispatch.gather_met_share", "exec.predicate_compiles", "graph.reach_rows_share"])
    assert {m["name"] for m in mf.metrics_of(manifest, "end_to_end", CELL)} == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms"}


def test_the_configuration_is_snbsf3ic1_s_graph_asked_for_the_persons(cfg):
    with open(os.path.join(BENCH, "configs", "snbsf3ic1.json")) as f:
        ic1 = json.load(f)
    for key in ("ns", "db", "node_table", "edge_table", "ddl", "hops", "sizes", "expected_strategies", "reduced"):
        assert cfg[key] == ic1[key], key
    for key in ("degree_sigma", "degree_cap", "name_exponent"):
        assert cfg["generator"][key] == ic1["generator"][key]
    st = cfg["statements"]
    assert st["primary"]["dispatches"] == 1 == st["probe"]["dispatches"] and st["primary"]["bind"] == "q"
    assert st["primary"]["sql"].count("array::distinct(") == 4 and st["primary"]["sql"].endswith("LIMIT 20")
    assert cfg["load"]["ask_before_edges"] == st["primary"]["sql"] and cfg["load"]["probe"] == "probe"
    assert cfg["kind"] == "graph_filtered_reach" and cfg["kernel"] == "graph_reach" and cfg["limit"] == 20
    assert {k: v for k, v in cfg["correct"].items() if k != "why"} == {
        "wrong_ids_max": 0, "duplicates_max": 0, "ring_violations_max": 0, "short_answers_max": 0}
    assert len(cfg["guarantees"]) == 11 and len(cfg["assumed"]) >= 8
    assert not any("WALKS" in a for a in cfg["assumed"])  # the assumption this configuration retires


def test_the_kernel_s_need_counts_the_algorithm():
    kernels = mf.load_modules(BENCH, "kernels", None)
    need = kernels["graph_reach"].need
    assert re.match(kernels["graph_reach"].MODULE, "jit_chain_reach_batch") and not re.match(
        kernels["graph_reach"].MODULE, "jit_chain_count_batch")
    shapes = {"nodes": 24328, "edges": 1130494, "hops": 3}
    one = need(shapes, 1.0, 1.0)
    adjacency = 4.0 * (1130494 + 24328 + 1)
    assert one["flops"] == 2.0 * 2 * 1130494
    assert one["bytes"] == 2 * adjacency + 2 * 2 * 24328 + 4 * 24328 / 8
    eight = need(shapes, 8.0, 1.0)  # riders share the adjacency
    assert eight["bytes"] - one["bytes"] == 7 * (2 * 2 * 24328 + 4 * 24328 / 8) and eight["flops"] == 8 * one["flops"]


def span(name, dur=0.1, **labels):
    return {"id": 7, "parent": 1, "name": name, "labels": {k: str(v) for k, v in labels.items()},
            "start_ms": 0.6, "dur_ms": dur, "error": None}


def doc(*spans):
    root = {"id": 1, "parent": None, "name": "ws_rpc", "labels": {}, "start_ms": 0.0, "dur_ms": 9.0, "error": None}
    return {"record": {}, "doc": {"trace_id": "t", "ts": 0.0, "spans": [root, *spans]}}


def statement(fill=None, hits=2, launches=1, ids=(3, 40, 500), **how):
    how = {"form": "csc", "operand": "composed", "filter": "fused", **how}
    spans = [span("graph_prepare", 0.4 if fill is None else fill, memo="fill", depth=1, rings=3, ids=ids[0], **how)]
    spans += [span("graph_prepare", 0.01, memo="hit", depth=2 + i, rings=3, ids=ids[1 + i], **how) for i in range(hits)]
    return doc(*spans, *[span("dispatch_launch", 1.0, batch=8, lanes=8, slots=2359296) for _ in range(launches)])


def test_the_three_span_readers_on_hand_written_docs():
    readers = mf.load_modules(BENCH, "layer_metrics", "NAME")
    share, ms, ids = (readers[n].read for n in ("graph.reach_device_share", "graph.reach_prepare_ms", "graph.reach_ids_mean"))
    count = doc(span("graph_prepare", 0.3, form="csc", operand="composed", filter="fused", first_hop="rows"),
                span("dispatch_launch", 1.0, batch=8, lanes=8, sweeps=1))
    tagged = [statement(), statement(fill=0.6, ids=(0, 2, 10)), count]
    assert share({"tagged": tagged}) == 1.0 and ms({"tagged": tagged}) == pytest.approx(0.52)
    assert ids({"tagged": tagged}) == (543 + 12) / 2
    walked = doc(span("graph_prepare", 900.0, form="host", filter="host", memo="fill", depth=3, rings=0, ids=7))
    split = statement(launches=2)
    on_host = statement(form="host", operand="none", launches=0)
    assert share({"tagged": [statement(), walked, split, on_host]}) == 0.25
    assert ms({"tagged": [statement(), walked]}) == pytest.approx(0.42)  # a walk is no preparation
    assert ids({"tagged": [walked]}) == 7.0
    # a count cell, and a program older than the labels: nothing to read, and no raise
    for reader in (share, ms, ids):
        assert reader({"tagged": [count]}) is None and reader({"tagged": []}) is None
    assert readers["graph_reach_roofline"].read({"kernel": {"name": "graph_csc"}, "slice": None}) is None


def test_the_lane_and_filter_readers_read_the_set_statements_and_nobody_else_s():
    readers = mf.load_modules(BENCH, "layer_metrics", "NAME")
    fill, ms, built = (readers[n].read for n in (
        "graph.reach_lane_fill", "graph.reach_filter_prepare_ms", "graph.reach_filter_build_share"))

    def asked(batch, outcome, dur):
        d = statement()
        d["doc"]["spans"] = [s for s in d["doc"]["spans"] if s["name"] != "dispatch_launch"] + [
            span("dispatch_launch", 1.0, batch=batch, lanes=8, slots=2359296), span("graph_filter", dur, outcome=outcome)]
        return d

    # a count statement's spans beside them: eight of eight lanes, a slow build
    count = doc(span("graph_prepare", 0.3, form="csc", operand="composed", filter="fused", first_hop="rows"),
                span("dispatch_launch", 1.0, batch=8, lanes=8, sweeps=1), span("graph_filter", 50.0, outcome="build"))
    tagged = [asked(4, "hit", 0.02), asked(4, "hit", 0.04), asked(2, "build", 0.9), count]
    # two riders of one dispatch of four and one of a dispatch of two: 1.5 dispatches' worth, a fill of 1/3
    assert fill({"tagged": tagged}) == pytest.approx((3 / 8) / (1 / 4 + 1 / 4 + 1 / 2))
    assert ms({"tagged": tagged}) == pytest.approx(0.04) and built({"tagged": tagged}) == pytest.approx(1 / 3)
    for reader in (fill, ms, built):
        assert reader({"tagged": [count]}) is None and reader({"tagged": []}) is None
