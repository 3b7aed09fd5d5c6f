"""A predicate on a count chain's final node part rides the device count
(ISSUE 31): `count(->knows->person->knows->person->knows->(person WHERE ...))`
is one dispatch on both count forms, equal to a plain int64 walk over the
edge list; riders that bind different values share a dispatch and a compiled
program; an acknowledged UPDATE of the filtered field and an acknowledged
RELATE are seen by the next count; what cannot ride takes the KV walk and
says `filter=host`; the bare count runs the program it ran before. The graph
is skewed and person 0 has 300 friends."""

import threading
import time
from collections import Counter

import numpy as np
import pytest

from surrealdb_tpu import cnf, compile_log, telemetry, tracing
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.idx import graph_csr
from surrealdb_tpu.sql.value import Thing
from test_graph_count_lanes import HeldQueue
from test_graph_dense_exact import forms, lognormal_hub

NS, DB = "t", "t"
N, HUB = 400, 300
NAMES = ["Anna", "Bo", "Chen", "Dara", "Eli", "Fay", "Gus"]
CHAIN = "->knows->person->knows->person->knows->"
BARE = f"SELECT count({CHAIN}person) AS c FROM type::thing('person', $q.p)"
BY_NAME = f"SELECT count({CHAIN}(person WHERE firstName = $q.fn)) AS c FROM type::thing('person', $q.p)"
# what makes the program choose each count form (tests/test_graph_count_form_span.py)
ROUTES = {
    "dense_limbs": {"TPU_GRAPH_COUNT_EDGES": 1},
    "csc_composed": {"TPU_GRAPH_COUNT_EDGES": 1, "TPU_GRAPH_DENSE_MAX": N - 1},
}
FORM = {"dense_limbs": "dense", "csc_composed": "csc"}


def person(i: int) -> dict:
    """Skewed names (Anna is every second person's), an age, a nested field."""
    name = NAMES[min(int(np.log2((i * 7919) % 97 + 1)), len(NAMES) - 1)]
    return {"id": i, "firstName": name, "age": i % 50, "home": {"city": "c%d" % (i % 3)}}


def walk_ending(edges, start: int, pred, persons=None, steps: int = 3) -> int:
    """The plain reference: x = e_start A A A by explicit loops over the edge
    list in int64, then the sum over the persons `pred` accepts."""
    persons = persons or [person(i) for i in range(N)]
    x = np.zeros(N, dtype=np.int64)
    x[start] = 1
    for _ in range(steps):
        y = np.zeros(N, dtype=np.int64)
        for a, b in edges:
            y[b] += x[a]
        x = y
    return int(sum(int(x[u]) for u in range(N) if pred(persons[u])))


@pytest.fixture(scope="module")
def edges():
    e = lognormal_hub(N, HUB, seed=5)
    assert np.bincount(e[:, 0]).max() >= 256
    return [(int(a), int(b)) for a, b in e]


@pytest.fixture
def loaded(ds, monkeypatch, edges, request):
    """The graph served, with the knobs of the route the test names (a
    parameter `route`), or the dense one."""
    telemetry.reset()
    compile_log.reset()
    tracing.store_reset()
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    callspec = getattr(request.node, "callspec", None)
    route = callspec.params.get("route", "dense_limbs") if callspec else "dense_limbs"
    for name, value in ROUTES[route].items():
        monkeypatch.setattr(cnf, name, value)
    sess = Session.owner(NS, DB)
    ds.execute("DEFINE TABLE person SCHEMALESS; DEFINE TABLE knows SCHEMALESS", sess)
    ds.execute("INSERT INTO person $rows RETURN NONE", sess, {"rows": [person(i) for i in range(N)]})
    rows = [{"in": Thing("person", a), "out": Thing("person", b)} for a, b in edges]
    (res,) = ds.execute("INSERT RELATION INTO knows $rows RETURN NONE", sess, {"rows": rows})
    assert res["status"] == "OK", res
    yield ds, sess
    tracing.store_reset()


def ask(ds, sess, sql: str, q: dict, tid: str):
    """(the count, the labels of its graph_prepare span, its graph_filter spans)"""
    with tracing.request("count", trace_id=tid):
        out = ds.execute(sql, sess, {"q": q})
    assert all(r["status"] == "OK" for r in out), out
    spans = tracing.get_trace(tid)["spans"]
    (prepare,) = [s["labels"] for s in spans if s["name"] == "graph_prepare"]
    return out[-1]["result"][0]["c"], prepare, [s["labels"] for s in spans if s["name"] == "graph_filter"]


def served_compiles() -> list:
    """What the statements of this test compiled: a background prewarm that
    an earlier test of the worker left running logs its shapes (`prewarm`)
    into the same process-wide log."""
    return [e for e in compile_log.events() if e["mode"] != "prewarm"]


def routes_counted() -> dict:
    return {dict(k)["route"]: int(v) for k, v in telemetry.counters_matching("graph_count_filter").items()}


# ------------------------------------------------------------------ the answer
PREDICATES = {
    "equality": ("firstName = $q.fn", {"fn": "Chen"}, lambda p: p["firstName"] == "Chen"),
    "range": ("age >= $q.lo AND age < $q.hi", {"lo": 10, "hi": 30}, lambda p: 10 <= p["age"] < 30),
    "and_or": ("(firstName = $q.fn OR firstName = 'Bo') AND !(age > $q.hi)", {"fn": "Dara", "hi": 25},
               lambda p: p["firstName"] in ("Dara", "Bo") and not p["age"] > 25),
    "nested_field_and_in": ("home.city = 'c1' AND firstName IN ['Anna', 'Eli']", {},
                            lambda p: p["home"]["city"] == "c1" and p["firstName"] in ("Anna", "Eli")),
    "missing_field_orders_first": ("nick < 5 OR age = $q.lo", {"lo": 3}, lambda p: True),
}


@pytest.mark.parametrize("predicate", sorted(PREDICATES))
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_filtered_count_is_the_walk_that_ends_at_a_passing_person(loaded, edges, route, predicate):
    ds, sess = loaded
    cond, bound, pred = PREDICATES[predicate]
    sql = f"SELECT count({CHAIN}(person WHERE {cond})) AS c FROM type::thing('person', $q.p)"
    before = ds.dispatch.stats()["submitted"]
    for start in (0, 7, 11):
        got, prepare, filters = ask(ds, sess, sql, {"p": start, **bound}, f"{route}-{predicate}-{start}")
        assert got == walk_ending(edges, start, pred)
        assert prepare == {"form": FORM[route], "filter": "fused",
                           **({"operand": "composed", "first_hop": "rows"} if route == "csc_composed" else {})}
        # the first statement makes the weights, the next two find them
        assert [f["outcome"] for f in filters] == ["build" if start == 0 else "hit"]
        assert int(filters[0]["rows"]) == sum(pred(person(i)) for i in range(N))
    assert ds.dispatch.stats()["submitted"] - before == 3  # one dispatch a statement
    assert forms() == {FORM[route]: 3} and routes_counted() == {"fused": 3}
    assert {e["subsystem"] for e in served_compiles()} == {"graph_" + FORM[route]}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_name_nobody_has_counts_zero_and_one_everybody_has_counts_the_bare_walk(loaded, edges, route):
    ds, sess = loaded
    assert ask(ds, sess, BY_NAME, {"p": 0, "fn": "Nobody"}, "nobody")[0] == 0
    everybody = f"SELECT count({CHAIN}(person WHERE age >= 0)) AS c FROM type::thing('person', $q.p)"
    bare, prepare, filters = ask(ds, sess, BARE, {"p": 0}, "bare")
    assert prepare["filter"] == "none" and filters == []
    assert ask(ds, sess, everybody, {"p": 0}, "everybody")[0] == bare == walk_ending(edges, 0, lambda p: True)


def test_a_small_chain_is_counted_on_the_host_with_the_mask(loaded, edges, monkeypatch):
    ds, sess = loaded
    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 10**15)
    monkeypatch.setattr(cnf, "TPU_GRAPH_ONDEVICE_THRESHOLD", 10**9)
    before = ds.dispatch.stats()["submitted"]
    got, prepare, filters = ask(ds, sess, BY_NAME, {"p": 7, "fn": "Bo"}, "small")
    assert got == walk_ending(edges, 7, lambda p: p["firstName"] == "Bo")
    assert prepare == {"form": "host", "filter": "fused"} and [f["outcome"] for f in filters] == ["build"]
    assert ds.dispatch.stats()["submitted"] == before and served_compiles() == []


# ------------------------------------------------------------------ batches
def serve_batch(ds, sess, monkeypatch, requests):
    """Every request's count through ds.execute(), the first alone (it holds
    the bucket) and the rest as ONE batch behind it."""
    q, got = HeldQueue(), {}
    monkeypatch.setattr(ds, "dispatch", q)

    def rider(i, sql, bound):
        got[i] = ask(ds, sess, sql, bound, f"rider-{i}")

    threads = [threading.Thread(target=rider, args=(i, sql, bound)) for i, (sql, bound) in enumerate(requests)]
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
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_riders_that_bind_different_values_share_one_dispatch(loaded, edges, monkeypatch, route, riders):
    ds, sess = loaded
    bound = [{"p": (i * 37) % N, "fn": NAMES[i % len(NAMES)]} for i in range(riders + 1)]
    assert len({b["fn"] for b in bound[1:]}) == min(riders, len(NAMES))  # the batch's riders differ
    answers, q = serve_batch(ds, sess, monkeypatch, [(BY_NAME, b) for b in bound])
    for b, (got, prepare, filters) in zip(bound, answers):
        assert got == walk_ending(edges, b["p"], lambda p, fn=b["fn"]: p["firstName"] == fn)
        assert prepare["filter"] == "fused" and prepare["form"] == FORM[route] and len(filters) == 1
    assert q.width_distribution() == ({1: 2} if riders == 1 else {1: 1, riders: 1})
    launches = [[s["labels"] for s in tracing.get_trace(f"rider-{i}")["spans"] if s["name"] == "dispatch_launch"]
                for i in range(1, riders + 1)]
    # the sparse count reads its first hop from the operator's rows: one swept hop of three pairs
    swept = {"sweeps": "1"} if route == "csc_composed" else {}
    assert launches == [[{"batch": str(riders), "lanes": "16" if riders == 9 else "8", **swept}]] * riders
    assert routes_counted() == {"fused": riders + 1}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_filtered_and_a_bare_count_queue_apart_and_both_are_right(loaded, edges, route):
    ds, sess = loaded
    requests = [(BY_NAME, {"p": 3, "fn": "Anna"}), (BARE, {"p": 5}), (BY_NAME, {"p": 5, "fn": "Eli"}), (BARE, {"p": 9})]
    answers = [ask(ds, sess, sql, q, f"apart-{i}") for i, (sql, q) in enumerate(requests)]
    assert [a[0] for a in answers] == [
        walk_ending(edges, 3, lambda p: p["firstName"] == "Anna"), walk_ending(edges, 5, lambda p: True),
        walk_ending(edges, 5, lambda p: p["firstName"] == "Eli"), walk_ending(edges, 9, lambda p: True)]
    assert [a[1]["filter"] for a in answers] == ["fused", "none", "fused", "none"]
    # one bucket the filtered counts share whatever they bound, one the bare counts
    assert len(ds.dispatch._buckets) == 2 and ds.dispatch.stats()["submitted"] == 4


# ------------------------------------------------------------------ shapes
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_after_the_warm_up_a_value_never_seen_compiles_nothing(loaded, edges, monkeypatch, route):
    ds, sess = loaded
    assert ask(ds, sess, BARE, {"p": 0}, "first")[1]["filter"] == "none"  # builds the mirrors
    compile_log.reset()
    ds.graph_mirrors._warmed_pairs.clear()
    ds.graph_mirrors.warm_count_kernels(NS, DB)
    warmed = compile_log.events()
    assert {e["mode"] for e in warmed} == {"prewarm"} and telemetry.counters_matching("prewarm_errors") == {}
    # the filtered shapes beside the bare ones: every lane count, as often as any other, once a chain
    # length (three of the person pair; the dense form holds the knows->person->knows pair too)
    weighted = Counter(int(e["shape"].split("x")[0]) for e in warmed if e["shape"].endswith("xw"))
    assert sorted(weighted) == [8, 16, 32, 64] and min(weighted.values()) >= (3 if route == "csc_composed" else 6)
    assert len(warmed) >= 2 * sum(weighted.values())
    for i, fn in enumerate(NAMES + ["Nobody"]):
        got, prepare, _ = ask(ds, sess, BY_NAME, {"p": i, "fn": fn}, f"warm-{i}")
        assert got == walk_ending(edges, i, lambda p, fn=fn: p["firstName"] == fn) and prepare["filter"] == "fused"
    bound = [{"p": i, "fn": NAMES[i % 7]} for i in range(10)]
    serve_batch(ds, sess, monkeypatch, [(BY_NAME, b) for b in bound])  # a batch of 9: 16 lanes
    assert served_compiles() == []


def test_the_weights_are_kept_a_binding_and_dropped_with_the_mirrors(loaded, edges):
    ds, sess = loaded
    gm = ds.graph_mirrors
    for fn in ("Anna", "Bo", "Anna"):
        ask(ds, sess, BY_NAME, {"p": 1, "fn": fn}, f"keep-{fn}-{len(gm._endw._d)}")
    assert len(gm._endw._d) == 2 and all(k[0][:2] == (NS, DB) for k in gm._endw._d)
    (entry,) = [e for k, (e, _) in gm._endw._d.items() if "'Bo'" in repr(k[1])]
    w = np.asarray(entry["w"])
    passing = {i for i in range(N) if person(i)["firstName"] == "Bo"}
    space = gm.table_space(NS, DB, "person")
    local = {g: j for j, g in enumerate(space["globals"])}
    it = gm.interner(NS, DB)
    want = np.zeros_like(w)
    for a, b in edges:
        if b in passing:
            want[local[it.lookup(Thing("person", a))]] += 1
    assert (w == want).all() and entry["rows"] == len(passing)
    gm.drop_table(NS, DB, "knows")
    assert len(gm._endw._d) == 0 and set(gm._mirror_rows) == {(NS, DB, "person")}
    gm.drop_table(NS, DB, "person")
    assert gm._mirror_rows == {}


# ------------------------------------------------------------------ guarantees
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_an_acknowledged_update_of_the_filtered_field_is_seen_by_the_next_count(loaded, edges, monkeypatch, route):
    ds, sess = loaded
    monkeypatch.setattr(cnf, "COLUMN_REBUILD_DEBOUNCE_SECS", 0.0)
    persons = [person(i) for i in range(N)]
    is_zed = lambda p: p["firstName"] == "Zed"  # noqa: E731
    assert ask(ds, sess, BY_NAME, {"p": 0, "fn": "Zed"}, "before")[0] == 0
    renamed = [b for a, b in edges if a == 0][:5]
    for u in renamed:
        (res,) = ds.execute(f"UPDATE person:{u} SET firstName = 'Zed'", sess)
        assert res["status"] == "OK", res
        persons[u]["firstName"] = "Zed"
    got, prepare, filters = ask(ds, sess, BY_NAME, {"p": 0, "fn": "Zed"}, "after")
    assert got == walk_ending(edges, 0, is_zed, persons) > 0
    assert prepare["filter"] == "fused" and filters[0]["outcome"] == "build" and int(filters[0]["rows"]) == len(set(renamed))


def test_while_the_column_mirror_is_stale_the_count_walks_the_kv_and_is_right(loaded, edges, monkeypatch):
    ds, sess = loaded
    monkeypatch.setattr(cnf, "COLUMN_REBUILD_DEBOUNCE_SECS", 3600.0)
    assert ask(ds, sess, BY_NAME, {"p": 7, "fn": "Zed"}, "before")[0] == 0
    friend = next(b for a, b in edges if a == 7)
    ds.column_mirrors.shutdown()  # no background rebuild: the mirror stays stale
    (res,) = ds.execute(f"UPDATE person:{friend} SET firstName = 'Zed'", sess)
    assert res["status"] == "OK", res
    persons = [person(i) for i in range(N)]
    persons[friend]["firstName"] = "Zed"
    got, prepare, filters = ask(ds, sess, BY_NAME, {"p": 7, "fn": "Zed"}, "stale")
    assert got == walk_ending(edges, 7, lambda p: p["firstName"] == "Zed", persons) > 0
    assert prepare == {"form": "host", "filter": "host"} and filters == []


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_an_acknowledged_relate_is_seen_by_the_next_count(loaded, edges, route):
    ds, sess = loaded
    is_gus = lambda p: p["firstName"] == "Gus"  # noqa: E731
    before = ask(ds, sess, BY_NAME, {"p": 0, "fn": "Gus"}, "before")[0]
    assert before == walk_ending(edges, 0, is_gus)
    gus = next(i for i in range(N) if is_gus(person(i)))
    friend = next(b for a, b in edges if a == 0)
    more = edges + [(friend, gus), (gus, gus)]
    (res,) = ds.execute(f"RELATE person:{friend}->knows->person:{gus}; RELATE person:{gus}->knows->person:{gus}", sess)[-1:]
    assert res["status"] == "OK", res
    got, prepare, filters = ask(ds, sess, BY_NAME, {"p": 0, "fn": "Gus"}, "after")
    assert got == walk_ending(more, 0, is_gus) > before
    assert prepare["filter"] == "fused" and filters[0]["outcome"] == "build"  # the operator's generation moved


# ------------------------------------------------------------------ what cannot ride
WALKED = {
    "not_vectorisable": (f"{CHAIN}(person WHERE string::len(firstName) = $q.k)", lambda x, e: None),
    "cond_on_a_middle_part": ("->knows->(person WHERE firstName = $q.fn)->knows->person->knows->person", None),
    "cond_on_an_edge_part": ("->knows->person->knows->person->(knows WHERE id != $q.fn)->person", None),
    "two_tables_in_the_last_part": (f"{CHAIN}(person, knows WHERE firstName = $q.fn)", None),
    "a_row_bound_parameter": (f"{CHAIN}(person WHERE firstName = $parent.firstName)", None),
}


def walked_reference(edges, which: str, start: int, q: dict) -> int:
    p = [person(i) for i in range(N)]
    if which == "not_vectorisable":
        return walk_ending(edges, start, lambda r: len(r["firstName"]) == q["k"])
    if which == "cond_on_a_middle_part":
        first = [b for a, b in edges if a == start and p[b]["firstName"] == q["fn"]]
        return sum(walk_ending(edges, b, lambda r: True, steps=2) for b in first)
    if which == "a_row_bound_parameter":
        return walk_ending(edges, start, lambda r: r["firstName"] == p[start]["firstName"])
    if which == "two_tables_in_the_last_part":
        return walk_ending(edges, start, lambda r: r["firstName"] == q["fn"])
    return walk_ending(edges, start, lambda r: True)


@pytest.mark.parametrize("which", sorted(WALKED))
def test_what_cannot_ride_takes_the_kv_walk_and_says_so(loaded, edges, which):
    ds, sess = loaded
    sql = f"SELECT count({WALKED[which][0]}) AS c FROM type::thing('person', $q.p)"
    q = {"p": 11, "fn": "Anna", "k": 4}
    before = ds.dispatch.stats()["submitted"]
    got, prepare, filters = ask(ds, sess, sql, q, which)
    assert got == walked_reference(edges, which, 11, q)
    assert prepare == {"form": "host", "filter": "host"} and filters == []
    assert ds.dispatch.stats()["submitted"] == before and routes_counted() == {"host": 1}


def test_an_open_transaction_with_edge_writes_keeps_the_exact_kv_walk(loaded, edges):
    ds, sess = loaded
    anna = lambda p: p["firstName"] == "Anna"  # noqa: E731
    committed = ask(ds, sess, BY_NAME, {"p": 11, "fn": "Anna"}, "committed")
    assert committed[0] == walk_ending(edges, 11, anna) and committed[1]["filter"] == "fused"
    target = next(i for i in range(N) if anna(person(i)))
    text = f"BEGIN; RELATE person:11->knows->person:{target}; {BY_NAME}; COMMIT;"
    got, prepare, filters = ask(ds, sess, text, {"p": 11, "fn": "Anna"}, "open")
    assert got == walk_ending(edges + [(11, target)], 11, anna) > committed[0]  # its own uncommitted edge counts
    assert prepare == {"form": "host", "filter": "host"} and filters == []
    after = ask(ds, sess, BY_NAME, {"p": 11, "fn": "Anna"}, "after")
    assert after[0] == got and after[1]["filter"] == "fused"  # acknowledged: the mirrors have it


# ------------------------------------------------------------------ the bare count
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_the_bare_count_runs_the_program_it_ran_with_the_inputs_it_had(loaded, edges, monkeypatch, route):
    ds, sess = loaded
    graph_csr._kernels()
    name = "chain_count_batch" + ("_dense" if route == "dense_limbs" else "")
    real, calls = graph_csr._JITTED[name], []

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)

    monkeypatch.setitem(graph_csr._JITTED, name, spy)
    got, prepare, filters = ask(ds, sess, BARE, {"p": 0}, "bare")
    assert got == walk_ending(edges, 0, lambda p: True) and prepare["filter"] == "none" and filters == []
    ((args, kwargs),) = calls
    assert kwargs.get("end_weights") is None
    # the last pair's out-degrees (dense) / its source-side indptr (csc): what the count always ended in
    assert args[1] is not None and (route == "dense_limbs" or len(args[1]) == 1)
    # seeds at the frontier pad (dense); their rows at the pad the sparse operator fixes (person 0 has 300 friends)
    assert args[2].shape == args[3].shape == ((8, 256) if route == "dense_limbs" else (8, 512))
    assert route == "dense_limbs" or (len(args[0]) == 1 and prepare["first_hop"] == "rows")
    (event,) = served_compiles()
    assert not event["shape"].endswith("w")
    # and a filtered count of the same chain ends in weights, under a shape of its own
    ask(ds, sess, BY_NAME, {"p": 0, "fn": "Anna"}, "filtered")
    (_, kw) = calls[-1]
    assert len(kw["end_weights"]) == 8 and all(w.shape == kw["end_weights"][0].shape for w in kw["end_weights"])
    assert [e["shape"].endswith("xw") for e in served_compiles()] == [False, True]


def test_the_served_program_keeps_the_module_name_trace_readers_match():
    import jax
    import jax.numpy as jnp

    graph_csr._kernels()
    ends = (jax.ShapeDtypeStruct((512,), jnp.int32),) * 8
    hop = ((jax.ShapeDtypeStruct((513,), jnp.int32), jax.ShapeDtypeStruct((1024,), jnp.int32)),)
    lanes = jax.ShapeDtypeStruct((8, 256), jnp.int32)
    lowered = graph_csr._JITTED["chain_count_batch"].lower((hop, hop), (), lanes, lanes, n_cap=512, end_weights=ends)
    assert "jit_chain_count_batch" in lowered.as_text()[:400]


def test_a_field_of_a_bound_parameter_is_a_constant_and_a_row_s_is_not():
    from surrealdb_tpu.ops.predicates import _is_const
    from surrealdb_tpu.syn import parse_value

    assert _is_const(parse_value("$q.fn")) and _is_const(parse_value("$q.a.b")) and _is_const(parse_value("$fn"))
    for text in ("$this.firstName", "$parent.firstName", "firstName", "$q[0]", "$q.fn + 1"):
        assert not _is_const(parse_value(text)), text
