"""The dense count form carries its counts as int32 limbs: exact under 2**31
at any degree, bit for bit the CSC form's answer, and chosen from what the
operator is (ISSUE 25). References are int64 NumPy walks over the edge list."""

import numpy as np
import pytest

from surrealdb_tpu import cnf, compile_log, key as keys, telemetry
from surrealdb_tpu.dbs.dispatch import DispatchQueue
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.idx import graph_csr
from surrealdb_tpu.idx.graph_csr import GraphMirrors
from surrealdb_tpu.sql.value import Thing

NS, DB = "t", "t"
PAIR = [(["person"], [keys.DIR_OUT], ["knows"]), (["knows"], [keys.DIR_OUT], ["person"])]


@pytest.fixture(autouse=True)
def fresh_counters():
    telemetry.reset()
    compile_log.reset()


def mirrors_of(n: int, edges: np.ndarray):
    """person -> knows -> person mirrors of `edges` ([E, 2] person ids, one
    knows record a row), built in memory as a table build leaves them."""
    gm = GraphMirrors()
    it = gm.interner(NS, DB)
    persons = [it.intern(Thing("person", i)) for i in range(n)]
    records = [it.intern(Thing("knows", j)) for j in range(len(edges))]
    out: dict = {}
    for j, (a, _) in enumerate(edges.tolist()):
        out.setdefault(persons[a], []).append(records[j])
    gm._get_or_create(NS, DB, "person", keys.DIR_OUT, "knows").load(out)
    gm._get_or_create(NS, DB, "knows", keys.DIR_OUT, "person").load(
        {records[j]: [persons[b]] for j, (_, b) in enumerate(edges.tolist())}
    )
    return gm, np.asarray(persons, dtype=np.int32)


def walk_count(n: int, edges: np.ndarray, seeds: dict, pairs: int, passing=None) -> int:
    """Walks of `pairs` knows records from the weighted seeds, in int64;
    with `passing` (a boolean mask over the persons) those that end there."""
    x = np.zeros(n, dtype=np.int64)
    for s, w in seeds.items():
        x[s] += w
    for _ in range(pairs):
        y = np.zeros(n, dtype=np.int64)
        np.add.at(y, edges[:, 1], x[edges[:, 0]])
        x = y
    return int(x.sum() if passing is None else x[passing].sum())


def as_int32(v: int) -> int:
    return (v + 2**31) % 2**32 - 2**31


def device_count(gm, persons, seeds: dict, pairs: int) -> int:
    frontier = np.asarray(sorted(persons[s] for s in seeds), dtype=np.int32)
    by_global = {int(persons[s]): w for s, w in seeds.items()}
    counts = np.asarray([by_global[int(g)] for g in frontier], dtype=np.int32)
    return gm._device_chain(NS, DB, frontier, counts, PAIR * pairs, count_only=True,
                            dispatch=DispatchQueue())


def forms() -> dict:
    return {dict(k)["form"]: int(v) for k, v in telemetry.counters_matching("graph_count_form").items()}


def near_complete(n: int) -> np.ndarray:
    a, b = np.divmod(np.arange(n * n), n)
    return np.stack([a, b], axis=1)[a != b]


def lognormal_hub(n: int = 1500, hub_degree: int = 900, seed: int = 3) -> np.ndarray:
    """Skewed out-degrees (log-normal, sigma 1.14 as `snbsf1`'s) and person 0
    with `hub_degree` friends: the shape the float32 guard refused."""
    rng = np.random.default_rng(seed)
    deg = np.minimum(np.rint(rng.lognormal(2.0, 1.14, n)).astype(np.int64), 400)
    deg[0] = hub_degree
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, size=src.size)
    dst[:hub_degree] = rng.permutation(n - 1)[:hub_degree] + 1
    return np.stack([src, dst], axis=1)


GRAPHS = {
    # 3-pair counts ~2.7e7: past float32's 2**24, and 299**3 past the old guard
    "near_complete_300": lambda: (300, near_complete(300), 7),
    "lognormal_hub_900": lambda: (1500, lognormal_hub(), 0),
}


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name):
        if name not in cache:
            n, edges, start = GRAPHS[name]()
            cache[name] = (n, edges, start) + mirrors_of(n, edges)
        return cache[name]

    return get


@pytest.mark.parametrize("pairs", [1, 2, 3])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_dense_counts_equal_the_int64_walk_on_graphs_the_old_guard_refused(built, graph, pairs):
    n, edges, start, gm, persons = built(graph)
    assert np.bincount(edges[:, 0]).max() >= 256
    one = walk_count(n, edges, {start: 1}, pairs)
    assert one < 2**31
    if graph == "near_complete_300" and pairs == 3:
        assert one > 2**24
    assert device_count(gm, persons, {start: 1}, pairs) == one
    # seed weights above 255 need more than one limb; past 2**31 the int32 sum wraps
    heavy = {start: 1000, start + 1: 257, start + 5: 70_001}
    assert device_count(gm, persons, heavy, pairs) == as_int32(walk_count(n, edges, heavy, pairs))
    assert forms() == {"dense": 2}
    assert {e["subsystem"] for e in compile_log.events()} == {"graph_dense"}


@pytest.mark.parametrize("pairs", [1, 2, 3])
def test_dense_and_csc_forms_give_the_same_int32(monkeypatch, pairs):
    n, edges = 120, near_complete(120)
    seeds = {3: 5000, 4: 1, 77: 300}  # 119**3 x 5301 wraps int32 at three pairs
    gm, persons = mirrors_of(n, edges)
    dense = device_count(gm, persons, seeds, pairs)
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", n - 1)
    gm2, persons2 = mirrors_of(n, edges)
    csc = device_count(gm2, persons2, seeds, pairs)
    assert forms() == {"dense": 1, "csc": 1}
    assert dense == csc == as_int32(walk_count(n, edges, seeds, pairs))


def parallel_edges(sources: int, copies: int) -> tuple:
    """`sources` persons, each with `copies` knows records to person 0."""
    n = sources + 1
    src = np.repeat(np.arange(1, n), copies)
    return n, np.stack([src, np.zeros_like(src)], axis=1)


def test_an_operator_at_the_limb_limit_is_dense_and_exact():
    # column sum 273 x 241 = 65,793: 255 x that is 2**24 - 1, the last exact float32 sum
    n, edges = parallel_edges(273, 241)
    gm, persons = mirrors_of(n, edges)
    assert gm._dense_pair(NS, DB, *PAIR) is not None
    seeds = {s: 255 for s in range(1, n)}
    assert device_count(gm, persons, seeds, 1) == 255 * 65_793 == 2**24 - 1
    assert forms() == {"dense": 1}


@pytest.mark.parametrize("sources, copies", [(2, 256), (274, 241)], ids=["multiplicity", "column_sum"])
def test_an_operator_past_the_limb_limit_is_refused_and_served_exactly_by_csc(sources, copies):
    n, edges = parallel_edges(sources, copies)
    gm, persons = mirrors_of(n, edges)
    assert gm._dense_pair(NS, DB, *PAIR) is None
    composed = gm._dense[next(iter(gm._dense))]
    assert gm._dense_pair(NS, DB, *PAIR) is None
    assert gm._dense[next(iter(gm._dense))] is composed  # the refusal is not recomposed a statement
    seeds = {s: 255 for s in range(1, n)}
    assert device_count(gm, persons, seeds, 1) == walk_count(n, edges, seeds, 1) == 255 * sources * copies
    assert forms() == {"csc": 1}
    assert {e["subsystem"] for e in compile_log.events()} == {"graph_csc"}


def serve_graph(ds, n: int, edges: np.ndarray):
    """`n` persons and one knows record an edge, loaded through ds.execute()."""
    sess = Session.owner(NS, DB)
    ds.execute("DEFINE TABLE person SCHEMALESS; DEFINE TABLE knows SCHEMALESS", sess)
    ds.execute("INSERT INTO person $rows RETURN NONE", sess, {"rows": [{"id": i} for i in range(n)]})
    rows = [{"in": Thing("person", int(a)), "out": Thing("person", int(b))} for a, b in edges]
    (res,) = ds.execute("INSERT RELATION INTO knows $rows RETURN NONE", sess, {"rows": rows})
    assert res["status"] == "OK", res
    return sess


HOP3 = "SELECT count(->knows->person->knows->person->knows->person) AS c FROM person:{}"


def test_a_served_count_on_a_degree_256_graph_is_dense(ds, monkeypatch):
    """Through ds.execute(): the compile log names `graph_dense` and not
    `graph_csc`, and `graph_count_form` counts one dense chain a statement."""
    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 1)
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    n, hub = 320, 300
    edges = np.asarray([(0, j) for j in range(1, hub + 1)] + [(j, (j * 7) % n) for j in range(1, n)]
                       + [(j, 0) for j in range(1, n, 3)])
    sess = serve_graph(ds, n, edges)
    sql = HOP3
    for start in (0, 5, 9):
        (res,) = ds.execute(sql.format(start), sess)
        assert res["status"] == "OK", res
        assert res["result"][0]["c"] == walk_count(n, edges, {start: 1}, 3)
    assert forms() == {"dense": 3}
    subsystems = {e["subsystem"] for e in compile_log.events()}
    assert "graph_dense" in subsystems and "graph_csc" not in subsystems
    assert 'surreal_graph_count_form_total{form="dense"} 3' in telemetry.render_prometheus()
    # a chain too small for the device is counted too, as the host's
    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 10**9)
    (res,) = ds.execute("SELECT count(->knows->person) AS c FROM person:5", sess)
    assert res["result"][0]["c"] == walk_count(n, edges, {5: 1}, 1)
    assert forms() == {"dense": 3, "host": 1}


@pytest.mark.parametrize("form", ["dense", "csc"])
def test_concurrent_served_counts_all_answer_the_walk(ds, monkeypatch, form):
    """Sixteen sessions ask 3-hop counts at once, twice over: every statement
    is OK and equals the int64 walk, whichever riders shared its dispatch,
    and every one was submitted to the dispatch queue in the asked form."""
    import threading

    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 1)
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    n = 400
    if form == "csc":
        monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", n - 1)
    edges = lognormal_hub(n, hub_degree=120, seed=11)
    sess = serve_graph(ds, n, edges)
    sql = HOP3
    (res,) = ds.execute(sql.format(0), sess)  # builds the mirrors, compiles the shape
    assert res["result"][0]["c"] == walk_count(n, edges, {0: 1}, 3)
    threads, rounds = 16, 2
    starts = [(i * 37) % n for i in range(threads * rounds)]
    got, errors = {}, []
    barrier = threading.Barrier(threads)

    def client(i):
        barrier.wait()
        for r in range(rounds):
            j = i * rounds + r
            try:
                (res,) = ds.execute(sql.format(starts[j]), sess)
                assert res["status"] == "OK", res
                got[j] = res["result"][0]["c"]
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)

    before = ds.dispatch.stats()["submitted"]
    ts = [threading.Thread(target=client, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errors, errors[:1]
    assert got == {j: walk_count(n, edges, {starts[j]: 1}, 3) for j in range(len(starts))}
    assert ds.dispatch.stats()["submitted"] - before == len(starts)
    assert forms() == {form: len(starts) + 1}


# ------------------------------------------------------------------ the bucket (ISSUE 45)
@pytest.mark.parametrize("pairs, paced", [
    (1, False),  # no operator product: a dot product, the queue's own depth
    (2, True), (3, True), (4, True),  # a product or more: the same 8 lanes at any width, so one deep, and it gathers
])
def test_a_dense_count_with_an_operator_product_is_one_deep_and_gathers(built, pairs, paced):
    from surrealdb_tpu.dbs import dispatch

    n, edges, start, gm, persons = built("lognormal_hub_900")
    q = DispatchQueue()
    frontier, counts = np.asarray([persons[start]], dtype=np.int32), np.asarray([1], dtype=np.int32)
    got = gm._device_chain(NS, DB, frontier, counts, PAIR * pairs, count_only=True, dispatch=q)
    assert got == as_int32(walk_count(n, edges, {start: 1}, pairs)) and forms() == {"dense": 1}
    ((key, bucket),) = q._buckets.items()
    assert key[0] == "gdense" and len(key[3]) == pairs - 1
    assert (bucket.depth, bucket.gather) == ((dispatch.SWEEP_DEPTH, True) if paced else (q._depth(), False))
    assert dispatch.SWEEP_DEPTH == 1 and q._depth() == cnf.DISPATCH_PIPELINE_DEPTH == 2


ENDING = {  # the final part, and which persons pass it (None: all)
    "bare": ("person", None),
    "by_name": ("(person WHERE firstName = $fn)", lambda n: np.arange(n) % 3 == 1),
}


@pytest.mark.parametrize("pairs", [1, 3])
@pytest.mark.parametrize("ending", sorted(ENDING))
def test_eight_sessions_of_dense_counts_get_the_walk_s_counts_from_the_family_s_bucket(ds, monkeypatch, ending, pairs):
    """Eight threads in a closed loop, each its own starts, bare and ending
    in a predicate: every count is the int64 walk's, all rode the one
    `gdense` bucket of their form, whose depth is the family's (one deep
    and gathering with an operator product, the queue's own without), and
    some batch was more than two wide."""
    import threading

    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 1)
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    n, threads, rounds = 400, 8, 6
    edges = lognormal_hub(n, hub_degree=120, seed=13)
    sess = serve_graph(ds, n, edges)
    ds.execute("UPDATE person SET firstName = ['Anna', 'Bo', 'Chen'][id.id() % 3] RETURN NONE", sess)
    part, passes = ENDING[ending]
    passing = passes(n) if passes else None
    sql = f"SELECT count({'->knows->person' * (pairs - 1)}->knows->{part}) AS c FROM type::thing('person', $p)"

    def walk(start: int) -> int:
        return walk_count(n, edges, {start: 1}, pairs, passing)

    def ask(start: int) -> int:
        (res,) = ds.execute(sql, sess, {"p": start, "fn": "Bo"})
        assert res["status"] == "OK", res
        return res["result"][0]["c"]

    assert ask(0) == walk(0)  # builds the mirrors and the end weights, compiles the shape
    assert passing is None or 0 < walk(0) < walk_count(n, edges, {0: 1}, pairs)  # the predicate cuts the count
    starts = [(i * 37 + 5) % n for i in range(threads * rounds)]
    got, errors = {}, []
    barrier = threading.Barrier(threads)

    def client(i):
        barrier.wait()
        for j in range(i * rounds, (i + 1) * rounds):
            try:
                got[j] = ask(starts[j])
            except BaseException as e:  # noqa: BLE001 - reported below
                errors.append(e)

    w0, s0 = ds.dispatch.width_distribution(), ds.dispatch.stats()
    ts = [threading.Thread(target=client, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errors, errors[:1]
    assert got == {j: walk(starts[j]) for j in range(len(starts))}
    assert len(set(starts)) == len(starts) and forms() == {"dense": len(starts) + 1}
    w1, s1 = ds.dispatch.width_distribution(), ds.dispatch.stats()
    widths = {w: c - w0.get(w, 0) for w, c in w1.items() if c != w0.get(w, 0)}
    assert sum(w * c for w, c in widths.items()) == s1["submitted"] - s0["submitted"] == len(starts)
    assert max(widths) > 2, widths
    ((key, bucket),) = [(k, b) for k, b in ds.dispatch._buckets.items() if k[0] == "gdense"]
    assert isinstance(key[4], tuple) == (ending == "by_name")  # ("w", the weights' length), or the id of the last pair's degrees
    paced = pairs > 1
    assert (bucket.depth, bucket.gather) == ((1, True) if paced else (cnf.DISPATCH_PIPELINE_DEPTH, False))
    # only a gathering bucket counts a wait, and no wait is met more than once
    assert s1["gather_met"] - s0["gather_met"] <= s1["gather_waits"] - s0["gather_waits"] <= (s1["dispatches"] - s0["dispatches"]) * paced


def test_the_dense_entry_keeps_the_module_name_trace_readers_match():
    import jax
    import jax.numpy as jnp

    graph_csr._kernels()
    lowered = graph_csr._JITTED["chain_count_batch_dense"].lower(
        (jax.ShapeDtypeStruct((128, 128), jnp.bfloat16),),
        jax.ShapeDtypeStruct((128,), jnp.int32),
        jax.ShapeDtypeStruct((32, 256), jnp.int32),
        jax.ShapeDtypeStruct((32, 256), jnp.int32),
        n0=128,
    )
    assert lowered.as_text().lstrip().startswith("module @jit_chain_count_batch")
    assert [str(o.dtype) for o in jax.tree_util.tree_leaves(lowered.out_info)] == ["int32"]
