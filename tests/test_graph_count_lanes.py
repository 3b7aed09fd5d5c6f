"""A batched graph count runs at the lanes its riders fill (ISSUE 30): the
lane rule, the same int32 at every lane count on the three device routes,
a warm-up that compiles exactly the rule's vocabulary, and the `lanes` label
and counter that say what a batch ran at. Batches are made through
DispatchQueue.submit itself: a lead rider holds the bucket while the rest
queue up behind it, so they ride one dispatch. References are int64 NumPy
walks over the edge list."""

import threading
import time
from collections import Counter

import numpy as np
import pytest

from surrealdb_tpu import cnf, compile_log, telemetry, tracing
from surrealdb_tpu.dbs.dispatch import DispatchQueue
from surrealdb_tpu.idx import graph_csr
from surrealdb_tpu.utils.num import COUNT_LANES_MIN, count_lane_set, count_lanes
from test_graph_dense_exact import DB, NS, PAIR, as_int32, forms, lognormal_hub, mirrors_of, near_complete, walk_count

VOCABULARY = (8, 16, 32, 64)


@pytest.fixture(autouse=True)
def fresh_counters(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    telemetry.reset()
    compile_log.reset()
    tracing.store_reset()
    yield
    tracing.store_reset()


# ------------------------------------------------------------------ the rule
@pytest.mark.parametrize("riders, lanes", [(1, 8), (2, 8), (7, 8), (8, 8), (9, 16), (16, 16), (17, 32), (32, 32),
                                           (33, 64), (64, 64)])
def test_the_lane_count_follows_the_riders(riders, lanes):
    assert count_lanes(riders) == lanes
    frs, cws = graph_csr._stack_lanes([(np.arange(4), np.ones(4))] * riders, 4, 99)
    assert frs.shape == cws.shape == (lanes, 4) and frs.dtype == cws.dtype == np.int32
    assert (frs[riders:] == 99).all() and not cws[riders:].any()  # padding lanes: sentinel seeds, no weight


def test_the_vocabulary_is_what_the_rule_returns_up_to_the_width_cap():
    assert COUNT_LANES_MIN == 8 and cnf.DISPATCH_MAX_WIDTH == 64
    assert count_lane_set() == VOCABULARY == tuple(sorted({count_lanes(r) for r in range(1, 65)}))
    assert (count_lane_set(1), count_lane_set(8), count_lane_set(9), count_lane_set(32)) == ((8,), (8,), (8, 16), (8, 16, 32))
    assert not hasattr(cnf, "TPU_GRAPH_BATCH_LANES")


# ------------------------------------------------------------------ batches
class HeldQueue(DispatchQueue):
    """A dispatch queue whose first launch waits: whatever is submitted
    meanwhile queues behind it and rides ONE dispatch when `release` is set."""

    def __init__(self):
        super().__init__()
        self.started, self.release = threading.Event(), threading.Event()

    def submit(self, key, payload, runner, **bucket):
        def held(payloads):
            if not self.started.is_set():
                self.started.set()
                assert self.release.wait(30)
            return runner(payloads)

        return super().submit(key, payload, held, **bucket)

    def queued(self) -> int:
        return sum(len(b.queue) for b in list(self._buckets.values()))


def seeds_of(i: int, n: int) -> dict:
    """Rider i's weighted start persons: distinct a rider, heavy enough that
    three pairs over near_complete(120) pass 2**31 and the int32 sum wraps."""
    return {(3 + i) % n: 5000 + i, (40 + i) % n: 1, (77 + 2 * i) % n: 300}


def serve_batch(gm, persons, seed_sets, pairs: int):
    """Every seed set's count, the first served alone (a batch of one that
    holds the bucket) and the rest as ONE batch behind it. Returns the
    counts in order and the queue."""
    q, got = HeldQueue(), {}

    def rider(i, seeds):
        frontier = np.asarray(sorted(persons[s] for s in seeds), dtype=np.int32)
        weight = {int(persons[s]): w for s, w in seeds.items()}
        counts = np.asarray([weight[int(g)] for g in frontier], dtype=np.int32)
        with tracing.request("req", trace_id=f"rider-{i}"), telemetry.span("statement"):
            got[i] = gm._device_chain(NS, DB, frontier, counts, PAIR * pairs, count_only=True, dispatch=q)

    threads = [threading.Thread(target=rider, args=(i, s)) for i, s in enumerate(seed_sets)]
    threads[0].start()
    assert q.started.wait(30)
    for t in threads[1:]:
        t.start()
    deadline = time.monotonic() + 30
    while q.queued() < len(seed_sets) - 1 and time.monotonic() < deadline:
        time.sleep(0.002)
    q.release.set()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads) and len(got) == len(seed_sets)
    return [got[i] for i in range(len(seed_sets))], q


def launch_labels(rider: int) -> dict:
    (span,) = [s for s in tracing.get_trace(f"rider-{rider}")["spans"] if s["name"] == "dispatch_launch"]
    return span["labels"]


def lane_counter() -> dict:
    return {int(dict(k)["lanes"]): int(v) for k, v in telemetry.counters_matching("graph_count_lanes").items()}


# `swept`: what a sparse count's launch says beside its lanes. Three seeds of 119 friends each pass the
# composed operator's row pad (128), so the kernel sweeps from the seeds: two pairs, or five record-level specs
ROUTES = {
    "dense_limbs": {"form": "dense", "dense_max": None, "compose": True, "swept": {}},
    "csc_composed": {"form": "csc", "dense_max": 1, "compose": True, "swept": {"sweeps": "2"}},
    "csc_records": {"form": "csc", "dense_max": 1, "compose": False, "swept": {"sweeps": "5"}},
}


@pytest.fixture(scope="module")
def graph():
    n = 120
    return n, near_complete(n)


@pytest.mark.parametrize("riders", [1, 5, 8, 9, 17])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_the_same_int32_at_every_lane_count_on_every_device_route(monkeypatch, graph, route, riders):
    n, edges = graph
    how = ROUTES[route]
    if how["dense_max"] is not None:
        monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", how["dense_max"])
    gm, persons = mirrors_of(n, edges)
    if not how["compose"]:
        monkeypatch.setattr(gm, "_csc_pair", lambda *a, **k: None)
    seed_sets = [seeds_of(i, n) for i in range(riders + 1)]
    walks = [walk_count(n, edges, s, 3) for s in seed_sets]
    assert min(walks) >= 2**31  # every rider's count wraps
    got, q = serve_batch(gm, persons, seed_sets, 3)
    assert got == [as_int32(w) for w in walks]
    assert forms() == {how["form"]: riders + 1}
    # the lead alone, then the rest in one dispatch at the lanes they fill
    assert q.width_distribution() == ({1: 2} if riders == 1 else {1: 1, riders: 1})
    assert launch_labels(0) == {"batch": "1", "lanes": "8", **how["swept"]}
    for i in range(1, riders + 1):
        assert launch_labels(i) == {"batch": str(riders), "lanes": str(count_lanes(riders)), **how["swept"]}
    assert lane_counter() == Counter([8, count_lanes(riders)])  # one a dispatch, from the value the label has
    shapes = {int(e["shape"].split("x")[0]) for e in compile_log.events()}
    assert shapes == {8, count_lanes(riders)}


# ------------------------------------------------------------------ warm-up
@pytest.mark.parametrize("route", ["dense_limbs", "csc_composed"])
def test_after_the_warm_up_no_batch_width_compiles(monkeypatch, route):
    if ROUTES[route]["dense_max"] is not None:
        monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", ROUTES[route]["dense_max"])
    n, edges = 50, lognormal_hub(50, 30, seed=9)
    gm, persons = mirrors_of(n, edges)
    gm.warm_count_kernels(NS, DB)
    warmed = compile_log.events()
    assert {e["mode"] for e in warmed} == {"prewarm"}
    assert {e["subsystem"] for e in warmed} == {"graph_" + ("dense" if route == "dense_limbs" else "csc")}
    # exactly the lane counts the runners can return, each as often as any other (once a pair and chain length)
    per_lanes = Counter(int(e["shape"].split("x")[0]) for e in warmed)
    assert tuple(sorted(per_lanes)) == VOCABULARY and len(set(per_lanes.values())) == 1
    assert telemetry.counters_matching("prewarm_errors") == {}
    assert lane_counter() == {}  # a warm-up is no served batch
    for riders in (1, 9, 17, 33):
        seed_sets = [{i % n: 1 + i} for i in range(riders + 1)]
        got, _ = serve_batch(gm, persons, seed_sets, 3)
        assert got == [walk_count(n, edges, s, 3) for s in seed_sets]
    assert compile_log.events() == warmed
    assert lane_counter() == {8: 5, 16: 1, 32: 1, 64: 1}


# ------------------------------------------------------------------ other runners
def test_a_knn_dispatch_has_no_lanes_label(ds, monkeypatch):
    monkeypatch.setattr(cnf, "TPU_KNN_ONDEVICE_THRESHOLD", 64)  # 256 rows reach the dispatch queue
    rng = np.random.default_rng(3)
    ddl = "DEFINE TABLE item; DEFINE INDEX ix ON item FIELDS emb HNSW DIMENSION 8 DIST EUCLIDEAN EFC 16;"
    assert all(r["status"] == "OK" for r in ds.execute(ddl))
    rows = [{"id": i, "emb": rng.normal(size=8).tolist()} for i in range(256)]
    assert ds.execute("INSERT INTO item $rows RETURN NONE", vars={"rows": rows})[-1]["status"] == "OK"
    tracing.store_reset()
    out = ds.execute("SELECT id FROM item WHERE emb <|5,16|> $q", vars={"q": rng.normal(size=8).tolist()})
    assert out[-1]["status"] == "OK" and len(out[-1]["result"]) == 5
    launches = [s for tid in tracing.trace_ids() for s in tracing.get_trace(tid)["spans"] if s["name"] == "dispatch_launch"]
    assert launches and all(s["labels"] == {"batch": "1"} for s in launches)
    assert lane_counter() == {}


def test_a_runner_that_says_nothing_labels_nothing():
    q = DispatchQueue()
    with tracing.request("req", trace_id="plain"):
        assert q.submit("k", 1, lambda ps: (lambda: [p + 1 for p in ps])) == 2
        assert q.submit("k", 2, lambda ps: [p + 1 for p in ps]) == 3
    launches = [s for s in tracing.get_trace("plain")["spans"] if s["name"] == "dispatch_launch"]
    assert [s["labels"] for s in launches] == [{"batch": "1"}, {"batch": "1"}]


# ------------------------------------------------------------------ audit shapes
@pytest.mark.parametrize("subsystem", ["graph_dense", "graph_csc"])
def test_graftcheck_audits_the_count_kernels_as_served(subsystem):
    """`python -m scripts.analysis` lowers every registered shape; here the two
    count kernels' contracts alone, at each lane count of the vocabulary."""
    from scripts.graftcheck import lowering, registry, rules

    (contract,) = registry.resolve_contracts([subsystem])
    # the vocabulary twice: ending in the last pair's degrees, and in a rider's own end weights (ISSUE 31)
    lane_shapes = [s for s in contract["shapes"] if "paths" not in s]
    for weighted in (False, True):
        assert tuple(s["lanes"] for s in lane_shapes if s["weighted"] is weighted) == VOCABULARY
    # and, for the sparse kernel, one slot count that is no power of two (ISSUE 34: tests/test_graph_path_slots.py)
    assert len(lane_shapes) == 2 * len(VOCABULARY) == len(contract["shapes"]) - (subsystem == "graph_csc")
    for shape in contract["shapes"]:
        low = lowering.lower_site(contract, shape)
        assert rules.check(contract, shape, low) == [] and low.collectives == {}
