"""Every kNN serving strategy of `KnnPlan.iterate` against ONE ground truth.

One seeded corpus, one NumPy float32 reference, the statement the benchmark
asks (`benchmarks/configs/vec1m768.json`), with and without a residual
`AND flag = true`. Each case routes the planner to one strategy the way the
suite's other tests do (monkeypatched `cnf` thresholds, the virtual
8-device mesh for the two sharded ones, `wait_ivf` for determinism) and
holds the answer to the reference:

- the strategy counter rose for exactly the expected name;
- exact strategies return the float32 top-10 among the matching rows, the
  served distances inside the benchmark's bf16 limit (`distance_rms_rel`);
- IVF strategies return a full 10 matching rows, recall@10 >= 0.9; asked
  with the filter, a third of 3,072 rows pass, which is fewer slots than
  the widened probe would gather (idx/ivf.py::filtered_route), so the three
  IVF strategies score the passing rows exactly (`exact-subset*`; the
  widened route is held by tests/test_knn_filtered.py).

This is the net under ROADMAP D12 (the strategy choice as one decision
function): a refactor of `iterate` keeps every case green.
"""

import contextlib
import threading

import numpy as np
import pytest

from surrealdb_tpu import bg, cnf, telemetry
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.kvs.ds import Datastore

N, DIM, K, QUERIES = 3072, 32, 10, 6
SQL = "SELECT id, vector::distance::knn() AS d FROM item WHERE emb <|10,64|> $q"
SQL_FLAG = SQL + " AND flag = true"
# benchmarks/configs/vec1m768.json `correct.distance_rms_rel_max`: r.m.s.
# error of the served squared distances over their mean, bf16 corpus
DISTANCE_RMS_REL_MAX = 0.00065
RECALL_MIN = 0.9
NEVER = 1 << 60

STRATEGIES = (
    "ivf-sharded", "exact-sharded(ivf-training)", "exact-sharded",
    "ivf", "exact-device(ivf-training)", "exact-device",
    "ivf-host", "exact-host", "exact-overlay",
    "exact-subset-sharded", "exact-subset", "exact-subset-host",
)
# what serves the FILTERED statement where an IVF strategy serves the bare one
FILTERED = {
    "ivf-sharded": "exact-subset-sharded", "ivf": "exact-subset", "ivf-host": "exact-subset-host",
}

# strategy -> (TPU_ANN_MIN_ROWS, TPU_KNN_ONDEVICE_THRESHOLD, how the case is
# prepared). The threshold at NEVER keeps a case off the mesh branch; the
# lambda mesh is tests/test_column_scan.py's single-chip route.
ROUTES = {
    "ivf-sharded": (64, 1, "train"),
    "exact-sharded": (NEVER, 1, None),
    "ivf": (64, NEVER, "train"),
    "exact-device(ivf-training)": (64, NEVER, "hold-training"),
    "exact-device": (NEVER, 1, "one-chip"),
    "ivf-host": (64, NEVER, "train-then-disable"),
    "exact-host": (NEVER, NEVER, "disable"),
}


@pytest.fixture(scope="module")
def corpus():
    """Clustered rows (IVF's lists then mean something), every third
    flagged, queries near corpus rows; the float32 reference's top-10 over
    all rows and over the flagged ones."""
    rng = np.random.default_rng(29)
    centres = rng.standard_normal((48, DIM)).astype(np.float32) * 4.0
    vecs = (
        centres[rng.integers(0, len(centres), N)]
        + rng.standard_normal((N, DIM)).astype(np.float32)
    ).astype(np.float32)
    flags = np.arange(N) % 3 == 0
    qs = (vecs[rng.choice(N, QUERIES, replace=False)]
          + 0.05 * rng.standard_normal((QUERIES, DIM))).astype(np.float32)
    d2 = ((vecs[None, :, :] - qs[:, None, :]) ** 2).sum(axis=2, dtype=np.float32)

    def top(mask):
        masked = np.where(mask[None, :], d2, np.inf)
        return np.argsort(masked, axis=1, kind="stable")[:, :K]

    return {
        "vecs": vecs, "flags": flags, "qs": qs, "d2": d2,
        "top": {False: top(np.ones(N, dtype=bool)), True: top(flags)},
    }


def _load(corpus):
    ds = Datastore("memory")
    s = Session.owner()
    s.ns, s.db = "test", "test"
    ds.execute(
        "DEFINE TABLE item SCHEMALESS; "
        "DEFINE INDEX iv ON item FIELDS emb HNSW DIMENSION 32 DIST EUCLIDEAN EFC 64;", s)
    vecs, flags = corpus["vecs"], corpus["flags"]
    out = ds.execute("INSERT INTO item $rows", s, vars={"rows": [
        {"id": i, "emb": vecs[i].tolist(), "flag": bool(flags[i])} for i in range(N)
    ]})
    assert out[-1]["status"] == "OK", out[-1]
    return ds, s


def _ask(ds, s, sql, q):
    out = ds.execute(sql, s, vars={"q": q.tolist()})
    assert out[-1]["status"] == "OK", out[-1]
    rows = out[-1]["result"]
    return [int(r["id"].id) for r in rows], [float(r["d"]) for r in rows]


def _strategy_counts():
    return {n: telemetry.get_counter("knn_strategy", strategy=n) for n in STRATEGIES}


def _rose(before):
    """The strategy counters that moved since `before`, and by how much."""
    return {n: v - before[n] for n, v in _strategy_counts().items() if v != before[n]}


def _check(corpus, strategy, filtered, answers, before):
    """`answers`: [(ids, dists)] a query, in `corpus['qs']` order."""
    assert _rose(before) == {strategy: len(answers)}
    flags, d2, truth = corpus["flags"], corpus["d2"], corpus["top"][filtered]
    hits, errs, refs = 0, [], []
    for qi, (ids, dists) in enumerate(answers):
        assert len(ids) == K and len(set(ids)) == K, (strategy, qi, ids)
        if filtered:
            assert all(flags[i] for i in ids), (strategy, qi, ids)
        assert dists == sorted(dists), (strategy, qi, dists)
        hits += len(set(ids) & set(truth[qi].tolist()))
        for i, d in zip(ids, dists):
            refs.append(float(d2[qi, i]))
            errs.append(d * d - float(d2[qi, i]))
    # the distance a row is served with is that row's distance, whatever
    # the strategy: bf16 on the device forms, float32 on the host ones
    rms_rel = float(np.sqrt(np.mean(np.square(errs)))) / float(np.mean(refs))
    assert rms_rel <= DISTANCE_RMS_REL_MAX, (strategy, rms_rel)
    recall = hits / (K * len(answers))
    if strategy.startswith("ivf"):
        assert recall >= RECALL_MIN, (strategy, recall)
    else:
        assert recall == 1.0, (strategy, recall)


@contextlib.contextmanager
def _routed(corpus, monkeypatch, strategy):
    """A loaded datastore on which the next kNN statements are served by
    `strategy`, and `before`: the strategy counters once it is prepared."""
    ann_min, ondevice, prepare = ROUTES[strategy]
    monkeypatch.setattr(cnf, "TPU_ANN_MIN_ROWS", ann_min)
    monkeypatch.setattr(cnf, "TPU_KNN_ONDEVICE_THRESHOLD", ondevice)
    monkeypatch.setattr(cnf, "TPU_DISABLE", prepare == "disable")
    monkeypatch.setattr(cnf, "COLUMN_MIRROR_MIN_ROWS", 4)
    ds, s = _load(corpus)
    release = threading.Event()
    try:
        if prepare == "one-chip":
            ds.mesh = lambda: None
        if prepare == "hold-training":
            # the quantizer "still training in the background", for as long
            # as the case asks: the first query kicks the task, the task
            # waits here, every query is served exactly meanwhile
            from surrealdb_tpu.idx.ivf import IvfState

            train = IvfState.train

            def held(*a, **kw):
                release.wait(60)
                return train(*a, **kw)

            monkeypatch.setattr(IvfState, "train", staticmethod(held))
        if prepare in ("train", "train-then-disable"):
            # the first query builds the mirror and kicks the training
            _ask(ds, s, SQL, corpus["qs"][0])
            mirror = ds.index_stores.get("test", "test", "item", "iv")
            assert mirror.wait_ivf(60), "background IVF training did not finish"
            if prepare == "train-then-disable":
                monkeypatch.setattr(cnf, "TPU_DISABLE", True)
        yield ds, s, _strategy_counts()
    finally:
        release.set()
        bg.wait_idle(60, owner=id(ds))
        ds.close()


@pytest.mark.parametrize("filtered", [False, True], ids=["all", "flag"])
@pytest.mark.parametrize("strategy", list(ROUTES))
def test_strategy_answers_the_reference(corpus, monkeypatch, strategy, filtered):
    with _routed(corpus, monkeypatch, strategy) as (ds, s, before):
        applied = telemetry.get_counter("knn_prefilter", outcome="applied")
        sql = SQL_FLAG if filtered else SQL
        answers = [_ask(ds, s, sql, q) for q in corpus["qs"]]
        served = FILTERED.get(strategy, strategy) if filtered else strategy
        _check(corpus, served, filtered, answers, before)
        if filtered:
            # top-k among the matching rows, not a post-filter: every
            # strategy consumed the columnar mask
            got = telemetry.get_counter("knn_prefilter", outcome="applied") - applied
            assert got == len(answers), (strategy, got)


@pytest.mark.parametrize("strategy", ["ivf-sharded", "ivf", "exact-device", "ivf-host"])
def test_concurrent_sessions_are_answered_as_one_is(corpus, monkeypatch, strategy):
    """Eight sessions ask the filtered statement at once, each the six
    queries in an order of its own: no statement fails, and every answer
    is the one the same query gets alone, whichever riders shared its
    dispatch (`ivf-host` has none: it runs on the asking thread)."""
    with _routed(corpus, monkeypatch, strategy) as (ds, s, before):
        alone = [_ask(ds, s, SQL_FLAG, q) for q in corpus["qs"]]
        sessions = 8
        got, errors = {}, []
        barrier = threading.Barrier(sessions)

        def client(i):
            barrier.wait()
            for j in range(QUERIES):
                qi = (i + j) % QUERIES
                try:
                    got[i, qi] = _ask(ds, s, SQL_FLAG, corpus["qs"][qi])
                except BaseException as e:  # noqa: BLE001 — reported below
                    errors.append(e)

        ts = [threading.Thread(target=client, args=(i,)) for i in range(sessions)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errors, errors[:1]
        assert len(got) == sessions * QUERIES
        for (i, qi), (ids, dists) in got.items():
            assert ids == alone[qi][0], (strategy, i, qi)
            # a wider tile sums in another order: float32 noise, no more
            np.testing.assert_allclose(dists, alone[qi][1], rtol=1e-4, atol=1e-3)
        assert _rose(before) == {FILTERED.get(strategy, strategy): len(alone) + len(got)}


def test_exact_overlay_answers_the_reference(corpus, monkeypatch):
    """A transaction with uncommitted writes to the index searches the
    mirror merged with its own rows, exactly, whatever the thresholds say;
    its residual WHERE is applied afterwards by design, so it is asked
    without one."""
    monkeypatch.setattr(cnf, "TPU_ANN_MIN_ROWS", 64)
    ds, s = _load(corpus)
    try:
        _ask(ds, s, SQL, corpus["qs"][0])  # builds the mirror
        bg.wait_idle(60, owner=id(ds))
        before = _strategy_counts()
        answers = []
        for q in corpus["qs"]:
            # the pending row sits on the query: it is the nearest, and the
            # committed top-10's first nine follow it
            out = ds.execute(
                "BEGIN; CREATE item:99999 SET emb = $q, flag = false; " + SQL + "; COMMIT; "
                "DELETE item:99999;",
                s, vars={"q": q.tolist()},
            )
            rows = out[-2]["result"]
            assert out[-2]["status"] == "OK" and len(rows) == K, out[-2]
            assert int(rows[0]["id"].id) == 99999 and float(rows[0]["d"]) < 0.05
            answers.append((
                [int(r["id"].id) for r in rows[1:]], [float(r["d"]) for r in rows[1:]],
            ))
        assert _rose(before) == {"exact-overlay": len(answers)}
        for qi, (ids, dists) in enumerate(answers):
            assert ids == corpus["top"][False][qi][: K - 1].tolist(), (qi, ids)
            np.testing.assert_allclose(
                np.square(dists), corpus["d2"][qi, ids], rtol=1e-4, atol=1e-4)
    finally:
        bg.wait_idle(60, owner=id(ds))
        ds.close()
