"""A selective WHERE beside `<|k,ef|>` (ISSUE 33): the statement returns the
k nearest among the rows whose committed fields pass, never a row that
fails, never fewer than k while k pass. Held here against a NumPy reference
of the test's own (mask, float64 distances, argsort) over five passing
shares and two metrics on the three IVF strategies; the one rule that
chooses between scoring the passing rows exactly (`subset`) and probing more
lists with the mask (`widened`); the slot filter's cache (made once a bound
value, dropped oldest first, never stale after an acknowledged write); the
spans and counters that say which route served; and riders under one mask
sharing a dispatch."""

import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import bg, cnf, compile_log, telemetry, tracing
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.idx import ivf as ivf_mod
from surrealdb_tpu.idx.ivf import default_nprobe, filtered_route, subset_size
from surrealdb_tpu.kvs.ds import Datastore
from test_graph_count_lanes import HeldQueue

NS, DB = "t", "t"
N, DIM, K, EF, QUERIES = 8192, 32, 10, 64, 6
NEVER = 1 << 60
SQL = "SELECT id, vector::distance::knn() AS d FROM item WHERE emb <|10,64|> $q.v"
SQL_N = SQL + " AND n >= $q.lo"
SHARES = {"0.1pct": 8, "1pct": 82, "10pct": 819, "50pct": 4096, "99pct": 8110}
# (TPU_KNN_ONDEVICE_THRESHOLD, TPU_DISABLE): tests/test_knn_strategies.py's routes
FAMILIES = {"ivf": (NEVER, False), "ivf-sharded": (1, False), "ivf-host": (NEVER, True)}
SUBSET = {"ivf": "exact-subset", "ivf-sharded": "exact-subset-sharded", "ivf-host": "exact-subset-host"}


def corpus(metric: str):
    """Clustered rows (so the lists mean something), `n` = the row's id,
    queries near corpus rows."""
    rng = np.random.default_rng(41 if metric == "euclidean" else 43)
    centres = rng.standard_normal((96, DIM)).astype(np.float32) * 3.0
    vecs = (centres[rng.integers(0, 96, N)] + 0.5 * rng.standard_normal((N, DIM))).astype(np.float32)
    qs = (vecs[rng.choice(N, QUERIES, replace=False)] + 0.05 * rng.standard_normal((QUERIES, DIM))).astype(np.float32)
    return vecs, qs


def nearest(vecs, q, passing, metric: str, k: int = K):
    """The plain reference: (ids, distances) of the `k` nearest rows of the
    mask, float64, nearest first."""
    x, q = vecs.astype(np.float64), q.astype(np.float64)
    if metric == "euclidean":
        d = np.sqrt(((x - q[None, :]) ** 2).sum(axis=1))
    else:
        d = 1.0 - (x @ q) / (np.linalg.norm(x, axis=1) * np.linalg.norm(q))
    d = np.where(passing, d, np.inf)
    order = np.argsort(d, kind="stable")[: min(k, int(passing.sum()))]
    return order.tolist(), d[order]


def load(metric: str):
    """(ds, session, rows, queries): the table loaded, both mirrors built,
    the quantizer trained."""
    vecs, qs = corpus(metric)
    ds, s = Datastore("memory"), Session.owner(NS, DB)
    ds.execute(
        "DEFINE TABLE item SCHEMALESS; "
        f"DEFINE INDEX iv ON item FIELDS emb HNSW DIMENSION {DIM} DIST {metric.upper()} EFC 64;", s)
    for lo in range(0, N, 4096):
        out = ds.execute("INSERT INTO item $rows RETURN NONE", s, vars={"rows": [
            {"id": i, "emb": vecs[i].tolist(), "n": i} for i in range(lo, lo + 4096)]})
        assert out[-1]["status"] == "OK", out[-1]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cnf, "TPU_KNN_ONDEVICE_THRESHOLD", NEVER)
        mp.setattr(cnf, "TPU_DISABLE", False)  # whatever family the first test to load is of
        ask(ds, s, SQL, {"v": qs[0].tolist()})
        assert ds.index_stores.get(NS, DB, "item", "iv").wait_ivf(120)
        bg.wait_idle(120, owner=id(ds))
    return ds, s, vecs, qs


@pytest.fixture(scope="module")
def tables():
    made = {}

    def get(metric):
        if metric not in made:
            made[metric] = load(metric)
            telemetry.reset()  # the load's own statements are not the test's
            tracing.store_reset()
        return made[metric]

    yield get
    for ds, *_ in made.values():
        bg.wait_idle(60, owner=id(ds))
        ds.close()


@pytest.fixture
def routed(monkeypatch, request):
    """The knobs that send a statement to the test's strategy family (a
    parameter `family`, else single-device `ivf`); counters and traces
    start from nothing."""
    callspec = getattr(request.node, "callspec", None)
    family = callspec.params.get("family", "ivf") if callspec else "ivf"
    ondevice, disable = FAMILIES[family]
    monkeypatch.setattr(cnf, "TPU_KNN_ONDEVICE_THRESHOLD", ondevice)
    monkeypatch.setattr(cnf, "TPU_DISABLE", disable)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    # a write is followed by a rebuilt column mirror, not by the stale window
    monkeypatch.setattr(cnf, "COLUMN_REBUILD_DEBOUNCE_SECS", 0.0)
    telemetry.reset()
    tracing.store_reset()
    yield family
    tracing.store_reset()


def ask(ds, s, sql, q, tid=None):
    """(ids, distances, the statement's spans by name -> labels)"""
    if tid is None:
        out, spans = ds.execute(sql, s, vars={"q": q}), []
    else:
        with tracing.request("knn", trace_id=tid):
            out = ds.execute(sql, s, vars={"q": q})
        spans = tracing.get_trace(tid)["spans"]
    assert out[-1]["status"] == "OK", out[-1]
    rows = out[-1]["result"]
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp["labels"])
    return [int(r["id"].id) for r in rows], [float(r["d"]) for r in rows], by_name


def counted(name: str, label: str) -> dict:
    return {dict(k)[label]: int(v) for k, v in telemetry.counters_matching(name).items()}


def expected_route(ds, passing: int) -> str:
    """The rule, by the test's own arithmetic: the smaller row count wins."""
    state = ds.index_stores.get(NS, DB, "item", "iv").ivf
    pad = 1 << (max(len(l) for l in state.lists) - 1).bit_length()
    nprobe = default_nprobe(state.nlists, EF)
    subset_rows = max(1 << max(passing - 1, 0).bit_length(), 1024)
    probes = -(-nprobe * N // max(passing, 1))
    widened_rows = min(state.nlists, 1 << (probes - 1).bit_length()) * pad
    return "subset" if subset_rows <= widened_rows else "widened"


# ------------------------------------------------------------------ the answer
@pytest.mark.parametrize("metric", ["euclidean", "cosine"])
@pytest.mark.parametrize("share", list(SHARES))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_filtered_search_is_the_nearest_among_the_rows_that_pass(tables, routed, family, share, metric):
    ds, s, vecs, qs = tables(metric)
    passing_rows = SHARES[share]
    lo = N - passing_rows
    passing = np.arange(N) >= lo
    route = expected_route(ds, passing_rows)
    assert route == {"0.1pct": "subset", "1pct": "subset", "10pct": "subset",
                     "50pct": "widened", "99pct": "widened"}[share]
    hits = 0
    for qi, q in enumerate(qs):
        ids, dists, spans = ask(ds, s, SQL_N, {"v": q.tolist(), "lo": lo}, f"{family}-{share}-{metric}-{qi}")
        want, want_d = nearest(vecs, q, passing, metric)
        # never a row that fails, k rows whenever k pass, the P rows when P < k
        assert all(i >= lo for i in ids), (ids, lo)
        assert len(ids) == len(set(ids)) == min(K, passing_rows)
        assert dists == sorted(dists)
        hits += len(set(ids) & set(want))
        if route == "subset":  # exact: every passing row was scored
            if metric == "euclidean":
                assert ids == want
            else:  # 1 - cos in float32 may swap two rows a few ulps of 1.0 apart
                ref_all = dict(zip(*nearest(vecs, q, passing, metric, k=N)))
                np.testing.assert_allclose([ref_all[i] for i in ids], want_d, rtol=0, atol=2e-6)
        # the distance a row is served with is that row's distance
        ref = {i: d for i, d in zip(*nearest(vecs, q, passing, metric, k=N))}
        np.testing.assert_allclose(dists, [ref[i] for i in ids], rtol=2e-3, atol=2e-3)
        if family != "ivf-host":  # the host strategies submit nothing, so prepare nothing
            assert spans["knn_prepare"] == [{"filter": route}]
        assert [f["rows"] for f in spans["knn_filter"]] == [str(passing_rows)]
    assert hits / (min(K, passing_rows) * len(qs)) >= 0.95
    served = SUBSET[family] if route == "subset" else family
    assert counted("knn_strategy", "strategy") == {served: len(qs)}
    assert counted("knn_filter_route", "route") == {route: len(qs)}


def test_a_filter_nothing_passes_answers_nothing(tables, routed):
    ds, s, vecs, qs = tables("euclidean")
    before = ds.dispatch.stats()["submitted"]
    ids, _, spans = ask(ds, s, SQL_N, {"v": qs[0].tolist(), "lo": N}, "nothing")
    assert ids == [] and spans["knn_filter"][0]["rows"] == "0"
    assert ds.dispatch.stats()["submitted"] == before
    assert counted("knn_filter_route", "route") == {"subset": 1}


# ------------------------------------------------------------------ the rule
@pytest.mark.parametrize("passing,alive,nlists,nprobe,pad,want", [
    (5_000, 500_000, 1_100, 6, 2_048, ("subset", 8_192)),       # the cell: 8,192 slots against 1,024 lists
    (990_000, 1_000_000, 2_059, 6, 2_048, ("widened", 8)),      # VectorDBBench's "filter 1%": 99% pass, 7 probes -> 8
    (250_000, 500_000, 1_100, 6, 2_048, ("widened", 16)),       # half pass: twice the probes, 12 -> 16
    (100_000, 500_000, 1_100, 6, 1_024, ("widened", 32)),       # a fifth pass: 30 -> 32
    (4, 10_000, 128, 6, 128, ("subset", 1_024)),                # fewer than k pass: the floor
    (0, 10_000, 128, 6, 128, ("subset", 1_024)),                # nothing passes
    (1_024, 3_072, 64, 6, 32, ("subset", 1_024)),               # 1,024 slots against 32 x 32 = 1,024: a tie goes to the exact search
    (1_024, 3_072, 64, 6, 16, ("widened", 32)),                 # ... and against 32 x 16 = 512 (18 probes -> 32)
    (2_048, 8_192, 128, 4, 128, ("subset", 2_048)),             # a tie again: 16 x 128
    (2_049, 8_192, 128, 4, 128, ("widened", 16)),               # one row more pads to 4,096: 16 x 128 = 2,048 wins
    (100, 100_000, 64, 6, 2_048, ("subset", 1_024)),            # the probes stop at the list count
    (3_000, 9_000, 24, 6, 64, ("widened", 24)),                 # ... which need be no power of two: 18 -> 32 -> 24 lists
])
def test_the_smaller_row_count_wins(passing, alive, nlists, nprobe, pad, want):
    assert filtered_route(passing, alive, nlists, nprobe, pad) == want
    how, arg = want
    slots = subset_size(passing)
    probes = -(-nprobe * alive // max(passing, 1))
    probes = min(nlists, 1 << (probes - 1).bit_length())
    assert (slots <= probes * pad) == (how == "subset") and arg == (slots if how == "subset" else probes)


def test_a_threshold_that_varies_meets_few_probe_counts():
    """The widened probe count is a compiled shape: over every passing
    count of the cell's corpus the rule names powers of two up to the list
    count and nothing else."""
    seen = {filtered_route(p, 500_000, 1_100, 6, 1_024) for p in range(1_000, 500_001, 997)}
    widened = sorted(arg for how, arg in seen if how == "widened")
    assert widened == [8, 16, 32, 64] and all(how in ("subset", "widened") for how, _ in seen)


def test_without_a_filter_the_search_is_the_bare_one(tables, routed, monkeypatch):
    """The unfiltered statement: the parent's dispatch key (no filter's
    name in it), the parent's program (`_ivf_search` with an all-true
    `slot_ok`), no `knn_filter` span, `filter=none`."""
    ds, s, vecs, qs = tables("euclidean")
    keys, oks = [], []
    submit, search = ds.dispatch.submit, ivf_mod._ivf_search

    def spy_submit(key, payload, runner):
        keys.append(key)
        return submit(key, payload, runner)

    def spy_search(q, cents, rows, mask, x, slot_ok, **kw):
        oks.append(np.asarray(slot_ok))
        return search(q, cents, rows, mask, x, slot_ok, **kw)

    monkeypatch.setattr(ds.dispatch, "submit", spy_submit)
    monkeypatch.setattr(ivf_mod, "_ivf_search", spy_search)
    ids, _, spans = ask(ds, s, SQL, {"v": qs[1].tolist()}, "bare")
    mirror = ds.index_stores.get(NS, DB, "item", "iv")
    matrix, _, _ = mirror.device_snapshot()
    assert keys == [("knn-ivf", id(matrix), id(mirror.ivf), "euclidean", K, default_nprobe(mirror.ivf.nlists, EF))]
    # the statement's launch, and the other tiles a first bare search warms behind it
    assert oks and all(ok.all() and ok.shape == (matrix.shape[0],) for ok in oks)
    assert spans["knn_prepare"] == [{"filter": "none"}] and "knn_filter" not in spans
    assert set(ids) == set(nearest(vecs, qs[1], np.ones(N, dtype=bool), "euclidean")[0])
    assert counted("knn_strategy", "strategy") == {"ivf": 1} and counted("knn_filter_route", "route") == {"none": 1}
    assert all(k[0][0] != SQL for k in mirror._filters._d)


# ------------------------------------------------------------------ the cache
def spy_keys(ds, monkeypatch) -> list:
    keys, submit = [], ds.dispatch.submit

    def spy(key, payload, runner):
        keys.append(key)
        return submit(key, payload, runner)

    monkeypatch.setattr(ds.dispatch, "submit", spy)
    return keys


@pytest.mark.parametrize("share", ["1pct", "50pct"])
def test_the_slot_filter_is_made_once_a_bound_value(tables, routed, monkeypatch, share):
    ds, s, vecs, qs = tables("euclidean")
    mirror = ds.index_stores.get(NS, DB, "item", "iv")
    with mirror._lock:
        mirror._filters.forget(lambda k, e: True)
    keys = spy_keys(ds, monkeypatch)
    lo = N - SHARES[share]
    outcomes = []
    for i, bound in enumerate([lo, lo, lo + 1, lo, lo + 1]):
        ids, _, spans = ask(ds, s, SQL_N, {"v": qs[i].tolist(), "lo": bound}, f"cache-{share}-{i}")
        assert ids == nearest(vecs, qs[i], np.arange(N) >= bound, "euclidean")[0] or share == "50pct"
        outcomes.append(spans["knn_filter"][0]["outcome"])
        # a build is timed under its own names, a look-up makes and uploads nothing
        assert ("knn_filter_build" in spans, "knn_filter_upload" in spans) == ((outcomes[-1] == "build"),) * 2
    # the first sight of a value makes its filter, every later statement finds it
    assert outcomes == ["build", "hit", "build", "hit", "hit"]
    assert len(mirror._filters._d) == 2
    # riders of one bound value share a dispatch key, another value's never do
    assert keys[0] == keys[1] == keys[3] and keys[2] == keys[4] and keys[0] != keys[2]
    assert counted("knn_prefilter", "outcome") == {"applied": 5}


def test_the_byte_budget_drops_the_oldest_filter(tables, routed):
    ds, s, vecs, qs = tables("euclidean")
    mirror = ds.index_stores.get(NS, DB, "item", "iv")
    with mirror._lock:
        mirror._filters.forget(lambda k, e: True)
    budget = mirror._filters.budget
    try:
        for i, lo in enumerate([N - 82, N - 83, N - 84]):
            ask(ds, s, SQL_N, {"v": qs[0].tolist(), "lo": lo})
            if i == 0:
                (first,) = [e for e, _ in mirror._filters._d.values()]
                mirror._filters.budget = 2 * first.nbytes() + 64  # room for two
        held = [e.rows for e, _ in mirror._filters._d.values()]
        assert held == [83, 84]  # the first went, oldest first
        # and is made again when asked for, pushing the next oldest out
        _, _, spans = ask(ds, s, SQL_N, {"v": qs[0].tolist(), "lo": N - 82}, "again")
        assert spans["knn_filter"] == [{"outcome": "build", "rows": "82"}]
        assert [e.rows for e, _ in mirror._filters._d.values()] == [84, 82]
    finally:
        mirror._filters.budget = budget


def test_an_acknowledged_update_is_seen_by_the_next_search(routed):
    ds, s, vecs, qs = load("euclidean")
    try:
        lo = N - 82
        passing = np.arange(N) >= lo
        q = {"v": qs[2].tolist(), "lo": lo}
        ids, _, spans = ask(ds, s, SQL_N, q, "before")
        assert ids == nearest(vecs, qs[2], passing, "euclidean")[0]
        # the nearest passing row leaves the slice, the nearest row of all joins it
        gone, joins = ids[0], nearest(vecs, qs[2], ~passing, "euclidean")[0][0]
        out = ds.execute(f"UPDATE item:{gone} SET n = -1; UPDATE item:{joins} SET n = {N + 5};", s)
        assert all(r["status"] == "OK" for r in out), out
        passing[gone], passing[joins] = False, True
        ids, _, spans = ask(ds, s, SQL_N, q, "after")
        assert ids == nearest(vecs, qs[2], passing, "euclidean")[0] and ids[0] == joins and gone not in ids
        # the same bound values, a new column mirror: made again, never served stale
        assert spans["knn_filter"] == [{"outcome": "build", "rows": "82"}]
        # and what the old column mirror's filter held went with it
        (kept,) = [e for e, _ in ds.index_stores.get(NS, DB, "item", "iv")._filters._d.values()]
        assert kept.col is ds.column_mirrors.get((NS, DB, "item"))
        assert ask(ds, s, SQL_N, q, "third")[2]["knn_filter"] == [{"outcome": "hit", "rows": "82"}]
    finally:
        bg.wait_idle(60, owner=id(ds))
        ds.close()


@pytest.mark.parametrize("share", ["1pct", "50pct"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_slot_a_delete_or_an_unset_left_behind_is_never_served(routed, monkeypatch, family, share):
    """A DELETE or an UNSET of the vector tombstones the record's slot and
    leaves its rid there until compaction; a re-CREATE takes a new slot.
    Both slots name one passing record: the filter counts and serves the
    live one only, at the distance of the vector it has now."""
    ds, s, vecs, qs = load("euclidean")
    monkeypatch.setattr(cnf, "TPU_ANN_MIN_ROWS", N - 8)  # a row fewer is still the quantizer's
    try:
        vecs = vecs.copy()
        lo = N - SHARES[share]
        passing = np.arange(N) >= lo
        q = {"v": qs[2].tolist(), "lo": lo}
        served = SUBSET[family] if share == "1pct" else family

        def check(tid, rows):
            ids, dists, spans = ask(ds, s, SQL_N, q, tid)
            want, _ = nearest(vecs, qs[2], passing, "euclidean")
            assert spans["knn_search"][0]["strategy"] == served
            assert spans["knn_filter"] == [{"outcome": "build", "rows": str(rows)}]
            assert len(ids) == len(set(ids)) == K and all(passing[i] for i in ids)
            ref = dict(zip(*nearest(vecs, qs[2], passing, "euclidean", k=len(vecs))))
            np.testing.assert_allclose(dists, [ref[i] for i in ids], rtol=2e-3, atol=2e-3)
            if share == "1pct":
                assert ids == want
            return ids

        before = check("before", SHARES[share])
        # the nearest passing record goes and comes back where the fourth was
        back = before[0]
        vecs[back] = vecs[before[3]] + np.float32(0.01)
        out = ds.execute(
            f"DELETE item:{back}; CREATE item:{back} SET emb = $v, n = {back};", s,
            vars={"v": vecs[back].tolist()})
        assert all(r["status"] == "OK" for r in out), out
        mirror = ds.index_stores.get(NS, DB, "item", "iv")
        assert mirror.n_slots == N + 1 and mirror.count() == N  # the old slot is still there, dead
        after = check("recreated", SHARES[share])
        assert back in after and after[0] != back
        # the nearest passing record now loses its vector: its row still passes
        bare = after[0]
        assert ds.execute(f"UPDATE item:{bare} UNSET emb", s)[-1]["status"] == "OK"
        passing[bare] = False  # to the reference: nothing to measure a distance to
        assert mirror.n_slots == N + 1 and mirror.count() == N - 1
        assert bare not in check("unset", SHARES[share] - 1)
        # and a row a bulk INSERT appends (the column mirror takes it as a delta,
        # the vector mirror as a new slot of the same list) is in the next answer
        vecs, passing = np.vstack([vecs, qs[2][None, :] + np.float32(0.05)]), np.append(passing, True)
        out = ds.execute("INSERT INTO item $rows RETURN NONE", s, vars={"rows": [
            {"id": N, "emb": vecs[N].tolist(), "n": N}]})
        assert out[-1]["status"] == "OK", out[-1]
        assert check("appended", SHARES[share])[0] == N
    finally:
        bg.wait_idle(60, owner=id(ds))
        ds.close()


def test_while_the_column_mirror_is_stale_the_filter_is_applied_afterwards(routed, monkeypatch):
    """Inside the rebuild debounce after a write the column mirror serves
    nobody: the search runs unfiltered and the executor filters its top-k
    (today's behaviour: right rows, maybe fewer than k), and the span says
    so."""
    ds, s, vecs, qs = load("euclidean")
    try:
        lo = N // 2
        q = {"v": qs[3].tolist(), "lo": lo}
        ask(ds, s, SQL_N, q)
        monkeypatch.setattr(cnf, "COLUMN_REBUILD_DEBOUNCE_SECS", 3600.0)
        assert ds.execute("UPDATE item:0 SET n = 0", s)[-1]["status"] == "OK"
        ids, _, spans = ask(ds, s, SQL_N, q, "stale")
        assert spans["knn_prepare"] == [{"filter": "post"}] and "knn_filter" not in spans
        assert all(i >= lo for i in ids) and len(ids) <= K
        assert counted("knn_filter_route", "route")["post"] == 1
        assert counted("knn_prefilter", "outcome")["unavailable"] == 1
    finally:
        bg.wait_idle(60, owner=id(ds))
        ds.close()


# ------------------------------------------------------------------ spans, counters, programs
def test_the_subset_search_is_one_program_of_its_own(tables, routed):
    """One dispatch a statement, a compile-log subsystem of its own, and no
    compile for a second bound value that pads to the same size."""
    ds, s, vecs, qs = tables("euclidean")
    ask(ds, s, SQL_N, {"v": qs[0].tolist(), "lo": N - 700})
    bg.wait_idle(120, owner=id(ds))  # the other tiles warm behind the first
    t_asked = time.time()
    before = ds.dispatch.stats()["submitted"]
    for i, lo in enumerate([N - 600, N - 900, N - 1000]):  # all pad to 1,024 slots
        ids, _, spans = ask(ds, s, SQL_N, {"v": qs[i].tolist(), "lo": lo}, f"program-{i}")
        assert ids == nearest(vecs, qs[i], np.arange(N) >= lo, "euclidean")[0]
        assert spans["knn_prepare"] == [{"filter": "subset"}]
        assert spans["knn_search"][0]["strategy"] == "exact-subset"
        assert [d["batch"] for d in spans["dispatch_launch"]] == ["1"]
    assert ds.dispatch.stats()["submitted"] - before == 3
    assert compile_log.events(since=t_asked) == []
    from surrealdb_tpu.ops.distances import knn_subset_search

    assert knn_subset_search.__name__ == "knn_subset_search"  # the trace's `jit_knn_subset_search`
    assert "knn_subset" in compile_log.KERNEL_SITES and "knn_subset_sharded" in compile_log.KERNEL_SITES


# ------------------------------------------------------------------ batches
@pytest.mark.parametrize("share", ["1pct", "50pct"])
def test_eight_sessions_under_one_mask_share_a_dispatch(tables, routed, monkeypatch, share):
    """The first statement holds the bucket; eight more with the same bound
    threshold queue behind it and ride ONE dispatch, a ninth with another
    threshold rides its own; each answers as it does alone."""
    ds, s, vecs, qs = tables("euclidean")
    lo = N - SHARES[share]
    requests = [(qs[i % QUERIES], lo) for i in range(9)] + [(qs[0], lo + 1)]
    alone = [ask(ds, s, SQL_N, {"v": q.tolist(), "lo": b})[:2] for q, b in requests]
    bg.wait_idle(120, owner=id(ds))
    held, got = HeldQueue(), {}
    monkeypatch.setattr(ds, "dispatch", held)

    def rider(i):
        q, b = requests[i]
        got[i] = ask(ds, s, SQL_N, {"v": q.tolist(), "lo": b}, f"rider-{share}-{i}")

    threads = [threading.Thread(target=rider, args=(i,)) for i in range(len(requests))]
    threads[0].start()
    assert held.started.wait(60)
    for t in threads[1:]:
        t.start()
    deadline = time.monotonic() + 60
    while held.queued() < 8 and time.monotonic() < deadline:  # the tenth has a bucket of its own
        time.sleep(0.002)
    held.release.set()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads) and len(got) == len(requests)
    for i, (ids, dists) in enumerate(alone):
        assert got[i][0] == ids
        np.testing.assert_allclose(got[i][1], dists, rtol=1e-4, atol=1e-3)  # a wider tile sums in another order
    assert held.width_distribution() == {1: 2, 8: 1}
    batches = [got[i][2]["dispatch_launch"][0]["batch"] for i in range(len(requests))]
    assert batches == ["1"] + ["8"] * 8 + ["1"]
    assert len(held._buckets) == 2  # one a mask
