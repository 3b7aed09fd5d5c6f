"""Columnar scan path: vectorized WHERE over the column mirror.

Covers the ISSUE 4 acceptance bars:
  - property test: columnar-path results == row-path results over
    randomized predicates AND randomized data including NONE/missing
    fields, NULLs, and type-mixed columns (ints/floats/bools/strings/
    lists/nested objects in the SAME field);
  - staleness is impossible: an uncommitted-txn write and a post-build
    commit never serve stale mask results;
  - unlowerable predicates fall back per-row with identical output;
  - scan_range boundary semantics (inclusive/exclusive begin/end with the
    `\\x00` key suffixing);
  - the kNN residual prefilter (exact strategies return k matching rows);
  - the count() GROUP ALL popcount fast path;
  - INFO FOR ROOT carries the slow-query ring + trace store.
"""

import random

import pytest

from surrealdb_tpu import cnf
from surrealdb_tpu.sql.value import Thing


@pytest.fixture(autouse=True)
def _small_mirror_floor():
    saved = cnf.COLUMN_MIRROR_MIN_ROWS, cnf.COLUMN_MIRROR, cnf.COLUMN_REBUILD_DEBOUNCE_SECS
    cnf.COLUMN_MIRROR_MIN_ROWS = 4
    cnf.COLUMN_MIRROR = True
    cnf.COLUMN_REBUILD_DEBOUNCE_SECS = 0.05
    yield
    (
        cnf.COLUMN_MIRROR_MIN_ROWS,
        cnf.COLUMN_MIRROR,
        cnf.COLUMN_REBUILD_DEBOUNCE_SECS,
    ) = saved


def ok(r):
    assert r["status"] == "OK", r
    return r["result"]


def both_paths(ds, sql, vars=None):
    """(columnar result, row-path result) for one statement."""
    cnf.COLUMN_MIRROR = True
    col = ok(ds.execute(sql, vars=vars)[-1])
    cnf.COLUMN_MIRROR = False
    row = ok(ds.execute(sql, vars=vars)[-1])
    cnf.COLUMN_MIRROR = True
    return col, row


# ------------------------------------------------------------------ property
def _random_rows(rng: random.Random, n: int):
    rows = []
    for i in range(n):
        r = {"id": i}
        roll = rng.random()
        if roll < 0.55:
            r["a"] = rng.choice([0, 1, 2, 3, 5, -7, 2.5, -0.0, 1e18])
        elif roll < 0.65:
            r["a"] = rng.choice(["x", "yy", "", "Zed"])
        elif roll < 0.72:
            r["a"] = rng.choice([True, False])
        elif roll < 0.78:
            r["a"] = None  # NULL
        elif roll < 0.84:
            pass  # missing -> NONE
        elif roll < 0.92:
            r["a"] = [rng.randint(0, 3), rng.randint(0, 3)]  # type-mixed
        else:
            r["a"] = {"y": rng.randint(0, 5)}
        if rng.random() < 0.8:
            r["b"] = rng.choice(["alpha", "beta", "gamma", "", "delta"])
        if rng.random() < 0.7:
            r["flag"] = rng.random() < 0.5
        if rng.random() < 0.5:
            r["nest"] = {"x": rng.randint(0, 9), "s": rng.choice(["p", "q"])}
        elif rng.random() < 0.2:
            r["nest"] = rng.choice([3, "str", [1, 2]])
        rows.append(r)
    return rows


_PREDICATES = [
    "a = 2",
    "a != 2",
    "a < 2",
    "a <= 2",
    "a > 2",
    "a >= 2",
    "a = 2.5",
    "a < 'y'",
    "a = 'x'",
    "a = true",
    "a = NONE",
    "a != NONE",
    "a = NULL",
    "a IN [1, 2, 'x']",
    "a NOT IN [0, 'yy']",
    "flag",
    "!flag",
    "flag = true AND a > 1",
    "a = 2 OR b = 'beta'",
    "!(a > 2) AND b != 'alpha'",
    "nest.x >= 5",
    "nest.x < 4 OR nest.s = 'p'",
    "b >= 'b' AND b <= 'g'",
    "a >= -1 AND a < 3 AND flag = false",
]


def test_columnar_equals_row_path_randomized(ds):
    rng = random.Random(1234)
    rows = _random_rows(rng, 400)
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": rows})[-1])
    for pred in _PREDICATES:
        sql = f"SELECT VALUE id FROM t WHERE {pred}"
        col, row = both_paths(ds, sql)
        # same rows, same ORDER (both paths stream in key-scan order)
        assert [str(x) for x in col] == [str(x) for x in row], pred
    from surrealdb_tpu import telemetry

    assert telemetry.get_counter("scan_strategy", strategy="columnar") > 0


def test_unlowerable_predicates_identical(ds):
    rows = _random_rows(random.Random(7), 120)
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": rows})[-1])
    for pred in (
        "b CONTAINS 'a'",  # containment operator
        "a = [1, 2]",  # array constant
        "id >= t:60",  # record-id constant
        "nest.x.y = 1",  # beyond materialized depth
    ):
        sql = f"SELECT VALUE id FROM t WHERE {pred}"
        col, row = both_paths(ds, sql)
        assert [str(x) for x in col] == [str(x) for x in row], pred


def test_projection_and_aggregates_identical(ds):
    rows = _random_rows(random.Random(99), 200)
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": rows})[-1])
    for sql in (
        "SELECT id, b FROM t WHERE a > 1 ORDER BY b LIMIT 7",
        "SELECT b, count() FROM t WHERE flag = true GROUP BY b",
        "SELECT count() FROM t WHERE a >= 0 GROUP ALL",
        "SELECT count() FROM t WHERE a = 'no-such-value-anywhere' GROUP ALL",
        "SELECT VALUE id FROM t WHERE a > 0 LIMIT 3 START 2",
    ):
        col, row = both_paths(ds, sql)
        assert col == row, sql


# ------------------------------------------------------------------ staleness
def test_own_txn_writes_never_stale(ds):
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": i} for i in range(50)]})[-1])
    ok(ds.execute("SELECT id FROM t WHERE a < 5")[-1])  # builds the mirror
    out = ds.execute("BEGIN; CREATE t:900 SET a = 2; SELECT VALUE id FROM t WHERE a = 2; COMMIT;")
    assert sorted(str(x) for x in ok(out[-1])) == ["t:2", "t:900"]


def test_post_build_commit_never_stale(ds):
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": i} for i in range(50)]})[-1])
    ok(ds.execute("SELECT id FROM t WHERE a < 5")[-1])  # builds the mirror
    # immediately-following commits must be visible with NO settling time
    ds.execute("CREATE t:901 SET a = 3")
    assert sorted(str(x) for x in ok(ds.execute("SELECT VALUE id FROM t WHERE a = 3")[-1])) == ["t:3", "t:901"]
    ds.execute("DELETE t:3")
    assert [str(x) for x in ok(ds.execute("SELECT VALUE id FROM t WHERE a = 3")[-1])] == ["t:901"]
    # after the debounced rebuild settles the columnar path serves again
    assert ds.column_mirrors.wait_rebuild(timeout=10)
    from surrealdb_tpu import telemetry

    before = telemetry.get_counter("scan_strategy", strategy="columnar")
    assert [str(x) for x in ok(ds.execute("SELECT VALUE id FROM t WHERE a = 3")[-1])] == ["t:901"]
    assert telemetry.get_counter("scan_strategy", strategy="columnar") == before + 1


@pytest.mark.parametrize("writes_to", ["other", "t"])
def test_a_commit_between_a_reader_s_snapshot_and_the_build_it_triggers(ds, monkeypatch, writes_to):
    """The reader that triggers a build opened its snapshot before the
    build's. A commit to ANOTHER table in between changes nothing the
    mirror holds, so the reader is served from it (it paid for the build:
    the read-back of a bulk load fell to a 50 s row scan this way in two
    benchmark runs of fourteen, PERF.md section 7); a commit to THE table
    in between is in the mirror and not in the reader's snapshot, so that
    reader takes the row path and counts what ITS snapshot holds."""
    from surrealdb_tpu import telemetry

    ds.execute("DEFINE TABLE t SCHEMALESS; DEFINE TABLE other SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": i} for i in range(40)]})[-1])
    build = ds.column_mirrors.build

    def write_then_build(store, ns, db, tb):
        ok(store.execute(f"CREATE {writes_to}:900 SET a = 900")[-1])
        return build(store, ns, db, tb)

    monkeypatch.setattr(ds.column_mirrors, "build", write_then_build)
    telemetry.reset()
    got = ok(ds.execute("SELECT count() AS c FROM t WHERE a >= 0 GROUP ALL")[-1])
    monkeypatch.undo()
    served = {dict(k)["strategy"] for k in telemetry.counters_matching("scan_strategy")}
    assert got == [{"c": 40}]  # the reader's snapshot predates row 900 either way
    assert served == ({"columnar_count"} if writes_to == "other" else {"row_fallback"})
    # the next reader's snapshot holds everything: served from the mirror that was built
    telemetry.reset()
    assert ok(ds.execute("SELECT count() AS c FROM t WHERE a >= 0 GROUP ALL")[-1]) == [
        {"c": 40 if writes_to == "other" else 41}]
    assert {dict(k)["strategy"] for k in telemetry.counters_matching("scan_strategy")} == {"columnar_count"}


def test_the_floor_of_a_build_through_the_group_commit_drain(ds, monkeypatch):
    """Three write transactions ride ONE group-commit flush (the leader
    commits each with its `column_sink`): each records its own commit beside
    the counter it bumped, so the table's record is its LAST member's, and
    a reader whose build is preceded by a drained commit to another table
    is still served from the mirror."""
    import threading
    import time as _t

    from surrealdb_tpu import cnf, telemetry
    from surrealdb_tpu.kvs.tx import Transaction

    assert cnf.GROUP_COMMIT
    ds.execute("DEFINE TABLE t SCHEMALESS; DEFINE TABLE other SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": i} for i in range(40)]})[-1])
    cm, key_t, key_o = ds.column_mirrors, ("test", "test", "t"), ("test", "test", "other")
    sinks, direct = [], Transaction.commit_direct

    def spy(self, column_sink=None):
        if self.write:
            sinks.append(column_sink)
        return direct(self, column_sink)

    monkeypatch.setattr(Transaction, "commit_direct", spy)
    telemetry.reset()
    writers = [threading.Thread(target=lambda q=q: ok(ds.execute(q)[-1])) for q in (
        "CREATE t:900 SET a = 900", "CREATE other:1 SET a = 1", "CREATE t:901 SET a = 901")]
    with ds.commit_lock:  # the flusher waits here, so all three are in its queue or its batch
        for w in writers:
            w.start()
        deadline = _t.monotonic() + 30
        while len(sinks) + len(ds.group_commit._queue) < 3 and _t.monotonic() < deadline:
            _t.sleep(0.002)
    for w in writers:
        w.join(30)
    assert len(sinks) == 3 and all(sk is not None for sk in sinks)  # every one by the leader
    store_version = ds.transaction(False)
    try:
        newest = store_version.tr.snapshot
    finally:
        store_version.cancel()
    # t's record: the counter its last commit left it at, and a commit of this drain
    counter, landed = cm.last_commit[key_t]
    assert counter == cm.versions[key_t] and newest - 2 <= landed <= newest
    assert cm.last_commit[key_o][0] == cm.versions[key_o]
    # a drained commit to `other` between a reader's snapshot and its build
    build = cm.build

    def write_then_build(store, ns, db, tb):
        ok(store.execute("CREATE other:2 SET a = 2")[-1])
        return build(store, ns, db, tb)

    monkeypatch.setattr(cm, "build", write_then_build)
    monkeypatch.setattr(cnf, "COLUMN_REBUILD_DEBOUNCE_SECS", 0.0)
    telemetry.reset()
    assert ok(ds.execute("SELECT count() AS c FROM t WHERE a >= 0 GROUP ALL")[-1]) == [{"c": 42}]
    assert len(sinks) == 4 and sinks[-1] is not None
    assert {dict(k)["strategy"] for k in telemetry.counters_matching("scan_strategy")} == {"columnar_count"}
    assert cm.get(key_t).built_store_version == landed  # the floor is t's last commit, not the snapshot


@pytest.mark.parametrize("then", ["gone", "made_again"])
def test_a_remove_table_between_a_reader_s_snapshot_and_the_build(ds, monkeypatch, then):
    """The reader's snapshot holds the 40 rows; a REMOVE TABLE lands before
    the build it triggers (and, `made_again`, a row is created after it).
    The drop bumps the table's counter and records no commit, so the build's
    floor is its own snapshot: that reader counts what ITS snapshot holds on
    the row path, never the mirror of a table it cannot see."""
    from surrealdb_tpu import cnf, telemetry

    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": i} for i in range(40)]})[-1])
    cm, key_t = ds.column_mirrors, ("test", "test", "t")
    build = cm.build

    def drop_then_build(store, ns, db, tb):
        ok(store.execute("REMOVE TABLE t")[-1])
        if then == "made_again":
            ok(store.execute("CREATE t:7 SET a = 7")[-1])
        return build(store, ns, db, tb)

    monkeypatch.setattr(cm, "build", drop_then_build)
    monkeypatch.setattr(cnf, "COLUMN_REBUILD_DEBOUNCE_SECS", 0.0)
    telemetry.reset()
    got = ok(ds.execute("SELECT count() AS c FROM t WHERE a >= 0 GROUP ALL")[-1])
    monkeypatch.setattr(cm, "build", build)
    assert got == [{"c": 40}]
    assert {dict(k)["strategy"] for k in telemetry.counters_matching("scan_strategy")} == {"row_fallback"}
    rec = cm.last_commit.get(key_t)
    if then == "gone":  # the drop recorded nothing: the counters stand apart
        assert rec is None or rec[0] != cm.versions[key_t]
    else:  # the CREATE after it is the table's last commit again
        assert rec[0] == cm.versions[key_t]
    # the next reader sees the table as it is now
    assert ok(ds.execute("SELECT count() AS c FROM t WHERE a >= 0 GROUP ALL")[-1]) == (
        [] if then == "gone" else [{"c": 1}])


def test_remove_table_never_serves_ghosts(ds):
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": 1} for i in range(20)]})[-1])
    assert len(ok(ds.execute("SELECT id FROM t WHERE a = 1")[-1])) == 20
    ds.execute("REMOVE TABLE t")
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": 1} for i in range(5)]})[-1])
    assert len(ok(ds.execute("SELECT id FROM t WHERE a = 1")[-1])) == 5


# ------------------------------------------------------------------ ranges
def test_scan_range_boundaries(ds):
    ds.execute("CREATE t:1; CREATE t:2; CREATE t:3; CREATE t:4; CREATE t:5;")

    def ids(sql):
        return [str(x) for x in ok(ds.execute(sql)[-1])]

    assert ids("SELECT VALUE id FROM t:2..4") == ["t:2", "t:3"]
    assert ids("SELECT VALUE id FROM t:2..=4") == ["t:2", "t:3", "t:4"]
    assert ids("SELECT VALUE id FROM t:2>..4") == ["t:3"]
    assert ids("SELECT VALUE id FROM t:2>..=4") == ["t:3", "t:4"]
    assert ids("SELECT VALUE id FROM t:..3") == ["t:1", "t:2"]
    assert ids("SELECT VALUE id FROM t:..=3") == ["t:1", "t:2", "t:3"]
    assert ids("SELECT VALUE id FROM t:4..") == ["t:4", "t:5"]
    assert ids("SELECT VALUE id FROM t:4>..") == ["t:5"]
    # empty and inverted ranges
    assert ids("SELECT VALUE id FROM t:3..3") == []
    assert ids("SELECT VALUE id FROM t:3..=3") == ["t:3"]
    assert ids("SELECT VALUE id FROM t:5..2") == []


def test_scan_range_string_id_prefix_boundary(ds):
    # "aab" sorts AFTER "aa" but shares its encoded prefix: the \x00
    # suffixing of an exclusive begin must skip exactly "aa", keeping "aab"
    ds.execute("CREATE s:aa; CREATE s:aab; CREATE s:ab;")

    def ids(sql):
        return [str(x) for x in ok(ds.execute(sql)[-1])]

    assert ids("SELECT VALUE id FROM s:aa>..=ab") == ["s:aab", "s:ab"]
    assert ids("SELECT VALUE id FROM s:aa..ab") == ["s:aa", "s:aab"]
    assert ids("SELECT VALUE id FROM s:aa..=aab") == ["s:aa", "s:aab"]


def test_range_scan_respects_deadline(ds):
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i} for i in range(600)]})[-1])
    out = ds.execute("SELECT * FROM t TIMEOUT 0s")[-1]
    assert out["status"] == "ERR" and "exceeded" in str(out["result"]).lower()


# ------------------------------------------------------------------ knn prefilter
def test_knn_prefilter_exact_host(ds):
    import numpy as np

    saved = cnf.TPU_DISABLE
    cnf.TPU_DISABLE = True
    try:
        ds.execute(
            "DEFINE TABLE v SCHEMALESS; "
            "DEFINE INDEX ie ON v FIELDS emb HNSW DIMENSION 4 DIST EUCLIDEAN EFC 16"
        )
        rng = np.random.default_rng(0)
        rows = [
            {"id": i, "emb": rng.standard_normal(4).tolist(), "flag": i % 4 == 0}
            for i in range(200)
        ]
        ok(ds.execute("INSERT INTO v $rows", vars={"rows": rows})[-1])
        q = {"q": rows[0]["emb"]}
        out = ok(
            ds.execute(
                "SELECT VALUE id FROM v WHERE emb <|6|> $q AND flag = true", vars=q
            )[-1]
        )
        # exact strategy + lowerable residual -> k results, ALL matching
        assert len(out) == 6
        assert all(int(str(x).split(":")[1]) % 4 == 0 for x in out)
        from surrealdb_tpu import telemetry

        assert telemetry.get_counter("knn_prefilter", outcome="applied") > 0

        # prefilter off: post-filter semantics (<= k rows, still all matching)
        cnf.KNN_COLUMN_PREFILTER = False
        try:
            out2 = ok(
                ds.execute(
                    "SELECT VALUE id FROM v WHERE emb <|6|> $q AND flag = true", vars=q
                )[-1]
            )
        finally:
            cnf.KNN_COLUMN_PREFILTER = True
        assert len(out2) <= 6
        assert all(int(str(x).split(":")[1]) % 4 == 0 for x in out2)
    finally:
        cnf.TPU_DISABLE = saved


# ------------------------------------------------------------------ plumbing
def test_explain_shows_columnar_plan(ds):
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": i} for i in range(30)]})[-1])
    plan = ok(ds.execute("SELECT * FROM t WHERE a = 1 EXPLAIN")[-1])
    assert plan[0]["detail"]["plan"]["strategy"] == "columnar-scan"
    # WITH NOINDEX forces the plain scan
    plan = ok(ds.execute("SELECT * FROM t WITH NOINDEX WHERE a = 1 EXPLAIN")[-1])
    assert plan[0]["operation"] == "Iterate Table"


def test_small_tables_keep_row_path(ds):
    cnf.COLUMN_MIRROR_MIN_ROWS = 64
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": i} for i in range(10)]})[-1])
    plan = ok(ds.execute("SELECT * FROM t WHERE a = 1 EXPLAIN")[-1])
    assert plan[0]["operation"] == "Iterate Table"


def test_permissioned_sessions_keep_row_path(ds):
    from surrealdb_tpu.dbs.session import Session

    ds.execute(
        "DEFINE TABLE post SCHEMALESS PERMISSIONS FOR select WHERE published = true"
    )
    ok(
        ds.execute(
            "INSERT INTO post $rows",
            vars={"rows": [{"id": i, "published": i % 2 == 0, "a": 1} for i in range(40)]},
        )[-1]
    )
    sess = Session.anonymous("test", "test")
    out = ok(ds.execute("SELECT VALUE id FROM post WHERE a = 1", sess)[-1])
    assert len(out) == 20  # permission filter still applied per record


def test_info_for_root_system_section(ds):
    saved = cnf.SLOW_QUERY_THRESHOLD_SECS
    cnf.SLOW_QUERY_THRESHOLD_SECS = 0.0  # every statement is "slow"
    try:
        ds.execute("CREATE t:1 SET a = 1")
    finally:
        cnf.SLOW_QUERY_THRESHOLD_SECS = saved
    info = ok(ds.execute("INFO FOR ROOT")[-1])
    system = info["system"]
    assert {"slow_queries", "errors", "traces"} <= set(system)
    assert any("t:1" in str(e.get("sql", "")) for e in system["slow_queries"])
    # slow statements are always trace-kept: the ring joins the trace store
    tids = {e.get("trace_id") for e in system["slow_queries"]}
    assert any(t.get("trace_id") in tids for t in system["traces"])


def test_concurrent_writers_never_serve_stale(ds):
    """Racing writers vs columnar readers vs debounced rebuilds: a reader
    must never see a row that does not match its predicate (stale mask)."""
    import threading

    ds.execute("DEFINE TABLE t SCHEMALESS")
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": [{"id": i, "a": i % 10} for i in range(300)]})[-1])
    ok(ds.execute("SELECT id FROM t WHERE a = 1")[-1])  # build
    errors = []
    stop = threading.Event()

    def writer(wid):
        k = 0
        while not stop.is_set():
            i = 300 + wid * 100000 + k
            k += 1
            try:
                ds.execute(f"CREATE t:{i} SET a = {k % 10}")
            except Exception as e:  # noqa: BLE001
                if "conflict" not in str(e).lower():
                    errors.append(e)

    def reader():
        while not stop.is_set():
            out = ds.execute("SELECT VALUE a FROM t WHERE a = 3")[-1]
            if out["status"] != "OK" or any(v != 3 for v in out["result"]):
                errors.append(out)

    ths = [threading.Thread(target=writer, args=(w,)) for w in range(2)] + [
        threading.Thread(target=reader) for _ in range(2)
    ]
    for t in ths:
        t.start()
    import time

    time.sleep(2.0)
    stop.set()
    for t in ths:
        t.join()
    assert not errors, errors[:3]
    assert ds.column_mirrors.wait_rebuild(timeout=10)
    col, row = both_paths(ds, "SELECT count() FROM t WHERE a = 3 GROUP ALL")
    assert col == row


def test_depth_knob_beyond_materialized_falls_back(ds):
    """COLUMN_MIRROR_MAX_DEPTH above the builder's materialized depth must
    fall back (not serve a virtual all-NONE column for `a.b.c`)."""
    saved = cnf.COLUMN_MIRROR_MAX_DEPTH
    cnf.COLUMN_MIRROR_MAX_DEPTH = 3
    try:
        ds.execute("DEFINE TABLE t SCHEMALESS")
        rows = [{"id": i, "a": {"b": {"c": i % 4}}} for i in range(40)]
        ok(ds.execute("INSERT INTO t $rows", vars={"rows": rows})[-1])
        col, row = both_paths(ds, "SELECT VALUE id FROM t WHERE a.b.c = 1")
        assert [str(x) for x in col] == [str(x) for x in row]
        assert len(row) == 10
    finally:
        cnf.COLUMN_MIRROR_MAX_DEPTH = saved


def test_knn_prefilter_key_distinguishes_param_values(ds):
    """Same SQL text, different $param bindings -> different masks; the
    dispatch-coalescing key must differ, or a rider would silently get its
    top-k computed through the leader's (tighter/looser) mask."""
    import numpy as np

    saved = cnf.TPU_DISABLE, cnf.TPU_KNN_ONDEVICE_THRESHOLD
    cnf.TPU_DISABLE = False  # jax-CPU: exercises the exact-device branch
    cnf.TPU_KNN_ONDEVICE_THRESHOLD = 16
    ds.mesh = lambda: None  # single-chip path (the test mesh would shard)
    try:
        ds.execute(
            "DEFINE TABLE v SCHEMALESS; "
            "DEFINE INDEX ie ON v FIELDS emb HNSW DIMENSION 4 DIST EUCLIDEAN EFC 16"
        )
        rng = np.random.default_rng(1)
        rows = [
            {"id": i, "emb": rng.standard_normal(4).tolist(), "val": i % 100}
            for i in range(64)
        ]
        ok(ds.execute("INSERT INTO v $rows", vars={"rows": rows})[-1])
        keys_seen = []
        orig = ds.dispatch.submit

        def spy(key, payload, runner):
            keys_seen.append(key)
            return orig(key, payload, runner)

        ds.dispatch.submit = spy
        try:
            sql = "SELECT VALUE id FROM v WHERE emb <|4|> $q AND val < $t"
            for t in (10, 90):
                out = ds.execute(sql, vars={"q": rows[0]["emb"], "t": t})[-1]
                assert out["status"] == "OK"
                got = {int(str(x).split(":")[1]) for x in out["result"]}
                assert all(rows[i]["val"] < t for i in got), (t, got)
        finally:
            ds.dispatch.submit = orig
        knn_keys = [k for k in keys_seen if k and k[0] == "knn-exact"]
        assert len(knn_keys) == 2 and knn_keys[0] != knn_keys[1]
    finally:
        cnf.TPU_DISABLE, cnf.TPU_KNN_ONDEVICE_THRESHOLD = saved


def test_columnar_count_matches_row_path_on_things(ds):
    # records whose filter column holds record links (OTHER tag end-to-end)
    ds.execute("DEFINE TABLE t SCHEMALESS")
    rows = [{"id": i, "ref": Thing("x", i % 3), "a": i % 5} for i in range(80)]
    ok(ds.execute("INSERT INTO t $rows", vars={"rows": rows})[-1])
    col, row = both_paths(ds, "SELECT VALUE id FROM t WHERE ref = x:1 AND a < 4")
    assert [str(x) for x in col] == [str(x) for x in row]


# ------------------------------------------------------------------ widened fragment (r10)
def test_datetime_constants_lower_exactly(ds):
    """Datetime comparisons lower onto the int64 nanos plane — exact even
    where f64 loses nanosecond precision (epoch nanos >> 2^53)."""
    ds.execute("DEFINE TABLE ev SCHEMALESS")
    rows = [
        {"id": i, "ts_txt": f"2024-03-{1 + i % 27:02d}T10:00:00Z", "n": i}
        for i in range(80)
    ]
    for r in rows:
        ok(ds.execute(f"CREATE ev:{r['id']} SET ts = d'{r['ts_txt']}', n = {r['n']}")[-1])
    # mixed rows stay exact via needs_row
    ok(ds.execute("CREATE ev:900 SET ts = [1,2]; CREATE ev:901 SET n = -1")[-1])
    for sql in (
        "SELECT VALUE id FROM ev WHERE ts > d'2024-03-15T00:00:00Z'",
        "SELECT VALUE id FROM ev WHERE ts = d'2024-03-01T10:00:00Z'",
        "SELECT VALUE id FROM ev WHERE ts <= d'2024-03-04T10:00:00Z' AND n > 10",
        "SELECT VALUE id FROM ev WHERE ts != NONE",
        "SELECT VALUE id FROM ev WHERE ts",  # truthy(datetime) is True
    ):
        col, row = both_paths(ds, sql)
        assert col == row, sql
    plan = ok(ds.execute("SELECT * FROM ev WHERE ts > d'2024-03-15T00:00:00Z' EXPLAIN")[-1])
    assert plan[0]["detail"]["plan"]["strategy"] == "columnar-scan"


def test_datetime_nanos_precision_on_the_int64_plane(ds):
    """Two datetimes 1ns apart MUST compare distinct (f64 nanos would tie)."""
    ds.execute("DEFINE TABLE tick SCHEMALESS")
    ok(ds.execute(
        "CREATE tick:1 SET ts = d'2024-01-01T00:00:00.000000001Z';"
        "CREATE tick:2 SET ts = d'2024-01-01T00:00:00.000000002Z';"
        "CREATE tick:3 SET ts = d'2024-01-01T00:00:00.000000002Z';"
        # padding so the table crosses the mirror floor
        + "".join(f"CREATE tick:{i} SET ts = d'2024-01-02T00:00:00Z';" for i in range(4, 12))
    )[-1])
    sql = "SELECT VALUE id FROM tick WHERE ts = d'2024-01-01T00:00:00.000000002Z'"
    col, row = both_paths(ds, sql)
    assert col == row == [Thing("tick", 2), Thing("tick", 3)]


def test_contains_on_string_columns_lowers(ds):
    ds.execute("DEFINE TABLE s SCHEMALESS")
    rows = [
        {"id": i, "name": f"item-{'xy' if i % 3 else 'qz'}-{i}"} for i in range(60)
    ]
    ok(ds.execute("INSERT INTO s $rows", vars={"rows": rows})[-1])
    # type-mixed cells: arrays/numbers must keep row-path semantics exactly
    ok(ds.execute("CREATE s:800 SET name = ['qz']; CREATE s:801 SET name = 7")[-1])
    for sql in (
        "SELECT VALUE id FROM s WHERE name CONTAINS 'qz'",
        "SELECT VALUE id FROM s WHERE name CONTAINSNOT 'xy'",
        "SELECT VALUE id FROM s WHERE name CONTAINS '-1' AND name CONTAINS 'xy'",
        "SELECT VALUE id FROM s WHERE name CONTAINS ''",
    ):
        col, row = both_paths(ds, sql)
        assert col == row, sql
    plan = ok(ds.execute("SELECT * FROM s WHERE name CONTAINS 'qz' EXPLAIN")[-1])
    assert plan[0]["detail"]["plan"]["strategy"] == "columnar-scan"
    # a non-string needle refuses to lower (row path, same answer)
    col, row = both_paths(ds, "SELECT VALUE id FROM s WHERE name CONTAINS 3")
    assert col == row
