"""Workload statistics plane (ISSUE 15): statement fingerprints, per-shape
plan-mix accounting with flip detection, the always-on sampling profiler,
and every surfacing layer.

The contracts under test:

- fingerprint normalization: literal / parameter / whitespace / keyword-case
  variants of ONE statement collapse to one fingerprint, while shape-
  distinct statements (different idioms, projections, operators, tables)
  never collide — the property the whole plane stands on;
- the bounded LRU store: eviction at the cap (counted), record() safe
  under a many-thread hammer, every execution conserved;
- plan-mix accounting off the REAL executor: a columnar-served SELECT
  lands `columnar-scan` in its fingerprint's mix, the mirror standing
  down lands `row`, and the transition is a counted PLAN FLIP with a
  `stats.plan_flip` event joined to the statement;
- rings join the plane: slow-query entries, error-ring entries and kept
  traces carry the fingerprint id, `/statements?fingerprint=` filters;
- the sampling profiler: samples attribute to `bg:<kind>`-named threads
  and to the active statement fingerprint, folded stacks export in
  flamegraph collapsed format, aggregates stay bounded;
- surfacing: system-gated GET /statements (+`?cluster=1` federated
  node-tagged from a 2-node cluster), INFO FOR ROOT, bundle sections
  12/13;
- the end-to-end drift proof: the same SELECT battery with the mirror
  enabled then force-declined mid-run records the flip in one
  fingerprint's plan-mix vector, shows up merged node-tagged over
  `?cluster=1`, and the two `/statements` snapshots name that
  fingerprint.
"""

import json
import random
import string
import threading
import time
import urllib.request

import pytest

import jax.numpy  # noqa: F401 — concurrent lazy first-import races otherwise

from surrealdb_tpu import cnf, events, profiler, stats, telemetry, tracing
from surrealdb_tpu.cluster import ClusterConfig, attach
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.net.server import serve


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


@pytest.fixture(autouse=True)
def _fresh_plane():
    """Module-global stores, per-test isolation."""
    stats.reset()
    profiler.reset()
    yield
    stats.reset()
    profiler.reset()


@pytest.fixture(autouse=True)
def _small_mirror_floor():
    saved = (
        cnf.COLUMN_MIRROR_MIN_ROWS, cnf.COLUMN_MIRROR,
        cnf.COLUMN_REBUILD_DEBOUNCE_SECS,
    )
    cnf.COLUMN_MIRROR_MIN_ROWS = 4
    cnf.COLUMN_MIRROR = True
    cnf.COLUMN_REBUILD_DEBOUNCE_SECS = 0.05
    yield
    (
        cnf.COLUMN_MIRROR_MIN_ROWS,
        cnf.COLUMN_MIRROR,
        cnf.COLUMN_REBUILD_DEBOUNCE_SECS,
    ) = saved


def fp_of(sql: str) -> str:
    return stats.fingerprint(sql)[0]


# ============================================================ fingerprinting
VARIANT_GROUPS = [
    # literals erase
    ["CREATE t SET x = 1", "CREATE t SET x = 2", "CREATE t SET x = 3.5",
     "CREATE  t  SET  x=99"],
    # strings and params erase (each its own marker — see distinctness below)
    ["SELECT * FROM person WHERE name = 'tobie'",
     "select * from person where name = \"jaime\"",
     "SELECT *\nFROM person\nWHERE name = 'x'"],
    # literal-list runs collapse regardless of length
    ["SELECT * FROM t WHERE n IN [1, 2, 3]",
     "SELECT * FROM t WHERE n IN [4]",
     "SELECT * FROM t WHERE n IN [9, 8, 7, 6, 5, 4, 3, 2, 1]"],
    # keyword case folds; comments vanish with tokenization
    ["DELETE person WHERE age < 18",
     "delete person where age < 99",
     "DELETE person /* minors */ WHERE age < 21"],
    # durations/datetimes are literals too
    ["UPDATE task SET due = 1h", "UPDATE task SET due = 30m"],
]

SHAPE_DISTINCT = [
    "SELECT * FROM person",
    "SELECT * FROM Person",                      # identifiers keep case
    "SELECT * FROM person WHERE age > 1",
    "SELECT * FROM person WHERE age < 1",        # operator differs
    "SELECT * FROM person WHERE age > $min",     # param vs literal
    "SELECT name FROM person",                   # projection differs
    "SELECT name, age FROM person",
    "SELECT count() FROM person GROUP ALL",
    "SELECT * FROM person ORDER BY age",
    "SELECT * FROM person ORDER BY age DESC",
    "SELECT * FROM person LIMIT 1",
    "SELECT * FROM other",
    "CREATE person SET age = 1",
    "UPDATE person SET age = 1",
    "UPSERT person SET age = 1",
    "DELETE person WHERE age = 1",
    "RELATE a:1->knows->b:2",
    "INSERT INTO person [{ }]",
    "RETURN 1",
    "INFO FOR DB",
]


@pytest.mark.parametrize("group", VARIANT_GROUPS)
def test_variants_of_one_statement_collapse(group):
    fps = {fp_of(sql) for sql in group}
    assert len(fps) == 1, {sql: stats.fingerprint(sql)[1] for sql in group}


def test_shape_distinct_statements_never_collide():
    fps = {}
    for sql in SHAPE_DISTINCT:
        fp = fp_of(sql)
        assert fp not in fps, (
            f"collision: {sql!r} and {fps[fp]!r} both -> "
            f"{stats.fingerprint(sql)[1]!r}"
        )
        fps[fp] = sql


def test_property_randomized_literal_variants(seeded_rng=7):
    """Property test: any template instantiated with random literals maps
    to ONE fingerprint; distinct templates never share one."""
    rng = random.Random(seeded_rng)
    templates = [
        ("CREATE acct SET bal = {n}, tag = '{s}'", 2),
        ("SELECT * FROM acct WHERE bal > {n} AND tag != '{s}'", 2),
        ("UPDATE acct SET bal = {n} WHERE tag = '{s}'", 2),
        ("SELECT * FROM acct WHERE bal IN [{n}, {n}, {n}]", 3),
        ("DELETE acct WHERE bal < {n}", 1),
    ]
    seen = {}
    for template, _ in templates:
        fps = set()
        for _ in range(25):
            sql = template
            while "{n}" in sql:
                sql = sql.replace("{n}", str(rng.randint(0, 10**6)), 1)
            while "{s}" in sql:
                sql = sql.replace(
                    "{s}",
                    "".join(rng.choices(string.ascii_lowercase, k=rng.randint(1, 12))),
                    1,
                )
            # whitespace noise must not mint a shape either
            if rng.random() < 0.5:
                sql = sql.replace(" ", "   ")
            fps.add(fp_of(sql))
        assert len(fps) == 1, template
        fp = fps.pop()
        assert fp not in seen, (template, seen[fp])
        seen[fp] = template


def test_unlexable_text_still_fingerprints():
    # fingerprinting must never fail a statement that reached execution
    fp, norm = stats.fingerprint("SELECT 'unterminated FROM t WHERE x = 5")
    assert fp and "5" not in norm
    assert fp == stats.fingerprint("SELECT 'unterminated FROM t WHERE x = 9")[0]


# ============================================================ the LRU store
def test_lru_eviction_bounds_the_store(monkeypatch):
    monkeypatch.setattr(cnf, "STATEMENTS_STORE_SIZE", 16)
    for i in range(40):
        fp, norm = stats.fingerprint(f"SELECT * FROM tb{i}")
        stats.record(fp, norm, "SelectStatement", 0.001)
    assert stats.size() == 16
    snap = stats.snapshot()
    assert snap["evicted"] == 24
    assert telemetry.get_counter("statements_evicted_total") >= 24
    # the SURVIVORS are the most recently used shapes
    kept = {e["sql"] for e in stats.statements(limit=50)}
    assert "SELECT * FROM tb39" in kept and "SELECT * FROM tb0" not in kept


def test_record_hammer_conserves_every_call():
    fps = [stats.fingerprint(f"SELECT * FROM h{i}") for i in range(8)]
    n_threads, per_thread = 8, 200

    def hammer(tid):
        rng = random.Random(tid)
        for _ in range(per_thread):
            fp, norm = fps[rng.randrange(len(fps))]
            stats.record(
                fp, norm, "SelectStatement", 0.0001,
                plan=[{"plan": "TableScan"}] if rng.random() < 0.5 else
                [{"strategy": "columnar-scan"}],
            )

    threads = [
        threading.Thread(target=hammer, args=(t,), name=f"bg:stats_hammer:{t}")
        for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rows = stats.statements(limit=20)
    assert sum(r["calls"] for r in rows) == n_threads * per_thread
    # every execution's scan decision is in the mix — none lost under race
    assert sum(
        sum(r["plan_mix"].values()) for r in rows
    ) == n_threads * per_thread


def test_activation_nests_and_restores():
    assert stats.active_fingerprint() is None
    t1 = stats.activate("aaaa")
    assert stats.active_fingerprint() == "aaaa"
    t2 = stats.activate("bbbb")
    assert stats.active_fingerprint() == "bbbb"
    stats.deactivate(t2)
    assert stats.active_fingerprint() == "aaaa"
    stats.deactivate(t1)
    assert stats.active_fingerprint() is None


# ============================================================ plan mix + flips
def seed_rows(ds, n=24):
    ok(ds.execute("DEFINE TABLE acct SCHEMALESS;")[0])
    for i in range(n):
        ok(ds.execute(f"CREATE acct:{i} SET bal = {i}, grp = {i % 3};")[0])


def test_executed_statements_record_plan_mix(ds):
    seed_rows(ds)
    sql = "SELECT * FROM acct WHERE bal > 5"
    for _ in range(3):
        ok(ds.execute(sql)[-1])
    row = stats.get(fp_of(sql))
    assert row is not None and row["calls"] == 3
    assert row["plan_mix"].get("columnar-scan", 0) >= 1, row["plan_mix"]
    assert row["kind"] == "SelectStatement"
    assert row["p99_ms"] is not None and row["rows_out"] > 0


def test_bulk_insert_records_rows_in(ds):
    ok(ds.execute("DEFINE TABLE bk SCHEMALESS;")[0])
    # past SURREAL_BULK_INSERT_MIN the vectorized ingest path engages and
    # its row counter becomes the statement's rows_in delta
    n = max(cnf.BULK_INSERT_MIN, 64) + 16
    rows = [{"id": i, "v": i} for i in range(n)]
    ok(ds.execute("INSERT INTO bk $rows RETURN NONE", vars={"rows": rows})[-1])
    row = stats.get(fp_of("INSERT INTO bk $rows RETURN NONE"))
    assert row is not None and row["rows_in"] == n, row


def test_plan_flip_detected_counted_and_joined(ds):
    seed_rows(ds)
    sql = "SELECT * FROM acct WHERE bal > 7"
    before = telemetry.get_counter("statement_plan_flips")
    for _ in range(2):
        ok(ds.execute(sql)[-1])
    cnf.COLUMN_MIRROR = False  # the mirror stands down mid-run
    ok(ds.execute(sql)[-1])
    row = stats.get(fp_of(sql))
    assert row["plan_flips"] >= 1, row
    assert row["flip_log"][-1]["from"].startswith("columnar")
    assert row["flip_log"][-1]["to"] == "row"
    assert row["plan_mix"].get("row", 0) >= 1
    assert telemetry.get_counter("statement_plan_flips") > before
    flips = events.snapshot(kind_prefix="stats.plan_flip")
    assert flips and flips[-1]["fingerprint"] == fp_of(sql)


def test_errors_and_slow_ring_carry_fingerprint(ds, monkeypatch):
    seed_rows(ds, n=6)
    bad = "CREATE acct:1 SET bal = 0"  # duplicate id: a clean ERR
    r = ds.execute(bad)[-1]
    assert r["status"] == "ERR"
    err_row = stats.get(fp_of(bad))
    assert err_row is not None and err_row["errors"] == 1
    errs = [e for e in telemetry.recent_errors() if e.get("fingerprint")]
    assert any(e["fingerprint"] == fp_of(bad) for e in errs)

    monkeypatch.setattr(cnf, "SLOW_QUERY_THRESHOLD_SECS", 0.0)
    slow_sql = "SELECT * FROM acct WHERE grp = 1"
    ok(ds.execute(slow_sql)[-1])
    slow = telemetry.slow_queries()[-1]
    assert slow["fingerprint"] == fp_of(slow_sql)
    assert stats.get(fp_of(slow_sql))["slow"] == 1
    # the kept trace carries it too: /slow -> stats row joins in one hop
    kept = [t for t in tracing.list_traces()
            if t.get("fingerprint") == fp_of(slow_sql)]
    assert kept, tracing.list_traces()
    # and the /statements view filters by it
    only = stats.statements(fingerprint=fp_of(slow_sql))
    assert len(only) == 1 and only[0]["fingerprint"] == fp_of(slow_sql)


# ============================================================ profiler
def test_profiler_attributes_threads_and_fingerprints():
    fp = fp_of("SELECT * FROM prof_t WHERE x > 1")
    stop = threading.Event()

    def busy():
        tok = stats.activate(fp)
        try:
            while not stop.is_set():
                time.sleep(0.002)
        finally:
            stats.deactivate(tok)

    t = threading.Thread(target=busy, name="bg:fixture_worker:prof_t")
    t.start()
    try:
        time.sleep(0.02)
        for _ in range(5):
            assert profiler.sample_once() > 0
    finally:
        stop.set()
        t.join()
    rep = profiler.report()
    # the deterministic bg:<kind> name is the series; the target stripped
    assert rep["by_thread"].get("bg:fixture_worker", 0) >= 5, rep["by_thread"]
    assert rep["by_fingerprint"].get(fp, 0) >= 5, rep["by_fingerprint"]
    assert rep["samples"] >= 5 and rep["ticks"] >= 5
    # folded stacks export in flamegraph collapsed format
    folded = profiler.folded_text()
    lines = [ln for ln in folded.splitlines() if ln.startswith("bg:fixture_worker;")]
    assert lines, folded[:400]
    head, _, count = lines[0].rpartition(" ")
    assert int(count) >= 1 and ";" in head and ":" in head


def test_profiler_stack_series_bounded(monkeypatch):
    monkeypatch.setattr(cnf, "PROFILE_MAX_STACKS", 16)
    # depth-varied recursion mints distinct stacks past the cap
    stop = threading.Event()
    depth_box = [1]

    def recur(n):
        if n <= 0:
            time.sleep(0.003)
            return
        recur(n - 1)

    def busy():
        while not stop.is_set():
            recur(depth_box[0] % 40)
            depth_box[0] += 1

    t = threading.Thread(target=busy, name="bg:fixture_depth:x")
    t.start()
    try:
        for _ in range(80):
            profiler.sample_once()
    finally:
        stop.set()
        t.join()
    rep = profiler.report()
    assert rep["distinct_stacks"] <= 16 + len(rep["by_thread"]), rep["distinct_stacks"]


def test_profiler_service_runs_and_pauses(monkeypatch, ds):
    # the Datastore boot started the process-global service (PROFILE_HZ>0
    # by default); it samples without any explicit tick
    import surrealdb_tpu.profiler as prof

    assert prof.ensure_started() is True
    prof.resume()
    deadline = time.time() + 5.0
    while time.time() < deadline and prof.report()["samples"] == 0:
        time.sleep(0.05)
    assert prof.report()["samples"] > 0
    prof.pause()
    time.sleep(0.3)
    base = prof.report()["samples"]
    time.sleep(0.5)
    assert prof.report()["samples"] == base  # parked sampler takes none
    prof.resume()
    # the engine's own bg threads attribute by kind
    by_thread = prof.report()["by_thread"]
    assert any(k.startswith("bg:") or k == "MainThread" for k in by_thread)


# ============================================================ surfacing
def _serve(auth_enabled=False):
    return serve("memory", port=0, auth_enabled=auth_enabled).start_background()


def test_statements_endpoint_serves_and_filters():
    srv = _serve()
    try:
        import http.client

        conn = http.client.HTTPConnection(srv.host, srv.port)
        hdrs = {"surreal-ns": "t", "surreal-db": "t"}
        conn.request("POST", "/sql", "CREATE e:1 SET v = 1; SELECT * FROM e;", hdrs)
        conn.getresponse().read()
        conn.request("GET", "/statements", headers=hdrs)
        r = conn.getresponse()
        rows = json.loads(r.read())
        assert r.status == 200 and len(rows) >= 2
        sel = next(e for e in rows if e["kind"] == "SelectStatement")
        assert sel["calls"] == 1 and sel["plan_mix"]
        conn.request(
            "GET", f"/statements?fingerprint={sel['fingerprint']}&limit=5",
            headers=hdrs,
        )
        r = conn.getresponse()
        only = json.loads(r.read())
        assert [e["fingerprint"] for e in only] == [sel["fingerprint"]]
        conn.close()
    finally:
        srv.shutdown()


def test_info_for_root_and_bundle_sections(ds):
    seed_rows(ds, n=8)
    ok(ds.execute("SELECT * FROM acct WHERE bal > 2")[-1])
    info = ok(ds.execute("INFO FOR ROOT")[-1])
    assert any(
        e["kind"] == "SelectStatement" for e in info["system"]["statements"]
    )
    from surrealdb_tpu.bundle import BUNDLE_SCHEMA, debug_bundle

    assert BUNDLE_SCHEMA == "surrealdb-tpu-bundle/11"
    b = debug_bundle(ds)
    assert b["statements"]["fingerprints"] >= 1
    assert b["statements"]["top"]
    assert "by_thread" in b["profiler"] and "hz" in b["profiler"]


# ============================================================ cluster + drift
class Cluster2:
    """Two in-process nodes on one ring (the test_cluster_obs harness
    shape), for the federated /statements and the drift proof."""

    def __init__(self):
        self.servers = [
            serve("memory", port=0, auth_enabled=False).start_background()
            for _ in range(2)
        ]
        self.nodes = [
            {"id": f"n{i + 1}", "url": srv.url}
            for i, srv in enumerate(self.servers)
        ]
        self.datastores = [s.httpd.RequestHandlerClass.ds for s in self.servers]
        for i, ds in enumerate(self.datastores):
            attach(ds, ClusterConfig(self.nodes, f"n{i + 1}", secret="stats-secret"))
        self.s = Session.owner("t", "t")

    @property
    def coord(self):
        return self.datastores[0]

    def http_get(self, path, i=0):
        with urllib.request.urlopen(self.servers[i].url + path, timeout=30) as r:
            return r.status, r.read()

    def close(self):
        for srv in self.servers:
            srv.shutdown()
        for ds in self.datastores:
            ds.close()


@pytest.fixture()
def cluster2():
    c = Cluster2()
    yield c
    c.close()


def test_drift_proof_end_to_end(cluster2):
    """The acceptance walk: same SELECT battery twice — mirror enabled,
    then force-declined mid-run — the fingerprint's plan-mix vector
    records the flip, `/statements?cluster=1` shows it merged node-tagged
    from a 2-node cluster, and the two `/statements` snapshots themselves
    name that fingerprint: columnar-only before, mostly `row` after."""
    import copy

    c = cluster2
    ok(c.coord.execute("DEFINE TABLE drift SCHEMALESS", c.s)[0])
    for i in range(24):
        ok(c.coord.execute(f"CREATE drift:{i} SET val = {i}", c.s)[0])
    battery = [
        "SELECT * FROM drift WHERE val > 4",
        "SELECT * FROM drift WHERE val > 18",
    ]

    # window A: mirror enabled — shard-local executions serve columnar
    for _ in range(3):
        for sql in battery:
            ok(c.coord.execute(sql, c.s)[-1])
    colfps = [
        e for e in stats.statements(limit=100)
        if any(str(k).startswith("columnar") for k in e["plan_mix"])
    ]
    assert colfps, [e["plan_mix"] for e in stats.statements(limit=100)]
    window_a = copy.deepcopy(stats.statements(limit=100))

    # mid-run decline: the mirror stands down, the SAME battery re-runs
    cnf.COLUMN_MIRROR = False
    for _ in range(5):
        for sql in battery:
            ok(c.coord.execute(sql, c.s)[-1])

    flipped = [
        e for e in stats.statements(limit=100)
        if e["plan_flips"] >= 1
        and any(str(k).startswith("columnar") for k in e["plan_mix"])
        and e["plan_mix"].get("row", 0) >= 1
    ]
    assert flipped, [
        (e["sql"], e["plan_mix"], e["plan_flips"])
        for e in stats.statements(limit=100)
    ]
    culprit = flipped[0]
    assert culprit["flip_log"][-1]["to"] == "row"

    # federated: the 2-node merge tags every entry with its serving node
    status, body = c.http_get(
        f"/statements?cluster=1&fingerprint={culprit['fingerprint']}"
        "&limit=20&sort=calls"
    )
    assert status == 200
    merged = json.loads(body)
    assert {e["node"] for e in merged} == {"n1", "n2"}, merged
    assert all(e["fingerprint"] == culprit["fingerprint"] for e in merged)

    # the two snapshots name the culprit on their own: the same
    # fingerprint, no flip and no `row` serve in window A, the dominant
    # plan gone from columnar to `row` in window B
    window_b = copy.deepcopy(stats.statements(limit=100))
    a = {e["fingerprint"]: e for e in window_a}[culprit["fingerprint"]]
    b = {e["fingerprint"]: e for e in window_b}[culprit["fingerprint"]]
    assert a["plan_flips"] == 0 and "row" not in a["plan_mix"], a
    assert max(a["plan_mix"], key=a["plan_mix"].get).startswith("columnar"), a
    assert b["plan_flips"] >= 1 and b["calls"] > a["calls"], (a, b)
    grew = {k: v - a["plan_mix"].get(k, 0) for k, v in b["plan_mix"].items()}
    assert max(grew, key=grew.get) == "row", grew
