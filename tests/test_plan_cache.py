"""Fingerprint-keyed plan & pipeline cache (ISSUE 18): cached serves must
be byte-identical to cold plans, with zero stale serves across every
invalidation axis.

The contracts under test:

- warm serves return EXACTLY what a cache-disabled datastore returns for
  the same script — the core property, checked transcript-for-transcript
  (status + result, times stripped) and fuzzed over random literals;
- literal variants of one shape share an entry and serve from the shared
  template with per-execution slot bindings (hits counted, `/statements`
  annotated, bundle section present);
- DDL invalidates: DEFINE INDEX / REMOVE INDEX / REMOVE TABLE between
  warm serves never yields a result the cold ladder would not produce,
  and the invalidation is counted with cause `ddl`;
- a mirror decline mid-run (plan-mix flip) evicts the flipped
  fingerprint's entry — visible as a `plan_cache.evict` EVENT and a
  `plan_cache_invalidations{cause=flip}` METRIC — and the shape still
  answers correctly afterwards;
- session/tenant scope: a plan warmed under one (ns, db) never leaks
  rows into another tenant or privilege level;
- cluster: repeated SELECTs hit the epoch-guarded scatter-route cache
  (`plan_cache_hits{kind=cluster_route}`), and an epoch bump mid-stream
  invalidates it without changing a single result byte;
- a concurrent writer/reader/DDL hammer serves only self-consistent
  results and converges to the cold-replay final state.
"""

import json
import random
import threading

import pytest

import jax.numpy  # noqa: F401 — concurrent lazy first-import races otherwise

from surrealdb_tpu import cnf, events, stats, telemetry
from surrealdb_tpu.dbs.session import Session


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


@pytest.fixture(autouse=True)
def _fresh_plane():
    """stats is module-global (the plan-flip fan-out rides it); the plan
    cache itself is per-datastore, so a fresh ds is a fresh cache."""
    stats.reset()
    yield
    stats.reset()


@pytest.fixture(autouse=True)
def _knobs():
    saved = (
        cnf.PLAN_CACHE, cnf.PLAN_CACHE_MIN_HITS,
        cnf.COLUMN_MIRROR, cnf.COLUMN_MIRROR_MIN_ROWS,
        cnf.COLUMN_REBUILD_DEBOUNCE_SECS,
    )
    cnf.PLAN_CACHE = True
    cnf.PLAN_CACHE_MIN_HITS = 1  # install on first observe: tests exercise
    # the serve path, not the warmup counter
    cnf.COLUMN_MIRROR_MIN_ROWS = 4
    cnf.COLUMN_MIRROR = True
    cnf.COLUMN_REBUILD_DEBOUNCE_SECS = 0.05
    yield
    (
        cnf.PLAN_CACHE, cnf.PLAN_CACHE_MIN_HITS,
        cnf.COLUMN_MIRROR, cnf.COLUMN_MIRROR_MIN_ROWS,
        cnf.COLUMN_REBUILD_DEBOUNCE_SECS,
    ) = saved


def _mk_ds(enabled=True):
    from surrealdb_tpu.kvs.ds import Datastore

    saved = cnf.PLAN_CACHE
    cnf.PLAN_CACHE = enabled
    try:
        return Datastore("memory")
    finally:
        cnf.PLAN_CACHE = saved


@pytest.fixture()
def ds():
    d = _mk_ds(True)
    yield d
    d.close()


def fp_of(sql: str) -> str:
    return stats.fingerprint(sql)[0]


# ------------------------------------------------------------ the property
def _norm(responses):
    """A transcript entry: status + result, execution time stripped."""
    return json.dumps(
        [{"status": r["status"], "result": r.get("result")} for r in responses],
        default=str, sort_keys=True,
    )


def run_script(d, script):
    """Execute [(sql, vars, session), ...] in order; return the
    normalized transcript."""
    out = []
    for sql, vars, sess in script:
        out.append(
            _norm(d.execute(sql, sess, dict(vars) if vars else None))
        )
    return out


def assert_warm_equals_cold(script):
    """THE property: a plan-cache-enabled datastore and a disabled one
    produce byte-identical transcripts for the same script."""
    warm_ds, cold_ds = _mk_ds(True), _mk_ds(False)
    try:
        warm = run_script(warm_ds, script)
        stats.reset()  # per-ds replay, shared stats plane: avoid cross-talk
        cold = run_script(cold_ds, script)
        for i, (w, c) in enumerate(zip(warm, cold)):
            assert w == c, (
                f"statement {i} diverged warm-vs-cold:\n"
                f"  sql:  {script[i][0]}\n  warm: {w}\n  cold: {c}"
            )
        return warm_ds
    finally:
        cold_ds.close()


def seed(script, n=12, tb="person"):
    for i in range(n):
        script.append(
            (f"CREATE {tb}:{i} SET name = 'p{i:03d}', age = {i * 7 % 60}, "
             f"band = {i % 3}", None, None)
        )


# ============================================================ warm ≡ cold
def test_warm_serve_byte_identical_and_counted():
    script = []
    seed(script)
    # literal variants of ONE shape, repeated so serves go warm
    for lo in (10, 20, 30, 10, 40, 20, 10, 55):
        script.append(
            (f"SELECT * FROM person WHERE age > {lo} ORDER BY age, name",
             None, None)
        )
    warm_ds = assert_warm_equals_cold(script)
    try:
        fp = fp_of("SELECT * FROM person WHERE age > 10 ORDER BY age, name")
        desc = warm_ds.plan_cache.describe(fp)
        assert desc is not None and desc["cached"], desc
        assert desc["hits"] >= 4, desc
        snap = warm_ds.plan_cache.snapshot()
        assert snap["enabled"] and snap["entries"] >= 1, snap
        assert snap["hits"]["ast"] >= 4, snap
    finally:
        warm_ds.close()


def test_param_spelling_and_projection_shapes():
    script = []
    seed(script)
    for x in (5, 25, 45, 25, 5):
        script.append(
            ("SELECT name, age FROM person WHERE age > $x ORDER BY name",
             {"x": x}, None)
        )
        script.append(
            (f"SELECT name FROM person WHERE band = {x % 3} ORDER BY name",
             None, None)
        )
        script.append(
            ("SELECT count() FROM person GROUP ALL", None, None)
        )
    assert_warm_equals_cold(script).close()


# ============================================================ DDL axes
def test_ddl_define_remove_index_and_table_between_warm_serves():
    sel = "SELECT * FROM person WHERE age > 14 ORDER BY age, name"
    script = []
    seed(script)
    script += [(sel, None, None)] * 3  # warm install + serves
    script.append(("DEFINE INDEX iage ON person FIELDS age", None, None))
    script += [(sel, None, None)] * 2  # must re-plan onto the index
    script.append(("REMOVE INDEX iage ON TABLE person", None, None))
    script += [(sel, None, None)] * 2  # must re-plan back to the scan
    script.append(("UPDATE person:3 SET age = 15", None, None))
    script += [(sel, None, None)]  # writes visible through warm serves
    script.append(("REMOVE TABLE person", None, None))
    script += [(sel, None, None)]  # empty — never the cached rows
    warm_ds = assert_warm_equals_cold(script)
    try:
        assert telemetry.get_counter(
            "plan_cache_invalidations", cause="ddl"
        ) > 0
    finally:
        warm_ds.close()


def test_ddl_in_explicit_transaction_holds_the_bracket():
    sel = "SELECT * FROM person WHERE band = 1 ORDER BY name"
    script = []
    seed(script)
    script += [(sel, None, None)] * 3
    script.append(
        ("BEGIN; DEFINE INDEX iband ON person FIELDS band; "
         f"{sel}; COMMIT", None, None)
    )
    script += [(sel, None, None)] * 2
    assert_warm_equals_cold(script).close()


# ============================================================ plan flip
def test_mirror_decline_plan_flip_evicts_entry_event_and_metric(ds):
    sql = "SELECT * FROM acct WHERE bal > 7 ORDER BY bal"
    for i in range(12):
        ok(ds.execute(f"CREATE acct:{i} SET bal = {i}")[-1])
    for _ in range(4):
        ok(ds.execute(sql)[-1])  # columnar pipeline, warm
    fp = fp_of(sql)
    assert ds.plan_cache.describe(fp)["cached"]
    before_inv = telemetry.get_counter("plan_cache_invalidations", cause="flip")
    warm_rows = ok(ds.execute(sql)[-1])
    cnf.COLUMN_MIRROR = False  # the mirror stands down mid-run
    flipped_rows = ok(ds.execute(sql)[-1])
    assert flipped_rows == warm_rows  # same data, different plan
    # the stats plane detected the flip and evicted the fingerprint
    row = stats.get(fp)
    assert row["plan_flips"] >= 1, row
    desc = ds.plan_cache.describe(fp)
    assert desc is None or not desc["cached"], desc
    assert telemetry.get_counter(
        "plan_cache_invalidations", cause="flip"
    ) > before_inv
    ev = [e for e in events.snapshot(kind_prefix="plan_cache.evict")
          if e.get("fingerprint") == fp]
    assert ev and ev[-1]["cause"] == "flip", ev
    # and the shape still answers correctly (re-installs on the row plan)
    for _ in range(3):
        assert ok(ds.execute(sql)[-1]) == warm_rows


# ============================================================ scope axes
def test_tenant_scope_never_leaks(ds):
    a = Session.owner("nsa", "dba")
    b = Session.owner("nsb", "dbb")
    sql = "SELECT * FROM doc WHERE v > 0 ORDER BY v"
    for i in range(6):
        ok(ds.execute(f"CREATE doc:{i} SET v = {i + 1}, owner = 'a'", a)[-1])
        ok(ds.execute(
            f"CREATE doc:{i} SET v = {(i + 1) * 100}, owner = 'b'", b
        )[-1])
    for _ in range(4):
        rows_a = ok(ds.execute(sql, a)[-1])  # warms the shape under A
    assert all(r["owner"] == "a" and r["v"] < 100 for r in rows_a), rows_a
    # same TEXT under tenant B must serve B's rows, never A's cached plan
    rows_b = ok(ds.execute(sql, b)[-1])
    assert all(r["owner"] == "b" and r["v"] >= 100 for r in rows_b), rows_b
    assert len(rows_a) == len(rows_b) == 6
    # and the warmed entry is SHARED (one template), with per-scope routes
    assert ds.plan_cache.describe(fp_of(sql))["cached"]


def test_privilege_scope_respected(ds):
    owner = Session.owner("t", "t")
    sql = "SELECT name FROM secret ORDER BY name"
    ok(ds.execute("DEFINE TABLE secret PERMISSIONS NONE", owner)[-1])
    for i in range(4):
        ok(ds.execute(f"CREATE secret:{i} SET name = 'n{i}'", owner)[-1])
    for _ in range(4):
        rows = ok(ds.execute(sql, owner)[-1])  # warm under root
    assert len(rows) == 4
    # an anonymous session re-running the SAME text must not ride the
    # root-warmed route into the table
    anon = ds.execute(sql, Session.anonymous("t", "t"))[-1]
    assert anon["status"] != "OK" or anon["result"] in ([], None), anon


# ============================================================ epoch axis
def test_local_epoch_note_invalidates_and_stays_correct(ds):
    sql = "SELECT * FROM e WHERE v > 1 ORDER BY v"
    for i in range(5):
        ok(ds.execute(f"CREATE e:{i} SET v = {i}")[-1])
    base = [ok(ds.execute(sql)[-1]) for _ in range(3)][-1]
    ds.plan_cache.note_epoch(1)
    assert ok(ds.execute(sql)[-1]) == base
    before = telemetry.get_counter("plan_cache_invalidations", cause="epoch")
    ds.plan_cache.note_epoch(2)
    assert telemetry.get_counter(
        "plan_cache_invalidations", cause="epoch"
    ) > before
    assert ok(ds.execute(sql)[-1]) == base  # re-derived, never stale


def test_cluster_route_cache_hits_and_epoch_bump_mid_stream():
    from surrealdb_tpu.cluster import ClusterConfig, attach
    from surrealdb_tpu.net.server import serve

    servers = [
        serve("memory", port=0, auth_enabled=False).start_background()
        for _ in range(2)
    ]
    try:
        nodes = [
            {"id": f"n{i + 1}", "url": srv.url}
            for i, srv in enumerate(servers)
        ]
        dss = [s.httpd.RequestHandlerClass.ds for s in servers]
        for i, d in enumerate(dss):
            attach(d, ClusterConfig(nodes, f"n{i + 1}", secret="pc-secret"))
        s = Session.owner("t", "t")
        coord = dss[0]
        for i in range(12):
            ok(coord.execute(f"CREATE person:{i} SET val = {i}", s)[-1])
        sql = "SELECT * FROM person WHERE val > 3 ORDER BY val"
        before_hits = telemetry.get_counter(
            "plan_cache_hits", kind="cluster_route"
        )
        base = None
        for _ in range(4):
            rows = ok(coord.execute(sql, s)[-1])
            assert base is None or rows == base
            base = rows
        assert telemetry.get_counter(
            "plan_cache_hits", kind="cluster_route"
        ) > before_hits
        # epoch bump mid-stream: the route cache clears, the next serve
        # re-classifies, and not one result byte changes
        m = coord.cluster.membership
        with m._lock:  # noqa: SLF001 — test-only epoch injection
            m._epoch += 1  # noqa: SLF001
        before_inv = telemetry.get_counter(
            "plan_cache_invalidations", cause="epoch"
        )
        assert ok(coord.execute(sql, s)[-1]) == base
        assert telemetry.get_counter(
            "plan_cache_invalidations", cause="epoch"
        ) > before_inv
        assert ok(coord.execute(sql, s)[-1]) == base  # re-installs, serves
    finally:
        for srv in servers:
            srv.shutdown()
        for d in dss:
            d.close()


# ============================================================ fuzz + hammer
def test_fuzz_warm_vs_cold_random_literals():
    rng = random.Random(0x18)
    script = []
    seed(script, n=16)
    templates = [
        lambda r: f"SELECT * FROM person WHERE age > {r.randrange(60)} "
                  "ORDER BY age, name",
        lambda r: f"SELECT name FROM person WHERE band = {r.randrange(3)} "
                  "ORDER BY name",
        lambda r: f"SELECT * FROM person WHERE age > {r.randrange(50)} "
                  f"AND band != {r.randrange(3)} ORDER BY name",
        lambda r: f"SELECT name, age FROM person WHERE name = "
                  f"'p{r.randrange(16):03d}'",
        lambda r: f"UPDATE person:{r.randrange(16)} SET "
                  f"age = {r.randrange(60)} RETURN AFTER",
        lambda r: "SELECT count() FROM person GROUP ALL",
        lambda r: f"SELECT math::sum(age) AS s FROM person "
                  f"WHERE band = {r.randrange(3)} GROUP ALL",
    ]
    for _ in range(120):
        script.append((rng.choice(templates)(rng), None, None))
    warm_ds = assert_warm_equals_cold(script)
    try:
        # the corpus actually exercised the cache, not just the cold path
        snap = warm_ds.plan_cache.snapshot()
        assert snap["hits"]["ast"] >= 40, snap
        assert snap["verifies"]["failed"] == 0, snap
    finally:
        warm_ds.close()


def test_concurrent_writer_reader_ddl_hammer():
    d = _mk_ds(True)
    errors = []
    NT, NI = 4, 30

    def writer(t):
        try:
            for i in range(NI):
                for _ in range(20):  # first-committer-wins: retry conflicts
                    r = d.execute(
                        f"UPSERT w:{t}_{i % 5} SET v = {i}, t = {t}"
                    )[-1]
                    if r["status"] == "OK":
                        break
                    assert "conflict" in str(r["result"]), r
                else:
                    raise AssertionError(f"writer {t} never committed {i}")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            for i in range(NI):
                r = d.execute(f"SELECT * FROM w WHERE v >= {i % 7}")[-1]
                assert r["status"] == "OK", r
                for row in r["result"]:
                    # a stale plan would leak rows violating the predicate
                    assert row["v"] >= i % 7, (i, row)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def ddl():
        try:
            for i in range(8):
                d.execute("DEFINE INDEX iv ON w FIELDS v")
                d.execute("REMOVE INDEX iv ON TABLE w")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = (
        [threading.Thread(target=writer, args=(t,)) for t in range(NT)]
        + [threading.Thread(target=reader) for _ in range(3)]
        + [threading.Thread(target=ddl)]
    )
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert not errors, errors[:3]
        # converged final state == cold replay of the deterministic tail:
        # each record's last write is iteration NI-1 - ((NI-1) % 5 offset)
        final = ok(d.execute("SELECT * FROM w ORDER BY id")[-1])
        cold = _mk_ds(False)
        try:
            for t in range(NT):
                for i in range(NI):
                    cold.execute(f"UPSERT w:{t}_{i % 5} SET v = {i}, t = {t}")
            expect = ok(cold.execute("SELECT * FROM w ORDER BY id")[-1])
        finally:
            cold.close()
        assert json.dumps(final, default=str) == json.dumps(
            expect, default=str
        )
    finally:
        d.close()


# ============================================================ surfacing
def test_statements_annotation_and_bundle_section(ds):
    sql = "SELECT * FROM s WHERE v > 0"
    for i in range(3):
        ok(ds.execute(f"CREATE s:{i} SET v = {i}")[-1])
    for _ in range(4):
        ok(ds.execute(sql)[-1])
    rows = ds.plan_cache.annotate(stats.statements(limit=20))
    tagged = [r for r in rows if r["fingerprint"] == fp_of(sql)]
    assert tagged and tagged[0]["plan_cache"]["cached"], tagged
    from surrealdb_tpu.bundle import debug_bundle

    b = debug_bundle(ds)
    assert b["schema"] == "surrealdb-tpu-bundle/11"
    assert b["plan_cache"]["enabled"] is True
    assert b["plan_cache"]["hits"]["ast"] >= 1, b["plan_cache"]


def test_snapshot_recent_evictions_name_fingerprint_and_cause(ds):
    # a thrashing fingerprint: warm, flip-evict, warm again, flip-evict
    sql = "SELECT * FROM adv WHERE x > 1"
    for i in range(6):
        ok(ds.execute(f"CREATE adv:{i} SET x = {i}")[-1])
    fp = fp_of(sql)
    for _ in range(2):
        for _ in range(3):
            ok(ds.execute(sql)[-1])
        ds.plan_cache.on_plan_flip(fp)
    mine = [
        ev for ev in ds.plan_cache.snapshot()["recent_evictions"]
        if ev["fp"] == fp
    ]
    assert len(mine) == 2, mine
    assert {ev["cause"] for ev in mine} == {"flip"}
    assert all(ev["ts"] > 0 for ev in mine)


# ============================================================ record ids
# ISSUE 39: an inline record id is a slot. A flavour is how the id is
# spelled; the bindable ones (one NUMBER / STRING / UUID token) share a
# template, an identifier id is kept by the fingerprint and is its own text.
_U = "018a6680-bef9-701b-9025-e1754f29{:04x}"
ID_FLAVOURS = {
    "int": (True, lambda i: str(10 + i)),
    "ident": (False, lambda i: f"alice{i}"),
    "backtick": (False, lambda i: f"`a b{i}`"),
    "angle": (False, lambda i: f"⟨a-b{i}⟩"),
    "uuid_string": (True, lambda i: "'" + _U.format(i) + "'"),
    "uuid": (True, lambda i: "u'" + _U.format(i) + "'"),
}
ID_STATEMENTS = {
    "select": lambda a, b: f"SELECT * FROM person:{a}",
    "hop3_count": lambda a, b: "SELECT count(->knows->person->knows->person->knows->person) "
                               f"AS c FROM person:{a}",
    "update": lambda a, b: f"UPDATE person:{a} SET seen = true",
    "delete": lambda a, b: f"DELETE person:{a} RETURN BEFORE",
    "relate": lambda a, b: f"RELATE person:{a}->likes->person:{b} RETURN in, out",
    "return_field": lambda a, b: f"RETURN person:{a}.name",
    "where_id": lambda a, b: f"SELECT name FROM person WHERE id = person:{a}",
    "subquery": lambda a, b: f"SELECT name, ->knows->person AS k FROM (SELECT * FROM person:{a})",
}
N_IDS = 7


def _both(setup):
    warm_ds, cold_ds = _mk_ds(True), _mk_ds(False)
    for d in (warm_ds, cold_ds):
        for sql in setup:
            ok(d.execute(sql)[-1])
    stats.reset()
    warm_ds.plan_cache.reset_window()
    return warm_ds, cold_ds


def _same_answers(warm_ds, cold_ds, statements):
    for sql in statements:
        w, c = _norm(warm_ds.execute(sql)), _norm(cold_ds.execute(sql))
        assert w == c, f"diverged warm-vs-cold:\n  sql:  {sql}\n  warm: {w}\n  cold: {c}"
        assert '"ERR"' not in w, (sql, w)


def _id_slots(d, sql):
    """The record-id slots of every variant cached for `sql`'s shape."""
    e = d.plan_cache._entries.get(fp_of(sql))
    return [v.id_types for v in e.variants] if e is not None else []


@pytest.mark.parametrize("flavour", sorted(ID_FLAVOURS))
@pytest.mark.parametrize("kind", sorted(ID_STATEMENTS))
def test_record_id_served_from_template_equals_cold(kind, flavour, monkeypatch):
    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 0)  # a small count rides the dispatch queue too
    bindable, spell = ID_FLAVOURS[flavour]
    ids = [spell(i) for i in range(N_IDS)]
    setup = [f"CREATE person:{x} SET name = 'p{i}', n = {100 + i}" for i, x in enumerate(ids)]
    setup += [f"RELATE person:{ids[i]}->knows->person:{ids[(i * 3 + j) % N_IDS]}"
              for i in range(N_IDS) for j in (1, 2)]
    warm_ds, cold_ds = _both(setup)
    try:
        stmts = [ID_STATEMENTS[kind](ids[i], ids[(i + 1) % N_IDS]) for i in range(N_IDS)]
        _same_answers(warm_ds, cold_ds, stmts + stmts)
        snap = warm_ds.plan_cache.snapshot()
        assert snap["verifies"]["failed"] == 0, snap
        if bindable:
            # one cold parse installs the template; every other text is
            # bound into it (four of them verified), then each again by digest
            assert snap["hits"]["ast"] == 2 * N_IDS - 1, snap
            assert snap["verifies"]["ok"] == 4, snap
            assert snap["misses"] == {"cold": 1}, snap
            assert all(_id_slots(warm_ds, s) for s in stmts)
        else:
            # its own fingerprint a text: parsed once, then served by digest
            assert snap["hits"]["ast"] == N_IDS, snap
            assert not any(t for s in stmts for t in _id_slots(warm_ds, s))
    finally:
        warm_ds.close()
        cold_ds.close()


# what must stay fixed: (setup, statements, whether the odd texts may share a
# shape with a template that has a record-id slot)
_INTS = [f"SELECT * FROM person:{10 + i} PARALLEL" for i in range(6)]
FIXED_CASES = {
    "flexible": (["CREATE likes:8abc2 SET n = 1", "CREATE likes:9abc2 SET n = 2", "CREATE likes:17abc2 SET n = 3"],
                 ["SELECT * FROM likes:8abc2", "SELECT * FROM likes:9abc2", "SELECT * FROM likes:17abc2"] * 2, False),
    "range": ([], [f"SELECT * FROM person:{i}..{i + 5}" for i in range(8, 16)], False),
    "array": ([f"CREATE person:[{i}, 'x'] SET n = {i}" for i in range(6)],
              [f"SELECT * FROM person:[{i}, 'x']" for i in range(6)] * 2, False),
    "object": ([f"CREATE person:{{ a: {i} }} SET n = {i}" for i in range(6)],
               [f"SELECT * FROM person:{{ a: {i} }}" for i in range(6)] * 2, False),
    "duplicated": ([], [f"SELECT * FROM person:{i} WHERE n = {i}" for i in range(10, 16)] * 2, False),
    "negative": ([f"CREATE person:-{i} SET n = {i}" for i in range(1, 7)],
                 [f"SELECT * FROM person:-{i}" for i in range(1, 7)] * 2, False),
    "expression_id": ([], [f"SELECT * FROM person:[{i} + 1]" for i in range(6)] * 2, False),
    # the parser builds int 42 for `person:42` and a string for each of these
    "int_against_string": (["CREATE person:⟨42⟩ SET n = 'str'"],
                           _INTS + ["SELECT * FROM person:'42' PARALLEL", "SELECT * FROM person:⟨42⟩ PARALLEL",
                                    "SELECT * FROM person:42 PARALLEL"], True),
    "number_read_as_text": (["CREATE person:⟨8e2⟩ SET n = 'e'", "CREATE person:⟨5f⟩ SET n = 'f'",
                             "CREATE person:⟨1h⟩ SET n = 'h'", "CREATE person:800 SET n = 800"],
                            _INTS + ["SELECT * FROM person:8e2 PARALLEL", "SELECT * FROM person:5f PARALLEL",
                                     "SELECT * FROM person:1h PARALLEL", "SELECT * FROM person:800 PARALLEL"], True),
    "glued_identifier": (["CREATE person:⟨9PARALLEL⟩ SET n = 'glued'"],
                         _INTS + ["SELECT * FROM person:9PARALLEL", "SELECT * FROM person:9 PARALLEL"], True),
}


@pytest.mark.parametrize("case", sorted(FIXED_CASES))
def test_record_id_that_must_stay_fixed(case):
    setup, stmts, beside_a_template = FIXED_CASES[case]
    setup = [f"CREATE person:{i} SET n = {i}" for i in range(8, 22)] + setup
    warm_ds, cold_ds = _both(setup)
    try:
        _same_answers(warm_ds, cold_ds, stmts)
        assert warm_ds.plan_cache.snapshot()["verifies"]["failed"] == 0
        if beside_a_template:
            # the ints earned trust: whatever came after was held to the
            # parser's reading by the signature alone
            assert warm_ds.plan_cache.snapshot()["verifies"]["ok"] == 4
            assert any(t for t in _id_slots(warm_ds, _INTS[0]))
        else:
            assert not any(t for s in stmts for t in _id_slots(warm_ds, s)), case
    finally:
        warm_ds.close()
        cold_ds.close()


def test_unaliased_projection_keeps_its_own_text_as_key(ds):
    """`SELECT 11, n` is keyed by repr(expr): a shared template would print
    the first-seen literal, so such a literal is no slot."""
    ok(ds.execute("CREATE person:1 SET n = 1, name = 'x'")[-1])
    for i in range(10, 17):
        assert ok(ds.execute(f"SELECT {i}, n FROM person")[-1]) == [{str(i): i, "n": 1}]
        row = ok(ds.execute(f"SELECT person:1.name, {i} AS k, math::max([n, {i + 100}]) FROM person")[-1])[0]
        assert row == {"person:1.name": "x", "k": i, "math::max": i + 100}
        assert ok(ds.execute(f"SELECT VALUE n + {i} FROM person")[-1]) == [1 + i]
    snap = ds.plan_cache.snapshot()
    assert snap["verifies"] == {"ok": 8, "failed": 0}, snap  # the aliased and the VALUE shapes bind


def test_graph_idiom_statement_earns_trust(ds):
    """PGraph names a slot the parser never sets: unset on both sides is equal."""
    for i in range(12):
        ok(ds.execute(f"CREATE person:{i} SET n = {100 + i}")[-1])
        ok(ds.execute(f"RELATE person:{i}->knows->person:{(i + 1) % 12}")[-1])
    ds.plan_cache.reset_window()
    for k in range(100, 112):
        assert ok(ds.execute(f"SELECT count(->knows->person) AS c FROM person WHERE n = {k}")[-1]) == [{"c": 1}]
    snap = ds.plan_cache.snapshot()
    assert snap["verifies"] == {"ok": 4, "failed": 0}, snap
    assert snap["hits"]["ast"] == 11, snap


def test_ast_equal_slot_set_on_one_side_only_is_unequal():
    from surrealdb_tpu.dbs.plan_cache import _ast_equal
    from surrealdb_tpu.syn import parse_query

    sql = "SELECT count(->knows->person) AS c FROM person"
    a, b = parse_query(sql).statements[0], parse_query(sql).statements[0]
    graph = lambda stm: stm.fields[0].expr.args[0].parts[0]  # noqa: E731
    assert not hasattr(graph(a), "expr_fields")  # the lazily set slot
    assert _ast_equal(a, b, ())
    graph(a).expr_fields = None
    assert not _ast_equal(a, b, ()) and not _ast_equal(b, a, ())
    graph(b).expr_fields = None
    assert _ast_equal(a, b, ())


def test_observe_refuses_an_unbindable_churning_shape_before_it_works(ds, monkeypatch):
    from surrealdb_tpu.dbs import plan_cache as pc

    calls = []
    real = pc._parameterize
    monkeypatch.setattr(pc, "_parameterize", lambda text, q: calls.append(text) or real(text, q))
    sql = "SELECT * FROM likes:{}abc2"
    for i in range(13):  # four variants, then nine capacity evictions
        ok(ds.execute(sql.format(i))[-1])
    entry = ds.plan_cache._entries[fp_of(sql.format(0))]
    assert len(calls) == 13 and entry.churn == 9 and not entry.bound
    for i in range(13, 53):
        ok(ds.execute(sql.format(i))[-1])
    assert len(calls) == 13 and entry.refused == 40
    # a shape that binds is never turned away, however it churned before
    entry.bound = True
    ok(ds.execute(sql.format(99))[-1])
    assert len(calls) == 14


def test_plan_fetch_span_says_how_the_plan_was_found(ds):
    from surrealdb_tpu import tracing

    def outcome(tid, sql):
        with tracing.request("test", trace_id=tid):
            ok(ds.execute(sql)[-1])
        return [s["labels"]["outcome"] for s in tracing.get_trace(tid)["spans"] if s["name"] == "plan_fetch"]

    ok(ds.execute("CREATE person:1 SET n = 1")[-1])
    assert outcome("pf-parse", "SELECT * FROM person:1") == ["parse"]
    assert outcome("pf-lexed", "SELECT * FROM person:2") == ["lexed"]
    assert outcome("pf-digest", "SELECT * FROM person:2") == ["digest"]
