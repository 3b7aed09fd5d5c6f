"""End-to-end SurrealQL execution tests (mirrors the reference's SQL-driven
sdk/tests/*.rs harness style: execute query strings against an in-memory
datastore, assert value-level results)."""

import pytest

from surrealdb_tpu.sql.value import NONE, Null, Thing


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


def err(resp):
    assert resp["status"] == "ERR", resp
    return resp["result"]


def test_create_and_select(ds):
    r = ds.execute("CREATE person:1 SET name = 'tobie', age = 33;")
    row = ok(r[0])[0]
    assert row["name"] == "tobie"
    assert row["age"] == 33
    assert row["id"] == Thing("person", 1)

    r = ds.execute("SELECT * FROM person;")
    rows = ok(r[0])
    assert len(rows) == 1
    assert rows[0]["name"] == "tobie"


def test_create_duplicate_errors(ds):
    ds.execute("CREATE person:1;")
    r = ds.execute("CREATE person:1;")
    assert "already exists" in err(r[0])


def test_create_random_id(ds):
    r = ds.execute("CREATE person SET x = 1;")
    row = ok(r[0])[0]
    assert isinstance(row["id"], Thing)
    assert row["id"].tb == "person"


def test_select_projection_and_where(ds):
    ds.execute(
        "CREATE person:1 SET name = 'a', age = 10;"
        "CREATE person:2 SET name = 'b', age = 20;"
        "CREATE person:3 SET name = 'c', age = 30;"
    )
    r = ds.execute("SELECT name FROM person WHERE age > 15 ORDER BY name;")
    assert ok(r[0]) == [{"name": "b"}, {"name": "c"}]

    r = ds.execute("SELECT VALUE name FROM person ORDER BY name DESC;")
    assert ok(r[0]) == ["c", "b", "a"]

    r = ds.execute("SELECT name, age * 2 AS dbl FROM person:2;")
    assert ok(r[0]) == [{"name": "b", "dbl": 40}]


def test_select_limit_start(ds):
    ds.execute("CREATE person:1 SET n = 1; CREATE person:2 SET n = 2; CREATE person:3 SET n = 3;")
    r = ds.execute("SELECT VALUE n FROM person ORDER BY n LIMIT 2 START 1;")
    assert ok(r[0]) == [2, 3]


def test_update_set_and_where(ds):
    ds.execute("CREATE person:1 SET age = 10; CREATE person:2 SET age = 20;")
    r = ds.execute("UPDATE person SET age += 1 WHERE age > 15;")
    rows = ok(r[0])
    assert len(rows) == 1
    assert rows[0]["age"] == 21
    # other record untouched
    r = ds.execute("SELECT VALUE age FROM person:1;")
    assert ok(r[0]) == [10]


def test_update_nonexistent_is_noop(ds):
    r = ds.execute("UPDATE person:404 SET x = 1;")
    assert ok(r[0]) == []


def test_upsert_creates(ds):
    r = ds.execute("UPSERT person:9 SET name = 'new';")
    assert ok(r[0])[0]["name"] == "new"
    r = ds.execute("UPSERT person:9 SET name = 'upd';")
    assert ok(r[0])[0]["name"] == "upd"


def test_delete(ds):
    ds.execute("CREATE person:1; CREATE person:2;")
    r = ds.execute("DELETE person:1;")
    assert ok(r[0]) == []
    r = ds.execute("SELECT VALUE id FROM person;")
    assert ok(r[0]) == [Thing("person", 2)]


def test_content_merge_patch(ds):
    ds.execute("CREATE person:1 SET a = 1, b = 2;")
    r = ds.execute("UPDATE person:1 CONTENT { c: 3 };")
    row = ok(r[0])[0]
    assert "a" not in row and row["c"] == 3

    r = ds.execute("UPDATE person:1 MERGE { d: 4 };")
    row = ok(r[0])[0]
    assert row["c"] == 3 and row["d"] == 4

    r = ds.execute('UPDATE person:1 PATCH [{ "op": "replace", "path": "/c", "value": 9 }];')
    assert ok(r[0])[0]["c"] == 9


def test_return_clauses(ds):
    r = ds.execute("CREATE person:1 SET x = 1 RETURN NONE;")
    assert ok(r[0]) == []
    r = ds.execute("UPDATE person:1 SET x = 2 RETURN BEFORE;")
    assert ok(r[0])[0]["x"] == 1
    r = ds.execute("UPDATE person:1 SET x = 3 RETURN DIFF;")
    diff = ok(r[0])[0]
    assert any(op["path"] == "/x" for op in diff)
    r = ds.execute("UPDATE person:1 SET x = 4 RETURN x;")
    assert ok(r[0]) == [{"x": 4}]


def test_insert(ds):
    r = ds.execute("INSERT INTO company { name: 'SurrealDB', founded: 2021 };")
    assert ok(r[0])[0]["name"] == "SurrealDB"
    r = ds.execute(
        "INSERT INTO company [{ id: company:x, name: 'X' }, { name: 'Y' }];"
    )
    rows = ok(r[0])
    assert len(rows) == 2
    r = ds.execute("INSERT INTO company (name, founded) VALUES ('A', 2000), ('B', 2001);")
    assert [x["name"] for x in ok(r[0])] == ["A", "B"]


def test_insert_ignore_and_duplicate(ds):
    ds.execute("INSERT INTO t { id: t:1, v: 1 };")
    r = ds.execute("INSERT IGNORE INTO t { id: t:1, v: 2 };")
    assert ok(r[0]) == []
    r = ds.execute("INSERT INTO t { id: t:1, v: 2 } ON DUPLICATE KEY UPDATE v = 9;")
    assert ok(r[0])[0]["v"] == 9


def test_relate_and_graph_traversal(ds):
    ds.execute(
        "CREATE person:1 SET name = 'a';"
        "CREATE person:2 SET name = 'b';"
        "CREATE person:3 SET name = 'c';"
    )
    ok_r = ds.execute("RELATE person:1->knows->person:2 SET weight = 0.5;")
    edge = ok(ok_r[0])[0]
    assert edge["in"] == Thing("person", 1)
    assert edge["out"] == Thing("person", 2)
    assert edge["weight"] == 0.5
    ds.execute("RELATE person:2->knows->person:3;")

    r = ds.execute("SELECT VALUE ->knows->person.name FROM person:1;")
    assert ok(r[0]) == [["b"]]

    # two hops
    r = ds.execute("SELECT VALUE ->knows->person->knows->person.name FROM person:1;")
    assert ok(r[0]) == [["c"]]

    # reverse
    r = ds.execute("SELECT VALUE <-knows<-person.name FROM person:2;")
    assert ok(r[0]) == [["a"]]


def test_graph_where_filter(ds):
    ds.execute(
        "CREATE person:1; CREATE person:2 SET age = 10; CREATE person:3 SET age = 30;"
        "RELATE person:1->knows->person:2;"
        "RELATE person:1->knows->person:3;"
    )
    r = ds.execute("SELECT VALUE ->knows->(person WHERE age > 20).age FROM person:1;")
    assert ok(r[0]) == [[30]]


def test_delete_cascades_edges(ds):
    ds.execute(
        "CREATE person:1; CREATE person:2;"
        "RELATE person:1->knows->person:2;"
    )
    ds.execute("DELETE person:2;")
    r = ds.execute("SELECT VALUE ->knows->person FROM person:1;")
    assert ok(r[0]) == [[]]
    # edge record itself removed
    r = ds.execute("SELECT * FROM knows;")
    assert ok(r[0]) == []


def test_group_by(ds):
    ds.execute(
        "CREATE p:1 SET city = 'x', pop = 10;"
        "CREATE p:2 SET city = 'x', pop = 20;"
        "CREATE p:3 SET city = 'y', pop = 5;"
    )
    r = ds.execute(
        "SELECT city, count() AS n, math::sum(pop) AS total FROM p GROUP BY city ORDER BY city;"
    )
    assert ok(r[0]) == [
        {"city": "x", "n": 2, "total": 30},
        {"city": "y", "n": 1, "total": 5},
    ]


def test_group_all(ds):
    ds.execute("CREATE p:1 SET v = 1; CREATE p:2 SET v = 2;")
    r = ds.execute("SELECT count() AS c, math::mean(v) AS m FROM p GROUP ALL;")
    assert ok(r[0]) == [{"c": 2, "m": 1.5}]


def test_split(ds):
    ds.execute("CREATE p:1 SET tags = ['a', 'b'];")
    r = ds.execute("SELECT tags FROM p SPLIT tags;")
    assert ok(r[0]) == [{"tags": "a"}, {"tags": "b"}]


def test_fetch(ds):
    ds.execute(
        "CREATE person:1 SET name = 'a';"
        "CREATE post:1 SET author = person:1, title = 't';"
    )
    r = ds.execute("SELECT * FROM post FETCH author;")
    row = ok(r[0])[0]
    assert row["author"]["name"] == "a"


def test_record_ranges(ds):
    ds.execute("CREATE t:1; CREATE t:2; CREATE t:3; CREATE t:4;")
    r = ds.execute("SELECT VALUE id FROM t:2..4;")
    assert ok(r[0]) == [Thing("t", 2), Thing("t", 3)]
    r = ds.execute("SELECT VALUE id FROM t:2..=4;")
    assert ok(r[0]) == [Thing("t", 2), Thing("t", 3), Thing("t", 4)]


def test_transactions_commit(ds):
    r = ds.execute(
        "BEGIN; CREATE person:1 SET x = 1; COMMIT; SELECT VALUE x FROM person:1;"
    )
    assert ok(r[0])[0]["x"] == 1
    assert ok(r[1]) == [1]


def test_transactions_cancel(ds):
    r = ds.execute("BEGIN; CREATE person:1; CANCEL; SELECT * FROM person;")
    assert r[0]["status"] == "ERR"
    assert "cancelled" in r[0]["result"]
    assert ok(r[1]) == []


def test_transactions_failure_rolls_back(ds):
    r = ds.execute(
        "BEGIN; CREATE person:1; CREATE person:1; COMMIT; SELECT * FROM person;"
    )
    # both statements errored (second poisoned the txn)
    assert r[0]["status"] == "ERR"
    assert r[1]["status"] == "ERR"
    assert ok(r[2]) == []


def test_let_and_params(ds):
    r = ds.execute("LET $x = 40; RETURN $x + 2;")
    assert ok(r[1]) == 42


def test_if_else(ds):
    r = ds.execute("RETURN IF 1 > 2 { 'a' } ELSE { 'b' };")
    assert ok(r[0]) == "b"


def test_for_loop(ds):
    r = ds.execute(
        "FOR $i IN [1, 2, 3] { CREATE type::thing('n', $i); }; SELECT VALUE id FROM n;"
    )
    assert len(ok(r[1])) == 3


def test_define_field_type_coercion(ds):
    ds.execute("DEFINE TABLE person SCHEMALESS; DEFINE FIELD age ON person TYPE int;")
    r = ds.execute("CREATE person:1 SET age = 42;")
    assert ok(r[0])[0]["age"] == 42
    # 42.0 is an integral float: coerces to int (reference int coercion)
    r = ds.execute("CREATE person:2 SET age = 42.0;")
    assert ok(r[0])[0]["age"] == 42
    # strings do NOT coerce (strict typing, reference behavior)
    r = ds.execute("CREATE person:3 SET age = 'nope';")
    assert "age" in err(r[0])


def test_define_field_default_and_value(ds):
    ds.execute(
        "DEFINE FIELD counted ON t DEFAULT 7;"
        "DEFINE FIELD dbl ON t VALUE $value * 2;"
    )
    r = ds.execute("CREATE t:1 SET dbl = 5;")
    row = ok(r[0])[0]
    assert row["counted"] == 7
    assert row["dbl"] == 10


def test_define_field_assert(ds):
    ds.execute("DEFINE FIELD email ON user ASSERT string::contains($value, '@');")
    r = ds.execute("CREATE user:1 SET email = 'a@b.c';")
    assert ok(r[0])[0]["email"] == "a@b.c"
    r = ds.execute("CREATE user:2 SET email = 'bogus';")
    assert "email" in err(r[0])


def test_schemafull_drops_undefined(ds):
    ds.execute(
        "DEFINE TABLE strict SCHEMAFULL; DEFINE FIELD a ON strict TYPE int;"
    )
    r = ds.execute("CREATE strict:1 SET a = 1, b = 2;")
    row = ok(r[0])[0]
    assert row["a"] == 1
    assert "b" not in row


def test_unique_index(ds):
    ds.execute("DEFINE INDEX email_ix ON user FIELDS email UNIQUE;")
    ds.execute("CREATE user:1 SET email = 'a@b.c';")
    r = ds.execute("CREATE user:2 SET email = 'a@b.c';")
    assert "already contains" in err(r[0])
    # updating the holder is fine
    r = ds.execute("UPDATE user:1 SET email = 'a@b.c', x = 1;")
    assert ok(r[0])[0]["x"] == 1


def test_index_plan_used(ds):
    ds.execute("DEFINE INDEX age_ix ON person FIELDS age;")
    for i in range(5):
        ds.execute(f"CREATE person:{i} SET age = {i * 10};")
    r = ds.execute("SELECT VALUE age FROM person WHERE age = 20;")
    assert ok(r[0]) == [20]
    r = ds.execute("SELECT * FROM person WHERE age = 20 EXPLAIN;")
    plan = ok(r[0])
    assert plan[0]["operation"] == "Iterate Index"
    assert plan[0]["detail"]["plan"]["index"] == "age_ix"


def test_index_range_plan(ds):
    ds.execute("DEFINE INDEX age_ix ON person FIELDS age;")
    for i in range(5):
        ds.execute(f"CREATE person:{i} SET age = {i * 10};")
    r = ds.execute("SELECT VALUE age FROM person WHERE age > 15 ORDER BY age;")
    assert ok(r[0]) == [20, 30, 40]


def test_events(ds):
    ds.execute(
        "DEFINE EVENT audit ON person WHEN $event = 'CREATE' THEN ("
        " CREATE log SET about = $after.id );"
    )
    ds.execute("CREATE person:1;")
    r = ds.execute("SELECT VALUE about FROM log;")
    assert ok(r[0]) == [Thing("person", 1)]


def test_info_for_db(ds):
    ds.execute("DEFINE TABLE t1; DEFINE TABLE t2;")
    r = ds.execute("INFO FOR DB;")
    info = ok(r[0])
    assert set(info["tables"].keys()) == {"t1", "t2"}


def test_only(ds):
    ds.execute("CREATE person:1 SET x = 1;")
    r = ds.execute("SELECT * FROM ONLY person:1;")
    assert ok(r[0])["x"] == 1
    r = ds.execute("CREATE ONLY person:2 SET y = 2;")
    assert ok(r[0])["y"] == 2


def test_changefeed(ds):
    ds.execute("DEFINE TABLE reading CHANGEFEED 1h;")
    ds.execute("CREATE reading:1 SET v = 9;")
    ds.execute("UPDATE reading:1 SET v = 10;")
    ds.execute("DELETE reading:1;")
    r = ds.execute("SHOW CHANGES FOR TABLE reading SINCE 0;")
    sets = ok(r[0])
    kinds = [list(c.keys())[0] for s in sets for c in s["changes"]]
    assert kinds == ["update", "update", "delete"]


def test_subquery_and_parent(ds):
    ds.execute("CREATE person:1 SET age = 10; CREATE person:2 SET age = 20;")
    r = ds.execute("SELECT age, (SELECT VALUE age FROM person WHERE age > $parent.age) AS older FROM person:1;")
    row = ok(r[0])[0]
    assert row["older"] == [20]


def test_remove_table(ds):
    ds.execute("CREATE t:1;")
    ds.execute("REMOVE TABLE t;")
    r = ds.execute("SELECT * FROM t;")
    assert ok(r[0]) == []


def test_mock_source(ds):
    r = ds.execute("CREATE |m:5|;")
    assert len(ok(r[0])) == 5


@pytest.mark.parametrize("sql, want", [
    ("SELECT VALUE n FROM [t:3, t:1, t:9, t:2]", [3, 1, 2]),
    ("SELECT VALUE n FROM [t:3, t:1, t:9, t:2] LIMIT 2", [3, 1]),
    ("SELECT VALUE n FROM [t:3, t:9, t:1, t:2] LIMIT 1 START 1", [1]),
    ("SELECT VALUE n FROM (SELECT VALUE id FROM t WHERE n > 1) ORDER BY n DESC", [3, 2]),
    ("SELECT VALUE n FROM [t:1, t:1]", [1, 1]),
    ("SELECT VALUE n FROM [t:2, 7, t:1]", [2, NONE, 1]),
])
def test_an_array_of_record_ids_is_one_source_read_in_order(ds, sql, want):
    """`FROM <array of record ids>` (what a graph traversal hands over) is
    one IThings source: the records in the array's order, a missing one
    skipped, read until the statement is full; a mixed array keeps the
    per-value path."""
    ds.execute("CREATE t:1 SET n = 1; CREATE t:2 SET n = 2; CREATE t:3 SET n = 3;")
    assert ok(ds.execute(sql)[0]) == want


def test_an_array_of_record_ids_explains_updates_and_deletes_an_id_at_a_time(ds):
    ds.execute("CREATE t:1 SET n = 1; CREATE t:2 SET n = 2; CREATE t:3 SET n = 3;")
    plan = ok(ds.execute("SELECT * FROM [t:2, t:1] EXPLAIN")[0])
    assert [(p["operation"], p["detail"]["thing"]) for p in plan] == [
        ("Iterate Thing", Thing("t", 2)), ("Iterate Thing", Thing("t", 1))]
    assert [r["n"] for r in ok(ds.execute("UPDATE [t:1, t:3] SET n += 10")[0])] == [11, 13]
    ds.execute("DELETE [t:1, t:2]")
    assert ok(ds.execute("SELECT VALUE n FROM t")[0]) == [13]


@pytest.mark.parametrize("parallel, sources", [(False, ["IThings"]), (True, ["IThing", "IThing", "IThing"])])
def test_a_parallel_select_keeps_a_source_an_id_so_their_dispatches_overlap(ds, monkeypatch, parallel, sources):
    """PARALLEL runs the statement's sources side by side
    (Iterator._iterate_parallel), which is where per-record device
    dispatches coalesce: there an array of record ids stays a source an id."""
    from surrealdb_tpu.dbs import iterator

    ds.execute("CREATE t:1 SET n = 1; CREATE t:2 SET n = 2; CREATE t:3 SET n = 3;")
    seen, side_by_side = [], []
    ingest = iterator.classify_sources
    run = iterator.Iterator._iterate_parallel
    monkeypatch.setattr("surrealdb_tpu.dbs.stmt_exec.classify_sources",
                        lambda *a, **k: seen.append([type(s).__name__ for s in ingest(*a, **k)]) or ingest(*a, **k))
    monkeypatch.setattr(iterator.Iterator, "_iterate_parallel", lambda self: side_by_side.append(len(self.entries)) or run(self))
    rows = ok(ds.execute("SELECT VALUE n FROM [t:3, t:1, t:2]" + (" PARALLEL" if parallel else ""))[0])
    assert rows == [3, 1, 2] and seen == [sources]
    assert side_by_side == ([3] if parallel else [])
