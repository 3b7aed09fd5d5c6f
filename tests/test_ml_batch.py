"""Batched ml:: over table scans + model permissions (VERDICT r2 item 5;
reference: core/src/sql/model.rs Model::compute permission check)."""

import pytest

from surrealdb_tpu.dbs.session import Session


LINEAR = {
    "format": "linear",
    "layers": [{"w": [[2.0], [3.0]], "b": [10.0], "activation": None}],
}


def _import(ds, name="score", version="1", perms_sql=""):
    from surrealdb_tpu.ml.exec import import_model

    ds.execute(f"DEFINE MODEL ml::{name}<{version}> {perms_sql};")
    import_model(ds, Session.owner(), name, version, LINEAR)


def _compiled_model(ds, name="score", version="1"):
    return ds._ml_cache[("test", "test", name, version)]


def test_select_scan_is_one_dispatch(ds):
    """N scanned rows -> exactly ONE CompiledModel.forward dispatch."""
    _import(ds)
    ds.execute(";".join(f"CREATE h:{i} SET f = [{i}.0, {i}.0]" for i in range(20)))
    out = ds.execute("SELECT id, ml::score<1>(f) AS s FROM h ORDER BY id;")
    rows = out[0]["result"]
    assert len(rows) == 20
    assert rows[3]["s"] == pytest.approx(10.0 + 5.0 * 3)
    cm = _compiled_model(ds)
    assert cm.dispatches == 1


def test_batched_matches_per_row_values(ds):
    _import(ds)
    ds.execute(";".join(f"CREATE h:{i} SET f = [{i}.0, {2*i}.0]" for i in range(7)))
    out = ds.execute("SELECT VALUE ml::score<1>(f) FROM h ORDER BY id;")
    assert out[0]["result"] == pytest.approx([10.0 + 2.0 * i + 6.0 * i for i in range(7)])


def test_batched_with_where_and_limit(ds):
    _import(ds)
    ds.execute(";".join(f"CREATE h:{i} SET f = [{i}.0, {i}.0], n = {i}" for i in range(10)))
    out = ds.execute(
        "SELECT id, ml::score<1>(f) AS s FROM h WHERE n >= 4 ORDER BY id LIMIT 3;"
    )
    rows = out[0]["result"]
    assert [r["s"] for r in rows] == pytest.approx([30.0, 35.0, 40.0])
    assert _compiled_model(ds).dispatches == 1


def test_rows_missing_field_fall_back(ds):
    """A row without the feature field only errors if the call is reached;
    under a conditional the scan still succeeds."""
    _import(ds)
    ds.execute("CREATE h:1 SET f = [1.0, 1.0]; CREATE h:2 SET g = 1;")
    out = ds.execute(
        "SELECT id, IF f THEN ml::score<1>(f) ELSE 0 END AS s FROM h ORDER BY id;"
    )
    rows = out[0]["result"]
    assert rows[0]["s"] == pytest.approx(15.0)
    assert rows[1]["s"] == 0
    # the reachable row was still served by the batch, not inline
    assert _compiled_model(ds).dispatches == 1


def test_nested_subquery_model_calls(ds):
    """A deferred subquery with its own ml:: calls must not clobber the
    outer projection's batch overrides."""
    _import(ds)
    ds.execute(";".join(f"CREATE h:{i} SET f = [{i}.0, {i}.0]" for i in range(4)))
    ds.execute("CREATE g:1 SET f = [1.0, 1.0];")
    out = ds.execute(
        "SELECT ml::score<1>(f) AS a, "
        "(SELECT VALUE ml::score<1>(f) FROM g) AS b FROM h ORDER BY id;"
    )
    rows = out[0]["result"]
    assert [r["a"] for r in rows] == pytest.approx([10.0, 15.0, 20.0, 25.0])
    assert all(r["b"] == pytest.approx([15.0]) for r in rows)


def test_model_permissions_none_denies_guest(ds):
    _import(ds, perms_sql="PERMISSIONS NONE")
    ds.execute("DEFINE TABLE pub PERMISSIONS FULL; CREATE pub:1 SET f = [1.0, 2.0];")
    anon = Session.anonymous("test", "test")
    out = ds.execute("SELECT ml::score<1>(f) AS s FROM pub;", anon)
    assert out[0]["status"] == "ERR"
    assert "not allow execution" in out[0]["result"]
    # owner unaffected
    out = ds.execute("SELECT ml::score<1>(f) AS s FROM pub;")
    assert out[0]["result"][0]["s"] == pytest.approx(18.0)


def test_model_permissions_full_admits_guest(ds):
    _import(ds, perms_sql="PERMISSIONS FULL")
    ds.execute("DEFINE TABLE pub PERMISSIONS FULL; CREATE pub:1 SET f = [1.0, 2.0];")
    anon = Session.anonymous("test", "test")
    out = ds.execute("SELECT ml::score<1>(f) AS s FROM pub;", anon)
    assert out[0]["status"] == "OK"
    assert out[0]["result"][0]["s"] == pytest.approx(18.0)


def test_function_permissions_none_denies_guest(ds):
    ds.execute("DEFINE FUNCTION fn::sq($x: number) { RETURN $x * $x } PERMISSIONS NONE;")
    ds.execute("DEFINE TABLE pub PERMISSIONS FULL; CREATE pub:1 SET v = 3;")
    anon = Session.anonymous("test", "test")
    out = ds.execute("SELECT fn::sq(v) AS s FROM pub;", anon)
    assert out[0]["status"] == "ERR"
    out = ds.execute("RETURN fn::sq(3);")
    assert out[0]["result"] == 9


# ------------------------------------------------------------------ columnar
def test_columnar_scan_over_vector_mirror(ds):
    """SELECT VALUE ml::m(field) FROM t with a vector index on `field`
    scores the device-resident mirror in ONE dispatch, matching the
    row-collected path's values."""
    _import(ds)
    ds.execute("DEFINE INDEX iv ON h FIELDS f HNSW DIMENSION 2;")
    ds.execute(";".join(f"CREATE h:{i} SET f = [{i}.0, {2*i}.0]" for i in range(12)))
    out = ds.execute("SELECT VALUE ml::score<1>(f) FROM h;")
    vals = sorted(out[-1]["result"])
    assert vals == sorted(10.0 + 2.0 * i + 6.0 * i for i in range(12))
    cm = _compiled_model(ds)
    assert cm.dispatches == 1

    # a WHERE clause falls back to the row path (still batched, 1 dispatch)
    out = ds.execute("SELECT VALUE ml::score<1>(f) FROM h WHERE f[0] > 5;")
    assert len(out[-1]["result"]) == 6
    assert cm.dispatches == 2


def test_columnar_scan_skipped_when_mirror_incomplete(ds):
    """A record missing the indexed field keeps the row path (the columnar
    scan would silently drop it instead of erroring per-row)."""
    _import(ds)
    ds.execute("DEFINE INDEX iv ON h FIELDS f HNSW DIMENSION 2;")
    ds.execute("CREATE h:1 SET f = [1.0, 1.0]; CREATE h:2 SET g = 1;")
    out = ds.execute("SELECT VALUE ml::score<1>(f) FROM h;")
    assert out[-1]["status"] == "ERR"  # row 2's missing field errors, as per-row does


def test_columnar_scan_skipped_inside_write_txn(ds):
    _import(ds)
    ds.execute("DEFINE INDEX iv ON h FIELDS f HNSW DIMENSION 2;")
    ds.execute("CREATE h:1 SET f = [1.0, 1.0];")
    out = ds.execute(
        "BEGIN; CREATE h:2 SET f = [2.0, 2.0]; "
        "SELECT VALUE ml::score<1>(f) FROM h; COMMIT;"
    )
    # the uncommitted row must be visible -> row path, 2 results
    assert len(out[-1]["result"]) == 2


def test_columnar_scan_key_order_after_mixed_inserts(ds):
    """Columnar results come back in table key order, matching the row
    path, even when mirror slot order differs (review r3 regression)."""
    _import(ds)
    ds.execute("DEFINE INDEX iv ON h FIELDS f HNSW DIMENSION 2;")
    ds.execute(";".join(f"CREATE h:{i} SET f = [{i}.0, {i}.0]" for i in (5, 6, 7)))
    ds.execute("SELECT VALUE ml::score<1>(f) FROM h;")  # build mirror
    ds.execute("CREATE h:1 SET f = [1.0, 1.0];")  # appends to a later slot
    fast = ds.execute("SELECT VALUE ml::score<1>(f) FROM h;")[-1]["result"]
    slow = ds.execute("SELECT VALUE ml::score<1>(f) FROM h WHERE f[0] >= 0;")[-1]["result"]
    assert fast == slow  # positionally identical, key order


# ------------------------------------------------------------------ the parser's note of a projection's ml:: calls
# (ISSUE 46) {shape: (the statement with a literal to vary, the calls its OUTER projection batches)}
NOTED = {
    "plain": ("SELECT id, ml::score<1>(f) AS s FROM h WHERE n >= {n} ORDER BY id", 1),
    "in_arguments": ("SELECT id, math::max([ml::score<1>(f), 0]) AS s FROM h WHERE n >= {n} ORDER BY id", 1),
    "in_subquery": ("SELECT id, (SELECT VALUE ml::score<1>(f) FROM g) AS s FROM h WHERE n >= {n} ORDER BY id", 0),
    "none": ("SELECT id, n AS s FROM h WHERE n >= {n} ORDER BY id", 0),
}


@pytest.mark.parametrize("shape", sorted(NOTED))
def test_the_parser_s_note_is_what_the_walk_finds_however_the_statement_is_served(ds, monkeypatch, shape):
    """The calls the batched scoring runs are the nodes `ast.model_calls`
    finds in the field list (not those of a subquery, which binds another
    document; those in a function's arguments, yes), whether the statement
    was parsed, bound into its template (`lexed`) or found by its text
    (`digest`); and once the text is parsed nothing walks a field list."""
    from surrealdb_tpu import tracing
    from surrealdb_tpu.dbs import iterator
    from surrealdb_tpu.sql import ast

    _import(ds)
    ds.execute(";".join(f"CREATE h:{i} SET f = [{i}.0, {i}.0], n = {i}" for i in range(6)))
    ds.execute("CREATE g:1 SET f = [1.0, 1.0];")
    sql, outer = NOTED[shape]
    real_walk = ast.walk_exprs
    found, walks, real_find = [], [], iterator.find_model_calls
    monkeypatch.setattr(iterator, "find_model_calls", lambda stm: found.append((stm, real_find(stm))) or found[-1][1])

    def walk_spy(node, visit, _depth=0):
        if not _depth:  # the walk recurses through the module's name
            walks.append(node)
        return real_walk(node, visit, _depth)

    monkeypatch.setattr(ast, "walk_exprs", walk_spy)
    answers = {}
    for how, n in (("parse", 3), ("parse", 3), ("digest", 3), ("lexed", 4)):  # the second parse installs the template
        del found[:], walks[:]
        with tracing.request("test", trace_id=f"noted-{shape}-{how}"):
            (res,) = ds.execute(sql.format(n=n))
        assert res["status"] == "OK", res
        spans = tracing.get_trace(f"noted-{shape}-{how}")["spans"]
        assert [s["labels"]["outcome"] for s in spans if s["name"] == "plan_fetch"] == [how]
        # the walk runs where a parser has read an `ml::` inside a field list (the subquery's is inside the outer
        # SELECT's too), so in a parse, and in the one a `lexed` serve is verified by; no execution walks
        assert len(walks) == (0 if how == "digest" else {"none": 0, "in_subquery": 2}.get(shape, 1))
        # the outer SELECT's iterator is made first; the others are the subquery's, one a row
        assert [len(calls) for _, calls in found] == [outer] + [1] * (len(found) - 1)
        for stm, calls in found:
            assert stm.ml_calls is not None and list(map(id, calls)) == list(map(id, ast.model_calls(stm.fields)))
        del walks[:]
        answers[how] = res["result"]
    assert answers["parse"] == answers["digest"] and len(answers["parse"]) == 3 and answers["lexed"] == answers["parse"][1:]
    if shape in ("plain", "in_arguments"):
        assert [r["s"] for r in answers["parse"]] == pytest.approx([25.0, 30.0, 35.0])
        assert _compiled_model(ds).dispatches == 4  # one batch a statement
    if shape == "in_subquery":
        assert all(r["s"] == pytest.approx([15.0]) for r in answers["parse"])


def test_a_select_no_parser_made_has_no_note_and_is_walked(ds):
    """The cluster coordinator builds its post-merge SELECT in code, and
    swaps a statement's field list for the replay: no note, so the walk."""
    from surrealdb_tpu.dbs.iterator import find_model_calls
    from surrealdb_tpu.sql import ast
    from surrealdb_tpu.sql.statements import Field, SelectStatement
    from surrealdb_tpu.syn.parser import parse_query

    stm = parse_query("SELECT ml::score<1>(f) AS s, n FROM h").statements[0]
    (call,) = stm.ml_calls
    assert isinstance(call, ast.ModelCall) and find_model_calls(stm) is stm.ml_calls
    built = SelectStatement([Field(call, alias=None)], stm.what)
    assert built.ml_calls is None and find_model_calls(built) == (call,)
    assert parse_query("SELECT n FROM h").statements[0].ml_calls == ()


def test_the_note_opens_no_second_way_to_an_unaliased_projection_s_literals():
    """An unaliased `ml::m(5)` is a column named by its own text, so its
    literal stays a fixed token of the template (plan_cache); the note holds
    the same ModelCall and must not hand that literal to the slots."""
    from surrealdb_tpu.dbs import plan_cache as pc
    from surrealdb_tpu.sql import ast
    from surrealdb_tpu.syn.parser import parse_query

    text = "SELECT ml::score<1>([7.5, n]), ml::score<1>([8.5, n]) AS s FROM h WHERE n > 3"
    query = parse_query(text)
    variant = pc._parameterize(text, query)
    named, aliased = query.statements[0].ml_calls
    assert [type(x) for x in named.args[0].items[:1] + aliased.args[0].items[:1]] == [ast.Literal, ast.SlotLiteral]
    assert variant.defaults == (8.5, 3)
