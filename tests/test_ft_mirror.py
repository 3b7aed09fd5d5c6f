"""FT mirror tests: CSR postings replica, incremental maintenance, overlay
semantics (idx/ft_mirror.py; reference analog: core/src/idx/ft/ +
trees/store/cache.rs generation swap). The device route has
tests/test_ft_device.py."""

import numpy as np
import pytest

from surrealdb_tpu.sql.value import Thing


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


def setup_ix(ds):
    ds.execute(
        "DEFINE ANALYZER simple TOKENIZERS blank,class FILTERS lowercase;"
        "DEFINE INDEX body_ix ON doc FIELDS body SEARCH ANALYZER simple BM25;"
    )


def _mirror(ds):
    return ds.index_stores.get("test", "test", "doc", "body_ix")


def test_mirror_built_once_and_maintained(ds):
    setup_ix(ds)
    ds.execute("CREATE doc:1 SET body = 'alpha beta'; CREATE doc:2 SET body = 'alpha gamma';")
    r = ds.execute("SELECT VALUE id FROM doc WHERE body @@ 'alpha' ORDER BY id;")
    assert ok(r[0]) == [Thing("doc", 1), Thing("doc", 2)]
    m = _mirror(ds)
    assert m is not None and m.built and m.count() == 2
    # incremental: new doc, updated doc, deleted doc — no rebuild
    ds.execute("CREATE doc:3 SET body = 'alpha delta';")
    ds.execute("UPDATE doc:1 SET body = 'epsilon only';")
    ds.execute("DELETE doc:2;")
    assert _mirror(ds) is m  # same object, not rebuilt
    r = ds.execute("SELECT VALUE id FROM doc WHERE body @@ 'alpha';")
    assert ok(r[0]) == [Thing("doc", 3)]
    r = ds.execute("SELECT VALUE id FROM doc WHERE body @@ 'epsilon';")
    assert ok(r[0]) == [Thing("doc", 1)]
    assert m.count() == 2


def test_mirror_matches_exact_scores(ds):
    """Mirror BM25 scores must equal the exact KV-path scores."""
    setup_ix(ds)
    for i in range(30):
        words = " ".join(f"w{j}" for j in range(i % 5 + 1)) + (" common" * (i % 3 + 1))
        ds.execute(f"CREATE doc:{i} SET body = '{words}';")
    q = "SELECT id, search::score(1) AS s FROM doc WHERE body @1@ 'common w1' ORDER BY id;"
    mirror_rows = ok(ds.execute(q)[0])
    # exact path: FtIndex.search straight off the KV postings
    from surrealdb_tpu.dbs.executor import Executor
    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.idx.ft_index import FtIndex

    ex = Executor(ds, Session.owner())
    txn = ds.transaction(False)
    ex.txn = txn
    try:
        from surrealdb_tpu.dbs.context import Context

        ctx = Context(ex, ex.session)
        ix = txn.all_tb_indexes("test", "test", "doc")[0]
        exact = {
            (rid.tb, repr(rid.id)): s
            for rid, s in FtIndex.for_index(ctx, ix).search(ctx, "common w1")
        }
    finally:
        txn.cancel()
    assert len(mirror_rows) == len(exact) > 0
    for row in mirror_rows:
        key = (row["id"].tb, repr(row["id"].id))
        assert row["s"] == pytest.approx(exact[key], rel=1e-5)


def test_uncommitted_writes_use_exact_overlay(ds):
    """A txn's own FT writes must be visible to its MATCHES queries and must
    never leak into the shared mirror."""
    setup_ix(ds)
    ds.execute("CREATE doc:1 SET body = 'alpha';")
    ds.execute("SELECT * FROM doc WHERE body @@ 'alpha';")  # builds mirror
    m = _mirror(ds)
    out = ds.execute(
        "BEGIN;"
        "CREATE doc:9 SET body = 'alpha zulu';"
        "SELECT VALUE id FROM doc WHERE body @@ 'zulu';"
        "COMMIT;"
    )
    # the SELECT ran inside the txn, before the mirror delta applied: the
    # exact overlay must have served the uncommitted doc
    assert ok(out[-1]) == [Thing("doc", 9)]
    assert m.count() == 2  # delta applied at commit, incrementally
    # a cancelled txn's writes never reach the mirror
    ds.execute("BEGIN; CREATE doc:10 SET body = 'alpha yankee'; CANCEL;")
    assert m.count() == 2
    r = ds.execute("SELECT VALUE id FROM doc WHERE body @@ 'yankee';")
    assert ok(r[0]) == []


def test_highlight_still_works_via_mirror_path(ds):
    ds.execute(
        "DEFINE ANALYZER simple TOKENIZERS blank,class FILTERS lowercase;"
        "DEFINE INDEX body_ix ON doc FIELDS body SEARCH ANALYZER simple BM25 HIGHLIGHTS;"
    )
    ds.execute("CREATE doc:1 SET body = 'alpha beta gamma';")
    r = ds.execute(
        "SELECT search::highlight('<b>', '</b>', 1) AS h FROM doc WHERE body @1@ 'beta';"
    )
    assert ok(r[0])[0]["h"] == "alpha <b>beta</b> gamma"


def test_zero_token_doc_dc_accounting(ds):
    """A doc whose field analyzes to zero tokens must round-trip dc
    correctly through insert + delete (mirror vs KV stats)."""
    from surrealdb_tpu.dbs.session import Session

    s = Session.owner()
    s.ns, s.db = "test", "test"
    ds.execute(
        "DEFINE ANALYZER a TOKENIZERS blank FILTERS lowercase; "
        "DEFINE TABLE d SCHEMALESS; "
        "DEFINE INDEX f ON d FIELDS body SEARCH ANALYZER a BM25;", s)
    ds.execute("INSERT INTO d $rows", s, vars={"rows": [
        {"id": i, "body": "alpha beta"} for i in range(10)]})
    # build the mirror
    ds.execute("SELECT id FROM d WHERE body @1@ 'alpha'", s)
    mirror = ds.index_stores.get("test", "test", "d", "f")
    base = mirror.count()
    for _ in range(3):
        ds.execute("CREATE d:999 SET body = ''", s)   # zero tokens, present
        ds.execute("DELETE d:999", s)
    assert mirror.count() == base, (mirror.count(), base)
    out = ds.execute("SELECT count() FROM d WHERE body @1@ 'alpha' GROUP ALL", s)
    assert out[-1]["result"][0]["count"] == 10
