"""Ingest pipeline v2: mirror delta-feed, group commit, batch changefeed,
bulk RELATE routing.

The load-bearing property: bulk-with-delta-feed ≡ the per-row pipeline ≡
post-rebuild mirrors — same rows, same filtered results, same ORDER — and a
delta that cannot apply falls back to the debounced rebuild without ever
serving a stale mask.
"""

import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import cnf, telemetry
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.kvs.ds import Datastore
from surrealdb_tpu.sql.value import NONE, Datetime, Thing

KEY3 = ("test", "test", "t")


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


def q(ds, sql, vars=None):
    return ok(ds.execute(sql, vars=vars)[-1])


@pytest.fixture()
def small_bulk(monkeypatch):
    """Make tiny batches take the bulk path and tiny tables mirrorable."""
    monkeypatch.setattr(cnf, "BULK_INSERT_MIN", 8)
    monkeypatch.setattr(cnf, "COLUMN_MIRROR_MIN_ROWS", 8)
    yield monkeypatch


def counter(name) -> float:
    return sum(telemetry.counters_matching(name).values())


def delta_outcomes() -> dict:
    return {
        dict(k).get("outcome"): v
        for k, v in telemetry.counters_matching("column_mirror_delta").items()
    }


# ------------------------------------------------------------------ delta feed
def test_delta_feed_applies_and_serves_in_key_order(small_bulk):
    ds = Datastore("memory")
    try:
        q(ds, "DEFINE TABLE t SCHEMALESS")
        # first batch: even ids; mirror builds on the first columnar query
        q(ds, "INSERT INTO t $rows RETURN NONE",
          {"rows": [{"id": i * 2, "v": i} for i in range(64)]})
        q(ds, "SELECT VALUE id FROM t WHERE v < 1000")
        m0 = ds.column_mirrors.get(KEY3)
        assert m0 is not None and m0.n == 64
        applied0 = delta_outcomes().get("applied", 0)
        # second batch: ODD ids interleave below existing keys — the scan
        # output must still stream in record-key order like the row path
        q(ds, "INSERT INTO t $rows RETURN NONE",
          {"rows": [{"id": i * 2 + 1, "v": i + 1000} for i in range(64)]})
        m1 = ds.column_mirrors.get(KEY3)
        assert m1 is not None and m1.delta_fed and m1.n == 128
        assert delta_outcomes().get("applied", 0) == applied0 + 1
        col = q(ds, "SELECT VALUE id FROM t WHERE v < 2000")
        saved = cnf.COLUMN_MIRROR
        cnf.COLUMN_MIRROR = False
        try:
            row = q(ds, "SELECT VALUE id FROM t WHERE v < 2000")
        finally:
            cnf.COLUMN_MIRROR = saved
        assert [str(x) for x in col] == [str(x) for x in row]  # incl. ORDER
    finally:
        ds.close()


@pytest.mark.parametrize("feed", [True, False], ids=["feed-on", "feed-off"])
def test_sustained_ingest_never_serves_stale(small_bulk, feed):
    """A serving table ingesting steadily: rounds of a bulk INSERT and, at
    once, a filtered SELECT against the live mirror. With the delta feed
    on the mirror is current after every commit and serves; with it off
    every bulk op leaves the mirror behind and the query falls to the row
    path. Either way each round's answer is the row path's, never a mask
    of the table as it was."""
    small_bulk.setattr(cnf, "COLUMN_DELTA_FEED", feed)
    ds = Datastore("memory")
    sql = "SELECT VALUE id FROM t WHERE flag = true AND val < 10"
    try:
        q(ds, "DEFINE TABLE t SCHEMALESS")
        q(ds, "INSERT INTO t $rows RETURN NONE",
          {"rows": [{"id": i, "val": i % 100, "flag": i % 4 == 0} for i in range(256)]})
        q(ds, sql)  # builds the mirror
        assert ds.column_mirrors.get(KEY3) is not None
        applied0 = delta_outcomes().get("applied", 0)
        rounds, batch = 4, 64
        for rnd in range(rounds):
            base = 10_000 + rnd * batch
            q(ds, "INSERT INTO t $rows RETURN NONE",
              {"rows": [{"id": base + j, "val": 5, "flag": j % 2 == 0} for j in range(batch)]})
            got = [str(x) for x in q(ds, sql)]
            small_bulk.setattr(cnf, "COLUMN_MIRROR", False)
            want = [str(x) for x in q(ds, sql)]
            small_bulk.setattr(cnf, "COLUMN_MIRROR", True)
            assert got == want, (rnd, len(got), len(want))
            assert len(got) == 9 + (rnd + 1) * batch // 2
        # on: one delta a commit; off: bulk.py offers the mirror nothing
        applied = delta_outcomes().get("applied", 0) - applied0
        assert applied == (rounds if feed else 0)
    finally:
        ds.close()


def _rand_rows(rng, n, base):
    """Type-mixed rows: ints/floats/strings/bools/datetimes/NONE/missing,
    nested objects, lists (nested-unsafe parents), record links."""
    rows = []
    for i in range(n):
        r = {"id": base + i, "v": int(rng.integers(0, 100))}
        kind = int(rng.integers(0, 8))
        if kind == 0:
            r["x"] = float(rng.random() * 50)
        elif kind == 1:
            r["x"] = f"s{int(rng.integers(0, 5))}"
        elif kind == 2:
            r["x"] = bool(rng.integers(0, 2))
        elif kind == 3:
            r["x"] = NONE
        elif kind == 4:
            r["x"] = Datetime(int(rng.integers(0, 10**15)))
        elif kind == 5:
            r["x"] = [1, 2, int(rng.integers(0, 9))]
        elif kind == 6:
            r["x"] = {"b": int(rng.integers(0, 40)), "c": f"n{i % 3}"}
        # kind 7: x missing entirely
        if rng.random() < 0.3:
            r["nested"] = {"b": int(rng.integers(0, 40))}
        if rng.random() < 0.2:
            r["link"] = Thing("other", i)
        rows.append(r)
    return rows


PREDICATES = [
    "SELECT VALUE id FROM t WHERE v < 50",
    "SELECT VALUE id FROM t WHERE x > 10",
    "SELECT VALUE id FROM t WHERE x = 's1'",
    "SELECT VALUE id FROM t WHERE x CONTAINS 's'",
    "SELECT VALUE id FROM t WHERE nested.b > 20",
    "SELECT VALUE id FROM t WHERE x.b > 20 AND v < 80",
    "SELECT VALUE id FROM t WHERE x > d'2001-09-09T01:46:40Z'",
    "SELECT VALUE id FROM t WHERE x",
    "SELECT count() FROM t WHERE v >= 25 GROUP ALL",
]


def test_delta_feed_property_three_way(small_bulk):
    """bulk+delta ≡ per-row pipeline ≡ post-rebuild mirror, over randomized
    type-mixed rows and a predicate battery."""
    rng = np.random.default_rng(42)
    ds_bulk = Datastore("memory")
    ds_row = Datastore("memory")
    try:
        batches = [_rand_rows(rng, 48, b * 1000) for b in range(4)]
        for target in (ds_bulk, ds_row):
            q(target, "DEFINE TABLE t SCHEMALESS")
        # bulk ds: mirror first (so later batches delta-feed), bulk min low
        q(ds_bulk, "INSERT INTO t $rows RETURN NONE", {"rows": batches[0]})
        q(ds_bulk, "SELECT VALUE id FROM t WHERE v < 1000")
        assert ds_bulk.column_mirrors.get(KEY3) is not None
        for b in batches[1:]:
            q(ds_bulk, "INSERT INTO t $rows RETURN NONE", {"rows": b})
        assert delta_outcomes().get("applied", 0) >= 1
        # per-row ds: force the row pipeline
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 10**9)
        for b in batches:
            q(ds_row, "INSERT INTO t $rows RETURN NONE", {"rows": b})
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 8)

        def norm(res):
            return [repr(x) for x in res]

        for sql in PREDICATES:
            got = norm(q(ds_bulk, sql))
            want = norm(q(ds_row, sql))
            assert got == want, f"{sql}: delta-fed {got[:5]}... != row {want[:5]}..."
        # post-rebuild equivalence: a fresh scan-built mirror answers the
        # same as the delta-fed one did
        before = {sql: norm(q(ds_bulk, sql)) for sql in PREDICATES}
        ds_bulk.column_mirrors.clear()
        for sql in PREDICATES:
            assert norm(q(ds_bulk, sql)) == before[sql], sql
        rebuilt = ds_bulk.column_mirrors.get(KEY3)
        assert rebuilt is not None and not rebuilt.delta_fed
    finally:
        ds_bulk.close()
        ds_row.close()


def test_delta_feed_unique_ignore_conflicts(small_bulk):
    """IGNORE-skipped unique-index conflicts never enter the delta."""
    ds = Datastore("memory")
    ds2 = Datastore("memory")
    try:
        for target in (ds, ds2):
            q(target, "DEFINE TABLE t SCHEMALESS")
            q(target, "DEFINE INDEX uq ON t FIELDS u UNIQUE")
        rows1 = [{"id": i, "u": i % 24, "v": i} for i in range(32)]
        rows2 = [{"id": 100 + i, "u": i % 48, "v": i} for i in range(64)]
        q(ds, "INSERT IGNORE INTO t $rows RETURN NONE", {"rows": rows1})
        q(ds, "SELECT VALUE id FROM t WHERE v < 10**6")
        q(ds, "INSERT IGNORE INTO t $rows RETURN NONE", {"rows": rows2})
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 10**9)
        q(ds2, "INSERT IGNORE INTO t $rows RETURN NONE", {"rows": rows1})
        q(ds2, "INSERT IGNORE INTO t $rows RETURN NONE", {"rows": rows2})
        for sql in (
            "SELECT VALUE id FROM t WHERE v >= 0",
            "SELECT count() FROM t WHERE u < 24 GROUP ALL",
        ):
            assert [repr(x) for x in q(ds, sql)] == [repr(x) for x in q(ds2, sql)]
    finally:
        ds.close()
        ds2.close()


def test_failed_delta_apply_falls_back_to_rebuild(small_bulk, monkeypatch):
    """A delta-apply crash must not fail the commit NOR serve stale masks:
    the mirror version mismatch sends readers to the row path until the
    debounced rebuild lands."""
    from surrealdb_tpu.idx import column_mirror as cmod

    ds = Datastore("memory")
    try:
        q(ds, "DEFINE TABLE t SCHEMALESS")
        q(ds, "INSERT INTO t $rows RETURN NONE",
          {"rows": [{"id": i, "v": i} for i in range(64)]})
        assert q(ds, "SELECT VALUE id FROM t WHERE v < 10") == q(
            ds, "SELECT VALUE id FROM t WHERE v < 10"
        )
        assert ds.column_mirrors.get(KEY3) is not None

        def boom(docs):
            raise RuntimeError("delta apply wedged")

        monkeypatch.setattr(cmod, "_build_block", boom)
        q(ds, "INSERT INTO t $rows RETURN NONE",
          {"rows": [{"id": 100 + i, "v": 5} for i in range(64)]})  # commit OK
        # immediately query: the stale mirror must NOT serve (version
        # mismatch) — results must include the new rows via the row path
        got = q(ds, "SELECT count() FROM t WHERE v = 5 GROUP ALL")
        assert got and got[0]["count"] == 64 + 1  # 64 new + id=5
        monkeypatch.undo()
        assert ds.column_mirrors.wait_rebuild(10)
        got = q(ds, "SELECT count() FROM t WHERE v = 5 GROUP ALL")
        assert got and got[0]["count"] == 65
        m = ds.column_mirrors.get(KEY3)
        assert m is not None and m.n == 128
    finally:
        ds.close()


def test_interleaved_row_write_declines_delta(small_bulk):
    """A txn that bulk-inserts AND row-writes the same table cannot express
    its write-set as a delta — it must decline, and results stay exact."""
    ds = Datastore("memory")
    try:
        q(ds, "DEFINE TABLE t SCHEMALESS")
        q(ds, "INSERT INTO t $rows RETURN NONE",
          {"rows": [{"id": i, "v": i} for i in range(64)]})
        q(ds, "SELECT VALUE id FROM t WHERE v < 10")
        applied0 = delta_outcomes().get("applied", 0)
        out = ds.execute(
            "BEGIN; INSERT INTO t $rows RETURN NONE; "
            "UPDATE t:1 SET v = 999; COMMIT;",
            vars={"rows": [{"id": 200 + i, "v": 7} for i in range(64)]},
        )
        for r in out:
            assert r["status"] == "OK", r
        assert delta_outcomes().get("applied", 0) == applied0  # declined
        got = q(ds, "SELECT count() FROM t WHERE v = 7 GROUP ALL")
        assert got and got[0]["count"] == 64 + 1
        assert q(ds, "SELECT VALUE v FROM t:1") == [999]
    finally:
        ds.close()


# ------------------------------------------------------------------ group commit
def test_group_commit_concurrent_commits_all_land():
    ds = Datastore("memory")
    try:
        q(ds, "DEFINE TABLE g SCHEMALESS")
        errs = []

        def worker(i):
            try:
                s = Session.owner()
                for j in range(5):
                    r = ds.execute(
                        "CREATE $id SET v = 1",
                        s,
                        vars={"id": Thing("g", i * 100 + j)},
                    )
                    assert r[-1]["status"] == "OK", r
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        got = q(ds, "SELECT count() FROM g GROUP ALL")
        assert got[0]["count"] == 40
    finally:
        ds.close()
    # the ephemeral flusher exits after its linger — no thread leak
    deadline = time.monotonic() + cnf.GROUP_COMMIT_LINGER_SECS + 2.0
    while time.monotonic() < deadline:
        if not any(
            t.name.startswith("bg:group_commit") and t.is_alive()
            for t in threading.enumerate()
        ):
            break
        time.sleep(0.05)
    assert not any(
        t.name.startswith("bg:group_commit") and t.is_alive()
        for t in threading.enumerate()
    )


def test_group_commit_conflict_propagates_to_the_right_submitter():
    from surrealdb_tpu.err import TxConflictError

    ds = Datastore("memory")
    try:
        t1 = ds.transaction(True)
        t2 = ds.transaction(True)
        t1.set(b"kx", b"1")
        t2.set(b"kx", b"2")
        t1.commit()  # through the group coalescer
        with pytest.raises(TxConflictError):
            t2.commit()
    finally:
        ds.close()


def test_group_commit_on_commit_reentrancy_no_deadlock():
    """An on_commit callback that commits another write txn runs ON the
    flusher thread — it must bypass the queue, not wait on itself."""
    ds = Datastore("memory")
    try:
        done = []

        def side_effect():
            t2 = ds.transaction(True)
            t2.set(b"side", b"1")
            t2.commit()
            done.append(True)

        t1 = ds.transaction(True)
        t1.set(b"main", b"1")
        t1.on_commit(side_effect)
        t1.commit()  # would deadlock if the callback queued behind itself
        assert done == [True]
        t3 = ds.transaction(False)
        assert t3.get(b"side") == b"1"
        t3.cancel()
    finally:
        ds.close()


# ------------------------------------------------------------------ changefeed
def test_changefeed_batch_entry_equivalence(small_bulk):
    """One batch entry per bulk op; reader-side expansion replays exactly
    the committed documents — pinned at the entry's commit version even
    after later updates."""
    ds = Datastore("memory")
    ds2 = Datastore("memory")
    try:
        for target in (ds, ds2):
            q(target, "DEFINE TABLE c CHANGEFEED 1h")
        rows = [{"id": i, "v": i * 10} for i in range(32)]
        q(ds, "INSERT INTO c $rows RETURN NONE", {"rows": rows})
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 10**9)
        q(ds2, "INSERT INTO c $rows RETURN NONE", {"rows": rows})
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 8)
        for target in (ds, ds2):
            q(target, "UPDATE c:3 SET v = -1")

        def updates(target):
            out = {}
            for cs in q(target, "SHOW CHANGES FOR TABLE c SINCE 0"):
                for ch in cs["changes"]:
                    if "update" in ch:
                        doc = ch["update"]
                        out.setdefault(str(doc["id"]), []).append(doc["v"])
            return out

        got, want = updates(ds), updates(ds2)
        assert got == want
        assert got["c:3"] == [30, -1]  # pinned replay THEN the update
        # and the bulk op stored ONE mutation record, not 32
        sets = q(ds, "SHOW CHANGES FOR TABLE c SINCE 0")
        assert len(sets) == 2 and len(sets[0]["changes"]) == 32
    finally:
        ds.close()
        ds2.close()


# ------------------------------------------------------------------ RELATE
def test_bulk_relate_routes_through_edge_writer(small_bulk):
    ds = Datastore("memory")
    ds2 = Datastore("memory")
    try:
        for target in (ds, ds2):
            q(target, "DEFINE TABLE person SCHEMALESS")
            q(target, "INSERT INTO person $rows RETURN NONE",
              {"rows": [{"id": i} for i in range(16)]})
        froms = [Thing("person", i) for i in range(8)]
        withs = [Thing("person", 8 + i) for i in range(8)]
        batches0 = counter("bulk_insert_batches")
        r = ok(ds.execute(
            "RELATE $f->knows->$w", vars={"f": froms, "w": withs}
        )[-1])
        assert counter("bulk_insert_batches") == batches0 + 1
        assert len(r) == 64 and all(isinstance(e["id"], Thing) for e in r)
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 10**9)
        ok(ds2.execute("RELATE $f->knows->$w", vars={"f": froms, "w": withs})[-1])

        def edges(target):
            got = q(target, "SELECT VALUE ->knows->person FROM person:0")
            return sorted(repr(t) for t in got[0])

        assert edges(ds) == edges(ds2)
        cnt = q(ds, "SELECT count() FROM knows GROUP ALL")
        assert cnt[0]["count"] == 64
        # UNIQUE / edge-dependent data clauses keep the per-row pipeline
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 8)
        b0 = counter("bulk_insert_batches")
        ok(ds.execute(
            "RELATE $f->liked->$w UNIQUE", vars={"f": froms, "w": withs}
        )[-1])
        ok(ds.execute(
            "RELATE $f->sourced->$w SET src = $in", vars={"f": froms, "w": withs}
        )[-1])
        assert counter("bulk_insert_batches") == b0
        # an edge-INDEPENDENT SET joins the bulk edge writer (ISSUE 11)
        r2 = ok(ds.execute(
            "RELATE $f->rated->$w SET score = 1", vars={"f": froms, "w": withs}
        )[-1])
        assert counter("bulk_insert_batches") == b0 + 1
        assert all(e["score"] == 1 for e in r2)
    finally:
        ds.close()
        ds2.close()


def test_bulk_relate_set_content_parity(small_bulk):
    """The bulk stamp of an edge-independent SET/CONTENT clause must
    produce byte-identical records to the per-row pipeline (the ROADMAP
    carried item: clauses that provably don't reference $in/$out join the
    bulk edge writer)."""
    ds = Datastore("memory")
    ds2 = Datastore("memory")
    try:
        for target in (ds, ds2):
            q(target, "DEFINE TABLE person SCHEMALESS")
            q(target, "INSERT INTO person $rows RETURN NONE",
              {"rows": [{"id": i} for i in range(16)]})
        froms = [Thing("person", i) for i in range(8)]
        withs = [Thing("person", 8 + i) for i in range(8)]
        vars_ = {
            "f": froms, "w": withs,
            "tag": "manual", "weights": [1, 2],
        }
        stmts = [
            "RELATE $f->knows->$w SET kind = $tag, weight = 1 + 2, "
            "meta = { src: $tag, ws: $weights }",
            "RELATE $f->likes->$w CONTENT { kind: $tag, strength: 0.5 }",
        ]
        b0 = counter("bulk_insert_batches")
        for stmt in stmts:
            ok(ds.execute(stmt, vars=vars_)[-1])
        assert counter("bulk_insert_batches") == b0 + len(stmts)
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 10**9)  # per-row twin
        for stmt in stmts:
            ok(ds2.execute(stmt, vars=vars_)[-1])

        def edges(target, tb):
            rows = q(target, f"SELECT * OMIT id FROM {tb}")
            return sorted(
                (repr(r["in"]), repr(r["out"]),
                 sorted((k, repr(v)) for k, v in r.items()
                        if k not in ("in", "out")))
                for r in rows
            )

        for tb in ("knows", "likes"):
            assert edges(ds, tb) == edges(ds2, tb)
        # nested containers must not alias across edges: mutate one edge's
        # meta and assert its neighbours are untouched
        rows = q(ds, "SELECT id FROM knows LIMIT 2")
        q(ds, "UPDATE $r SET meta.ws += 99", {"r": rows[0]["id"]})
        others = q(ds, "SELECT meta FROM knows WHERE id != $r",
                   {"r": rows[0]["id"]})
        assert all(o["meta"]["ws"] == [1, 2] for o in others)
    finally:
        ds.close()
        ds2.close()


# ------------------------------------------------------------------ vector bulk
def test_vector_apply_many_matches_per_row(small_bulk):
    ds = Datastore("memory")
    ds2 = Datastore("memory")
    try:
        rng = np.random.default_rng(5)
        x = rng.standard_normal((128, 8)).astype(np.float32)
        for target in (ds, ds2):
            q(target, "DEFINE TABLE it SCHEMALESS")
            q(target, "DEFINE INDEX v ON it FIELDS emb HNSW DIMENSION 8")
        q(ds, "INSERT INTO it $rows RETURN NONE",
          {"rows": [{"id": i, "emb": x[i]} for i in range(128)]})
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 10**9)
        q(ds2, "INSERT INTO it $rows RETURN NONE",
          {"rows": [{"id": i, "emb": x[i].tolist()} for i in range(128)]})
        small_bulk.setattr(cnf, "BULK_INSERT_MIN", 8)
        for target in (ds, ds2):
            got = q(target, "SELECT VALUE id FROM it WHERE emb <|5|> $q",
                    {"q": x[17].tolist()})
            assert str(got[0]) == "it:17"
        m1 = ds.index_stores.get("test", "test", "it", "v")
        m2 = ds2.index_stores.get("test", "test", "it", "v")
        assert m1.count() == m2.count() == 128
    finally:
        ds.close()
        ds2.close()


def test_group_commit_survives_flusher_crash(monkeypatch):
    """An exception escaping the flusher must not latch _live: the next
    commit self-rescues (or respawns) instead of polling forever."""
    from surrealdb_tpu.kvs import ds as dsmod

    ds = Datastore("memory")
    try:
        crashed = []
        real_flush = dsmod.GroupCommit._flush

        def boom(self, batch):
            if not crashed:
                crashed.append(True)
                raise MemoryError("flusher wedged")
            return real_flush(self, batch)

        monkeypatch.setattr(dsmod.GroupCommit, "_flush", boom)
        t1 = ds.transaction(True)
        t1.set(b"a", b"1")
        try:
            t1.commit()  # served by the rescue path after the crash
        except Exception:
            t1.cancel()  # a surfaced error is acceptable; a hang is not
        monkeypatch.undo()
        t2 = ds.transaction(True)
        t2.set(b"b", b"2")
        t2.commit()  # must complete, not spin on a dead flusher
        t3 = ds.transaction(False)
        assert t3.get(b"b") == b"2"
        t3.cancel()
    finally:
        ds.close()


def test_knn_overlay_handles_uncommitted_bulk_vectors(small_bulk):
    """kNN inside the same txn as an uncommitted bulk INSERT serves the
    exact overlay — the bulk vector block must expand per row."""
    ds = Datastore("memory")
    try:
        rng = np.random.default_rng(9)
        x = rng.standard_normal((80, 8)).astype(np.float32)
        q(ds, "DEFINE TABLE it SCHEMALESS")
        q(ds, "DEFINE INDEX v ON it FIELDS emb HNSW DIMENSION 8")
        out = ds.execute(
            "BEGIN; INSERT INTO it $rows RETURN NONE; "
            "SELECT VALUE id FROM it WHERE emb <|3|> $q; COMMIT;",
            vars={
                "rows": [{"id": i, "emb": x[i]} for i in range(80)],
                "q": x[17].tolist(),
            },
        )
        for r in out:
            assert r["status"] == "OK", r
        assert str(out[-1]["result"][0]) == "it:17"
    finally:
        ds.close()
