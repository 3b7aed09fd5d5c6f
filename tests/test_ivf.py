"""IVF ANN index tests: recall floors vs brute force (reference:
core/src/idx/trees/hnsw/mod.rs:828-951 recall suite), SQL-level execution
through the planner, incremental mirror maintenance, and in-transaction
overlay semantics."""

import numpy as np
import pytest


def _mixture(n, d, clusters=32, seed=3):
    """Gaussian-mixture corpus — the shape real embedding sets have."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(clusters, d)).astype(np.float32) * 4.0
    assign = rng.integers(0, clusters, size=n)
    return centers[assign] + rng.normal(size=(n, d)).astype(np.float32)


def _brute(q, x, k):
    d = ((x - q[None, :]) ** 2).sum(1)
    return set(np.argsort(d)[:k].tolist())


def test_ivf_recall_floor():
    from surrealdb_tpu.idx.ivf import IvfState, default_nprobe

    n, d, k = 20000, 32, 10
    x = _mixture(n, d)
    alive = np.ones(n, dtype=bool)
    ivf = IvfState.train(x, alive)
    import jax.numpy as jnp

    mat = jnp.asarray(x)
    nprobe = default_nprobe(ivf.nlists, 150)
    rng = np.random.default_rng(11)
    hits = total = 0
    for qi in rng.integers(0, n, size=50):
        q = x[qi]
        dists, slots = ivf.search(q, mat, "euclidean", k, nprobe)
        got = {int(s) for s, dd in zip(slots, dists) if s >= 0 and np.isfinite(dd)}
        want = _brute(q, x, k)
        hits += len(got & want)
        total += k
    recall = hits / total
    assert recall >= 0.9, f"recall@10 = {recall:.3f} < 0.9"
    # sublinear: candidates examined ≤ nprobe/nlists of the corpus (+ padding)
    maxlen = max(len(l) for l in ivf.lists)
    assert nprobe * maxlen < n, "IVF probes the whole corpus"


def test_bounded_gather_gives_the_vmap_answers(monkeypatch):
    """A query tile whose candidate gather exceeds the device's budget runs
    as sequential sub-batches inside the kernel: same shapes, same answers."""
    import jax.numpy as jnp

    from surrealdb_tpu.idx import ivf as ivfm

    x = _mixture(4096, 32, seed=9)
    state = ivfm.IvfState.train(x, np.ones(len(x), dtype=bool))
    mat = jnp.asarray(x)
    qs = x[:8] + 0.01
    want_d, want_r = state.search_batch(qs, mat, "euclidean", 10, 4)
    from surrealdb_tpu.ops import distances

    monkeypatch.setattr(distances, "gather_budget_bytes", lambda: 1)  # one query at a time
    ivfm._ivf_search.clear_cache()
    try:
        got_d, got_r = state.search_batch(qs, mat, "euclidean", 10, 4)
    finally:
        ivfm._ivf_search.clear_cache()
    assert (got_r == want_r).all()
    # |q|^2 + |x|^2 - 2qx cancels: the batched and the one-at-a-time matmul
    # round differently by ~eps * |x|^2 (f32, |x|^2 ~ 500) in d^2
    np.testing.assert_allclose(got_d**2, want_d**2, atol=1e-3)


def test_ivf_self_hit():
    """Every corpus point must find itself at distance 0."""
    from surrealdb_tpu.idx.ivf import IvfState

    x = _mixture(5000, 16, seed=5)
    ivf = IvfState.train(x, np.ones(len(x), dtype=bool))
    import jax.numpy as jnp

    mat = jnp.asarray(x)
    rng = np.random.default_rng(2)
    for qi in rng.integers(0, len(x), size=20):
        dists, slots = ivf.search(x[qi], mat, "euclidean", 1, max(ivf.nlists // 8, 1))
        # f32 matmul-decomposed euclidean has ~1e-2 noise at these norms
        assert int(slots[0]) == qi and dists[0] < 0.1


@pytest.fixture()
def vec_ds(ds):
    ds.execute("DEFINE INDEX v ON item FIELDS emb HNSW DIMENSION 8 DIST EUCLIDEAN;")
    rng = np.random.default_rng(9)
    x = _mixture(300, 8, clusters=8, seed=9)
    stmts = [
        f"CREATE item:{i} SET emb = [{', '.join(f'{v:.5f}' for v in row)}]"
        for i, row in enumerate(x)
    ]
    ds.execute(";".join(stmts))
    return ds, x


def _knn_ids(ds, q, k=5, ef=None):
    qs = "[" + ", ".join(f"{v:.5f}" for v in q) + "]"
    op = f"<|{k},{ef}|>" if ef else f"<|{k}|>"
    out = ds.execute(f"SELECT VALUE id FROM item WHERE emb {op} {qs};")
    return [t.id for t in out[0]["result"]]


def test_sql_knn_exact_small(vec_ds):
    """Below TPU_ANN_MIN_ROWS the plan is exact — matches brute force."""
    ds, x = vec_ds
    got = _knn_ids(ds, x[7], k=5)
    assert set(got) == _brute(x[7], x, 5)


def test_sql_knn_ivf_path(vec_ds):
    """Forcing the ANN threshold down routes the same query through IVF;
    results overlap brute force (recall) and include the query point."""
    from surrealdb_tpu import cnf

    ds, x = vec_ds
    old = cnf.TPU_ANN_MIN_ROWS
    cnf.TPU_ANN_MIN_ROWS = 10
    try:
        ds.index_stores.clear()
        # first ANN query serves exact and kicks background training —
        # correct results, no latency cliff
        got = _knn_ids(ds, x[7], k=5, ef=400)
        assert 7 in got and len(set(got) & _brute(x[7], x, 5)) >= 4
        mirror = ds.index_stores.get("test", "test", "item", "v")
        assert mirror.wait_ivf(30), "background IVF training did not finish"
        assert mirror.ivf_status()["state"] == "ready"
        got = _knn_ids(ds, x[7], k=5, ef=400)  # now through IVF
        assert 7 in got, "self-hit missed"
        assert len(set(got) & _brute(x[7], x, 5)) >= 4
    finally:
        cnf.TPU_ANN_MIN_ROWS = old


def test_sql_knn_incremental_no_rescan(vec_ds):
    """After the mirror builds, writes maintain it by delta — a rebuild scan
    would raise (VERDICT r1 item 4)."""
    from surrealdb_tpu.idx import vector_index

    ds, x = vec_ds
    _knn_ids(ds, x[0], k=3)  # builds the mirror

    orig = vector_index.scan_vectors

    def boom(*a, **k):
        raise AssertionError("vector mirror rebuilt instead of delta-maintained")

    vector_index.scan_vectors = boom
    try:
        ds.execute("CREATE item:999 SET emb = [9.0, 9.0, 9.0, 9.0, 9.0, 9.0, 9.0, 9.0];")
        got = _knn_ids(ds, [9.0] * 8, k=1)
        assert got == [999]
        ds.execute("DELETE item:999;")
        got = _knn_ids(ds, [9.0] * 8, k=1)
        assert got != [999]
        # update moves the record in vector space
        ds.execute("UPDATE item:5 SET emb = [-9.0, -9.0, -9.0, -9.0, -9.0, -9.0, -9.0, -9.0];")
        got = _knn_ids(ds, [-9.0] * 8, k=1)
        assert got == [5]
    finally:
        vector_index.scan_vectors = orig


def test_sql_knn_txn_overlay(vec_ds):
    """Uncommitted writes are visible to kNN inside their own transaction
    (exact overlay path); a cancelled transaction leaves no trace in the
    shared mirror."""
    ds, x = vec_ds
    _knn_ids(ds, x[0], k=3)  # build mirror
    out = ds.execute(
        "BEGIN;"
        " CREATE item:777 SET emb = [7.5, 7.5, 7.5, 7.5, 7.5, 7.5, 7.5, 7.5];"
        " SELECT VALUE id FROM item WHERE emb <|1|> [7.5, 7.5, 7.5, 7.5, 7.5, 7.5, 7.5, 7.5];"
        " COMMIT;"
    )
    ids = [t.id for t in out[-1]["result"]]
    assert ids == [777], out[-1]
    ds.execute("DELETE item:777;")

    # cancelled txn: the pending row must never reach the mirror
    ds.execute(
        "BEGIN;"
        " CREATE item:888 SET emb = [8.5, 8.5, 8.5, 8.5, 8.5, 8.5, 8.5, 8.5];"
        " CANCEL;"
    )
    got = _knn_ids(ds, [8.5] * 8, k=1)
    assert got and got != [888]


def test_mtree_exact_contract(ds, monkeypatch):
    """DEFINE INDEX ... MTREE must return EXACT kNN results (reference
    mtree.rs:135 — an exact metric tree), never approximate IVF, even at
    sizes where HNSW indexes would route to ANN."""
    import numpy as np
    from surrealdb_tpu import cnf
    from surrealdb_tpu.dbs.session import Session

    monkeypatch.setattr(cnf, "TPU_ANN_MIN_ROWS", 64)
    monkeypatch.setattr(cnf, "TPU_KNN_ONDEVICE_THRESHOLD", 1 << 60)

    s = Session.owner()
    s.ns, s.db = "test", "test"
    rng = np.random.default_rng(3)
    vecs = rng.standard_normal((400, 16)).astype(np.float32)
    ds.execute(
        "DEFINE TABLE item SCHEMALESS; "
        "DEFINE INDEX im ON item FIELDS emb MTREE DIMENSION 16 DIST EUCLIDEAN;", s)
    ds.execute("INSERT INTO item $rows", s, vars={
        "rows": [{"id": i, "emb": vecs[i].tolist()} for i in range(400)]})
    q = vecs[7] + 0.01
    out = ds.execute("SELECT id FROM item WHERE emb <|10,4|> $q", s, vars={"q": q.tolist()})
    got = [int(str(r["id"]).split(":")[1]) for r in out[-1]["result"]]
    d = ((vecs - q) ** 2).sum(axis=1)
    want = set(np.argsort(d)[:10].tolist())
    assert set(got) == want, (sorted(got), sorted(want))


def test_ivf_strategies_consume_columnar_prefilter(ds, monkeypatch):
    """r10 carried item: the `ivf` (device kernel) and `ivf-host` kNN
    strategies consume the columnar residual-WHERE mask — top-k computed
    among MATCHING rows, not post-filtered below k."""
    import numpy as np
    from surrealdb_tpu import cnf, telemetry
    from surrealdb_tpu.dbs.session import Session

    monkeypatch.setattr(cnf, "TPU_ANN_MIN_ROWS", 64)
    # keep the test off the MESH branch (the suite runs on a virtual
    # 8-device mesh; tests/test_knn_strategies.py holds `ivf-sharded` to
    # the same prefilter)
    monkeypatch.setattr(cnf, "TPU_KNN_ONDEVICE_THRESHOLD", 1 << 60)
    monkeypatch.setattr(cnf, "COLUMN_MIRROR_MIN_ROWS", 4)

    s = Session.owner()
    s.ns, s.db = "test", "test"
    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((400, 8)).astype(np.float32)
    ds.execute(
        "DEFINE TABLE item SCHEMALESS; "
        "DEFINE INDEX iv ON item FIELDS emb HNSW DIMENSION 8 DIST EUCLIDEAN;", s)
    ds.execute("INSERT INTO item $rows", s, vars={
        "rows": [
            {"id": i, "emb": vecs[i].tolist(), "flag": bool(i % 2)}
            for i in range(400)
        ]})
    q = {"q": (vecs[31] + 0.01).tolist()}
    sql = "SELECT id FROM item WHERE emb <|8,80|> $q AND flag = true"

    # build mirror + train quantizer (wait_ivf = deterministic)
    ds.execute("SELECT id FROM item WHERE emb <|4,16|> $q", s, vars=dict(q))
    mirror = ds.index_stores.get("test", "test", "item", "iv")
    assert mirror.wait_ivf(60)

    def run_and_check(expected_strategy):
        out = ds.execute(sql, s, vars=dict(q))
        rows = out[-1]["result"]
        ids = [int(str(r["id"]).split(":")[1]) for r in rows]
        # every result matches the residual WHERE, and the probe found a
        # full k among matching rows (post-filter would thin this out)
        assert all(i % 2 for i in ids), ids
        assert len(ids) == 8, ids
        assert telemetry.get_counter("knn_strategy", strategy=expected_strategy) > 0

    applied0 = telemetry.get_counter("knn_prefilter", outcome="applied")
    run_and_check("ivf")  # device kernel path
    monkeypatch.setattr(cnf, "TPU_DISABLE", True)
    run_and_check("ivf-host")  # numpy probe+rerank twin
    assert telemetry.get_counter("knn_prefilter", outcome="applied") >= applied0 + 2
