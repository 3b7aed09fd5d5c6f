"""SurrealML tests: weight storage, ml:: execution (single + batched device
path), HTTP import/export, and lifecycle (reference: core/src/sql/model.rs,
tests/ml_integration.rs linear model flow)."""

import json

import numpy as np
import pytest


LINEAR = {
    "name": "house",
    "version": "1.0.0",
    "format": "linear",
    "layers": [{"w": [[2.0], [3.0]], "b": [10.0], "activation": None}],
}


@pytest.fixture()
def ml_ds(ds):
    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.ml.exec import import_model

    ds.execute("DEFINE MODEL ml::house<1.0.0>;")
    import_model(ds, Session.owner(), "house", "1.0.0", LINEAR)
    return ds


def test_ml_single_row(ml_ds):
    out = ml_ds.execute("RETURN ml::house<1.0.0>([1.0, 2.0]);")
    assert out[0]["result"] == pytest.approx(2.0 + 6.0 + 10.0)


def test_ml_batched_rows(ml_ds):
    out = ml_ds.execute("RETURN ml::house<1.0.0>([[1.0, 2.0], [0.0, 0.0], [2.0, 1.0]]);")
    assert out[0]["result"] == pytest.approx([18.0, 10.0, 17.0])


def test_ml_over_table_scan(ml_ds):
    """BASELINE config 5 shape: model scored over a full table scan with ONE
    batched call (subquery gathers the feature rows)."""
    ml_ds.execute(";".join(f"CREATE h:{i} SET f = [{i}.0, {i}.0]" for i in range(8)))
    out = ml_ds.execute(
        "RETURN ml::house<1.0.0>((SELECT VALUE f FROM h ORDER BY id));"
    )
    assert out[0]["result"] == pytest.approx([10.0 + 5.0 * i for i in range(8)])


def test_ml_mlp_matches_numpy(ds):
    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.ml.exec import import_model

    rng = np.random.default_rng(4)
    w1, b1 = rng.normal(size=(4, 8)), rng.normal(size=8)
    w2, b2 = rng.normal(size=(8, 1)), rng.normal(size=1)
    spec = {
        "format": "mlp",
        "layers": [
            {"w": w1.tolist(), "b": b1.tolist(), "activation": "relu"},
            {"w": w2.tolist(), "b": b2.tolist(), "activation": None},
        ],
    }
    ds.execute("DEFINE MODEL ml::net<2>;")
    import_model(ds, Session.owner(), "net", "2", spec)
    x = rng.normal(size=(5, 4))
    want = np.maximum(x @ w1 + b1, 0) @ w2 + b2
    arg = json.dumps(x.tolist())
    out = ds.execute(f"RETURN ml::net<2>({arg});")
    assert out[0]["result"] == pytest.approx(want[:, 0].tolist(), rel=1e-3, abs=1e-3)


def test_ml_missing_weights_errors(ds):
    ds.execute("DEFINE MODEL ml::empty<1>;")
    out = ds.execute("RETURN ml::empty<1>([1.0]);")
    assert out[0]["status"] == "ERR"
    assert "no stored weights" in out[0]["result"]


def test_ml_remove_model(ml_ds):
    ml_ds.execute("REMOVE MODEL ml::house<1.0.0>;")
    out = ml_ds.execute("RETURN ml::house<1.0.0>([1.0, 2.0]);")
    assert out[0]["status"] == "ERR"


def test_ml_http_roundtrip(ds):
    import base64
    import http.client

    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.net.server import Server

    ds.execute("DEFINE USER dbu ON DATABASE PASSWORD 'pw' ROLES OWNER;")
    ds.execute(
        "DEFINE ACCESS account ON DATABASE TYPE RECORD "
        "SIGNUP (CREATE user SET email = $email) "
        "SIGNIN (SELECT * FROM user WHERE email = $email);"
    )
    srv = Server(ds, port=0, auth_enabled=True).start_background()
    try:
        hdrs = {
            "Authorization": "Basic " + base64.b64encode(b"dbu:pw").decode(),
            "surreal-ns": "test",
            "surreal-db": "test",
            "Content-Type": "application/json",
        }
        c = http.client.HTTPConnection(srv.host, srv.port)
        c.request("POST", "/ml/import", json.dumps(LINEAR), hdrs)
        r = c.getresponse()
        out = json.loads(r.read())
        assert r.status == 200 and out["name"] == "house"

        c.request("GET", "/ml/export/house/1.0.0", headers=hdrs)
        r = c.getresponse()
        spec = json.loads(r.read())
        assert r.status == 200 and spec["layers"][0]["w"] == [[2.0], [3.0]]

        # record-access users may not import models
        c.request(
            "POST", "/signup",
            json.dumps({"ns": "test", "db": "test", "ac": "account", "email": "x@y.z"}),
            {"Content-Type": "application/json"},
        )
        token = json.loads(c.getresponse().read())["token"]
        rec_hdrs = {
            "Authorization": f"Bearer {token}",
            "surreal-ns": "test",
            "surreal-db": "test",
            "Content-Type": "application/json",
        }
        c.request("POST", "/ml/import", json.dumps(LINEAR), rec_hdrs)
        r = c.getresponse()
        r.read()
        assert r.status == 401
        c.close()
    finally:
        srv.shutdown()


def test_ml_sdk_and_cli(tmp_path):
    from surrealdb_tpu.sdk import Surreal

    with Surreal("mem://") as db:
        db.use("test", "test")
        db.query("DEFINE MODEL ml::house<1.0.0>;")
        db.import_model(LINEAR)
        out = db.query("RETURN ml::house<1.0.0>([1.0, 1.0]);")
        assert out[0]["result"] == pytest.approx(15.0)
        spec = db.export_model("house", "1.0.0")
        assert spec["layers"][0]["b"] == [10.0]


def test_ml_remove_model_gcs_blob(ml_ds):
    """REMOVE MODEL deletes the content-addressed weights blob when no other
    model version references it (ADVICE r2: orphaned blobs)."""
    from surrealdb_tpu import key as keys
    from surrealdb_tpu.key.encode import prefix_end

    pre = keys.blob_prefix("test", "test")
    txn = ml_ds.transaction(False)
    try:
        assert txn.scan(pre, prefix_end(pre))  # blob exists before
    finally:
        txn.cancel()
    ml_ds.execute("REMOVE MODEL ml::house<1.0.0>;")
    txn = ml_ds.transaction(False)
    try:
        assert not txn.scan(pre, prefix_end(pre))  # blob gone after
    finally:
        txn.cancel()


def test_ml_remove_database_clears_compiled_cache(ml_ds):
    """A recreated database must not serve the removed database's compiled
    weights from the cache (ADVICE r2 medium)."""
    assert ml_ds.execute("RETURN ml::house<1.0.0>([1.0, 2.0]);")[0]["status"] == "OK"
    ml_ds.execute("REMOVE DATABASE test;")
    out = ml_ds.execute("RETURN ml::house<1.0.0>([1.0, 2.0]);")
    assert out[0]["status"] == "ERR"
    assert "does not exist" in out[0]["result"]
