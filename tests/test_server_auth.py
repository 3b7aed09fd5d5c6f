"""Server auth-gating + REST escaping tests (review regressions)."""

import base64
import http.client
import json

import pytest


@pytest.fixture()
def authed_server(ds):
    from surrealdb_tpu.net.server import Server
    from surrealdb_tpu.dbs.session import Session

    ds.execute("CREATE a:1;")
    ds.execute(
        "DEFINE USER nsu ON NAMESPACE PASSWORD 'pw' ROLES EDITOR;",
        Session.owner("test", None),
    )
    srv = Server(ds, port=0, auth_enabled=True).start_background()
    yield srv
    srv.shutdown()


def _conn(srv):
    return http.client.HTTPConnection(srv.host, srv.port)


def test_anonymous_rejected(authed_server):
    c = _conn(authed_server)
    hdrs = {"surreal-ns": "test", "surreal-db": "test"}
    c.request("POST", "/sql", "SELECT * FROM a;", hdrs)
    r = c.getresponse(); r.read()
    assert r.status == 401
    c.request("GET", "/export", headers=hdrs)
    r = c.getresponse(); r.read()
    assert r.status == 401
    c.request("GET", "/key/a", headers=hdrs)
    r = c.getresponse(); r.read()
    assert r.status == 401
    c.close()


def test_ns_user_basic_auth(authed_server):
    hdrs = {
        "Authorization": "Basic " + base64.b64encode(b"nsu:pw").decode(),
        "surreal-ns": "test",
        "surreal-db": "test",
    }
    c = _conn(authed_server)
    c.request("POST", "/sql", "RETURN 1;", hdrs)
    r = c.getresponse()
    out = json.loads(r.read())
    assert r.status == 200 and out[0]["result"] == 1
    c.close()


def test_key_route_escapes_ids(authed_server):
    hdrs = {
        "Authorization": "Basic " + base64.b64encode(b"nsu:pw").decode(),
        "surreal-ns": "test",
        "surreal-db": "test",
        "Content-Type": "application/json",
    }
    c = _conn(authed_server)
    weird = "8424486b-85b3-4448-ac8d-5d51083391c7"
    c.request("POST", f"/key/widget/{weird}", json.dumps({"v": 1}), hdrs)
    out = json.loads(c.getresponse().read())
    assert out[0]["status"] == "OK", out
    c.request("GET", f"/key/widget/{weird}", headers=hdrs)
    out = json.loads(c.getresponse().read())
    assert out[0]["result"][0]["v"] == 1
    # an id shaped like an injection stays an id
    from urllib.parse import quote

    evil = quote("1;REMOVE TABLE widget", safe="")
    c.request("POST", "/key/widget/" + evil, json.dumps({"v": 2}), hdrs)
    out = json.loads(c.getresponse().read())
    assert out[0]["status"] == "OK", out
    c.request("GET", f"/key/widget/{weird}", headers=hdrs)
    out = json.loads(c.getresponse().read())
    assert out[0]["result"], "table must still exist"
    c.close()


# every observability route that serves statement text, tenant names or
# span trees, on both front ends: the threaded handler and the event loop
# (the default ingress, the one a deployment is served from)
_GATED = ("/slow", "/traces", "/trace/abcd", "/debug/bundle", "/events",
          "/statements", "/tenants")


@pytest.mark.parametrize("front", ["threaded", "loop"])
@pytest.mark.parametrize("route", _GATED)
def test_observability_route_needs_a_system_user(ds, monkeypatch, front, route):
    from surrealdb_tpu import cnf
    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.net.server import Server

    monkeypatch.setattr(cnf, "NET_LOOP", front == "loop")
    ds.execute(
        "DEFINE USER nsu ON NAMESPACE PASSWORD 'pw' ROLES EDITOR;",
        Session.owner("test", None),
    )
    srv = Server(ds, port=0, auth_enabled=True).start_background()
    try:
        assert srv.loop_mode is (front == "loop")
        c = _conn(srv)
        c.request("GET", route)
        r = c.getresponse(); r.read()
        assert r.status == 401, (front, route)
        # a system user passes the gate (no trace has the id `abcd`)
        c.request("GET", route, headers={
            "Authorization": "Basic " + base64.b64encode(b"nsu:pw").decode(),
            "surreal-ns": "test",
        })
        r = c.getresponse(); r.read()
        assert r.status == (404 if route.startswith("/trace/") else 200), (front, route)
        # /metrics carries no statement text and stays open
        c.request("GET", "/metrics")
        r = c.getresponse(); r.read()
        assert r.status == 200
        c.close()
    finally:
        srv.shutdown()


def test_insert_ignore_relation(ds):
    ds.execute("CREATE a:1; CREATE b:1; RELATE a:1->likes->b:1;")
    edge = ds.execute("SELECT VALUE id FROM likes;")[0]["result"][0]
    r = ds.execute(
        f"INSERT IGNORE RELATION [{{ id: {edge}, in: a:1, out: b:1, extra: 1 }}];"
    )
    assert r[0]["result"] == []
    row = ds.execute("SELECT * FROM likes;")[0]["result"][0]
    assert "extra" not in row


def test_bm25_single_arg(ds):
    r = ds.execute("DEFINE INDEX i1 ON t FIELDS body SEARCH ANALYZER like BM25(1.2);")
    assert r[0]["status"] == "OK", r


def test_wire_rejects_pickle_ext(authed_server):
    """ADVICE r1: EXT_PYOBJ from the network must never reach pickle.loads."""
    import msgpack
    import os
    import pickle

    marker = "/tmp/surreal_tpu_pickle_pwn"
    if os.path.exists(marker):
        os.unlink(marker)

    class Boom:
        def __reduce__(self):
            return (open, (marker, "w"))

    body = msgpack.packb(msgpack.ExtType(32, pickle.dumps(Boom())))
    c = _conn(authed_server)
    c.request("POST", "/rpc", body, {"Content-Type": "application/msgpack"})
    r = c.getresponse()
    r.read()
    assert r.status == 400
    assert not os.path.exists(marker), "pickle payload was executed"
    c.close()


def test_rpc_http_anonymous_guard(authed_server):
    """ADVICE r1: anonymous POST /rpc may not run data methods."""
    c = _conn(authed_server)
    hdrs = {"Content-Type": "application/json", "surreal-ns": "test", "surreal-db": "test"}
    c.request("POST", "/rpc", json.dumps({"id": 1, "method": "query", "params": ["SELECT * FROM a"]}), hdrs)
    r = c.getresponse()
    r.read()
    assert r.status == 401
    c.request("POST", "/rpc", json.dumps({"id": 2, "method": "ping", "params": []}), hdrs)
    r = c.getresponse()
    out = json.loads(r.read())
    assert r.status == 200 and "error" not in out
    c.close()


@pytest.fixture()
def record_access_server(ds):
    from surrealdb_tpu.net.server import Server

    ds.execute("CREATE a:1;")
    # no WITH KEY — server must generate a random key so tokens round-trip
    ds.execute(
        "DEFINE ACCESS account ON DATABASE TYPE RECORD "
        "SIGNUP (CREATE user SET email = $email) "
        "SIGNIN (SELECT * FROM user WHERE email = $email);"
    )
    srv = Server(ds, port=0, auth_enabled=True).start_background()
    yield srv
    srv.shutdown()


def _record_token(srv):
    c = _conn(srv)
    c.request(
        "POST",
        "/signup",
        json.dumps({"ns": "test", "db": "test", "ac": "account", "email": "a@b.c"}),
        {"Content-Type": "application/json"},
    )
    out = json.loads(c.getresponse().read())
    c.close()
    return out["token"]


def test_record_user_cannot_export(record_access_server):
    """ADVICE r1: /export requires a system user, not record access."""
    token = _record_token(record_access_server)
    c = _conn(record_access_server)
    hdrs = {"Authorization": f"Bearer {token}", "surreal-ns": "test", "surreal-db": "test"}
    c.request("GET", "/export", headers=hdrs)
    r = c.getresponse()
    r.read()
    assert r.status == 401
    c.close()


def test_access_token_reauthenticates(record_access_server):
    """ADVICE r1: DEFINE ACCESS without WITH KEY gets a random key, so the
    issued token verifies when presented back."""
    token = _record_token(record_access_server)
    c = _conn(record_access_server)
    hdrs = {"Authorization": f"Bearer {token}", "surreal-ns": "test", "surreal-db": "test"}
    c.request("POST", "/sql", "RETURN 7;", hdrs)
    r = c.getresponse()
    out = json.loads(r.read())
    assert r.status == 200 and out[0]["result"] == 7
    c.close()


def test_wire_rejects_nested_pickle_ext(authed_server):
    """The EXT_PYOBJ rejection must hold at every nesting depth (review r2):
    a pickle ext hidden inside EXT_THING's payload must not decode."""
    import msgpack
    import os
    import pickle

    marker = "/tmp/surreal_tpu_nested_pwn"
    if os.path.exists(marker):
        os.unlink(marker)

    class Boom:
        def __reduce__(self):
            return (open, (marker, "w"))

    inner = msgpack.packb({"tb": "t", "id": msgpack.ExtType(32, pickle.dumps(Boom()))})
    body = msgpack.packb(msgpack.ExtType(2, inner))  # EXT_THING wrapper
    c = _conn(authed_server)
    c.request("POST", "/rpc", body, {"Content-Type": "application/msgpack"})
    r = c.getresponse()
    r.read()
    assert r.status == 400
    assert not os.path.exists(marker), "nested pickle payload was executed"
    c.close()


def test_ws_anonymous_guard(authed_server):
    """WS /rpc enforces the same default-deny guest policy as HTTP /rpc."""
    import socket as _socket

    from surrealdb_tpu.net import ws as wsproto

    sock = _socket.create_connection((authed_server.host, authed_server.port))
    sock.sendall(
        b"GET /rpc HTTP/1.1\r\nHost: x\r\nUpgrade: websocket\r\nConnection: Upgrade\r\n"
        b"Sec-WebSocket-Key: dGhlIHNhbXBsZSBub25jZQ==\r\nSec-WebSocket-Version: 13\r\n\r\n"
    )
    # read the 101 response headers
    buf = b""
    while b"\r\n\r\n" not in buf:
        buf += sock.recv(4096)
    f = sock.makefile("rb")

    def rpc(method, params):
        frame = wsproto.encode_frame(
            wsproto.OP_TEXT, json.dumps({"id": 1, "method": method, "params": params}).encode(), mask=True
        )
        sock.sendall(frame)
        op, payload = wsproto.read_frame(f)
        return json.loads(payload)

    out = rpc("query", ["SELECT * FROM a"])
    assert "error" in out, out
    out = rpc("ping", [])
    assert "error" not in out, out
    sock.close()


def test_wire_pack_degrades_closures():
    """wire_pack never emits EXT_PYOBJ; engine internals become strings."""
    from surrealdb_tpu.utils.ser import wire_pack, wire_unpack
    from surrealdb_tpu.sql.value import Thing

    from surrealdb_tpu.syn import parse_value

    clo = parse_value("|$x| $x + 1")
    out = wire_unpack(wire_pack({"c": clo, "t": Thing("a", 1)}))
    assert isinstance(out["c"], str)
    assert out["t"] == Thing("a", 1)
