"""SDK + server black-box tests (mirrors the reference's tests/http_integration
and ws_integration style, but in-process)."""

import threading
import time

import pytest

from surrealdb_tpu.sdk import Surreal


def test_local_sdk_crud():
    with Surreal("mem://") as db:
        db.use("t", "t")
        row = db.create("person:1", {"name": "a", "age": 30})
        assert row[0]["name"] == "a"
        assert db.select("person:1")[0]["age"] == 30
        db.merge("person:1", {"age": 31})
        assert db.select("person:1")[0]["age"] == 31
        out = db.query("SELECT VALUE age FROM person")
        assert out[0]["result"] == [31]
        deleted = db.delete("person:1")
        assert deleted[0]["name"] == "a"
        assert db.select("person") == []


def test_local_sdk_relate_and_live():
    with Surreal("mem://") as db:
        db.use("t", "t")
        db.create("person:1")
        db.create("person:2")
        db.relate("person:1", "knows", "person:2", {"w": 1})
        out = db.query("SELECT VALUE ->knows->person FROM person:1")
        assert len(out[0]["result"][0]) == 1

        stream = db.live("person")
        db.create("person:3", {"name": "c"})
        n = stream.next(timeout=1)
        assert n is not None
        assert n["action"] == "CREATE"
        assert n["result"]["name"] == "c"


def test_local_sdk_let_and_run():
    with Surreal("mem://") as db:
        db.use("t", "t")
        db.let("x", 5)
        assert db.query("RETURN $x * 2")[0]["result"] == 10
        assert db.run("math::abs", None, [-3]) == 3


def test_export_import_roundtrip():
    with Surreal("mem://") as db:
        db.use("t", "t")
        db.query("DEFINE TABLE person; DEFINE FIELD age ON person TYPE int;")
        db.create("person:1", {"name": "a", "age": 1})
        db.query("CREATE person:2 SET name = 'b', age = 2")
        db.relate("person:1", "knows", "person:2")
        dump = db.export()
    assert "DEFINE TABLE person" in dump
    assert "INSERT" in dump

    with Surreal("mem://") as db2:
        db2.use("t", "t")
        db2.import_(dump)
        rows = db2.select("person")
        assert len(rows) == 2
        out = db2.query("SELECT VALUE ->knows->person FROM person:1")
        assert len(out[0]["result"][0]) == 1


@pytest.fixture(scope="module")
def server():
    from surrealdb_tpu.net.server import serve

    srv = serve("memory", port=0, auth_enabled=False).start_background()
    # root user for auth tests
    from surrealdb_tpu.dbs.session import Session

    srv.httpd.RequestHandlerClass.ds.execute(
        "DEFINE USER root ON ROOT PASSWORD 'root' ROLES OWNER;", Session.owner(None, None)
    )
    yield srv
    srv.shutdown()


def test_http_health_version(server):
    import http.client

    conn = http.client.HTTPConnection(server.host, server.port)
    conn.request("GET", "/health")
    r = conn.getresponse()
    assert r.status == 200
    r.read()  # drain before reusing the keep-alive connection
    conn.request("GET", "/version")
    r = conn.getresponse()
    assert b"surrealdb-tpu" in r.read()
    conn.close()


def test_http_sql(server):
    import http.client
    import json

    conn = http.client.HTTPConnection(server.host, server.port)
    conn.request(
        "POST",
        "/sql",
        "CREATE hp:1 SET v = 9; SELECT VALUE v FROM hp;",
        {"surreal-ns": "t", "surreal-db": "t"},
    )
    r = conn.getresponse()
    out = json.loads(r.read())
    assert out[0]["status"] == "OK"
    assert out[1]["result"] == [9]
    conn.close()


def test_http_key_rest(server):
    import http.client
    import json

    conn = http.client.HTTPConnection(server.host, server.port)
    hdrs = {"surreal-ns": "t", "surreal-db": "t", "Content-Type": "application/json"}
    conn.request("POST", "/key/widget/w1", json.dumps({"size": 3}), hdrs)
    assert json.loads(conn.getresponse().read())[0]["status"] == "OK"
    conn.request("GET", "/key/widget/w1", headers=hdrs)
    out = json.loads(conn.getresponse().read())
    assert out[0]["result"][0]["size"] == 3
    conn.request("DELETE", "/key/widget/w1", headers=hdrs)
    conn.getresponse().read()
    conn.request("GET", "/key/widget/w1", headers=hdrs)
    assert json.loads(conn.getresponse().read())[0]["result"] == []
    conn.close()


def test_http_sdk_remote(server):
    db = Surreal(f"http://{server.host}:{server.port}")
    db.use("t", "t")
    db.create("remote:1", {"x": 1})
    assert db.select("remote:1")[0]["x"] == 1
    out = db.query("SELECT VALUE x FROM remote")
    assert out[0]["result"] == [1]
    db.close()


def test_ws_sdk_remote(server):
    db = Surreal(f"ws://{server.host}:{server.port}/rpc")
    db.use("t", "t")
    db.create("wsrec:1", {"x": 2})
    assert db.select("wsrec:1")[0]["x"] == 2

    stream = db.live("wsrec")
    time.sleep(0.05)
    db.create("wsrec:2", {"x": 3})
    n = stream.next(timeout=2)
    assert n is not None and n["action"] == "CREATE"
    db.close()


def test_ws_rpc_waits_while_the_socket_lives():
    """The WS engine has no deadline of its own (the first kNN statement
    after a 1M-row load runs for minutes): an RPC waits for its reply as
    long as the connection is alive and is released when it goes."""
    import socket

    from surrealdb_tpu.err import SurrealError
    from surrealdb_tpu.net import ws as wsproto
    from surrealdb_tpu.sdk.remote import WsEngine

    lsock = socket.create_server(("127.0.0.1", 0))
    conns = []

    def silent_server():  # upgrades, then never answers
        c, _ = lsock.accept()
        req = b""
        while b"\r\n\r\n" not in req:
            req += c.recv(4096)
        key = next(
            line.split(b":", 1)[1].strip().decode()
            for line in req.split(b"\r\n")
            if line.lower().startswith(b"sec-websocket-key:")
        )
        c.sendall(
            b"HTTP/1.1 101 Switching Protocols\r\nUpgrade: websocket\r\n"
            b"Connection: Upgrade\r\nSec-WebSocket-Accept: "
            + wsproto.accept_key(key).encode() + b"\r\n\r\n"
        )
        conns.append(c)

    t = threading.Thread(target=silent_server)
    t.start()
    eng = WsEngine(f"ws://127.0.0.1:{lsock.getsockname()[1]}/rpc")
    t.join()
    raised = []

    def call():
        try:
            eng.rpc("query", ["SELECT 1"])
        except SurrealError as e:
            raised.append(str(e))

    caller = threading.Thread(target=call)
    caller.start()
    caller.join(0.3)
    assert caller.is_alive()  # no reply yet, socket alive: still waiting
    conns[0].close()
    caller.join(5)
    assert not caller.is_alive()
    assert "closed before the reply" in raised[0]
    with pytest.raises(SurrealError, match="closed"):
        eng.rpc("ping", [])
    eng.close()
    lsock.close()


def test_signin_http(server):
    import http.client
    import json

    conn = http.client.HTTPConnection(server.host, server.port)
    conn.request(
        "POST", "/signin", json.dumps({"user": "root", "pass": "root"}),
        {"Content-Type": "application/json"},
    )
    out = json.loads(conn.getresponse().read())
    assert out.get("token"), out
    conn.close()
