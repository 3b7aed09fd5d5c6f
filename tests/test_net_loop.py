"""C1M network plane: event-loop ingress, per-tenant weighted-fair QoS,
and every overload contract — slowloris header dribblers, readers that
never drain their write queue, accept storms past the connection cap —
must end in a BOUNDED buffer and a clean counted close, never unbounded
memory. Plus the r19 live-query disconnect leak regression."""

import socket
import time

import pytest

from surrealdb_tpu import cnf, events, telemetry
from surrealdb_tpu.net import loop as netloop
from surrealdb_tpu.net import qos
from surrealdb_tpu.net.server import serve


@pytest.fixture()
def srv():
    qos.reset()
    events.reset()
    s = serve(auth_enabled=False, port=0).start_background()
    assert s.loop_mode, "event-loop ingress must be the default"
    yield s
    s.shutdown()
    qos.reset()


def _counter(name, **labels):
    # snapshot keys are flat strings: 'name' or 'name{k="v",k2="v2"}'
    snap = telemetry.snapshot()["counters"]
    total = 0.0
    for key, v in snap.items():
        kname, _, rest = key.partition("{")
        kl = {}
        if rest:
            for pair in rest.rstrip("}").split(","):
                k, _, val = pair.partition("=")
                kl[k.strip()] = val.strip().strip('"')
        if kname == name and all(kl.get(k) == v2 for k, v2 in labels.items()):
            total += v
    return total


def _http(body, ns="t", db="t", path="/sql"):
    body = body.encode() if isinstance(body, str) else body
    return (
        f"POST {path} HTTP/1.1\r\nHost: x\r\nsurreal-ns: {ns}\r\n"
        f"surreal-db: {db}\r\nContent-Length: {len(body)}\r\n\r\n"
    ).encode() + body


def _wait(pred, timeout=8.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


class _Sink:
    """Accumulate a VirtualConn's drained output across waits."""

    def __init__(self, vc):
        self.vc = vc
        self.buf = b""

    def has(self, needle: bytes) -> bool:
        self.buf += self.vc.take_output()
        return needle in self.buf


# ------------------------------------------------------------------ transport
def test_virtual_conn_serves_http(srv):
    vc = srv.netloop.loops[0].attach_virtual()
    sink = _Sink(vc)
    vc.feed(_http("RETURN 2 + 3;"))
    assert _wait(lambda: sink.has(b"HTTP/1.1 200")), sink.buf[:300]
    assert b"5" in sink.buf
    vc.close()


def test_keepalive_pipelining_on_one_virtual_conn(srv):
    vc = srv.netloop.loops[0].attach_virtual()
    sink = _Sink(vc)
    for i in range(3):
        vc.feed(_http(f"RETURN {i};"))

    def _three_done():
        sink.has(b"")  # drain whatever arrived
        return sink.buf.count(b"HTTP/1.1 200") >= 3

    assert _wait(_three_done), sink.buf[:400]
    assert sink.buf.count(b"HTTP/1.1 200") == 3
    vc.close()


def test_real_socket_roundtrip(srv):
    s = socket.create_connection((srv.host, srv.port), timeout=5)
    s.sendall(_http("RETURN 41 + 1;"))
    buf = b""
    s.settimeout(5)
    while b"42" not in buf:
        chunk = s.recv(4096)
        if not chunk:
            break
        buf += chunk
    assert b"HTTP/1.1 200" in buf and b"42" in buf
    s.close()


# ------------------------------------------------------------------ overload
def test_slowloris_header_dribbler_is_closed_within_bounds(srv, monkeypatch):
    monkeypatch.setattr(cnf, "NET_HEADER_TIMEOUT_SECS", 0.2)
    before = _counter("net_overload_close", reason="header_timeout")
    vc = srv.netloop.loops[0].attach_virtual()
    vc.feed(b"POST /sql HT")  # partial request line, never completes
    assert _wait(lambda: vc.closed), "slowloris conn never closed"
    assert _wait(
        lambda: _counter("net_overload_close", reason="header_timeout") > before
    )
    assert any(
        e["kind"] == "net.overload_close" and e.get("reason") == "header_timeout"
        for e in events.snapshot()
    )


def test_idle_keepalive_conn_survives_header_deadline(srv, monkeypatch):
    monkeypatch.setattr(cnf, "NET_HEADER_TIMEOUT_SECS", 0.2)
    vc = srv.netloop.loops[0].attach_virtual()
    # no bytes at all: an idle keep-alive socket is NOT a slowloris
    time.sleep(0.6)
    assert not vc.closed
    sink = _Sink(vc)
    vc.feed(_http("RETURN 7;"))
    assert _wait(lambda: sink.has(b"HTTP/1.1 200")), sink.buf[:300]
    vc.close()


def test_never_draining_reader_gets_backpressure_close(srv, monkeypatch):
    monkeypatch.setattr(cnf, "NET_WRITE_BUF_MAX", 8192)
    vc = srv.netloop.loops[0].attach_virtual(collect=False)  # never drains
    payload = "RETURN '" + "x" * 2000 + "';"
    for _ in range(20):
        if vc.closed:
            break
        vc.feed(_http(payload))
        time.sleep(0.05)
    assert _wait(lambda: vc.closed, timeout=10.0), (
        "reader that never drains must be closed, not buffered unboundedly"
    )
    assert _counter("net_backpressure_close") >= 1
    assert any(e["kind"] == "net.backpressure_close" for e in events.snapshot())


def test_accept_storm_sheds_past_conn_cap(srv, monkeypatch):
    monkeypatch.setattr(cnf, "NET_MAX_CONNS", 8)
    before = _counter("net_overload_close", reason="conn_cap")
    socks = []
    try:
        for _ in range(40):
            s = socket.create_connection((srv.host, srv.port), timeout=2)
            socks.append(s)
        assert _wait(
            lambda: _counter("net_overload_close", reason="conn_cap") > before
        ), "accept storm past the cap must shed (counted close)"
        assert any(
            e["kind"] == "net.overload_close" and e.get("reason") == "conn_cap"
            for e in events.snapshot()
        )
        # the loop held its bound: open conns stay at/under the cap
        assert srv.netloop.total_conns() <= 8
    finally:
        for s in socks:
            s.close()


# ------------------------------------------------------------------ QoS
def test_shed_is_observable_via_event_counter_and_503(srv, monkeypatch):
    # quota 1 in-flight, queue of 1: the flood's tail sheds with a 503
    monkeypatch.setattr(cnf, "NET_TENANT_INFLIGHT", 1)
    monkeypatch.setattr(cnf, "NET_ADMIT_QUEUE", 1)
    vc = srv.netloop.loops[0].attach_virtual()
    vc.feed(_http("RETURN sleep(400ms);", ns="acme", db="app"))
    time.sleep(0.1)  # let the slow request take the tenant's only slot
    # one request per conn: a single conn serializes its HTTP requests, so
    # the flood needs parallel connections to overflow the admission queue
    sinks = []
    for _ in range(4):  # 1 queues, the rest overflow the bounded queue
        vcn = srv.netloop.loops[0].attach_virtual()
        sinks.append(_Sink(vcn))
        vcn.feed(_http("RETURN 1;", ns="acme", db="app"))
    assert _wait(lambda: any(s.has(b"503") for s in sinks)), [
        s.buf[:120] for s in sinks
    ]
    shed_buf = next(s.buf for s in sinks if b"503" in s.buf)
    assert b"overloaded" in shed_buf
    ev = [e for e in events.snapshot() if e["kind"] == "net.admission_shed"]
    assert ev and ev[-1]["ns"] == "acme" and ev[-1]["db"] == "app"
    assert _counter("net_admission_shed") >= 1
    snap = qos.snapshot()
    assert snap["totals"]["shed"] >= 1
    top = {(t["ns"], t["db"]): t for t in snap["top"]}
    assert top[("acme", "app")]["shed"] >= 1
    vc.close()
    for s in sinks:
        s.vc.close()


def test_victim_tenant_is_never_shed_under_a_flood(srv, monkeypatch):
    """The isolation contract end to end: one tenant floods past its
    quota and queue and is shed; a well-behaved tenant's every request,
    sent into the same flood, is answered 200 and never shed."""
    monkeypatch.setattr(cnf, "NET_TENANT_INFLIGHT", 1)
    monkeypatch.setattr(cnf, "NET_ADMIT_QUEUE", 1)
    loop0 = srv.netloop.loops[0]
    hog = loop0.attach_virtual()
    hog.feed(_http("RETURN sleep(600ms);", ns="abuser", db="app"))
    time.sleep(0.1)  # the slow request holds the abuser's only slot
    flood = []
    for _ in range(6):  # 1 queues, the rest overflow the bounded queue
        vcn = loop0.attach_virtual()
        flood.append(_Sink(vcn))
        vcn.feed(_http("RETURN 1;", ns="abuser", db="app"))
    victims = []
    for i in range(8):  # one at a time: the victim stays inside its quota
        vcn = loop0.attach_virtual()
        sink = _Sink(vcn)
        victims.append(sink)
        vcn.feed(_http(f"RETURN {7000 + i};", ns="victim", db="app"))
        assert _wait(lambda: sink.has(str(7000 + i).encode())), sink.buf[:200]
        # the status line, not the bytes: a random trace id holds "503" in one reply of seventy
        assert b"200 OK" in sink.buf and b"HTTP/1.1 503" not in sink.buf, sink.buf[:200]
    assert _wait(lambda: any(s.has(b"503") for s in flood)), [
        s.buf[:120] for s in flood
    ]
    top = {(t["ns"], t["db"]): t for t in qos.snapshot()["top"]}
    assert top[("abuser", "app")]["shed"] >= 1
    assert top[("victim", "app")]["shed"] == 0
    assert top[("victim", "app")]["admitted"] == len(victims)
    shed = [e for e in events.snapshot() if e["kind"] == "net.admission_shed"]
    assert shed and all(e["ns"] == "abuser" for e in shed), shed
    hog.close()
    for s in flood + victims:
        s.vc.close()


def test_throttle_queues_then_admits(srv, monkeypatch):
    monkeypatch.setattr(cnf, "NET_TENANT_INFLIGHT", 1)
    monkeypatch.setattr(cnf, "NET_ADMIT_QUEUE", 64)
    vc = srv.netloop.loops[0].attach_virtual()
    vc.feed(_http("RETURN sleep(200ms);", ns="busy", db="app"))
    time.sleep(0.05)
    vc2 = srv.netloop.loops[0].attach_virtual()
    sink2 = _Sink(vc2)
    vc2.feed(_http("RETURN 42;", ns="busy", db="app"))
    # throttled, not shed: the second request eventually completes
    assert _wait(lambda: sink2.has(b"42"), timeout=12.0), sink2.buf[:300]
    assert any(e["kind"] == "net.throttle" for e in events.snapshot())
    assert qos.snapshot()["totals"]["throttled"] >= 1
    vc.close()
    vc2.close()


def test_per_tenant_quota_isolates_floods(monkeypatch):
    qos.reset()
    monkeypatch.setattr(cnf, "NET_TENANT_INFLIGHT", 1)
    got = []
    for i in range(5):
        qos.submit("heavy", "app", lambda i=i: got.append(("A", i)))
    for i in range(2):
        qos.submit("light", "app", lambda i=i: got.append(("B", i)))
    # quota 1 each: the flood holds ONE slot; the light tenant still admits
    assert ("A", 0) in got and ("B", 0) in got
    assert len(got) == 2
    qos.release("heavy", "app")
    assert ("A", 1) in got  # FIFO within the tenant
    qos.reset()


def test_wfq_drain_order_prefers_cheap_tenant(monkeypatch):
    """Start-time fair queueing: the tenant whose admits cost less (per the
    r16 stats estimate) accrues virtual time slower, so a contended drain
    serves it first — weighted fairness, not FIFO arrival order."""
    qos.reset()
    monkeypatch.setattr(cnf, "NET_TENANT_RATE", 50.0)
    monkeypatch.setattr(cnf, "NET_TENANT_BURST", 1.0)
    monkeypatch.setattr(
        qos, "cost_estimate_ms", lambda fp: 100.0 if fp == "hvy" else 1.0
    )
    got = []
    # each tenant burns its 1-token burst on the first admit; the second
    # submit queues until the bucket refills
    qos.submit("pig", "a", lambda: got.append("H1"), fingerprint="hvy")
    qos.submit("pig", "a", lambda: got.append("H2"), fingerprint="hvy")
    qos.submit("mouse", "a", lambda: got.append("L1"), fingerprint="chp")
    qos.submit("mouse", "a", lambda: got.append("L2"), fingerprint="chp")
    assert got == ["H1", "L1"]
    time.sleep(0.06)  # both buckets refill >= 1 token
    qos.poll()  # ONE contended drain pass over both queues
    assert got.index("L2") < got.index("H2"), got
    qos.reset()


def test_tenant_weight_derives_from_accounting(monkeypatch):
    import surrealdb_tpu.accounting as acct

    qos.reset()
    assert qos.tenant_weight("never", "seen") == 1.0
    monkeypatch.setattr(acct, "get", lambda ns, db: {"exec_s": 8.0})
    monkeypatch.setattr(acct, "global_totals", lambda: {"exec_s": 10.0})
    monkeypatch.setattr(acct, "size", lambda: 5)
    # fair share 2.0s vs 8.0s burned -> floor clamp
    assert qos.tenant_weight("pig", "app") == 0.25
    monkeypatch.setattr(acct, "get", lambda ns, db: {"exec_s": 0.1})
    # 2.0 / 0.1 = 20 -> ceiling clamp
    assert qos.tenant_weight("mouse", "app") == 4.0


def test_internal_class_has_dedicated_slots(monkeypatch):
    qos.reset()
    monkeypatch.setattr(cnf, "NET_TENANT_INFLIGHT", 1)
    got = []
    qos.submit("t", "t", lambda: got.append("tenant1"))
    qos.submit("t", "t", lambda: got.append("tenant2"))  # queued behind quota
    qos.submit(None, None, lambda: got.append("internal"), cls=qos.INTERNAL)
    # the cluster channel never waits behind a tenant's quota
    assert "internal" in got
    assert "tenant2" not in got
    qos.release("t", "t")
    assert "tenant2" in got
    qos.release("t", "t")
    qos.release(None, None, cls=qos.INTERNAL)
    qos.reset()


def test_metrics_and_bundle_expose_net_plane(srv):
    vc = srv.netloop.loops[0].attach_virtual()
    sink = _Sink(vc)
    vc.feed(_http("RETURN 1;"))
    assert _wait(lambda: sink.has(b"HTTP/1.1 200"))
    telemetry.collect_node_metrics()
    out = telemetry.render_prometheus()
    for series in (
        "surreal_net_open_connections",
        "surreal_net_write_queued_bytes",
        "surreal_net_admission_queued",
        "surreal_net_admission_inflight",
    ):
        assert series in out, f"{series} missing from /metrics"
    from surrealdb_tpu import bundle

    b = bundle.debug_bundle(srv.httpd.RequestHandlerClass.ds)
    assert b["schema"] == "surrealdb-tpu-bundle/11"
    assert "net" in b and b["net"]["enabled"]
    assert b["net"]["servers"], "live server missing from bundle net section"
    assert b["net"]["servers"][0]["conns"] >= 1
    assert b["net"]["qos"]["totals"]["admitted"] >= 1
    ttfb = b["net"]["servers"][0]["accept_to_first_byte"]
    assert ttfb["samples"] >= 1 and ttfb["p99_ms"] is not None
    vc.close()


# ------------------------------------------------------------------ live leak
def test_ws_disconnect_sweeps_live_queries(srv):
    """r19 regression: a WS close/error path used to leave the
    connection's live-query registrations in the hub forever."""
    from surrealdb_tpu.sdk.remote import WsEngine

    ds = srv.httpd.RequestHandlerClass.ds
    base = ds.notifications.live_count()
    eng = WsEngine(f"ws://{srv.host}:{srv.port}/rpc")
    eng.rpc("use", ["t", "t"])
    for _ in range(3):
        eng.rpc("live", ["person"])
    assert ds.notifications.live_count() == base + 3
    # abrupt close — no KILLs, no close frame: the worst-case error path.
    # shutdown() (not just close()) so the FIN actually goes out: the SDK's
    # reader thread is parked in recv() and pins the fd open otherwise
    eng.sock.shutdown(socket.SHUT_RDWR)
    eng.sock.close()
    assert _wait(lambda: ds.notifications.live_count() == base, timeout=10.0), (
        f"live queries leaked after disconnect: {ds.notifications.live_count()}"
    )


def test_ws_clean_close_also_sweeps(srv):
    from surrealdb_tpu.net import ws as wsproto
    from surrealdb_tpu.sdk.remote import WsEngine

    ds = srv.httpd.RequestHandlerClass.ds
    base = ds.notifications.live_count()
    eng = WsEngine(f"ws://{srv.host}:{srv.port}/rpc")
    eng.rpc("use", ["t", "t"])
    eng.rpc("live", ["person"])
    assert ds.notifications.live_count() == base + 1
    # protocol-level close frame
    eng.sock.sendall(wsproto.encode_frame(wsproto.OP_CLOSE, b"", mask=True))
    assert _wait(lambda: ds.notifications.live_count() == base, timeout=10.0)
    eng.sock.close()
