"""Request-scoped tracing: trace propagation across ingresses, span-tree
parentage (including dispatch fan-out re-parenting), tail-based sampling
bounds, the /trace endpoints + auth posture, slow-query linkage, node
runtime metrics, and the telemetry registry hammer (thread-safety)."""

import json
import socket
import threading
import time

import pytest

from surrealdb_tpu import cnf, telemetry, tracing
from surrealdb_tpu.dbs.dispatch import DispatchQueue


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    tracing.store_reset()
    yield
    tracing.store_reset()


@pytest.fixture()
def sample_all(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)


def _spans_by_name(doc, name):
    return [s for s in doc["spans"] if s["name"] == name]


def _parent_of(doc, span):
    return next((s for s in doc["spans"] if s["id"] == span["parent"]), None)


# ------------------------------------------------------------------ core tree
def test_execute_builds_span_tree(ds, sample_all):
    ds.execute("CREATE t:1 SET v = 1; SELECT * FROM t;")
    ids = tracing.trace_ids()
    assert len(ids) == 1
    doc = tracing.get_trace(ids[0])
    root = next(s for s in doc["spans"] if s["parent"] is None)
    assert root["name"] == "execute"
    stmts = _spans_by_name(doc, "statement")
    assert {s["labels"]["kind"] for s in stmts} == {
        "CreateStatement", "SelectStatement",
    }
    # executor -> statement -> planner parentage
    assert all(s["parent"] == root["id"] for s in stmts)
    plan = _spans_by_name(doc, "plan")[0]
    sel = next(s for s in stmts if s["labels"]["kind"] == "SelectStatement")
    assert plan["parent"] == sel["id"]
    # kvs level: the write's commit is a node too
    assert _spans_by_name(doc, "txn_commit")
    # session info rides the doc (auth LEVEL only)
    assert doc["ns"] == "test" and doc["auth"] == "root"
    # nested tree + chrome export agree with the flat list
    tree = tracing.span_tree(doc)
    assert len(tree) == 1 and tree[0]["name"] == "execute"
    chrome = tracing.to_chrome(doc)
    assert len(chrome["traceEvents"]) == len(doc["spans"])
    assert all(e["ph"] == "X" for e in chrome["traceEvents"])


def test_return_is_not_an_error(ds, sample_all):
    ds.execute("RETURN 5;")
    doc = tracing.get_trace(tracing.trace_ids()[-1])
    assert doc["error"] is None


# ------------------------------------------------------------------ sampling
def test_sampling_bounds(ds, monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 0.0)
    ds.execute("RETURN 1;")
    assert tracing.trace_ids() == []  # fast + OK + unsampled -> dropped
    ds.execute("THROW 'boom';")
    assert len(tracing.trace_ids()) == 1  # errored -> always retained
    assert tracing.get_trace(tracing.trace_ids()[0])["sampled"] == "pinned"
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    ds.execute("RETURN 2;")
    assert len(tracing.trace_ids()) == 2  # sample=1 -> everything retained


def test_store_is_bounded(ds, monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    monkeypatch.setattr(cnf, "TRACE_STORE_SIZE", 8)
    for i in range(20):
        ds.execute(f"RETURN {i};")
    assert len(tracing.trace_ids()) == 8


def test_pinned_traces_survive_client_tagged_flood(ds, monkeypatch):
    """Eviction prefers weaker retention classes: a flood of client-tagged
    traces (anyone can send a traceparent) must not flush the pinned
    errored/slow traces the slow-query log cites."""
    monkeypatch.setattr(cnf, "TRACE_STORE_SIZE", 8)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 0.0)
    ds.execute("THROW 'keep me';")  # pinned
    keep = tracing.trace_ids()[0]
    for i in range(20):
        with tracing.request("flood", trace_id=f"{i:032x}"):
            pass
    assert len(tracing.trace_ids()) == 8
    assert tracing.get_trace(keep) is not None


def test_reused_trace_id_never_downgrades(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    tid = "ee" * 16
    with tracing.request("r1", trace_id=tid):
        tracing.force_keep()
    assert tracing.get_trace(tid)["name"] == "r1"
    with tracing.request("r2", trace_id=tid):  # client rank < pinned
        pass
    assert tracing.get_trace(tid)["name"] == "r1"  # not downgraded
    with tracing.request("r3", trace_id=tid):
        tracing.force_keep()
    assert tracing.get_trace(tid)["name"] == "r3"  # same rank: latest wins


def test_span_cap_counts_drops(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    monkeypatch.setattr(cnf, "TRACE_MAX_SPANS", 4)
    with tracing.request("r"):
        for _ in range(10):
            with telemetry.span("s"):
                pass
    doc = tracing.get_trace(tracing.trace_ids()[0])
    assert len(doc["spans"]) == 4
    assert doc["dropped_spans"] == 7  # 6 dropped children + the root itself


def test_disabled_records_nothing(ds, monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_ENABLED", False)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    ds.execute("THROW 'boom';")
    assert tracing.trace_ids() == []
    assert tracing.current() is None


# ------------------------------------------------------------------ http
def _serve(auth_enabled=False):
    from surrealdb_tpu.net.server import serve

    return serve("memory", port=0, auth_enabled=auth_enabled).start_background()


def test_http_traceparent_honored_and_echoed(sample_all):
    import http.client

    srv = _serve()
    try:
        conn = http.client.HTTPConnection(srv.host, srv.port)
        tid = "ab" * 16
        hdrs = {
            "surreal-ns": "t", "surreal-db": "t",
            "traceparent": f"00-{tid}-00000000000000aa-01",
        }
        conn.request("POST", "/sql", "CREATE m:1 SET v = 2; SELECT * FROM m;", hdrs)
        r = conn.getresponse()
        r.read()
        assert r.status == 200
        assert r.getheader("surreal-trace-id") == tid
        assert r.getheader("traceparent").split("-")[1] == tid

        conn.request("GET", f"/trace/{tid}", headers={"surreal-ns": "t"})
        r = conn.getresponse()
        doc = json.loads(r.read())
        assert r.status == 200
        assert doc["trace_id"] == tid
        assert doc["client_parent"] == "00000000000000aa"
        # the acceptance tree: ingress -> executor -> statement -> kvs
        root = next(s for s in doc["spans"] if s["parent"] is None)
        assert root["name"] == "http_request" and root["labels"]["route"] == "sql"
        execute = _spans_by_name(doc, "execute")[0]
        assert execute["parent"] == root["id"]
        stmts = _spans_by_name(doc, "statement")
        assert len(stmts) == 2 and all(s["parent"] == execute["id"] for s in stmts)
        assert _spans_by_name(doc, "txn_commit")
        assert doc["tree"][0]["name"] == "http_request"

        # a fresh request without inbound context still echoes a usable id
        conn.request("POST", "/sql", "RETURN 1;", {"surreal-ns": "t", "surreal-db": "t"})
        r = conn.getresponse()
        r.read()
        new_tid = r.getheader("surreal-trace-id")
        assert new_tid and new_tid != tid
        conn.request("GET", f"/trace/{new_tid}", headers={"surreal-ns": "t"})
        r = conn.getresponse()
        r.read()
        assert r.status == 200

        # chrome export round-trips
        conn.request("GET", f"/trace/{tid}?format=chrome", headers={"surreal-ns": "t"})
        r = conn.getresponse()
        chrome = json.loads(r.read())
        assert r.status == 200 and chrome["traceEvents"]

        # /traces index lists both
        conn.request("GET", "/traces", headers={"surreal-ns": "t"})
        r = conn.getresponse()
        listing = json.loads(r.read())
        assert {t["trace_id"] for t in listing} >= {tid, new_tid}
        conn.close()
    finally:
        srv.shutdown()


def test_trace_not_found_404(sample_all):
    import http.client

    srv = _serve()
    try:
        conn = http.client.HTTPConnection(srv.host, srv.port)
        conn.request("GET", "/trace/" + "0" * 32)
        r = conn.getresponse()
        r.read()
        assert r.status == 404
        conn.close()
    finally:
        srv.shutdown()


# ------------------------------------------------------------------ websocket
def test_ws_client_trace_id_stable_across_statements(sample_all):
    from surrealdb_tpu.net import ws as wsproto

    srv = _serve()
    try:
        sock = socket.create_connection((srv.host, srv.port))
        leftover = wsproto.client_handshake(sock, f"{srv.host}:{srv.port}", "/rpc")
        bs = wsproto.BufferedSocket(sock, leftover)

        def rpc(req):
            sock.sendall(
                wsproto.encode_frame(
                    wsproto.OP_TEXT, json.dumps(req).encode(), mask=True
                )
            )
            _, payload = wsproto.read_frame(bs)
            return json.loads(payload)

        rpc({"id": 1, "method": "use", "params": ["t", "t"]})
        tid = "cd" * 16
        resp = rpc(
            {
                "id": 2,
                "method": "query",
                "params": ["CREATE w:1 SET v = 1; SELECT * FROM w; RETURN 3;"],
                "trace": tid,
            }
        )
        assert resp["trace"] == tid  # honored AND echoed
        assert len(resp["result"]) == 3
        doc = tracing.get_trace(tid)
        assert doc is not None
        root = next(s for s in doc["spans"] if s["parent"] is None)
        assert root["name"] == "ws_rpc" and root["labels"]["method"] == "query"
        # one trace spans the whole multi-statement query
        stmts = _spans_by_name(doc, "statement")
        assert len(stmts) == 3
        execute = _spans_by_name(doc, "execute")[0]
        assert all(s["parent"] == execute["id"] for s in stmts)
        sock.close()
    finally:
        srv.shutdown()


def test_ws_errored_frame_echoes_retrievable_trace(sample_all):
    """An RPC frame that fails (unknown method here) must still echo a
    trace id that GET /trace/:id resolves — the error trace is pinned."""
    from surrealdb_tpu.net import ws as wsproto

    srv = _serve()
    try:
        sock = socket.create_connection((srv.host, srv.port))
        leftover = wsproto.client_handshake(sock, f"{srv.host}:{srv.port}", "/rpc")
        bs = wsproto.BufferedSocket(sock, leftover)
        sock.sendall(
            wsproto.encode_frame(
                wsproto.OP_TEXT,
                json.dumps(
                    {"id": 9, "method": "nosuch", "params": [], "trace": "my weird id!"}
                ).encode(),
                mask=True,
            )
        )
        _, payload = wsproto.read_frame(bs)
        resp = json.loads(payload)
        assert "error" in resp
        # the echoed id is the STORED (sanitized) one, and it resolves
        assert resp["trace"] == "myweirdid"
        doc = tracing.get_trace(resp["trace"])
        assert doc is not None and doc["error"] == "SurrealError"
        sock.close()
    finally:
        srv.shutdown()


# ------------------------------------------------------------------ dispatch
def test_dispatch_fanout_reparents_on_every_rider(sample_all):
    q = DispatchQueue()
    gate = threading.Event()
    started = threading.Event()

    def runner(ps):
        if list(ps) == ["lead"]:
            started.set()
            gate.wait(5)
        return [p.upper() for p in ps]

    results = {}

    def client(payload):
        with tracing.request("req", client=payload):
            with telemetry.span("statement", kind="Select"):
                results[payload] = q.submit("k", payload, runner)

    lead = threading.Thread(target=client, args=("lead",))
    lead.start()
    assert started.wait(5)
    followers = [threading.Thread(target=client, args=(p,)) for p in ("f1", "f2")]
    for t in followers:
        t.start()
    time.sleep(0.3)  # let the followers enqueue behind the busy bucket
    gate.set()
    lead.join()
    for t in followers:
        t.join()
    assert results == {"lead": "LEAD", "f1": "F1", "f2": "F2"}

    seen = {}
    for tid in tracing.trace_ids():
        doc = tracing.get_trace(tid)
        root = next(s for s in doc["spans"] if s["parent"] is None)
        stmt = _spans_by_name(doc, "statement")[0]
        launch = _spans_by_name(doc, "dispatch_launch")
        wait = _spans_by_name(doc, "dispatch_queue_wait")
        # every rider's trace carries the kernel spans, parented under ITS
        # OWN statement span — not the leader's
        assert len(launch) == 1 and launch[0]["parent"] == stmt["id"]
        assert len(wait) == 1 and wait[0]["parent"] == stmt["id"]
        seen[root["labels"]["client"]] = int(launch[0]["labels"]["batch"])
    assert set(seen) == {"lead", "f1", "f2"}
    # the two followers coalesced into one batch of 2
    assert seen["f1"] == seen["f2"] == 2


def test_dispatch_failure_recorded_in_trace(sample_all):
    q = DispatchQueue()

    def broken(ps):
        raise ValueError("bad shape")

    with tracing.request("req"):
        with pytest.raises(ValueError):
            q.submit("k", 1, broken)
    doc = tracing.get_trace(tracing.trace_ids()[0])
    fail = _spans_by_name(doc, "dispatch_fail")[0]
    assert fail["error"] == "ValueError"


# ------------------------------------------------------------------ slow/error joins
def test_slow_query_entry_links_to_retrievable_trace(ds, monkeypatch):
    monkeypatch.setattr(cnf, "SLOW_QUERY_THRESHOLD_SECS", 0.0)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 0.0)  # retention must be forced
    ds.execute("CREATE s:1 SET v = 1;")
    entries = telemetry.slow_queries()
    assert entries
    e = entries[-1]
    assert e["trace_id"] is not None
    assert e["session"] == {"ns": "test", "db": "test", "auth": "root"}
    # the /slow -> /trace/:id hop resolves even with sampling off
    assert tracing.get_trace(e["trace_id"]) is not None


def test_statement_error_joinable_via_error_ring(ds, monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 0.0)
    ds.execute("THROW 'kaput';")
    errs = telemetry.recent_errors()
    assert errs
    e = errs[-1]
    assert e["kind"] == "ThrowStatement" and "kaput" in e["error"]
    assert e["session"]["auth"] == "root"
    assert tracing.get_trace(e["trace_id"]) is not None
    assert telemetry.get_counter("statement_errors", kind="ThrowStatement") == 1
    assert telemetry.snapshot()["errors"]


# ------------------------------------------------------------------ node metrics
def test_node_runtime_metrics_exposed(ds):
    ds.enable_notifications()
    ds.notifications.subscribe("lq-1")
    ds.notifications.subscribe("lq-2")
    telemetry.collect_node_metrics(ds)
    text = telemetry.render_prometheus()
    assert "surreal_process_resident_memory_bytes" in text  # linux /proc
    assert "surreal_live_queries 2" in text


def test_metrics_endpoint_serves_node_gauges():
    import http.client

    srv = _serve()
    try:
        conn = http.client.HTTPConnection(srv.host, srv.port)
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        text = r.read().decode()
        assert r.status == 200
        assert "surreal_process_resident_memory_bytes" in text
        conn.close()
    finally:
        srv.shutdown()


# ------------------------------------------------------------------ parsing
def test_traceparent_parsing():
    tid = "ab" * 16
    assert tracing.parse_traceparent(f"00-{tid}-00000000000000aa-01") == (
        tid, "00000000000000aa",
    )
    for bad in ("", "garbage", f"00-{tid}-shortpid-01", "00-" + "0" * 32 + "-00000000000000aa-01"):
        assert tracing.parse_traceparent(bad) is None
    assert tracing.format_traceparent(tid, 1) == f"00-{tid}-0000000000000001-01"
    # opaque client ids are sanitized, hex ids pass through
    assert tracing.normalize_trace_id("AB" * 16) == tid
    assert tracing.normalize_trace_id("my id!! ❄") == "myid"
    assert len(tracing.normalize_trace_id("!!!")) == 32  # nothing survives -> fresh


# ------------------------------------------------------------------ hammer
def test_telemetry_registry_hammer():
    """Satellite: counters/gauges/histograms hammered from many threads
    while snapshot()/render/reset() race — no exception, and with the
    chaos off the totals are exact (no lost read-modify-write)."""
    N, M = 8, 250
    errs = []

    def work():
        try:
            for j in range(M):
                telemetry.inc("hammer_total")
                telemetry.observe("hammer_phase", 0.001, phase="x")
                telemetry.observe_hist("hammer_sizes", j % 7, buckets=(1, 4, 16))
                telemetry.gauge_add("hammer_gauge", 1)
                with telemetry.span("hammer_span", kind="k"):
                    pass
        except Exception as e:  # noqa: BLE001 — the assertion below reports
            errs.append(e)

    stop = threading.Event()

    def chaos():
        while not stop.is_set():
            telemetry.snapshot()
            telemetry.render_prometheus()
            telemetry.reset()

    ct = threading.Thread(target=chaos)
    ct.start()
    ts = [threading.Thread(target=work) for _ in range(N)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stop.set()
    ct.join()
    assert not errs

    # deterministic phase: no reset racing -> totals must be exact
    telemetry.reset()
    ts = [threading.Thread(target=work) for _ in range(N)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert telemetry.get_counter("hammer_total") == N * M
    snap = telemetry.snapshot()
    assert snap["histograms"]["hammer_sizes"]["count"] == N * M
    assert snap["durations"]['hammer_phase{phase="x"}']["count"] == N * M
    assert snap["gauges"]["hammer_gauge"] == N * M
