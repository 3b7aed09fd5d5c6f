"""Cluster observability plane (ISSUE 10): the structured event timeline,
federated metrics/bundles/events from the coordinator, per-shard query
profiles (EXPLAIN ANALYZE), and the slow-ring join that makes a slow
remote shard visible on the coordinator.

The operational contract under test: one scrape / one bundle / one
timeline / one per-statement profile from the coordinator, degraded-
tolerant when a member is down — and every degraded read, breaker flip
and flap joinable to the statement trace it affected.
"""

import json
import time
import urllib.request
import uuid

import numpy as np
import pytest

import jax.numpy  # noqa: F401 — concurrent lazy first-import races otherwise

from surrealdb_tpu import cnf, events, faults, telemetry, tracing
from surrealdb_tpu.cluster import ClusterConfig, attach
from surrealdb_tpu.cluster.federation import (
    federated_bundle,
    federated_events,
    federated_metrics,
)
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.kvs.ds import Datastore
from surrealdb_tpu.net.server import serve


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


class Cluster:
    """N in-process nodes (full Datastore + HTTP server each) on one ring."""

    def __init__(self, n=2, secret="obs-secret"):
        self.servers = [
            serve("memory", port=0, auth_enabled=False).start_background()
            for _ in range(n)
        ]
        self.nodes = [
            {"id": f"n{i + 1}", "url": srv.url}
            for i, srv in enumerate(self.servers)
        ]
        self.datastores = [s.httpd.RequestHandlerClass.ds for s in self.servers]
        for i, ds in enumerate(self.datastores):
            attach(ds, ClusterConfig(self.nodes, f"n{i + 1}", secret=secret))
        self.s = Session.owner("t", "t")
        self.killed = set()

    @property
    def coord(self):
        return self.datastores[0]

    def kill(self, i):
        self.servers[i].shutdown()
        self.killed.add(i)

    def http_get(self, path, i=0):
        with urllib.request.urlopen(self.servers[i].url + path, timeout=30) as r:
            return r.status, r.read()

    def close(self):
        for i, srv in enumerate(self.servers):
            if i not in self.killed:
                srv.shutdown()
        for ds in self.datastores:
            ds.close()


@pytest.fixture()
def cluster2():
    c = Cluster(2)
    yield c
    c.close()


@pytest.fixture()
def cluster3():
    c = Cluster(3)
    yield c
    c.close()


def seed_items(c, n=96, dim=4):
    rng = np.random.default_rng(7)
    ok(c.coord.execute(
        "DEFINE TABLE item SCHEMALESS; "
        f"DEFINE INDEX iemb ON item FIELDS emb MTREE DIMENSION {dim}",
        c.s,
    )[0])
    corpus = rng.standard_normal((n, dim)).astype(np.float32)
    rows = [
        {"id": i, "emb": corpus[i].tolist(), "val": i % 10} for i in range(n)
    ]
    ok(c.coord.execute("INSERT INTO item $rows RETURN NONE", c.s, {"rows": rows})[0])
    return corpus


# ------------------------------------------------------------------ events.py
def test_event_registry_emit_and_filters():
    ev = events.emit("cluster.admission_shed", reason="test")
    assert ev["kind"] == "cluster.admission_shed" and ev["seq"] > 0
    assert ev["trace_id"] is None  # emitted outside any request
    with pytest.raises(events.UnknownEventKind):
        events.emit("made.up_kind")
    seq0 = events.last_seq()
    events.emit("fault.trip", site="x", action="error")
    events.emit("cluster.node_down", node="nX")
    tail = events.since(seq0)
    assert [e["kind"] for e in tail] == ["fault.trip", "cluster.node_down"]
    assert events.snapshot(kind_prefix="cluster.", limit=1)[-1]["kind"] == (
        "cluster.node_down"
    )
    # counter rides the closed registry
    assert telemetry.get_counter("events_emitted", kind="cluster.node_down") >= 1


def test_event_trace_link_is_captured_at_emit():
    tid = uuid.uuid4().hex
    with tracing.request("evt-test", trace_id=tid):
        ev = events.emit("cluster.degraded_read", node="nY")
    assert ev["trace_id"] == tid
    # explicit override (the watchdog citing a task's arming trace)
    ev2 = events.emit("bg.stall", trace_id="abc123", task="ivf_train")
    assert ev2["trace_id"] == "abc123"


# ------------------------------------------------------------ EXPLAIN ANALYZE
def test_explain_analyze_single_node():
    ds = Datastore("memory")
    s = Session.owner("t", "t")
    try:
        ok(ds.execute("DEFINE TABLE p SCHEMALESS", s)[0])
        ok(ds.execute(
            "INSERT INTO p $rows", s,
            {"rows": [{"id": i, "v": i} for i in range(20)]},
        )[0])
        plain = ok(ds.execute("SELECT * FROM p WHERE v < 7 EXPLAIN", s)[0])
        analyzed = ok(
            ds.execute("SELECT * FROM p WHERE v < 7 EXPLAIN ANALYZE", s)[0]
        )
        # the plan rows are the same; ANALYZE appends the Execute row
        assert analyzed[: len(plain)] == plain
        ex = analyzed[-1]
        assert ex["operation"] == "Execute"
        assert ex["detail"]["rows"] == 7
        assert ex["detail"]["duration_ms"] >= 0
        # the statement round-trips through its repr
        stm = "SELECT * FROM p WHERE v < 7 EXPLAIN ANALYZE"
        from surrealdb_tpu.syn import parse_query

        assert repr(parse_query(stm).statements[0]).endswith("EXPLAIN ANALYZE")
    finally:
        ds.close()


def test_cluster_explain_analyze_reports_per_shard_timings(cluster2):
    corpus = seed_items(cluster2)
    # make the remote shard decisively the slow one so the ordering
    # assertion can't flake on scheduler noise (the self node never goes
    # through the HTTP handler, so the latency fires only on n2)
    faults.enable("cluster.rpc.handle", "latency-80")
    try:
        tid = uuid.uuid4().hex
        with tracing.request("ea-test", trace_id=tid):
            tracing.force_keep()
            r = cluster2.coord.execute(
                "SELECT id FROM item WHERE emb <|5|> $q EXPLAIN ANALYZE",
                cluster2.s, {"q": (corpus[3] + 0.01).tolist()},
            )
        ops = ok(r[0])
    finally:
        faults.disable("cluster.rpc.handle")
    by_op = {}
    for op in ops:
        by_op.setdefault(op["operation"], []).append(op["detail"])
    assert by_op["Cluster Scatter"][0]["kind"] == "knn"
    shards = {d["node"]: d for d in by_op["Shard"]}
    assert set(shards) == {"n1", "n2"}  # every live node reports timings
    for d in shards.values():
        assert d["rpc_ms"] > 0 and d["calls"] >= 1
    assert by_op["Merge"][0]["merge_ms"] >= 0
    assert by_op["Execute"][0]["rows"] == 5

    # the slowest Shard row names the same node as the trace's slowest
    # cluster_rpc span — profile and span tree are two views of one fact
    slowest_shard = max(shards, key=lambda n: shards[n]["max_rpc_ms"])
    doc = tracing.get_trace(tid)
    assert doc is not None
    rpc = [
        (sp["labels"]["node"], sp["dur_ms"])
        for sp in doc["spans"]
        if sp["name"] == "cluster_rpc"
    ]
    assert rpc, doc["spans"]
    slowest_span = max(rpc, key=lambda p: p[1])[0]
    assert slowest_shard == slowest_span == "n2"
    # and the profile itself is pinned onto the trace doc
    profs = doc.get("cluster_profiles") or []
    assert profs and set(profs[-1]["shards"]) == {"n1", "n2"}


# ------------------------------------------------------------ slow-ring join
def test_slow_remote_shard_joins_coordinator_ring(cluster2, monkeypatch):
    seed_items(cluster2, n=48)
    monkeypatch.setattr(cnf, "SLOW_QUERY_THRESHOLD_SECS", 0.0)
    ok(cluster2.coord.execute("SELECT * FROM item WHERE val < 3", cluster2.s)[0])
    entries = [e for e in telemetry.slow_queries() if e.get("cluster")]
    assert entries, "coordinator ring has no cluster statement entry"
    e = entries[-1]
    prof = e["cluster"]["profile"]
    assert set(prof["shards"]) == {"n1", "n2"}
    assert prof["duration_ms"] > 0 and e["kind"] == "SelectStatement"
    # the remote shard's OWN inner-statement entry rides along, node-tagged
    remote = e["cluster"]["remote_slow"]
    assert remote and all(x.get("node") in ("n1", "n2") for x in remote)
    assert any(x["node"] == "n2" for x in remote)


def test_cluster_error_joins_coordinator_error_ring(cluster2, monkeypatch):
    """A scattered statement that FAILS (node down, no replication to
    cover) lands in the coordinator's error ring with its per-shard view —
    before this, a cluster statement error left no ring entry at all."""
    seed_items(cluster2, n=12)
    monkeypatch.setattr(cnf, "CLUSTER_RF", 1)  # no failover coverage
    monkeypatch.setattr(cnf, "CLUSTER_RETRY_MAX", 0)
    monkeypatch.setattr(cnf, "CLUSTER_RPC_TIMEOUT_SECS", 1.0)
    cluster2.kill(1)
    before = len([e for e in telemetry.recent_errors() if e.get("cluster")])
    r = cluster2.coord.execute("SELECT * FROM item WHERE val < 3", cluster2.s)
    assert r[0]["status"] == "ERR"
    entries = [e for e in telemetry.recent_errors() if e.get("cluster")]
    assert len(entries) > before
    e = entries[-1]
    assert e["kind"] == "SelectStatement" and e["trace_id"]
    assert e["cluster"]["shards"].get("n2", {}).get("errors", 0) >= 1


# ------------------------------------------------------------ federation
def test_federated_metrics_relabels_every_node(cluster2):
    seed_items(cluster2, n=24)
    text = federated_metrics(cluster2.coord)
    assert 'node="n1"' in text and 'node="n2"' in text
    assert 'surreal_cluster_scrape_up{node="n1"} 1' in text
    assert 'surreal_cluster_scrape_up{node="n2"} 1' in text
    # over HTTP with the query flag; without it the scrape stays node-local
    status, body = cluster2.http_get("/metrics?cluster=1")
    assert status == 200 and b'node="n2"' in body
    status, body = cluster2.http_get("/metrics")
    assert status == 200 and b'cluster_scrape_up' not in body


def test_federated_bundle_marks_dead_node_unreachable(cluster2, monkeypatch):
    seed_items(cluster2, n=24)
    fb = federated_bundle(cluster2.coord)
    assert fb["schema"] == "surrealdb-tpu-bundle/11" and fb["cluster"] is True
    assert fb["coordinator"] == "n1" and set(fb["nodes"]) == {"n1", "n2"}
    for nid in ("n1", "n2"):
        b = fb["nodes"][nid]
        assert b.get("schema") == "surrealdb-tpu-bundle/11"
        assert "events" in b and "traces" in b and "engine" in b

    monkeypatch.setattr(cnf, "CLUSTER_RPC_TIMEOUT_SECS", 1.5)
    cluster2.kill(1)
    status, body = cluster2.http_get("/debug/bundle?cluster=1")
    assert status == 200  # degraded-tolerant: the request still answers
    fb2 = json.loads(body)
    assert fb2["nodes"]["n2"].get("unreachable") is True
    assert fb2["nodes"]["n2"].get("error")
    assert fb2["nodes"]["n1"].get("schema") == "surrealdb-tpu-bundle/11"


@pytest.mark.parametrize("route", ["/statements", "/tenants"])
def test_killed_member_marks_unreachable_not_silent(cluster2, monkeypatch, route):
    """A federated list view against a cluster that LOST a member answers
    200 with the dead node marked unreachable, the live member's rows in
    the same partial view: labeled partial, never silently shrunk."""
    monkeypatch.setattr(cnf, "CLUSTER_RPC_TIMEOUT_SECS", 1.5)
    ok(cluster2.coord.execute("CREATE k:1 SET v = 1", cluster2.s)[0])
    cluster2.kill(1)
    status, body = cluster2.http_get(route + "?cluster=1")
    assert status == 200
    entries = json.loads(body)
    dead = [e for e in entries if e.get("unreachable")]
    assert [e["node"] for e in dead] == ["n2"], entries
    assert dead[0].get("error")
    assert any(e.get("node") == "n1" and not e.get("unreachable") for e in entries)


def test_events_endpoint_and_federation(cluster2):
    seed_items(cluster2, n=12)
    events.emit("cluster.node_down", node="fake")
    status, body = cluster2.http_get("/events?kind=cluster.")
    assert status == 200
    evs = json.loads(body)
    assert evs and all(e["kind"].startswith("cluster.") for e in evs)
    merged = federated_events(cluster2.coord, kind_prefix="cluster.")
    assert merged and all("node" in e for e in merged)
    assert {e["node"] for e in merged} >= {"n1"}
    status, body = cluster2.http_get("/events?cluster=1&limit=5")
    assert status == 200 and isinstance(json.loads(body), list)


# ------------------------------------ cross-node trace completeness (chaos)
def test_trace_complete_and_timeline_ordered_under_mid_scatter_kill(
    cluster3, monkeypatch
):
    """Satellite 4: kill a node mid-scatter (failpoint cluster.rpc.send),
    then assert (a) the coordinator's trace has no orphan spans, (b) the
    event timeline shows flap -> breaker-open -> degraded-read IN ORDER,
    all trace-linked to the statement, and (c) the federated bundle marks
    a dead member unreachable while still answering."""
    corpus = seed_items(cluster3, n=60)
    monkeypatch.setattr(cnf, "CLUSTER_BREAKER_THRESHOLD", 1)
    monkeypatch.setattr(cnf, "CLUSTER_RPC_TIMEOUT_SECS", 2.0)
    # no retries: the injected send failure must FAIL OVER (a successful
    # retry would erase the degraded read this test asserts)
    monkeypatch.setattr(cnf, "CLUSTER_RETRY_MAX", 0)
    seq0 = events.last_seq()
    tid = uuid.uuid4().hex
    faults.enable("cluster.rpc.send", "error-oserror", count=1)
    try:
        with tracing.request("chaos-scatter", trace_id=tid):
            tracing.force_keep()
            r = cluster3.coord.execute(
                "SELECT id FROM item WHERE emb <|4|> $q",
                cluster3.s, {"q": (corpus[5] + 0.01).tolist()},
            )
        assert r[0]["status"] == "OK", r
        assert r[0].get("degraded") is True
        assert len(ok(r[0])) == 4  # replicas covered: the answer is complete
    finally:
        faults.disable("cluster.rpc.send")

    # (a) no orphan spans: every parent resolves inside the doc; grafted
    # remote spans re-parented under their cluster_rpc span
    doc = tracing.get_trace(tid)
    assert doc is not None
    ids = {sp["id"] for sp in doc["spans"]}
    roots = [sp for sp in doc["spans"] if sp["parent"] is None]
    assert len(roots) == 1, roots
    for sp in doc["spans"]:
        if sp["parent"] is not None:
            assert sp["parent"] in ids, f"orphan span {sp}"

    # (b) flap -> breaker-open -> degraded-read, in order, trace-linked
    tail = events.since(seq0)
    victims = {e.get("node") for e in tail if e["kind"] == "cluster.node_down"}
    assert len(victims) == 1
    victim = victims.pop()
    flap = next(e for e in tail if e["kind"] == "cluster.node_down")
    brk = next(e for e in tail if e["kind"] == "cluster.breaker_open")
    deg = next(e for e in tail if e["kind"] == "cluster.degraded_read")
    assert flap["seq"] < brk["seq"] < deg["seq"]
    assert flap["node"] == brk["node"] == deg["node"] == victim
    for e in (flap, brk, deg):
        assert e["trace_id"] == tid, e

    # (c) a REAL dead member shows up unreachable in the federated bundle
    cluster3.kill(2)
    monkeypatch.setattr(cnf, "CLUSTER_RPC_TIMEOUT_SECS", 1.0)
    fb = federated_bundle(cluster3.coord)
    assert fb["nodes"]["n3"].get("unreachable") is True
    assert fb["nodes"]["n1"].get("schema") == "surrealdb-tpu-bundle/11"


# ------------------------------------------------------------ profile store
def test_executor_tracks_slowest_profile(cluster2):
    corpus = seed_items(cluster2, n=48)
    ex = cluster2.coord.cluster.executor
    ex.reset_profiles()
    assert ex.slowest_profile() is None
    ok(cluster2.coord.execute("SELECT * FROM item WHERE val < 2", cluster2.s)[0])
    ok(cluster2.coord.execute(
        "SELECT id FROM item WHERE emb <|3|> $q", cluster2.s,
        {"q": (corpus[0] + 0.01).tolist()},
    )[0])
    prof = ex.slowest_profile()
    assert prof is not None and set(prof["shards"]) == {"n1", "n2"}
    assert prof["duration_ms"] > 0
    assert prof["scatter"] in ("scan", "knn")
    ex.reset_profiles()
    assert ex.slowest_profile() is None


# ------------------------------------------------------------ admission shed
def test_admission_shed_emits_event(monkeypatch):
    from surrealdb_tpu.cluster.executor import (
        ClusterOverloadedError,
        _Admission,
    )

    adm = _Admission()
    monkeypatch.setattr(cnf, "CLUSTER_MAX_INFLIGHT", 1)
    monkeypatch.setattr(cnf, "CLUSTER_ADMIT_QUEUE", 0)
    seq0 = events.last_seq()
    adm.acquire()
    with pytest.raises(ClusterOverloadedError):
        adm.acquire()
    adm.release()
    shed = [e for e in events.since(seq0) if e["kind"] == "cluster.admission_shed"]
    assert shed and shed[0]["reason"] == "queue_full"
