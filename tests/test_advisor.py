"""Advisor plane (surrealdb_tpu/advisor.py): observe -> propose.

Covers the lifecycle contract the bench artifact replays:
- the one construction door: `propose()` validates kind registry +
  evidence chain shape (graftlint GL014 polices call sites statically);
- stable ids: re-proposing the same (kind, subject) RE-ARMS the stored
  record instead of minting a duplicate;
- decay: a proposal whose evidence stays gone for ADVISOR_EXPIRE_SWEEPS
  consecutive sweeps expires into the bounded ring, with the
  `advisor.expired` event emitted;
- analyzers end-to-end: a scan-heavy window over an unindexed predicate
  yields an `index.create` proposal whose fingerprints resolve in the
  stats store;
- surfacing: system-gated GET /advisor (401 for non-system users),
  `?cluster=1` federated merge DEDUPED by stable id and node-tagged;
- the dead-member contract (satellite): /statements?cluster=1,
  /tenants?cluster=1 and /advisor?cluster=1 against a cluster with a
  KILLED node answer 200 with the member marked unreachable — a partial
  view is labeled partial, never silently shrunk.
"""

import json
import urllib.request

import pytest

import jax.numpy  # noqa: F401 — concurrent lazy first-import races otherwise

from surrealdb_tpu import accounting, advisor, cnf, events, stats, telemetry
from surrealdb_tpu.cluster import ClusterConfig, attach
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.net.server import serve


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


EV = [{"plane": "stats", "metric": "calls", "window": "cumulative",
       "value": 10, "threshold": 8}]


@pytest.fixture(autouse=True)
def _fresh_plane():
    """Module-global store, per-test isolation. The background sweep loop
    is PARKED (the bench A/B pattern): an interval sweep firing mid-test
    would age manual proposals toward expiry under our feet — explicit
    sweep_once() calls still run while paused."""
    advisor.pause()
    advisor.reset()
    stats.reset()
    accounting.reset()
    yield
    advisor.reset()
    stats.reset()
    accounting.reset()
    advisor.resume()


# ============================================================ the one door
def test_propose_validates_kind_and_evidence():
    with pytest.raises(advisor.UnknownProposalKind):
        advisor.propose("index.invent", "t:fp", evidence=EV)
    with pytest.raises(ValueError):
        advisor.propose("index.create", "t:fp", evidence=[])
    with pytest.raises(ValueError):  # no plane/metric
        advisor.propose("index.create", "t:fp", evidence=[{"value": 1}])
    with pytest.raises(ValueError):  # unregistered plane
        advisor.propose(
            "index.create", "t:fp",
            evidence=[{"plane": "vibes", "metric": "calls"}],
        )


def test_stable_id_rearms_instead_of_duplicating():
    a = advisor.propose("index.create", "person:abc", evidence=EV)
    b = advisor.propose(
        "index.create", "person:abc", evidence=EV, severity="warn",
    )
    assert a["id"] == b["id"] and advisor.size() == 1
    assert a["armed"] == 0 and b["armed"] == 1
    assert b["severity"] == "warn"  # re-arm refreshes the record
    c = advisor.propose("index.create", "person:OTHER", evidence=EV)
    assert c["id"] != a["id"] and advisor.size() == 2
    # the id is a pure digest of (kind, subject): stable across processes
    assert a["id"] == advisor._digest("index.create", "person:abc")


def test_proposal_event_emitted_once_and_kinds_registered():
    assert "advisor.proposal" in events.KINDS
    assert "advisor.expired" in events.KINDS
    before = events.last_seq()
    advisor.propose("tenant.quota_review", "t.t", evidence=[
        {"plane": "accounting", "metric": "breaches.total",
         "window": "cumulative", "value": 4, "threshold": 3},
    ], tenant=("t", "t"))
    advisor.propose("tenant.quota_review", "t.t", evidence=[
        {"plane": "accounting", "metric": "breaches.total",
         "window": "cumulative", "value": 5, "threshold": 3},
    ], tenant=("t", "t"))  # re-arm: no second event
    emitted = [
        e for e in events.since(before) if e["kind"] == "advisor.proposal"
    ]
    assert len(emitted) == 1
    assert emitted[0]["proposal_kind"] == "tenant.quota_review"


def test_store_is_lru_bounded(monkeypatch):
    monkeypatch.setattr(cnf, "ADVISOR_STORE_SIZE", 8)
    for i in range(12):
        advisor.propose("index.create", f"t:fp{i}", evidence=EV)
    assert advisor.size() == 8
    assert advisor.snapshot()["evicted"] == 4


# ============================================================ decay
def test_expiry_after_consecutive_evidence_free_sweeps(monkeypatch):
    monkeypatch.setattr(cnf, "ADVISOR_EXPIRE_SWEEPS", 3)
    rec = advisor.propose("mirror.field_budget", "column_mirror", evidence=[
        {"plane": "telemetry", "metric": "column_pipeline.declines",
         "window": "delta", "value": 40, "threshold": 32},
    ])
    before = events.last_seq()
    # empty planes: three sweeps find no evidence -> the proposal decays
    for i in range(3):
        assert advisor.get(rec["id"]) is not None, f"expired early at {i}"
        advisor.sweep_once(None)
    assert advisor.get(rec["id"]) is None
    snap = advisor.snapshot()
    assert rec["id"] in [r["id"] for r in snap["expired"]]
    expired_ev = [
        e for e in events.since(before) if e["kind"] == "advisor.expired"
    ]
    assert len(expired_ev) == 1 and expired_ev[0]["id"] == rec["id"]


def test_rearm_clears_the_miss_streak(monkeypatch):
    monkeypatch.setattr(cnf, "ADVISOR_EXPIRE_SWEEPS", 3)
    rec = advisor.propose("index.drop", "t.t.tb.ix", evidence=[
        {"plane": "idx", "metric": "plan_mix.index", "window": "cumulative",
         "value": 0, "threshold": 0},
    ])
    advisor.sweep_once(None)
    advisor.sweep_once(None)  # miss_count == 2, one sweep from death
    advisor.propose("index.drop", "t.t.tb.ix", evidence=[
        {"plane": "idx", "metric": "plan_mix.index", "window": "cumulative",
         "value": 0, "threshold": 0},
    ])
    advisor.sweep_once(None)
    advisor.sweep_once(None)
    assert advisor.get(rec["id"]) is not None  # streak restarted at re-arm


def test_sweep_refreshes_gauges_and_metrics():
    advisor.propose("cluster.rebalance", "epoch1:n9", severity="warn",
                    evidence=[
                        {"plane": "cluster", "metric": "scatter_calls.skew",
                         "window": "cumulative", "value": 5.0,
                         "threshold": 3.0},
                    ])
    advisor.sweep_once(None)
    g = telemetry.gauges_matching("advisor_proposals")
    live = {dict(k).get("kind"): v for k, v in g.items()}
    assert live.get("cluster.rebalance") == 1
    assert advisor.snapshot()["sweeps"] >= 1


# ============================================================ analyzers
def test_scan_heavy_window_yields_index_create_with_resolving_evidence(
    ds, monkeypatch
):
    monkeypatch.setattr(cnf, "ADVISOR_MIN_CALLS", 3)
    monkeypatch.setattr(cnf, "ADVISOR_SCAN_ROWS", 16)
    s = Session.owner("t", "t")
    ok(ds.execute("DEFINE TABLE advt SCHEMALESS", s)[0])
    rows = [{"id": i, "val": int(i % 97)} for i in range(128)]
    ok(ds.execute("INSERT INTO advt $rows RETURN NONE", s, {"rows": rows})[0])
    for _ in range(4):
        ok(ds.execute("SELECT id FROM advt WHERE val > 50", s)[0])
    rep = advisor.sweep_once(ds)
    assert rep["created"] >= 1
    props = advisor.proposals(kind="index.create")
    assert props, advisor.snapshot()
    p = props[0]
    assert p["subject"].startswith("advt:")
    # every fingerprint the proposal cites resolves in the stats store
    known = {e["fingerprint"] for e in stats.statements(limit=50)}
    assert p["fingerprints"] and set(p["fingerprints"]) <= known
    # every evidence entry names a registered plane and a numeric value
    for e in p["evidence"]:
        assert e["plane"] in advisor.EVIDENCE_PLANES
        assert e["metric"] and isinstance(e["value"], (int, float))


def test_cost_hook_margin_lands_in_stats(ds):
    """Satellite: choose_strategy's est_cost note (chosen AND declined
    modeled costs) accumulates on the statement's stats entry — the
    break-even margin the advisor's index math consumes."""
    s = Session.owner("t", "t")
    ok(ds.execute("DEFINE TABLE costt SCHEMALESS", s)[0])
    rows = [{"id": i, "v": float(i), "g": i % 7} for i in range(256)]
    ok(ds.execute("INSERT INTO costt $rows RETURN NONE", s, {"rows": rows})[0])
    for _ in range(2):
        ok(ds.execute(
            "SELECT id, v FROM costt WHERE v >= 0 ORDER BY v DESC LIMIT 5", s
        )[0])
    ent = next(
        e for e in stats.statements(limit=50)
        if "costt" in (e.get("sql") or "") and "ORDER" in (e.get("sql") or "")
    )
    cost = ent.get("cost")
    assert cost and cost["notes"] >= 2, ent
    assert cost["unit"] == "row-visits"
    # columnar chosen over row: the declined row path costs MORE
    assert cost["declined"] > cost["chosen"] > 0
    assert cost["margin"] > 0 and cost["margin_per_call"] > 0


# ============================================================ surfacing
def _serve(auth_enabled=False):
    return serve("memory", port=0, auth_enabled=auth_enabled).start_background()


def test_advisor_endpoint_serves_snapshot_and_kind_filter():
    srv = _serve()
    try:
        advisor.propose("ivf.retrain", "t.t.item.emb", severity="warn",
                        evidence=[
                            {"plane": "idx", "metric": "ivf.size_ratio",
                             "window": "now", "value": 2.0, "threshold": 1.5},
                        ])
        with urllib.request.urlopen(srv.url + "/advisor", timeout=30) as r:
            assert r.status == 200
            snap = json.loads(r.read())
        assert snap["kinds"] and snap["proposals"]
        assert any(p["kind"] == "ivf.retrain" for p in snap["proposals"])
        with urllib.request.urlopen(
            srv.url + "/advisor?kind=index.create", timeout=30
        ) as r:
            body = json.loads(r.read())
        assert body["proposals"] == []  # filtered out
    finally:
        srv.shutdown()


def test_advisor_endpoint_rejects_non_system_users():
    srv = _serve(auth_enabled=True)
    try:
        import http.client

        conn = http.client.HTTPConnection(srv.host, srv.port)
        conn.request("GET", "/advisor")
        r = conn.getresponse()
        r.read()
        assert r.status == 401
        conn.close()
    finally:
        srv.shutdown()


def test_info_for_root_and_bundle_section(ds):
    advisor.propose("index.create", "x:fp", evidence=EV)
    s = Session.owner("t", "t")  # noqa: F841 — root info needs no session
    info = ok(ds.execute("INFO FOR ROOT")[-1])
    assert info["system"]["advisor"]["proposals"]
    from surrealdb_tpu.bundle import debug_bundle

    b = debug_bundle(ds)
    assert b["advisor"]["proposals"] and b["advisor"]["enabled"] is not None


# ============================================================ cluster
class Cluster2:
    """Two in-process nodes on one ring (the test_accounting harness
    shape), for the federated /advisor merge and the dead-member
    contract."""

    def __init__(self):
        self.servers = [
            serve("memory", port=0, auth_enabled=False).start_background()
            for _ in range(2)
        ]
        self.nodes = [
            {"id": f"n{i + 1}", "url": srv.url}
            for i, srv in enumerate(self.servers)
        ]
        self.datastores = [s.httpd.RequestHandlerClass.ds for s in self.servers]
        for i, ds in enumerate(self.datastores):
            attach(ds, ClusterConfig(self.nodes, f"n{i + 1}", secret="adv-secret"))
        self.s = Session.owner("t", "t")

    @property
    def coord(self):
        return self.datastores[0]

    def http_get(self, path, i=0):
        with urllib.request.urlopen(self.servers[i].url + path, timeout=30) as r:
            return r.status, r.read()

    def close(self):
        for srv in self.servers:
            srv.shutdown()
        for ds in self.datastores:
            ds.close()


@pytest.fixture()
def cluster2():
    c = Cluster2()
    yield c
    c.close()


def test_federated_advisor_dedups_by_stable_id_and_node_tags(cluster2):
    c = cluster2
    advisor.propose("cluster.rebalance", "epoch1:n2", severity="warn",
                    evidence=[
                        {"plane": "cluster", "metric": "scatter_calls.skew",
                         "window": "cumulative", "value": 4.0,
                         "threshold": 3.0},
                    ])
    status, body = c.http_get("/advisor?cluster=1")
    assert status == 200
    merged = json.loads(body)
    assert merged["unreachable"] == []
    props = merged["proposals"]
    # in-process caveat: one shared store — BOTH members report the same
    # stable id, and the merge collapses them to ONE node-tagged record
    assert len(props) == 1
    assert sorted(props[0]["nodes"]) == ["n1", "n2"]
    assert props[0]["kind"] == "cluster.rebalance"


def test_killed_member_marks_unreachable_not_silent(cluster2):
    """Satellite regression: federated observability views against a
    cluster that LOST a member must answer 200 with the dead node marked
    unreachable — across /statements, /tenants AND /advisor."""
    c = cluster2
    ok(c.coord.execute("CREATE k:1 SET v = 1", c.s)[0])
    advisor.propose("index.create", "k:deadfp", evidence=EV)
    # kill node 2: its RPC port stops answering, its ds stays closed
    c.servers[1].shutdown()
    for path, unwrap in (
        ("/statements?cluster=1", None),
        ("/tenants?cluster=1", None),
        ("/advisor?cluster=1", "unreachable"),
    ):
        status, body = c.http_get(path)
        assert status == 200, path
        doc = json.loads(body)
        entries = doc[unwrap] if unwrap else doc
        dead = [
            e for e in entries
            if isinstance(e, dict) and e.get("unreachable")
        ]
        assert dead and dead[0]["node"] == "n2", (path, doc)
        assert dead[0].get("error"), path
    # the live member's data still rides in the same partial view
    status, body = c.http_get("/advisor?cluster=1")
    live = [p for p in json.loads(body)["proposals"] if p.get("id")]
    assert live and "n1" in live[0]["nodes"]
