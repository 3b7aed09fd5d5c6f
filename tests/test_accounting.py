"""Tenant cost-attribution plane (ISSUE 16): per-(ns, db) resource meters
behind the one write door `accounting.charge()`, the observe-only budget
plane, and every surfacing layer.

The contracts under test:

- the write door: charge() accumulates per-tenant AND global meters
  atomically, keeps the fingerprint / node / bg-kind drill-downs bounded,
  evicts past the store cap (counted), and is safe under a many-thread
  hammer;
- CONSERVATION: for a mixed multi-namespace workload through the REAL
  executor, the per-tenant sums equal the independent global telemetry
  counters (cpu, rows scanned/returned, bg time) and the dispatch-queue
  timers within 1% — nothing double-counted, nothing dropped;
- ATTRIBUTION: an abusive namespace hammering full scans owns >= 90% of
  the scan volume; coalesced device batches split their occupancy across
  every rider's tenant; bg tasks bill the tenant that armed them; the
  sampling profiler attributes stacks per tenant;
- the budget plane: a soft limit crossed from below emits ONE
  `tenant.budget_exceeded` event (trace-linked, fingerprint-carrying) +
  the `tenant_budget_breaches{ns}` counter — observe-only, nothing is
  throttled;
- surfacing: system-gated GET /tenants (sortable, 401 for non-system
  users, `?cluster=1` federated node-tagged from a 2-node cluster),
  INFO FOR ROOT, bundle section 14, `/sql` byte metering;
- coordinator-only statements (cluster routing refusals): their error
  ring entries carry session{ns, db} instead of vanishing.
"""

import json
import threading
import time
import urllib.request

import pytest

import jax.numpy  # noqa: F401 — concurrent lazy first-import races otherwise

from surrealdb_tpu import accounting, cnf, events, profiler, telemetry
from surrealdb_tpu.cluster import ClusterConfig, attach
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.net.server import serve


def ok(resp):
    assert resp["status"] == "OK", resp
    return resp["result"]


@pytest.fixture(autouse=True)
def _fresh_plane():
    """Module-global store, per-test isolation."""
    accounting.reset()
    yield
    accounting.reset()


# ============================================================ the write door
def test_charge_accumulates_per_tenant_and_global():
    accounting.charge("acme", "app", statements=1, exec_s=0.5, rows_scanned=10)
    accounting.charge("acme", "app", statements=1, exec_s=0.25)
    accounting.charge("globex", "app", statements=1, exec_s=1.0)
    e = accounting.get("acme", "app")
    assert e["statements"] == 2 and e["exec_s"] == 0.75
    assert e["rows_scanned"] == 10
    g = accounting.global_totals()
    assert g["statements"] == 3 and g["exec_s"] == 1.75
    # top sorts by the requested meter, descending
    top = accounting.top(sort="exec_s")
    assert [t["ns"] for t in top] == ["globex", "acme"]
    top = accounting.top(sort="rows_scanned")
    assert top[0]["ns"] == "acme"
    # unknown sort keys fall back instead of erroring (bounded surface)
    assert accounting.top(sort="'; DROP") == accounting.top(sort="exec_s")


def test_none_session_folds_to_unattributed_tenant():
    accounting.charge(None, None, statements=1)
    e = accounting.get(None, None)
    assert e is not None and e["ns"] == "" and e["db"] == ""


def test_fingerprint_node_and_bg_drilldowns():
    accounting.charge("t", "t", fingerprint="fp1", statements=1, exec_s=0.1)
    accounting.charge("t", "t", fingerprint="fp1", statements=1, exec_s=0.1)
    accounting.charge("t", "t", fingerprint="fp2", statements=1, exec_s=0.9)
    accounting.charge("t", "t", node="n2", scatter_rpc_s=0.05, scatter_calls=2)
    accounting.charge("t", "t", bg_kind="column_mirror", bg_s=0.2, bg_tasks=1)
    e = accounting.get("t", "t")
    by_fp = {f["fingerprint"]: f for f in e["by_fp"]}
    assert by_fp["fp1"]["statements"] == 2
    assert e["by_node"]["n2"]["scatter_calls"] == 2
    assert e["bg_kinds"]["column_mirror"] == pytest.approx(0.2)


def test_fp_drilldown_is_lru_bounded(monkeypatch):
    monkeypatch.setattr(cnf, "TENANT_FP_CAP", 4)
    for i in range(10):
        accounting.charge("t", "t", fingerprint=f"fp{i}", statements=1)
    accounting.charge("t", "t", fingerprint="fp6", statements=1)  # refresh
    e = accounting.get("t", "t")
    kept = [f["fingerprint"] for f in e["by_fp"]]
    assert len(kept) == 4 and "fp6" in kept and "fp0" not in kept
    # the tenant-level meters never lost the evicted fingerprints' charges
    assert e["statements"] == 11


def test_store_eviction_at_cap_is_counted(monkeypatch):
    monkeypatch.setattr(cnf, "TENANT_STORE_SIZE", 8)
    ev0 = telemetry.get_counter("tenant_evictions")
    for i in range(12):
        accounting.charge(f"ns{i}", "app", statements=1)
    assert accounting.size() == 8
    assert accounting.snapshot(limit=1)["evicted"] == 4
    assert telemetry.get_counter("tenant_evictions") - ev0 == 4
    # LRU: the oldest namespaces went first
    kept = {e["ns"] for e in accounting.top(limit=20)}
    assert "ns0" not in kept and "ns11" in kept


def test_charge_is_thread_safe_and_conserved():
    def hammer(ns):
        for _ in range(200):
            accounting.charge(ns, "app", statements=1, exec_s=0.001)

    threads = [
        threading.Thread(target=hammer, args=(f"ns{i % 3}",)) for i in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    per = accounting.top(limit=10)
    assert sum(e["statements"] for e in per) == 1200
    assert accounting.global_totals()["statements"] == 1200


def test_disabled_accounting_charges_nothing(monkeypatch):
    monkeypatch.setattr(cnf, "TENANT_ACCOUNTING", False)
    accounting.charge("t", "t", statements=1)
    assert accounting.size() == 0


# ============================================================ tenant context
def test_activation_contextvar_and_thread_table():
    assert accounting.current_tenant() is None
    tok = accounting.activate("acme", "app")
    try:
        assert accounting.current_tenant() == ("acme", "app")
        ident = threading.get_ident()
        assert accounting.active_tenant(ident) == ("acme", "app")
        # cross-thread read (the profiler's access pattern)
        seen = []
        t = threading.Thread(target=lambda: seen.append(
            accounting.active_tenant(ident)
        ))
        t.start()
        t.join()
        assert seen == [("acme", "app")]
    finally:
        accounting.deactivate(tok)
    assert accounting.current_tenant() is None


def test_activation_nests():
    t1 = accounting.activate("a", "x")
    t2 = accounting.activate("b", "y")
    assert accounting.current_tenant() == ("b", "y")
    accounting.deactivate(t2)
    assert accounting.current_tenant() == ("a", "x")
    accounting.deactivate(t1)


def test_tally_is_statement_local():
    prev = accounting.tally_begin()
    accounting.tally(rows_scanned=128)
    accounting.tally(rows_scanned=64, bytes_in=10)
    got = accounting.tally_end(prev)
    assert got == {"rows_scanned": 192.0, "bytes_in": 10.0}
    # ended: further tallies do not leak anywhere
    assert accounting.tally_end(accounting.tally_begin()) == {}


# ============================================================ budget plane
def test_budget_crossing_emits_once_with_counter(monkeypatch):
    monkeypatch.setattr(cnf, "TENANT_BUDGET_CPU_S", "acme:1.0")
    c0 = telemetry.get_counter("tenant_budget_breaches", ns="acme")
    n0 = len(events.snapshot(kind_prefix="tenant.budget_exceeded"))
    accounting.charge("acme", "app", cpu_s=0.8)
    assert len(events.snapshot(kind_prefix="tenant.budget_exceeded")) == n0
    accounting.charge("acme", "app", fingerprint="fpX", cpu_s=0.5)  # crosses
    evs = events.snapshot(kind_prefix="tenant.budget_exceeded")
    assert len(evs) == n0 + 1
    ev = evs[-1]
    assert ev["ns"] == "acme" and ev["meter"] == "cpu_s"
    assert ev["limit"] == 1.0 and ev["fingerprint"] == "fpX"
    assert telemetry.get_counter("tenant_budget_breaches", ns="acme") == c0 + 1
    # already above the limit: no re-emission (crossing-from-below only)
    accounting.charge("acme", "app", cpu_s=5.0)
    assert len(events.snapshot(kind_prefix="tenant.budget_exceeded")) == n0 + 1
    assert accounting.get("acme", "app")["breaches"] == {"cpu_s": 1}
    # other tenants are not limited by acme's clause
    accounting.charge("globex", "app", cpu_s=50.0)
    assert len(events.snapshot(kind_prefix="tenant.budget_exceeded")) == n0 + 1


def test_budget_plain_spec_applies_to_all_tenants(monkeypatch):
    monkeypatch.setattr(cnf, "TENANT_BUDGET_ROWS", "100")
    n0 = len(events.snapshot(kind_prefix="tenant.budget_exceeded"))
    accounting.charge("a", "x", rows_scanned=150)
    accounting.charge("b", "y", rows_scanned=150)
    assert len(events.snapshot(kind_prefix="tenant.budget_exceeded")) == n0 + 2


def test_budget_malformed_clause_disables_itself(monkeypatch):
    monkeypatch.setattr(cnf, "TENANT_BUDGET_ROWS", "acme:oops,globex:10")
    n0 = len(events.snapshot(kind_prefix="tenant.budget_exceeded"))
    accounting.charge("acme", "app", rows_scanned=1e9)
    accounting.charge("globex", "app", rows_scanned=50)
    evs = events.snapshot(kind_prefix="tenant.budget_exceeded")
    assert len(evs) == n0 + 1 and evs[-1]["ns"] == "globex"


def test_budget_breach_kind_is_registered():
    assert "tenant.budget_exceeded" in events.KINDS


# ===================================================== executor conservation
def _seed_ns(ds, s, n=100):
    ok(ds.execute("DEFINE TABLE item SCHEMALESS", s)[0])
    rows = [{"id": i, "val": i / float(n)} for i in range(n)]
    ok(ds.execute("INSERT INTO item $rows", s, {"rows": rows})[0])


def test_conservation_and_attribution_end_to_end(ds):
    """The acceptance property: 3 namespaces, mixed scans/point reads, the
    per-tenant sums equal the independent global counters within 1%, and
    the abusive namespace owns >= 90% of the scan volume."""
    sessions = {
        ns: Session.owner(ns, "app") for ns in ("acme", "globex", "abusive")
    }
    for s in sessions.values():
        _seed_ns(ds, s)
    accounting.reset()
    cpu0 = telemetry.get_counter("statement_cpu_seconds")
    scan0 = telemetry.get_counter("statement_rows_scanned")
    ret0 = telemetry.get_counter("statement_rows_returned")
    for _ in range(5):
        ok(ds.execute("SELECT * FROM item WHERE val >= 0", sessions["abusive"])[0])
        for ns in ("acme", "globex"):
            ok(ds.execute("SELECT * FROM item:7", sessions[ns])[0])
    per = accounting.top(limit=50)

    def total(meter):
        return sum(e.get(meter) or 0.0 for e in per)

    d_cpu = telemetry.get_counter("statement_cpu_seconds") - cpu0
    d_scan = telemetry.get_counter("statement_rows_scanned") - scan0
    d_ret = telemetry.get_counter("statement_rows_returned") - ret0
    assert d_cpu > 0 and d_scan > 0 and d_ret > 0
    assert total("cpu_s") == pytest.approx(d_cpu, rel=0.01)
    assert total("rows_scanned") == pytest.approx(d_scan, rel=0.01)
    assert total("rows_returned") == pytest.approx(d_ret, rel=0.01)
    # attribution: the scans all landed on the abusive namespace
    by_ns = {e["ns"]: e for e in per}
    bench_scanned = {
        ns: by_ns[ns].get("rows_scanned") or 0.0
        for ns in ("acme", "globex", "abusive") if ns in by_ns
    }
    share = bench_scanned["abusive"] / max(sum(bench_scanned.values()), 1e-9)
    assert share >= 0.9, bench_scanned
    # per-statement drill-down rode along
    assert by_ns["abusive"]["by_fp"], by_ns["abusive"]


def test_executor_breach_is_trace_linked_and_fingerprinted(ds, monkeypatch):
    s = Session.owner("abusive", "app")
    _seed_ns(ds, s)
    monkeypatch.setattr(cnf, "TENANT_BUDGET_ROWS", "abusive:50")
    accounting.reset()
    n0 = len(events.snapshot(kind_prefix="tenant.budget_exceeded"))
    ok(ds.execute("SELECT * FROM item WHERE val >= 0", s)[0])  # scans 100
    evs = events.snapshot(kind_prefix="tenant.budget_exceeded")
    assert len(evs) == n0 + 1
    ev = evs[-1]
    assert ev["ns"] == "abusive" and ev["meter"] == "rows_scanned"
    assert ev.get("fingerprint")
    # breach -> /trace/:id stays one hop: the event names a KEPT trace
    from surrealdb_tpu import tracing

    assert ev.get("trace_id") and tracing.get_trace(ev["trace_id"]) is not None


def test_bg_tasks_bill_the_arming_tenant():
    from surrealdb_tpu import bg

    bg0 = telemetry.get_counter("bg_task_seconds")
    tok = accounting.activate("acme", "app")
    try:
        tid = bg.spawn("acct_probe", "t", time.sleep, 0.05)
    finally:
        accounting.deactivate(tok)
    deadline = time.time() + 10
    while time.time() < deadline:
        rec = bg.get(tid)
        if rec is not None and rec.get("duration_s") is not None:
            break
        time.sleep(0.02)
    e = accounting.get("acme", "app")
    assert e is not None and e["bg_tasks"] >= 1
    assert e["bg_kinds"].get("acct_probe", 0.0) >= 0.05
    assert telemetry.get_counter("bg_task_seconds") - bg0 == pytest.approx(
        accounting.global_totals().get("bg_s", 0.0), rel=0.01
    )


def test_coalesced_dispatch_splits_across_riders(ds):
    """Two tenants riding ONE coalesced device batch each get an equal
    share of its occupancy, and the shares sum to the queue's own
    launch+collect timers (conservation at the dispatch layer)."""
    q = ds.dispatch
    st0 = q.stats()
    barrier = threading.Barrier(2)
    errs = []

    def runner(payloads):
        time.sleep(0.02)  # measurable occupancy
        return [p * 2 for p in payloads]

    def rider(ns):
        tok = accounting.activate(ns, "app")
        try:
            barrier.wait(timeout=10)
            for _ in range(4):
                q.submit("acct-test", 21, runner)
        except Exception as e:  # noqa: BLE001 — surfaced by the assert
            errs.append(e)
        finally:
            accounting.deactivate(tok)

    threads = [threading.Thread(target=rider, args=(ns,)) for ns in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    st1 = q.stats()
    spent = (st1["launch_s"] - st0["launch_s"]) + (
        st1["collect_s"] - st0["collect_s"]
    )
    ea, eb = accounting.get("a", "app"), accounting.get("b", "app")
    assert ea and eb and ea["dispatch_s"] > 0 and eb["dispatch_s"] > 0
    assert ea["dispatch_batches"] >= 1 and eb["dispatch_batches"] >= 1
    total = ea["dispatch_s"] + eb["dispatch_s"]
    # riders' shares sum to the queue's own timers (rounded to 4dp there)
    assert total == pytest.approx(spent, rel=0.01, abs=2e-4)


def test_profiler_attributes_samples_per_tenant():
    stop = threading.Event()

    def busy():
        tok = accounting.activate("acme", "app")
        try:
            while not stop.is_set():
                sum(i * i for i in range(500))
        finally:
            accounting.deactivate(tok)

    t = threading.Thread(target=busy, name="acct-busy")
    t.start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline:
            profiler.sample_once()
            if profiler.report().get("by_tenant", {}).get("acme.app"):
                break
    finally:
        stop.set()
        t.join()
    rep = profiler.report()
    assert rep["by_tenant"].get("acme.app", 0) >= 1
    profiler.reset()
    assert profiler.report()["by_tenant"] == {}


# ============================================================ surfacing
def _serve(auth_enabled=False):
    return serve("memory", port=0, auth_enabled=auth_enabled).start_background()


def test_tenants_endpoint_serves_sorted_and_meters_bytes():
    srv = _serve()
    try:
        import http.client

        conn = http.client.HTTPConnection(srv.host, srv.port)
        body = "CREATE e:1 SET v = 1; SELECT * FROM e;"
        conn.request("POST", "/sql", body, {"surreal-ns": "t", "surreal-db": "t"})
        conn.getresponse().read()
        conn.request(
            "GET", "/tenants?sort=statements&limit=5",
            headers={"surreal-ns": "t", "surreal-db": "t"},
        )
        r = conn.getresponse()
        rows = json.loads(r.read())
        assert r.status == 200 and rows
        e = next(e for e in rows if e["ns"] == "t")
        assert e["statements"] >= 2 and e["by_fp"]
        # the protocol edge metered the request/response bytes
        assert e["bytes_in"] >= len(body) and e["bytes_out"] > 0
        conn.close()
    finally:
        srv.shutdown()


def test_info_for_root_and_bundle_section(ds):
    s = Session.owner("t", "t")
    _seed_ns(ds, s, n=8)
    info = ok(ds.execute("INFO FOR ROOT")[-1])
    assert any(e["ns"] == "t" for e in info["system"]["tenants"])
    from surrealdb_tpu.bundle import BUNDLE_SCHEMA, debug_bundle

    assert BUNDLE_SCHEMA == "surrealdb-tpu-bundle/11"
    b = debug_bundle(ds)
    assert b["tenants"]["tenants"] >= 1 and b["tenants"]["top"]
    assert "global" in b["tenants"]


# ============================================================ cluster
class Cluster2:
    """Two in-process nodes on one ring (the test_stats harness shape),
    for the federated /tenants merge and coordinator-only accounting."""

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
            attach(ds, ClusterConfig(self.nodes, f"n{i + 1}", secret="acct-secret"))
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


def test_federated_tenants_merge_is_node_tagged(cluster2):
    c = cluster2
    ok(c.coord.execute("DEFINE TABLE item SCHEMALESS", c.s)[0])
    rows = [{"id": i, "val": float(i)} for i in range(40)]
    ok(c.coord.execute("INSERT INTO item $rows", c.s, {"rows": rows})[0])
    for _ in range(3):
        ok(c.coord.execute("SELECT * FROM item WHERE val >= 0", c.s)[0])
    status, body = c.http_get("/tenants?cluster=1&sort=rows_scanned&limit=10")
    assert status == 200
    merged = json.loads(body)
    assert merged and all(e.get("node") for e in merged)
    assert any(e["ns"] == "t" for e in merged)
    # scatter cost landed at the coordinator with a per-node breakdown
    e = accounting.get("t", "t")
    assert e is not None and e["scatter_calls"] >= 1
    assert e["by_node"], e
    # in-process caveat: one shared store — both node tags report it
    assert {e["node"] for e in merged} <= {"n1", "n2"}


def test_coordinator_refusal_keeps_session_in_error_ring(cluster2):
    """Satellite fix: a cluster-routed statement that errors at the
    COORDINATOR (no shard ever ran, no local execution) must still land
    in the error ring — session-tagged — and charge its tenant."""
    c = cluster2
    s = Session.owner("ringns", "ringdb")
    r = c.coord.execute("BEGIN", s)
    assert r[0]["status"] == "ERR"
    entry = next(
        (
            e
            for e in reversed(telemetry.recent_errors())
            if (e.get("session") or {}).get("ns") == "ringns"
        ),
        None,
    )
    assert entry is not None, telemetry.recent_errors()[-3:]
    assert entry["session"]["db"] == "ringdb" and entry.get("fingerprint")
    e = accounting.get("ringns", "ringdb")
    assert e is not None and e["errors"] >= 1 and e["statements"] >= 1
