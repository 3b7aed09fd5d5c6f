"""Flight recorder: background-task registry lifecycle + watchdog stalls,
XLA compile-event attribution (one trace owns the compile, riders see a
cache hit), the one-shot debug bundle (HTTP + INFO FOR ROOT + SDK) and
teardown joins on Datastore.close()."""

import json
import threading
import time

import http.client
import pytest

from surrealdb_tpu import bg, cnf, compile_log, telemetry, tracing
from surrealdb_tpu.bundle import SECTIONS, debug_bundle


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    tracing.store_reset()
    bg.reset()
    compile_log.reset()
    yield
    bg.reset()
    compile_log.reset()
    tracing.store_reset()


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


# ------------------------------------------------------------------ lifecycle
def test_task_lifecycle_done():
    tid = bg.register("column_mirror", target="a.b.t", trace_id=None)
    assert bg.get(tid)["state"] == "scheduled"
    with bg.run(tid, rename_thread=False):
        assert bg.get(tid)["state"] == "running"
    rec = bg.get(tid)
    assert rec["state"] == "done"
    assert rec["duration_s"] is not None and rec["error"] is None
    assert telemetry.get_counter("bg_tasks", kind="column_mirror", state="done") == 1


def test_task_failure_recorded():
    tid = bg.register("ivf_train", target="t.ix", trace_id=None)
    with pytest.raises(RuntimeError):
        with bg.run(tid, rename_thread=False):
            raise RuntimeError("boom")
    rec = bg.get(tid)
    assert rec["state"] == "failed" and "boom" in rec["error"]
    assert telemetry.get_counter("bg_tasks", kind="ivf_train", state="failed") == 1


def test_task_links_parent_trace(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    with tracing.request("execute") as tr:
        tid = bg.register("column_mirror", target="x.y.z")
    assert bg.get(tid)["trace_id"] == tr.trace_id


def test_spawn_names_thread_and_finishes():
    seen = {}

    def body():
        seen["name"] = threading.current_thread().name

    tid = bg.spawn("shape_warm", "knn_exact:k10", body)
    assert bg.wait_idle(5.0)
    assert seen["name"] == "bg:shape_warm:knn_exact:k10"
    assert bg.get(tid)["state"] == "done"


def test_window_accounting():
    t0 = time.time()
    tid = bg.register("changefeed_gc", target="memory", trace_id=None)
    with bg.run(tid, rename_thread=False):
        time.sleep(0.02)
    win = bg.window(t0)
    assert any(t["id"] == tid and t["overlap_s"] > 0 for t in win)
    # a window opened after the task ended must not include it
    assert not any(t["id"] == tid for t in bg.window(time.time() + 1, time.time() + 2))


# ------------------------------------------------------------------ watchdog
def test_watchdog_flags_stalled_then_recovered(monkeypatch):
    monkeypatch.setattr(cnf, "BG_WATCHDOG_INTERVAL_SECS", 0.05)
    release = threading.Event()
    tid = bg.register("column_mirror", target="wedged", deadline=0.1, trace_id=None)

    def body():
        with bg.run(tid):
            release.wait(10)

    th = threading.Thread(target=body)
    th.start()
    try:
        assert _wait(lambda: bg.get(tid)["state"] == "stalled")
        assert telemetry.get_counter("bg_task_stalled", kind="column_mirror") == 1
        # surfaces on /metrics ...
        assert "surreal_bg_task_stalled_total" in telemetry.render_prometheus()
        # ... and in the bundle's live task list
        b = debug_bundle(None)
        assert any(
            t["state"] == "stalled" and t["target"] == "wedged"
            for t in b["tasks"]["live"]
        )
        assert b["tasks"]["stalled_total"] >= 1
    finally:
        release.set()
        th.join(10)
    rec = bg.get(tid)
    assert rec["state"] == "done" and rec["stalled"] is True  # sticky flag
    assert telemetry.get_counter("bg_task_recovered", kind="column_mirror") == 1


def test_wedged_mirror_rebuild_surfaces(ds, monkeypatch):
    """The ISSUE's acceptance scenario: a deliberately wedged column-mirror
    rebuild flips to `stalled` and surfaces in /metrics + the bundle."""
    monkeypatch.setattr(cnf, "COLUMN_REBUILD_DEBOUNCE_SECS", 0.05)
    monkeypatch.setattr(cnf, "BG_WATCHDOG_INTERVAL_SECS", 0.05)
    monkeypatch.setitem(bg.KIND_DEADLINES, "column_mirror", 0.15)
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ds.execute(
        "INSERT INTO t $rows",
        vars={"rows": [{"id": i, "a": i % 10} for i in range(100)]},
    )
    ds.execute("SELECT id FROM t WHERE a = 1")  # builds + registers the mirror
    release = threading.Event()
    orig = type(ds.column_mirrors).build

    def wedged(self, dss, ns, db, tb):
        release.wait(10)
        return orig(self, dss, ns, db, tb)

    monkeypatch.setattr(type(ds.column_mirrors), "build", wedged)
    try:
        ds.execute("CREATE t:200 SET a = 5")  # arms the debounced rebuild
        assert _wait(
            lambda: any(
                t["kind"] == "column_mirror" and t["state"] == "stalled"
                for t in bg.snapshot()["live"]
            ),
            timeout=8.0,
        )
        assert telemetry.get_counter("bg_task_stalled", kind="column_mirror") >= 1
        assert "surreal_bg_task_stalled_total" in telemetry.render_prometheus()
        # the watchdog sampled WHERE the wedged thread is stuck
        # (sys._current_frames): the stack tail names the wedge site
        assert _wait(
            lambda: any(
                t["state"] == "stalled"
                and t["stack"]
                and any("wedged" in ln for ln in t["stack"])
                for t in bg.snapshot()["live"]
            ),
            timeout=4.0,
        )
        b = debug_bundle(ds)
        stalled = [t for t in b["tasks"]["live"] if t["state"] == "stalled"]
        assert any(t["target"].endswith(".t") for t in stalled)
        assert any(t["stack"] for t in stalled)  # stack rides into the bundle
        # the engine section knows the mirror is stale + a rebuild exists
        key = next(k for k in b["engine"]["column_mirrors"] if k.endswith(".t"))
        assert b["engine"]["column_mirrors"][key]["stale"] is True
    finally:
        release.set()
    assert ds.column_mirrors.wait_rebuild(10)


# ------------------------------------------------------------------ compiles
def test_compile_attributed_to_exactly_one_trace(monkeypatch):
    """An unwarmed shape queried concurrently: the compile lands as an
    `xla_compile` span in exactly ONE trace; riders see a cache hit."""
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    from surrealdb_tpu.dbs.dispatch import DispatchQueue

    q = DispatchQueue(max_width=8)
    shape = ("testk", 8, 128)

    def runner(payloads):
        with compile_log.tracked("test", shape):
            time.sleep(0.01)
        return [p * 2 for p in payloads]

    n = 4
    barrier = threading.Barrier(n)
    results = {}

    def client(i):
        with tracing.request(f"knn_req_{i}"):
            barrier.wait()
            results[i] = q.submit("bucket", i, runner)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert results == {i: i * 2 for i in range(n)}
    docs = [tracing.get_trace(t) for t in tracing.trace_ids()]
    with_compile = [
        d for d in docs if any(s["name"] == "xla_compile" for s in d["spans"])
    ]
    assert len(with_compile) == 1  # exactly one owner
    evs = compile_log.events()
    assert len(evs) == 1 and evs[0]["mode"] == "on_demand"
    assert evs[0]["trace_id"] == with_compile[0]["trace_id"]
    # a later rider through the same shape is a recorded cache hit
    with tracing.request("rider"):
        q.submit("bucket", 9, runner)
    assert (
        telemetry.get_counter(
            "compile_cache", subsystem="test", shape="testkx8x128", outcome="hit"
        )
        >= 1
    )
    assert len(compile_log.events()) == 1  # still one compile


def test_prewarm_compile_mode_and_no_span(monkeypatch):
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    with tracing.request("warm_kick"):
        with compile_log.tracked("knn_exact", (1, 64, 256), prewarmed=True):
            pass
    (ev,) = compile_log.events()
    assert ev["mode"] == "prewarm" and ev["trace_id"] is None
    doc = tracing.get_trace(tracing.trace_ids()[0])
    assert not any(s["name"] == "xla_compile" for s in doc["spans"])
    assert (
        telemetry.get_counter("compile_events", subsystem="knn_exact", mode="prewarm")
        == 1
    )


def test_compile_without_trace_is_startup():
    with compile_log.tracked("graph_dense", (32, 256, 128)):
        pass
    (ev,) = compile_log.events()
    assert ev["mode"] == "startup" and ev["trace_id"] is None


def test_concurrent_caller_records_wait_not_phantom_hit(monkeypatch):
    """A caller racing an in-flight first compile blocks behind XLA's
    compile lock: that must surface as an attributed wait, not a hit."""
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    shape = ("race", 8, 64)
    entered = threading.Event()
    release = threading.Event()

    def winner():
        with compile_log.tracked("knn_exact", shape, prewarmed=True):
            entered.set()
            release.wait(10)

    th = threading.Thread(target=winner)
    th.start()
    assert entered.wait(5)
    done = []

    def loser():
        with tracing.request("blocked_query"):
            with compile_log.tracked("knn_exact", shape):
                pass  # in reality this would block inside XLA
            done.append(True)

    th2 = threading.Thread(target=loser)
    th2.start()
    th2.join(5)
    release.set()
    th.join(5)
    assert done
    assert (
        telemetry.get_counter(
            "compile_cache", subsystem="knn_exact", shape="racex8x64", outcome="wait"
        )
        == 1
    )
    # the wait landed in the blocked query's trace; only ONE compile event
    doc = next(
        d
        for t in tracing.trace_ids()
        for d in (tracing.get_trace(t),)
        if d and d["name"] == "blocked_query"
    )
    assert any(s["name"] == "xla_compile_wait" for s in doc["spans"])
    assert len(compile_log.events()) == 1
    # once the compile has LANDED, later callers are plain hits
    with compile_log.tracked("knn_exact", shape):
        pass
    assert (
        telemetry.get_counter(
            "compile_cache", subsystem="knn_exact", shape="racex8x64", outcome="hit"
        )
        == 1
    )


# ------------------------------------------------------------------ bundle
def test_bundle_has_all_six_sections(ds):
    ds.execute("CREATE t:1 SET a = 1")
    b = debug_bundle(ds)
    for sec in SECTIONS:
        assert sec in b, sec
    assert b["schema"] == "surrealdb-tpu-bundle/11"
    assert b["engine"]["dispatch"]["stats"]["submitted"] >= 0
    assert "memory_bytes" in b["engine"]
    # a ds-less bundle (the tier-1 failure hook) still carries every section
    b0 = debug_bundle(None)
    for sec in SECTIONS:
        assert sec in b0, sec


def test_bundle_http_endpoint():
    from surrealdb_tpu.net.server import serve

    srv = serve("memory", port=0, auth_enabled=False).start_background()
    try:
        srv.httpd.RequestHandlerClass.ds.execute("CREATE t:1 SET a = 1")
        conn = http.client.HTTPConnection(srv.host, srv.port)
        conn.request("GET", "/debug/bundle")
        r = conn.getresponse()
        assert r.status == 200
        b = json.loads(r.read())
        for sec in SECTIONS:
            assert sec in b, sec
        conn.close()
    finally:
        srv.shutdown()


def test_info_for_root_carries_bundle(ds):
    out = ds.execute("INFO FOR ROOT")[-1]
    assert out["status"] == "OK"
    b = out["result"]["system"]["bundle"]
    for sec in SECTIONS:
        assert sec in b, sec


def test_sdk_local_debug_bundle():
    from surrealdb_tpu.sdk import Surreal

    with Surreal("mem://") as db:
        db.use("test", "test")
        db.query("CREATE t:1 SET a = 1")
        b = db._engine.debug_bundle()
        for sec in SECTIONS:
            assert sec in b, sec


def test_changefeed_gc_task_counted_not_hoarded(ds):
    ds.tick()
    # the sweep ran under the task lifecycle (watchdog-covered, counted)...
    assert telemetry.get_counter("bg_tasks", kind="changefeed_gc", state="done") >= 1
    # ...but an uneventful 10s-tick sweep must not flood the bounded
    # finished ring and evict diagnostically useful records
    assert not any(t["kind"] == "changefeed_gc" for t in bg.snapshot()["recent"])


# ------------------------------------------------------------------ teardown
def test_datastore_close_joins_background(monkeypatch):
    monkeypatch.setattr(cnf, "COLUMN_REBUILD_DEBOUNCE_SECS", 30.0)  # stays armed
    from surrealdb_tpu.kvs.ds import Datastore

    ds = Datastore("memory")
    ds.execute("DEFINE TABLE t SCHEMALESS")
    ds.execute(
        "INSERT INTO t $rows",
        vars={"rows": [{"id": i, "a": i % 10} for i in range(100)]},
    )
    ds.execute("SELECT id FROM t WHERE a = 1")  # build mirror
    ds.execute("CREATE t:900 SET a = 5")  # arm a 30s rebuild timer
    assert ds.column_mirrors._timers
    ds.close()
    assert not ds.column_mirrors._timers
    snap = bg.snapshot()
    assert not [
        t for t in snap["live"] if t["state"] in ("running", "stalled")
    ]
    # the armed-but-never-run task resolved as cancelled, not leaked
    assert any(
        t["kind"] == "column_mirror" and t["error"] and "cancelled" in t["error"]
        for t in snap["recent"]
    )
    # registry idle -> watchdog parked (no daemon-thread leaks)
    assert not snap["watchdog_alive"]
