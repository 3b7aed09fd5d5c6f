"""No dark time in a served statement: the wire spans round the `ws_rpc` root
of a loop-served WebSocket request, the executor's spans inside it, the
interpreter's collections, the load path's stages, and the `durations`
summary now read off the histograms."""

import gc
import json
import socket
import time

import numpy as np
import pytest

from surrealdb_tpu import bg, cnf, telemetry, tracing

WIRE_BEFORE = ["ws_decode", "ws_admit_wait", "ws_exec_wait"]
WIRE_AFTER = ["ws_encode", "ws_write"]
KNN_SQL = "SELECT id, vector::distance::knn() AS d FROM item WHERE emb <|5,16|> $q"
COUNT_SQL = "SELECT count(->knows->person->knows->person) AS c FROM person:1"
HYBRID_SQL = ("SELECT id, vector::distance::knn() AS d, array::distinct(->refs->item->refs->item) AS ctx "
              "FROM item WHERE emb <|5,16|> $q")


def _complete(tid, timeout=5.0):
    """The stored doc of `tid` once the reply's flush has completed it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        doc = tracing.get_trace(tid)
        if doc is not None and any(s["name"] == "ws_write" for s in doc["spans"]):
            return doc
        time.sleep(0.005)
    raise AssertionError(f"trace {tid} was never completed: {tracing.get_trace(tid)}")


def _named(doc, name):
    return [s for s in doc["spans"] if s["name"] == name]


def _end(span):
    return span["start_ms"] + span["dur_ms"]


@pytest.fixture(scope="module")
def served():
    """One loop-served WS session: `use`, then four tagged frames (the first
    kNN statement after a load, the same statement again, a graph count, a
    search whose every hit carries the set two steps away)."""
    from surrealdb_tpu.dbs.session import Session
    from surrealdb_tpu.net import ws as wsproto
    from surrealdb_tpu.net.server import serve
    from surrealdb_tpu.sql.value import Thing

    mp = pytest.MonkeyPatch()
    mp.setattr(cnf, "TPU_KNN_ONDEVICE_THRESHOLD", 64)  # 256 rows reach the dispatch queue
    mp.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 0)  # and so does a small count
    telemetry.reset()
    tracing.store_reset()
    srv = serve("memory", port=0, auth_enabled=False).start_background()
    try:
        assert cnf.NET_LOOP  # the event-loop ingress: the threaded one has no wire spans
        s = Session.owner("t", "t")
        rng = np.random.default_rng(3)
        ddl = "DEFINE TABLE item; DEFINE INDEX ix ON item FIELDS emb HNSW DIMENSION 8 DIST EUCLIDEAN EFC 16;"
        assert all(r["status"] == "OK" for r in srv.ds.execute(ddl, s))
        rows = [{"id": i, "emb": rng.normal(size=8).tolist()} for i in range(256)]
        assert srv.ds.execute("INSERT INTO item $rows RETURN NONE", s, vars={"rows": rows})[-1]["status"] == "OK"
        people = [{"id": i} for i in range(40)]
        assert srv.ds.execute("INSERT INTO person $rows RETURN NONE", s, vars={"rows": people})[-1]["status"] == "OK"
        edges = [{"in": Thing("person", i), "out": Thing("person", (i * 7 + j) % 40)}
                 for i in range(40) for j in range(1, 4)]
        out = srv.ds.execute("INSERT RELATION INTO knows $rows RETURN NONE", s, vars={"rows": edges})
        assert out[-1]["status"] == "OK", out
        refs = [{"in": Thing("item", i), "out": Thing("item", (i * 5 + j) % 256)} for i in range(256) for j in range(1, 4)]
        out = srv.ds.execute("INSERT RELATION INTO refs $rows RETURN NONE", s, vars={"rows": refs})
        assert out[-1]["status"] == "OK", out

        sock = socket.create_connection((srv.host, srv.port))
        bs = wsproto.BufferedSocket(sock, wsproto.client_handshake(sock, f"{srv.host}:{srv.port}", "/rpc"))

        def rpc(req):
            sock.sendall(wsproto.encode_frame(wsproto.OP_TEXT, json.dumps(req).encode(), mask=True))
            return json.loads(wsproto.read_frame(bs)[1])

        first = rpc({"id": 1, "method": "use", "params": ["t", "t"], "trace": "wire-use"})
        q = rng.normal(size=8).tolist()
        replies = {"wire-use": first}
        for i, (tid, sql) in enumerate([("wire-first", KNN_SQL), ("wire-second", KNN_SQL), ("wire-count", COUNT_SQL),
                                       ("wire-hybrid", HYBRID_SQL)]):
            replies[tid] = rpc({"id": 2 + i, "method": "query", "params": [sql, {"q": q}], "trace": tid})
            assert replies[tid]["trace"] == tid
            assert all(r["status"] == "OK" for r in replies[tid]["result"]), replies[tid]
        docs = {tid: _complete(tid) for tid in replies}
        sock.close()
        snap = bg.snapshot()
        yield {"docs": docs, "replies": replies, "tasks": snap["live"] + snap["recent"]}
    finally:
        srv.shutdown()
        srv.ds.close()
        mp.undo()


# ------------------------------------------------------------------ wire spans
def test_a_hybrid_statement_s_tree_holds_one_search_one_group_and_launches_of_both_families(served):
    doc = served["docs"]["wire-hybrid"]
    rows = served["replies"]["wire-hybrid"]["result"][-1]["result"]
    assert len(rows) == 5 and all(len(r["ctx"]) > 0 for r in rows)
    assert len(_named(doc, "knn_prepare")) == 1
    (group,) = _named(doc, "graph_reach_group")
    assert group["labels"] == {**group["labels"], "rows": "5", "riders": "5", "launches": "1", "filter": "none", "depth": "2"}
    launches = _named(doc, "dispatch_launch")
    swept = [l for l in launches if "slots" in l["labels"]]
    assert len(launches) == 2 and len(swept) == 1 and swept[0]["labels"]["batch"] == "5"
    search = next(l for l in launches if "slots" not in l["labels"])
    assert _end(search) <= group["start_ms"] + 0.002 <= swept[0]["start_ms"] + 0.004  # the hits, then the group, then its launch
    # each phase of the one launch is on the statement once, whatever riders it carried
    for name in ("dispatch_queue_wait", "dispatch_wake"):
        assert len(_named(doc, name)) == 2, name
    (collect,) = _named(doc, "dispatch_collect")  # the sweep's: this small search's runner has one phase
    assert collect["labels"]["batch"] == "5"
    memo = [s["labels"]["memo"] for s in _named(doc, "graph_prepare")]
    assert memo.count("fill") == 1 and memo.count("hit") == 5


def test_ws_rpc_is_the_only_parentless_span_with_its_labels(served):
    for tid, method in (("wire-use", "use"), ("wire-second", "query")):
        doc = served["docs"][tid]
        roots = [s for s in doc["spans"] if s["parent"] is None]
        assert [r["name"] for r in roots] == ["ws_rpc"]
        assert roots[0]["labels"] == {"method": method}
        assert doc["name"] == "ws_rpc" and doc["duration_ms"] == roots[0]["dur_ms"]


@pytest.mark.parametrize("name", WIRE_BEFORE + WIRE_AFTER + ["ws_conn_idle"])
def test_wire_span_is_a_child_of_the_root_outside_its_interval(served, name):
    doc = served["docs"]["wire-second"]
    root = next(s for s in doc["spans"] if s["parent"] is None)
    (span,) = _named(doc, name)
    assert span["parent"] == root["id"] and span["dur_ms"] >= 0
    if name in WIRE_AFTER:
        assert span["start_ms"] >= _end(root) - 0.002
    else:
        assert _end(span) <= root["start_ms"] + 0.002


def test_wire_spans_are_in_order_and_do_not_overlap(served):
    doc = served["docs"]["wire-second"]
    root = next(s for s in doc["spans"] if s["parent"] is None)
    order = ["ws_conn_idle"] + WIRE_BEFORE + ["ws_rpc"] + WIRE_AFTER
    chain = [_named(doc, n)[0] for n in order]
    for a, b in zip(chain, chain[1:]):
        # rounded to a microsecond each, so allow two
        assert _end(a) <= b["start_ms"] + 0.002, (a["name"], b["name"])
    assert chain[4] is root
    # no dark time between them either: each starts where the last one ended
    for a, b in zip(chain, chain[1:]):
        assert b["start_ms"] - _end(a) < 0.1, (a["name"], b["name"])


def test_ws_write_ends_at_or_after_ws_encode(served):
    for doc in served["docs"].values():
        assert _end(_named(doc, "ws_write")[0]) >= _end(_named(doc, "ws_encode")[0])


@pytest.mark.parametrize("tid, expected", [("wire-use", 0), ("wire-first", 1), ("wire-second", 1)])
def test_conn_idle_is_absent_on_a_first_frame_only(served, tid, expected):
    assert len(_named(served["docs"][tid], "ws_conn_idle")) == expected


def test_ts_and_start_ms_count_from_one_instant(served):
    """`ts` is the wall clock at the trace's t0, which is where the root
    opens: earlier spans have negative starts, the root starts at ~0."""
    doc = served["docs"]["wire-second"]
    root = next(s for s in doc["spans"] if s["parent"] is None)
    assert 0 <= root["start_ms"] < 0.5
    assert _named(doc, "ws_decode")[0]["start_ms"] < 0
    assert abs(doc["ts"] - time.time()) < 600


def test_every_reply_echoes_an_id_whose_doc_the_flush_completed(served):
    """The doc is stored before the reply is handed out (so `/trace/:id`
    never dangles) and again, with `ws_write`, when the reply is flushed."""
    for tid, reply in served["replies"].items():
        assert reply["trace"] == tid and _named(served["docs"][tid], "ws_write")


# ------------------------------------------------------------------ executor spans
@pytest.mark.parametrize(
    "name, parent",
    [("plan_fetch", "execute"), ("stmt_accounting", "execute"), ("select_setup", "statement"),
     ("knn_prepare", "knn_search"), ("dispatch_wake", "knn_search"), ("materialise", "statement")],
)
def test_executor_span_of_a_knn_statement(served, name, parent):
    doc = served["docs"]["wire-second"]
    found = _named(doc, name)
    assert len(found) == (2 if name == "stmt_accounting" else 1)
    by_id = {s["id"]: s for s in doc["spans"]}
    assert all(by_id[s["parent"]]["name"] == parent for s in found)
    root = next(s for s in doc["spans"] if s["parent"] is None)
    assert all(s["start_ms"] >= root["start_ms"] and _end(s) <= _end(root) + 0.002 for s in found)


def test_plan_fetch_says_whether_it_parsed(served):
    assert _named(served["docs"]["wire-first"], "plan_fetch")[0]["labels"]["outcome"] in ("digest", "lexed", "parse")
    assert {s["labels"]["phase"] for s in _named(served["docs"]["wire-second"], "stmt_accounting")} == {"begin", "end"}


def test_materialise_starts_where_the_operator_ended(served):
    doc = served["docs"]["wire-second"]
    assert abs(_named(doc, "materialise")[0]["start_ms"] - _end(_named(doc, "knn_search")[0])) < 0.01


def test_little_of_a_served_statement_is_unnamed(served):
    """The union of the named spans (containers left out) covers the server's
    stretch of a warm statement, bar a small remainder."""
    containers = ("ws_rpc", "rpc_method", "execute", "statement", "knn_search")
    doc = served["docs"]["wire-second"]
    lo, hi = _named(doc, "ws_decode")[0]["start_ms"], _end(_named(doc, "ws_write")[0])
    cur, covered = lo, 0.0
    for s in sorted((s for s in doc["spans"] if s["name"] not in containers and s["name"] != "ws_conn_idle"),
                    key=lambda s: s["start_ms"]):
        a, b = max(s["start_ms"], cur), min(_end(s), hi)
        if b > a:
            covered, cur = covered + (b - a), b
    assert covered >= 0.8 * (hi - lo), (covered, hi - lo, doc["spans"])


# ------------------------------------------------------------------ load path
@pytest.mark.parametrize("name", ["mirror_scan", "mirror_stack"])
def test_the_first_statement_names_the_mirror_build(served, name):
    (span,) = _named(served["docs"]["wire-first"], name)
    assert span["labels"]["rows"] == "256"
    assert not _named(served["docs"]["wire-second"], name)


@pytest.mark.parametrize("name", ["graph_prepare", "dispatch_launch", "materialise"])
def test_a_graph_count_names_its_stages(served, name):
    assert _named(served["docs"]["wire-count"], name)


@pytest.mark.parametrize("name, label", [("graph_scan", "edges"), ("graph_dense_compose", "bytes")])
def test_a_graph_build_is_named_by_whoever_ran_it(served, name, label):
    """The first count statement, or the debounced prewarm task if that got there first."""
    in_trace = _named(served["docs"]["wire-count"], name)
    in_tasks = [st for t in served["tasks"] for st in t["stages"] if st["name"] == name]
    assert in_trace or in_tasks
    assert all(int(s["labels"][label]) >= 0 for s in in_trace) and all(st[label] >= 0 for st in in_tasks)


@pytest.mark.parametrize("name", ["graph_csr_build", "graph_csc_build"])
def test_graph_operator_builds_are_spans_of_the_trace_that_pays(name):
    from surrealdb_tpu.idx.graph_csr import NodeInterner, PointerCsr
    from surrealdb_tpu.sql.value import Thing

    it = NodeInterner()
    ids = [it.intern(Thing("person", i)) for i in range(8)]
    csr = PointerCsr(it)
    csr.load({ids[i]: [ids[(i + 1) % 8], ids[(i + 3) % 8]] for i in range(8)})
    with tracing.request("build", trace_id="graph-build-" + name):
        csr.device_csc()
    (span,) = _named(tracing.get_trace("graph-build-" + name), name)
    assert int(span["labels"]["bytes"]) > 0


@pytest.mark.parametrize("name", ["ivf_train", "ivf_assign", "ivf_lists"])
def test_a_background_task_keeps_its_stages_in_its_record(name):
    """IVF training runs in a `bg` task, outside any trace: its stages go
    into that task's record."""
    from surrealdb_tpu.idx.ivf import IvfState

    rng = np.random.default_rng(1)
    data = rng.normal(size=(512, 8)).astype(np.float32)
    tid = bg.register("ivf_train", target="item.ix", trace_id=None)
    with bg.run(tid, rename_thread=False):
        IvfState.train(data, np.ones(512, dtype=bool), nlists=8)
    stages = {s["name"]: s for s in bg.get(tid)["stages"]}
    assert stages[name]["dur_ms"] >= 0 and stages[name]["rows"] == 512
    if name == "ivf_train":
        assert stages[name]["lists"] == 8 and stages[name]["iters"] == 8


def test_a_stage_outside_a_trace_and_a_task_goes_nowhere():
    telemetry.stage("mirror_scan", time.perf_counter(), 0.001, rows=1)  # must not raise


# ------------------------------------------------------------------ collections
@pytest.mark.parametrize("gen", [0, 1, 2])
def test_a_collection_inside_a_traced_statement_is_a_span_and_a_count(ds, gen, monkeypatch):
    from surrealdb_tpu.kvs.ds import Datastore

    key = f'gc_collections{{gen="{gen}"}}'
    before = telemetry.snapshot()["counters"].get(key, 0.0)
    real = Datastore.process

    def collecting(self, *a, **kw):
        gc.collect(gen)
        return real(self, *a, **kw)

    monkeypatch.setattr(Datastore, "process", collecting)
    monkeypatch.setattr(cnf, "TRACE_SAMPLE", 1.0)
    with tracing.request("probe", trace_id=f"gc-{gen}"):
        assert ds.execute("RETURN 1")[0]["status"] == "OK"
    doc = tracing.get_trace(f"gc-{gen}")
    pauses = [s for s in _named(doc, "gc_pause") if s["labels"]["gen"] == str(gen)]
    assert pauses and all(p["dur_ms"] >= 0 for p in pauses)
    by_id = {s["id"]: s for s in doc["spans"]}
    # (a spontaneous gen-0 collection may land in the request before `execute` opens)
    assert "execute" in {by_id[p["parent"]]["name"] for p in pauses}
    snap = telemetry.snapshot()
    assert snap["counters"][key] >= before + 1
    assert snap["histograms"][f'gc_pause_duration_seconds{{gen="{gen}"}}']["count"] == snap["counters"][key]


def test_metrics_expose_the_two_collection_families():
    gc.collect()
    text = telemetry.render_prometheus()
    assert '# TYPE surreal_gc_collections_total counter' in text
    assert 'surreal_gc_collections_total{gen="2"}' in text
    assert 'surreal_gc_pause_duration_seconds_bucket{gen="2",le="+Inf"}' in text
    assert any(n == "gc_collections" for n, _, _ in telemetry.export_state()["counters"])


def test_a_collection_outside_any_trace_is_counted_and_takes_no_lock():
    """The hook may run under `telemetry.registry` (any allocation can trip
    the collector), so it must not take it."""
    telemetry.reset()
    with telemetry._lock:
        gc.collect()
    assert telemetry.snapshot()["counters"]['gc_collections{gen="2"}'] >= 1


@pytest.mark.parametrize("armed, over_s, frozen", [(True, 0.0, True), (True, 3600.0, False), (False, 0.0, False)])
def test_a_server_s_process_freezes_what_survives_a_long_full_collection(monkeypatch, armed, over_s, frozen):
    """A full collection walks every tracked object with every thread
    stopped. Armed (a process that serves a datastore: net/server.py), the
    hook moves the survivors of one that took GC_FREEZE_OVER_S or longer out
    of the collector's sight; a short one, a young one, and a process that
    only imports the library leave the collector as it is."""
    monkeypatch.setattr(telemetry, "_gc_freeze", armed)
    monkeypatch.setattr(telemetry, "GC_FREEZE_OVER_S", over_s)
    gc.unfreeze()
    try:
        gc.collect(0), gc.collect(1)
        assert gc.get_freeze_count() == 0
        held = [{"k": [i]} for i in range(1000)]
        gc.collect()
        assert (gc.get_freeze_count() > 1000) is frozen
        if frozen:
            before = gc.get_freeze_count()
            del held  # what is frozen is still freed when its last reference goes
            assert gc.get_freeze_count() <= before - 2000
            telemetry.collect_node_metrics()
            # (other threads free frozen objects meanwhile)
            assert abs(telemetry.snapshot()["gauges"]["gc_frozen_objects"] - gc.get_freeze_count()) < 1000
    finally:
        gc.unfreeze()


def test_starting_a_server_arms_the_freeze(monkeypatch):
    from surrealdb_tpu.net.server import serve

    monkeypatch.setattr(telemetry, "_gc_freeze", False)
    srv = serve("memory", port=0, auth_enabled=False)
    try:
        assert telemetry._gc_freeze is True
    finally:
        srv.shutdown()
        srv.ds.close()


# ------------------------------------------------------------------ durations
def test_durations_are_read_off_the_histograms():
    assert not hasattr(telemetry, "_durations")
    telemetry.reset()
    telemetry.observe("wire_probe", 0.25, phase="a")
    telemetry.observe("wire_probe", 0.75, phase="a")
    telemetry.observe("wire_probe_bare", 0.5)
    telemetry.observe_hist("wire_probe_sizes", 3)
    snap = telemetry.snapshot()
    assert snap["durations"]['wire_probe{phase="a"}'] == {"count": 2, "total_s": 1.0, "max_s": 0.75}
    assert snap["durations"]["wire_probe_bare"] == {"count": 1, "total_s": 0.5, "max_s": 0.5}
    assert not [k for k in snap["durations"] if k.startswith("wire_probe_sizes")]
    assert snap["histograms"]['wire_probe_duration_seconds{phase="a"}']["count"] == 2
    assert isinstance(snap["durations"]['wire_probe{phase="a"}']["count"], int)
