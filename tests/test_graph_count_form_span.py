"""A count's `graph_prepare` span says which form served it, with the label
the `graph_count_form` counter took, and every form gives the int64 walk
count (ISSUE 27). The graph is skewed and person 0 has 300 friends."""

import numpy as np
import pytest

from surrealdb_tpu import bg, cnf, compile_log, telemetry, tracing
from surrealdb_tpu.dbs.session import Session
from surrealdb_tpu.sql.value import Thing
from test_graph_dense_exact import forms, lognormal_hub, walk_count

NS, DB = "t", "t"
N, HUB = 400, 300
SQL = "SELECT count(->knows->person->knows->person->knows->person) AS c FROM person:{}"
# what makes the program choose each form, from what it observes: a node table
# over TPU_GRAPH_DENSE_MAX is refused the dense operator; a chain whose work
# estimate stays under TPU_GRAPH_COUNT_EDGES (and whose frontiers stay under
# the on-device threshold) is walked on the host
CHOOSES = {
    "dense": {"TPU_GRAPH_COUNT_EDGES": 1},
    "csc": {"TPU_GRAPH_COUNT_EDGES": 1, "TPU_GRAPH_DENSE_MAX": N - 1},
    "host": {"TPU_GRAPH_COUNT_EDGES": 10**15, "TPU_GRAPH_ONDEVICE_THRESHOLD": 10**9},
}


@pytest.fixture
def loaded(ds, monkeypatch):
    # a shape warmer an earlier file of this worker left compiling would log
    # its subsystem under the test that asks which subsystems compiled
    assert bg.wait_idle(120.0)
    telemetry.reset()
    compile_log.reset()
    tracing.store_reset()
    monkeypatch.setattr(cnf, "GRAPH_PREWARM", False)
    edges = lognormal_hub(N, HUB, seed=5)
    sess = Session.owner(NS, DB)
    ds.execute("DEFINE TABLE person SCHEMALESS; DEFINE TABLE knows SCHEMALESS", sess)
    ds.execute("INSERT INTO person $rows RETURN NONE", sess, {"rows": [{"id": i} for i in range(N)]})
    rows = [{"in": Thing("person", int(a)), "out": Thing("person", int(b))} for a, b in edges]
    (res,) = ds.execute("INSERT RELATION INTO knows $rows RETURN NONE", sess, {"rows": rows})
    assert res["status"] == "OK", res
    assert np.bincount(edges[:, 0]).max() >= 256
    return ds, sess, edges


@pytest.mark.parametrize("form", sorted(CHOOSES))
def test_the_span_names_the_form_the_counter_took_and_the_count_is_the_walk(loaded, monkeypatch, form):
    ds, sess, edges = loaded
    for name, value in CHOOSES[form].items():
        monkeypatch.setattr(cnf, name, value)
    starts = (0, 7, 11)
    for start in starts:
        tid = f"count-{form}-{start}"
        with tracing.request("count", trace_id=tid):
            (res,) = ds.execute(SQL.format(start), sess)
        assert res["status"] == "OK", res
        assert res["result"][0]["c"] == walk_count(N, edges, {start: 1}, 3)
        spans = [s for s in tracing.get_trace(tid)["spans"] if s["name"] == "graph_prepare"]
        assert [s["labels"]["form"] for s in spans] == [form]
    assert forms() == {form: len(starts)}
    served_by = {"dense": "graph_dense", "csc": "graph_csc", "host": None}[form]
    assert {e["subsystem"] for e in compile_log.events()} == ({served_by} if served_by else set())


def test_the_first_use_of_the_csc_arrays_times_their_upload_apart_from_their_build():
    from surrealdb_tpu.idx.graph_csr import NodeInterner, PointerCsr

    it = NodeInterner()
    ids = [it.intern(Thing("person", i)) for i in range(8)]
    csr = PointerCsr(it)
    csr.load({ids[i]: [ids[(i + 1) % 8], ids[(i + 3) % 8]] for i in range(8)})
    with tracing.request("build", trace_id="graph-csc-upload"):
        cptr, csrc = csr.device_csc()
        csr.device_csc()  # the arrays stay: no second build, no second upload
    spans = {s["name"]: s for s in tracing.get_trace("graph-csc-upload")["spans"]}
    names = [s["name"] for s in tracing.get_trace("graph-csc-upload")["spans"]]
    assert names.count("graph_csc_build") == 1 and names.count("graph_csc_upload") == 1
    assert int(spans["graph_csc_upload"]["labels"]["bytes"]) == cptr.nbytes + csrc.nbytes
    build, upload = spans["graph_csc_build"], spans["graph_csc_upload"]
    assert upload["start_ms"] >= build["start_ms"] + build["dur_ms"] - 0.01  # a doc's times are rounded
