"""The readers of the served path's spans: each on a hand-written trace doc
(known spans, known value; a doc without its span reads None, never 0), the
manifest with their ten entries, and a traced rehearsal with all ten on its
last line."""

import pytest

from harness import manifest as mf
from test_bench_rehearsal import fresh_program_state, rehearse, well_formed  # noqa: F401

NEW = ["wire.client_ms", "wire.codec_ms", "wire.exec_wait_ms", "wire.write_ms", "exec.plan_fetch_ms",
       "exec.materialise_ms", "dispatch.launch_ms", "dispatch.collect_ms", "host.gc_ms", "stmt.unattributed_ms"]


def span(i, parent, name, start, dur):
    return {"id": i, "parent": parent, "name": name, "labels": {}, "start_ms": start, "dur_ms": dur, "error": None}


def doc_with(*skip):
    """One served kNN statement, 12.0 ms from `ws_decode` to the end of
    `ws_write`, of which 0.7 ms lie in no leaf span; the client saw 13.0 ms."""
    spans = [
        span(20, 1, "ws_conn_idle", -5.0, 4.0),
        span(21, 1, "ws_decode", -1.0, 0.4),
        span(22, 1, "ws_admit_wait", -0.6, 0.1),
        span(23, 1, "ws_exec_wait", -0.5, 0.5),
        span(1, None, "ws_rpc", 0.0, 10.0),
        span(2, 1, "rpc_method", 0.0, 10.0),
        span(3, 2, "execute", 0.1, 9.8),                 # 0.1 before it, unnamed
        span(4, 3, "plan_fetch", 0.1, 0.2),
        span(5, 3, "statement", 0.5, 9.0),               # 0.3-0.5 unnamed
        span(6, 5, "knn_search", 0.5, 8.0),
        span(7, 6, "dispatch_queue_wait", 0.5, 0.1),
        span(8, 6, "dispatch_launch", 0.6, 1.4),
        span(9, 6, "dispatch_collect", 2.0, 6.5),
        span(10, 6, "gc_pause", 3.0, 0.8),               # inside the collect: counted once
        span(11, 5, "materialise", 8.5, 1.0),
        span(12, 5, "gc_pause", 9.5, 0.25),              # 9.5-9.75 named by it alone
        span(24, 1, "ws_encode", 10.0, 0.3),             # 9.75-10.0 unnamed
        span(25, 1, "ws_write", 10.3, 0.7),
    ]
    return {"trace_id": "t", "ts": 0.0, "spans": [s for s in spans if s["name"] not in skip]}


def ctx_of(*docs):
    return {"tagged": [{"record": {"t0": 100.0, "t1": 100.013}, "doc": d} for d in docs]}


@pytest.fixture(scope="module")
def readers():
    return mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")


@pytest.mark.parametrize(
    "name, value, needs",
    [
        ("wire.client_ms", 1.0, "ws_write"),
        ("wire.codec_ms", 0.7, "ws_encode"),
        ("wire.exec_wait_ms", 0.6, "ws_exec_wait"),
        ("wire.write_ms", 0.7, "ws_write"),
        ("exec.plan_fetch_ms", 0.2, "plan_fetch"),
        ("exec.materialise_ms", 1.0, "materialise"),
        ("dispatch.launch_ms", 1.4, "dispatch_launch"),
        ("dispatch.collect_ms", 6.5, "dispatch_collect"),
        ("host.gc_ms", 1.05, "ws_write"),
        ("stmt.unattributed_ms", 0.55, "ws_decode"),
    ],
)
def test_a_reader_on_a_hand_written_doc(readers, name, value, needs):
    read = readers[name].read
    assert read(ctx_of(doc_with())) == pytest.approx(value, abs=1e-9)
    assert read(ctx_of(doc_with(needs))) is None
    assert read(ctx_of()) is None
    # the parent commit's doc: the root and what is inside it, no wire span, no new executor span
    old = doc_with("ws_conn_idle", "ws_decode", "ws_admit_wait", "ws_exec_wait", "ws_encode", "ws_write",
                   "plan_fetch", "materialise", "gc_pause")
    assert read(ctx_of(old)) == (value if name.startswith("dispatch.") else None)


def test_unattributed_is_what_the_leaf_spans_leave_of_the_served_stretch(readers):
    # 12.0 served; unnamed: 0.0-0.1, 0.3-0.5 and 9.75-10.0
    assert readers["stmt.unattributed_ms"].unattributed_ms(doc_with()) == pytest.approx(0.55)
    # a rider's span that began before its request (a leader's pipeline wait) is clipped to the stretch
    early = doc_with()
    early["spans"].append(span(30, 6, "dispatch_pipeline_wait", -9.0, 9.05))
    assert readers["stmt.unattributed_ms"].unattributed_ms(early) == pytest.approx(0.5)
    assert readers["wire.client_ms"].served_ms(doc_with()) == pytest.approx(12.0)


def test_the_wire_metrics_add_up_to_the_outside_one(readers):
    """`wire.ms` (client latency less the root) is the four `wire.*` parts plus
    the `ws_decode`-`ws_write` stretch's own gaps outside the root (none here)."""
    ctx = ctx_of(doc_with(), doc_with())
    parts = sum(readers[n].read(ctx) for n in NEW[:4])
    assert parts == pytest.approx(readers["wire.ms"].read(ctx))


def test_gc_is_a_mean_and_zero_where_no_statement_met_a_collection(readers):
    assert readers["host.gc_ms"].read(ctx_of(doc_with("gc_pause"))) == 0.0
    assert readers["host.gc_ms"].read(ctx_of(doc_with(), doc_with("gc_pause"))) == pytest.approx(0.525)


def test_the_manifest_ends_with_the_ten_entries_and_has_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    assert [m["name"] for m in manifest["per_layer"][-10:]] == NEW
    assert manifest["per_layer"][0]["name"] == "wire.ms"
    for m in manifest["per_layer"][-10:]:
        assert m["unit"] == "ms" and m["better"] == "lower" and m["source"] == "program_span" and "workloads" not in m


@pytest.mark.parametrize("workload", ["vec1m768.knn_c1", "snbsf1.hop3_c8"])
def test_a_traced_rehearsal_reports_all_ten(workload, capsys):
    manifest = mf.load()
    line, phases = rehearse(workload, True, capsys)
    well_formed(line, manifest, workload, True)
    assert line["correct"] is True, phases["check"]
    assert set(NEW) <= set(line["metrics"]), sorted(set(NEW) - set(line["metrics"]))
    v = {n: line["metrics"][n]["value"] for n in line["metrics"]}
    assert v["host.gc_ms"] >= 0.0 and all(v[n] > 0 for n in NEW if n not in ("host.gc_ms", "stmt.unattributed_ms"))
    # the four parts make up the outside metric, to within what no span names
    parts = v["wire.client_ms"] + v["wire.codec_ms"] + v["wire.exec_wait_ms"] + v["wire.write_ms"]
    assert abs(parts - v["wire.ms"]) <= max(v["stmt.unattributed_ms"], 0.25 * v["wire.ms"])
    assert 0 <= v["stmt.unattributed_ms"] < 0.2 * (v["wire.ms"] + v["exec.host_ms"] + v["dispatch.launch_ms"] + v["dispatch.collect_ms"])
