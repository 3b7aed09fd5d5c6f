"""`vec500k768f`, VectorDBBench's filtered search as a deployment (ISSUE 33):
the manifest's new entries, the configuration beside `vec1m768`'s key by key,
the filtered reference against a brute-force loop, the int8 control and the
two guarantee numbers, the four readers on hand-written docs, and a CPU
rehearsal of the cell whose every search is served by the subset route."""

import json
import os

import numpy as np
import pytest

import run as bench_run
from harness import manifest as mf
from test_bench_rehearsal import CPU, TUNING, fresh_program_state, well_formed  # noqa: F401
from test_bench_served_spans import ctx_of

CELL, BARE_CELL = "vec500k768f.knn99p_c1", "vec1m768.knn_c1"
READERS = ["knn.filter_subset_share", "knn.filter_prepare_ms", "knn.filter_build_share", "knn_subset_roofline"]
SIZES = {"rows": 8192, "pool": 64, "centres": 32, "pass_rows": 82}


def config(name):
    with open(os.path.join(mf.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    return mf.load_modules(mf.BENCH_DIR, "deployments", "KIND")["vector_knn_filtered"]


# ------------------------------------------------------------------ manifest
def test_the_manifest_has_the_deployment_its_cell_and_its_readers_and_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    cfg = config("vec500k768f")
    (entry,) = [c for c in manifest["configs"] if c["name"] == "vec500k768f"]
    assert entry["reduced"] == ["rows"] == cfg["reduced"] and entry["file"] == "benchmarks/configs/vec500k768f.json"
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "Filtering Search Performance Test" in entry["source"] and "Filter 99%" in entry["source"]
    assert manifest["configs"].index(entry) > [c["name"] for c in manifest["configs"]].index("vec1m768")
    cell = mf.cell(manifest, CELL)
    assert cell == {**cell, "config": "vec500k768f", "traffic": "ws_closed_c1", "chips": 1} and len(cell["why"]) <= 200
    assert {m["name"] for m in mf.metrics_of(manifest, "end_to_end", CELL)} == {
        "setup_s", "stmt_per_s", "p50_ms", "p95_ms", "recall_at_10"}
    (recall,) = [m for m in manifest["end_to_end"] if m["name"] == "recall_at_10"]
    assert recall["workloads"][0] == BARE_CELL and CELL in recall["workloads"][1:]  # after the cell that was there
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(READERS[0])
    assert names[at : at + 4] == READERS and at > names.index("graph.filter_build_share")  # together, in order, after
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL]
    assert [by_name[n]["layer"] for n in READERS] == ["kernels", "mirrors", "mirrors", "kernels"]
    assert [by_name[n]["moves"] for n in READERS] == ["p50_ms", "p50_ms", "p95_ms", "p50_ms"]
    assert [by_name[n]["source"] for n in READERS] == ["program_span"] * 3 + ["device_trace"]
    # the cell reports what knn_c1 reports of the un-listed metrics, and neither IVF metric
    mine = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    theirs = {m["name"] for m in mf.metrics_of(manifest, "per_layer", BARE_CELL)}
    assert mine - theirs == set(READERS) and theirs - mine == {"ivf_roofline", "ivf.longest_list"}


def test_the_configuration_is_vec1m768_s_shapes_with_a_filter_and_half_the_rows():
    bare, f = config("vec1m768"), config("vec500k768f")
    for key in ("ns", "db", "table", "index", "ddl", "dim", "k", "ef", "distance_field", "device_elem_bytes",
                "precision", "reduced"):
        assert f[key] == bare[key], key
    for key in ("centres", "sigma", "query_noise", "corpus_seed"):
        assert f["generator"][key] == bare["generator"][key], key
    assert f["kind"] == "vector_knn_filtered" and f["kernel"] == "knn_subset" and f["filter_field"] == "n"
    assert f["sizes"] == {"rows": 500_000, "pool": bare["sizes"]["pool"], "pass_rows": 5_000}
    assert f["sizes"]["rows"] * 2 == bare["sizes"]["rows"] and f["sizes"]["pass_rows"] * 100 == f["sizes"]["rows"]
    assert "half of the source's 1M" in f["reduced_why"]
    st, st_bare = f["statements"]["primary"], bare["statements"]["primary"]
    assert st["sql"] == st_bare["sql"] + ".v AND n >= $q.lo" and (st["bind"], st["dispatches"]) == ("q", 1)
    assert f["expected_strategies"] == ["exact-subset"] and bare["expected_strategies"] == ["ivf"]
    assert f["guarantees"][:4] == bare["guarantees"] and len(f["guarantees"]) == 6
    assert "n >= 0" in f["guarantees"][4] and "never fewer than k" in f["guarantees"][5]
    for key in ("recall_at_10_min", "unmatched_id_share_max"):
        assert f["correct"][key] == bare["correct"][key], key
    # far neighbours: both the bf16 reading and the int8 control's fall, and the limit lies between them
    assert f["correct"]["distance_rms_rel_max"] == 0.00024 < bare["correct"]["distance_rms_rel_max"]
    assert f["correct"]["filter_violations_max"] == f["correct"]["short_answers_max"] == 0
    assert f["assumed"][0] == bare["assumed"][0] and f["assumed"][2] == bare["assumed"][2] and len(f["assumed"]) == 6
    assert any("copy of the record id" in a for a in f["assumed"]) and any("4,000 tight clusters" in a for a in f["assumed"])


# ------------------------------------------------------------------ data and reference
def test_the_corpus_is_vector_knn_s_and_every_entry_binds_the_one_threshold(kind):
    base = mf.load_modules(mf.BENCH_DIR, "deployments", "KIND")["vector_knn"]
    cfg, seed = config("vec500k768f"), 2**31 + 77
    mine, theirs = kind.generate(cfg, SIZES, seed), base.generate(config("vec1m768"), SIZES, seed)
    assert (mine["corpus"] == theirs["corpus"]).all() and (mine["queries"] == theirs["queries"]).all()
    assert mine["lo"] == SIZES["rows"] - SIZES["pass_rows"] and (mine["n"] == np.arange(SIZES["rows"])).all()
    pool = kind.pool(cfg, mine)
    assert len(pool) == SIZES["pool"] and {e["lo"] for e in pool} == {mine["lo"]}
    assert all(set(e) == {"v", "lo"} and len(e["v"]) == cfg["dim"] for e in pool)
    assert pool[3]["v"] == base.pool(cfg, theirs)[3]
    json.dumps(pool)
    other = kind.generate(cfg, SIZES, seed + 1)  # the seed draws the queries, not the corpus
    assert (other["corpus"] == mine["corpus"]).all() and not (other["queries"] == mine["queries"]).all()
    assert kind.count_sql(cfg) == [("SELECT count() AS c FROM item WHERE n >= 0 GROUP ALL", None)]
    shapes = kind.kernel_shapes(cfg, mine, {})
    assert shapes == {"dim": 768, "pass_rows": SIZES["pass_rows"], "corpus_elem_bytes": 2, "slot_bytes": 4}


def test_the_reference_is_the_brute_force_loop_over_the_rows_that_pass_and_the_controls_fail(kind):
    cfg = {**config("vec500k768f"), "dim": 16}
    small = {"rows": 600, "pool": 12, "centres": 8, "pass_rows": 90}
    data = kind.generate(cfg, small, 2**31 + 5)
    ref = kind.reference(cfg, data)
    lo = data["lo"]
    assert ref["lo"] == lo == 510 and ref["pass_rows"] == 90 and ref["ids"].shape == (12, 64)
    for qi in range(12):
        dist = {}
        for i in range(600):
            if int(data["n"][i]) >= lo:
                diff = data["corpus"][i].astype(np.float64) - data["queries"][qi].astype(np.float64)
                dist[i] = float(diff @ diff)
        order = sorted(dist, key=dist.get)
        assert ref["ids"][qi].tolist() == order[:64]
        np.testing.assert_allclose(ref["d2"][qi], [dist[i] for i in order[:64]], rtol=1e-12)
    k = cfg["k"]
    exact = [{"status": "OK", "q": qi, "ids": ref["ids"][qi, :k].tolist(),
              "values": {"d": np.sqrt(ref["d2"][qi, :k]).tolist()}} for qi in range(12)]
    sound = kind.check(cfg, ref, exact)
    assert [n[:2] for n in sound["numbers"]] == [
        ["recall_at_10", 1.0], ["distance_rms_rel", pytest.approx(0.0, abs=1e-12)], ["unmatched_id_share", 0.0],
        ["filter_violations", 0], ["short_answers", 0]]
    assert sound["metrics"] == {"recall_at_10": 1.0} and sound["compared"]["answers"] == 12
    limit = cfg["correct"]["distance_rms_rel_max"]
    assert sound["control"]["distance_rms_rel_int8"] > limit  # the precision below fails the limit
    # the unfiltered nearest rows instead: most fail the filter, and recall falls with them
    base = mf.load_modules(mf.BENCH_DIR, "deployments", "KIND")["vector_knn"]
    bare = base.exact_neighbours(data["corpus"], data["queries"], k)
    unfiltered = [{**r, "ids": bare[qi].tolist()} for qi, r in enumerate(exact)]
    numbers = {n[0]: n for n in kind.check(cfg, ref, unfiltered)["numbers"]}
    assert numbers["filter_violations"][1] == int((bare < lo).sum()) > 0 and numbers["recall_at_10"][1] < 0.95
    # a post-filtered answer: right rows, too few of them
    short = [{**r, "ids": r["ids"][:4], "values": {"d": r["values"]["d"][:4]}} for r in exact]
    numbers = {n[0]: n for n in kind.check(cfg, ref, short)["numbers"]}
    assert numbers["short_answers"][1] == 12 and numbers["filter_violations"][1] == 0
    assert numbers["recall_at_10"][1] == pytest.approx(0.4)


def test_the_kernel_counts_the_passing_rows_once_a_statement():
    kernel = mf.load_modules(mf.BENCH_DIR, "kernels", None)["knn_subset"]
    import re

    assert re.match(kernel.MODULE, "jit_knn_subset_search(123)") and not re.match(kernel.MODULE, "jit__ivf_search")
    shapes = {"dim": 768, "pass_rows": 5000, "corpus_elem_bytes": 2, "slot_bytes": 4}
    need = kernel.need(shapes, 100.0, 100.0)
    assert need == {"flops": 100 * 2.0 * 768 * 5000, "bytes": 100 * 5000 * (768 * 2 + 4)}
    assert kernel.need(shapes, 100.0, 7.0) == need  # riders of one dispatch each read their rows


# ------------------------------------------------------------------ readers
def span(name, dur=0.1, **labels):
    return {"id": 7, "parent": 5, "name": name, "labels": labels, "start_ms": 0.6, "dur_ms": dur, "error": None}


def doc(*spans):
    root = {"id": 1, "parent": None, "name": "ws_rpc", "labels": {}, "start_ms": 0.0, "dur_ms": 9.0, "error": None}
    return {"trace_id": "t", "ts": 0.0, "spans": [root, *spans]}


def test_the_four_readers_on_hand_written_docs():
    readers = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")
    share, ms, build, roof = (readers[n].read for n in READERS)
    for name in READERS:
        assert readers[name].NAME == name
    docs = ctx_of(
        doc(span("knn_prepare", 0.4, filter="subset"), span("knn_filter", 0.02, outcome="hit", rows="5000")),
        doc(span("knn_prepare", 9.0, filter="subset"), span("knn_filter", 8.0, outcome="build", rows="5000"),
            span("knn_filter_build", 7.0, bytes="524288"), span("knn_filter_upload", 0.9, bytes="557056")),
        doc(span("knn_prepare", 0.3, filter="none")),
        doc(span("knn_prepare", 0.5, filter="widened"), span("knn_filter", 0.03, outcome="hit", rows="400000")),
        doc(span("knn_prepare", 0.2, filter="post")),
        doc(span("knn_prepare", 0.3, filter="subset"), span("knn_filter", 0.04, outcome="hit", rows="0")),
    )
    assert share(docs) == 0.6 and ms(docs) == pytest.approx(0.035) and build(docs) == 0.25
    # nothing to read is None, never 0: no tagged statement, an unfiltered cell, the parent's program (no label, no span)
    bare = ctx_of(doc(span("knn_prepare", 0.2, filter="none")))
    old = ctx_of(doc(span("knn_prepare", 0.2)))
    for nothing in (ctx_of(), ctx_of(doc()), bare, old):
        assert share(nothing) is None and ms(nothing) is None and build(nothing) is None and roof(nothing) is None
    # the roofline share: least time for the passing rows over the kernel's time in the slice
    kernel = mf.load_modules(mf.BENCH_DIR, "kernels", None)["knn_subset"]
    shapes = {"dim": 768, "pass_rows": 5000, "corpus_elem_bytes": 2, "slot_bytes": 4}
    ctx = {"tagged": [], "kernel": {"name": "knn_subset", "need": kernel.need, "shapes": shapes},
           "slice": {"reduced": {"kernel_s": 0.1}, "dispatch": {"submitted": 500, "dispatches": 500}},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    least = 500 * 5000 * (768 * 2 + 4) / 819e9
    assert roof(ctx) == pytest.approx(100.0 * least / 0.1) and 0 < roof(ctx) < 100
    assert roof({**ctx, "kernel": {**ctx["kernel"], "name": "ivf"}}) is None and roof({**ctx, "slice": None}) is None


# ------------------------------------------------------------------ rehearsal
def rehearse(trace, capsys, seconds=3.0):
    manifest = mf.load()
    line = bench_run.run(manifest, CELL, 2**31 + 11, seconds, trace, CPU, sizes=SIZES, tuning=TUNING)
    phases = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return manifest, line, {p["phase"]: p for p in phases}


def test_a_traced_rehearsal_is_correct_and_every_search_is_served_by_the_subset_route(capsys):
    from surrealdb_tpu import telemetry

    manifest, line, phases = rehearse(True, capsys)
    well_formed(line, manifest, CELL, True)
    assert line["correct"] is True, phases["check"]
    numbers = {n["name"]: n for n in phases["check"]["numbers"]}
    assert numbers["recall_at_10"]["value"] == 1.0  # float32 on the CPU: the exact search is exact
    assert numbers["filter_violations"]["value"] == numbers["short_answers"]["value"] == 0
    assert numbers["unexpected_strategies"]["value"] == numbers["statements_not_dispatched"]["value"] == 0
    assert phases["check"]["compared"]["answers"] == line["attempted"] > 0
    assert phases["check"]["control"]["distance_rms_rel_int8"] > config("vec500k768f")["correct"]["distance_rms_rel_max"]
    assert phases["ingest"]["read_back"] == phases["ingest"]["acknowledged"] == SIZES["rows"]
    served = phases["window"]["strategies"]  # counted at the window's edges: a request or two in flight either side
    assert set(served) == {"exact-subset"} and abs(served["exact-subset"] - line["attempted"]) <= 4
    assert line["metrics"]["knn.filter_subset_share"] == {"value": 1.0, "unit": "ratio"}
    assert 0 < line["metrics"]["knn.filter_prepare_ms"]["value"] < 5
    assert 0 <= line["metrics"]["knn.filter_build_share"]["value"] < 0.5
    assert line["metrics"]["dispatch.width_mean"]["value"] == 1.0 and phases["traced"]["tagged"] > 0
    assert phases["traced"]["kernel_shapes"]["pass_rows"] == SIZES["pass_rows"]
    # the CPU backend has no device plane: no kernel time, no roofline
    assert not {"kernel.ms_per_dispatch", "knn_subset_roofline", "ivf_roofline", "ivf.longest_list"} & set(line["metrics"])
    routes = {dict(k)["route"]: int(v) for k, v in telemetry.counters_matching("knn_filter_route").items()}
    assert set(routes) <= {"subset", "masked"} and routes["subset"] >= phases["window"]["all_requests"]


def test_an_untraced_rehearsal_reports_the_five_end_to_end_metrics(capsys):
    manifest, line, phases = rehearse(False, capsys)
    well_formed(line, manifest, CELL, False)
    assert line["correct"] is True, phases["check"]
    assert set(line["metrics"]) == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms", "recall_at_10"}
    assert line["metrics"]["recall_at_10"]["value"] == 1.0


def test_a_program_that_filters_afterwards_comes_out_not_correct(monkeypatch, capsys):
    """The parent's behaviour under this cell: the mask rides six probes (or
    none), the answers come back short or wrong, and the run says so."""
    from surrealdb_tpu.idx import knn

    monkeypatch.setattr(knn.KnnPlan, "_slot_filter", lambda self, *a, **kw: None)
    manifest, line, phases = rehearse(False, capsys)
    assert line["correct"] is False
    failed = {n["name"] for n in phases["check"]["numbers"] if not n["ok"]}
    assert {"recall_at_10", "short_answers", "unexpected_strategies"} <= failed
