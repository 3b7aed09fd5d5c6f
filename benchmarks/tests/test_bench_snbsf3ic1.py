"""`snbsf3ic1`, LDBC SNB Interactive's IC1 as a deployment (ISSUE 31): the
manifest's new entries, the configuration beside `snbsf3`'s, the reference
against a brute-force walk, the bfloat16 control, the three readers of the
filter's span and label on hand-written docs, a CPU rehearsal of the cell,
and the loader's probe refusing a program that cannot serve it."""

import json
import os

import numpy as np
import pytest

import run as bench_run
from harness import manifest as mf
from test_bench_rehearsal import CPU, TUNING, fresh_program_state, well_formed  # noqa: F401
from test_bench_served_spans import ctx_of

CELL, BARE_CELL, DENSE_CELL = "snbsf3ic1.name3_c8", "snbsf3.hop3_c8", "snbsf1.hop3_c8"
READERS = ["graph.filter_fused_share", "graph.filter_prepare_ms", "graph.filter_build_share"]
APPENDED_TO = ["graph_csc_roofline", "graph.count_form_csc_share", "graph.prepare_ms", "graph.csc_composed_share",
               "graph.lane_fill"]
# small, and still a graph whose 3-hop count the program sends to the device from the seed
SIZES = {"nodes": 1200, "pairs": 30_000, "pool": 64, "names": 32}


def config(name):
    with open(os.path.join(mf.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    return mf.load_modules(mf.BENCH_DIR, "deployments", "KIND")["graph_filtered_count"]


# ------------------------------------------------------------------ manifest
def test_the_manifest_has_the_deployment_its_cell_and_its_readers_and_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    entry = [c for c in manifest["configs"] if c["name"] == "snbsf3ic1"]
    assert len(entry) == 1 and entry[0]["reduced"] == ["tables"] == config("snbsf3ic1")["reduced"]
    assert entry[0]["source"] == config("snbsf3ic1")["source"] and "IC1" in entry[0]["source"]
    assert len(entry[0]["source"]) <= 200 and entry[0]["file"] == "benchmarks/configs/snbsf3ic1.json"
    cell = mf.cell(manifest, CELL)
    assert cell == {**cell, "config": "snbsf3ic1", "traffic": "ws_closed_c8", "chips": 1} and len(cell["why"]) <= 200
    assert {m["name"] for m in mf.metrics_of(manifest, "end_to_end", CELL)} == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in APPENDED_TO:  # after the cells that were there
        assert by_name[name]["workloads"][-1] == CELL and by_name[name]["workloads"][:2] == [DENSE_CELL, BARE_CELL]
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(READERS[0])
    assert names[at : at + 3] == READERS  # these, together and in order
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["source"] == "program_span"
    assert [by_name[n]["layer"] for n in READERS] == ["kernels", "mirrors", "mirrors"]
    assert [by_name[n]["moves"] for n in READERS] == ["p50_ms", "p50_ms", "p95_ms"]


def test_the_configuration_is_snbsf3_s_graph_with_a_predicate_on_the_last_part():
    bare, ic1 = config("snbsf3"), config("snbsf3ic1")
    assert ic1["kind"] == "graph_filtered_count" and ic1["kernel"] == bare["kernel"] == "graph_csc"
    assert {k: v for k, v in ic1["sizes"].items() if k != "names"} == bare["sizes"] and ic1["sizes"]["names"] == 1024
    for key in ("degree_sigma", "degree_cap"):
        assert ic1["generator"][key] == bare["generator"][key]
    for key in ("ns", "db", "node_table", "edge_table", "ddl", "hops", "count_field", "expected_strategies", "reduced"):
        assert ic1[key] == bare[key], key
    st = ic1["statements"]["primary"]
    assert st["bind"] == "q" and st["dispatches"] == 1 and ic1["load"]["ask_before_edges"] == st["sql"]
    assert "(person WHERE firstName = $q.fn)" in st["sql"] and "$q.p" in st["sql"] and "{arg}" not in st["sql"]
    # snbsf3's five guarantees (the fourth says which walks count), and the two the filter adds
    assert [g for i, g in enumerate(ic1["guarantees"][:5]) if i != 3] == [g for i, g in enumerate(bare["guarantees"]) if i != 3]
    assert len(ic1["guarantees"]) == 7 and "person row is read back" in ic1["guarantees"][5]
    assert "committed firstName" in ic1["guarantees"][6] and "exact" in ic1["guarantees"][6]
    assert ic1["correct"]["count_mismatches_max"] == 0 and len(ic1["assumed"]) >= 6
    assert any("DISTINCT" in a and "WALKS" in a for a in ic1["assumed"])


# ------------------------------------------------------------------ data and reference
def test_a_seed_gives_the_kind_the_graph_it_gives_graph_count(kind):
    base = mf.load_modules(mf.BENCH_DIR, "deployments", "KIND")["graph_count"]
    cfg, seed = config("snbsf3ic1"), 2**31 + 77
    mine, theirs = kind.generate(cfg, SIZES, seed), base.generate(config("snbsf3"), SIZES, seed)
    assert (mine["pairs"] == theirs["pairs"]).all() and (mine["starts"] == theirs["starts"]).all()
    again = kind.generate(cfg, SIZES, seed)
    assert again["names"] == mine["names"] and (again["first"] == mine["first"]).all()
    assert kind.pool(cfg, again) == kind.pool(cfg, mine)
    other = kind.generate(cfg, SIZES, seed + 1)
    assert other["names"] != mine["names"]
    pool = kind.pool(cfg, mine)
    assert len(pool) == SIZES["pool"] and len(set(mine["names"])) == SIZES["names"]
    assert [e["p"] for e in pool] == [int(s) for s in mine["starts"]]
    # every name asked is somebody's; the commonest name is the first of the dictionary
    held = {mine["names"][i] for i in mine["first"]}
    assert all(e["fn"] in held for e in pool)
    assert np.bincount(mine["first"], minlength=SIZES["names"]).argmax() == 0
    row = kind.person(mine, 5)
    assert set(row) == {"id", "firstName", "lastName", "gender", "birthday", "creationDate", "locationIP", "browserUsed"}
    assert row["firstName"] == mine["names"][int(mine["first"][5])] and kind.person(mine, 5) == row


def brute_force(pairs, nodes, start, first, name_id) -> int:
    out = {}
    for a, b in pairs.tolist():
        out.setdefault(a, []).append(b)
    total = 0
    for u in out.get(start, ()):
        for v in out.get(u, ()):
            for w in out.get(v, ()):
                total += int(first[w] == name_id)
    return total


def test_the_reference_is_the_brute_force_walk_and_the_bfloat16_control_fails_the_limit(kind):
    cfg = config("snbsf3ic1")
    small = {"nodes": 150, "pairs": 1500, "pool": 24, "names": 6}
    data = kind.generate(cfg, small, 2**31 + 5)
    ref = kind.reference(cfg, data)
    want = [brute_force(data["pairs"], 150, int(p), data["first"], int(a)) for p, a in zip(data["starts"], data["asked"])]
    assert ref["counts"].tolist() == want and max(want) > 256
    records = [{"status": "OK", "q": i, "values": {"c": [int(c)]}} for i, c in enumerate(ref["counts"])]
    sound = kind.check(cfg, ref, records)
    assert sound["numbers"] == [["count_mismatches", 0, "<=", 0]] and sound["compared"] == {"answers": 24}
    assert sound["control"]["count_mismatches_bf16"] > 0
    held_in_bf16 = [{**r, "values": {"c": [int(c)]}} for r, c in zip(records, ref["counts_control"])]
    (number,) = kind.check(cfg, ref, held_in_bf16)["numbers"]
    assert number[0] == "count_mismatches" and number[1] == sound["control"]["count_mismatches_bf16"] > number[3]


# ------------------------------------------------------------------ readers
def span(name, dur=0.1, **labels):
    return {"id": 7, "parent": 5, "name": name, "labels": labels, "start_ms": 0.6, "dur_ms": dur, "error": None}


def doc(*spans):
    root = {"id": 1, "parent": None, "name": "ws_rpc", "labels": {}, "start_ms": 0.0, "dur_ms": 9.0, "error": None}
    return {"trace_id": "t", "ts": 0.0, "spans": [root, *spans]}


def test_the_three_readers_on_hand_written_docs():
    readers = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")
    fused, ms, build = (readers[n].read for n in READERS)
    for name in READERS:
        r = readers[name]
        assert (r.NAME, r.SOURCE) == (name, "program_span")
    docs = ctx_of(
        doc(span("graph_prepare", 0.4, form="csc", filter="fused", operand="composed"), span("graph_filter", 0.05, outcome="hit", rows="3")),
        doc(span("graph_prepare", 3.0, form="csc", filter="fused", operand="composed"), span("graph_filter", 2.5, outcome="build", rows="90")),
        doc(span("graph_prepare", 0.2, form="csc", filter="none", operand="composed")),
        doc(span("graph_prepare", 900.0, form="host", filter="host")),
        doc(span("graph_prepare", 0.3, form="dense", filter="fused"), span("graph_filter", 0.07, outcome="hit", rows="0")),
    )
    assert fused(docs) == 0.75 and ms(docs) == pytest.approx(0.07) and build(docs) == pytest.approx(1 / 3)
    # nothing to read is None, never 0: no tagged statement, a bare-count cell, the parent's program (no label, no span)
    bare = ctx_of(doc(span("graph_prepare", 0.2, form="csc", filter="none", operand="composed")))
    old = ctx_of(doc(span("graph_prepare", 0.2, form="csc", operand="composed")))
    for nothing in (ctx_of(), ctx_of(doc()), bare, old):
        assert fused(nothing) is None and ms(nothing) is None and build(nothing) is None


# ------------------------------------------------------------------ rehearsal
def rehearse(trace, capsys, seconds=3.0):
    manifest = mf.load()
    line = bench_run.run(manifest, CELL, 2**31 + 11, seconds, trace, CPU, sizes=SIZES, tuning=TUNING)
    phases = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return manifest, line, {p["phase"]: p for p in phases}


@pytest.mark.parametrize("form", ["csc", "dense"])
def test_a_traced_rehearsal_is_correct_and_every_count_s_filter_is_fused(form, monkeypatch, capsys):
    """At the small size the graph fits a dense operator; with the dense limit
    under its node count the program chooses as it does at 24,328 persons."""
    from surrealdb_tpu import cnf, telemetry

    if form == "csc":
        monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", SIZES["nodes"] // 2)
    manifest, line, phases = rehearse(True, capsys)
    well_formed(line, manifest, CELL, True)
    assert line["correct"] is True, phases["check"]
    assert phases["check"]["compared"]["answers"] == line["attempted"] > 0
    assert phases["check"]["control"]["count_mismatches_bf16"] > 0
    assert phases["ingest"]["read_back"] == phases["ingest"]["acknowledged"] == SIZES["nodes"] + 2 * SIZES["pairs"]
    share = 1.0 if form == "csc" else 0.0
    assert line["metrics"]["graph.filter_fused_share"] == {"value": 1.0, "unit": "ratio"}
    assert line["metrics"]["graph.count_form_csc_share"]["value"] == line["metrics"]["graph.csc_composed_share"]["value"] == share
    assert 0 < line["metrics"]["graph.filter_prepare_ms"]["value"] < line["metrics"]["graph.prepare_ms"]["value"] < 50
    assert 0 <= line["metrics"]["graph.filter_build_share"]["value"] <= 1
    assert line["metrics"]["graph.lane_fill"]["value"] > 0 and phases["traced"]["tagged"] > 0
    routes = {dict(k)["route"]: int(v) for k, v in telemetry.counters_matching("graph_count_filter").items()}
    assert set(routes) == {"fused"} and routes["fused"] >= phases["window"]["all_requests"] + 2  # the loader's two


def test_an_untraced_rehearsal_reports_the_four_end_to_end_metrics(capsys):
    manifest, line, phases = rehearse(False, capsys)
    well_formed(line, manifest, CELL, False)
    assert line["correct"] is True, phases["check"]
    assert set(line["metrics"]) == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms"}


def test_the_loader_s_probe_refuses_a_program_that_walks_the_filtered_count_on_the_host(monkeypatch, capsys):
    """A program whose filtered chain is not eligible (the parent's: any part
    with a WHERE falls back) answers the probe right, by the host walk, and
    makes no dispatch: the loader raises before any client starts."""
    from surrealdb_tpu.sql import path

    monkeypatch.setattr(path, "_filtered_chain_count", lambda ctx, rid, parts: None)
    with pytest.raises(RuntimeError, match="served by the host walk"):
        rehearse(False, capsys)
