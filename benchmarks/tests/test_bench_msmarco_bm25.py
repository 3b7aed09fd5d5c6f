"""`msmarco_bm25`, full-text BM25 top-10 as a deployment (ISSUE 38): the
manifest's new entries, the reference against a brute-force scorer, the
bfloat16-scored control failing its limit, the kernel's need on hand-worked
riders, the four readers on hand-written docs, and a CPU rehearsal of the
cell whose every search is served by the device route (with the route
forced to the host underneath it comes out not correct)."""

import json
import math
import os

import numpy as np
import pytest

import run as bench_run  # noqa: F401  (puts benchmarks/ on the path as the command does)
from harness import manifest as mf
from test_bench_rehearsal import CPU, TUNING, fresh_program_state, well_formed  # noqa: F401
from test_bench_served_spans import ctx_of

CELL = "msmarco_bm25.and_top10_c8"
READERS = ["bm25_and_roofline", "ft.device_share", "ft.prepare_ms", "ft.slot_fill"]
SIZES = {"rows": 6000, "pool": 64, "vocabulary": 20000}
K1, B = 1.2, 0.75


def config():
    with open(os.path.join(mf.BENCH_DIR, "configs", "msmarco_bm25.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    return mf.load_modules(mf.BENCH_DIR, "deployments", "KIND")["fulltext_bm25"]


# ------------------------------------------------------------------ manifest
def test_the_manifest_has_the_deployment_its_cell_and_its_readers_and_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    cfg = config()
    (entry,) = [c for c in manifest["configs"] if c["name"] == "msmarco_bm25"]
    assert entry["reduced"] == ["rows"] == cfg["reduced"] and entry["file"] == "benchmarks/configs/msmarco_bm25.json"
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "arXiv:1611.09268" in entry["source"] and "8,841,823 passages" in entry["source"]
    cell = mf.cell(manifest, CELL)
    assert cell == {**cell, "config": "msmarco_bm25", "traffic": "ws_closed_c8", "chips": 1} and len(cell["why"]) <= 200
    assert {m["name"] for m in mf.metrics_of(manifest, "end_to_end", CELL)} == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms"}
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(READERS[0])
    assert names[at : at + 4] == READERS and at > names.index("dispatch.wake_ms")  # together, in order, after
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "p50_ms"
    assert [by_name[n]["layer"] for n in READERS] == ["kernels", "kernels", "mirrors", "kernels"]
    assert [by_name[n]["source"] for n in READERS] == ["device_trace", "program_span", "program_span", "program_counter"]
    # the cell reports every list-less metric the other c8 cells report, and no other cell's own
    mine = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    assert mine == {m["name"] for m in manifest["per_layer"] if "workloads" not in m} | set(READERS)


def test_the_configuration_states_the_deployment_and_changes_no_width():
    cfg = config()
    assert cfg["kind"] == "fulltext_bm25" and cfg["kernel"] == "bm25_and" and cfg["expected_strategies"] == []
    assert cfg["ddl"] == [
        "DEFINE ANALYZER simple TOKENIZERS blank FILTERS lowercase",
        "DEFINE TABLE passage SCHEMALESS",
        "DEFINE INDEX passage_body ON passage FIELDS body SEARCH ANALYZER simple BM25",
    ]
    st = cfg["statements"]["primary"]
    assert st["sql"] == "SELECT id, search::score(1) AS s FROM passage WHERE body @1@ $q ORDER BY s DESC LIMIT 10"
    assert (st["bind"], st["dispatches"]) == ("q", 1)
    assert (cfg["k"], cfg["k1"], cfg["b"]) == (10, 1.2, 0.75)
    assert cfg["sizes"]["rows"] in (500_000, 250_000) and cfg["sizes"]["pool"] == 1024 and cfg["sizes"]["vocabulary"] == 1_000_000
    g = cfg["generator"]
    assert (g["corpus_seed"], g["length_mean"], g["length_sigma"], g["length_min"], g["length_max"], g["rank_shift"]) == (
        5, 56, 0.5, 8, 256, 2.7)
    assert g["query_terms"] == {"2": 0.4, "3": 0.4, "4": 0.2}
    c = cfg["correct"]
    assert (c["and_violations_max"], c["short_answers_max"], c["missed_better_max"]) == (0, 0, 0)
    assert 1e-6 < c["score_rel_err_max"] < 1e-3 and c["why"]
    assert len(cfg["guarantees"]) >= 5 and len(cfg["assumed"]) >= 6 and cfg["reduced_why"]
    for needle in ("synthetic", "conjunctive", "recalled", "blank"):
        assert any(needle in a for a in cfg["assumed"]), needle


# ------------------------------------------------------------------ generator and reference
@pytest.fixture(scope="module")
def small(kind):
    cfg = config()
    data = kind.generate(cfg, {"rows": 300, "pool": 48, "vocabulary": 400}, 7)
    ref = kind.reference(cfg, data)
    return cfg, data, ref


def test_the_generator_is_the_stated_one(kind):
    cfg = config()
    a = kind.generate(cfg, {"rows": 3000, "pool": 200, "vocabulary": 5000}, 2**31 + 5)
    b = kind.generate(cfg, {"rows": 3000, "pool": 200, "vocabulary": 5000}, 11)
    assert a["bodies"] == b["bodies"] and a["queries"] == b["queries"]  # one corpus and one pool, whatever the seed
    lens = np.diff(a["offsets"])
    assert lens.min() >= 8 and lens.max() <= 256 and 50 < lens.mean() < 62
    assert a["bodies"][0].split() == [f"w{r}" for r in a["tokens"][: lens[0]].tolist()]
    terms = [len(q.split()) for q in a["queries"]]
    assert set(terms) == {2, 3, 4} and 0.3 < terms.count(2) / 200 < 0.5
    for q in a["queries"][:50]:  # every query is words of one passage
        words = set(q.split())
        assert len(words) == len(q.split()) and any(words <= set(body.split()) for body in a["bodies"])
    counts = np.bincount(a["tokens"])
    assert counts[1] > counts[10] > counts[100] > 0 and 1.5 < counts[1] / counts[5] < 2.7  # (5 + 2.7) / (1 + 2.7) = 2.08


def brute_force(bodies, query, k):
    """BM25 top k by a loop over every passage."""
    docs = [b.lower().split() for b in bodies]
    n, avg = len(docs), sum(len(d) for d in docs) / len(docs)
    words = list(dict.fromkeys(query.lower().split()))
    df = {w: sum(w in d for d in docs) for w in words}
    scored = []
    for i, d in enumerate(docs):
        if all(w in d for w in words):
            s = 0.0
            for w in words:
                tf = d.count(w)
                idf = math.log(1.0 + (n - df[w] + 0.5) / (df[w] + 0.5))
                s += idf * tf * (K1 + 1.0) / (tf + K1 * (1.0 - B + B * len(d) / avg))
            scored.append((-s, i))
    scored.sort()
    return [i for _, i in scored[:k]], [-s for s, _ in scored[:k]], len(scored)


def test_the_reference_is_a_brute_force_scorer(kind, small):
    cfg, data, ref = small
    text_index = kind.Inverted(data["bodies"])  # from the text, as a tokeniser would
    assert text_index.vocab.tolist() == ref["index"].vocab.tolist()
    assert (text_index.post_doc == ref["index"].post_doc).all() and (text_index.post_tf == ref["index"].post_tf).all()
    full = 0
    for q, ans in zip(data["queries"], ref["answers"]):
        ids, scores, matches = brute_force(data["bodies"], q, 10)
        assert ans["ids"].tolist() == ids and ans["matches"] == matches
        np.testing.assert_allclose(ans["scores"], scores, rtol=1e-12)
        full += matches >= 10
    assert full > 3
    assert data["shapes"]["k"] == 10 and data["shapes"]["tf_bytes"] == 1
    lists = [ref["index"].lists(q) for q in data["queries"]]
    assert data["shapes"]["candidates_mean"] == pytest.approx(np.mean([l[0][0].size for l in lists]))
    assert data["shapes"]["lookups_mean"] == pytest.approx(np.mean([l[0][0].size * (len(l) - 1) for l in lists]))


def records_of(ref, scores="scores"):
    return [
        {"status": "OK", "q": q, "ids": [int(i) for i in a["ids"]], "values": {"s": [float(x) for x in a[scores]]}}
        for q, a in enumerate(ref["answers"])
    ]


def numbers(out):
    return {n: (v, lim) for n, v, _, lim in out["numbers"]}


def test_the_check_passes_the_reference_itself_and_fails_the_bfloat16_control(kind, small):
    cfg, _, ref = small
    sound = numbers(kind.check(cfg, ref, records_of(ref)))
    assert all(v <= lim for v, lim in sound.values()) and sound["score_rel_err"][0] == 0.0
    out = kind.check(cfg, ref, records_of(ref, "control"))
    control = numbers(out)
    v, lim = control["score_rel_err"]
    assert v > 50 * lim  # bfloat16 keeps three digits
    assert out["control"]["score_rel_err_bfloat16"] == pytest.approx(v)
    assert control["and_violations"][0] == 0 and control["short_answers"][0] == 0


def test_the_check_counts_what_breaks_the_guarantee(kind, small):
    cfg, _, ref = small
    full = next(q for q, a in enumerate(ref["answers"]) if a["matches"] > 10)
    recs = records_of(ref)
    recs[full]["ids"] = recs[full]["ids"][:-1]  # short while ten match
    recs[full]["values"]["s"] = recs[full]["values"]["s"][:-1]
    got = numbers(kind.check(cfg, ref, recs))
    assert got["short_answers"][0] == 1 and got["missed_better"][0] == 1 and got["and_violations"][0] == 0
    recs = records_of(ref)
    lacking = next(i for i in range(300) if i not in ref["index"].matches(ref["index"].lists(ref["queries"][full])))
    recs[full]["ids"][-1] = int(lacking)  # a passage that lacks a term, in the tenth's place and under its score
    got = numbers(kind.check(cfg, ref, recs))
    assert got["and_violations"][0] == 1 and got["short_answers"][0] == 0
    recs = records_of(ref)
    worse = [int(i) for i in ref["index"].matches(ref["index"].lists(ref["queries"][full]))
             if i not in ref["answers"][full]["ids"]][0]
    recs[full]["ids"][0] = worse  # a real match standing where the best belongs, under the best's score
    got = numbers(kind.check(cfg, ref, recs))
    assert got["missed_better"][0] == 1 and got["and_violations"][0] == 0 and got["score_rel_err"][0] > cfg["correct"]["score_rel_err_max"]


# ------------------------------------------------------------------ the kernel's need
def test_the_need_on_two_hand_worked_riders():
    need = mf.load_modules(mf.BENCH_DIR, "kernels", None)["bm25_and"].need
    # a two-term rider: rarest list 100 postings, each looked up once in the other term, 40 match
    one = need({"candidates_mean": 100.0, "lookups_mean": 100.0, "matches_mean": 40.0, "tf_bytes": 1, "k": 10}, 1, 1)
    assert one["bytes"] == 100 * 5 + 100 * 5 + 40 * 4 + 10 * 8 == 1240
    assert one["flops"] == 10 * (100 + 100)
    # a four-term rider over 2,000 candidates, 7 matching, 16-bit tfs; three statements a dispatch cost three times one
    shapes = {"candidates_mean": 2000.0, "lookups_mean": 6000.0, "matches_mean": 7.0, "tf_bytes": 2, "k": 10}
    three = need(shapes, 3, 1)
    assert three["bytes"] == 3 * (2000 * 6 + 6000 * 6 + 7 * 4 + 80) == 144_324
    assert three["flops"] == 3 * 10 * 8000
    assert need(shapes, 3, 3) == three  # nothing is shared by the riders of a launch


# ------------------------------------------------------------------ the readers
def doc(spans):
    return {"ts": 0.0, "spans": [{"name": n, "start_ms": 0.0, "dur_ms": d, "labels": l} for n, d, l in spans]}


def test_the_readers_on_hand_written_docs(monkeypatch):
    readers = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")
    docs = [
        doc([("ft_prepare", 0.20, {"route": "device", "terms": "2", "slots": "1024"}), ("dispatch_launch", 0.5, {"batch": "1", "slots": "1024"})]),
        doc([("ft_prepare", 0.40, {"route": "device", "terms": "3", "slots": "524288"})]),
        doc([("ft_prepare", 0.90, {"route": "host", "terms": "9", "slots": "0"})]),
        doc([("knn_prepare", 0.3, {"filter": "none"})]),
    ]
    ctx = ctx_of(*docs)
    assert readers["ft.device_share"].read(ctx) == pytest.approx(2 / 3)
    assert readers["ft.prepare_ms"].read(ctx) == pytest.approx(0.40)
    bare = ctx_of(docs[3])
    assert readers["ft.device_share"].read(bare) is None and readers["ft.prepare_ms"].read(bare) is None
    from surrealdb_tpu import telemetry

    telemetry.reset()
    assert readers["ft.slot_fill"].read(ctx) is None  # a program without the counters
    telemetry.inc("ft_postings", by=300.0)
    telemetry.inc("ft_slots", by=1024.0 + 8 * 1024.0)
    assert readers["ft.slot_fill"].read(ctx) == pytest.approx(300 / 9216)
    assert readers["bm25_and_roofline"].read({**ctx, "kernel": None, "slice": None}) is None
    for name in READERS:
        r = readers[name]
        assert (r.NAME, r.MOVES) == (name, "p50_ms")


# ------------------------------------------------------------------ rehearsal
def rehearse(trace, capsys, seconds=3.0):
    manifest = mf.load()
    line = bench_run.run(manifest, CELL, 2**31 + 9, seconds, trace, CPU, sizes=SIZES, tuning=TUNING)
    phases = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return manifest, line, {p["phase"]: p for p in phases}


def test_the_cell_rehearsed_is_correct_and_every_search_rides_the_device(capsys):
    manifest, line, phases = rehearse(True, capsys)
    well_formed(line, manifest, CELL, True)
    assert line["correct"] is True, phases["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    by_name = {n["name"]: n for n in phases["check"]["numbers"]}
    assert by_name["score_rel_err"]["value"] < 2e-6 < 1e-3 < phases["check"]["control"]["score_rel_err_bfloat16"]
    assert by_name["statements_not_dispatched"]["value"] == 0 and by_name["compiles_in_window"]["value"] == 0
    got = {n: m["value"] for n, m in line["metrics"].items()}
    assert got["ft.device_share"] == 1.0 and 0.0 < got["ft.slot_fill"] < 1.0 and got["ft.prepare_ms"] > 0
    assert "bm25_and_roofline" not in got and "kernel.ms_per_dispatch" not in got  # no device plane on the CPU
    listless = {m["name"] for m in manifest["per_layer"] if "workloads" not in m} - {"kernel.ms_per_dispatch"}
    assert listless <= set(got), listless - set(got)
    bgline = phases["background"]
    assert bgline["postings"] > 0 and bgline["sparse_steps"][0] == 1024 and bgline["doc_slots"] >= SIZES["rows"]
    assert phases["traced"]["kernel_shapes"]["k"] == 10 and phases["traced"]["kernel_shapes"]["candidates_mean"] > 0
    assert phases["window"]["strategies"] == {}


def test_with_the_route_forced_to_the_host_the_cell_is_not_correct(monkeypatch, capsys):
    """The host route's answers equal the reference's too: only the dispatch counter shows it."""
    from surrealdb_tpu.idx.ft_search import MatchesPlan

    real = MatchesPlan._route
    monkeypatch.setattr(MatchesPlan, "_route", lambda self, ctx, terms: "host" if real(self, ctx, terms) == "device" else real(self, ctx, terms))
    _, line, phases = rehearse(False, capsys)
    assert line["correct"] is False
    bad = [n["name"] for n in phases["check"]["numbers"] if not n["ok"]]
    assert bad == ["statements_not_dispatched"]
