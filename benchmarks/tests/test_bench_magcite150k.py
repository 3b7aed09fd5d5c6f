"""`magcite150k`, the north star's hybrid statement as a deployment (ISSUE 47):
a CPU rehearsal of its cell, untraced and traced (the loader's probe, the
window's six numbers, the span readers), and a set altered where it is
produced coming out not correct. The reference, the check, the readers on
hand-written docs, the manifest's entries and the configuration are held by
`tests/test_hybrid_reach.py` (tier 1)."""

import json
import threading

from harness import manifest as mf
from test_bench_rehearsal import CPU, TUNING, fresh_program_state, well_formed  # noqa: F401

import run as bench_run

CELL = "magcite150k.knn2hop_c8"
SIZES = {"papers": 8192, "cites": 87245, "pool": 64, "centres": 32, "pass_share": 0.5}
SEED = 2**31 + 7


def rehearse(trace, capsys, seconds=3.0):
    line = bench_run.run(mf.load(), CELL, SEED, seconds, trace, CPU, sizes=SIZES, tuning=TUNING)
    phases = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return line, {p["phase"]: p for p in phases}


def test_the_untraced_rehearsal_is_correct_and_every_statement_is_eleven_riders(capsys):
    manifest = mf.load()
    line, phases = rehearse(False, capsys)
    well_formed(line, manifest, CELL, False)
    assert line["correct"] is True, phases["check"]
    assert set(line["metrics"]) == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms", "recall_at_10"}
    numbers = {n["name"]: n for n in phases["check"]["numbers"]}
    for name in ("filter_violations", "short_answers", "wrong_reach_counts", "statements_not_dispatched"):
        assert numbers[name]["value"] == 0 == numbers[name]["limit"]
    assert numbers["recall_at_10"]["value"] >= 0.95
    compared, control = phases["check"]["compared"], phases["check"]["control"]
    assert compared["rows"] == 10 * compared["answers"] == 10 * line["attempted"] > 0
    # the two controls count other sizes for rows of the window: without the year nearly every row's, as walks a good share
    assert control["wrong_reach_counts_unmasked"] > compared["rows"] // 2 and control["wrong_reach_counts_multiset"] > 0
    assert phases["ingest"]["probe"] == {**phases["ingest"]["probe"], "statements": 32, "rows": 320}
    assert {"ivf_wait_s", "prewarm_wait_s"} <= set(phases["background"])
    # a statement's ten set riders share a launch: batches of ten and its multiples
    assert any(int(w) >= 10 for w in phases["window"]["widths"])


def test_the_traced_rehearsal_reads_the_span_readers_and_invents_no_device_number(capsys):
    manifest = mf.load()
    line, phases = rehearse(True, capsys)
    well_formed(line, manifest, CELL, True)
    assert line["correct"] is True and phases["traced"]["tagged"] > 0
    assert line["metrics"]["graph.reach_group_rows"]["value"] == 10.0
    assert 1.0 <= line["metrics"]["graph.reach_group_launches"]["value"] <= 2.0
    assert line["metrics"]["knn.filter_widened_share"]["value"] == 1.0
    assert line["metrics"]["hybrid.knn_stage_ms"]["value"] > 0 and line["metrics"]["hybrid.reach_stage_ms"]["value"] > 0
    assert not {"hybrid_reach_roofline", "hybrid.knn_kernel_ms", "kernel.ms_per_dispatch"} & set(line["metrics"])
    # the older cells' listed readers have nothing to read here: their lists do not name the cell
    assert not {"graph.reach_device_share", "knn.filter_subset_share", "graph_reach_roofline"} & set(line["metrics"])


def test_a_set_altered_where_it_is_produced_is_not_correct(monkeypatch, capsys):
    """The fault begins when the load generator's clients start, after the
    loader's probe (which would refuse it: whole sets): it is the window's
    count of every row's set that has to see it."""
    from surrealdb_tpu.idx import graph_csr

    real_ids = graph_csr._ring_ids
    started = threading.Event()

    class Clients(bench_run.Clients):
        def __init__(self, *a, **k):
            started.set()
            super().__init__(*a, **k)

    monkeypatch.setattr(bench_run, "Clients", Clients)

    def lost(words):
        ids = real_ids(words)
        return ids[1:] if started.is_set() else ids  # each swept ring's first paper vanishes

    monkeypatch.setattr(graph_csr, "_ring_ids", lost)
    line, phases = rehearse(False, capsys)
    assert line["correct"] is False
    bad = [n["name"] for n in phases["check"]["numbers"] if not n["ok"]]
    assert bad == ["wrong_reach_counts"], phases["check"]["numbers"]
