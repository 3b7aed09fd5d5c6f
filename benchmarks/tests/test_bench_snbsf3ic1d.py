"""`snbsf3ic1d`, LDBC SNB Interactive's IC1 as the source states it, as a
deployment (ISSUE 44): a CPU rehearsal of its cell, untraced and traced (the
loader's probes, the window's four numbers, the six span readers), and an
answer altered where it is produced coming out not correct. The reference, the
check, the readers on hand-written docs, the manifest's entries and the
configuration are held by `tests/test_graph_reach.py` (tier 1)."""

import json
import threading

import pytest

from harness import manifest as mf
from test_bench_rehearsal import CPU, TUNING, fresh_program_state, well_formed  # noqa: F401

import run as bench_run

CELL = "snbsf3ic1d.near20_c8"
# small, with names few enough that most balls pass 20 persons and some do not
SIZES = {"nodes": 1200, "pairs": 30_000, "pool": 64, "names": 32}
SEED = 2**31 + 7


def rehearse(trace, capsys, seconds=3.0):
    line = bench_run.run(mf.load(), CELL, SEED, seconds, trace, CPU, sizes=SIZES, tuning=TUNING)
    phases = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return line, {p["phase"]: p for p in phases}


def test_the_untraced_rehearsal_is_correct_and_every_statement_is_one_dispatch(capsys):
    manifest = mf.load()
    line, phases = rehearse(False, capsys)
    well_formed(line, manifest, CELL, False)
    assert line["correct"] is True, phases["check"]
    assert set(line["metrics"]) == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms"}
    numbers = {n["name"]: n for n in phases["check"]["numbers"]}
    for name in ("wrong_ids", "duplicates", "ring_violations", "short_answers", "statements_not_dispatched"):
        assert numbers[name]["value"] == 0 == numbers[name]["limit"]
    compared = phases["check"]["compared"]
    assert compared["answers"] == line["attempted"] > 0 and 0 < compared["rows"] <= 20 * compared["answers"]
    # the control without the name answers persons of other names to every request
    assert phases["check"]["control"]["wrong_ids_unmasked"] >= compared["answers"]
    # the loader's probe from eight sessions at once: its statements and their dispatches' widths are on the ingest line
    asked = phases["ingest"]["probe_sessions"]
    assert asked["statements"] == 8 * 32 == sum(int(w) * n for w, n in asked["widths"].items())
    assert max(int(w) for w in asked["widths"]) >= 2
    assert set(phases["background"]) >= {"prewarm_wait_s"} and "full_collection_s" not in phases["background"]
    # a dispatch a statement: the window's counters differ by the statements in flight at its ends
    assert abs(phases["window"]["dispatch"]["submitted"] - phases["window"]["completed"]) <= 8


def test_the_traced_rehearsal_reads_the_six_span_metrics_and_invents_no_device_number(capsys):
    manifest = mf.load()
    line, phases = rehearse(True, capsys)
    well_formed(line, manifest, CELL, True)
    assert line["correct"] is True and phases["traced"]["tagged"] > 0
    assert line["metrics"]["graph.reach_device_share"]["value"] == 1.0
    assert line["metrics"]["graph.reach_prepare_ms"]["value"] > 0
    assert line["metrics"]["graph.reach_ids_mean"]["value"] > 0
    assert 0 < line["metrics"]["graph.reach_lane_fill"]["value"] <= 1.0
    assert line["metrics"]["graph.reach_filter_prepare_ms"]["value"] > 0
    assert 0 <= line["metrics"]["graph.reach_filter_build_share"]["value"] <= 1.0
    assert {"exec.materialise_ms", "dispatch.fetch_ms", "wire.write_ms"} <= set(line["metrics"])
    assert not {"graph_reach_roofline", "kernel.ms_per_dispatch"} & set(line["metrics"])
    # the count cells' readers have nothing to read here: their lists do not name the cell
    assert not {"graph.prepare_ms", "graph.count_form_csc_share", "graph.first_hop_rows_share"} & set(line["metrics"])


@pytest.mark.parametrize("fault, number", [("lost", "ring_violations"), ("unmasked", "wrong_ids")])
def test_a_ring_altered_where_it_is_produced_is_not_correct(monkeypatch, capsys, fault, number):
    """The fault begins when the load generator's clients start, after the
    loader's probes (which would refuse it: whole rings, a statement at a
    time and from eight sessions): it is the window's check that has to see
    it."""
    from surrealdb_tpu.idx import graph_csr

    real_ids, real_mask = graph_csr._ring_ids, graph_csr.GraphMirrors._reach_mask
    started = threading.Event()

    class Clients(bench_run.Clients):
        def __init__(self, *a, **k):
            started.set()
            super().__init__(*a, **k)

    monkeypatch.setattr(bench_run, "Clients", Clients)
    served = started.is_set

    def lost(words):
        ids = real_ids(words)
        return ids[1:] if served() else ids  # each swept ring's first person vanishes

    def unmasked(self, end, op, n_cap):
        return real_mask(self, None if served() else end, op, n_cap)

    if fault == "lost":
        monkeypatch.setattr(graph_csr, "_ring_ids", lost)
    else:
        monkeypatch.setattr(graph_csr.GraphMirrors, "_reach_mask", unmasked)
    line, phases = rehearse(False, capsys)
    assert line["correct"] is False
    bad = [n["name"] for n in phases["check"]["numbers"] if not n["ok"]]
    assert number in bad, phases["check"]["numbers"]
