"""`graph.lane_fill` (ISSUE 30): the manifest entry after the entries that were
there, the reader on hand-written docs, and in a traced CPU rehearsal of each
graph cell the fill beside `dispatch.width_mean`. (A new file: a program PR
edits none of the benchmark's; this one pins no entry to the END of
`per_layer`, where the next PR appends.)"""

import pytest

from harness import manifest as mf
from test_bench_rehearsal import SIZES, fresh_program_state, rehearse, well_formed  # noqa: F401
from test_bench_served_spans import ctx_of
from test_bench_snbsf3 import CELL, DENSE_CELL, doc

NAME = "graph.lane_fill"


def launch(batch=None, lanes=None):
    labels = {k: str(v) for k, v in (("batch", batch), ("lanes", lanes)) if v is not None}
    return {"id": 8, "parent": 5, "name": "dispatch_launch", "labels": labels, "start_ms": 1.0, "dur_ms": 2.0, "error": None}


def reader():
    return mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")[NAME]


def test_the_manifest_has_the_entry_after_those_that_were_there_and_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.count(NAME) == 1 and names.index(NAME) > names.index("graph.csc_composed_share")
    assert manifest["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "ratio", "better": "higher", "source": "program_span", "layer": "kernels",
        "moves": "p50_ms", "workloads": [DENSE_CELL, CELL]}
    r = reader()
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == ("ratio", "kernels", "p50_ms", "program_span")


@pytest.mark.parametrize("launches, fill", [
    ([(1, 8)], 1 / 8),
    ([(8, 8)], 1.0),
    ([(5, 8)] * 5, 5 / 8),  # one dispatch seen through its five riders is still one dispatch
    # a cycle of eight sessions in batches of 1, 2 and 5, every rider tagged: three dispatches, 8 riders in 24 lanes
    ([(1, 8)] + [(2, 8)] * 2 + [(5, 8)] * 5, 1 / 3),
    ([(1, 8), (5, 8)], (1 / 8 + 1 / 8) / (1 + 1 / 5)),  # one rider of each: the batch of five stands for a fifth
    ([(9, 16)] * 9 + [(1, 8)], (9 / 16 + 1 / 8) / 2),  # lane counts differ: the mean over dispatches of batch / lanes
    ([(1, 32)] + [(2, 32)] * 2 + [(5, 32)] * 5, 1 / 12),  # the floor of 32 this rule replaced
], ids=["alone", "full", "one_dispatch_five_riders", "cycle_1_2_5", "sampled_riders", "two_lane_counts", "floor_of_32"])
def test_the_reader_on_hand_written_docs(launches, fill):
    assert reader().read(ctx_of(*[doc(launch(b, l)) for b, l in launches])) == pytest.approx(fill)


def test_nothing_to_read_is_none_and_never_zero():
    read = reader().read
    # no tagged statement, none with the span, a span with `batch` alone (the parent's program; a kNN dispatch)
    for nothing in (ctx_of(), ctx_of(doc()), ctx_of(doc(launch())), ctx_of(doc(launch(3)), doc(launch(1)))):
        assert read(nothing) is None
    # a launch without `lanes` does not dilute the fill
    assert read(ctx_of(doc(launch(3)), doc(launch(4, 8)))) == 0.5


@pytest.mark.parametrize("workload, sparse", [(CELL, True), (DENSE_CELL, False)])
def test_a_traced_rehearsal_reports_the_fill_on_both_forms(workload, sparse, monkeypatch, capsys):
    """As `test_bench_csc_composed_share.py` rehearses the cells: the sparse
    cell's rehearsal puts the dense limit under its node count."""
    from surrealdb_tpu import cnf, telemetry

    manifest = mf.load()
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", SIZES["snbsf1"]["nodes"] // 2 if sparse else cnf.TPU_GRAPH_DENSE_MAX)
    line, phases = rehearse(workload, True, capsys)
    well_formed(line, manifest, workload, True)
    assert line["correct"] is True, phases["check"]
    fill = line["metrics"][NAME]
    assert fill["unit"] == "ratio" and 1 / 8 <= fill["value"] <= 1.0
    lanes = {int(dict(k)["lanes"]): int(v) for k, v in telemetry.counters_matching("graph_count_lanes").items()}
    # eight sessions never pass 8 riders: every dispatch of the cell ran at 8 lanes, none at the old 32
    assert set(lanes) == {8} and lanes[8] >= phases["window"]["all_requests"] / 8
