"""`dispatch.gather_met_share` (ISSUE 45): the manifest entry after those that
were there with the four cells whose buckets gather, the reader over a
window's counter deltas, and in a traced CPU rehearsal of the dense graph
cell the share beside the counters it is made of. (A new file: a program PR
edits none of the benchmark's; it pins no entry to the END of `per_layer`,
where the next PR appends.)"""

import pytest

from harness import manifest as mf
from test_bench_dispatch_occupancy import OLD, window
from test_bench_rehearsal import fresh_program_state, rehearse, well_formed  # noqa: F401

NAME = "dispatch.gather_met_share"
CELLS = ["snbsf1.hop3_c8", "snbsf3.hop3_c8", "snbsf3ic1.name3_c8", "snbsf3ic1d.near20_c8"]
COUNTERS = ("gather_waits", "gather_met", "gather_wait_s")


def reader():
    return mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")[NAME]


def test_the_manifest_has_the_entry_after_those_that_were_there_and_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.count(NAME) == 1 and names.index(NAME) > names.index("graph.reach_filter_build_share")  # PR 44's last
    assert manifest["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "ratio", "better": "higher", "source": "program_counter", "layer": "dispatch",
        "moves": "p95_ms", "workloads": CELLS}
    r = reader()
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == ("ratio", "dispatch", "p95_ms", "program_counter")
    # the cells whose buckets gather, and no other
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(CELLS) < cells
    for cell in cells:
        assert (NAME in {m["name"] for m in mf.metrics_of(manifest, "per_layer", cell)}) == (cell in CELLS)


@pytest.mark.parametrize("counters, share", [
    ({"gather_waits": 10, "gather_met": 9, "gather_wait_s": 0.004}, 0.9),
    ({"gather_waits": 1, "gather_met": 0, "gather_wait_s": 0.0007}, 0.0),  # a reading: the one rider had left
    ({"gather_waits": 3514, "gather_met": 3514, "gather_wait_s": 1.9}, 1.0),
    ({"gather_waits": 0, "gather_met": 0, "gather_wait_s": 0.0}, None),  # no leader waited: nothing to share out
    ({}, None),  # the parent's program: no such counter
    ({"gather_waits": 4}, None),  # half a program is none
], ids=["nine_of_ten", "none_met", "all_met", "no_waits", "no_counters", "waits_alone"])
def test_the_reader_over_a_window_s_counter_deltas(counters, share):
    got = reader().read(window(**OLD, **counters))
    assert got == (pytest.approx(share) if share is not None else None)


def test_a_traced_rehearsal_of_the_dense_cell_reports_the_share_of_its_own_counters(capsys):
    manifest = mf.load()
    line, phases = rehearse("snbsf1.hop3_c8", True, capsys)
    well_formed(line, manifest, "snbsf1.hop3_c8", True)
    assert line["correct"] is True, phases["check"]
    d = phases["window"]["dispatch"]
    assert set(COUNTERS) <= set(d) and 0 <= d["gather_met"] <= d["gather_waits"] <= d["dispatches"]
    # eight sessions on a one-deep gathering bucket: leaders wait, and for no longer than the window
    assert d["gather_waits"] > 0 and 0.0 < d["gather_wait_s"] < phases["window"]["seconds"]
    assert line["metrics"][NAME] == {"value": pytest.approx(d["gather_met"] / d["gather_waits"]), "unit": "ratio"}
    assert max(int(w) for w in phases["window"]["widths"]) > 2


def test_a_cell_whose_buckets_do_not_gather_does_not_report_it(capsys):
    manifest = mf.load()
    line, phases = rehearse("vec1m768.knn_c1", True, capsys)
    assert line["correct"] is True, phases["check"]
    assert NAME not in line["metrics"]
    d = phases["window"]["dispatch"]
    assert (d["gather_waits"], d["gather_met"], d["gather_wait_s"]) == (0, 0, 0.0)
