"""`graph.reach_rows_share` (ISSUE 48): the manifest entry at the end of
`per_layer`, the reader on hand-written span documents (a fill that read the
rows, a fill without the label, no set statement), and the share in a traced
CPU rehearsal of the two cells that list it: 1.0 where the pair of operators
bounds every walk by a pad (`magcite150k`), 0.0 where a chain of three pairs
sweeps (`snbsf3ic1d`). (A new file: a program PR edits none of the
benchmark's.)"""

import pytest

from harness import manifest as mf
from test_bench_rehearsal import fresh_program_state  # noqa: F401
from test_bench_served_spans import ctx_of
from test_bench_snbsf3 import doc

NAME = "graph.reach_rows_share"
HYBRID, NEAR = "magcite150k.knn2hop_c8", "snbsf3ic1d.near20_c8"


def prepare(**labels):
    return {"id": 7, "parent": 5, "name": "graph_prepare", "labels": labels, "start_ms": 0.6, "dur_ms": 0.2, "error": None}


@pytest.fixture(scope="module")
def read():
    return mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")[NAME].read


def test_the_manifest_ends_with_the_entry_and_has_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    assert manifest["per_layer"][-1] == {
        "name": NAME, "unit": "ratio", "better": "higher", "source": "program_span", "layer": "kernels",
        "moves": "p50_ms", "workloads": [HYBRID, NEAR]}
    reader = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")[NAME]
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == ("ratio", "kernels", "p50_ms", "program_span")
    for cell in (HYBRID, NEAR):
        assert NAME in {m["name"] for m in mf.metrics_of(manifest, "per_layer", cell)}
    assert NAME not in {m["name"] for m in mf.metrics_of(manifest, "per_layer", "snbsf3.hop3_c8")}


@pytest.mark.parametrize("docs, share", [
    # the statement's fill read the rows; its rows' expressions read the memo and say nothing of the hop
    ([[dict(form="csc", memo="fill", last_hop="rows"), dict(form="csc", memo="hit"), dict(form="csc", memo="hit")]], 1.0),
    # a program older than the label (the parent's): a fill without it counts as swept
    ([[dict(form="csc", memo="fill"), dict(form="csc", memo="hit")]], 0.0),
    ([[dict(form="csc", memo="fill", last_hop="sweep")]], 0.0),
    # no set statement: a count's span has no `memo`; the host's walk is no launch
    ([[dict(form="csc", first_hop="rows")], [dict(form="host", memo="fill")], []], None),
    ([], None),
    ([[dict(form="csc", memo="fill", last_hop="rows")], [dict(form="csc", memo="fill", last_hop="sweep")],
      [dict(form="csc", memo="fill")], [dict(form="host", memo="fill")], [dict(form="csc", memo="fill", last_hop="rows")]], 0.5),
], ids=["rows", "no_label", "sweep", "no_set_statement", "nothing_tagged", "mixed"])
def test_the_reader_on_hand_written_docs(read, docs, share):
    got = read(ctx_of(*[doc(*[prepare(**labels) for labels in spans]) for spans in docs]))
    assert got is None if share is None else got == pytest.approx(share)


@pytest.mark.parametrize("cell, share", [(HYBRID, 1.0), (NEAR, 0.0)])
def test_a_traced_rehearsal_reports_the_share(cell, share, capsys):
    import test_bench_magcite150k as hybrid
    import test_bench_snbsf3ic1d as near

    line, phases = (hybrid if cell == HYBRID else near).rehearse(True, capsys)
    assert line["correct"] is True and phases["traced"]["tagged"] > 0
    assert line["metrics"][NAME]["value"] == share
