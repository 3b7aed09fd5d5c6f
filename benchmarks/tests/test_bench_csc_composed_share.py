"""`graph.csc_composed_share` (ISSUE 28): the manifest entry at the end of
`per_layer`, the reader on hand-written docs, and in a traced CPU rehearsal of
each graph cell the new share beside `graph.count_form_csc_share`. (A new file:
`test_bench_snbsf3.py` is the benchmark's, and a program PR edits none of
those; its check that PR 27's three entries end `per_layer` is one this entry
moves, as `PERF.md` section 7 says.)"""

import pytest

from harness import manifest as mf
from test_bench_rehearsal import SIZES, fresh_program_state, rehearse, well_formed  # noqa: F401
from test_bench_served_spans import ctx_of
from test_bench_snbsf3 import CELL, DENSE_CELL, doc

NAME, FORM_SHARE = "graph.csc_composed_share", "graph.count_form_csc_share"


def prepare(form=None, operand=None):
    labels = {k: v for k, v in (("form", form), ("operand", operand)) if v is not None}
    return {"id": 7, "parent": 5, "name": "graph_prepare", "labels": labels, "start_ms": 0.6, "dur_ms": 0.2, "error": None}


def test_the_manifest_ends_with_the_entry_and_has_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    assert manifest["per_layer"][-1] == {
        "name": NAME, "unit": "ratio", "better": "higher", "source": "program_span", "layer": "kernels",
        "moves": "p50_ms", "workloads": [DENSE_CELL, CELL]}
    reader = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")[NAME]
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == ("ratio", "kernels", "p50_ms", "program_span")


@pytest.mark.parametrize("spans, share", [
    ([("csc", "composed")], 1.0),
    ([("csc", "records")], 0.0),
    ([("csc", None)], 0.0),  # the parent's program: a form and no operand is not composed
    ([("dense", None), ("dense", None)], 0.0),
    ([("host", None)], 0.0),
    ([("csc", "composed"), ("csc", "records"), ("dense", None), ("host", None)], 0.25),
    ([("csc", "composed"), ("csc", "composed"), ("csc", None)], 2 / 3),
    ([("dense", "composed")], 0.0),  # only a csc count has an operand to be composed
], ids=["composed", "records", "no_operand", "dense", "host", "mixed", "older_and_newer", "operand_on_dense"])
def test_the_reader_on_hand_written_docs(spans, share):
    read = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")[NAME].read
    assert read(ctx_of(*[doc(prepare(form, operand)) for form, operand in spans])) == pytest.approx(share)


def test_nothing_to_read_is_none_and_never_zero():
    read = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")[NAME].read
    # no tagged statement, none with the span, a span with no form label (older than PR 27)
    for nothing in (ctx_of(), ctx_of(doc()), ctx_of(doc(), doc()), ctx_of(doc(prepare())), ctx_of(doc(prepare(None, "composed")))):
        assert read(nothing) is None
    # a statement without the span does not dilute the share
    assert read(ctx_of(doc(), doc(prepare("csc", "composed")))) == 1.0


@pytest.mark.parametrize("workload, form_share, composed_share", [(CELL, 1.0, 1.0), (DENSE_CELL, 0.0, 0.0)])
def test_a_traced_rehearsal_reports_the_operand_beside_the_form(workload, form_share, composed_share, monkeypatch, capsys):
    """As `test_bench_snbsf3.py` rehearses the cells: the new cell's rehearsal
    puts the dense limit under its node count, so the program chooses as it
    does at 24,328 persons, and the chain of `->knows->person` pairs sweeps
    the composed operator."""
    from surrealdb_tpu import cnf, telemetry

    manifest = mf.load()
    nodes = SIZES["snbsf1"]["nodes"]
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", nodes // 2 if form_share else cnf.TPU_GRAPH_DENSE_MAX)
    line, phases = rehearse(workload, True, capsys)
    well_formed(line, manifest, workload, True)
    assert line["correct"] is True, phases["check"]
    assert line["metrics"][FORM_SHARE] == {"value": form_share, "unit": "ratio"}
    assert line["metrics"][NAME] == {"value": composed_share, "unit": "ratio"}
    operands = {dict(k)["operand"]: int(v) for k, v in telemetry.counters_matching("graph_csc_operand").items()}
    forms = {dict(k)["form"]: int(v) for k, v in telemetry.counters_matching("graph_count_form").items()}
    # counted in one call from one argument: every csc count names its operand
    assert operands == ({"composed": forms["csc"]} if form_share else {})
    assert forms.get("csc", 0) >= (phases["window"]["all_requests"] if form_share else 0)
