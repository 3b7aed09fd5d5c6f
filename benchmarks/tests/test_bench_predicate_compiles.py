"""`exec.predicate_compiles` (ISSUE 46): the WHERE trees a tagged statement
compiles. Its entry (found by name: the next PR appends after it) and its two
cells, the reader over hand-made spans, and a traced CPU rehearsal of
`snbsf3ic1d.near20_c8`, whose statement's three `array::distinct(<chain>)`
expressions share one preparation: one compile."""

import json

from harness import manifest as mf
from test_bench_rehearsal import CPU, TUNING, fresh_program_state, well_formed  # noqa: F401

import run as bench_run

NAME = "exec.predicate_compiles"
CELLS = ["snbsf3ic1d.near20_c8", "snbsf3ic1.name3_c8"]
SIZES = {"nodes": 300, "pairs": 2400, "pool": 24, "names": 6}  # tests/test_graph_reach.py's
SEED = 2**31 + 46


def reader():
    return mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")[NAME]


def test_the_entry_is_there_once_and_names_the_two_cells_whose_statement_compiles_a_riding_where():
    manifest = mf.load()
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.count(NAME) == 1 and names.index(NAME) > names.index("dispatch.gather_met_share")  # PR 45's
    entry = manifest["per_layer"][names.index(NAME)]
    assert entry == {"name": NAME, "unit": "count/stmt", "better": "lower", "source": "program_span",
                     "layer": "parse/plan + executor", "moves": "p50_ms", "workloads": CELLS}
    r = reader()
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (entry["unit"], entry["layer"], entry["moves"], entry["source"])
    # the two cells, and no other
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(CELLS) < cells
    for cell in cells:
        assert (NAME in {m["name"] for m in mf.metrics_of(manifest, "per_layer", cell)}) == (cell in CELLS)


def tagged(*counts):
    other = [{"name": "graph_prepare", "labels": {"memo": "hit"}}, {"name": "stmt_execute", "labels": {}}]
    return {"tagged": [{"doc": {"spans": other + [{"name": "predicate_compile", "labels": {}}] * n}} for n in counts]}


def test_the_reader_counts_a_statement_s_compiles_and_takes_the_median_of_those_that_compiled():
    read = reader().read
    assert read(tagged(5, 5, 5)) == 5 and read(tagged(1)) == 1
    assert read(tagged(1, 1, 5)) == 1 and read(tagged(0, 0, 1, 0)) == 1  # a statement that compiled none is no reading
    assert read(tagged(0, 0)) is None and read(tagged()) is None and read({"tagged": [{"doc": {"spans": []}}]}) is None


def test_a_traced_rehearsal_of_the_set_cell_compiles_one_predicate_a_statement(capsys):
    manifest = mf.load()
    line = bench_run.run(manifest, CELLS[0], SEED, 3.0, True, CPU, sizes=SIZES, tuning=TUNING)
    phases = {p["phase"]: p for p in map(json.loads, capsys.readouterr().out.splitlines())}
    well_formed(line, manifest, CELLS[0], True)
    assert line["correct"] is True and phases["traced"]["tagged"] > 0
    assert line["metrics"][NAME]["value"] == 1 and line["metrics"][NAME]["unit"] == "count/stmt"
    assert line["metrics"]["graph.reach_device_share"]["value"] == 1.0
