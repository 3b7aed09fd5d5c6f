"""`snbsf3`, the friendship graph the dense count form does not hold: the
manifest with its entries, the configuration against `snbsf1`'s, the two
readers of the `graph_prepare` span and the windowed kernel time on
hand-written docs, and a CPU rehearsal of each graph cell that says which
count form served it."""

import json
import os

import pytest

from harness import manifest as mf
from test_bench_rehearsal import SIZES, fresh_program_state, rehearse, well_formed  # noqa: F401
from test_bench_served_spans import ctx_of

CELL, DENSE_CELL = "snbsf3.hop3_c8", "snbsf1.hop3_c8"
READERS = ["graph.count_form_csc_share", "graph.prepare_ms"]
KERNEL_MS = "kernel.window_ms_per_dispatch"
# what ISSUE 27 changes of snbsf1.json: the scale and the texts that describe what serves it
DIFFERS = {"name", "source", "deployment", "sizes", "precision", "reduced_why", "assumed", "generator", "correct"}


def config(name):
    with open(os.path.join(mf.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def test_the_manifest_has_the_deployment_and_no_problems():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    assert [c["name"] for c in manifest["configs"]][-1] == "snbsf3"
    assert manifest["workloads"][-1] == {**manifest["workloads"][-1], "name": CELL, "config": "snbsf3",
                                         "traffic": "ws_closed_c8", "chips": 1}
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    entry = manifest["configs"][-1]
    assert entry["reduced"] == ["tables"] == config("snbsf3")["reduced"]
    assert entry["source"] == config("snbsf3")["source"] and "SF3" in entry["source"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert by_name["graph_csc_roofline"]["workloads"] == [DENSE_CELL, CELL]
    for name in READERS:
        assert by_name[name]["workloads"] == [DENSE_CELL, CELL] and by_name[name]["source"] == "program_span"
    assert {m["name"] for m in mf.metrics_of(manifest, "end_to_end", CELL)} == {"setup_s", "stmt_per_s", "p50_ms", "p95_ms"}
    assert by_name[KERNEL_MS] == {**by_name[KERNEL_MS], "workloads": [CELL], "layer": "kernels", "source": "device_trace"}
    # appended: the driver's check reads an entry put in the middle of a list as a change to the one it displaced
    assert [m["name"] for m in manifest["per_layer"]][-3:] == READERS + [KERNEL_MS]


def test_the_configuration_is_snbsf1_at_the_source_s_next_scale():
    one, three = config("snbsf1"), config("snbsf3")
    assert set(one) == set(three)
    assert {k for k in one if one[k] != three[k]} == DIFFERS
    assert three["sizes"] == {"nodes": 24328, "pairs": 565247, "pool": one["sizes"]["pool"]}
    for group, text in (("generator", "what"), ("correct", "why")):
        assert {k for k in one[group] if one[group][k] != three[group][k]} == {text}
    assert three["correct"]["count_mismatches_max"] == 0 and three["expected_strategies"] == []
    assert len(three["source"]) <= 200 and three["kind"] == "graph_count" and three["kernel"] == "graph_csc"
    # the person table passes what a dense operator may span, at the source's own scale
    from surrealdb_tpu import cnf

    assert one["sizes"]["nodes"] <= cnf.TPU_GRAPH_DENSE_MAX < three["sizes"]["nodes"]
    assert any("24,328" in a and "565,247" in a for a in three["assumed"]) and len(three["assumed"]) >= 4


def prepare(form, dur=0.2):
    labels = {} if form is None else {"form": form}
    return {"id": 7, "parent": 5, "name": "graph_prepare", "labels": labels, "start_ms": 0.6, "dur_ms": dur, "error": None}


def doc(*spans):
    root = {"id": 1, "parent": None, "name": "ws_rpc", "labels": {}, "start_ms": 0.0, "dur_ms": 9.0, "error": None}
    return {"trace_id": "t", "ts": 0.0, "spans": [root, *spans]}


def test_the_two_readers_on_hand_written_docs():
    readers = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")
    share, ms = readers["graph.count_form_csc_share"].read, readers["graph.prepare_ms"].read
    mixed = ctx_of(doc(prepare("csc", 0.3)), doc(prepare("dense", 0.1)), doc(prepare("csc", 0.2)), doc(prepare("host", 5.0)))
    # a host-served count closes the span at its walk's end: a whole count, not a preparation
    assert share(mixed) == 0.5 and ms(mixed) == pytest.approx(0.2)
    assert ms(ctx_of(doc(prepare("host", 5.0)))) is None
    assert share(ctx_of(doc(prepare("dense")), doc(prepare("dense")))) == 0.0
    assert share(ctx_of(doc(prepare("csc")))) == 1.0
    # nothing to read is None, never 0: no tagged statement, none with the span, or (the parent's
    # program) a span without the label
    for nothing in (ctx_of(), ctx_of(doc()), ctx_of(doc(), doc())):
        assert share(nothing) is None and ms(nothing) is None
    old = ctx_of(doc(prepare(None, 0.4)))
    assert share(old) is None and ms(old) == pytest.approx(0.4)


def collected(ts, end_ms, batch):
    """A statement whose trace began at wall `ts` and whose batch's collect ended `end_ms` after that."""
    span = {"id": 9, "parent": 5, "name": "dispatch_collect", "labels": {"batch": batch},
            "start_ms": end_ms - 2980.0, "dur_ms": 2980.0, "error": None}
    return {**doc(span), "ts": ts}


def test_the_windowed_kernel_time_is_the_period_between_batches_times_the_kernel_s_share_of_the_recorded_slice():
    read = mf.load_modules(mf.BENCH_DIR, "layer_metrics", "NAME")[KERNEL_MS].read
    # the device was recorded from 0.02 s to 3.9 s of the slice, with 0.004 s between two kernels
    sliced = {"reduced": {"kernel_s": 3.876, "window_s": 4.0, "kernel_launches": 3,
                          "gaps": [[3.9, 0.1], [0.0, 0.02], [1.4, 0.004]]}, "dispatch": {"dispatches": 2}}
    # five batches 1,494 ms apart, the fourth with no tagged rider; riders of one batch on their own clocks
    docs = [collected(1.7e9, 4500.0, 2), collected(1.7e9 + 0.02, 4480.0004, 2), collected(1.7e9 + 1.5, 4494.0, 1),
            collected(1.7e9 + 3.0, 4488.0, 5), collected(1.7e9 + 3.1, 4388.0003, 5), collected(1.7e9 + 6.0, 4476.0, 1)]
    ctx = {**ctx_of(*docs), "slice": sliced}
    assert read(ctx) == pytest.approx(1494.0 * 3.876 / 3.88, rel=1e-6)
    # nothing to read is None: no device trace, none of the kernel in it, fewer than three batches told apart
    assert read({**ctx, "slice": None}) is None
    assert read({**ctx, "slice": {**sliced, "reduced": {**sliced["reduced"], "kernel_launches": 0}}}) is None
    assert read({**ctx_of(*docs[:3]), "slice": sliced}) is None and read({**ctx_of(), "slice": sliced}) is None


@pytest.mark.parametrize("workload, share", [(CELL, 1.0), (DENSE_CELL, 0.0)])
def test_a_traced_rehearsal_says_which_form_served_the_cell(workload, share, monkeypatch, capsys):
    """At the small graph sizes both cells fit a dense operator, so the new
    cell's rehearsal puts the dense limit under its node count: the program
    then chooses as it does at 24,328 persons."""
    from surrealdb_tpu import cnf, telemetry

    manifest = mf.load()
    nodes = SIZES["snbsf1"]["nodes"]
    monkeypatch.setattr(cnf, "TPU_GRAPH_DENSE_MAX", nodes // 2 if share else cnf.TPU_GRAPH_DENSE_MAX)
    assert (nodes > cnf.TPU_GRAPH_DENSE_MAX) == bool(share)
    line, phases = rehearse(workload, True, capsys)
    well_formed(line, manifest, workload, True)
    assert line["correct"] is True, phases["check"]
    assert line["metrics"]["graph.count_form_csc_share"] == {"value": share, "unit": "ratio"}
    assert 0 < line["metrics"]["graph.prepare_ms"]["value"] < 50
    forms = {dict(k)["form"]: int(v) for k, v in telemetry.counters_matching("graph_count_form").items()}
    served = forms.pop("csc" if share else "dense")
    assert set(forms) <= {"host"} and forms.get("host", 0) <= 1  # the loader's count before the edges
    assert served >= phases["window"]["all_requests"] and phases["traced"]["tagged"] > 0
