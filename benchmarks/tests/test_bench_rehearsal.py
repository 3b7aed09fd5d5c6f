"""`run()` rehearsed on the CPU backend at tiny sizes: everything but the
look for a chip. The device numbers are the one thing a rehearsal cannot
give, and it must not invent them."""

import json
import os
import shutil

import pytest

import run as bench_run
from harness import manifest as mf

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
TUNING = {"quiet_s": 1.0, "warm_min_s": 1.0, "slice_delay_s": 0.3, "slice_s": 1.0}
SIZES = {
    "vec1m768": {"rows": 8192, "pool": 64, "centres": 32},
    # small, and still with a person of 256 friends or more: past that the program's dense
    # float32 form refuses itself, and the count is served by the sparse kernel as at full size
    "snbsf1": {"nodes": 1200, "pairs": 30_000, "pool": 64},
}


@pytest.fixture(autouse=True)
def fresh_program_state():
    from surrealdb_tpu import bg, compile_log, telemetry, tracing

    telemetry.reset()
    compile_log.reset()
    bg.reset()
    tracing.store_reset()


def rehearse(workload, trace, capsys, manifest=None, bench_dir=mf.BENCH_DIR, seconds=3.0):
    manifest = manifest or mf.load()
    config = mf.cell(manifest, workload)["config"]
    sizes = SIZES.get(config, SIZES["snbsf1"])
    line = bench_run.run(manifest, workload, 2**31 + 7, seconds, trace, CPU, sizes=sizes,
                         tuning=TUNING, bench_dir=bench_dir)
    phases = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return line, {p["phase"]: p for p in phases}


def well_formed(line, manifest, workload, trace):
    assert set(line) - {"breakdown"} == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in mf.metrics_of(manifest, group, workload)}
    for name, m in line["metrics"].items():
        assert m["unit"] == allowed[name] and isinstance(m["value"], (int, float))
    json.dumps(line)


@pytest.mark.parametrize("workload", ["vec1m768.knn_c1", "snbsf1.hop3_c8"])
def test_untraced_rehearsal_ends_in_a_well_formed_correct_line(workload, capsys):
    manifest = mf.load()
    line, phases = rehearse(workload, False, capsys)
    well_formed(line, manifest, workload, False)
    assert line["correct"] is True, phases["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in mf.metrics_of(manifest, "end_to_end", workload)}
    assert list(phases)[:8] == ["rtt", "generate", "reference", "ingest", "first_stmt", "background", "warm", "window"]
    for p in phases.values():
        assert p["platform"] == "cpu" and p["device_count"] == 1
    assert phases["window"]["completed"] == line["attempted"]
    # every number compared is printed beside its limit
    assert all({"name", "value", "relation", "limit", "ok"} <= set(n) for n in phases["check"]["numbers"])


def test_traced_rehearsal_reads_the_host_side_layers_and_invents_no_device_number(capsys):
    manifest = mf.load()
    line, phases = rehearse("snbsf1.hop3_c8", True, capsys)
    well_formed(line, manifest, "snbsf1.hop3_c8", True)
    assert line["correct"] is True
    assert {"wire.ms", "exec.host_ms", "dispatch.width_mean", "dispatch.queue_wait_ms",
            "load.first_stmt_s", "load.rows_per_s"} <= set(line["metrics"])
    # the CPU backend has no device plane: no kernel time, no roofline, no busy share
    assert not {"kernel.ms_per_dispatch", "graph_csc_roofline"} & set(line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert phases["traced"]["tagged"] > 0


def test_traced_vector_rehearsal_reports_the_trained_state(capsys):
    manifest = mf.load()
    line, phases = rehearse("vec1m768.knn_c1", True, capsys)
    well_formed(line, manifest, "vec1m768.knn_c1", True)
    assert line["correct"] is True, phases["check"]
    assert line["metrics"]["ivf.longest_list"]["value"] == phases["background"]["longest_list"] > 0
    assert line["metrics"]["dispatch.width_mean"]["value"] == 1.0
    assert "ivf_roofline" not in line["metrics"]


def test_a_count_altered_where_it_is_produced_is_not_correct(monkeypatch, capsys):
    from surrealdb_tpu.idx.graph_csr import GraphMirrors

    real = GraphMirrors._device_chain

    def off_by_one(self, *a, count_only=False, **kw):
        out = real(self, *a, count_only=count_only, **kw)
        return out + 1 if count_only else out

    monkeypatch.setattr(GraphMirrors, "_device_chain", off_by_one)
    line, phases = rehearse("snbsf1.hop3_c8", False, capsys)
    assert line["correct"] is False
    bad = [n["name"] for n in phases["check"]["numbers"] if not n["ok"]]
    assert bad == ["count_mismatches"]


def test_a_distance_altered_where_it_is_produced_is_not_correct(monkeypatch, capsys):
    from surrealdb_tpu.idx.ivf import IvfState

    real = IvfState.search_batch_launch

    def stretched(self, *a, **kw):
        collect = real(self, *a, **kw)

        def finish():
            dd, rr = collect()
            return dd * 1.01, rr

        return finish

    monkeypatch.setattr(IvfState, "search_batch_launch", stretched)
    line, phases = rehearse("vec1m768.knn_c1", False, capsys)
    assert line["correct"] is False
    bad = [n["name"] for n in phases["check"]["numbers"] if not n["ok"]]
    assert bad == ["distance_rms_rel"]


def test_a_count_served_by_the_host_walk_is_not_correct(monkeypatch, capsys):
    """The host walk's counts equal NumPy's: only the dispatch counter shows it."""
    from surrealdb_tpu import cnf

    monkeypatch.setattr(cnf, "TPU_GRAPH_COUNT_EDGES", 1 << 40)
    monkeypatch.setattr(cnf, "TPU_GRAPH_ONDEVICE_THRESHOLD", 1 << 40)
    line, phases = rehearse("snbsf1.hop3_c8", False, capsys)
    assert line["correct"] is False
    bad = [n["name"] for n in phases["check"]["numbers"] if not n["ok"]]
    assert bad == ["statements_not_dispatched"]


def test_a_new_cell_and_metric_are_files_and_entries_only(tmp_path, capsys):
    """A later PR's cell: one configuration, one mix, one per-layer metric,
    three manifest entries, and not one edit to a file that was there."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(mf.BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = mf.load()
    cfg = mf.load_json(str(bench), "configs", "snbsf1")
    cfg.update(name="graphsmall")
    (bench / "configs" / "graphsmall.json").write_text(json.dumps(cfg))
    mix = mf.load_json(str(bench), "traffic", "ws_closed_c8")
    mix.update(name="ws_closed_c3", clients=3, processes=2)
    (bench / "traffic" / "ws_closed_c3.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "window_statements.py").write_text(
        'NAME, UNIT, LAYER, MOVES, SOURCE = "window.statements", "stmt", "wire", "stmt_per_s", "program_counter"\n'
        "def read(ctx):\n    return len(ctx['window']['records'])\n"
    )
    manifest["configs"].append({"name": "graphsmall", "source": "a test", "why": "a test",
                                "file": "benchmarks/configs/graphsmall.json", "reduced": cfg["reduced"]})
    manifest["workloads"].append({"name": "graphsmall.c3", "config": "graphsmall", "traffic": "ws_closed_c3",
                                  "chips": 1, "why": "a test"})
    manifest["per_layer"].append({"name": "window.statements", "unit": "stmt", "better": "higher",
                                  "source": "program_counter", "layer": "wire", "moves": "stmt_per_s",
                                  "workloads": ["graphsmall.c3"]})
    assert mf.problems(manifest, str(bench)) == []
    line, phases = rehearse("graphsmall.c3", True, capsys, manifest=manifest, bench_dir=str(bench))
    assert line["correct"] is True, phases["check"]
    assert line["metrics"]["window.statements"]["value"] == line["attempted"]
    assert "graph_csc_roofline" not in line["metrics"]
