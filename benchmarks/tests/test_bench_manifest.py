"""BENCHMARK.json resolves, by name, to files that agree with it."""

import copy
import json
import os
import shutil

import pytest

from harness import device, manifest as mf

ROOT = mf.ROOT


@pytest.fixture(scope="module")
def manifest():
    return mf.load()


def test_the_committed_manifest_has_no_problems(manifest):
    assert mf.problems(manifest) == []


def test_contract_limits(manifest):
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51 and isinstance(manifest["run_seconds"], int)
    assert manifest["paths"] == ["benchmarks"]
    assert all(not w.startswith("/") and ".." not in w for w in manifest["command"])
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert c["file"].startswith("benchmarks/")
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(1, len(manifest["workloads"]) // 2)


def test_every_config_file_states_its_rule(manifest):
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for key in ("source", "kind", "sizes", "statements", "guarantees", "precision",
                    "correct", "reduced", "assumed", "kernel", "expected_strategies"):
            assert key in cfg, f"{c['name']}: {key}"
        assert cfg["correct"]["why"]
        assert all(isinstance(s["dispatches"], int) for s in cfg["statements"].values())


@pytest.mark.parametrize(
    "plant, message",
    [
        (lambda m: m["workloads"][0].update(traffic="no_such_mix"), "no traffic/no_such_mix.json"),
        (lambda m: m["workloads"][0].update(config="nope"), "no config 'nope'"),
        (lambda m: m["per_layer"][0].update(moves="qps"), "no end-to-end metric"),
        (lambda m: m["per_layer"][0].update(moves="recall_at_10"), "does not report"),
        (lambda m: m["per_layer"][0].update(name="wire ms"), "outside the allowed characters"),
        (lambda m: m["end_to_end"][1].update(unit="statements per second"), "outside the allowed"),
        (lambda m: m["per_layer"][0].update(unit="s"), "differs from its reader"),
        (lambda m: m["per_layer"].append(dict(m["per_layer"][0], name="new.metric")), "no layer_metrics"),
        (lambda m: m["configs"][0].update(reduced=[]), "`reduced` differs"),
        (lambda m: m.update(extra=1), "top-level keys"),
        (lambda m: m["workloads"][0].update(traffic="planted_open"), "has loop 'open'"),
        (lambda m: m["workloads"][0].update(traffic="planted_http"), "has transport 'http_sql'"),
        (lambda m: m["workloads"][0].update(traffic="planted_stmt"), "states no `dispatches`"),
    ],
)
def test_a_planted_fault_is_a_problem(manifest, plant, message, tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(mf.BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__", "tests", "fixtures"))
    mix = mf.load_json(str(bench), "traffic", "ws_closed_c8")
    for name, change in (("planted_open", {"loop": "open"}), ("planted_http", {"transport": "http_sql"}),
                         ("planted_stmt", {"statements": [{"name": "secondary", "weight": 1.0}]})):
        (bench / "traffic" / f"{name}.json").write_text(json.dumps({**mix, "name": name, **change}))
    planted = copy.deepcopy(manifest)
    plant(planted)
    assert any(message in p for p in mf.problems(planted, str(bench))), mf.problems(planted, str(bench))


def test_peaks_are_keyed_by_device_kind_with_no_default():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="TPU v9"):
        device.peaks("TPU v9")
    with open(os.path.join(mf.BENCH_DIR, "harness", "peaks.json")) as f:
        assert "Google Cloud documentation" in json.load(f)["source"]


def test_no_chip_no_number():
    device.require_chips({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1)
    with pytest.raises(device.NoChip, match="not 'tpu'"):
        device.require_chips({"platform": "cpu", "kind": "cpu", "count": 1}, 1)
    with pytest.raises(device.NoChip, match="asks for 4"):
        device.require_chips({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 4)


def test_main_refuses_the_cpu_backend(capsys):
    import run

    assert run.main(["--workload", "snbsf1.hop3_c8", "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
    assert run.main(["--workload", "no.such", "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_nothing_imports_the_repo_s_other_benchmarks():
    for dirpath, _, files in os.walk(mf.BENCH_DIR):
        for name in files:
            if name.endswith(".py") and "tests" not in dirpath:
                with open(os.path.join(dirpath, name)) as f:
                    text = f.read()
                for banned in ("import bench\n", "import chip_smoke", "from scripts", "import scripts"):
                    assert banned not in text, (name, banned)
