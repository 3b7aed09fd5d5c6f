"""graftcheck: registry completeness (no kernel ships unaudited), the
GC001–GC004 rules firing on seeded violations and staying silent on the
real kernels, baseline mechanics, and the kernel_audit report flowing
into bundles."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from scripts.graftcheck import engine, lowering, registry, rules  # noqa: E402
from scripts.graftcheck.lowering import Lowered  # noqa: E402


def _run_cli(*args, timeout=420):
    env = {**os.environ}
    env.pop("XLA_FLAGS", None)  # the CLI pins its own simulated mesh
    env.pop("JAX_PLATFORMS", None)
    return subprocess.run(
        [sys.executable, "-m", "scripts.graftcheck", *args],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=timeout,
    )


# ------------------------------------------------------------ completeness
def test_every_tracked_subsystem_is_registered():
    """The acceptance criterion that makes the gate closed-world: a
    compile_log.tracked() subsystem in the source with no KERNEL_SITES
    entry is a kernel shipping unaudited (and vice versa a stale
    registration) — mirrors the graftlint repo-lints-clean test."""
    problems = registry.completeness_problems()
    assert problems == [], "\n".join(problems)


def test_tracked_scan_sees_the_known_kernels():
    subs = registry.tracked_subsystems()
    assert {
        "knn_exact", "knn_sharded", "ivf", "ivf_sharded", "bm25",
        "graph_dense", "graph_csc", "graph_chain", "ml_forward",
    } <= subs


def test_every_registered_contract_resolves_and_validates():
    contracts = registry.resolve_contracts()
    from surrealdb_tpu import compile_log

    assert {c["subsystem"] for c in contracts} == set(compile_log.KERNEL_SITES)
    for c in contracts:
        engine.validate_contract(c)  # raises on malformed
        assert c["kind"] in ("single", "sharded")
        if c["kind"] == "sharded":
            # sharded sites must DECLARE their collective budget
            assert tuple(c["allowed_collectives"]) == ("all-gather",)
        else:
            assert tuple(c["allowed_collectives"]) == ()


def test_unknown_site_is_a_contract_error():
    with pytest.raises(engine.ContractError):
        registry.resolve_contracts(["no_such_kernel"])


# ------------------------------------------------------------ rules (in-proc)
def _fixture(name):
    from scripts.graftcheck import fixtures

    return next(c for c in fixtures.fixture_sites() if c["subsystem"] == name)


def _audit_one(contract):
    shape = contract["shapes"][0]
    low = lowering.lower_site(contract, shape)
    return rules.check(contract, shape, low), low


def test_gc001_fires_on_host_callback_fixture():
    findings, low = _audit_one(_fixture("fixture_callback"))
    assert any(f.rule == "GC001" for f in findings)
    assert "pure_callback" in low.primitives


def test_gc001_fires_on_debug_effect_fixture():
    findings, _ = _audit_one(_fixture("fixture_debug_effect"))
    assert any(f.rule == "GC001" for f in findings)


def test_gc002_fires_on_f64_fixture_and_out_dtype_drift():
    findings, low = _audit_one(_fixture("fixture_f64"))
    assert any(f.rule == "GC002" and "f64" in f.key for f in findings)
    findings, _ = _audit_one(_fixture("fixture_out_dtype"))
    assert any(f.rule == "GC002" and "out-dtype" in f.key for f in findings)


def test_real_single_device_kernels_audit_clean():
    """The clean-twin direction: the registered single-device kernels
    (the ones lowerable without the 8-device mesh) produce zero findings
    in-process."""
    for contract in registry.resolve_contracts(["knn_exact", "bm25"]):
        shape = contract["shapes"][0]
        low = lowering.lower_site(contract, shape)
        assert rules.check(contract, shape, low) == []
        assert low.hlo_sha256 and low.collectives == {}


def test_gc003_gather_then_slice_detector_is_ssa_aware():
    low = Lowered(subsystem="s", label="l")
    low.hlo_text = (
        ' %12 = "stablehlo.all_gather"(%11) : (tensor<8x3xf32>) -> tensor<8x24xf32>\n'
        " %13 = stablehlo.dynamic_slice %12, %c0, %c1, sizes = [8, 3]"
        " : (tensor<8x24xf32>) -> tensor<8x3xf32>\n"
    )
    lowering._scan_hlo(low)
    assert low.gather_feeds_dynamic_slice
    assert low.collectives == {"all-gather": 1}
    # a dynamic_slice over something ELSE is not the reshard signature
    low2 = Lowered(subsystem="s", label="l")
    low2.hlo_text = (
        ' %12 = "stablehlo.all_gather"(%11) : (tensor<8x3xf32>) -> tensor<8x24xf32>\n'
        " %13 = stablehlo.dynamic_slice %4, %c0, %c1, sizes = [8, 3]"
        " : (tensor<8x24xf32>) -> tensor<8x3xf32>\n"
    )
    lowering._scan_hlo(low2)
    assert not low2.gather_feeds_dynamic_slice


def test_gc004_flags_dynamic_dims_and_ops():
    contract = {"kind": "single", "allowed_collectives": (), "out_dtypes": ("float32",)}
    low = Lowered(subsystem="s", label="l")
    low.hlo_text = "%0 = stablehlo.abs %arg0 : tensor<?x16xf32>\n"
    lowering._scan_hlo(low)
    assert low.has_dynamic_dims
    assert rules.RULES["GC004"][0](contract, {"label": "l"}, low)
    low2 = Lowered(subsystem="s", label="l")
    low2.hlo_text = "%0 = stablehlo.dynamic_reshape %arg0, %1 : tensor<16xf32>\n"
    lowering._scan_hlo(low2)
    assert low2.dynamic_shape_ops == ["dynamic_reshape"]


def test_inline_suppression_on_the_declaration():
    contract = dict(_fixture("fixture_f64"))
    contract["suppress"] = ("GC002",)
    shape = contract["shapes"][0]
    low = lowering.lower_site(contract, shape)
    assert [f for f in rules.check(contract, shape, low) if f.rule == "GC002"] == []


# ------------------------------------------------------------ baseline
def test_baseline_grandfathers_then_catches_new(tmp_path):
    f1 = engine.Finding("GC002", "knn_exact", "t8", "msg", "GC002:knn_exact:t8:f64")
    f2 = engine.Finding("GC003", "ivf_sharded", "t1", "msg", "GC003:ivf_sharded:t1:all-reduce")
    bpath = tmp_path / "baseline.json"
    engine.write_baseline([f1], str(bpath))
    baseline = engine.load_baseline(str(bpath))
    new, stale = engine.apply_baseline([f1], baseline)
    assert new == [] and stale == []
    new, stale = engine.apply_baseline([f1, f2], baseline)
    assert [f.key for f in new] == [f2.key] and stale == []
    new, stale = engine.apply_baseline([], baseline)
    assert new == [] and stale == [f1.key]


# ------------------------------------------------------------ the CLI
def test_cli_fixtures_exit_nonzero_with_all_rules():
    """Acceptance: the gate exits non-zero on the seeded violation
    fixtures — host callback, f64 promotion, undeclared collective and
    the gather-then-slice reshard — proving it can actually fail."""
    r = _run_cli("--fixtures")
    assert r.returncode == 1, r.stdout + r.stderr
    for rule in ("GC001", "GC002", "GC003"):
        assert rule in r.stdout, r.stdout
    assert "all-reduce" in r.stdout
    assert "dynamic-slice" in r.stdout


def test_cli_sharded_sites_lower_clean_under_8_device_mesh():
    """Acceptance: the sharded kNN/IVF lowerings are free of undeclared
    all-gathers under the simulated 8-device mesh (the CLI pins
    XLA_FLAGS before jax loads — that's why this is a subprocess)."""
    r = _run_cli("--sites", "knn_sharded,ivf_sharded")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new" in r.stdout


# ------------------------------------------------------------ report plumbing
def _fake_results():
    low = Lowered(subsystem="knn_exact", label="t8")
    low.hlo_sha256 = "a" * 64
    low.collectives = {}
    low.out_dtypes = ["float32", "int32"]
    contract = {
        "subsystem": "knn_exact", "module": "m", "kind": "single",
        "allowed_collectives": (), "out_dtypes": ("float32", "int32"),
    }
    return [(contract, {"label": "t8"}, low, [])]


def test_report_roundtrips_and_validates_in_bundle(tmp_path, monkeypatch):
    from scripts.graftcheck import report as report_mod

    rep = report_mod.build_report(_fake_results())
    assert rep["summary"] == {"sites": 1, "shapes": 1, "findings": 0}
    assert rep["kernels"]["knn_exact"]["shapes"]["t8"]["rules"]["GC003"] == "pass"
    path = tmp_path / "rep.json"
    report_mod.write_report(rep, str(path))

    from surrealdb_tpu import cnf
    from surrealdb_tpu.bundle import debug_bundle

    monkeypatch.setattr(cnf, "KERNEL_AUDIT_REPORT", str(path))
    ka = debug_bundle(None)["kernel_audit"]
    # the section is the report, whole, under `available`: the kernel, the
    # shape's lowered-HLO digest and a verdict for every rule
    assert ka["available"] is True
    assert ka["summary"] == rep["summary"]
    shape = ka["kernels"]["knn_exact"]["shapes"]["t8"]
    assert shape["hlo_sha256"] == "a" * 64
    assert shape["rules"] == rep["kernels"]["knn_exact"]["shapes"]["t8"]["rules"]
    assert set(shape["rules"].values()) == {"pass"}
    # and an absent report degrades to available: false, never a crash
    monkeypatch.setattr(cnf, "KERNEL_AUDIT_REPORT", str(tmp_path / "nope.json"))
    assert debug_bundle(None)["kernel_audit"]["available"] is False


def test_pin_env_forces_the_mesh_device_count(monkeypatch):
    """An ambient smaller device count must be OVERRIDDEN, not kept —
    otherwise every sharded lowering fails GC000 with a make_mesh error."""
    import scripts.graftcheck.__main__ as cli

    monkeypatch.setenv(
        "XLA_FLAGS", "--foo=1 --xla_force_host_platform_device_count=2"
    )
    cli._pin_env()
    assert "--xla_force_host_platform_device_count=8" in os.environ["XLA_FLAGS"]
    assert "device_count=2" not in os.environ["XLA_FLAGS"]
    assert "--foo=1" in os.environ["XLA_FLAGS"]
